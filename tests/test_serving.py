"""The inference plane: continuous-batching scheduler correctness,
shape-bucket compile hygiene, and the elastic multi-replica serving
engine (``rl/scheduler.py`` + ``rl/generation_service.ServingEngine``).

The contracts pinned here (ISSUE 14 acceptance):

- token-level batching is INVISIBLE in the output: every sequence's
  sampled tail exactly matches an unbatched full-forward reference,
  whatever traffic it was interleaved with (sampling is a pure
  function of (seed, position));
- ONE compiled decode program at steady state — admissions and
  evictions never retrace;
- block churn leaks nothing;
- drain (SIGUSR1/SIGTERM) and crash (SIGKILL) both complete every
  request exactly once on the survivors;
- the whole-batch surface (``generate`` / ``sync_weights``) an RLHF
  trainer uses: a publish reaches it, an unchanged version adopts
  nothing, a stopped replica trips the request timeout, and a call
  fails at once when no replica is left alive.
"""

import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

CFG = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none", dtype=jnp.float32,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)

SERVE_CFG_KW = dict(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=64, remat="none",
    dtype="float32",  # exact parity with the fp32 reference
)


@jax.jit
def _last_logits(tokens):
    """The whole forward of one sequence, one program a length (called
    eagerly it compiled every operation anew for every length: most of
    this file's seconds)."""
    return llama.forward(
        params=PARAMS,
        tokens=tokens,
        cfg=CFG,
        attention_fn=llama.dot_product_attention,
    )[0, -1]


def unbatched_reference(prompt, max_new, seed, temp, eos=None):
    """The O(T^2) full-forward loop, one sequence at a time — the
    ground truth continuous batching must be invisible against."""
    toks = list(int(t) for t in prompt)
    key = jax.random.PRNGKey(seed)
    for _ in range(max_new):
        logits = _last_logits(jnp.asarray([toks], jnp.int32))
        pos = len(toks)
        if temp <= 0:
            tok = int(jnp.argmax(logits))
        else:
            tok = int(
                jax.random.categorical(
                    jax.random.fold_in(key, pos), logits / temp
                )
            )
        toks.append(tok)
        if eos is not None and tok == eos:
            break
    return np.asarray(toks, np.int32)


def _scheduler(temp=0.0, eos=None, max_slots=4, prefill_chunk=3):
    sch = ContinuousBatchingScheduler(
        CFG,
        SchedulerConfig(
            max_slots=max_slots, block_size=4, num_blocks=64,
            max_seq_len=64, prefill_chunk=prefill_chunk,
            temperature=temp, eos_id=eos,
        ),
    )
    sch.sync_weights(PARAMS)
    return sch


PROMPTS = [
    np.array([5, 9, 2], np.int32),
    np.array([11, 3, 7, 8, 1, 2, 9], np.int32),  # > prefill_chunk
    np.array([1, 2], np.int32),
    np.array([30, 31, 32, 33], np.int32),
]


class TestSchedulerParity:
    def test_greedy_tails_match_unbatched_reference(self):
        """Mixed-length prompts interleaved in 4 slots with chunked
        prefill: every tail equals the lone-sequence reference."""
        sch = _scheduler(temp=0.0)
        ids = [
            sch.submit(p, max_new=6, seed=50 + i)
            for i, p in enumerate(PROMPTS)
        ]
        res = {r.req_id: r for r in sch.run()}
        assert len(res) == len(PROMPTS)
        for i, p in enumerate(PROMPTS):
            ref = unbatched_reference(p, 6, 50 + i, temp=0.0)
            np.testing.assert_array_equal(res[ids[i]].tokens, ref)
            assert res[ids[i]].finish_reason == "length"

    def test_sampled_tails_match_reference_and_eos_stops_early(self):
        """temp > 0: sampling is (seed, position)-pure, so batched
        tails still match; an EOS ends its sequence the moment it is
        sampled while other lanes keep decoding."""
        temp = 0.8
        # pick an eos that provably fires: the reference's 2nd
        # sampled token for prompt 0
        probe = unbatched_reference(PROMPTS[0], 6, 50, temp=temp)
        eos = int(probe[PROMPTS[0].size + 1])
        sch = _scheduler(temp=temp, eos=eos)
        ids = [
            sch.submit(p, max_new=6, seed=50 + i)
            for i, p in enumerate(PROMPTS)
        ]
        res = {r.req_id: r for r in sch.run()}
        stopped_early = 0
        for i, p in enumerate(PROMPTS):
            ref = unbatched_reference(
                p, 6, 50 + i, temp=temp, eos=eos
            )
            np.testing.assert_array_equal(res[ids[i]].tokens, ref)
            if res[ids[i]].finish_reason == "eos":
                stopped_early += 1
                assert res[ids[i]].tokens[-1] == eos
                assert res[ids[i]].new_tokens < 6
        assert stopped_early >= 1  # the probe guarantees seq 0

    def test_one_decode_program_across_churn(self):
        """Admissions, evictions, EOS exits, queue pressure: the
        decode program must compile exactly ONCE."""
        sch = _scheduler(temp=0.0, max_slots=2)  # forces queueing
        for i, p in enumerate(PROMPTS * 2):
            sch.submit(p, max_new=4, seed=i)
        sch.run()
        counts = sch.compile_counts()
        assert counts["decode"] == 1, counts
        assert counts["prefill"] == 1, counts

    def test_block_churn_no_leak(self):
        sch = _scheduler(temp=0.0, max_slots=2)
        for i, p in enumerate(PROMPTS * 3):
            sch.submit(p, max_new=4, seed=i)
        sch.run()
        stats = sch.block_pool.stats()
        assert stats["used_blocks"] == 0
        assert stats["live_sequences"] == 0
        assert stats["allocs"] == stats["frees"] > 0
        assert sch.idle

    def test_prefill_chunk_overrunning_table_stays_exact(self):
        """A padded final chunk whose tail runs PAST the block table
        must route those writes to the null block — a clamped gather
        would alias the last real block and race pad garbage against
        real prompt K/V.  Geometry chosen so chunk positions exceed
        max_blocks * block_size."""
        sch = ContinuousBatchingScheduler(
            CFG,
            SchedulerConfig(
                max_slots=2, block_size=4, num_blocks=64,
                max_seq_len=24, prefill_chunk=16, temperature=0.0,
            ),
        )
        sch.sync_weights(PARAMS)
        prompt = np.arange(1, 20, dtype=np.int32)  # 19 tokens
        rid = sch.submit(prompt, max_new=5, seed=3)
        res = {r.req_id: r for r in sch.run()}
        ref = unbatched_reference(prompt, 5, 3, temp=0.0)
        np.testing.assert_array_equal(res[rid].tokens, ref)

    def test_submit_rejects_empty_prompt_and_post_drain(self):
        sch = _scheduler(temp=0.0)
        with pytest.raises(ValueError, match="at least one token"):
            sch.submit(np.array([], np.int32), max_new=2)
        sch.submit(PROMPTS[0], max_new=2, seed=0)
        sch.drain()
        with pytest.raises(RuntimeError, match="draining"):
            sch.submit(PROMPTS[0], max_new=2, seed=0)

    def test_drain_hands_back_requeueable_requests(self):
        """Drain mid-flight; a fresh scheduler serving the handed-back
        requests produces EXACTLY the uninterrupted results (the
        elastic-replica requeue contract)."""
        sch = _scheduler(temp=0.0)
        ids = [
            sch.submit(p, max_new=6, seed=50 + i)
            for i, p in enumerate(PROMPTS)
        ]
        early = []
        for _ in range(3):  # mid-flight: some prefilled, none done
            early.extend(sch.step())
        requeued = sch.drain()
        assert sch.block_pool.used_blocks == 0
        done = {r.req_id for r in early}
        assert done.union(r.req_id for r in requeued) == set(ids)
        fresh = _scheduler(temp=0.0)
        for req in requeued:
            fresh.submit(
                req.prompt, max_new=req.max_new, seed=req.seed,
                req_id=req.req_id,
            )
        res = {r.req_id: r for r in fresh.run()}
        res.update({r.req_id: r for r in early})
        for i, p in enumerate(PROMPTS):
            ref = unbatched_reference(p, 6, 50 + i, temp=0.0)
            np.testing.assert_array_equal(res[ids[i]].tokens, ref)


class TestIncrementalAllocation:
    """ISSUE 15 tentpole: watermark admission + on-demand growth +
    lowest-priority preemption + deterministic resume, and
    prefix-cached shared blocks."""

    @pytest.mark.parametrize("temp", [0.0, 0.8])
    def test_churn_at_pool_exhaustion_exact_tails(
        self, monkeypatch, temp
    ):
        """Admit/grow/preempt/resume interleavings on a pool far
        below worst-case demand: ONE compiled decode program, at
        least one real preemption, and tails EXACTLY equal to the
        unbatched reference at temp 0 and 0.8 (resume is (seed,
        position)-pure)."""
        monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        sch = ContinuousBatchingScheduler(
            CFG,
            SchedulerConfig(
                max_slots=4, block_size=4, num_blocks=9,
                max_seq_len=64, prefill_chunk=3, temperature=temp,
            ),
        )
        sch.sync_weights(PARAMS)
        ids = [
            sch.submit(p, max_new=12, seed=50 + i)
            for i, p in enumerate(PROMPTS)
        ]
        res = {r.req_id: r for r in sch.run()}
        st = sch.stats()
        assert st["preemptions"] >= 1, st
        assert st["grown_blocks"] > 0, st
        assert sch.compile_counts()["decode"] == 1
        assert st["used_blocks"] == 0  # nothing leaked
        for i, p in enumerate(PROMPTS):
            ref = unbatched_reference(p, 12, 50 + i, temp=temp)
            np.testing.assert_array_equal(res[ids[i]].tokens, ref)

    def test_pool_at_half_of_worst_case_demand_completes_everything(
        self, monkeypatch
    ):
        """More requests than lanes on a pool HALF of what the lanes'
        worst cases (prompt + budget) add up to, under the default
        admission watermark: every request completes with the
        reference's tail, through ONE compiled decode program, nothing
        leaks — and more lanes run at once than their worst cases
        would fit (admission holds a lane's prompt, not its budget).
        A request whose own worst case exceeds the pool is refused at
        ``submit``."""
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        slots, bs, max_new = 4, 4, 16
        worst = -(-(8 + max_new) // bs)  # blocks, longest prompt
        sch = ContinuousBatchingScheduler(
            CFG,
            SchedulerConfig(
                max_slots=slots, block_size=bs,
                num_blocks=slots * worst // 2 + 1,
                max_seq_len=64, prefill_chunk=8, temperature=0.0,
            ),
        )
        sch.sync_weights(PARAMS)
        rng = np.random.default_rng(23)
        prompts = [
            rng.integers(0, 97, (int(rng.integers(4, 9)),)).astype(
                np.int32
            )
            for _ in range(10)
        ]
        ids = [
            sch.submit(p, max_new=max_new, seed=500 + i)
            for i, p in enumerate(prompts)
        ]
        res, most_lanes = {}, 0
        while len(res) < len(ids):
            for r in sch.step():
                res[r.req_id] = r
            most_lanes = max(most_lanes, sch.active_count)
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(
                res[ids[i]].tokens,
                unbatched_reference(p, max_new, 500 + i, temp=0.0),
            )
        st = sch.stats()
        assert sch.compile_counts()["decode"] == 1
        assert st["used_blocks"] == 0  # nothing leaked
        assert st["grown_blocks"] > 0, st
        assert most_lanes > (slots * worst // 2) // worst, most_lanes
        with pytest.raises(ValueError, match="blocks > pool"):
            sch.submit(np.arange(1, 30, dtype=np.int32), max_new=24)

    def test_shared_system_prompt_hit_count_and_blocks_saved(self):
        """Eight requests behind one 32-token system prompt (four full
        blocks of 8) that an earlier request left in the index: every
        admission takes all four blocks from it — 32 hits of 32
        lookups — prefills only its own tail, and serves the
        reference's tokens."""
        rng = np.random.default_rng(31)
        system = rng.integers(0, 97, (32,)).astype(np.int32)
        prompts = [
            np.concatenate([
                system,
                rng.integers(
                    0, 97, (int(rng.integers(2, 7)),)
                ).astype(np.int32),
            ])
            for _ in range(8)
        ]
        sch = ContinuousBatchingScheduler(
            CFG,
            SchedulerConfig(
                max_slots=4, block_size=8, num_blocks=128,
                max_seq_len=64, prefill_chunk=8, temperature=0.0,
            ),
        )
        sch.sync_weights(PARAMS)
        sch.submit(system, max_new=2, seed=0)
        sch.run()
        before = sch.stats()
        assert before["prefix_hits"] == 0
        ids = [
            sch.submit(p, max_new=4, seed=900 + i)
            for i, p in enumerate(prompts)
        ]
        res = {r.req_id: r for r in sch.run()}
        st = sch.stats()
        assert st["prefix_hits"] == 4 * len(prompts)
        assert (
            st["prefix_queries"] - before["prefix_queries"]
            == 4 * len(prompts)
        )
        # blocks saved: nobody prefilled the system prompt again
        assert (
            st["total_prefill_tokens"] - before["total_prefill_tokens"]
            == sum(p.size - system.size for p in prompts)
        )
        assert [res[i].stats["prefix_hit_blocks"] for i in ids] == (
            [4] * len(prompts)
        )
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(
                res[ids[i]].tokens,
                unbatched_reference(p, 4, 900 + i, temp=0.0),
            )

    def test_prefix_cache_shares_blocks_exactly(self, monkeypatch):
        """Sequential requests with a common 16-token system prompt:
        later admissions map the cached physical blocks (hit rate >
        0, fewer prefill tokens) and every tail stays exact."""
        system = np.arange(1, 17, dtype=np.int32)  # 4 full blocks
        prompts = [
            np.concatenate([system, np.array([40 + i, 41 + i],
                                             np.int32)])
            for i in range(3)
        ]
        sch = _scheduler(temp=0.0)
        assert sch.prefix_cache
        for i, p in enumerate(prompts):
            rid = sch.submit(p, max_new=5, seed=70 + i)
            res = {r.req_id: r for r in sch.run()}
            np.testing.assert_array_equal(
                res[rid].tokens,
                unbatched_reference(p, 5, 70 + i, temp=0.0),
            )
        st = sch.stats()
        assert st["prefix_hits"] > 0
        assert st["prefix_hit_rate"] > 0.5
        # requests 2 and 3 skipped the shared blocks' prefill: far
        # fewer prompt tokens prefilled than 3 full prompts
        assert st["total_prefill_tokens"] < 3 * prompts[0].size

    def test_preempted_drain_hand_back_carries_resume(self,
                                                      monkeypatch):
        """Evict-then-drain (the double-free guard's race): preempt a
        sequence, drain mid-flight, and the pool must come back empty
        with every request handed back exactly once."""
        monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        sch = ContinuousBatchingScheduler(
            CFG,
            SchedulerConfig(
                max_slots=4, block_size=4, num_blocks=9,
                max_seq_len=64, prefill_chunk=3, temperature=0.0,
            ),
        )
        sch.sync_weights(PARAMS)
        ids = [
            sch.submit(p, max_new=12, seed=50 + i)
            for i, p in enumerate(PROMPTS)
        ]
        done = []
        while sch.stats()["preemptions"] == 0 and not sch.idle:
            done.extend(sch.step())
        requeued = sch.drain()  # the drain leg right after an evict
        assert sch.block_pool.used_blocks == 0
        handed = {r.req_id for r in requeued}
        finished = {r.req_id for r in done}
        assert handed | finished == set(ids)
        assert not handed & finished


class TestMultiTokenDecode:
    """ISSUE 15 tentpole: ``DLROVER_TPU_DECODE_STEPS=K`` fused
    windows — K-greedy self-drafting + one batched verify forward."""

    def _run(self, max_new=8, temp=0.0, eos=None, seeds=50):
        sch = _scheduler(temp=temp, eos=eos)
        ids = [
            sch.submit(p, max_new=max_new, seed=seeds + i)
            for i, p in enumerate(PROMPTS)
        ]
        res = {r.req_id: r for r in sch.run()}
        return sch, ids, res

    def test_k4_temp0_exact_with_fewer_dispatches(self, monkeypatch):
        """The acceptance pin: K=4 emits token streams EXACTLY equal
        to the K=1 loop while issuing measurably fewer host
        dispatches per token, still on ONE compiled decode program."""
        monkeypatch.delenv("DLROVER_TPU_DECODE_STEPS", raising=False)
        base_sch, base_ids, base_res = self._run()
        base_dispatch = base_sch.stats()["dispatches"]
        monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "4")
        sch, ids, res = self._run()
        st = sch.stats()
        assert sch.decode_k == 4
        for bid, rid in zip(base_ids, ids):
            np.testing.assert_array_equal(
                res[rid].tokens, base_res[bid].tokens
            )
        for i, p in enumerate(PROMPTS):
            np.testing.assert_array_equal(
                res[ids[i]].tokens,
                unbatched_reference(p, 8, 50 + i, temp=0.0),
            )
        assert sch.compile_counts()["decode"] == 1
        # the dispatch amortization actually happened
        assert st["dispatches"] < base_dispatch, (
            st["dispatches"], base_dispatch
        )
        assert st["accepted_per_step"] > 1.0, st

    def test_k3_temp08_eos_matches_reference(self, monkeypatch):
        """Sampled temperature + EOS early-stop under K=3: tails
        still match the unbatched reference (rejection-style
        acceptance; on CPU the verify logits agree bit-for-bit, so
        even the sampled path is exact here)."""
        temp = 0.8
        probe = unbatched_reference(PROMPTS[0], 8, 50, temp=temp)
        eos = int(probe[PROMPTS[0].size + 1])
        monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "3")
        sch, ids, res = self._run(temp=temp, eos=eos)
        for i, p in enumerate(PROMPTS):
            np.testing.assert_array_equal(
                res[ids[i]].tokens,
                unbatched_reference(p, 8, 50 + i, temp=temp,
                                    eos=eos),
            )
        assert sch.stats()["accepted_tokens"] > 0

    def test_k1_default_is_the_pr13_loop(self, monkeypatch):
        """DECODE_STEPS unset/1: no fused program is even built —
        the PR-13 one-token loop verbatim."""
        monkeypatch.delenv("DLROVER_TPU_DECODE_STEPS", raising=False)
        sch = _scheduler(temp=0.0)
        assert sch.decode_k == 1
        assert sch._decode_multi_jit is None


class TestDispatcherTieBreak:
    def test_lowest_replica_id_wins_ties(self):
        """Satellite: the least-outstanding routing tie-break is the
        LOWEST replica id, whatever order the alive list arrives in
        — bench runs and the kill-one-mid-load test reproduce across
        dict orderings."""
        from types import SimpleNamespace

        from dlrover_tpu.rl.generation_service import (
            least_outstanding,
        )

        def rep(idx, n):
            return SimpleNamespace(idx=idx, outstanding=dict.fromkeys(
                range(n)))

        a, b, c = rep(0, 2), rep(1, 1), rep(2, 1)
        for order in ([a, b, c], [c, b, a], [b, c, a]):
            assert least_outstanding(order).idx == 1
        # all equal -> replica 0
        a, b, c = rep(0, 3), rep(1, 3), rep(2, 3)
        for order in ([c, a, b], [b, a, c], [a, c, b]):
            assert least_outstanding(order).idx == 0

    def test_engine_submit_rejects_pool_exceeding_request(self):
        """Dispatcher-side mirror of the scheduler's pool guard: a
        request whose worst case exceeds a replica's whole pool must
        fail at ``ServingEngine.submit`` — raised in
        the worker loop it would kill the replica and the on-death
        redispatch would then cascade it onto the survivors."""
        import threading
        from collections import deque

        from dlrover_tpu.rl.generation_service import ServingEngine

        eng = object.__new__(ServingEngine)
        eng._closed = False
        eng._max_new = 12
        eng._max_seq_len = 64
        eng._lock = threading.Lock()
        eng._reqs = {}
        eng._dispatch_q = deque()
        eng._next_id = 0
        eng._spec = {"sched": {"num_blocks": 5, "block_size": 4}}
        prompt = np.arange(1, 8, dtype=np.int32)  # needs 5 > 4 blocks
        with pytest.raises(ValueError, match="replica pool"):
            eng.submit(prompt, max_new=12)
        assert eng.submit(prompt, max_new=8) == 0  # 4 blocks: fits

    def test_dispatcher_fails_rejected_request_immediately(self):
        """A replica-side REJECT (belt-and-suspenders for env skew /
        malformed ring messages) must complete the request with an
        error RIGHT AWAY — silence would block the caller for the
        whole request timeout."""
        import threading

        from dlrover_tpu.observability.metrics import Histogram
        from dlrover_tpu.rl import generation_service as gs

        eng = object.__new__(gs.ServingEngine)
        eng._lock = threading.Lock()
        eng._reqs = {}
        eng._completed = set()
        eng._completed_total = 0
        eng._latency = Histogram()
        inflight = gs._InFlight(
            req_id=5, prompt=np.array([1], np.int32), max_new=2,
            seed=0, submit_t=0.0,
        )
        eng._reqs[5] = inflight

        class FakeRing:
            def __init__(self):
                self.msgs = [
                    {
                        "meta": np.asarray(
                            [5, gs._KIND_REJECT, 0, 0, 0, 0],
                            np.int64,
                        ),
                        "tokens": np.zeros((4,), np.int32),
                        "times": np.zeros((8,), np.float64),
                    }
                ]

            def try_get(self):
                return self.msgs.pop(0) if self.msgs else None

        rep = gs._Replica(0, proc=None, req_ring=None,
                          resp_ring=FakeRing())
        rep.outstanding[5] = inflight
        eng._handle_responses(rep)
        assert inflight.done.is_set()
        assert not rep.outstanding
        with pytest.raises(RuntimeError, match="rejected"):
            eng.result(5, timeout=1.0)


class TestShapeBuckets:
    """Satellite: ``DLROVER_TPU_GEN_BUCKETS`` — compile once per
    bucket, results identical to the exact-shape path."""

    @pytest.mark.parametrize("temperature", [0.0, 1.0])
    def test_jit_sampler_buckets(self, monkeypatch, temperature):
        """Bucketed == exact at greedy AND at temperature > 0 (the
        batch dim is never padded, so categorical's noise is
        untouched; only causally-invisible length padding happens)."""
        from dlrover_tpu.rl.inference import JitSamplerBackend

        def fwd(p, t):
            return llama.forward(
                p, t, CFG, attention_fn=llama.dot_product_attention
            )

        rng = jax.random.PRNGKey(1)
        gen = np.random.default_rng(0)
        monkeypatch.delenv("DLROVER_TPU_GEN_BUCKETS", raising=False)
        exact = JitSamplerBackend(fwd, max_new_tokens=4,
                                  temperature=temperature)
        prompts = {
            plen: jnp.asarray(
                gen.integers(0, 97, (2, plen)), jnp.int32
            )
            for plen in (3, 5, 8, 11)
        }
        want = {
            plen: np.asarray(exact.generate(p, rng, PARAMS))
            for plen, p in prompts.items()
        }
        assert exact.compile_count() == 4  # one per distinct [B, P]

        monkeypatch.setenv("DLROVER_TPU_GEN_BUCKETS", "8,16")
        bucketed = JitSamplerBackend(fwd, max_new_tokens=4,
                                     temperature=temperature)
        for plen, p in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(bucketed.generate(p, rng, PARAMS)),
                want[plen],
            )
        # 3/5/8 share the 8-bucket, 11 lands in 16: two programs
        assert bucketed.compile_count() == 2

    def test_kv_cache_buckets(self, monkeypatch):
        from dlrover_tpu.rl.inference import KVCacheBackend

        rng = jax.random.PRNGKey(1)
        gen = np.random.default_rng(3)
        monkeypatch.delenv("DLROVER_TPU_GEN_BUCKETS", raising=False)
        exact = KVCacheBackend(CFG, max_new_tokens=4,
                               temperature=0.0)
        prompts = {
            plen: jnp.asarray(
                gen.integers(0, 97, (2, plen)), jnp.int32
            )
            for plen in (3, 5, 8)
        }
        want = {
            plen: np.asarray(exact.generate(p, rng, PARAMS))
            for plen, p in prompts.items()
        }
        assert exact.compile_count() == 3

        monkeypatch.setenv("DLROVER_TPU_GEN_BUCKETS", "8")
        bucketed = KVCacheBackend(CFG, max_new_tokens=4,
                                  temperature=0.0)
        for plen, p in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(bucketed.generate(p, rng, PARAMS)),
                want[plen],
            )
        assert bucketed.compile_count() == 1  # all in the 8-bucket

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    def test_kv_cache_scan_prefill_equals_the_batched_one(
        self, monkeypatch, temperature
    ):
        """A model that brings no prefill function feeds its prompt
        one position at a time through ``lax.scan`` (what a backend
        is told by ``prefill_fn=None``, and by nothing else): the
        same tokens as the one batched prefill forward."""
        from dlrover_tpu.rl.inference import KVCacheBackend

        monkeypatch.delenv("DLROVER_TPU_GEN_BUCKETS", raising=False)
        prompts = jnp.asarray(
            np.random.default_rng(4).integers(0, 97, (2, 6)),
            jnp.int32,
        )
        rng = jax.random.PRNGKey(1)
        batched = KVCacheBackend(
            CFG, max_new_tokens=5, temperature=temperature
        )
        scanned = KVCacheBackend(
            CFG, max_new_tokens=5, temperature=temperature,
            prefill_fn=None,
        )
        assert batched._prefill is not None
        assert scanned._prefill is None
        np.testing.assert_array_equal(
            np.asarray(scanned.generate(prompts, rng, PARAMS)),
            np.asarray(batched.generate(prompts, rng, PARAMS)),
        )


@pytest.fixture(scope="class")
def serving_engine(tmp_path_factory):
    os.environ["DLROVER_TPU_SOCKET_DIR"] = str(
        tmp_path_factory.mktemp("socks")
    )
    from dlrover_tpu.rl.generation_service import ServingEngine

    eng = ServingEngine(
        factory="dlrover_tpu.rl.generation_service:tiny_llama_factory",
        factory_kwargs=SERVE_CFG_KW,
        max_new_tokens=6,
        temperature=0.0,
        name=f"serve-test-{os.getpid()}",
        num_replicas=2,
        max_slots=4,
        block_size=4,
        num_blocks=64,
        max_seq_len=48,
        prefill_chunk=8,
    )
    yield eng
    eng.close()


class TestServingEngineElastic:
    """One engine session walks the whole elastic story: serve, weight
    publish, drain (SIGUSR1), scale-out, crash (SIGKILL) — every
    request completes exactly once throughout."""

    def test_serves_and_matches_reference(self, serving_engine):
        eng = serving_engine
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, 97, (int(rng.integers(2, 10)),)).astype(
                np.int32
            )
            for _ in range(8)
        ]
        ids = [
            eng.submit(p, max_new=6, seed=900 + i)
            for i, p in enumerate(prompts)
        ]
        res = [eng.result(rid, timeout=180.0) for rid in ids]
        used = {r["replica"] for r in res}
        assert used == {0, 1}  # both replicas actually served
        for i, (p, r) in enumerate(zip(prompts, res)):
            ref = unbatched_reference(p, 6, 900 + i, temp=0.0)
            np.testing.assert_array_equal(r["tokens"], ref)

    def test_weight_publish_reaches_replicas(self, serving_engine):
        """A shm publish changes what EVERY replica generates (the
        one-segment fan-out path)."""
        eng = serving_engine
        new_params = llama.init_params(
            jax.random.PRNGKey(123), llama.LlamaConfig(**SERVE_CFG_KW)
        )
        eng.sync_weights(new_params)
        assert eng.publish_s > 0
        prompt = np.array([4, 8, 15, 16], np.int32)
        seen = {}
        for i in range(6):  # least-loaded routing alternates
            rid = eng.submit(prompt, max_new=4, seed=7)
            res = eng.result(rid, timeout=180.0)
            seen.setdefault(res["replica"], res["tokens"])
            assert res["version"] >= 1
        for replica, toks in seen.items():
            np.testing.assert_array_equal(
                toks, next(iter(seen.values()))
            )

    def test_drain_scaleout_kill(self, serving_engine):
        eng = serving_engine
        rng = np.random.default_rng(1)
        # drain replica 0 mid-load (SIGTERM rides the same PR-9
        # handler as SIGUSR1): zero lost requests
        ids = [
            eng.submit(rng.integers(0, 97, (6,)), max_new=8,
                       seed=300 + i)
            for i in range(10)
        ]
        eng.drain_replica(0, sig=signal.SIGTERM)
        res = [eng.result(rid, timeout=180.0) for rid in ids]
        assert len(res) == 10
        status = eng.status()
        assert status["replicas"][0]["drained"] is True
        assert not status["replicas"][0]["alive"]
        # deterministic sampling: a drained-and-requeued request's
        # tail matches the reference regardless of which replica ran
        for i, r in enumerate(res):
            assert r["finish_reason"] in ("length", "eos")
        # scale out, then hard-kill mid-load: exactly-once completion
        new_idx = eng.add_replica()
        assert new_idx == 2
        ids = [
            eng.submit(rng.integers(0, 97, (6,)), max_new=8,
                       seed=400 + i)
            for i in range(10)
        ]
        eng.kill_replica(1)
        res = [eng.result(rid, timeout=180.0) for rid in ids]
        assert len(res) == len(set(ids)) == 10
        status = eng.status()
        assert status["queue_depth"] == 0
        assert status["replicas"][1]["alive"] is False
        assert status["replicas"][2]["alive"] is True


@pytest.fixture(scope="class")
def one_replica_engine(tmp_path_factory):
    """A one-replica engine with a registry of its own: what an RLHF
    trainer holds (``examples/rlhf_ppo.py --cross_process``)."""
    from dlrover_tpu.observability.metrics import (
        MetricsRegistry,
        set_default_registry,
    )
    from dlrover_tpu.rl.generation_service import ServingEngine

    os.environ["DLROVER_TPU_SOCKET_DIR"] = str(
        tmp_path_factory.mktemp("sk1")
    )
    reg = MetricsRegistry(
        path=str(tmp_path_factory.mktemp("reg1") / "m.prom")
    )
    set_default_registry(reg)
    eng = ServingEngine(
        factory="dlrover_tpu.rl.generation_service:tiny_llama_factory",
        factory_kwargs=SERVE_CFG_KW,
        max_new_tokens=4,
        temperature=0.0,
        name=f"serve-one-{os.getpid()}",
        num_replicas=1,
        max_slots=4,
        block_size=4,
        num_blocks=64,
        max_seq_len=48,
        prefill_chunk=8,
    )
    yield eng, reg
    eng.close()
    set_default_registry(MetricsRegistry())


def _adoptions(eng, seed0, timeout=60.0):
    """Serve until a replica STATS row (sent once a second, while the
    replica steps) has reached ``status()``; returns the row."""
    stamp = eng._replicas[0].stats
    deadline = time.monotonic() + timeout
    i = 0
    while time.monotonic() < deadline:
        rid = eng.submit(
            np.array([4, 8, 15, 16], np.int32), max_new=4,
            seed=seed0 + i,
        )
        eng.result(rid, timeout=180.0)
        i += 1
        if eng._replicas[0].stats is not stamp:
            return eng.status()["replicas"][0]
    raise AssertionError("no STATS row reached the dispatcher")


def _publish(eng, key):
    """A policy of the case's own, published; returns its version.  A
    case stands on this and not on what an earlier case left."""
    eng.sync_weights(
        llama.init_params(
            jax.random.PRNGKey(key), llama.LlamaConfig(**SERVE_CFG_KW)
        )
    )
    return eng.status()["version"]


class TestServingEngineWholeBatchSurface:
    """``generate`` / ``sync_weights`` on one engine session, in the
    order a trainer meets them; the kill comes last.  Every case
    publishes for itself: one that fails leaves the others green."""

    def test_a_publish_reaches_generate(self, one_replica_engine):
        """Two different policies published through shm: greedy
        generations equal a local sampler's with the same weights
        (exact cross-process weight fidelity), version by version."""
        from dlrover_tpu.rl.generation_service import (
            tiny_llama_factory,
        )
        from dlrover_tpu.rl.inference import JitSamplerBackend

        eng, _ = one_replica_engine
        cfg = llama.LlamaConfig(**SERVE_CFG_KW)
        local = JitSamplerBackend(
            tiny_llama_factory(**SERVE_CFG_KW)["forward_fn"],
            max_new_tokens=4, temperature=0.0,
        )
        prompts = np.array([[5, 9, 2], [11, 3, 7]], np.int32)
        for i, key in enumerate((1, 42)):
            params = llama.init_params(jax.random.PRNGKey(key), cfg)
            eng.sync_weights(params)
            assert eng.publish_s > 0
            np.testing.assert_array_equal(
                eng.generate(prompts, seed=0),
                np.asarray(local.generate(
                    jnp.asarray(prompts), jax.random.PRNGKey(0),
                    params=params,
                )),
            )
            assert eng.status()["version"] == i + 1

    def test_an_unchanged_version_adopts_nothing(
        self, one_replica_engine
    ):
        """No new publish: the replica's adoption count stays where
        the publishes left it (one a version), over a second of traffic
        and more, and finding that out costs no meta RPC (the generation
        side-segment); the same request gives the same tokens."""
        eng, _ = one_replica_engine
        version = _publish(eng, 7)
        row = _adoptions(eng, seed0=2000)
        assert row["adoptions"] == version, row
        prompts = np.array([[1, 2]], np.int32)
        first = eng.generate(prompts, seed=0)
        again = _adoptions(eng, seed0=3000)
        assert again["adoptions"] == version, again
        assert again["meta_rpcs"] == row["meta_rpcs"], (row, again)
        np.testing.assert_array_equal(
            first, eng.generate(prompts, seed=0)
        )

    def test_status_of_a_one_replica_engine(self, one_replica_engine):
        """The pane has the SLO quantiles and the health rows
        whatever the fleet's size, and the replica's SLO series are
        in the registry."""
        eng, reg = one_replica_engine
        _publish(eng, 8)
        eng.generate(np.arange(12, dtype=np.int32).reshape(6, 2), seed=0)
        status = eng.status()
        assert set(status) == {
            "replicas", "queue_depth", "completed", "p50_latency_s",
            "p99_latency_s", "version", "slo", "health",
        }
        assert status["replicas"][0]["role"] == "decode"
        assert status["slo"]["ttft_p99_s"] > 0
        assert status["completed"] >= 6
        assert reg.histogram(
            "dlrover_tpu_serving_ttft_seconds",
            labels={"replica": "0"},
        ).count == status["completed"]

    def test_a_stopped_replica_trips_the_request_timeout(
        self, one_replica_engine, monkeypatch
    ):
        """``DLROVER_TPU_GEN_TIMEOUT_S`` is what a call waits for a
        replica that is alive and silent (SIGSTOP), not 600 s; the
        request is served once the replica runs again."""
        eng, _ = one_replica_engine
        proc = eng._replicas[0].proc
        monkeypatch.setenv("DLROVER_TPU_GEN_TIMEOUT_S", "2")
        proc.send_signal(signal.SIGSTOP)
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError, match="within 2"):
                eng.generate(np.array([[5, 9, 2]], np.int32), seed=0)
            assert time.monotonic() - t0 < 30
        finally:
            proc.send_signal(signal.SIGCONT)
        monkeypatch.delenv("DLROVER_TPU_GEN_TIMEOUT_S")
        assert eng.generate(
            np.array([[5, 9, 2]], np.int32), seed=0
        ).shape == (1, 7)

    def test_a_killed_replica_fails_the_call_at_once(
        self, one_replica_engine
    ):
        """The only replica SIGKILLed: ``generate`` raises with what
        happened now, not after the 600 s request timeout."""
        eng, _ = one_replica_engine
        eng.kill_replica(0)
        eng._replicas[0].proc.wait(timeout=30)
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="no replica is alive"):
            eng.generate(np.array([[1, 2]], np.int32), seed=0)
        assert time.monotonic() - t0 < 30


class TestTopServingPane:
    def test_render_shows_serving_pane(self):
        sys.path.insert(
            0,
            os.path.join(
                os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))
                ),
                "scripts",
            ),
        )
        import top

        frame = top.render(
            {
                "health": {"job": "j", "nodes": []},
                "ledger": {"goodput": 0.5},
                "serving": {
                    "queue_depth": 3,
                    "completed": 41,
                    "p50_latency_s": 0.1,
                    "p99_latency_s": 0.9,
                    "version": 2,
                    "replicas": [
                        {"idx": 0, "alive": True, "outstanding": 4,
                         "tokens_per_s": 120.5, "queue_depth": 1,
                         "kv_blocks_used": 17,
                         "kv_utilization": 0.62,
                         "preemptions": 3,
                         "prefix_hit_rate": 0.254},
                        {"idx": 1, "alive": False, "drained": True,
                         "outstanding": 0},
                    ],
                },
            }
        )
        assert "serving: queue 3" in frame
        assert "p99 0.900s" in frame
        assert "drained" in frame
        assert "120.5" in frame
        # ISSUE-15 columns: utilization / preemptions / prefix hits
        assert "kvutil" in frame and "preempt" in frame
        assert "0.62" in frame
        assert "25.4%" in frame
