"""Fleet-level serving (ISSUE 17): SLO-class lanes and disaggregated
KV block shipping.

The contracts pinned here (ISSUE 17 acceptance):

- class-aware preemption evicts batch lanes before interactive ones
  at equal KV pressure, never the reverse; within a class the victim
  is the lane with the fewest generated tokens, the youngest first;
- shipped block regions are bitwise the prefill worker's pool
  content, so a decode continuation over an adopted prefill equals
  the lone-scheduler reference token for token;
- adoption never retraces the decode program
  (``compile_counts()["decode"] == 1`` stays true across it);
- an interactive request is admitted before every batch request that
  was queued when it arrived; what a prefill worker ships is what the
  decode side adopts, one for one.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    BlockPool,
    PagedCacheConfig,
    extract_block_regions,
    init_block_pool,
    insert_block_regions,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

CFG = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none", dtype=jnp.float32,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)


@jax.jit
def _last_logits(tokens):
    """The whole forward of one sequence, one program a length (called
    eagerly it compiled every operation anew for every length)."""
    return llama.forward(
        params=PARAMS,
        tokens=tokens,
        cfg=CFG,
        attention_fn=llama.dot_product_attention,
    )[0, -1]


def unbatched_reference(prompt, max_new):
    """Greedy lone-sequence full-forward loop — the ground truth any
    scheduling/shipping path must be invisible against."""
    toks = list(int(t) for t in prompt)
    for _ in range(max_new):
        logits = _last_logits(jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits)))
    return np.asarray(toks, np.int32)


def _scheduler(role="unified", max_slots=4, num_blocks=64,
               prefill_chunk=3, block_size=4):
    sch = ContinuousBatchingScheduler(
        CFG,
        SchedulerConfig(
            max_slots=max_slots, block_size=block_size,
            num_blocks=num_blocks, max_seq_len=64,
            prefill_chunk=prefill_chunk, temperature=0.0,
        ),
        role=role,
    )
    sch.sync_weights(PARAMS)
    return sch


def _slot_of(sch, slo_class):
    for i, sl in enumerate(sch._slots):
        if sl.req is not None and sl.req.slo_class == slo_class:
            return i
    raise AssertionError(f"no active {slo_class} slot")


class TestClassAwarePreemption:
    """The victim rule: class first, then the PR-14 rule."""

    def _age_batch_then_admit_interactive(self):
        """Batch lane with a long generated tail, interactive lane
        freshly admitted — the configuration where the PR-14 rule
        (fewest generated) and the class-aware rule disagree."""
        sch = _scheduler(max_slots=2)
        sch.submit(np.array([5, 9, 2], np.int32), max_new=12,
                   seed=1, slo_class="batch", tenant="bulk")
        for _ in range(6):  # prefill + grow the batch tail
            sch.step()
        sch.submit(np.array([7, 1], np.int32), max_new=12,
                   seed=2, slo_class="interactive", tenant="chat")
        for _ in range(2):  # admit + first tokens
            sch.step()
        assert sch._slots[_slot_of(sch, "batch")].generated
        return sch

    def test_victim_is_batch_not_interactive(self):
        """The interactive lane has FEWER generated tokens (the
        within-class victim), but the batch lane must be evicted —
        batch outranks interactive as a victim, never the reverse."""
        sch = self._age_batch_then_admit_interactive()
        b, i = _slot_of(sch, "batch"), _slot_of(sch, "interactive")
        assert len(sch._slots[i].generated) < len(
            sch._slots[b].generated
        )
        assert sch._pick_victim(exclude=-1) == b

    def test_within_a_class_the_victim_is_fewest_generated(self):
        """Three batch lanes of different ages: the victim is the one
        with the fewest generated tokens, and between two lanes that
        tie, the one admitted last."""
        sch = _scheduler(max_slots=3)
        sch.submit(np.array([5, 9, 2], np.int32), max_new=12, seed=1)
        for _ in range(5):  # an old lane with a tail
            sch.step()
        sch.submit(np.array([7, 1], np.int32), max_new=12, seed=2)
        sch.submit(np.array([8, 4], np.int32), max_new=12, seed=3)
        for _ in range(2):  # both prefilled in turn, then decoding
            sch.step()
        lanes = {
            sl.req.req_id: (i, sl)
            for i, sl in enumerate(sch._slots) if sl.req is not None
        }
        assert len(lanes) == 3
        old, mid, young = (lanes[r] for r in sorted(lanes))
        assert len(old[1].generated) > len(mid[1].generated)
        expect = min(
            (mid, young),
            key=lambda t: (len(t[1].generated), -t[1].admit_seq),
        )[0]
        assert sch._pick_victim(exclude=-1) == expect
        if len(mid[1].generated) == len(young[1].generated):
            assert expect == young[0]  # the tie goes to the youngest
        # the chosen lane itself is never offered
        assert sch._pick_victim(exclude=expect) != expect

    def test_preemption_churn_matches_reference(self, monkeypatch):
        """Mixed-class traffic through a pool small enough to force
        preemption: every tail still equals the lone-sequence greedy
        reference (restart-from-prompt is deterministic), and batch
        lanes actually got preempted."""
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
        sch = _scheduler(max_slots=4, num_blocks=9)
        rng = np.random.default_rng(3)
        prompts = [
            rng.integers(0, 97, (int(rng.integers(2, 8)),)).astype(
                np.int32
            )
            for _ in range(6)
        ]
        ids = [
            sch.submit(
                p, max_new=12, seed=60 + i,
                slo_class=("interactive" if i % 3 == 0 else "batch"),
                tenant=f"t{i % 2}",
            )
            for i, p in enumerate(prompts)
        ]
        res = {r.req_id: r for r in sch.run()}
        assert sch.preemptions > 0
        for rid, p in zip(ids, prompts):
            np.testing.assert_array_equal(
                res[rid].tokens, unbatched_reference(p, 12)
            )


class TestKVBlockShipping:
    def test_extract_insert_roundtrip_bitwise(self):
        """Tiles pulled from one pool and spliced into another at
        DIFFERENT block ids are bit-exact, and untouched blocks of
        the receiving pool keep their bytes."""
        cache_cfg = PagedCacheConfig(
            n_layers=2, n_kv_heads=2, head_dim=8, num_blocks=10,
            block_size=4, dtype=jnp.float32,
        )
        rng = np.random.default_rng(7)
        shape = init_block_pool(cache_cfg)["k"].shape
        src = {
            "k": jnp.asarray(rng.normal(size=shape), jnp.float32),
            "v": jnp.asarray(rng.normal(size=shape), jnp.float32),
        }
        dst = {
            "k": jnp.asarray(rng.normal(size=shape), jnp.float32),
            "v": jnp.asarray(rng.normal(size=shape), jnp.float32),
        }
        before = {n: np.asarray(a) for n, a in dst.items()}
        for src_ids, dst_ids in (
            ([3], [7]),                      # single block
            ([1, 4, 5], [2, 8, 9]),          # multi, non-contiguous
        ):
            k, v = extract_block_regions(src, src_ids)
            np.testing.assert_array_equal(
                k, np.asarray(src["k"])[:, src_ids]
            )
            out = insert_block_regions(dst, dst_ids, k, v)
            for name, region in (("k", k), ("v", v)):
                got = np.asarray(out[name])
                assert (
                    got[:, dst_ids].tobytes() == region.tobytes()
                ), "shipped tiles must be bitwise-identical"
                untouched = [
                    b for b in range(10) if b not in dst_ids
                ]
                np.testing.assert_array_equal(
                    got[:, untouched], before[name][:, untouched]
                )

    def test_adopted_decode_matches_reference_compile_once(self):
        """End-to-end disaggregation in-process: a prefill-role
        scheduler fills and ships the KV blocks, a second scheduler
        adopts them and decodes.  The adopted tail equals the
        lone-scheduler greedy reference (the ship is invisible), and
        the decode program of the adopting scheduler stays at ONE
        compile even while local requests interleave."""
        prompt = np.array(
            [11, 3, 7, 8, 1, 2, 9, 30, 31], np.int32
        )
        pre = _scheduler(role="prefill", max_slots=2)
        rid = pre.submit(prompt, max_new=6, seed=5)
        for _ in range(20):
            pre.step()
            if pre.shipped:
                break
        assert len(pre.shipped) == 1
        payload = pre.shipped.pop()
        assert payload["req_id"] == rid
        assert payload["n_blocks"] == len(prompt) // 4 + 1

        dec = _scheduler(role="unified", max_slots=2)
        # a local request first, so adoption lands in a scheduler
        # whose decode program is already compiled and batched
        local = dec.submit(
            np.array([5, 9, 2], np.int32), max_new=6, seed=50
        )
        dec.step()
        adopted = dec.submit(
            prompt, max_new=6, seed=5,
            shipped={
                "k": payload["k"],
                "v": payload["v"],
                "first_token": payload["first_token"],
            },
        )
        res = {r.req_id: r for r in dec.run()}
        assert dec.shipped_in == 1
        np.testing.assert_array_equal(
            res[adopted].tokens, unbatched_reference(prompt, 6)
        )
        np.testing.assert_array_equal(
            res[local].tokens,
            unbatched_reference(np.array([5, 9, 2], np.int32), 6),
        )
        assert dec.compile_counts()["decode"] == 1


    def test_every_shipped_prefill_is_adopted_with_its_tail(self):
        """Five prompts through a prefill worker and into a decode
        scheduler of two lanes: ``shipped_out == shipped_in``, every
        adopted tail is the reference's, the full prompt blocks that
        came in are indexed for later prompts, and nothing is left in
        either pool."""
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(0, 97, (int(rng.integers(5, 14)),)).astype(
                np.int32
            )
            for _ in range(5)
        ]
        pre = _scheduler(role="prefill", max_slots=2)
        ids = [
            pre.submit(p, max_new=6, seed=5 + i)
            for i, p in enumerate(prompts)
        ]
        for _ in range(60):
            pre.step()
            if len(pre.shipped) == len(prompts):
                break
        assert pre.shipped_out == len(prompts)
        assert pre.idle and pre.block_pool.used_blocks == 0
        shipped = {rec["req_id"]: rec for rec in pre.shipped}
        dec = _scheduler(role="unified", max_slots=2)
        for rid, p in zip(ids, prompts):
            rec = shipped[rid]
            assert rec["n_blocks"] == -(-p.size // 4)
            dec.submit(
                p, max_new=6, seed=5 + rid, req_id=rid,
                shipped={k: rec[k] for k in ("k", "v", "first_token")},
            )
        res = {r.req_id: r for r in dec.run()}
        assert dec.shipped_in == pre.shipped_out == len(prompts)
        for rid, p in zip(ids, prompts):
            np.testing.assert_array_equal(
                res[rid].tokens, unbatched_reference(p, 6)
            )
        assert dec.compile_counts()["decode"] == 1
        assert dec.stats()["total_prefill_tokens"] == 0
        assert dec.block_pool.used_blocks == 0
        assert dec.block_pool.cached_shared_blocks == sum(
            p.size // 4 for p in prompts
        )


class TestAdmissionLanes:
    def test_interactive_is_admitted_before_queued_batch(self):
        """Two lanes busy with batch work and three more batch
        requests queued when an interactive request arrives: it is
        admitted before all three (its ``admit_seq`` is the next one
        given out), and everything still completes."""
        sch = _scheduler(max_slots=2)
        for i in range(5):
            sch.submit(np.array([5, 9, 2 + i], np.int32), max_new=6,
                       seed=i, slo_class="batch", tenant="bulk")
        sch.step()
        assert sch.active_count == 2 and sch.queue_depth == 3
        chat = sch.submit(np.array([7, 1], np.int32), max_new=6,
                          seed=9, slo_class="interactive",
                          tenant="chat")
        order = []
        done = []
        while not sch.idle:
            done.extend(sch.step())
            for sl in sorted(
                (sl for sl in sch._slots if sl.req is not None),
                key=lambda sl: sl.admit_seq,
            ):
                if sl.req.req_id not in order:
                    order.append(sl.req.req_id)
        assert order[:2] == [0, 1]
        assert order[2] == chat, order
        assert sorted(order) == sorted(r.req_id for r in done)
        assert len(done) == 6 and sch._queued_interactive == 0

    def test_on_admits_interactive_first(self):
        sch = _scheduler()
        sch.submit(np.array([5, 9, 2], np.int32), max_new=2, seed=1,
                   slo_class="batch", tenant="bulk")
        sch.submit(np.array([8, 4], np.int32), max_new=2, seed=2,
                   slo_class="batch", tenant="bulk")
        sch.submit(np.array([7, 1], np.int32), max_new=2, seed=3,
                   slo_class="interactive", tenant="chat")
        assert sch._queue[2].slo_class == "interactive"
        assert sch._pick_next_index() == 2
        assert sch._queued_interactive == 1
        sch.run()
        assert sch._queued_interactive == 0
