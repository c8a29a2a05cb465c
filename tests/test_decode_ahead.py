"""The plain decode loop one step ahead of the host (``rl/scheduler.py``).

Step n+1 is dispatched from the device's own token vector before step
n's tokens are read.  Every case compares the run-ahead scheduler,
request by request, with the SAME scheduler held in lockstep — the
commit-first rule of ``step()`` forced here by naming a cause
(``_sync_cause``), as multi-token decode names one; there is no product
switch — over the tiny dense model and the tiny Falcon-H1, whose lanes
keep a recurrent state beside their pages.
"""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import tiny_families as T  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.observability import events as ev  # noqa: E402
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    FINISH_EOS,
    FINISH_LENGTH,
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

SCHED = dict(
    max_slots=3, block_size=4, num_blocks=64, max_seq_len=64,
    prefill_chunk=8, temperature=1.0,
)
# (prompt length, max_new): mixed, one chunk and several, one token only
TRAFFIC = ((5, 9), (19, 4), (8, 12), (11, 1), (6, 7), (14, 10), (7, 2))

DENSE_CFG = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none", dtype=jnp.float32,
)
FALCON = T.parts("falcon_h1", 128)


class _Model:
    def __init__(self, name):
        self.name = name
        if name == "dense":
            self.cfg, self.kw, self.vocab = DENSE_CFG, {}, 97
            self.params = [
                llama.init_params(jax.random.PRNGKey(s), DENSE_CFG)
                for s in (0, 1)
            ]
        else:
            self.cfg, self.vocab = FALCON["cfg"], FALCON["cfg"].vocab_size
            self.kw = {
                k: FALCON[k] for k in (
                    "paged_decode_fn", "paged_prefill_fn",
                    "serving_params_fn",
                )
            }
            self.params = [
                T.params("falcon_h1", 2**31 + s) for s in (11, 12)
            ]

    def scheduler(self, lockstep=False, events=None, **overrides):
        sch = ContinuousBatchingScheduler(
            self.cfg, SchedulerConfig(**dict(SCHED, **overrides)),
            capture_logprobs=True, events=events, **self.kw,
        )
        if lockstep:
            # what ``decode_k > 1`` does by construction: every decode
            # step first commits what is in flight
            sch._sync_cause = "test"
        sch.sync_weights(self.params[0])
        return sch

    def prompts(self, traffic=TRAFFIC, seed=3):
        rng = np.random.default_rng(seed)
        return [
            rng.integers(0, self.vocab, size=plen).astype(np.int32)
            for plen, _ in traffic
        ]

    def submit(self, sch, traffic=TRAFFIC, only=None):
        for i, prompt in enumerate(self.prompts(traffic)):
            if only is None or i == only:
                sch.submit(prompt, max_new=traffic[i][1], seed=50 + i)


MODELS = {}


@pytest.fixture(params=["dense", "falcon_h1"])
def model(request):
    if request.param not in MODELS:
        MODELS[request.param] = _Model(request.param)
    return MODELS[request.param]


@pytest.fixture(autouse=True)
def _exact_float32():
    with jax.default_matmul_precision("highest"):
        yield


def by_id(results):
    got = {r.req_id: r for r in results}
    assert len(got) == len(results), "a request was served twice"
    return got


def assert_same(got, want, atol=0.0):
    assert sorted(got) == sorted(want)
    for i in want:
        assert got[i].finish_reason == want[i].finish_reason, i
        np.testing.assert_array_equal(got[i].tokens, want[i].tokens)
        assert got[i].new_tokens == want[i].new_tokens
        assert got[i].logprobs.shape == (got[i].new_tokens,)
        if atol:
            np.testing.assert_allclose(
                got[i].logprobs, want[i].logprobs, atol=atol
            )
        else:
            np.testing.assert_array_equal(
                got[i].logprobs, want[i].logprobs
            )


def serve_both(model, traffic=TRAFFIC, **overrides):
    """The same traffic through the run-ahead scheduler and through
    the one in lockstep; returns both schedulers and both result maps."""
    out = []
    for lockstep in (False, True):
        sch = model.scheduler(lockstep=lockstep, **overrides)
        model.submit(sch, traffic)
        out += [sch, by_id(sch.run())]
    return out


def an_eos(model, first):
    """A token id that ends some request of TRAFFIC early: its first
    token (``first``) or one in the middle of its tail, found in a run
    without an EOS."""
    sch = model.scheduler(lockstep=True)
    model.submit(sch)
    tails = {
        i: r.tokens[TRAFFIC[i][0]:] for i, r in by_id(sch.run()).items()
    }
    for i, tail in sorted(tails.items()):
        if first and len(tail) > 3:
            return int(tail[0])
        if not first and len(tail) > 5:
            # not some request's first token: that is the other case
            firsts = {int(t[0]) for t in tails.values()}
            for tok in tail[2:-2]:
                if int(tok) not in firsts:
                    return int(tok)
    raise AssertionError("no token fits")


# ---------------------------------------------------------------- churn


def test_mixed_lengths_finish_by_length(model):
    ahead, got, lock, want = serve_both(model)
    assert_same(got, want)
    assert sorted(got) == list(range(len(TRAFFIC)))
    for i, (plen, max_new) in enumerate(TRAFFIC):
        assert got[i].finish_reason == FINISH_LENGTH
        assert got[i].new_tokens == max_new
        assert got[i].tokens.size == plen + max_new
    assert ahead.compile_counts()["decode"] == 1
    assert lock.compile_counts()["decode"] == 1
    st, ref = ahead.stats(), lock.stats()
    assert st["ahead_steps"] > 0 and st["sync_steps"] == {}
    assert st["overrun_tokens"] == 0
    assert ref["ahead_steps"] == 0 and ref["sync_steps"]["test"] > 0
    assert st["total_new_tokens"] == sum(m for _, m in TRAFFIC)
    assert ahead.idle and lock.idle


def test_eos_mid_batch_discards_the_overrun_step(model):
    eos = an_eos(model, first=False)
    ahead, got, lock, want = serve_both(model, eos_id=eos)
    assert_same(got, want)
    ended = [i for i in got if got[i].finish_reason == FINISH_EOS]
    assert ended
    for i in ended:
        tail = got[i].tokens[TRAFFIC[i][0]:]
        # the reply ends AT the EOS: the step computed past it is gone
        assert int(tail[-1]) == eos and eos not in tail[:-1].tolist()
        assert got[i].new_tokens == tail.size < TRAFFIC[i][1]
    st = ahead.stats()
    assert st["overrun_tokens"] >= len(ended)
    assert lock.stats()["overrun_tokens"] == 0
    assert st["total_new_tokens"] == sum(r.new_tokens for r in got.values())
    assert ahead.compile_counts()["decode"] == 1


def test_eos_on_the_first_token(model):
    eos = an_eos(model, first=True)
    ahead, got, lock, want = serve_both(model, eos_id=eos)
    assert_same(got, want)
    at_once = [
        i for i in got
        if got[i].finish_reason == FINISH_EOS and got[i].new_tokens == 1
    ]
    assert at_once
    # its decode steps were dispatched before the token was read
    assert ahead.stats()["overrun_tokens"] >= len(at_once)
    assert ahead.idle


def test_a_preemption_commits_first(model, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
    monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
    traffic = ((6, 22), (5, 24), (7, 20), (6, 18))
    _, calm, _, _ = serve_both(model, traffic)
    ahead, got, lock, want = serve_both(model, traffic, num_blocks=13)
    assert ahead.preemptions > 0 and lock.preemptions > 0
    assert ahead.stats()["sync_steps"].get("preempt", 0) > 0
    # a resumed sequence re-prefills its tail: the same tokens, the
    # logprobs through another program
    assert_same(got, want, atol=5e-5)
    assert_same(got, calm, atol=5e-5)
    assert ahead.compile_counts()["decode"] == 1


def test_sync_weights_with_a_step_in_flight(model):
    # one request a lane, so that a token's iteration (and with it the
    # weights it is sampled under) is the same in both loops
    traffic = ((5, 9), (19, 6), (8, 12))

    def drive(lockstep):
        sch = model.scheduler(lockstep=lockstep)
        model.submit(sch, traffic)
        out = []
        for _ in range(5):
            out += sch.step()
        assert sch._inflight
        sch.sync_weights(model.params[1])
        assert not sch._inflight
        assert sch.stats()["sync_steps"]["sync_weights"] == 1
        return sch, by_id(out + sch.run())

    ahead, got = drive(False)
    lock, want = drive(True)
    assert_same(got, want)
    # the new weights were served: not what the old ones alone give
    _, old, _, _ = serve_both(model, traffic)
    assert any(
        not np.array_equal(got[i].tokens, old[i].tokens) for i in got
    )


def test_drain_with_a_step_in_flight_hands_back_whole_tails(model):
    _, calm, _, _ = serve_both(model)
    sch = model.scheduler()
    model.submit(sch)
    out = []
    for _ in range(6):
        out += sch.step()
    assert sch._inflight
    sampled = sch.total_new_tokens + sum(
        sl.ahead for sl in sch._slots
    )
    handed = sch.drain()
    assert not sch._inflight
    assert sch.stats()["sync_steps"]["drain"] == 1
    out += sch.settle()  # what the commit finished
    done = by_id(out)
    assert not set(done) & {r.req_id for r in handed}
    assert sorted(set(done) | {r.req_id for r in handed}) == list(
        range(len(TRAFFIC))
    )
    # every token sampled so far is in a reply or in a tail
    assert sampled == sum(r.new_tokens for r in done.values()) + sum(
        r.resume_tokens.size for r in handed
    )
    for r in handed:
        plen = TRAFFIC[r.req_id][0]
        np.testing.assert_array_equal(
            r.resume_tokens,
            calm[r.req_id].tokens[plen:plen + r.resume_tokens.size],
        )
        assert r.resume_logprobs.size == r.resume_tokens.size
    # resumed elsewhere, each request ends as if nothing had happened
    other = model.scheduler()
    for r in handed:
        other.submit(
            r.prompt, max_new=r.max_new, seed=r.seed, req_id=r.req_id,
            resume_tokens=r.resume_tokens,
            resume_logprobs=r.resume_logprobs,
        )
    done.update(by_id(other.run()))
    assert_same(done, calm, atol=5e-5)


def test_idle_is_false_while_a_step_is_uncommitted(model):
    eos = an_eos(model, first=False)
    sch = model.scheduler(eos_id=eos)
    model.submit(sch)
    out = []
    for _ in range(200):
        if sch.idle:
            break
        out += sch.step()
        assert not (sch._inflight and sch.idle)
    assert sch.idle and not sch._inflight
    assert len(by_id(out)) == len(TRAFFIC)
    # one request alone, ended by EOS: its overrun step outlives it
    alone = model.scheduler(eos_id=eos)
    i = next(
        i for i, r in by_id(out).items()
        if r.finish_reason == FINISH_EOS and r.new_tokens > 1
    )
    model.submit(alone, only=i)
    res = []
    while not res:
        res = alone.step()
    assert res[0].finish_reason == FINISH_EOS
    assert alone._inflight and not alone.active_count
    assert not alone.idle
    assert alone.run() == [] and alone.idle
    assert alone.stats()["overrun_tokens"] == 1


def test_records_agree_with_the_counters(model, tmp_path):
    eos = an_eos(model, first=False)
    path = str(tmp_path / "events.jsonl")
    sch = model.scheduler(
        events=ev.EventLogger(path=path, job="ahead"), eos_id=eos
    )
    model.submit(sch)
    got = by_id(sch.run())
    events = ev.read_events(path)
    steps = [e["labels"] for e in events if e["name"] == "serve_step"]
    served = [e["labels"] for e in events if e["name"] == "serve_request"]
    assert sorted(s["req_id"] for s in served) == sorted(got)
    for labels in steps:
        assert 0 <= labels["lanes_ahead"] <= labels["lanes_decode"]
        assert labels["lanes_decode"] <= labels["slots"]
        assert labels["overrun_tokens"] >= 0
    st = sch.stats()
    assert st["ahead_steps"] == sum(s["lanes_ahead"] > 0 for s in steps)
    assert st["overrun_tokens"] == sum(s["overrun_tokens"] for s in steps)
    assert st["overrun_tokens"] > 0 and st["sync_steps"] == {}
    # every lane-step dispatched was committed or counted as overrun
    assert sum(s["lanes_decode"] for s in steps) == sum(
        s["new_tokens"] for s in steps
    ) + st["overrun_tokens"]
    # in this traffic nothing forces a commit first: every decode lane
    # ran ahead
    assert sum(s["lanes_ahead"] for s in steps) == sum(
        s["lanes_decode"] for s in steps
    )
    assert sch.compile_counts()["decode"] == 1


# ------------------------------------------------------ the serving copy


def _three_leaf_programs(model):
    """The model's step programs injected WITHOUT its ``serving_params``
    rule: the scheduler then serves the tree it is given, ``wq``,
    ``wk``, ``wv`` apart, through the programs' three-projection
    branch."""
    if model.name == "dense":
        return {
            kw: partial(getattr(llama, fn), cfg=model.cfg)
            for kw, fn in (
                ("paged_decode_fn", "paged_decode_step"),
                ("paged_prefill_fn", "paged_prefill_chunk"),
                ("paged_verify_fn", "paged_verify_step"),
            )
        }
    return {
        k: v for k, v in model.kw.items() if k != "serving_params_fn"
    }


def test_run_ahead_serves_the_same_from_the_fused_copy(model):
    """The loop one step ahead on the scheduler's own serving copy
    (``wqkv``: one projection and a split) against the same loop on the
    training tree: every request's tokens equal and its logprobs to
    float32 rounding — in the float32 of this file there is no cast, so
    the copy is the fusion alone, and the CPU's float32 matmul blocks a
    ``[D, 3D]`` product otherwise than three ``[D, D]`` ones (in the
    cells' bfloat16 the programs agree to the bit:
    ``tests/test_serving_weight_cast.py``, ``tests/test_falcon_h1.py``)."""
    fused = model.scheduler()
    assert "wqkv" in fused._params["layers"]
    assert not {"wq", "wk", "wv"} & set(fused._params["layers"])
    apart = ContinuousBatchingScheduler(
        model.cfg, SchedulerConfig(**SCHED), capture_logprobs=True,
        **_three_leaf_programs(model),
    )
    apart.sync_weights(model.params[0])
    assert apart._params is model.params[0]
    results = []
    for sch in (fused, apart):
        model.submit(sch)
        results.append(by_id(sch.run()))
        assert sch.stats()["ahead_steps"] > 0
    assert_same(*results, atol=5e-6)


def test_an_adoption_with_a_step_in_flight_compiles_no_second_copy(model):
    """``sync_weights`` mid-run: the step in flight is committed, the
    previous serving copy dropped, and the new one made by the program
    the first adoption compiled — the module-level jitted copy has
    nothing new to trace for a tree of the same shapes."""
    from dlrover_tpu.common.jax_env import CompileMeter

    sch = model.scheduler()
    model.submit(sch)
    for _ in range(4):
        sch.step()
    assert sch._inflight
    first = sch._params["layers"]["wqkv"]
    programs = llama._cast_and_fuse._cache_size()
    meter = CompileMeter()
    sch.sync_weights(model.params[1])
    assert meter.snapshot()["compile_s"] == 0
    assert llama._cast_and_fuse._cache_size() == programs
    assert sch._params["layers"]["wqkv"] is not first
    assert len(sch.run()) == len(TRAFFIC)
