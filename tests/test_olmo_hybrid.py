"""Olmo-Hybrid (``models/olmo_hybrid.py``: gated delta-rule layers that
keep a state and no keys, between full-attention layers that keep keys
and no state) on the serving plane, at tiny sizes on the CPU.

The chain of evidence: the benchmark's plain reference (the recurrence
token by token, no cache) = the program's whole-sequence forward (the
chunked scan) = its step programs driven by hand on LOGITS (prefill in
chunks, then decode through the paged cache) = what the scheduler
serves with the state of the linear layers and the pages of the full
layer in one pool.  Head sizes are not powers of two (12 x 24; 3 KV
heads of 24), so the state is packed and the pages lie flat.

Tolerances: float32 weights and compute on both sides; what differs is
the order of the sums (sub-chunks of 64 against token by token, a
paged cache against a dense causal product).  Logits are of order 3:
5e-5 is rounding; a bfloat16 state or int8 weights miss it by 1e-2 and
more (``test_the_tolerance_sees_a_rounded_state``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_olmo_hybrid as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import llama, olmo_hybrid  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    init_block_pool,
    paged_cache_config,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

HF = T.config("olmo_hybrid")
PARTS = T.parts("olmo_hybrid", 128)
CFG = PARTS["cfg"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=64, max_seq_len=64,
    prefill_chunk=8, temperature=1.0,
)
TOL = 5e-5


@pytest.fixture(scope="module")
def params():
    return T.params("olmo_hybrid", 2**31 + 17)


@pytest.fixture(autouse=True)
def _exact_float32():
    with jax.default_matmul_precision("highest"):
        yield


def make_scheduler(params, events=None, **overrides):
    return T.scheduler(
        PARTS, dict(SCHED, **overrides), params, events=events
    )


def prompts_of(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, HF["vocab_size"], size=n).astype(np.int32)
        for n in lengths
    ]


def serve(sch, prompts, max_new=9):
    for i, p in enumerate(prompts):
        sch.submit(p, max_new=max_new + i, seed=i)
    return {r.req_id: r for r in sch.run()}


# ------------------------------------------ (a) reference = forward


def test_forward_matches_the_reference_on_logits(params):
    tokens = jnp.asarray(prompts_of((70, 70), seed=3))  # > one sub-chunk
    got = PARTS["forward_fn"](params, tokens)
    want = R.logits(params, tokens, HF)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < TOL


def test_init_params_has_the_reference_tree():
    template = jax.eval_shape(PARTS["params_template_fn"])
    assert jax.tree_util.tree_map(
        lambda a: a.shape, template
    ) == R.model_shapes(HF)


def test_config_takes_the_published_keys_and_refuses_a_rotation():
    assert CFG.layer_types == tuple(HF["layer_types"])
    assert CFG.layer_keeps() == ("state", "state", "state", "pages")
    assert (CFG.n_layers, CFG.n_kv_heads, CFG.head_dim) == (4, 3, 24)
    with pytest.raises(ValueError, match="rope_theta"):
        olmo_hybrid.OlmoHybridConfig.tiny(
            rope_parameters={"rope_theta": 500000.0}
        )
    with pytest.raises(ValueError, match="layer_types of 2 entries"):
        olmo_hybrid.OlmoHybridConfig.tiny(layer_types=HF["layer_types"][:2])


# ------------------------- (b) step programs by hand, on logits


_PROGRAMS = {}


def _programs(backend):
    """The two step programs jitted once a backend (the backend is read
    when a program is traced)."""
    if backend not in _PROGRAMS:
        def under(fn):
            jitted = jax.jit(fn)

            def call(*args):
                with pytest.MonkeyPatch.context() as mp:
                    if backend:
                        mp.setenv("DLROVER_TPU_PAGED_KERNEL", backend)
                    return jitted(*args)
            return call

        _PROGRAMS[backend] = (
            under(PARTS["paged_prefill_fn"]), under(PARTS["paged_decode_fn"])
        )
    return _PROGRAMS[backend]


def _drive(params, tokens, prompt_len, chunk, lane=2, backend=None,
           pool=None):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into
    ``lane``, then decode the rest: the logits of every position, and
    the pool."""
    prefill, decode = _programs(backend)
    serving = PARTS["serving_params_fn"](params)
    cache = paged_cache_config(CFG, 40, 4, 4, chunk)
    if pool is None:
        pool = init_block_pool(cache)
    table = jnp.arange(1, 17, dtype=jnp.int32) + 16 * (lane % 2)
    rows = []
    for start in range(0, prompt_len, chunk):
        real = min(chunk, prompt_len - start)
        piece = jnp.zeros((1, chunk), jnp.int32).at[0, :real].set(
            tokens[start:start + real]
        )
        logits, pool = prefill(
            serving, piece, pool, table, jnp.int32(start), jnp.int32(lane),
            jnp.int32(real),
        )
        rows.append(logits[0, :real])
    for t in range(prompt_len, tokens.shape[0]):
        one_hot = jnp.arange(4) == lane
        logits, pool = decode(
            serving, jnp.where(one_hot, tokens[t], 0), pool,
            jnp.where(one_hot[:, None], table[None], 0),
            jnp.where(one_hot, t, 0), one_hot,
        )
        rows.append(logits[lane][None])
    return jnp.concatenate(rows), pool


@pytest.mark.parametrize("prompt_len,backend", [
    (32, "jnp"),  # ends ON a chunk boundary
    (32, "pallas"),
    (37, "jnp"),  # ends off one
    (37, "pallas"),
    (5, "pallas"),  # shorter than a chunk
])
def test_chunked_prefill_then_paged_decode_match_the_reference(
        params, prompt_len, backend):
    tokens = jnp.asarray(prompts_of((44,), seed=prompt_len)[0])
    got, _ = _drive(params, tokens, prompt_len, 16, backend=backend)
    want = R.logits(params, tokens[None], HF)[0]
    assert float(jnp.abs(got - want).max()) < TOL


def test_a_lane_reused_after_a_finish_starts_from_zero(params):
    """The second prompt prefills into the lane the first left its
    state, conv tail and pages in: ``start == 0`` zeroes the state."""
    first, second = (jnp.asarray(p) for p in prompts_of((30, 23), seed=8))
    _, pool = _drive(params, first, 20, 8)
    assert float(jnp.abs(pool["gdn"][:, 2]).max()) > 0
    got, _ = _drive(params, second, 19, 8, pool=pool)
    want = R.logits(params, second[None], HF)[0]
    assert float(jnp.abs(got - want).max()) < TOL


def test_the_tolerance_sees_a_rounded_state(params):
    """A state rounded to bfloat16 between prefill and decode — what a
    bfloat16 slab would do at every token — misses the tolerance."""
    tokens = jnp.asarray(prompts_of((40,), seed=4)[0])
    serving = PARTS["serving_params_fn"](params)
    want = R.logits(params, tokens[None], HF)[0]
    _, pool = _drive(params, tokens[:32], 32, 16)
    pool = dict(
        pool, gdn=pool["gdn"].astype(jnp.bfloat16).astype(jnp.float32)
    )
    lane, table = 2, jnp.arange(1, 17, dtype=jnp.int32)
    one_hot = jnp.arange(4) == lane
    logits, _ = PARTS["paged_decode_fn"](
        serving, jnp.where(one_hot, tokens[32], 0), pool,
        jnp.where(one_hot[:, None], table[None], 0),
        jnp.where(one_hot, 32, 0), one_hot,
    )
    assert float(jnp.abs(logits[lane] - want[32]).max()) > 20 * TOL


def test_an_inactive_lane_comes_out_of_decode_bitwise_untouched(params):
    tokens = jnp.asarray(prompts_of((20,), seed=6)[0])
    _, pool = _drive(params, tokens, 20, 8, lane=1)
    serving = PARTS["serving_params_fn"](params)
    active = jnp.arange(4) == 3  # another lane decodes
    _, after = PARTS["paged_decode_fn"](
        serving, jnp.full((4,), 7, jnp.int32), pool,
        jnp.zeros((4, 16), jnp.int32).at[3].set(jnp.arange(17, 33)),
        jnp.zeros((4,), jnp.int32), active,
    )
    for leaf in ("conv", "gdn"):
        np.testing.assert_array_equal(
            np.asarray(after[leaf][:, 1]), np.asarray(pool[leaf][:, 1])
        )
    assert float(jnp.abs(after["gdn"][:, 3]).max()) > 0


# ------------------------------------------------ (c) the scheduler


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


def test_served_logprobs_match_the_reference(params):
    # six prompts on three lanes, one a multiple of the chunk of 8:
    # admissions happen while other lanes decode, slots are reused
    prompts = prompts_of((5, 13, 19, 16, 3, 11))
    sch = make_scheduler(params)
    res = serve(sch, prompts)
    assert sorted(res) == list(range(6))
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.new_tokens == 9 + i and r.logprobs.size == r.new_tokens
        np.testing.assert_allclose(
            r.logprobs, reference_logprobs(params, r, p.size), atol=TOL
        )
    # one decode program, whatever the traffic
    assert sch.compile_counts() == {"decode": 1, "prefill": 1, "sample": 1}
    st = sch.stats()
    assert st["state_resets"] == 6 and st["state_bytes"] == sch.state_bytes > 0
    assert st["prefix_hits"] == 0 and st["prefix_queries"] == 0


def test_the_pool_holds_pages_for_one_layer_and_state_for_three(params):
    sch = make_scheduler(params)
    shapes = {k: v.shape for k, v in sch._pool.items()}
    assert shapes == {
        "k": (1, 64, 4 * 3, 24), "v": (1, 64, 4 * 3, 24),
        "conv": (3, 3, 3 * 192), "gdn": (3, 3, 1, 12, 96),
    }
    st = sch.block_pool.stats()
    assert (st["paged_layers"], st["state_layers"]) == (1, 3)
    assert sch.state_bytes == 3 * 3 * (3 * 192 + 12 * 96) * 4


def test_a_reused_slot_starts_from_a_zero_state(params):
    prompts = prompts_of((12, 7), seed=5)
    one_lane = make_scheduler(params, max_slots=1)
    both = serve(one_lane, prompts)  # the second request reuses slot 0
    alone = make_scheduler(params, max_slots=1)
    alone.submit(prompts[1], max_new=10, seed=1)
    fresh = alone.run()[0]
    assert (both[1].tokens == fresh.tokens).all()
    np.testing.assert_array_equal(both[1].logprobs, fresh.logprobs)


def test_a_preempted_sequence_reproduces_its_tokens(params):
    prompts = prompts_of((9, 14, 6), seed=9)
    calm = serve(make_scheduler(params), prompts, max_new=12)
    sch = make_scheduler(params)
    for i, p in enumerate(prompts):
        sch.submit(p, max_new=12 + i, seed=i)
    out = []
    for _ in range(6):  # every lane decoding, a few tokens in
        out.extend(sch.step())
    victim = next(
        i for i, sl in enumerate(sch._slots) if sl.phase == "decode"
    )
    sch._preempt(victim)  # re-prefills prompt + tail from token 0
    out.extend(sch.run())
    assert sch.preemptions == 1
    got = {r.req_id: r for r in out}
    for i in calm:
        assert (got[i].tokens == calm[i].tokens).all()
        np.testing.assert_allclose(got[i].logprobs, calm[i].logprobs, atol=TOL)


def test_a_common_prefix_is_prefilled_for_each_request(params):
    shared = prompts_of((16,), seed=2)[0]  # four full blocks of 4
    tails = prompts_of((5, 7), seed=4)
    prompts = [np.concatenate([shared, t]) for t in tails]
    sch = make_scheduler(params, max_slots=1)  # one after the other
    together = serve(sch, prompts)
    for i, p in enumerate(prompts):
        alone = make_scheduler(params, max_slots=1)
        alone.submit(p, max_new=9 + i, seed=i)
        want = alone.run()[0]
        assert (together[i].tokens == want.tokens).all()
    st = sch.stats()
    assert st["prefix_hits"] == 0 and st["prefix_queries"] == 0
    assert st["prefix_hits_skipped"] == 2
    assert sch.block_pool.cached_shared_blocks == 0


def _build(monkeypatch, env=None, **kw):
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    return ContinuousBatchingScheduler(
        CFG, SchedulerConfig(**SCHED),
        paged_decode_fn=PARTS["paged_decode_fn"],
        paged_prefill_fn=PARTS["paged_prefill_fn"], **kw,
    )


@pytest.mark.parametrize("case,env,kw,why", [
    ("decode_k", {"DLROVER_TPU_DECODE_STEPS": "3"}, {}, "roll the state back"),
    ("draft", {}, {"draft_cfg": llama.LlamaConfig.tiny()}, "draft model"),
    ("prefill_role", {}, {"role": "prefill"}, "K/V\\s+blocks only"),
])
def test_unsound_combinations_are_refused_by_name(
        monkeypatch, case, env, kw, why):
    """The lines that refuse Falcon-H1's state refuse this model's."""
    with pytest.raises(ValueError, match=why) as err:
        _build(monkeypatch, env, **kw)
    assert "per-lane state (conv, gdn)" in str(err.value)


def test_the_plain_construction_is_accepted(monkeypatch):
    sch = _build(monkeypatch)
    assert sch.lane_state and not sch.prefix_cache


def test_serve_step_says_how_the_cache_divides(params, tmp_path):
    from dlrover_tpu.observability.events import EventLogger, read_events

    path = str(tmp_path / "events.jsonl")
    sch = make_scheduler(params, events=EventLogger(path))
    serve(sch, prompts_of((5, 13)))
    steps = [
        e["labels"] for e in read_events(path)
        if e.get("name") == "serve_step"
    ]
    assert steps
    block = 2 * 4 * 3 * 24 * 4  # K and V of one block over ONE layer
    for labels in steps:
        assert (labels["state_layers"], labels["paged_layers"]) == (3, 1)
        assert labels["state_bytes"] == sch.state_bytes
        live = labels["cache_bytes"] - labels["state_bytes"]
        assert live >= 0 and live % block == 0
    assert max(s["cache_bytes"] for s in steps) > sch.state_bytes
    assert sum(s["state_resets"] for s in steps) == 2


# ------------------------------------------------- (d) the serving copy


def test_serving_params_fuses_each_kinds_input_projections(params):
    serving = olmo_hybrid.serving_params(params, CFG)
    linear, full = serving["layers"][0], serving["layers"][3]
    assert "w_in" in linear and not set(olmo_hybrid._LINEAR_IN) & set(linear)
    assert "wqkv" in full and not set(olmo_hybrid._FULL_IN) & set(full)
    assert linear["w_in"].shape == (72, 2 * 48 + 2 * 96 + 2 * 4)
    assert full["wqkv"].shape == (72, 3 * 72)
    # a leaf that needs neither cast nor fusion is the caller's array
    assert linear["conv_w"] is params["layers"][0]["conv_w"]
    # a tree that is already a serving copy comes back as it is
    assert olmo_hybrid.serving_params(serving, CFG) is serving


def test_step_programs_take_either_tree(params):
    tokens = jnp.asarray(prompts_of((8,), seed=2)[0])[None]
    pool = init_block_pool(paged_cache_config(CFG, 40, 4, 4, 8))
    args = (
        tokens, pool, jnp.arange(1, 17, dtype=jnp.int32), jnp.int32(0),
        jnp.int32(0), jnp.int32(8),
    )
    fused, _ = PARTS["paged_prefill_fn"](
        olmo_hybrid.serving_params(params, CFG), *args
    )
    plain, _ = PARTS["paged_prefill_fn"](params, *args)
    assert float(jnp.abs(fused - plain).max()) < TOL
