"""Serving from a resident compute-dtype copy of the weights (ISSUE 25).

Contracts pinned here, all on the CPU with a tiny configuration that
computes in bfloat16 over float32 params (the replica's situation):

- ``llama.serving_params`` casts exactly the nine leaves the serving
  programs cast on entry, leaves the norm scales alone, and returns the
  SAME arrays where the dtype already matches;
- every paged step program gives bitwise the same logits and pool for
  float32 params and for ``serving_params`` of them;
- ``ContinuousBatchingScheduler.sync_weights`` keeps only that copy,
  serves bitwise the same tokens and logprobs from it, drops the
  previous copy before it makes the next, and writes one ``weight_cast``
  span per adoption.
"""

import gc
import os
import sys
import weakref

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.observability import events as ev  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    PagedCacheConfig,
    init_block_pool,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

SIZES = dict(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none",
)
CFG = llama.LlamaConfig.tiny(dtype=jnp.bfloat16, **SIZES)
CFG_F32 = llama.LlamaConfig.tiny(dtype=jnp.float32, **SIZES)
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)  # float32
MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORM_LEAVES = ("attn_norm", "mlp_norm")
LANES, BLOCK, NUM_BLOCKS, MAX_BLOCKS, WINDOW = 3, 4, 16, 4, 4


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _matmul_dtypes(params):
    return {
        params["embed"].dtype,
        params["lm_head"].dtype,
        *(params["layers"][k].dtype for k in MATMUL_LEAVES),
    }


# ------------------------------------------------------ serving_params


def test_serving_params_casts_the_nine_leaves_the_programs_cast():
    served = llama.serving_params(PARAMS, CFG)
    assert _matmul_dtypes(served) == {jnp.dtype(jnp.bfloat16)}
    for k in NORM_LEAVES:
        assert served["layers"][k] is PARAMS["layers"][k]
    assert served["final_norm"] is PARAMS["final_norm"]
    assert sum(
        a is not b for a, b in zip(_leaves(PARAMS), _leaves(served))
    ) == 9
    # the same values the programs' own astype produces
    np.testing.assert_array_equal(
        np.asarray(served["layers"]["w_up"].astype(jnp.float32)),
        np.asarray(
            PARAMS["layers"]["w_up"].astype(jnp.bfloat16).astype(
                jnp.float32
            )
        ),
    )
    # the caller's tree is not touched
    assert _matmul_dtypes(PARAMS) == {jnp.dtype(jnp.float32)}


@pytest.mark.parametrize(
    "params,cfg",
    [
        pytest.param(PARAMS, CFG_F32, id="float32-compute"),
        pytest.param(
            llama.serving_params(PARAMS, CFG), CFG, id="already-bf16"
        ),
    ],
)
def test_serving_params_returns_the_same_arrays_when_dtype_matches(
    params, cfg
):
    served = llama.serving_params(params, cfg)
    assert all(
        a is b for a, b in zip(_leaves(params), _leaves(served))
    )


# ------------------------------------------------------- step programs


def _pool(seed):
    pool = init_block_pool(
        PagedCacheConfig(
            n_layers=CFG.n_layers, n_kv_heads=CFG.n_kv_heads,
            head_dim=CFG.head_dim, num_blocks=NUM_BLOCKS,
            block_size=BLOCK, dtype=CFG.dtype,
        )
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "k": jax.random.normal(k1, pool["k"].shape, CFG.dtype),
        "v": jax.random.normal(k2, pool["v"].shape, CFG.dtype),
    }


TABLES = jnp.asarray(
    1 + np.arange(LANES * MAX_BLOCKS).reshape(LANES, MAX_BLOCKS),
    jnp.int32,
)
POSITIONS = jnp.asarray([5, 0, 9], jnp.int32)
ACTIVE = jnp.asarray([True, False, True])
WINDOW_TOKENS = jnp.asarray(
    np.random.default_rng(3).integers(0, 97, (LANES, WINDOW)), jnp.int32
)


def _program_case(name):
    """(jitted ``fn(params)``) for one step program on fixed inputs."""
    lanes = (TABLES, POSITIONS, ACTIVE)
    if name == "paged_decode_step":
        return lambda p: llama.paged_decode_step(
            p, WINDOW_TOKENS[:, 0], _pool(1), *lanes, CFG
        )
    if name == "paged_prefill_chunk":
        return lambda p: llama.paged_prefill_chunk(
            p, WINDOW_TOKENS.reshape(1, -1)[:, :8], _pool(1),
            TABLES[0], jnp.int32(4), CFG,
        )
    if name == "paged_verify_step":
        return lambda p: llama.paged_verify_step(
            p, WINDOW_TOKENS, _pool(1), *lanes, CFG
        )
    return lambda p: llama.paged_verify_write_step(
        p, WINDOW_TOKENS, _pool(1), *lanes, CFG
    )


@pytest.mark.parametrize(
    "name",
    [
        "paged_decode_step",
        "paged_prefill_chunk",
        "paged_verify_step",
        "paged_verify_write_step",
    ],
)
def test_step_programs_are_bitwise_equal_on_the_resident_copy(name):
    """float32 params (cast inside the program, as before) against
    ``serving_params`` of them (the program's cast is a no-op): logits
    and written pool must not differ in one bit."""
    fn = jax.jit(_program_case(name))
    given = fn(PARAMS)
    served = fn(llama.serving_params(PARAMS, CFG))
    assert jax.tree_util.tree_structure(
        given
    ) == jax.tree_util.tree_structure(served)
    for a, b in zip(_leaves(given), _leaves(served)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


# ------------------------------------------------------------ scheduler


def _scheduler(monkeypatch, events_path=None, decode_steps="1", **kw):
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", decode_steps)
    return ContinuousBatchingScheduler(
        CFG,
        SchedulerConfig(
            max_slots=4, block_size=4, num_blocks=64, max_seq_len=64,
            prefill_chunk=8, temperature=1.0, max_new_default=10,
        ),
        events=(
            ev.EventLogger(path=str(events_path), job="weight-cast")
            if events_path else None
        ),
        capture_logprobs=True,
        **kw,
    )


def _serve(sch, n=6, seed=11):
    rng = np.random.default_rng(seed)
    for i in range(n):
        sch.submit(
            rng.integers(0, 97, (int(rng.integers(5, 20)),)).astype(
                np.int32
            ),
            max_new=10, seed=100 + i,
        )
    return sorted(sch.run(), key=lambda r: r.req_id)


def _weight_casts(path):
    return [
        e for e in ev.read_events(str(path)) if e["name"] == "weight_cast"
    ]


@pytest.mark.parametrize("decode_steps", ["1", "3"])
def test_scheduler_serves_bitwise_the_same_from_its_own_copy(
    monkeypatch, decode_steps
):
    """Synced with float32 params the scheduler holds only compute-dtype
    matmul leaves, and serves what a scheduler synced with hand-cast
    params serves: same tokens, same captured logprobs."""
    own = _scheduler(monkeypatch, decode_steps=decode_steps)
    own.sync_weights(PARAMS)
    assert _matmul_dtypes(own._params) == {jnp.dtype(jnp.bfloat16)}
    assert own._params["final_norm"].dtype == jnp.float32

    hand_cast = jax.tree_util.tree_map(lambda x: x, PARAMS)
    hand_cast["embed"] = PARAMS["embed"].astype(jnp.bfloat16)
    hand_cast["lm_head"] = PARAMS["lm_head"].astype(jnp.bfloat16)
    for k in MATMUL_LEAVES:
        hand_cast["layers"][k] = PARAMS["layers"][k].astype(jnp.bfloat16)
    by_hand = _scheduler(monkeypatch, decode_steps=decode_steps)
    by_hand.sync_weights(hand_cast)
    assert all(
        a is b
        for a, b in zip(_leaves(hand_cast), _leaves(by_hand._params))
    )

    got, want = _serve(own), _serve(by_hand)
    assert len(got) == len(want) == 6
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.logprobs.size == a.new_tokens > 0
        np.testing.assert_array_equal(a.logprobs, b.logprobs)


def test_second_sync_frees_the_first_copy_and_each_writes_a_span(
    tmp_path, monkeypatch
):
    path = tmp_path / "events.jsonl"
    sch = _scheduler(monkeypatch, events_path=path)
    sch.sync_weights(PARAMS)
    first = sch._params["layers"]["w_up"]
    assert first is not PARAMS["layers"]["w_up"]
    gone = weakref.ref(first)
    del first
    assert len(_serve(sch, n=2)) == 2

    sch.sync_weights(PARAMS, generation=7)
    gc.collect()
    assert gone() is None, "the first serving copy is still referenced"

    # the identity path: nothing to cast, nothing copied
    served = llama.serving_params(PARAMS, CFG)
    sch.sync_weights(served)
    assert all(
        a is b for a, b in zip(_leaves(served), _leaves(sch._params))
    )

    tree_bytes = sum(x.nbytes for x in _leaves(PARAMS))
    served_bytes = sum(x.nbytes for x in _leaves(served))
    assert served_bytes < tree_bytes
    spans = _weight_casts(path)
    assert [e["labels"] for e in spans] == [
        dict(bytes_in=tree_bytes, bytes_out=served_bytes, leaves_cast=9),
        dict(
            bytes_in=tree_bytes, bytes_out=served_bytes, leaves_cast=9,
            generation=7,
        ),
        dict(bytes_in=served_bytes, bytes_out=served_bytes, leaves_cast=0),
    ]
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in spans)


def test_draft_model_is_cast_with_its_own_dtype(tmp_path, monkeypatch):
    """The drafter's copy follows ``draft_cfg.dtype``, not the policy's;
    one span covers both trees of the adoption."""
    draft_cfg = llama.LlamaConfig.tiny(
        dtype=jnp.float16, **{**SIZES, "n_layers": 1}
    )
    draft_params = llama.init_params(jax.random.PRNGKey(1), draft_cfg)
    path = tmp_path / "events.jsonl"
    sch = _scheduler(
        monkeypatch, events_path=path, decode_steps="3",
        draft_cfg=draft_cfg,
    )
    assert sch.draft
    sch.sync_weights(PARAMS, draft_params)
    assert _matmul_dtypes(sch._params) == {jnp.dtype(jnp.bfloat16)}
    assert _matmul_dtypes(sch._draft_params) == {jnp.dtype(jnp.float16)}
    assert sch._draft_params["final_norm"] is draft_params["final_norm"]
    results = _serve(sch, n=3)
    assert [r.new_tokens for r in results] == [10, 10, 10]

    # a policy-only adoption keeps the drafter's copy
    kept = sch._draft_params
    sch.sync_weights(PARAMS)
    assert sch._draft_params is kept
    both = sum(x.nbytes for x in _leaves((PARAMS, draft_params)))
    assert [e["labels"]["leaves_cast"] for e in _weight_casts(path)] == [
        18, 9,
    ]
    assert _weight_casts(path)[0]["labels"]["bytes_in"] == both


def test_injected_programs_serve_the_params_they_were_given(monkeypatch):
    """The cast rule belongs to the llama programs: a scheduler built on
    another decode program holds the caller's tree unchanged."""
    from functools import partial

    sch = _scheduler(
        monkeypatch,
        paged_decode_fn=partial(llama.paged_decode_step, cfg=CFG),
    )
    sch.sync_weights(PARAMS)
    assert sch._params is PARAMS
    assert len(_serve(sch, n=2)) == 2
