"""Serving from a resident copy of the weights in the compute dtype
(ISSUE 25) and the serving layout (ISSUE 34: ``wq``, ``wk``, ``wv`` as
one leaf ``wqkv``), made by one jitted program an adoption.

Contracts pinned here, all on the CPU with a tiny configuration that
computes in bfloat16 over float32 params (the replica's situation):

- ``llama.serving_params`` casts exactly the leaves the serving programs
  cast on entry, holds the three attention input projections fused and
  not apart, leaves the norm scales alone, returns every other leaf
  whose dtype already matches as the SAME array, is idempotent, and
  compiles nothing the second time it sees the same shapes;
- every paged step program gives bitwise the same logits and pool for
  float32 params and for ``serving_params`` of them, at GQA and at MHA
  widths (the Falcon-H1 programs: ``tests/test_falcon_h1.py``);
- ``ContinuousBatchingScheduler.sync_weights`` keeps only that copy,
  serves bitwise the same tokens and logprobs from it, drops the
  previous copy before it makes the next, and writes one ``weight_cast``
  span per adoption.
"""

import gc
import os
import sys
import weakref
from functools import partial

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
# MHA: the split's offsets are n_heads * head_dim apart, as in cell C
CFG_MHA = llama.LlamaConfig.tiny(
    dtype=jnp.bfloat16, **{**SIZES, "n_kv_heads": 4}
)
PARAMS_MHA = llama.init_params(jax.random.PRNGKey(2), CFG_MHA)
QKV_LEAVES = ("wq", "wk", "wv")
MATMUL_LEAVES = QKV_LEAVES + ("wo", "w_gate", "w_up", "w_down")
SERVED_MATMUL_LEAVES = ("wqkv",) + MATMUL_LEAVES[3:]
NORM_LEAVES = ("attn_norm", "mlp_norm")
LANES, BLOCK, NUM_BLOCKS, MAX_BLOCKS, WINDOW = 3, 4, 16, 4, 4


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _matmul_dtypes(params):
    """Dtypes of the leaves the step programs cast, of a training tree
    or of a serving copy (which holds exactly one of the two layouts)."""
    layers = params["layers"]
    names = SERVED_MATMUL_LEAVES if "wqkv" in layers else MATMUL_LEAVES
    assert not ("wqkv" in layers and set(QKV_LEAVES) & set(layers))
    return {
        params["embed"].dtype,
        params["lm_head"].dtype,
        *(layers[k].dtype for k in names),
    }


def _copied(given, served):
    """Leaves of ``served`` that are none of ``given``'s arrays."""
    ids = {id(x) for x in _leaves(given)}
    return sum(id(x) not in ids for x in _leaves(served))


# ------------------------------------------------------ serving_params


def test_serving_params_casts_the_leaves_the_programs_cast():
    before = dict(PARAMS["layers"])
    served = llama.serving_params(PARAMS, CFG)
    assert _matmul_dtypes(served) == {jnp.dtype(jnp.bfloat16)}
    for k in NORM_LEAVES:
        assert served["layers"][k] is PARAMS["layers"][k]
    assert served["final_norm"] is PARAMS["final_norm"]
    # embed, lm_head, the fused leaf and the four other matrices
    assert _copied(PARAMS, served) == 7
    # the same values the programs' own astype produces
    np.testing.assert_array_equal(
        np.asarray(served["layers"]["w_up"].astype(jnp.float32)),
        np.asarray(
            PARAMS["layers"]["w_up"].astype(jnp.bfloat16).astype(
                jnp.float32
            )
        ),
    )
    # the caller's tree is not touched: same keys, same arrays
    assert _matmul_dtypes(PARAMS) == {jnp.dtype(jnp.float32)}
    assert PARAMS["layers"].keys() == before.keys()
    assert all(PARAMS["layers"][k] is before[k] for k in before)


@pytest.mark.parametrize(
    "params,cfg",
    [
        pytest.param(PARAMS, CFG, id="gqa"),
        pytest.param(PARAMS_MHA, CFG_MHA, id="mha"),
    ],
)
def test_serving_params_holds_q_k_v_as_one_fused_leaf(params, cfg):
    """``wqkv`` is ``[L, D, (n_heads + 2 * n_kv_heads) * head_dim]``:
    q's columns, then k's, then v's, each the cast of its leaf."""
    served = llama.serving_params(params, cfg)["layers"]
    assert not set(QKV_LEAVES) & set(served)
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    assert served["wqkv"].shape == (cfg.n_layers, cfg.dim, q + 2 * kv)
    assert served["wqkv"].dtype == jnp.bfloat16
    for name, lo, hi in (
        ("wq", 0, q), ("wk", q, q + kv), ("wv", q + kv, q + 2 * kv)
    ):
        np.testing.assert_array_equal(
            np.asarray(served["wqkv"][..., lo:hi].astype(jnp.float32)),
            np.asarray(
                params["layers"][name].astype(jnp.bfloat16).astype(
                    jnp.float32
                )
            ),
        )


@pytest.mark.parametrize(
    "params,cfg,fused",
    [
        pytest.param(PARAMS, CFG_F32, 1, id="float32-compute"),
        pytest.param(
            jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), PARAMS
            ), CFG, 1, id="already-bf16",
        ),
        pytest.param(
            llama.serving_params(PARAMS, CFG), CFG, 0, id="serving-copy"
        ),
    ],
)
def test_serving_params_returns_the_same_arrays_when_dtype_matches(
    params, cfg, fused
):
    """Every leaf but the fused one is the caller's array where the
    dtype already matches; the whole of a tree that is already a
    serving copy is (idempotent)."""
    served = llama.serving_params(params, cfg)
    assert _copied(params, served) == fused
    assert jax.tree_util.tree_structure(
        served
    ) == jax.tree_util.tree_structure(llama.serving_params(PARAMS, CFG))
    if not fused:
        assert served is params


def test_a_second_copy_of_the_same_shapes_compiles_nothing():
    """The copy is ONE module-level jitted program: a later adoption of
    a tree of the same shapes and dtypes finds it compiled, and a tree
    with nothing to cast or fuse does not call it at all."""
    programs = llama._cast_and_fuse._cache_size
    first = llama.serving_params(PARAMS, CFG)
    compiled = programs()
    assert compiled >= 1
    again = llama.serving_params(
        jax.tree_util.tree_map(lambda x: x + 1, PARAMS), CFG
    )
    assert programs() == compiled
    assert again["layers"]["wqkv"] is not first["layers"]["wqkv"]
    assert llama.serving_params(first, CFG) is first
    assert programs() == compiled


_COPY_IN_A_FRESH_PROCESS = """
import json, jax, jax.numpy as jnp
from dlrover_tpu.common.jax_env import CompileMeter
from dlrover_tpu.models import llama

meter = CompileMeter()
cfg = llama.LlamaConfig.tiny(dtype=jnp.bfloat16)
params = llama.init_params(jax.random.PRNGKey(0), cfg)
before = meter.snapshot()
jax.block_until_ready(llama.serving_params(params, cfg))
after = meter.snapshot()
print(json.dumps(dict(
    {k: after[k] - before[k] for k in after},
    threshold=jax.config.jax_persistent_cache_min_compile_time_secs,
)))
"""


def test_a_second_process_loads_the_copy_program_from_the_cache(tmp_path):
    """Every replica compiles the copy program at its start, in well
    under the second below which JAX keeps no compile: ``serving_copy``
    keeps it all the same, so the second process to make a copy of the
    same shapes loads the program (a hit, no miss) — and leaves the
    process's threshold as it found it."""
    import json
    import subprocess

    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        PYTHONPATH=os.path.dirname(os.path.dirname(__file__)),
    )
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _COPY_IN_A_FRESH_PROCESS], env=env,
            capture_output=True, text=True, timeout=300, check=True,
        )
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert (first["cache_misses"], first["cache_hits"]) == (1, 0)
    assert (second["cache_misses"], second["cache_hits"]) == (0, 1)
    assert first["threshold"] == second["threshold"] == 1.0
    assert len(os.listdir(tmp_path / "cache")) == 1


def test_the_copy_program_takes_only_the_leaves_that_need_work():
    """A bf16 tree that keeps ``wq``, ``wk``, ``wv`` hands the jitted
    program those three and nothing else (a leaf that went through it
    would come back as a copy); a tree whose embedding alone is float32
    hands it the embedding and no ``layers`` leaf."""
    served = llama.serving_params(PARAMS, CFG)
    apart = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), PARAMS
    )
    again = llama.serving_params(apart, CFG)
    kept = [k for k in again["layers"] if k != "wqkv"]
    assert all(again["layers"][k] is apart["layers"][k] for k in kept)
    assert again["embed"] is apart["embed"]
    np.testing.assert_array_equal(
        np.asarray(again["layers"]["wqkv"].astype(jnp.float32)),
        np.asarray(served["layers"]["wqkv"].astype(jnp.float32)),
    )
    embed_only = {**served, "embed": PARAMS["embed"]}
    got = llama.serving_params(embed_only, CFG)
    assert got["embed"].dtype == jnp.bfloat16
    assert _copied(embed_only, got) == 1


# ------------------------------------------------------- step programs


def _pool(seed, cfg):
    pool = init_block_pool(
        PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, num_blocks=NUM_BLOCKS,
            block_size=BLOCK, dtype=cfg.dtype,
        )
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "k": jax.random.normal(k1, pool["k"].shape, cfg.dtype),
        "v": jax.random.normal(k2, pool["v"].shape, cfg.dtype),
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
STEP_PROGRAMS = (
    "paged_decode_step",
    "paged_prefill_chunk",
    "paged_verify_step",
    "paged_verify_write_step",
)


def _program_case(name, cfg=CFG):
    """(jitted ``fn(params)``) for one step program on fixed inputs."""
    lanes = (TABLES, POSITIONS, ACTIVE)
    if name == "paged_decode_step":
        return lambda p: llama.paged_decode_step(
            p, WINDOW_TOKENS[:, 0], _pool(1, cfg), *lanes, cfg
        )
    if name == "paged_prefill_chunk":
        return lambda p: llama.paged_prefill_chunk(
            p, WINDOW_TOKENS.reshape(1, -1)[:, :8], _pool(1, cfg),
            TABLES[0], jnp.int32(4), cfg,
        )
    if name == "paged_verify_step":
        return lambda p: llama.paged_verify_step(
            p, WINDOW_TOKENS, _pool(1, cfg), *lanes, cfg
        )
    return lambda p: llama.paged_verify_write_step(
        p, WINDOW_TOKENS, _pool(1, cfg), *lanes, cfg
    )


def _assert_bitwise_equal(given, served):
    assert jax.tree_util.tree_structure(
        given
    ) == jax.tree_util.tree_structure(served)
    for a, b in zip(_leaves(given), _leaves(served)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


@pytest.mark.parametrize("name", STEP_PROGRAMS)
@pytest.mark.parametrize(
    "params,cfg",
    [
        pytest.param(PARAMS, CFG, id="gqa"),
        pytest.param(PARAMS_MHA, CFG_MHA, id="mha"),
    ],
)
def test_step_programs_are_bitwise_equal_on_the_resident_copy(
    name, params, cfg
):
    """float32 params (cast inside the program, three projections, as
    before) against ``serving_params`` of them (the program's cast is a
    no-op, one fused projection and a split): logits and written pool
    must not differ in one bit — each output column is the same
    float32 sum over the same ``D`` products either way."""
    fn = jax.jit(_program_case(name, cfg))
    _assert_bitwise_equal(
        fn(params), fn(llama.serving_params(params, cfg))
    )


@pytest.mark.parametrize(
    "name", [n for n in STEP_PROGRAMS if n != "paged_verify_step"]
)
def test_a_split_with_k_and_v_exchanged_is_seen(name):
    """The mutation the pins above must catch: a fused leaf laid out q,
    v, k (so the split hands v's columns out as k) changes the logits of
    every program that projects k and v (the read-only verify takes
    only q)."""
    fn = _program_case(name)
    want = jax.jit(fn)(PARAMS)
    exchanged = llama.serving_params(PARAMS, CFG)
    kv = CFG.n_kv_heads * CFG.head_dim
    w = exchanged["layers"]["wqkv"]
    exchanged["layers"]["wqkv"] = jnp.concatenate(
        [w[..., :-2 * kv], w[..., -kv:], w[..., -2 * kv:-kv]], axis=-1
    )
    got = jax.jit(fn)(exchanged)
    logits = lambda out: np.asarray(  # noqa: E731
        _leaves(out)[0].astype(jnp.float32)
    )
    assert not np.array_equal(logits(want), logits(got))


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
    # injected programs serve the tree they are given: the hand-cast
    # one keeps ``wq``, ``wk``, ``wv`` and takes the three projections
    by_hand = _scheduler(
        monkeypatch, decode_steps=decode_steps,
        **{
            kw: partial(getattr(llama, fn), cfg=CFG)
            for kw, fn in (
                ("paged_decode_fn", "paged_decode_step"),
                ("paged_prefill_fn", "paged_prefill_chunk"),
                ("paged_verify_fn", "paged_verify_step"),
            )
        },
    )
    by_hand.sync_weights(hand_cast)
    assert by_hand._params is hand_cast

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

    programs = llama._cast_and_fuse._cache_size()
    sch.sync_weights(PARAMS, generation=7)
    assert llama._cast_and_fuse._cache_size() == programs
    gc.collect()
    assert gone() is None, "the first serving copy is still referenced"

    # the identity path: nothing to cast, nothing copied
    served = llama.serving_params(PARAMS, CFG)
    programs = llama._cast_and_fuse._cache_size()
    sch.sync_weights(served)
    assert sch._params is served
    assert llama._cast_and_fuse._cache_size() == programs

    tree_bytes = sum(x.nbytes for x in _leaves(PARAMS))
    served_bytes = sum(x.nbytes for x in _leaves(served))
    assert served_bytes < tree_bytes
    # a published bf16 tree that keeps ``wq``, ``wk``, ``wv``: only the
    # fused leaf is new
    bf16 = jax.tree_util.tree_map(lambda x: x, served)
    w = bf16["layers"].pop("wqkv")
    q, kv = CFG.n_heads * CFG.head_dim, CFG.n_kv_heads * CFG.head_dim
    bf16["layers"].update(
        wq=w[..., :q], wk=w[..., q:q + kv], wv=w[..., q + kv:]
    )
    sch.sync_weights(bf16)
    assert sch._params["layers"]["w_up"] is served["layers"]["w_up"]

    spans = _weight_casts(path)
    # 7: embed, lm_head, wqkv, wo, w_gate, w_up, w_down; the fused leaf
    # holds the bytes of the three it replaces (``served_bytes`` is what
    # it was when the copy kept them apart: 49920)
    assert served_bytes == 49920
    assert [e["labels"] for e in spans] == [
        dict(
            bytes_in=tree_bytes, bytes_out=served_bytes, leaves_cast=7,
            leaves_fused=3,
        ),
        dict(
            bytes_in=tree_bytes, bytes_out=served_bytes, leaves_cast=7,
            leaves_fused=3, generation=7,
        ),
        dict(
            bytes_in=served_bytes, bytes_out=served_bytes, leaves_cast=0,
            leaves_fused=0,
        ),
        dict(
            bytes_in=served_bytes, bytes_out=served_bytes, leaves_cast=1,
            leaves_fused=3,
        ),
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
    assert [
        (e["labels"]["leaves_cast"], e["labels"]["leaves_fused"])
        for e in _weight_casts(path)
    ] == [(14, 6), (7, 3)]
    assert _weight_casts(path)[0]["labels"]["bytes_in"] == both


def test_injected_programs_serve_the_params_they_were_given(monkeypatch):
    """The cast rule belongs to the llama programs: a scheduler built on
    another decode program holds the caller's tree unchanged."""
    sch = _scheduler(
        monkeypatch,
        paged_decode_fn=partial(llama.paged_decode_step, cfg=CFG),
    )
    sch.sync_weights(PARAMS)
    assert sch._params is PARAMS
    assert len(_serve(sch, n=2)) == 2
