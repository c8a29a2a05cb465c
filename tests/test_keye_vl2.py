"""Keye-VL-2.0's decoder (``models/keye_vl2.py``: sparse experts in
every layer, a learned top-k indexer over a paged index-key cache) on
the serving plane, at tiny sizes on the CPU.

The chain of evidence: the benchmark's plain reference
(``benchmarks/reference_keye_vl2.py``, which imports nothing of the
program) = the program's whole-sequence forward = what the scheduler
serves through chunked prefill and paged decode with the index key as a
third paged leaf.  The tiny configuration's ``topk`` (16) is below its
sequences, so every test that serves also selects.
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

import reference_keye_vl2 as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import keye_vl2, llama  # noqa: E402
from dlrover_tpu.observability.events import EventLogger  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.rl.generation_service import (  # noqa: E402
    tiny_llama_factory,
)
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    extract_block_regions,
    init_block_pool,
    insert_block_regions,
    paged_cache_config,
    region_nbytes_per_block,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

HF = T.config("keye_vl2")
PARTS = T.parts("keye_vl2", 64)
CFG = PARTS["cfg"]
TOPK = HF["sa_config"]["topk"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=48, max_seq_len=64,
    prefill_chunk=12, temperature=1.0,
)


@pytest.fixture(scope="module")
def params():
    return T.params("keye_vl2", 2**31 + 42)


@pytest.fixture(autouse=True)
def _exact_float32():
    with jax.default_matmul_precision("highest"):
        yield


def make_scheduler(params, events=None, capture_logprobs=True,
                   role="unified", **overrides):
    return T.scheduler(
        PARTS, dict(SCHED, **overrides), params, events=events,
        capture_logprobs=capture_logprobs, role=role,
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


# ------------------------------------- (a) the forward is the reference


def test_init_params_has_the_reference_tree():
    ours = jax.eval_shape(
        lambda: keye_vl2.init_params(jax.random.PRNGKey(0), CFG)
    )
    assert jax.tree_util.tree_map(
        lambda a: a.shape, ours
    ) == jax.tree_util.tree_map(
        tuple, R.model_shapes(HF), is_leaf=lambda x: isinstance(x, tuple)
    )


def test_forward_matches_the_reference_per_token(params):
    tokens = np.stack(prompts_of((48, 48), seed=3))  # 3 x topk
    logits, experts = keye_vl2.forward(
        params, jnp.asarray(tokens), CFG, return_experts=True
    )
    logp = jax.nn.log_softmax(logits, -1)
    got = np.take_along_axis(
        np.asarray(logp)[:, :-1], tokens[:, 1:, None], -1
    )[..., 0]
    want = np.asarray(R.token_logprobs(params, tokens, HF))
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the router's choices are the reference's own top-k: forced onto
    # them it reads the same logprobs and no slack anywhere
    forced, slack = R.token_logprobs_forced(
        params, tokens, HF, {"experts": np.asarray(experts)}
    )
    np.testing.assert_allclose(np.asarray(forced), want, atol=5e-5)
    assert float(np.asarray(slack).max()) == 0.0


def test_the_reference_reports_a_wrong_router(params):
    tokens = np.stack(prompts_of((24,), seed=4))
    _, experts = keye_vl2.forward(
        params, jnp.asarray(tokens), CFG, return_experts=True
    )
    experts = np.array(experts)
    k, e = HF["num_experts_per_tok"], HF["num_experts"]

    def slack_of(served):
        return np.asarray(R.token_logprobs_forced(
            params, tokens, HF, {"experts": served}
        )[1])

    swapped = experts.copy()  # one row sent to the experts it left out
    left_out = [x for x in range(e) if x not in experts[0, 5, 1]]
    swapped[0, 5, 1] = left_out[:k]
    got = slack_of(swapped)
    assert got[0, 5] > 0 and np.isfinite(got).all()
    assert (np.delete(got[0], 5)[:5] == 0).all()
    for bad in (-1, e, experts[0, 7, 0, 1]):  # malformed: inf
        broken = experts.copy()
        broken[0, 7, 0, 0] = bad
        assert np.isinf(slack_of(broken)[0, 7])


# ------------------------ (b) chunked prefill + paged decode = the same


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


def test_served_logprobs_match_the_reference(params):
    # six prompts on three lanes, chunks of 12: the prompts of 30 and
    # 41 have a chunk boundary past topk = 16, every request but the
    # shortest decodes past it, slots and blocks are reused
    prompts = prompts_of((30, 7, 25, 12, 41, 18))
    sch = make_scheduler(params)
    res = serve(sch, prompts)
    assert sorted(res) == list(range(6))
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.new_tokens == 9 + i and r.logprobs.size == r.new_tokens
        np.testing.assert_allclose(
            r.logprobs, reference_logprobs(params, r, p.size), atol=5e-5
        )
    assert sch.compile_counts() == {"decode": 1, "prefill": 1, "sample": 1}
    st = sch.stats()
    assert st["prefix_hits"] == 0 and st["prefix_queries"] == 0
    assert st["prefix_hits_skipped"] == 6
    assert st["sel_rows"] > 0 and st["index_bytes"] > 0


def test_blocks_freed_and_reused_serve_the_same(params):
    # one lane and a pool that holds one request at a time: the second
    # and third requests' blocks are the first's, freed, in another
    # order of use (a selected row must be read through the table)
    prompts = prompts_of((33, 21, 38), seed=6)
    sch = make_scheduler(params, max_slots=1, num_blocks=16)
    res = serve(sch, prompts, max_new=12)
    for i, p in enumerate(prompts):
        np.testing.assert_allclose(
            res[i].logprobs, reference_logprobs(params, res[i], p.size),
            atol=5e-5,
        )


def test_a_preempted_sequence_reproduces_its_tokens_and_rows(params):
    prompts = prompts_of((19, 24, 17), seed=9)
    calm = serve(make_scheduler(params), prompts, max_new=12)
    sch = make_scheduler(params)
    for i, p in enumerate(prompts):
        sch.submit(p, max_new=12 + i, seed=i)
    out = []
    for _ in range(8):
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
        np.testing.assert_allclose(
            got[i].logprobs, calm[i].logprobs, atol=5e-5
        )
        np.testing.assert_array_equal(
            got[i].per_token["experts"], calm[i].per_token["experts"]
        )


# ------------------------------------------- (c) the expert layer


def _layer(params, i):
    return {k: v[i] for k, v in params["layers"].items()}


@pytest.mark.parametrize("rows", [5, 64])
def test_uneven_expert_load_drops_no_assignment(params, rows):
    """A router that sends EVERY row to expert 0 first and nothing to
    experts 5-7: no capacity, nothing dropped — an expert given every
    row takes every row, and the result is the reference's
    one-expert-at-a-time sum."""
    lp = dict(_layer(params, 1))
    rng = np.random.default_rng(rows)
    u = rng.normal(size=64).astype(np.float32)
    # every row leans the same way, so one column answers them all
    x = (rng.normal(size=(rows, 64)) + 4.0 * u).astype(np.float32)
    router = np.array(lp["router"])
    router[:, 0] = u
    router[:, 5:] = -u[:, None]
    lp["router"] = jnp.asarray(router)
    h = np.asarray(R._rms_norm(x, lp["mlp_norm"], HF["rms_norm_eps"]))
    own = np.argsort(-(h @ router), -1, kind="stable")[:, :2]
    counts = np.bincount(own.reshape(-1), minlength=8)
    assert counts[0] == rows and (counts[5:] == 0).all()
    out, ids = keye_vl2._experts(jnp.asarray(x), lp, CFG)
    assert (np.sort(np.asarray(ids), -1) == np.sort(own, -1)).all()
    stacked = {k: v[None] for k, v in lp.items()}
    want, slack = R._experts(jnp.asarray(h), stacked, 0, HF, None)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    assert float(np.asarray(slack).max()) == 0.0


@pytest.mark.parametrize("n,tile", [(5, 8), (64, 8), (64, 16), (1, 8)])
def test_tile_aligned_layout_holds_every_assignment(n, tile):
    from dlrover_tpu.ops.grouped_gemm import tile_aligned_layout

    rng = np.random.default_rng(n + tile)
    # skewed: expert 2 most rows, experts 0 and 7 none
    ids = rng.choice(
        np.arange(1, 7), size=n * 2, p=[.1, .5, .1, .1, .1, .1]
    ).astype(np.int32)
    src, valid, dest, tile_expert, n_tiles = (
        np.asarray(a) for a in tile_aligned_layout(jnp.asarray(ids), 8, tile)
    )
    sizes = np.bincount(ids, minlength=8)
    assert src.size == -(-(ids.size + 8 * (tile - 1)) // tile) * tile
    assert int(n_tiles[0]) == int(np.ceil(sizes / tile).sum())
    assert valid.sum() == ids.size  # nothing dropped, whatever the load
    assert sorted(src[valid]) == list(range(ids.size))
    assert (src[dest] == np.arange(ids.size)).all() and valid[dest].all()
    for t in range(src.size // tile):
        rows = slice(t * tile, (t + 1) * tile)
        if t < n_tiles[0]:  # a tile belongs to ONE expert
            assert (ids[src[rows][valid[rows]]] == tile_expert[t]).all()
            assert valid[rows].any()
        else:  # nothing to fetch: the last used tile's expert again
            assert not valid[rows].any()
            assert tile_expert[t] == tile_expert[n_tiles[0] - 1]
    starts = {int(ids[src[p]]): p for p in np.flatnonzero(valid)[::-1]}
    assert all(p % tile == 0 for p in starts.values())


@pytest.mark.parametrize("n", [3, 40])
def test_the_expert_kernel_reads_its_layers_experts_in_place(
    params, n, monkeypatch
):
    """The tiled kernel (interpreted) on the stacks of BOTH layers,
    told ``first_group = layer * E``, equals the plain path on that
    layer's own slice and the reference's one-at-a-time sum."""
    from dlrover_tpu.ops.grouped_gemm import expert_ffn

    monkeypatch.setenv("DLROVER_TPU_PALLAS_INTERPRET", "1")
    layers = params["layers"]
    stacks, _ = keye_vl2._expert_stacks(layers, CFG)
    assert stacks[0].shape == (2 * 8, 64, 32)
    lp = _layer(params, 1)
    x = np.random.default_rng(n).normal(size=(n, 64)).astype(np.float32)
    h, ids, gates = keye_vl2._route(jnp.asarray(x), lp, CFG)
    got = {
        backend: np.asarray(expert_ffn(
            h, ids, gates, *stacks, jnp.int32(8), 8, backend
        ))
        for backend in ("pallas", "jnp")
    }
    np.testing.assert_allclose(got["pallas"], got["jnp"], atol=2e-5)
    want, _ = R._experts(
        h, {k: v[None] for k, v in lp.items()}, 0, HF, None
    )
    np.testing.assert_allclose(got["pallas"], np.asarray(want), atol=2e-5)


# ------------------------------------------- (d) the selection is exact


def _stable_topk(scores, k):
    """Row by row the positions of the ``k`` largest, equal scores
    lowest position first (numpy's stable sort of the negated row)."""
    return np.argsort(-scores, -1, kind="stable")[..., :k]


@pytest.mark.parametrize("k", [1, 5, 16])
def test_exact_topk_is_argsort_with_ties_lowest_first(k):
    rng = np.random.default_rng(k)
    # few distinct values, so most rows tie AT the k-th largest; both
    # signs, a zero, a denormal and -inf (a masked key) among them
    scores = rng.choice(
        np.array([-np.inf, -2.5, -1e-30, 0.0, 1e-30, 0.5, 3.0], np.float32),
        size=(9, 40),
    )
    scores[0] = 0.5  # one value: the first k positions
    scores[1, k:] = -np.inf  # exactly k finite scores
    want = _stable_topk(scores, k)
    # one block a position: a row IS its position
    ids = np.asarray(pa.exact_topk_rows(
        jnp.asarray(scores), k, jnp.tile(jnp.arange(40, dtype=jnp.int32), (9, 1))
    ))
    np.testing.assert_array_equal(ids, want)
    mask = np.asarray(pa.exact_topk_mask(jnp.asarray(scores), k))
    for row in range(scores.shape[0]):
        finite = [i for i in want[row] if np.isfinite(scores[row, i])]
        assert sorted(np.flatnonzero(mask[row])) == sorted(finite)


def test_a_negative_zero_ties_with_zero():
    """``w * relu(...)`` of a negative head weight is ``-0.0``: the
    same score as ``0.0`` to the rule, in the sort and in the mask."""
    scores = np.array(
        [[0.0, -0.0, 1.0, -0.0, 0.0, -1.0],
         [-0.0, 0.0, -0.0, 0.0, 2.0, 0.0]], np.float32,
    )
    tables = jnp.tile(jnp.arange(6, dtype=jnp.int32), (2, 1))
    for k in (2, 3, 4):
        want = _stable_topk(scores, k)
        got = pa.exact_topk_rows(jnp.asarray(scores), k, tables)
        np.testing.assert_array_equal(np.asarray(got), want)
        mask = np.asarray(pa.exact_topk_mask(jnp.asarray(scores), k))
        for row in range(2):
            assert sorted(np.flatnonzero(mask[row])) == sorted(want[row])


def test_topk_rows_are_the_topk_positions_through_the_table():
    rng = np.random.default_rng(8)
    bs, mb, k = 4, 6, 7
    scores = rng.choice(
        np.array([-np.inf, -1.0, 0.0, 0.5, 2.0], np.float32), size=(3, 24)
    )
    tables = rng.permutation(np.arange(1, 40))[: 3 * mb].reshape(3, mb)
    tables = tables.astype(np.int32)
    ids = _stable_topk(scores, k)
    want = np.take_along_axis(tables, ids // bs, 1) * bs + ids % bs
    got = pa.exact_topk_rows(jnp.asarray(scores), k, jnp.asarray(tables))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_below_topk_every_visible_key_is_selected():
    rng = np.random.default_rng(2)
    c, t, start = 6, 24, 3
    scores = rng.normal(size=(c, t)).astype(np.float32)
    visible = np.arange(t)[None] <= (start + np.arange(c))[:, None]
    masked = np.where(visible, scores, -np.inf)
    mask = np.asarray(pa.exact_topk_mask(jnp.asarray(masked), 16))
    np.testing.assert_array_equal(mask, visible)  # 4..9 keys a row < 16


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_selected_rows_are_read_through_the_lanes_own_table(
    backend, monkeypatch
):
    """Two lanes whose tables interleave and run backwards (blocks
    freed and handed out again): row ``selected[b, i]`` is position
    ``i``'s of lane ``b``, wherever its block lies."""
    monkeypatch.setenv("DLROVER_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(5)
    bs, nkv, d, nh, n_sel = 4, 2, 16, 4, 8
    k_pool = rng.normal(size=(12, bs, nkv, d)).astype(np.float32)
    v_pool = rng.normal(size=(12, bs, nkv, d)).astype(np.float32)
    tables = np.array([[9, 2, 7, 4, 0], [3, 8, 1, 0, 0]], np.int32)
    lens = np.array([15, 10], np.int32)
    q = rng.normal(size=(2, nh, d)).astype(np.float32)
    selected = np.stack([
        rng.permutation(lens[0])[:n_sel], rng.permutation(lens[1])[:n_sel]
    ]).astype(np.int32)
    counts = np.array([n_sel, 5], np.int32)  # lane 1 reads 5 rows only
    rows = np.take_along_axis(tables, selected // bs, 1) * bs + selected % bs
    got = np.asarray(pa.sparse_rows_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(rows), jnp.asarray(counts), backend=backend,
    ))
    for b in range(2):
        pos = selected[b, :counts[b]]
        rows = tables[b][pos // bs] * bs + pos % bs
        k = k_pool.reshape(-1, nkv, d)[rows]
        v = v_pool.reshape(-1, nkv, d)[rows]
        for h in range(nh):
            logit = k[:, h // 2] @ q[b, h] * d ** -0.5
            p = np.exp(logit - logit.max())
            want = (p / p.sum()) @ v[:, h // 2]
            np.testing.assert_allclose(got[b, h], want, atol=2e-5)


@pytest.mark.parametrize("start", [0, 24, 40])
def test_the_index_score_kernel_is_the_plain_scan(start, monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_PALLAS_INTERPRET", "1")
    from dlrover_tpu.ops.paged_kernels import index_scores_kernel

    rng = np.random.default_rng(start)
    c, t, heads, d = 24, 64, 3, 8
    qi = jnp.asarray(rng.normal(size=(c, heads, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, heads)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    want = np.asarray(
        pa.prefill_index_scores(qi, w, keys, jnp.int32(start), backend="jnp")
    )
    got = np.asarray(index_scores_kernel(
        qi, w, keys, jnp.int32(start), block_q=8, block_k=16
    ))
    visible = np.arange(t)[None] <= (start + np.arange(c))[:, None]
    np.testing.assert_array_equal(np.isfinite(got), visible)
    np.testing.assert_array_equal(np.isfinite(want), visible)
    np.testing.assert_allclose(got[visible], want[visible], atol=1e-5)


_SMALL = dict(block_q=8, block_k=16)  # 3 query blocks x 4 key blocks


@pytest.mark.parametrize("start,kv_len,heads,blocks,dtype", [
    (0, 24, (4, 2), _SMALL, jnp.float32),
    (24, 48, (4, 2), _SMALL, jnp.float32),
    (40, 61, (4, 2), _SMALL, jnp.float32),
    # a KV head's eight query heads a grid step, as in the cell: key
    # blocks past ``kv_len`` and past each query block's reach, ``kv_len``
    # inside a key block, a selection inside the causal mask
    (0, 24, (16, 2), _SMALL, jnp.float32),
    (24, 48, (16, 2), _SMALL, jnp.float32),
    (40, 61, (16, 2), _SMALL, jnp.float32),
    (24, 48, (2, 2), _SMALL, jnp.float32),  # a group of one
    (24, 48, (8, 1), dict(block_q=24, block_k=16), jnp.float32),
    (24, 48, (16, 2), dict(block_q=8, block_k=64), jnp.float32),
    # the kernel's own blocks on extents smaller than they are
    (0, 24, (16, 2), {}, jnp.float32),
    (40, 64, (16, 2), {}, jnp.float32),
    (24, 48, (16, 2), _SMALL, jnp.bfloat16),
    (40, 64, (8, 1), {}, jnp.bfloat16),
])
def test_the_prefill_kernel_is_the_plain_selected_attention(
    start, kv_len, heads, blocks, dtype, monkeypatch
):
    """The flash form (interpreted) of a chunk's attention over the
    keys ``taken`` marks equals the plain XLA form, key blocks past the
    chunk's reach skipped; a row that reads nothing comes out zero."""
    monkeypatch.setenv("DLROVER_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(start)
    (nh, nkv), c, t, d = heads, 24, 64, 16
    q = rng.normal(size=(c, nh, d)).astype(np.float32)
    k = rng.normal(size=(t, nkv, d)).astype(np.float32)
    v = rng.normal(size=(t, nkv, d)).astype(np.float32)
    visible = np.arange(t)[None] <= (start + np.arange(c))[:, None]
    taken = visible & (rng.random((c, t)) < 0.4)
    taken[3] = False  # a row with no key at all
    args = tuple(jnp.asarray(a, dtype) for a in (q, k, v)) + (
        jnp.asarray(taken),
    )
    want = pa.selected_prefill_attention(
        *args, jnp.int32(start), jnp.int32(kv_len), backend="jnp"
    )
    from dlrover_tpu.ops.paged_kernels import selected_prefill_kernel

    got = selected_prefill_kernel(
        *args, jnp.int32(start), jnp.int32(kv_len), **blocks
    )
    assert got.dtype == want.dtype and got.shape == (c, nh, d)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        # bfloat16: the probabilities are rounded once a key block, and
        # the two forms' key blocks differ
        atol=2e-5 if dtype == jnp.float32 else 2e-2,
    )
    assert (np.asarray(got, np.float32)[3] == 0).all()


def test_a_chunk_the_kernels_blocks_do_not_tile_keeps_the_xla_form(
    monkeypatch
):
    """``selected_prefill_attention`` under the Pallas backend sends a
    chunk to the kernel where its rows and keys tile by the kernel's
    blocks (an extent under a block is one block), and keeps the XLA
    form for the rest."""
    from dlrover_tpu.ops import paged_kernels as pk

    sent = []
    monkeypatch.setattr(
        pk, "selected_prefill_kernel",
        lambda q, *a, **kw: sent.append(q.shape[0]) or q,
    )
    monkeypatch.setattr(pk, "SELECTED_BLOCK_Q", 8)
    monkeypatch.setattr(pk, "SELECTED_BLOCK_K", 16)

    def run(c, t):
        q = jnp.zeros((c, 4, 8))
        kv = jnp.zeros((t, 2, 8))
        return pa.selected_prefill_attention(
            q, kv, kv, jnp.ones((c, t), bool), jnp.int32(0), jnp.int32(t),
            key_block=t, backend="pallas",
        )

    for c, t in [(16, 32), (4, 12), (8, 48)]:
        run(c, t)
    assert sent == [16, 4, 8]
    for c, t in [(12, 32), (16, 40)]:
        run(c, t)
    assert sent == [16, 4, 8]


# ------------------------------------ (e) the third paged leaf in a block


def test_the_pool_holds_the_index_key_in_k_and_vs_blocks():
    cache = paged_cache_config(CFG, 10, 4, 3)
    assert cache.paged_names == ("k", "v", "ik") and cache.lane_state == ()
    pool = init_block_pool(cache)
    assert pool["ik"].shape == (2, 10, 1, 4 * 8)  # a block's keys one row
    assert pool["k"].shape == pool["v"].shape == (2, 10, 4, 2, 16)
    assert region_nbytes_per_block(pool, "ik") == 2 * 4 * 8 * 4
    assert region_nbytes_per_block(pool) == 2 * 4 * 2 * 16 * 4


def test_a_leaf_name_can_be_declared_once():
    class Twice:
        n_layers, n_kv_heads, head_dim, dtype = 1, 1, 8, jnp.float32

        def lane_state(self):
            return {"ik": ((3,), jnp.float32)}

        def paged_leaves(self):
            return {"ik": ((8,), jnp.float32)}

    with pytest.raises(ValueError, match=r"\['ik'\] are taken"):
        paged_cache_config(Twice(), 4, 4, 1)


def test_a_block_ship_carries_the_index_key_bit_for_bit():
    cache = paged_cache_config(CFG, 10, 4, 3)
    rng = np.random.default_rng(7)

    def filled():
        return {
            n: jnp.asarray(rng.normal(size=a.shape), a.dtype)
            for n, a in init_block_pool(cache).items()
        }

    src, dst = filled(), filled()
    before = {n: np.asarray(a) for n, a in dst.items()}
    src_ids, dst_ids = [1, 4, 5], [2, 8, 9]
    regions = extract_block_regions(src, src_ids, cache.paged_names)
    assert len(regions) == 3
    out = insert_block_regions(
        dst, dst_ids, *regions, leaves=cache.paged_names
    )
    untouched = [b for b in range(10) if b not in dst_ids]
    for name, region in zip(cache.paged_names, regions):
        got = np.asarray(out[name])
        assert got[:, dst_ids].tobytes() == region.tobytes()
        assert region.tobytes() == np.asarray(src[name])[:, src_ids].tobytes()
        np.testing.assert_array_equal(
            got[:, untouched], before[name][:, untouched]
        )
    with pytest.raises(ValueError, match="2 region"):
        insert_block_regions(
            dst, dst_ids, *regions[:2], leaves=cache.paged_names
        )


def test_a_shipped_prefill_is_adopted_with_its_index_keys(params):
    prompt = prompts_of((27,), seed=12)[0]  # past topk, 6 full blocks
    alone = make_scheduler(params, capture_logprobs=False, max_slots=1)
    alone.submit(prompt, max_new=10, seed=4)
    want = alone.run()[0]
    pre = make_scheduler(
        params, capture_logprobs=False, max_slots=2, role="prefill"
    )
    rid = pre.submit(prompt, max_new=10, seed=4)
    for _ in range(20):
        pre.step()
        if pre.shipped:
            break
    payload = pre.shipped.pop()
    assert payload["req_id"] == rid and payload["n_blocks"] == 7
    assert payload["ik"].shape == (2, 7, 1, 4 * 8)
    dec = make_scheduler(params, capture_logprobs=False, max_slots=2)
    adopted = dec.submit(
        prompt, max_new=10, seed=4,
        shipped={
            name: payload[name] for name in ("k", "v", "ik", "first_token")
        },
    )
    got = {r.req_id: r for r in dec.run()}[adopted]
    assert dec.shipped_in == 1
    np.testing.assert_array_equal(got.tokens, want.tokens)


def test_a_prefix_hit_reuses_the_index_keys_bit_for_bit(params):
    shared = prompts_of((20,), seed=2)[0]  # five full blocks of 4
    tails = prompts_of((5, 9), seed=4)
    prompts = [np.concatenate([shared, t]) for t in tails]
    sch = make_scheduler(params, capture_logprobs=False, max_slots=1)
    assert sch.prefix_cache and sch.per_token == {}
    sch.submit(prompts[0], max_new=8, seed=0)
    first = sch.run()[0]
    assert first.per_token == {}
    # the five shared blocks stay indexed; what they hold of each leaf
    kept = [
        b for b in range(1, SCHED["num_blocks"])
        if np.asarray(sch._pool["ik"])[:, b].any()
    ][:5]
    before = {
        n: np.asarray(sch._pool[n])[:, kept].tobytes()
        for n in ("k", "v", "ik")
    }
    sch.submit(prompts[1], max_new=8, seed=1)
    second = sch.run()[0]
    st = sch.stats()
    assert st["prefix_hits"] >= 1 and st["prefix_hits_skipped"] == 0
    for n in ("k", "v", "ik"):
        assert np.asarray(sch._pool[n])[:, kept].tobytes() == before[n]
    alone = make_scheduler(params, capture_logprobs=False, max_slots=1)
    alone.submit(prompts[1], max_new=8, seed=1)
    want = alone.run()[0]
    np.testing.assert_array_equal(second.tokens, want.tokens)
    assert second.stats["prefix_hit_blocks"] == 5


# ------------------------------------ (f) the per-position rows of a reply


def test_every_computed_position_has_its_experts(params):
    prompts = prompts_of((30, 7, 25, 12))
    sch = make_scheduler(params)
    res = serve(sch, prompts, max_new=10)
    fwd = jax.jit(
        lambda t: keye_vl2.forward(params, t, CFG, return_experts=True)[1]
    )
    for r in res.values():
        rows = r.per_token["experts"]
        assert rows.shape == (r.tokens.size, 2, 2) and rows.dtype == np.int32
        # the last new token was sampled and never computed
        assert (rows[-1] == -1).all() and (rows[:-1] >= 0).all()
        np.testing.assert_array_equal(
            rows[:-1], np.asarray(fwd(r.tokens[None]))[0, :-1]
        )
        assert (np.diff(np.sort(rows[:-1], -1), axis=-1) > 0).all()


def test_without_logprobs_no_rows_are_kept(params):
    sch = make_scheduler(params, capture_logprobs=False)
    res = serve(sch, prompts_of((9, 21)), max_new=4)
    assert all(r.per_token == {} for r in res.values())
    assert sch.per_token == {}


def test_serve_step_carries_the_selection_and_expert_labels(params, tmp_path):
    path = str(tmp_path / "events.jsonl")
    sch = make_scheduler(params, events=EventLogger(path=path))
    serve(sch, prompts_of((22, 18, 30)), max_new=6)
    from dlrover_tpu.observability.events import read_events

    steps = [
        e["labels"] for e in read_events(path) if e["name"] == "serve_step"
    ]
    decoded = [s for s in steps if s.get("lanes_decode", 0) > 0]
    assert decoded
    ik_bytes = 2 * 8 * 4  # layers x index dim x float32, a cached token
    for s in decoded:
        assert 0 < s["sel_rows"] <= TOPK * s["lanes_decode"]
        # the picked rows are gathered: what is read is what is used
        assert s["read_rows"] == s["sel_rows"]
        assert s["index_bytes"] % ik_bytes == 0
        assert s["index_bytes"] // ik_bytes >= s["sel_rows"]
    routed = [s for s in steps if "experts_hit" in s]
    assert routed
    for s in routed:
        assert s["experts"] == 8 and 1 <= s["experts_hit"] <= 8
        assert s["expert_rows_max"] >= s["expert_rows_mean"] > 0
    st = sch.stats()
    assert st["sel_rows"] == sum(s["sel_rows"] for s in steps)
    assert st["index_bytes"] == sum(s["index_bytes"] for s in steps)
    assert st["steps"] == len(routed)
    assert st["expert_rows_max"] == sum(s["expert_rows_max"] for s in routed)


# --------------------------- unsound combinations are refused by name


def _build(monkeypatch, env=None, **kw):
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    return ContinuousBatchingScheduler(
        CFG, SchedulerConfig(**SCHED),
        paged_decode_fn=PARTS["paged_decode_fn"],
        paged_prefill_fn=PARTS["paged_prefill_fn"], **kw,
    )


@pytest.mark.parametrize("case,env,kw,why", [
    ("decode_k", {"DLROVER_TPU_DECODE_STEPS": "3"}, {},
     "verify program reads K and V only"),
    ("draft", {}, {"draft_cfg": llama.LlamaConfig.tiny()}, "draft model"),
])
def test_unsound_combinations_are_refused_at_construction(
    monkeypatch, case, env, kw, why
):
    with pytest.raises(ValueError, match=why) as err:
        _build(monkeypatch, env, **kw)
    assert "pages more than K and V (ik)" in str(err.value)


def test_the_plain_construction_is_accepted(monkeypatch):
    sch = _build(monkeypatch, capture_logprobs=True)
    assert sorted(sch._pool) == ["ik", "k", "v"]
    assert sch.per_token and not sch.prefix_cache and not sch.lane_state


# -------------------- (g) the blocks in the benchmark carry nothing more


def _dense_parts():
    return tiny_llama_factory(**dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=128,
    ))


def _hybrid_parts():
    return T.parts("falcon_h1", 128)


@pytest.mark.parametrize("parts_of,leaves", [
    (_dense_parts, ["k", "v"]),
    (_hybrid_parts, ["conv", "k", "ssm", "v"]),
])
def test_other_models_pools_and_programs_hold_no_third_leaf(
    parts_of, leaves
):
    parts = parts_of()
    kw = {
        k: parts[k] for k in ("paged_decode_fn", "paged_prefill_fn")
        if k in parts
    }
    sch = ContinuousBatchingScheduler(
        parts["cfg"], SchedulerConfig(**SCHED), capture_logprobs=True, **kw
    )
    assert sorted(sch._pool) == leaves
    assert sch.pool_cfg.paged_leaves == ()
    assert sch.pool_cfg.paged_names == ("k", "v")
    assert sch.per_token == {}
    st = sch.stats()
    assert "sel_rows" not in st and "experts_hit" not in st
    assert sch._selection_labels() == {}
    # the decode program returns the pool, the tokens and the logprobs:
    # no fourth value, and its pool has the same leaves
    sch.sync_weights(parts["params_template_fn"]())
    lanes = np.zeros((3, SCHED["max_seq_len"] // 4 + 2), np.int32)
    out = jax.eval_shape(
        sch._decode_jit, sch._params, sch._pool,
        jnp.zeros((3,), jnp.int32), lanes, sch._keys,
    )
    assert len(out) == 3 and sorted(out[0]) == leaves


@pytest.mark.parametrize("start, c", [(0, 8), (8, 8), (6, 5), (20, 8)])
def test_a_run_is_written_the_same_by_rows_by_cells_and_by_blocks(start, c):
    """``write_rows`` (K and V over the pool's token rows) and
    ``write_leaf_run`` (a leaf's whole blocks) against ``write`` /
    ``write_leaf``, one (block, offset) cell a position: the same pool
    but for the null block, with a run that starts inside a block, ends
    inside one, or runs past the table."""
    rng = np.random.default_rng(start)
    bs, mb, layers, blocks = 4, 6, 2, 9

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k0, v0 = normal(layers, blocks, bs, 2, 8), normal(layers, blocks, bs, 2, 8)
    ik0 = normal(layers, blocks, bs * 5)
    k_new, v_new, ik_new = normal(c, 2, 8), normal(c, 2, 8), normal(c, 5)
    table = np.asarray([3, 7, 1, 8, 2, 5], np.int32)
    positions = start + np.arange(c)
    blks = jnp.asarray(np.where(
        positions // bs < mb, table[np.minimum(positions // bs, mb - 1)], 0
    ))
    offs = jnp.asarray(positions % bs)

    def by_cells(carry, xs, kv):
        kv = kv.write(k_new, v_new, blks, offs)
        return carry, xs, kv.write_leaf("ik", ik_new, blks, offs)

    def by_rows_and_blocks(carry, xs, kv):
        kv = kv.write_rows(k_new, v_new, blks, offs)
        return carry, xs, kv.write_leaf_run(
            "ik", ik_new, jnp.asarray(table), jnp.int32(start)
        )

    want, got = (
        jax.tree_util.tree_leaves(pa.scan_layers_over_pool(
            body, jnp.int32(0), jnp.arange(layers), k0, v0,
            paged={"ik": ik0},
        )[2:])
        for body in (by_cells, by_rows_and_blocks)
    )
    for a, b in zip(want, got):
        np.testing.assert_array_equal(
            np.asarray(a)[:, 1:], np.asarray(b)[:, 1:]
        )
    assert not np.array_equal(np.asarray(got[0])[:, 1:], np.asarray(k0)[:, 1:])


def test_a_layer_scan_without_further_leaves_returns_four_values():
    k = jnp.zeros((2, 3, 4, 1, 8))

    def body(carry, xs, kv):
        assert kv.paged == {}
        return carry + 1, xs, kv

    out = pa.scan_layers_over_pool(body, jnp.int32(0), jnp.arange(2), k, k)
    assert len(out) == 4 and int(out[0]) == 2
    ik = jnp.zeros((2, 3, 4 * 5))  # blocks of 4 rows, 5 wide

    def writes(carry, xs, kv):
        kv = kv.write_leaf(
            "ik", jnp.full((1, 5), xs + 1.0), jnp.array([2]), jnp.array([1])
        )
        return carry, xs, kv

    *_, paged = pa.scan_layers_over_pool(
        writes, jnp.int32(0), jnp.arange(2), k, k, paged={"ik": ik}
    )
    got = np.asarray(paged["ik"])
    assert got.shape == ik.shape
    got = got.reshape(2, 3, 4, 5)
    assert (got[0, 2, 1] == 1).all() and (got[1, 2, 1] == 2).all()
    assert got.sum() == 5 * 3
