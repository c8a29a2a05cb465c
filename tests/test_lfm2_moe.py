"""LFM2's mixture-of-experts decoder (``models/lfm2_moe.py``: gated
short-convolution layers that keep a two-row tail a lane, grouped-query
attention layers whose heads lie two a row of the page pool,
sigmoid-routed experts all held) on the serving plane, at tiny sizes on
the CPU.

The chain of evidence: the benchmark's plain reference
(``benchmarks/reference_lfm2_moe.py``, which imports nothing of the
program; the convolution over shifted copies of the whole sequence,
multi-head attention, no cache) = the program's whole-sequence forward =
what the scheduler serves through chunked prefill (the tail carried in
the lane's slab, chunk edges inside the taps) and paged decode (the tail
shifted in place, the packed rows read by the paged kernels).  Logits are
compared, never tokens.
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

import family_lfm2_moe as F  # noqa: E402
import reference_lfm2_moe as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import lfm2_moe as M  # noqa: E402
from dlrover_tpu.observability.events import EventLogger  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    init_block_pool,
    lane_state_nbytes,
    paged_cache_config,
)

HF = T.config("lfm2_moe")
PUBLISHED = T.published("lfm2-24b-a2b")
KW = T.kwargs("lfm2_moe", 96)
PARTS = T.parts("lfm2_moe", 96)
CFG = PARTS["cfg"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=80, max_seq_len=96,
    prefill_chunk=20, temperature=1.0,
)


@pytest.fixture(scope="module")
def params():
    return T.params("lfm2_moe", 2**31 + 59)


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


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


# ------------------------------------- (a) the forward is the reference


def test_init_params_has_the_reference_tree():
    ours = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), CFG))
    assert jax.tree_util.tree_map(
        lambda a: a.shape, ours
    ) == jax.tree_util.tree_map(
        tuple, R.model_shapes(HF), is_leaf=lambda x: isinstance(x, tuple)
    )
    assert "lm_head" not in ours  # the head is the embedding
    assert CFG.layer_types == tuple(HF["layer_types"])
    assert CFG.layer_keeps() == ("state", "state", "pages", "state", "pages")
    assert (CFG.head_dim, CFG.kv_row_heads, CFG.n_expert_layers) == (16, 2, 3)


def test_forward_matches_the_reference_logits(params):
    """Logits of two sequences over every kind of layer (conv and
    attention under a dense FF and under experts), on the tree as seeded
    and on its serving copy (fused ``wqkv``)."""
    tokens = np.stack(prompts_of((70, 70), seed=3))
    want = np.asarray(R.logits(params, tokens, HF))
    for tree in (params, M.serving_params(params, CFG)):
        got, ids = M.forward(tree, jnp.asarray(tokens), CFG,
                             return_experts=True)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
        assert ids.shape == (2, 70, 3, 2)
    served = M.serving_params(params, CFG)
    assert "wqkv" in served["layers"][2] and "wq" not in served["layers"][2]
    assert served["layers"][0]["conv_w"] is params["layers"][0]["conv_w"]
    assert M.serving_params(served, CFG) is served


@pytest.mark.parametrize("fault", [
    "taps_newest_first", "silu_after_the_taps", "no_gate_b", "no_gate_c",
    "no_k_norm", "no_rotation",
])
def test_a_wrong_form_moves_the_forward(params, monkeypatch, fault):
    """The controls of the benchmark's probe at the tiny size: each
    moves the forward's logits by orders more than the forward differs
    from the reference."""
    tokens = jnp.asarray(np.stack(prompts_of((48,), seed=5)))
    sound = M.forward(params, tokens, CFG)
    if fault == "taps_newest_first":
        conv = M._causal_conv
        monkeypatch.setattr(
            M, "_causal_conv", lambda w, t, act=None: conv(w, t[::-1], act)
        )
    elif fault == "silu_after_the_taps":
        conv = M._causal_conv
        monkeypatch.setattr(
            M, "_causal_conv", lambda w, t, act=None: conv(w, t)
        )
    elif fault in ("no_gate_b", "no_gate_c"):
        inputs = M._conv_inputs

        def faulty(h, lp, cfg):
            u, c = inputs(h, lp, cfg)
            b, _, x = jnp.split(h @ lp["w_in"], 3, axis=-1)
            return (x, c) if fault == "no_gate_b" else (u, jnp.ones_like(c))

        monkeypatch.setattr(M, "_conv_inputs", faulty)
    elif fault == "no_k_norm":
        norm = M._head_norm
        monkeypatch.setattr(
            M, "_head_norm",
            lambda x, w, eps: x if x.shape[-2] == 2 else norm(x, w, eps),
        )
    else:
        monkeypatch.setattr(M, "apply_rope", lambda x, cos, sin: x)
    assert float(jnp.abs(M.forward(params, tokens, CFG) - sound).max()) > 5e-3


# --------------------------- (b) the served path is the reference's forward


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_served_logprobs_match_the_reference(params, backend, monkeypatch):
    # prompts on three lanes, chunks of 20 over blocks of 4: one chunk,
    # two, three and four; last chunks of 1, 2 and 3 real tokens (21,
    # 42, 63: chunk edges inside the three taps) and a full one (40);
    # slots and blocks reused, so a lane's second request starts from a
    # zero tail.  ``pallas``: the kernels interpreted (paged_full_decode,
    # paged_prefill_full over rows of two heads, moe_expert_ffn)
    monkeypatch.setenv(PAGED_KERNEL_ENV, backend)
    prompts = prompts_of(
        (21, 7, 42, 40, 63, 18, 1) if backend == "jnp" else (21, 7, 42)
    )
    sch = make_scheduler(params)
    res = serve(sch, prompts)
    assert sorted(res) == list(range(len(prompts)))
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.new_tokens == 9 + i and r.logprobs.size == r.new_tokens
        np.testing.assert_allclose(
            r.logprobs, reference_logprobs(params, r, p.size), atol=2e-4
        )
        # every computed position has its experts, ids among all 8
        rows = r.per_token["experts"]
        assert rows.shape == (r.tokens.size, 3, 2)
        assert (rows[:-1] >= 0).all() and (rows[:-1] < 8).all()
        assert (rows[-1] == -1).all()
        # forced onto them the reference reads the same logprobs, and
        # in float32 the choice has no slack
        forced, routed = R.forced_readings(
            params, r.tokens[None], HF, {"experts": rows[None]}
        )
        np.testing.assert_allclose(
            np.asarray(forced)[0, p.size - 1:], r.logprobs, atol=2e-4
        )
        assert float(np.asarray(routed)[0].max()) == 0.0
    counts = sch.compile_counts()
    assert counts["decode"] == 1 and counts["sample"] == 1
    st = sch.stats()
    assert st["prefix_hits"] == 0
    assert st["prefix_hits_skipped"] == len(prompts)
    assert st["state_resets"] == len(prompts)


def test_two_lanes_of_different_lengths_decode_in_one_step(params):
    """Two lanes at positions 5 and 37 and an idle one between them
    through ONE decode step: each active lane's logits are the
    reference's at its own position, the idle lane's tail comes out
    bitwise as it went in."""
    served = M.serving_params(params, CFG)
    cache = paged_cache_config(CFG, 40, 4, 3, 20)
    pool = init_block_pool(cache)
    prompts = prompts_of((5, 37), seed=11)
    tables = np.zeros((3, 24), np.int32)
    tables[0, :2] = (1, 2)
    tables[2, :10] = np.arange(3, 13)
    # one program a step, as the scheduler runs them (called eagerly the
    # two steps compiled an operation at a time: 54 s)
    prefill_chunk = jax.jit(partial(M.paged_prefill_chunk, cfg=CFG))
    for lane, p in ((0, prompts[0]), (2, prompts[1])):
        for start in range(0, p.size, 20):
            chunk = np.zeros((1, 20), np.int32)
            real = min(20, p.size - start)
            chunk[0, :real] = p[start:start + real]
            _, pool, _ = prefill_chunk(
                served, jnp.asarray(chunk), pool, jnp.asarray(tables[lane]),
                jnp.int32(start), jnp.int32(lane), jnp.int32(real),
            )
    marker = jnp.full_like(pool["conv"][:, 1], 7.25)
    pool = dict(pool, conv=pool["conv"].at[:, 1].set(marker))
    nxt = np.array([9, 0, 200], np.int32)
    logits, after, rows = jax.jit(partial(M.paged_decode_step, cfg=CFG))(
        served, jnp.asarray(nxt), pool, jnp.asarray(tables),
        jnp.asarray([5, 0, 37], jnp.int32),
        jnp.asarray([True, False, True]),
    )
    np.testing.assert_array_equal(
        np.asarray(after["conv"][:, 1]), np.asarray(marker)
    )
    assert rows["experts"].shape == (3, 3, 2)
    for lane, p in ((0, prompts[0]), (2, prompts[1])):
        seq = np.concatenate([p, nxt[lane:lane + 1]])
        want = np.asarray(R.logits(params, seq[None], HF))[0, -1]
        np.testing.assert_allclose(
            np.asarray(logits[lane]), want, atol=2e-4
        )
        # the tail is the last two inputs u of the lane's sequence
        assert float(jnp.abs(after["conv"][:, lane]).max()) > 0


def test_a_chunk_of_one_token_reaches_back_into_the_old_tail(params):
    """The tail after chunks of real lengths 3 then 1 is the tail after
    one chunk of 4: the one-token chunk keeps the newer row of the old
    tail and appends its own."""
    served = M.serving_params(params, CFG)
    cache = paged_cache_config(CFG, 20, 4, 2, 8)
    table = jnp.asarray(np.arange(1, 9, dtype=np.int32))
    p = prompts_of((4,), seed=13)[0]

    def run(cuts):
        pool, start = init_block_pool(cache), 0
        for real in cuts:
            chunk = np.zeros((1, 8), np.int32)
            chunk[0, :real] = p[start:start + real]
            _, pool, _ = M.paged_prefill_chunk(
                served, jnp.asarray(chunk), pool, table, jnp.int32(start),
                jnp.int32(1), jnp.int32(real), CFG,
            )
            start += real
        return pool

    whole, cut = run((4,)), run((3, 1))
    np.testing.assert_allclose(
        np.asarray(cut["conv"]), np.asarray(whole["conv"]), atol=1e-6
    )
    assert float(jnp.abs(whole["conv"][:, 1]).min()) > 0
    assert float(jnp.abs(whole["conv"][:, 0]).max()) == 0  # lane 0 untouched
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cut[leaf][:, 1]), np.asarray(whole[leaf][:, 1]),
            atol=1e-6,
        )


def test_the_reference_reports_a_wrong_router(params):
    """Experts the reference would not have taken read a slack over 0,
    a malformed row reads inf."""
    tokens = prompts_of((24,), seed=9)[0]
    _, ids = M.forward(params, jnp.asarray(tokens[None]), CFG,
                       return_experts=True)
    ids = np.asarray(ids)
    _, slack = R.forced_readings(params, tokens[None], HF, {"experts": ids})
    assert float(np.asarray(slack).max()) == 0.0
    wrong = ids.copy()
    wrong[0, 5, 1] = (ids[0, 5, 1] + np.array([1, 2])) % 8
    _, slack = R.forced_readings(params, tokens[None], HF, {"experts": wrong})
    assert float(np.asarray(slack)[0, 5]) > 0
    wrong[0, 6, 0] = (-1, 3)
    _, slack = R.forced_readings(params, tokens[None], HF, {"experts": wrong})
    assert np.isinf(np.asarray(slack)[0, 6])


# ---------------------------------------------------------- (c) the router


def test_the_router_is_the_references(params):
    """Scores, the bias in the selection only, the 1e-6 beside the sum
    and ties to the lowest id, against the reference's own lines."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    lp = params["layers"][3]
    h, ids, w = M._route(x, lp, CFG, renorm_eps=M.RENORM_EPS)
    hr = R._rms_norm(x, lp["mlp_norm"], HF["norm_eps"])
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-6)
    s = jax.nn.sigmoid(hr @ lp["router"])
    want = jax.lax.top_k(s + lp["router_bias"], 2)[1]
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want))
    taken = jnp.take_along_axis(s, want, -1)
    np.testing.assert_allclose(
        np.asarray(w),
        np.asarray(taken / (taken.sum(-1, keepdims=True) + 1e-6)), atol=1e-7,
    )
    # the bias decides: without it other experts are taken somewhere
    plain = jax.lax.top_k(s, 2)[1]
    assert (np.sort(np.asarray(plain)) != np.sort(np.asarray(want))).any()
    # weights near zero: the 1e-6 shows (1e-20 would renormalise to 1)
    small = dict(
        lp, mlp_norm=jnp.ones((64,)), router=-0.5 * jnp.ones((64, 8))
    )
    _, _, tiny_w = M._route(
        jnp.ones((3, 64)), small, CFG, renorm_eps=M.RENORM_EPS
    )
    assert float(tiny_w.sum(-1).max()) < 1e-6  # s = sigmoid(-32) each
    _, _, unit_w = M._route(jnp.ones((3, 64)), small, CFG)
    np.testing.assert_allclose(np.asarray(unit_w.sum(-1)), 1.0, atol=1e-6)
    # equal scores (no bias): the lowest ids
    flat = dict(
        lp, router=jnp.zeros_like(lp["router"]),
        router_bias=jnp.zeros_like(lp["router_bias"]),
    )
    _, tie_ids, _ = M._route(x, flat, CFG, renorm_eps=M.RENORM_EPS)
    np.testing.assert_array_equal(
        np.asarray(tie_ids), np.tile(np.array([0, 1]), (40, 1))
    )


@pytest.mark.parametrize("shares", [1, 2])
def test_the_shares_add_up_to_the_uncut_layer(params, shares):
    """With every expert held (the benchmark's cut: share 1 of 1)
    ``expert_ffn`` is the dense sum over the experts the reference
    computes; at two shares of four experts the two partial sums add up
    to it: what a share leaves out is what the other adds."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    lp = params["layers"][2]
    hr = R._rms_norm(x, lp["mlp_norm"], HF["norm_eps"])
    want, _ = R._experts(hr, lp, HF, None)
    held = 8 // shares
    routed = jnp.zeros_like(x)
    for share in range(shares):
        cut = M.Lfm2MoeConfig(**dict(
            KW, held_experts=held, first_expert=held * share
        ))
        mine = {
            n: lp[n][held * share:held * (share + 1)]
            for n in ("w_gate", "w_up", "w_down")
        }
        y, _ = M._ff(x, {**lp, **mine}, cut)
        routed = routed + (y - x)
    np.testing.assert_allclose(
        np.asarray(routed), np.asarray(want), atol=2e-5
    )


# -------------------------------------------------- (d) the cache manager


def test_the_pool_holds_tails_for_conv_layers_and_packed_rows_for_the_others():
    cache = paged_cache_config(CFG, 10, 4, 3, 20)
    assert cache.pages_kv and cache.layer_keeps == (
        "state", "state", "pages", "state", "pages"
    )
    assert (cache.n_state_layers, cache.n_full_layers) == (3, 2)
    # two heads of 16 a row: ONE row of 32 a token
    assert (cache.n_kv_heads, cache.head_dim) == (1, 32)
    pool = init_block_pool(cache)
    assert sorted(pool) == ["conv", "k", "v"]
    assert cache.flat_pages
    assert pool["k"].shape == pool["v"].shape == (2, 10, 4 * 1, 32)
    assert pool["conv"].shape == (3, 3, 2 * 64)
    assert pool["conv"].dtype == jnp.float32
    assert lane_state_nbytes(pool, cache) == 3 * 3 * 128 * 4


def test_the_published_cut_is_two_layers_of_rows_and_six_of_tails():
    """To the byte: 2048 B a token and attention layer in bfloat16 in
    rows of 128 lanes, 16 384 B a lane and conv layer in float32."""
    cfg = M.Lfm2MoeConfig(**F.model_kwargs(PUBLISHED, 4096))
    assert (cfg.head_dim, cfg.kv_row_heads) == (64, 2)
    cache = paged_cache_config(cfg, 72832, 16, 256, 512)
    assert (cache.n_state_layers, cache.n_full_layers) == (6, 2)
    assert (cache.n_kv_heads, cache.head_dim) == (4, 128)
    shapes = jax.eval_shape(lambda: init_block_pool(cache))
    assert shapes["k"].shape == (2, 72832, 16 * 4, 128)
    assert shapes["conv"].shape == (6, 256, 4096)
    per_token = sum(
        np.prod(shapes[n].shape[2:]) * 2 for n in ("k", "v")
    ) // 16
    assert per_token == F.cache_bytes_per_token_layer(PUBLISHED) == 2048
    assert 4 * 4096 == F.lane_state_bytes_per_layer(PUBLISHED) == 16384
    pool_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for n, a in shapes.items() if n in ("k", "v")
    )
    assert round(pool_bytes / 1e9, 2) == 4.77


@pytest.mark.parametrize("heads", [(8, 64), (4, 32), (2, 64)])
def test_a_pool_that_would_be_padded_is_refused_by_name(heads):
    """``paged_cache_config`` builds no pool whose minor axis is half the
    device's lanes (or less) where whole rows of the heads exist, unless
    the model declares them."""

    class Narrow:
        n_layers, dtype = 2, jnp.bfloat16
        n_kv_heads, head_dim = heads

    with pytest.raises(ValueError, match="declare kv_row_heads"):
        paged_cache_config(Narrow, 8, 4, 2)

    class Declared(Narrow):
        kv_row_heads = 128 // heads[1]

    cache = paged_cache_config(Declared, 8, 4, 2)
    assert cache.head_dim == 128
    assert cache.n_kv_heads * 128 == heads[0] * heads[1]

    class Wrong(Narrow):
        kv_row_heads = 3 if heads[0] % 3 else 8

    with pytest.raises(ValueError, match="kv_row_heads"):
        paged_cache_config(Wrong, 8, 4, 2)


def test_heads_of_128_take_the_path_they_took():
    """A model whose heads fill the lanes declares nothing and gets the
    pool it got; one row a head is the identity of both helpers."""
    from dlrover_tpu.models import llama, trinity

    for cfg, want in (
        (llama.LlamaConfig.llama2_7b(), (32, 128)),
        (trinity.TrinityConfig(), (8, 128)),
    ):
        cache = paged_cache_config(cfg, 8, 16, 2, 128)
        assert (cache.n_kv_heads, cache.head_dim) == want
        assert not hasattr(cfg, "kv_row_heads")
    q = jnp.ones((3, 8, 128))
    assert pa.row_queries(q, 4, 1) is q and pa.row_outputs(q, 4, 1) is q


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_packed_rows_read_back_every_heads_keys_and_values(backend):
    """Write then read through the packed layout equals plain
    grouped-query attention over the unpacked keys and values, for every
    head — decode over two lanes and a chunk — and with the two halves
    of every row exchanged it does not."""
    rng = np.random.default_rng(2)
    nh, nkv, hd, r, bs, t = 8, 4, 16, 2, 4, 11
    k = jnp.asarray(rng.standard_normal((2, t, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, t, nkv, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, nh, hd)), jnp.float32)
    lens = np.array([t, 6])
    kv = pa.LayerPool(
        jnp.zeros((9, bs, nkv // r, r * hd)), jnp.zeros((9, bs, nkv // r, r * hd)),
        jnp.int32(0), jnp.int32(0),
    )
    tables = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    for lane in range(2):
        at = np.arange(lens[lane])
        kv = kv.write(
            k[lane, :lens[lane]].reshape(-1, nkv // r, r * hd),
            v[lane, :lens[lane]].reshape(-1, nkv // r, r * hd),
            jnp.asarray(tables[lane][at // bs]), jnp.asarray(at % bs),
        )

    def plain(q1, k1, v1):
        kk, vv = (jnp.repeat(a, nh // nkv, axis=1) for a in (k1, v1))
        att = jax.nn.softmax(
            jnp.einsum("hd,thd->ht", q1, kk) * hd ** -0.5, -1
        )
        return jnp.einsum("ht,thd->hd", att, vv)

    def decode(kv):
        out = pa.paged_decode_attention(
            pa.row_queries(q * r ** 0.5, nkv, r), kv.k, kv.v,
            jnp.asarray(tables), jnp.asarray(lens, jnp.int32), backend,
        )
        return pa.row_outputs(out, nkv, r)

    got = decode(kv)
    for lane in range(2):
        want = plain(q[lane], k[lane, :lens[lane]], v[lane, :lens[lane]])
        np.testing.assert_allclose(
            np.asarray(got[lane]), np.asarray(want), atol=2e-5
        )
    # the fault the layout can have: a row's two halves exchanged
    swapped = kv._replace(
        k=jnp.concatenate([kv.k[..., hd:], kv.k[..., :hd]], -1)
    )
    assert float(jnp.abs(decode(swapped) - got).max()) > 1e-2
    # a chunk of 3 queries at positions 8..10 of lane 0
    qc = jnp.asarray(rng.standard_normal((3, nh, hd)), jnp.float32)
    out = pa.paged_chunk_attention(
        pa.row_queries(qc * r ** 0.5, nkv, r),
        pa.gather_heads_by_position(kv.k, jnp.asarray(tables[0])),
        pa.gather_heads_by_position(kv.v, jnp.asarray(tables[0])),
        jnp.int32(8), jnp.int32(0), None, backend,
    )
    out = pa.row_outputs(out, nkv, r)
    for i in range(3):
        want = plain(qc[i], k[0, :9 + i], v[0, :9 + i])
        np.testing.assert_allclose(
            np.asarray(out[i]), np.asarray(want), atol=2e-5
        )


def test_prefix_hits_and_block_sharing_are_refused(params, monkeypatch):
    sch = make_scheduler(params)
    assert not sch.prefix_cache and sch.lane_state and sch.per_token
    same = prompts_of((24,), seed=17) * 2
    res = serve(sch, same)
    assert sch.stats()["prefix_hits"] == 0
    assert sch.stats()["prefix_hits_skipped"] == 2
    np.testing.assert_array_equal(res[0].tokens[:24], res[1].tokens[:24])
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "2")
    with pytest.raises(ValueError, match="multi-token decode"):
        make_scheduler(params)


# ------------------------------------------------ (e) spans and counters


def test_serve_step_carries_the_routers_and_the_caches_labels(
        params, tmp_path):
    from dlrover_tpu.observability.events import read_events

    path = str(tmp_path / "events.jsonl")
    sch = make_scheduler(params, events=EventLogger(path=path))
    serve(sch, prompts_of((30, 9, 25)))
    steps = [
        e["labels"] for e in read_events(path)
        if e.get("name") == "serve_step" and e["labels"]["lanes_decode"] > 0
    ]
    assert steps
    for rec in steps:
        assert (rec["state_layers"], rec["paged_layers"]) == (3, 2)
        assert rec["state_bytes"] == 3 * 3 * 128 * 4
        assert rec["cache_bytes"] > rec["state_bytes"]
        # every cached position of the decoding lanes, over two layers
        assert rec["kv_rows_full"] >= 2 * rec["lanes_decode"]
        assert rec["kv_rows_full"] % 2 == 0
    # the router's labels ride on the record that COMMITS a step
    routed = [rec for rec in steps if "experts" in rec]
    assert routed
    for rec in routed:
        assert rec["experts"] == 8 and 0 < rec["experts_hit"] <= 8
        assert rec["expert_rows"] == rec["expert_rows_local"] > 0
        assert rec["expert_rows"] % (3 * 2) == 0  # lanes x layers x k
    chunks = [
        e["labels"] for e in read_events(path) if e.get("name") == "prefill"
    ]
    assert sorted((c["rows"], c["kv_len"]) for c in chunks)[-1] == (20, 20)
