"""DeepSeek-V3.2's decoder (``models/deepseek_v32.py``: latent
attention over a cache of one compressed row a token, a learned top-k
indexer, a share of group-routed experts) on the serving plane, at tiny
sizes on the CPU.

The chain of evidence: the benchmark's plain reference
(``benchmarks/reference_deepseek_v32.py``, which imports nothing of the
program; multi-head form, no cache) = the program's whole-sequence
forward = what the scheduler serves through chunked prefill (the rows
decompressed) and paged decode (the rows read in absorbed form) over a
pool that holds no ``k`` and no ``v``.  Logits are compared, never
tokens.  The tiny configuration's ``index_topk`` (32) is below its
sequences, so every test that serves also selects, and its router takes
1 of 2 groups.
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

import family_deepseek_v32 as F  # noqa: E402
import reference_deepseek_v32 as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import deepseek_v32 as M, llama  # noqa: E402
from dlrover_tpu.observability.events import EventLogger  # noqa: E402
import dlrover_tpu.ops.grouped_gemm  # noqa: E402,F401  (the MODULE:
# ``from dlrover_tpu.ops import grouped_gemm`` is the function)
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    block_nbytes,
    extract_block_regions,
    init_block_pool,
    insert_block_regions,
    paged_cache_config,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

HF = T.config("deepseek_v32")
PUBLISHED = T.published("deepseek-v3.2")
grouped_gemm = sys.modules["dlrover_tpu.ops.grouped_gemm"]
KW = T.kwargs("deepseek_v32", 64)
PARTS = T.parts("deepseek_v32", 64)
CFG = PARTS["cfg"]
TOPK = HF["index_topk"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=48, max_seq_len=64,
    prefill_chunk=12, temperature=1.0,
)


@pytest.fixture(scope="module")
def params():
    return T.params("deepseek_v32", 2**31 + 42)


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
        lambda: M.init_params(jax.random.PRNGKey(0), CFG)
    )
    assert jax.tree_util.tree_map(
        lambda a: a.shape, ours
    ) == jax.tree_util.tree_map(
        tuple, R.model_shapes(HF), is_leaf=lambda x: isinstance(x, tuple)
    )


def test_forward_matches_the_reference_per_token(params):
    tokens = np.stack(prompts_of((48, 48), seed=3))  # 3 x index_topk
    logits, experts = M.forward(
        params, jnp.asarray(tokens), CFG, return_experts=True
    )
    logp = jax.nn.log_softmax(logits, -1)
    got = np.take_along_axis(
        np.asarray(logp)[:, :-1], tokens[:, 1:, None], -1
    )[..., 0]
    want = np.asarray(R.token_logprobs(params, tokens, HF))
    np.testing.assert_allclose(got, want, atol=5e-5)
    # the router's choices are the reference's own: forced onto them it
    # reads the same logprobs and no slack anywhere
    forced, slack = R.token_logprobs_forced(
        params, tokens, HF, {"experts": np.asarray(experts)}
    )
    np.testing.assert_allclose(np.asarray(forced), want, atol=5e-5)
    assert float(np.asarray(slack).max()) == 0.0
    # the serving copy (W_kvb as its two views) computes the same
    served = M.forward(
        M.serving_params(params, CFG), jnp.asarray(tokens), CFG
    )
    np.testing.assert_allclose(
        np.asarray(served), np.asarray(logits), atol=5e-5
    )


def test_the_reference_reports_a_wrong_router_and_a_wrong_group(params):
    tokens = np.stack(prompts_of((24,), seed=4))
    _, experts = M.forward(
        params, jnp.asarray(tokens), CFG, return_experts=True
    )
    experts = np.array(experts)
    e = F.router_width(HF)
    per_group = e // HF["n_group"]

    forced = jax.jit(lambda served: R.token_logprobs_forced(
        params, tokens, HF, {"experts": served}
    )[1])

    def slack_of(served):
        return np.asarray(forced(served))

    # the other experts of the SAME group: a wrong choice inside it
    group = experts[0, 5, 1, 0] // per_group
    inside = [
        x for x in range(group * per_group, (group + 1) * per_group)
        if x not in experts[0, 5, 1]
    ]
    swapped = experts.copy()
    swapped[0, 5, 1] = inside[:2]
    got = slack_of(swapped)
    assert got[0, 5] > 0 and np.isfinite(got).all()
    assert (np.delete(got[0], 5)[:5] == 0).all()
    # the two best experts of the OTHER group: the group limit is seen
    other = 1 - group
    moved = experts.copy()
    moved[0, 6, 0] = [other * per_group, other * per_group + 1]
    assert slack_of(moved)[0, 6] > 0
    # one expert from each group: more groups than topk_group (1)
    split = experts.copy()
    split[0, 7, 0] = [0, per_group]
    assert np.isinf(slack_of(split)[0, 7])
    for bad in (-1, e, experts[0, 8, 0, 1]):  # malformed: inf
        broken = experts.copy()
        broken[0, 8, 0, 0] = bad
        assert np.isinf(slack_of(broken)[0, 8])


# ------------------------ (b) chunked prefill + paged decode = the same


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_served_logprobs_match_the_reference(params, backend, monkeypatch):
    # six prompts on three lanes, chunks of 12: the prompts of 30 and
    # 41 end past index_topk = 32 (41 with a chunk boundary past it), the
    # longer requests decode past it (contexts on both sides of the
    # selection), slots and blocks are reused.  ``pallas``: the kernels
    # interpreted (mla_sparse_decode, mla_prefill, index_scores,
    # moe_expert_ffn)
    monkeypatch.setenv(PAGED_KERNEL_ENV, backend)
    prompts = prompts_of(
        (30, 7, 25, 12, 41, 18) if backend == "jnp" else (30, 7, 41)
    )
    sch = make_scheduler(params)
    res = serve(sch, prompts)
    assert sorted(res) == list(range(len(prompts)))
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.new_tokens == 9 + i and r.logprobs.size == r.new_tokens
        np.testing.assert_allclose(
            r.logprobs, reference_logprobs(params, r, p.size), atol=5e-5
        )
        # every computed position has its experts, ids among all 8
        rows = r.per_token["experts"]
        assert rows.shape == (r.tokens.size, 2, 2)
        assert (rows[:-1] >= 0).all() and (rows[:-1] < 8).all()
        assert (rows[-1] == -1).all()
        # and the keys its indexer picked in every layer, a bit a
        # position: min(t + 1, index_topk) of the keys s <= t
        picked = r.per_token["selection"]
        assert picked.shape == (r.tokens.size, 3, CFG.selection_words)
        assert picked.dtype == np.int32 and (picked[-1] == -1).all()
        bits = unpacked(picked[:-1])  # [positions, layers, 64]
        at = np.arange(r.tokens.size - 1)
        assert (
            bits.sum(-1) == np.minimum(at + 1, TOPK)[:, None]
        ).all()
        assert not (bits & (np.arange(64) > at[:, None, None])).any()
        # forced onto both choices the reference reads the same
        # logprobs, and in float32 neither choice has any slack
        forced, routed, chosen = R.forced_readings(
            params, r.tokens[None], HF,
            {n: a[None] for n, a in r.per_token.items()},
        )
        np.testing.assert_allclose(
            np.asarray(forced)[0, p.size - 1:], r.logprobs, atol=5e-5
        )
        assert float(np.asarray(routed)[0].max()) == 0.0
        assert float(np.asarray(chosen)[0].max()) < 1e-5
    assert sch.compile_counts() == {"decode": 1, "prefill": 1, "sample": 1}
    st = sch.stats()
    assert st["prefix_hits"] == 0
    assert st["prefix_hits_skipped"] == len(prompts)
    assert st["sel_rows"] > 0


def unpacked(words):
    """int32 ``[..., W]`` -> bool ``[..., 32 W]``, bit ``b`` of word
    ``j`` position ``32 j + b``."""
    bits = (words[..., None] >> np.arange(32)) & 1
    return bits.reshape(words.shape[:-1] + (-1,)).astype(bool)


def packed(bits):
    words = bits.reshape(bits.shape[:-1] + (-1, 32)).astype(np.uint32)
    return (words << np.arange(32, dtype=np.uint32)).sum(
        -1, dtype=np.uint32
    ).view(np.int32)


def test_a_selection_is_packed_a_bit_a_position():
    rng = np.random.default_rng(3)
    taken = rng.random((5, 50)) < 0.4
    words = np.asarray(M.pack_selection(jnp.asarray(taken), 2))
    assert words.shape == (5, 2) and words.dtype == np.int32
    assert (unpacked(words)[:, :50] == taken).all()
    assert not unpacked(words)[:, 50:].any()
    # the reference reads it the same, and counts every bit
    theirs, count = R._unpack(jnp.asarray(words), 40)
    assert (np.asarray(theirs) == taken[:, :40]).all()
    assert (np.asarray(count) == taken.sum(-1)).all()
    # narrower than the positions it is asked for: the rest unpicked
    assert np.asarray(M.pack_selection(jnp.asarray(taken), 1)).shape == (5, 1)
    theirs, _ = R._unpack(jnp.asarray(words[:, :1]), 40)
    assert (np.asarray(theirs)[:, :32] == taken[:, :32]).all()
    assert not np.asarray(theirs)[:, 32:].any()


@pytest.fixture(scope="module")
def one_served(params):
    """One request served in float32 (prompt 30, 10 new), with what it
    chose, and the reference's readings forced onto it."""
    with jax.default_matmul_precision("highest"):
        sch = make_scheduler(params)
        r = serve(sch, prompts_of((30,), seed=8), max_new=10)[0]
    read = jax.jit(lambda served: R.forced_readings(
        params, r.tokens[None], HF, served
    ))

    def readings(selection):
        with jax.default_matmul_precision("highest"):
            return [np.asarray(a)[0] for a in read({
                "experts": r.per_token["experts"][None],
                "selection": selection[None],
            })]

    return r, readings


@pytest.mark.parametrize("fault", [
    "the newest keys", "a key dropped", "a key it cannot see",
    "never computed",
])
def test_the_reference_follows_and_judges_a_served_selection(
    one_served, fault
):
    """Forced onto the served side's picks the reference attends over
    THEM, and says how far under its own scores a pick lies below a key
    left out; a row that is no selection of the query's reads inf and
    the reference's own is taken in its place."""
    r, readings = one_served
    sound_logp, _, sound_slack = readings(r.per_token["selection"])
    assert sound_slack[:-1].max() < 1e-5
    t, layer = 37, 1  # a decoded position past index_topk
    bits = unpacked(r.per_token["selection"].copy())
    assert bits[t, layer, :t + 1].sum() == TOPK < t
    taken = np.flatnonzero(bits[t, layer])
    if fault == "the newest keys":  # as a bypassed indexer picks
        assert not bits[t, layer, t + 1 - TOPK:t + 1].all()
        bits[t, layer] = False
        bits[t, layer, t + 1 - TOPK:t + 1] = True
    elif fault == "a key dropped":
        bits[t, layer, taken[0]] = False
    elif fault == "a key it cannot see":
        bits[t, layer, taken[0]], bits[t, layer, t + 1] = False, True
    else:
        bits[t] = True  # -1, as a position never computed holds
    logp, _, slack = readings(packed(bits))
    others = np.delete(np.arange(slack.size - 1), t)
    assert (slack[others] < 1e-5).all()
    if fault == "the newest keys":
        # a whole selection, not the indexer's: seen in the slack,
        # finite, and the attention of this and every later position
        # has moved
        assert 1e-3 < slack[t] < np.inf
        assert np.abs(logp - sound_logp)[t:].max() > 1e-4
        np.testing.assert_allclose(logp[:t], sound_logp[:t], atol=1e-6)
    else:
        assert np.isinf(slack[t])
        np.testing.assert_allclose(logp, sound_logp, atol=5e-5)


def test_one_slack_a_position_holds_both_choices(one_served, params):
    """``reference_check.py`` takes ONE slack a position: the larger of
    the router's and ``assumed.selection_slack_weight`` times the
    selection's."""
    r, readings = one_served
    bits = unpacked(r.per_token["selection"].copy())
    bits[37, 1] = False
    bits[37, 1, 38 - TOPK:38] = True
    _, routed, chosen = readings(packed(bits))
    for weight in (1.0, 0.25):
        cfg = dict(HF, assumed=dict(
            HF["assumed"], selection_slack_weight=weight
        ))
        with jax.default_matmul_precision("highest"):
            _, slack = R.token_logprobs_forced(params, r.tokens[None], cfg, {
                "experts": r.per_token["experts"][None],
                "selection": packed(bits)[None],
            })
        np.testing.assert_allclose(
            np.asarray(slack)[0], np.maximum(routed, weight * chosen),
            rtol=1e-6,
        )
        assert np.asarray(slack)[0, 37] > 0


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


# --------------------------------------------- (c) the pieces, one by one


def test_the_absorbed_form_is_the_decompressed_form():
    """Decode reads a picked row as key and value of every head
    (``q_nope W_uk`` against the latent, ``sum p c`` then ``W_uv``); the
    reference decompresses ``k_nope`` and ``v`` a head.  Float32, the
    rotated keys two tokens a row as the pool holds them."""
    rng = np.random.default_rng(0)
    b, h, k, rank, dn, dr, dv = 3, 4, 10, 32, 16, 8, 16
    f32 = np.float32
    c_pool = rng.standard_normal((40, rank)).astype(f32)
    pe_tok = rng.standard_normal((40, dr)).astype(f32)
    w_uk = rng.standard_normal((h, dn, rank)).astype(f32) * rank ** -0.5
    w_uv = rng.standard_normal((h, rank, dv)).astype(f32) * rank ** -0.5
    q_nope = rng.standard_normal((b, h, dn)).astype(f32)
    q_pe = rng.standard_normal((b, h, dr)).astype(f32)
    rows = np.stack([rng.permutation(40)[:k] for _ in range(b)])
    counts = np.array([k, 4, 1])
    scale = 0.3
    latent = pa.latent_rows_decode_attention(
        jnp.einsum("bhd,hdc->bhc", q_nope, w_uk), jnp.asarray(q_pe),
        jnp.asarray(c_pool), jnp.asarray(pe_tok.reshape(20, 2 * dr)),
        jnp.asarray(rows, jnp.int32), jnp.asarray(counts, jnp.int32),
        scale, "jnp",
    )
    got = np.asarray(jnp.einsum("bhc,hcd->bhd", latent, w_uv))
    for i in range(b):
        sel = rows[i, :counts[i]]
        k_nope = np.einsum("sc,hdc->shd", c_pool[sel], w_uk)
        v = np.einsum("sc,hcd->shd", c_pool[sel], w_uv)
        logits = (
            np.einsum("hd,shd->hs", q_nope[i], k_nope)
            + np.einsum("hd,sd->hs", q_pe[i], pe_tok[sel])
        ) * scale
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            got[i], np.einsum("hs,shd->hd", p, v), atol=2e-5
        )


@pytest.mark.parametrize("counts", [(10, 4, 1), (16, 16, 9)])
def test_the_decode_kernel_is_the_plain_absorbed_attention(counts):
    rng = np.random.default_rng(1)
    b, h, k, rank, lanes = 3, 4, 16, 32, 16
    q_c = jnp.asarray(rng.standard_normal((b, h, rank)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((b, h, 8)), jnp.float32)
    c_pool = jnp.asarray(rng.standard_normal((64, rank)), jnp.float32)
    pe_pool = jnp.asarray(rng.standard_normal((32, lanes)), jnp.float32)
    rows = jnp.asarray(
        np.stack([rng.permutation(64)[:k] for _ in range(b)]), jnp.int32
    )
    args = (q_c, q_pe, c_pool, pe_pool, rows, jnp.asarray(counts), 0.25)
    np.testing.assert_allclose(
        np.asarray(pa.latent_rows_decode_attention(*args, "pallas")),
        np.asarray(pa.latent_rows_decode_attention(*args, "jnp")),
        atol=2e-5,
    )


@pytest.mark.parametrize("start,kv_len", [(0, 16), (16, 32), (40, 56)])
def test_the_prefill_kernel_is_the_plain_multi_head_attention(start, kv_len):
    """Keys of one width, values of another, a selection that is data."""
    rng = np.random.default_rng(2)
    c, h, t, dk, dv = 16, 4, 64, 24, 16
    q = jnp.asarray(rng.standard_normal((c, h, dk)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((h, t, dk)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((h, t, dv)), jnp.float32)
    causal = np.arange(t)[None] <= (start + np.arange(c))[:, None]
    taken = jnp.asarray(causal & (rng.random((c, t)) < 0.6) | (
        np.arange(t)[None] == (start + np.arange(c))[:, None]
    ))
    args = (q, k, v, taken, jnp.int32(start), jnp.int32(kv_len), 0.2)
    got = pa.latent_prefill_attention(*args, "pallas")
    assert got.shape == (c, h, dv)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(pa.latent_prefill_attention(*args, "jnp")), atol=2e-5,
    )


def _stream_case(lens, k, scores="random", *, h=4, bs=16, mb=8, rank=32,
                 dr=8, minor=16, seed=3, past=()):
    """Two leaves whose blocks lie scattered, every lane holding the
    blocks of its ``lens`` positions and the null block behind them;
    NaN in the null block, in every block no lane holds and in a last
    held block's rows past the length.  ``scores``: ``random``, or
    ``tied`` (a few values, so that the ``k``-th is shared).  ``past``:
    ``(lane, entry)`` pairs of held table entries that name a block PAST
    the leaves: a gather reads the leaves' last block there, which keeps
    its numbers, and the block the entry named before is NaN."""
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    b, t, n = len(lens), mb * bs, len(lens) * mb + 1
    c_leaf = rng.standard_normal((n, bs, rank)).astype(np.float32)
    pe_tok = rng.standard_normal((n, bs, dr)).astype(np.float32)
    tables = np.zeros((b, mb), np.int32)
    free = 1 + rng.permutation(n - 1)
    unheld = np.ones(n, bool)
    for i, length in enumerate(lens):
        held = -(-int(length) // bs)
        tables[i, :held] = free[i * mb:i * mb + held]
        unheld[tables[i, :held]] = False
        if length % bs:
            c_leaf[tables[i, held - 1], length % bs:] = np.nan
            pe_tok[tables[i, held - 1], length % bs:] = np.nan
    for n_past, (i, entry) in enumerate(past):
        assert entry < -(-int(lens[i]) // bs)
        unheld[tables[i, entry]], unheld[n - 1] = True, False
        tables[i, entry] = n + 3 * n_past
    c_leaf[unheld] = pe_tok[unheld] = np.nan
    score = rng.standard_normal((b, t)).astype(np.float32)
    if scores == "tied":
        score = np.round(score)
    score[np.arange(t)[None] >= lens[:, None]] = -np.inf
    score = jnp.asarray(score)
    tables = jnp.asarray(tables)
    n_sel = min(k, t)
    rows, taken = pa.exact_topk_rows(score, n_sel, tables, with_mask=True)
    np.testing.assert_array_equal(
        np.asarray(taken), np.asarray(pa.exact_topk_mask(score, n_sel))
    )
    return dict(
        # logits of one size whatever the rank
        q_c=jnp.asarray(
            rng.standard_normal((b, h, rank)) * (32 / rank) ** 0.5,
            jnp.float32,
        ),
        q_pe=jnp.asarray(rng.standard_normal((b, h, dr)), jnp.float32),
        c=jnp.asarray(c_leaf),
        pe=jnp.asarray(pe_tok.reshape(n, bs * dr // minor, minor)),
        tables=tables, lens=jnp.asarray(lens), taken=taken, rows=rows,
        score=score, n_sel=n_sel, c_np=c_leaf, pe_np=pe_tok,
    )


def _absorbed_by_hand(a, scale):
    """numpy, a lane at a time over the picked positions alone."""
    taken = np.asarray(a["taken"])
    # an entry past the leaves reads their last block, as a gather does
    tables = np.minimum(np.asarray(a["tables"]), len(a["c_np"]) - 1)
    out = np.zeros(a["q_c"].shape, np.float32)
    bs = a["c_np"].shape[1]
    for i, length in enumerate(np.asarray(a["lens"])):
        at = np.flatnonzero(taken[i, :length])
        if not at.size:
            continue
        c = a["c_np"][tables[i, at // bs], at % bs]
        pe = a["pe_np"][tables[i, at // bs], at % bs]
        logits = (
            np.asarray(a["q_c"][i]) @ c.T + np.asarray(a["q_pe"][i]) @ pe.T
        ) * scale
        p = np.exp(logits - logits.max(-1, keepdims=True))
        out[i] = (p / p.sum(-1, keepdims=True)) @ c
    return out


#: the blocks a group follow from the shapes (two slots of a leaf under
#: 1 MiB): a table of 8 blocks is one group; float32 latents of 512 are
#: read 16 blocks (two slabs of 128 positions) a group, of 1024 8 blocks
_ONE, _G16, _G8 = dict(), dict(rank=512, mb=40), dict(rank=1024, mb=20)
#: groups wider than a turn of the issue loop (``STREAM_UNROLL`` = 16
#: blocks): 32 blocks of latents of 256 in a table of 80, and a table of
#: 48 in one group; and entries of the second group that name a block
#: past the leaves
_G32, _G48 = dict(rank=256, mb=80), dict(mb=48)
_PAST = dict(_G16, past=((0, 21), (1, 18)))


@pytest.mark.parametrize("lens,k,scores,dims", [
    ((0, 1, 20), 32, "random", _ONE),  # empty, one row, under the top k
    ((255, 256, 257), 12, "random", _G16),  # around the first group's edge
    ((511, 512, 513), 12, "random", _G16),  # around the second's
    ((640, 0, 40), 12, "random", _G16),  # a full table, an idle lane between
    ((640, 640, 300), 5, "random", _G16),  # a sparse choice
    ((128, 77, 16), 128, "random", _ONE),  # a dense one: every row
    ((640, 290, 33), 12, "tied", _G16),  # a choice that ends on a tie
    ((320, 129, 33), 12, "tied", _G8),  # ... a slab a group
    # 1, 15, 16 and 17 blocks of the second group (a remainder alone, a
    # whole turn, a turn and a remainder; each waited for block by
    # block), and a full table: two whole groups of two turns, then 16
    ((520, 752, 760, 777, 1280), 12, "random", _G32),
    # 1, 15, 16, 17, 33 and all 48 blocks of the one group
    ((9, 240, 250, 272, 520, 768), 12, "random", _G48),
    ((1280, 0, 1279), 1280, "random", _G32),  # every row, two lanes apart
    # every row picked, so that what a clamped entry reads is weighed
    ((640, 290, 33), 640, "random", _PAST),
], ids=["short", "edge1", "edge2", "idle", "sparse", "dense", "tie", "tie1",
        "turns32", "turns48", "dense32", "past"])
def test_the_streamed_decode_kernel_reads_its_own_blocks_under_the_mask(
    lens, k, scores, dims
):
    """``mla_stream_decode_kernel`` (interpret mode) and the jnp form
    of the streamed fetch against numpy over the picked rows: a position
    that is not picked, or lies past the length, weighs nothing whatever
    its block holds, and a lane of length 0 returns exact zeros.  The
    copies are :func:`_stream_lane_blocks`': whole turns of 16 blocks, a
    remainder, a group held in part waited for block by block, and a
    table entry past the leaves clamped as the jnp form's gather clamps
    it."""
    from dlrover_tpu.ops import paged_kernels as pk

    a = _stream_case(lens, k, scores, **dims)
    want = _absorbed_by_hand(a, 0.25)
    args = (a["q_c"], a["q_pe"], a["c"], a["pe"], a["tables"], a["lens"])
    got = np.asarray(pk.mla_stream_decode_kernel(
        *args, a["taken"], scale=0.25
    ))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not got[np.asarray(a["lens"]) == 0].any()
    # the op's jnp form of the same fetch, whatever the table's width
    np.testing.assert_allclose(
        np.asarray(pa.latent_decode_attention(
            *args, pa.LatentSelection(a["taken"], None), 0.25, backend="jnp"
        )), want, atol=2e-5,
    )


#: the tiny indexers: DeepSeek-V3.2's (2 heads of 16, blocks of 4 in one
#: row of 64: four keys a row) and Keye-VL-2.0's (2 heads of 8, a row of
#: 32); and the published rows at small depth (a key a row of 128, two
#: keys a row) in blocks of 16
_INDEXERS = {
    "v": dict(h=2, di=16, bs=4, minor=64),
    "k": dict(h=2, di=8, bs=4, minor=32),
    "v128": dict(h=8, di=128, bs=16, minor=128),
    "k64": dict(h=16, di=64, bs=16, minor=128),
}


def _index_case(shape, lens, *, mb=10, layers=1, layer=0, idle=(), seed=7):
    """An index-key leaf of ``layers`` layers whose blocks lie
    scattered, every lane holding the blocks of its ``lens`` positions
    of layer ``layer`` and the null block behind them; NaN in every
    block no lane holds (the other layers' whole), in a last held
    block's keys past the length and in the null block past its first
    key.  ``idle``: lanes that do not decode — an all-null table read as
    one position, as the decode step hands them."""
    h, di, bs, minor = (
        _INDEXERS[shape][n] for n in ("h", "di", "bs", "minor")
    )
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    b, nb = len(lens), len(lens) * mb + 1
    leaf = rng.standard_normal((layers * nb, bs, di)).astype(np.float32)
    tables = np.zeros((b, mb), np.int32)
    free = 1 + rng.permutation(nb - 1)
    unheld = np.ones(layers * nb, bool)
    base = layer * nb
    for i, length in enumerate(lens):
        if i in idle:
            continue
        held = min(-(-int(length) // bs), mb)
        tables[i, :held] = free[i * mb:i * mb + held]
        unheld[base + tables[i, :held]] = False
        if length % bs and length < mb * bs:
            leaf[base + tables[i, held - 1], length % bs:] = np.nan
    leaf[unheld] = np.nan
    leaf[base, 0] = rng.standard_normal(di)  # the null block's first key
    return dict(
        qi=jnp.asarray(rng.standard_normal((b, h, di)), jnp.float32),
        w=jnp.asarray(rng.standard_normal((b, h)), jnp.float32),
        leaf=jnp.asarray(leaf.reshape(layers * nb, bs * di // minor, minor)),
        tables=jnp.asarray(tables) + base, lens=jnp.asarray(lens),
        di=di, bs=bs,
    )


@pytest.mark.parametrize("shape,lens,kw", [
    # empty, one key, inside a block, a whole table, past the table
    ("v", (0, 1, 6, 40, 43), {}),
    ("k", (0, 1, 6, 40, 43), {}),
    # a lane that does not decode between two that do
    ("v", (33, 1, 18), dict(idle=(1,))),
    ("k", (33, 1, 18), dict(idle=(1,))),
    # two layers in one leaf: the tables address the second's blocks
    ("v", (40, 7, 21), dict(layers=2, layer=1)),
    ("k", (40, 7, 21), dict(layers=2, layer=1)),
    # groups of 3 entries of a table of 10: a group held in part, a
    # last group shorter than the others
    ("v", (40, 11, 12, 13, 25), dict(span=3)),
    ("k", (40, 11, 12, 13, 25), dict(span=3)),
    # the published rows: a key a row of 128 lanes, and two keys a row
    ("v128", (160, 0, 17, 100), dict(span=4)),
    ("k64", (160, 0, 17, 100), dict(span=4)),
], ids=["v-edges", "k-edges", "v-idle", "k-idle", "v-layer1", "k-layer1",
        "v-groups", "k-groups", "v-rows128", "k-rows128"])
def test_the_index_scores_are_read_from_the_leaf_in_place(shape, lens, kw):
    """``index_decode_scores_kernel`` (interpret mode) against
    ``decode_index_scores`` of the GATHERED keys: the same float32
    scores to 1e-5, ``-inf`` at the same positions — past the length,
    and over every table entry the lane does not hold, whose blocks are
    full of NaN here and poison nothing — and the same exact choice, as
    a mask and as rows, wherever the k-th score stands clear of the
    next by more than that."""
    from dlrover_tpu.ops import paged_kernels as pk

    kw = dict(kw)
    span = kw.pop("span", None)
    a = _index_case(shape, lens, **kw)
    want = np.asarray(pa.decode_index_scores(
        a["qi"], a["w"],
        pa.gather_index_keys(a["leaf"], a["tables"], a["di"], "jnp"),
        a["lens"],
    ))
    got = np.asarray(pk.index_decode_scores_kernel(
        a["qi"], a["w"], a["leaf"], a["tables"], a["lens"], span=span
    ))
    t = a["tables"].shape[1] * a["bs"]
    assert got.shape == want.shape == (len(lens), t)
    counts = np.minimum(np.asarray(a["lens"]), t)
    finite = np.arange(t)[None] < counts[:, None]
    np.testing.assert_array_equal(np.isfinite(want), finite)
    np.testing.assert_array_equal(np.isneginf(got), ~finite)
    # 1e-5 of the scores' size: the heads' sum runs in another order
    size = max(1.0, float(np.abs(want[finite]).max()))
    np.testing.assert_allclose(
        got[finite], want[finite], rtol=1e-5, atol=1e-5 * size
    )
    # the seam hands the kernel the same leaf and tables
    view = pa.gather_index_keys(a["leaf"], a["tables"], a["di"], "pallas")
    assert isinstance(view, pa.IndexKeyView)
    assert view.shape == (len(lens), t, a["di"])
    np.testing.assert_array_equal(np.asarray(pa.decode_index_scores(
        a["qi"], a["w"], view, a["lens"]
    )), np.asarray(pk.index_decode_scores_kernel(
        a["qi"], a["w"], a["leaf"], a["tables"], a["lens"]
    )))
    for k in (1, 5, 16):
        ordered = -np.sort(-want, axis=1)
        with np.errstate(invalid="ignore"):  # -inf less -inf
            clear = (counts <= k) | (
                ordered[:, k - 1] - ordered[:, min(k, t - 1)] > 1e-4 * size
            )
        assert clear.any()
        for pick in (
            lambda s: pa.exact_topk_mask(jnp.asarray(s), k),
            lambda s: pa.exact_topk_rows(
                jnp.asarray(s), k, a["tables"], with_mask=True
            )[1],
        ):
            np.testing.assert_array_equal(
                np.asarray(pick(got))[clear], np.asarray(pick(want))[clear]
            )


@pytest.mark.parametrize("k,streams", [(32, True), (31, False)])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_decode_attention_picks_its_fetch_by_the_tables_width(
    k, streams, backend, monkeypatch
):
    """A table of 128 positions: the selection is a mask alone, and the
    lane's blocks are streamed, while the table holds at most
    ``LATENT_STREAM_WIDTH`` times what is picked; else it names the rows
    too and they are gathered — one choice, made where the selection is
    prepared, and one answer either way."""
    from dlrover_tpu.ops import paged_kernels as pk

    assert pa.latent_decode_streams(128, k) == streams
    assert pa.latent_decode_streams(8192, 2048)
    assert not pa.latent_decode_streams(32768, 2048)
    ran = []
    for name in ("mla_stream_decode_kernel", "mla_sparse_decode_kernel"):
        kernel = getattr(pk, name)
        monkeypatch.setattr(
            pk, name,
            lambda *args, _n=name, _k=kernel, **kw: (
                ran.append(_n), _k(*args, **kw)
            )[1],
        )
    a = _stream_case((128, 50, 0, 97), k)
    if not streams:  # the gathered fetch sends what it masks to row 0
        a["c"], a["pe"] = jnp.nan_to_num(a["c"]), jnp.nan_to_num(a["pe"])
    picked = pa.latent_decode_selection(a["score"], a["n_sel"], a["tables"])
    np.testing.assert_array_equal(
        np.asarray(picked.taken), np.asarray(a["taken"])
    )
    assert (picked.rows is None) == streams
    if not streams:
        np.testing.assert_array_equal(
            np.asarray(picked.rows), np.asarray(a["rows"])
        )
    args = (a["q_c"], a["q_pe"], a["c"], a["pe"], a["tables"], a["lens"])
    got = pa.latent_decode_attention(*args, picked, 0.25, backend)
    want = _absorbed_by_hand(a, 0.25)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    if backend == "pallas":
        assert ran == [
            "mla_stream_decode_kernel" if streams
            else "mla_sparse_decode_kernel"
        ]
    if not streams:  # the other fetch of the same choice: the same sum
        other = pa.latent_decode_attention(
            *args, pa.LatentSelection(a["taken"], None), 0.25, backend
        )
        np.testing.assert_allclose(np.asarray(other), want, atol=2e-5)


@pytest.mark.parametrize("k", [1, 7, 40, 64])
def test_the_selection_as_a_mask_is_the_sorts_choice(k):
    """A decode step that streams takes ``exact_topk_mask`` for its
    selection: the positions ``exact_topk_rows`` names and marks, equal
    scores lowest position first, ``-inf`` never."""
    rng = np.random.default_rng(k)
    scores = np.round(rng.standard_normal((5, 64)) * 2).astype(np.float32)
    scores[1, 30:] = -np.inf
    scores[2, :] = 1.0
    scores[3, 3:] = -np.inf
    scores[4, ::2] *= 0.0  # both zeros are one value
    tables = jnp.arange(5 * 4, dtype=jnp.int32).reshape(5, 4)
    rows, taken = pa.exact_topk_rows(
        jnp.asarray(scores), k, tables, with_mask=True
    )
    got = np.asarray(pa.exact_topk_mask(jnp.asarray(scores), k))
    np.testing.assert_array_equal(got, np.asarray(taken))
    for i in range(5):
        order = np.argsort(-scores[i], kind="stable")[:k]
        order = order[np.isfinite(scores[i, order])]
        assert set(np.flatnonzero(got[i])) == set(order)
        # 16 positions a block: row = table[p // 16] * 16 + p % 16
        assert set(np.asarray(rows[i])[:order.size]) == {
            int(tables[i, p // 16]) * 16 + p % 16 for p in order
        }


def _loop_router(score, k, n_group, topk_group):
    """The group limit as a loop over groups: stable sorts, so equal
    scores go to the lowest id, for groups as for experts."""
    out = []
    for row in np.asarray(score, np.float64):
        groups = row.reshape(n_group, -1)
        gscore = [np.sort(g)[::-1][:2].sum() for g in groups]
        best = np.argsort(-np.asarray(gscore), kind="stable")[:topk_group]
        size = groups.shape[1]
        masked = np.full_like(row, -np.inf)
        for g in best:
            masked[g * size:(g + 1) * size] = row[g * size:(g + 1) * size]
        out.append(np.argsort(-masked, kind="stable")[:k])
    return np.stack(out)


@pytest.mark.parametrize("n_group,topk_group,k", [
    (8, 4, 8), (2, 1, 2), (4, 4, 3), (1, 1, 4),
])
def test_the_group_limited_router_is_the_loop_over_groups(
    n_group, topk_group, k
):
    rng = np.random.default_rng(n_group)
    # a coarse grid of scores: ties between experts AND between groups
    score = rng.integers(0, 6, size=(64, 32)).astype(np.float32) / 4
    got = np.asarray(M.group_limited_topk(
        jnp.asarray(score), k, n_group, topk_group
    ))
    want = _loop_router(score, k, n_group, topk_group)
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    np.testing.assert_array_equal(got, want)


def test_the_yarn_tables_are_the_closed_form():
    """At the published widths: 32 frequencies, the first dims as
    ``theta ** (-2 j / 64)``, the last divided by 40, a linear ramp
    between the correction dims of ``beta_fast`` 32 and ``beta_slow`` 1
    over 4096 positions (dims 10 and 23); the program's table is the
    reference's."""
    cfg = M.DeepSeekV32Config()
    got = M.yarn_inv_freq(cfg)
    j = np.arange(32)
    plain = 1e4 ** (-2.0 * j / 64)
    assert got.shape == (32,)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], plain[23:] / 40, rtol=1e-12)
    ramp = (j - 10) / 13.0
    mid = slice(11, 23)
    np.testing.assert_allclose(
        got[mid], plain[mid] / 40 * ramp[mid] + plain[mid] * (1 - ramp[mid]),
        rtol=1e-12,
    )
    np.testing.assert_allclose(
        got, np.asarray(R.yarn_inv_freq(PUBLISHED)), rtol=1e-6
    )
    assert abs(cfg.softmax_scale - 0.13523) < 1e-5
    assert abs(R.softmax_scale(PUBLISHED) - cfg.softmax_scale) < 1e-9
    cos, sin = M._rope_tables(cfg, jnp.asarray([0, 5]))
    np.testing.assert_allclose(np.asarray(cos)[1], np.cos(5 * got), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin)[0], 0.0, atol=1e-7)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The four shares' routed terms plus the shared expert ONCE are
    the layer with every expert here: what a share leaves out is what
    the other shares add."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    lp = dict(params["layers"][1])
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    full = {  # the router's 8 experts
        n: jax.random.normal(k, (8,) + lp[n].shape[1:], jnp.float32) * 0.2
        for n, k in zip(("w_gate", "w_up", "w_down"), keys)
    }
    whole = M.DeepSeekV32Config(**dict(KW, held_experts=8, first_expert=0))
    want, ids = M._mlp(x, {**lp, **full}, whole)
    routed = jnp.zeros_like(x)
    shared = None
    for share in range(4):
        cut = M.DeepSeekV32Config(**dict(KW, first_expert=2 * share))
        held = {n: w[2 * share:2 * share + 2] for n, w in full.items()}
        y, ids_s = M._mlp(x, {**lp, **held}, cut)
        np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids))
        nothing = {n: jnp.zeros_like(w) for n, w in held.items()}
        shared, _ = M._mlp(x, {**lp, **nothing}, cut)  # x + Shared(h')
        routed = routed + (y - shared)
    np.testing.assert_allclose(
        np.asarray(shared + routed), np.asarray(want), atol=2e-5
    )
    # and the reference leaves out the same terms: share 0's layer
    h = R._rms_norm(x, lp["mlp_norm"], HF["rms_norm_eps"])
    ref, _ = R._experts(h, lp, HF, None)
    mine, _ = M._mlp(x, lp, CFG)
    np.testing.assert_allclose(
        np.asarray(mine - x), np.asarray(ref), atol=2e-5
    )


def test_a_wide_expert_is_taken_in_blocks_of_its_width(monkeypatch):
    """DeepSeek-V3.2's 7168 x 2048 expert does not fit fast memory
    twice: the kernel takes it in two blocks of its width; every width
    the benchmark had before is taken whole."""
    assert grouped_gemm.expert_width_blocks(7168, 2048, 2) == 2
    assert grouped_gemm.expert_width_blocks(3072, 3072, 2) == 1
    assert grouped_gemm.expert_width_blocks(2048, 768, 2) == 1
    rng = np.random.default_rng(7)
    rows = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    w = [
        jnp.asarray(rng.standard_normal(s), jnp.float32) * 0.1
        for s in ((3, 64, 256), (3, 64, 256), (3, 256, 64))
    ]
    groups, used = jnp.asarray([0, 2, 2, 1]), jnp.asarray([3])
    whole = grouped_gemm.expert_ffn_tiles(rows, *w, groups, used, 8)
    # the same kernel with room for half of this expert
    monkeypatch.setattr(
        grouped_gemm, "_EXPERT_WHOLE_BYTES", 2 * 3 * 64 * 128 * 4
    )
    assert grouped_gemm.expert_width_blocks(64, 256, 4) == 2
    split = grouped_gemm.expert_ffn_tiles(rows, *w, groups, used, 8)
    np.testing.assert_allclose(
        np.asarray(split), np.asarray(whole), atol=2e-5
    )
    assert (np.asarray(split)[24:] == 0).all()  # the tile past the used


# --------------------------- (d) a cache without keys and without values


def test_the_pool_holds_the_paged_leaves_alone():
    cache = paged_cache_config(CFG, 10, 4, 3)
    assert not cache.pages_kv
    assert cache.paged_names == ("c", "kpe", "ik")
    assert (cache.n_kv_heads, cache.head_dim) == (0, 0)
    pool = init_block_pool(cache)
    assert sorted(pool) == ["c", "ik", "kpe"]  # no k, no v, no stand-in
    assert pool["c"].shape == (3, 10, 4, 32)  # a latent a row
    assert pool["kpe"].shape == (3, 10, 1, 32)  # a block's keys one row
    assert pool["ik"].shape == (3, 10, 1, 4 * 16)  # in rows, as Keye-VL's
    # bytes a block over the three leaves and three layers, float32
    assert block_nbytes(pool, cache.paged_names) == 3 * 4 * (32 + 8 + 16) * 4


def test_a_token_of_a_layer_keeps_1408_bytes_at_the_published_widths():
    cfg = T.parts(PUBLISHED, 8192, "bfloat16")["cfg"]
    cache = paged_cache_config(cfg, 65, 16, 32, 512)
    pool = jax.eval_shape(lambda: init_block_pool(cache))
    assert sorted(pool) == ["c", "ik", "kpe"]
    assert pool["c"].shape == (7, 65, 16, 512)
    assert pool["kpe"].shape == (7, 65, 8, 128)  # two tokens a row
    assert pool["ik"].shape == (7, 65, 16, 128)  # an index key a row
    per_token_layer = sum(
        a.size * a.dtype.itemsize for a in pool.values()
    ) // (7 * 65 * 16)
    assert per_token_layer == 1408
    assert per_token_layer == F.cache_bytes_per_token_layer(PUBLISHED)
    assert per_token_layer == (
        PUBLISHED["deployment"]["cache_bytes_per_token_layer"]
    )
    # against 128 heads of 192 + 128 in bfloat16
    assert 128 * (192 + 128) * 2 == 81920


def test_a_block_ship_carries_every_leaf_bit_for_bit():
    cache = paged_cache_config(CFG, 10, 4, 3)
    rng = np.random.default_rng(0)
    pool = {
        n: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for n, a in init_block_pool(cache).items()
    }
    regions = extract_block_regions(pool, [3, 7], cache.paged_names)
    assert [r.shape for r in regions] == [
        (3, 2, 4, 32), (3, 2, 1, 32), (3, 2, 1, 4 * 16)
    ]
    other = insert_block_regions(
        init_block_pool(cache), [5, 1], *regions, leaves=cache.paged_names
    )
    for n in pool:
        np.testing.assert_array_equal(
            np.asarray(other[n][:, [5, 1]]), np.asarray(pool[n][:, [3, 7]])
        )


@pytest.mark.parametrize("start, c", [(0, 8), (8, 8), (6, 5), (20, 8)])
def test_a_run_is_written_the_same_by_rows_and_by_blocks(start, c):
    """A prefill chunk's rows (``write_leaf_run``: whole blocks) and a
    decode step's (``write_leaf_rows``: a row, or a token's lanes of a
    row) land in the same cells of a leaf kept in rows."""
    cache = paged_cache_config(CFG, 12, 4, 3)
    pool = init_block_pool(cache)
    table = jnp.asarray([3, 9, 1, 7, 5, 2, 8, 4], jnp.int32)
    rng = np.random.default_rng(start)
    for name, width in (("c", 32), ("kpe", 8)):
        flat = pool[name].reshape((-1,) + pool[name].shape[2:])
        kv = pa.LayerPool(None, None, jnp.int32(12), jnp.int32(1),
                          {name: flat})
        rows = jnp.asarray(rng.standard_normal((c, width)), jnp.float32)
        positions = start + np.arange(c)
        by_rows = kv.write_leaf_rows(
            name, rows, table[positions // 4], jnp.asarray(positions % 4)
        ).paged[name]
        by_blocks = kv.write_leaf_run(
            name, rows, table, jnp.int32(start)
        ).paged[name]
        np.testing.assert_array_equal(
            np.asarray(by_rows), np.asarray(by_blocks)
        )
        assert float(jnp.abs(by_rows).sum()) > 0
        # layer 1's blocks alone were written
        assert float(jnp.abs(by_rows[:12]).sum()) == 0


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
    ("prefill_role", {}, {"role": "prefill"},
     "the prefill role: a shipped prefill carries K and V regions"),
])
def test_what_it_cannot_do_yet_is_refused_by_name(
    monkeypatch, case, env, kw, why
):
    with pytest.raises(ValueError, match=why) as err:
        _build(monkeypatch, env, **kw)
    assert "pages more than K and V (c, kpe, ik)" in str(err.value)


@pytest.mark.parametrize("declares,why", [
    (dict(), "declares no paged_leaves"),
    (dict(lane_state=lambda: {"s": ((2,), jnp.float32)},
          paged_leaves=lambda: {"c": ((8,), jnp.float32)}),
     r"beside lane_state\(\) without layer_keeps"),
    (dict(lane_state=lambda: {"s": ((2,), jnp.float32)},
          layer_keeps=lambda: ("both", "pages"),
          paged_leaves=lambda: {"c": ((8,), jnp.float32)}),
     'a layer that keeps "both"'),
    (dict(layer_keeps=lambda: ("pages", "pages"),
          paged_leaves=lambda: {"c": ((8,), jnp.float32)}),
     r"beside layer_keeps\(\) without lane_state"),
    (dict(layer_windows=lambda: (4, None),
          paged_leaves=lambda: {"c": ((8,), jnp.float32)}),
     "beside layer_windows"),
    (dict(paged_leaves=lambda: {"c": ((8,), jnp.float32)},
          paged_leaf_rows=lambda: {"x": 128}), "is no paged leaf"),
    (dict(paged_leaves=lambda: {"c": ((6,), jnp.float32)},
          paged_leaf_rows=lambda: {"c": 8}), "hold no whole token"),
])
def test_a_declaration_that_cannot_be_laid_out_is_refused(declares, why):
    model = type("Model", (), dict(
        n_layers=2, dtype=jnp.float32, pages_kv=False,
        **{k: staticmethod(v) for k, v in declares.items()},
    ))()
    with pytest.raises(ValueError, match=why):
        paged_cache_config(model, 4, 4, 1, 8)


def test_the_plain_construction_is_accepted(monkeypatch):
    sch = _build(monkeypatch, capture_logprobs=True)
    assert sorted(sch._pool) == ["c", "ik", "kpe"]
    assert sch.per_token and not sch.prefix_cache and not sch.lane_state


# ------------------------------------------------------ (e) the records


def test_serve_step_carries_the_rows_the_experts_and_the_cache(
    params, tmp_path
):
    path = str(tmp_path / "events.jsonl")
    traced = []

    def decode_fn(params, tokens, pool, block_tables, *rest):
        traced.append((block_tables.shape[1], pool["c"].shape[2]))
        return PARTS["paged_decode_fn"](
            params, tokens, pool, block_tables, *rest
        )

    sch = ContinuousBatchingScheduler(
        CFG, SchedulerConfig(**SCHED), paged_decode_fn=decode_fn,
        paged_prefill_fn=PARTS["paged_prefill_fn"],
        serving_params_fn=PARTS["serving_params_fn"],
        capture_logprobs=True, events=EventLogger(path=path),
    )
    sch.sync_weights(params)
    serve(sch, prompts_of((22, 18, 30)), max_new=6)
    # ``read_rows`` is the host's arithmetic, not a count from the
    # device: it holds while the decode program is traced with the table
    # width and the block size the scheduler reckons with
    assert traced == [(sch.sched.max_blocks_per_seq, sch.sched.block_size)]
    assert pa.latent_decode_streams(
        sch.sched.max_blocks_per_seq * sch.sched.block_size, TOPK
    )
    from dlrover_tpu.observability.events import read_events

    events = read_events(path)
    steps = [e["labels"] for e in events if e["name"] == "serve_step"]
    decoded = [s for s in steps if s.get("lanes_decode", 0) > 0]
    assert decoded
    block_bytes = 3 * 4 * (32 + 8 + 16) * 4
    for s in steps:
        assert s["cache_bytes"] % block_bytes == 0
        # the paged leaves are not the index keys alone
        assert "index_bytes" not in s
    for s in decoded:
        assert 0 < s["sel_rows"] <= TOPK * s["lanes_decode"]
        assert s["sel_rows"] <= s["cached_rows"]
        # a table of 64 positions, top 32: decode attention streams the
        # blocks of 4 a lane holds — every cached row, rounded up
        assert s["cached_rows"] <= s["read_rows"] < (
            s["cached_rows"] + 4 * s["lanes_decode"]
        )
        assert s["read_rows"] % 4 == 0
    assert any(s["sel_rows"] < s["cached_rows"] for s in decoded)
    assert max(s["cache_bytes"] for s in steps) > 0
    routed = [s for s in steps if "experts_hit" in s]
    assert routed
    for s in routed:
        assert s["experts"] == 2 and 0 <= s["experts_hit"] <= 2
        assert 0 <= s["expert_rows_local"] <= s["expert_rows"]
    chunks = [e["labels"] for e in events if e["name"] == "prefill"]
    assert chunks and all(
        0 < c["rows"] <= 12 and c["kv_len"] >= c["rows"] for c in chunks
    )


def test_the_rows_a_lane_reads_follow_the_attentions_own_choice():
    """``decode_read_rows``: the held blocks where decode attention
    streams (the table at most ``LATENT_STREAM_WIDTH`` times
    ``index_topk``), the picked rows where it gathers — the one test of
    shapes that prepares the selection
    (``latent_decode_selection``)."""
    cfg = M.DeepSeekV32Config()
    assert pa.latent_decode_streams(8192, 2048)
    assert cfg.decode_read_rows(3900, 8192, 16) == 3904
    assert cfg.decode_read_rows(1, 8192, 16) == 16
    assert cfg.decode_read_rows(100, 1024, 16) == 112  # top = the table
    assert not pa.latent_decode_streams(32768, 2048)
    assert cfg.decode_read_rows(3900, 32768, 16) == 2048
    assert cfg.decode_read_rows(100, 131072, 16) == 100


def test_the_kernels_counts_are_the_issues():
    """One lane and layer at the published widths: 2048 rows of 576
    (2.36 MB) and the lane's queries and outputs; 128 heads x 2048 rows
    x (576 + 512) x 2 = 570 MFLOP — 242 operations a byte of rows, on
    the v5e's ridge (197e12 / 819e9 = 240.5)."""
    one = dict(PUBLISHED, num_hidden_layers=1)
    flops = F.mla_decode_flops(one, 2048, 1)
    moved = F.mla_decode_bytes(one, 2048, 1)
    assert flops == 128 * 2048 * (576 + 512) * 2 == 570425344
    assert moved == 2048 * 576 * 2 + 128 * (576 + 512) * 2
    assert 241 < flops / (2048 * 576 * 2) < 243
    # a 512-row chunk behind 2048 cached rows: every query reads 2048
    assert F.prefill_attention_flops(one, 512, 2560) == (
        128 * 2 * (192 + 128) * 512 * 2048
    )
    # the first chunk is causal: sum of t + 1
    assert F.prefill_attention_flops(one, 512, 512) == (
        128 * 2 * (192 + 128) * (512 * 513 // 2)
    )
    assert F.layers_of_kind(PUBLISHED) == {"dense": 1, "expert": 6}
    assert F.expert_bytes(PUBLISHED) == 3 * 7168 * 2048 * 2
