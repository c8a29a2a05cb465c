"""Kimi Linear's decoder (``models/kimi_linear.py``: Kimi Delta
Attention layers that keep a per-channel-gated state a lane, NoPE
latent-attention layers that keep one compressed row a token, a share
of sigmoid-routed experts) on the serving plane, at tiny sizes on the
CPU.

The chain of evidence: the benchmark's plain reference
(``benchmarks/reference_kimi_linear.py``, which imports nothing of the
program; the recurrence token by token, multi-head attention, no cache)
= the program's whole-sequence forward = what the scheduler serves
through chunked prefill (the state carried in the lane's slabs, the rows
decompressed) and paged decode (the state updated in place, the rows
read in absorbed form) over a pool that holds state slabs for three
layers, latent pages for one and no ``k`` and no ``v``.  Logits are
compared, never tokens.
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

import family_kimi_linear as F  # noqa: E402
import reference_kimi_linear as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import kimi_linear as M, llama  # noqa: E402
from dlrover_tpu.observability.events import EventLogger  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    block_nbytes,
    init_block_pool,
    lane_state_nbytes,
    paged_cache_config,
    region_nbytes_per_block,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

HF = T.config("kimi_linear")
PUBLISHED = T.published("kimi-linear-48b-a3b")
KW = T.kwargs("kimi_linear", 96)
PARTS = T.parts("kimi_linear", 96)
CFG = PARTS["cfg"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=80, max_seq_len=96,
    prefill_chunk=20, temperature=1.0,
)


@pytest.fixture(scope="module")
def params():
    return T.params("kimi_linear", 2**31 + 42)


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


# ------------------------------------- (a) the forward is the reference


def test_init_params_has_the_reference_tree():
    ours = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), CFG))
    assert jax.tree_util.tree_map(
        lambda a: a.shape, ours
    ) == jax.tree_util.tree_map(
        tuple, R.model_shapes(HF), is_leaf=lambda x: isinstance(x, tuple)
    )
    assert CFG.layer_kinds == ("kda", "kda", "kda", "mla")
    assert R.layer_kinds(HF) == list(CFG.layer_kinds)


def test_forward_matches_the_reference_logits(params):
    """Logits, two sequences past one sub-chunk of the scan, on the tree
    as seeded and on its serving copy (fused ``w_in``, ``w_uk`` /
    ``w_uv``)."""
    tokens = np.stack(prompts_of((80, 80), seed=3))
    want = np.asarray(R.logits(params, tokens, HF))
    for tree in (params, M.serving_params(params, CFG)):
        got, ids = M.forward(tree, jnp.asarray(tokens), CFG,
                             return_experts=True)
        np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
        assert ids.shape == (2, 80, 3, 2)
    served = M.serving_params(params, CFG)
    assert "w_in" in served["layers"][0] and "wq" not in served["layers"][0]
    assert "w_uk" in served["layers"][3] and "wkv_b" not in served["layers"][3]
    assert M.serving_params(served, CFG) is served


def test_the_seeded_decays_differ_a_channel(params):
    """At a zero gate logit a head's decays spread from ~0.9 (0.67 in
    the last head) to ~0.9999: a decay taken a head is another model."""
    lp = params["layers"][0]
    alpha, _ = M._kda_gates(
        jnp.zeros((64,)), jnp.zeros((4,)), lp, CFG
    )
    alpha = np.asarray(alpha)
    assert alpha.shape == (4, 16)
    assert alpha.max() > 0.999 and alpha.min() < 0.95
    assert (alpha.max(-1) / alpha.min(-1) > 1.02).all()


@pytest.mark.parametrize("fault", ["mean_decay", "no_decay"])
def test_the_per_channel_decay_matters(params, monkeypatch, fault):
    """The scalar-gate control: a head's decays replaced by their mean
    (``ops/gdn.py``'s rule), or dropped, moves the forward's logits by
    orders more than the forward differs from the reference."""
    tokens = jnp.asarray(np.stack(prompts_of((64,), seed=5)))
    sound = M.forward(params, tokens, CFG)
    gates = M._kda_gates

    def faulty(f, b, lp, cfg):
        alpha, beta = gates(f, b, lp, cfg)
        if fault == "no_decay":
            return jnp.ones_like(alpha), beta
        mean = jnp.mean(alpha, -1, keepdims=True)
        return jnp.broadcast_to(mean, alpha.shape), beta

    monkeypatch.setattr(M, "_kda_gates", faulty)
    assert float(jnp.abs(M.forward(params, tokens, CFG) - sound).max()) > 5e-3


# --------------------------- (b) the served path is the reference's forward


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_served_logprobs_match_the_reference(params, backend, monkeypatch):
    # prompts on three lanes, chunks of 20 (not a multiple of the block
    # of 4 nor of the scan's sub-chunk): one chunk, two and three, a
    # chunk's padded tail, slots and blocks reused.  ``pallas``: the
    # kernels interpreted (kda_decode_update, mla_sparse_decode,
    # mla_prefill, moe_expert_ffn)
    monkeypatch.setenv(PAGED_KERNEL_ENV, backend)
    prompts = prompts_of(
        (30, 7, 45, 20, 61, 18) if backend == "jnp" else (30, 7, 45)
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
        assert rows.shape == (r.tokens.size, 3, 2)
        assert (rows[:-1] >= 0).all() and (rows[:-1] < 8).all()
        assert (rows[-1] == -1).all()
        # forced onto them the reference reads the same logprobs, and
        # in float32 the choice has no slack
        forced, routed = R.forced_readings(
            params, r.tokens[None], HF, {"experts": rows[None]}
        )
        np.testing.assert_allclose(
            np.asarray(forced)[0, p.size - 1:], r.logprobs, atol=5e-5
        )
        assert float(np.asarray(routed)[0].max()) == 0.0
    assert sch.compile_counts() == {"decode": 1, "prefill": 1, "sample": 1}
    st = sch.stats()
    assert st["prefix_hits"] == 0
    assert st["prefix_hits_skipped"] == len(prompts)
    assert st["state_resets"] == len(prompts)


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


def test_the_absorbed_form_is_the_decompressed_form():
    """Decode attention over the cached rows themselves (``q_nope W_uk``
    against ``[c_kv, k_pe]``, then ``W_uv``) equals multi-head attention
    over the decompressed keys and values, in float32."""
    rng = np.random.default_rng(3)
    h, rank, dn, dr, dv, t = 4, 32, 16, 8, 16, 37
    q = jnp.asarray(rng.standard_normal((h, dn + dr)), jnp.float32)
    c_kv = jnp.asarray(rng.standard_normal((t, rank)), jnp.float32)
    k_pe = jnp.asarray(rng.standard_normal((t, dr)), jnp.float32)
    wkv_b = jnp.asarray(
        rng.standard_normal((rank, h * (dn + dv))), jnp.float32
    ) * rank ** -0.5
    cfg = M.KimiLinearConfig.tiny(dtype=jnp.float32)
    w_uk, w_uv = M._kv_up({"wkv_b": wkv_b}, cfg)
    scale = (dn + dr) ** -0.5
    # decompressed
    kv = (c_kv @ wkv_b).reshape(t, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (t, h, dr))], -1
    )
    p = jax.nn.softmax(jnp.einsum("hd,thd->ht", q, k) * scale, -1)
    want = jnp.einsum("ht,thd->hd", p, kv[..., dn:])
    # absorbed
    q_c = M._per_head(q[None, :, :dn], w_uk, jnp.float32)
    latent = pa._absorbed_attention(
        q_c, q[None, :, dn:], c_kv[None], k_pe[None],
        jnp.ones((1, t), bool), scale,
    )
    got = M._per_head(latent, w_uv, jnp.float32)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The four shares' routed terms plus the shared expert ONCE are the
    layer with every expert here: what a share leaves out is what the
    other shares add."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    lp = dict(params["layers"][1])
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    full = {  # the router's 8 experts
        n: jax.random.normal(k, (8,) + lp[n].shape[1:], jnp.float32) * 0.2
        for n, k in zip(("w_gate", "w_up", "w_down"), keys)
    }
    whole = M.KimiLinearConfig(**dict(KW, held_experts=8, first_expert=0))
    want, ids = M._mlp(x, {**lp, **full}, whole)
    routed = jnp.zeros_like(x)
    shared = None
    for share in range(4):
        cut = M.KimiLinearConfig(**dict(KW, first_expert=2 * share))
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


# -------------------------------------------------- (c) the cache manager


def test_the_pool_holds_slabs_for_state_layers_and_leaves_for_the_others():
    cache = paged_cache_config(CFG, 10, 4, 3, 20)
    assert not cache.pages_kv and cache.layer_keeps == (
        "state", "state", "state", "pages"
    )
    assert (cache.n_state_layers, cache.n_paged_layers) == (3, 1)
    assert cache.paged_names == ("c", "kpe")
    pool = init_block_pool(cache)
    assert sorted(pool) == ["c", "conv", "kda", "kpe"]  # no k, no v
    assert pool["c"].shape == (1, 10, 4, 32)  # ONE layer pages
    assert pool["kpe"].shape == (1, 10, 1, 32)
    assert pool["conv"].shape == (3, 3, 3 * 3 * 64)  # three hold state
    assert pool["kda"].shape == (3, 3, 4, 16, 16)
    assert pool["kda"].dtype == pool["conv"].dtype == jnp.float32
    # bytes a block over the one layer that pages, float32 here
    assert block_nbytes(pool, cache.paged_names) == 4 * (32 + 8) * 4
    assert region_nbytes_per_block(pool, "c") == 4 * 32 * 4
    assert lane_state_nbytes(pool, cache) == 3 * 3 * (576 + 4 * 256) * 4


def test_the_published_cut_is_three_layers_of_leaves_and_nine_of_state():
    """To the byte: 1152 B a token and MLA layer in bfloat16, 2 244 608 B
    a lane and KDA layer in float32."""
    cfg = T.parts(PUBLISHED, 8192, "bfloat16")["cfg"]
    cache = paged_cache_config(cfg, 65, 16, 128, 512)
    pool = jax.eval_shape(lambda: init_block_pool(cache))
    assert sorted(pool) == ["c", "conv", "kda", "kpe"]
    assert pool["c"].shape == (3, 65, 16, 512)
    assert pool["kpe"].shape == (3, 65, 8, 128)  # two tokens a row
    assert pool["conv"].shape == (9, 128, 3 * 12288)
    assert pool["kda"].shape == (9, 128, 32, 128, 128)
    paged = sum(pool[n].size * pool[n].dtype.itemsize for n in ("c", "kpe"))
    assert paged // (3 * 65 * 16) == 1152 == F.cache_bytes_per_token_layer(
        PUBLISHED
    )
    state = sum(
        pool[n].size * pool[n].dtype.itemsize for n in ("conv", "kda")
    )
    assert state // (9 * 128) == 2244608 == F.lane_state_bytes_per_layer(
        PUBLISHED
    )
    assert state == 2585788416  # 2.59 GB


@pytest.mark.parametrize("declares,why", [
    (dict(layer_keeps=None), r"beside lane_state\(\) without layer_keeps"),
    (dict(layer_keeps=lambda: ("state", "both", "state", "pages")),
     'a layer that keeps "both"'),
    (dict(lane_state=None), r"beside layer_keeps\(\) without lane_state"),
    (dict(layer_keeps=lambda: ("state",) * 4), "leaves no layer that keeps "
     "pages"),
    (dict(layer_keeps=lambda: ("pages",) * 4), "leaves no layer to keep"),
    (dict(layer_keeps=lambda: ("state", "pages")), "names 2 layers of 4"),
])
def test_a_declaration_that_cannot_be_laid_out_is_refused(declares, why):
    model = type("Model", (), dict(
        n_layers=4, dtype=jnp.float32, pages_kv=False,
        paged_leaves=staticmethod(CFG.paged_leaves),
        lane_state=staticmethod(CFG.lane_state),
        layer_keeps=staticmethod(CFG.layer_keeps),
    ))
    for name, value in declares.items():
        setattr(model, name, value and staticmethod(value))
    with pytest.raises(ValueError, match=why):
        paged_cache_config(model(), 4, 4, 1, 8)


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
     "a rejected draft would have to roll the state back"),
    ("draft", {}, {"draft_cfg": llama.LlamaConfig.tiny()}, "draft model"),
    ("prefill_role", {}, {"role": "prefill"}, "the prefill role"),
])
def test_what_it_cannot_do_yet_is_refused_by_name(
    monkeypatch, case, env, kw, why
):
    with pytest.raises(ValueError, match=why) as err:
        _build(monkeypatch, env, **kw)
    assert "keeps per-lane state (conv, kda)" in str(err.value)


def test_the_plain_construction_is_accepted(monkeypatch):
    sch = _build(monkeypatch, capture_logprobs=True)
    assert sorted(sch._pool) == ["c", "conv", "kda", "kpe"]
    assert sch.per_token and sch.lane_state and not sch.prefix_cache
    assert sch.state_bytes == 3 * 3 * (576 + 4 * 256) * 4


# ------------------------------------------------------ (d) the records


def test_serve_step_carries_both_kinds_of_cache_and_the_rows_read(
    params, tmp_path
):
    path = str(tmp_path / "events.jsonl")
    sch = make_scheduler(params, events=EventLogger(path=path))
    serve(sch, prompts_of((22, 18, 30)), max_new=6)
    from dlrover_tpu.observability.events import read_events

    events = read_events(path)
    steps = [e["labels"] for e in events if e["name"] == "serve_step"]
    decoded = [s for s in steps if s.get("lanes_decode", 0) > 0]
    assert decoded
    block_bytes = 4 * (32 + 8) * 4  # one MLA layer, float32
    for s in steps:
        assert (s["state_layers"], s["paged_layers"]) == (3, 1)
        assert s["state_bytes"] == sch.state_bytes
        assert (s["cache_bytes"] - s["state_bytes"]) % block_bytes == 0
        assert "index_bytes" not in s
    assert max(s["cache_bytes"] - s["state_bytes"] for s in steps) > 0
    for s in decoded:
        # no indexer: every cached row is picked, and read in whole
        # blocks of 4
        assert s["sel_rows"] == s["cached_rows"] > 0
        assert s["cached_rows"] <= s["read_rows"] < (
            s["cached_rows"] + 4 * s["lanes_decode"]
        )
    routed = [s for s in steps if "experts_hit" in s]
    assert routed
    for s in routed:
        assert s["experts"] == 2 and 0 <= s["experts_hit"] <= 2
        assert 0 <= s["expert_rows_local"] <= s["expert_rows"]
    chunks = [e["labels"] for e in events if e["name"] == "prefill"]
    assert chunks and all(
        0 < c["rows"] <= 20 and c["kv_len"] >= c["rows"] for c in chunks
    )


def test_the_kernels_counts_are_the_issues():
    """One lane at the published widths: 32 heads x 128 x 128 float32
    read and written (2 x 2.10 MB) and the token's operands; a latent
    row of 1152 B read by 32 heads at 60 operations a byte."""
    one = F.kda_update_bytes(PUBLISHED, 1)
    assert one == 2 * 32 * 128 * 128 * 4 + (5 * 4096 + 32) * 4
    assert F.kda_update_bytes(PUBLISHED, 128) == 128 * one
    assert round(128 * 9 * 2 * 32 * 128 * 128 * 4 / 1e9, 2) == 4.83
    assert F.layers_of_kind(PUBLISHED) == {
        "kda": 9, "mla": 3, "dense": 1, "expert": 11,
    }
    flops = F.mla_decode_flops(PUBLISHED, 1000, 1)
    moved = F.mla_decode_bytes(PUBLISHED, 1000, 1)
    assert flops == 3 * 32 * 1000 * (576 + 512) * 2
    assert moved == 3 * (1000 * 576 * 2 + 32 * (576 + 512) * 2)
    assert 59 < flops / (3 * 1000 * 576 * 2) < 61
    # a 512-row chunk behind 2048 cached rows: causal, no selection
    assert F.prefill_attention_flops(PUBLISHED, 512, 2560) == (
        3 * 32 * 2 * (192 + 128) * sum(range(2049, 2561))
    )
    assert F.expert_bytes(PUBLISHED) == 3 * 2304 * 1024 * 2
    # 103.2 M + 8 x 160.4 M + 3 x 150.0 M + 2 x 47.2 M
    assert round(F.total_params(PUBLISHED) / 1e6) == 1931
    assert round(sum(F._kda_params(PUBLISHED)) / 1e6, 2) == 39.51
    assert round(sum(F._mla_params(PUBLISHED)) / 1e6, 2) == 29.11
