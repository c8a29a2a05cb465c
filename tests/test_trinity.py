"""ISSUE 44: Trinity-Large's decoder on the serving plane — window and
full attention layers over a cache whose allocator knows the kinds of
layers, and one chip's share of a layer's routed experts beside a
shared expert (``models/trinity.py``, ``rl/kv_cache.WindowBlocks``,
``ops/grouped_gemm.expert_ffn``'s share, ``ops/paged_kernels``).

Everything is compared with ``benchmarks/reference_trinity.py`` — plain
``jax.numpy`` in float32 that imports nothing of the program — on its
seeded weights, at a small size on the CPU, the Pallas kernels
interpreted: the whole forward, prefill in chunks then decode through
the two-kinded cache with prompts longer than ``window + chunk`` (window
blocks given back and taken again) at a chunk boundary that is no block
boundary, the experts each position chose, and the eight shares of an
expert layer against the uncut layer.
"""

import os
import sys
from functools import partial

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.join(REPO, "benchmarks")):
    if path not in sys.path:
        sys.path.insert(0, path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import reference_trinity as ref  # noqa: E402
import tiny_families as T  # noqa: E402
from dlrover_tpu.models import trinity  # noqa: E402
from dlrover_tpu.ops.grouped_gemm import (  # noqa: E402
    expert_ffn,
    expert_ffn_tiles,
    expert_tile,
    grouped_gemm,
    sort_tokens_by_expert,
    tile_aligned_layout,
)
from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV  # noqa: E402
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

#: the tiny configuration of the family as its file would hold it: a
#: window of 32 below its sequences, 2 of 8 experts held (share 1 of 4)
FILE = dict(
    family="family_trinity",
    vocab_size=256, hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_experts=2,
    num_experts_per_tok=2, num_shared_experts=1, score_func="sigmoid",
    route_norm=True, route_scale=2.448, n_group=1, topk_group=1,
    sliding_window=32, global_attn_every_n_layers=4, mup_enabled=True,
    rms_norm_eps=1e-5, rope_theta=10000,
    deployment=dict(chips_sharing_a_layer=4, share=1),
)
MAX_LEN = 128
TOL = 5e-5  # float32 on both sides: the order of the sums


def program_cfg(file_cfg=FILE, **kw):
    import family_trinity

    kwargs = family_trinity.model_kwargs(file_cfg, MAX_LEN)
    kwargs.update(dtype=jnp.float32, **kw)
    return trinity.TrinityConfig(**kwargs)


@pytest.fixture(scope="module")
def seeded():
    """The reference's seeded tree in float32 (its matrices hold
    bfloat16 values; both sides compute on the same numbers)."""
    return T.params(FILE, 2**31 + 44, "float32")


def reference_logprobs(params, tokens, chosen=None):
    pad = np.zeros((1, MAX_LEN), np.int32)
    pad[0, : tokens.size] = tokens
    if chosen is None:
        return np.asarray(ref.token_logprobs(params, pad, FILE))[0]
    served = np.full((1, MAX_LEN) + chosen.shape[1:], -1, np.int32)
    served[0, : tokens.size] = chosen
    logp, slack = ref.token_logprobs_forced(
        params, pad, FILE, {"experts": served}
    )
    return np.asarray(logp)[0], np.asarray(slack)[0]


def scheduler(cfg, backend, monkeypatch, **sched):
    monkeypatch.setenv(PAGED_KERNEL_ENV, backend)
    geometry = dict(
        max_slots=3, block_size=4, num_blocks=120, max_seq_len=MAX_LEN,
        prefill_chunk=10, temperature=1.0,
    )
    geometry.update(sched)
    return ContinuousBatchingScheduler(
        cfg, SchedulerConfig(**geometry),
        paged_decode_fn=partial(trinity.paged_decode_step, cfg=cfg),
        paged_prefill_fn=partial(trinity.paged_prefill_chunk, cfg=cfg),
        serving_params_fn=partial(trinity.serving_params, cfg=cfg),
        capture_logprobs=True,
    )


class TestTheBlock:
    def test_the_trees_are_one(self, seeded):
        cfg = program_cfg()
        template = jax.eval_shape(
            lambda: trinity.init_params(jax.random.PRNGKey(0), cfg)
        )
        assert jax.tree_util.tree_map(
            lambda a: a.shape, seeded
        ) == jax.tree_util.tree_map(lambda a: a.shape, template)
        assert cfg.layer_windows() == (32, 32, 32, None, 32)
        assert cfg.per_token_outputs() == {"experts": ((4, 2), "int32")}
        assert (cfg.num_experts, cfg.held_experts, cfg.first_expert) == (
            8, 2, 2
        )

    def test_the_forward_is_the_reference(self, seeded):
        cfg = program_cfg()
        tokens = np.random.default_rng(1).integers(
            0, 256, size=(2, 97), dtype=np.int32
        )
        with jax.default_matmul_precision("highest"):
            logits, chosen = trinity.forward(
                seeded, tokens[:, :-1], cfg, return_experts=True
            )
        got = jnp.take_along_axis(
            jax.nn.log_softmax(logits, -1), tokens[:, 1:, None], -1
        )[..., 0]
        want = ref.token_logprobs(seeded, tokens, FILE)
        assert float(jnp.max(jnp.abs(got - want))) < TOL
        # the experts it chose are a valid top-k of the reference's own
        # scores: forced onto them the reference reads no slack
        _, slack = ref.token_logprobs_forced(
            seeded, tokens, FILE,
            {"experts": np.concatenate(
                [np.asarray(chosen), np.zeros((2, 1, 4, 2), np.int32)], 1
            )},
        )
        assert float(jnp.max(slack[:, :-1])) < 1e-5

    def test_the_serving_copy_fuses_the_four_projections_once(self, seeded):
        cfg = program_cfg()
        served = trinity.serving_params(seeded, cfg)
        lp = served["layers"][1]
        assert "wqkvg" in lp and not {"wq", "wk", "wv", "wg"} & set(lp)
        assert lp["wqkvg"].shape == (64, (4 + 2 + 2 + 4) * 16)
        assert lp["w_gate"] is seeded["layers"][1]["w_gate"]
        assert trinity.serving_params(served, cfg) is served
        tokens = np.random.default_rng(2).integers(0, 256, size=(1, 40))
        a = trinity.forward(seeded, tokens, cfg)
        b = trinity.forward(served, tokens, cfg)
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_prefill_in_chunks_then_decode_is_the_reference(
    backend, seeded, monkeypatch
):
    """Prompts of 20-100 tokens in chunks of 10 over blocks of 4 (a
    chunk ends inside a block), answers of 12: the longer ones pass
    window + chunk = 42, so window blocks are given back and re-issued
    while three lanes run side by side.  Every answer token's logprob
    is the reference's, the experts returned are its own top-k."""
    cfg = program_cfg()
    sch = scheduler(cfg, backend, monkeypatch)
    sch.sync_weights(seeded)
    rng = np.random.default_rng(0)
    for i, n in enumerate([70, 90, 20, 55, 100, 43]):
        sch.submit(rng.integers(0, 256, size=n), max_new=12, seed=i)
    results = sch.run()
    assert len(results) == 6
    for r in results:
        t, n = r.tokens, r.new_tokens
        chosen = r.per_token["experts"]
        assert chosen.shape == (t.size, 4, 2)
        assert (chosen[-1] == -1).all() and (chosen[:-1] >= 0).all()
        want, slack = reference_logprobs(seeded, t, chosen)
        diff = np.abs(want[t.size - n - 1: t.size - 1] - r.logprobs)
        assert diff.max() < TOL, (r.req_id, t.size, diff.max())
        assert slack[: t.size - 1].max() < 1e-5
    stats = sch.stats()
    assert sch.compile_counts()["decode"] == 1
    assert stats["preemptions"] == 0
    assert stats["window_blocks_released"] > 0
    assert stats["window_blocks_live"] == 0 == stats["full_blocks_live"]
    # a lane's ring: ceil((32 - 1 + 10) / 4) + 1 blocks, three lanes
    assert stats["window_blocks_peak"] <= 3 * 12
    assert stats["prefix_hits"] == 0 and stats["prefix_hits_skipped"] == 6


def test_the_ring_view_is_rounded_to_the_kernels_key_block():
    """The window layers' position-ordered view is whole key blocks of
    the chunk kernel, whatever that block is: the cell's ring of 385
    blocks of 16 becomes 7 x 1024 rows, a ring shorter than a block
    stays its own length (the kernel then takes it as one block)."""
    from dlrover_tpu.models.trinity import _key_view_blocks
    from dlrover_tpu.ops.paged_kernels import CHUNK_KEY_BLOCK as bk

    for ring, bs in ((385, 16), (200, 16), (65, 16), (97, 32)):
        view = _key_view_blocks(ring, bs)
        assert view >= ring and view * bs % bk == 0
        assert (view - ring) * bs < bk
    assert _key_view_blocks(385, 16) == 7 * bk // 16 == 448
    assert _key_view_blocks(bk // 16, 16) == bk // 16
    assert _key_view_blocks(12, 4) == 12


def test_the_replica_reports_a_pool_of_two_kinds(monkeypatch):
    """``device_report``'s ``pool`` / ``pool_bytes``: the full layer's
    blocks as the traffic asked, the window layers' as the program sized
    them (three lanes' rings of 12 and a null block)."""
    import json

    sch = scheduler(program_cfg(), "jnp", monkeypatch)
    report = sch.pool_report()
    pool = json.loads(report["pool"])
    assert pool["k"] == pool["v"] == [1, 120, 4, 2, 16]
    assert pool["wk"] == pool["wv"] == [4, 3 * 12 + 1, 4, 2, 16]
    assert report["pool_bytes"] == 4 * 2 * (120 + 4 * 37) * 4 * 2 * 16


def test_a_released_block_that_is_read_shows(seeded, monkeypatch):
    """The planted fault of the cell's rehearsal, at the unit's size:
    the position-ordered view of a lane's ring starts ONE BLOCK EARLY
    once the window has passed — at an entry whose block was given back
    (the null block by then, or another lane's) — and the served
    logprobs leave the reference's."""
    from dlrover_tpu.ops import paged_attention as pa

    view = pa.window_table_view

    def one_block_early(ring, first_block, n_blocks=None):
        return view(ring, first_block - (first_block > 0), n_blocks)

    monkeypatch.setattr(pa, "window_table_view", one_block_early)
    cfg = program_cfg()
    sch = scheduler(cfg, "jnp", monkeypatch)
    sch.sync_weights(seeded)
    rng = np.random.default_rng(0)
    for i, n in enumerate([70, 90, 60]):
        sch.submit(rng.integers(0, 256, size=n), max_new=12, seed=i)
    worst = 0.0
    for r in sch.run():
        t, n = r.tokens, r.new_tokens
        want, _ = reference_logprobs(seeded, t, r.per_token["experts"])
        worst = max(worst, np.abs(
            want[t.size - n - 1: t.size - 1] - r.logprobs
        ).max())
    assert worst > 0.05


class TestRefusedAtConstruction:
    @pytest.mark.parametrize("how,why", [
        (dict(env={"DLROVER_TPU_DECODE_STEPS": "3"}), "multi-token decode"),
        (dict(draft=True), "a draft model"),
        (dict(role="prefill"), "the prefill role"),
        (dict(max_seq_len=64), "max_seq_len 64"),
    ])
    def test_what_cannot_be_sound_is_named(self, how, why, monkeypatch):
        cfg = program_cfg()
        for name, value in how.get("env", {}).items():
            monkeypatch.setenv(name, value)
        kwargs = {}
        if how.get("draft"):
            from dlrover_tpu.models.llama import LlamaConfig

            kwargs["draft_cfg"] = LlamaConfig.tiny()
        with pytest.raises(ValueError, match=why):
            ContinuousBatchingScheduler(
                cfg,
                SchedulerConfig(
                    max_slots=2, block_size=4, num_blocks=80,
                    max_seq_len=how.get("max_seq_len", MAX_LEN),
                    prefill_chunk=10,
                ),
                paged_decode_fn=partial(trinity.paged_decode_step, cfg=cfg),
                paged_prefill_fn=partial(
                    trinity.paged_prefill_chunk, cfg=cfg
                ),
                role=how.get("role", "unified"), **kwargs,
            )

    def test_a_config_the_block_does_not_model_is_named(self):
        for kw, why in (
            (dict(score_func="softmax"), "score_func"),
            (dict(n_group=8, topk_group=4), "group limit"),
            (dict(held_experts=6, first_expert=4), "held experts"),
        ):
            with pytest.raises(ValueError, match=why):
                trinity.TrinityConfig.tiny(**kw)


def test_the_step_records_say_what_the_window_did(seeded, monkeypatch,
                                                  tmp_path):
    from dlrover_tpu.observability.events import EventLogger, read_events

    cfg = program_cfg()
    monkeypatch.setenv(PAGED_KERNEL_ENV, "jnp")
    path = str(tmp_path / "events.jsonl")
    sch = ContinuousBatchingScheduler(
        cfg,
        SchedulerConfig(max_slots=2, block_size=4, num_blocks=80,
                        max_seq_len=MAX_LEN, prefill_chunk=10),
        paged_decode_fn=partial(trinity.paged_decode_step, cfg=cfg),
        paged_prefill_fn=partial(trinity.paged_prefill_chunk, cfg=cfg),
        serving_params_fn=partial(trinity.serving_params, cfg=cfg),
        capture_logprobs=True, events=EventLogger(path=path),
    )
    sch.sync_weights(seeded)
    rng = np.random.default_rng(4)
    for i, n in enumerate([50, 75]):
        sch.submit(rng.integers(0, 256, size=n), max_new=8, seed=i)
    sch.run()
    events = read_events(path)
    steps = [e["labels"] for e in events if e["name"] == "serve_step"]
    decoding = [s for s in steps if s["lanes_decode"]]
    assert decoding
    for s in decoding:
        # four window layers read at most the window a lane, the one
        # full layer every cached position
        assert 0 < s["kv_rows_window"] <= 4 * 32 * s["lanes_decode"]
        assert s["kv_rows_full"] * 4 >= s["kv_rows_window"]
    taken = sum(s["window_blocks_taken"] for s in steps)
    released = sum(s["window_blocks_released"] for s in steps)
    assert 0 < released < taken == sch.stats()["window_blocks_allocated"]
    hit = [s for s in steps if "experts_hit" in s]
    assert hit and all(
        s["experts"] == 2 and 0 <= s["experts_hit"] <= 2
        and s["expert_rows_local"] <= s["expert_rows"]
        and s["expert_rows"] % (4 * 2) == 0  # lanes x layers x k
        for s in hit
    )
    chunks = [e["labels"] for e in events if e["name"] == "prefill"]
    assert sorted((c["rows"], c["kv_len"]) for c in chunks)[-1] == (10, 70)
    assert {(c["rows"], c["kv_len"]) for c in chunks} >= {(5, 75), (10, 50)}


# ------------------------------------------------------- the expert share


def _one_expert_at_a_time(x, ids, gates, w_gate, w_up, w_down, first, held):
    out = np.zeros(x.shape, np.float64)
    x64 = np.asarray(x, np.float64)
    for e in range(held):
        g, u, d = (np.asarray(w[e], np.float64) for w in (w_gate, w_up, w_down))
        gate = x64 @ g
        y = ((gate / (1 + np.exp(-gate))) * (x64 @ u)) @ d
        weight = (np.asarray(gates) * (np.asarray(ids) == first + e)).sum(-1)
        out += weight[:, None] * y
    return out


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("first,held", [(0, 3), (5, 3), (0, 8), (6, 2)])
def test_expert_ffn_computes_its_share_and_zero_for_the_rest(
    backend, first, held
):
    rng = np.random.default_rng(first * 10 + held)
    n, d, f, experts, k = 37, 16, 24, 8, 2
    x = rng.normal(size=(n, d)).astype(np.float32)
    ids = np.stack([rng.permutation(experts)[:k] for _ in range(n)]).astype(
        np.int32
    )
    gates = rng.uniform(0.1, 1, size=(n, k)).astype(np.float32)
    stacks = [
        rng.normal(size=(held,) + shape).astype(np.float32) * 0.3
        for shape in ((d, f), (d, f), (f, d))
    ]
    got = expert_ffn(
        jnp.asarray(x), jnp.asarray(ids), jnp.asarray(gates), *stacks, 0,
        experts, backend, first_expert=first, held=held,
    )
    want = _one_expert_at_a_time(x, ids, gates, *stacks, first, held)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # rows none of whose experts live here read exact zeros
    absent = ~((ids >= first) & (ids < first + held)).any(-1)
    assert absent.any() or held == experts
    assert not np.asarray(got)[absent].any()


def _expert_ffn_before(x, expert_ids, gates, w_gate, w_up, w_down,
                       first_group, num_experts, backend):
    """``ops/grouped_gemm.expert_ffn`` as it was before it knew of a
    share (PR 42), to the letter: what a caller that holds every expert
    must still get, bit for bit."""
    n, k = expert_ids.shape
    flat = expert_ids.reshape(-1)
    if backend != "pallas":
        order, sizes = sort_tokens_by_expert(flat, num_experts)
        rows = x[order // k]

        def take(w):
            return jax.lax.dynamic_slice_in_dim(
                w, first_group, num_experts, 0
            )

        act = jax.nn.silu(
            grouped_gemm(rows, take(w_gate), sizes)
        ) * grouped_gemm(rows, take(w_up), sizes)
        out = grouped_gemm(act, take(w_down), sizes).astype(jnp.float32)
        out = jnp.zeros_like(out).at[order].set(out)
    else:
        tile = expert_tile(n * k, num_experts, x.dtype)
        src, _, dest, tile_expert, n_tiles = tile_aligned_layout(
            flat, num_experts, tile
        )
        out = expert_ffn_tiles(
            x[src // k], w_gate, w_up, w_down, tile_expert + first_group,
            n_tiles, tile,
        )[dest].astype(jnp.float32)
    return (out * gates.reshape(-1)[:, None]).reshape(n, k, -1).sum(1)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_a_caller_that_holds_every_expert_gets_bitwise_what_it_got(backend):
    """The sparse block of PR 42 calls ``expert_ffn`` without a share,
    with every layer's experts in one stack and the layer an offset."""
    rng = np.random.default_rng(42)
    n, d, f, experts, k, layers = 24, 16, 24, 8, 2, 3
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    ids = jnp.asarray(
        np.stack([rng.permutation(experts)[:k] for _ in range(n)]), jnp.int32
    )
    gates = jnp.asarray(rng.uniform(0.1, 1, size=(n, k)), jnp.float32)
    stacks = [
        jnp.asarray(rng.normal(size=(layers * experts,) + s) * 0.3,
                    jnp.bfloat16)
        for s in ((d, f), (d, f), (f, d))
    ]
    for layer in range(layers):
        args = (x, ids, gates, *stacks, layer * experts, experts, backend)
        a = jax.jit(expert_ffn, static_argnums=(7, 8))(*args)
        b = jax.jit(_expert_ffn_before, static_argnums=(7, 8))(*args)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_the_eight_shares_add_up_to_the_uncut_layer(seeded):
    """The share test: an expert layer cut over four chips — each the
    shared expert and its 2 of the 8 routed experts — against the
    reference of the WHOLE layer (every expert held by one chip): the
    shares' routed parts, with the shared expert counted once, add up
    to it.  Both the program's layer and the reference's share."""
    whole = dict(FILE, num_experts=8,
                 deployment=dict(chips_sharing_a_layer=1, share=0))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(50, 64)), jnp.float32)
    lp = dict(seeded["layers"][2])
    h = ref._rms_norm(x, lp["mlp_norm"], FILE["rms_norm_eps"])
    for name, shape in (("w_gate", (8, 64, 32)), ("w_up", (8, 64, 32)),
                        ("w_down", (8, 32, 64))):
        lp[name] = jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(jnp.asarray(h), lp, whole, None)
        shared = ref._swiglu(
            jnp.asarray(h), lp["shared_gate"], lp["shared_up"],
            lp["shared_down"],
        )
        routed_ref, routed_prog = 0.0, 0.0
        for share in range(4):
            cut = dict(FILE, deployment=dict(chips_sharing_a_layer=4,
                                             share=share))
            mine = dict(lp, **{
                n: lp[n][2 * share: 2 * share + 2]
                for n in ("w_gate", "w_up", "w_down")
            })
            part, _ = ref._experts(jnp.asarray(h), mine, cut, None)
            routed_ref = routed_ref + (part - shared)
            # the program's router and its share of the experts
            cfg = program_cfg(cut)
            hp, ids, w = trinity._route(x, mine, cfg)
            routed_prog = routed_prog + expert_ffn(
                hp, ids, w, mine["w_gate"], mine["w_up"], mine["w_down"],
                0, cfg.num_experts, "jnp", first_expert=cfg.first_expert,
                held=cfg.held_experts,
            )
    for routed in (routed_ref, routed_prog):
        np.testing.assert_allclose(
            np.asarray(shared + routed), np.asarray(want), atol=2e-5
        )
