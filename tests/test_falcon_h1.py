"""The hybrid block (``models/falcon_h1.py``: Mamba-2 heads beside
attention heads) on the serving plane, at tiny sizes on the CPU.

The chain of evidence: the published implementation (``transformers``'
``FalconH1ForCausalLM``, its torch slow path) = the benchmark's plain
reference = the program's whole-sequence forward = what the scheduler
serves through chunked prefill and paged decode with the per-lane state
in its pool.  Every multiplier of the tiny configuration is away from 1.
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

import reference_falcon_h1 as R  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import falcon_h1, llama  # noqa: E402
from dlrover_tpu.ops import ssm  # noqa: E402
from dlrover_tpu.rl.generation_service import (  # noqa: E402
    tiny_llama_factory,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)

#: the published keys alone: ``transformers`` is handed them as they are
HF = {
    k: v for k, v in T.config("falcon_h1").items()
    if k not in ("source", "family", "reduced", "assumed")
}
PARTS = T.parts("falcon_h1", 128)
CFG = PARTS["cfg"]
SCHED = dict(
    max_slots=3, block_size=4, num_blocks=64, max_seq_len=64,
    prefill_chunk=8, temperature=1.0,
)


@pytest.fixture(scope="module")
def params():
    return T.params("falcon_h1", 2**31 + 11)


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


# ------------------------------------------------- (a) the published code


def test_reference_matches_transformers_slow_path(params):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "FalconH1ForCausalLM"):
        pytest.skip("this transformers has no falcon_h1")
    hc = transformers.FalconH1Config(
        **HF, attention_bias=False, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_rms_norm=True,
        mamba_norm_before_gate=False, mlp_bias=False,
        projectors_bias=False, hidden_act="silu",
        tie_word_embeddings=False, attention_dropout=0.0,
    )
    hc._attn_implementation = "eager"
    model = transformers.FalconH1ForCausalLM(hc).eval()

    def t(x, transpose=False):
        x = np.asarray(x.astype(jnp.float32))
        return torch.tensor(x.T.copy() if transpose else x)

    lp = params["layers"]
    state = {
        "model.embed_tokens.weight": t(params["embed"]),
        "model.final_layernorm.weight": t(params["final_norm"]),
        "lm_head.weight": t(params["lm_head"], True),
    }
    names = {
        "norm": ("input_layernorm.weight", False),
        "in_proj": ("mamba.in_proj.weight", True),
        "conv_b": ("mamba.conv1d.bias", False),
        "dt_bias": ("mamba.dt_bias", False),
        "A_log": ("mamba.A_log", False),
        "D": ("mamba.D", False),
        "ssm_norm": ("mamba.norm.weight", False),
        "out_proj": ("mamba.out_proj.weight", True),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "mlp_norm": ("pre_ff_layernorm.weight", False),
        "w_gate": ("feed_forward.gate_proj.weight", True),
        "w_up": ("feed_forward.up_proj.weight", True),
        "w_down": ("feed_forward.down_proj.weight", True),
    }
    for i in range(HF["num_hidden_layers"]):
        for ours, (theirs, transpose) in names.items():
            state[f"model.layers.{i}.{theirs}"] = t(lp[ours][i], transpose)
        # conv_w [K, C] -> conv1d.weight [C, 1, K]
        state[f"model.layers.{i}.mamba.conv1d.weight"] = t(
            lp["conv_w"][i], True
        )[:, None, :]
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("mup_vector" in m or "inv_freq" in m
                                  for m in missing), (missing, unexpected)
    # 19 tokens: no multiple of mamba_chunk_size 8, so the published
    # chunked path pads
    tokens = np.random.default_rng(0).integers(
        0, HF["vocab_size"], size=(2, 19)
    )
    with torch.no_grad():
        theirs = model(torch.tensor(tokens), use_cache=False).logits.numpy()
    ours = np.asarray(R.logits(params, tokens.astype(np.int32), HF))
    assert np.abs(ours).std() > 0.3  # the seeded scales make logits speak
    np.testing.assert_allclose(ours, theirs, atol=1e-4, rtol=0)


# ------------------------------------- (b), (c) the program's own forward


def test_forward_matches_the_reference_per_token(params):
    tokens = np.random.default_rng(3).integers(
        0, HF["vocab_size"], size=(2, 22), dtype=np.int32
    )
    got = jax.nn.log_softmax(
        falcon_h1.forward(params, tokens[:, :-1], CFG), -1
    )
    got = jnp.take_along_axis(got, tokens[:, 1:, None], -1)[..., 0]
    ref = R.token_logprobs(params, tokens, HF)
    assert float(jnp.max(jnp.abs(got - ref))) < 2e-5


def test_init_params_has_the_reference_tree():
    mine = jax.eval_shape(
        lambda: falcon_h1.init_params(jax.random.PRNGKey(0), CFG)
    )
    assert jax.tree_util.tree_map(lambda a: a.shape, mine) == R.model_shapes(HF)


@pytest.mark.parametrize("length,chunk", [(37, 8), (5, 8), (16, 16), (23, 64)])
def test_chunked_scan_is_the_token_recurrence(length, chunk):
    k = jax.random.split(jax.random.PRNGKey(length), 6)
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = jax.random.normal(k[0], (b, length, h, p))
    dt = 0.3 * jax.nn.softplus(jax.random.normal(k[1], (b, length, h)))
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    bm = jax.random.normal(k[2], (b, length, g, n))
    cm = jax.random.normal(k[3], (b, length, g, n))
    d = 1.0 + 0.1 * jax.random.normal(k[4], (h,))
    s0 = jax.random.normal(k[5], (b, h, p, n))
    y_ref, s_ref = ssm.ssm_scan_reference(x, dt, a, bm, cm, d, s0)
    y, s = ssm.ssd_chunk_scan(x, dt, a, bm, cm, d, s0, chunk)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)


def test_a_token_with_zero_dt_advances_nothing():
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (1, 6, 4, 8))
    dt = jnp.full((1, 6, 4), 0.2).at[:, 4:].set(0.0)  # a padded tail
    a = -jnp.ones((4,))
    bm, cm = (jax.random.normal(k[i], (1, 6, 2, 16)) for i in (1, 2))
    s0 = jax.random.normal(k[3], (1, 4, 8, 16))
    _, s_all = ssm.ssd_chunk_scan(x, dt, a, bm, cm, jnp.ones(4), s0, 8)
    _, s_real = ssm.ssd_chunk_scan(
        x[:, :4], dt[:, :4], a, bm[:, :4], cm[:, :4], jnp.ones(4), s0, 8
    )
    np.testing.assert_allclose(s_all, s_real, atol=1e-6)


# ------------------------------------------------ (j) the decode kernel


@pytest.mark.parametrize("layer", [0, 2])
def test_decode_update_kernel_matches_jnp(layer):
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    L, S, h, p, g, n = 3, 5, 4, 8, 2, 16
    state = jax.random.normal(k[0], (L, S, h, p, n))
    x = jax.random.normal(k[1], (S, h, p))
    dt = (0.3 * jax.nn.softplus(jax.random.normal(k[2], (S, h)))).at[2].set(0)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    bm, cm = (jax.random.normal(k[i], (S, g, n)) for i in (3, 4))
    d = 1.0 + 0.1 * jax.random.normal(k[5], (h,))
    y_j, s_j = ssm.ssm_decode_update(
        state, jnp.int32(layer), x, dt, a, bm, cm, d, backend="jnp"
    )
    y_k, s_k = jax.jit(
        lambda st, i: ssm.ssm_decode_update(
            st, i, x, dt, a, bm, cm, d, backend="pallas"
        )
    )(state, jnp.int32(layer))  # interpret mode off the chip
    np.testing.assert_allclose(y_k, y_j, atol=1e-5)
    np.testing.assert_allclose(s_k, s_j, atol=1e-6)
    # a lane with dt == 0 and every other layer: bitwise as given
    assert bool((s_k[layer, 2] == state[layer, 2]).all())
    others = [i for i in range(L) if i != layer]
    assert bool((s_k[jnp.array(others)] == state[jnp.array(others)]).all())
    # and the recurrence itself, one step
    y_r, s_r = ssm.ssm_scan_reference(
        x[:, None], dt[:, None], a, bm[:, None], cm[:, None], d,
        state[layer],
    )
    np.testing.assert_allclose(y_j, y_r[:, 0], atol=1e-5)
    np.testing.assert_allclose(s_j[layer], s_r, atol=1e-6)


# --------------------------------- (d)-(i) through the serving scheduler


def reference_logprobs(params, result, prompt_len):
    ref = np.asarray(R.token_logprobs(params, result.tokens[None], HF))[0]
    return ref[prompt_len - 1:]


def test_served_logprobs_match_the_reference(params):
    # six prompts on three lanes, none a multiple of the chunk of 8:
    # admissions happen while other lanes decode, slots are reused
    prompts = prompts_of((5, 13, 19, 9, 3, 11))
    sch = make_scheduler(params)
    res = serve(sch, prompts)
    assert sorted(res) == list(range(6))
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.new_tokens == 9 + i and r.logprobs.size == r.new_tokens
        np.testing.assert_allclose(
            r.logprobs, reference_logprobs(params, r, p.size), atol=5e-5
        )
    # (i) one decode program, whatever the traffic
    assert sch.compile_counts() == {"decode": 1, "prefill": 1, "sample": 1}
    st = sch.stats()
    assert st["state_resets"] == 6 and st["state_bytes"] == sch.state_bytes > 0
    assert st["prefix_hits"] == 0 and st["prefix_queries"] == 0


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
        np.testing.assert_allclose(
            got[i].logprobs, calm[i].logprobs, atol=5e-5
        )


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
        np.testing.assert_array_equal(together[i].logprobs, want.logprobs)
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
def test_unsound_combinations_are_refused_at_construction(
    monkeypatch, case, env, kw, why
):
    with pytest.raises(ValueError, match=why) as err:
        _build(monkeypatch, env, **kw)
    assert "per-lane state (conv, ssm)" in str(err.value)


def test_the_plain_construction_is_accepted(monkeypatch):
    assert _build(monkeypatch).lane_state


def test_an_inactive_lane_comes_out_of_decode_untouched(params):
    sch = make_scheduler(params)
    long, short = prompts_of((20, 4), seed=6)
    sch.submit(short, max_new=20, seed=0)
    sch.step()  # short: prefilled, decoding from now on
    sch.submit(long, max_new=4, seed=1)
    sch.step()  # long: first chunk of three; short decodes beside it
    lane = next(i for i, sl in enumerate(sch._slots) if sl.phase == "prefill")
    before = {k: np.asarray(sch._pool[k][:, lane]) for k in ("conv", "ssm")}
    assert np.abs(before["ssm"]).max() > 0
    # a decode step alone (no chunk of the prefilling lane in between)
    assert sch._dispatch_decode() == 1
    for k in before:
        np.testing.assert_array_equal(np.asarray(sch._pool[k][:, lane]),
                                      before[k])


def test_serve_step_carries_the_state_labels(params, tmp_path):
    from dlrover_tpu.observability.events import EventLogger, read_events

    path = str(tmp_path / "events.jsonl")
    sch = make_scheduler(params, events=EventLogger(path))
    serve(sch, prompts_of((5, 13)))
    steps = [
        e for e in read_events(path) if e.get("name") == "serve_step"
    ]
    assert steps and all(
        e["labels"]["state_bytes"] == sch.state_bytes for e in steps
    )
    assert sum(e["labels"]["state_resets"] for e in steps) == 2


# ------------------------------------------------- (k) the serving copy


def _tiny(kv_heads, dtype=jnp.bfloat16):
    cfg = falcon_h1.FalconH1Config.tiny(
        num_key_value_heads=kv_heads, dtype=dtype
    )
    return cfg, falcon_h1.init_params(jax.random.PRNGKey(kv_heads), cfg)


@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_serving_params_holds_q_k_v_as_one_fused_leaf(kv_heads):
    """``wqkv`` ``[L, D, (heads + 2 * kv_heads) * head_dim]`` in place of
    ``wq``, ``wk``, ``wv``; the caller's tree untouched; idempotent; a
    tree already in the compute dtype shares every other leaf."""
    cfg, tree = _tiny(kv_heads)
    keys = set(tree["layers"])
    served = falcon_h1.serving_params(tree, cfg)
    assert set(tree["layers"]) == keys
    assert set(served["layers"]) == keys - {"wq", "wk", "wv"} | {"wqkv"}
    q, kv = 4 * cfg.head_dim, kv_heads * cfg.head_dim
    assert served["layers"]["wqkv"].shape == (2, 64, q + 2 * kv)
    for name, lo, hi in (
        ("wq", 0, q), ("wk", q, q + kv), ("wv", q + kv, q + 2 * kv)
    ):
        np.testing.assert_array_equal(
            np.asarray(
                served["layers"]["wqkv"][..., lo:hi].astype(jnp.float32)
            ),
            np.asarray(
                tree["layers"][name].astype(cfg.dtype).astype(jnp.float32)
            ),
        )
    for name in ("in_proj", "wo", "w_down"):
        assert served["layers"][name].dtype == cfg.dtype
    for name in ("norm", "conv_w", "dt_bias", "A_log", "D"):
        assert served["layers"][name] is tree["layers"][name]
    assert falcon_h1.serving_params(served, cfg) is served
    leaves = jax.tree_util.tree_leaves
    # the seeded tree of cell F: already bfloat16, q/k/v apart
    bf16 = jax.tree_util.tree_map(lambda x: x, served)
    w = bf16["layers"].pop("wqkv")
    bf16["layers"].update(
        wq=w[..., :q], wk=w[..., q:q + kv], wv=w[..., q + kv:]
    )
    ids = {id(x) for x in leaves(bf16)}
    fresh = [
        x for x in leaves(falcon_h1.serving_params(bf16, cfg))
        if id(x) not in ids
    ]
    assert [x.shape for x in fresh] == [w.shape]


@pytest.mark.parametrize("program", ["prefill_chunk", "decode"])
@pytest.mark.parametrize("kv_heads", [2, 4], ids=["gqa", "mha"])
def test_step_programs_are_bitwise_equal_on_the_serving_copy(
    program, kv_heads
):
    """The float32 tree (three projections, cast inside) against its
    serving copy (one fused projection and a split, ``key_multiplier``
    on the k third): the same logits, K/V pool, conv tails and states to
    the bit, in the cells' bfloat16."""
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    cfg, tree = _tiny(kv_heads)
    lanes, bs, mb = 3, 4, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 4)

    def pool():  # donated by neither call, but rebuilt to be sure
        zero = init_block_pool(paged_cache_config(cfg, 16, bs, lanes))
        return {
            name: jax.random.normal(k, zero[name].shape, zero[name].dtype)
            for name, k in zip(("k", "v", "conv", "ssm"), keys)
        }

    tables = jnp.asarray(
        1 + np.arange(lanes * mb).reshape(lanes, mb), jnp.int32
    )
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 256, (lanes, 8)), jnp.int32
    )
    if program == "decode":
        fn = lambda p: falcon_h1.paged_decode_step(  # noqa: E731
            p, tokens[:, 0], pool(), tables,
            jnp.asarray([5, 0, 9], jnp.int32),
            jnp.asarray([True, False, True]), cfg,
        )
    else:
        fn = lambda p: falcon_h1.paged_prefill_chunk(  # noqa: E731
            p, tokens[:1], pool(), tables[0], jnp.int32(4),
            jnp.int32(1), jnp.int32(6), cfg,
        )
    fn = jax.jit(fn)
    given, served = fn(tree), fn(falcon_h1.serving_params(tree, cfg))
    assert jax.tree_util.tree_structure(
        given
    ) == jax.tree_util.tree_structure(served)
    for a, b in zip(
        jax.tree_util.tree_leaves(given), jax.tree_util.tree_leaves(served)
    ):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)),
        )


@pytest.mark.parametrize(
    "dtype,cast", [(jnp.float32, 9), (jnp.bfloat16, 1)],
    ids=["float32-tree", "bf16-tree"],
)
def test_weight_cast_says_what_the_hybrid_copy_made(tmp_path, dtype, cast):
    """One ``weight_cast`` span an adoption: a float32 tree has its nine
    matrices, embedding and head written (nine leaves, q/k/v as one);
    cell F's tree, already bfloat16, only the fused leaf; a second
    adoption of the same shapes compiles nothing and a serving copy is
    served as given."""
    from dlrover_tpu.observability.events import EventLogger, read_events

    cfg, tree = _tiny(2)
    matrices = (
        "in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_gate", "w_up",
        "w_down",
    )
    tree = {
        **tree,
        "embed": tree["embed"].astype(dtype),
        "lm_head": tree["lm_head"].astype(dtype),
        "layers": {
            **tree["layers"],
            **{k: tree["layers"][k].astype(dtype) for k in matrices},
        },
    }
    path = str(tmp_path / "events.jsonl")
    sch = ContinuousBatchingScheduler(
        cfg, SchedulerConfig(**SCHED),
        paged_decode_fn=partial(falcon_h1.paged_decode_step, cfg=cfg),
        paged_prefill_fn=partial(falcon_h1.paged_prefill_chunk, cfg=cfg),
        serving_params_fn=partial(falcon_h1.serving_params, cfg=cfg),
        events=EventLogger(path),
    )
    sch.sync_weights(tree)
    programs = llama._cast_and_fuse._cache_size()
    sch.sync_weights(tree, generation=2)
    assert llama._cast_and_fuse._cache_size() == programs
    served = sch._params
    sch.sync_weights(served)
    assert sch._params is served
    nbytes = lambda t: sum(  # noqa: E731
        x.nbytes for x in jax.tree_util.tree_leaves(t)
    )
    labels = [
        e["labels"] for e in read_events(path)
        if e.get("name") == "weight_cast"
    ]
    made = dict(
        bytes_in=nbytes(tree), bytes_out=nbytes(served), leaves_cast=cast,
        leaves_fused=3,
    )
    assert labels == [
        made,
        dict(made, generation=2),
        dict(
            bytes_in=nbytes(served), bytes_out=nbytes(served),
            leaves_cast=0, leaves_fused=0,
        ),
    ]
    assert (nbytes(tree) == nbytes(served)) == (dtype == jnp.bfloat16)


# ------------------------------------------ (l) the dense block unchanged


def test_a_model_without_lane_state_has_a_pool_of_k_and_v():
    parts = tiny_llama_factory(**dict(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=128,
    ))
    sch = ContinuousBatchingScheduler(parts["cfg"], SchedulerConfig(**SCHED))
    assert sorted(sch._pool) == ["k", "v"]
    assert not sch.lane_state and sch.state_bytes == 0
    assert sch.pool_cfg.lane_state == ()
    st = sch.stats()
    assert (st["state_bytes"], st["state_resets"],
            st["prefix_hits_skipped"]) == (0, 0, 0)
    # its prefill program is called with the chunk's table and start
    # only, in both programs its chunks go through (one without a head,
    # one for a prompt's last chunk)
    seen = []
    chunk = partial(llama.paged_prefill_chunk, cfg=parts["cfg"])

    def spy(*args):
        seen.append(len(args))
        return chunk(*args)

    sch = ContinuousBatchingScheduler(
        parts["cfg"], SchedulerConfig(**SCHED), paged_prefill_fn=spy
    )
    sch.sync_weights(parts["params_template_fn"]())
    sch.submit(np.arange(5, dtype=np.int32), max_new=2)
    sch.submit(np.arange(11, dtype=np.int32), max_new=2)
    sch.run()
    assert seen == [5, 5]
