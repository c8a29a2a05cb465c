"""The contract between the program and the benchmark, in tier-1: for
every configuration of ``BENCHMARK.json``, the family module its file
names resolves and provides its parts; its ``model_kwargs`` build the
program's model object; at a tiny override of the sizes the program's
forward and the family's plain reference agree per token on the
family's seeded weights; and the family's counts are the size of that
tree.  A program PR that renames what a family imports fails here and
not on the chip.

The cases are those of ``benchmarks/tests/test_family_contract.py``
(which a benchmark PR keeps beside the harness, outside tier-1), made
family-aware: a family without a training path (``train_parts`` raises
``CellFailed``) skips the training cases instead of failing them.
"""

import json
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

import harness  # noqa: E402
import tiny_families as T  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CONFIGS = {c["name"]: c for c in json.load(_f)["configs"]}

SEQ = 24
#: float32 weights and compute on both sides; what differs is the
#: order of the sums (the program scans over layers, chunks its scan)
TOL = 2e-4
#: sizes that keep a family's shape and fit a CPU test; every other key
#: (each multiplier, eps, theta) is the configuration's own
TINY = {
    "family_dense": lambda cfg: dict(
        hidden_size=64, intermediate_size=160, num_hidden_layers=2,
        vocab_size=384, num_attention_heads=8,
        num_key_value_heads=max(
            8 // (cfg["num_attention_heads"] // cfg["num_key_value_heads"]),
            1,
        ),
    ),
    "family_falcon_h1": lambda cfg: dict(
        hidden_size=64, intermediate_size=160, num_hidden_layers=2,
        vocab_size=384, num_attention_heads=10, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=16, mamba_chunk_size=8,
    ),
    # ``topk`` below SEQ: the forward and the reference both select
    "family_keye_vl2": lambda cfg: dict(
        hidden_size=64, num_hidden_layers=2, vocab_size=384,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=32, num_experts=8, num_local_experts=8,
        num_experts_per_tok=2,
        sa_config=dict(
            cfg["sa_config"], indexer_head_dim=8, indexer_num_heads=2,
            topk=16,
        ),
    ),
    # a window below SEQ; 2 routed experts held of the router's 2 x 8
    # (``deployment`` stays the configuration's: eight chips a layer)
    "family_trinity": lambda cfg: dict(
        hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
        num_expert_layers=4, vocab_size=384, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_experts=2, num_experts_per_tok=2,
        sliding_window=16,
    ),
    # one whole period of the published pattern; head sizes that are
    # not powers of two (a packed state, flat pages)
    "family_olmo_hybrid": lambda cfg: dict(
        hidden_size=72, intermediate_size=96, num_hidden_layers=4,
        layer_types=cfg["layer_types"][:4], vocab_size=384,
        num_attention_heads=3, num_key_value_heads=3,
        linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=12, linear_value_head_dim=24,
    ),
    # ``index_topk`` below SEQ: forward and reference both select; 2
    # groups of which 1 is taken; 2 routed experts held of the router's
    # 32 x 2 (``deployment`` stays the configuration's: 32 chips a layer)
    "family_deepseek_v32": lambda cfg: dict(
        hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
        num_dense_layers=1, num_expert_layers=2, vocab_size=384,
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=2, num_experts_per_tok=2, n_group=2,
        topk_group=1, index_n_heads=2, index_head_dim=16, index_topk=16,
    ),
    # one dense layer and one whole period (KDA KDA KDA MLA, numbered
    # from 1 as published); 2 routed experts held of the router's 16 x 2
    # (``deployment`` stays the configuration's: 16 chips a layer)
    "family_kimi_linear": lambda cfg: dict(
        hidden_size=64, num_hidden_layers=4, num_dense_layers=1,
        num_expert_layers=3, vocab_size=384, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=2, num_experts_per_token=2,
        linear_attn_config=dict(
            cfg["linear_attn_config"], kda_layers=[1, 2, 3],
            full_attn_layers=[4], num_heads=4, head_dim=16,
        ),
    ),
    # both dense layers and one whole period of the published pattern
    # behind them (layers 2-5: full_attention conv conv conv); heads of
    # 16, two KV heads a row of the pool; every one of 8 experts held
    # (``deployment`` stays the configuration's: one chip a layer)
    "family_lfm2_moe": lambda cfg: dict(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=6, layer_types=cfg["published"]["layer_types"][:6],
        num_expert_layers=4, vocab_size=384, num_attention_heads=4,
        num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
    ),
}


def tiny_cfg(name):
    """The configuration at the tiny sizes of its family: what
    ``tiny_families`` is asked for (its parts and weights are made once
    a configuration, for every case below)."""
    with open(os.path.join(REPO, CONFIGS[name]["file"])) as f:
        cfg = json.load(f)
    return dict(cfg, **TINY[cfg["family"]](cfg))


def train_parts_or_skip(fam, cfg):
    try:
        return fam.train_parts(cfg, SEQ)
    except harness.CellFailed as e:
        pytest.skip(f"{cfg['family']} has no training path: {e}")


@pytest.fixture(params=sorted(CONFIGS))
def cfg(request):
    return tiny_cfg(request.param)


def test_the_family_resolves_and_provides_its_parts(cfg):
    fam = harness.family(cfg)
    for name in (
        "model_kwargs", "train_parts", "serving_parts", "seeded_params",
        "token_logprobs", "matmul_params", "total_params",
    ):
        assert callable(getattr(fam, name)), name
    kwargs = fam.model_kwargs(cfg, SEQ)
    assert json.loads(json.dumps(kwargs)) == kwargs  # rides through JSON
    served = fam.serving_parts(**kwargs, dtype="bfloat16")  # as a rollout cell
    assert {"forward_fn", "params_template_fn", "cfg"} <= set(served)
    # the scheduler's side of the contract: K/V geometry as attributes
    for attr in ("n_layers", "n_kv_heads", "head_dim", "dtype"):
        assert hasattr(served["cfg"], attr), attr
    assert served["cfg"].n_layers == cfg["num_hidden_layers"]


def test_train_parts_build_the_serving_model(cfg):
    fam = harness.family(cfg)
    parts = train_parts_or_skip(fam, cfg)
    assert {"model", "init_params_fn", "loss_fn", "param_axes",
            "forward"} <= set(parts)
    assert callable(fam.train_flops_per_token)
    assert T.parts(cfg, SEQ, "bfloat16")["cfg"] == parts["model"]


def test_the_counts_are_the_tree(cfg):
    fam = harness.family(cfg)
    params = T.params(cfg, 2**31 + 5)
    served = T.parts(cfg, SEQ)
    # the reference's tree IS the program's: same leaves, same shapes
    template = jax.eval_shape(served["params_template_fn"])
    assert jax.tree_util.tree_map(
        lambda a: a.shape, params
    ) == jax.tree_util.tree_map(lambda a: a.shape, template)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == fam.total_params(cfg)
    assert 0 < fam.matmul_params(cfg) < n


def test_program_and_reference_agree_per_token(cfg):
    fam = harness.family(cfg)
    params = T.params(cfg, 2**31 + 5)
    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], size=(2, SEQ + 1), dtype=np.int32
    )
    served = T.parts(cfg, SEQ)
    with jax.default_matmul_precision("highest"):
        got = jax.nn.log_softmax(
            served["forward_fn"](params, tokens[:, :-1]).astype(jnp.float32),
            -1,
        )
    got = jnp.take_along_axis(got, tokens[:, 1:, None], -1)[..., 0]
    ref = fam.token_logprobs(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


def test_the_training_loss_is_the_reference_mean(cfg):
    fam = harness.family(cfg)
    parts = train_parts_or_skip(fam, cfg)
    params = T.params(cfg, 2**31 + 5)
    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], size=(2, SEQ + 1), dtype=np.int32
    )
    ref = fam.token_logprobs(params, tokens, cfg)
    loss = parts["loss_fn"](params, {"tokens": tokens})
    loss = loss[0] if isinstance(loss, tuple) else loss
    assert abs(float(loss) + float(jnp.mean(ref))) < 5e-3  # bf16 compute
    assert fam.train_flops_per_token(cfg, SEQ) > 6 * fam.matmul_params(cfg)
