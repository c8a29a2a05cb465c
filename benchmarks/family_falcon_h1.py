"""The Falcon-H1 family: a hybrid block — Mamba-2 (SSD) heads beside
grouped-query attention heads on one normalised input, a gated MLP,
constant multipliers throughout — which ``dlrover_tpu.models.falcon_h1``
serves with a per-lane recurrent state next to the paged K/V cache.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (the program has none: no backward pass of the chunked
scan, no logical axes for the SSM leaves), so ``train_parts`` fails by
name; its reference is ``reference_falcon_h1.py``; its counts are here,
with the byte function of the one kernel the block adds
(``ssm_update_bytes``, read by ``kernel.ssm_update_bw_pct``).

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs",
    "matmul_params", "total_params", "ssm_update_bytes",
]

#: the published keys the program's config object takes under their
#: own names
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
    "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size",
    "rms_norm_eps", "rope_theta", "embedding_multiplier",
    "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
)

#: bytes of one element of the recurrent state as the program holds it
#: (``FalconH1Config.lane_state``: float32) and of the kernel's other
#: operands (it is handed float32 ``x``, ``B``, ``C``, ``dt`` and
#: returns float32 ``y``)
STATE_ITEMSIZE = 4
OPERAND_ITEMSIZE = 4


def seeded_params(cfg, seed):
    import reference_falcon_h1

    return reference_falcon_h1.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_falcon_h1

    return reference_falcon_h1.token_logprobs(params, tokens, cfg)


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``FalconH1Config`` from the
    configuration file's (Hugging Face) keys.  A program without the
    model (a commit before it) fails the cell here, at once and by
    name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.falcon_h1") is not None,
        "this program has no dlrover_tpu.models.falcon_h1: it cannot "
        "serve a configuration of family_falcon_h1",
    )
    return dict({k: cfg[k] for k in _MODEL_KEYS}, max_seq_len=max_seq_len)


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_falcon_h1 has no training path: "
        "dlrover_tpu.models.falcon_h1 provides no loss_fn and no "
        "param_logical_axes (the chunked scan has no backward pass "
        "here), so a `train` or `resume` cell cannot run this "
        "configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import falcon_h1_factory

    return falcon_h1_factory(**model_kwargs)


def _layer_matmul_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    d_ssm = cfg["mamba_d_ssm"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    in_proj = d * (2 * d_ssm + 2 * gn + cfg["mamba_n_heads"])
    attention = d * hd * (
        2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    )
    return (
        in_proj + d_ssm * d + attention + 3 * d * cfg["intermediate_size"]
    )


def _layer_small_params(cfg):
    d_ssm = cfg["mamba_d_ssm"]
    conv_dim = d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return (
        2 * cfg["hidden_size"]  # the two RMSNorm weights
        + (cfg["mamba_d_conv"] + 1) * conv_dim  # taps and bias
        + 3 * cfg["mamba_n_heads"]  # dt_bias, A_log, D
        + d_ssm  # the gated norm's weight
    )


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products: the
    nine matrices of every layer and the head (not the embedding, a
    lookup)."""
    return (
        cfg["num_hidden_layers"] * _layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the tree the program holds."""
    return (
        cfg["num_hidden_layers"]
        * (_layer_matmul_params(cfg) + _layer_small_params(cfg))
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
        + cfg["hidden_size"]  # final norm
    )


def ssm_update_bytes(cfg, lanes):
    """Bytes ONE call of ``ssm_decode_update`` (one layer, one token a
    lane) has to move: each lane's state read and written once, and the
    token's ``x`` and ``y`` (heads x head size), ``B`` and ``C`` (groups
    x state size) and ``dt`` (heads).  What the kernel is handed
    beyond that (the decay broadcast along the state axis) is its own
    affair and not counted: the share reads lower for it, never
    higher."""
    heads, p, n = (
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    )
    groups = cfg["mamba_n_groups"]
    state = 2 * heads * p * n * STATE_ITEMSIZE
    operands = (2 * heads * p + 2 * groups * n + heads) * OPERAND_ITEMSIZE
    return lanes * (state + operands)
