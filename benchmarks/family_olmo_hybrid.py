"""The Olmo-Hybrid family: gated delta-rule (linear-attention) layers
that keep a recurrent state and no keys, between full-attention layers
that keep keys and no state, a norm on every sublayer's output and a
gated MLP — which ``dlrover_tpu.models.olmo_hybrid`` serves over a cache
that is told, a layer, which of the two that layer keeps.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (the program has none: no backward pass of the chunked
scan, no logical axes for its leaves), so ``train_parts`` fails by name;
its reference is ``reference_olmo_hybrid.py``; its counts are here, with
the byte function of the one kernel the model adds
(``gdn_update_bytes``, read by ``kernel.gdn_update_bw_pct``).

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs",
    "matmul_params", "total_params", "gdn_update_bytes",
]

#: the published keys the program's config object takes under their
#: own names
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "layer_types",
    "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rms_norm_eps",
    "rope_parameters",
)

#: bytes of one element of the recurrent state as the program holds it
#: (``OlmoHybridConfig.lane_state``: float32) and of the kernel's other
#: operands (it is handed float32 ``q``, ``k``, ``v``, ``alpha``,
#: ``beta`` and returns float32 ``o``)
STATE_ITEMSIZE = 4
OPERAND_ITEMSIZE = 4
LINEAR = "linear_attention"


def seeded_params(cfg, seed):
    import reference_olmo_hybrid

    return reference_olmo_hybrid.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_olmo_hybrid

    return reference_olmo_hybrid.token_logprobs(params, tokens, cfg)


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``OlmoHybridConfig`` from the
    configuration file's (Hugging Face) keys.  A program without the
    model (a commit before it) fails the cell here, at once and by
    name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.olmo_hybrid")
        is not None,
        "this program has no dlrover_tpu.models.olmo_hybrid: it cannot "
        "serve a configuration of family_olmo_hybrid",
    )
    return dict(
        {k: cfg[k] for k in _MODEL_KEYS}, layer_types=_layer_types(cfg),
        max_seq_len=max_seq_len,
    )


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_olmo_hybrid has no training path: "
        "dlrover_tpu.models.olmo_hybrid provides no loss_fn and no "
        "param_logical_axes (the gated delta rule's chunked scan has no "
        "backward pass here), so a `train` or `resume` cell cannot run "
        "this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import olmo_hybrid_factory

    return olmo_hybrid_factory(**model_kwargs)


def _layer_types(cfg):
    """The kinds of the configuration's layers: ``layer_types`` read up
    to ``num_hidden_layers`` (the list is cut with the depth)."""
    from harness import require

    n = cfg["num_hidden_layers"]
    require(
        len(cfg["layer_types"]) >= n,
        f"layer_types names {len(cfg['layer_types'])} layers of {n}",
    )
    return list(cfg["layer_types"][:n])


def _dims(cfg):
    heads = cfg["linear_num_value_heads"]
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_dim = heads * cfg["linear_value_head_dim"]
    return heads, key_dim, value_dim


def _layer_params(cfg, kind):
    """-> (parameters a token is multiplied with in matrix products,
    the others) of one layer of ``kind``."""
    d = cfg["hidden_size"]
    mlp = 3 * d * cfg["intermediate_size"]
    if kind == LINEAR:
        heads, key_dim, value_dim = _dims(cfg)
        matmul = (
            d * (2 * key_dim + 2 * value_dim + 2 * heads) + value_dim * d
        )
        small = (
            cfg["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
            + 2 * heads  # A_log, dt_bias
            + cfg["linear_value_head_dim"]  # the gated norm's weight
        )
    else:
        kv = (
            cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
        )
        matmul = 2 * d * d + 2 * d * kv
        small = d + kv  # the q and k norms' weights
    return matmul + mlp, small + 2 * d  # and the two output norms


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products: the
    matrices of every layer and the head (not the embedding, a
    lookup)."""
    return sum(
        _layer_params(cfg, kind)[0] for kind in _layer_types(cfg)
    ) + cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg):
    """Every parameter of the tree the program holds."""
    return (
        sum(sum(_layer_params(cfg, kind)) for kind in _layer_types(cfg))
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
        + cfg["hidden_size"]  # final norm
    )


def gdn_update_bytes(cfg, lanes):
    """Bytes ONE call of ``gdn_decode_update`` (one linear layer, one
    token a lane) has to move: each lane's state read and written once,
    and the token's ``q`` and ``k`` (heads x key size), ``v`` and ``o``
    (heads x value size), ``alpha`` and ``beta`` (heads).  The
    mathematics' bytes, whatever the layout pads and whatever else the
    kernel is handed (``alpha`` and ``beta`` spread over a head's
    columns): the share reads lower for it, never higher."""
    heads, dk, dv = (
        cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
        cfg["linear_value_head_dim"],
    )
    state = 2 * heads * dk * dv * STATE_ITEMSIZE
    operands = (2 * heads * dk + 2 * heads * dv + 2 * heads) * (
        OPERAND_ITEMSIZE
    )
    return lanes * (state + operands)
