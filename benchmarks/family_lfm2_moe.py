"""The LFM2 mixture-of-experts family (``model_type`` ``lfm2_moe``): gated
short-convolution layers — two gates around a 3-tap depthwise sum, which
keep the last two rows of its input a lane and no keys — 3 : 1 with
grouped-query attention layers whose heads are 64 wide, and behind
``num_dense_layers`` dense layers routed experts chosen by the top-k of
``sigmoid(router) + bias``, none shared; which
``dlrover_tpu.models.lfm2_moe`` serves over a cache that holds the tails
for the layers of the first kind and, for the second, pages whose rows
are two KV heads side by side, holding EVERY expert of a layer.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (``train_parts`` fails by name); its reference is
``reference_lfm2_moe.py``, which is FORCED onto the served side's experts
(``token_logprobs_forced``), each choice held to the reference's own
float32 scores by a slack; its counts are here, with the byte function
of the decode attention over the 64-wide heads (read by
``readers_window.py``).

**What the file's keys mean.**  Every key of the catalog's ``config``
under its own name; ``num_experts`` is how many routed experts are HELD
here, and ``deployment`` says over how many chips a layer is shared and
which share this is — one and 0 at the benchmark's cut: the router
scores ``num_experts * chips_sharing_a_layer`` experts and the held ones
are ``share * num_experts ..``.  ``layer_types`` is cut with the depth.
``num_expert_layers`` is this file's own key beside the published
``num_dense_layers`` (which ``readers_window.expert_bandwidth_share_decode``
reads).

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "forced_readings", "matmul_params", "total_params", "layers_of_kind",
    "expert_bytes", "cache_bytes_per_token_layer",
    "lane_state_bytes_per_layer", "full_decode_bytes",
]

#: the published top-level keys the program's config object takes under
#: their own names (``num_experts`` apart)
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "layer_types",
    "num_attention_heads", "num_key_value_heads", "num_dense_layers",
    "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor", "conv_L_cache", "conv_bias", "norm_eps",
    "rope_parameters",
)

#: bytes of one element of a cached key or value, q, o and a weight as
#: the program holds them (bfloat16), and of the conv tail (float32)
ITEMSIZE = 2
STATE_ITEMSIZE = 4
CONV, FULL = "conv", "full_attention"


def seeded_params(cfg, seed):
    import reference_lfm2_moe

    from dlrover_tpu.common.jax_env import kept_in_compile_cache

    # a leaf's program compiles in under a second, which JAX alone does
    # not keep: the replica and then the reference's process would each
    # compile them all again in every run
    with kept_in_compile_cache():
        return reference_lfm2_moe.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_lfm2_moe

    return reference_lfm2_moe.token_logprobs(params, tokens, cfg)


def forced_readings(params, tokens, cfg, served):
    """(logprobs, the router's slack): what ``reference_check.py`` takes
    and ``tolerance_probe_lfm2_moe.py`` reads."""
    import reference_lfm2_moe

    return reference_lfm2_moe.forced_readings(params, tokens, cfg, served)


token_logprobs_forced = forced_readings


def router_width(cfg):
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``Lfm2MoeConfig`` from the
    configuration file's (Hugging Face) keys and its ``deployment``.  A
    program without the model (a commit before it) fails the cell here,
    at once and by name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.lfm2_moe") is not None,
        "this program has no dlrover_tpu.models.lfm2_moe: it cannot "
        "serve a configuration of family_lfm2_moe",
    )
    require(
        len(cfg["layer_types"]) == cfg["num_hidden_layers"],
        f"layer_types names {len(cfg['layer_types'])} layers, not "
        f"num_hidden_layers' {cfg['num_hidden_layers']}",
    )
    return dict(
        {k: cfg[k] for k in _MODEL_KEYS},
        num_experts=router_width(cfg),
        held_experts=cfg["num_experts"],
        first_expert=cfg["num_experts"] * cfg["deployment"]["share"],
        max_seq_len=max_seq_len,
    )


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_lfm2_moe has no training path: "
        "dlrover_tpu.models.lfm2_moe provides no loss_fn and no "
        "param_logical_axes (no backward pass through the router, "
        "expert_ffn or a convolution whose tail is carried), so a "
        "`train` or `resume` cell cannot run this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import lfm2_moe_factory

    return lfm2_moe_factory(**model_kwargs)


# ---------------------------------------------------------------- counts


def layers_of_kind(cfg):
    """``{"conv": n, "full": n, "dense": n, "expert": n}``: the layers of
    each kind of operator and of each kind of FF."""
    kinds = list(cfg["layer_types"])
    conv, full = kinds.count(CONV), kinds.count(FULL)
    dense = cfg["num_dense_layers"]
    return {
        "conv": conv, "full": full, "dense": dense,
        "expert": conv + full - dense,
    }


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _conv_params(cfg):
    """-> (parameters a token is multiplied with in matrix products, the
    others) of one conv operator: ``W_in`` (D -> 3 D) and ``W_out``; the
    taps."""
    d = cfg["hidden_size"]
    return 3 * d * d + d * d, cfg["conv_L_cache"] * d


def _attn_params(cfg):
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * nh * hd + 2 * d * nkv * hd, 2 * hd  # q_norm, k_norm


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products ON THIS
    CHIP, in expectation: the operators' matrices, the dense layers'
    FF, and in an expert layer the router and the ACTIVE LOCAL experts —
    of a token's ``num_experts_per_tok`` choices among the router's width
    the share that falls on the ``num_experts`` held here under a flat
    router (all 4 at the benchmark's cut, where every expert is held) —
    and the head (the embedding read as a matrix)."""
    d = cfg["hidden_size"]
    kinds = layers_of_kind(cfg)
    local = (
        cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)
    )
    expert_layer = d * router_width(cfg) + local * _expert_params(cfg)
    return int(
        kinds["conv"] * _conv_params(cfg)[0]
        + kinds["full"] * _attn_params(cfg)[0]
        + kinds["dense"] * 3 * d * cfg["intermediate_size"]
        + kinds["expert"] * expert_layer
        + d * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the tree the program holds: every held expert
    of every expert layer, the embedding ONCE (it is the head)."""
    d = cfg["hidden_size"]
    kinds = layers_of_kind(cfg)
    return (
        kinds["conv"] * sum(_conv_params(cfg))
        + kinds["full"] * sum(_attn_params(cfg))
        + (kinds["conv"] + kinds["full"]) * 2 * d  # two pre-norms a layer
        + kinds["dense"] * 3 * d * cfg["intermediate_size"]
        + kinds["expert"] * (
            (d + 1) * router_width(cfg)  # the router and its bias
            + cfg["num_experts"] * _expert_params(cfg)
        )
        + d * cfg["vocab_size"]
        + d  # final norm
    )


def cache_bytes_per_token_layer(cfg):
    """Bytes one token keeps in one attention layer: K and V of every KV
    head (2048 at the published widths); a conv layer keeps nothing a
    token."""
    return 2 * cfg["num_key_value_heads"] * _head_dim(cfg) * ITEMSIZE


def lane_state_bytes_per_layer(cfg):
    """Bytes one lane keeps in one conv layer: the float32 tail, ``taps
    - 1`` rows of ``hidden_size`` (16 384 at the published widths)."""
    return STATE_ITEMSIZE * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]


# ------------------------------------------- the kernels' bytes and FLOPs


def full_decode_bytes(cfg, rows, lanes):
    """Bytes the decode attention kernel over the 64-wide heads
    (``paged_full_decode`` on rows of two heads) has to move in ONE
    decode step: ``rows`` token rows — the step's ``kv_rows_full`` label,
    every cached position of each decoding lane summed over the lanes
    and the attention layers — each K and V of every KV head once, and a
    lane's queries and outputs an attention layer at their OWN 64 (the
    zero halves the kernel is handed beside them are not the
    mathematics': the share reads lower for them, never higher)."""
    hd = _head_dim(cfg)
    qo = 2 * cfg["num_attention_heads"] * hd * ITEMSIZE
    return (
        rows * cache_bytes_per_token_layer(cfg)
        + layers_of_kind(cfg)["full"] * lanes * qo
    )


def expert_bytes(cfg):
    """Bytes of ONE routed expert's three matrices: what the expert
    kernel has to read for an expert that a step's rows hit."""
    return _expert_params(cfg) * ITEMSIZE
