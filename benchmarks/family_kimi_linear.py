"""The Kimi Linear family (``model_type`` ``kimi_linear``): Kimi Delta
Attention layers — a gated delta rule whose decay is a vector of
``head_dim`` a head, which keep a recurrent state a lane and no keys —
3 : 1 with NoPE latent-attention layers that keep ONE compressed row a
token and no state, and behind ``first_k_dense_replace`` dense layers a
shared expert beside routed experts chosen by the top-k of
``sigmoid(router) + bias``; which ``dlrover_tpu.models.kimi_linear``
serves over a cache that holds state slabs for the layers of the first
kind, latent pages for the second and no per-head keys or values,
holding ONE CHIP'S SHARE of each layer's experts.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (``train_parts`` fails by name); its reference is
``reference_kimi_linear.py``, which is FORCED onto the served side's
experts (``token_logprobs_forced``), each choice held to the reference's
own float32 scores by a slack, and is given the same share; its counts
are here, with the byte and operation functions of the kernels the block
runs (read by ``readers_roofline.py``, ``readers_window.py`` and
``readers_hybrid.py``).

**What the file's keys mean for a share.**  ``num_experts`` is how many
routed experts are HELD here (``reduced``; ``published`` carries the
model's 256); ``deployment`` says over how many chips a layer is shared
and which share this is, so the router scores ``num_experts *
chips_sharing_a_layer`` experts and the held ones are ``share *
num_experts ..``.  ``vocab_size`` is the slice held here.
``linear_attn_config``'s two lists number the layers from 1 and are cut
with the depth.  ``num_dense_layers`` repeats ``first_k_dense_replace``
under the name ``readers_window.expert_bandwidth_share_decode`` reads.

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "forced_readings", "matmul_params", "total_params", "layers_of_kind",
    "expert_bytes", "cache_bytes_per_token_layer", "kda_update_bytes",
    "mla_decode_bytes", "mla_decode_flops", "prefill_attention_flops",
]

#: the published top-level keys the program's config object takes under
#: their own names (``num_experts`` and ``linear_attn_config`` apart)
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "first_k_dense_replace", "intermediate_size", "moe_intermediate_size",
    "num_experts_per_token", "num_shared_experts", "num_expert_group",
    "topk_group", "moe_renormalize", "moe_router_activation_func",
    "routed_scaling_factor", "num_attention_heads", "kv_lora_rank",
    "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "mla_use_nope", "rope_scaling", "rms_norm_eps",
)

#: bytes of one element of a cached row, q, o and a weight as the
#: program holds them (bfloat16), and of the recurrent state and the
#: decode update's other operands (float32)
ITEMSIZE = 2
STATE_ITEMSIZE = 4
OPERAND_ITEMSIZE = 4


def seeded_params(cfg, seed):
    import reference_kimi_linear

    from dlrover_tpu.common.jax_env import kept_in_compile_cache

    # a leaf's program compiles in under a second, which JAX alone does
    # not keep: the replica and then the reference's process would each
    # compile them all again in every run
    with kept_in_compile_cache():
        return reference_kimi_linear.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_kimi_linear

    return reference_kimi_linear.token_logprobs(params, tokens, cfg)


def forced_readings(params, tokens, cfg, served):
    """(logprobs, the router's slack): what ``reference_check.py`` takes
    and ``tolerance_probe_kimi_linear.py`` reads."""
    import reference_kimi_linear

    return reference_kimi_linear.forced_readings(params, tokens, cfg, served)


token_logprobs_forced = forced_readings


def router_width(cfg):
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``KimiLinearConfig`` from the
    configuration file's (Hugging Face) keys and its ``deployment``.  A
    program without the model (a commit before it) fails the cell here,
    at once and by name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.kimi_linear")
        is not None,
        "this program has no dlrover_tpu.models.kimi_linear: it cannot "
        "serve a configuration of family_kimi_linear",
    )
    la = cfg["linear_attn_config"]
    layers = sorted(la["kda_layers"] + la["full_attn_layers"])
    require(
        layers == list(range(1, cfg["num_hidden_layers"] + 1)),
        "linear_attn_config's kda_layers and full_attn_layers name "
        f"{layers}, not each of the layers 1 .. "
        f"{cfg['num_hidden_layers']} once",
    )
    return dict(
        {k: cfg[k] for k in _MODEL_KEYS},
        kda_layers=list(la["kda_layers"]),
        full_attn_layers=list(la["full_attn_layers"]),
        linear_num_heads=la["num_heads"], linear_head_dim=la["head_dim"],
        short_conv_kernel_size=la["short_conv_kernel_size"],
        num_experts=router_width(cfg),
        held_experts=cfg["num_experts"],
        first_expert=cfg["num_experts"] * cfg["deployment"]["share"],
        max_seq_len=max_seq_len,
    )


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_kimi_linear has no training path: "
        "dlrover_tpu.models.kimi_linear provides no loss_fn and no "
        "param_logical_axes (no backward pass through the router, the "
        "share of a layer's experts or the delta rule's chunked scan), "
        "so a `train` or `resume` cell cannot run this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import kimi_linear_factory

    return kimi_linear_factory(**model_kwargs)


# ---------------------------------------------------------------- counts


def layers_of_kind(cfg):
    """``{"kda": n, "mla": n, "dense": n, "expert": n}``: the layers of
    each kind of mixer and of each kind of MLP."""
    la, dense = cfg["linear_attn_config"], cfg["first_k_dense_replace"]
    # from the two lists alone (``model_kwargs`` holds them to the
    # depth): ``readers_hybrid.of_kind`` hands a reader this
    # configuration with ``num_hidden_layers`` read as ONE kind's count
    kda, mla = len(la["kda_layers"]), len(la["full_attn_layers"])
    return {
        "kda": kda, "mla": mla, "dense": dense, "expert": kda + mla - dense,
    }


def _kda_dims(cfg):
    la = cfg["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def _kda_params(cfg):
    """-> (parameters a token is multiplied with in matrix products, the
    others) of one KDA mixer: three projections and ``W_o``, the two
    low-rank gates, ``W_b``; the conv taps, ``A_log``, ``dt_bias``, the
    gated norm's weight."""
    d = cfg["hidden_size"]
    heads, hd, taps = _kda_dims(cfg)
    kd = heads * hd
    return (
        4 * d * kd + 2 * (d * hd + hd * kd) + d * heads,
        taps * 3 * kd + heads + kd + hd,
    )


def _mla_params(cfg):
    d, nh, rkv = (
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    )
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    return (
        d * nh * (dn + dr) + d * (rkv + dr) + rkv * nh * (dn + dv)
        + nh * dv * d,
        rkv,  # the latent's norm
    )


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products ON THIS
    CHIP, in expectation: the mixers' matrices, the dense layer's MLP,
    and in an expert layer the router, the shared expert and the ACTIVE
    LOCAL experts — of a token's ``num_experts_per_token`` choices among
    the router's width the share that falls on the ``num_experts`` held
    here under a flat router (8 x 16 / 256 = 0.5 an expert layer at the
    benchmark's cut) — and the head's slice (not the embedding, a
    lookup)."""
    d = cfg["hidden_size"]
    kinds = layers_of_kind(cfg)
    local = (
        cfg["num_experts_per_token"] * cfg["num_experts"] / router_width(cfg)
    )
    expert_layer = (
        d * router_width(cfg)
        + (cfg["num_shared_experts"] + local) * _expert_params(cfg)
    )
    return int(
        kinds["kda"] * _kda_params(cfg)[0]
        + kinds["mla"] * _mla_params(cfg)[0]
        + kinds["dense"] * 3 * d * cfg["intermediate_size"]
        + kinds["expert"] * expert_layer
        + d * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the tree the program holds: the held experts
    of every expert layer, the slice of the vocabulary."""
    d = cfg["hidden_size"]
    kinds = layers_of_kind(cfg)
    return (
        kinds["kda"] * sum(_kda_params(cfg))
        + kinds["mla"] * sum(_mla_params(cfg))
        + (kinds["kda"] + kinds["mla"]) * 2 * d  # two pre-norms a layer
        + kinds["dense"] * 3 * d * cfg["intermediate_size"]
        + kinds["expert"] * (
            (d + 1) * router_width(cfg)  # the router and its bias
            + (cfg["num_shared_experts"] + cfg["num_experts"])
            * _expert_params(cfg)
        )
        + 2 * d * cfg["vocab_size"]
        + d  # final norm
    )


def cache_bytes_per_token_layer(cfg):
    """Bytes one token keeps in one MLA layer: the latent row (1152 at
    the published widths); a KDA layer keeps nothing a token."""
    return ITEMSIZE * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def lane_state_bytes_per_layer(cfg):
    """Bytes one lane keeps in one KDA layer: the float32 state of every
    head and the float32 conv tail (2 244 608 at the published
    widths)."""
    heads, hd, taps = _kda_dims(cfg)
    return STATE_ITEMSIZE * (
        heads * hd * hd + (taps - 1) * 3 * heads * hd
    )


# ------------------------------------------- the kernels' bytes and FLOPs


def kda_update_bytes(cfg, lanes):
    """Bytes ONE call of ``kda_decode_update`` (one KDA layer, one token
    a lane) has to move: each lane's state read and written once, and
    the token's ``q``, ``k`` and decay ``a`` (heads x key size), ``v``
    and ``o`` (heads x value size) and ``beta`` (heads).  The
    mathematics' bytes, whatever else the kernel is handed (``beta``
    spread over a head's columns): the share reads lower for it, never
    higher."""
    heads, hd, _ = _kda_dims(cfg)
    state = 2 * heads * hd * hd * STATE_ITEMSIZE
    operands = (3 * heads * hd + 2 * heads * hd + heads) * OPERAND_ITEMSIZE
    return lanes * (state + operands)


def _row_widths(cfg):
    """(key width, value width) of the cached row in absorbed form."""
    return (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    )


def mla_decode_bytes(cfg, rows, lanes):
    """Bytes the absorbed decode kernel has to move in ONE decode step:
    ``rows`` latent rows — the step's ``sel_rows`` label, here each
    decoding lane's cached positions summed over the lanes, an MLA layer
    — in every MLA layer, read once for all heads, and each lane's
    absorbed queries and summed latents a layer."""
    dk, dv = _row_widths(cfg)
    qo = cfg["num_attention_heads"] * (dk + dv) * ITEMSIZE
    return layers_of_kind(cfg)["mla"] * (rows * dk * ITEMSIZE + lanes * qo)


def mla_decode_flops(cfg, rows, lanes):
    """Operations of the same step: every head scores a row over its key
    width and sums it over its value width, two operations a product (60
    a byte at 32 heads: memory-bound on a v5e, whose ridge is 242)."""
    del lanes
    dk, dv = _row_widths(cfg)
    return (
        layers_of_kind(cfg)["mla"] * 2 * cfg["num_attention_heads"]
        * rows * (dk + dv)
    )


def prefill_attention_flops(cfg, rows, kv_len):
    """Operations the latent attention of ONE prefill chunk needs over
    the MLA layers, in the multi-head form it is computed in: the
    chunk's ``rows`` real queries are positions ``kv_len - rows ..
    kv_len - 1``; a query at ``t`` reads its ``t + 1`` keys, each a
    product of ``nope + rope`` (q.k) and one of ``v`` (p.v) a head, two
    operations a product.  Decompressing keys and values from the rows
    is not counted: the kernel's time does not hold it."""
    first = kv_len - rows
    keys = rows * (2 * first + rows + 1) // 2
    per_key = 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    return (
        layers_of_kind(cfg)["mla"] * cfg["num_attention_heads"] * per_key
        * keys
    )


def expert_bytes(cfg):
    """Bytes of ONE routed expert's three matrices: what the expert
    kernel has to read for an expert that a step's rows hit."""
    return _expert_params(cfg) * ITEMSIZE
