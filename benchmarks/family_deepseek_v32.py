"""The DeepSeek-V3.2 family (``model_type`` ``deepseek_v32``): latent
attention — a low-rank query and ONE compressed row a token that every
head reads as key and value — under a learned top-k indexer, and behind
``first_k_dense_replace`` dense layers a shared expert beside routed
experts chosen by a group-limited top-k of ``sigmoid(router) + bias``;
which ``dlrover_tpu.models.deepseek_v32`` serves over a cache that holds
no per-head keys or values, holding ONE CHIP'S SHARE of each layer's
experts.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (``train_parts`` fails by name); its reference is
``reference_deepseek_v32.py``, which is FORCED onto the served side's
choices (``token_logprobs_forced``) — the experts of every expert layer
AND the keys the indexer picked in every layer, each held to the
reference's own float32 scores by a slack — and is given the same
share; its counts are here,
with the byte and operation functions of the kernels the block adds
(read by ``readers_latent.py`` and ``readers_window.py``).

**What the file's keys mean for a share.**  ``n_routed_experts`` is how
many routed experts are HELD here (``reduced``; ``published`` carries
the model's 256); ``deployment`` says over how many chips a layer is
shared and which share this is, so the router scores ``n_routed_experts
* chips_sharing_a_layer`` experts and the held ones are ``share *
n_routed_experts ..``.  ``vocab_size`` is the slice held here.
``num_dense_layers`` repeats ``first_k_dense_replace`` under the name
``readers_window.expert_bandwidth_share_decode`` reads.

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "forced_readings", "matmul_params", "total_params", "layers_of_kind",
    "mla_decode_bytes", "mla_decode_flops", "prefill_attention_flops",
    "expert_bytes",
]

#: the published top-level keys the program's config object takes under
#: their own names (``n_routed_experts`` and ``rope_scaling`` apart)
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "n_shared_experts", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "scoring_func", "topk_method",
    "index_n_heads", "index_head_dim", "index_topk", "rms_norm_eps",
    "rope_theta",
)
#: ``rope_scaling``'s keys, which the program's config takes flattened
_ROPE_KEYS = (
    "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
    "mscale", "mscale_all_dim",
)

#: bytes of one element of a cached row, q, o and a weight as the
#: program holds them (bfloat16)
ITEMSIZE = 2


def seeded_params(cfg, seed):
    import reference_deepseek_v32

    from dlrover_tpu.common.jax_env import kept_in_compile_cache

    # a leaf's program compiles in under a second, which JAX alone does
    # not keep: the replica and then the reference's process would each
    # compile them all again in every run
    with kept_in_compile_cache():
        return reference_deepseek_v32.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_deepseek_v32

    return reference_deepseek_v32.token_logprobs(params, tokens, cfg)


def token_logprobs_forced(params, tokens, cfg, served):
    import reference_deepseek_v32

    return reference_deepseek_v32.token_logprobs_forced(
        params, tokens, cfg, served
    )


def forced_readings(params, tokens, cfg, served):
    """(logprobs, the router's slack, the selection's slack), apart:
    what ``tolerance_probe_deepseek_v32.py`` reads."""
    import reference_deepseek_v32

    return reference_deepseek_v32.forced_readings(
        params, tokens, cfg, served
    )


def router_width(cfg):
    return cfg["n_routed_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``DeepSeekV32Config`` from the
    configuration file's (Hugging Face) keys and its ``deployment``.  A
    program without the model (a commit before it) fails the cell here,
    at once and by name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.deepseek_v32")
        is not None,
        "this program has no dlrover_tpu.models.deepseek_v32: it cannot "
        "serve a configuration of family_deepseek_v32",
    )
    scaling = cfg["rope_scaling"]
    require(
        scaling is not None and scaling.get("type") == "yarn",
        "family_deepseek_v32 rotates by YaRN: rope_scaling.type is "
        f"{scaling and scaling.get('type')!r}",
    )
    return dict(
        {k: cfg[k] for k in _MODEL_KEYS},
        **{"rope_" + k: scaling[k] for k in _ROPE_KEYS},
        n_routed_experts=router_width(cfg),
        held_experts=cfg["n_routed_experts"],
        first_expert=cfg["n_routed_experts"] * cfg["deployment"]["share"],
        max_seq_len=max_seq_len,
    )


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_deepseek_v32 has no training path: "
        "dlrover_tpu.models.deepseek_v32 provides no loss_fn and no "
        "param_logical_axes (no backward pass through the router, the "
        "share of a layer's experts or the selection), so a `train` or "
        "`resume` cell cannot run this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import deepseek_v32_factory

    return deepseek_v32_factory(**model_kwargs)


# ---------------------------------------------------------------- counts


def layers_of_kind(cfg):
    """``{"dense": n, "expert": n}``: the layers of each kind of MLP;
    every layer's attention is latent."""
    dense = cfg["first_k_dense_replace"]
    return {"dense": dense, "expert": cfg["num_hidden_layers"] - dense}


def _attention_params(cfg):
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    return (
        d * rq + rq * nh * (dn + dr) + d * (rkv + dr)
        + rkv * nh * (dn + dv) + nh * dv * d
    )


def _indexer_params(cfg):
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * hi * di + cfg["hidden_size"] * (di + hi)


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_small_params(cfg, dense):
    # two RMSNorm weights, the norms of the two latents, the index key's
    # LayerNorm (weight and bias), the selection bias
    return (
        2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
        + 2 * cfg["index_head_dim"] + (0 if dense else router_width(cfg))
    )


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products ON THIS
    CHIP, in expectation: attention and the indexer's projections, a
    dense layer's MLP, and in an expert layer the router, the shared
    expert and the ACTIVE LOCAL experts — of a token's
    ``num_experts_per_tok`` choices among the router's width the share
    that falls on the ``n_routed_experts`` held here under a flat router
    (8 x 8 / 256 = 0.25 an expert layer at the benchmark's cut) — and
    the head's slice (not the embedding, a lookup)."""
    d = cfg["hidden_size"]
    kinds = layers_of_kind(cfg)
    local = (
        cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
        / router_width(cfg)
    )
    expert_layer = (
        d * router_width(cfg)
        + cfg["n_shared_experts"] * _expert_params(cfg)
        + local * _expert_params(cfg)
    )
    return int(
        cfg["num_hidden_layers"] * (
            _attention_params(cfg) + _indexer_params(cfg)
        )
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
        cfg["num_hidden_layers"] * (
            _attention_params(cfg) + _indexer_params(cfg)
        )
        + kinds["dense"] * (
            3 * d * cfg["intermediate_size"] + _layer_small_params(cfg, True)
        )
        + kinds["expert"] * (
            d * router_width(cfg)
            + (cfg["n_shared_experts"] + cfg["n_routed_experts"])
            * _expert_params(cfg)
            + _layer_small_params(cfg, False)
        )
        + 2 * d * cfg["vocab_size"]
        + d  # final norm
    )


def cache_bytes_per_token_layer(cfg):
    """Bytes one token keeps in one layer: the latent row and the index
    key (1408 at the published widths)."""
    return ITEMSIZE * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["index_head_dim"]
    )


# ------------------------------------------- the kernels' bytes and FLOPs


def _row_widths(cfg):
    """(key width, value width) of the cached row in absorbed form."""
    return (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    )


def mla_decode_bytes(cfg, rows, lanes):
    """Bytes the absorbed decode kernel has to move in ONE decode step
    whatever it reads: ``rows`` selected latent rows — the step's
    ``sel_rows`` label, each decoding lane's ``min(cached,
    index_topk)`` summed over the lanes, a layer — in every layer, read
    once for all heads, and each lane's absorbed queries and summed
    latents a layer."""
    dk, dv = _row_widths(cfg)
    qo = cfg["num_attention_heads"] * (dk + dv) * ITEMSIZE
    return cfg["num_hidden_layers"] * (rows * dk * ITEMSIZE + lanes * qo)


def mla_decode_flops(cfg, rows, lanes):
    """Operations of the same step: every head scores a row over its
    key width and sums it over its value width, two operations a
    product."""
    del lanes
    dk, dv = _row_widths(cfg)
    return (
        cfg["num_hidden_layers"] * 2 * cfg["num_attention_heads"]
        * rows * (dk + dv)
    )


def prefill_attention_flops(cfg, rows, kv_len):
    """Operations the attention of ONE prefill chunk needs over all
    layers, in the multi-head form it is computed in: the chunk's
    ``rows`` real queries are positions ``kv_len - rows .. kv_len - 1``;
    a query at ``t`` reads ``min(t + 1, index_topk)`` keys (the
    selection, not the padded width), each key a product of ``nope +
    rope`` (q.k) and one of ``v`` (p.v) a head, two operations a
    product.  Decompressing keys and values from the rows is not
    counted: the kernel's time does not hold it."""
    first = kv_len - rows
    k = cfg["index_topk"]
    under = max(min(k, kv_len) - first, 0)  # rows with t + 1 <= topk
    keys = under * (2 * first + under + 1) // 2 + (rows - under) * k
    per_key = 2 * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    return (
        cfg["num_hidden_layers"] * cfg["num_attention_heads"] * per_key * keys
    )


def expert_bytes(cfg):
    """Bytes of ONE routed expert's three matrices: what the expert
    kernel has to read for an expert that a step's rows hit."""
    return _expert_params(cfg) * ITEMSIZE
