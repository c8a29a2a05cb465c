"""The Trinity family (``model_type`` ``afmoe``): a decoder whose layers
are of two kinds of attention — a window of ``sliding_window`` keys with
rotary positions, and every ``global_attn_every_n_layers``-th layer over
every key without any — with a gated attention output, four norms a
block, and behind ``num_dense_layers`` dense layers a shared expert
beside routed experts chosen by ``sigmoid(router) + bias``; which
``dlrover_tpu.models.trinity`` serves over a cache whose allocator knows
the kinds of layers, holding ONE CHIP'S SHARE of each layer's experts.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (``train_parts`` fails by name); its reference is
``reference_trinity.py``, which FORCES THE ROUTER onto the served side's
choices (``token_logprobs_forced``) and is given the same share; its
counts are here, with the byte and operation functions of the kernels
the block adds (read by ``readers_window.py``).

**What the file's keys mean for a share.**  ``num_experts`` is how many
routed experts are HELD here (``reduced``; ``published`` carries the
model's 256); ``deployment`` says over how many chips a layer is shared
and which share this is, so the router scores ``num_experts *
chips_sharing_a_layer`` experts and the held ones are ``share *
num_experts ..``.  ``vocab_size`` is the slice held here.

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "matmul_params", "total_params", "layers_of_kind",
    "window_decode_bytes", "full_decode_bytes", "prefill_attention_flops",
    "expert_bytes",
]

#: the published top-level keys the program's config object takes under
#: their own names (``num_experts`` apart: see above)
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_dense_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "num_shared_experts", "score_func", "route_norm", "route_scale",
    "n_group", "topk_group", "sliding_window",
    "global_attn_every_n_layers", "mup_enabled", "rms_norm_eps",
    "rope_theta",
)

#: bytes of one element of K, V, q, o and a weight as the program holds
#: them (bfloat16)
ITEMSIZE = 2


def seeded_params(cfg, seed):
    import reference_trinity

    return reference_trinity.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_trinity

    return reference_trinity.token_logprobs(params, tokens, cfg)


def token_logprobs_forced(params, tokens, cfg, served):
    import reference_trinity

    return reference_trinity.token_logprobs_forced(
        params, tokens, cfg, served
    )


def router_width(cfg):
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``TrinityConfig`` from the
    configuration file's (Hugging Face) keys and its ``deployment``.  A
    program without the model (a commit before it) fails the cell here,
    at once and by name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.trinity") is not None,
        "this program has no dlrover_tpu.models.trinity: it cannot "
        "serve a configuration of family_trinity",
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
        "family_trinity has no training path: dlrover_tpu.models.trinity "
        "provides no loss_fn and no param_logical_axes (the share of a "
        "layer's experts has no exchange and no backward pass), so a "
        "`train` or `resume` cell cannot run this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import trinity_factory

    return trinity_factory(**model_kwargs)


# ---------------------------------------------------------------- counts


def layers_of_kind(cfg):
    """``{"window": n, "full": n}``: the layers of each kind of
    attention (full iff ``(i + 1) % global_attn_every_n_layers == 0``)."""
    full = sum(
        (i + 1) % cfg["global_attn_every_n_layers"] == 0
        for i in range(cfg["num_hidden_layers"])
    )
    return {"window": cfg["num_hidden_layers"] - full, "full": full}


def _attention_params(cfg):
    # q, the output gate and o: heads x head_dim each; k and v: kv heads
    return cfg["hidden_size"] * cfg["head_dim"] * (
        3 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
    )


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_small_params(cfg, dense):
    # four RMSNorm weights, the q and k head norms, the selection bias
    return 4 * cfg["hidden_size"] + 2 * cfg["head_dim"] + (
        0 if dense else router_width(cfg)
    )


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products ON THIS
    CHIP, in expectation: attention (q, k, v, gate, o), a dense layer's
    MLP, and in an expert layer the router, the shared expert and the
    ACTIVE LOCAL experts — of a token's ``num_experts_per_tok`` choices
    among the router's width the share that falls on the ``num_experts``
    held here under a flat router (4 x 32 / 256 = 0.5 an expert layer at
    the benchmark's cut) — and the head's slice (not the embedding, a
    lookup)."""
    d = cfg["hidden_size"]
    dense, experts = cfg["num_dense_layers"], (
        cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    )
    local = (
        cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)
    )
    expert_layer = (
        d * router_width(cfg)
        + cfg["num_shared_experts"] * _expert_params(cfg)
        + local * _expert_params(cfg)
    )
    return int(
        cfg["num_hidden_layers"] * _attention_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + experts * expert_layer
        + d * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the tree the program holds: the held experts
    of every expert layer, the slice of the vocabulary."""
    d = cfg["hidden_size"]
    dense, experts = cfg["num_dense_layers"], (
        cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    )
    return (
        cfg["num_hidden_layers"] * _attention_params(cfg)
        + dense * (
            3 * d * cfg["intermediate_size"] + _layer_small_params(cfg, True)
        )
        + experts * (
            d * router_width(cfg)
            + (cfg["num_shared_experts"] + cfg["num_experts"])
            * _expert_params(cfg)
            + _layer_small_params(cfg, False)
        )
        + 2 * d * cfg["vocab_size"]
        + d  # final norm
    )


# ------------------------------------------- the kernels' bytes and FLOPs


def _decode_bytes(cfg, kv_rows, lane_calls):
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE
    qo = 2 * cfg["num_attention_heads"] * cfg["head_dim"] * ITEMSIZE
    return kv_rows * row + lane_calls * qo


def window_decode_bytes(cfg, kv_rows, lanes):
    """Bytes the window layers' decode kernel has to move in ONE decode
    step whatever it reads: ``kv_rows`` token rows of K and of V (every
    KV head) — the step's ``kv_rows_window`` label, each decoding lane's
    ``min(cached, sliding_window)`` summed over lanes and window layers
    — and each lane's q and o a window layer.  A floor: the kernel
    fetches whole blocks, the first of them from its first token."""
    return _decode_bytes(
        cfg, kv_rows, lanes * layers_of_kind(cfg)["window"]
    )


def full_decode_bytes(cfg, kv_rows, lanes):
    """As :func:`window_decode_bytes` for the full layers: ``kv_rows``
    is the step's ``kv_rows_full`` label, every cached position of each
    decoding lane, summed over lanes and full layers."""
    return _decode_bytes(cfg, kv_rows, lanes * layers_of_kind(cfg)["full"])


def prefill_attention_flops(cfg, rows, kv_len):
    """Operations the attention of ONE prefill chunk needs over all
    layers: the chunk's ``rows`` real queries are positions ``kv_len -
    rows .. kv_len - 1``; a query at ``t`` reads ``t + 1`` keys on a
    full layer and ``min(t + 1, sliding_window)`` on a window layer (the
    keys a causal, windowed row may see, not the padded width), each key
    two products of ``head_dim`` a head (q.k and p.v), two operations a
    product."""
    first = kv_len - rows
    full = rows * (first + kv_len + 1) // 2  # sum of t + 1
    w = cfg["sliding_window"]
    under = max(min(w, kv_len) - first, 0)  # rows with t + 1 <= w
    window = under * (2 * first + under + 1) // 2 + (rows - under) * w
    kinds = layers_of_kind(cfg)
    keys = kinds["full"] * full + kinds["window"] * window
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys


def expert_bytes(cfg):
    """Bytes of ONE routed expert's three matrices: what the expert
    kernel has to read for an expert that a step's rows hit."""
    return _expert_params(cfg) * ITEMSIZE
