"""The Keye-VL-2.0 family: a decoder whose every block holds a layer of
routed experts (top-k of a softmax router, none shared) and a learned
indexer that picks the ``topk`` cached tokens a query attends to — which
``dlrover_tpu.models.keye_vl2`` serves with an index-key cache paged
beside K and V.

What a family provides is set out in ``family_dense.py``.  This one has
no training path (the program has none: no backward pass through the
selection, no expert-parallel share), so ``train_parts`` fails by name;
its reference is ``reference_keye_vl2.py``, which FORCES THE ROUTER onto
the served side's choices (``token_logprobs_forced``; the indexer's
selection stays the reference's own); its counts are here — the
parameters a token is multiplied with are the ACTIVE ones, k experts a
layer — with the byte function of the one kernel the block adds
(``sparse_decode_bytes``, read by ``kernel.sparse_paged_bw_pct``).

Importing this module imports neither JAX nor the program.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "matmul_params", "total_params", "sparse_decode_bytes",
]

#: the published top-level keys the program's config object takes under
#: their own names (``sa_config``'s three are flattened beside them)
_MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "moe_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "rms_norm_eps", "rope_theta",
)
_INDEXER_KEYS = ("indexer_head_dim", "indexer_num_heads", "topk")

#: bytes of one element of K, V, q and o as the program holds them
#: (bfloat16) and of one selected position's id (int32)
KV_ITEMSIZE = 2
ID_ITEMSIZE = 4


def seeded_params(cfg, seed):
    import reference_keye_vl2

    return reference_keye_vl2.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_keye_vl2

    return reference_keye_vl2.token_logprobs(params, tokens, cfg)


def token_logprobs_forced(params, tokens, cfg, served):
    import reference_keye_vl2

    return reference_keye_vl2.token_logprobs_forced(
        params, tokens, cfg, served
    )


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``KeyeVL2Config`` from the
    configuration file's (Hugging Face) keys.  A program without the
    model (a commit before it) fails the cell here, at once and by
    name, before any replica is started."""
    import importlib.util

    from harness import require

    require(
        importlib.util.find_spec("dlrover_tpu.models.keye_vl2") is not None,
        "this program has no dlrover_tpu.models.keye_vl2: it cannot "
        "serve a configuration of family_keye_vl2",
    )
    return dict(
        {k: cfg[k] for k in _MODEL_KEYS},
        **{k: cfg["sa_config"][k] for k in _INDEXER_KEYS},
        max_seq_len=max_seq_len,
    )


def train_parts(cfg, seq):
    from harness import CellFailed

    raise CellFailed(
        "family_keye_vl2 has no training path: "
        "dlrover_tpu.models.keye_vl2 provides no loss_fn and no "
        "param_logical_axes (no backward pass through the indexer's "
        "selection, no expert-parallel share of a layer), so a `train` "
        "or `resume` cell cannot run this configuration"
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import keye_vl2_factory

    return keye_vl2_factory(**model_kwargs)


def _attention_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (
        d * hd * (
            2 * cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
        )
        + d * (hi * di + di + hi)  # the indexer's three projections
    )


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_small_params(cfg):
    di = cfg["sa_config"]["indexer_head_dim"]
    return (
        2 * cfg["hidden_size"]  # the two RMSNorm weights
        + 2 * cfg["head_dim"]  # q and k head norms
        + 2 * di  # the index key's LayerNorm weight and bias
    )


def matmul_params(cfg):
    """Parameters a token is multiplied with in matrix products: the
    attention and indexer projections, the router and the ACTIVE experts
    (``num_experts_per_tok`` of them) of every layer, and the head (not
    the embedding, a lookup)."""
    layer = (
        _attention_params(cfg)
        + cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * _expert_params(cfg)
    )
    return (
        cfg["num_hidden_layers"] * layer
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the tree the program holds: all the experts."""
    layer = (
        _attention_params(cfg)
        + cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts"] * _expert_params(cfg)
        + _layer_small_params(cfg)
    )
    return (
        cfg["num_hidden_layers"] * layer
        + 2 * cfg["hidden_size"] * cfg["vocab_size"]
        + cfg["hidden_size"]  # final norm
    )


def sparse_decode_bytes(cfg, lanes):
    """Bytes ONE call of the selected-rows decode attention (one layer,
    one token a lane) has to move whatever the kernel reads: for each
    lane ``topk`` selected token rows of K and of V (every KV head), the
    ids that name them, and the lane's q and o (every head).  A lane
    with fewer than ``topk`` cached tokens moves fewer: the count is the
    most a lane can ask for, so ``lanes`` must be lanes that decode —
    ``readers_sparse.kernel_bandwidth_share_lanes`` takes their mean
    from the ``serve_step`` records, never ``max_slots``."""
    hd = cfg["head_dim"]
    topk = cfg["sa_config"]["topk"]
    rows = 2 * cfg["num_key_value_heads"] * hd * KV_ITEMSIZE + ID_ITEMSIZE
    qo = 2 * cfg["num_attention_heads"] * hd * KV_ITEMSIZE
    return lanes * (topk * rows + qo)
