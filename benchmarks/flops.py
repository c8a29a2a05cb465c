"""Operations and bytes a configuration needs, from its shapes alone.

Kept with the benchmark so that no PR that claims a gain can move them.
``cfg`` is a configuration file's dict (Hugging Face key names).  The
counts (``matmul_params``, ``total_params``, ``train_flops_per_token``)
are the dense family's (``family_dense.py``); ``mfu_pct`` and
``peak_for`` serve every family.
"""


def layer_matmul_params(cfg):
    """Parameters of one block that sit in matrix multiplications."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    mlp = 3 * d * cfg["intermediate_size"]
    return q + kv + o + mlp


def matmul_params(cfg):
    """Every parameter a token is multiplied with: the blocks and the
    output head.  The embedding table is a lookup, not a matmul."""
    return (
        cfg["num_hidden_layers"] * layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def total_params(cfg):
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return matmul_params(cfg) + cfg["vocab_size"] * d + norms


def train_flops_per_token(cfg, seq):
    """Required forward + backward FLOPs per trained token: 6 per matmul
    parameter (2 forward, 4 backward) plus causal attention — QK^T and
    PV are 2 * 2 * seq * head_dim * heads FLOPs a token forward over the
    full square, halved by the causal mask, tripled for the backward.
    Remat recompute and the embedding lookup are not required work and
    are not counted."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    attn_fwd = 2 * 2 * seq * hd * cfg["num_attention_heads"] / 2
    return 6 * matmul_params(cfg) + 3 * attn_fwd * cfg["num_hidden_layers"]


def mfu_pct(flops_per_token, tokens_per_step, step_s, peak_flops_per_s,
            chips=1):
    """Model-free: the required FLOPs per token are the configuration's
    family's count."""
    return (
        100.0 * flops_per_token * tokens_per_step
        / step_s / (peak_flops_per_s * chips)
    )


def peak_for(peaks, device_kind):
    """The row of ``peaks.json`` for a device kind; an unknown device is
    an error, never a default."""
    row = peaks.get(device_kind)
    if not isinstance(row, dict):
        raise LookupError(
            f"no peak for device kind {device_kind!r} in peaks.json"
        )
    return row
