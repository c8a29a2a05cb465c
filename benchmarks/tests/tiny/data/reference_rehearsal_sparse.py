"""The sparse rehearsal family's plain reference: a decoder whose MLP is
a layer of routed experts.  Per layer: RMSNorm, causal multi-head
attention with rotary embedding at FULL strength (no multiplier on any
branch), RMSNorm, then a router over ``n_routed_experts`` — sigmoid
scores, the selection made on ``score + bias``, the gates the chosen
scores normalised to 1 and scaled by ``routed_scaling_factor`` — whose
chosen SwiGLU experts are summed beside one shared expert.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no import of the program.
It WALKS ITS EXPERTS ONE AT A TIME (a scan over the stacked expert
weights, every expert over every position, weighted by a gate that is 0
where it was not chosen): the working set is one expert's, which is
what lets a reference of this kind fit beside a real configuration's
weights.

**Forced routing.**  ``forward(..., chosen=None)`` routes itself.  With
``chosen [B, S, layers, k]`` it takes, at every position and layer,
those experts instead of its own top-k, computes their gates from ITS
OWN scores, and reports per position the largest, over the layers, of

    slack = max(select[left out]) - min(select[taken]),  floored at 0

in units of the selection score (a sigmoid probability plus the bias):
0 where the taken set is a valid top-k of the reference's float32
scores on that forced path, ``inf`` where the row is malformed (an id
outside ``[0, n_routed_experts)``, a duplicate, another count than
``num_experts_per_tok``).
"""

import jax
import jax.numpy as jnp

__all__ = ["seeded_params", "token_logprobs", "token_logprobs_forced"]


def model_shapes(cfg):
    d, L, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    return {
        "embed": (v, d),
        "layers": {
            "attn_norm": (L, d),
            "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
            "wo": (L, d, d),
            "mlp_norm": (L, d),
            "router": (L, d, e),
            "router_bias": (L, e),
            "w_gate": (L, e, d, f), "w_up": (L, e, d, f),
            "w_down": (L, e, f, d),
            "shared_gate": (L, d, fs), "shared_up": (L, d, fs),
            "shared_down": (L, fs, d),
        },
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def seeded_params(cfg, seed):
    """Weights from ``seed`` in one jitted call, float32 arrays whose
    values are bfloat16's (the configuration states ``torch_dtype``
    bfloat16: a serving side that casts them loses nothing): matrices
    normal(0, fan_in ** -0.5), norm scales 1, the selection bias uniform
    in +-0.1."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )

    @jax.jit
    def make(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            name, k = jax.tree_util.keystr(path), jax.random.fold_in(key, i)
            if "norm" in name:
                leaf = jnp.ones(shape, jnp.float32)
            elif "router_bias" in name:
                leaf = jax.random.uniform(k, shape, jnp.float32, -0.1, 0.1)
            else:
                fan_in = shape[-1] if "embed" in name else shape[-2]
                leaf = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            leaves.append(leaf.astype(jnp.bfloat16).astype(jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(jax.random.PRNGKey((seed + 2) % (2**31 - 1)))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (x[i], x[i + D/2])."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _routed(h, lp, i, cfg, chosen):
    """One layer's experts over ``h [B, S, D]`` -> (their sum beside the
    shared expert, slack [B, S]); ``chosen [B, S, k']`` or None."""
    e, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ lp["router"][i])
    select = scores + lp["router_bias"][i]
    if chosen is None:
        chosen = jax.lax.top_k(select, k)[1]
    in_range = (chosen >= 0) & (chosen < e)
    # [B, S, E]: how often the row names each expert
    named = jnp.sum(
        jax.nn.one_hot(chosen, e, dtype=jnp.float32) * in_range[..., None], -2
    )
    taken = named > 0
    well_formed = (
        jnp.all(in_range, -1) & jnp.all(named <= 1, -1)
        & (chosen.shape[-1] == k)
    )
    slack = jnp.max(jnp.where(taken, -jnp.inf, select), -1) - jnp.min(
        jnp.where(taken, select, jnp.inf), -1
    )
    slack = jnp.where(well_formed, jnp.maximum(slack, 0.0), jnp.inf)
    gates = jnp.where(taken, scores, 0.0)
    gates = gates / jnp.sum(gates, -1, keepdims=True).clip(1e-20)
    gates = gates * cfg["routed_scaling_factor"]

    def one_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[..., None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert,
        _swiglu(h, lp["shared_gate"][i], lp["shared_up"][i],
                lp["shared_down"][i]),
        (lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i],
         jnp.moveaxis(gates, -1, 0)),
    )
    return out, slack


def forward(params, tokens, cfg, chosen=None):
    """tokens [B, S] -> (float32 logits [B, S, V], slack [B, S])."""
    nh = cfg["num_attention_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        b, s, d = x.shape
        hd = d // nh
        causal = jnp.tril(jnp.ones((s, s), bool))
        lp = params["layers"]
        worst = jnp.zeros((b, s), jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            h = _rms_norm(x, lp["attn_norm"][i], eps)
            q = _rope((h @ lp["wq"][i]).reshape(b, s, nh, hd), theta)
            k = _rope((h @ lp["wk"][i]).reshape(b, s, nh, hd), theta)
            v = (h @ lp["wv"][i]).reshape(b, s, nh, hd)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
            x = x + out @ lp["wo"][i]
            h = _rms_norm(x, lp["mlp_norm"][i], eps)
            out, slack = _routed(
                h, lp, i, cfg, None if chosen is None else chosen[:, :, i]
            )
            x, worst = x + out, jnp.maximum(worst, slack)
        x = _rms_norm(x, params["final_norm"], eps)
        return x @ params["lm_head"], worst


def _answer_logprobs(logits, tokens):
    logp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]


def token_logprobs(params, tokens, cfg):
    """[B, S] -> [B, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1]),
    the reference routing itself."""
    return _answer_logprobs(forward(params, tokens[:, :-1], cfg)[0], tokens)


def token_logprobs_forced(params, tokens, cfg, served):
    """As ``token_logprobs`` with every choice taken from
    ``served["experts"] [B, S, layers, k]`` (row ``j``: what the served
    side chose while it computed position ``j``) -> (logprobs, slack),
    both [B, S - 1] float32."""
    logits, slack = forward(
        params, tokens[:, :-1], cfg, served["experts"][:, :-1]
    )
    return _answer_logprobs(logits, tokens), slack
