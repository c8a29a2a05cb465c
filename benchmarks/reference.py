"""The plain reference of both configurations' block: RMSNorm, rotary
position embedding (split-half pairs, as the published checkpoints'
``rotate_half``), grouped-query causal attention, SwiGLU, no biases, an
untied output head — Mistral-7B (arXiv:2310.06825) and DeepSeek-LLM 7B
(arXiv:2401.02954) are both exactly this block.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (a TPU multiplies float32 in
bfloat16 passes otherwise): no kernels, no cache, no remat, no scan, no
import of the program.  It reads the program's parameter TREE (data:
``embed [V, D]``, ``layers.{attn_norm, wq, wk, wv, wo, mlp_norm, w_gate,
w_up, w_down}`` stacked on a leading layer axis, ``final_norm``,
``lm_head [D, V]``) and nothing else of it.  Sliding-window attention is
not modelled: every sequence here is shorter than Mistral's window.
"""

import jax
import jax.numpy as jnp


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree for a configuration dict
    (Hugging Face key names)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = d // cfg["num_attention_heads"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mlp, v = cfg["intermediate_size"], cfg["vocab_size"]
    return {
        "embed": (v, d),
        "layers": {
            "attn_norm": (L, d),
            "wq": (L, d, nh * hd),
            "wk": (L, d, nkv * hd),
            "wv": (L, d, nkv * hd),
            "wo": (L, nh * hd, d),
            "mlp_norm": (L, d),
            "w_gate": (L, d, mlp),
            "w_up": (L, d, mlp),
            "w_down": (L, mlp, d),
        },
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def seeded_params(cfg, seed):
    """Float32 weights from ``seed``, made on the device in one jitted
    call: normal(0, fan_in ** -0.5) matrices, norm scales 1."""
    shapes = model_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )

    @jax.jit
    def make(key):
        leaves = []
        for i, (path, shape) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            if "norm" in name:
                leaves.append(jnp.ones(shape, jnp.float32))
                continue
            fan_in = shape[-1] if "embed" in name else shape[-2]
            leaves.append(
                jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) * fan_in ** -0.5
            )
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(jax.random.PRNGKey(seed % (2**31 - 1)))


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


def logits(params, tokens, cfg):
    """tokens [B, S] -> float32 logits [B, S, V]."""
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        b, s, d = x.shape
        hd = d // nh
        causal = jnp.tril(jnp.ones((s, s), bool))
        lp = params["layers"]
        for i in range(cfg["num_hidden_layers"]):
            h = _rms_norm(x, lp["attn_norm"][i], eps)
            q = _rope((h @ lp["wq"][i]).reshape(b, s, nh, hd), theta)
            k = _rope((h @ lp["wk"][i]).reshape(b, s, nkv, hd), theta)
            v = (h @ lp["wv"][i]).reshape(b, s, nkv, hd)
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
            out = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
            x = x + out @ lp["wo"][i]
            h = _rms_norm(x, lp["mlp_norm"][i], eps)
            x = x + (
                jax.nn.silu(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])
            ) @ lp["w_down"][i]
        x = _rms_norm(x, params["final_norm"], eps)
        return x @ params["lm_head"].astype(jnp.float32)


def token_logprobs(params, tokens, cfg):
    """[B, S] -> [B, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1])."""
    logp = jax.nn.log_softmax(logits(params, tokens[:, :-1], cfg), -1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]

