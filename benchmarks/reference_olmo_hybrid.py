"""The plain reference of the Olmo-Hybrid decoder (``model_type:
olmo_hybrid``; published description: ``allenai/Olmo-Hybrid-7B``
``config.json``, whose ``linear_*`` keys are Qwen3-Next's names for
FLA's ``GatedDeltaNet``): gated delta-rule layers between full-attention
layers, a norm on each sublayer's OUTPUT, a gated MLP.

With ``h`` a block's input at one position (float32 throughout)::

    linear layer:
      q~ = W_q h   k~ = W_k h   v~ = W_v h
      [q~, k~, v~] <- SiLU(causal depthwise conv, 4 taps, no bias)
      per head i: q = q~_i / |q~_i|_2 * dk^-1/2, k = k~_i / |k~_i|_2,
                  v = v~_i                               (eps 1e-6)
      beta = 2 sigmoid(W_b h)_i          (linear_allow_neg_eigval)
      alpha = exp(-exp(A_log_i) softplus((W_a h)_i + dt_bias_i))
      u = beta (v - alpha S^T k);  S <- alpha S + k (x) u;  o = S^T q
      y_i = RMSNorm_dv(o; weight) * SiLU((W_g h)_i);  out = W_o y
    full layer:
      q = RMSNorm(W_q h), k = RMSNorm(W_k h) over the whole projection,
      causal softmax(q . k / sqrt(head_dim)) v per head, W_o; no rotation
    block:  x <- x + RMSNorm(mixer(x));  x <- x + RMSNorm(MLP(x))
            MLP(x) = W_down(SiLU(W_gate x) * W_up x)
    final RMSNorm, untied head

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching of the recurrence — it is a plain ``lax.scan`` over positions,
independent of the program's chunked (WY) form — and no import of the
program.  It reads the program's parameter TREE (data: ``embed [V, D]``;
``layers`` a tuple of dicts, a linear layer's ``{wq, wk, wv, wg, wa, wb,
conv_w, A_log, dt_bias, gdn_norm, wo}`` or a full layer's ``{wq, wk,
wv, wo, q_norm, k_norm}`` beside ``{post_attn_norm, post_mlp_norm,
w_gate, w_up, w_down}``; ``final_norm``; ``lm_head [D, V]``) and a
configuration dict with the published key names.

Departures from the published description, each without effect on the
mathematics:

- what ``config.json`` has no key for is *assumed* and listed in the
  configuration file's ``assumed`` (the norm placement, the q/k norm,
  no rotation where ``rope_theta`` is null, the form of the gated delta
  rule's lines);
- linear weights are stored ``[in, out]`` (the program's layout), the
  depthwise convolution's as ``conv_w [K, channels]`` with ``conv_w[k]``
  = ``conv1d.weight[:, 0, k]`` (tap ``K - 1`` is the current token), one
  leaf over the channels ``[q | k | v]`` where the published module has
  three convolutions;
- the recurrence runs token by token from a zero state (the published
  chunked kernel is an algorithm for the same sum);
- everything is computed in float32 from weights held in bfloat16 (the
  checkpoint's dtype), upcast a layer at a time;
- ``token_logprobs`` takes the head's log-sum-exp in blocks of the
  vocabulary.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: rows of the vocabulary a block of the head's log-sum-exp holds
HEAD_BLOCK = 32768
#: under the square root of q's and k's L2 norm
L2_EPS = 1e-6
LINEAR = "linear_attention"

#: the leaves rounded once to bfloat16 and held so
MATRICES = (
    "embed", "lm_head", "wq", "wk", "wv", "wg", "wa", "wb", "wo",
    "w_gate", "w_up", "w_down",
)
#: ``W_a`` and ``W_b`` against the other matrices (``seeded_params``)
GATE_SCALE = 0.25


def dims(cfg):
    """The sizes the equations use, from the published keys."""
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim = cfg["linear_num_key_heads"] * dk
    return {
        "heads": heads, "dk": dk, "dv": dv, "key_dim": key_dim,
        "value_dim": heads * dv, "conv_dim": 2 * key_dim + heads * dv,
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
    }


def layer_shapes(cfg, layer):
    d, f, m = cfg["hidden_size"], cfg["intermediate_size"], dims(cfg)
    out = {
        "post_attn_norm": (d,), "post_mlp_norm": (d,),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }
    if cfg["layer_types"][layer] == LINEAR:
        out.update(
            wq=(d, m["key_dim"]), wk=(d, m["key_dim"]),
            wv=(d, m["value_dim"]), wg=(d, m["value_dim"]),
            wa=(d, m["heads"]), wb=(d, m["heads"]),
            conv_w=(cfg["linear_conv_kernel_dim"], m["conv_dim"]),
            A_log=(m["heads"],), dt_bias=(m["heads"],),
            gdn_norm=(m["dv"],), wo=(m["value_dim"], d),
        )
    else:
        kv = cfg["num_key_value_heads"] * m["head_dim"]
        out.update(
            wq=(d, d), wk=(d, kv), wv=(d, kv), wo=(d, d),
            q_norm=(d,), k_norm=(kv,),
        )
    return out


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg["num_hidden_layers"])
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


@functools.partial(jax.jit, static_argnums=(2, 3))
def _make(key, i, name, shape):
    # the key and the leaf's index are ARGUMENTS: closed over, every
    # seed and every layer would be another program to compile
    k = jax.random.fold_in(key, i)
    if name in MATRICES:
        scale = 1.0 if name == "embed" else shape[-2] ** -0.5
        if name in ("wa", "wb"):
            scale *= GATE_SCALE
        # block by block along the leading axis (an eighth of the
        # rows), so that the float32 draw beside the bfloat16 leaf is
        # one block and not the whole
        blocks = 8 if shape[0] % 8 == 0 else 1
        rows = shape[0] // blocks

        def fill(j, out):
            w = jax.random.normal(
                jax.random.fold_in(k, j), (rows,) + shape[1:], F32
            ) * scale
            return jax.lax.dynamic_update_slice_in_dim(
                out, w.astype(jnp.bfloat16), j * rows, 0
            )

        return jax.lax.fori_loop(
            0, blocks, fill, jnp.zeros(shape, jnp.bfloat16)
        )
    if name == "A_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))
    if name == "dt_bias":
        dt = jnp.exp(
            jax.random.uniform(k, shape, F32)
            * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
        )
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    if name == "conv_w":
        return 0.5 * jax.random.normal(k, shape, F32)
    return 1.0 + 0.1 * jax.random.normal(k, shape, F32)


def seeded_params(cfg, seed):
    """Weights from ``seed``, made leaf by leaf on the device.

    Matrices and the head: ``normal(0, fan_in ** -0.5)``, rounded ONCE
    to bfloat16 and held so — what the replica serves and what
    ``hidden`` upcasts.  The embedding ``normal(0, 1)``: a block adds
    its sublayers' outputs behind a norm, at a standard deviation of 1,
    and a token's row has to reach the residual stream at that order or
    no logit would tell one token from another.  ``W_a`` and ``W_b`` a
    quarter of that scale: the blocks read the residual stream without
    a norm before them, its scale grows to ~5 over twelve layers, and
    gate logits of that size would pin ``alpha`` to 0 and ``beta`` to 0
    or 2.

    Small leaves, float32: norm weights ``1 + 0.1 normal`` (a weight of
    exactly 1 would hide a norm applied to the wrong tensor), conv taps
    ``normal(0, 0.5)``, ``A_log = log(1 .. heads)`` and ``dt_bias`` so
    that ``softplus(dt_bias)`` is log-uniform in [1e-3, 1e-1], as the
    published Mamba-2 / GatedDeltaNet code initialises them: at a zero
    gate logit the decays ``alpha`` spread over (0.04, 0.999) — a state
    that forgets at once, or never, would hide a dropped state."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        ),
    )
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    leaves = [
        _make(key, i, path[-1].key, shape)
        for i, (path, shape) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _linear_mixer(h, lp, cfg):
    """The gated delta-rule mixer on ``h [B, S, D]`` -> ``[B, S, D]``."""
    m = dims(cfg)
    b, s, _ = h.shape
    heads, dk, dv = m["heads"], m["dk"], m["dv"]
    taps = cfg["linear_conv_kernel_dim"]
    raw = jnp.concatenate([h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]], -1)
    # depthwise causal convolution: tap k reaches K - 1 - k tokens back
    padded = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(
        sum(lp["conv_w"][k] * padded[:, k:k + s] for k in range(taps))
    )
    q, k, v = jnp.split(conv, (m["key_dim"], 2 * m["key_dim"]), axis=-1)
    q = _l2_normed(q.reshape(b, s, heads, dk)) * dk ** -0.5
    k = _l2_normed(k.reshape(b, s, heads, dk))
    v = v.reshape(b, s, heads, dv)
    beta = jax.nn.sigmoid(h @ lp["wb"])
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(
        -jnp.exp(lp["A_log"]) * jax.nn.softplus(h @ lp["wa"] + lp["dt_bias"])
    )

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp  # [B, H, dk] x 2, [B, H, dv], [B, H] x 2
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        u = b_t[..., None] * (v_t - a_t[..., None] * read)
        state = (
            a_t[..., None, None] * state
            + k_t[..., :, None] * u[..., None, :]
        )
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((b, heads, dk, dv), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, alpha, beta)),
    )
    o = jnp.moveaxis(o, 0, 1)  # [B, S, H, dv]
    o = _rms_norm(o, lp["gdn_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(h @ lp["wg"]).reshape(b, s, heads, dv)
    return (o * gate).reshape(b, s, heads * dv) @ lp["wo"]


def _full_mixer(h, lp, cfg):
    b, s, _ = h.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = dims(cfg)["head_dim"], cfg["rms_norm_eps"]
    q = _rms_norm(h @ lp["wq"], lp["q_norm"], eps).reshape(b, s, nh, hd)
    k = _rms_norm(h @ lp["wk"], lp["k_norm"], eps).reshape(b, s, nkv, hd)
    v = (h @ lp["wv"]).reshape(b, s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, nh * hd)
    return out @ lp["wo"]


def hidden(params, tokens, cfg):
    """tokens [B, S] -> the final normalised hidden state [B, S, D]."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    # (``layer_types`` read up to the depth: the list is cut with it)
    for i in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][i]
        lp = jax.tree_util.tree_map(
            lambda w: w.astype(F32), params["layers"][i]
        )
        mixer = _linear_mixer if kind == LINEAR else _full_mixer
        x = x + _rms_norm(mixer(x, lp, cfg), lp["post_attn_norm"], eps)
        mlp = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        x = x + _rms_norm(mlp, lp["post_mlp_norm"], eps)
    return _rms_norm(x, params["final_norm"].astype(F32), eps)


def logits(params, tokens, cfg):
    """tokens [B, S] -> float32 logits [B, S, V] (small sizes only)."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg) @ params["lm_head"].astype(F32)


def token_logprobs(params, tokens, cfg):
    """[B, S] -> [B, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1]),
    the log-sum-exp over the vocabulary taken block by block."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens[:, :-1], cfg)
        target = tokens[:, 1:]
        v = cfg["vocab_size"]
        top = jnp.full(target.shape, -jnp.inf, F32)
        total = jnp.zeros(target.shape, F32)
        picked = jnp.zeros(target.shape, F32)
        for lo in range(0, v, HEAD_BLOCK):
            hi = min(lo + HEAD_BLOCK, v)
            block = x @ params["lm_head"][:, lo:hi].astype(F32)
            new_top = jnp.maximum(top, block.max(-1))
            total = total * jnp.exp(top - new_top) + jnp.exp(
                block - new_top[..., None]
            ).sum(-1)
            top = new_top
            inside = (target >= lo) & (target < hi)
            picked = picked + jnp.where(
                inside,
                jnp.take_along_axis(
                    block, jnp.clip(target - lo, 0, hi - lo - 1)[..., None],
                    -1,
                )[..., 0],
                0.0,
            )
        return picked - (top + jnp.log(total))
