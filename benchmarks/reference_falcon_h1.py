"""The plain reference of the Falcon-H1 block (``model_type:
falcon_h1``; published description: ``transformers/models/falcon_h1/
modeling_falcon_h1.py``, whose ``torch_forward`` is the slow path this
follows): Mamba-2 (SSD) heads beside attention heads on one normalised
input, then a gated MLP, with the published constant multipliers kept
as part of the model.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
chunking — the recurrence is a plain ``lax.scan`` over tokens — and no
import of the program.  It reads the program's parameter TREE (data:
``embed [V, D]``; ``layers.{norm, in_proj, conv_w, conv_b, dt_bias,
A_log, D, ssm_norm, out_proj, wq, wk, wv, wo, mlp_norm, w_gate, w_up,
w_down}`` stacked on a leading layer axis; ``final_norm``; ``lm_head
[D, V]``) and a configuration dict with the published key names.

Departures from ``modeling_falcon_h1.py``, each without effect on the
mathematics:

- linear weights are stored ``[in, out]`` (the program's layout), the
  depthwise convolution's as ``conv_w [K, channels]`` with ``conv_w[k]``
  = ``conv1d.weight[:, 0, k]`` (tap ``K - 1`` is the current token);
- the recurrence runs token by token from a zero state instead of in
  chunks of ``mamba_chunk_size`` (the chunked form is an algorithm for
  the same sum; the published single-token path is this recurrence);
- ``time_step_limit`` (0, inf) clamps nothing and is left out;
  ``attention_mask`` padding does not exist here (no padded batch);
- everything is computed in float32 from weights held in bfloat16 (the
  checkpoint's dtype), upcast a layer at a time, where the published
  code computes in the checkpoint's dtype outside the recurrence;
- ``token_logprobs`` takes the head's log-sum-exp in blocks of the
  vocabulary, so that 4 x 1024 positions of 261120 logits are never
  held at once.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: rows of the vocabulary a block of the head's log-sum-exp holds
HEAD_BLOCK = 32768


def dims(cfg):
    """The sizes the equations use, from the published keys."""
    d_ssm = cfg["mamba_d_ssm"] or cfg["mamba_expand"] * cfg["hidden_size"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {
        "d_ssm": d_ssm,
        "gn": gn,
        "conv_dim": d_ssm + 2 * gn,
        "in_proj": 2 * d_ssm + 2 * gn + cfg["mamba_n_heads"],
        "segments": (d_ssm, d_ssm, gn, gn, cfg["mamba_n_heads"]),
    }


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree."""
    d, L, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    nh, nkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    m, mlp, heads = dims(cfg), cfg["intermediate_size"], cfg["mamba_n_heads"]
    return {
        "embed": (v, d),
        "layers": {
            "norm": (L, d),
            "in_proj": (L, d, m["in_proj"]),
            "conv_w": (L, cfg["mamba_d_conv"], m["conv_dim"]),
            "conv_b": (L, m["conv_dim"]),
            "dt_bias": (L, heads),
            "A_log": (L, heads),
            "D": (L, heads),
            "ssm_norm": (L, m["d_ssm"]),
            "out_proj": (L, m["d_ssm"], d),
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


def mup_vector(cfg):
    """``ssm_multipliers`` spread over ``in_proj``'s output channels
    ``[z | x | B | C | dt]`` (the published ``compute_mup_vector``)."""
    return jnp.concatenate([
        jnp.full((n,), s, F32)
        for n, s in zip(dims(cfg)["segments"], cfg["ssm_multipliers"])
    ])


def output_multipliers(cfg):
    """For each matrix, the constant multipliers between it and what
    its output feeds (a scalar, or one value a column for ``in_proj``):
    the seeded weights are divided by them (``seeded_params``)."""
    return {
        "embed": cfg["embedding_multiplier"],
        "in_proj": cfg["ssm_in_multiplier"] * mup_vector(cfg),
        "out_proj": cfg["ssm_out_multiplier"],
        "wq": cfg["attention_in_multiplier"],
        "wk": cfg["attention_in_multiplier"] * cfg["key_multiplier"],
        "wv": cfg["attention_in_multiplier"],
        "wo": cfg["attention_out_multiplier"],
        "w_gate": cfg["mlp_multipliers"][0],
        "w_up": 1.0,
        "w_down": cfg["mlp_multipliers"][1],
        "lm_head": cfg["lm_head_multiplier"],
    }


def seeded_params(cfg, seed):
    """Weights from ``seed``, made leaf by leaf on the device (the tree
    in float32 would be 21 GB at the benchmark's size).

    Matrices, embedding and head: ``normal(0, fan_in ** -0.5)`` divided
    by the constant multipliers on the matrix's output path, rounded
    ONCE to bfloat16 and held so — what the replica serves and what
    ``logits`` upcasts.  Why the division: at the published multipliers
    (``lm_head_multiplier`` 1/128, ``key_multiplier`` 0.011, ...) the
    dense family's scales would give logits of standard deviation 0.008
    and a uniform softmax: every logprob would be -ln(vocab) whatever
    the cache held, and no tolerance could see a fault.  Divided, q.k /
    sqrt(head_dim) and the logits have standard deviation about 1 and
    the SSM, attention and MLP branches each reach the residual stream
    at its order of magnitude.  The multipliers themselves stay in the
    model.

    Small leaves, float32: norm weights and ``D`` ``1 + 0.1 normal`` (a
    weight of exactly 1 would hide a norm applied to the wrong
    tensor), conv taps ``normal(0, K ** -0.5)``, conv bias ``0.1
    normal``, ``A_log = log(1 .. heads)`` as the published code
    initialises it, ``dt_bias`` so that ``softplus(dt_bias)`` is
    log-uniform in [1e-3, 1e-1] (the published ``time_step_min`` /
    ``time_step_max``): the decays ``exp(dt A)`` then spread over
    (0.04, 0.999) — a state that forgets at once, or never, would hide
    a dropped state."""
    shapes = model_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    mult = output_multipliers(cfg)
    heads, taps = cfg["mamba_n_heads"], cfg["mamba_d_conv"]

    def make(key, i, name, shape):
        # the key is an ARGUMENT: closed over, every seed would be
        # another program to compile (19 of them, half a minute of
        # every run's set-up)
        k = jax.random.fold_in(key, i)
        if name in mult:
            # block by block along the leading axis (a layer, or an
            # eighth of the rows), so that the float32 draw beside the
            # bfloat16 leaf is one block and not the 5 GB whole
            fan_in = shape[-1] if name == "embed" else shape[-2]
            blocks = shape[0] if len(shape) == 3 else (
                8 if shape[0] % 8 == 0 else 1
            )
            rows = shape[0] // blocks

            def fill(j, out):
                w = jax.random.normal(
                    jax.random.fold_in(k, j), (rows,) + shape[1:], F32
                ) * fan_in ** -0.5
                return jax.lax.dynamic_update_slice_in_dim(
                    out, (w / mult[name]).astype(jnp.bfloat16), j * rows, 0
                )

            return jax.lax.fori_loop(
                0, blocks, fill, jnp.zeros(shape, jnp.bfloat16)
            )
        if name == "A_log":
            return jnp.broadcast_to(
                jnp.log(jnp.arange(1, heads + 1, dtype=F32)), shape
            )
        if name == "dt_bias":
            dt = jnp.exp(
                jax.random.uniform(k, shape, F32)
                * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
            )
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        if name == "conv_w":
            return jax.random.normal(k, shape, F32) * taps ** -0.5
        if name == "conv_b":
            return 0.1 * jax.random.normal(k, shape, F32)
        return 1.0 + 0.1 * jax.random.normal(k, shape, F32)

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    leaves = [
        jax.jit(make, static_argnums=(1, 2, 3))(key, i, path[-1].key, shape)
        for i, (path, shape) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [B, S, H, D]: rotate the pairs (x[i], x[i + D/2]) — the
    published ``rotate_half``."""
    s, half = x.shape[1], x.shape[-1] // 2
    # float(): the published rope_theta is the integer 1e11
    freqs = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ssm_heads(h, lp, cfg):
    """The Mamba-2 branch on ``h [B, S, D]`` -> ``[B, S, D]`` (before
    ``ssm_out_multiplier``)."""
    m = dims(cfg)
    b, s, _ = h.shape
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n, taps = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    proj = ((h * cfg["ssm_in_multiplier"]) @ lp["in_proj"]) * mup_vector(cfg)
    z, xbc, dt = jnp.split(
        proj, (m["d_ssm"], m["d_ssm"] + m["conv_dim"]), axis=-1
    )
    # depthwise causal convolution: tap k reaches K - 1 - k tokens back
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = lp["conv_b"] + sum(
        lp["conv_w"][k] * padded[:, k:k + s] for k in range(taps)
    )
    xbc = jax.nn.silu(conv)
    x, bm, cm = jnp.split(xbc, (m["d_ssm"], m["d_ssm"] + m["gn"]), axis=-1)
    x = x.reshape(b, s, heads, p)
    # head i reads group i // (heads / groups)
    bm = jnp.repeat(bm.reshape(b, s, g, n), heads // g, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, n), heads // g, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])  # [B, S, heads]
    a = -jnp.exp(lp["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp  # [B, heads, P], [B, heads, N] x 2
        state = (
            jnp.exp(dt_t * a)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t)
        return state, y_t + lp["D"][None, :, None] * x_t

    _, ys = jax.lax.scan(
        step, jnp.zeros((b, heads, p, n), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, bm, cm, dt)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, m["d_ssm"])
    # gated norm, mamba_norm_before_gate false: gate first, then RMSNorm
    # over each of the groups
    y = (y * jax.nn.silu(z)).reshape(b, s, g, m["d_ssm"] // g)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, -1, keepdims=True) + cfg["rms_norm_eps"]
    )
    return (y.reshape(b, s, m["d_ssm"]) * lp["ssm_norm"]) @ lp["out_proj"]


def _attention_heads(h, lp, cfg):
    b, s, _ = h.shape
    nh, nkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    h = h * cfg["attention_in_multiplier"]
    q = _rope((h @ lp["wq"]).reshape(b, s, nh, hd), cfg["rope_theta"])
    k = _rope(
        ((h @ lp["wk"]) * cfg["key_multiplier"]).reshape(b, s, nkv, hd),
        cfg["rope_theta"],
    )
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
    x = params["embed"][tokens].astype(F32) * cfg["embedding_multiplier"]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(
            lambda w: w[i].astype(F32), params["layers"]
        )
        h = _rms_norm(x, lp["norm"], eps)
        x = (
            x
            + _ssm_heads(h, lp, cfg) * cfg["ssm_out_multiplier"]
            + _attention_heads(h, lp, cfg) * cfg["attention_out_multiplier"]
        )
        h = _rms_norm(x, lp["mlp_norm"], eps)
        gate = jax.nn.silu((h @ lp["w_gate"]) * cfg["mlp_multipliers"][0])
        x = x + ((gate * (h @ lp["w_up"])) @ lp["w_down"]) * (
            cfg["mlp_multipliers"][1]
        )
    return _rms_norm(x, params["final_norm"].astype(F32), eps)


def logits(params, tokens, cfg):
    """tokens [B, S] -> float32 logits [B, S, V] (small sizes only)."""
    with jax.default_matmul_precision("highest"):
        return (
            hidden(params, tokens, cfg) @ params["lm_head"].astype(F32)
        ) * cfg["lm_head_multiplier"]


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
            block = (
                x @ params["lm_head"][:, lo:hi].astype(F32)
            ) * cfg["lm_head_multiplier"]
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
