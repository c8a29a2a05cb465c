"""The plain reference of ``family_trinity``: Trinity-Large's decoder
(``afmoe``) — grouped-query attention with per-head RMSNorm of q and k
and an elementwise sigmoid gate on its output, WINDOW layers (rotary,
keys ``t - sliding_window < s <= t``) and FULL layers (no rotation,
every ``s <= t``; full iff ``(i + 1) % global_attn_every_n_layers ==
0``), four RMSNorms a block, a dense SwiGLU in the leading layers and,
in the others, a shared expert beside routed experts chosen by the
top-k of ``sigmoid(router) + bias`` and weighted by the chosen sigmoids
normalised to ``route_scale``.  The equations are in
``configs/trinity-large-preview.json`` under ``assumed`` and in
``dlrover_tpu/models/trinity.py``'s docstring; this file imports
nothing of the program and reads only its parameter TREE.

**The share.**  The tree holds the experts that live on this chip
(``num_experts`` of the file; ``deployment`` says which of how many):
the router is as wide as the deployment's, the top-k is over all of it,
and only the held experts' terms are summed — what the absent ones
would add is left out here as in the program, and that partial sum goes
on to the next layer.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache.  It
holds the seeded tree as it is (matrices whose values are bfloat16's,
held in bfloat16; router, bias and norms in float32) and upcasts a
matrix where it multiplies.  So that 4 x 32768 tokens fit on one chip
after the replica has exited it walks ONE SEQUENCE at a time, its
attention in blocks of ``Q_BLOCK`` queries (a full layer's block
against every key, a window layer's against the ``sliding_window +
Q_BLOCK`` keys before its end), ITS EXPERTS ONE AT A TIME (a scan over
the held experts, every one over every position, weighted by a gate
that is 0 where it was not chosen) and the head in blocks of
``HEAD_BLOCK`` rows (only the next token's logprob is kept).

**The router is forced.**  ``token_logprobs_forced`` takes, at every
position and expert layer, the experts the served side chose
(``served["experts"] [n, L, expert layers, k]``, ids among ALL of the
router's) in place of its own top-k, computes ``s``, the weights and
everything else itself, and reports per position the largest, over the
layers, of

    slack = max(score[left out]) - min(score[taken]),  floored at 0

in units of the selection score ``s + b``: 0 where the taken set is a
valid top-k of the reference's scores on that forced path, ``inf``
where the row is malformed (an id outside the router, a duplicate, -1
at a computed position).
"""

import jax
import jax.numpy as jnp

__all__ = ["seeded_params", "token_logprobs", "token_logprobs_forced"]

F32 = jnp.float32
#: queries a block of attention holds against its keys
Q_BLOCK = 128
#: rows a block of the head holds against the whole vocabulary
HEAD_BLOCK = 512


def router_width(cfg):
    """Experts the router scores: the held ones times the chips that
    share a layer."""
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def first_expert(cfg):
    return cfg["num_experts"] * cfg["deployment"]["share"]


def layer_window(cfg, i):
    """The window of layer ``i``, None for a full layer."""
    full = (i + 1) % cfg["global_attn_every_n_layers"] == 0
    return None if full else cfg["sliding_window"]


def layer_shapes(cfg, i):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {
        "attn_norm": (d,), "post_attn_norm": (d,), "mlp_norm": (d,),
        "post_mlp_norm": (d,), "q_norm": (hd,), "k_norm": (hd,),
        "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
        "wg": (d, nh * hd), "wo": (nh * hd, d),
    }
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
        fs, r = f * cfg["num_shared_experts"], router_width(cfg)
        out.update(
            router=(d, r), router_bias=(r,),
            shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d),
            w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d),
        )
    return out


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree for a configuration dict
    (the published key names): a dict a layer, no stack."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg["num_hidden_layers"])
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def seeded_params(cfg, seed):
    """Weights from ``seed``, made leaf by leaf on the device.

    Matrices and the head: ``normal(0, fan_in ** -0.5)``, rounded ONCE
    to bfloat16 and held so; the embedding ``normal(0, hidden ** -0.5)``
    in bfloat16, which the mup multiplier ``sqrt(hidden)`` brings to a
    standard deviation of 1 — at 1 before it the residual stream would
    be 55 times what a block adds behind its post-norm, and no fault of
    a block would move a logit.  With q and k normalised by the model
    itself these scales give attention logits, the gate's and the
    router's logits and output logits a standard deviation near 1 each.
    The router: float32, the same scale; its selection bias ``0.1
    normal`` float32, so that it flips a visible share of selections (a
    zero bias would hide a bias applied wrongly).  Norm weights ``1 +
    0.1 normal``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        ),
    )
    d = cfg["hidden_size"]

    def make(key, i, name, shape):
        # the key is an ARGUMENT: closed over, every seed would be
        # another program to compile
        k = jax.random.fold_in(key, i)
        if name == "router_bias":
            return 0.1 * jax.random.normal(k, shape, F32)
        if "norm" in name:
            return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        if name == "router":
            return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
        scale = d ** -0.5 if name == "embed" else shape[-2] ** -0.5
        # block by block along the leading axis (an expert, or an
        # eighth of the rows), so that the float32 draw beside the
        # bfloat16 leaf is one block and not the whole
        blocks = shape[0] if len(shape) >= 3 else (
            8 if shape[0] % 8 == 0 else 1
        )
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

    key = jax.random.PRNGKey(seed % (2**31 - 1))
    leaves = [
        jax.jit(make, static_argnums=(1, 2, 3))(
            key, i, path[-1].key, shape
        )
        for i, (path, shape) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """``x [S, ..., D]``, position the leading axis: rotate the pairs
    ``(x[i], x[i + D/2])``."""
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mat(w):
    return w.astype(F32)


def _attention(h, lp, window, cfg):
    """One layer's gated attention over ``h [S, D]`` (normalised input)
    -> ``[S, heads * head_dim]``, before ``W_o``."""
    s = h.shape[0]
    nh, nkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    eps = cfg["rms_norm_eps"]
    q = _rms_norm((h @ _mat(lp["wq"])).reshape(s, nh, hd), lp["q_norm"], eps)
    k = _rms_norm((h @ _mat(lp["wk"])).reshape(s, nkv, hd), lp["k_norm"], eps)
    v = (h @ _mat(lp["wv"])).reshape(s, nkv, hd)
    gate = jax.nn.sigmoid(h @ _mat(lp["wg"]))
    if window is not None:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    qb = min(Q_BLOCK, s)
    pad = -s % qb
    # a window layer's block reads the ``span`` keys that end with its
    # last query; keys are padded in front so that the slice never
    # leaves the array, and the mask drops what the padding holds
    span = s if window is None else min(window + qb, s + pad)
    front = 0 if window is None else span - qb
    k_p = jnp.pad(k, ((front, pad), (0, 0), (0, 0)))
    v_p = jnp.pad(v, ((front, pad), (0, 0), (0, 0)))

    def block(args):
        q_b, at = args  # [qb, H, hd], positions [qb]
        if window is None:
            k_b, v_b, keys_at = k, v, jnp.arange(s)
        else:
            # in padded coordinates the block's last query sits at
            # ``at[0] + qb - 1 + front``: the slice ends with it
            k_b = jax.lax.dynamic_slice_in_dim(k_p, at[0], span, 0)
            v_b = jax.lax.dynamic_slice_in_dim(v_p, at[0], span, 0)
            keys_at = at[0] - front + jnp.arange(span)
        seen = (keys_at[None] <= at[:, None]) & (keys_at[None] >= 0)
        if window is not None:
            seen = seen & (keys_at[None] > at[:, None] - window)
        att = jnp.einsum(
            "qkgd,skd->qkgs", q_b.reshape(qb, nkv, nh // nkv, hd), k_b
        ) * hd ** -0.5
        att = jax.nn.softmax(
            jnp.where(seen[:, None, None], att, -jnp.inf), -1
        )
        return jnp.einsum("qkgs,skd->qkgd", att, v_b).reshape(qb, nh * hd)

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, qb) + a.shape[1:])

    out = jax.lax.map(block, (blocks(q), blocks(jnp.arange(s))))
    return out.reshape(-1, nh * hd)[:s] * gate


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _mat(w_gate)) * (h @ _mat(w_up))) @ _mat(w_down)


def _experts(h, lp, cfg, chosen):
    """One expert layer over ``h [S, D]`` -> (the shared expert plus the
    HELD routed experts' weighted sum ``[S, D]``, slack ``[S]``);
    ``chosen [S, k']`` ids among all of the router's, or None (the
    reference's own top-k)."""
    r, k = router_width(cfg), cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    score = s + lp["router_bias"]
    if chosen is None:
        chosen = jax.lax.top_k(score, k)[1]
    in_range = (chosen >= 0) & (chosen < r)
    # [S, R]: how often the row names each expert
    named = jnp.sum(
        jax.nn.one_hot(chosen, r, dtype=F32) * in_range[..., None], -2
    )
    taken = named > 0
    well_formed = (
        jnp.all(in_range, -1) & jnp.all(named <= 1, -1)
        & (chosen.shape[-1] == k)
    )
    slack = jnp.max(jnp.where(taken, -jnp.inf, score), -1) - jnp.min(
        jnp.where(taken, score, jnp.inf), -1
    )
    slack = jnp.where(well_formed, jnp.maximum(slack, 0.0), jnp.inf)
    g = jnp.where(taken, s, 0.0)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * cfg["route_scale"]
    # this chip's share: the held experts' gates, the others' terms left out
    first = first_expert(cfg)
    g = g[:, first:first + cfg["num_experts"]]

    def one_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert,
        _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),
        (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(g, -1, 0)),
    )
    return out, slack


def _sequence(params, tokens, cfg, chosen):
    """One sequence ``tokens [S]`` -> (the next token's logprob at
    every position ``[S]``: row ``j`` scores ``tokens[j + 1]``, the
    last row a padding target; slack ``[S]``)."""
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(F32)
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    worst = jnp.zeros((s,), F32)
    expert_layer = 0
    for i, lp in enumerate(params["layers"]):
        h = _rms_norm(x, lp["attn_norm"], eps)
        attn = _attention(h, lp, layer_window(cfg, i), cfg) @ _mat(lp["wo"])
        x = x + _rms_norm(attn, lp["post_attn_norm"], eps)
        h = _rms_norm(x, lp["mlp_norm"], eps)
        if "router" in lp:
            out, slack = _experts(
                h, lp, cfg,
                None if chosen is None else chosen[:, expert_layer],
            )
            worst, expert_layer = jnp.maximum(worst, slack), expert_layer + 1
        else:
            out = _swiglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
        x = x + _rms_norm(out, lp["post_mlp_norm"], eps)
    x = _rms_norm(x, params["final_norm"], eps)
    target = jnp.concatenate([tokens[1:], tokens[:1] * 0])
    hb = min(HEAD_BLOCK, s)
    pad = -s % hb
    head = _mat(params["lm_head"])

    def rows(args):
        x_b, t_b = args
        logp = jax.nn.log_softmax(x_b @ head, -1)
        return jnp.take_along_axis(logp, t_b[:, None], -1)[:, 0]

    logp = jax.lax.map(rows, (
        jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, hb, x.shape[-1]),
        jnp.pad(target, (0, pad)).reshape(-1, hb),
    ))
    return logp.reshape(-1)[:s], worst


def _forward(params, tokens, cfg, chosen=None):
    """tokens [n, S] -> (logprobs [n, S - 1], slack [n, S - 1]), one
    sequence at a time."""
    with jax.default_matmul_precision("highest"):
        if chosen is None:
            logp, slack = jax.lax.map(
                lambda t: _sequence(params, t, cfg, None), tokens
            )
        else:
            logp, slack = jax.lax.map(
                lambda a: _sequence(params, a[0], cfg, a[1]),
                (tokens, chosen),
            )
    return logp[:, :-1], slack[:, :-1]


def token_logprobs(params, tokens, cfg):
    """[n, S] -> [n, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1]),
    the reference routing itself."""
    return _forward(params, jnp.asarray(tokens), cfg)[0]


def token_logprobs_forced(params, tokens, cfg, served):
    """As ``token_logprobs`` with every ROUTER choice taken from
    ``served["experts"] [n, S, expert layers, k]`` (row ``j``: what the
    served side chose while it computed position ``j``) -> (logprobs,
    slack), both [n, S - 1] float32."""
    return _forward(
        params, jnp.asarray(tokens), cfg, jnp.asarray(served["experts"])
    )
