"""The plain reference of ``family_keye_vl2``: Keye-VL-2.0's language
decoder — grouped-query attention with per-head RMSNorm of q and k,
a learned indexer that picks the ``topk`` keys a query reads, and a
layer of routed experts (top-k of a softmax router, weights renormalised,
no shared expert) in every block.  The equations are in
``configs/keye-vl-2.0-30b-a3b.json`` under ``assumed`` and in
``dlrover_tpu/models/keye_vl2.py``'s docstring; this file imports
nothing of the program and reads only its parameter TREE.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache.  It
holds the seeded tree as it is (matrices whose values are bfloat16's,
held in bfloat16; the router, the norms in float32) and upcasts a
matrix where it multiplies.  So that 4 x 16384 tokens fit on one chip
after the replica has exited it walks ONE SEQUENCE at a time, its
attention in blocks of ``Q_BLOCK`` queries (index scores, the top-k and
the softmax of one block against every key), ITS EXPERTS ONE AT A TIME
(a scan over the stacked expert weights, every expert over every
position, weighted by a gate that is 0 where it was not chosen) and the
head in blocks of ``HEAD_BLOCK`` rows (only the next token's logprob
is kept).

**The router is forced, the indexer is not.**  ``token_logprobs_forced``
takes, at every position and layer, the experts the served side chose
(``served["experts"] [n, L, layers, k]``) in place of its own top-k,
computes their weights from ITS OWN float32 router logits, and reports
per position the largest, over the layers, of

    slack = max(logit[left out]) - min(logit[taken]),  floored at 0

in units of a float32 router logit: 0 where the taken set is a valid
top-k of the reference's logits on that forced path, ``inf`` where the
row is malformed (an id outside ``[0, num_experts)``, a duplicate, -1
at a computed position).  The indexer's selection is the reference's
own float32 top-``topk`` (equal scores lowest position first): forcing
it would need ``[positions, layers, topk]`` ids a reply.
"""

import jax
import jax.numpy as jnp

__all__ = ["seeded_params", "token_logprobs", "token_logprobs_forced"]

F32 = jnp.float32
#: queries a block of attention holds against every key
Q_BLOCK = 256
#: rows a block of the head holds against the whole vocabulary
HEAD_BLOCK = 512


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree for a configuration dict
    (the published key names)."""
    d, L, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    nh, nkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "embed": (v, d),
        "layers": {
            "attn_norm": (L, d),
            "wq": (L, d, nh * hd),
            "wk": (L, d, nkv * hd),
            "wv": (L, d, nkv * hd),
            "q_norm": (L, hd),
            "k_norm": (L, hd),
            "wo": (L, nh * hd, d),
            "wi_q": (L, d, hi * di),
            "wi_k": (L, d, di),
            "wi_w": (L, d, hi),
            "ik_norm": (L, di),
            "ik_norm_bias": (L, di),
            "mlp_norm": (L, d),
            "router": (L, d, e),
            "w_gate": (L, e, d, f),
            "w_up": (L, e, d, f),
            "w_down": (L, e, f, d),
        },
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def seeded_params(cfg, seed):
    """Weights from ``seed``, made leaf by leaf on the device.

    Matrices and the head: ``normal(0, fan_in ** -0.5)``, rounded ONCE
    to bfloat16 and held so (what the replica serves and what this file
    upcasts); the embedding ``normal(0, 1)`` in bfloat16.  With q, k and
    the index key normalised by the model itself, these scales give
    attention logits, index scores (``w`` carries ``heads ** -0.5 * dim
    ** -0.5``), router logits and output logits a standard deviation
    near 1 each: a flat router or a flat indexer would hide a wrong
    page, and a softmax over 151936 flat logits would hide everything.
    The router: float32, the same scale (its logits decide a discrete
    choice; the program computes them in float32 too).  Norm weights
    ``1 + 0.1 normal`` (a weight of exactly 1 would hide a norm applied
    to the wrong tensor), the index key's LayerNorm bias ``0.1
    normal``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )

    def make(key, i, name, shape):
        # the key is an ARGUMENT: closed over, every seed would be
        # another program to compile
        k = jax.random.fold_in(key, i)
        if name.endswith("_bias"):
            return 0.1 * jax.random.normal(k, shape, F32)
        if "norm" in name:
            return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        if name == "router":
            return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
        scale = 1.0 if name == "embed" else shape[-2] ** -0.5
        # block by block along the leading axis (a layer, or an eighth
        # of the rows), so that the float32 draw beside the bfloat16
        # leaf is one block and not the whole
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


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


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


def _attention(h, lp, i, cfg):
    """One layer's attention over ``h [S, D]`` (normalised input) ->
    ``[S, heads * head_dim]``: every query reads the ``topk`` keys of
    largest index score at or before it."""
    s = h.shape[0]
    nh, nkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"],
    )
    sa = cfg["sa_config"]
    hi, di, topk = (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    )
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = _rms_norm((h @ _mat(lp["wq"][i])).reshape(s, nh, hd),
                  lp["q_norm"][i], eps)
    k = _rms_norm((h @ _mat(lp["wk"][i])).reshape(s, nkv, hd),
                  lp["k_norm"][i], eps)
    v = (h @ _mat(lp["wv"][i])).reshape(s, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    qi = _rope((h @ _mat(lp["wi_q"][i])).reshape(s, hi, di), theta)
    ik = _rope(
        _layer_norm(h @ _mat(lp["wi_k"][i]), lp["ik_norm"][i],
                    lp["ik_norm_bias"][i], eps),
        theta,
    )
    w = (h @ _mat(lp["wi_w"][i])) * (hi ** -0.5 * di ** -0.5)
    n_sel = min(topk, s)
    qb = min(Q_BLOCK, s)
    pad = -s % qb
    keys_at = jnp.arange(s)

    def block(args):
        q_b, qi_b, w_b, at = args  # [qb, ...], positions [qb]
        score = jnp.einsum(
            "qh,qhs->qs", w_b,
            jax.nn.relu(jnp.einsum("qhd,sd->qhs", qi_b, ik)),
        )
        causal = keys_at[None] <= at[:, None]
        score = jnp.where(causal, score, -jnp.inf)
        # the top-k positions, equal scores lowest position first; a
        # row with fewer than k keys before it names masked ones too
        ids = jax.lax.top_k(score, n_sel)[1]
        taken = jnp.zeros(score.shape, bool).at[
            jnp.arange(qb)[:, None], ids
        ].set(True) & causal
        att = jnp.einsum(
            "qkgd,skd->qkgs", q_b.reshape(qb, nkv, nh // nkv, hd), k
        ) * hd ** -0.5
        att = jax.nn.softmax(
            jnp.where(taken[:, None, None], att, -jnp.inf), -1
        )
        return jnp.einsum("qkgs,skd->qkgd", att, v).reshape(qb, nh * hd)

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, qb) + a.shape[1:])

    out = jax.lax.map(block, (blocks(q), blocks(qi), blocks(w),
                              blocks(keys_at)))
    return out.reshape(-1, nh * hd)[:s]


def _experts(h, lp, i, cfg, chosen):
    """One layer's experts over ``h [S, D]`` -> (their weighted sum
    ``[S, D]``, slack ``[S]``); ``chosen [S, k']`` or None (the
    reference's own top-k)."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = h @ lp["router"][i].astype(F32)
    if chosen is None:
        chosen = jax.lax.top_k(logits, k)[1]
    in_range = (chosen >= 0) & (chosen < e)
    # [S, E]: how often the row names each expert
    named = jnp.sum(
        jax.nn.one_hot(chosen, e, dtype=F32) * in_range[..., None], -2
    )
    taken = named > 0
    well_formed = (
        jnp.all(in_range, -1) & jnp.all(named <= 1, -1)
        & (chosen.shape[-1] == k)
    )
    slack = jnp.max(jnp.where(taken, -jnp.inf, logits), -1) - jnp.min(
        jnp.where(taken, logits, jnp.inf), -1
    )
    slack = jnp.where(well_formed, jnp.maximum(slack, 0.0), jnp.inf)
    # softmax over all experts, the taken ones renormalised to 1
    g = jnp.where(taken, jax.nn.softmax(logits, -1), 0.0)
    g = g / jnp.sum(g, -1, keepdims=True).clip(1e-30)

    def one_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        y = (jax.nn.silu(h @ _mat(w_gate)) * (h @ _mat(w_up))) @ _mat(w_down)
        return out + gate[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["w_gate"][i], lp["w_up"][i], lp["w_down"][i],
         jnp.moveaxis(g, -1, 0)),
    )
    return out, slack


def _sequence(params, tokens, cfg, chosen):
    """One sequence ``tokens [S]`` -> (the next token's logprob at
    every position ``[S]``: row ``j`` scores ``tokens[j + 1]``, the
    last row a padding target; slack ``[S]``)."""
    eps = cfg["rms_norm_eps"]
    lp = params["layers"]
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(F32)
    worst = jnp.zeros((s,), F32)
    for i in range(cfg["num_hidden_layers"]):
        h = _rms_norm(x, lp["attn_norm"][i], eps)
        x = x + _attention(h, lp, i, cfg) @ _mat(lp["wo"][i])
        h = _rms_norm(x, lp["mlp_norm"][i], eps)
        out, slack = _experts(
            h, lp, i, cfg, None if chosen is None else chosen[:, i]
        )
        x, worst = x + out, jnp.maximum(worst, slack)
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
    the reference routing and selecting itself."""
    return _forward(params, jnp.asarray(tokens), cfg)[0]


def token_logprobs_forced(params, tokens, cfg, served):
    """As ``token_logprobs`` with every ROUTER choice taken from
    ``served["experts"] [n, S, layers, k]`` (row ``j``: what the served
    side chose while it computed position ``j``) -> (logprobs, slack),
    both [n, S - 1] float32.  The indexer's selection stays the
    reference's own."""
    return _forward(
        params, jnp.asarray(tokens), cfg, jnp.asarray(served["experts"])
    )
