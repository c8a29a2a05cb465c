"""The plain reference of ``family_deepseek_v32``: DeepSeek-V3.2's
decoder — latent attention (a low-rank query, ONE compressed key/value
row and ONE rotated shared key a token, decompressed a head), a learned
top-k indexer fed from the query latent, a dense SwiGLU in the leading
layers and, in the others, a shared expert beside routed experts chosen
by the group-limited top-k of ``sigmoid(router) + bias``.  The
equations are in ``configs/deepseek-v3.2.json`` under ``assumed`` and in
``dlrover_tpu/models/deepseek_v32.py``'s docstring; this file imports
nothing of the program and reads only its parameter TREE.

**The share.**  The tree holds the experts that live on this chip
(``n_routed_experts`` of the file; ``deployment`` says which of how
many): the router is as wide as the deployment's, groups and top-k are
over all of it, and only the held experts' terms are summed — what the
absent ones would add is left out here as in the program.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, attention in MULTI-HEAD
(decompressed) form: no absorbed product, no kernels, no cache, no
batching.  It holds the seeded tree as it is (matrices whose values are
bfloat16's, held in bfloat16; router, bias and norms in float32) and
upcasts a matrix where it multiplies.  So that 8192 positions fit on
one chip after the replica has exited it walks ONE SEQUENCE at a time,
its index scores, selection and attention in blocks of ``Q_BLOCK``
queries against every key, ITS EXPERTS ONE AT A TIME and the head in
blocks of ``HEAD_BLOCK`` rows.  Left to itself (``token_logprobs``)
its selection is ITS OWN, exact, in float32.

**Both choices are forced.**  A top-k is a choice made INSIDE the
model: a sound bfloat16 run and this reference disagree about the keys
near the 2048th index score as about the experts near the 8th router
score, and with seeded weights a key left out carries as much of the
softmax as one taken, so the disagreement moves every later token.
``token_logprobs_forced`` therefore takes, at every position, what the
served side chose in place of its own — in every layer the keys its
indexer picked (``served["selection"] [n, L, layers, W]`` int32, a bit
a position: bit ``b`` of word ``j`` is position ``32 j + b``) and in
every expert layer its experts (``served["experts"] [n, L, expert
layers, k]``, ids among ALL of the router's) — computes index scores,
``s``, the weights and everything else itself, and holds each choice
to its own float32 scores by a slack.

*The selection's*, in units of an index score ``I[t, s]``: the best key
the served side left out minus the worst it took, floored at 0 (0
while ``t < index_topk``: every key is taken); ``inf`` where the row is
no selection of that query's (a key it cannot see, another count than
``min(t + 1, index_topk)``), and the reference's own is then taken in
its place.  *The router's*, in units of ``s + b``, the largest over the
layers: among the experts that the group limit leaves open — the served
side's groups, filled up to ``topk_group`` with the reference's best —

    max(score[open, left out]) - min(score[taken]),  floored at 0,

and, where a served expert lies OUTSIDE the reference's own
``topk_group`` groups, how far its group's score (the sum of its two
largest ``s + b``) lies below the last group the reference took: within
the cell's ``routing_slack_max`` that is a near-tie of two groups, above
it a wrong group.  ``inf`` where a row is malformed (an id outside the
router, a duplicate, -1 at a computed position, more groups than
``topk_group``).  ``reference_check.py`` takes ONE slack a position:
the larger of the two, the selection's multiplied by the
configuration's ``assumed.selection_slack_weight`` (the ratio of the
two limits the tolerance probe set, so that the cell's
``routing_slack_max`` holds both).
"""

import math

import jax
import jax.numpy as jnp

__all__ = [
    "forced_readings", "seeded_params", "token_logprobs",
    "token_logprobs_forced",
]

F32 = jnp.float32
#: queries a block of index scores and attention holds against its keys
Q_BLOCK = 64
#: rows a block of the head holds against the whole vocabulary
HEAD_BLOCK = 512


def router_width(cfg):
    """Experts the router scores: the held ones times the chips that
    share a layer."""
    return cfg["n_routed_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def first_expert(cfg):
    return cfg["n_routed_experts"] * cfg["deployment"]["share"]


def layer_shapes(cfg, i):
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    out = {
        "attn_norm": (d,), "mlp_norm": (d,),
        "wq_a": (d, rq), "q_norm": (rq,), "wq_b": (rq, nh * (dn + dr)),
        "wkv_a": (d, rkv + dr), "kv_norm": (rkv,),
        "wkv_b": (rkv, nh * (dn + dv)), "wo": (nh * dv, d),
        "wi_q": (rq, hi * di), "wi_k": (d, di), "wi_w": (d, hi),
        "ik_norm": (di,), "ik_norm_bias": (di,),
    }
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
        fs, r = f * cfg["n_shared_experts"], router_width(cfg)
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
    to bfloat16 and held so; the embedding ``normal(0, 1)`` in bfloat16.
    With the model's own norms (of the query latent, of the key/value
    latent, of the index key) these give attention logits a standard
    deviation near 1.9 (``192 ** 0.5`` times the published scale
    0.13523), index scores near 0.7, router logits and output logits
    near 1.  (``W_o`` at four times that scale was tried on the chip,
    PR 53: over ~2000 nearly flat keys a head's output is an eighth of
    one value's size.  It made a sound run's worst token of 4480 read
    0.13-0.26 — the served side's bfloat16 selection against the
    reference's own float32 one, amplified — where rounding the weights
    to int8 reads 0.17, and was taken out.)  The router: float32, the
    matrices' scale; its selection bias ``0.1 normal``
    float32, so that it flips a visible share of selections and of
    groups.  Norm weights ``1 + 0.1 normal``, the index key's LayerNorm
    bias ``0.1 normal``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        ),
    )

    def make(key, i, name, shape):
        # the key and the leaf's number are ARGUMENTS: closed over, every
        # seed and every leaf would be another program to compile (a
        # short compile, which the persistent cache does not keep, in
        # the replica and again in the reference's process); so a name
        # and shape compile once for all the layers
        k = jax.random.fold_in(key, i)
        if name.endswith("_bias"):
            return 0.1 * jax.random.normal(k, shape, F32)
        if "norm" in name:
            return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        if name == "router":
            return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
        scale = 1.0 if name == "embed" else shape[-2] ** -0.5
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
    make = jax.jit(make, static_argnums=(2, 3))
    leaves = [
        make(key, jnp.uint32(i), path[-1].key, shape)
        for i, (path, shape) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ----------------------------------------------------------- the equations


def yarn_inv_freq(cfg):
    """The closed form of the rotation's frequencies, ``[rope / 2]``:
    dim ``j`` turns ``f_j = theta ** (-2 j / rope)`` a position, and
    YaRN makes it ``f_j / factor * r_j + f_j * (1 - r_j)`` with ``r_j =
    clip((j - low) / (high - low), 0, 1)``, ``low`` / ``high`` the floor
    / ceiling of ``rope * ln(original / (2 pi beta)) / (2 ln theta)``
    at ``beta_fast`` / ``beta_slow``: a dim that turns more than
    ``beta_fast`` times over the original context keeps its frequency,
    one that turns less than ``beta_slow`` times is interpolated."""
    sc = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    out = []
    for j in range(dim // 2):
        f = base ** (-2.0 * j / dim)
        if sc is not None and sc["factor"] > 1:
            def at(beta):
                return dim * math.log(
                    sc["original_max_position_embeddings"]
                    / (beta * 2 * math.pi)
                ) / (2 * math.log(base))

            low = max(math.floor(at(sc["beta_fast"])), 0)
            high = min(math.ceil(at(sc["beta_slow"])), dim - 1)
            r = min(max((j - low) / max(high - low, 1e-3), 0.0), 1.0)
            f = f / sc["factor"] * r + f * (1 - r)
        out.append(f)
    return jnp.asarray(out, F32)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = 1.0
    if sc is not None and sc["factor"] > 1:
        m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    ) ** -0.5 * m * m


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, inv_freq):
    """``x [S, ..., D]``, position the leading axis: rotate the pairs
    ``(x[i], x[i + n/2])`` of the FIRST ``n = 2 len(inv_freq)`` dims,
    the rest as it is."""
    s, half = x.shape[0], inv_freq.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def _mat(w):
    return w.astype(F32)


def _topk_mask(score, k):
    """``[Q, S]`` float32, ``-inf`` where a key is not visible -> bool:
    row by row the ``k`` largest, equal scores lowest position first;
    every finite one where a row has at most ``k``."""
    finite = jnp.isfinite(score)
    if score.shape[-1] <= k:
        return finite
    kth = jnp.sort(score, -1)[:, -k][:, None]
    above, equal = score > kth, score == kth
    room = k - jnp.sum(above, -1, keepdims=True)
    return (above | (equal & (jnp.cumsum(equal, -1) <= room))) & finite


def _unpack(words, s):
    """int32 ``[Q, W]``, a bit a position (bit ``b`` of word ``j`` is
    position ``32 j + b``) -> (bool ``[Q, s]``, the bits set in all of
    ``W`` words ``[Q]``)."""
    bits = ((words[:, :, None] >> jnp.arange(32)) & 1).reshape(
        words.shape[0], -1
    )
    total = jnp.sum(bits, -1)
    bits = jnp.pad(bits[:, :s], ((0, 0), (0, max(s - bits.shape[1], 0))))
    return bits.astype(bool), total


def _attention(h, lp, cfg, inv_freq, picked):
    """One layer's attention over ``h [S, D]`` (normalised input) ->
    (``[S, heads * v]``, before ``W_o``; the selection's slack ``[S]``).
    ``picked [S, W]`` int32: the positions the served side's indexer
    took, a bit a position, or None (the reference's own selection,
    slack 0)."""
    s = h.shape[0]
    nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    eps, scale = cfg["rms_norm_eps"], softmax_scale(cfg)
    c_q = _rms_norm(h @ _mat(lp["wq_a"]), lp["q_norm"], eps)
    q = (c_q @ _mat(lp["wq_b"])).reshape(s, nh, -1)
    q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], inv_freq)
    kva = h @ _mat(lp["wkv_a"])
    c_kv = _rms_norm(kva[:, :rkv], lp["kv_norm"], eps)
    k_pe = _rope(kva[:, rkv:], inv_freq)  # ONE for all heads
    kv = (c_kv @ _mat(lp["wkv_b"])).reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    # the indexer: queries from the query latent, key and head weights
    # from the hidden state
    qi = _rope((c_q @ _mat(lp["wi_q"])).reshape(s, hi, di), inv_freq)
    raw = h @ _mat(lp["wi_k"])
    mean = jnp.mean(raw, -1, keepdims=True)
    var = jnp.mean((raw - mean) ** 2, -1, keepdims=True)
    ik = (raw - mean) * jax.lax.rsqrt(var + eps)
    ik = _rope(ik * lp["ik_norm"] + lp["ik_norm_bias"], inv_freq)
    w = (h @ _mat(lp["wi_w"])) * (hi ** -0.5 * di ** -0.5)
    qb = min(Q_BLOCK, s)
    pad = -s % qb
    keys_at = jnp.arange(s)

    def block(args):
        q_n, q_p, qi_b, w_b, at, *served = args
        index = jnp.einsum(
            "qh,qhs->qs", w_b,
            jax.nn.relu(jnp.einsum("qhd,sd->qhs", qi_b, ik)),
        )
        seen = keys_at[None] <= at[:, None]
        taken = _topk_mask(
            jnp.where(seen, index, -jnp.inf), cfg["index_topk"]
        )
        slack = jnp.zeros((qb,), F32)
        if served:
            # the served side's choice in place of the reference's own,
            # and how far under the reference's scores the worst key it
            # took lies below the best it left out; a row that is no
            # selection of this query's (a key it cannot see, another
            # count than min(t + 1, index_topk)) reads inf, and the
            # reference's own is taken in its place
            theirs, count = _unpack(served[0], s)
            whole = (
                jnp.sum(theirs & seen, -1) == count
            ) & (count == jnp.minimum(at + 1, cfg["index_topk"]))
            slack = jnp.max(
                jnp.where(seen & ~theirs, index, -jnp.inf), -1
            ) - jnp.min(jnp.where(theirs, index, jnp.inf), -1)
            slack = jnp.where(whole, jnp.maximum(slack, 0.0), jnp.inf)
            taken = jnp.where(whole[:, None], theirs, taken)
        att = (
            jnp.einsum("qhd,shd->qhs", q_n, k_nope)
            + jnp.einsum("qhd,sd->qhs", q_p, k_pe)
        ) * scale
        att = jax.nn.softmax(jnp.where(taken[:, None], att, -jnp.inf), -1)
        # a padded query row sees nothing: its softmax is NaN, dropped
        return jnp.einsum("qhs,shd->qhd", att, v).reshape(qb, nh * dv), slack

    def blocks(a, fill=0):
        a = jnp.pad(
            a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), constant_values=fill
        )
        return a.reshape((-1, qb) + a.shape[1:])

    out, slack = jax.lax.map(block, (
        blocks(q_nope), blocks(q_pe), blocks(qi), blocks(w),
        # a padded row reads key 0, like a real row would
        blocks(keys_at),
    ) + (() if picked is None else (blocks(picked),)))
    return out.reshape(-1, nh * dv)[:s], slack.reshape(-1)[:s]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _mat(w_gate)) * (h @ _mat(w_up))) @ _mat(w_down)


def _group_scores(score, n_group):
    """``[S, R]`` -> ``[S, n_group]``: a group's score is the sum of its
    two largest."""
    grouped = score.reshape(score.shape[0], n_group, -1)
    return jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)


def _best_groups(group_score, n):
    """bool ``[S, n_group]``: the ``n`` largest, equal scores lowest id
    first."""
    best = jax.lax.top_k(group_score, n)[1]
    return jnp.any(
        best[..., None] == jnp.arange(group_score.shape[-1]), axis=1
    )


def _experts(h, lp, cfg, chosen):
    """One expert layer over ``h [S, D]`` -> (the shared expert plus the
    HELD routed experts' weighted sum ``[S, D]``, slack ``[S]``);
    ``chosen [S, k']`` ids among all of the router's, or None (the
    reference's own choice)."""
    r, k = router_width(cfg), cfg["num_experts_per_tok"]
    ng, tg = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    score = s + lp["router_bias"]
    group_score = _group_scores(score, ng)
    own_groups = _best_groups(group_score, tg)
    if chosen is None:
        chosen = jax.lax.top_k(
            jnp.where(jnp.repeat(own_groups, r // ng, 1), score, -jnp.inf), k
        )[1]
    in_range = (chosen >= 0) & (chosen < r)
    # [S, R]: how often the row names each expert
    named = jnp.sum(
        jax.nn.one_hot(chosen, r, dtype=F32) * in_range[..., None], -2
    )
    taken = named > 0
    served_groups = jnp.any(taken.reshape(-1, ng, r // ng), -1)
    well_formed = (
        jnp.all(in_range, -1) & jnp.all(named <= 1, -1)
        & (chosen.shape[-1] == k) & (jnp.sum(served_groups, -1) <= tg)
    )
    # a served group outside the reference's own: how far below the
    # last group the reference took
    last_own = jnp.min(jnp.where(own_groups, group_score, jnp.inf), -1)
    group_slack = last_own - jnp.min(
        jnp.where(served_groups, group_score, jnp.inf), -1
    )
    # the experts the group limit leaves open: the served side's groups,
    # filled up with the reference's best
    open_groups = _best_groups(
        jnp.where(served_groups, jnp.inf, group_score), tg
    )
    left_out = jnp.repeat(open_groups, r // ng, 1) & ~taken
    slack = jnp.max(jnp.where(left_out, score, -jnp.inf), -1) - jnp.min(
        jnp.where(taken, score, jnp.inf), -1
    )
    slack = jnp.maximum(jnp.maximum(slack, group_slack), 0.0)
    slack = jnp.where(well_formed, slack, jnp.inf)
    g = jnp.where(taken, s, 0.0)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    # this chip's share: the held experts' gates, the others' terms left out
    first = first_expert(cfg)
    g = g[:, first:first + cfg["n_routed_experts"]]

    def one_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert,
        _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"]),
        (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(g, -1, 0)),
    )
    return out, slack


def _sequence(params, tokens, cfg, chosen, picked):
    """One sequence ``tokens [S]`` -> (the next token's logprob at
    every position ``[S]``: row ``j`` scores ``tokens[j + 1]``, the
    last row a padding target; the router's slack ``[S]``; the
    selection's slack ``[S]``).  ``chosen [S, expert layers, k]`` /
    ``picked [S, layers, W]``: the served side's choices, or None."""
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[0]
    inv_freq = yarn_inv_freq(cfg)
    x = params["embed"][tokens].astype(F32)
    worst, worst_picked = jnp.zeros((s,), F32), jnp.zeros((s,), F32)
    expert_layer = 0
    for i, lp in enumerate(params["layers"]):
        h = _rms_norm(x, lp["attn_norm"], eps)
        att, slack = _attention(
            h, lp, cfg, inv_freq, None if picked is None else picked[:, i]
        )
        worst_picked = jnp.maximum(worst_picked, slack)
        x = x + att @ _mat(lp["wo"])
        h = _rms_norm(x, lp["mlp_norm"], eps)
        if "router" in lp:
            out, slack = _experts(
                h, lp, cfg,
                None if chosen is None else chosen[:, expert_layer],
            )
            worst, expert_layer = jnp.maximum(worst, slack), expert_layer + 1
        else:
            out = _swiglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
        x = x + out
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
    return logp.reshape(-1)[:s], worst, worst_picked


def _forward(params, tokens, cfg, served=None):
    """tokens [n, S] -> (logprobs, the router's slack, the selection's
    slack), each [n, S - 1], one sequence at a time; ``served``: the
    served side's choices ``{"experts": [n, S, expert layers, k],
    "selection": [n, S, layers, W]}``, either or neither."""
    names = [n for n in ("experts", "selection") if n in (served or {})]
    given = tuple(jnp.asarray(served[n]) for n in names)

    def one(args):
        forced = dict(zip(names, args[1:]))
        return _sequence(
            params, args[0], cfg, forced.get("experts"),
            forced.get("selection"),
        )

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(one, (tokens,) + given)
    return tuple(a[:, :-1] for a in out)


def token_logprobs(params, tokens, cfg):
    """[n, S] -> [n, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1]),
    the reference routing and selecting itself."""
    return _forward(params, jnp.asarray(tokens), cfg)[0]


def forced_readings(params, tokens, cfg, served):
    """As ``token_logprobs`` with every choice the served side made
    taken in place of the reference's own — the experts of every expert
    layer (``served["experts"] [n, S, expert layers, k]``) and the keys
    the indexer picked in every layer (``served["selection"] [n, S,
    layers, W]`` int32, a bit a position); row ``j`` is what the served
    side chose while it computed position ``j`` -> (logprobs, the
    router's slack in units of ``s + b``, the selection's slack in
    units of an index score), each [n, S - 1] float32."""
    return _forward(params, jnp.asarray(tokens), cfg, served)


def token_logprobs_forced(params, tokens, cfg, served):
    """:func:`forced_readings` as ``reference_check.py`` takes them:
    (logprobs, slack), the slack ONE number a position — the larger of
    the router's and of the selection's, the latter in the router's
    units by ``assumed.selection_slack_weight`` of the configuration
    (the two limits' ratio, so that ``routing_slack_max`` holds both)."""
    logp, routed, picked = forced_readings(params, tokens, cfg, served)
    weight = cfg["assumed"]["selection_slack_weight"]
    return logp, jnp.maximum(routed, picked * weight)
