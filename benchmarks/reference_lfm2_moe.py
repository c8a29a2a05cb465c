"""The plain reference of ``family_lfm2_moe``: LFM2's mixture-of-experts
decoder (``model_type`` ``lfm2_moe``; ``LiquidAI/LFM2-24B-A2B``
``config.json``) — gated short-convolution layers 3 : 1 with
grouped-query attention layers whose heads are ``hidden_size /
num_attention_heads`` = 64 wide, a dense SwiGLU in the ``num_dense_layers``
leading layers and, in the others, routed experts chosen by the top-k of
``sigmoid(router) + bias``, none shared.

With ``h = RMSNorm(x)`` an operator's input at one position (float32
throughout; every block is ``x += Op(RMSNorm_op(x))``, ``x +=
FF(RMSNorm_ffn(x))``; ``h0 = Embed[token]``, no multiplier)::

    conv layer (D channels, K = conv_L_cache taps):
      [B | C | X] = W_in h            (split in this order, no bias)
      u = B * X
      c_t = sum_{j=0..K-1} w_j * u_(t-K+1+j)   (depthwise, causal, zeros
            before the sequence, no bias, NO activation; w_(K-1) on the
            current token)
      Op = W_out (C * c)
    full_attention layer:
      q = W_q h (H x hd), k = W_k h, v = W_v h (KV x hd), no biases
      q, k <- RMSNorm over hd of every head (one weight of hd each)
      q, k <- RoPE (all hd dims, split-half pairs, rope_theta)
      causal softmax(hd^-1/2 q . k) v, head i reads KV head i // (H / KV)
      Op = W_o [heads]
    FF: layers < num_dense_layers SwiGLU(intermediate_size); else s =
      sigmoid(W_r h) over num_experts, the num_experts_per_tok of largest
      s + b, w_e = s_e / (sum of the taken s + 1e-6) *
      routed_scaling_factor, FF = sum_e w_e Expert_e(h)
    final RMSNorm; the head is the embedding transposed

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the convolution as a loop
over its ``K`` shifted copies of the whole sequence, multi-head attention
in blocks of queries against every key, the experts one at a time, no
kernels, no cache, no batching, and no import of the program.  It reads
the program's parameter TREE (data) and a configuration dict with the
published key names.

**Departures from the published description, and what it does not
say.**  The catalog's ``config`` has no key for any of the following;
each follows the public modelling code, from memory (no network), and the
configuration file lists them under ``assumed``: the norm placement
(pre-norm, a final norm); the tied head; the conv operator's form (the
split order ``B, C, X``, the tap order, no activation); the head norms
before the rotation, the split-half pairing and ``head_dim = hidden_size
/ num_attention_heads``; the router's form (sigmoid, the bias in the
selection only, the ``1e-6``, ties to the lowest id).  One departure is
this file's own: the weights are SEEDED, and because the head is the
embedding, the embedding is drawn at the head's scale (``hidden ** -0.5``
a row entry, not the ``normal(0, 1)`` of the untied families' seeded
trees: logits of standard deviation 45 would make the sampler a
near-argmax), and the selection bias at ``0.03`` and not their ``0.1``
(``seeded_params`` says why).

**The share.**  ``deployment.chips_sharing_a_layer`` is 1 here — every
expert of a layer is held — but the tree and the sums are written for a
share as ``reference_kimi_linear.py``'s are: the router is as wide as
the deployment's, the top-k is over all of it, and only the held
experts' terms are summed.

**The experts are forced.**  ``token_logprobs_forced`` takes, at every
position, the experts the served side chose in place of its own
(``served["experts"] [n, L, expert layers, k]``, ids among ALL of the
router's), computes ``s``, the weights and everything else itself, and
holds each choice to its own float32 scores by a slack, in units of ``s
+ b``: the best expert left out minus the worst taken, floored at 0, the
largest over the layers; ``inf`` where a row is malformed (an id outside
the router, a duplicate, -1 at a computed position).

So that 4096 positions fit on one chip after the replica has exited it
walks ONE SEQUENCE at a time, its attention in blocks of ``Q_BLOCK``
queries against every key, ITS EXPERTS ONE AT A TIME and the head in
blocks of ``HEAD_BLOCK`` rows.
"""

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "forced_readings", "seeded_params", "token_logprobs",
    "token_logprobs_forced",
]

F32 = jnp.float32
#: queries a block of attention holds against its keys
Q_BLOCK = 128
#: rows a block of the head holds against the whole vocabulary
HEAD_BLOCK = 256
#: beside the sum of the taken scores (``norm_topk_prob``)
RENORM_EPS = 1e-6
CONV, FULL = "conv", "full_attention"
#: the seeded selection bias's standard deviation (``seeded_params``)
BIAS_SCALE = 0.03

#: the leaves rounded once to bfloat16 and held so
MATRICES = (
    "embed", "w_in", "w_out", "wq", "wk", "wv", "wo", "mlp_gate", "mlp_up",
    "mlp_down", "w_gate", "w_up", "w_down",
)


def router_width(cfg):
    """Experts the router scores: the held ones times the chips that
    share a layer."""
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def first_expert(cfg):
    return cfg["num_experts"] * cfg["deployment"]["share"]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_shapes(cfg, i):
    d, hd = cfg["hidden_size"], head_dim(cfg)
    out = {"op_norm": (d,), "mlp_norm": (d,)}
    if cfg["layer_types"][i] == CONV:
        out.update(
            w_in=(d, 3 * d), conv_w=(cfg["conv_L_cache"], d), w_out=(d, d)
        )
    else:
        nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out.update(
            wq=(d, nh * hd), wk=(d, nkv * hd), wv=(d, nkv * hd),
            wo=(nh * hd, d), q_norm=(hd,), k_norm=(hd,),
        )
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e, r = (
            cfg["moe_intermediate_size"], cfg["num_experts"],
            router_width(cfg),
        )
        out.update(
            router=(d, r), router_bias=(r,),
            w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d),
        )
    return out


def model_shapes(cfg):
    """``{name: shape}`` of the parameter tree for a configuration dict
    (the published key names): a dict a layer, no stack, no ``lm_head``
    (the head is ``embed``)."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"layer_types of {len(cfg['layer_types'])} entries for "
            f"{cfg['num_hidden_layers']} layers"
        )
    return {
        "embed": (cfg["vocab_size"], cfg["hidden_size"]),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg["num_hidden_layers"])
        ),
        "final_norm": (cfg["hidden_size"],),
    }


@functools.partial(jax.jit, static_argnums=(2, 3))
def _make(key, i, name, shape):
    # the key and the leaf's number are ARGUMENTS: closed over, every
    # seed and every leaf would be another program to compile; so a name
    # and shape compile once for all the layers
    k = jax.random.fold_in(key, i)
    if name == "router_bias":
        return BIAS_SCALE * jax.random.normal(k, shape, F32)
    if name == "router":
        return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
    if name == "conv_w":
        return 0.5 * jax.random.normal(k, shape, F32)
    if name not in MATRICES:  # a norm's weight
        return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
    # the embedding is the head: its fan-in is its minor axis
    scale = (shape[-1] if name == "embed" else shape[-2]) ** -0.5
    # block by block along the leading axis (an expert, or an eighth of
    # the rows), so that the float32 draw beside the bfloat16 leaf is
    # one block and not the whole
    blocks = shape[0] if len(shape) >= 3 else (8 if shape[0] % 8 == 0 else 1)
    rows = shape[0] // blocks

    def fill(j, out):
        w = jax.random.normal(
            jax.random.fold_in(k, j), (rows,) + shape[1:], F32
        ) * scale
        return jax.lax.dynamic_update_slice_in_dim(
            out, w.astype(jnp.bfloat16), j * rows, 0
        )

    return jax.lax.fori_loop(0, blocks, fill, jnp.zeros(shape, jnp.bfloat16))


def seeded_params(cfg, seed):
    """Weights from ``seed``, made leaf by leaf on the device.

    Matrices: ``normal(0, fan_in ** -0.5)``, rounded ONCE to bfloat16
    and held so; the embedding, which is the head too, ``normal(0,
    hidden ** -0.5)`` in bfloat16 (the module's docstring says why not
    1).  Behind the blocks' pre-norms these give the gates ``B``, ``C``,
    ``X``, attention logits (64 dims of unit products at scale ``64 **
    -0.5``), router logits and output logits a standard deviation near
    1.  The router: float32, the matrices' scale; its selection bias
    ``0.03 normal`` float32: it changes the experts of ~60 % of the
    positions (so a dropped bias shows), and leaves every expert within
    reach.  NOT the ``0.1`` of the other families' seeded trees: a score
    is a sigmoid, below 1, and with 4 of 64 taken the fourth-best score
    of a token lies near 0.83 — an expert whose bias is under -0.17
    (one in twenty at 0.1) is never taken and one near -0.1 hardly ever:
    the first chip run of the cell read ``moe.experts_hit_pct`` 86 and
    ``moe.rows_max_over_mean`` 6.4 (my chip run, PR 59, call 215), where
    a deployment's bias is what BALANCES the load.  Norm weights ``1 + 0.1 normal`` (a weight of exactly 1
    would hide a norm applied to the wrong tensor), conv taps ``normal(0,
    0.5)`` (three taps of like size: a tap order reversed, or a tail
    dropped, is another function at every token)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model_shapes(cfg),
        is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        ),
    )
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    leaves = [
        _make(key, jnp.uint32(i), path[-1].key, shape)
        for i, (path, shape) in enumerate(flat)
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ----------------------------------------------------------- the equations


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mat(w):
    return w.astype(F32)


def _conv_op(h, lp, cfg):
    """The gated short convolution on ``h [S, D]`` -> ``[S, D]``: the
    whole sequence from zeros before it, tap ``j`` on the copy shifted
    ``K - 1 - j`` tokens back."""
    s, taps = h.shape[0], cfg["conv_L_cache"]
    b, c, x = jnp.split(h @ _mat(lp["w_in"]), 3, axis=-1)
    u = jnp.pad(b * x, ((taps - 1, 0), (0, 0)))
    conv = sum(lp["conv_w"][j] * u[j:j + s] for j in range(taps))
    return (c * conv) @ _mat(lp["w_out"])


def _rotated(x, positions, theta):
    """RoPE on ``x [S, heads, hd]``: dims ``i`` and ``i + hd / 2`` a
    pair, at frequency ``theta ** (-i / (hd / 2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention_op(h, lp, cfg):
    """One grouped-query attention layer over ``h [S, D]`` -> ``[S,
    D]``, in blocks of queries."""
    s, hd, eps = h.shape[0], head_dim(cfg), cfg["norm_eps"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    at = jnp.arange(s)
    q = _rms_norm((h @ _mat(lp["wq"])).reshape(s, nh, hd), lp["q_norm"], eps)
    k = _rms_norm((h @ _mat(lp["wk"])).reshape(s, nkv, hd), lp["k_norm"], eps)
    v = (h @ _mat(lp["wv"])).reshape(s, nkv, hd)
    q, k = _rotated(q, at, theta), _rotated(k, at, theta)
    # a query head's own KV head, spelt out
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    qb = min(Q_BLOCK, s)
    pad = -s % qb

    def block(args):
        q_b, at_b = args
        att = jnp.einsum("qhd,shd->qhs", q_b, k) * hd ** -0.5
        seen = at[None] <= at_b[:, None]
        att = jax.nn.softmax(jnp.where(seen[:, None], att, -jnp.inf), -1)
        return jnp.einsum("qhs,shd->qhd", att, v).reshape(qb, nh * hd)

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, qb) + a.shape[1:])

    # a padded row reads key 0, like a real row would
    out = jax.lax.map(block, (blocks(q), blocks(at)))
    return out.reshape(-1, nh * hd)[:s] @ _mat(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _mat(w_gate)) * (h @ _mat(w_up))) @ _mat(w_down)


def _experts(h, lp, cfg, chosen):
    """One expert layer over ``h [S, D]`` -> (the HELD routed experts'
    weighted sum ``[S, D]``, slack ``[S]``); ``chosen [S, k']`` ids among
    all of the router's, or None (the reference's own choice)."""
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
    g = g / (jnp.sum(g, -1, keepdims=True) + RENORM_EPS)
    g = g * cfg["routed_scaling_factor"]
    # this chip's share: the held experts' gates, the others' terms left out
    first = first_expert(cfg)
    g = g[:, first:first + cfg["num_experts"]]

    def one_expert(out, expert):
        w_gate, w_up, w_down, gate = expert
        return out + gate[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], jnp.moveaxis(g, -1, 0)),
    )
    return out, slack


def _hidden(params, tokens, cfg, chosen):
    """One sequence ``tokens [S]`` -> (the final normalised hidden state
    ``[S, D]``, the router's slack ``[S]``)."""
    eps = cfg["norm_eps"]
    x = params["embed"][tokens].astype(F32)
    worst = jnp.zeros((tokens.shape[0],), F32)
    expert_layer = 0
    for lp, kind in zip(params["layers"], cfg["layer_types"]):
        h = _rms_norm(x, lp["op_norm"], eps)
        x = x + (_conv_op if kind == CONV else _attention_op)(h, lp, cfg)
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
    return _rms_norm(x, params["final_norm"], eps), worst


def _sequence(params, tokens, cfg, chosen):
    """One sequence ``tokens [S]`` -> (the next token's logprob at every
    position ``[S]``: row ``j`` scores ``tokens[j + 1]``, the last row a
    padding target; the router's slack ``[S]``)."""
    s = tokens.shape[0]
    x, worst = _hidden(params, tokens, cfg, chosen)
    target = jnp.concatenate([tokens[1:], tokens[:1] * 0])
    hb = min(HEAD_BLOCK, s)
    pad = -s % hb
    head = _mat(params["embed"]).T

    def rows(args):
        x_b, t_b = args
        logp = jax.nn.log_softmax(x_b @ head, -1)
        return jnp.take_along_axis(logp, t_b[:, None], -1)[:, 0]

    logp = jax.lax.map(rows, (
        jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, hb, x.shape[-1]),
        jnp.pad(target, (0, pad)).reshape(-1, hb),
    ))
    return logp.reshape(-1)[:s], worst


def _forward(params, tokens, cfg, served=None):
    """tokens [n, S] -> (logprobs, the router's slack), each [n, S - 1],
    one sequence at a time; ``served``: the served side's choices
    ``{"experts": [n, S, expert layers, k]}`` or None."""
    given = () if not served else (jnp.asarray(served["experts"]),)

    def one(args):
        return _sequence(
            params, args[0], cfg, args[1] if given else None
        )

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(one, (tokens,) + given)
    return tuple(a[:, :-1] for a in out)


def logits(params, tokens, cfg):
    """tokens [n, S] -> float32 logits [n, S, V], the reference routing
    itself (small sizes only: for the tests)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _hidden(params, t, cfg, None)[0] @ _mat(params["embed"]).T
            for t in jnp.asarray(tokens)
        ])


def token_logprobs(params, tokens, cfg):
    """[n, S] -> [n, S - 1]: log p(tokens[:, i + 1] | tokens[:, :i + 1]),
    the reference routing itself."""
    return _forward(params, jnp.asarray(tokens), cfg)[0]


def forced_readings(params, tokens, cfg, served):
    """As ``token_logprobs`` with the experts the served side chose
    (``served["experts"] [n, S, expert layers, k]``; row ``j`` is what
    it chose while it computed position ``j``) taken in place of the
    reference's own -> (logprobs, the router's slack in units of ``s +
    b``), each [n, S - 1] float32."""
    return _forward(params, jnp.asarray(tokens), cfg, served)


#: ``reference_check.py`` takes (logprobs, ONE slack a position): the
#: router's is the only choice this model makes
token_logprobs_forced = forced_readings
