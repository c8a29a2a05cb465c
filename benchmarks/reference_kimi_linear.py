"""The plain reference of ``family_kimi_linear``: Kimi Linear's decoder
(``model_type`` ``kimi_linear``; ``moonshotai/Kimi-Linear-48B-A3B-
Instruct`` ``config.json``; arXiv:2510.26692) — Kimi Delta Attention
layers (a gated delta rule whose decay is a vector of ``head_dim`` a
head) 3 : 1 with NoPE latent-attention layers, a dense SwiGLU in the
leading layer and, in the others, a shared expert beside routed experts
chosen by the top-k of ``sigmoid(router) + bias``.

With ``h = RMSNorm(x)`` a mixer's input at one position (float32
throughout; every block is ``x += Mixer(RMSNorm(x))``, ``x +=
MLP(RMSNorm(x))``)::

    KDA layer (H heads of dk = dv = head_dim):
      q~ = W_q h   k~ = W_k h   v~ = W_v h
      [q~, k~, v~] <- SiLU(causal depthwise conv, 4 taps, no bias)
      per head i: q = q~_i / |q~_i|_2 * dk^-1/2, k = k~_i / |k~_i|_2,
                  v = v~_i                               (eps 1e-6)
      a = exp(-exp(A_log_i) softplus((W_f2 W_f1 h)_i + dt_bias_i))
          a vector of dk a head
      beta = sigmoid(W_b h)_i
      S' = diag(a) S;  u = beta (v - S'^T k);  S <- S' + k (x) u
      o = S^T q
      y_i = RMSNorm_dv(o; weight) * sigmoid((W_g2 W_g1 h)_i);  W_o y
    MLA layer:
      q = W_q h, a head [q_nope, q_pe];  W_kva h -> [c, k_pe]
      c_kv = RMSNorm(c);  W_kvb c_kv -> a head [k_nope, v]
      k = [k_nope, k_pe]  (nothing rotated: mla_use_nope)
      causal softmax((nope + rope)^-1/2 q . k) v per head, W_o
    MLP: layer 1 SwiGLU(intermediate_size); else s = sigmoid(W_r h'),
      the num_experts_per_token of largest s + b, w_e = s_e / sum s *
      routed_scaling_factor, Shared(h') + sum_e w_e Expert_e(h')
    final RMSNorm, untied head

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence is a plain
``lax.scan`` over positions from a zero state (independent of the
program's chunked WY form), attention in MULTI-HEAD (decompressed) form,
no kernels, no cache, no batching, and no import of the program.  It
reads the program's parameter TREE (data) and a configuration dict with
the published key names (``linear_attn_config`` nested as published,
its layer lists numbered from 1).

**The share.**  The tree holds the experts that live on this chip
(``num_experts`` of the file; ``deployment`` says which of how many):
the router is as wide as the deployment's, the top-k is over all of it,
and only the held experts' terms are summed — what the absent ones would
add is left out here as in the program.

**The experts are forced.**  ``token_logprobs_forced`` takes, at every
position, the experts the served side chose in place of its own
(``served["experts"] [n, L, expert layers, k]``, ids among ALL of the
router's), computes ``s``, the weights and everything else itself, and
holds each choice to its own float32 scores by a slack, in units of ``s
+ b``: the best expert left out minus the worst taken, floored at 0, the
largest over the layers; ``inf`` where a row is malformed (an id outside
the router, a duplicate, -1 at a computed position).

So that 8192 positions fit on one chip after the replica has exited it
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
Q_BLOCK = 64
#: rows a block of the head holds against the whole vocabulary
HEAD_BLOCK = 512
#: under the square root of q's and k's L2 norm
L2_EPS = 1e-6
KDA, MLA = "kda", "mla"

#: the leaves rounded once to bfloat16 and held so
MATRICES = (
    "embed", "lm_head", "wq", "wk", "wv", "wf1", "wf2", "wg1", "wg2", "wb",
    "wo", "wkv_a", "wkv_b", "mlp_gate", "mlp_up", "mlp_down", "shared_gate",
    "shared_up", "shared_down", "w_gate", "w_up", "w_down",
)
#: ``A = exp(A_log)`` runs from 1 to this over the heads
A_MAX = 4.0
#: ``softplus(dt_bias)`` is log-uniform between these over the channels
DT_RANGE = (1e-4, 1e-1)


def router_width(cfg):
    """Experts the router scores: the held ones times the chips that
    share a layer."""
    return cfg["num_experts"] * cfg["deployment"]["chips_sharing_a_layer"]


def first_expert(cfg):
    return cfg["num_experts"] * cfg["deployment"]["share"]


def layer_kinds(cfg):
    """``KDA`` / ``MLA`` a layer of the file's depth: the published
    lists number the layers from 1."""
    la = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if (i in la["kda_layers"]) == (i in la["full_attn_layers"]):
            raise ValueError(
                f"layer {i} is in both or neither of kda_layers and "
                "full_attn_layers"
            )
        kinds.append(KDA if i in la["kda_layers"] else MLA)
    return kinds


def layer_shapes(cfg, i):
    d, la = cfg["hidden_size"], cfg["linear_attn_config"]
    out = {"attn_norm": (d,), "mlp_norm": (d,)}
    if layer_kinds(cfg)[i] == KDA:
        h, hd, taps = la["num_heads"], la["head_dim"], (
            la["short_conv_kernel_size"]
        )
        kd = h * hd
        out.update(
            wq=(d, kd), wk=(d, kd), wv=(d, kd),
            wf1=(d, hd), wf2=(hd, kd), wg1=(d, hd), wg2=(hd, kd),
            wb=(d, h), conv_w=(taps, 3 * kd), A_log=(h,), dt_bias=(kd,),
            kda_norm=(hd,), wo=(kd, d),
        )
    else:
        nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        dn, dr, dv = (
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"],
        )
        out.update(
            wq=(d, nh * (dn + dr)), wkv_a=(d, rkv + dr), kv_norm=(rkv,),
            wkv_b=(rkv, nh * (dn + dv)), wo=(nh * dv, d),
        )
    if i < cfg["first_k_dense_replace"]:
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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _make(key, i, name, shape):
    # the key and the leaf's number are ARGUMENTS: closed over, every
    # seed and every leaf would be another program to compile; so a name
    # and shape compile once for all the layers
    k = jax.random.fold_in(key, i)
    if name == "router_bias":
        return 0.1 * jax.random.normal(k, shape, F32)
    if name == "router":
        return jax.random.normal(k, shape, F32) * shape[-2] ** -0.5
    if name == "A_log":
        return jnp.log(jnp.linspace(1.0, A_MAX, shape[0], dtype=F32))
    if name == "dt_bias":
        lo, hi = (jnp.log(x) for x in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(k, shape, F32) * (hi - lo) + lo)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    if name == "conv_w":
        return 0.5 * jax.random.normal(k, shape, F32)
    if name not in MATRICES:  # a norm's weight
        return 1.0 + 0.1 * jax.random.normal(k, shape, F32)
    scale = 1.0 if name == "embed" else shape[-2] ** -0.5
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

    Matrices and the head: ``normal(0, fan_in ** -0.5)``, rounded ONCE
    to bfloat16 and held so; the embedding ``normal(0, 1)`` in bfloat16.
    Behind the blocks' pre-norms these give attention logits (192 dims
    of unit products at scale ``192 ** -0.5``), router logits, the
    decay's and the gates' logits and the output logits a standard
    deviation near 1.  The router: float32, the matrices' scale; its
    selection bias ``0.1 normal`` float32, so that it flips a visible
    share of selections.  Norm weights ``1 + 0.1 normal`` (a weight of
    exactly 1 would hide a norm applied to the wrong tensor), conv taps
    ``normal(0, 0.5)``.

    The decay: ``A = exp(A_log)`` runs 1 .. 4 over the heads and
    ``softplus(dt_bias)`` is log-uniform in [1e-4, 1e-1] over a head's
    CHANNELS, so at a zero gate logit a head's ``head_dim`` decays spread
    from ~0.9 (0.67 in the last head) to ~0.9999 — the token's own logit
    moves each by a factor ``e^+-1`` in the exponent, no more: a decay
    taken a head (the scalar gate) is another function on every head,
    and a state that forgets at once, or never, would hide a dropped
    state."""
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


def _l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _mat(w):
    return w.astype(F32)


def _kda_mixer(h, lp, cfg):
    """The Kimi Delta Attention mixer on ``h [S, D]`` -> ``[S, D]``: the
    recurrence one token at a time from a zero state."""
    la = cfg["linear_attn_config"]
    heads, hd, taps = (
        la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    )
    s, kd = h.shape[0], heads * hd
    raw = jnp.concatenate(
        [h @ _mat(lp["wq"]), h @ _mat(lp["wk"]), h @ _mat(lp["wv"])], -1
    )
    # depthwise causal convolution: tap k reaches K - 1 - k tokens back
    padded = jnp.pad(raw, ((taps - 1, 0), (0, 0)))
    conv = jax.nn.silu(
        sum(lp["conv_w"][k] * padded[k:k + s] for k in range(taps))
    )
    q, k, v = jnp.split(conv, (kd, 2 * kd), axis=-1)
    q = _l2_normed(q.reshape(s, heads, hd)) * hd ** -0.5
    k = _l2_normed(k.reshape(s, heads, hd))
    v = v.reshape(s, heads, hd)
    decay_logit = (h @ _mat(lp["wf1"])) @ _mat(lp["wf2"]) + lp["dt_bias"]
    a = jnp.exp(
        -jnp.exp(lp["A_log"])[:, None]
        * jax.nn.softplus(decay_logit).reshape(s, heads, hd)
    )
    beta = jax.nn.sigmoid(h @ _mat(lp["wb"]))

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp  # [H, dk] x 2, [H, dv], [H, dk], [H]
        state = a_t[..., None] * state
        read = jnp.einsum("hkv,hk->hv", state, k_t)
        u = b_t[:, None] * (v_t - read)
        state = state + k_t[..., None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((heads, hd, hd), F32), (q, k, v, a, beta)
    )
    o = _rms_norm(o, lp["kda_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid((h @ _mat(lp["wg1"])) @ _mat(lp["wg2"]))
    return (o * gate.reshape(s, heads, hd)).reshape(s, kd) @ _mat(lp["wo"])


def _mla_mixer(h, lp, cfg):
    """One latent-attention layer over ``h [S, D]`` -> ``[S, D]``, every
    head its own decompressed keys and values, in blocks of queries."""
    s = h.shape[0]
    nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    q = (h @ _mat(lp["wq"])).reshape(s, nh, dn + dr)
    kva = h @ _mat(lp["wkv_a"])
    c_kv = _rms_norm(kva[:, :rkv], lp["kv_norm"], cfg["rms_norm_eps"])
    k_pe = kva[:, rkv:]  # ONE for all heads, not rotated
    kv = (c_kv @ _mat(lp["wkv_b"])).reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    qb = min(Q_BLOCK, s)
    pad = -s % qb
    keys_at = jnp.arange(s)

    def block(args):
        q_b, at = args
        att = (
            jnp.einsum("qhd,shd->qhs", q_b[..., :dn], k_nope)
            + jnp.einsum("qhd,sd->qhs", q_b[..., dn:], k_pe)
        ) * (dn + dr) ** -0.5
        seen = keys_at[None] <= at[:, None]
        att = jax.nn.softmax(jnp.where(seen[:, None], att, -jnp.inf), -1)
        return jnp.einsum("qhs,shd->qhd", att, v).reshape(qb, nh * dv)

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, qb) + a.shape[1:])

    # a padded row reads key 0, like a real row would
    out = jax.lax.map(block, (blocks(q), blocks(keys_at)))
    return out.reshape(-1, nh * dv)[:s] @ _mat(lp["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _mat(w_gate)) * (h @ _mat(w_up))) @ _mat(w_down)


def _experts(h, lp, cfg, chosen):
    """One expert layer over ``h [S, D]`` -> (the shared expert plus the
    HELD routed experts' weighted sum ``[S, D]``, slack ``[S]``);
    ``chosen [S, k']`` ids among all of the router's, or None (the
    reference's own choice)."""
    r, k = router_width(cfg), cfg["num_experts_per_token"]
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
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
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


def _hidden(params, tokens, cfg, chosen):
    """One sequence ``tokens [S]`` -> (the final normalised hidden state
    ``[S, D]``, the router's slack ``[S]``)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    worst = jnp.zeros((tokens.shape[0],), F32)
    expert_layer = 0
    for lp, kind in zip(params["layers"], layer_kinds(cfg)):
        h = _rms_norm(x, lp["attn_norm"], eps)
        x = x + (_kda_mixer if kind == KDA else _mla_mixer)(h, lp, cfg)
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
            _hidden(params, t, cfg, None)[0] @ _mat(params["lm_head"])
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
