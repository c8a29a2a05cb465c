"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``) for the serving plane:
gated delta-rule (linear-attention) layers that keep a state and no
keys, between full-attention layers that keep keys and no state, over a
cache that knows which layer keeps what.

Published description: ``allenai/Olmo-Hybrid-7B`` ``config.json``
(``layer_types``: three ``linear_attention`` then one
``full_attention``, eight times; the ``linear_*`` keys as Qwen3-Next
names them).  ``h`` is a block's input at one position; what the config
has no key for is marked *assumed* (the benchmark's configuration file
says the same under ``assumed``).  The state and its recurrence are
float32.

**Linear layer** (a gated delta rule, FLA's ``GatedDeltaNet``; the form
of every line the keys do not fix *assumed*)::

    q~ = W_q h   k~ = W_k h   (heads x linear_key_head_dim)
    v~ = W_v h                (heads x linear_value_head_dim)
    [q~, k~, v~] <- SiLU(causal depthwise conv, linear_conv_kernel_dim
                         taps, no bias, over each channel)
    per head i:  q = q~_i / |q~_i|_2 * dk^-1/2   k = k~_i / |k~_i|_2
                 v = v~_i                                   (eps 1e-6)
    beta  = 2 * sigmoid(W_b h)_i     (the 2: linear_allow_neg_eigval)
    alpha = exp(-exp(A_log_i) * softplus((W_a h)_i + dt_bias_i))
    S [dk, dv]:  u = beta (v - alpha S^T k)
                 S <- alpha S + k (x) u          o = S^T q
    y_i = RMSNorm_dv(o; weight) * SiLU((W_g h)_i)
    out = W_o [y_1 .. y_H]

  A lane keeps, a linear layer, ``S`` of every head and the conv tail
  (the last ``taps - 1`` pre-convolution rows).

**Full layer**: MHA; ``q = RMSNorm(W_q h)``, ``k = RMSNorm(W_k h)`` over
the WHOLE projection before the split into heads (*assumed*: the Olmo
2 / 3 family's q/k norm), causal softmax over every cached position at
scale ``head_dim ** -0.5``, ``W_o``.  No rotation: ``rope_parameters.
rope_theta`` is published ``null`` and read as it stands — positions
come from the recurrent layers (*assumed*; a theta is refused by name).

**Block, both kinds** (*assumed*: the family's norm placement, a norm
on each sublayer's OUTPUT and no pre-norm)::

    x <- x + RMSNorm(mixer(x))     x <- x + RMSNorm(MLP(x))
    MLP(x) = W_down(SiLU(W_gate x) * W_up x)

Final RMSNorm, untied head.

**The cache.**  ``lane_state()`` declares the conv tail and the state
a lane keeps, ``layer_keeps()`` which layers keep them and which keep
pages (``rl/kv_cache.paged_cache_config``): the pool holds ``k``, ``v``
``[full layers, blocks, ...]`` and ``conv``, ``gdn`` ``[linear layers,
lanes, ...]``, each addressed by the layer's rank among its kind.  The
state lies as ``ops/gdn.state_shape`` packs it (heads side by side in
the minor axis, a multiple of 128 lanes), and a block's K (or V) as
``[block_size * KV, D]`` (``flat_pages``): 30 KV heads are no multiple
of the chip's sublane tile, ``[block_size, 30, D]`` would be padded to
32 in memory and copied whole into the view the paged kernels take of
it.  The layers differ, so they are UNROLLED, each with its own leaves
(``params["layers"]`` is a tuple of dicts: no stack is sliced), the
page pool rides through them flat and every slab is written in place at
a static rank.  A prefill chunk is ONE lane's: it reads that lane's
conv tail and state where they lie, one ``dynamic_slice`` of the
stacked pool at ``(rank, lane, ...)`` — the mirror of the
``dynamic_update_slice`` that writes them back; a ``pool[rank]`` first
would copy all 64 lanes' slabs of the layer, 141.6 MB for 2.2 — and
its WY systems are inverted by products (``ops/gdn``), so the chunk
program calls no routine of the compiler's library.  The decode step
takes the layer as its kernel's scalar-prefetch index.

There is no training path.
"""

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.models.keye_vl2 import _proj
from dlrover_tpu.models.llama import rms_norm
from dlrover_tpu.models.trinity import _key_view_blocks, _swiglu
from dlrover_tpu.ops.gdn import (
    gdn_chunk_scan,
    gdn_decode_update,
    pack_state,
    state_shape,
    unpack_state,
)

LINEAR, FULL = "linear_attention", "full_attention"
#: the sub-chunk of the prefill's WY form (``ops/gdn.gdn_chunk_scan``)
GDN_CHUNK = 64
#: under the square root of q's and k's L2 norm
L2_EPS = 1e-6


@dataclass(frozen=True)
class OlmoHybridConfig:
    """The published ``config.json`` keys that shape the model, under
    their own names; ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL) * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    rope_parameters: Any = (("rope_theta", None),)
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # the keywords ride through JSON: lists and dicts come back, and
        # a frozen dataclass must stay hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(
                self, "rope_parameters",
                tuple(sorted(self.rope_parameters.items())),
            )
        for ok, what in (
            (len(self.layer_types) == self.num_hidden_layers,
             f"layer_types of {len(self.layer_types)} entries for "
             f"{self.num_hidden_layers} layers"),
            (set(self.layer_types) <= {LINEAR, FULL},
             f"a layer type other than {LINEAR} / {FULL}"),
            (FULL in self.layer_types, "a model without a full layer"),
            (dict(self.rope_parameters).get("rope_theta") is None,
             "a rope_theta (the full layers rotate nothing)"),
            (self.linear_num_key_heads == self.linear_num_value_heads,
             "linear_num_value_heads != linear_num_key_heads"),
            (self.hidden_size % self.num_attention_heads == 0,
             "hidden_size not a multiple of num_attention_heads"),
        ):
            if not ok:
                raise ValueError(f"{what} is not modelled")

    # what the serving scheduler reads off a model config
    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    #: a block's K (or V) lies ``[block_size * KV, D]`` in the pool
    #: (``rl/kv_cache.init_block_pool``): the step programs below write
    #: and read that form
    flat_pages = True

    def layer_keeps(self) -> Tuple[str, ...]:
        """What each layer keeps (``rl/kv_cache.paged_cache_config``): a
        linear layer the lane state and no keys, a full layer pages and
        no state."""
        return tuple(
            "state" if kind == LINEAR else "pages"
            for kind in self.layer_types
        )

    def lane_state(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per LINEAR layer and lane: ``{leaf: (shape, dtype)}``.  The
        conv tail is the last ``taps - 1`` inputs of the depthwise
        convolution, oldest first, side by side in ONE axis (3 x 11520
        is 270 lane tiles with the lanes as sublanes; as ``[3, 11520]``
        the chip would pad the 3 rows to a tile of 8); the recurrent
        state is float32 — a head whose ``alpha`` is 0.999
        rounds away in bfloat16 what it should keep — and lies as
        ``ops/gdn.state_shape`` packs it (two heads of 192 side by
        side: 384 is 3 lane tiles, where ``[96, 192]`` would be padded
        to ``[96, 256]``)."""
        return {
            "conv": (
                ((self.linear_conv_kernel_dim - 1) * self.conv_dim,),
                jnp.float32,
            ),
            "gdn": (
                state_shape(
                    self.linear_num_value_heads, self.linear_key_head_dim,
                    self.linear_value_head_dim,
                ),
                jnp.float32,
            ),
        }

    @staticmethod
    def tiny(**overrides) -> "OlmoHybridConfig":
        """Test-sized: one period of two linear layers and a full one,
        head sizes that are not powers of two."""
        base = dict(
            vocab_size=256, hidden_size=72, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=3,
            num_key_value_heads=3, layer_types=(LINEAR, LINEAR, FULL),
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=12, linear_value_head_dim=24,
            max_seq_len=128,
        )
        base.update(overrides)
        return OlmoHybridConfig(**base)


# ---------------------------------------------------------------- params

_LINEAR_IN = ("wq", "wk", "wv", "wg", "wa", "wb")
_FULL_IN = ("wq", "wk", "wv")
# of the serving copy, which holds a layer's input projections fused
_SERVING_MATMUL_LEAVES = ("w_in", "wqkv", "wo", "w_gate", "w_up", "w_down")


def layer_shapes(cfg: OlmoHybridConfig, layer: int) -> Dict:
    """``{name: shape}`` of ONE layer's leaves.  ``conv_w[k]`` multiplies
    the input ``taps - 1 - k`` tokens back (``k = taps - 1`` is the
    current token: the published ``conv1d.weight[:, 0, k]``), over the
    channels ``[q | k | v]``."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    out = {
        "post_attn_norm": (d,), "post_mlp_norm": (d,),
        "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }
    if cfg.layer_types[layer] == LINEAR:
        h = cfg.linear_num_value_heads
        out.update(
            wq=(d, cfg.key_dim), wk=(d, cfg.key_dim), wv=(d, cfg.value_dim),
            wg=(d, cfg.value_dim), wa=(d, h), wb=(d, h),
            conv_w=(cfg.linear_conv_kernel_dim, cfg.conv_dim),
            A_log=(h,), dt_bias=(h,),
            gdn_norm=(cfg.linear_value_head_dim,),
            wo=(cfg.value_dim, d),
        )
    else:
        kv = cfg.num_key_value_heads * cfg.head_dim
        out.update(
            wq=(d, d), wk=(d, kv), wv=(d, kv), wo=(d, d),
            q_norm=(d,), k_norm=(kv,),
        )
    return out


def param_shapes(cfg: OlmoHybridConfig) -> Dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg.num_hidden_layers)
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def init_params(key, cfg: OlmoHybridConfig) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, the
    embedding ``normal(0, 1)`` (what a block adds behind its norm has
    that scale), norm weights 1, conv taps ``normal(0, taps ** -0.5)``,
    ``A = 1 .. heads`` and ``dt`` log-uniform in [1e-3, 1e-1] as the
    published Mamba-2 / GatedDeltaNet code initialises them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        )
    )
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if "norm" in name:
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif name == "dt_bias":
            dt = jnp.exp(
                jax.random.uniform(k, shape, jnp.float32)
                * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
            )
            leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        else:
            scale = 1.0 if name == "embed" else (
                shape[0] if name == "conv_w" else shape[-2]
            ) ** -0.5
            leaf = jax.random.normal(k, shape, jnp.float32) * scale
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _fused_name(lp) -> Tuple[str, Tuple[str, ...]]:
    """The fused input leaf of a layer's kind and what it is made of
    (only a linear layer has an output gate ``wg`` / ``w_in``)."""
    if "wg" in lp or "w_in" in lp:
        return "w_in", _LINEAR_IN
    return "wqkv", _FULL_IN


@jax.jit
def _cast_and_fuse(work, dtype_of):
    dt = dtype_of.dtype

    def layer(lp):
        fused, parts = _fused_name(lp)
        out = {n: w.astype(dt) for n, w in lp.items() if n not in parts}
        if all(n in lp for n in parts):
            out[fused] = jnp.concatenate(
                [lp[n].astype(dt) for n in parts], axis=-1
            )
        return out

    return {
        **{n: w.astype(dt) for n, w in work.items() if n != "layers"},
        "layers": tuple(layer(lp) for lp in work["layers"]),
    }


def serving_params(params: Dict, cfg: OlmoHybridConfig) -> Dict:
    """The tree the serving programs compute on: the embedding, the
    head and every matrix in ``cfg.dtype``; a linear layer's ``wq``,
    ``wk``, ``wv``, ``wg``, ``wa``, ``wb`` as ONE leaf ``w_in`` and a
    full layer's ``wq``, ``wk``, ``wv`` as ONE leaf ``wqkv`` (one matmul
    a layer reads it in place; the parts are not in the returned tree);
    norms, conv taps, ``A_log`` and ``dt_bias`` as given.  One jitted
    program over the leaves that need either; a leaf that needs neither
    stays the caller's array, and a tree that is already a serving copy
    comes back as it is."""
    dt = jnp.dtype(cfg.dtype)

    def todo(lp):
        fused, parts = _fused_name(lp)
        names = [
            n for n in lp
            if n in _SERVING_MATMUL_LEAVES and lp[n].dtype != dt
        ]
        return names + ([] if fused in lp else list(parts))

    work = {
        n: params[n] for n in ("embed", "lm_head") if params[n].dtype != dt
    }
    per_layer = [todo(lp) for lp in params["layers"]]
    if not work and not any(per_layer):
        return params
    work["layers"] = tuple(
        {n: lp[n] for n in names}
        for lp, names in zip(params["layers"], per_layer)
    )
    with kept_in_compile_cache():
        done = _cast_and_fuse(work, jnp.zeros((), dt))
    layers = tuple(
        {**{n: w for n, w in lp.items() if n not in names}, **new}
        for lp, names, new in zip(params["layers"], per_layer, done["layers"])
    )
    return {**params, **{n: done[n] for n in work if n != "layers"},
            "layers": layers}


# ---------------------------------------------------------------- pieces


def _linear_inputs(x, lp, cfg: OlmoHybridConfig):
    """``x [..., D]`` -> float32 raw ``qkv [..., conv_dim]``, gate
    logits ``[..., value_dim]``, ``a`` and ``b`` ``[..., heads]``."""
    dt = cfg.dtype
    if "w_in" in lp:
        p = jnp.matmul(
            x, lp["w_in"].astype(dt), preferred_element_type=jnp.float32
        )
        cuts = (cfg.conv_dim, cfg.conv_dim + cfg.value_dim)
        qkv, g, ab = jnp.split(p, cuts, axis=-1)
        a, b = jnp.split(ab, 2, axis=-1)
        return qkv, g, a, b

    def one(name):
        return jnp.matmul(
            x, lp[name].astype(dt), preferred_element_type=jnp.float32
        )

    return (
        jnp.concatenate([one("wq"), one("wk"), one("wv")], axis=-1),
        one("wg"), one("wa"), one("wb"),
    )


def _causal_conv(window, conv_w, act=jax.nn.silu):
    """``window [..., T + K - 1, C]`` (the tail before the run, then
    the run) -> ``act(conv) [..., T, C]``; no bias (``act`` None: the
    sum as it is, ``models/lfm2_moe.py``)."""
    k = conv_w.shape[0]
    t = window.shape[-2] - (k - 1)
    out = 0.0
    for j in range(k):
        out = out + conv_w[j] * lax.slice_in_dim(
            window, j, j + t, axis=window.ndim - 2
        )
    return out if act is None else act(out)


def _conv_step(window, conv_w, act=jax.nn.silu):
    """:func:`_causal_conv` for ONE token of every lane, the window's
    ``K`` rows side by side ``[B, K * C]`` (oldest first: the lane's
    tail, then the token) -> ``[B, C]``: every operand stays ``[B,
    C]``, whole lane tiles."""
    k, c = conv_w.shape
    out = 0.0
    for j in range(k):
        out = out + conv_w[j] * window[:, j * c:(j + 1) * c]
    return out if act is None else act(out)


def _l2_normed(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _qkv_heads(qkv, cfg: OlmoHybridConfig):
    """Convolved ``[..., conv_dim]`` -> ``q`` (L2-normalised, scaled),
    ``k`` (L2-normalised) ``[..., H, dk]`` and ``v [..., H, dv]``."""
    h, dk, dv = (
        cfg.linear_num_value_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim,
    )
    q, k, v = jnp.split(qkv, (cfg.key_dim, 2 * cfg.key_dim), axis=-1)
    lead = qkv.shape[:-1]
    return (
        _l2_normed(q.reshape(lead + (h, dk))) * dk ** -0.5,
        _l2_normed(k.reshape(lead + (h, dk))),
        v.reshape(lead + (h, dv)),
    )


def _gates(a, b, lp, cfg: OlmoHybridConfig):
    """Raw ``a``, ``b`` ``[..., H]`` -> the decay ``alpha`` in (0, 1)
    and the write strength ``beta`` in (0, 2)."""
    alpha = jnp.exp(
        -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    )
    beta = jax.nn.sigmoid(b)
    return alpha, 2.0 * beta if cfg.linear_allow_neg_eigval else beta


def _linear_output(x, o, g, lp, cfg: OlmoHybridConfig):
    """``x + RMSNorm(W_o [RMSNorm_dv(o_i) * SiLU(g_i)])``; ``o [..., H,
    dv]`` float32, ``g [..., H * dv]``."""
    o = o * lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps
    ) * lp["gdn_norm"]
    y = o.reshape(g.shape) * jax.nn.silu(g)
    out = _proj(y.astype(cfg.dtype), lp["wo"], cfg.dtype)
    return x + rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps)


def _full_inputs(x, lp, cfg: OlmoHybridConfig):
    """``x [A, B, D]`` -> q ``[A, B, H, hd]`` and k ``[A, B, KV, hd]``
    (normalised over the whole projection), v ``[A, B, KV, hd]``."""
    dt = cfg.dtype
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    if "wqkv" in lp:
        q, k, v = jnp.split(
            _proj(x, lp["wqkv"], dt), (nh * hd, (nh + nkv) * hd), axis=-1
        )
    else:
        q, k, v = (_proj(x, lp[n], dt) for n in _FULL_IN)
    lead = x.shape[:-1]
    return (
        rms_norm(q, lp["q_norm"], cfg.rms_norm_eps).reshape(lead + (nh, hd)),
        rms_norm(k, lp["k_norm"], cfg.rms_norm_eps).reshape(lead + (nkv, hd)),
        v.reshape(lead + (nkv, hd)),
    )


def _full_output(x, attn, lp, cfg: OlmoHybridConfig):
    out = _proj(attn, lp["wo"], cfg.dtype)
    return x + rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps)


def _mlp(x, lp, cfg: OlmoHybridConfig):
    y = _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.dtype)
    return x + rms_norm(y, lp["post_mlp_norm"], cfg.rms_norm_eps)


@jax.named_scope("head")
def _logits(x, params, cfg: OlmoHybridConfig):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


@jax.named_scope("embed")
def _embed(params, tokens, cfg: OlmoHybridConfig):
    return params["embed"].astype(cfg.dtype)[tokens]


def _kind_scope(kind: str):
    """The device scope of a layer's kind, entered INSIDE ``attn``
    (``observability/events.py`` ``DEVICE_SCOPES``)."""
    if kind == LINEAR:
        return jax.named_scope("linear")
    return jax.named_scope("full")


def _ranks(cfg: OlmoHybridConfig):
    """Each layer's rank among its kind: where its slab or its blocks
    lie in the pool."""
    seen = {LINEAR: 0, FULL: 0}
    out = []
    for kind in cfg.layer_types:
        out.append(seen[kind])
        seen[kind] += 1
    return out


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: OlmoHybridConfig):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, no cache, the recurrence as the chunked scan from
    a zero state.  For tests and as the serving worker's ``forward_fn``;
    dense in ``T x T``."""
    from dlrover_tpu.ops.paged_attention import NEG_INF

    dt = cfg.dtype
    bsz, t = tokens.shape
    h, dk, dv = (
        cfg.linear_num_value_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim,
    )
    taps = cfg.linear_conv_kernel_dim
    x = _embed(params, tokens, cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))
    for lp, kind in zip(params["layers"], cfg.layer_types):
        if kind == LINEAR:
            qkv, g, a, b = _linear_inputs(x, lp, cfg)
            window = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
            q, k, v = _qkv_heads(_causal_conv(window, lp["conv_w"]), cfg)
            alpha, beta = _gates(a, b, lp, cfg)
            o, _ = gdn_chunk_scan(
                q, k, v, alpha, beta,
                jnp.zeros((bsz, h, dk, dv), jnp.float32), GDN_CHUNK,
            )
            x = _linear_output(x, o, g, lp, cfg)
        else:
            q, k, v = _full_inputs(x, lp, cfg)
            nkv, hd = cfg.num_key_value_heads, cfg.head_dim
            att = jnp.einsum(
                "btkgd,bskd->bkgts",
                q.reshape(bsz, t, nkv, -1, hd), k,
                preferred_element_type=jnp.float32,
            ) * hd ** -0.5
            att = jax.nn.softmax(jnp.where(causal, att, NEG_INF), -1)
            out = jnp.einsum(
                "bkgts,bskd->btkgd", att.astype(dt), v,
                preferred_element_type=jnp.float32,
            ).astype(dt)
            x = _full_output(x, out.reshape(bsz, t, -1), lp, cfg)
        x = _mlp(x, lp, cfg)
    return _logits(x, params, cfg)


# ------------------------------------------------------- serving programs


class _Pages:
    """The full layers' pages of a step program: ``k``, ``v`` ``[full
    layers, blocks, block_size * KV, D]`` flat over the layers, a
    block's rows in the pool's own memory order (row ``t * KV + h`` is
    token ``t`` of KV head ``h``).  The ``j``-th full layer addresses
    block ``id`` at ``j * blocks + id``."""

    def __init__(self, pool: Dict, n_kv: int):
        self._shape = pool["k"].shape
        self.n_blocks, rows, self.head_dim = self._shape[1:]
        self.n_kv = n_kv  # the rows a token has in a block
        self.block_size = rows // self.n_kv
        self.k, self.v = (
            pool[n].reshape((-1,) + self._shape[2:]) for n in ("k", "v")
        )

    def write(self, j: int, k_new, v_new, block_ids, offsets):
        """One token a lane: K and V ``[B, KV, D]`` into cell
        ``offsets[b]`` of block ``block_ids[b]`` of full layer ``j``.
        The blocks are read, overlaid with the token's rows and written
        back WHOLE: a scatter of whole rows of the pool is one
        operation, where ``[KV, D]`` windows into a block's rows are a
        loop over the lanes (``ops/paged_attention.write_leaf_run``
        says the same of a paged leaf).  Lanes that do not decode meet
        in the null block."""
        ids = block_ids + j * self.n_blocks
        rows = self.block_size * self.n_kv
        token = lax.broadcasted_iota(jnp.int32, (1, rows), 1) // self.n_kv
        mine = (token == offsets[:, None])[..., None]  # [B, rows, 1]

        def put(pages, new):
            new = jnp.tile(new.astype(pages.dtype), (1, self.block_size, 1))
            return pages.at[ids].set(jnp.where(mine, new, pages[ids]))

        self.k, self.v = put(self.k, k_new), put(self.v, v_new)

    def write_run(self, j: int, k_rows, v_rows, table, start, real):
        """A prefill chunk: K and V ``[C, KV, D]`` of positions ``start
        .. start + C - 1``, of which the first ``real`` are written,
        into one sequence's blocks of full layer ``j`` — the blocks the
        run touches are read, overlaid and written back whole (``C /
        block_size + 1`` whole rows, not ``C`` windows).  Positions
        past the table go to the null block."""
        c, bs, mb = k_rows.shape[0], self.block_size, table.shape[0]
        at = start // bs + jnp.arange(-(-c // bs) + 1)  # table entries
        ids = jnp.where(
            at < mb, table[jnp.minimum(at, mb - 1)], 0
        ) + j * self.n_blocks
        rel = (at[:, None] * bs + jnp.arange(bs)[None]) - start
        mine = ((rel >= 0) & (rel < real))[..., None, None]
        rel = jnp.clip(rel, 0, c - 1)
        tile = (-1, bs, self.n_kv, self.head_dim)

        def put(pages, new):
            merged = jnp.where(
                mine, new.astype(pages.dtype)[rel], pages[ids].reshape(tile)
            )
            return pages.at[ids].set(merged.reshape((-1,) + pages.shape[1:]))

        self.k, self.v = put(self.k, k_rows), put(self.v, v_rows)

    def by_block(self):
        """``k``, ``v`` as ``[blocks, block_size, KV, D]``: the view the
        paged decode ops take (and turn back into this one: free)."""
        shape = (-1, self.block_size, self.n_kv, self.head_dim)
        return self.k.reshape(shape), self.v.reshape(shape)

    def by_position(self, j: int, table):
        """One sequence's K and V ``[KV, n * block_size, D]`` by
        position, from its ``table [n]`` over full layer ``j``'s
        blocks."""
        return tuple(
            jnp.moveaxis(
                pages[table + j * self.n_blocks].reshape(
                    -1, self.n_kv, self.head_dim
                ), 1, 0,
            )
            for pages in (self.k, self.v)
        )

    def stacked(self) -> Dict:
        return {
            "k": self.k.reshape(self._shape),
            "v": self.v.reshape(self._shape),
        }


def _key_view(table, block_size: int):
    """One sequence's table in position order, padded with the null
    block to the chunk kernel's key block where it is longer than one
    (``models/trinity._key_view_blocks``; the rows behind are above
    every query's causal reach)."""
    blocks = table.shape[0]
    return jnp.pad(table, (0, _key_view_blocks(blocks, block_size) - blocks))


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # k, v [Lf, blocks, bs, KV, D]; conv, gdn [Ll, lanes, ...]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    lane: jnp.ndarray,  # scalar int32: the lane whose state this is
    real: jnp.ndarray,  # scalar int32: tokens of the chunk that are real
    cfg: OlmoHybridConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """Prefill ``real`` prompt positions of ONE sequence: the full
    layers' K/V into its paged blocks, the linear layers' conv tail and
    recurrent state into its lane's slabs.  The state starts from zero
    at ``start_pos == 0`` and from the lane's slab otherwise (the chunk
    before left it there, whatever other lanes did in between); the
    padded tail advances neither the state nor the conv tail (``alpha
    == 1``, ``beta == 0`` there) and writes its K/V to the null block.
    Returns (logits [1, C, vocab], pool)."""
    from dlrover_tpu.ops.paged_attention import (
        paged_chunk_attention,
        paged_kernel_backend,
    )

    _, c = tokens.shape
    pages = _Pages(pool, cfg.num_key_value_heads)
    bs = pages.block_size
    heads, taps = cfg.linear_num_value_heads, cfg.linear_conv_kernel_dim
    backend = paged_kernel_backend()
    steps = jnp.arange(c)
    valid = steps < real
    fresh = start_pos == 0
    x = _embed(params, tokens, cfg)
    with jax.named_scope("attn"):
        view = _key_view(block_table, bs)
    conv_all, gdn_all = pool["conv"], pool["gdn"]
    for lp, kind, j in zip(params["layers"], cfg.layer_types, _ranks(cfg)):
        with jax.named_scope("attn"), _kind_scope(kind):
            if kind == LINEAR:
                qkv, g, a, b = _linear_inputs(x, lp, cfg)
                # the lane's slabs are read where they lie, as they are
                # written back: one slice of the stacked pool (a
                # ``conv_all[j]`` would copy every lane's)
                tail = jnp.where(
                    fresh, 0.0,
                    lax.dynamic_slice(
                        conv_all, (j, lane, 0), (1, 1) + conv_all.shape[2:]
                    ),
                ).reshape(taps - 1, cfg.conv_dim)
                window = jnp.concatenate([tail, qkv[0]], axis=0)
                q, k, v = _qkv_heads(_causal_conv(window, lp["conv_w"]), cfg)
                # the inputs of the last K-1 REAL tokens (reaching back
                # into the old tail where the chunk holds fewer)
                conv_all = lax.dynamic_update_slice(
                    conv_all,
                    lax.dynamic_slice_in_dim(
                        window, real, taps - 1, 0
                    ).reshape(1, 1, -1),
                    (j, lane, 0),
                )
                alpha, beta = _gates(a[0], b[0], lp, cfg)
                state = jnp.where(
                    fresh, 0.0,
                    unpack_state(
                        lax.dynamic_slice(
                            gdn_all, (j, lane, 0, 0, 0),
                            (1, 1) + gdn_all.shape[2:],
                        )[0, 0],
                        heads,
                    ),
                )
                with jax.named_scope("gdn_scan"):
                    o, state = gdn_chunk_scan(
                        q[None], k[None], v[None],
                        jnp.where(valid[:, None], alpha, 1.0)[None],
                        jnp.where(valid[:, None], beta, 0.0)[None],
                        state[None], GDN_CHUNK,
                    )
                gdn_all = lax.dynamic_update_slice(
                    gdn_all,
                    pack_state(state).astype(gdn_all.dtype)[None],
                    (j, lane, 0, 0, 0),
                )
                x = _linear_output(x, o, g, lp, cfg)
            else:
                q, k, v = _full_inputs(x, lp, cfg)
                pages.write_run(j, k[0], v[0], block_table, start_pos, real)
                attn = paged_chunk_attention(
                    q[0], *pages.by_position(j, view),
                    start_pos, jnp.int32(0), None, backend,
                    name="paged_prefill_full",
                )
                x = _full_output(x, attn.reshape(1, c, -1), lp, cfg)
        with jax.named_scope("mlp"):
            x = _mlp(x, lp, cfg)
    return _logits(x, params, cfg), {
        **pages.stacked(), "conv": conv_all, "gdn": gdn_all,
    }


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # k, v [Lf, blocks, bs, KV, D]; conv, gdn [Ll, lanes, ...]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: OlmoHybridConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching decode step: every ACTIVE lane advances
    by one token.  An inactive lane — free, or in the middle of its
    prefill — writes its K/V to the null block and comes out with its
    conv tail and its recurrent state bitwise as they went in.  Shapes
    depend on (lanes, pool geometry) only: compiled once."""
    from dlrover_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_backend,
    )

    n = tokens.shape[0]
    pages = _Pages(pool, cfg.num_key_value_heads)
    bs, mb = pages.block_size, block_tables.shape[1]
    backend = paged_kernel_backend()
    x = _embed(params, tokens, cfg)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        blk_idx = positions // bs
        blk = jnp.where(
            active & (blk_idx < mb),
            jnp.take_along_axis(
                block_tables, jnp.minimum(blk_idx, mb - 1)[:, None], axis=1
            )[:, 0],
            0,
        )
        off = jnp.where(active, positions % bs, 0)
        seq_lens = jnp.where(active, positions + 1, 1)
    conv_all, gdn_all = pool["conv"], pool["gdn"]
    for lp, kind, j in zip(params["layers"], cfg.layer_types, _ranks(cfg)):
        with jax.named_scope("attn"), _kind_scope(kind):
            if kind == LINEAR:
                qkv, g, a, b = _linear_inputs(x[:, 0], lp, cfg)
                conv = conv_all[j]  # [B, (K - 1) * Cd], oldest first
                window = jnp.concatenate([conv, qkv], axis=-1)
                q, k, v = _qkv_heads(_conv_step(window, lp["conv_w"]), cfg)
                conv_all = conv_all.at[j].set(
                    jnp.where(
                        active[:, None], window[:, cfg.conv_dim:], conv
                    )
                )
                alpha, beta = _gates(a, b, lp, cfg)
                o, gdn_all = gdn_decode_update(
                    gdn_all, jnp.int32(j), q, k, v, alpha, beta, active,
                    backend,
                )
                x = _linear_output(x, o[:, None], g[:, None], lp, cfg)
            else:
                q, k, v = _full_inputs(x, lp, cfg)
                pages.write(j, k[:, 0], v[:, 0], blk, off)
                attn = paged_decode_attention(
                    q[:, 0], *pages.by_block(),
                    block_tables + j * pages.n_blocks, seq_lens,
                    backend, name="paged_full_decode",
                )
                x = _full_output(x, attn.reshape(n, 1, -1), lp, cfg)
        with jax.named_scope("mlp"):
            x = _mlp(x, lp, cfg)
    return _logits(x, params, cfg)[:, 0], {
        **pages.stacked(), "conv": conv_all, "gdn": gdn_all,
    }
