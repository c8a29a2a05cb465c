"""LFM2's mixture-of-experts decoder (``model_type`` ``lfm2_moe``) for
the serving plane: gated short-convolution layers that keep the last two
rows of their convolution's input a lane and no keys, between
grouped-query attention layers whose heads are 64 wide, over 64
sigmoid-routed experts held whole.

Published description: ``LiquidAI/LFM2-24B-A2B`` ``config.json``
(``layer_types``: ``conv conv full_attention conv``, ten times).  Every
block is ``x += Op(RMSNorm(x))``, ``x += FF(RMSNorm(x))``; ``h`` is an
operator's normalised input at one position.  What the config has no
key for is marked *assumed* (the benchmark's configuration file lists
the same under ``assumed``; the forms follow the public modelling code,
from memory).

**Conv layer** (``D`` = ``hidden_size`` channels, ``K`` = ``conv_L_cache``
taps)::

    [B | C | X] = W_in h           (D -> 3 D, split in this order,
                                    no bias: *assumed*)
    u   = B * X                    (elementwise)
    c_t = sum_j w_j * u_(t-K+1+j)  (depthwise over the D channels,
                                    causal, w_(K-1) on the current
                                    token, zeros before the sequence,
                                    ``conv_bias`` false, NO activation)
    x  += W_out (C * c)

  A lane keeps, a conv layer, the tail ``[u_(t-K+1) .. u_(t-1)]`` —
  ``K - 1`` = 2 rows of ``D`` — and nothing else: the tail IS the state.

**Attention layer**: ``q = W_q h`` (heads x 64), ``k = W_k h``, ``v = W_v
h`` (kv heads x 64), no biases; RMSNorm over the 64 of every head of
``q`` and of ``k`` (one weight of 64 each) BEFORE the rotation; RoPE on
all 64 dims, split-half pairs, ``rope_theta``; causal softmax at scale
``64 ** -0.5``, query head ``i`` reads KV head ``i // group``; ``x += W_o
[heads]``.  The head size is ``hidden_size / num_attention_heads``
(*assumed*: the config has no ``head_dim``).

**FF.**  Layers ``< num_dense_layers``: SwiGLU of ``intermediate_size``.
The others: ``s = sigmoid(W_r h)`` in float32 over ``num_experts``; the
``num_experts_per_tok`` of largest ``s + b`` (``use_expert_bias``: ``b``
float32, enters the selection only; equal scores lowest id first); ``w_e
= s_e / (sum of the taken s + 1e-6) * routed_scaling_factor``
(``norm_topk_prob``); ``FF = sum_e w_e Expert_e(h)``, each a SwiGLU of
``moe_intermediate_size``; no shared expert, nothing dropped — the
router in the form of ``models/trinity._route``, imported.  Final
RMSNorm; the head is the embedding transposed (*assumed*: the family
ties them).

**The cache.**  ``lane_state()`` declares the tail, ``layer_keeps()``
which layers keep it and which keep pages
(``rl/kv_cache.paged_cache_config``), and ``kv_row_heads`` that a row of
the page pool holds TWO KV heads side by side: ``k``, ``v`` ``[attention
layers, blocks, block_size * KV / 2, 128]`` (``flat_pages``, as
``models/olmo_hybrid.py``'s) — the same bytes in the same order as
``[.., block_size, KV, 64]``, with the chip's 128 lanes as the minor axis
(a minor axis of 64 is padded to 128 or laid out otherwise, and the
paged kernels are built for rows of 128).  The paged kernels see a model
of ``KV / 2`` KV heads of 128: a query head lies in its own half of a
128-wide row, zeros in the other (``ops/paged_attention.row_queries``),
so its scores are its own head's, and of the 128-wide sum over the
values it keeps its own half (``row_outputs``).  The write of a token
is the plain one (a token's ``[KV, 64]`` IS ``[KV / 2, 128]``), and no
step program copies or relays out the pool.  ``conv`` is ``[conv layers,
lanes, 2 x D]`` float32, each addressed by the layer's rank among its
kind.

The layers differ, so they are unrolled, each with its own leaves.  There
is no training path.
"""

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.models.keye_vl2 import (
    _embed,
    _head_norm,
    _proj,
    _rope_tables,
)
from dlrover_tpu.models.llama import _apply_rope_rows, apply_rope, rms_norm
from dlrover_tpu.models.olmo_hybrid import (
    _causal_conv,
    _conv_step,
    _key_view,
    _Pages,
)
from dlrover_tpu.models.trinity import _route, _stack_experts, _swiglu
from dlrover_tpu.ops.grouped_gemm import expert_ffn
from dlrover_tpu.rl.kv_cache import ROW_LANES

CONV, FULL = "conv", "full_attention"
#: beside the sum of the taken scores (``norm_topk_prob``)
RENORM_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys that shape the decoder, under
    their own names; ``first_expert`` / ``held_experts`` say which of the
    ``num_experts`` this chip holds (all of them by default), and
    ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = (CONV, CONV, FULL, CONV) * 10
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_parameters: Any = (("rope_theta", 1e6), ("rope_type", "default"))
    first_expert: int = 0
    held_experts: Optional[int] = None
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
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", self.num_experts)
        rope = dict(self.rope_parameters)
        for ok, what in (
            (len(self.layer_types) == self.num_hidden_layers,
             f"layer_types of {len(self.layer_types)} entries for "
             f"{self.num_hidden_layers} layers"),
            (set(self.layer_types) <= {CONV, FULL},
             f"a layer type other than {CONV} / {FULL}"),
            (CONV in self.layer_types and FULL in self.layer_types,
             "a model of one kind of layer"),
            (rope.get("rope_type", "default") == "default"
             and rope.get("rope_theta"),
             "rope_parameters other than a plain rope_theta"),
            (not self.conv_bias, "conv_bias true"),
            (self.conv_L_cache >= 2, "a convolution of one tap (no tail)"),
            (self.norm_topk_prob, "norm_topk_prob false"),
            (self.use_expert_bias, "use_expert_bias false"),
            (self.hidden_size % self.num_attention_heads == 0
             and self.num_attention_heads % self.num_key_value_heads == 0,
             "heads that do not divide hidden_size, or KV heads that do "
             "not divide the heads"),
            (0 <= self.first_expert
             and self.first_expert + self.held_experts <= self.num_experts,
             "held experts outside the router's"),
            (0 <= self.num_dense_layers < self.num_hidden_layers,
             "no expert layer"),
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
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def rope_theta(self) -> float:
        return float(dict(self.rope_parameters)["rope_theta"])

    # the names ``models/trinity._route`` reads
    @property
    def rms_norm_eps(self) -> float:
        return self.norm_eps

    @property
    def route_scale(self) -> float:
        return self.routed_scaling_factor

    @property
    def kv_row_heads(self) -> int:
        """To ``rl/kv_cache.paged_cache_config``: KV heads side by side
        in one row of the page pool — as many 64-wide heads as the
        chip's 128 lanes hold (2), and no more than there are (a tiny
        test's heads are narrower still; its rows hold them all)."""
        return max(1, min(self.num_key_value_heads,
                          ROW_LANES // self.head_dim))

    #: a block's K (or V) lies ``[block_size * KV / r, r * hd]`` in the
    #: pool (``rl/kv_cache.init_block_pool``): the step programs below
    #: write whole blocks of that form (``models/olmo_hybrid._Pages``) —
    #: with 4 rows a token, as with Olmo-Hybrid's 30, a scatter of a
    #: chunk's rows into ``[block_size, 4, 128]`` makes the compiler lay
    #: the pool out tokens-minor and copy it whole
    flat_pages = True

    def layer_keeps(self) -> Tuple[str, ...]:
        """What each layer keeps (``rl/kv_cache.paged_cache_config``): a
        conv layer the tail and no keys, an attention layer pages and no
        state."""
        return tuple(
            "state" if kind == CONV else "pages" for kind in self.layer_types
        )

    def lane_state(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per CONV layer and lane: the tail — the last ``taps - 1``
        inputs ``u = B * X`` of the depthwise convolution, oldest first,
        side by side in ONE axis (``models/olmo_hybrid.py`` says why: as
        ``[2, D]`` the chip would pad the 2 rows to a tile of 8),
        float32 (``u`` is a product of two float32 projections; rounded
        to bfloat16 it would differ from the ``u`` the same token's
        convolution took a step earlier)."""
        return {
            "conv": (
                ((self.conv_L_cache - 1) * self.hidden_size,), jnp.float32
            ),
        }

    def per_token_outputs(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        """What a step program returns for every row it computes, beside
        the logits: the experts chosen, ids among ALL of the router's,
        for the expert layers."""
        return {
            "experts": (
                (self.n_expert_layers, self.num_experts_per_tok), "int32"
            ),
        }

    @staticmethod
    def tiny(**overrides) -> "Lfm2MoeConfig":
        """Test-sized: both dense layers and one period beyond them
        (every kind of layer under every kind of FF), two KV heads a
        row."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=5,
            layer_types=(CONV, CONV, FULL, CONV, FULL),
            num_attention_heads=4, num_key_value_heads=2,
            num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
            max_seq_len=128,
        )
        base.update(overrides)
        return Lfm2MoeConfig(**base)


# ---------------------------------------------------------------- params

_FULL_IN = ("wq", "wk", "wv")
# of the serving copy, which holds an attention layer's input projections
# fused; the router and its bias stay float32 (they decide a discrete
# choice), and so do the norms and the taps
_SERVING_MATMUL_LEAVES = (
    "w_in", "w_out", "wqkv", "wo", "mlp_gate", "mlp_up", "mlp_down",
    "w_gate", "w_up", "w_down",
)


def layer_shapes(cfg: Lfm2MoeConfig, layer: int) -> Dict:
    """``{name: shape}`` of ONE layer's leaves.  ``w_in``'s columns are
    ``[B | C | X]``; ``conv_w[j]`` multiplies the input ``taps - 1 - j``
    tokens back (``j = taps - 1`` is the current token: the published
    ``conv.weight[:, 0, j]``)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    out = {"op_norm": (d,), "mlp_norm": (d,)}
    if cfg.layer_types[layer] == CONV:
        out.update(
            w_in=(d, 3 * d), conv_w=(cfg.conv_L_cache, d), w_out=(d, d)
        )
    else:
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        out.update(
            wq=(d, nh * hd), wk=(d, nkv * hd), wv=(d, nkv * hd),
            wo=(nh * hd, d), q_norm=(hd,), k_norm=(hd,),
        )
    if layer < cfg.num_dense_layers:
        f = cfg.intermediate_size
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, cfg.held_experts
        out.update(
            router=(d, cfg.num_experts), router_bias=(cfg.num_experts,),
            w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d),
        )
    return out


def param_shapes(cfg: Lfm2MoeConfig) -> Dict:
    """No ``lm_head``: the head is ``embed`` transposed."""
    return {
        "embed": (cfg.vocab_size, cfg.hidden_size),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg.num_hidden_layers)
        ),
        "final_norm": (cfg.hidden_size,),
    }


def init_params(key, cfg: Lfm2MoeConfig) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, the
    embedding ``normal(0, hidden ** -0.5)`` (it is the head as well: its
    logits then have the scale of any other projection), norm weights 1,
    conv taps ``normal(0, taps ** -0.5)``, the selection bias ``0.1
    normal``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple) and (
            not x or isinstance(x[0], int)
        )
    )
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name == "router_bias":
            leaf = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif "norm" in name:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-1] if name == "embed" else (
                shape[0] if name == "conv_w" else shape[-2]
            )
            leaf = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@jax.jit
def _cast_and_fuse(work, dtype_of):
    dt = dtype_of.dtype

    def layer(lp):
        out = {n: w.astype(dt) for n, w in lp.items() if n not in _FULL_IN}
        if all(n in lp for n in _FULL_IN):
            out["wqkv"] = jnp.concatenate(
                [lp[n].astype(dt) for n in _FULL_IN], axis=-1
            )
        return out

    return {
        **{n: w.astype(dt) for n, w in work.items() if n != "layers"},
        "layers": tuple(layer(lp) for lp in work["layers"]),
    }


def serving_params(params: Dict, cfg: Lfm2MoeConfig) -> Dict:
    """The tree the serving programs compute on: the embedding (which is
    the head) and every matrix in ``cfg.dtype``; an attention layer's
    ``wq``, ``wk``, ``wv`` as ONE leaf ``wqkv`` (one matmul a layer reads
    it in place; the parts are not in the returned tree); router, bias,
    norms and conv taps as given.  One jitted program over the leaves
    that need either; a leaf that needs neither stays the caller's
    array, and a tree that is already a serving copy comes back as it
    is."""
    dt = jnp.dtype(cfg.dtype)

    def todo(lp):
        names = [
            n for n in lp
            if n in _SERVING_MATMUL_LEAVES and lp[n].dtype != dt
        ]
        return names + (list(_FULL_IN) if "wq" in lp else [])

    work = {n: params[n] for n in ("embed",) if params[n].dtype != dt}
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


def _conv_inputs(h, lp, cfg: Lfm2MoeConfig):
    """``h [..., D]`` (normalised) -> float32 ``u = B * X`` (the
    convolution's input) and the output gate ``C``, ``[..., D]`` each."""
    p = jnp.matmul(
        h, lp["w_in"].astype(cfg.dtype), preferred_element_type=jnp.float32
    )
    b, c, x = jnp.split(p, 3, axis=-1)
    return b * x, c


def _conv_output(x, c, conv, lp, cfg: Lfm2MoeConfig):
    """``x + W_out (C * conv)``; both float32 ``[..., D]``."""
    return x + _proj((c * conv).astype(cfg.dtype), lp["w_out"], cfg.dtype)


def _attn_inputs(h, lp, cfg: Lfm2MoeConfig):
    """``h [..., D]`` -> q ``[..., H, hd]`` and k ``[..., KV, hd]``
    (head-normalised, before the rotation) and v ``[..., KV, hd]``, in
    the compute dtype."""
    dt = cfg.dtype
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    if "wqkv" in lp:
        q, k, v = jnp.split(
            _proj(h, lp["wqkv"], dt), (nh * hd, (nh + nkv) * hd), axis=-1
        )
    else:
        q, k, v = (_proj(h, lp[n], dt) for n in _FULL_IN)
    lead = h.shape[:-1]
    return (
        _head_norm(q.reshape(lead + (nh, hd)), lp["q_norm"], cfg.norm_eps),
        _head_norm(k.reshape(lead + (nkv, hd)), lp["k_norm"], cfg.norm_eps),
        v.reshape(lead + (nkv, hd)),
    )


def _ff(x, lp, cfg: Lfm2MoeConfig, backend: str = "jnp"):
    """``x [N, D]`` -> (``x + FF(RMSNorm(x))``, the experts chosen ``[N,
    k]`` or None for a dense layer)."""
    dt = cfg.dtype
    if "router" not in lp:
        h = rms_norm(x[None], lp["mlp_norm"], cfg.norm_eps)[0]
        return x + _swiglu(
            h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], dt
        ), None
    h, ids, w = _route(x, lp, cfg, renorm_eps=RENORM_EPS)
    y = expert_ffn(
        h, ids, w, lp["w_gate"].astype(dt), lp["w_up"].astype(dt),
        lp["w_down"].astype(dt), 0, cfg.num_experts, backend,
        first_expert=cfg.first_expert, held=cfg.held_experts,
    ).astype(dt)
    return x + y, ids


@jax.named_scope("head")
def _logits(x, params, cfg: Lfm2MoeConfig):
    """``[B, S, D]`` -> float32 logits over the vocabulary: the final
    norm, then the embedding read as the head where it lies (its rows
    are the head's columns: a contraction over both minor axes, no
    transposed copy)."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def _kind_scope(kind: str):
    """The device scope of a layer's kind, entered INSIDE ``attn``
    (``observability/events.py`` ``DEVICE_SCOPES``)."""
    if kind == CONV:
        return jax.named_scope("conv")
    return jax.named_scope("full")


def _ranks(cfg: Lfm2MoeConfig):
    """Each layer's rank among its kind: where its slab or its blocks
    lie in the pool."""
    seen = {CONV: 0, FULL: 0}
    out = []
    for kind in cfg.layer_types:
        out.append(seen[kind])
        seen[kind] += 1
    return out


def _row_scale(cfg: Lfm2MoeConfig) -> float:
    """What a query is multiplied with (inside its rotation, in float32:
    no rounding of its own) so that the paged kernels' ``row width **
    -0.5`` is the model's ``head_dim ** -0.5``."""
    return float(cfg.kv_row_heads) ** 0.5


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: Lfm2MoeConfig,
            return_experts: bool = False):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole sequence
    at once, no cache, the convolution from a zero tail
    (``return_experts``: and the experts chosen, ``[B, T, expert layers,
    k]``).  For tests and as the serving worker's ``forward_fn``; dense
    in ``T x T``."""
    from dlrover_tpu.ops.paged_attention import NEG_INF

    dt = cfg.dtype
    bsz, t = tokens.shape
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(t)
    cos, sin = _rope_tables(cfg.rope_theta, hd, positions)
    causal = positions[None] <= positions[:, None]
    chosen = []
    for lp, kind in zip(params["layers"], cfg.layer_types):
        h = rms_norm(x, lp["op_norm"], cfg.norm_eps)
        if kind == CONV:
            u, c = _conv_inputs(h, lp, cfg)
            window = jnp.pad(u, ((0, 0), (cfg.conv_L_cache - 1, 0), (0, 0)))
            x = _conv_output(
                x, c, _causal_conv(window, lp["conv_w"], act=None), lp, cfg
            )
        else:
            q, k, v = _attn_inputs(h, lp, cfg)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            att = jnp.einsum(
                "btkgd,bskd->bkgts", q.reshape(bsz, t, nkv, nh // nkv, hd),
                k, preferred_element_type=jnp.float32,
            ) * hd ** -0.5
            att = jax.nn.softmax(jnp.where(causal, att, NEG_INF), -1)
            out = jnp.einsum(
                "bkgts,bskd->btkgd", att.astype(dt), v,
                preferred_element_type=jnp.float32,
            ).astype(dt)
            x = x + _proj(out.reshape(bsz, t, nh * hd), lp["wo"], dt)
        y, ids = _ff(x.reshape(bsz * t, -1), lp, cfg)
        x = y.reshape(x.shape)
        chosen.append(ids)
    logits = _logits(x, params, cfg)
    if return_experts:
        return logits, _stack_experts(chosen, cfg).reshape(
            bsz, t, cfg.n_expert_layers, -1
        )
    return logits


# ------------------------------------------------------- serving programs


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # k, v [La, blocks, bs * KV / r, r * hd]; conv [Lc, lanes, ..]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    lane: jnp.ndarray,  # scalar int32: the lane whose tail this is
    real: jnp.ndarray,  # scalar int32: tokens of the chunk that are real
    cfg: Lfm2MoeConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Prefill ``real`` prompt positions of ONE sequence: the attention
    layers' K/V into its paged blocks, the conv layers' tail into its
    lane's slab.  The tail starts from zero at ``start_pos == 0`` and
    from the lane's slab otherwise (the chunk before left it there,
    whatever other lanes did in between), and comes out as the inputs of
    the last ``taps - 1`` REAL tokens, whatever the chunk's real length
    (one token reaches back into the old tail); the padded rows' K/V are
    not written.  Returns (logits [1, C, vocab], pool, {"experts": [C,
    expert layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        paged_chunk_attention,
        paged_kernel_backend,
        row_outputs,
        row_queries,
    )

    dt, d, taps, r = cfg.dtype, cfg.hidden_size, cfg.conv_L_cache, (
        cfg.kv_row_heads
    )
    nkv = cfg.num_key_value_heads
    c = tokens.shape[1]
    pages = _Pages(pool, nkv // r)
    backend = paged_kernel_backend()
    positions = start_pos + jnp.arange(c)
    fresh = start_pos == 0
    x = _embed(params, tokens, cfg)  # [1, C, D]
    with jax.named_scope("attn"):
        cos, sin = _rope_tables(cfg.rope_theta, cfg.head_dim, positions)
        view = _key_view(block_table, pages.block_size)
    conv_all = pool["conv"]
    chosen = []
    for lp, kind, j in zip(params["layers"], cfg.layer_types, _ranks(cfg)):
        with jax.named_scope("attn"), _kind_scope(kind):
            h = rms_norm(x, lp["op_norm"], cfg.norm_eps)
            if kind == CONV:
                u, gate = _conv_inputs(h[0], lp, cfg)
                # the lane's tail is read where it lies, as it is
                # written back: one slice of the stacked pool
                tail = jnp.where(
                    fresh, 0.0,
                    lax.dynamic_slice(
                        conv_all, (j, lane, 0), (1, 1, (taps - 1) * d)
                    ),
                ).reshape(taps - 1, d)
                window = jnp.concatenate([tail, u], axis=0)
                conv = _causal_conv(window, lp["conv_w"], act=None)
                # the inputs of the last K-1 REAL tokens (reaching back
                # into the old tail where the chunk holds fewer)
                conv_all = lax.dynamic_update_slice(
                    conv_all,
                    lax.dynamic_slice_in_dim(
                        window, real, taps - 1, 0
                    ).reshape(1, 1, -1),
                    (j, lane, 0),
                )
                x = _conv_output(x, gate[None], conv[None], lp, cfg)
            else:
                q, k, v = _attn_inputs(h, lp, cfg)
                s = _row_scale(cfg)
                q, k = apply_rope(q, cos * s, sin * s), apply_rope(k, cos, sin)
                # a token's [KV, hd] IS its [KV / r, r * hd] rows
                pages.write_run(
                    j, k[0].reshape(c, nkv // r, -1),
                    v[0].reshape(c, nkv // r, -1), block_table, start_pos,
                    real,
                )
                attn = paged_chunk_attention(
                    row_queries(q[0], nkv, r), *pages.by_position(j, view),
                    start_pos, jnp.int32(0), None, backend,
                    name="paged_prefill_full",
                )
                x = x + _proj(
                    row_outputs(attn, nkv, r).reshape(1, c, -1), lp["wo"], dt
                )
        with jax.named_scope("mlp"):
            y, ids = _ff(x[0], lp, cfg, backend)
            x = y[None]
        chosen.append(ids)
    return (
        _logits(x, params, cfg), {**pages.stacked(), "conv": conv_all},
        {"experts": _stack_experts(chosen, cfg)},
    )


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # k, v [La, blocks, bs * KV / r, r * hd]; conv [Lc, lanes, ..]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: Lfm2MoeConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One continuous-batching decode step: every ACTIVE lane advances by
    one token — a conv layer's tail shifted by its ``u``, an attention
    layer's K/V written and every cached position read.  An inactive
    lane — free, or in the middle of its prefill — writes its K/V to the
    null block and comes out with its tail bitwise as it went in.
    Shapes depend on (lanes, pool geometry) only: compiled once.
    Returns (logits [B, vocab], pool, {"experts": [B, expert layers,
    k]})."""
    from dlrover_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_backend,
        row_outputs,
        row_queries,
    )

    dt, d, r = cfg.dtype, cfg.hidden_size, cfg.kv_row_heads
    nkv = cfg.num_key_value_heads
    n = tokens.shape[0]
    pages = _Pages(pool, nkv // r)
    bs, mb = pages.block_size, block_tables.shape[1]
    backend = paged_kernel_backend()
    x = _embed(params, tokens, cfg)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        cos, sin = _rope_tables(cfg.rope_theta, cfg.head_dim, positions)
        # a lane that does not decode, or runs past its table, writes
        # to the null block
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
    conv_all = pool["conv"]
    chosen = []
    for lp, kind, j in zip(params["layers"], cfg.layer_types, _ranks(cfg)):
        with jax.named_scope("attn"), _kind_scope(kind):
            h = rms_norm(x, lp["op_norm"], cfg.norm_eps)
            if kind == CONV:
                u, gate = _conv_inputs(h[:, 0], lp, cfg)
                tail = conv_all[j]  # [B, (K - 1) * D], oldest first
                window = jnp.concatenate([tail, u], axis=-1)
                conv = _conv_step(window, lp["conv_w"], act=None)
                conv_all = conv_all.at[j].set(
                    jnp.where(active[:, None], window[:, d:], tail)
                )
                x = _conv_output(x, gate[:, None], conv[:, None], lp, cfg)
            else:
                q, k, v = _attn_inputs(h, lp, cfg)
                s = _row_scale(cfg)
                q = _apply_rope_rows(q, cos * s, sin * s)
                k = _apply_rope_rows(k, cos, sin)
                pages.write(
                    j, k[:, 0].reshape(n, nkv // r, -1),
                    v[:, 0].reshape(n, nkv // r, -1), blk, off,
                )
                attn = paged_decode_attention(
                    row_queries(q[:, 0], nkv, r), *pages.by_block(),
                    block_tables + j * pages.n_blocks, seq_lens, backend,
                    name="paged_full_decode",
                )
                x = x + _proj(
                    row_outputs(attn, nkv, r).reshape(n, 1, -1), lp["wo"], dt
                )
        with jax.named_scope("mlp"):
            y, ids = _ff(x[:, 0], lp, cfg, backend)
            x = y[:, None]
        chosen.append(ids)
    return (
        _logits(x, params, cfg)[:, 0], {**pages.stacked(), "conv": conv_all},
        {"experts": _stack_experts(chosen, cfg)},
    )
