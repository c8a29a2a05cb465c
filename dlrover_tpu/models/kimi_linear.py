"""Kimi Linear's decoder (``model_type`` ``kimi_linear``) for the
serving plane: Kimi Delta Attention (KDA) layers that keep a
per-channel-gated state a head and no keys, between NoPE latent-attention
(MLA) layers that keep ONE compressed row a token and no state, over one
chip's share of a layer's sigmoid-routed experts beside a shared one.

Published description: ``moonshotai/Kimi-Linear-48B-A3B-Instruct``
``config.json`` (``linear_attn_config``: ``kda_layers`` /
``full_attn_layers`` number the layers FROM 1; technical report
arXiv:2510.26692).  Every block is ``x += Mixer(RMSNorm(x))``, ``x +=
MLP(RMSNorm(x))``; ``h`` is a mixer's normalised input at one position.
What the config has no key for is marked *assumed* (the benchmark's
configuration file lists the same under ``assumed``; the forms follow
the public modelling code and FLA's ``KimiDeltaAttention``).

**KDA layer** (``H`` heads of ``dk = dv = head_dim``)::

    q~ = W_q h   k~ = W_k h   v~ = W_v h      (each H x head_dim)
    [q~, k~, v~] <- SiLU(causal depthwise conv, short_conv_kernel_size
                         taps, no bias, over each channel)   (*assumed*)
    per head i:  q = q~_i / |q~_i|_2 * dk^-1/2   k = k~_i / |k~_i|_2
                 v = v~_i                                   (eps 1e-6)
    a_i  = exp(-exp(A_log_i) * softplus((W_f2 W_f1 h)_i + dt_bias_i))
           a VECTOR of dk a head, in (0, 1)   (W_f1: D -> head_dim,
           W_f2: head_dim -> H x dk, no biases: *assumed*)
    beta = sigmoid(W_b h)_i                  (not doubled: *assumed*)
    S [dk, dv]:  S' = diag(a) S;  u = beta (v - S'^T k)
                 S <- S' + k (x) u          o = S^T q
    y_i = RMSNorm_dv(o; one weight of dv) * sigmoid((W_g2 W_g1 h)_i)
    x  += W_o [y_1 .. y_H]

  A lane keeps, a KDA layer, ``S`` of every head (float32) and the conv
  tail (the last ``taps - 1`` pre-convolution rows of ``[q~, k~, v~]``).

**MLA layer** (``q_lora_rank`` null: a full-rank query)::

    q = W_q h, a head [q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]
    W_kva h -> [c (kv_lora_rank), k_pe (qk_rope_head_dim)]
    c_kv = RMSNorm(c);  THE CACHED ROW IS [c_kv, k_pe]
    W_kvb c_kv -> a head [k_nope, v];  k = [k_nope, k_pe]
    o[t] = sum over every s <= t of
           softmax_s((nope + rope)^-1/2 q[t] . k[s]) v[s];  x += W_o o

  NOTHING is rotated (``mla_use_nope``): positions come from the KDA
  layers, ``rope_theta`` is inert and the ``pe`` dims are 64 more
  un-rotated dims shared by the heads (*assumed*).

**MLP.**  Layers ``< first_k_dense_replace``: SwiGLU of
``intermediate_size``.  The others, ``h' = RMSNorm(x)``: ``s =
sigmoid(W_r h')`` in float32 over ``num_experts``; the
``num_experts_per_token`` of largest ``s + b`` (``b`` enters the
selection only; ``num_expert_group`` = ``topk_group`` = 1: no group
limit; equal scores lowest id first); ``w_e = s_e / sum s *
routed_scaling_factor``; ``x += Shared(h') + sum_e w_e Expert_e(h')`` —
``models/deepseek_v32._mlp`` at one group, imported.  Final RMSNorm,
untied head.

**The share.**  ``held_experts`` of the ``num_experts`` live here
(``first_expert ..``), as in ``models/trinity.py``: the router keeps its
width and its top-k, the layer computes the assignments that fall on its
own experts and what the absent experts would add is left out.

**The cache** — the first model that keeps BOTH kinds without keys:
``pages_kv = False`` beside ``lane_state()`` with ``layer_keeps()``
(``rl/kv_cache.paged_cache_config``).  The pool holds ``c [MLA layers,
blocks, block_size, kv_lora_rank]`` and ``kpe [MLA layers, blocks,
block_size * rope / 128, 128]`` (``paged_leaf_rows()``, as
``models/deepseek_v32.py`` lays them), and ``conv [KDA layers, lanes, 3 x
conv_dim]``, ``kda [KDA layers, lanes, H, dk, dv]`` float32, each
addressed by the layer's rank among its kind.

**Two forms of one attention**, as ``models/deepseek_v32.py``: decode is
ABSORBED (``q_nope W_uk`` against the cached row itself, every held row
read: ``ops/paged_kernels.mla_stream_decode_kernel`` under the
selection "all rows"), a prefill chunk DECOMPRESSES the rows it may see
and attends in multi-head form under the causal mask
(``ops/paged_kernels.mla_prefill_kernel``).

The layers differ, so they are unrolled; what the step programs' loops
call for a layer are ``jax.jit``-wrapped pieces made inside the
program's own call (``models/deepseek_v32.py``'s docstring says why):
one for a KDA layer's mixer, one for an MLA layer's, one ``mlp`` that JAX
traces once for the dense layer and once for the expert layers.  The
layer's rank is an ARGUMENT, so a program traces and lowers one block a
kind, not twelve layers.  There is no training path.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.models.deepseek_v32 import (
    _kv_up,
    _mlp,
    _mlp_leaves,
    _per_head,
)
from dlrover_tpu.models.keye_vl2 import (
    _embed,
    _logits,
    _prefill_widths,
    _proj,
)
from dlrover_tpu.models.llama import rms_norm
from dlrover_tpu.models.olmo_hybrid import (
    _causal_conv,
    _conv_step,
    _qkv_heads,
)
from dlrover_tpu.models.trinity import _stack_experts
from dlrover_tpu.ops.kda import kda_chunk_scan, kda_decode_update

KDA, MLA = "kda", "mla"
#: the sub-chunk of the prefill's WY form (``ops/kda.kda_chunk_scan``)
KDA_CHUNK = 64


@dataclass(frozen=True)
class KimiLinearConfig:
    """The published ``config.json`` keys that shape the decoder, under
    their own names (``linear_attn_config``'s flattened: ``kda_layers``,
    ``full_attn_layers`` — numbered from 1 as published —,
    ``linear_num_heads``, ``linear_head_dim``,
    ``short_conv_kernel_size``); ``first_expert`` / ``held_experts`` say
    which of the ``num_experts`` this chip holds (all of them by
    default), and ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    num_expert_group: int = 1
    topk_group: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    q_lora_rank: Any = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_scaling: Any = None
    kda_layers: Tuple[int, ...] = tuple(
        i for i in range(1, 27) if i % 4
    )
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    rms_norm_eps: float = 1e-5
    first_expert: int = 0
    held_experts: Optional[int] = None
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    #: to ``rl/kv_cache.paged_cache_config``: a token keeps no per-head
    #: keys and values, the pool pages the ``paged_leaves()`` alone
    pages_kv = False

    def __post_init__(self):
        # the keywords ride through JSON: lists come back, and a frozen
        # dataclass must stay hashable
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", self.num_experts)
        layers = sorted(self.kda_layers + self.full_attn_layers)
        for ok, what in (
            (layers == list(range(1, self.num_hidden_layers + 1)),
             "kda_layers and full_attn_layers that do not name each of "
             f"the layers 1 .. {self.num_hidden_layers} once"),
            (bool(self.kda_layers) and bool(self.full_attn_layers),
             "a model of one kind of layer"),
            (self.moe_router_activation_func == "sigmoid",
             "moe_router_activation_func other than sigmoid"),
            (self.moe_renormalize, "moe_renormalize false"),
            (self.num_expert_group == 1 and self.topk_group == 1,
             "a group limit (num_expert_group / topk_group other than 1)"),
            (self.q_lora_rank is None, "a q_lora_rank (the query is full "
             "rank)"),
            (self.mla_use_nope and self.rope_scaling is None,
             "rotated latent attention (mla_use_nope false, or a "
             "rope_scaling)"),
            (0 <= self.first_expert
             and self.first_expert + self.held_experts <= self.num_experts,
             "held experts outside the router's"),
            (0 <= self.first_k_dense_replace < self.num_hidden_layers,
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
        """The latent row is every head's key and value: one."""
        return 1

    @property
    def head_dim(self) -> int:
        """The width of that one row."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def topk(self) -> int:
        """The rows a decode query reads at most: there is no indexer,
        every held row is read (the scheduler's ``sel_rows`` label is
        then the rows read, ``cached_rows`` the same)."""
        return self.max_seq_len

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``KDA`` / ``MLA`` a layer, in the program's order (layer 0 is
        the published layer 1)."""
        return tuple(
            KDA if i + 1 in self.kda_layers else MLA
            for i in range(self.num_hidden_layers)
        )

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    # the names ``models/olmo_hybrid._qkv_heads`` reads
    @property
    def linear_num_value_heads(self) -> int:
        return self.linear_num_heads

    @property
    def linear_key_head_dim(self) -> int:
        return self.linear_head_dim

    @property
    def linear_value_head_dim(self) -> int:
        return self.linear_head_dim

    @property
    def key_dim(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.key_dim

    # the names ``models/deepseek_v32._mlp`` / ``_route`` read
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    @property
    def n_group(self) -> int:
        return self.num_expert_group

    def layer_keeps(self) -> Tuple[str, ...]:
        """What each layer keeps (``rl/kv_cache.paged_cache_config``): a
        KDA layer the lane state and no row, an MLA layer the latent
        row's pages and no state."""
        return tuple(
            "state" if kind == KDA else "pages" for kind in self.layer_kinds
        )

    def lane_state(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per KDA layer and lane: the conv tail — the last ``taps - 1``
        inputs of the three depthwise convolutions, oldest first, side
        by side in ONE axis (``models/olmo_hybrid.py`` says why) — and
        the recurrent state, float32 (a channel whose decay is 0.9999
        rounds away in bfloat16 what it should keep); at ``dv`` 128 a
        head's ``[dk, dv]`` is whole lane tiles and lies unpacked."""
        h, d = self.linear_num_heads, self.linear_head_dim
        return {
            "conv": (
                ((self.short_conv_kernel_size - 1) * self.conv_dim,),
                jnp.float32,
            ),
            "kda": ((h, d, d), jnp.float32),
        }

    def paged_leaves(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per MLA layer and TOKEN: the cached row's two parts, in the
        compute dtype."""
        return {
            "c": ((self.kv_lora_rank,), self.dtype),
            "kpe": ((self.qk_rope_head_dim,), self.dtype),
        }

    def paged_leaf_rows(self) -> Dict[str, int]:
        """Both leaves are read row by row (``models/deepseek_v32.py``):
        a latent a row, the shared keys in rows of the device's 128
        lanes."""
        return {
            "c": self.kv_lora_rank,
            "kpe": max(128, self.qk_rope_head_dim),
        }

    def per_token_outputs(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        """What a step program returns for every row it computes, beside
        the logits: the experts chosen, ids among ALL of the router's,
        for the expert layers."""
        return {
            "experts": (
                (self.n_expert_layers, self.num_experts_per_token), "int32"
            ),
        }

    def decode_read_rows(
        self, cached: int, table_positions: int, block_size: int
    ) -> int:
        """The rows decode attention fetches an MLA layer for a lane of
        ``cached`` positions: every row of the blocks it holds
        (``ops/paged_attention.latent_decode_read_rows`` under the
        selection "all rows")."""
        from dlrover_tpu.ops.paged_attention import latent_decode_read_rows

        return latent_decode_read_rows(
            cached, table_positions, table_positions, block_size
        )

    @staticmethod
    def tiny(**overrides) -> "KimiLinearConfig":
        """Test-sized: one dense layer and one period (KDA KDA KDA MLA),
        2 of 8 experts held."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=4,
            first_k_dense_replace=1, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8,
            num_experts_per_token=2, num_attention_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kda_layers=(1, 2, 3), full_attn_layers=(4,),
            linear_num_heads=4, linear_head_dim=16, held_experts=2,
            max_seq_len=128,
        )
        base.update(overrides)
        return KimiLinearConfig(**base)


# ---------------------------------------------------------------- params

_KDA_IN = ("wq", "wk", "wv", "wf1", "wg1", "wb")
# of the serving copy, which holds a KDA layer's input projections fused
# and ``wkv_b`` as its two views; the router and its bias stay float32
_SERVING_MATMUL_LEAVES = (
    "w_in", "wf2", "wg2", "wq", "wkv_a", "w_uk", "w_uv", "wo",
    "mlp_gate", "mlp_up", "mlp_down", "shared_gate", "shared_up",
    "shared_down", "w_gate", "w_up", "w_down",
)


def layer_shapes(cfg: KimiLinearConfig, layer: int) -> Dict:
    """``{name: shape}`` of ONE layer's leaves.  ``conv_w[k]`` multiplies
    the input ``taps - 1 - k`` tokens back, over the channels ``[q | k |
    v]`` (one leaf where the published module has three convolutions)."""
    d = cfg.hidden_size
    out = {"attn_norm": (d,), "mlp_norm": (d,)}
    if cfg.layer_kinds[layer] == KDA:
        h, hd, kd = cfg.linear_num_heads, cfg.linear_head_dim, cfg.key_dim
        out.update(
            wq=(d, kd), wk=(d, kd), wv=(d, kd),
            wf1=(d, hd), wf2=(hd, kd), wg1=(d, hd), wg2=(hd, kd),
            wb=(d, h), conv_w=(cfg.short_conv_kernel_size, cfg.conv_dim),
            A_log=(h,), dt_bias=(kd,), kda_norm=(hd,), wo=(kd, d),
        )
    else:
        nh = cfg.num_attention_heads
        dn, dr, dv = (
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        )
        out.update(
            wq=(d, nh * (dn + dr)), wkv_a=(d, cfg.kv_lora_rank + dr),
            kv_norm=(cfg.kv_lora_rank,),
            wkv_b=(cfg.kv_lora_rank, nh * (dn + dv)), wo=(nh * dv, d),
        )
    if layer < cfg.first_k_dense_replace:
        f = cfg.intermediate_size
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, cfg.held_experts
        fs = f * cfg.num_shared_experts
        out.update(
            router=(d, cfg.num_experts), router_bias=(cfg.num_experts,),
            shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d),
            w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d),
        )
    return out


def param_shapes(cfg: KimiLinearConfig) -> Dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg.num_hidden_layers)
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def init_params(key, cfg: KimiLinearConfig) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, norm
    weights 1, conv taps ``normal(0, taps ** -0.5)``, ``A = 1 .. heads``
    and ``dt`` log-uniform in [1e-3, 1e-1] a channel (as the published
    GatedDeltaNet / KDA code initialises them), the selection bias ``0.1
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
        elif name == "A_log":
            leaf = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        elif name == "dt_bias":
            dt = jnp.exp(
                jax.random.uniform(k, shape, jnp.float32)
                * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
            )
            leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        else:
            fan_in = shape[-1] if name == "embed" else (
                shape[0] if name == "conv_w" else shape[-2]
            )
            leaf = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@partial(jax.jit, static_argnums=(2,))
def _cast_fuse_and_split(work, dtype_of, cfg):
    dt = dtype_of.dtype

    def layer(lp):
        out = {
            n: w.astype(dt) for n, w in lp.items()
            if n != "wkv_b" and not ("wf1" in lp and n in _KDA_IN)
        }
        if "wf1" in lp:  # a KDA layer's six input projections, fused
            out["w_in"] = jnp.concatenate(
                [lp[n].astype(dt) for n in _KDA_IN], axis=-1
            )
        if "wkv_b" in lp:
            w_uk, w_uv = _kv_up(lp, cfg)
            out.update(w_uk=w_uk.astype(dt), w_uv=w_uv.astype(dt))
        return out

    return {
        **{n: w.astype(dt) for n, w in work.items() if n != "layers"},
        "layers": tuple(layer(lp) for lp in work["layers"]),
    }


def serving_params(params: Dict, cfg: KimiLinearConfig) -> Dict:
    """The tree the serving programs compute on: the embedding, the head
    and every matrix in ``cfg.dtype``; a KDA layer's ``wq``, ``wk``,
    ``wv``, ``wf1``, ``wg1``, ``wb`` as ONE leaf ``w_in`` (one matmul a
    layer reads it in place; the parts are not in the returned tree); an
    MLA layer's ``wkv_b`` as its two views ``w_uk`` / ``w_uv`` (made
    ONCE, here); router, bias, norms, conv taps, ``A_log`` and
    ``dt_bias`` as given.  One jitted program over the leaves that need
    any of it; a leaf that needs none stays the caller's array, and a
    tree that is already a serving copy comes back as it is."""
    dt = jnp.dtype(cfg.dtype)

    def todo(lp):
        names = [
            n for n in lp
            if n == "wkv_b"
            or (n in _SERVING_MATMUL_LEAVES and lp[n].dtype != dt)
        ]
        if "wf1" in lp:
            names = [n for n in names if n not in _KDA_IN] + list(_KDA_IN)
        return names

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
        done = _cast_fuse_and_split(work, jnp.zeros((), dt), cfg)
    layers = tuple(
        {**{n: w for n, w in lp.items() if n not in names}, **new}
        for lp, names, new in zip(params["layers"], per_layer, done["layers"])
    )
    return {**params, **{n: done[n] for n in work if n != "layers"},
            "layers": layers}


# ---------------------------------------------------------------- pieces


def _kda_inputs(h, lp, cfg: KimiLinearConfig):
    """``h [..., D]`` (normalised) -> float32 raw ``qkv [..., conv_dim]``,
    the decay's logits ``f [..., H * dk]``, the output gate's logits ``g
    [..., H * dv]`` and ``b [..., H]``."""
    dt, hd = cfg.dtype, cfg.linear_head_dim
    if "w_in" in lp:
        p = jnp.matmul(
            h, lp["w_in"].astype(dt), preferred_element_type=jnp.float32
        )
        qkv, f_low, g_low, b = jnp.split(
            p, (cfg.conv_dim, cfg.conv_dim + hd, cfg.conv_dim + 2 * hd),
            axis=-1,
        )
    else:
        def one(name):
            return jnp.matmul(
                h, lp[name].astype(dt), preferred_element_type=jnp.float32
            )

        qkv = jnp.concatenate([one("wq"), one("wk"), one("wv")], axis=-1)
        f_low, g_low, b = one("wf1"), one("wg1"), one("wb")

    def up(low, name):
        return jnp.matmul(
            low.astype(dt), lp[name].astype(dt),
            preferred_element_type=jnp.float32,
        )

    return qkv, up(f_low, "wf2"), up(g_low, "wg2"), b


def _kda_gates(f, b, lp, cfg: KimiLinearConfig):
    """Raw ``f [..., H * dk]``, ``b [..., H]`` -> the decay ``alpha
    [..., H, dk]`` in (0, 1), one a key channel, and the write strength
    ``beta [..., H]`` in (0, 1)."""
    h, dk = cfg.linear_num_heads, cfg.linear_head_dim
    alpha = jnp.exp(
        -jnp.exp(lp["A_log"])[:, None]
        * jax.nn.softplus(f + lp["dt_bias"]).reshape(f.shape[:-1] + (h, dk))
    )
    return alpha, jax.nn.sigmoid(b)


def _kda_output(x, o, g, lp, cfg: KimiLinearConfig):
    """``x + W_o [RMSNorm_dv(o_i) * sigmoid(g_i)]``; ``o [..., H, dv]``
    float32, ``g [..., H * dv]``."""
    o = o * lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps
    ) * lp["kda_norm"]
    y = o.reshape(g.shape) * jax.nn.sigmoid(g)
    return x + _proj(y.astype(cfg.dtype), lp["wo"], cfg.dtype)


def _mla_query(h, lp, cfg: KimiLinearConfig):
    """``h [..., D]`` -> ``q [..., H, nope + rope]`` in the compute
    dtype; nothing is rotated."""
    return _proj(h, lp["wq"], cfg.dtype).reshape(
        h.shape[:-1] + (cfg.num_attention_heads, -1)
    )


def _latent_row(h, lp, cfg: KimiLinearConfig):
    """``h [..., D]`` -> the cached row's two parts: ``RMSNorm(c) [...,
    rank]`` and the un-rotated ``k_pe [..., rope]``."""
    kva = _proj(h, lp["wkv_a"], cfg.dtype)
    c_kv = rms_norm(
        kva[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_norm_eps
    )
    return c_kv, kva[..., cfg.kv_lora_rank:]


def _ranks(cfg: KimiLinearConfig):
    """Each layer's rank among its kind: where its slab or its blocks
    lie in the pool."""
    seen = {KDA: 0, MLA: 0}
    out = []
    for kind in cfg.layer_kinds:
        out.append(seen[kind])
        seen[kind] += 1
    return out


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: KimiLinearConfig,
            return_experts: bool = False):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole sequence
    at once, no cache, the recurrence as the chunked scan from a zero
    state, attention in multi-head (decompressed) form
    (``return_experts``: and the experts chosen, ``[B, T, expert layers,
    k]``).  For tests and as the serving worker's ``forward_fn``; dense
    in ``T x T``.  Takes the tree as :func:`init_params` makes it or its
    serving copy."""
    from dlrover_tpu.ops.paged_attention import NEG_INF

    dt, dn = cfg.dtype, cfg.qk_nope_head_dim
    bsz, t = tokens.shape
    h_lin, hd = cfg.linear_num_heads, cfg.linear_head_dim
    taps = cfg.short_conv_kernel_size
    x = _embed(params, tokens, cfg)
    causal = jnp.tril(jnp.ones((t, t), bool))
    chosen = []
    for lp, kind in zip(params["layers"], cfg.layer_kinds):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        if kind == KDA:
            qkv, f, g, b = _kda_inputs(h, lp, cfg)
            window = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
            q, k, v = _qkv_heads(_causal_conv(window, lp["conv_w"]), cfg)
            alpha, beta = _kda_gates(f, b, lp, cfg)
            o, _ = kda_chunk_scan(
                q, k, v, alpha, beta,
                jnp.zeros((bsz, h_lin, hd, hd), jnp.float32), KDA_CHUNK,
            )
            x = _kda_output(x, o, g, lp, cfg)
        else:
            q = _mla_query(h, lp, cfg)
            c_kv, k_pe = _latent_row(h, lp, cfg)
            w_uk, w_uv = _kv_up(lp, cfg)
            k_nope = jnp.einsum(
                "bsc,hdc->bshd", c_kv, w_uk.astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)
            v = jnp.einsum(
                "bsc,hcd->bshd", c_kv, w_uv.astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)
            att = jnp.einsum(
                "bthd,bshd->bhts", q[..., :dn], k_nope,
                preferred_element_type=jnp.float32,
            ) + jnp.einsum(
                "bthd,bsd->bhts", q[..., dn:], k_pe,
                preferred_element_type=jnp.float32,
            )
            att = jax.nn.softmax(
                jnp.where(causal, att * cfg.softmax_scale, NEG_INF), -1
            )
            out = jnp.einsum(
                "bhts,bshd->bthd", att.astype(dt), v,
                preferred_element_type=jnp.float32,
            ).astype(dt)
            x = x + _proj(out.reshape(bsz, t, -1), lp["wo"], dt)
        y, ids = _mlp(x.reshape(bsz * t, -1), lp, cfg)
        x = y.reshape(x.shape)
        chosen.append(ids)
    logits = _logits(x, params, cfg)
    if return_experts:
        return logits, _stack_experts(chosen, cfg).reshape(
            bsz, t, cfg.n_expert_layers, -1
        )
    return logits


# ------------------------------------------------------- serving programs


class _Cache:
    """What a step program holds of the pool: the MLA layers' paged
    leaves, every such layer's blocks in one buffer each (the ``j``-th
    MLA layer addresses its blocks at ``j * num_blocks``:
    ``ops/paged_attention.LayerPool``, which here carries no ``k`` /
    ``v``), and the KDA layers' slabs, stacked; and the walk over the
    layers."""

    def __init__(self, pool: Dict):
        self._shapes = {n: pool[n].shape for n in ("c", "kpe")}
        self.flat = {
            n: pool[n].reshape((-1,) + pool[n].shape[2:])
            for n in self._shapes
        }
        self.num_blocks, self.block_size = pool["c"].shape[1:3]
        self.conv, self.kda = pool["conv"], pool["kda"]

    def layer(self, j: int):
        from dlrover_tpu.ops.paged_attention import LayerPool

        return LayerPool(
            None, None, jnp.int32(j * self.num_blocks), jnp.int32(j),
            self.flat,
        )

    def walk(self, layers, x, kda, mla, mlp, cfg: KimiLinearConfig):
        """The layers in turn through a step program's pieces: ``kda(x,
        a layer's mixer leaves, this cache, rank) -> x`` (which hands
        its jitted piece the slabs and keeps what it returns),
        ``mla(x, leaves, its LayerPool) -> (x, paged leaves)`` and
        ``mlp(x, a layer's MLP leaves) -> (x, experts chosen)``, the
        last two jitted.  A piece's scopes are entered on BOTH sides of
        its call (``models/deepseek_v32._Leaves.walk`` says why).  ->
        (x, the program's per-position rows)."""
        chosen = []
        for lp, kind, j in zip(layers, cfg.layer_kinds, _ranks(cfg)):
            of_mlp = _mlp_leaves(lp)
            of_attn = {n: w for n, w in lp.items() if n not in of_mlp}
            if kind == KDA:
                with jax.named_scope("attn"), jax.named_scope("linear"):
                    x = kda(x, of_attn, self, j)
            else:
                with jax.named_scope("attn"), jax.named_scope("latent"):
                    x, self.flat = mla(x, of_attn, self.layer(j))
            with jax.named_scope("mlp"):
                x, ids = mlp(x, of_mlp)
            chosen.append(ids)
        return x, {"experts": _stack_experts(chosen, cfg)}

    def stacked(self) -> Dict:
        return {
            **{
                n: self.flat[n].reshape(shape)
                for n, shape in self._shapes.items()
            },
            "conv": self.conv, "kda": self.kda,
        }


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # c, kpe [Lm, blocks, ...]; conv, kda [Lk, lanes, ...]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    lane: jnp.ndarray,  # scalar int32: the lane whose state this is
    real: jnp.ndarray,  # scalar int32: tokens of the chunk that are real
    cfg: KimiLinearConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Prefill ``real`` prompt positions of ONE sequence: the MLA
    layers' latent rows into its paged blocks, the KDA layers' conv tail
    and recurrent state into its lane's slabs.  The state starts from
    zero at ``start_pos == 0`` and from the lane's slab otherwise; the
    padded tail advances neither the state nor the conv tail (``alpha ==
    1``, ``beta == 0`` there) and writes its latent rows ahead of the
    prompt into the sequence's own reservation (decode overwrites each
    position before a query can see it).  Returns (logits [1, C, vocab],
    pool, {"experts": [C, expert layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        latent_prefill_attention,
        paged_kernel_backend,
    )

    dt, dr, rank = cfg.dtype, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    c = tokens.shape[1]
    cache = _Cache(pool)
    bs, mb = cache.block_size, block_table.shape[0]
    heads, hd = cfg.linear_num_heads, cfg.linear_head_dim
    taps = cfg.short_conv_kernel_size
    backend = paged_kernel_backend()
    positions = start_pos + jnp.arange(c)
    valid = jnp.arange(c) < real
    fresh = start_pos == 0
    x = _embed(params, tokens, cfg)[0]  # [C, D]
    # the chunk sees ``start_pos + C`` cached positions, the table holds
    # ``mb * bs``: decompression and attention run over the narrowest of
    # a few static widths that holds what it sees
    widths = _prefill_widths(mb * bs, bs)
    bucket = jnp.searchsorted(
        jnp.asarray(widths), jnp.minimum(start_pos + c, mb * bs)
    ).astype(jnp.int32)

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    @jax.named_scope("linear")
    def kda_layer(x, lp, conv_all, kda_all, j):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        qkv, f, g, b = _kda_inputs(h, lp, cfg)
        # the lane's slabs are read where they lie, as they are written
        # back: one slice of the stacked pool
        tail = jnp.where(
            fresh, 0.0,
            lax.dynamic_slice(
                conv_all, (j, lane, 0), (1, 1) + conv_all.shape[2:]
            ),
        ).reshape(taps - 1, cfg.conv_dim)
        window = jnp.concatenate([tail, qkv], axis=0)
        q, k, v = _qkv_heads(_causal_conv(window, lp["conv_w"]), cfg)
        # the inputs of the last K-1 REAL tokens (reaching back into the
        # old tail where the chunk holds fewer)
        conv_all = lax.dynamic_update_slice(
            conv_all,
            lax.dynamic_slice_in_dim(window, real, taps - 1, 0).reshape(
                1, 1, -1
            ),
            (j, lane, 0),
        )
        alpha, beta = _kda_gates(f, b, lp, cfg)
        state = jnp.where(
            fresh, 0.0,
            lax.dynamic_slice(
                kda_all, (j, lane, 0, 0, 0), (1, 1, heads, hd, hd)
            )[0],
        )
        with jax.named_scope("kda_scan"):
            o, state = kda_chunk_scan(
                q[None], k[None], v[None],
                jnp.where(valid[:, None, None], alpha, 1.0)[None],
                jnp.where(valid[:, None], beta, 0.0)[None],
                state, KDA_CHUNK,
            )
        kda_all = lax.dynamic_update_slice(
            kda_all, state.astype(kda_all.dtype)[None], (j, lane, 0, 0, 0)
        )
        return _kda_output(x, o[0], g, lp, cfg), conv_all, kda_all

    def kda(x, lp, cache, j):
        # ONE lane's rows of the slabs, at a rank that is an argument
        x, cache.conv, cache.kda = kda_layer(
            x, lp, cache.conv, cache.kda, jnp.int32(j)
        )
        return x

    def attend(width, q, c_kv, k_pe, w_uk, w_uv):
        c_kv, k_pe = c_kv[:width], k_pe[:width]
        k = jnp.concatenate([
            jnp.einsum(
                "sc,hdc->hsd", c_kv, w_uk, preferred_element_type=jnp.float32
            ).astype(dt),
            jnp.broadcast_to(k_pe[None], (w_uk.shape[0], width, dr)),
        ], axis=-1)
        v = jnp.einsum(
            "sc,hcd->hsd", c_kv, w_uv, preferred_element_type=jnp.float32
        ).astype(dt)
        seen = jnp.arange(width)[None] <= positions[:, None]
        return latent_prefill_attention(
            q, k, v, seen, start_pos, start_pos + c, cfg.softmax_scale,
            backend,
        )

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def mla(x, lp, kv):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = _mla_query(h, lp, cfg)
        c_kv, k_pe = _latent_row(h, lp, cfg)
        kv = kv.write_leaf_run(
            "c", c_kv, block_table, start_pos
        ).write_leaf_run("kpe", k_pe, block_table, start_pos)
        # the sequence's rows by position, ONCE: a branch that took the
        # pool itself had it copied into it
        table = kv.tables(block_table)
        w_uk, w_uv = _kv_up(lp, cfg)
        attn = lax.switch(
            bucket,
            [partial(attend, width) for width in widths],
            q,
            kv.paged["c"][table].reshape(mb * bs, rank),
            kv.paged["kpe"][table].reshape(mb * bs, dr),
            w_uk.astype(dt), w_uv.astype(dt),
        )
        return x + _proj(attn.reshape(c, -1), lp["wo"], dt), kv.paged

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("mlp")
    def mlp(x, lp):
        return _mlp(x, lp, cfg, backend)

    x, rows = cache.walk(params["layers"], x, kda, mla, mlp, cfg)
    return _logits(x[None], params, cfg), cache.stacked(), rows


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # c, kpe [Lm, blocks, ...]; conv, kda [Lk, lanes, ...]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: KimiLinearConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One continuous-batching decode step: every ACTIVE lane advances
    by one token — a KDA layer's state and conv tail in place, an MLA
    layer's latent row written and every row the lane holds read in
    ABSORBED form.  An inactive lane — free, or in the middle of its
    prefill — writes to the null block, reads nothing and comes out with
    its conv tail and its recurrent state bitwise as they went in.
    Shapes depend on (lanes, pool geometry) only: compiled once.
    Returns (logits [B, vocab], pool, {"experts": [B, expert layers,
    k]})."""
    from dlrover_tpu.ops.paged_attention import (
        LatentSelection,
        latent_decode_attention,
        paged_kernel_backend,
    )

    dt, dn = cfg.dtype, cfg.qk_nope_head_dim
    n = tokens.shape[0]
    cache = _Cache(pool)
    bs, mb = cache.block_size, block_tables.shape[1]
    backend = paged_kernel_backend()
    x = _embed(params, tokens, cfg)  # [B, D]
    with jax.named_scope("attn"), jax.named_scope("latent"):
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
        held = jnp.where(active, positions + 1, 0)  # what attention reads
        # no indexer: the selection is every row (the kernel streams
        # the lane's own blocks and masks what lies past its length)
        every_row = LatentSelection(jnp.ones((n, mb * bs), bool), None)

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("attn")
    @jax.named_scope("linear")
    def kda_layer(x, lp, conv, kda_all, j):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        qkv, f, g, b = _kda_inputs(h, lp, cfg)
        window = jnp.concatenate([conv, qkv], axis=-1)  # oldest first
        q, k, v = _qkv_heads(_conv_step(window, lp["conv_w"]), cfg)
        conv = jnp.where(active[:, None], window[:, cfg.conv_dim:], conv)
        alpha, beta = _kda_gates(f, b, lp, cfg)
        o, kda_all = kda_decode_update(
            kda_all, j, q, k, v, alpha, beta, active, backend
        )
        return _kda_output(x, o, g, lp, cfg), conv, kda_all

    def kda(x, lp, cache, j):
        # every lane's conv tail of the layer, cut and put back at a
        # STATIC rank (under a traced one the compiler lays the whole
        # slab lanes-minor and copies it in and out of every step); the
        # state's slab is the kernel's to address, its rank an argument
        x, conv, cache.kda = kda_layer(
            x, lp, cache.conv[j], cache.kda, jnp.int32(j)
        )
        cache.conv = cache.conv.at[j].set(conv)
        return x

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def mla(x, lp, kv):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = _mla_query(h, lp, cfg)
        w_uk, w_uv = _kv_up(lp, cfg)
        q_c = _per_head(q[..., :dn], w_uk, dt)
        c_kv, k_pe = _latent_row(h, lp, cfg)
        kv = kv.write_leaf_rows("c", c_kv, blk, off)
        kv = kv.write_leaf_rows("kpe", k_pe, blk, off)
        latent = latent_decode_attention(
            q_c, q[..., dn:], kv.paged["c"], kv.paged["kpe"],
            kv.tables(block_tables), held, every_row, cfg.softmax_scale,
            backend,
        )
        attn = _per_head(latent, w_uv, dt)
        return x + _proj(attn.reshape(n, -1), lp["wo"], dt), kv.paged

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("mlp")
    def mlp(x, lp):
        return _mlp(x, lp, cfg, backend)

    x, rows = cache.walk(params["layers"], x, kda, mla, mlp, cfg)
    return _logits(x[:, None], params, cfg)[:, 0], cache.stacked(), rows
