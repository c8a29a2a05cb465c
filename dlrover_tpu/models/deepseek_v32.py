"""DeepSeek-V3.2's decoder (``model_type`` ``deepseek_v32``) for the
serving plane: latent attention over a cache of ONE compressed row a
token, a learned top-k indexer that picks the rows a query reads, and
one chip's share of a layer's group-routed experts beside a shared one.

Published description: ``deepseek-ai/DeepSeek-V3.2`` ``config.json``.
One block, with ``h = RMSNorm(x)``, ``t`` a query position and ``s <=
t`` a key position (what the config has no key for is listed under
``assumed`` in the benchmark's configuration file):

- query: ``c_q = RMSNorm(W_qa h)`` (``q_lora_rank``); ``q = W_qb c_q``,
  a head ``[q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]``,
  ``q_pe`` rotated.
- latent: ``W_kva h`` -> ``[c (kv_lora_rank), k_pe (qk_rope_head_dim)]``;
  ``c_kv = RMSNorm(c)``; ``k_pe`` rotated, ONE for all heads.  **The
  cached row is** ``[c_kv, k_pe]``.  ``W_kvb c_kv`` gives a head its
  ``[k_nope, v]``; ``k = [k_nope, k_pe]``.
- rotation: YaRN (:func:`yarn_inv_freq`), split-half pairs, cos / sin
  unscaled; the scores' scale is ``(nope + rope) ** -0.5 * (0.1 *
  mscale_all_dim * ln factor + 1) ** 2``.
- indexer: ``qi = W_iq c_q`` (``index_n_heads`` x ``index_head_dim``),
  the first ``qk_rope_head_dim`` dims rotated; ``ik = LayerNorm(W_ik
  h)``, rotated the same way, ONE a token — **the second cached row**;
  ``w = W_iw h * heads ** -0.5 * dim ** -0.5``; ``I[t, s] = sum_j w[t,
  j] relu(qi[t, j] . ik[s])`` in float32; ``S_t`` = the ``index_topk``
  positions ``s <= t`` of largest ``I`` (equal scores lowest position
  first), all of them while ``t < index_topk``.  EXACT.  While logprobs
  are captured the step programs return ``S_t`` of every row and layer
  beside the experts chosen (``per_token_outputs()``: ``selection``, a
  bit a position), so that a float32 reference can follow the picks a
  bfloat16 run made and judge them by its own scores.
- ``o[t] = sum over s in S_t of softmax_s(scale q[t] . k[s]) v[s]`` a
  head; ``x += W_o o``.
- layers ``< first_k_dense_replace``: ``x += SwiGLU(RMSNorm(x))`` of
  ``intermediate_size``.  The others, ``h' = RMSNorm(x)``: ``s =
  sigmoid(W_r h')`` in float32 over ``n_routed_experts``; ``n_group``
  groups, a group's score the sum of its two largest ``s + b``; the
  ``topk_group`` best groups; among their experts the
  ``num_experts_per_tok`` of largest ``s + b`` (``b`` enters the
  selection only); ``w_e = s_e / sum s * routed_scaling_factor``; ``x
  += Shared(h') + sum_e w_e Expert_e(h')``, no capacity, no drop.
- final RMSNorm, untied head.  The multi-token-prediction module is not
  here.

**The share.**  ``held_experts`` of the ``n_routed_experts`` live here
(``first_expert ..``), as in ``models/trinity.py``: the router keeps
its width, its groups and its top-k, the layer computes the assignments
that fall on its own experts and what the absent experts would add is
left out.

**The cache.**  ``pages_kv = False``: a token keeps NO per-head keys
and values, so the pool (``rl/kv_cache.paged_cache_config``) holds the
``paged_leaves()`` alone, the cached row in its two parts — ``c [L,
blocks, block_size, kv_lora_rank]`` and ``kpe [L, blocks, block_size *
qk_rope_head_dim / 128, 128]`` — and ``ik [L, blocks, block_size *
index_head_dim / 128, 128]``, all in ROWS (``paged_leaf_rows()``)
because decode reads them row by row — a block of each is one copy of
a kernel that streams a lane's blocks, a row one element of the gather
under a very wide table —, two tokens' rotated keys a row and one
index key: a minor axis of 576 or of 64 is one the device pads or lays
out blocks-minor, and every program then copies the leaf.  1408 bytes
a token and layer in bfloat16 at the published widths, against 81 920
for 128 heads of 192 + 128.

**Two forms of one attention.**  Decode is ABSORBED: ``q_nope W_uk``
is a ``kv_lora_rank``-wide query a head, scored with ``q_pe`` against
the cached row itself; the output is ``sum p c_kv``, then ``W_uv``,
then ``W_o`` — a row is read once for all heads, by a kernel that
copies the blocks a lane holds itself and masks the rows not picked
(``ops/paged_kernels.mla_stream_decode_kernel``; under a table more
than ``LATENT_STREAM_WIDTH`` times ``index_topk`` wide the picked rows
are gathered for ``mla_sparse_decode_kernel``:
``ops/paged_attention.latent_decode_selection`` picks by the shapes).
A prefill chunk
DECOMPRESSES the rows it may see (``W_uk`` / ``W_uv``, views of
``W_kvb`` made once in :func:`serving_params`) and attends in
multi-head form under each query's selection
(``ops/paged_kernels.mla_prefill_kernel``): ``2 * H * (192 + 128)``
operations a key against the absorbed form's ``2 * H * (576 + 512)``.

The layers differ (a dense MLP, then experts), so they are unrolled,
each with its own leaves (``params["layers"]`` is a tuple of dicts).
What the two step programs' loops call for a layer, though, are
``jax.jit``-wrapped pieces: ONE set for the attention of all layers —
the latent row's write, the indexer, the attention over what it picked,
each piece a sub-scope of ``attn``; a layer's attention leaves, the
flat paged leaves and the layer's offsets are its arguments — and one
``mlp`` (``_Leaves.walk``), which JAX traces once for the dense layers
and once for the expert layers because their leaves differ.  JAX finds
the later layers' calls in its trace cache and lowers one private
function a piece and kind, which the module calls a layer; XLA inlines
the calls.  A replica so traces and lowers one layer's attention and
two MLPs a program instead of every layer at EVERY start, whatever the
compile cache holds (``PERF.md`` section 6, PR 56: the unrolled loops
cost ~21 s of every start; what XLA compiles from the calls is the
unrolled program but for its scheduler's choices).  The pieces are made
INSIDE the step program's call and die with its trace: JAX keys a trace
by function and argument types, not by what the function looks up, so a
piece kept at module level would hand the NEXT program the indexer it
traced before ``ops.paged_attention.decode_index_scores`` was replaced
— which the benchmark's planted faults and the tests do between two
traces (``tests/test_deepseek_v32_blocks.py``).
There is no training path.
"""

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.models.keye_vl2 import (
    _embed,
    _index_key_and_weights,
    _logits,
    _prefill_widths,
    _proj,
    _rotate,
)
from dlrover_tpu.models.llama import rms_norm
from dlrover_tpu.models.trinity import _stack_experts, _swiglu
from dlrover_tpu.ops.grouped_gemm import expert_ffn


@dataclass(frozen=True)
class DeepSeekV32Config:
    """The published ``config.json`` keys that shape the decoder, under
    their own names (``rope_scaling``'s flattened: ``rope_factor``,
    ``rope_original_max_position_embeddings``, ``rope_beta_fast``,
    ``rope_beta_slow``, ``rope_mscale``, ``rope_mscale_all_dim``);
    ``first_expert`` / ``held_experts`` say which of the
    ``n_routed_experts`` this chip holds (all of them by default), and
    ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    first_expert: int = 0
    held_experts: Optional[int] = None
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16

    #: to ``rl/kv_cache.paged_cache_config``: a token keeps no per-head
    #: keys and values, the pool is the ``paged_leaves()`` alone
    pages_kv = False

    def __post_init__(self):
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", self.n_routed_experts)
        for ok, what in (
            (self.scoring_func == "sigmoid",
             "scoring_func other than sigmoid"),
            (self.topk_method == "noaux_tc",
             "topk_method other than noaux_tc"),
            (self.norm_topk_prob, "norm_topk_prob false"),
            (self.n_routed_experts % self.n_group == 0
             and 1 <= self.topk_group <= self.n_group
             and self.n_routed_experts // self.n_group >= 2,
             "groups that do not divide the experts, or of one expert"),
            (0 <= self.first_expert
             and self.first_expert + self.held_experts
             <= self.n_routed_experts,
             "held experts outside the router's"),
            (0 <= self.first_k_dense_replace < self.num_hidden_layers,
             "no expert layer"),
            (self.qk_rope_head_dim <= self.index_head_dim,
             "an index key narrower than its rotated part"),
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
        return self.index_topk

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        mscale = 0.1 * self.rope_mscale_all_dim * math.log(
            self.rope_factor
        ) + 1.0 if self.rope_factor > 1 else 1.0
        return (
            self.qk_nope_head_dim + self.qk_rope_head_dim
        ) ** -0.5 * mscale * mscale

    def paged_leaves(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per layer and TOKEN, and nothing else: the cached row's two
        parts (the normalised latent, the rotated shared key) and one
        index key, in the compute dtype."""
        return {
            "c": ((self.kv_lora_rank,), self.dtype),
            "kpe": ((self.qk_rope_head_dim,), self.dtype),
            "ik": ((self.index_head_dim,), self.dtype),
        }

    def paged_leaf_rows(self) -> Dict[str, int]:
        """The leaves decode reads row by row, and the rows a block of
        each lies in: a latent a row; the rotated keys and the index
        keys in rows of (at least) the device's 128 lanes, whole
        tokens' a row (an index key a row at 128)."""
        return {
            "c": self.kv_lora_rank,
            "kpe": max(128, self.qk_rope_head_dim),
            "ik": max(128, self.index_head_dim),
        }

    def per_token_outputs(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        """What a step program returns for every row it computes,
        beside the logits: the experts chosen, ids among ALL of the
        router's, for the expert layers; and the positions the indexer
        picked in every layer, a bit a position (:func:`pack_selection`:
        bit ``b`` of word ``j`` is position ``32 j + b``) — what a
        reference has to be told to follow the served side's choices."""
        return {
            "experts": (
                (self.n_expert_layers, self.num_experts_per_tok), "int32"
            ),
            "selection": (
                (self.num_hidden_layers, self.selection_words), "int32"
            ),
        }

    def decode_read_rows(
        self, cached: int, table_positions: int, block_size: int
    ) -> int:
        """The rows decode attention fetches a layer for a lane of
        ``cached`` positions, by the attention's own test of shapes
        (``ops/paged_attention.latent_decode_read_rows``): arithmetic
        on the host for the scheduler's ``read_rows`` label, which
        learns no model's name."""
        from dlrover_tpu.ops.paged_attention import latent_decode_read_rows

        return latent_decode_read_rows(
            cached, table_positions, min(self.index_topk, table_positions),
            block_size,
        )

    @property
    def selection_words(self) -> int:
        return -(-self.max_seq_len // 32)

    @staticmethod
    def tiny(**overrides) -> "DeepSeekV32Config":
        """Test-sized, every mechanism present: ``index_topk`` below
        its sequences, 2 groups of which 1 is taken, 2 of 8 experts
        held, one dense layer before two expert layers."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, n_group=2, topk_group=1,
            index_n_heads=2, index_head_dim=16, index_topk=32,
            rope_original_max_position_embeddings=32, held_experts=2,
            max_seq_len=128,
        )
        base.update(overrides)
        return DeepSeekV32Config(**base)


# ---------------------------------------------------------------- params

# of the serving copy, which holds ``wkv_b`` as its two views; the
# router and its bias stay float32 (they decide a discrete choice)
_SERVING_MATMUL_LEAVES = (
    "wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo", "wi_q", "wi_k", "wi_w",
    "mlp_gate", "mlp_up", "mlp_down", "shared_gate", "shared_up",
    "shared_down", "w_gate", "w_up", "w_down",
)


def layer_shapes(cfg: DeepSeekV32Config, layer: int) -> Dict:
    """``{name: shape}`` of ONE layer's leaves: a dense MLP below
    ``first_k_dense_replace``, else router, bias, shared expert and the
    HELD experts' matrices."""
    d, nh = cfg.hidden_size, cfg.num_attention_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    out = {
        "attn_norm": (d,), "mlp_norm": (d,),
        "wq_a": (d, cfg.q_lora_rank), "q_norm": (cfg.q_lora_rank,),
        "wq_b": (cfg.q_lora_rank, nh * (dn + dr)),
        "wkv_a": (d, cfg.kv_lora_rank + dr), "kv_norm": (cfg.kv_lora_rank,),
        "wkv_b": (cfg.kv_lora_rank, nh * (dn + dv)),
        "wo": (nh * dv, d),
        "wi_q": (cfg.q_lora_rank, hi * di), "wi_k": (d, di),
        "wi_w": (d, hi), "ik_norm": (di,), "ik_norm_bias": (di,),
    }
    if layer < cfg.first_k_dense_replace:
        f = cfg.intermediate_size
        out.update(mlp_gate=(d, f), mlp_up=(d, f), mlp_down=(f, d))
    else:
        f, e = cfg.moe_intermediate_size, cfg.held_experts
        fs = f * cfg.n_shared_experts
        out.update(
            router=(d, cfg.n_routed_experts),
            router_bias=(cfg.n_routed_experts,),
            shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d),
            w_gate=(e, d, f), w_up=(e, d, f), w_down=(e, f, d),
        )
    return out


def param_shapes(cfg: DeepSeekV32Config) -> Dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg.num_hidden_layers)
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def init_params(key, cfg: DeepSeekV32Config) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, norm
    weights 1, the index key's LayerNorm bias 0, the selection bias
    ``0.1 normal``."""
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
        elif name.endswith("_bias"):
            leaf = jnp.zeros(shape, jnp.float32)
        elif "norm" in name:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-1] if name == "embed" else shape[-2]
            leaf = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _kv_up(lp, cfg: DeepSeekV32Config):
    """``W_kvb``'s two views: ``W_uk [H, nope, rank]`` (a head's
    ``k_nope = W_uk c_kv``, and the absorbed query ``q_nope W_uk``) and
    ``W_uv [H, rank, v]`` — the serving copy's own leaves, or made from
    the tree's ``wkv_b``."""
    if "w_uk" in lp:
        return lp["w_uk"], lp["w_uv"]
    w = lp["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads, -1
    )
    dn = cfg.qk_nope_head_dim
    return (
        jnp.transpose(w[..., :dn], (1, 2, 0)),
        jnp.transpose(w[..., dn:], (1, 0, 2)),
    )


@partial(jax.jit, static_argnums=(2,))
def _cast_and_split(work, dtype_of, cfg):
    dt = dtype_of.dtype

    def layer(lp):
        out = {n: w.astype(dt) for n, w in lp.items() if n != "wkv_b"}
        if "wkv_b" in lp:
            w_uk, w_uv = _kv_up(lp, cfg)
            out.update(w_uk=w_uk.astype(dt), w_uv=w_uv.astype(dt))
        return out

    return {
        **{n: w.astype(dt) for n, w in work.items() if n != "layers"},
        "layers": tuple(layer(lp) for lp in work["layers"]),
    }


def serving_params(params: Dict, cfg: DeepSeekV32Config) -> Dict:
    """The tree the serving programs compute on: the embedding, the
    head and every matrix in ``cfg.dtype``, ``wkv_b`` as its two views
    ``w_uk`` / ``w_uv`` (made ONCE, here); router, bias and norms as
    given.  One jitted program over the leaves that need either; a leaf
    that needs neither stays the caller's array, and a tree that is
    already a serving copy comes back as it is."""
    dt = jnp.dtype(cfg.dtype)

    def todo(lp):
        return [
            n for n in lp
            if n == "wkv_b"
            or (n in _SERVING_MATMUL_LEAVES and lp[n].dtype != dt)
        ]

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
        done = _cast_and_split(work, jnp.zeros((), dt), cfg)
    layers = tuple(
        {**{n: w for n, w in lp.items() if n not in names}, **new}
        for lp, names, new in zip(params["layers"], per_layer, done["layers"])
    )
    return {**params, **{n: done[n] for n in work if n != "layers"},
            "layers": layers}


# ---------------------------------------------------------------- pieces


def yarn_inv_freq(cfg: DeepSeekV32Config) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotation frequencies (float64 on
    the host): ``theta ** (-2 j / dim)``, blended with the same divided
    by ``rope_factor`` by a linear ramp between the correction dims of
    ``rope_beta_fast`` and ``rope_beta_slow`` — dims that turn more
    often than ``beta_fast`` over the original context keep their
    frequency, dims that turn less often than ``beta_slow`` are
    interpolated."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return freq

    def correction_dim(turns):
        return dim * math.log(
            cfg.rope_original_max_position_embeddings / (turns * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float64) - low)
        / max(high - low, 1e-3), 0, 1,
    )
    return freq / cfg.rope_factor * ramp + freq * (1 - ramp)


def _rope_tables(cfg: DeepSeekV32Config, positions):
    """[S] -> cos / sin [S, qk_rope_head_dim / 2] (float32)."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32
    )[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate_lead(x, cos, sin, n: int):
    """:func:`models.keye_vl2._rotate` on the first ``n`` of the last
    axis, the rest as it is."""
    return jnp.concatenate(
        [_rotate(x[..., :n], cos, sin), x[..., n:]], axis=-1
    )


def _queries(h, lp, cfg: DeepSeekV32Config):
    """``h [..., D]`` -> ``q_nope [..., H, nope]``, ``q_pe [..., H,
    rope]`` (before the rotation) and the query latent ``c_q [...,
    q_lora_rank]`` the indexer reads, all in the compute dtype."""
    dt, nh = cfg.dtype, cfg.num_attention_heads
    c_q = rms_norm(_proj(h, lp["wq_a"], dt), lp["q_norm"], cfg.rms_norm_eps)
    q = _proj(c_q, lp["wq_b"], dt).reshape(h.shape[:-1] + (nh, -1))
    return (
        q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:], c_q
    )


def _latent_row(h, lp, cfg: DeepSeekV32Config, cos, sin):
    """``h [..., D]`` -> the cached row's two parts: ``RMSNorm(c) [...,
    rank]`` and the rotated ``k_pe [..., rope]``; ``cos`` / ``sin``
    broadcastable to ``[..., rope / 2]``."""
    kva = _proj(h, lp["wkv_a"], cfg.dtype)
    c_kv = rms_norm(
        kva[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_norm_eps
    )
    return c_kv, _rotate(kva[..., cfg.kv_lora_rank:], cos, sin)


def _per_head(x, w, dt):
    """``x [B, H, K]`` times a head's own ``w [H, K, N]`` -> ``[B, H,
    N]``: the absorbed query ``q_nope W_uk`` and the output's ``W_uv``.
    The head leads both operands of the product (the CPU backend has no
    bfloat16 product with a batch axis in the middle)."""
    out = jnp.einsum(
        "hbk,hkn->hbn", jnp.swapaxes(x, 0, 1), w.astype(dt),
        preferred_element_type=jnp.float32,
    )
    return jnp.swapaxes(out, 0, 1).astype(dt)


def _indexer_inputs(h, c_q, lp, cfg: DeepSeekV32Config):
    """Index queries ``[..., Hi, Di]`` from the query latent, the index
    key ``[..., Di]`` and the float32 head weights ``[..., Hi]`` from
    the hidden state (both before the rope)."""
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    qi = _proj(c_q, lp["wi_q"], cfg.dtype).reshape(c_q.shape[:-1] + (hi, di))
    ik, w = _index_key_and_weights(h, lp, hi, di, cfg.rms_norm_eps, cfg.dtype)
    return qi, ik, w


def pack_selection(taken, words: int):
    """bool ``[N, T]`` (a row's picked positions) -> int32 ``[N,
    words]``, a bit a position: bit ``b`` of word ``j`` is position ``32
    j + b``; positions past ``T`` read 0."""
    n, t = taken.shape
    taken = jnp.pad(
        taken[:, :32 * words], ((0, 0), (0, max(32 * words - t, 0)))
    )
    bits = taken.reshape(n, words, 32).astype(jnp.uint32) << jnp.arange(
        32, dtype=jnp.uint32
    )
    return lax.bitcast_convert_type(
        jnp.sum(bits, -1, dtype=jnp.uint32), jnp.int32
    )


def group_limited_topk(score, k: int, n_group: int, topk_group: int):
    """``score [N, E]`` (the selection score ``s + b``) -> the ids ``[N,
    k]`` of the ``k`` largest among the experts of the ``topk_group``
    best of ``n_group`` equal groups, a group's score the sum of its
    two largest; equal scores lowest id first, for groups as for
    experts."""
    n, e = score.shape
    if n_group > 1:
        grouped = score.reshape(n, n_group, e // n_group)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], -1)
        _, best = lax.top_k(group_score, topk_group)
        allowed = jnp.any(
            best[..., None] == jnp.arange(n_group)[None, None], axis=1
        )
        score = jnp.where(
            jnp.repeat(allowed, e // n_group, axis=1), score, -jnp.inf
        )
    return lax.top_k(score, k)[1].astype(jnp.int32)


def _route(x, lp, cfg: DeepSeekV32Config):
    """The router on ``x [N, D]``: float32 norm, float32 logits over
    every expert at full precision, ``s = sigmoid``, the group-limited
    top-k of ``s + b`` and the chosen experts' ``s`` normalised to
    ``routed_scaling_factor``.  -> (h' [N, D] in the compute dtype, ids
    [N, k] int32 among ALL experts, weights [N, k] float32)."""
    xf = x.astype(jnp.float32)
    hf = xf * lax.rsqrt(
        jnp.mean(xf * xf, -1, keepdims=True) + cfg.rms_norm_eps
    ) * lp["mlp_norm"]
    s = jax.nn.sigmoid(jnp.matmul(
        hf, lp["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    ids = group_limited_topk(
        s + lp["router_bias"], cfg.num_experts_per_tok, cfg.n_group,
        cfg.topk_group,
    )
    chosen = jnp.take_along_axis(s, ids, -1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return hf.astype(cfg.dtype), ids, w * cfg.routed_scaling_factor


_MLP_LEAVES = (
    "mlp_norm", "mlp_gate", "mlp_up", "mlp_down", "router", "router_bias",
    "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down",
)


def _mlp_leaves(lp):
    """The leaves of a layer that :func:`_mlp` reads — it takes them
    through here, so one left off the list fails there by its name; the
    others, under the same names and shapes in every layer, are its
    attention's (``_Leaves.walk`` splits a layer so)."""
    return {n: lp[n] for n in _MLP_LEAVES if n in lp}


def _mlp(x, lp, cfg: DeepSeekV32Config, backend: str = "jnp"):
    """``x [N, D]`` -> (``x + MLP(RMSNorm(x))``, the experts chosen
    ``[N, k]`` or None for a dense layer)."""
    dt, lp = cfg.dtype, _mlp_leaves(lp)
    if "router" not in lp:
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        return x + _swiglu(
            h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"], dt
        ), None
    h, ids, w = _route(x, lp, cfg)
    y = _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"], dt)
    y = y + expert_ffn(
        h, ids, w, lp["w_gate"].astype(dt), lp["w_up"].astype(dt),
        lp["w_down"].astype(dt), 0, cfg.n_routed_experts, backend,
        first_expert=cfg.first_expert, held=cfg.held_experts,
    ).astype(dt)
    return x + y, ids




# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: DeepSeekV32Config,
            return_experts: bool = False):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, no cache, attention in multi-head (decompressed)
    form (``return_experts``: and the experts chosen, ``[B, T, expert
    layers, k]``).  For tests and as the serving worker's
    ``forward_fn``; dense in ``T x T``.  Takes the tree as
    :func:`init_params` makes it or its serving copy."""
    from dlrover_tpu.ops.paged_attention import NEG_INF, exact_topk_mask

    dt, dr = cfg.dtype, cfg.qk_rope_head_dim
    bsz, t = tokens.shape
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(t)
    cos, sin = _rope_tables(cfg, positions)
    causal = positions[None] <= positions[:, None]
    n_sel = min(cfg.index_topk, t)
    chosen = []
    for lp in params["layers"]:
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_pe, c_q = _queries(h, lp, cfg)
        q_pe = _rotate(q_pe, cos[None, :, None], sin[None, :, None])
        c_kv, k_pe = _latent_row(h, lp, cfg, cos[None], sin[None])
        qi, ik, w = _indexer_inputs(h, c_q, lp, cfg)
        qi = _rotate_lead(qi, cos[None, :, None], sin[None, :, None], dr)
        ik = _rotate_lead(ik, cos[None], sin[None], dr)
        s = jnp.einsum(
            "bthd,bsd->bhts", qi, ik, preferred_element_type=jnp.float32
        )
        score = jnp.einsum("bth,bhts->bts", w, jax.nn.relu(s))
        score = jnp.where(causal[None], score, -jnp.inf)
        taken = jax.vmap(lambda sc: exact_topk_mask(sc, n_sel))(score)
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
            "bthd,bshd->bhts", q_nope, k_nope,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bthd,bsd->bhts", q_pe, k_pe, preferred_element_type=jnp.float32
        )
        att = jax.nn.softmax(
            jnp.where(taken[:, None], att * cfg.softmax_scale, NEG_INF), -1
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


class _Leaves:
    """The paged leaves of a step program, every layer's blocks in one
    buffer each, and the walk over the layers: layer ``l`` addresses
    its blocks at ``l * num_blocks`` (``ops/paged_attention.LayerPool``,
    which here carries no ``k`` / ``v``)."""

    def __init__(self, pool: Dict):
        self._shapes = {n: pool[n].shape for n in ("c", "kpe", "ik")}
        self.flat = {
            n: pool[n].reshape((-1,) + pool[n].shape[2:])
            for n in self._shapes
        }
        self.num_blocks = pool["c"].shape[1]
        self.block_size = pool["c"].shape[2]

    def layer(self, i: int):
        from dlrover_tpu.ops.paged_attention import LayerPool

        return LayerPool(
            None, None, jnp.int32(i * self.num_blocks), jnp.int32(i),
            self.flat,
        )

    def walk(self, layers, x, attention, mlp, cfg):
        """The layers in turn through a step program's jitted pieces
        (the module docstring says why jitted, and why anew for every
        program): ``attention(x, a layer's attention leaves, its
        LayerPool) -> (x, paged leaves, selection)``, which calls the
        pieces of attention, and the jitted ``mlp(x, a layer's MLP
        leaves) -> (x, experts chosen)``, which JAX traces once for the
        dense layers and once for the expert layers, whose leaves
        differ.  -> (x, the program's per-position rows).

        A piece's scopes are entered on BOTH sides of its call, because
        the inliner hands on a path in two ways.  What XLA itself makes
        inside an inlined piece (fusions, copies) carries the CALL's
        path and nothing else: the caller enters part and sub-part
        around the call.  An operation inside a loop or a branch of a
        piece keeps the path it has INSIDE the piece and loses the
        caller's: the piece enters role, part and sub-part itself."""
        chosen, picked = [], []
        for i, lp in enumerate(layers):
            of_mlp = _mlp_leaves(lp)
            of_attn = {n: w for n, w in lp.items() if n not in of_mlp}
            x, self.flat, taken = attention(x, of_attn, self.layer(i))
            with jax.named_scope("mlp"):
                x, ids = mlp(x, of_mlp)
            chosen.append(ids)
            picked.append(taken)
        return x, {
            "experts": _stack_experts(chosen, cfg),
            "selection": jnp.stack(picked, axis=1),
        }

    def stacked(self) -> Dict:
        return {
            n: self.flat[n].reshape(shape)
            for n, shape in self._shapes.items()
        }


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # c [L, blocks, bs, rank]; kpe [.., bs * rope / 128, 128]; ik
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    cfg: DeepSeekV32Config,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Prefill C prompt positions of ONE sequence: the latent row and
    the index key into its paged blocks, every row's selection taken
    inside the causal mask from the index keys cached so far (the
    chunk's own included), keys and values of the positions the chunk
    may see rebuilt from their rows, attention in multi-head form under
    the selection.  Padded tail positions write ahead of the prompt into
    the sequence's own reservation: decode overwrites each position
    before a query can see it.  Returns (logits [1, C, vocab], pool,
    {"experts": [C, expert layers, k], "selection": [C, layers,
    words]})."""
    from dlrover_tpu.ops.paged_attention import (
        exact_topk_mask,
        gather_index_keys,
        latent_prefill_attention,
        paged_kernel_backend,
        prefill_index_scores,
    )

    dt, dr, rank = cfg.dtype, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    c = tokens.shape[1]
    leaves = _Leaves(pool)
    bs, mb = leaves.block_size, block_table.shape[0]
    backend = paged_kernel_backend()
    positions = start_pos + jnp.arange(c)
    x = _embed(params, tokens, cfg)[0]  # [C, D]
    with jax.named_scope("attn"), jax.named_scope("latent"):
        cos, sin = _rope_tables(cfg, positions)
    # the chunk sees ``start_pos + C`` cached positions, the table holds
    # ``mb * bs``: scores, selection, decompression and attention run
    # over the narrowest of a few static widths that holds what it sees
    widths = _prefill_widths(mb * bs, bs)
    bucket = jnp.searchsorted(
        jnp.asarray(widths), jnp.minimum(start_pos + c, mb * bs)
    ).astype(jnp.int32)

    def attend(width, q, qi, w, keys, c_kv, k_pe, w_uk, w_uv):
        with jax.named_scope("indexer"):
            taken = exact_topk_mask(
                prefill_index_scores(qi, w, keys[:width], start_pos),
                min(cfg.index_topk, width),
            )
        with jax.named_scope("latent"):
            c_kv, k_pe = c_kv[:width], k_pe[:width]
            k = jnp.concatenate([
                jnp.einsum(
                    "sc,hdc->hsd", c_kv, w_uk,
                    preferred_element_type=jnp.float32,
                ).astype(dt),
                jnp.broadcast_to(k_pe[None], (w_uk.shape[0], width, dr)),
            ], axis=-1)
            v = jnp.einsum(
                "sc,hcd->hsd", c_kv, w_uv, preferred_element_type=jnp.float32
            ).astype(dt)
            return latent_prefill_attention(
                q, k, v, taken, start_pos, start_pos + c,
                cfg.softmax_scale, backend,
            ), pack_selection(taken, cfg.selection_words)

    # a layer's attention, one jitted piece a sub-scope: each is traced
    # for the first layer and found for the others (module docstring);
    # what differs by layer is an argument, what does not is closed over
    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def write_rows(x, lp, kv):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_pe, c_q = _queries(h, lp, cfg)
        q = jnp.concatenate(
            [q_nope, _rotate(q_pe, cos[:, None], sin[:, None])], axis=-1
        )
        c_kv, k_pe = _latent_row(h, lp, cfg, cos, sin)
        kv = kv.write_leaf_run(
            "c", c_kv, block_table, start_pos
        ).write_leaf_run("kpe", k_pe, block_table, start_pos)
        return h, c_q, q, kv.paged

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    @jax.named_scope("indexer")
    def index(h, c_q, lp, kv):
        qi, ik, w = _indexer_inputs(h, c_q, lp, cfg)
        qi = _rotate_lead(qi, cos[:, None], sin[:, None], dr)
        kv = kv.write_leaf_run(
            "ik", _rotate_lead(ik, cos, sin, dr), block_table, start_pos
        )
        keys = gather_index_keys(
            kv.paged["ik"], kv.tables(block_table), cfg.index_head_dim
        )
        return qi, w, keys, kv.paged

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    def attend_width(q, qi, w, keys, lp, kv):
        # the sequence's rows by position, ONCE: a branch that took
        # the pool itself had it copied into it
        table = kv.tables(block_table)
        w_uk, w_uv = _kv_up(lp, cfg)
        return lax.switch(
            bucket,
            [partial(attend, width) for width in widths],
            q, qi, w, keys,
            kv.paged["c"][table].reshape(mb * bs, rank),
            kv.paged["kpe"][table].reshape(mb * bs, dr),
            w_uk.astype(dt), w_uv.astype(dt),
        )

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def project(x, attn, lp):
        return x + _proj(attn.reshape(c, -1), lp["wo"], dt)

    def attention(x, lp, kv):
        with jax.named_scope("attn"), jax.named_scope("latent"):
            h, c_q, q, paged = write_rows(x, lp, kv)
        with jax.named_scope("attn"), jax.named_scope("indexer"):
            qi, w, keys, paged = index(h, c_q, lp, kv._replace(paged=paged))
        with jax.named_scope("attn"):
            attn, taken = attend_width(
                q, qi, w, keys, lp, kv._replace(paged=paged)
            )
            with jax.named_scope("latent"):
                x = project(x, attn, lp)
        return x, paged, taken

    @jax.jit
    @jax.named_scope("prefill")
    @jax.named_scope("mlp")
    def mlp(x, lp):
        return _mlp(x, lp, cfg, backend)

    x, rows = leaves.walk(params["layers"], x, attention, mlp, cfg)
    return (
        _logits(x[None], params, cfg), {**pool, **leaves.stacked()}, rows
    )


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # c [L, blocks, bs, rank]; kpe [.., bs * rope / 128, 128]; ik
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: DeepSeekV32Config,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One continuous-batching decode step: every active lane writes
    its latent row and index key, scores its index query against every
    index key it has cached — read from the blocks the lane holds, in
    place (``ops/paged_attention.gather_index_keys`` hands
    ``decode_index_scores`` a view of the leaf under the Pallas
    backend, and gathers every entry of every table under ``jnp``) —,
    takes the exact top ``index_topk``
    positions (all of them below it) and attends over those rows alone
    in ABSORBED form — the row is key and value of every head —,
    reading the blocks it holds itself under the selection's mask
    (``ops/paged_attention.latent_decode_selection``: the picked rows
    are gathered only under a table many times ``index_topk`` wide).  An
    inactive lane writes to the null block and reads nothing.
    Shapes depend on (lanes, pool geometry) only: compiled once.
    Returns (logits [B, vocab], pool, {"experts": [B, expert layers,
    k], "selection": [B, layers, words]})."""
    from dlrover_tpu.ops.paged_attention import (
        decode_index_scores,
        gather_index_keys,
        latent_decode_attention,
        latent_decode_selection,
        paged_kernel_backend,
    )

    dt, dr = cfg.dtype, cfg.qk_rope_head_dim
    n = tokens.shape[0]
    leaves = _Leaves(pool)
    bs, mb = leaves.block_size, block_tables.shape[1]
    backend = paged_kernel_backend()
    x = _embed(params, tokens, cfg)  # [B, D]
    with jax.named_scope("attn"), jax.named_scope("latent"):
        cos, sin = _rope_tables(cfg, positions)
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
        held = jnp.where(active, positions + 1, 0)  # what attention reads
        n_sel = min(cfg.index_topk, mb * bs)

    # a layer's attention in jitted pieces, as the chunk's
    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def write_rows(x, lp, kv):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_pe, c_q = _queries(h, lp, cfg)
        q_c = _per_head(q_nope, _kv_up(lp, cfg)[0], dt)
        q_pe = _rotate(q_pe, cos[:, None], sin[:, None])
        c_kv, k_pe = _latent_row(h, lp, cfg, cos, sin)
        kv = kv.write_leaf_rows("c", c_kv, blk, off)
        kv = kv.write_leaf_rows("kpe", k_pe, blk, off)
        return h, c_q, q_c, q_pe, kv.paged

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("attn")
    @jax.named_scope("indexer")
    def index(h, c_q, lp, kv):
        tables = kv.tables(block_tables)
        qi, ik, w = _indexer_inputs(h, c_q, lp, cfg)
        qi = _rotate_lead(qi, cos[:, None], sin[:, None], dr)
        kv = kv.write_leaf_rows(
            "ik", _rotate_lead(ik, cos, sin, dr), blk, off
        )
        keys = gather_index_keys(kv.paged["ik"], tables, cfg.index_head_dim)
        # a mask over the positions where attention reads a lane's
        # blocks itself, the rows to gather beside it under a table
        # much wider than what is picked
        sel = latent_decode_selection(
            decode_index_scores(qi, w, keys, seq_lens), n_sel, tables
        )
        return sel, pack_selection(sel.taken, cfg.selection_words), kv.paged

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("attn")
    @jax.named_scope("latent")
    def attend(x, q_c, q_pe, sel, lp, kv):
        latent = latent_decode_attention(
            q_c, q_pe, kv.paged["c"], kv.paged["kpe"],
            kv.tables(block_tables), held, sel, cfg.softmax_scale, backend,
        )
        attn = _per_head(latent, _kv_up(lp, cfg)[1], dt)
        return x + _proj(attn.reshape(n, -1), lp["wo"], dt)

    def attention(x, lp, kv):
        with jax.named_scope("attn"), jax.named_scope("latent"):
            h, c_q, q_c, q_pe, paged = write_rows(x, lp, kv)
        with jax.named_scope("attn"), jax.named_scope("indexer"):
            sel, taken, paged = index(h, c_q, lp, kv._replace(paged=paged))
        with jax.named_scope("attn"), jax.named_scope("latent"):
            x = attend(x, q_c, q_pe, sel, lp, kv._replace(paged=paged))
        return x, paged, taken

    @jax.jit
    @jax.named_scope("decode")
    @jax.named_scope("mlp")
    def mlp(x, lp):
        return _mlp(x, lp, cfg, backend)

    x, rows = leaves.walk(params["layers"], x, attention, mlp, cfg)
    return (
        _logits(x[:, None], params, cfg)[:, 0],
        {**pool, **leaves.stacked()},
        rows,
    )
