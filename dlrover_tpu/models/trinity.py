"""Trinity-Large's decoder (``model_type`` ``afmoe``) for the serving
plane: layers of two kinds of attention over a cache that knows which
is which, and one chip's share of a layer's routed experts beside a
shared expert.

Published description: ``arcee-ai/Trinity-Large-Preview``
``config.json``.  One block, with ``t`` a query position and ``s`` a
key position (what the config has no key for is marked *assumed*; the
configuration file of the benchmark says the same under ``assumed``):

- embedding: ``x0 = Embed[token] * sqrt(hidden_size)`` (``mup_enabled``;
  the factor *assumed*).
- attention: ``h = RMSNorm_in(x)``; ``q = W_q h`` (heads x head_dim),
  ``k = W_k h``, ``v = W_v h`` (kv heads x head_dim), ``g = W_g h``
  (heads x head_dim); RMSNorm over ``head_dim`` of every head of ``q``
  and of ``k`` (*assumed*).  ``layer_types[i]`` is ``full_attention``
  iff ``(i + 1) % global_attn_every_n_layers == 0``:

  - a WINDOW layer rotates ``q`` and ``k`` (RoPE, split-half pairs,
    ``rope_theta``) and reads the keys ``t - sliding_window < s <= t``;
  - a FULL layer reads every ``s <= t`` and rotates NOTHING (*assumed*:
    the family's description, local layers rotary, global layers
    without positions).

  ``o = softmax(q . k / sqrt(head_dim)) v``, grouped-query; ``x +=
  RMSNorm_post_attn(W_o (o * sigmoid(g)))`` — the elementwise output
  gate (its form *assumed*).
- ``x += RMSNorm_post_mlp(MLP(RMSNorm_pre_mlp(x)))``: four norms a
  block (sandwich norm).
- layers ``< num_dense_layers``: ``MLP(h) = W_down(silu(W_gate h) *
  W_up h)`` of width ``intermediate_size``.
- the other layers: ``s = sigmoid(W_r h)`` in float32 over all
  ``num_experts``; the top ``num_experts_per_tok`` of ``s + b`` (``b``
  a float32 selection bias, *assumed* to enter the selection only;
  equal scores lowest id first); weights ``w_e = s_e / (sum of the
  chosen s + 1e-20) * route_scale``; ``MLP(h) = Shared(h) + sum_e w_e
  Expert_e(h)``, each a SwiGLU of width ``moe_intermediate_size``
  (``Shared``: times ``num_shared_experts``).  No capacity, no drop.
- final RMSNorm, untied head.

**The share.**  ``held_experts`` of the ``num_experts`` live here
(``first_expert ..``): the router keeps its width and its top-k, the
layer computes the assignments that fall on its own experts
(``ops/grouped_gemm.expert_ffn``) and what the absent experts would
add is left out — that partial sum goes on to the next layer.  Nothing
here stands in for the other chips or their exchange.

**The cache.**  ``layer_windows()`` declares the window layers to
``rl/kv_cache.paged_cache_config``: the pool then holds ``k``, ``v``
for the full layers under the sequence's table and ``wk``, ``wv`` for
the window layers under a second table a lane, a ring of ``W`` entries
(``rl/kv_cache.WindowBlocks``).  A step program receives both tables
side by side, ``[..., max_blocks + W]`` with ``max_blocks =
ceil(max_seq_len / block_size)`` — the scheduler checks that its
``max_seq_len`` is this config's.  The layers differ (dense and expert
MLPs, two kinds of attention), so they are UNROLLED, each with its own
leaves (``params["layers"]`` is a tuple of dicts: no stack is sliced),
and each pool rides through them flat, ``[layers of the kind *
blocks, ...]``, written in place.

There is no training path.
"""

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.models.keye_vl2 import _head_norm, _proj, _rope_tables
from dlrover_tpu.models.llama import _apply_rope_rows, apply_rope, rms_norm
from dlrover_tpu.ops.grouped_gemm import expert_ffn


@dataclass(frozen=True)
class TrinityConfig:
    """The published ``config.json`` keys that shape the decoder, under
    their own names; ``first_expert`` / ``held_experts`` say which of
    the ``num_experts`` this chip holds (all of them by default), and
    ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 200192
    hidden_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e4
    first_expert: int = 0
    held_experts: Optional[int] = None
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", self.num_experts)
        for ok, what in (
            (self.score_func == "sigmoid", "score_func other than sigmoid"),
            (self.route_norm, "route_norm false"),
            (self.n_group == 1 and self.topk_group == 1,
             "a group limit on the router (n_group / topk_group > 1)"),
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
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """A window a layer, ``None`` where the layer keeps every
        position (``layer_types``: full iff ``(i + 1) %
        global_attn_every_n_layers == 0``)."""
        n = self.global_attn_every_n_layers
        return tuple(
            None if (i + 1) % n == 0 else self.sliding_window
            for i in range(self.num_hidden_layers)
        )

    def per_token_outputs(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        """What a step program returns for every row it computes,
        beside the logits: the experts chosen, ids among ALL of the
        router's, for the expert layers."""
        return {
            "experts": (
                (self.n_expert_layers, self.num_experts_per_tok), "int32"
            )
        }

    @staticmethod
    def tiny(**overrides) -> "TrinityConfig":
        """Test-sized: one dense layer and a whole period of expert
        layers, a window below its sequences, 2 of 8 experts held."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=5,
            num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            held_experts=2, sliding_window=32, max_seq_len=128,
        )
        base.update(overrides)
        return TrinityConfig(**base)


# ---------------------------------------------------------------- params

_ATTN_LEAVES = ("wq", "wk", "wv", "wg")
# of the serving copy, which holds the four input projections fused;
# the router and its bias stay float32 (they decide a discrete choice)
_SERVING_MATMUL_LEAVES = (
    "wqkvg", "wo", "mlp_gate", "mlp_up", "mlp_down", "shared_gate",
    "shared_up", "shared_down", "w_gate", "w_up", "w_down",
)


def layer_shapes(cfg: TrinityConfig, layer: int) -> Dict:
    """``{name: shape}`` of ONE layer's leaves: a dense MLP below
    ``num_dense_layers``, else router, bias, shared expert and the HELD
    experts' matrices."""
    d, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {
        "attn_norm": (d,), "post_attn_norm": (d,), "mlp_norm": (d,),
        "post_mlp_norm": (d,), "q_norm": (hd,), "k_norm": (hd,),
        "wq": (d, nh * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
        "wg": (d, nh * hd), "wo": (nh * hd, d),
    }
    if layer < cfg.num_dense_layers:
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


def param_shapes(cfg: TrinityConfig) -> Dict:
    d, v = cfg.hidden_size, cfg.vocab_size
    return {
        "embed": (v, d),
        "layers": tuple(
            layer_shapes(cfg, i) for i in range(cfg.num_hidden_layers)
        ),
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def init_params(key, cfg: TrinityConfig) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)`` (the
    embedding ``hidden ** -0.5``, which the mup multiplier brings to 1),
    norm weights 1, the selection bias ``0.1 normal``."""
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
            fan_in = shape[-1] if name == "embed" else shape[-2]
            leaf = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@jax.jit
def _cast_and_fuse(work, dtype_of):
    dt = dtype_of.dtype

    def layer(lp):
        out = {n: w.astype(dt) for n, w in lp.items() if n not in _ATTN_LEAVES}
        if all(n in lp for n in _ATTN_LEAVES):
            out["wqkvg"] = jnp.concatenate(
                [lp[n].astype(dt) for n in _ATTN_LEAVES], axis=-1
            )
        return out

    return {
        **{n: w.astype(dt) for n, w in work.items() if n != "layers"},
        "layers": tuple(layer(lp) for lp in work["layers"]),
    }


def serving_params(params: Dict, cfg: TrinityConfig) -> Dict:
    """The tree the serving programs compute on: the embedding, the
    head and every matrix in ``cfg.dtype``, ``wq``, ``wk``, ``wv``,
    ``wg`` as ONE leaf ``wqkvg`` (one matmul a layer reads it in
    place); router, bias and norms as given.  One jitted program over
    the leaves that need either; a leaf that needs neither stays the
    caller's array, and a tree that is already a serving copy comes
    back as it is."""
    dt = jnp.dtype(cfg.dtype)

    def todo(lp):
        names = [
            n for n in lp
            if n in _SERVING_MATMUL_LEAVES and lp[n].dtype != dt
        ]
        return names + ([] if "wqkvg" in lp else list(_ATTN_LEAVES))

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


def _attn_inputs(h, lp, cfg: TrinityConfig):
    """``h [..., D]`` -> q ``[..., H, hd]`` and k ``[..., KV, hd]``
    (head-normalised, before any rotation), v ``[..., KV, hd]`` and the
    output gate's logits ``[..., H * hd]``, all in the compute dtype."""
    dt = cfg.dtype
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    if "wqkvg" in lp:
        q, k, v, g = jnp.split(
            _proj(h, lp["wqkvg"], dt),
            (nh * hd, (nh + nkv) * hd, (nh + 2 * nkv) * hd), axis=-1,
        )
    else:
        q, k, v, g = (_proj(h, lp[n], dt) for n in _ATTN_LEAVES)
    lead = h.shape[:-1]
    return (
        _head_norm(q.reshape(lead + (nh, hd)), lp["q_norm"],
                   cfg.rms_norm_eps),
        _head_norm(k.reshape(lead + (nkv, hd)), lp["k_norm"],
                   cfg.rms_norm_eps),
        v.reshape(lead + (nkv, hd)),
        g,
    )


def _attn_output(x, attn, g, lp, cfg: TrinityConfig):
    """``x + RMSNorm_post_attn(W_o (attn * sigmoid(g)))``; ``attn``
    ``[..., H * hd]`` as ``g``."""
    gated = (
        attn.astype(jnp.float32) * jax.nn.sigmoid(g.astype(jnp.float32))
    ).astype(cfg.dtype)
    out = _proj(gated, lp["wo"], cfg.dtype)
    return x + rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps)


def _swiglu(h, w_gate, w_up, w_down, dt):
    gate = jnp.matmul(h, w_gate.astype(dt), preferred_element_type=jnp.float32)
    up = jnp.matmul(h, w_up.astype(dt), preferred_element_type=jnp.float32)
    return _proj((jax.nn.silu(gate) * up).astype(dt), w_down, dt)


def _route(x, lp, cfg: TrinityConfig, renorm_eps: float = 1e-20):
    """The router on ``x [N, D]``: float32 norm, float32 logits over
    every expert at full precision, ``s = sigmoid``, the top-k of ``s +
    b`` and the chosen experts' ``s`` normalised (``renorm_eps`` beside
    their sum: ``models/lfm2_moe.py``'s is 1e-6) to ``route_scale``.
    -> (h' [N, D] in the compute dtype, ids [N, k] int32 among ALL
    experts, weights [N, k] float32)."""
    xf = x.astype(jnp.float32)
    hf = xf * lax.rsqrt(
        jnp.mean(xf * xf, -1, keepdims=True) + cfg.rms_norm_eps
    ) * lp["mlp_norm"]
    s = jax.nn.sigmoid(jnp.matmul(
        hf, lp["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    ))
    _, ids = lax.top_k(s + lp["router_bias"], cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, ids, -1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + renorm_eps)
    return hf.astype(cfg.dtype), ids.astype(jnp.int32), w * cfg.route_scale


def _mlp(x, lp, cfg: TrinityConfig, backend: str = "jnp"):
    """``x [N, D]`` -> (``x + RMSNorm_post_mlp(MLP(RMSNorm_pre_mlp(x)))``,
    the experts chosen ``[N, k]`` or None for a dense layer)."""
    dt = cfg.dtype
    if "router" not in lp:
        h = rms_norm(x[None], lp["mlp_norm"], cfg.rms_norm_eps)[0]
        y, ids = _swiglu(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"],
                         dt), None
    else:
        h, ids, w = _route(x, lp, cfg)
        y = _swiglu(
            h, lp["shared_gate"], lp["shared_up"], lp["shared_down"], dt
        )
        y = y + expert_ffn(
            h, ids, w, lp["w_gate"].astype(dt), lp["w_up"].astype(dt),
            lp["w_down"].astype(dt), 0, cfg.num_experts, backend,
            first_expert=cfg.first_expert, held=cfg.held_experts,
        ).astype(dt)
    y = rms_norm(y[None], lp["post_mlp_norm"], cfg.rms_norm_eps)[0]
    return x + y, ids


@jax.named_scope("head")
def _logits(x, params, cfg: TrinityConfig):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


@jax.named_scope("embed")
def _embed(params, tokens, cfg: TrinityConfig):
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.mup_enabled:
        x = (x.astype(jnp.float32) * cfg.hidden_size ** 0.5).astype(cfg.dtype)
    return x


def _kind_scope(window: Optional[int]):
    """The device scope of a layer's kind, entered INSIDE ``attn``
    (``observability/events.py`` ``DEVICE_SCOPES``)."""
    if window is None:
        return jax.named_scope("full")
    return jax.named_scope("window")


def _stack_experts(ids, cfg: TrinityConfig):
    """The expert layers' choices ``[rows, expert layers, k]``."""
    return jnp.stack([i for i in ids if i is not None], axis=1)


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: TrinityConfig,
            return_experts: bool = False):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, no cache (``return_experts``: and the experts
    chosen, ``[B, T, expert layers, k]``).  For tests and as the serving
    worker's ``forward_fn``; dense in ``T x T``."""
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
    for lp, window in zip(params["layers"], cfg.layer_windows()):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v, g = _attn_inputs(h, lp, cfg)
        visible = causal
        if window is not None:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            visible = causal & (
                positions[None] > positions[:, None] - window
            )
        att = jnp.einsum(
            "btkgd,bskd->bkgts", q.reshape(bsz, t, nkv, nh // nkv, hd), k,
            preferred_element_type=jnp.float32,
        ) * hd ** -0.5
        att = jax.nn.softmax(jnp.where(visible, att, NEG_INF), -1)
        out = jnp.einsum(
            "bkgts,bskd->btkgd", att.astype(dt), v,
            preferred_element_type=jnp.float32,
        ).astype(dt)
        x = _attn_output(x, out.reshape(bsz, t, nh * hd), g, lp, cfg)
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


class _Pools:
    """The two pools of a step program, flat, and the walk over the
    layers: layer ``i`` is the ``j``-th of its kind and addresses its
    blocks at ``j * blocks of the kind``
    (``ops/paged_attention.LayerPool``)."""

    def __init__(self, pool: Dict, cfg: TrinityConfig):
        self._shapes = {n: pool[n].shape for n in ("k", "v", "wk", "wv")}
        self._flat = {
            n: pool[n].reshape((-1,) + pool[n].shape[2:])
            for n in self._shapes
        }
        self._seen = {"full": 0, "window": 0}
        self.block_size = pool["k"].shape[2]
        self.max_blocks = -(-cfg.max_seq_len // self.block_size)

    def layer(self, window: Optional[int]):
        from dlrover_tpu.ops.paged_attention import LayerPool

        kind = "full" if window is None else "window"
        k, v = ("k", "v") if window is None else ("wk", "wv")
        j = self._seen[kind]
        self._seen[kind] += 1
        return LayerPool(
            self._flat[k], self._flat[v],
            jnp.int32(j * self._shapes[k][1]), jnp.int32(j),
        )

    def keep(self, window: Optional[int], kv):
        k, v = ("k", "v") if window is None else ("wk", "wv")
        self._flat[k], self._flat[v] = kv.k, kv.v

    def stacked(self) -> Dict:
        return {
            n: self._flat[n].reshape(shape)
            for n, shape in self._shapes.items()
        }


def _split_tables(block_tables, pools: _Pools):
    """A lane's two tables from the row the scheduler uploads: the
    sequence's ``[..., max_blocks]``, then its ring over the window
    layers' blocks."""
    mb = pools.max_blocks
    if block_tables.shape[-1] <= mb:
        raise ValueError(
            f"a table of {block_tables.shape[-1]} entries holds no ring "
            f"behind the sequence's {mb} (max_seq_len / block_size): the "
            "scheduler's max_seq_len must be this config's"
        )
    return block_tables[..., :mb], block_tables[..., mb:]


def _key_view_blocks(ring_blocks: int, block_size: int) -> int:
    """Entries of the position-ordered view of a ring that a prefill
    chunk's kernel reads: the ring, rounded up to the kernel's key block
    where it is longer than one."""
    from dlrover_tpu.ops.paged_kernels import CHUNK_KEY_BLOCK as bk

    rows = ring_blocks * block_size
    if rows <= bk or bk % block_size:
        return ring_blocks
    return -(-rows // bk) * bk // block_size


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # k, v [Lf, blocks, bs, KV, D]; wk, wv [Lw, wblocks, ...]
    block_table: jnp.ndarray,  # [max_blocks + W] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    cfg: TrinityConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Prefill C prompt positions of ONE sequence: K and V into the
    blocks of each layer's kind, attention over the keys its kind reads
    — every cached position on a full layer, the window's on a window
    layer, whose table is read in position order from the block that
    holds the window's edge.  Padded tail positions write ahead into the
    sequence's own blocks.  Returns (logits [1, C, vocab], pool,
    {"experts": [C, expert layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        gather_heads_by_position,
        paged_chunk_attention,
        paged_kernel_backend,
        window_table_view,
    )

    _, c = tokens.shape
    pools = _Pools(pool, cfg)
    bs, mb = pools.block_size, pools.max_blocks
    table, ring = _split_tables(block_table, pools)
    w_blocks = ring.shape[0]
    backend = paged_kernel_backend()
    positions = start_pos + jnp.arange(c)
    x = _embed(params, tokens, cfg)
    with jax.named_scope("attn"):
        cos, sin = _rope_tables(cfg.rope_theta, cfg.head_dim, positions)
        blk_idx = positions // bs
        # a position past the sequence's table goes to the null block;
        # the ring holds every position of the chunk
        blocks = {
            "full": jnp.where(
                blk_idx < mb, table[jnp.minimum(blk_idx, mb - 1)], 0
            ),
            "window": ring[blk_idx % w_blocks],
        }
        first_block = jnp.maximum(
            start_pos - cfg.sliding_window + 1, 0
        ) // bs
        views = {
            "full": (table, jnp.int32(0)),
            "window": (
                window_table_view(
                    ring, first_block, _key_view_blocks(w_blocks, bs)
                ),
                first_block * bs,
            ),
        }
    chosen = []
    for lp, window in zip(params["layers"], cfg.layer_windows()):
        kind = "full" if window is None else "window"
        kv = pools.layer(window)
        with jax.named_scope("attn"), _kind_scope(window):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v, g = _attn_inputs(h, lp, cfg)
            if window is not None:
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            kv = kv.write_rows(k[0], v[0], blocks[kind], positions % bs)
            view, key0 = views[kind]
            attn = paged_chunk_attention(
                q[0],
                gather_heads_by_position(kv.k, kv.tables(view)),
                gather_heads_by_position(kv.v, kv.tables(view)),
                start_pos, key0, window, backend,
                name=f"paged_prefill_{kind}",
            )
            x = _attn_output(x, attn.reshape(1, c, -1), g, lp, cfg)
        pools.keep(window, kv)
        with jax.named_scope("mlp"):
            y, ids = _mlp(x[0], lp, cfg, backend)
            x = y[None]
        chosen.append(ids)
    return (
        _logits(x, params, cfg),
        {**pool, **pools.stacked()},
        {"experts": _stack_experts(chosen, cfg)},
    )


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # k, v [Lf, blocks, bs, KV, D]; wk, wv [Lw, wblocks, ...]
    block_tables: jnp.ndarray,  # [B, max_blocks + W] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: TrinityConfig,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One continuous-batching decode step: every active lane writes
    its K and V into the blocks of each layer's kind and attends over
    every cached position on a full layer and over the live window,
    from the block that holds its edge, on a window layer.  An inactive
    lane writes to the null blocks and reads one masked row.  Shapes
    depend on (lanes, pool geometry) only: compiled once.  Returns
    (logits [B, vocab], pool, {"experts": [B, expert layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        paged_decode_attention,
        paged_kernel_backend,
        window_table_view,
    )

    n = tokens.shape[0]
    pools = _Pools(pool, cfg)
    bs, mb = pools.block_size, pools.max_blocks
    tables, rings = _split_tables(block_tables, pools)
    w_blocks = rings.shape[1]
    backend = paged_kernel_backend()
    x = _embed(params, tokens, cfg)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        cos, sin = _rope_tables(cfg.rope_theta, cfg.head_dim, positions)
        blk_idx = positions // bs

        def entry(tbl, idx):
            return jnp.take_along_axis(tbl, idx[:, None], axis=1)[:, 0]

        # a lane that does not decode, or runs past its table, writes
        # to the null block
        blocks = {
            "full": jnp.where(
                active & (blk_idx < mb),
                entry(tables, jnp.minimum(blk_idx, mb - 1)), 0,
            ),
            "window": jnp.where(active, entry(rings, blk_idx % w_blocks), 0),
        }
        off = jnp.where(active, positions % bs, 0)
        edge = jnp.maximum(positions - cfg.sliding_window + 1, 0)
        first_block = edge // bs
        # what a kernel is told of each kind: the table in position
        # order, how many of its positions are cached, and the first
        # that counts
        reads = {
            "full": (tables, jnp.where(active, positions + 1, 1), None),
            "window": (
                jnp.where(
                    active[:, None], window_table_view(rings, first_block), 0
                ),
                jnp.where(active, positions + 1 - first_block * bs, 1),
                jnp.where(active, edge - first_block * bs, 0),
            ),
        }
    chosen = []
    for lp, window in zip(params["layers"], cfg.layer_windows()):
        kind = "full" if window is None else "window"
        kv = pools.layer(window)
        with jax.named_scope("attn"), _kind_scope(window):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v, g = _attn_inputs(h, lp, cfg)
            if window is not None:
                q = _apply_rope_rows(q, cos, sin)
                k = _apply_rope_rows(k, cos, sin)
            kv = kv.write(k[:, 0], v[:, 0], blocks[kind], off)
            view, lens, first = reads[kind]
            attn = paged_decode_attention(
                q[:, 0], kv.k, kv.v, kv.tables(view), lens, backend,
                first=first, name=f"paged_{kind}_decode",
            )
            x = _attn_output(x, attn.reshape(n, 1, -1), g, lp, cfg)
        pools.keep(window, kv)
        with jax.named_scope("mlp"):
            y, ids = _mlp(x[:, 0], lp, cfg, backend)
            x = y[:, None]
        chosen.append(ids)
    return (
        _logits(x, params, cfg)[:, 0],
        {**pool, **pools.stacked()},
        {"experts": _stack_experts(chosen, cfg)},
    )
