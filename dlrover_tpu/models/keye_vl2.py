"""Keye-VL-2.0's language decoder for the serving plane: sparse experts
in every layer and a learned top-k indexer inside attention.

Published description: ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` ``config.json``
(a Qwen3-MoE text decoder plus ``sa_config``, a DeepSeek-Sparse-
Attention indexer; the vision tower is not here).  One block, with ``h
= RMSNorm(x)``, ``t`` a query position and ``s <= t`` a key position:

- attention input: ``q = W_q h`` (heads x head_dim), ``k = W_k h``, ``v
  = W_v h`` (kv heads x head_dim); RMSNorm of every head of ``q`` and of
  ``k`` over ``head_dim``; RoPE (split-half pairs) on all of it.  Text
  positions only: the three M-RoPE components are equal, which is the
  plain 1-D rotation.
- indexer: ``qi_t = W_iq h_t`` (index heads x index dim), ``ik_s =
  LayerNorm(W_ik h_s)`` — ONE index key a token —, ``w_t = W_iw h_t *
  heads ** -0.5 * dim ** -0.5``; RoPE on both; ``I[t, s] = sum_j w[t, j]
  * relu(qi[t, j] . ik[s])`` in float32; ``S_t`` = the ``topk``
  positions ``s <= t`` of largest ``I[t, s]`` (equal scores lowest
  position first), all of them while ``t < topk``.  The selection is
  EXACT.
- ``o_t = softmax over s in S_t of (q_t . k_s / sqrt(head_dim)) v_s``,
  grouped-query; ``x += W_o o``.
- experts, every layer: ``g = softmax(W_r h')`` in float32 over all
  experts (``h' = RMSNorm(x)``), the top-k of it, weights renormalised
  to 1; ``x += sum_e weight_e * W_down^e(silu(W_gate^e h') * W_up^e
  h')``.  No shared expert, no capacity: every assignment is computed
  (tokens sorted by expert, one ragged matmul a projection —
  ``ops/grouped_gemm``), the same layer at 16 decode rows and at a
  2048-row prefill chunk.
- final RMSNorm, untied head.

What the serving plane needs of a model (``rl/scheduler.py`` says what
it takes) is here in the shape ``models/llama.py`` gives it, with two
declarations of its own: ``paged_leaves()`` — the index key lives in
the SAME blocks as K and V, a third paged leaf ``ik [L, blocks,
block_size * index_dim / 128, 128]`` (it has positions: shared by
prefix, shipped and freed with its block; in rows of 128 lanes,
``paged_leaf_rows()``, which the decode step's index scores are read
from in place) — and ``per_token_outputs()`` — each step
program also returns the experts it sent every position to, ``[rows,
layers, k]``, which a reply carries so that a float32 reference can be
held to the served routing.  There is no training path, and the
expert-parallel share of a layer is not here: this path holds every
expert.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models.llama import (
    _apply_rope_rows,
    apply_rope,
    qkv_heads,
    rms_norm,
    serving_copy,
)
from dlrover_tpu.ops.grouped_gemm import expert_ffn


@dataclass(frozen=True)
class KeyeVL2Config:
    """The published ``config.json`` keys that shape the decoder, under
    their own names (``sa_config``'s flattened: ``indexer_head_dim``,
    ``indexer_num_heads``, ``topk``); ``max_seq_len`` and ``dtype`` are
    the program's."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    indexer_head_dim: int = 64
    indexer_num_heads: int = 16
    topk: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if not self.norm_topk_prob:
            raise ValueError(
                "norm_topk_prob false is not modelled: the chosen "
                "experts' weights are renormalised to 1"
            )

    # what the serving scheduler reads off a model config
    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    def paged_leaves(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per layer and TOKEN, beside K and V: ``{leaf: (shape,
        dtype)}``.  One index key a token, in the compute dtype."""
        return {"ik": ((self.indexer_head_dim,), self.dtype)}

    def paged_leaf_rows(self) -> Dict[str, int]:
        """The index keys lie in rows of (at least) the device's 128
        lanes, whole tokens' a row (two at 64): the rows the decode
        step's scores are read from in place."""
        return {"ik": max(128, self.indexer_head_dim)}

    def per_token_outputs(self) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        """What a step program returns for every row it computes,
        beside the logits: ``{name: (shape after the row axis,
        dtype)}``."""
        return {
            "experts": (
                (self.num_hidden_layers, self.num_experts_per_tok), "int32"
            )
        }

    @staticmethod
    def tiny(**overrides) -> "KeyeVL2Config":
        """Test-sized config whose ``topk`` is below its sequences."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            indexer_head_dim=8, indexer_num_heads=2, topk=16,
            max_seq_len=64,
        )
        base.update(overrides)
        return KeyeVL2Config(**base)


# ---------------------------------------------------------------- params

# of the serving copy, which holds ``wq``, ``wk``, ``wv`` fused; the
# router stays as given (float32: its logits decide a discrete choice)
_SERVING_MATMUL_LEAVES = (
    "wqkv", "wo", "wi_q", "wi_k", "wi_w", "w_gate", "w_up", "w_down"
)


def param_shapes(cfg: KeyeVL2Config) -> Dict:
    """``{name: shape}`` of the parameter tree, layers stacked on a
    leading axis, an expert's matrices on the next."""
    d, L, v = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    e, f = cfg.num_experts, cfg.moe_intermediate_size
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


def init_params(key, cfg: KeyeVL2Config) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, norm
    weights 1, the index key's LayerNorm bias 0."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name.endswith("_bias"):
            leaf = jnp.zeros(shape, jnp.float32)
        elif "norm" in name:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            fan_in = shape[-1] if name == "embed" else shape[-2]
            leaf = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            ) * fan_in ** -0.5
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def serving_params(params: Dict, cfg: KeyeVL2Config) -> Dict:
    """The tree the serving programs compute on: the embedding, the
    head, the projections and the experts in ``cfg.dtype`` with ``wq``,
    ``wk``, ``wv`` as ONE leaf ``wqkv`` (``llama.serving_copy``); the
    router and the norms as given.  A tree published in the compute
    dtype keeps every leaf but the fused one."""
    return serving_copy(params, cfg.dtype, _SERVING_MATMUL_LEAVES)


# ---------------------------------------------------------------- pieces


def _proj(a, w, dt):
    return jnp.matmul(
        a, w.astype(dt), preferred_element_type=jnp.float32
    ).astype(dt)


def _rope_tables(theta: float, dim: int, positions):
    """[S] -> cos/sin [S, dim / 2] (float32)."""
    half = dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(x, cos, sin):
    """``x [..., dim]`` against ``cos``/``sin`` broadcastable to ``[...,
    dim / 2]``: the split-half rotation, in float32, cast back."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _head_norm(x, weight, eps: float):
    """RMSNorm over the last axis of ``[..., heads, head_dim]``."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


def _qkv(h, lp, cfg: KeyeVL2Config):
    q, k, v = qkv_heads(
        h, lp, cfg.dtype, cfg.num_attention_heads,
        cfg.num_key_value_heads, cfg.head_dim,
    )
    return (
        _head_norm(q, lp["q_norm"], cfg.rms_norm_eps),
        _head_norm(k, lp["k_norm"], cfg.rms_norm_eps),
        v,
    )


def _indexer_inputs(h, lp, cfg: KeyeVL2Config):
    """``h [..., D]`` -> index queries ``[..., Hi, Di]`` and the index
    key ``[..., Di]`` (both before the rope, compute dtype), and the
    float32 head weights ``[..., Hi]``."""
    dt = cfg.dtype
    hi, di = cfg.indexer_num_heads, cfg.indexer_head_dim
    qi = _proj(h, lp["wi_q"], dt).reshape(h.shape[:-1] + (hi, di))
    ik, w = _index_key_and_weights(h, lp, hi, di, cfg.rms_norm_eps, dt)
    return qi, ik, w


def _index_key_and_weights(h, lp, hi: int, di: int, eps: float, dt):
    """``h [..., D]`` -> the index key ``LayerNorm(W_ik h) [..., Di]``
    (weight and bias; before the rope, compute dtype) and the float32
    head weights ``W_iw h * Hi ** -0.5 * Di ** -0.5 [..., Hi]``: the
    part of an indexer that reads the hidden state whatever feeds its
    queries."""
    raw = jnp.matmul(
        h, lp["wi_k"].astype(dt), preferred_element_type=jnp.float32
    )
    mean = jnp.mean(raw, -1, keepdims=True)
    var = jnp.mean((raw - mean) ** 2, -1, keepdims=True)
    ik = (raw - mean) * lax.rsqrt(var + eps)
    ik = (ik * lp["ik_norm"] + lp["ik_norm_bias"]).astype(dt)
    w = jnp.matmul(
        h, lp["wi_w"].astype(dt), preferred_element_type=jnp.float32
    ) * (hi ** -0.5 * di ** -0.5)
    return ik, w


def _route(x, lp, cfg: KeyeVL2Config):
    """The router on ``x [N, D]``: float32 norm, float32 logits over
    every expert at full precision, the top-k of them and the chosen
    experts' softmax weights renormalised to 1 (the same numbers as
    ``g_e / sum g``).  -> (h' [N, D] in the compute dtype, ids [N, k]
    int32, weights [N, k] float32)."""
    xf = x.astype(jnp.float32)
    hf = xf * lax.rsqrt(
        jnp.mean(xf * xf, -1, keepdims=True) + cfg.rms_norm_eps
    ) * lp["mlp_norm"]
    logits = jnp.matmul(
        hf, lp["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    top, ids = lax.top_k(logits, cfg.num_experts_per_tok)
    return hf.astype(cfg.dtype), ids.astype(jnp.int32), jax.nn.softmax(top, -1)


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _expert_stacks(layers: Dict, cfg: KeyeVL2Config):
    """Every layer's expert matrices as ``[L * E, ...]`` views of the
    stacked leaves, and the layers without them: a serving program
    closes over the first and scans over the second, so that no layer's
    ``[E, D, F]`` is sliced out of its stack (1.2 GB a layer at the
    published sizes) — a layer reads its experts at ``layer * E``."""
    stacks = tuple(
        layers[n].reshape((-1,) + layers[n].shape[2:]).astype(cfg.dtype)
        for n in _EXPERT_LEAVES
    )
    rest = {n: v for n, v in layers.items() if n not in _EXPERT_LEAVES}
    return stacks, rest


def _experts(x, lp, cfg: KeyeVL2Config, stacks=None, layer=0):
    """The expert layer on ``x [N, D]`` -> (its output ``[N, D]``, the
    experts chosen ``[N, k]``).  Every one of the ``N * k`` assignments
    is computed (``ops/grouped_gemm.expert_ffn``): no capacity and no
    drop — an expert given every row takes every row.  ``stacks``:
    ``_expert_stacks``' views and the serving plane's kernel backend;
    without them the layer's own matrices ``lp[...]`` in plain XLA."""
    h, ids, gates = _route(x, lp, cfg)
    if stacks is None:
        stacks = tuple(lp[n].astype(cfg.dtype) for n in _EXPERT_LEAVES)
        backend = "jnp"
    else:
        from dlrover_tpu.ops.paged_attention import paged_kernel_backend

        backend = paged_kernel_backend()
    out = expert_ffn(
        h, ids, gates, *stacks, layer * cfg.num_experts, cfg.num_experts,
        backend,
    )
    return out.astype(cfg.dtype), ids


@jax.named_scope("head")
def _logits(x, params, cfg: KeyeVL2Config):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


@jax.named_scope("embed")
def _embed(params, tokens, cfg: KeyeVL2Config):
    return params["embed"].astype(cfg.dtype)[tokens]


def _selection_size(cfg: KeyeVL2Config, cached: int) -> int:
    return min(cfg.topk, cached)


def _prefill_widths(positions: int, block_size: int, parts: int = 4):
    """The static widths (cached positions read) a prefill chunk picks
    from: ``parts`` equal steps up to the table's ``positions``, each a
    multiple of the prefill attention's key block (of the block size
    where the table is shorter than one)."""
    unit = 1024 if positions > 1024 else block_size
    step = -(-positions // (parts * unit)) * unit
    return tuple(sorted({
        min(positions, step * i) for i in range(1, parts + 1)
    }))


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: KeyeVL2Config,
            return_experts: bool = False):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, no cache (``return_experts``: and the experts
    chosen, ``[B, T, layers, k]``).  For tests and as the serving
    worker's ``forward_fn``; dense in ``T x T``."""
    from dlrover_tpu.ops.paged_attention import NEG_INF, exact_topk_mask

    dt = cfg.dtype
    bsz, t = tokens.shape
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    x = _embed(params, tokens, cfg)
    positions = jnp.arange(t)
    cos, sin = _rope_tables(cfg.rope_theta, hd, positions)
    icos, isin = _rope_tables(cfg.rope_theta, cfg.indexer_head_dim, positions)
    causal = positions[None] <= positions[:, None]
    n_sel = _selection_size(cfg, t)

    def body(x, lp):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(h, lp, cfg)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        qi, ik, w = _indexer_inputs(h, lp, cfg)
        qi = _rotate(qi, icos[None, :, None], isin[None, :, None])
        ik = _rotate(ik, icos[None], isin[None])
        s = jnp.einsum(
            "bthd,bsd->bhts", qi, ik, preferred_element_type=jnp.float32
        )
        score = jnp.einsum("bth,bhts->bts", w, jax.nn.relu(s))
        score = jnp.where(causal[None], score, -jnp.inf)
        taken = jax.vmap(lambda sc: exact_topk_mask(sc, n_sel))(score)
        qg = q.reshape(bsz, t, nkv, nh // nkv, hd)
        att = jnp.einsum(
            "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
        ) * hd ** -0.5
        att = jax.nn.softmax(
            jnp.where(taken[:, None, None], att, NEG_INF), -1
        )
        out = jnp.einsum(
            "bkgts,bskd->btkgd", att.astype(dt), v,
            preferred_element_type=jnp.float32,
        ).astype(dt)
        x = x + _proj(out.reshape(bsz, t, nh * hd), lp["wo"], dt)
        y, ids = _experts(x.reshape(bsz * t, -1), lp, cfg)
        return x + y.reshape(x.shape), ids.reshape(bsz, t, -1)

    x, ids = lax.scan(body, x, params["layers"])
    logits = _logits(x, params, cfg)
    if return_experts:
        return logits, jnp.moveaxis(ids, 0, 2)
    return logits


# ------------------------------------------------------- serving programs


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # k, v [L, N, bs, KV, D]; ik [L, N, bs * Di / 128, 128]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    cfg: KeyeVL2Config,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """Prefill C prompt positions of ONE sequence: K, V and the index
    key into its paged blocks, every row's selection taken inside the
    causal mask from the index keys cached so far (the chunk's own
    included).  Padded tail positions write ahead of the prompt into
    the sequence's own reservation, as the dense block's do: decode
    overwrites each position before a query can see it.  Returns
    (logits [1, C, vocab], pool, {"experts": [C, layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        exact_topk_mask,
        gather_index_keys,
        gather_sequence,
        prefill_index_scores,
        scan_layers_over_pool,
        selected_prefill_attention,
    )

    dt = cfg.dtype
    _, c = tokens.shape
    bs, mb = pool["k"].shape[2], block_table.shape[0]
    positions = start_pos + jnp.arange(c)
    x = _embed(params, tokens, cfg)
    with jax.named_scope("attn"):
        cos, sin = _rope_tables(cfg.rope_theta, cfg.head_dim, positions)
        # a position past the table goes to the null block
        blk_idx = positions // bs
        blks = jnp.where(
            blk_idx < mb, block_table[jnp.minimum(blk_idx, mb - 1)], 0
        )
    with jax.named_scope("attn"), jax.named_scope("indexer"):
        icos, isin = _rope_tables(
            cfg.rope_theta, cfg.indexer_head_dim, positions
        )
    stacks, layers = _expert_stacks(params["layers"], cfg)
    # the chunk sees ``start_pos + C`` cached positions, the table holds
    # ``mb * bs``: scores, selection and attention run over the
    # narrowest of a few static widths that holds what it sees
    widths = _prefill_widths(mb * bs, bs)
    bucket = jnp.searchsorted(
        jnp.asarray(widths), jnp.minimum(start_pos + c, mb * bs)
    ).astype(jnp.int32)

    def attend(width, q, qi, w, keys, k, v):
        with jax.named_scope("indexer"):
            taken = exact_topk_mask(
                prefill_index_scores(qi, w, keys[:width], start_pos),
                _selection_size(cfg, width),
            )
        return selected_prefill_attention(
            q, k[:width], v[:width], taken, start_pos, start_pos + c
        )

    def body(x, lp, kv):
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h, lp, cfg)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            kv = kv.write_rows(k[0], v[0], blks, positions % bs)
        with jax.named_scope("attn"), jax.named_scope("indexer"):
            qi, ik, w = _indexer_inputs(h[0], lp, cfg)
            qi = _rotate(qi, icos[:, None], isin[:, None])
            kv = kv.write_leaf_run(
                "ik", _rotate(ik, icos, isin), block_table, start_pos
            )
            keys = gather_index_keys(
                kv.paged["ik"], kv.tables(block_table), cfg.indexer_head_dim
            )
        with jax.named_scope("attn"):
            # the sequence's rows by position, ONCE: a branch that took
            # the pools themselves had them copied into it
            table = kv.tables(block_table)
            attn = lax.switch(
                bucket,
                [partial(attend, width) for width in widths],
                q[0], qi, w, keys, gather_sequence(kv.k, table),
                gather_sequence(kv.v, table),
            )
            x = x + _proj(attn.reshape(1, c, -1), lp["wo"], dt)
        with jax.named_scope("mlp"):
            y, ids = _experts(x[0], lp, cfg, stacks, kv.layer)
            x = x + y[None]
        return x, ids, kv

    x, ids, new_k, new_v, paged = scan_layers_over_pool(
        body, x, layers, pool["k"], pool["v"],
        paged={"ik": pool["ik"]},
    )
    return (
        _logits(x, params, cfg),
        {"k": new_k, "v": new_v, **paged},
        {"experts": jnp.moveaxis(ids, 0, 1)},
    )


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # k, v [L, N, bs, KV, D]; ik [L, N, bs * Di / 128, 128]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: KeyeVL2Config,
) -> Tuple[jnp.ndarray, Dict, Dict]:
    """One continuous-batching decode step: every active lane writes
    its K, V and index key, scores its index query against every index
    key it has cached (read from the blocks the lane holds, in place,
    under the Pallas backend: ``ops/paged_attention.gather_index_keys``),
    takes the exact top ``topk`` positions (all of
    them below ``topk``) and attends over those token rows alone.  An
    inactive lane writes to the null block and reads one masked row.
    Shapes depend on (lanes, pool geometry) only: compiled once.
    Returns (logits [B, vocab], pool, {"experts": [B, layers, k]})."""
    from dlrover_tpu.ops.paged_attention import (
        decode_index_scores,
        exact_topk_rows,
        gather_index_keys,
        scan_layers_over_pool,
        sparse_rows_decode_attention,
    )

    dt = cfg.dtype
    n = tokens.shape[0]
    bs, mb = pool["k"].shape[2], block_tables.shape[1]
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
    with jax.named_scope("attn"), jax.named_scope("indexer"):
        icos, isin = _rope_tables(
            cfg.rope_theta, cfg.indexer_head_dim, positions
        )
        n_sel = _selection_size(cfg, mb * bs)
        counts = jnp.minimum(seq_lens, n_sel)
    stacks, layers = _expert_stacks(params["layers"], cfg)

    def body(x, lp, kv):
        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q, k, v = _qkv(h, lp, cfg)
            q = _apply_rope_rows(q, cos, sin)
            k = _apply_rope_rows(k, cos, sin)
            kv = kv.write(k[:, 0], v[:, 0], blk, off)
        with jax.named_scope("attn"), jax.named_scope("indexer"):
            qi, ik, w = _indexer_inputs(h[:, 0], lp, cfg)
            qi = _rotate(qi, icos[:, None], isin[:, None])
            kv = kv.write_leaf_rows("ik", _rotate(ik, icos, isin), blk, off)
            keys = gather_index_keys(
                kv.paged["ik"], kv.tables(block_tables),
                cfg.indexer_head_dim,
            )
            rows = exact_topk_rows(
                decode_index_scores(qi, w, keys, seq_lens), n_sel,
                kv.tables(block_tables),
            )
        with jax.named_scope("attn"):
            attn = sparse_rows_decode_attention(
                q[:, 0], kv.k, kv.v, rows, counts
            )
            x = x + _proj(attn.reshape(n, 1, -1), lp["wo"], dt)
        with jax.named_scope("mlp"):
            y, ids = _experts(x[:, 0], lp, cfg, stacks, kv.layer)
            x = x + y[:, None]
        return x, ids, kv

    x, ids, new_k, new_v, paged = scan_layers_over_pool(
        body, x, layers, pool["k"], pool["v"],
        paged={"ik": pool["ik"]},
    )
    return (
        _logits(x, params, cfg)[:, 0],
        {"k": new_k, "v": new_v, **paged},
        {"experts": jnp.moveaxis(ids, 0, 1)},
    )
