"""Llama-family transformer, TPU-first functional JAX.

Role parity: the reference accelerates user-supplied HF/Megatron models
(``atorch`` injects FA/TP/MoE into them — SURVEY.md §2.6); a TPU
framework must ship the model family itself.  This is the flagship:
RMSNorm + RoPE + GQA + SwiGLU, bfloat16 activations, layers stacked on
a leading dim and executed with ``lax.scan`` (one compiled block for
all layers — fast compile, XLA-friendly), every parameter carrying a
logical-axes annotation consumed by
``dlrover_tpu.parallel.sharding.LogicalAxisRules``.

Design notes (TPU):
- params are a plain dict pytree; "layers" is a stacked leading axis —
  sharding it on the "pipe" mesh axis gives pipeline stages for free.
- attention is exposed through a pluggable kernel so
  ``dlrover_tpu.ops`` can swap in Pallas flash / ring attention.
- all matmuls run in bfloat16 with fp32 accumulation
  (``preferred_element_type``) — the MXU contract.
"""

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.jax_env import kept_in_compile_cache
from dlrover_tpu.parallel import remat as rematlib
from dlrover_tpu.parallel import sharding as sh


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # what the scanned block keeps for its backward: a NAMED policy
    # (a rung of parallel/remat.py's ladder — "full" | "flash" | "qkv"
    # | "matmuls" | "none" — or "dots") is obeyed; "auto" leaves it to
    # the strategy, and where that names none either to the rung
    # resolved from the compiled step's memory ("full" until resolved)
    remat: str = rematlib.AUTO
    # fused-CE row-chunk size (peak logits memory = chunk x vocab fp32;
    # larger chunks = fewer scan trips, bigger lm-head matmuls)
    ce_chunk_rows: int = 512
    # source checkpoint tied lm_head to the embedding (HF
    # tie_word_embeddings); the framework keeps them separate
    # (vocab-sharded lm_head), but HF export must honor the tie
    tie_word_embeddings: bool = False

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Test-sized config (virtual-device CI)."""
        base = dict(
            vocab_size=256,
            dim=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            mlp_dim=128,
            max_seq_len=128,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**overrides) -> "LlamaConfig":
        base = dict(
            vocab_size=32000,
            dim=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            mlp_dim=11008,
            max_seq_len=4096,
        )
        base.update(overrides)
        return LlamaConfig(**base)


# ---------------------------------------------------------------- params


def init_params(key, cfg: LlamaConfig) -> Dict:
    """Stacked-layer param pytree; fp32 master weights."""
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    d, hd = cfg.dim, cfg.head_dim
    nh, nkv, mlp, L = cfg.n_heads, cfg.n_kv_heads, cfg.mlp_dim, cfg.n_layers

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense_init(key, *shape, in_axis: int = 0):
        fan_in = shape[in_axis]
        return (
            jax.random.normal(key, shape, dtype=jnp.float32)
            * (fan_in**-0.5)
        )

    keys = jax.random.split(k_layers, 7)
    layer = {
        "attn_norm": norm_init(L, d),
        "wq": dense_init(keys[0], L, d, nh * hd, in_axis=1),
        "wk": dense_init(keys[1], L, d, nkv * hd, in_axis=1),
        "wv": dense_init(keys[2], L, d, nkv * hd, in_axis=1),
        "wo": dense_init(keys[3], L, nh * hd, d, in_axis=1),
        "mlp_norm": norm_init(L, d),
        "w_gate": dense_init(keys[4], L, d, mlp, in_axis=1),
        "w_up": dense_init(keys[5], L, d, mlp, in_axis=1),
        "w_down": dense_init(keys[6], L, mlp, d, in_axis=1),
    }
    return {
        "embed": dense_init(k_embed, cfg.vocab_size, d, in_axis=1),
        "layers": layer,
        "final_norm": norm_init(d),
        "lm_head": dense_init(k_out, d, cfg.vocab_size, in_axis=0),
    }


def loss_fn_ngrouped(
    parts,
    batch: Dict,
    cfg: LlamaConfig,
    attention_fn=None,
    fused_ce: Optional[bool] = None,
) -> jnp.ndarray:
    """``loss_fn`` over an N-group param split: group 0 carries the
    embedding + the first layer segment, middle groups a contiguous
    layer segment each, the last group the tail segment + final norm
    + lm head.  ``jax.grad(..., argnums=i)`` materializes only group
    i's dW carries — at ~3B params on a 16 GB chip the full grads
    tree cannot coexist with the params, so the offloaded step runs
    one backward per group
    (``optimizers.host_offload.build_grouped_offload_step``); more
    groups shrink the peak dW tree further."""
    parts = tuple(parts)
    if len(parts) == 1:
        return loss_fn(parts[0], batch, cfg, attention_fn, fused_ce)
    params = {
        "embed": parts[0]["embed"],
        "layers": tuple(p["layers"] for p in parts),
        "final_norm": parts[-1]["final_norm"],
        "lm_head": parts[-1]["lm_head"],
    }
    return loss_fn(params, batch, cfg, attention_fn, fused_ce)


def loss_fn_grouped(
    params_a: Dict,
    params_b: Dict,
    batch: Dict,
    cfg: LlamaConfig,
    attention_fn=None,
    fused_ce: Optional[bool] = None,
) -> jnp.ndarray:
    """Two-group form of :func:`loss_fn_ngrouped` (kept for the
    legacy ``build_grouped_offload_step`` calling convention)."""
    return loss_fn_ngrouped(
        (params_a, params_b), batch, cfg, attention_fn, fused_ce
    )


def init_ngrouped_params(key, cfg: LlamaConfig, boundaries):
    """Build an N-group layer split WITHOUT materializing the full
    stacked tree (at 3B the fp32 full tree plus its slices would not
    fit): each group initializes from a per-segment config.
    ``boundaries`` are the strictly-increasing layer split points
    (``len(boundaries) + 1`` groups; ``accelerate.solver.
    solve_offload_groups`` chooses them from the per-layer footprint).
    Returns a list of thunks so the caller can free each group's fp32
    source before the next materializes."""
    import dataclasses

    bounds = [0] + list(boundaries) + [cfg.n_layers]
    for lo, hi in zip(bounds, bounds[1:]):
        if hi <= lo:
            raise ValueError(
                f"boundaries {tuple(boundaries)} must be strictly "
                f"increasing within (0, {cfg.n_layers})"
            )
    n_groups = len(bounds) - 1
    keys = jax.random.split(key, n_groups)

    def make(i: int):
        seg_cfg = dataclasses.replace(
            cfg, n_layers=bounds[i + 1] - bounds[i]
        )

        def init() -> Dict:
            t = init_params(keys[i], seg_cfg)
            part = {"layers": t["layers"]}
            if i == 0:
                part["embed"] = t["embed"]
            if i == n_groups - 1:
                part["final_norm"] = t["final_norm"]
                part["lm_head"] = t["lm_head"]
            return part

        return init

    return [make(i) for i in range(n_groups)]


def init_grouped_params(key, cfg: LlamaConfig, boundary: int):
    """Two-group form of :func:`init_ngrouped_params`: returns
    ``(init_a, init_b)`` thunks splitting the stack at ``boundary``."""
    init_a, init_b = init_ngrouped_params(key, cfg, (boundary,))
    return init_a, init_b


def param_logical_axes(cfg: LlamaConfig) -> Dict:
    """Same structure as ``init_params``, leaves = logical-axes tuples
    (None = replicated dim)."""
    return {
        "embed": (sh.VOCAB, sh.EMBED),
        "layers": {
            "attn_norm": (sh.LAYERS, None),
            "wq": (sh.LAYERS, sh.EMBED, sh.HEADS),
            "wk": (sh.LAYERS, sh.EMBED, sh.KV_HEADS),
            "wv": (sh.LAYERS, sh.EMBED, sh.KV_HEADS),
            "wo": (sh.LAYERS, sh.HEADS, sh.EMBED),
            "mlp_norm": (sh.LAYERS, None),
            "w_gate": (sh.LAYERS, sh.EMBED, sh.MLP),
            "w_up": (sh.LAYERS, sh.EMBED, sh.MLP),
            "w_down": (sh.LAYERS, sh.MLP, sh.EMBED),
        },
        "final_norm": (None,),
        "lm_head": (sh.EMBED, sh.VOCAB),
    }


def count_params(params) -> int:
    return sum(
        x.size for x in jax.tree_util.tree_leaves(params)
    )


# --------------------------------------------------------------- modules


def rms_norm(x, weight, eps: float):
    # fused Pallas forward on TPU (saved-rstd backward); plain XLA
    # elsewhere — see ops/fused.py.  Both paths scale in fp32 and
    # cast once, so values are identical across backends.
    from dlrover_tpu.ops.fused import rms_norm as _fused
    from dlrover_tpu.ops.pallas_utils import use_interpret
    from dlrover_tpu.parallel.mesh import get_mesh_context

    ctx = get_mesh_context()
    if (
        ctx is None or ctx.mesh.size == 1 or x.ndim != 3
        or use_interpret()  # off-TPU the fused op is plain XLA
    ):
        return _fused(x, weight, eps)
    # GSPMD cannot partition a Mosaic kernel: run it per shard of the
    # [batch, seq, embed] activation (the normalized dim is whole)
    from jax.sharding import PartitionSpec

    from dlrover_tpu.accelerate.module_replace import shard_mapped

    spec = sh.filter_spec_for_mesh(
        _current_rules().spec((sh.BATCH, sh.SEQ, sh.EMBED)), ctx.mesh
    )
    return shard_mapped(
        lambda a, w: _fused(a, w, eps),
        ctx,
        in_specs=(spec, PartitionSpec()),
        out_specs=spec,
    )(x, weight)


def rope_frequencies(cfg: LlamaConfig, positions):
    """[S] -> cos/sin [S, head_dim/2] (fp32)."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; rotate pairs (split-half convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def dot_product_attention(q, k, v, causal: bool = True):
    """Reference attention kernel [B,S,H,D]x[B,S,KV,D]; the ops package
    swaps this for Pallas flash attention on real TPU."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    group = nh // nkv
    q = q.reshape(b, s, nkv, group, d)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return out.reshape(b, s, nh, d)


AttentionFn = Callable[..., jnp.ndarray]


def _layer_forward(
    cfg: LlamaConfig,
    attention_fn: AttentionFn,
    lp: Dict,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
) -> jnp.ndarray:
    b, s, d = x.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype

    def proj(a, w):
        # fp32 MXU accumulation, bf16 storage (the contract above)
        return jnp.matmul(
            a, w.astype(dt), preferred_element_type=jnp.float32
        ).astype(dt)

    # device scopes (observability/events.py DEVICE_SCOPES): the
    # backward and the checkpoint's replay keep these names on their
    # paths, so a device trace splits the step by the model's part
    with jax.named_scope("attn"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = proj(h, lp["wq"]).reshape(b, s, nh, hd)
        k = proj(h, lp["wk"]).reshape(b, s, nkv, hd)
        v = proj(h, lp["wv"]).reshape(b, s, nkv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q = sh.apply_sharding_constraint(
            q, (sh.BATCH, sh.SEQ, sh.HEADS, None), _current_rules()
        )
        # the values a remat rung may keep (parallel/remat.py): named
        # where the backward reads them, so q and k after RoPE
        q = rematlib.keep(q, rematlib.ATTN_Q)
        k = rematlib.keep(k, rematlib.ATTN_K)
        v = rematlib.keep(v, rematlib.ATTN_V)
        attn = rematlib.keep(
            attention_fn(q, k, v, causal=True), rematlib.ATTN_OUT
        )
        x = rematlib.keep(
            x + proj(attn.reshape(b, s, nh * hd), lp["wo"]),
            rematlib.ATTN_RESID,
        )

    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = rematlib.keep(proj(h, lp["w_gate"]), rematlib.MLP_GATE)
        up = rematlib.keep(proj(h, lp["w_up"]), rematlib.MLP_UP)
        x = x + proj(jax.nn.silu(gate) * up, lp["w_down"])
    return x


# activation-sharding rules used inside forward; set by the trainer
_rules_holder = {"rules": None}


def set_activation_rules(rules):
    _rules_holder["rules"] = rules


def _current_rules():
    rules = _rules_holder["rules"]
    if rules is None:
        rules = sh.active_rules()
    if rules is None:
        from dlrover_tpu.parallel.mesh import get_mesh_context

        ctx = get_mesh_context()
        if ctx is not None and ctx.rules is not None:
            rules = ctx.rules
    if rules is None:
        rules = sh.default_rules(fsdp=False)
    return rules


def _default_attention() -> AttentionFn:
    """Strategy-selected kernel (the module-replace pass, resolved at
    trace time): ring attention under seq>1 meshes, Pallas flash
    attention on TPU, dense reference otherwise.  See
    ``dlrover_tpu.accelerate.module_replace``."""
    from dlrover_tpu.accelerate.module_replace import select_attention
    from dlrover_tpu.parallel.mesh import get_mesh_context

    return select_attention(get_mesh_context(), _current_rules())


def forward_hidden(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    attention_fn: Optional[AttentionFn] = None,
) -> jnp.ndarray:
    """tokens [B, S] int32 -> final-norm hidden states [B, S, D]
    (``cfg.dtype``) — the pre-lm-head activations, so the loss can fuse
    the vocab projection (``ops.fused.fused_linear_cross_entropy``)."""
    if attention_fn is None:
        attention_fn = _default_attention()
    dt = cfg.dtype
    b, s = tokens.shape
    # Gather over an fsdp-sharded embed dim would force the partitioner
    # to move the fsdp axis from dim -1 (table layout) to dim 0 (batch
    # layout) through the gather — an involuntary full remat.  Voluntarily
    # all-gather the (small) table's embed dim first; vocab stays sharded.
    with jax.named_scope("embed"):
        table = sh.apply_sharding_constraint(
            params["embed"].astype(dt), (sh.VOCAB, None), _current_rules()
        )
        x = table[tokens]
        x = sh.apply_sharding_constraint(
            x, (sh.BATCH, sh.SEQ, sh.EMBED), _current_rules()
        )
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, jnp.arange(s))

    # what the block keeps for its backward, resolved at trace time
    # like the attention kernel: this config's own NAMED policy, else
    # the strategy's, else the rung resolved from the compiled step's
    # memory (parallel/remat.py; the step is traced inside its scope)
    policy, source = rematlib.select(cfg.remat)
    block = rematlib.checkpointed(
        partial(_layer_forward, cfg, attention_fn), policy
    )

    # strategy-selected layer executor: lax.scan normally, the GPipe
    # shard_map pipeline when the mesh runs pipe > 1 (module-replace
    # pass, resolved at trace time like the attention kernel)
    from dlrover_tpu.accelerate.module_replace import (
        select_layer_executor,
    )
    from dlrover_tpu.parallel.mesh import get_mesh_context

    execute_layers = select_layer_executor(get_mesh_context())
    layers = params["layers"]
    # a tuple/list of stacked subtrees runs as SEQUENTIAL scan
    # segments — the grouped-backward path (host_offload
    # build_grouped_offload_step) splits the stack so each group's
    # dW carries materialize alone
    segments = (
        layers if isinstance(layers, (list, tuple)) else (layers,)
    )
    for seg in segments:
        x = execute_layers(block, seg, x, cos, sin)
    rematlib.report(
        policy, source, cfg.n_layers, x.size * x.dtype.itemsize
    )
    with jax.named_scope("head_loss"):
        return rms_norm(x, params["final_norm"], cfg.norm_eps)


def forward(
    params: Dict,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    attention_fn: Optional[AttentionFn] = None,
) -> jnp.ndarray:
    """tokens [B, S] int32 -> logits [B, S, vocab] (fp32)."""
    x = forward_hidden(params, tokens, cfg, attention_fn)
    with jax.named_scope("head_loss"):
        logits = jnp.einsum(
            "bsd,dv->bsv",
            x,
            params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return logits


# ------------------------------------------------------ KV-cache decode


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int) -> Dict:
    """Per-layer K/V cache for autoregressive decode, stacked on the
    layer dim like the params ([L, B, max_len, KV, head_dim])."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
    }


def decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current position's token ids
    cache: Dict,
    pos: jnp.ndarray,  # scalar int32: position being decoded
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """One cached decode step: logits [B, vocab] for position ``pos``
    plus the updated cache.  The inference dual of ``forward`` — prior
    positions' K/V are read from the cache instead of recomputed, so a
    T-token generation costs O(T) attention instead of O(T^2) forward
    passes (the vLLM-style serving path, on the training mesh)."""
    dt = cfg.dtype
    b = tokens.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"].astype(dt)[tokens][:, None]  # [B,1,D]
    cos, sin = rope_frequencies(cfg, pos[None])  # [1, hd/2]

    def body(x, layer_in):
        lp, k_cache, v_cache = layer_in

        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = apply_rope(proj(h, lp["wq"]).reshape(b, 1, nh, hd), cos, sin)
        k = apply_rope(
            proj(h, lp["wk"]).reshape(b, 1, nkv, hd), cos, sin
        )
        v = proj(h, lp["wv"]).reshape(b, 1, nkv, hd)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k, (0, pos, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v, (0, pos, 0, 0)
        )
        # attention of the single query over the cached prefix
        group = nh // nkv
        qg = q.reshape(b, nkv, group, hd)
        logits = jnp.einsum(
            "bkgd,bskd->bkgs", qg, k_cache,
            preferred_element_type=jnp.float32,
        ) * (hd**-0.5)
        valid = (
            jnp.arange(k_cache.shape[1]) <= pos
        )  # causal: prefix only
        logits = jnp.where(valid[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        attn = jnp.einsum(
            "bkgs,bskd->bkgd", probs.astype(dt), v_cache,
            preferred_element_type=jnp.float32,
        ).astype(dt)
        x = x + proj(
            attn.reshape(b, 1, nh * hd), lp["wo"]
        )
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(proj(h, lp["w_gate"]))
        up = proj(h, lp["w_up"])
        x = x + proj(gate * up, lp["w_down"])
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(dt),
        preferred_element_type=jnp.float32,
    )
    return logits[:, 0], {"k": new_k, "v": new_v}


def prefill(
    params: Dict,
    tokens: jnp.ndarray,  # [B, P] prompt tokens
    cache: Dict,
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """Batched single-forward prefill: one causal pass over the whole
    prompt that writes every position's K/V into the cache — the
    replacement for feeding the prompt one token at a time through
    ``decode_step`` under ``lax.scan`` (P cached steps -> 1 forward).
    Returns (logits [B, P, vocab] fp32, cache); callers gather the
    last *real* position's row to sample the first new token."""
    dt = cfg.dtype
    b, p = tokens.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"].astype(dt)[tokens]  # [B, P, D]
    cos, sin = rope_frequencies(cfg, jnp.arange(p))

    def body(x, layer_in):
        lp, k_cache, v_cache = layer_in

        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = apply_rope(proj(h, lp["wq"]).reshape(b, p, nh, hd), cos, sin)
        k = apply_rope(
            proj(h, lp["wk"]).reshape(b, p, nkv, hd), cos, sin
        )
        v = proj(h, lp["wv"]).reshape(b, p, nkv, hd)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k, (0, 0, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v, (0, 0, 0, 0)
        )
        attn = dot_product_attention(q, k, v, causal=True)
        x = x + proj(attn.reshape(b, p, nh * hd), lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(proj(h, lp["w_gate"]))
        up = proj(h, lp["w_up"])
        x = x + proj(gate * up, lp["w_down"])
        return x, (k_cache, v_cache)

    x, (new_k, new_v) = lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(dt),
        preferred_element_type=jnp.float32,
    )
    return logits, {"k": new_k, "v": new_v}


# ----------------------------------------------- paged (block-table) decode

# the leaves of the serving copy's ``layers`` that the serving programs
# below cast (``proj``: ``w.astype(dt)``; ``wqkv`` stands for the three
# of ``_QKV_LEAVES``); the norm scales stay as they are — ``rms_norm``
# scales in fp32, and casting them would change the result
_SERVING_MATMUL_LEAVES = ("wqkv", "wo", "w_gate", "w_up", "w_down")
_QKV_LEAVES = ("wq", "wk", "wv")


@partial(jax.jit, static_argnames="dtype")
def _cast_and_fuse(work: Dict, dtype) -> Dict:
    """ONE program for everything a serving copy has to write: every
    leaf of ``work`` in ``dtype``, and where its ``layers`` hold ``wq``,
    ``wk``, ``wv``, the three as one leaf ``wqkv`` (q's columns, then
    k's, then v's).  One compile and one dispatch an adoption where a
    cast and a ``concatenate`` a leaf were a program each, and nothing
    is left beside the result: compiled for the chip at 7B widths it
    holds no temporary bytes (``tests/test_tpu_compile.py``).  Nothing
    is donated: the given leaves are the caller's.  Jitted at module
    level, so a second copy of the same shapes compiles nothing."""
    out = jax.tree_util.tree_map(lambda x: x.astype(dtype), work)
    layers = out["layers"]
    if all(name in layers for name in _QKV_LEAVES):
        layers["wqkv"] = jnp.concatenate(
            [layers.pop(name) for name in _QKV_LEAVES], axis=-1
        )
    return out


def serving_copy(params: Dict, dtype, matmul_leaves) -> Dict:
    """``params`` as a model's serving programs want them resident:
    ``embed``, ``lm_head`` and the ``matmul_leaves`` of ``layers`` in
    ``dtype``, and ``wq``, ``wk``, ``wv`` as ONE leaf ``wqkv`` ``[L, D,
    (n_heads + 2 * n_kv_heads) * head_dim]`` in their place.  Only the
    leaves that need either go through ``_cast_and_fuse``, in one call;
    every other leaf is passed around it and stays the caller's array,
    and a tree with nothing to do comes back as it is.  Shared with
    ``models/falcon_h1.py``, whose blocks name the leaves alike."""
    dt = jnp.dtype(dtype)
    layers = params["layers"]
    names = [
        name for name in matmul_leaves
        if name in layers and layers[name].dtype != dt
    ]
    if "wqkv" not in layers:
        names += _QKV_LEAVES
    work = {
        name: params[name] for name in ("embed", "lm_head")
        if params[name].dtype != dt
    }
    if not (work or names):
        return params
    work["layers"] = {name: layers[name] for name in names}
    # every serving process compiles this program at its start, in about
    # half a second: kept in the persistent cache, a replica's second
    # start loads it (the five eager casts it replaces were five
    # compiles a start)
    with kept_in_compile_cache():
        done = _cast_and_fuse(work, dt)
    kept = {
        name: leaf for name, leaf in layers.items() if name not in names
    }
    return {**params, **done, "layers": {**kept, **done["layers"]}}


def serving_params(params: Dict, cfg: LlamaConfig) -> Dict:
    """The tree the serving programs below compute on, made ONCE.

    Dtype: every leaf they cast on entry (``embed``, ``lm_head`` and the
    matmul weights of ``layers``) in ``cfg.dtype``, everything else as
    given.  The programs' own ``.astype(dt)`` is then a no-op, so a
    caller that serves many steps from unchanged weights
    (``rl/scheduler.py``) pays the cast — at 7B widths more HBM traffic
    than the step's matmuls — once per adoption, not once per step.

    Layout: ``wq``, ``wk`` and ``wv`` are held as one leaf ``wqkv`` and
    are NOT in the returned tree.  Cut out of the stacked ``[L, D, D]``
    leaves one by one, each was materialised in a buffer of its own and
    copied into another layout before its matmul, in every layer of
    every step program (a fifth of a decode step's device time at 7B
    widths); the fused leaf is read in place by one matmul, like ``wo``.
    The programs take either tree and give the same result
    (``qkv_heads``); checkpoints, published policies and training keep
    the three leaves.

    The copy is ONE jitted program (``serving_copy``), cast and fusion
    in one pass.  Every leaf that needs neither is returned as the SAME
    array, and so is the whole of a tree that is already a serving
    copy."""
    return serving_copy(params, cfg.dtype, _SERVING_MATMUL_LEAVES)


def qkv_heads(h, lp, dt, nh: int, nkv: int, hd: int):
    """``h [..., D]`` -> ``q [..., nh, hd]``, ``k`` and ``v`` ``[...,
    nkv, hd]`` in ``dt``, before the rope: one matmul and a split where
    ``lp`` is a layer of the serving copy (``wqkv``), three where it is
    a layer of the training tree — chosen by the tree's keys at trace
    time, with the same result."""

    def proj(w):
        return jnp.matmul(
            h, w.astype(dt), preferred_element_type=jnp.float32
        ).astype(dt)

    if "wqkv" in lp:
        q, k, v = jnp.split(
            proj(lp["wqkv"]), (nh * hd, (nh + nkv) * hd), axis=-1
        )
    else:
        q, k, v = (proj(lp[name]) for name in _QKV_LEAVES)
    lead = h.shape[:-1]
    return (
        q.reshape(lead + (nh, hd)),
        k.reshape(lead + (nkv, hd)),
        v.reshape(lead + (nkv, hd)),
    )


def _apply_rope_rows(x, cos, sin):
    """x: [B, 1, H, D] single position per row; cos/sin [B, D/2]
    (each row at its OWN position — the continuous-batching decode
    case, where slot b sits at position ``positions[b]``)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, None, None, :]
    sin = sin[:, None, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per slot
    pool: Dict,  # {"k","v"}: [L, num_blocks, block_size, KV, D]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per slot
    active: jnp.ndarray,  # [B] bool: slot holds a live sequence
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching decode step: every ACTIVE slot advances
    its own sequence by one token at its own position.  All shapes are
    functions of (max_slots, pool geometry) only — admissions and
    evictions change the *contents* of ``block_tables`` / ``positions``
    / ``active``, never the program, so this compiles exactly once.

    Inactive lanes write to the null block (id 0) and read garbage
    that callers discard; their table rows must be zeroed on eviction
    so a freed block re-issued to another sequence is never gathered
    through a stale table.

    The pool rides WHOLE in the layer scan's carry
    (``ops/paged_attention.scan_layers_over_pool``, as in the three
    programs below): layer ``l`` writes its 16 rows in place at block
    ``l * num_blocks + id``.  Scanned in and out, the pool was sliced,
    copied and re-stacked — all 1.5 GB of it, three times a step.

    The attention call dispatches per ``DLROVER_TPU_PAGED_KERNEL``
    (``ops/paged_attention.paged_kernel_backend``): the streamed Pallas
    decode kernel or the gather-based jnp reference.  The choice is
    resolved at trace time, so the compile-once contract above holds
    under either backend."""
    from dlrover_tpu.ops.paged_attention import (
        paged_decode_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    b = tokens.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs = pool["k"].shape[2]
    mb = block_tables.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens][:, None]  # [B, 1, D]
    # the rope tables and the write routing: attention's, once a step
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, positions)  # [B, hd/2]
        # a position past the table (a multi-token draft window running
        # beyond the sequence's budget) must write to the null block — a
        # clamped gather would alias the LAST real block and scribble
        # draft garbage over real K/V
        blk_idx = positions // bs
        blk = jnp.where(
            active & (blk_idx < mb),
            jnp.take_along_axis(
                block_tables, jnp.minimum(blk_idx, mb - 1)[:, None],
                axis=1,
            )[:, 0],
            0,
        )
        off = jnp.where(active, positions % bs, 0)
        seq_lens = jnp.where(active, positions + 1, 1)

    def body(x, lp, kv):
        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = qkv_heads(h, lp, dt, nh, nkv, hd)
            q = _apply_rope_rows(q, cos, sin)
            k = _apply_rope_rows(k, cos, sin)
            kv = kv.write(k[:, 0], v[:, 0], blk, off)
            attn = paged_decode_attention(
                q[:, 0], kv.k, kv.v, kv.tables(block_tables), seq_lens
            )
            x = x + proj(attn.reshape(b, 1, nh * hd), lp["wo"])
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(proj(h, lp["w_gate"]))
            up = proj(h, lp["w_up"])
            x = x + proj(gate * up, lp["w_down"])
        return x, None, kv

    x, _, new_k, new_v = scan_layers_over_pool(
        body, x, params["layers"], pool["k"], pool["v"]
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(dt),
            preferred_element_type=jnp.float32,
        )
    return logits[:, 0], {"k": new_k, "v": new_v}


def _apply_rope_grid(x, cos, sin):
    """x: [B, C, H, D]; cos/sin [B, C, D/2] — every (lane, window
    offset) pair rotated at its OWN position (the multi-token verify
    case, where lane b's window starts at ``positions[b]``)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


@jax.named_scope("verify")
def paged_verify_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B, C]: window of C tokens per lane
    pool: Dict,  # {"k","v"}: [L, num_blocks, block_size, KV, D]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32: lane's first window position
    active: jnp.ndarray,  # [B] bool: lane holds a live sequence
    cfg: LlamaConfig,
) -> jnp.ndarray:
    """The speculative-decode verify forward: score a C-token draft
    window for every lane in ONE forward.  ``tokens[b, i]`` sits at
    position ``positions[b] + i``; its K/V must already be in the
    pool (the draft loop wrote it), so this is READ-ONLY — the pool is
    never touched, which keeps the drafted cache bit-identical whether
    or not verification ran.  Returns logits ``[B, C, vocab]`` (fp32);
    row ``i`` predicts the token at position ``positions[b] + i + 1``.
    Inactive lanes compute on garbage their caller discards.

    The attention call dispatches per ``DLROVER_TPU_PAGED_KERNEL``:
    the fused Pallas verify kernel shares one paged-prefix pass across
    the window's C positions; the jnp reference re-gathers the pool."""
    from dlrover_tpu.ops.paged_attention import (
        paged_verify_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    b, c = tokens.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos_grid = positions[:, None] + jnp.arange(c)[None]  # [B, C]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]  # [B, C, D]
    # the rope tables and the write routing: attention's, once a step
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, pos_grid.reshape(-1))
        cos = cos.reshape(b, c, -1)
        sin = sin.reshape(b, c, -1)
        safe_pos = jnp.where(active, positions, 0)

    def body(x, lp, kv):
        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q, _, _ = qkv_heads(h, lp, dt, nh, nkv, hd)
            q = _apply_rope_grid(q, cos, sin)
            attn = paged_verify_attention(
                q, kv.k, kv.v, kv.tables(block_tables), safe_pos
            )
            x = x + proj(attn.reshape(b, c, nh * hd), lp["wo"])
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(proj(h, lp["w_gate"]))
            up = proj(h, lp["w_up"])
            x = x + proj(gate * up, lp["w_down"])
        return x, None

    x, _ = scan_layers_over_pool(
        body, x, params["layers"], pool["k"], pool["v"], read_only=True
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(dt),
            preferred_element_type=jnp.float32,
        )
    return logits


@jax.named_scope("verify")
def paged_verify_write_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B, C]: window of C tokens per lane
    pool: Dict,  # {"k","v"}: [L, num_blocks, block_size, KV, D]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32: lane's first window position
    active: jnp.ndarray,  # [B] bool: lane holds a live sequence
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """Verify a C-token draft window AND write its K/V into the pool.

    The separate-drafter flywheel path: a small DRAFT model ran the
    draft loop against its OWN pool, so — unlike the self-drafting
    ``paged_verify_step`` — the POLICY's K/V for the window positions
    does not exist yet.  This forward scores the window exactly like
    ``paged_verify_step`` while also projecting k/v and scattering
    them at positions ``positions[b] + i`` (null-block routing for
    inactive lanes and past-table positions, the ``paged_decode_step``
    discipline), so the policy cache ends the step as if the policy
    had decoded the window itself.  Rejected draft tail positions are
    overwritten by later decode/draft writes before they become
    attendable — same garbage discipline as padded prefill tails.
    Returns (logits [B, C, vocab] fp32, pool)."""
    from dlrover_tpu.ops.paged_attention import (
        paged_verify_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    b, c = tokens.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs = pool["k"].shape[2]
    mb = block_tables.shape[1]
    pos_grid = positions[:, None] + jnp.arange(c)[None]  # [B, C]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]  # [B, C, D]
    # the rope tables and the write routing: attention's, once a step
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, pos_grid.reshape(-1))
        cos = cos.reshape(b, c, -1)
        sin = sin.reshape(b, c, -1)
        safe_pos = jnp.where(active, positions, 0)
        # per-(lane, offset) write routing — flattened to [B*C] for the
        # scatter; inactive lanes and past-table positions hit block 0
        blk_idx = pos_grid // bs  # [B, C]
        blks = jnp.where(
            active[:, None] & (blk_idx < mb),
            jnp.take_along_axis(
                block_tables, jnp.minimum(blk_idx, mb - 1), axis=1
            ),
            0,
        ).reshape(-1)
        offs = jnp.where(active[:, None], pos_grid % bs, 0).reshape(-1)

    def body(x, lp, kv):
        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = qkv_heads(h, lp, dt, nh, nkv, hd)
            q = _apply_rope_grid(q, cos, sin)
            k = _apply_rope_grid(k, cos, sin)
            kv = kv.write(
                k.reshape(b * c, nkv, hd), v.reshape(b * c, nkv, hd),
                blks, offs,
            )
            attn = paged_verify_attention(
                q, kv.k, kv.v, kv.tables(block_tables), safe_pos
            )
            x = x + proj(attn.reshape(b, c, nh * hd), lp["wo"])
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(proj(h, lp["w_gate"]))
            up = proj(h, lp["w_up"])
            x = x + proj(gate * up, lp["w_down"])
        return x, None, kv

    x, _, new_k, new_v = scan_layers_over_pool(
        body, x, params["layers"], pool["k"], pool["v"]
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(dt),
            preferred_element_type=jnp.float32,
        )
    return logits, {"k": new_k, "v": new_v}


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk
    pool: Dict,  # {"k","v"}: [L, num_blocks, block_size, KV, D]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: chunk's first position
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Dict]:
    """Prefill C prompt positions of ONE sequence into its paged
    blocks (fixed chunk shape — a long prompt runs as several chunks
    interleaved with other sequences' decode steps, so it can never
    stall them).  Padded tail positions write ahead of the prompt into
    the sequence's own reservation; decode overwrites each position
    before it becomes visible, so the garbage is never attended.
    Returns (logits [1, C, vocab], pool)."""
    from dlrover_tpu.ops.paged_attention import (
        paged_prefill_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    b, c = tokens.shape
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bs = pool["k"].shape[2]
    positions = start_pos + jnp.arange(c)  # [C]
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]  # [1, C, D]
    # the rope tables and the write routing: attention's, once a step
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, positions)
        # a padded chunk tail can run past the table: route those writes
        # to the null block explicitly — a clamped gather would alias the
        # sequence's LAST real block and let pad garbage race real K/V
        blk_idx = positions // bs
        mb = block_table.shape[0]
        blks = jnp.where(
            blk_idx < mb,
            block_table[jnp.minimum(blk_idx, mb - 1)],
            0,
        )  # [C]
        offs = positions % bs

    def body(x, lp, kv):
        def proj(a, w):
            return jnp.matmul(
                a, w.astype(dt), preferred_element_type=jnp.float32
            ).astype(dt)

        with jax.named_scope("attn"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q, k, v = qkv_heads(h, lp, dt, nh, nkv, hd)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            kv = kv.write(k[0], v[0], blks, offs)
            attn = paged_prefill_attention(
                q[0], kv.k, kv.v, kv.tables(block_table), start_pos
            )
            x = x + proj(attn[None].reshape(b, c, nh * hd), lp["wo"])
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            gate = jax.nn.silu(proj(h, lp["w_gate"]))
            up = proj(h, lp["w_up"])
            x = x + proj(gate * up, lp["w_down"])
        return x, None, kv

    x, _, new_k, new_v = scan_layers_over_pool(
        body, x, params["layers"], pool["k"], pool["v"]
    )
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, params["lm_head"].astype(dt),
            preferred_element_type=jnp.float32,
        )
    return logits, {"k": new_k, "v": new_v}


# fused CE kicks in for real vocabularies; tiny test configs keep the
# dense form so the loss is bit-identical to the naive reference
_FUSED_CE_MIN_VOCAB = 8192


def loss_fn(
    params: Dict,
    batch: Dict,
    cfg: LlamaConfig,
    attention_fn: Optional[AttentionFn] = None,
    fused_ce: Optional[bool] = None,
) -> jnp.ndarray:
    """Next-token cross entropy; batch = {"tokens": [B, S+1]} or
    {"inputs", "targets"} (+ optional "mask").

    ``fused_ce`` (default: auto — on when vocab >= 8192) routes the
    lm-head projection through
    ``ops.fused.fused_linear_cross_entropy`` so fp32 logits are never
    materialized at [B, S, V] — the dominant activation at long seq."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    mask = batch.get("mask")
    if fused_ce is None:
        fused_ce = cfg.vocab_size >= _FUSED_CE_MIN_VOCAB
    if fused_ce:
        from dlrover_tpu.ops.fused import fused_linear_cross_entropy

        hidden = forward_hidden(params, inputs, cfg, attention_fn)
        with jax.named_scope("head_loss"):
            return fused_linear_cross_entropy(
                hidden,
                params["lm_head"],
                targets,
                mask,
                chunk_rows=cfg.ce_chunk_rows,
            )
    logits = forward(params, inputs, cfg, attention_fn)
    with jax.named_scope("head_loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, targets[..., None], axis=-1
        ).squeeze(-1)
        if mask is not None:
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
        return jnp.mean(nll)
