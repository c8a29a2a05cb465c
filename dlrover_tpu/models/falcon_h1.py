"""Falcon-H1: a hybrid decoder block — Mamba-2 (SSD) heads beside
attention heads on the same normalised input — for the serving plane.

Published description: ``transformers/models/falcon_h1`` (family
Falcon-H1 0.5B-34B, 2025-05).  With ``h = RMSNorm(x)``:

- SSM heads: ``p = in_proj(h * ssm_in_multiplier) * mup`` split into
  ``[z | x | B | C | dt]``; ``xBC = silu(causal_conv1d(x|B|C))``
  (depthwise, ``mamba_d_conv`` taps, bias); per head ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t`` with ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``y =
  RMSNorm_grouped(y * silu(z))`` (``mamba_norm_before_gate`` false);
  ``m = out_proj(y) * ssm_out_multiplier``.
- Attention heads, in parallel on ``h``: GQA with full rotary, ``k``
  scaled by ``key_multiplier``; ``a = o_proj(attn) *
  attention_out_multiplier``.
- ``x += m + a``; then the gated MLP on ``RMSNorm(x)`` with
  ``mlp_multipliers`` on the gate and on the output.
- Embedding times ``embedding_multiplier``, final RMSNorm, logits times
  ``lm_head_multiplier``.

What the serving plane needs of a model (``rl/scheduler.py`` says what
it takes) is here in the shape ``models/llama.py`` gives it: a config
whose ``n_layers`` / ``n_kv_heads`` / ``head_dim`` / ``dtype`` are the
paged K/V geometry and whose ``lane_state()`` declares what ELSE a lane
keeps — the conv tail and the recurrent state, which have no positions,
cannot be shared by prefix and are overwritten by every token;
``paged_prefill_chunk`` (told the lane and how many tokens of the
padded chunk are real) and ``paged_decode_step`` (advances only
``active`` lanes).  Attention goes through the shared
``ops/paged_attention`` ops, so ``DLROVER_TPU_PAGED_KERNEL`` selects
the same kernels as for the dense block; the decode recurrence is
``ops/ssm.ssm_decode_update``.  Parameters are stacked on a leading
layer axis.  There is no training path: no loss, no logical axes.
"""

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.models.llama import (
    _apply_rope_rows,
    apply_rope,
    dot_product_attention,
    qkv_heads,
    rms_norm,
    rope_frequencies,
    serving_copy,
)
from dlrover_tpu.ops.ssm import ssd_chunk_scan, ssm_decode_update


@dataclass(frozen=True)
class FalconH1Config:
    """The published ``config.json`` keys that shape the model, under
    their own names; ``max_seq_len`` and ``dtype`` are the program's."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, ...] = (1.0, 1.0)
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # the keywords ride through JSON: lists come back, and a frozen
        # dataclass must stay hashable
        for name in ("ssm_multipliers", "mlp_multipliers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        # published as the integer 100000000000, more than an int32
        object.__setattr__(self, "rope_theta", float(self.rope_theta))
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must equal mamba_d_ssm"
            )

    # what the serving scheduler reads off a model config
    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_dim(self) -> int:
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    def lane_state(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Per layer and LANE, beside the paged K/V: ``{leaf: (shape,
        dtype)}``.  The conv tail is the last ``d_conv - 1`` inputs of
        the depthwise convolution (channels minormost: 5120 is 40 lane
        tiles, a minor axis of 3 would be padded to 128); the
        recurrent state is float32 — it is a running sum over the
        whole sequence, and rounding it to bfloat16 every token would
        lose what a slowly decaying head remembers."""
        return {
            "conv": ((self.mamba_d_conv - 1, self.conv_dim), jnp.float32),
            "ssm": (
                (self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
                jnp.float32,
            ),
        }

    @staticmethod
    def tiny(**overrides) -> "FalconH1Config":
        """Test-sized config with every multiplier away from 1."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=128, mamba_d_ssm=64, mamba_n_heads=4,
            mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
            mamba_d_conv=4, mamba_chunk_size=8, max_seq_len=128,
            embedding_multiplier=1.7, lm_head_multiplier=0.6,
            attention_in_multiplier=0.9, attention_out_multiplier=0.7,
            key_multiplier=0.8, ssm_in_multiplier=1.2,
            ssm_out_multiplier=0.75,
            ssm_multipliers=(0.9, 1.1, 0.8, 1.2, 0.7),
            mlp_multipliers=(0.85, 0.65),
        )
        base.update(overrides)
        return FalconH1Config(**base)


# ---------------------------------------------------------------- params

# of the serving copy, which holds ``wq``, ``wk``, ``wv`` fused
_SERVING_MATMUL_LEAVES = (
    "in_proj", "out_proj", "wqkv", "wo", "w_gate", "w_up", "w_down"
)


def param_shapes(cfg: FalconH1Config) -> Dict:
    """``{name: shape}`` of the parameter tree, layers stacked on a
    leading axis.  ``conv_w[l, k]`` multiplies the input ``d_conv - 1 -
    k`` tokens back (``k = d_conv - 1`` is the current token: the
    published ``conv1d.weight[:, 0, k]``)."""
    d, L, v = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    nh, nkv, hd = (
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    )
    return {
        "embed": (v, d),
        "layers": {
            "norm": (L, d),
            "in_proj": (L, d, cfg.in_proj_dim),
            "conv_w": (L, cfg.mamba_d_conv, cfg.conv_dim),
            "conv_b": (L, cfg.conv_dim),
            "dt_bias": (L, cfg.mamba_n_heads),
            "A_log": (L, cfg.mamba_n_heads),
            "D": (L, cfg.mamba_n_heads),
            "ssm_norm": (L, cfg.mamba_d_ssm),
            "out_proj": (L, cfg.mamba_d_ssm, d),
            "wq": (L, d, nh * hd),
            "wk": (L, d, nkv * hd),
            "wv": (L, d, nkv * hd),
            "wo": (L, nh * hd, d),
            "mlp_norm": (L, d),
            "w_gate": (L, d, cfg.intermediate_size),
            "w_up": (L, d, cfg.intermediate_size),
            "w_down": (L, cfg.intermediate_size, d),
        },
        "final_norm": (d,),
        "lm_head": (d, v),
    }


def init_params(key, cfg: FalconH1Config) -> Dict:
    """Float32 weights: matrices ``normal(0, fan_in ** -0.5)``, norm
    weights and ``D`` 1, ``A = 1 .. heads`` and ``dt`` log-uniform in
    [1e-3, 1e-1] as the published code initialises them."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    heads = cfg.mamba_n_heads
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name in ("norm", "ssm_norm", "mlp_norm", "final_norm", "D"):
            leaf = jnp.ones(shape, jnp.float32)
        elif name == "conv_b":
            leaf = jnp.zeros(shape, jnp.float32)
        elif name == "A_log":
            leaf = jnp.broadcast_to(
                jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)), shape
            )
        elif name == "dt_bias":
            dt = jnp.exp(
                jax.random.uniform(k, shape, jnp.float32)
                * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3)
            )
            leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
        else:
            fan_in = {
                "embed": shape[-1], "conv_w": cfg.mamba_d_conv,
            }.get(name, shape[-2])
            leaf = (
                jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            )
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def serving_params(params: Dict, cfg: FalconH1Config) -> Dict:
    """The tree the serving programs compute on: the embedding, the
    head and the matrices of a layer in ``cfg.dtype``, with ``wq``,
    ``wk`` and ``wv`` held as ONE leaf ``wqkv`` ``[L, D, (heads + 2 *
    kv_heads) * head_dim]`` that one matmul reads in place (the three
    are not in the returned tree); the small float32 leaves (norms,
    conv, ``dt_bias``, ``A_log``, ``D``) as given.  Made by
    ``llama.serving_copy`` in one jitted program; every leaf that needs
    neither cast nor fusion is returned as the same array, so of a
    checkpoint published in the compute dtype only the fused leaf is
    new, and a tree that is already a serving copy comes back as it
    is."""
    return serving_copy(params, cfg.dtype, _SERVING_MATMUL_LEAVES)


# ---------------------------------------------------------------- pieces


def _mup(cfg: FalconH1Config) -> jnp.ndarray:
    """The per-channel multiplier of ``in_proj``'s output."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    sizes = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([
        jnp.full((n,), m, jnp.float32)
        for n, m in zip(sizes, cfg.ssm_multipliers)
    ])


def _proj(a, w, dt):
    return jnp.matmul(
        a, w.astype(dt), preferred_element_type=jnp.float32
    ).astype(dt)


def _ssm_inputs(h, lp, cfg: FalconH1Config):
    """``h [..., D]`` -> float32 ``z [..., d_ssm]``, raw ``xBC [...,
    conv_dim]``, raw ``dt [..., heads]``."""
    p = jnp.matmul(
        h * jnp.asarray(cfg.ssm_in_multiplier, h.dtype),
        lp["in_proj"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ) * _mup(cfg)
    return jnp.split(
        p, (cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.conv_dim), axis=-1
    )


def _causal_conv(window, lp):
    """``window [..., T + K - 1, C]`` (the tail before the run, then
    the run) -> ``silu(conv) [..., T, C]``."""
    k = lp["conv_w"].shape[0]
    t = window.shape[-2] - (k - 1)
    out = lp["conv_b"]
    for j in range(k):
        out = out + lp["conv_w"][j] * lax.slice_in_dim(
            window, j, j + t, axis=window.ndim - 2
        )
    return jax.nn.silu(out)


def _split_xbc(xbc, cfg: FalconH1Config):
    """``[..., conv_dim]`` -> ``x [..., H, P]``, ``B``/``C`` ``[..., G,
    N]``."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    x, b, c = jnp.split(xbc, (cfg.mamba_d_ssm, cfg.mamba_d_ssm + gn), -1)
    lead = xbc.shape[:-1]
    return (
        x.reshape(lead + (cfg.mamba_n_heads, cfg.mamba_d_head)),
        b.reshape(lead + (cfg.mamba_n_groups, cfg.mamba_d_state)),
        c.reshape(lead + (cfg.mamba_n_groups, cfg.mamba_d_state)),
    )


def _gated_norm(y, z, weight, cfg: FalconH1Config):
    """``RMSNorm`` over each of the ``mamba_n_groups`` groups of ``y *
    silu(z)``, times the weight; float32."""
    g = cfg.mamba_n_groups
    y = y * jax.nn.silu(z)
    grouped = y.reshape(y.shape[:-1] + (g, y.shape[-1] // g))
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, -1, keepdims=True) + cfg.rms_norm_eps
    )
    return grouped.reshape(y.shape) * weight


def _mlp(x, lp, cfg: FalconH1Config):
    dt = cfg.dtype
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(
        _proj(h, lp["w_gate"], dt)
        * jnp.asarray(cfg.mlp_multipliers[0], dt)
    )
    return _proj(gate * _proj(h, lp["w_up"], dt), lp["w_down"], dt) * (
        jnp.asarray(cfg.mlp_multipliers[1], dt)
    )


def _qkv(h, lp, cfg: FalconH1Config):
    dt = cfg.dtype
    q, k, v = qkv_heads(
        h * jnp.asarray(cfg.attention_in_multiplier, dt), lp, dt,
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
    )
    return q, k * jnp.asarray(cfg.key_multiplier, dt), v


@jax.named_scope("head")
def _logits(x, params, cfg: FalconH1Config):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    ) * cfg.lm_head_multiplier


@jax.named_scope("embed")
def _embed(params, tokens, cfg: FalconH1Config):
    return params["embed"].astype(cfg.dtype)[tokens] * jnp.asarray(
        cfg.embedding_multiplier, cfg.dtype
    )


# ------------------------------------------------------- whole sequences


def forward(params: Dict, tokens: jnp.ndarray, cfg: FalconH1Config):
    """tokens [B, T] -> float32 logits [B, T, vocab]: the whole
    sequence at once, the recurrence as a chunked scan from a zero
    state (chunks of ``mamba_chunk_size``)."""
    dt = cfg.dtype
    bsz, t = tokens.shape
    x = _embed(params, tokens, cfg)
    cos, sin = rope_frequencies(cfg, jnp.arange(t))
    k_taps = cfg.mamba_d_conv

    def body(x, lp):
        h = rms_norm(x, lp["norm"], cfg.rms_norm_eps)
        z, xbc, dt_raw = _ssm_inputs(h, lp, cfg)
        window = jnp.pad(xbc, ((0, 0), (k_taps - 1, 0), (0, 0)))
        xs, b, c = _split_xbc(_causal_conv(window, lp), cfg)
        y, _ = ssd_chunk_scan(
            xs, jax.nn.softplus(dt_raw + lp["dt_bias"]),
            -jnp.exp(lp["A_log"]), b, c, lp["D"],
            jnp.zeros(
                (bsz, cfg.mamba_n_heads, cfg.mamba_d_head,
                 cfg.mamba_d_state), jnp.float32,
            ),
            cfg.mamba_chunk_size,
        )
        y = _gated_norm(
            y.reshape(bsz, t, cfg.mamba_d_ssm), z, lp["ssm_norm"], cfg
        )
        m = _proj(y.astype(dt), lp["out_proj"], dt) * jnp.asarray(
            cfg.ssm_out_multiplier, dt
        )
        q, k, v = _qkv(h, lp, cfg)
        attn = dot_product_attention(
            apply_rope(q, cos, sin), apply_rope(k, cos, sin), v
        )
        a = _proj(
            attn.reshape(bsz, t, -1), lp["wo"], dt
        ) * jnp.asarray(cfg.attention_out_multiplier, dt)
        x = x + m + a
        return x + _mlp(x, lp, cfg), None

    x, _ = lax.scan(body, x, params["layers"])
    return _logits(x, params, cfg)


# ------------------------------------------------------- serving programs


@jax.named_scope("prefill")
def paged_prefill_chunk(
    params: Dict,
    tokens: jnp.ndarray,  # [1, C] one sequence's prompt chunk, padded
    pool: Dict,  # k, v [L, blocks, bs, KV, D]; conv, ssm [L, lanes, ...]
    block_table: jnp.ndarray,  # [max_blocks] int32
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    lane: jnp.ndarray,  # scalar int32: the lane whose state this is
    real: jnp.ndarray,  # scalar int32: tokens of the chunk that are real
    cfg: FalconH1Config,
) -> Tuple[jnp.ndarray, Dict]:
    """Prefill ``real`` prompt positions of ONE sequence: K/V into its
    paged blocks, the conv tail and the recurrent state into its
    lane's slab.  The state starts from zero at ``start_pos == 0`` and
    from the lane's slab otherwise (the chunk before left it there,
    whatever other lanes did in between); the padded tail advances
    neither the state nor the conv tail (``dt == 0`` there) and writes
    its K/V to the null block.  Returns (logits [1, C, vocab], pool)."""
    from dlrover_tpu.ops.paged_attention import (
        paged_prefill_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    _, c = tokens.shape
    bs, mb = pool["k"].shape[2], block_table.shape[0]
    k_taps = cfg.mamba_d_conv
    steps = jnp.arange(c)
    valid = steps < real
    positions = start_pos + steps
    x = _embed(params, tokens, cfg)
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, positions)
        blk_idx = positions // bs
        blks = jnp.where(
            valid & (blk_idx < mb),
            block_table[jnp.minimum(blk_idx, mb - 1)], 0,
        )
        offs = jnp.where(valid, positions % bs, 0)
    fresh = start_pos == 0

    def body(carry, layer_in, kv):
        x, ssm_all = carry
        lp, conv = layer_in
        with jax.named_scope("attn"):  # the ONE norm of both mixers
            h = rms_norm(x, lp["norm"], cfg.rms_norm_eps)
        with jax.named_scope("ssm"):
            z, xbc, dt_raw = _ssm_inputs(h, lp, cfg)
            tail = jnp.where(
                fresh, 0.0, lax.dynamic_index_in_dim(conv, lane, 0, False)
            )
            window = jnp.concatenate([tail, xbc[0]], axis=0)  # [K-1+C, Cd]
            xs, b, cc = _split_xbc(_causal_conv(window, lp), cfg)
            # the inputs of the last K-1 REAL tokens (reaching back into
            # the old tail where the chunk holds fewer)
            conv = lax.dynamic_update_index_in_dim(
                conv,
                lax.dynamic_slice_in_dim(window, real, k_taps - 1, 0),
                lane, 0,
            )
            state = jnp.where(
                fresh, 0.0,
                lax.dynamic_slice(
                    ssm_all, (kv.layer, lane, 0, 0, 0),
                    (1, 1) + ssm_all.shape[2:],
                )[0],
            )
            y, state = ssd_chunk_scan(
                xs[None],
                jnp.where(
                    valid[:, None],
                    jax.nn.softplus(dt_raw[0] + lp["dt_bias"]),
                    0.0,
                )[None],
                -jnp.exp(lp["A_log"]), b[None], cc[None], lp["D"], state,
                cfg.mamba_chunk_size,
            )
            ssm_all = lax.dynamic_update_slice(
                ssm_all, state[None].astype(ssm_all.dtype),
                (kv.layer, lane, 0, 0, 0),
            )
            y = _gated_norm(
                y.reshape(1, c, cfg.mamba_d_ssm), z, lp["ssm_norm"], cfg
            )
            m = _proj(y.astype(dt), lp["out_proj"], dt) * jnp.asarray(
                cfg.ssm_out_multiplier, dt
            )
        with jax.named_scope("attn"):
            q, k, v = _qkv(h, lp, cfg)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            kv = kv.write(k[0], v[0], blks, offs)
            attn = paged_prefill_attention(
                q[0], kv.k, kv.v, kv.tables(block_table), start_pos
            )
            a = _proj(
                attn.reshape(1, c, -1), lp["wo"], dt
            ) * jnp.asarray(cfg.attention_out_multiplier, dt)
            x = x + m + a
        with jax.named_scope("mlp"):
            x = x + _mlp(x, lp, cfg)
        return (x, ssm_all), conv, kv

    (x, ssm_all), new_conv, new_k, new_v = scan_layers_over_pool(
        body, (x, pool["ssm"]), (params["layers"], pool["conv"]),
        pool["k"], pool["v"],
    )
    return _logits(x, params, cfg), {
        "k": new_k, "v": new_v, "conv": new_conv, "ssm": ssm_all,
    }


@jax.named_scope("decode")
def paged_decode_step(
    params: Dict,
    tokens: jnp.ndarray,  # [B] current token per lane
    pool: Dict,  # k, v [L, blocks, bs, KV, D]; conv, ssm [L, lanes, ...]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    positions: jnp.ndarray,  # [B] int32 position being decoded per lane
    active: jnp.ndarray,  # [B] bool: the lane decodes this step
    cfg: FalconH1Config,
) -> Tuple[jnp.ndarray, Dict]:
    """One continuous-batching decode step: every ACTIVE lane advances
    by one token.  An inactive lane — free, or in the middle of its
    prefill — writes its K/V to the null block and comes out with its
    conv tail and its recurrent state bitwise as they went in.  Shapes
    depend on (lanes, pool geometry) only: compiled once."""
    from dlrover_tpu.ops.paged_attention import (
        paged_decode_attention,
        scan_layers_over_pool,
    )

    dt = cfg.dtype
    n = tokens.shape[0]
    bs, mb = pool["k"].shape[2], block_tables.shape[1]
    x = _embed(params, tokens, cfg)[:, None]  # [B, 1, D]
    with jax.named_scope("attn"):
        cos, sin = rope_frequencies(cfg, positions)
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

    def body(carry, layer_in, kv):
        x, ssm_all = carry
        lp, conv = layer_in
        with jax.named_scope("attn"):  # the ONE norm of both mixers
            h = rms_norm(x, lp["norm"], cfg.rms_norm_eps)
        with jax.named_scope("ssm"):
            z, xbc, dt_raw = _ssm_inputs(h[:, 0], lp, cfg)
            # [B, K, Cd]
            window = jnp.concatenate([conv, xbc[:, None]], axis=1)
            xs, b, c = _split_xbc(_causal_conv(window, lp)[:, 0], cfg)
            conv = jnp.where(active[:, None, None], window[:, 1:], conv)
            y, ssm_all = ssm_decode_update(
                ssm_all, kv.layer, xs,
                jnp.where(
                    active[:, None],
                    jax.nn.softplus(dt_raw + lp["dt_bias"]),
                    0.0,
                ),
                -jnp.exp(lp["A_log"]), b, c, lp["D"],
            )
            y = _gated_norm(
                y.reshape(n, cfg.mamba_d_ssm), z, lp["ssm_norm"], cfg
            )
            m = _proj(y.astype(dt), lp["out_proj"], dt) * jnp.asarray(
                cfg.ssm_out_multiplier, dt
            )
        with jax.named_scope("attn"):
            q, k, v = _qkv(h, lp, cfg)
            q = _apply_rope_rows(q, cos, sin)
            k = _apply_rope_rows(k, cos, sin)
            kv = kv.write(k[:, 0], v[:, 0], blk, off)
            attn = paged_decode_attention(
                q[:, 0], kv.k, kv.v, kv.tables(block_tables), seq_lens
            )
            a = _proj(
                attn.reshape(n, 1, -1), lp["wo"], dt
            ) * jnp.asarray(cfg.attention_out_multiplier, dt)
            x = x + m[:, None] + a
        with jax.named_scope("mlp"):
            x = x + _mlp(x, lp, cfg)
        return (x, ssm_all), conv, kv

    (x, ssm_all), new_conv, new_k, new_v = scan_layers_over_pool(
        body, (x, pool["ssm"]), (params["layers"], pool["conv"]),
        pool["k"], pool["v"],
    )
    return _logits(x, params, cfg)[:, 0], {
        "k": new_k, "v": new_v, "conv": new_conv, "ssm": ssm_all,
    }
