"""Pallas blockwise int8 quantize/dequantize — low-bit optimizer states.

Reference parity: atorch's CUDA quantization kernels
(``atorch/atorch/ops/csrc/quantization/quantize.cu:150``,
``dequantize.cu:67``, ``quantization_optimizer.cu:686``) which store
Adam moments in 1-byte formats.  The TPU form is a Pallas kernel pair:
per-block absmax scaling to int8 (symmetric, matching the reference's
signed dynamic quantization), tiled (block, 128)-aligned for the VPU.

Used by ``dlrover_tpu.optimizers.low_bit`` to keep optimizer state in
1 byte/param (4x HBM saving vs fp32 moments).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas_utils import use_interpret

# quantization block: one scale per BLOCK elements
BLOCK = 1024
_LANES = 128
_SUBLANES = BLOCK // _LANES
# Mosaic requires the scales output's second-minor block dim to be a
# multiple of 8 (or the whole array): handle 8 quant blocks per kernel
# invocation so the scales block is a legal (8, 1)
_GROUP = 8


def _quant_kernel(x_ref, q_ref, scale_ref, *, group: int):
    # x: [group * _SUBLANES, 128]; static unrolled loop per quant
    # block — no in-kernel reshapes, one scalar scale store per block
    for g in range(group):
        lo, hi = g * _SUBLANES, (g + 1) * _SUBLANES
        x = x_ref[lo:hi].astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x))
        scale = jnp.maximum(absmax / 127.0, 1e-12)
        q_ref[lo:hi] = jnp.clip(
            jnp.round(x / scale), -127, 127
        ).astype(jnp.int8)
        scale_ref[g, 0] = scale


def _dequant_kernel(q_ref, scale_ref, x_ref, *, group: int):
    for g in range(group):
        lo, hi = g * _SUBLANES, (g + 1) * _SUBLANES
        x_ref[lo:hi] = (
            q_ref[lo:hi].astype(jnp.float32) * scale_ref[g, 0]
        )


def _group_for(n_blocks: int) -> int:
    """Scales block legality: second-minor block dim must be a
    multiple of 8 OR the whole array dim — small tensors use one
    whole-array invocation instead of paying 8-block padding."""
    return n_blocks if n_blocks < _GROUP else _GROUP


@jax.jit
def _quantize_2d(x):
    n_blocks = x.shape[0] // _SUBLANES
    group = _group_for(n_blocks)
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, group=group),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, jnp.int8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ),
        grid=(n_blocks // group,),
        in_specs=[
            pl.BlockSpec(
                (group * _SUBLANES, _LANES), lambda i: (i, 0)
            ),
        ],
        out_specs=(
            pl.BlockSpec(
                (group * _SUBLANES, _LANES), lambda i: (i, 0)
            ),
            pl.BlockSpec(
                (group, 1), lambda i: (i, 0),
                memory_space=pltpu.SMEM,
            ),
        ),
        interpret=use_interpret(),
    )(x)
    return q, scales


@jax.jit
def _dequantize_2d(q, scales):
    n_blocks = q.shape[0] // _SUBLANES
    group = _group_for(n_blocks)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, group=group),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid=(n_blocks // group,),
        in_specs=[
            pl.BlockSpec(
                (group * _SUBLANES, _LANES), lambda i: (i, 0)
            ),
            pl.BlockSpec(
                (group, 1), lambda i: (i, 0),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (group * _SUBLANES, _LANES), lambda i: (i, 0)
        ),
        interpret=use_interpret(),
    )(q, scales)


def _fused_adam_kernel(
    g_ref, mu_q_ref, mu_s_ref, nu_q_ref, nu_s_ref, bc1_ref, bc2_ref,
    upd_ref, mu_q_out, mu_s_out, nu_q_out, nu_s_out,
    *, group: int, lr: float, b1: float, b2: float, eps: float,
):
    """One pass over a moment block: dequant -> Adam moment update ->
    update value -> requant.  Replaces 4 pallas_calls + XLA glue per
    leaf (reference fuses exactly this on CUDA:
    ``quantization_optimizer.cu:686``); int8 payloads are read and
    written ONCE and the f32 moments never touch HBM."""
    bc1 = bc1_ref[0, 0]
    bc2 = bc2_ref[0, 0]
    for i in range(group):
        lo, hi = i * _SUBLANES, (i + 1) * _SUBLANES
        g = g_ref[lo:hi].astype(jnp.float32)
        mu = mu_q_ref[lo:hi].astype(jnp.float32) * mu_s_ref[i, 0]
        # nu is stored as sqrt(nu) — see optimizers/low_bit.py for the
        # dynamic-range rationale
        nu_root = nu_q_ref[lo:hi].astype(jnp.float32) * nu_s_ref[i, 0]
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu_root * nu_root + (1.0 - b2) * g * g
        upd_ref[lo:hi] = -lr * (mu / bc1) / (
            jnp.sqrt(nu / bc2) + eps
        )
        s_mu = jnp.maximum(jnp.max(jnp.abs(mu)) / 127.0, 1e-12)
        mu_q_out[lo:hi] = jnp.clip(
            jnp.round(mu / s_mu), -127, 127
        ).astype(jnp.int8)
        mu_s_out[i, 0] = s_mu
        nu_root_new = jnp.sqrt(nu)
        s_nu = jnp.maximum(
            jnp.max(jnp.abs(nu_root_new)) / 127.0, 1e-12
        )
        nu_q_out[lo:hi] = jnp.clip(
            jnp.round(nu_root_new / s_nu), -127, 127
        ).astype(jnp.int8)
        nu_s_out[i, 0] = s_nu


@functools.partial(
    jax.jit, static_argnames=("lr", "b1", "b2", "eps")
)
def _fused_adam_2d(g2, mu_q, mu_s, nu_q, nu_s, bc1, bc2,
                   *, lr, b1, b2, eps):
    n_blocks = g2.shape[0] // _SUBLANES
    group = _group_for(n_blocks)
    smem_scalar = pl.BlockSpec(
        (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
    )
    data_spec = pl.BlockSpec(
        (group * _SUBLANES, _LANES), lambda i: (i, 0)
    )
    scale_spec = pl.BlockSpec(
        (group, 1), lambda i: (i, 0), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        functools.partial(
            _fused_adam_kernel,
            group=group, lr=lr, b1=b1, b2=b2, eps=eps,
        ),
        out_shape=(
            jax.ShapeDtypeStruct(g2.shape, jnp.float32),
            jax.ShapeDtypeStruct(g2.shape, jnp.int8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
            jax.ShapeDtypeStruct(g2.shape, jnp.int8),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ),
        grid=(n_blocks // group,),
        in_specs=[
            data_spec,  # grads
            data_spec,  # mu int8
            scale_spec,  # mu scales
            data_spec,  # nu int8
            scale_spec,  # nu scales
            smem_scalar,  # bias correction 1
            smem_scalar,  # bias correction 2
        ],
        out_specs=(
            data_spec,   # update
            data_spec,   # new mu int8
            scale_spec,  # new mu scales
            data_spec,   # new nu int8
            scale_spec,  # new nu scales
        ),
        interpret=use_interpret(),
    )(g2, mu_q, mu_s, nu_q, nu_s, bc1, bc2)


def fused_int8_adam_update(
    grad, mu_q, mu_scales, nu_q, nu_scales, meta,
    bc1, bc2, *, lr, b1, b2, eps,
):
    """Fused Adam step over int8 moments.

    ``meta`` is the ``(orig_shape, n)`` pair from
    :func:`quantize_blockwise`; ``bc1``/``bc2`` are the (traced)
    bias-correction scalars.  Returns ``(update, new_mu_q,
    new_mu_scales, new_nu_q, new_nu_scales)`` with the update shaped
    like ``grad``.  Pad-region lanes compute garbage updates that the
    final slice discards; their moment blocks quantize the padded
    zeros, exactly like the unfused path."""
    shape, n = meta
    if n == 0:
        return (
            jnp.zeros(shape, jnp.float32),
            mu_q, mu_scales, nu_q, nu_scales,
        )
    flat = grad.reshape(-1).astype(jnp.float32)
    flat, _ = _pad_to_blocks(flat)
    g2 = flat.reshape(-1, _LANES)
    bc1 = jnp.asarray(bc1, jnp.float32).reshape(1, 1)
    bc2 = jnp.asarray(bc2, jnp.float32).reshape(1, 1)
    upd2, mu_q2, mu_s2, nu_q2, nu_s2 = _fused_adam_2d(
        g2, mu_q, mu_scales, nu_q, nu_scales, bc1, bc2,
        lr=lr, b1=b1, b2=b2, eps=eps,
    )
    upd = upd2.reshape(-1)[:n].reshape(shape)
    return upd, mu_q2, mu_s2, nu_q2, nu_s2


def _pad_to_blocks(flat):
    n = flat.shape[0]
    padded = ((n + BLOCK - 1) // BLOCK) * BLOCK
    n_blocks = padded // BLOCK
    if n_blocks > _GROUP and n_blocks % _GROUP:
        # large tensors round their BLOCK count to a full kernel group
        n_blocks += _GROUP - (n_blocks % _GROUP)
        padded = n_blocks * BLOCK
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat, n


def quantize_blockwise(x: jnp.ndarray):
    """Any-shape fp array -> (int8 payload [P/128,128], scales, meta)."""
    flat = x.reshape(-1).astype(jnp.float32)
    if flat.shape[0] == 0:  # zero-size leaf: nothing to quantize
        return (
            jnp.zeros((0, _LANES), jnp.int8),
            jnp.zeros((0, 1), jnp.float32),
            (x.shape, 0),
        )
    flat, n = _pad_to_blocks(flat)
    x2 = flat.reshape(-1, _LANES)
    q, scales = _quantize_2d(x2)
    return q, scales, (x.shape, n)


def dequantize_blockwise(q, scales, meta, dtype=jnp.float32):
    shape, n = meta
    if n == 0:
        return jnp.zeros(shape, dtype)
    out = _dequantize_2d(q, scales).reshape(-1)[:n]
    return out.reshape(shape).astype(dtype)
