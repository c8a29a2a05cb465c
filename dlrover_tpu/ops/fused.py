"""Fused normalization + fused linear-cross-entropy.

Reference parity: atorch ships a fused LayerNorm module
(``atorch/atorch/normalization/layernorm.py``) and fused losses
(``atorch/atorch/modules/transformer/losses.py``) as CUDA-side fusions.
The TPU forms:

* ``rms_norm`` — a Pallas forward kernel that computes the row rstd and
  the normalized output in one VMEM pass (one HBM read of ``x`` instead
  of the two XLA sometimes emits for the mean-of-squares + scale pair),
  with a ``custom_vjp`` whose backward reuses the saved rstd — no
  variance recompute.  The flagship llama family is RMSNorm, so that is
  the fused form; LayerNorm callers get the same treatment via
  ``layer_norm`` (plain XLA — its mean+var already fuse well and no
  model here is LayerNorm-hot).
* ``fused_linear_cross_entropy`` — the last-layer fusion that matters
  on TPU: next-token CE normally materializes fp32 logits ``[B*S, V]``
  *twice* (logits + log-softmax), ~0.5 GB per 4k-seq batch row at
  V=32k.  The fused form chunks the rows, computes
  ``chunk @ W -> logsumexp -> nll`` under ``jax.checkpoint`` inside a
  ``lax.scan``, so peak logits memory is ``chunk x V`` and the backward
  recomputes each chunk's logits while accumulating ``dW`` in fp32.
  Pure XLA (matmul-dominated — the MXU path — so a hand kernel would
  only get in the way of the compiler's own pipelining); exact same
  math as the dense loss.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas_utils import named_kernel, use_interpret

_LANES = 128
_ROWS = 8  # row block: one sublane tile


# ------------------------------------------------------------ RMSNorm


def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y_ref[...] = (
        x * rstd * w_ref[...].astype(jnp.float32)
    ).astype(y_ref.dtype)
    rstd_ref[...] = rstd


def _rms_fwd_pallas(x2, w, eps):
    n, d = x2.shape
    grid = n // _ROWS
    return named_kernel(
        "rmsnorm_fwd",
        pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            out_shape=(
                jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                jax.ShapeDtypeStruct((n, 1), jnp.float32),
            ),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((_ROWS, d), lambda i: (i, 0)),
                pl.BlockSpec((d,), lambda i: (0,)),
            ],
            out_specs=(
                pl.BlockSpec((_ROWS, d), lambda i: (i, 0)),
                pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
            ),
            interpret=use_interpret(),
            name="rmsnorm_fwd",
        ),
    )(x2, w)


def _rms_plain(x, weight, eps):
    # weight multiply in fp32 with ONE final cast — the same rounding
    # as the Pallas kernel, so both paths produce identical values
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    return (
        (xf * rstd * weight.astype(jnp.float32)).astype(dtype),
        rstd,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps: float = 1e-5):
    """``x * rsqrt(mean(x^2) + eps) * weight`` over the last dim.

    Any leading shape; fused Pallas forward when the last dim is
    lane-aligned, plain XLA otherwise.  Numerics identical to the
    unfused form (fp32 statistics, output in ``x.dtype``).
    """
    return _rms_fwd(x, weight, eps)[0]


def _rms_fwd(x, weight, eps: float):
    d = x.shape[-1]
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    if use_interpret() or d % _LANES or n % _ROWS or n == 0:
        # off-TPU (or misaligned) the plain form is already one fused
        # XLA loop; the kernel itself is covered via interpret in tests
        y, rstd = _rms_plain(x, weight, eps)
        return y, (x, weight, rstd)
    x2 = x.reshape(n, d)
    y2, rstd = _rms_fwd_pallas(x2, weight, eps)
    return y2.reshape(*lead, d), (x, weight, rstd.reshape(*lead, 1))


def _rms_bwd(eps: float, res, g):
    x, weight, rstd = res
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    xhat = xf * rstd
    dxhat = gf * wf
    # d/dx of x*rsqrt(mean x^2 + eps): rstd * (dxhat - xhat * mean(dxhat*xhat))
    dot = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / d
    dx = (rstd * (dxhat - xhat * dot)).astype(x.dtype)
    dw = jnp.sum(
        (gf * xhat).reshape(-1, d), axis=0
    ).astype(weight.dtype)
    return dx, dw


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm over the last dim (fp32 statistics).  XLA fuses the
    mean/var/scale chain on TPU already; kept for API parity with the
    reference's fused module."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y.astype(dtype) * weight.astype(dtype)) + bias.astype(dtype)


# ---------------------------------------- fused linear cross entropy


def _chunk_nll(h_c, t_c, m_c, w, dtype):
    """[C, D] rows -> (sum nll, sum mask) for one chunk; logits exist
    only inside this (rematerialized) scope."""
    logits = jnp.matmul(
        h_c, w.astype(dtype), preferred_element_type=jnp.float32
    )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, t_c[:, None], axis=-1
    ).squeeze(-1)
    nll = lse - picked
    return jnp.sum(nll * m_c), jnp.sum(m_c)


def fused_linear_cross_entropy(
    hidden: jnp.ndarray,
    w_vocab: jnp.ndarray,
    targets: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    chunk_rows: int = 512,
) -> jnp.ndarray:
    """Mean next-token cross entropy of ``hidden @ w_vocab`` against
    ``targets`` without materializing the full logits tensor.

    hidden: [..., D] (bf16/fp32), w_vocab: [D, V], targets: [...] int,
    mask: optional [...] weights.  Rows are processed in
    ``chunk_rows``-sized chunks under ``jax.checkpoint`` inside a
    ``lax.scan`` — peak extra memory is one fp32 ``[chunk_rows, V]``
    block in forward AND backward (the backward recomputes each chunk's
    logits and accumulates ``dW`` chunk by chunk via the scan's
    cotangent sum).  Exact same math as dense CE (fp32 logits and
    reductions).
    """
    d = hidden.shape[-1]
    dtype = hidden.dtype
    h = hidden.reshape(-1, d)
    t = targets.reshape(-1)
    n = h.shape[0]
    m = (
        jnp.ones((n,), jnp.float32)
        if mask is None
        else mask.reshape(-1).astype(jnp.float32)
    )

    chunk = min(chunk_rows, n)
    n_pad = ((n + chunk - 1) // chunk) * chunk
    if n_pad != n:
        h = jnp.pad(h, ((0, n_pad - n), (0, 0)))
        t = jnp.pad(t, (0, n_pad - n))
        m = jnp.pad(m, (0, n_pad - n))  # padded rows carry zero weight
    n_chunks = n_pad // chunk

    body = jax.checkpoint(
        functools.partial(_chunk_nll, w=w_vocab, dtype=dtype)
    )

    def step(carry, xs):
        tot, cnt = carry
        h_c, t_c, m_c = xs
        s, c = body(h_c, t_c, m_c)
        return (tot + s, cnt + c), None

    (total, count), _ = lax.scan(
        step,
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (
            h.reshape(n_chunks, chunk, d),
            t.reshape(n_chunks, chunk),
            m.reshape(n_chunks, chunk),
        ),
    )
    return total / jnp.maximum(count, 1.0)
