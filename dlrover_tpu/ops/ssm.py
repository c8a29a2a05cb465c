"""State-space (Mamba-2 / SSD) ops of the serving plane's hybrid block.

A head of the recurrence keeps a state ``S [P, N]`` (head size x state
size) per sequence and advances it once a token:

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t C_t + D * x_t

``x_t [P]``, ``B_t``/``C_t`` ``[N]`` (shared by the heads of a group),
``dt_t > 0`` and ``A < 0`` scalars of the head.  Three ops:

- :func:`ssm_decode_update` — one token for every LANE of a
  continuous-batching decode step.  Memory-bound: a lane's state is
  read and written once, ``2 * H * P * N * 4`` bytes, for ``H * P``
  outputs.  The Pallas kernel (``ssm_decode_update`` on a device
  trace) updates the state IN PLACE in the stacked ``[layers, lanes,
  H, P, N]`` buffer the scheduler owns: the layer index rides in as a
  scalar-prefetch operand and the index maps address that layer's
  blocks, so no layer slab is ever sliced out of the buffer or written
  back into it.  A lane with ``dt == 0`` keeps its state bitwise
  (``exp(0) * S + 0``): that is how the caller leaves an inactive lane
  untouched.
- :func:`ssd_chunk_scan` — a run of tokens of one or more sequences in
  matmul form (the SSD algorithm of Mamba-2, arXiv:2405.21060, section
  6): within a chunk the recurrence is a masked ``(C B^T * L) X``
  product, between chunks a state is carried in float32.  Plain XLA;
  a token with ``dt == 0`` advances nothing, which is how a padded
  tail stays out of the state.
- :func:`ssm_scan_reference` — the recurrence token by token: what
  the other two must reproduce, and the decode update's jnp form.

Backend: the decode update follows ``DLROVER_TPU_PAGED_KERNEL`` like
the paged attention ops (``ops/paged_attention.paged_kernel_backend``):
the Pallas kernel on a TPU and wherever ``pallas`` is forced
(interpret mode off the chip), the jnp form otherwise.  A kernel that
fails to lower raises; nothing gives way silently.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def _head_block(heads_per_group: int) -> int:
    """Heads a grid step of the kernel holds: a block never straddles
    two groups (one ``B``/``C`` row a step), and 8 heads are one
    float32 sublane tile of the ``[heads, P]`` input and output."""
    hb = min(heads_per_group, 8)
    while heads_per_group % hb:
        hb -= 1
    return hb


def _update_kernel(layer_ref, s_ref, x_ref, a_ref, b_ref, c_ref,
                   y_ref, o_ref, *, hb: int):
    """One (lane, head block): ``hb`` states ``[P, N]`` in, out and one
    output row a head.  ``x`` arrives with ``P`` on the lane axis and
    is needed along sublanes (``x[p] * B[n]``), ``y`` leaves the lane
    reduction along sublanes and is stored along lanes: one small
    transpose each way a block."""
    del layer_ref  # consumed by the index maps
    b_row = b_ref[0, 0]  # [1, N]
    c_row = c_ref[0, 0]
    x_cols = x_ref[0].T  # [P, hb]
    ys = []
    for h in range(hb):
        s = (
            a_ref[0, h:h + 1, :] * s_ref[0, 0, h]
            + x_cols[:, h:h + 1] * b_row
        )
        o_ref[0, 0, h] = s
        ys.append(jnp.sum(s * c_row, axis=1, keepdims=True))  # [P, 1]
    y_ref[0] = jnp.concatenate(ys, axis=1).T


def _update_call(layer, state, dtx, decay, b, c):
    """``state [L, S, H, P, N]`` (aliased to the second output), ``dtx
    [S, H, P]`` (= dt * x), ``decay [S, H, N]`` (= exp(dt * A), one row
    a head), ``b``/``c`` ``[S, G, 1, N]`` -> ``(S C [S, H, P], state)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops.pallas_utils import named_kernel, use_interpret

    _, lanes, heads, p, n = state.shape
    per_group = heads // b.shape[1]
    hb = _head_block(per_group)

    def state_index(lane, j, layer_ref):
        return (layer_ref[0], lane, j, 0, 0)

    def head_index(lane, j, layer_ref):
        del layer_ref
        return (lane, j, 0)

    def group_index(lane, j, layer_ref):
        del layer_ref
        return (lane, (j * hb) // per_group, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(lanes, heads // hb),
        in_specs=[
            pl.BlockSpec((1, 1, hb, p, n), state_index),
            pl.BlockSpec((1, hb, p), head_index),
            pl.BlockSpec((1, hb, n), head_index),
            pl.BlockSpec((1, 1, 1, n), group_index),
            pl.BlockSpec((1, 1, 1, n), group_index),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), head_index),
            pl.BlockSpec((1, 1, hb, p, n), state_index),
        ],
    )
    name = "ssm_decode_update"
    return named_kernel(
        name,
        pl.pallas_call(
            functools.partial(_update_kernel, hb=hb),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((lanes, heads, p), jnp.float32),
                jax.ShapeDtypeStruct(state.shape, state.dtype),
            ],
            # operand 1 (after the scalar-prefetch layer index) is the
            # state: blocks of other layers are never visited and keep
            # their contents
            input_output_aliases={1: 1},
            interpret=use_interpret(),
            name=name,
        ),
    )(layer, state, dtx, decay, b, c)


def ssm_decode_update(
    state: jnp.ndarray,  # [L, S, H, P, N] float32, every layer's states
    layer: jnp.ndarray,  # scalar int32: the layer to advance
    x: jnp.ndarray,  # [S, H, P]
    dt: jnp.ndarray,  # [S, H] (0 leaves the lane's state as it is)
    a: jnp.ndarray,  # [H], negative
    b: jnp.ndarray,  # [S, G, N]
    c: jnp.ndarray,  # [S, G, N]
    d: jnp.ndarray,  # [H]
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every lane through layer ``layer``'s recurrence.
    Returns ``(y [S, H, P] float32, state)`` with that layer's states
    advanced and every other layer's as given."""
    from dlrover_tpu.ops.paged_attention import paged_kernel_backend

    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    if (backend or paged_kernel_backend()) == "pallas":
        n = state.shape[-1]
        decay = jnp.exp(dt * a.astype(f32)[None, :])  # [S, H]
        sc, state = _update_call(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            state,
            dt[..., None] * x,
            jnp.broadcast_to(decay[..., None], decay.shape + (n,)),
            b[:, :, None, :],
            c[:, :, None, :],
        )
        return sc + d.astype(f32)[None, :, None] * x, state
    # the recurrence itself, one token long
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, new = ssm_scan_reference(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], d, old
    )
    state = lax.dynamic_update_index_in_dim(
        state, new.astype(state.dtype), layer, 0
    )
    return y[:, 0], state


def ssd_chunk_scan(
    x: jnp.ndarray,  # [B, T, H, P]
    dt: jnp.ndarray,  # [B, T, H] (0 for a token that must not count)
    a: jnp.ndarray,  # [H], negative
    b: jnp.ndarray,  # [B, T, G, N]
    c: jnp.ndarray,  # [B, T, G, N]
    d: jnp.ndarray,  # [H]
    state: jnp.ndarray,  # [B, H, P, N] float32: the state before x[:, 0]
    chunk: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over ``T`` tokens in chunks of ``chunk``, in
    float32 at the highest matmul precision (these products are a
    thousandth of the block's operations, and the state they build is
    read by every later token).  ``T`` need not be a multiple of the
    chunk: the run is padded with ``dt == 0`` tokens, which advance
    nothing.  Returns ``(y [B, T, H, P], state after the last token)``.
    """
    f32 = jnp.float32
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    q = int(chunk)
    pad = (-t) % q
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if pad:
        x, dt, b, c = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    nc = (t + pad) // q
    x = x.reshape(bsz, nc, q, g, rep, p)
    dt = dt.reshape(bsz, nc, q, g, rep)
    b = b.reshape(bsz, nc, q, g, n)
    c = c.reshape(bsz, nc, q, g, n)
    da = dt * a.astype(f32).reshape(g, rep)  # [B, nc, q, G, R], <= 0
    cum = jnp.cumsum(da, axis=2)  # decay exponent up to and with token i
    dtx = dt[..., None] * x
    # within a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j)
    # dt_j x_j
    cb = jnp.einsum("bcign,bcjgn->bcgij", c, b, precision=_HIGHEST)
    cum_h = jnp.moveaxis(cum, 2, -1)  # [B, nc, G, R, q]
    seg = cum_h[..., :, None] - cum_h[..., None, :]  # [.., i, j]
    causal = jnp.tril(jnp.ones((q, q), bool))
    weights = cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcigrp", weights, dtx, precision=_HIGHEST
    )
    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, q, G, R]
    added = jnp.einsum(
        "bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, dtx, b, precision=_HIGHEST
    )
    whole = jnp.exp(cum[:, :, -1])  # [B, nc, G, R]: a chunk's decay

    def carry(s, inp):
        w, add = inp
        return w[..., None, None] * s + add, s  # emits the state BEFORE

    last, before = lax.scan(
        carry,
        state.astype(f32).reshape(bsz, g, rep, p, n),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(added, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # [B, nc, G, R, P, N]
    # the carried state's part: y_i += exp(cum_i) * (S_before C_i)
    y = y + jnp.einsum(
        "bcign,bcgrpn,bcigr->bcigrp", c, before, jnp.exp(cum),
        precision=_HIGHEST,
    )
    y = y + d.astype(f32).reshape(g, rep)[..., None] * x
    y = y.reshape(bsz, nc * q, h, p)[:, :t]
    return y, last.reshape(bsz, h, p, n)


def ssm_scan_reference(x, dt, a, b, c, d, state):
    """The recurrence one token at a time (``lax.scan``), float32: what
    the two ops above must reproduce.  Shapes as
    :func:`ssd_chunk_scan`."""
    f32 = jnp.float32
    rep = x.shape[2] // b.shape[2]
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    b = jnp.repeat(b, rep, axis=2)
    c = jnp.repeat(c, rep, axis=2)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp  # [B, H, P], [B, H], [B, H, N] x 2
        s = (
            jnp.exp(dt_t * a.astype(f32))[..., None, None] * s
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        )
        y_t = jnp.sum(s * c_t[:, :, None, :], -1)
        return s, y_t + d.astype(f32)[None, :, None] * x_t

    last, ys = lax.scan(
        step, state.astype(f32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)),
    )
    return jnp.moveaxis(ys, 0, 1), last
