"""Gated delta-rule (linear-attention) ops of the serving plane.

A head keeps a state ``S [dk, dv]`` (key size x value size) per
sequence and advances it once a token (Gated DeltaNet, arXiv:2412.06464;
FLA's ``GatedDeltaNet``):

    u_t = beta_t * (v_t - alpha_t * S_{t-1}^T k_t)
    S_t = alpha_t * S_{t-1} + k_t (x) u_t
    o_t = S_t^T q_t

``q_t``, ``k_t`` ``[dk]``, ``v_t`` ``[dv]``, ``alpha_t`` in (0, 1] and
``beta_t`` in [0, 2] scalars of the head.  Unlike ``ops/ssm.py``'s
diagonal recurrence the update READS the state through ``k`` before it
writes: ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
v_t^T``.  Three ops:

- :func:`gdn_decode_update` — one token for every LANE of a
  continuous-batching decode step.  Memory-bound: a lane's state is
  read and written once, ``2 * H * dk * dv * 4`` bytes.  The Pallas
  kernel (``gdn_decode_update`` on a device trace) updates the state IN
  PLACE in the stacked ``[layers, lanes, ...]`` buffer the scheduler
  owns: the layer index rides in as a scalar-prefetch operand, so no
  layer slab is sliced out or written back, and a lane whose ``real``
  is 0 is copied through bitwise.
- :func:`gdn_chunk_scan` — a run of tokens in sub-chunks by the WY
  form: within a sub-chunk of ``C`` tokens, with ``g_t`` the running
  product of ``alpha`` and ``A[t, j] = beta_t (g_t / g_j) (k_t . k_j)``
  for ``j < t``, the rows ``u_t`` solve the unit lower-triangular system
  ``(I + A) U = beta (V - diag(g) K S_0)``; then ``O = diag(g) Q S_0 +
  tril(Q K^T * decay) U`` and ``S_C = g_C S_0 + (K * g_C / g)^T U``.
  The part of ``U`` that does not depend on ``S_0`` and the part that
  multiplies it are solved for every sub-chunk at once: ``(I + A)^-1``
  is built by products on the MXU (:func:`_unit_lower_inverse`:
  forward substitution in blocks that double, two ``[C, C]`` products a
  doubling — a triangular-solve routine of the compiler's library
  costs a layer more than the rest of the scan) and multiplies
  ``[beta V | beta g K]`` once; a ``lax.scan`` carries ``S`` from one
  sub-chunk to the next.  Plain XLA, float32 at the highest matmul
  precision; a token with ``alpha == 1`` and ``beta == 0`` advances
  nothing, which is how a padded tail stays out of the state.
- :func:`gdn_scan_reference` — the recurrence token by token: what the
  other two must reproduce, and the decode update's jnp form.

**The state's layout.**  The slab a lane keeps is not ``[H, dk, dv]``:
at 96 x 192 the chip's ``(8, 128)`` tiling would pad 192 to 256, a third
more bytes than the mathematics moves, in memory and in every decode
step.  :func:`state_shape` packs ``g`` heads side by side in the minor
axis, the fewest whose ``g * dv`` is a multiple of 128 (two at 192:
``[H / 2, dk, 384]``), and :func:`pack_state` / :func:`unpack_state`
go between the two.  ``S^T k`` is then a multiply by ``k`` broadcast
along its head's columns and a reduction over the ``dk`` rows, ``k (x)
u`` a broadcast outer product: vector work beside a memory-bound
kernel.

Backend: the decode update follows ``DLROVER_TPU_PAGED_KERNEL`` like
``ops/ssm.ssm_decode_update``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------------ the layout


def state_group(heads: int, dv: int) -> int:
    """Heads packed side by side in the state's minor axis: the fewest
    that divide ``heads`` and make ``g * dv`` a multiple of 128 lanes,
    or all of them where none does."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * dv) % 128 == 0:
            return g
    return heads


def state_shape(heads: int, dk: int, dv: int) -> Tuple[int, int, int]:
    """``(H / g, dk, g * dv)``: what a lane keeps of one layer."""
    g = state_group(heads, dv)
    return (heads // g, dk, g * dv)


def pack_state(s: jnp.ndarray) -> jnp.ndarray:
    """``[..., H, dk, dv]`` -> ``[..., H / g, dk, g * dv]``."""
    heads, dk, dv = s.shape[-3:]
    g = state_group(heads, dv)
    lead = s.shape[:-3]
    s = s.reshape(lead + (heads // g, g, dk, dv))
    return jnp.swapaxes(s, -3, -2).reshape(lead + (heads // g, dk, g * dv))


def unpack_state(s: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``[..., H / g, dk, g * dv]`` -> ``[..., H, dk, dv]``."""
    groups, dk, width = s.shape[-3:]
    g = heads // groups
    lead = s.shape[:-3]
    s = s.reshape(lead + (groups, dk, g, width // g))
    return jnp.swapaxes(s, -3, -2).reshape(lead + (heads, dk, width // g))


# ------------------------------------------------------- the decode kernel


def _update_kernel(layer_ref, real_ref, s_ref, q_ref, k_ref, v_ref, a_ref,
                   b_ref, y_ref, o_ref, *, g: int, dv: int):
    """One lane: every group's ``[dk, g * dv]`` state in, out, and one
    output row a group.  ``q`` and ``k`` arrive ``[dk, H]`` (a head a
    column) and are broadcast along their head's ``dv`` columns;
    ``v``, ``alpha``, ``beta`` and the output are rows over the
    columns."""
    from jax.experimental import pallas as pl

    del layer_ref  # consumed by the index maps
    groups, dk, width = s_ref.shape[2:]
    lane = pl.program_id(0)

    @pl.when(real_ref[lane] == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(real_ref[lane] != 0)
    def _():
        col = lax.broadcasted_iota(jnp.int32, (dk, width), 1)
        q_all, k_all = q_ref[0], k_ref[0]  # [dk, H]

        def along_columns(x_all, p):
            """Head ``p * g + i``'s column over columns ``[i * dv, (i +
            1) * dv)``."""
            out = jnp.broadcast_to(
                x_all[:, p * g + g - 1:p * g + g], (dk, width)
            )
            for i in range(g - 2, -1, -1):
                out = jnp.where(
                    col < (i + 1) * dv,
                    jnp.broadcast_to(
                        x_all[:, p * g + i:p * g + i + 1], (dk, width)
                    ),
                    out,
                )
            return out

        for p in range(groups):
            s = s_ref[0, 0, p]
            kx = along_columns(k_all, p)
            a = a_ref[0, p:p + 1, :]
            sk = jnp.sum(s * kx, axis=0, keepdims=True)
            u = b_ref[0, p:p + 1, :] * (v_ref[0, p:p + 1, :] - a * sk)
            s = a * s + kx * u
            o_ref[0, 0, p] = s
            y_ref[0, p:p + 1, :] = jnp.sum(
                s * along_columns(q_all, p), axis=0, keepdims=True
            )


def _update_call(layer, real, state, q, k, v, a, b, *, g: int, dv: int):
    """``state [L, S, G, dk, W]`` (aliased to the second output), ``q``
    / ``k`` ``[S, dk, H]``, ``v`` / ``a`` / ``b`` ``[S, G, W]`` rows
    over the state's columns -> ``(S^T q [S, G, W], state)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops.pallas_utils import named_kernel, use_interpret

    _, lanes, groups, dk, width = state.shape
    heads = q.shape[-1]

    def state_index(lane, layer_ref, real_ref):
        del real_ref
        return (layer_ref[0], lane, 0, 0, 0)

    def lane_index(lane, layer_ref, real_ref):
        del layer_ref, real_ref
        return (lane, 0, 0)

    rows = pl.BlockSpec((1, groups, width), lane_index)
    cols = pl.BlockSpec((1, dk, heads), lane_index)
    slab = pl.BlockSpec((1, 1, groups, dk, width), state_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes,),
        in_specs=[slab, cols, cols, rows, rows, rows],
        out_specs=[rows, slab],
    )
    name = "gdn_decode_update"
    block = groups * dk * width * state.dtype.itemsize
    return named_kernel(
        name,
        pl.pallas_call(
            functools.partial(_update_kernel, g=g, dv=dv),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((lanes, groups, width), jnp.float32),
                jax.ShapeDtypeStruct(state.shape, state.dtype),
            ],
            # operand 2 (after the scalar-prefetch layer index and lane
            # mask) is the state: blocks of other layers are never
            # visited and keep their contents
            input_output_aliases={2: 1},
            interpret=use_interpret(),
            name=name,
            # a lane's state in and out, each double-buffered, and the
            # body's temporaries
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=int(max(32 << 20, 6 * block + (8 << 20)))
            ),
        ),
    )(layer, real, state, q, k, v, a, b)


def gdn_decode_update(
    state: jnp.ndarray,  # [L, S, H / g, dk, g * dv] float32 (state_shape)
    layer: jnp.ndarray,  # scalar int32: the slab to advance
    q: jnp.ndarray,  # [S, H, dk]
    k: jnp.ndarray,  # [S, H, dk]
    v: jnp.ndarray,  # [S, H, dv]
    alpha: jnp.ndarray,  # [S, H] in (0, 1]
    beta: jnp.ndarray,  # [S, H] in [0, 2]
    real: Optional[jnp.ndarray] = None,  # [S] bool: the lane advances
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every lane through slab ``layer``'s recurrence.
    Returns ``(o [S, H, dv] float32, state)`` with that slab's states
    advanced and every other slab's as given; a lane whose ``real`` is
    false keeps its state bitwise and reads zeros."""
    from dlrover_tpu.ops.paged_attention import paged_kernel_backend

    f32 = jnp.float32
    lanes, heads, dk = q.shape
    dv = v.shape[-1]
    q, k, v, alpha, beta = (t.astype(f32) for t in (q, k, v, alpha, beta))
    if real is None:
        real = jnp.ones((lanes,), bool)
    if (backend or paged_kernel_backend()) == "pallas":
        groups, _, width = state.shape[2:]
        g = heads // groups

        def rows(x):  # [S, H] -> [S, G, W]: a head's scalar a column
            return jnp.repeat(x, dv, axis=-1).reshape(lanes, groups, width)

        o, state = _update_call(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            real.astype(jnp.int32),
            state,
            jnp.swapaxes(q, 1, 2),
            jnp.swapaxes(k, 1, 2),
            v.reshape(lanes, groups, width),
            rows(alpha),
            rows(beta),
            g=g, dv=dv,
        )
        return o.reshape(lanes, heads, dv), state
    # the recurrence itself, one token long
    packed = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    old = unpack_state(packed, heads)
    o, new = gdn_scan_reference(
        q[:, None], k[:, None], v[:, None], alpha[:, None], beta[:, None],
        old,
    )
    keep = real[:, None, None, None]
    state = lax.dynamic_update_index_in_dim(
        state,
        jnp.where(keep, pack_state(new).astype(state.dtype), packed),
        layer, 0,
    )
    return jnp.where(real[:, None, None], o[:, 0], 0.0), state


# ------------------------------------------------------- the chunked form


def _unit_lower_inverse(system: jnp.ndarray) -> jnp.ndarray:
    """The inverse of unit lower-triangular ``system [..., c, c]``
    (zeros above the diagonal, ``c`` a power of 2), float32, by
    products: forward substitution in blocks that double.  A block of
    one row is its own inverse, and two neighbours ``P``, ``Q`` joined
    by the entries ``R`` below ``P`` and left of ``Q`` invert to
    ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``.  With
    ``X`` the block-diagonal matrix of the inverses so far and ``R``
    every such join of one size at once, that is ``X - X R X`` on the
    whole ``[c, c]`` matrix: two products a doubling, ``log2(c) - 1``
    doublings (the first needs none: ``X`` is the identity there).
    Every entry comes out of the sums a triangular solve would form,
    so keys that repeat (``A`` far from small: a prompt that repeats a
    token) are inverted as exactly as by one; the shorter product ``(I
    - A)(I + A^2)(I + A^4)...`` is not (``A`` is nilpotent, but its
    powers outgrow float32's digits before they cancel)."""
    c = system.shape[-1]
    if c & (c - 1):
        raise ValueError(f"a sub-chunk of {c} tokens is not a power of 2")
    at = jnp.arange(c)

    def joins(b):  # the entries that join two blocks of ``b`` to one
        pair, block = at // (2 * b), at // b
        return jnp.where(
            (pair[:, None] == pair[None, :])
            & (block[:, None] != block[None, :]),
            system, 0.0,
        )

    inv = jnp.eye(c, dtype=system.dtype) - joins(1)
    b = 2
    while b < c:
        inv = inv - jnp.matmul(
            jnp.matmul(inv, joins(b), precision=_HIGHEST), inv,
            precision=_HIGHEST,
        )
        b *= 2
    return inv


def gdn_chunk_scan(
    q: jnp.ndarray,  # [B, T, H, dk]
    k: jnp.ndarray,  # [B, T, H, dk]
    v: jnp.ndarray,  # [B, T, H, dv]
    alpha: jnp.ndarray,  # [B, T, H] (1 for a token that must not count)
    beta: jnp.ndarray,  # [B, T, H] (0 for a token that must not count)
    state: jnp.ndarray,  # [B, H, dk, dv] float32: the state before q[:, 0]
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over ``T`` tokens in sub-chunks of ``chunk`` by
    the WY form (module docstring), float32 at the highest matmul
    precision.  ``T`` need not be a multiple of the sub-chunk: the run
    is padded with ``alpha == 1``, ``beta == 0`` tokens, which advance
    nothing.  Returns ``(o [B, T, H, dv], state after the last
    token)``."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    pad = (-t) % c
    q, k, v, alpha, beta = (x.astype(f32) for x in (q, k, v, alpha, beta))
    if pad:
        q, k, v, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, beta)
        )
        alpha = jnp.pad(
            alpha, ((0, 0), (0, pad), (0, 0)), constant_values=1.0
        )
    nc = (t + pad) // c

    def chunks(x):  # [B, T', H, ...] -> [B, nc, H, c, ...]
        return jnp.moveaxis(x.reshape((bsz, nc, c) + x.shape[2:]), 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta)  # [B, nc, H, c]
    # the decay's exponent up to and with token i.  A decay that
    # underflowed to 0 reads as the smallest NORMAL number (the chip
    # flushes anything smaller to 0, and ``log(0)`` differences are NaN)
    tiny = jnp.finfo(f32).tiny
    cum = jnp.cumsum(jnp.log(jnp.maximum(chunks(alpha), tiny)), axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]  # [.., i, j]: g_i / g_j
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    kk = jnp.einsum("bnhid,bnhjd->bnhij", k, k, precision=_HIGHEST)
    system = jnp.where(
        jnp.tril(jnp.ones((c, c), bool), -1),
        beta[..., :, None] * kk * decay, 0.0,
    ) + jnp.eye(c, dtype=f32)
    g = jnp.exp(cum)  # [B, nc, H, c]
    # (I + A)^-1 [beta V | beta g K]: what U is without the carried
    # state, and what multiplies the carried state
    solved = jnp.einsum(
        "bnhij,bnhjv->bnhiv",
        _unit_lower_inverse(system),
        jnp.concatenate(
            [beta[..., None] * v, (beta * g)[..., None] * k], axis=-1
        ),
        precision=_HIGHEST,
    )
    u_free, u_state = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("bnhid,bnhjd->bnhij", q, k, precision=_HIGHEST) * decay
    q_state = g[..., None] * q  # [B, nc, H, c, dk]
    whole = g[..., -1]  # [B, nc, H]: a sub-chunk's whole decay
    k_end = jnp.exp(cum[..., -1:] - cum)[..., None] * k  # (g_C / g_j) k_j

    def step(s, xs):
        u_free, u_state, qk, q_state, whole, k_end = xs
        u = u_free - jnp.einsum(
            "bhik,bhkv->bhiv", u_state, s, precision=_HIGHEST
        )
        o = jnp.einsum(
            "bhik,bhkv->bhiv", q_state, s, precision=_HIGHEST
        ) + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HIGHEST)
        s = whole[..., None, None] * s + jnp.einsum(
            "bhjk,bhjv->bhkv", k_end, u, precision=_HIGHEST
        )
        return s, o

    last, o = lax.scan(
        step, state.astype(f32),
        tuple(
            jnp.moveaxis(x, 1, 0)
            for x in (u_free, u_state, qk, q_state, whole, k_end)
        ),
    )
    # [nc, B, H, c, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, 0, 1)
    o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * c, h, dv)[:, :t]
    return o, last


def gdn_scan_reference(q, k, v, alpha, beta, state):
    """The recurrence one token at a time (``lax.scan``), float32: what
    the two ops above must reproduce.  Shapes as
    :func:`gdn_chunk_scan`."""
    f32 = jnp.float32
    q, k, v, alpha, beta = (x.astype(f32) for x in (q, k, v, alpha, beta))

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp  # [B, H, dk] x 2, [B, H, dv], [B, H] x 2
        sk = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HIGHEST)
        u = b_t[..., None] * (v_t - a_t[..., None] * sk)
        s = a_t[..., None, None] * s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    last, o = lax.scan(
        step, state.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)),
    )
    return jnp.moveaxis(o, 0, 1), last
