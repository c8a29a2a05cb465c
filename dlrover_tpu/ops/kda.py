"""Kimi Delta Attention (KDA) ops of the serving plane: the gated delta
rule of ``ops/gdn.py`` with a decay a KEY CHANNEL instead of one a head.

A head keeps a state ``S [dk, dv]`` per sequence and advances it once a
token (Kimi Linear, arXiv:2510.26692; FLA's ``KimiDeltaAttention``):

    S'  = diag(a_t) S_{t-1}
    u_t = beta_t * (v_t - S'^T k_t)
    S_t = S' + k_t (x) u_t
    o_t = S_t^T q_t

``q_t``, ``k_t``, ``a_t`` ``[dk]`` (``a_t`` in (0, 1], one factor a ROW
of the state), ``v_t`` ``[dv]``, ``beta_t`` in [0, 1] a scalar of the
head: ``S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t
v_t^T``.  Three ops, in the shape of ``ops/gdn.py``'s:

- :func:`kda_decode_update` — one token for every LANE of a
  continuous-batching decode step.  Memory-bound: a lane's state is read
  and written once, ``2 * H * dk * dv * 4`` bytes (2.10 MB each way at
  32 heads of 128 x 128) against ~3 MFLOP.  The Pallas kernel
  (``kda_decode_update`` on a device trace) updates the state IN PLACE
  in the stacked ``[state layers, lanes, H, dk, dv]`` buffer the
  scheduler owns: the layer index rides in as a scalar-prefetch
  operand, and a lane whose ``real`` is 0 is copied through bitwise.
  At ``dv`` 128 a head's ``[dk, dv]`` is whole lane tiles: no heads are
  packed (``ops/gdn.state_shape`` packs two of 192).
- :func:`kda_chunk_scan` — a run of tokens in sub-chunks by the WY form
  with the decay INSIDE the products.  With ``G_t [dk]`` the running sum
  of ``log a`` a channel over the sub-chunk and

      A[t, j] = beta_t sum_c k_t[c] exp(G_t[c] - G_j[c]) k_j[c]  (j < t)

  the rows ``u_t`` solve ``(I + A) U = beta (V - (K exp(G)) S_0)``;
  then ``O = (Q exp(G)) S_0 + tril(P) U`` with ``P[t, j] = sum_c q_t[c]
  exp(G_t[c] - G_j[c]) k_j[c]`` and ``S_C = diag(exp(G_C)) S_0 + (K
  exp(G_C - G))^T U``.  The decay does not factor out of ``K K^T`` as a
  ``[C, C]`` ratio matrix, and the product ``(k exp(G - G_0)) (k exp(G_0
  - G))^T`` over a whole sub-chunk overflows under a strong decay
  (``exp(G_0 - G_j)`` after 63 tokens at ``a`` = 0.1 is 1e63).  So the
  sub-chunk is cut into BLOCKS of :data:`KDA_BLOCK` tokens, as FLA's
  chunk form cuts it: against an EARLIER block the reference point is
  the END of the block before the row's own — ``exp(G_t - R)`` and
  ``exp(R - G_j)`` are then both at most 1 —, and within a block the
  differences ``G_t - G_j`` (at most 0 under the causal mask) are
  exponentiated directly.  Nothing is ever raised to a positive power:
  finite for every ``a`` in (0, 1], and a factor that underflows stands
  for a product smaller still.  ``(I + A)^-1`` by products
  (``ops/gdn._unit_lower_inverse``), a ``lax.scan`` carries ``S`` from
  one sub-chunk to the next; plain XLA, float32 at the highest matmul
  precision; a token with ``a == 1`` and ``beta == 0`` advances nothing
  (``ops/gdn``'s padding rule).
- :func:`kda_scan_reference` — the recurrence token by token: what the
  other two must reproduce, and the decode update's jnp form.

Backend: the decode update follows ``DLROVER_TPU_PAGED_KERNEL`` like
``ops/gdn.gdn_decode_update``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.ops.gdn import _HIGHEST, _unit_lower_inverse

#: tokens a block of the chunk form: within one the decay's differences
#: are exponentiated directly, across two through a reference point
KDA_BLOCK = 16


# ------------------------------------------------------- the decode kernel


def _update_kernel(layer_ref, real_ref, s_ref, q_ref, k_ref, a_ref, v_ref,
                   b_ref, y_ref, o_ref):
    """One lane: every head's ``[dk, dv]`` state in, out, and one output
    row a head.  ``q``, ``k`` and the decay ``a`` arrive ``[dk, H]`` (a
    head a column) and are broadcast along the state's columns; ``v``,
    ``beta`` and the output are rows over the columns."""
    from jax.experimental import pallas as pl

    del layer_ref  # consumed by the index maps
    heads, dk, dv = s_ref.shape[2:]
    lane = pl.program_id(0)

    @pl.when(real_ref[lane] == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(real_ref[lane] != 0)
    def _():
        q_all, k_all, a_all = q_ref[0], k_ref[0], a_ref[0]  # [dk, H]

        def along_columns(x_all, p):
            return jnp.broadcast_to(x_all[:, p:p + 1], (dk, dv))

        for p in range(heads):
            kx = along_columns(k_all, p)
            s = along_columns(a_all, p) * s_ref[0, 0, p]
            sk = jnp.sum(s * kx, axis=0, keepdims=True)
            u = b_ref[0, p:p + 1, :] * (v_ref[0, p:p + 1, :] - sk)
            s = s + kx * u
            o_ref[0, 0, p] = s
            y_ref[0, p:p + 1, :] = jnp.sum(
                s * along_columns(q_all, p), axis=0, keepdims=True
            )


def _update_call(layer, real, state, q, k, a, v, b):
    """``state [L, S, H, dk, dv]`` (aliased to the second output), ``q``
    / ``k`` / ``a`` ``[S, dk, H]``, ``v`` / ``b`` ``[S, H, dv]`` rows
    over the state's columns -> ``(S^T q [S, H, dv], state)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops.pallas_utils import named_kernel, use_interpret

    _, lanes, heads, dk, dv = state.shape

    def state_index(lane, layer_ref, real_ref):
        del real_ref
        return (layer_ref[0], lane, 0, 0, 0)

    def lane_index(lane, layer_ref, real_ref):
        del layer_ref, real_ref
        return (lane, 0, 0)

    rows = pl.BlockSpec((1, heads, dv), lane_index)
    cols = pl.BlockSpec((1, dk, heads), lane_index)
    slab = pl.BlockSpec((1, 1, heads, dk, dv), state_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(lanes,),
        in_specs=[slab, cols, cols, cols, rows, rows],
        out_specs=[rows, slab],
    )
    name = "kda_decode_update"
    block = heads * dk * dv * state.dtype.itemsize
    return named_kernel(
        name,
        pl.pallas_call(
            _update_kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((lanes, heads, dv), jnp.float32),
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
    )(layer, real, state, q, k, a, v, b)


def kda_decode_update(
    state: jnp.ndarray,  # [L, S, H, dk, dv] float32
    layer: jnp.ndarray,  # scalar int32: the slab to advance
    q: jnp.ndarray,  # [S, H, dk]
    k: jnp.ndarray,  # [S, H, dk]
    v: jnp.ndarray,  # [S, H, dv]
    alpha: jnp.ndarray,  # [S, H, dk] in (0, 1]: a decay a key channel
    beta: jnp.ndarray,  # [S, H] in [0, 1]
    real: Optional[jnp.ndarray] = None,  # [S] bool: the lane advances
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of every lane through slab ``layer``'s recurrence.
    Returns ``(o [S, H, dv] float32, state)`` with that slab's states
    advanced and every other slab's as given; a lane whose ``real`` is
    false keeps its state bitwise and reads zeros."""
    from dlrover_tpu.ops.paged_attention import paged_kernel_backend

    f32 = jnp.float32
    lanes, heads, _ = q.shape
    dv = v.shape[-1]
    q, k, v, alpha, beta = (t.astype(f32) for t in (q, k, v, alpha, beta))
    if real is None:
        real = jnp.ones((lanes,), bool)
    if (backend or paged_kernel_backend()) == "pallas":
        return _update_call(
            jnp.reshape(layer, (1,)).astype(jnp.int32),
            real.astype(jnp.int32),
            state,
            jnp.swapaxes(q, 1, 2),
            jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(alpha, 1, 2),
            v,
            jnp.broadcast_to(beta[..., None], (lanes, heads, dv)),
        )
    # the recurrence itself, one token long
    old = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, new = kda_scan_reference(
        q[:, None], k[:, None], v[:, None], alpha[:, None], beta[:, None],
        old,
    )
    keep = real[:, None, None, None]
    state = lax.dynamic_update_index_in_dim(
        state, jnp.where(keep, new.astype(state.dtype), old), layer, 0
    )
    return jnp.where(real[:, None, None], o[:, 0], 0.0), state


# ------------------------------------------------------- the chunked form


def _decayed_products(rows, keys, cum, strict_upto):
    """``M[t, j] = sum_c rows_t[c] exp(G_t[c] - G_j[c]) keys_j[c]`` for
    ``j <= t`` (0 above the diagonal), ``rows`` / ``keys`` / ``cum``
    ``[..., nb, b, dk]`` a sub-chunk in blocks -> ``[..., nb * b, nb *
    b]``.  Across blocks through the reference point ``R`` = the decay's
    exponent at the END of the block before the row's own (both factors
    at most 1); within a block the differences directly
    (``strict_upto``: the block-diagonal factors ``exp(G_t - G_j)``,
    ``[..., nb, b, b, dk]``, made once for both callers)."""
    nb, b, dk = cum.shape[-3:]
    lead = cum.shape[:-3]
    ref = jnp.concatenate(
        [jnp.zeros_like(cum[..., :1, -1, :]), cum[..., :-1, -1, :]], axis=-2
    )  # [..., nb, dk]
    rows_ref = rows * jnp.exp(cum - ref[..., :, None, :])
    # a key of block J against the rows of every LATER block I
    earlier = (
        jnp.arange(nb)[None, :] < jnp.arange(nb)[:, None]
    )[:, :, None, None]  # [I, J, 1, 1]
    keys_ref = keys[..., None, :, :, :] * jnp.exp(jnp.where(
        earlier, ref[..., :, None, None, :] - cum[..., None, :, :, :],
        -jnp.inf,
    ))  # [..., I, J, b, dk]
    across = jnp.einsum(
        "...itd,...ijsd->...itjs", rows_ref, keys_ref, precision=_HIGHEST
    )  # [..., I, b, J, b]
    within = jnp.sum(
        rows[..., :, None, :] * strict_upto * keys[..., None, :, :], -1
    )  # [..., nb, b, b]
    within = within[..., :, :, None, :] * jnp.eye(
        nb, dtype=within.dtype
    )[:, None, :, None]
    return (across + within).reshape(lead + (nb * b, nb * b))


def kda_chunk_scan(
    q: jnp.ndarray,  # [B, T, H, dk]
    k: jnp.ndarray,  # [B, T, H, dk]
    v: jnp.ndarray,  # [B, T, H, dv]
    alpha: jnp.ndarray,  # [B, T, H, dk] (1 for a token that must not count)
    beta: jnp.ndarray,  # [B, T, H] (0 for a token that must not count)
    state: jnp.ndarray,  # [B, H, dk, dv] float32: the state before q[:, 0]
    chunk: int = 64,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over ``T`` tokens in sub-chunks of ``chunk`` (a
    power of 2, in blocks of :data:`KDA_BLOCK` where it is longer) by
    the WY form (module docstring), float32 at the highest matmul
    precision.  ``T`` need not be a multiple of the sub-chunk: the run
    is padded with ``alpha == 1``, ``beta == 0`` tokens, which advance
    nothing.  Returns ``(o [B, T, H, dv], state after the last
    token)``."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    b = min(KDA_BLOCK, c)
    if c % b:
        raise ValueError(f"a sub-chunk of {c} tokens in blocks of {b}")
    nb = c // b
    pad = (-t) % c
    q, k, v, alpha, beta = (x.astype(f32) for x in (q, k, v, alpha, beta))
    if pad:
        q, k, v, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, beta)
        )
        alpha = jnp.pad(
            alpha, ((0, 0), (0, pad), (0, 0), (0, 0)), constant_values=1.0
        )
    nc = (t + pad) // c

    def chunks(x):  # [B, T', H, ...] -> [B, nc, H, c, ...]
        return jnp.moveaxis(x.reshape((bsz, nc, c) + x.shape[2:]), 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta)  # [B, nc, H, c]
    # the decay's exponent a channel up to and with token i; a decay
    # that underflowed to 0 reads as the smallest NORMAL number
    # (``ops/gdn.gdn_chunk_scan`` says why)
    tiny = jnp.finfo(f32).tiny
    cum = jnp.cumsum(jnp.log(jnp.maximum(chunks(alpha), tiny)), axis=-2)

    def blocks(x):  # [..., c, dk] -> [..., nb, b, dk]
        return x.reshape(x.shape[:-2] + (nb, b, x.shape[-1]))

    cum_b = blocks(cum)
    causal = jnp.tril(jnp.ones((b, b), bool))[..., None]
    upto = jnp.exp(jnp.where(
        causal, cum_b[..., :, None, :] - cum_b[..., None, :, :], -jnp.inf
    ))  # [B, nc, H, nb, b, b, dk]: exp(G_t - G_j), j <= t of one block
    kk = _decayed_products(blocks(k), blocks(k), cum_b, upto)
    qk = _decayed_products(blocks(q), blocks(k), cum_b, upto)
    system = jnp.where(
        jnp.tril(jnp.ones((c, c), bool), -1), beta[..., :, None] * kk, 0.0
    ) + jnp.eye(c, dtype=f32)
    g = jnp.exp(cum)  # [B, nc, H, c, dk]
    # (I + A)^-1 [beta V | beta (K exp(G))]: what U is without the
    # carried state, and what multiplies the carried state
    solved = jnp.einsum(
        "bnhij,bnhjv->bnhiv",
        _unit_lower_inverse(system),
        jnp.concatenate([beta[..., None] * v, beta[..., None] * g * k], -1),
        precision=_HIGHEST,
    )
    u_free, u_state = solved[..., :dv], solved[..., dv:]
    q_state = g * q  # [B, nc, H, c, dk]
    whole = g[..., -1, :]  # [B, nc, H, dk]: a sub-chunk's whole decay
    k_end = jnp.exp(cum[..., -1:, :] - cum) * k  # exp(G_C - G_j) k_j

    def step(s, xs):
        u_free, u_state, qk, q_state, whole, k_end = xs
        u = u_free - jnp.einsum(
            "bhik,bhkv->bhiv", u_state, s, precision=_HIGHEST
        )
        o = jnp.einsum(
            "bhik,bhkv->bhiv", q_state, s, precision=_HIGHEST
        ) + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HIGHEST)
        s = whole[..., None] * s + jnp.einsum(
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


def kda_scan_reference(q, k, v, alpha, beta, state):
    """The recurrence one token at a time (``lax.scan``), float32: what
    the two ops above must reproduce.  Shapes as
    :func:`kda_chunk_scan`."""
    f32 = jnp.float32
    q, k, v, alpha, beta = (x.astype(f32) for x in (q, k, v, alpha, beta))

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t = inp  # [B, H, dk] x 2, [B, H, dv], ...
        s = a_t[..., None] * s
        sk = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HIGHEST)
        u = b_t[..., None] * (v_t - sk)
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    last, o = lax.scan(
        step, state.astype(f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta)),
    )
    return jnp.moveaxis(o, 0, 1), last
