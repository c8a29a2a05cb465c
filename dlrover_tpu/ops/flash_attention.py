"""Pallas TPU flash attention (FA2: forward + backward kernels).

Reference parity: the flash-attention injection layer of atorch
(``modules/transformer/layers.py:801`` ``FlashMHA``/FA2 wrappers) and
tfplus's TF flash-attention custom ops
(``tfplus/flash_attn/kernels/flash_attention_fwd_kernel.cc:172``,
``flash_attention_bwd_kernel.cc:167``).  Those wrap Dao's CUDA kernels;
on TPU the kernels are ours: online-softmax blockwise attention that
never materializes the [S, S] score matrix, tiled for the MXU
(128-aligned blocks, fp32 accumulators in VMEM scratch).

FA2 recipe: the forward saves the per-row log-sum-exp (LSE) alongside
the output; the backward recomputes probabilities blockwise from
(q, k, lse) — ``p = exp(qk^T·scale − lse)`` — and accumulates
``dv = pᵀ·dO``, ``ds = p∘(dO·vᵀ − Δ)·scale`` (Δ = rowsum(dO∘O)),
``dk = dsᵀ·q``, ``dq = ds·k`` in two kernels: one gridded over KV
blocks (dk/dv), one over Q blocks (dq).  TPU's sequential grid makes
the accumulation race-free — no atomics, a VMEM scratch accumulates
across the innermost grid dimension.

Layout contract: q, k, v are ``[B, S, H, D]`` (seq-major, the layout
the rest of the framework uses); GQA is handled by logical kv-head
broadcast in the index maps (backward materializes per-q-head dk/dv,
then sums over the head group).

On non-TPU backends (CI's virtual CPU devices) the kernels run in
Pallas interpret mode automatically.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas_utils import use_interpret
from dlrover_tpu.parallel import remat

NEG_INF = -1e30


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    seq_len: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: a K block strictly above the diagonal is fully masked —
    # skip its matmuls entirely (~2x FLOPs saved on long sequences)
    if causal:
        visible = kj * block_k <= qi * block_q + block_q - 1
    else:
        visible = True

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]  # [BK, D]

        s = (
            jax.lax.dot_general(
                q,
                k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # [BQ, BK]

        # bounds mask: the last K block is padded when seq_len is not a
        # multiple of block_k; padded columns MUST NOT feed the softmax
        # denominator, and padded V rows hold undefined data (possibly
        # NaN — 0 * NaN = NaN would poison the accumulator), so both
        # sides are masked.
        padded_k = seq_len % block_k != 0
        if padded_k:
            row_valid = (
                kj * block_k + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                < seq_len
            )
            v = jnp.where(row_valid, v, 0.0)
        if causal or padded_k:
            k_pos = kj * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = jnp.ones((block_q, block_k), dtype=bool)
            if padded_k:
                keep &= k_pos < seq_len
            if causal:
                q_pos = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                keep &= q_pos >= k_pos
            s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [BQ, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [BQ, BK]

        l_new = l_scr[:, :1] * alpha + jnp.sum(
            p, axis=-1, keepdims=True
        )
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # log-sum-exp residual for the FA2 backward: p = exp(s - lse);
        # [BQ, 1] — the trailing unit dim keeps Mosaic's block-shape
        # rule (last dim equal to the array dim) without the 128-lane
        # broadcast the stock kernel pays
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(denom)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k")
)
def _flash_fwd(
    q: jnp.ndarray,  # [B, H, S, D]
    k: jnp.ndarray,  # [B, KV, S, D]  (KV divides H: GQA)
    v: jnp.ndarray,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
) -> jnp.ndarray:
    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv  # GQA: K/V blocks are shared by `group` q heads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    grid = (b, h, pl.cdiv(s, block_q), pl.cdiv(s, block_k))

    kernel = functools.partial(
        _flash_fwd_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_len=s,
    )
    # the kv index map folds the head group: no materialized repeat
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda b_, h_, i, j: (b_, h_ // group, j, 0),
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),  # lse
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)
            ),
            kv_spec,
            kv_spec,
        ],
        out_specs=(
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),  # output accum
        ],
        interpret=use_interpret(),
    )(q, k, v)


def _bwd_block_math(q, k, v, do, lse, delta, glse, keep, sm_scale):
    """Shared FA2 block algebra (fp32): returns (p, ds) for one
    [BQ, BK] tile.  ``lse``/``delta``/``glse`` are [BQ, 1]; ``keep`` is
    the combined causal/bounds mask or None.

    ``glse`` is the cotangent of the lse output (zero for the plain
    attention path): ∂lse/∂s_j = p_j, so it folds into ds as
    ``p∘(dp − Δ + glse)·scale`` — this is what makes the lse-returning
    variant (ring attention's inner kernel) differentiable."""
    s = (
        jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # [BQ, BK]
    p = jnp.exp(s - lse)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    dp = jax.lax.dot_general(
        do, v,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [BQ, BK]
    correction = dp - delta if glse is None else dp - delta + glse
    ds = p * correction * sm_scale
    if keep is not None:
        # p=0 alone is not enough: out-of-range rows load garbage
        # lse/delta (possibly NaN), and 0 * NaN = NaN
        ds = jnp.where(keep, ds, 0.0)
    return p, ds


def _bwd_masks(qi, kj, block_q, block_k, seq_len, causal):
    """The keep mask for a (qi, kj) tile, or None when nothing masks."""
    padded_q = seq_len % block_q != 0
    padded_k = seq_len % block_k != 0
    if not (causal or padded_q or padded_k):
        return None
    q_pos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kj * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    keep = jnp.ones((block_q, block_k), dtype=bool)
    if causal:
        keep &= q_pos >= k_pos
    if padded_q:
        # out-of-range q rows carry uninitialized lse/delta/do — a
        # stray p=inf there would poison the dk/dv accumulators
        keep &= q_pos < seq_len
    if padded_k:
        keep &= k_pos < seq_len
    return keep


def _flash_bwd_dkv_kernel(
    *refs, sm_scale, causal, block_q, block_k, seq_len, has_glse,
):
    if has_glse:
        (q_ref, do_ref, lse_ref, delta_ref, glse_ref, k_ref, v_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        glse_ref = None
    kj = pl.program_id(2)
    qi = pl.program_id(3)  # innermost: dk/dv accumulate across it
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: this K block sees no Q block strictly below the diagonal
    visible = (
        kj * block_k <= qi * block_q + block_q - 1 if causal else True
    )

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]  # [BQ, D]
        do = do_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]
        if seq_len % block_q != 0:
            # OOB q rows load garbage (NaN in interpret mode); the
            # p/ds masks zero their own entries, but dv = p^T·dO and
            # dk = ds^T·q contract over q rows — 0·NaN = NaN, so the
            # garbage operand rows must be zeroed too
            q_valid = (
                qi * block_q
                + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
                < seq_len
            )
            q = jnp.where(q_valid, q, 0)
            do = jnp.where(q_valid, do, 0)
        keep = _bwd_masks(qi, kj, block_q, block_k, seq_len, causal)
        p, ds = _bwd_block_math(
            q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
            glse_ref[0, 0] if glse_ref is not None else None,
            keep, sm_scale,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T dO: [BK, D]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T q: [BK, D]

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    *refs, sm_scale, causal, block_q, block_k, seq_len, has_glse,
):
    if has_glse:
        (q_ref, do_ref, lse_ref, delta_ref, glse_ref, k_ref, v_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
         dq_ref, dq_scr) = refs
        glse_ref = None
    qi = pl.program_id(2)
    kj = pl.program_id(3)  # innermost: dq accumulates across it
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    visible = (
        kj * block_k <= qi * block_q + block_q - 1 if causal else True
    )

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if seq_len % block_k != 0:
            # dq = ds·k contracts over k rows: zero the OOB garbage
            # rows (ds already masks its own OOB columns)
            k_valid = (
                kj * block_k
                + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                < seq_len
            )
            k = jnp.where(k_valid, k, 0)
        keep = _bwd_masks(qi, kj, block_q, block_k, seq_len, causal)
        _, ds = _bwd_block_math(
            q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
            glse_ref[0, 0] if glse_ref is not None else None,
            keep, sm_scale,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds k: [BQ, D]

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k")
)
def _flash_bwd(
    q, k, v, out, lse, g, g_lse, causal, sm_scale, block_q, block_k
):
    """FA2 backward: dq via one kernel (grid q-major), dk/dv via another
    (grid k-major); GQA dk/dv materialize per q-head then sum over the
    head group.  ``g_lse`` [B,H,S,1] is the lse-output cotangent (zeros
    for the plain path)."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)

    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )  # [B, H, S, 1]
    has_glse = g_lse is not None
    glse_in = (
        (g_lse.astype(jnp.float32),) if has_glse else ()
    )

    qd_spec = lambda qpos: pl.BlockSpec(  # noqa: E731
        (1, 1, block_q, d),
        (lambda b_, h_, i, j: (b_, h_, i, 0))
        if qpos == "outer"
        else (lambda b_, h_, i, j: (b_, h_, j, 0)),
    )
    row_spec = lambda qpos: pl.BlockSpec(  # noqa: E731
        (1, 1, block_q, 1),
        (lambda b_, h_, i, j: (b_, h_, i, 0))
        if qpos == "outer"
        else (lambda b_, h_, i, j: (b_, h_, j, 0)),
    )
    kv_spec_for = lambda kpos: pl.BlockSpec(  # noqa: E731
        (1, 1, block_k, d),
        (lambda b_, h_, i, j: (b_, h_ // group, i, 0))
        if kpos == "outer"
        else (lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
    )

    common = dict(
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_len=s,
        has_glse=has_glse,
    )

    def _in_specs(qpos, kpos):
        """q/do/lse/delta [+glse] then k/v; glse only when present so
        the plain backward pays no extra buffer or VMEM load."""
        specs = [
            qd_spec(qpos),  # q
            qd_spec(qpos),  # do
            row_spec(qpos),  # lse
            row_spec(qpos),  # delta
        ]
        if has_glse:
            specs.append(row_spec(qpos))  # glse
        specs += [kv_spec_for(kpos), kv_spec_for(kpos)]  # k, v
        return specs

    # dk/dv: grid (b, h, kj, qi) — qi innermost accumulates in scratch
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
        ),
        grid=(b, h, nk, nq),
        in_specs=_in_specs("inner", "outer"),
        out_specs=(
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_, i, 0)
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=use_interpret(),
    )(q, g, lse, delta, *glse_in, k, v)

    # GQA: fold per-q-head dk/dv back onto the kv heads
    if group > 1:
        dk = dk_h.reshape(b, kv, group, s, d).sum(axis=2)
        dv = dv_h.reshape(b, kv, group, s, d).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h

    # dq: grid (b, h, qi, kj) — kj innermost accumulates in scratch
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), jnp.float32),
        grid=(b, h, nq, nk),
        in_specs=_in_specs("outer", "inner"),
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)
        ),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=use_interpret(),
    )(q, g, lse, delta, *glse_in, k, v)

    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_hsd(q, k, v, causal, sm_scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    # named for the remat ladder: a checkpoint policy that keeps the
    # two does not replay ``_flash_fwd`` to rebuild the residuals
    out = remat.keep(out, remat.ATTN_OUT)
    lse = remat.keep(lse, remat.ATTN_LSE)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(
        q, k, v, out, lse, g, None, causal, sm_scale, block_q, block_k
    )


_flash_attention_hsd.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_lse_hsd(q, k, v, causal, sm_scale, block_q, block_k):
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)


def _fa_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(causal, sm_scale, block_q, block_k, res, cts):
    q, k, v, out, lse = res
    g, g_lse = cts
    return _flash_bwd(
        q, k, v, out, lse, g, g_lse, causal, sm_scale, block_q, block_k
    )


_flash_attention_lse_hsd.defvjp(_fa_lse_fwd, _fa_lse_bwd)


def flash_attention_lse(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, KV, D]
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ``[B, S, H]`` — the residual that lets callers merge
    partial attention over KV blocks exactly (ring attention's inner
    kernel).  Differentiable in both outputs (the lse cotangent folds
    into ds inside the backward kernels)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if block_q is None:
        block_q = _default_blocks(q.shape[1])[0]
    if block_k is None:
        block_k = _default_blocks(q.shape[1])[1]
    nh, nkv = q.shape[2], k.shape[2]
    if nh % nkv != 0:
        raise ValueError(f"heads {nh} not a multiple of kv {nkv}")
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out, lse = _flash_attention_lse_hsd(
        qt, kt, vt, causal, sm_scale, block_q, block_k
    )
    # [B,H,S,D] -> [B,S,H,D]; lse [B,H,S,1] -> [B,S,H]
    return (
        jnp.swapaxes(out, 1, 2),
        jnp.swapaxes(lse[..., 0], 1, 2),
    )


def _default_blocks(seq_len: int) -> Tuple[int, int]:
    """(block_q, block_k), measured on v5e ([.,.,8,128] bf16):
    end-to-end on the llama-0.6b train step at seq 2048, asymmetric
    1024x512 beats 512x512 (0.5219 vs 0.5185 MFU) — a taller q tile
    halves the grid's q loop while the 512 k tile keeps the working
    set in VMEM; 512x256 loses badly (0.465).  Longer sequences keep
    the larger tiles to amortize grid overhead over the longer KV
    loop."""
    if seq_len >= 8192:
        return 1024, 1024
    return (1024, 512) if seq_len >= 2048 else (512, 512)


def flash_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, KV, D]
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Drop-in replacement for
    ``dlrover_tpu.models.llama.dot_product_attention`` (same [B,S,H,D]
    layout + GQA broadcast).

    Default blocks are sequence-adaptive (512 short / 1024 long, see
    ``_default_blocks``); at [8,2048,8,128] bf16 the tuned kernel runs
    fwd+bwd 7.6x faster than naive 128x128 blocking and 4.4x faster
    than the dense XLA path, and stays functional to 32k tokens on one
    chip where dense attention cannot materialize the score matrix."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if block_q is None:
        block_q = _default_blocks(q.shape[1])[0]
    if block_k is None:
        block_k = _default_blocks(q.shape[1])[1]
    nh, nkv = q.shape[2], k.shape[2]
    if nh % nkv != 0:
        raise ValueError(f"heads {nh} not a multiple of kv {nkv}")
    # GQA stays logical: the kernel's kv index map folds the group
    # [B,S,H,D] -> [B,H,S,D]
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    out = _flash_attention_hsd(
        qt, kt, vt, causal, sm_scale, block_q, block_k
    )
    return jnp.swapaxes(out, 1, 2)
