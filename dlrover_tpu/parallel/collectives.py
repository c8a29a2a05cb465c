"""Sequence/expert-parallel collectives for use inside ``shard_map``.

Reference parity:

- Ulysses all-to-all: ``atorch/atorch/distributed/distributed.py:474``
  (``_SeqAllToAll`` autograd: scatter_idx/gather_idx exchange) and
  ``seq_all_to_all:500``.  Here it is a single ``lax.all_to_all`` whose
  transpose rule gives the backward pass for free — no custom autograd.
- Ring primitives: the micro-Q all-gather ring of
  ``modules/distributed_transformer/commu_utils.py`` becomes
  ``lax.ppermute`` rotation (the idiomatic ICI ring).
- Distributed softmax: ``distributed_attention.py:21``
  (``DistributedSoftmax``: global max+sum via allreduce over the
  sharded sequence) becomes two ``psum``/``pmax`` calls.
- Expert dispatch: ``modules/moe/moe_layer.py:87`` (``_AllToAll``)
  becomes ``lax.all_to_all`` over the "expert" axis.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def device_varying(x, axis_name):
    """Mark a freshly-created array as device-varying over ``axis_name``
    (shard_map vma typing for scan carries)."""
    return lax.pcast(x, axis_name, to="varying")


def seq_all_to_all(
    x: jnp.ndarray,
    axis_name: str,
    scatter_axis: int,
    gather_axis: int,
    tiled: bool = True,
) -> jnp.ndarray:
    """Ulysses exchange: scatter ``scatter_axis`` over the mesh axis,
    gather ``gather_axis`` from it.

    Attention usage (inside shard_map, seq sharded per device):
    ``q,k,v: [B, S/p, H, D] -> [B, S, H/p, D]`` via
    ``seq_all_to_all(x, "seq", scatter_axis=2, gather_axis=1)`` —
    full sequence per head-group; inverse after attention.
    """
    return lax.all_to_all(
        x,
        axis_name,
        split_axis=scatter_axis,
        concat_axis=gather_axis,
        tiled=tiled,
    )


def ring_permute(x: jnp.ndarray, axis_name: str, shift: int = 1):
    """Rotate a block to the next device on the ring (ppermute); the
    building block of ring attention's KV rotation."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def distributed_softmax(
    logits: jnp.ndarray, axis_name: str, axis: int = -1
) -> jnp.ndarray:
    """Softmax over an axis that is sharded across ``axis_name``:
    global max (pmax) then global sum (psum) — numerically identical to
    a softmax over the gathered axis (reference ``DistributedSoftmax``).
    """
    local_max = jnp.max(logits, axis=axis, keepdims=True)
    global_max = lax.pmax(local_max, axis_name)
    unnorm = jnp.exp(logits - global_max)
    denom = lax.psum(
        jnp.sum(unnorm, axis=axis, keepdims=True), axis_name
    )
    return unnorm / denom


def expert_all_to_all(
    x: jnp.ndarray, axis_name: str, split_axis: int = 0, concat_axis: int = 0
):
    """MoE dispatch/combine exchange over the expert mesh axis."""
    return lax.all_to_all(
        x,
        axis_name,
        split_axis=split_axis,
        concat_axis=concat_axis,
        tiled=True,
    )


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    inner_attention: Optional[callable] = None,
    causal: bool = True,
) -> jnp.ndarray:
    """Ulysses sequence parallelism (inside shard_map): exchange the
    sharded seq dim for the head dim around any attention kernel.

    q,k,v ``[B, S/p, H, D]`` -> attention sees ``[B, S, H/p, D]``
    (full sequence, head subset) -> output back to ``[B, S/p, H, D]``.
    Reference: ``SequenceParallelOptimization`` + ``_SeqAllToAll``
    (``distributed/distributed.py:474``).
    """
    if inner_attention is None:
        from dlrover_tpu.models.llama import dot_product_attention

        inner_attention = dot_product_attention
    q, k, v = (
        seq_all_to_all(x, axis_name, scatter_axis=2, gather_axis=1)
        for x in (q, k, v)
    )
    out = inner_attention(q, k, v, causal=causal)
    return seq_all_to_all(out, axis_name, scatter_axis=1, gather_axis=2)


def grad_sync(grads, axis_names):
    """Mean-reduce gradients over the given data-flavored axes — what
    DDP's bucketed allreduce becomes (a single pmean per leaf; XLA
    fuses and schedules them)."""
    if not axis_names:
        return grads
    return jax.tree_util.tree_map(
        lambda g: lax.pmean(g, axis_names), grads
    )


def _dense_block_lse(q, k, v, causal: bool, scale: float):
    """Dense (out, lse) for one KV block — the ring's inner kernel when
    flash attention is disabled (DLROVER_TPU_FLASH_ATTENTION=0).
    q [B,S,H,D], k/v [B,X,KV,D]; lse [B,S,H]."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, d)
    logits = (
        jnp.einsum(
            "bqkgd,bxkd->bqkgx", qg, k,
            preferred_element_type=jnp.float32,
        ).astype(jnp.float32)
        * scale
    )
    if causal:
        x = k.shape[1]
        mask = jnp.arange(s)[:, None] >= jnp.arange(x)[None, :]
        logits = jnp.where(
            mask[None, :, None, None], logits,
            jnp.finfo(jnp.float32).max * -1.0,
        )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [b,s,kv,g]
    p = jnp.exp(logits - lse[..., None])
    out = jnp.einsum(
        "bqkgx,bxkd->bqkgd", p, v.astype(jnp.float32)
    )
    return (
        out.reshape(b, s, h, d).astype(q.dtype),
        lse.reshape(b, s, h),
    )


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Blockwise ring attention over a sequence-sharded mesh axis.

    Reference parity: ``DistributedSelfAttention``
    (``distributed_attention.py:79``) — the reference all-gathers Q in
    micro-chunks and reduce-scatters the context; the TPU-idiomatic
    dual keeps Q resident and rotates the KV shard around the ring with
    ``ppermute`` (one hop per step, overlapping compute), merging each
    block's contribution with log-sum-exp statistics so the softmax is
    exact.

    The per-block computation is the Pallas flash-attention kernel
    (``flash_attention_lse`` — its lse output is exactly the residual
    the merge needs); under ``causal``, blocks strictly above the
    diagonal are skipped entirely (no QK^T, no PV — ~2x FLOPs saved),
    the diagonal block runs the kernel's internal triangular mask, and
    blocks below run unmasked.

    Shapes (inside shard_map): q ``[B, S/p, H, D]``, k/v
    ``[B, S/p, KV, D]`` with KV dividing H (GQA handled inside the
    kernel); returns the context for the local Q chunk.
    """
    from dlrover_tpu.ops.flash_attention import flash_attention_lse

    if use_flash is None:
        from dlrover_tpu.accelerate.module_replace import _flash_enabled

        use_flash = _flash_enabled(None)

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    if scale is None:
        scale = q.shape[-1] ** -0.5

    b, s, h, d = q.shape
    neg_inf = jnp.finfo(jnp.float32).max * -1.0

    def inner(qq, kc, vc, causal_):
        if use_flash:
            return flash_attention_lse(
                qq, kc, vc, causal=causal_, sm_scale=scale,
                block_q=block_q, block_k=block_k,
            )
        return _dense_block_lse(qq, kc, vc, causal_, scale)

    def full_block(kv_pair):
        kc, vc = kv_pair
        return inner(q, kc, vc, False)

    def diag_block(kv_pair):
        kc, vc = kv_pair
        return inner(q, kc, vc, True)

    def skip_block(kv_pair):
        # invisible under causal: contributes nothing (lse = -inf)
        return (
            jnp.zeros((b, s, h, d), dtype=q.dtype),
            jnp.full((b, s, h), neg_inf, dtype=jnp.float32),
        )

    def block(carry, step):
        kc, vc, acc, m_run, den = carry
        # after `step` rotations (shift=+1) the chunk we hold
        # originated `step` positions behind us on the ring
        src_idx = (my_idx - step) % n
        if causal:
            # whole-block visibility by ring position: src > my is
            # strictly above the diagonal
            branch = jnp.where(
                src_idx > my_idx, 0, jnp.where(src_idx < my_idx, 1, 2)
            )
            out_i, lse_i = lax.switch(
                branch, [skip_block, full_block, diag_block], (kc, vc)
            )
        else:
            out_i, lse_i = full_block((kc, vc))
        # online merge of normalized block outputs via lse
        m_new = jnp.maximum(m_run, lse_i)
        alpha = jnp.exp(m_run - m_new)[..., None]
        beta = jnp.exp(lse_i - m_new)[..., None]
        acc = acc * alpha + out_i.astype(jnp.float32) * beta
        den = den * alpha[..., 0] + beta[..., 0]
        # rotate KV to the next ring position
        kc = ring_permute(kc, axis_name)
        vc = ring_permute(vc, axis_name)
        return (kc, vc, acc, m_new, den), None

    acc0 = device_varying(
        jnp.zeros((b, s, h, d), dtype=jnp.float32), axis_name
    )
    m0 = device_varying(
        jnp.full((b, s, h), neg_inf, dtype=jnp.float32), axis_name
    )
    den0 = device_varying(
        jnp.zeros((b, s, h), dtype=jnp.float32), axis_name
    )
    (kc, vc, acc, m_run, den), _ = lax.scan(
        block, (k, v, acc0, m0, den0), jnp.arange(n)
    )
    return (acc / den[..., None]).astype(q.dtype)
