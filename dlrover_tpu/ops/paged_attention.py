"""Paged (block-table) KV attention for continuous-batching decode.

Reference parity: vLLM's PagedAttention — the serving-side dual of the
flash kernels next door.  The KV cache is a pool of fixed-size blocks
(``[num_blocks, block_size, KV, head_dim]`` per layer); a sequence owns
a list of block ids (its *block table*) instead of a contiguous slab,
so admission/eviction churn never copies or fragments cache memory.

Two ops, both pure-jnp reference implementations that run on CPU CI:

- :func:`paged_decode_attention` — one query token per sequence
  (``[B, H, D]``) over each sequence's paged prefix; the decode-hot op.
- :func:`paged_prefill_attention` — a chunk of C query tokens for ONE
  sequence over its paged prefix (causal within the chunk); the
  chunked-prefill op.

Layout contract (Pallas-friendly, so a Mosaic kernel can swap in
without touching callers): ``head_dim`` is the minormost (lane) axis,
``block_size`` the sublane axis of each block — a block is a
``[block_size, KV, head_dim]`` contiguous tile, and a kernel grid over
(sequence, block-table entry) streams exactly one tile per step, the
same shape the flash kernels tile at 128-aligned boundaries.  The
gather here (``pool[tables]``) is the reference semantics of that
grid; on TPU the kernel would DMA blocks VMEM-resident instead of
materializing the gathered ``[B, T, KV, D]`` intermediate.

A third op serves the multi-token (speculative self-drafting) decode
path:

- :func:`paged_verify_attention` — K query tokens PER LANE (``[B, C,
  H, D]``) over each lane's paged prefix, causal within the window;
  the one-forward verification of a K-token draft.

Masking contract: key position ``t`` is visible iff ``t < seq_len``
(decode) / ``t <= query_pos`` (prefill/verify).  Block 0 is the NULL
block — schedulers point unallocated table entries and inactive lanes
at it; its contents are garbage by design and every read of it is
masked.

Sharing contract (prefix caching): a block is IMMUTABLE once all
``block_size`` positions are written, so several sequences' tables may
alias the same physical block id read-only — the gather is oblivious
to aliasing, and no copy-on-write is needed because writers only ever
touch a sequence's private tail blocks (``rl/kv_cache.py`` enforces
the ownership discipline).

Step-program contract (how a model's serving program walks its layers;
:func:`scan_layers_over_pool` is the one place that does it): outside
the programs the cache is stacked ``[L, num_blocks, block_size, KV,
D]``; inside one, the pool rides WHOLE in the layer scan's carry,
viewed ``[L * num_blocks, ...]`` (merging the two leading axes is
free), and layer ``l`` offsets every block id it writes or reads by
``l * num_blocks`` — its null block is block ``l * num_blocks``.  A
pool is NEVER handed to ``lax.scan`` as a scanned input or taken back
as a stacked output: XLA then slices each layer out, updates a copy
and re-stacks it, i.e. moves the whole pool three times a step
(``tests/test_tpu_compile.py`` pins the compiled programs).
"""

import os
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30

#: Backend selector for the decode-hot ops (decode + verify; prefill
#: stays jnp).  ``auto`` picks the Pallas kernels on a TPU (compiled)
#: and wherever interpret mode is explicitly forced; ``jnp`` is the
#: kill-switch that pins the original gather-based reference
#: byte-for-byte; ``pallas`` forces the kernels anywhere.  A kernel
#: that fails to import or lower raises — no backend gives way to the
#: reference silently.
PAGED_KERNEL_ENV = "DLROVER_TPU_PAGED_KERNEL"

_VALID_BACKENDS = ("auto", "pallas", "jnp")


def paged_kernel_backend() -> str:
    """Resolve the active decode/verify backend: ``pallas`` or ``jnp``.

    ``auto`` picks the Pallas kernels where they compile to metal (a
    TPU host), and on other hosts only when interpret mode is
    explicitly forced (``DLROVER_TPU_PALLAS_INTERPRET=1`` — the
    run-the-real-kernel-slowly debug/CI switch); otherwise the jnp
    reference, which XLA fuses well enough on CPU that interpret mode
    would only burn CI wall-clock.  ``DLROVER_TPU_PAGED_KERNEL=pallas``
    forces the kernels anywhere (interpret off-TPU).

    Read at trace time: the scheduler's jitted decode step bakes the
    choice into its one compiled executable, so
    ``compile_counts()["decode"] == 1`` holds under either backend.
    """
    env = os.getenv(PAGED_KERNEL_ENV, "auto").strip().lower() or "auto"
    if env not in _VALID_BACKENDS:
        raise ValueError(
            f"{PAGED_KERNEL_ENV}={env!r}: expected one of {_VALID_BACKENDS}"
        )
    if env != "auto":
        return env
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        from dlrover_tpu.ops.pallas_utils import INTERPRET_ENV, _TRUE

        if os.getenv(INTERPRET_ENV, "").strip().lower() not in _TRUE:
            return "jnp"
    return "pallas"


def _gather_pool(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """``[num_blocks, bs, KV, D]`` gathered by ``[..., max_blocks]``
    tables -> ``[..., max_blocks * bs, KV, D]`` (the logical
    contiguous view of each sequence's paged cache)."""
    g = pool[tables]  # [..., MB, bs, KV, D]
    shape = g.shape[:-4] + (g.shape[-4] * g.shape[-3],) + g.shape[-2:]
    return g.reshape(shape)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D] one query token per sequence
    k_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32 block ids
    seq_lens: jnp.ndarray,  # [B] int32: valid positions per sequence
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
) -> jnp.ndarray:
    """Single-token GQA attention over each sequence's paged prefix.

    Returns ``[B, H, D]``.  fp32 logits/softmax accumulation (the MXU
    contract the dense kernels follow); masked lanes contribute
    exactly zero weight, so garbage in unallocated/null blocks can
    never leak into the output.  Lanes with ``seq_lens == 0`` return
    exact zeros.  Dispatches to the streamed Pallas kernel or this jnp
    reference per ``backend`` / :func:`paged_kernel_backend`.
    """
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import paged_decode_kernel

        return paged_decode_kernel(q, k_pool, v_pool, block_tables, seq_lens)
    b, nh, d = q.shape
    nkv = k_pool.shape[2]
    group = nh // nkv
    k = _gather_pool(k_pool, block_tables)  # [B, T, KV, D]
    v = _gather_pool(v_pool, block_tables)
    t = k.shape[1]
    qg = q.reshape(b, nkv, group, d)
    logits = jnp.einsum(
        "bkgd,btkd->bkgt", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    valid = jnp.arange(t)[None] < seq_lens[:, None]  # [B, T]
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # Empty lanes (seq_lens == 0) have every key masked; softmax over
    # an all-NEG_INF row is uniform-over-garbage, so zero it outright.
    probs = jnp.where(seq_lens[:, None, None, None] > 0, probs, 0.0)
    out = jnp.einsum(
        "bkgt,btkd->bkgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return out.reshape(b, nh, d)


def paged_prefill_attention(
    q: jnp.ndarray,  # [C, H, D] chunk of query tokens, one sequence
    k_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    block_table: jnp.ndarray,  # [max_blocks] int32: ONE sequence's table
    start_pos: jnp.ndarray,  # scalar int32: chunk's first position
) -> jnp.ndarray:
    """Chunked-prefill attention: query position ``start_pos + i``
    attends keys at positions ``<= start_pos + i`` (cached prefix +
    causal within the chunk).  The chunk's K/V must already be written
    into the pool.  Returns ``[C, H, D]``."""
    c, nh, d = q.shape
    nkv = k_pool.shape[2]
    group = nh // nkv
    k = _gather_pool(k_pool, block_table)  # [T, KV, D]
    v = _gather_pool(v_pool, block_table)
    t = k.shape[0]
    qg = q.reshape(c, nkv, group, d)
    logits = jnp.einsum(
        "ckgd,tkd->ckgt", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    q_pos = start_pos + jnp.arange(c)  # [C]
    visible = jnp.arange(t)[None] <= q_pos[:, None]  # [C, T]
    logits = jnp.where(visible[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "ckgt,tkd->ckgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return out.reshape(c, nh, d)


def paged_verify_attention(
    q: jnp.ndarray,  # [B, C, H, D] a window of C query tokens per lane
    k_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    block_tables: jnp.ndarray,  # [B, max_blocks] int32 block ids
    positions: jnp.ndarray,  # [B] int32: lane's first window position
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
) -> jnp.ndarray:
    """Batched-lane windowed attention: query ``i`` of lane ``b`` (at
    position ``positions[b] + i``) attends keys at positions
    ``<= positions[b] + i`` — the cached prefix plus causal within the
    window.  The window's own K/V must already sit in the pool (the
    draft loop wrote it); this op never writes.  Returns
    ``[B, C, H, D]``.  The decode-hot verify forward of speculative
    multi-token decode: one call scores a K-token draft for every
    lane.  Dispatches like :func:`paged_decode_attention`: the fused
    Pallas verify kernel shares one prefix pass across the K window
    positions; this jnp reference re-gathers the pool."""
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import paged_verify_kernel

        return paged_verify_kernel(q, k_pool, v_pool, block_tables, positions)
    b, c, nh, d = q.shape
    nkv = k_pool.shape[2]
    group = nh // nkv
    k = _gather_pool(k_pool, block_tables)  # [B, T, KV, D]
    v = _gather_pool(v_pool, block_tables)
    t = k.shape[1]
    qg = q.reshape(b, c, nkv, group, d)
    logits = jnp.einsum(
        "bckgd,btkd->bckgt", qg, k,
        preferred_element_type=jnp.float32,
    ) * (d**-0.5)
    q_pos = positions[:, None] + jnp.arange(c)[None]  # [B, C]
    visible = (
        jnp.arange(t)[None, None] <= q_pos[:, :, None]
    )  # [B, C, T]
    logits = jnp.where(visible[:, :, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bckgt,btkd->bckgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return out.reshape(b, c, nh, d)


def write_block_kv(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_new: jnp.ndarray,  # [N, KV, D] one token's K per write
    v_new: jnp.ndarray,
    block_ids: jnp.ndarray,  # [N] int32 destination block per token
    offsets: jnp.ndarray,  # [N] int32 in-block slot per token
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter N tokens' K/V into their (block, offset) cells.

    Callers route masked-out writes (inactive lanes, padded chunk
    tail) to the null block (id 0) — concurrent lanes may collide
    there, which is fine: null-block contents are never unmasked."""
    k_pool = k_pool.at[block_ids, offsets].set(k_new)
    v_pool = v_pool.at[block_ids, offsets].set(v_new)
    return k_pool, v_pool


class LayerPool(NamedTuple):
    """What one layer of a step program sees of the K/V cache: the
    WHOLE pool, every layer's blocks in one ``[L * num_blocks,
    block_size, KV, D]`` buffer, and where this layer's blocks start.
    A block id of a table means ``base + id`` here; ``base`` itself is
    the layer's null block."""

    k: jnp.ndarray  # [L * num_blocks, block_size, KV, D]
    v: jnp.ndarray
    base: jnp.ndarray  # scalar int32: layer * num_blocks
    layer: jnp.ndarray  # scalar int32

    def tables(self, block_tables: jnp.ndarray) -> jnp.ndarray:
        """A sequence's (or every lane's) table, addressing this
        layer's blocks — what the ``paged_*_attention`` ops take."""
        return block_tables + self.base

    def write(
        self,
        k_new: jnp.ndarray,  # [N, KV, D]
        v_new: jnp.ndarray,
        block_ids: jnp.ndarray,  # [N] int32, ids of a TABLE (0 = null)
        offsets: jnp.ndarray,  # [N] int32
    ) -> "LayerPool":
        """:func:`write_block_kv` into this layer's blocks."""
        k, v = write_block_kv(
            self.k, self.v, k_new, v_new, block_ids + self.base, offsets
        )
        return self._replace(k=k, v=v)


def scan_layers_over_pool(
    body: Callable,
    carry,
    xs,  # per-layer scanned inputs: params["layers"], small state
    k_pool: jnp.ndarray,  # [L, num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,
    read_only: bool = False,
):
    """``lax.scan`` over a model's layers with the K/V pool in the
    CARRY (the step-program contract of the module docstring).

    ``body(carry, xs_l, kv: LayerPool) -> (carry, ys_l, kv)`` — or
    ``-> (carry, ys_l)`` when ``read_only``: the flat pools are then
    closed over and nothing of them is carried or returned.  Returns
    ``(carry, ys, k_pool, v_pool)`` with the pools back in their
    stacked shape (``(carry, ys)`` when ``read_only``)."""
    n_layers, n_blocks = k_pool.shape[:2]
    flat = (n_layers * n_blocks,) + k_pool.shape[2:]
    k_flat, v_flat = k_pool.reshape(flat), v_pool.reshape(flat)

    if read_only:

        def step(c, xs_l):
            carry, layer = c
            kv = LayerPool(k_flat, v_flat, layer * n_blocks, layer)
            carry, ys_l = body(carry, xs_l, kv)
            return (carry, layer + 1), ys_l

        (carry, _), ys = lax.scan(step, (carry, jnp.int32(0)), xs)
        return carry, ys

    def step(c, xs_l):
        carry, k, v, layer = c
        carry, ys_l, kv = body(
            carry, xs_l, LayerPool(k, v, layer * n_blocks, layer)
        )
        return (carry, kv.k, kv.v, layer + 1), ys_l

    (carry, k_flat, v_flat, _), ys = lax.scan(
        step, (carry, k_flat, v_flat, jnp.int32(0)), xs
    )
    return (
        carry, ys, k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape)
    )
