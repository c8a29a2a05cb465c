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

import math
import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

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


def gather_sequence(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
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
    first: Optional[jnp.ndarray] = None,  # [B] int32
    name: str = "paged_decode",
) -> jnp.ndarray:
    """Single-token GQA attention over each sequence's paged prefix.

    ``first``: table positions before ``first[b]`` are masked as well
    (a layer with a window, whose table starts at the block holding the
    window's edge: :func:`window_table_view`); ``name``: the Pallas
    kernel's name in a device trace.

    Returns ``[B, H, D]``.  fp32 logits/softmax accumulation (the MXU
    contract the dense kernels follow); masked lanes contribute
    exactly zero weight, so garbage in unallocated/null blocks can
    never leak into the output.  Lanes with ``seq_lens == 0`` return
    exact zeros.  Dispatches to the streamed Pallas kernel or this jnp
    reference per ``backend`` / :func:`paged_kernel_backend`.
    """
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import paged_decode_kernel

        return paged_decode_kernel(
            q, k_pool, v_pool, block_tables, seq_lens, first=first, name=name
        )
    b, nh, d = q.shape
    nkv = k_pool.shape[2]
    group = nh // nkv
    k = gather_sequence(k_pool, block_tables)  # [B, T, KV, D]
    v = gather_sequence(v_pool, block_tables)
    t = k.shape[1]
    qg = q.reshape(b, nkv, group, d)
    logits = jnp.einsum(
        "bkgd,btkd->bkgt", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    valid = jnp.arange(t)[None] < seq_lens[:, None]  # [B, T]
    if first is not None:
        valid = valid & (jnp.arange(t)[None] >= first[:, None])
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
    k = gather_sequence(k_pool, block_table)  # [T, KV, D]
    v = gather_sequence(v_pool, block_table)
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


def window_table_view(
    ring: jnp.ndarray,  # [..., W] int32: a lane's ring over window blocks
    first_block: jnp.ndarray,  # [...] int32: the first block that counts
    n_blocks: Optional[int] = None,  # entries of the view (default W)
) -> jnp.ndarray:
    """A lane's table over the blocks of the layers WITH a window, in
    position order from ``first_block``: the ring holds the block of
    logical index ``b`` at entry ``b % W`` (``rl/kv_cache.WindowBlocks``),
    so entry ``j`` of the view is ``ring[(first_block + j) % W]`` —
    position ``first_block * block_size + r`` at row ``r`` of the view's
    sequence.  Entries past ``W`` (a view padded to a kernel's key
    block) name the null block."""
    w = ring.shape[-1]
    j = jnp.arange(n_blocks or w, dtype=jnp.int32)
    idx = (first_block[..., None] + j) % w
    return jnp.where(
        j < w, jnp.take_along_axis(ring, idx, axis=-1), 0
    ).astype(jnp.int32)


def gather_heads_by_position(
    pool: jnp.ndarray,  # [num_blocks, bs, KV, D]
    table: jnp.ndarray,  # [n] int32: ONE sequence's blocks in position order
) -> jnp.ndarray:
    """``[KV, n * bs, D]``: the sequence's rows by position, a KV head
    the leading axis (what :func:`paged_chunk_attention` reads)."""
    g = pool[table]  # [n, bs, KV, D]
    return jnp.moveaxis(g.reshape((-1,) + g.shape[2:]), 1, 0)


def paged_chunk_attention(
    q: jnp.ndarray,  # [C, H, D] chunk of query tokens, one sequence
    k: jnp.ndarray,  # [KV, T, D] its keys by position: row r is position
    v: jnp.ndarray,  # ``key0 + r`` (:func:`gather_heads_by_position`)
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    key0: jnp.ndarray,  # scalar int32: the position of row 0
    window: Optional[int] = None,
    backend: Optional[str] = None,
    name: str = "paged_prefill",
) -> jnp.ndarray:
    """Chunked-prefill attention of a LONG context: query position
    ``t`` reads the keys ``s <= t`` and, with ``window``, ``s > t -
    window`` only (the chunk's K/V already written).  Returns ``[C, H,
    D]``.  The Pallas form streams key blocks with a running softmax
    and skips those wholly outside the mask
    (``ops/paged_kernels.chunk_prefill_kernel``); the jnp form holds
    the whole ``[C, H, T]`` logits, which only small shapes allow
    (:func:`paged_prefill_attention`'s gather and matmuls at 2048 rows
    x 48 heads x 32 k keys would be 12.9 GB of float32)."""
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import chunk_prefill_kernel

        return chunk_prefill_kernel(
            q, k, v, start_pos, key0, window=window, name=name
        )
    c, nh, d = q.shape
    nkv, t, _ = k.shape
    qg = q.reshape(c, nkv, nh // nkv, d)
    logits = jnp.einsum(
        "ckgd,ktd->ckgt", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    q_pos = (start_pos + jnp.arange(c))[:, None]
    k_pos = (key0 + jnp.arange(t))[None]
    visible = k_pos <= q_pos
    if window is not None:
        visible = visible & (k_pos > q_pos - window)
    logits = jnp.where(visible[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "ckgt,ktd->ckgd", probs.astype(v.dtype), v,
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
    k = gather_sequence(k_pool, block_tables)  # [B, T, KV, D]
    v = gather_sequence(v_pool, block_tables)
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


# ---------------------------------------------------------------------------
# heads narrower than the device's 128 lanes: ``r`` KV heads lie side by
# side in one row of the pool (``rl/kv_cache.paged_cache_config``,
# ``kv_row_heads``), ``[.., KV / r, r * hd]`` — the same bytes in the
# same order as ``[.., KV, hd]``, so a token's K (or V) is written as it
# comes (a reshape) and the kernels above read a model of ``KV / r`` KV
# heads of ``r * hd``.  What is left to the model: a query head lies in
# ITS KV head's part of a row-wide query, zeros elsewhere
# (:func:`row_queries`), so a score is its own head's; the kernels scale
# by ``(r * hd) ** -0.5``, so the model multiplies its queries by ``r **
# 0.5``; and of the row-wide sum over the values a head keeps its own
# part (:func:`row_outputs`).
# ---------------------------------------------------------------------------


def row_queries(q: jnp.ndarray, n_kv: int, r: int) -> jnp.ndarray:
    """``q [N, H, hd]`` -> ``[N, H, r * hd]``: head ``i`` (of KV head
    ``i // (H / n_kv)``, which is part ``kv % r`` of its row) in that
    part, exact zeros in the others."""
    if r == 1:
        return q
    n, nh, hd = q.shape
    parts = jnp.eye(r, dtype=q.dtype)[None, None, :, None, :, None]
    return (
        q.reshape(n, n_kv // r, r, nh // n_kv, 1, hd) * parts
    ).reshape(n, nh, r * hd)


def row_outputs(out: jnp.ndarray, n_kv: int, r: int) -> jnp.ndarray:
    """``[N, H, r * hd]`` (a paged attention's result over rows of ``r``
    heads) -> ``[N, H, hd]``: each head's own part."""
    if r == 1:
        return out
    n, nh, row = out.shape
    out = out.reshape(n, n_kv // r, r, nh // n_kv, r, row // r)
    return jnp.stack(
        [out[:, :, part, :, part] for part in range(r)], axis=2
    ).reshape(n, nh, row // r)


# ---------------------------------------------------------------------------
# learned sparse attention: an indexer scores every cached token from a
# paged INDEX-KEY cache beside the K/V pool, an EXACT top-k picks the
# token rows, and attention reads those rows alone.  A prefill chunk
# gathers ONE sequence's index keys by its table; a decode step under
# the Pallas backend gathers nothing: every lane's keys are scored from
# the leaf in place (``ops/paged_kernels.index_decode_scores_kernel``)
# ---------------------------------------------------------------------------


class IndexKeyView(NamedTuple):
    """Every lane's cached index keys WITHOUT a gather: the leaf where
    it lies and the lanes' tables — what :func:`gather_index_keys` hands
    :func:`decode_index_scores` under the Pallas backend, whose kernel
    copies the blocks a lane holds itself.  ``shape`` reads as the
    gathered array's would."""

    leaf: jnp.ndarray  # [num_blocks, block_size * Di / M, M], rows of M lanes
    tables: jnp.ndarray  # [B, max_blocks] int32 block ids IN the leaf
    width: int  # Di

    @property
    def shape(self) -> Tuple[int, int, int]:
        lanes, max_blocks = self.tables.shape
        block = math.prod(self.leaf.shape[1:]) // self.width
        return (lanes, max_blocks * block, self.width)


def gather_index_keys(
    ik_pool: jnp.ndarray,  # [num_blocks, ...] a block's index keys, in order
    tables: jnp.ndarray,  # [..., max_blocks] int32
    width: int,  # Di
    backend: Optional[str] = None,  # None -> DLROVER_TPU_PAGED_KERNEL
):
    """The index keys of each table's sequence, ``[..., max_blocks *
    block_size, Di]`` (position ``s`` at row ``s``), whichever way a
    block of the leaf lies (flat, or in rows: ``paged_leaf_rows()``).

    EVERY LANE's tables (``[B, max_blocks]``: a decode step) over a leaf
    in rows that hold whole keys are not gathered under the Pallas
    backend: an :class:`IndexKeyView` goes to :func:`decode_index_scores`
    instead (``[32, 8192, 128]`` bfloat16 a layer, 67 MB, held or not,
    at DeepSeek-V3.2's decode step).  The choice is of shapes and
    backend alone."""
    if (
        tables.ndim == 2 and ik_pool.ndim == 3
        and ik_pool.shape[-1] % width == 0
        and (backend or paged_kernel_backend()) == "pallas"
    ):
        return IndexKeyView(ik_pool, tables, width)
    keys = ik_pool[tables]
    return keys.reshape(tables.shape[:-1] + (-1, width))


def decode_index_scores(
    qi: jnp.ndarray,  # [B, Hi, Di] one index query per lane and head
    w: jnp.ndarray,  # [B, Hi] float32 head weights
    keys,  # [B, T, Di] each lane's cached index keys, or an IndexKeyView
    seq_lens: jnp.ndarray,  # [B] int32: valid positions per lane
) -> jnp.ndarray:
    """Index scores of each lane's query against its cached index keys
    (:func:`gather_index_keys`): ``I[b, s] = sum_h w[b, h] * relu(qi[b,
    h] . ik[s])``, float32 ``[B, T]``, ``-inf`` at ``s >= seq_lens[b]``
    (the null block and unwritten cells never score).  Handed a view of
    the leaf, ONE streamed kernel (``index_decode_scores`` in a device
    trace) reads the blocks each lane holds and writes the ``[B, T]``
    scores alone; handed gathered keys, two XLA products through ``[B,
    Hi, T]`` float32."""
    if isinstance(keys, IndexKeyView):
        from dlrover_tpu.ops.paged_kernels import index_decode_scores_kernel

        return index_decode_scores_kernel(
            qi, w, keys.leaf, keys.tables, seq_lens
        )
    s = jnp.einsum(
        "bhd,btd->bht", qi, keys, preferred_element_type=jnp.float32
    )
    score = jnp.einsum("bh,bht->bt", w.astype(jnp.float32), jax.nn.relu(s))
    valid = jnp.arange(keys.shape[1])[None] < seq_lens[:, None]
    return jnp.where(valid, score, -jnp.inf)


def prefill_index_scores(
    qi: jnp.ndarray,  # [C, Hi, Di] a chunk's index queries
    w: jnp.ndarray,  # [C, Hi] float32
    keys: jnp.ndarray,  # [T, Di] ONE sequence's cached index keys
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """As :func:`decode_index_scores` for a chunk of one sequence:
    float32 ``[C, T]``, ``-inf`` above the causal diagonal.  One head
    at a time, so that what is held is ``[C, T]`` and not ``[C, Hi,
    T]`` (2 GB at a 2048-row chunk over 16 k keys) — in a tile of fast
    memory under the Pallas backend where the shapes tile
    (``ops/paged_kernels.index_scores_kernel``), else in a scan."""
    c, t = qi.shape[0], keys.shape[0]
    if (
        (backend or paged_kernel_backend()) == "pallas"
        and c % min(256, c) == 0 and t % min(512, t) == 0
    ):
        from dlrover_tpu.ops.paged_kernels import index_scores_kernel

        return index_scores_kernel(qi, w, keys, start_pos)

    def one_head(acc, head):
        q_h, w_h = head
        s = jnp.einsum(
            "cd,td->ct", q_h, keys, preferred_element_type=jnp.float32
        )
        return acc + w_h[:, None] * jax.nn.relu(s), None

    score, _ = lax.scan(
        one_head, jnp.zeros((c, t), jnp.float32),
        (jnp.moveaxis(qi, 1, 0), jnp.moveaxis(w.astype(jnp.float32), 1, 0)),
    )
    visible = jnp.arange(t)[None] <= (start_pos + jnp.arange(c))[:, None]
    return jnp.where(visible, score, -jnp.inf)


def exact_topk_rows(
    scores: jnp.ndarray,  # [B, T] float32
    k: int,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32, T / max_blocks a block
    with_mask: bool = False,
):
    """The pool ROWS (``block * block_size + offset``, through each
    lane's table) of the ``k`` positions of largest score, exactly
    (``lax.approx_max_k`` below recall 1 would be an approximate answer
    where the model's is exact), equal scores lowest position first,
    int32 ``[B, k]``.  ONE stable sort by descending score that carries
    each position's row along: ``table[ids // bs] * bs + ids % bs``
    after a ``top_k`` is a gather of one int32 a selected row (~10 ns
    each on the chip: 0.33 ms a layer at 16 lanes x 2048, as long as
    fetching the rows themselves).  Where fewer than ``k`` scores are
    finite the tail names ``-inf`` positions: the caller knows the
    count.  ``with_mask``: and the same choice as a bool ``[B, T]`` over
    the positions (finite scores only), for a program that reports what
    it picked."""
    b, t = scores.shape
    bs = t // block_tables.shape[1]
    rows = (
        block_tables[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    ).reshape(b, t)
    neg, rows = lax.sort((-scores, rows), dimension=1, is_stable=True,
                         num_keys=1)
    if not with_mask:
        return rows[:, :k]
    # the same choice by POSITION: above the k-th value, and of the
    # scores equal to it the lowest positions, as the stable sort took
    kth = -neg[:, k - 1:k]
    above, equal = scores > kth, scores == kth
    room = k - jnp.sum(above, -1, keepdims=True)
    taken = above | (equal & (jnp.cumsum(equal, -1) <= room))
    return rows[:, :k], taken & jnp.isfinite(scores)


def _order_keys(scores: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' order
    (``-0.0`` as ``0.0``: equal scores are ONE value to the tie rule,
    as they are to a sort)."""
    bits = lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.int32
    )
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(keys, jnp.uint32) ^ jnp.uint32(1 << 31)


def exact_topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """``[C, T]`` float32 -> bool ``[C, T]``: row by row the ``k``
    positions of largest score (:func:`exact_topk_rows`' rule) as a
    mask — every finite score where a row has at most ``k`` of them.
    The ``k``-th largest value of a row
    is found on an order-preserving integer image of the scores, two
    bits a pass (16 counting passes of three thresholds each, no sort:
    a sort of ``[2048, 16384]`` is ~100 passes of twice the bytes);
    scores equal to it are taken lowest position first until the row
    holds ``k``.  Nothing is searched where no row has more than ``k``
    finite scores (a prompt's first chunk), and the tie rule's running
    count is taken only where some row has more scores AT its ``k``-th
    value than it has room for."""
    finite = jnp.isfinite(scores)

    def search(_):
        keys = _order_keys(scores)

        def two_bits(i, thr):
            shift = (30 - 2 * i).astype(jnp.uint32)
            best = thr
            for step in (1, 2, 3):  # ascending: the last that holds wins
                cand = thr | (jnp.uint32(step) << shift)
                enough = jnp.sum(keys >= cand, -1, keepdims=True) >= k
                best = jnp.where(enough, cand, best)
            return best

        thr = lax.fori_loop(
            0, 16, two_bits, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32)
        )
        above = keys > thr
        equal = keys == thr
        room = k - jnp.sum(above, -1, keepdims=True)
        crowded = jnp.any(jnp.sum(equal, -1, keepdims=True) > room)
        taken = lax.cond(
            crowded,
            lambda: above | (equal & (jnp.cumsum(equal, -1) <= room)),
            lambda: above | equal,
        )
        return taken & finite

    return lax.cond(
        jnp.any(jnp.sum(finite, -1) > k), search, lambda _: finite, None
    )


def sparse_rows_decode_attention(
    q: jnp.ndarray,  # [B, H, D] one query token per lane
    k_pool: jnp.ndarray,  # [num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,
    rows: jnp.ndarray,  # [B, K] int32 pool rows (exact_topk_rows)
    counts: jnp.ndarray,  # [B] int32: how many of a lane's K are real
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Single-token GQA attention over the SELECTED token rows of each
    lane's paged cache (:func:`exact_topk_rows`): the first
    ``counts[b]`` of a lane's rows count, the rest are masked (and
    routed to the null block).  Returns ``[B, H, D]``.

    The rows are fetched by one gather a pool (a token's KV heads are
    contiguous: ``KV * D`` elements a row) into ``[B, K, KV, D]``; the
    attention over them is the streamed decode kernel on that buffer,
    named ``sparse_paged_decode`` in a device trace, or the jnp
    reference."""
    b, nh, d = q.shape
    n_blocks, bs, nkv, _ = k_pool.shape
    n_sel = rows.shape[1]
    real = jnp.arange(n_sel)[None] < counts[:, None]
    rows = jnp.where(real, rows, 0)  # the rest to the null block
    flat = (n_blocks * bs, nkv, d)
    k = k_pool.reshape(flat)[rows]  # [B, K, KV, D]
    v = v_pool.reshape(flat)[rows]
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import sparse_decode_kernel

        return sparse_decode_kernel(q, k, v, counts)
    group = nh // nkv
    qg = q.reshape(b, nkv, group, d)
    logits = jnp.einsum(
        "bkgd,btkd->bkgt", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    logits = jnp.where(real[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(counts[:, None, None, None] > 0, probs, 0.0)
    out = jnp.einsum(
        "bkgt,btkd->bkgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    return out.reshape(b, nh, d)


def selected_prefill_attention(
    q: jnp.ndarray,  # [C, H, D] chunk of query tokens, one sequence
    k: jnp.ndarray,  # [T, KV, D] the sequence's cached keys, by position
    v: jnp.ndarray,
    taken: jnp.ndarray,  # [C, T] bool: the keys each query reads
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    kv_len: jnp.ndarray,  # scalar int32: keys past it are never read
    key_block: int = 1024,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Chunked-prefill attention where query ``i`` reads exactly the
    keys ``taken[i]`` marks (a selection inside the causal mask; the
    chunk's K/V already written, and gathered by position:
    :func:`gather_sequence`).  Key blocks of ``key_block`` with a
    running softmax, up to ``kv_len`` only: the logits of 2048 queries
    and 32 heads against 16 k keys would be 4 GB at once, and a chunk
    early in its prompt has few keys.  Returns ``[C, H, D]``.

    Under the Pallas backend, where the chunk's rows and keys tile by
    the kernel's blocks (256 rows of a KV head's every query head
    against 1024 keys a grid step, so a key block and the selection's
    tile are fetched once for the heads that share them; an extent
    under a block is one block), the flash form of the same sum
    (``ops/paged_kernels.selected_prefill_kernel``, which also skips
    the key blocks above the chunk's causal reach); else this one, in
    plain XLA."""
    c, nh, d = q.shape
    t, nkv = k.shape[:2]
    group = nh // nkv
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops import paged_kernels as pk

        if (
            c % min(pk.SELECTED_BLOCK_Q, c) == 0
            and t % min(pk.SELECTED_BLOCK_K, t) == 0
        ):
            return pk.selected_prefill_kernel(
                q, k, v, taken, start_pos, kv_len
            )
    kb = min(key_block, t)
    if t % kb:
        raise ValueError(f"{t} cached positions in key blocks of {kb}")
    qg = q.reshape(c, nkv, group, d)

    def one_block(j, state):
        m, l, acc = state
        k_j = lax.dynamic_slice_in_dim(k, j * kb, kb, 0)
        v_j = lax.dynamic_slice_in_dim(v, j * kb, kb, 0)
        keep = lax.dynamic_slice_in_dim(taken, j * kb, kb, 1)
        keep = keep[:, None, None]  # [C, 1, 1, kb]
        s = jnp.einsum(
            "ckgd,tkd->ckgt", qg, k_j, preferred_element_type=jnp.float32
        ) * (d**-0.5)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, -1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "ckgt,tkd->ckgd", p.astype(v.dtype), v_j,
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    lead = (c, nkv, group)
    _, l, acc = lax.fori_loop(
        0, (jnp.minimum(kv_len, t) + kb - 1) // kb, one_block,
        (
            jnp.full(lead, NEG_INF, jnp.float32),
            jnp.zeros(lead, jnp.float32),
            jnp.zeros(lead + (d,), jnp.float32),
        ),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(v.dtype).reshape(c, nh, d)


# ---------------------------------------------------------------------------
# latent attention (MLA): a token keeps ONE compressed row — no per-head
# keys or values — which decode reads in absorbed form (the row is every
# head's key and, in its leading part, every head's value) and a prefill
# chunk in decompressed, multi-head form
# ---------------------------------------------------------------------------


def latent_rows_decode_attention(
    q_c: jnp.ndarray,  # [B, H, Dc] absorbed queries: q_nope W_uk
    q_pe: jnp.ndarray,  # [B, H, Dr] rotated queries
    c_pool: jnp.ndarray,  # [rows, Dc] the latent pool as token rows
    pe_pool: jnp.ndarray,  # [rows * Dr / M, M]: M / Dr tokens' keys a row
    rows: jnp.ndarray,  # [B, K] int32 pool rows (exact_topk_rows)
    counts: jnp.ndarray,  # [B] int32: how many of a lane's K are real
    scale: float,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Single-token attention of every head over the SELECTED rows of
    each lane (:func:`exact_topk_rows`) in absorbed form: ``softmax(
    scale * (q_c . c + q_pe . k_pe)) c``, the first ``counts[b]`` of a
    lane's rows counted, the rest masked (and routed to row 0, a null
    block's).  Returns the summed latents ``[B, H, Dc]``; the caller
    applies ``W_uv``.

    The latents are fetched by one gather into ``[B, K, Dc]``.  The
    rotated shared keys lie ``M / Dr`` tokens a row of ``M`` lanes (a
    64-wide minor axis is one the device pads): the token's row is
    fetched whole, the other tokens' lanes zeroed, and the query laid
    under every token's lanes, so that ``q_pe . k_pe`` is one product of
    ``M``.  The attention over both is ``ops/paged_kernels.
    mla_sparse_decode_kernel`` (``mla_sparse_decode`` in a device
    trace) or the jnp reference."""
    n_sel, dr, lanes = rows.shape[1], q_pe.shape[-1], pe_pool.shape[-1]
    per_row = lanes // dr
    real = jnp.arange(n_sel)[None] < counts[:, None]
    rows = jnp.where(real, rows, 0)
    c = c_pool[rows]  # [B, K, Dc]
    mine = (jnp.arange(lanes) // dr)[None, None] == (rows % per_row)[..., None]
    pe = jnp.where(mine, pe_pool[rows // per_row], 0)  # [B, K, M]
    q_pe = jnp.tile(q_pe, (1, 1, per_row))
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import mla_sparse_decode_kernel

        return mla_sparse_decode_kernel(q_c, q_pe, c, pe, counts, scale=scale)
    return _absorbed_attention(q_c, q_pe, c, pe, real, scale)


def _absorbed_attention(q_c, q_pe, c, pe, keep, scale):
    """The jnp form of absorbed attention over rows laid out a lane:
    ``c`` ``[B, T, Dc]`` key and value, ``pe`` ``[B, T, M]`` against
    ``q_pe`` ``[B, H, M]``, ``keep`` ``[B, T]`` the rows that count (a
    lane with none returns zeros)."""
    logits = (
        jnp.einsum("bhd,btd->bht", q_c, c, preferred_element_type=jnp.float32)
        + jnp.einsum(
            "bhd,btd->bht", q_pe, pe, preferred_element_type=jnp.float32
        )
    ) * scale
    probs = jax.nn.softmax(jnp.where(keep[:, None], logits, NEG_INF), -1)
    probs = jnp.where(keep[:, None], probs, 0.0)
    return jnp.einsum(
        "bht,btd->bhd", probs.astype(c.dtype), c,
        preferred_element_type=jnp.float32,
    ).astype(c.dtype)


#: Decode attention over a latent cache streams a lane's own blocks
#: while its table holds at most this many times ``n_sel`` positions,
#: and gathers the picked rows of a wider one
#: (:func:`latent_decode_selection`).  Bare on a v5e (PR 54, 32 lanes x
#: 128 heads, top 2048 of a 512 + 64 wide row, ``scripts/
#: bench_paged_attention.py --latent-sweep``): the gathered fetch is
#: 1.59 ms a layer whatever a lane holds (1.07 at 2048 held), the
#: streamed one 0.39 / 0.74 / 1.09 / 1.43 / 2.13 / 2.83 / 5.59 ms at
#: 2 / 4 / 6 / 8 / 12 / 16 / 32 k held positions (0.17 us a position):
#: they cross at ~9.1 k held, 4.4 x 2048, and a table is an upper bound
#: of what its lanes hold.  The sweep's table was as wide as what a
#: lane holds, and the test is of the TABLE: a 32 k table whose lanes
#: hold 4 k is gathered at 1.59 ms where streaming would read 0.75.  No
#: benchmark cell has such a table (V's is 8192 = 4 x 2048, streamed):
#: a wide-table cell comes before anyone tunes this number
#: (``ROADMAP.md`` Queue 1).
LATENT_STREAM_WIDTH = 4


def latent_decode_streams(table_positions: int, n_sel: int) -> bool:
    """Does decode attention over ``n_sel`` picked rows of a table of
    ``table_positions`` read the lane's blocks itself, rather than
    gather the rows?  The ONE test, of shapes alone, behind
    :func:`latent_decode_selection` and
    :func:`latent_decode_read_rows`."""
    return table_positions <= LATENT_STREAM_WIDTH * n_sel


class LatentSelection(NamedTuple):
    """A decode step's picked positions, in the form
    :func:`latent_decode_attention` fetches them by."""

    taken: jnp.ndarray  # [B, T] bool: the positions a lane picked
    #: ``[B, n_sel]`` int32 leaf rows of the same choice where the rows
    #: are gathered; None where the lane's blocks are streamed
    rows: Optional[jnp.ndarray]


def latent_decode_selection(
    scores: jnp.ndarray,  # [B, T] float32 index scores, -inf past a lane
    n_sel: int,
    tables: jnp.ndarray,  # [B, MB] int32 block ids IN the leaves
) -> LatentSelection:
    """The exact top ``n_sel`` positions of every lane (equal scores
    lowest position first), prepared for the fetch the table's width
    calls for — where the choice between the two is made, once: a mask
    alone by the counting search where the blocks are streamed
    (``[32, 8192]``, top 2048, bare on a v5e: 67.5 us against the
    sort's 258.8; PR 54), the sort that carries each position's row
    where the rows are gathered."""
    if latent_decode_streams(scores.shape[1], n_sel):
        return LatentSelection(exact_topk_mask(scores, n_sel), None)
    rows, taken = exact_topk_rows(scores, n_sel, tables, with_mask=True)
    return LatentSelection(taken, rows)


def latent_decode_read_rows(
    cached: int, table_positions: int, n_sel: int, block_size: int
) -> int:
    """The rows :func:`latent_decode_attention` fetches a layer for a
    lane of ``cached`` positions under such a table: every row of the
    blocks it holds where they are streamed, else the rows picked.
    Host arithmetic for a scheduler's ``read_rows`` label — what the
    program WOULD read by its own test of shapes, not a count taken on
    the device."""
    if latent_decode_streams(table_positions, n_sel):
        return -(-cached // block_size) * block_size
    return min(cached, n_sel)


def latent_decode_attention(
    q_c: jnp.ndarray,  # [B, H, Dc] absorbed queries: q_nope W_uk
    q_pe: jnp.ndarray,  # [B, H, Dr] rotated queries
    c_leaf: jnp.ndarray,  # [N, bs, Dc] the latents' leaf
    pe_leaf: jnp.ndarray,  # [N, bs * Dr / M, M]: M / Dr tokens' keys a row
    tables: jnp.ndarray,  # [B, MB] int32 block ids IN the leaves
    seq_lens: jnp.ndarray,  # [B] int32: positions of a lane that count
    picked: LatentSelection,  # of :func:`latent_decode_selection`
    scale: float,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Single-token attention of every head over the positions each
    lane PICKED (below ``seq_lens``) in absorbed form: ``softmax(scale *
    (q_c . c + q_pe . k_pe)) c``.  Returns the summed latents ``[B, H,
    Dc]``; the caller applies ``W_uv``.  A lane of length 0 reads
    nothing and returns zeros.

    One sum, two ways to fetch, as the selection was prepared
    (:func:`latent_decode_selection`, by the table's width): a mask
    alone is STREAMED — the kernel copies the blocks a lane holds
    itself, scores every held row and masks the ones not picked
    (``ops/paged_kernels.mla_stream_decode_kernel``; a copy a block of
    16 rows costs what ~4 gathered rows do) — and where it names the
    rows they are gathered (:func:`latent_rows_decode_attention`).
    Both are ``mla_sparse_decode`` in a device trace."""
    bs, mb = c_leaf.shape[1], tables.shape[1]
    taken, rows = picked
    if rows is not None:
        return latent_rows_decode_attention(
            q_c, q_pe, c_leaf.reshape(-1, c_leaf.shape[-1]),
            pe_leaf.reshape(-1, pe_leaf.shape[-1]), rows,
            jnp.minimum(seq_lens, rows.shape[1]), scale, backend,
        )
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops.paged_kernels import mla_stream_decode_kernel

        return mla_stream_decode_kernel(
            q_c, q_pe, c_leaf, pe_leaf, tables, seq_lens, taken, scale=scale
        )
    b = q_c.shape[0]
    c = c_leaf[tables].reshape(b, mb * bs, -1)  # by position
    pe = pe_leaf[tables].reshape(b, mb * bs, -1)
    keep = taken & (jnp.arange(mb * bs)[None] < seq_lens[:, None])
    c = jnp.where(keep[..., None], c, 0)  # past the length lies garbage
    pe = jnp.where(keep[..., None], pe, 0)
    return _absorbed_attention(q_c, q_pe, c, pe, keep, scale)


def latent_prefill_attention(
    q: jnp.ndarray,  # [C, H, Dk] a chunk's queries, one sequence
    k: jnp.ndarray,  # [H, T, Dk] decompressed keys by position
    v: jnp.ndarray,  # [H, T, Dv] decompressed values
    taken: jnp.ndarray,  # [C, T] bool: the keys each query reads
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    kv_len: jnp.ndarray,  # scalar int32: keys past it are never read
    scale: float,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """A prefill chunk's attention in multi-head form, every head its
    own decompressed keys (``Dk``) and values (``Dv``, another width),
    query ``i`` reading exactly the keys ``taken[i]`` marks.  Under the
    Pallas backend, where rows and keys tile by its blocks,
    ``ops/paged_kernels.mla_prefill_kernel``; else one head at a time
    in plain XLA (``[C, T]`` float32 a head).  Returns ``[C, H, Dv]``."""
    c, t = taken.shape
    if (backend or paged_kernel_backend()) == "pallas":
        from dlrover_tpu.ops import paged_kernels as pk

        if (
            c % min(pk.MLA_BLOCK_Q, c) == 0
            and t % min(pk.SELECTED_BLOCK_K, t) == 0
        ):
            return pk.mla_prefill_kernel(
                q, k, v, taken, start_pos, kv_len, scale=scale
            )

    def one_head(head):
        q_h, k_h, v_h = head
        s = jnp.einsum(
            "cd,td->ct", q_h, k_h, preferred_element_type=jnp.float32
        ) * scale
        p = jax.nn.softmax(jnp.where(taken, s, NEG_INF), -1)
        p = jnp.where(jnp.any(taken, -1, keepdims=True), p, 0.0)
        return jnp.einsum(
            "ct,td->cd", p.astype(v_h.dtype), v_h,
            preferred_element_type=jnp.float32,
        ).astype(v_h.dtype)

    return jnp.swapaxes(lax.map(one_head, (jnp.swapaxes(q, 0, 1), k, v)), 0, 1)


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
    # what a model pages beside K and V (``paged_leaves()`` of its
    # config: an index key a token), ``{leaf: [L * num_blocks,
    # block_size * width]}``, or in rows where the model says so
    # (``paged_leaf_rows()``: ``[L * num_blocks, block_size * width /
    # minor, minor]``); empty for a block of keys and values only
    paged: Dict[str, jnp.ndarray] = {}

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

    def write_leaf(
        self,
        name: str,
        rows: jnp.ndarray,  # [N, ...] one token's row per write
        block_ids: jnp.ndarray,  # [N] int32, ids of a TABLE (0 = null)
        offsets: jnp.ndarray,  # [N] int32
    ) -> "LayerPool":
        """:func:`write_block_kv`'s sibling for a further paged leaf:
        the same cells of the same blocks, in this layer.  The leaf is
        ``[L * num_blocks, block_size * width]`` (a block's rows side
        by side, ``rl/kv_cache.init_block_pool``): row ``i`` goes to
        ``[offsets[i] * width, (offsets[i] + 1) * width)`` of its
        block's row, one scatter of ``width``-wide windows."""
        leaf = self.paged[name]
        rows = rows.reshape(rows.shape[0], -1).astype(leaf.dtype)
        leaf = lax.scatter(
            leaf,
            jnp.stack(
                [block_ids + self.base, offsets * rows.shape[1]], -1
            ),
            rows,
            lax.ScatterDimensionNumbers(
                update_window_dims=(1,),
                inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0, 1),
            ),
            mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )
        return self._replace(paged={**self.paged, name: leaf})

    def write_rows(
        self,
        k_new: jnp.ndarray,  # [N, KV, D]
        v_new: jnp.ndarray,
        block_ids: jnp.ndarray,  # [N] int32, ids of a TABLE (0 = null)
        offsets: jnp.ndarray,  # [N] int32
    ) -> "LayerPool":
        """:meth:`write` over the pool seen as token ROWS, ``row =
        block * block_size + offset`` (the view the selected-rows gather
        reads): the same cells, as the one-index scatter the compiler
        rewrites :func:`write_block_kv`'s two-index one into — written
        so here, the operation keeps its scope path into a device
        trace, which the rewritten one loses (1.4 % of the device's time
        at a 2048-row chunk)."""
        bs = self.k.shape[1]
        rows = (block_ids + self.base) * bs + offsets
        flat = (-1,) + self.k.shape[2:]
        return self._replace(
            k=self.k.reshape(flat).at[rows].set(k_new).reshape(self.k.shape),
            v=self.v.reshape(flat).at[rows].set(v_new).reshape(self.v.shape),
        )

    def write_leaf_rows(
        self,
        name: str,
        rows: jnp.ndarray,  # [N, width] one token's row per write
        block_ids: jnp.ndarray,  # [N] int32, ids of a TABLE (0 = null)
        offsets: jnp.ndarray,  # [N] int32
    ) -> "LayerPool":
        """:meth:`write_leaf` for a leaf whose blocks lie in ROWS,
        ``[L * num_blocks, block_size * width / minor, minor]``
        (``paged_leaf_rows()``, ``rl/kv_cache.init_block_pool``): where
        a token IS a row, one scatter of whole rows over the pool seen
        as rows, as :meth:`write_rows` writes K and V; where a row holds
        several tokens, one scatter of ``width``-wide windows into
        them."""
        leaf = self.paged[name]
        rows = rows.astype(leaf.dtype)
        per_block, minor = leaf.shape[1:]
        width = rows.shape[1]
        if width == minor:
            at = (block_ids + self.base) * per_block + offsets
            flat = leaf.reshape(-1, minor)
            leaf = flat.at[at].set(rows).reshape(leaf.shape)
        else:
            lane = offsets * width
            leaf = lax.scatter(
                leaf,
                jnp.stack(
                    [block_ids + self.base, lane // minor, lane % minor], -1
                ),
                rows,
                lax.ScatterDimensionNumbers(
                    update_window_dims=(1,),
                    inserted_window_dims=(0, 1),
                    scatter_dims_to_operand_dims=(0, 1, 2),
                ),
                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
        return self._replace(paged={**self.paged, name: leaf})

    def write_leaf_run(
        self,
        name: str,
        rows: jnp.ndarray,  # [C, ...] positions start .. start + C - 1
        block_table: jnp.ndarray,  # [max_blocks] int32: ONE sequence's
        start: jnp.ndarray,  # scalar int32
    ) -> "LayerPool":
        """:meth:`write_leaf` for a RUN of one sequence's positions (a
        prefill chunk): the blocks the run touches are read, overlaid
        with its rows and written back whole — ``C / block_size + 1``
        whole-row scatters where row-wise writes would be ``C`` windows
        into a minor axis, which the compiler runs one after the other.
        Positions past the table go to the null block."""
        leaf = self.paged[name]
        rows = rows.reshape(rows.shape[0], -1).astype(leaf.dtype)
        c, width = rows.shape
        # (a block's layout is the leaf's own: flat, or rows of some
        # minor width — the blocks read are viewed by token here)
        bs = math.prod(leaf.shape[1:]) // width
        mb = block_table.shape[0]
        at = start // bs + jnp.arange(-(-c // bs) + 1)  # table entries
        blocks = jnp.where(
            at < mb, block_table[jnp.minimum(at, mb - 1)], 0
        ) + self.base
        rel = (at[:, None] * bs + jnp.arange(bs)[None]) - start
        mine = ((rel >= 0) & (rel < c))[..., None]
        new = jnp.where(
            mine, rows[jnp.clip(rel, 0, c - 1)],
            leaf[blocks].reshape(-1, bs, width),
        )
        leaf = leaf.at[blocks].set(new.reshape((-1,) + leaf.shape[1:]))
        return self._replace(paged={**self.paged, name: leaf})


def scan_layers_over_pool(
    body: Callable,
    carry,
    xs,  # per-layer scanned inputs: params["layers"], small state
    k_pool: jnp.ndarray,  # [L, num_blocks, block_size, KV, D]
    v_pool: jnp.ndarray,
    read_only: bool = False,
    paged: Optional[Dict[str, jnp.ndarray]] = None,
):
    """``lax.scan`` over a model's layers with the K/V pool in the
    CARRY (the step-program contract of the module docstring).

    ``body(carry, xs_l, kv: LayerPool) -> (carry, ys_l, kv)`` — or
    ``-> (carry, ys_l)`` when ``read_only``: the flat pools are then
    closed over and nothing of them is carried or returned.  Returns
    ``(carry, ys, k_pool, v_pool)`` with the pools back in their
    stacked shape (``(carry, ys)`` when ``read_only``).

    ``paged``: the leaves a model pages beside K and V, ``{leaf: [L,
    num_blocks, block_size * width]}``.  They ride in the carry the same
    way (``kv.paged``, written by ``kv.write_leaf``) and come back
    stacked as a fifth result; a program that passes none carries
    nothing more than before."""
    n_layers, n_blocks = k_pool.shape[:2]

    def flatten(pool):
        return pool.reshape((n_layers * n_blocks,) + pool.shape[2:])

    k_flat, v_flat = flatten(k_pool), flatten(v_pool)
    stacked = paged or {}
    more = {name: flatten(leaf) for name, leaf in stacked.items()}

    if read_only:

        def step(c, xs_l):
            carry, layer = c
            kv = LayerPool(k_flat, v_flat, layer * n_blocks, layer, more)
            carry, ys_l = body(carry, xs_l, kv)
            return (carry, layer + 1), ys_l

        (carry, _), ys = lax.scan(step, (carry, jnp.int32(0)), xs)
        return carry, ys

    def step(c, xs_l):
        carry, k, v, leaves, layer = c
        carry, ys_l, kv = body(
            carry, xs_l, LayerPool(k, v, layer * n_blocks, layer, leaves)
        )
        return (carry, kv.k, kv.v, kv.paged, layer + 1), ys_l

    (carry, k_flat, v_flat, more, _), ys = lax.scan(
        step, (carry, k_flat, v_flat, more, jnp.int32(0)), xs
    )
    out = (
        carry, ys, k_flat.reshape(k_pool.shape), v_flat.reshape(v_pool.shape)
    )
    if paged is None:
        return out
    return out + (
        {name: more[name].reshape(stacked[name].shape) for name in more},
    )
