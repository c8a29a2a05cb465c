"""Pallas/Mosaic kernels for paged attention (decode + K-step verify).

The jnp reference path in ``ops/paged_attention.py`` services one
decode token by *gathering* the sequence's entire paged prefix into a
dense ``[B, max_blocks*block_size, KV, D]`` tensor — O(context) HBM
traffic for O(1) new work.  The kernels here stream the K/V pool
page by page instead, and the gather never materializes:

- the block table and sequence lengths ride in as **scalar-prefetch**
  operands (``pltpu.PrefetchScalarGridSpec``).  The **decode** kernel
  reads ``tables[b, j]`` itself: a grid step is a LANE, the pools go
  in whole and in place, and a loop over the blocks the lane holds
  copies ``kv_span`` pages a GROUP into one contiguous buffer
  (``pltpu.make_async_copy``, two slots a pool, the next group — or the
  next lane's first — in flight while this one is computed).  Its time
  follows what the lanes hold, not the table's width: a pipeline
  operand a page cost 0.14-0.23 us for every ENTRY of the table, live
  or dead (PERF.md, PR 45).  The **latent decode** kernel
  (``mla_stream_decode_kernel``, PR 54) is the same form over the two
  leaves of a cache that keeps ONE row a token: it copies the blocks a
  lane holds, scores every held row in absorbed form and takes the
  indexer's selection as a mask over positions — no picked row is
  gathered while a table holds a few times what is picked
  (``ops/paged_attention.latent_decode_selection`` chooses by the
  shapes; under a wider table the picked rows are gathered for
  ``mla_sparse_decode_kernel``).  The **index scores of a decode
  step** (``index_decode_scores_kernel``, PR 58) are the third of the
  form, over the ONE leaf of index keys, and the first written on the
  scaffold the three share (``_stream_lane_blocks``: the leaves to
  copy, the body to run a group): every held key is scored against the
  lane's index queries and the ``[B, T]`` float32 scores are all that
  is written — no key is gathered.  The **verify** kernel and the decode
  over rows already gathered (``sparse_decode_kernel``,
  ``mla_sparse_decode_kernel``) still stream pages as ``BlockSpec``
  operands whose index maps dereference the table, or the rows' count,
  before each grid step (``_paged_call``);
- softmax runs **online** per lane (running ``(m, l, acc)`` in VMEM
  scratch, the flash-attention recipe from ``ops/flash_attention.py``)
  with fp32 logits and accumulation; the decode kernel updates it once
  a group (the group's pages side by side are one wider page: one
  logits matmul, one ``p x V`` matmul);
- lanes past ``seq_lens`` and null-block-0 reads contribute exactly
  zero weight: out-of-window columns are masked to ``NEG_INF`` *and*
  their probability rows are zeroed explicitly, so a fully-masked lane
  (``seq_lens == 0``) returns exact zeros rather than uniform weights
  over garbage;
- the per-lane **early exit**: the decode kernel's loop runs
  ``ceil(held blocks / kv_span)`` times and fetches no page a lane does
  not hold (an empty lane runs no iteration).  Under ``_paged_call``
  it is in the index map: page indices are clamped to the lane's last
  valid block, so consecutive grid steps past a short sequence
  re-request the same page and the pipeline elides the copy, while
  ``pl.when`` skips their FLOPs — every such step still costs its
  operands' bookkeeping.

Layout contract (established in PR 13, unchanged): pools are
``[num_blocks, block_size, KV, head_dim]`` with ``head_dim`` minormost;
block 0 is the null block and is garbage by design.  The kernels view
a pool as ``[num_blocks, block_size * KV, head_dim]`` — merging the two
middle axes keeps the chip's tiled memory order, so the reshape is a
bitcast, not a copy (pinned in ``tests/test_tpu_compile.py``).  A page
is a ``[block_size * KV, head_dim]`` tile (row ``t * KV + h`` = token
``t`` of KV head ``h``) and ONE matmul scores every query row against
every row of the page (of the group's pages); a head-match
mask keeps each query row on its own KV head's columns.  That spends
KV times the useful MXU work on a memory-bound op in exchange for a
body Mosaic accepts: no per-head strided sublane read and no
transposed mask (the per-head formulation compiled in interpret mode
only).  Every index vector is built from an iota in the orientation it
is used in.

Tunables per kernel (see ``ops/autotune.py``): ``q_rows`` (query rows
per KV head; the TOTAL row count is padded to a sublane tile here) and
``kv_span`` (pool pages a group for decode; for verify the pages
streamed per grid step: the pool is passed ``kv_span`` times with
staggered index maps, which is how a pipelined kernel widens its KV
block without regathering).

CPU CI runs these kernels in interpret mode
(``ops/pallas_utils.use_interpret``); on TPU the same bodies lower to
Mosaic (``tests/test_tpu_compile.py`` compiles them for a described
v5e at head_dim 128, ``chip_smoke.py`` runs them on the chip).

**Head widths.**  Every kernel here is compiled and run at a minor axis
of 128 lanes and at no other: the dense, hybrid, window / full and
selected-keys models have heads of 128 (the benchmark's cells C, F, K,
T, O run them on the chip).  A model with NARROWER heads does not come
here with a minor axis of its own: it lays ``r = 128 / head_dim`` KV
heads side by side in one row of the pool (``rl/kv_cache.py``
``kv_row_heads``; ``models/lfm2_moe.py``: two heads of 64) and these
kernels read a model of ``KV / r`` heads of 128 — the queries zero
outside their own head's part of the row, the part cut from the result
(``ops/paged_attention.row_queries`` / ``row_outputs``).
``tests/test_tpu_compile.py`` compiles ``paged_full_decode`` and
``paged_prefill_full`` at that shape (``*_kv64``: 256 lanes, 4 rows of
128 a token, 8 query rows a KV row) and the benchmark's cell M runs them
on the chip.  The latent and index-key kernels further down have minor
axes of their own (512, 128: ``paged_leaf_rows()``), compiled and run at
V's, L's and K's shapes.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas_utils import named_kernel, use_interpret

NEG_INF = -1e30


def sublane_tile(dtype) -> int:
    """Minimum legal Mosaic sublane tile for ``dtype`` (lane is 128)."""
    itemsize = np.dtype(dtype).itemsize
    if itemsize >= 4:
        return 8
    if itemsize == 2:
        return 16
    return 32


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _iota_rows(n: int) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _iota_cols(n: int) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _online_update(m_scr, l_scr, acc_scr, s_log, v, keep=None):
    """One online-softmax step over every query row at once.

    ``s_log`` is fp32 ``[R, C]`` raw logits, ``keep`` a bool mask of
    the same shape, ``v`` ``[C, D]`` with garbage rows already zeroed.
    Probabilities are re-zeroed after the exp so a row with no visible
    keys accumulates ``l == 0`` (→ exact-zero output at finalize)
    instead of the uniform-over-garbage a plain softmax produces.
    ``keep=None`` says every row reads every column: no select before
    the max and none after the exp.
    """
    if keep is not None:
        s_log = jnp.where(keep, s_log, NEG_INF)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s_log, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s_log - m_new)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
        p.astype(v.dtype),
        v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, dtype=m_scr.dtype)
    l_scr[...] = jnp.zeros(l_scr.shape, dtype=l_scr.dtype)
    acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=acc_scr.dtype)


def _finalize(o_ref, m_scr, l_scr, acc_scr):
    denom = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _logits(q_ref, k_ref, scale):
    """fp32 ``[R, C]`` logits of every query row against every (token,
    KV head) column of one page; operands stay in the pool dtype so a
    bf16 pool feeds the MXU directly."""
    return (
        lax.dot_general(
            q_ref[0],
            k_ref[0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )


def _page_geometry(n_rows: int, per_head: int, block_size: int, n_kv: int):
    """Static index vectors of the one-matmul-per-page layout.

    A page arrives as ``[block_size * KV, D]`` (row ``t * KV + h`` is
    token ``t`` of KV head ``h`` — the pool's own memory order), so the
    logits of *all* heads come out of one ``[R, D] x [D, bs*KV]``
    matmul and a head-match mask keeps each query row on its own KV
    head's columns.  Everything is built from iotas in the orientation
    it is used in: no in-kernel transpose, no strided sublane read.
    """
    n_cols = block_size * n_kv
    row = _iota_rows(n_rows)  # [R, 1]
    col = _iota_cols(n_cols)  # [1, C]
    row_head = lax.div(row, per_head)
    row_in_head = lax.rem(row, per_head)
    same_head = row_head == lax.rem(col, n_kv)  # [R, C]
    col_tok = lax.div(col, n_kv)  # [1, C] token offset of each column
    v_tok = lax.div(_iota_rows(n_cols), n_kv)  # [C, 1] same, sublane-major
    return row_in_head, same_head, col_tok, v_tok


# ---------------------------------------------------------------------------
# decode: one query token per lane
# ---------------------------------------------------------------------------
# ``_decode_kernel`` under ``_paged_call`` (a grid step a span of table
# entries, a pipeline operand a page) serves ``sparse_decode_kernel``;
# ``paged_decode_kernel`` runs ``_stream_decode_kernel``.


def _decode_kernel(
    tables_ref,  # scalar prefetch [B, MB] — unused in body (index maps only)
    lens_ref,  # scalar prefetch [B]
    q_ref,  # [1, R, D]
    *rest,
    span: int,
    block_size: int,
    n_kv: int,
    gp: int,
    scale: float,
    lower: bool = False,
):
    k_refs = rest[:span]  # each [1, bs*KV, D]
    v_refs = rest[span : 2 * span]
    o_ref = rest[2 * span]
    m_scr, l_scr, acc_scr = rest[2 * span + 1 :]
    del tables_ref

    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    seq_len = lens_ref[b]
    # ``lower``: the scalars hold a second value a lane behind the
    # lengths, the first position of the table that counts (a window's
    # edge inside its first block); positions before it are masked
    first = lens_ref[pl.num_programs(0) + b] if lower else None

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    # Early exit: lanes whose prefix ended before this span of pages do
    # no work (their pages were index-clamped, so no fresh copy either).
    @pl.when(j * span * block_size < seq_len)
    def _compute():
        # consecutive pages hold consecutive tokens: the logits of a
        # GROUP of them side by side are those of one page of ``group *
        # block_size`` tokens, and one softmax update serves the group —
        # a page at a time, every page waited for the one before it (its
        # running maximum), which is what a step of 16 pages spent most
        # of its time on.  A group's float32 logits stay under 2 MiB.
        cols = block_size * n_kv
        rows = q_ref.shape[1]
        group = max(1, min(span, (2 << 20) // (rows * cols * 4)))
        v_tok = lax.div(_iota_rows(cols), n_kv)  # [C, 1] within a page
        for g0 in range(0, span, group):
            pages = range(g0, min(g0 + group, span))
            _, same_head, col_tok, _ = _page_geometry(
                rows, gp, block_size * len(pages), n_kv
            )
            start = (j * span + g0) * block_size
            keep = same_head & (start + col_tok < seq_len)  # [R, pages * C]
            if lower:
                keep = keep & (start + col_tok >= first)
            s_log = jnp.concatenate(
                [_logits(q_ref, k_refs[s], scale) for s in pages], axis=1
            )
            s_log = jnp.where(keep, s_log, NEG_INF)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s_log, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(keep, jnp.exp(s_log - m_new), 0.0)
            l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc_scr[...] * alpha
            for n, s in enumerate(pages):
                at = start + n * block_size + v_tok
                v_keep = at < seq_len
                if lower:
                    v_keep = v_keep & (at >= first)
                # Zero garbage V rows: 0 * NaN would poison the accumulator.
                v_page = v_refs[s][0]
                v_page = jnp.where(v_keep, v_page, jnp.zeros_like(v_page))
                acc = acc + lax.dot_general(
                    p[:, n * cols:(n + 1) * cols].astype(v_page.dtype),
                    v_page, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            acc_scr[...] = acc
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nj - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def _paged_call(
    kernel,
    qg: jnp.ndarray,  # [B, KV*rows_per_head, D]
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    scalars: jnp.ndarray,  # [B] int32: seq_lens (decode) / positions (verify)
    *,
    span: int,
    last_block,  # (scalars, b) -> last valid block index of lane b
    name: str,  # the kernel's name in a device trace
) -> jnp.ndarray:
    """Shared ``pallas_call`` plumbing of the two kernels: pad the query
    rows to a sublane tile, view the pools as ``[N, bs*KV, D]`` (a
    layout-preserving merge of the two middle axes) and stream ``span``
    pages per grid step through staggered index maps."""
    batch, n_rows, head_dim = qg.shape
    n_blocks, block_size, n_kv, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    nj = -(-max_blocks // span)
    rows_p = _round_up(n_rows, sublane_tile(qg.dtype))
    if rows_p > n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, rows_p - n_rows), (0, 0)))
    n_cols = block_size * n_kv
    k_flat = k_pool.reshape(n_blocks, n_cols, head_dim)
    v_flat = v_pool.reshape(n_blocks, n_cols, head_dim)

    def _q_index(b, j, tables, scal):
        del j, tables, scal
        return (b, 0, 0)

    def _page_index(b, j, tables, scal, s=0):
        # Clamp to the lane's last valid block: grid steps past a short
        # sequence re-request the same page, and the pipeline elides
        # the copy (the per-lane early exit for traffic).
        last = jnp.maximum(last_block(scal, b), 0)
        idx = jnp.minimum(j * span + s, jnp.minimum(last, max_blocks - 1))
        return (tables[b, idx], 0, 0)

    kv_specs = [
        pl.BlockSpec(
            (1, n_cols, head_dim), functools.partial(_page_index, s=s)
        )
        for s in range(span)
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, nj),
        in_specs=[pl.BlockSpec((1, rows_p, head_dim), _q_index)]
        + kv_specs
        + kv_specs,
        out_specs=pl.BlockSpec((1, rows_p, head_dim), _q_index),
        scratch_shapes=[
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, head_dim), jnp.float32),
        ],
    )
    out = named_kernel(
        name,
        pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (batch, rows_p, head_dim), qg.dtype
            ),
            interpret=use_interpret(),
            name=name,
        ),
    )(
        block_tables.astype(jnp.int32),
        scalars.astype(jnp.int32),
        qg,
        *([k_flat] * span),
        *([v_flat] * span),
    )
    return out[:, :n_rows]


def _stream_decode_kernel(
    tables_ref,  # scalar prefetch [B, MB]
    lens_ref,  # scalar prefetch [B], or [2 B]: lengths, then ``first``
    q_ref,  # [1, R, D]
    k_hbm,  # [N, bs*KV, D]: the whole pool, where it lies
    v_hbm,
    o_ref,  # [1, R, D]
    k_buf,  # [2, span, bs*KV, D]: two slots, a group of pages each
    v_buf,
    sems,  # DMA [2, 2]: (pool, slot)
    done,  # SMEM [1]: groups computed by the lanes before this one
    m_scr,
    l_scr,
    acc_scr,
    *,
    span: int,
    block_size: int,
    n_kv: int,
    gp: int,
    scale: float,
    lower: bool,
):
    """One lane a grid step; the kernel fetches its own pages.

    A lane's blocks are read in GROUPS of ``span`` table entries: each
    page is copied from where it lies in the pool into its place in one
    contiguous ``[span * bs*KV, D]`` buffer, so a group is one wider
    page — one logits matmul, one float32 softmax update, one ``p x V``
    matmul.  The loop runs ``ceil(blocks / span)`` times for the blocks
    the lane HOLDS, whatever the table's width; a group's copies are
    started one group ahead (two slots a pool), and the first group of
    the next lane before this lane's last is computed, so the slot
    parity (``done``) carries over the grid step."""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    max_blocks = tables_ref.shape[1]
    cols = block_size * n_kv  # rows of the buffer a page fills

    def held(lane):  # blocks a lane's table really holds
        blocks = lax.div(lens_ref[lane] + block_size - 1, block_size)
        return jnp.minimum(blocks, max_blocks)

    def groups(lane):
        return lax.div(held(lane) + span - 1, span)

    def copies(lane, i, slot, arrive):
        """Start, or wait for, the copy of every page lane ``lane``
        holds of its group ``i``, in a loop of as many turns (unrolled
        in Python, ``2 x span`` copies at each of four places cost a
        replica seconds of tracing a layer).  Entries past the lane's
        last block are neither read from the table (the last group may
        reach past its end) nor fetched: their rows of the buffer are
        stale, and masked.  A whole group is waited for at once: a
        slot's semaphore counts what has arrived, and ``span`` pages
        are the buffer's size."""
        n_pages = jnp.minimum(held(lane) - i * span, span)
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))

        def page(s, carry):
            block = tables_ref[lane, i * span + s]
            for pool, (hbm, buf) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    hbm.at[block], buf.at[slot, s], sems.at[pool, slot]
                )
                copy.wait() if arrive else copy.start()
            return carry

        if not arrive:
            lax.fori_loop(0, n_pages, page, 0)
            return

        @pl.when(n_pages == span)
        def _whole():
            for pool, (_, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sems.at[pool, slot]
                ).wait()

        @pl.when(n_pages < span)
        def _tail():
            lax.fori_loop(0, n_pages, page, 0)

    start = functools.partial(copies, arrive=False)
    wait = functools.partial(copies, arrive=True)

    @pl.when(b == 0)
    def _first_lane():
        done[0] = 0

    seq_len = lens_ref[b]
    first = lens_ref[lanes + b] if lower else None
    n_groups = groups(b)
    base = done[0]
    before = jnp.maximum(b - 1, 0)
    after = jnp.minimum(b + 1, lanes - 1)
    after_reads = (b + 1 < lanes) & (groups(after) > 0)

    # the lane before starts this lane's first group, if it ran at all
    @pl.when((n_groups > 0) & ((b == 0) | (groups(before) == 0)))
    def _own_first_group():
        start(b, 0, lax.rem(base, 2))

    _init_state(m_scr, l_scr, acc_scr)
    # the group as ONE page of ``span * block_size`` tokens
    _, same_head, col_tok, v_tok = _page_geometry(
        q_ref.shape[1], gp, block_size * span, n_kv
    )

    def group(i, carry):
        slot = lax.rem(base + i, 2)

        @pl.when(i + 1 < n_groups)
        def _next_group():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_groups) & after_reads)
        def _next_lane():
            start(after, 0, 1 - slot)

        wait(b, i, slot)
        at = i * span * block_size  # the group's first position
        keep = same_head & (at + col_tok < seq_len)  # [R, span * C]
        v_keep = at + v_tok < seq_len
        if lower:
            keep = keep & (at + col_tok >= first)
            v_keep = v_keep & (at + v_tok >= first)
        # Zero garbage V rows: 0 * NaN would poison the accumulator.
        v = v_buf[slot].reshape(span * cols, -1)
        v = jnp.where(v_keep, v, jnp.zeros_like(v))
        s_log = lax.dot_general(
            q_ref[0], k_buf[slot].reshape(span * cols, -1),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        _online_update(m_scr, l_scr, acc_scr, s_log, v, keep)
        return carry

    lax.fori_loop(0, n_groups, group, 0)
    done[0] = base + n_groups
    _finalize(o_ref, m_scr, l_scr, acc_scr)


def paged_decode_kernel(
    q: jnp.ndarray,  # [B, H, D]
    k_pool: jnp.ndarray,  # [N, bs, KV, D]
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MB] int32
    seq_lens: jnp.ndarray,  # [B] int32
    *,
    config: Optional[Dict[str, Any]] = None,
    first: Optional[jnp.ndarray] = None,  # [B] int32
    name: str = "paged_decode",
) -> jnp.ndarray:
    """Streamed paged GQA decode attention. Drop-in for the jnp path.

    A grid step is a lane; the pools go in whole and in place, and the
    kernel copies the pages a lane holds itself, ``kv_span`` of them a
    group (:func:`_stream_decode_kernel`): its time follows what the
    lanes hold, not the table's width.

    ``first``: positions of a lane's table before ``first[b]`` are
    masked too (the table of a layer with a window starts at the block
    that holds the window's edge, which need not be the block's first
    token).  ``name``: what the kernel is called in a device trace."""
    from dlrover_tpu.ops import autotune

    batch, n_heads, head_dim = q.shape
    n_blocks, block_size, n_kv, _ = k_pool.shape
    group = n_heads // n_kv
    max_blocks = block_tables.shape[1]
    if config is None:
        config = autotune.get_config(
            "decode",
            group=group,
            head_dim=head_dim,
            block_size=block_size,
            max_blocks=max_blocks,
            dtype=q.dtype,
        )
    span = max(1, min(int(config.get("kv_span", 1)), max_blocks))
    gp = max(int(config.get("q_rows", group)), group)

    qg = q.reshape(batch, n_kv, group, head_dim)
    if gp > group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    n_rows = n_kv * gp
    rows_p = _round_up(n_rows, sublane_tile(q.dtype))
    qg = qg.reshape(batch, n_rows, head_dim)
    if rows_p > n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, rows_p - n_rows), (0, 0)))
    n_cols = block_size * n_kv
    # of the fast memory a group's float32 logits stay under 2 MiB, and
    # each of the four buffers (two slots a pool) under 1 MiB
    page_bytes = n_cols * head_dim * k_pool.dtype.itemsize
    span = max(
        1,
        min(span, (2 << 20) // (rows_p * n_cols * 4), (1 << 20) // page_bytes),
    )

    def q_index(b, tables, scal):
        del tables, scal
        return (b, 0, 0)

    buf = pltpu.VMEM((2, span, n_cols, head_dim), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, rows_p, head_dim), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows_p, head_dim), q_index),
        scratch_shapes=[
            buf,
            buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, head_dim), jnp.float32),
        ],
    )
    out = named_kernel(
        name,
        pl.pallas_call(
            functools.partial(
                _stream_decode_kernel,
                span=span,
                block_size=block_size,
                n_kv=n_kv,
                gp=gp,
                scale=head_dim**-0.5,
                lower=first is not None,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (batch, rows_p, head_dim), q.dtype
            ),
            interpret=use_interpret(),
            name=name,
            # the slot parity and the next lane's first group carry
            # over a grid step: the lanes run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
        ),
    )(
        block_tables.astype(jnp.int32),
        (
            seq_lens if first is None else jnp.concatenate([seq_lens, first])
        ).astype(jnp.int32),
        qg,
        k_pool.reshape(n_blocks, n_cols, head_dim),
        v_pool.reshape(n_blocks, n_cols, head_dim),
    )
    out = out[:, :n_rows].reshape(batch, n_kv, gp, head_dim)[:, :, :group]
    return out.reshape(batch, n_heads, head_dim)


def sparse_decode_kernel(
    q: jnp.ndarray,  # [B, H, D]
    k_rows: jnp.ndarray,  # [B, K, KV, D] the selected token rows
    v_rows: jnp.ndarray,
    counts: jnp.ndarray,  # [B] int32: rows of a lane that are real
    *,
    page: int = 256,
) -> jnp.ndarray:
    """Decode attention over a lane's SELECTED rows, already gathered
    (``ops/paged_attention.sparse_rows_decode_attention``): the decode
    kernel above on the gathered buffer, viewed as each lane's own
    ``K / page`` pages of ``page`` tokens (a reshape of contiguous
    rows), under the name ``sparse_paged_decode``.  Rows past
    ``counts`` are masked as positions past ``seq_lens`` are, and a
    lane's pages past them are neither fetched nor computed."""
    batch, n_heads, head_dim = q.shape
    _, n_sel, n_kv, _ = k_rows.shape
    group = n_heads // n_kv
    page = int(np.gcd(n_sel, page))
    pages = n_sel // page
    shape = (batch * pages, page, n_kv, head_dim)
    tables = jnp.arange(batch * pages, dtype=jnp.int32).reshape(batch, pages)
    qg = q.reshape(batch, n_kv * group, head_dim)
    out = _paged_call(
        functools.partial(
            _decode_kernel,
            span=1,
            block_size=page,
            n_kv=n_kv,
            gp=group,
            scale=head_dim**-0.5,
        ),
        qg,
        k_rows.reshape(shape),
        v_rows.reshape(shape),
        tables,
        counts,
        span=1,
        last_block=lambda lens, b: lax.div(lens[b] + page - 1, page) - 1,
        name="sparse_paged_decode",
    )
    return out.reshape(batch, n_heads, head_dim)


def _index_scores_kernel(
    start_ref,  # scalar prefetch [1]: the chunk's first position
    q_ref,  # [Hi, BQ, Di] index queries, a head the leading axis
    w_ref,  # [Hi, BQ, 1] float32 head weights
    k_ref,  # [BK, Di] index keys
    o_ref,  # [BQ, BK] float32
    *,
    block_q: int,
    block_k: int,
):
    i, j = pl.program_id(0), pl.program_id(1)
    row = start_ref[0] + i * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    col = j * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    @pl.when(j * block_k <= start_ref[0] + (i + 1) * block_q - 1)
    def _compute():
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for h in range(q_ref.shape[0]):
            s = lax.dot_general(
                q_ref[h], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + w_ref[h] * jnp.maximum(s, 0.0)
        o_ref[...] = jnp.where(col <= row, acc, -jnp.inf)

    @pl.when(j * block_k > start_ref[0] + (i + 1) * block_q - 1)
    def _above():  # wholly above the causal diagonal
        o_ref[...] = jnp.full((block_q, block_k), -jnp.inf, jnp.float32)


def _index_scores_params(heads: int, bq: int, bk: int, d: int, dtype):
    """Fast memory for a step's index queries and head weights (a
    weight a lane-padded row: ``heads * block_q * 128`` float32), twice
    each, where that passes what a kernel has by default (64 heads of
    128: 24 MB); None where it does not (16 heads of 64: 5 MB)."""
    need = 2 * heads * bq * (d * np.dtype(dtype).itemsize + 128 * 4)
    if need <= (8 << 20):
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(need + 6 * bq * bk * 4 + (8 << 20))
    )


def index_scores_kernel(
    qi: jnp.ndarray,  # [C, Hi, Di] a chunk's index queries
    w: jnp.ndarray,  # [C, Hi] float32 head weights
    keys: jnp.ndarray,  # [T, Di] the sequence's index keys by position
    start_pos: jnp.ndarray,  # scalar int32
    *,
    block_q: int = 256,
    block_k: int = 512,
) -> jnp.ndarray:
    """``ops/paged_attention.prefill_index_scores``'s Pallas form
    (``index_scores`` in a device trace): ``I[t, s] = sum_h w[t, h] *
    relu(qi[t, h] . ik[s])`` in float32, ``-inf`` above the causal
    diagonal, a ``block_q x block_k`` tile a grid step with the heads'
    sum held in fast memory — the XLA form reads and writes the whole
    ``[C, T]`` float32 accumulator once a head."""
    c, heads, d = qi.shape
    t = keys.shape[0]
    bq, bk = min(block_q, c), min(block_k, t)
    if c % bq or t % bk:
        raise ValueError(f"a chunk of {c} x {t} keys in blocks {bq} x {bk}")

    def last_block(i, start):
        return (start[0] + (i + 1) * bq - 1) // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c // bq, t // bk),
        in_specs=[
            pl.BlockSpec((heads, bq, d), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec((heads, bq, 1), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec(
                (bk, d),
                lambda i, j, start: (jnp.minimum(j, last_block(i, start)), 0),
            ),
        ],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j, start: (i, j)),
    )
    return named_kernel(
        "index_scores",
        pl.pallas_call(
            functools.partial(
                _index_scores_kernel, block_q=bq, block_k=bk
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((c, t), jnp.float32),
            interpret=use_interpret(),
            name="index_scores",
            compiler_params=_index_scores_params(heads, bq, bk, d, qi.dtype),
        ),
    )(
        jnp.reshape(start_pos, (1,)).astype(jnp.int32),
        jnp.swapaxes(qi, 0, 1),
        jnp.swapaxes(w.astype(jnp.float32), 0, 1)[..., None],
        keys,
    )


def _rows_by_kv_head(q, block_q: int, n_kv: int):
    """``[C, H, D]`` queries as ``[KV * C / BQ, G * BQ, D]``: a grid
    step's rows are a KV head's query heads one after the other, the
    same ``block_q`` positions of each."""
    c, n_heads, d = q.shape
    nq, group = c // block_q, n_heads // n_kv
    qg = q.reshape(nq, block_q, n_kv, group, d).transpose(2, 0, 3, 1, 4)
    return qg.reshape(n_kv * nq, group * block_q, d)


def _rows_by_position(out, c: int, n_heads: int, block_q: int):
    """:func:`_rows_by_kv_head` undone: ``[C, H, D]``."""
    nq, d = c // block_q, out.shape[-1]
    n_kv = out.shape[0] // nq
    out = out.reshape(n_kv, nq, n_heads // n_kv, block_q, d)
    return out.transpose(1, 3, 0, 2, 4).reshape(c, n_heads, d)


def _chunk_step_params(rows: int, block_k: int):
    """Fast memory for a prefill step's ``[rows, block_k]`` float32
    logits and what the compiler keeps beside them (75 MB at 2048 x
    1024, 109 at 3072)."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(max(32 << 20, 8 * rows * block_k * 4 + (8 << 20)))
    )


# a query head's rows and the keys a grid step of
# ``selected_prefill_kernel`` takes; a chunk they do not tile keeps the
# XLA form (``ops/paged_attention.selected_prefill_attention``)
SELECTED_BLOCK_Q, SELECTED_BLOCK_K = 256, 1024


def _selected_prefill_kernel(
    bounds_ref,  # scalar prefetch [2]: the chunk's first position, kv_len
    q_ref,  # [1, G * BQ, D]: a KV head's query heads, BQ rows each
    k_ref,  # [1, BK, D]
    v_ref,
    keep_ref,  # [BQ, BK] int8: the keys each query reads, of every head
    o_ref,  # [1, G * BQ, D]
    m_scr,
    l_scr,
    acc_scr,
    *,
    block_q: int,
    block_k: int,
    scale: float,
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    # the last key any row of this query block may read: causal, and
    # nothing past what is cached (blocks past it were index-clamped)
    last = jnp.minimum(
        bounds_ref[1] - 1, bounds_ref[0] + (i + 1) * block_q - 1
    )

    @pl.when(j * block_k <= last)
    def _compute():
        # the selection is a token's, not a head's: the tile is fetched
        # and widened once, and laid under each head's rows
        keep = keep_ref[...].astype(jnp.int32)
        keep = jnp.concatenate([keep] * (q_ref.shape[1] // block_q), axis=0)
        _online_update(
            m_scr, l_scr, acc_scr, _logits(q_ref, k_ref, scale), v_ref[0],
            keep != 0,
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def selected_prefill_kernel(
    q: jnp.ndarray,  # [C, H, D] a chunk's queries
    k: jnp.ndarray,  # [T, KV, D] the sequence's keys by position
    v: jnp.ndarray,
    taken: jnp.ndarray,  # [C, T] bool: the keys each query reads
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    kv_len: jnp.ndarray,  # scalar int32: keys past it are never read
    *,
    block_q: int = SELECTED_BLOCK_Q,
    block_k: int = SELECTED_BLOCK_K,
) -> jnp.ndarray:
    """Chunked-prefill GQA attention where query ``i`` reads exactly
    the keys ``taken[i]`` marks (``ops/paged_attention.
    selected_prefill_attention``'s Pallas form, ``sparse_prefill`` in a
    device trace): a flash forward whose mask is data.  A grid step is
    one KV head's ``group`` query heads, ``block_q`` rows each, against
    ``block_k`` keys, the rows laid out as :func:`chunk_prefill_kernel`
    lays them: a key block, and the ``[block_q, block_k]`` int8 tile of
    the selection (a token's, the same for every head), are fetched
    once for the eight heads that share them, and what a step pays a
    row group — its fixed price, two cross-lane reductions, the rescale
    — it pays once 1024 keys.  Key blocks past the query block's causal
    reach or past ``kv_len`` are neither fetched nor computed, so a
    chunk early in its prompt costs its own keys.  The logits never
    leave the chip's fast memory (the XLA form writes and re-reads
    ``[C, H, key_block]`` float32 three times a key block: 0.8 GB a
    block at 2048 x 32).  Returns ``[C, H, D]``.

    At 32 / 4 heads of 128 the compiler's schedule of a step
    (``scripts/kernel_schedule.py sparse_prefill``) is 11 088 bundles
    for eight ``256 x 1024`` tiles of logits, 1 386 a 262 144 logits,
    where one head's ``512 x 512`` step was 2 178; on a v5e the bare
    kernel reads 1.07-1.23 us a 262 144 logits where that read
    2.22-2.35, and a 2048-row chunk that ends at 4096 / 8192 / 12288 /
    16384 keys 1.10 / 2.12 / 3.19 / 4.24 ms where it took 1.95 / 4.20 /
    6.44 / 8.66.  ``128 x 1024`` is within 2 %, ``256 x 512`` half as
    far from the old form, and the heads in a loop inside the step (the
    tile widened once) 12-15 % slower (PERF.md, PR 52)."""
    c, n_heads, d = q.shape
    t, n_kv, _ = k.shape
    bq, bk = min(block_q, c), min(block_k, t)
    if c % bq or t % bk:
        raise ValueError(f"a chunk of {c} x {t} keys in blocks {bq} x {bk}")
    out = _selected_prefill_call(
        _rows_by_kv_head(q, bq, n_kv), jnp.swapaxes(k, 0, 1),
        jnp.swapaxes(v, 0, 1), taken, start_pos, kv_len,
        block_q=bq, block_k=bk, scale=d**-0.5, name="sparse_prefill",
    )
    return _rows_by_position(out, c, n_heads, bq)


def _selected_prefill_call(
    qg: jnp.ndarray,  # [KV * C / BQ, G * BQ, Dk] (``_rows_by_kv_head``)
    k: jnp.ndarray,  # [KV, T, Dk] keys by position, a KV head leading
    v: jnp.ndarray,  # [KV, T, Dv]
    taken: jnp.ndarray,  # [C, T] bool
    start_pos: jnp.ndarray,
    kv_len: jnp.ndarray,
    *,
    block_q: int,
    block_k: int,
    scale: float,
    name: str,
) -> jnp.ndarray:
    """The ``pallas_call`` of :func:`selected_prefill_kernel` and
    :func:`mla_prefill_kernel` under ``name``: keys of ``Dk``, values
    of ``Dv`` (the two may differ).  Returns ``[KV * C / BQ, G * BQ,
    Dv]``."""
    n_kv, t, d = k.shape
    dv = v.shape[-1]
    bq, bk = block_q, block_k
    nq, rows = taken.shape[0] // bq, qg.shape[1]

    def last_block(i, bounds):
        last = jnp.minimum(bounds[1] - 1, bounds[0] + (i + 1) * bq - 1)
        return jnp.maximum(last, 0) // bk

    def q_index(h, i, j, bounds):
        del j, bounds
        return (h * nq + i, 0, 0)

    def kv_index(h, i, j, bounds):
        return (h, jnp.minimum(j, last_block(i, bounds)), 0)

    def keep_index(h, i, j, bounds):
        del h
        return (i, jnp.minimum(j, last_block(i, bounds)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kv, nq, t // bk),
        in_specs=[
            pl.BlockSpec((1, rows, d), q_index),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, dv), kv_index),
            pl.BlockSpec((bq, bk), keep_index),
        ],
        out_specs=pl.BlockSpec((1, rows, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    return named_kernel(
        name,
        pl.pallas_call(
            functools.partial(
                _selected_prefill_kernel, block_q=bq, block_k=bk,
                scale=scale,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_kv * nq, rows, dv), qg.dtype),
            interpret=use_interpret(),
            name=name,
            compiler_params=_chunk_step_params(rows, bk),
        ),
    )(
        jnp.stack([start_pos, kv_len]).astype(jnp.int32),
        qg, k, v, taken.astype(jnp.int8),
    )


# ---------------------------------------------------------------------------
# latent attention (MLA): ONE cached row a token serves every query head
# ---------------------------------------------------------------------------


# a head's rows a grid step of ``mla_prefill_kernel`` takes: the whole
# of a 512-row chunk, so that a head's key block is fetched once
MLA_BLOCK_Q = 512


def mla_prefill_kernel(
    q: jnp.ndarray,  # [C, H, Dk] a chunk's queries (nope | rope)
    k: jnp.ndarray,  # [H, T, Dk] decompressed keys by position, a head leading
    v: jnp.ndarray,  # [H, T, Dv] decompressed values
    taken: jnp.ndarray,  # [C, T] bool: the keys each query reads
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    kv_len: jnp.ndarray,  # scalar int32: keys past it are never read
    *,
    scale: float,
    block_q: int = MLA_BLOCK_Q,
    block_k: int = SELECTED_BLOCK_K,
) -> jnp.ndarray:
    """A prefill chunk's attention in MULTI-HEAD (decompressed) form
    under a per-query selection: :func:`selected_prefill_kernel`'s step
    (a flash forward whose mask is data, key blocks past the causal
    reach or ``kv_len`` neither fetched nor computed) with every head
    its own keys and values, of two widths (``Dk`` 192 = 128 + the 64
    rotated, ``Dv`` 128), ``mla_prefill`` in a device trace.  A grid
    step is one head's ``block_q`` rows — the whole of a 512-row chunk,
    so that a key block is fetched once a head — against ``block_k``
    keys.  ``k`` and ``v`` arrive a head leading, as the decompression
    writes them.  Returns ``[C, H, Dv]``."""
    c, n_heads, _ = q.shape
    t = k.shape[1]
    bq, bk = min(block_q, c), min(block_k, t)
    if c % bq or t % bk:
        raise ValueError(f"a chunk of {c} x {t} keys in blocks {bq} x {bk}")
    out = _selected_prefill_call(
        _rows_by_kv_head(q, bq, n_heads), k, v, taken, start_pos, kv_len,
        block_q=bq, block_k=bk, scale=scale, name="mla_prefill",
    )
    return _rows_by_position(out, c, n_heads, bq)


def _mla_decode_kernel(
    counts_ref,  # scalar prefetch [B]: rows of a lane that are real
    qc_ref,  # [1, H, Dc]: every head's absorbed query
    qpe_ref,  # [1, H, M]: every head's rotated query
    c_ref,  # [1, P, Dc]: a page of the lane's selected latents
    pe_ref,  # [1, P, M]: their rotated shared keys
    o_ref,  # [1, H, Dc]
    m_scr,
    l_scr,
    acc_scr,
    *,
    page: int,
    scale: float,
):
    b, j = pl.program_id(0), pl.program_id(1)
    count = counts_ref[b]

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    # a lane's pages past its rows were index-clamped: no copy, no work
    @pl.when(j * page < count)
    def _compute():
        c = c_ref[0]
        s_log = (
            lax.dot_general(
                qc_ref[0], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + lax.dot_general(
                qpe_ref[0], pe_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale
        # the latent is key and value at once
        _online_update(
            m_scr, l_scr, acc_scr, s_log, c,
            j * page + _iota_cols(page) < count,
        )

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def mla_sparse_decode_kernel(
    q_c: jnp.ndarray,  # [B, H, Dc] absorbed queries: q_nope W_uk
    q_pe: jnp.ndarray,  # [B, H, M] rotated queries
    c: jnp.ndarray,  # [B, K, Dc] the selected latents, gathered
    pe: jnp.ndarray,  # [B, K, M] their rotated shared keys
    counts: jnp.ndarray,  # [B] int32: rows of a lane that are real
    *,
    scale: float,
    page: int = 512,
) -> jnp.ndarray:
    """Decode attention in ABSORBED form over a lane's selected rows
    (``ops/paged_attention.latent_rows_decode_attention``),
    ``mla_sparse_decode`` in a device trace: a token's latent is the
    key of every head (with its one rotated shared key) and the value
    of every head, so a page of rows is fetched ONCE for 128 heads — ``2
    * H * (576 + 512)`` operations a row against ``2 * 576`` bytes, 242
    a byte at 128 heads: on the v5e's ridge.  Rows past ``counts`` are
    masked, and a lane's pages past them are neither fetched nor
    computed.  Returns ``[B, H, Dc]`` (the summed latents: the caller
    applies ``W_uv``)."""
    batch, n_heads, dc = q_c.shape
    n_sel, lanes = c.shape[1], pe.shape[-1]
    page = int(np.gcd(n_sel, page))

    def page_index(b, j, counts):
        last = jnp.maximum(lax.div(counts[b] + page - 1, page) - 1, 0)
        return (b, jnp.minimum(j, last), 0)

    def lane_index(b, j, counts):
        del j, counts
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, n_sel // page),
        in_specs=[
            pl.BlockSpec((1, n_heads, dc), lane_index),
            pl.BlockSpec((1, n_heads, lanes), lane_index),
            pl.BlockSpec((1, page, dc), page_index),
            pl.BlockSpec((1, page, lanes), page_index),
        ],
        out_specs=pl.BlockSpec((1, n_heads, dc), lane_index),
        scratch_shapes=[
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, dc), jnp.float32),
        ],
    )
    return named_kernel(
        "mla_sparse_decode",
        pl.pallas_call(
            functools.partial(_mla_decode_kernel, page=page, scale=scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((batch, n_heads, dc), q_c.dtype),
            interpret=use_interpret(),
            name="mla_sparse_decode",
        ),
    )(counts.astype(jnp.int32), q_c, q_pe, c, pe)


def _mla_stream_kernel(
    tables_ref,  # scalar prefetch [B, MB]: block ids in the leaves
    lens_ref,  # scalar prefetch [B]: positions of a lane that count
    qc_ref,  # [1, H, Dc]: every head's absorbed query
    qpe_ref,  # [1, H, M]: every head's rotated query, under each token's lanes
    picked_ref,  # [1, G, span * bs] int32: the selection, a group a row (one
    # row of 32-bit words is read at a dynamic index; of int8 it is not)
    c_hbm,  # [N, bs, Dc]: the latents' leaf, where it lies
    pe_hbm,  # [N, bs * Dr / M, M]: the rotated keys' leaf, M / Dr tokens a row
    o_ref,  # [1, H, Dc]
    c_buf,  # [2, span, bs, Dc]: two slots, a group of blocks each
    pe_buf,  # [2, span, bs * Dr / M, M]
    sems,  # DMA [2, 2]: (leaf, slot)
    done,  # SMEM [1]: groups computed by the lanes before this one
    m_scr,
    l_scr,
    acc_scr,
    *,
    span: int,
    block_size: int,
    slab: int,
    scale: float,
):
    """One lane a grid step; the kernel fetches the blocks the lane
    HOLDS from the two leaves itself (:func:`_stream_lane_blocks`:
    groups of ``span`` table entries, two slots a leaf, the next group
    — or the next lane's first — in flight) and attends in absorbed
    form over the positions its selection marks.  A position that is
    not picked has exactly zero weight; one past the length is zeroed
    before it is multiplied (the tail of the last held block, and the
    stale rows of a group the lane holds in part, are garbage).

    The rotated keys lie ``M / Dr`` tokens a row.  A 0/1 product a
    ``slab`` of positions lays each token's row under the token
    (``[slab, slab / per_row] x [slab / per_row, M]``: exact, a
    position's row is one row times 1), the other tokens' lanes are
    zeroed, and the query — laid under every token's lanes by the
    caller — meets it in one product of ``M``.

    Compiled under :data:`STREAM_PARAMS`, so NO dynamic address of the
    kernel is checked, and each is in range by construction: the
    scaffold clamps a table entry into its leaf and fills ``buf[slot,
    s]`` at ``slot`` 0 or 1 and ``s < span``, the slots' own sizes;
    ``c_buf[slot]`` / ``pe_buf[slot]`` are read at that ``slot``;
    ``picked_ref[0, i]`` at a group ``i`` the lane holds, below the
    ``ceil(MB / span)`` rows the caller pads the selection to (a lane
    holds at most ``MB`` entries); ``tables_ref`` / ``lens_ref`` at a
    lane of the grid and an entry below ``held(lane) <= MB``."""
    b = pl.program_id(0)
    max_blocks = tables_ref.shape[1]
    pe_rows, width = pe_hbm.shape[1:]  # rows a block, lanes a row
    per_row = block_size // pe_rows  # tokens a row
    dr = width // per_row
    seq_len = lens_ref[b]

    def held(lane):  # blocks a lane's table really holds
        blocks = lax.div(lens_ref[lane] + block_size - 1, block_size)
        return jnp.minimum(blocks, max_blocks)

    _init_state(m_scr, l_scr, acc_scr)
    n_pos = span * block_size  # positions a group
    pos_col = _iota_cols(n_pos)  # [1, P]
    pos_row = _iota_rows(n_pos)  # [P, 1]
    # a row of rotated keys: the position of each of its lanes' token
    pe_pos = per_row * _iota_rows(span * pe_rows) + lax.div(
        _iota_cols(width), dr
    )  # [P / per_row, M]
    # the 0/1 product of a slab, and the lanes that are a position's own
    lay = jnp.where(
        lax.div(_iota_rows(slab), per_row) == _iota_cols(slab // per_row),
        1.0, 0.0,
    )
    own = lax.rem(pos_row, per_row) == lax.div(_iota_cols(width), dr)

    def attend(i, slot):
        at = i * n_pos  # the group's first position
        # Zero what lies past the length: 0 * NaN would poison the
        # accumulator, and the 0/1 product a whole slab.
        c = c_buf[slot].reshape(n_pos, -1)
        c = jnp.where(at + pos_row < seq_len, c, jnp.zeros_like(c))
        pe = pe_buf[slot].reshape(span * pe_rows, width)
        pe = jnp.where(at + pe_pos < seq_len, pe, jnp.zeros_like(pe))
        rows = slab // per_row
        pe = jnp.concatenate([
            lax.dot_general(
                lay.astype(pe.dtype), pe[n * rows:(n + 1) * rows],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for n in range(n_pos // slab)
        ], axis=0)  # [P, M]: a position's row of keys
        pe = jnp.where(own, pe, 0.0).astype(c.dtype)
        s_log = (
            lax.dot_general(
                qc_ref[0], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + lax.dot_general(
                qpe_ref[0], pe, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * scale
        keep = (picked_ref[0, pl.ds(i, 1), :] != 0) & (at + pos_col < seq_len)
        # the latent is key and value at once
        _online_update(m_scr, l_scr, acc_scr, s_log, c, keep)

    _stream_lane_blocks(
        tables_ref, held, ((c_hbm, c_buf), (pe_hbm, pe_buf)), sems, done,
        span, attend,
    )
    _finalize(o_ref, m_scr, l_scr, acc_scr)


def mla_stream_decode_kernel(
    q_c: jnp.ndarray,  # [B, H, Dc] absorbed queries: q_nope W_uk
    q_pe: jnp.ndarray,  # [B, H, Dr] rotated queries
    c_leaf: jnp.ndarray,  # [N, bs, Dc] the latents' leaf, whole
    pe_leaf: jnp.ndarray,  # [N, bs * Dr / M, M] the rotated keys' leaf, whole
    block_tables: jnp.ndarray,  # [B, MB] int32 block ids IN the leaves
    seq_lens: jnp.ndarray,  # [B] int32: positions of a lane that count
    taken: jnp.ndarray,  # [B, MB * bs] bool: the positions a lane picked
    *,
    scale: float,
) -> jnp.ndarray:
    """Decode attention in ABSORBED form over the positions each lane
    PICKED of the blocks it holds, ``mla_sparse_decode`` in a device
    trace as the kernel over gathered rows is
    (:func:`mla_sparse_decode_kernel`): the two leaves go in whole and
    in place, a grid step is a lane, and the kernel copies the lane's
    blocks itself (:func:`_mla_stream_kernel`) — every held row is read
    and scored, the selection is a mask, and no row is gathered.  A lane
    of length 0 reads nothing and returns exact zeros.  Returns ``[B,
    H, Dc]`` (the summed latents: the caller applies ``W_uv``).

    The blocks a group follow from the shapes: a group's float32 logits
    stay under 2 MiB of the fast memory and the two slots of a leaf
    under 1 MiB, in whole slabs of 128 positions (32 blocks at
    DeepSeek-V3.2's widths; bare on a v5e 16 read 26 % slower at 4 k
    held positions, 56 within 2 % at 4-8 k and 6 % slower at 2 k:
    ``PERF.md`` section 6, PR 54)."""
    batch, n_heads, dc = q_c.shape
    _, block_size, _ = c_leaf.shape
    _, pe_rows, width = pe_leaf.shape
    max_blocks = block_tables.shape[1]
    per_row = block_size // pe_rows
    rows_p = _round_up(n_heads, sublane_tile(q_c.dtype))
    span = max(1, min(
        max_blocks,
        (2 << 20) // (rows_p * block_size * 4),
        (1 << 20) // (2 * block_size * dc * c_leaf.dtype.itemsize),
    ))
    per_slab = max(1, 128 // block_size)
    if span > per_slab:
        span -= span % per_slab
    n_pos = span * block_size
    slab = int(np.gcd(n_pos, 128))
    if slab % per_row:
        raise ValueError(
            f"{per_row} tokens a row of keys in slabs of {slab} positions"
        )
    n_groups = -(-max_blocks // span)
    picked = taken.astype(jnp.int32)
    if n_groups * n_pos > picked.shape[1]:
        picked = jnp.pad(
            picked, ((0, 0), (0, n_groups * n_pos - picked.shape[1]))
        )
    picked = picked.reshape(batch, n_groups, n_pos)
    if rows_p > n_heads:
        pad = ((0, 0), (0, rows_p - n_heads), (0, 0))
        q_c, q_pe = jnp.pad(q_c, pad), jnp.pad(q_pe, pad)

    def lane_index(b, tables, lens):
        del tables, lens
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, rows_p, dc), lane_index),
            pl.BlockSpec((1, rows_p, width), lane_index),
            pl.BlockSpec((1, n_groups, n_pos), lane_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows_p, dc), lane_index),
        scratch_shapes=[
            pltpu.VMEM((2, span, block_size, dc), c_leaf.dtype),
            pltpu.VMEM((2, span, pe_rows, width), pe_leaf.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, 128), jnp.float32),
            pltpu.VMEM((rows_p, dc), jnp.float32),
        ],
    )
    out = named_kernel(
        "mla_sparse_decode",
        pl.pallas_call(
            functools.partial(
                _mla_stream_kernel, span=span, block_size=block_size,
                slab=slab, scale=scale,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((batch, rows_p, dc), q_c.dtype),
            interpret=use_interpret(),
            name="mla_sparse_decode",
            compiler_params=pltpu.CompilerParams(**STREAM_PARAMS),
        ),
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        q_c, jnp.tile(q_pe, (1, 1, per_row)), picked, c_leaf, pe_leaf,
    )
    return out[:, :n_heads]


#: copies a turn of :func:`_stream_lane_blocks`' issue loop, and the
#: compiler parameters of a kernel built on it: the lanes run in order
#: (the slot parity and a lane's first group carry over a grid step),
#: and every address is in range by construction (a table entry is
#: clamped, a slot's index is below ``span``)
STREAM_UNROLL = 16
STREAM_PARAMS = dict(
    dimension_semantics=("arbitrary",), disable_bounds_checks=True
)


def _stream_lane_blocks(tables_ref, held, leaves, sems, done, span, body):
    """The copy-and-wait scaffold of a kernel that reads a lane's OWN
    blocks from leaves that go in whole (``memory_space=pl.ANY``), one
    lane a grid step (``dimension_semantics`` "arbitrary": the slot
    parity and a lane's first group carry over a step).  ``leaves``:
    ``(hbm, buf)`` a leaf to copy, ``hbm`` ``[N, ...]`` blocks and
    ``buf`` ``[2, span, ...]`` two slots of ``span`` blocks; ``sems``
    DMA ``[len(leaves), 2]``; ``done`` SMEM ``[1]``; ``held(lane)`` the
    table entries a lane really holds.  Groups of ``span`` entries: the
    next group — or the next lane's first — is in flight while
    ``body(i, slot)`` runs on group ``i``, whose held blocks lie in
    ``buf[slot, :n]`` of every leaf (the rest of the slot is stale).
    Entries past the lane's last block are neither read from the table
    nor fetched; an entry is clamped into its leaf, as a gather clamps,
    so a kernel built on this may drop the compiler's own bounds checks
    (:data:`STREAM_PARAMS`: they are 13 of the 21 bundles a copy costs
    the scalar core, ``PERF.md`` section 6, PR 58).
    (:func:`_index_decode_kernel` and :func:`_mla_stream_kernel` run on
    it; :func:`_stream_decode_kernel` carries this scaffold by hand
    still: ``ROADMAP.md`` Queue 3 item 17 moves it here.)"""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)

    def groups(lane):
        return lax.div(held(lane) + span - 1, span)

    def copies(lane, i, slot, arrive):
        """Start, or wait for, a copy a leaf of every block ``lane``
        holds of its group ``i`` — a loop of as many turns; a whole
        group is waited for at once (a slot's semaphore counts what
        arrived, and ``span`` blocks are the slot's size)."""
        n_blocks = jnp.minimum(held(lane) - i * span, span)

        def block(s, carry):
            at = tables_ref[lane, i * span + s]
            for leaf, (hbm, buf) in enumerate(leaves):
                copy = pltpu.make_async_copy(
                    hbm.at[jnp.clip(at, 0, hbm.shape[0] - 1)],
                    buf.at[slot, s], sems.at[leaf, slot],
                )
                copy.wait() if arrive else copy.start()
            return carry

        if not arrive:
            # some copies a turn: the scalar core issues them, and a
            # turn's branch is a fifth of what a copy costs alone
            unroll = min(STREAM_UNROLL, span)
            whole = lax.div(n_blocks, unroll)

            def turn(t, carry):
                for u in range(unroll):
                    block(t * unroll + u, carry)
                return carry

            lax.fori_loop(0, whole, turn, 0)
            lax.fori_loop(whole * unroll, n_blocks, block, 0)
            return

        @pl.when(n_blocks == span)
        def _whole():
            for leaf, (_, buf) in enumerate(leaves):
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sems.at[leaf, slot]
                ).wait()

        @pl.when(n_blocks < span)
        def _tail():
            lax.fori_loop(0, n_blocks, block, 0)

    @pl.when(b == 0)
    def _first_lane():
        done[0] = 0

    n_groups = groups(b)
    base = done[0]
    before = jnp.maximum(b - 1, 0)
    after = jnp.minimum(b + 1, lanes - 1)
    after_reads = (b + 1 < lanes) & (groups(after) > 0)

    # the lane before starts this lane's first group, if it ran at all
    @pl.when((n_groups > 0) & ((b == 0) | (groups(before) == 0)))
    def _own_first_group():
        copies(b, 0, lax.rem(base, 2), False)

    def group(i, carry):
        slot = lax.rem(base + i, 2)

        @pl.when(i + 1 < n_groups)
        def _next_group():
            copies(b, i + 1, 1 - slot, False)

        @pl.when((i + 1 == n_groups) & after_reads)
        def _next_lane():
            copies(after, 0, 1 - slot, False)

        copies(b, i, slot, True)
        body(i, slot)
        return carry

    lax.fori_loop(0, n_groups, group, 0)
    done[0] = base + n_groups


def _index_decode_kernel(
    tables_ref,  # scalar prefetch [B, MB]: block ids in the leaf
    lens_ref,  # scalar prefetch [B]: positions of a lane that count
    q_ref,  # [1, per_row * H, M]: the index queries, under each token's lanes
    w_ref,  # [1, per_row * H, 1] float32: the heads' weights, as often
    ik_hbm,  # [N, bs * Di / M, M]: the index keys' leaf, M / Di tokens a row
    o_ref,  # [1, groups * per_row, R] float32: a group's rows' scores a row
    ik_buf,  # [2, span, bs * Di / M, M]: two slots, a group of blocks each
    sems,  # DMA [1, 2]
    done,  # SMEM [1]: groups computed by the lanes before this one
    *,
    span: int,
    block_size: int,
    n_heads: int,
):
    """One lane a grid step: the index keys of the blocks the lane
    HOLDS are copied from the leaf where it lies
    (:func:`_stream_lane_blocks`) and meet the lane's index queries a
    group at a time, ``sum_h w[h] * relu(qi[h] . ik[s])`` in float32.
    The leaf lies in rows of ``M`` lanes, ``per_row = M / Di`` tokens a
    row; the caller laid the queries under each token's lanes (rows
    ``j * H ..`` of ``q_ref`` hold them under token ``j``'s, zeros
    elsewhere), so ONE product scores the ``per_row`` tokens of every
    row, and row ``i * per_row + j`` of the result holds token ``j`` of
    group ``i``'s rows.  A position past the length scores ``-inf``;
    where a row holds several tokens its keys are zeroed first (``0 *
    NaN`` of an unwritten neighbour would reach a position that
    counts); a group the lane does not hold is never visited and stays
    at the ``-inf`` the result starts from."""
    b = pl.program_id(0)
    max_blocks = tables_ref.shape[1]
    rows, width = ik_hbm.shape[1:]  # rows a block, lanes a row
    per_row = block_size // rows  # tokens a row
    n_rows = span * rows  # rows a group
    seq_len = lens_ref[b]

    def held(lane):  # blocks a lane's table really holds
        blocks = lax.div(lens_ref[lane] + block_size - 1, block_size)
        return jnp.minimum(blocks, max_blocks)

    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)
    q = q_ref[0]
    w = w_ref[0]  # [per_row * H, 1]
    row_col = _iota_cols(n_rows)  # [1, R]
    if per_row > 1:  # a row of keys: the position of each lane's token
        key_pos = per_row * _iota_rows(n_rows) + lax.div(
            _iota_cols(width), width // per_row
        )  # [R, M]

    def scores(i, slot):
        at = i * n_rows  # the group's first row
        keys = ik_buf[slot].reshape(n_rows, width)
        if per_row > 1:
            keys = jnp.where(
                at * per_row + key_pos < seq_len, keys, jnp.zeros_like(keys)
            )
        s = lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [per_row * H, R]
        s = jnp.maximum(s, 0.0) * w
        for j in range(per_row):  # a row's tokens, not the heads
            score = jnp.sum(
                s[j * n_heads:(j + 1) * n_heads], axis=0, keepdims=True
            )
            pos = (at + row_col) * per_row + j
            o_ref[0, pl.ds(i * per_row + j, 1), :] = jnp.where(
                pos < seq_len, score, -jnp.inf
            )

    _stream_lane_blocks(
        tables_ref, held, ((ik_hbm, ik_buf),), sems, done, span, scores
    )


def index_decode_span(qi, ik_leaf, max_blocks: int) -> int:
    """Table entries a group of :func:`index_decode_scores_kernel` for
    queries ``[B, Hi, Di]`` over a leaf ``[N, rows, M]``: the two slots
    of a group's blocks under 1 MiB and its float32 scores (a row a
    head and token of a leaf's row) under 1 MiB, a power of two."""
    _, rows, width = ik_leaf.shape
    score_rows = _round_up(qi.shape[1], 8) * (width // qi.shape[2])
    span = min(
        (1 << 20) // (2 * rows * width * ik_leaf.dtype.itemsize),
        (1 << 20) // (score_rows * rows * 4),
    )
    return max(1, min(1 << (max(span, 1).bit_length() - 1), max_blocks))


def index_decode_scores_kernel(
    qi: jnp.ndarray,  # [B, Hi, Di] one index query per lane and head
    w: jnp.ndarray,  # [B, Hi] float32 head weights
    ik_leaf: jnp.ndarray,  # [N, bs * Di / M, M] the index keys' leaf, whole
    block_tables: jnp.ndarray,  # [B, MB] int32 block ids IN the leaf
    seq_lens: jnp.ndarray,  # [B] int32: positions of a lane that count
    *,
    span: Optional[int] = None,
) -> jnp.ndarray:
    """The decode step's index scores, ``I[b, s] = sum_h w[b, h] *
    relu(qi[b, h] . ik[s])`` in float32 ``[B, MB * bs]``, ``-inf`` at
    ``s >= seq_lens[b]``, ``index_decode_scores`` in a device trace:
    what ``ops/paged_attention.decode_index_scores`` computes from
    gathered keys, read from the leaf in place — the leaf goes in whole,
    a grid step is a lane and the kernel copies the blocks the lane
    holds itself (:func:`_index_decode_kernel`); no key is gathered, no
    ``[B, Hi, T]`` scores are written.  The leaf lies in rows of ``M``
    lanes (``paged_leaf_rows()``: 128; a token a row at DeepSeek-V3.2's
    128-wide keys, two a row at Keye-VL-2.0's 64).  A lane of length 0
    reads nothing and scores ``-inf`` everywhere."""
    batch, n_heads, di = qi.shape
    _, rows, width = ik_leaf.shape
    max_blocks = block_tables.shape[1]
    if width % di:
        raise ValueError(f"index keys of {di} in rows of {width}")
    per_row = width // di
    block_size = rows * per_row
    heads_p = _round_up(n_heads, 8)
    span = span or index_decode_span(qi, ik_leaf, max_blocks)
    n_groups = -(-max_blocks // span)
    n_rows = span * rows
    pad = ((0, 0), (0, heads_p - n_heads))
    qi = jnp.pad(qi.astype(ik_leaf.dtype), pad + ((0, 0),))
    w = jnp.pad(w.astype(jnp.float32), pad)
    # rows j * H .. of the queries lie under token j's lanes of a row
    q_lay = jnp.concatenate([
        jnp.pad(qi, ((0, 0), (0, 0), (j * di, width - (j + 1) * di)))
        for j in range(per_row)
    ], axis=1)

    def lane_index(b, tables, lens):
        del tables, lens
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[
            pl.BlockSpec((1, per_row * heads_p, width), lane_index),
            pl.BlockSpec((1, per_row * heads_p, 1), lane_index),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_groups * per_row, n_rows), lane_index),
        scratch_shapes=[
            pltpu.VMEM((2, span, rows, width), ik_leaf.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = named_kernel(
        "index_decode_scores",
        pl.pallas_call(
            functools.partial(
                _index_decode_kernel, span=span, block_size=block_size,
                n_heads=heads_p,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (batch, n_groups * per_row, n_rows), jnp.float32
            ),
            interpret=use_interpret(),
            name="index_decode_scores",
            compiler_params=pltpu.CompilerParams(**STREAM_PARAMS),
        ),
    )(
        block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
        q_lay, jnp.tile(w, (1, per_row))[..., None], ik_leaf,
    )
    # [B, group, token of a row, row of the group] -> by position
    out = out.reshape(batch, n_groups, per_row, n_rows)
    out = jnp.swapaxes(out, 2, 3).reshape(batch, -1)
    return out[:, :max_blocks * block_size]


# keys a grid step of ``chunk_prefill_kernel`` reads; whoever lays out
# its keys (``models/trinity._key_view_blocks``) rounds to this
CHUNK_KEY_BLOCK = 1024


def _chunk_reach(p_min, key0, block_q, block_k, window, xp=jnp):
    """Of the key blocks of ``block_k`` positions from ``key0``, what a
    query block whose first row is at ``p_min`` reads: ``(first, last,
    whole_first, whole_last)``.  SOME row reads blocks ``first..last``
    (up to the last row's own position and, with a window, from the
    first row's edge); EVERY row reads blocks ``whole_first..whole_last``
    whole (they end at or before the first row's position and start
    inside the last row's window), so the mask cuts nothing there.  The
    kernel's predicates, its index maps and :func:`chunk_key_blocks`
    all come from here."""
    last = (p_min + block_q - 1 - key0) // block_k
    whole_last = (p_min + 1 - key0) // block_k - 1
    if window is None:
        return 0, last, 0, whole_last
    first = xp.maximum(p_min - window + 1 - key0, 0) // block_k
    edge = xp.maximum(p_min + block_q - window - key0, 0)
    return first, last, (edge + block_k - 1) // block_k, whole_last


def chunk_key_blocks(
    start_pos: int,
    key0: int,
    c: int,
    t: int,
    window: Optional[int] = None,
    block_q: int = 512,
    block_k: int = CHUNK_KEY_BLOCK,
):
    """``(computed, unmasked, skipped)``: of the (query block, key
    block) steps ``chunk_prefill_kernel`` runs a KV head for a chunk of
    ``c`` rows at ``start_pos`` over ``t`` keys from ``key0``, how many
    it computes, how many of those without a mask, and how many it
    skips.  Positions alone decide it, so this is a count, not a
    measurement."""
    bq, bk = min(block_q, c), min(block_k, t)
    nk = t // bk
    p_min = start_pos + bq * np.arange(c // bq)
    first, last, whole_first, whole_last = (
        np.broadcast_to(x, p_min.shape)
        for x in _chunk_reach(p_min, key0, bq, bk, window, xp=np)
    )
    last, whole_last = np.minimum(last, nk - 1), np.minimum(whole_last, nk - 1)
    computed = int(np.maximum(last - first + 1, 0).sum())
    unmasked = int(np.maximum(whole_last - whole_first + 1, 0).sum())
    return computed, unmasked, len(p_min) * nk - computed


def _chunk_prefill_kernel(
    bounds_ref,  # scalar prefetch [2]: the chunk's first position, key 0's
    q_ref,  # [1, G * BQ, D]: a KV head's query heads, BQ rows each
    k_ref,  # [1, BK, D]
    v_ref,
    o_ref,  # [1, G * BQ, D]
    m_scr,
    l_scr,
    acc_scr,
    *,
    block_q: int,
    block_k: int,
    window: Optional[int],
    scale: float,
):
    i, j = pl.program_id(1), pl.program_id(2)
    start, key0 = bounds_ref[0], bounds_ref[1]

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    p_min = start + i * block_q
    first, last, whole_first, whole_last = _chunk_reach(
        p_min, key0, block_q, block_k, window
    )
    whole = (j >= whole_first) & (j <= whole_last)

    @pl.when(whole)
    def _unmasked():
        _online_update(
            m_scr, l_scr, acc_scr, _logits(q_ref, k_ref, scale), v_ref[0]
        )

    @pl.when((j >= first) & (j <= last) & jnp.logical_not(whole))
    def _masked():
        shape = (q_ref.shape[1], block_k)
        q_pos = p_min + lax.rem(
            lax.broadcasted_iota(jnp.int32, shape, 0), block_q
        )
        k_pos = key0 + j * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
        keep = k_pos <= q_pos
        if window is not None:
            keep = keep & (k_pos > q_pos - window)
        _online_update(
            m_scr, l_scr, acc_scr, _logits(q_ref, k_ref, scale), v_ref[0],
            keep,
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def chunk_prefill_kernel(
    q: jnp.ndarray,  # [C, H, D] a chunk's queries
    k: jnp.ndarray,  # [KV, T, D] one sequence's keys: row r is position
    v: jnp.ndarray,  # ``key0 + r``
    start_pos: jnp.ndarray,  # scalar int32: the chunk's first position
    key0: jnp.ndarray,  # scalar int32: the position of row 0 (<= start_pos)
    *,
    window: Optional[int] = None,
    name: str = "paged_prefill",
    block_q: int = 512,
    block_k: int = CHUNK_KEY_BLOCK,
) -> jnp.ndarray:
    """Chunked-prefill GQA attention, causal and, with ``window``, over
    the keys ``t - window < s <= t`` only: a flash forward over one
    sequence's keys gathered BY POSITION from its pages
    (``ops/paged_attention.gather_heads_by_position``), the logits never
    leaving fast memory (48 heads x 2048 rows x 32 k keys of float32
    logits are 12.9 GB).  A grid step is one KV head's ``group`` query
    heads, ``block_q`` rows each, against ``block_k`` keys — a key block
    is fetched once for the six heads that share it — and the key blocks
    wholly above a query block's causal reach or wholly behind its
    window are neither fetched nor computed.  A computed block runs one
    of two bodies: the mask's iotas, compares and selects only where the
    diagonal or the window's edge cuts it, none where every row reads
    it whole (:func:`chunk_key_blocks` counts both).  Returns ``[C, H,
    D]``.  What a step pays a row group — two cross-lane reductions, a
    broadcast, the rescale — it pays once a KEY BLOCK: at 48 / 8 heads
    of 128 on a v5e a call over 1024-key blocks takes 23-38 % less than
    over 512, 2048 is slower again, and a step without the mask ~11 us
    where a masked one takes ~14 (PERF.md, PR 46)."""
    c, n_heads, d = q.shape
    n_kv, t, _ = k.shape
    group = n_heads // n_kv
    bq, bk = min(block_q, c), min(block_k, t)
    if c % bq or t % bk:
        raise ValueError(f"a chunk of {c} x {t} keys in blocks {bq} x {bk}")
    nq, rows = c // bq, group * bq

    def q_index(h, i, j, bounds):
        del j, bounds
        return (h * nq + i, 0, 0)

    def kv_index(h, i, j, bounds):
        first, last, _, _ = _chunk_reach(
            bounds[0] + i * bq, bounds[1], bq, bk, window
        )
        return (h, jnp.clip(j, first, jnp.minimum(last, t // bk - 1)), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_kv, nq, t // bk),
        in_specs=[
            pl.BlockSpec((1, rows, d), q_index),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, rows, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
    )
    out = named_kernel(
        name,
        pl.pallas_call(
            functools.partial(
                _chunk_prefill_kernel, block_q=bq, block_k=bk,
                window=window, scale=d**-0.5,
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_kv * nq, rows, d), q.dtype),
            interpret=use_interpret(),
            name=name,
            compiler_params=_chunk_step_params(rows, bk),
        ),
    )(
        jnp.stack([start_pos, key0]).astype(jnp.int32),
        _rows_by_kv_head(q, bq, n_kv),
        k,
        v,
    )
    return _rows_by_position(out, c, n_heads, bq)


# ---------------------------------------------------------------------------
# verify: K speculative query positions per lane share one prefix pass
# ---------------------------------------------------------------------------


def _verify_kernel(
    tables_ref,
    pos_ref,  # scalar prefetch [B] — position of each lane's first query
    q_ref,  # [1, R, D]
    *rest,
    span: int,
    block_size: int,
    n_kv: int,
    group: int,
    window: int,
    wp: int,
    scale: float,
):
    k_refs = rest[:span]
    v_refs = rest[span : 2 * span]
    o_ref = rest[2 * span]
    m_scr, l_scr, acc_scr = rest[2 * span + 1 :]
    del tables_ref

    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    pos = pos_ref[b]
    horizon = pos + window - 1  # last key any of the K queries may see

    @pl.when(j == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    @pl.when(j * span * block_size <= horizon)
    def _compute():
        # Row r of a head's WP-row block is query offset r // group
        # (rows r >= window*group are padding and fully masked).
        row_in_head, same_head, col_tok, v_tok = _page_geometry(
            q_ref.shape[1], wp, block_size, n_kv
        )
        q_pos = pos + lax.div(row_in_head, group)  # [R, 1]
        row_ok = same_head & (row_in_head < window * group)
        for s in range(span):
            start = (j * span + s) * block_size
            # A key is garbage unless visible to at least the last query.
            v_page = v_refs[s][0]
            v_page = jnp.where(
                start + v_tok <= horizon, v_page, jnp.zeros_like(v_page)
            )
            keep = row_ok & (start + col_tok <= q_pos)  # causal window
            _online_update(
                m_scr,
                l_scr,
                acc_scr,
                _logits(q_ref, k_refs[s], scale),
                v_page,
                keep,
            )

    @pl.when(j == nj - 1)
    def _done():
        _finalize(o_ref, m_scr, l_scr, acc_scr)


def paged_verify_kernel(
    q: jnp.ndarray,  # [B, C, H, D] — C = draft window (K steps)
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, MB] int32
    positions: jnp.ndarray,  # [B] int32 — position of q[:, 0]
    *,
    config: Optional[Dict[str, Any]] = None,
) -> jnp.ndarray:
    """Fused K-step speculative verify: one paged-prefix pass serves all
    K query positions of a lane, window mask applied in-kernel."""
    from dlrover_tpu.ops import autotune

    batch, window, n_heads, head_dim = q.shape
    _, block_size, n_kv, _ = k_pool.shape
    group = n_heads // n_kv
    max_blocks = block_tables.shape[1]
    rows = window * group
    if config is None:
        config = autotune.get_config(
            "verify",
            group=group,
            head_dim=head_dim,
            block_size=block_size,
            max_blocks=max_blocks,
            dtype=q.dtype,
            window=window,
        )
    span = max(1, min(int(config.get("kv_span", 1)), max_blocks))
    wp = max(int(config.get("q_rows", rows)), rows)

    # [B, C, KV, G, D] -> [B, KV, C*G, D]: a head's K windows are
    # contiguous rows, padded to wp per head.
    qg = q.reshape(batch, window, n_kv, group, head_dim)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(batch, n_kv, rows, head_dim)
    if wp > rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, wp - rows), (0, 0)))
    qg = qg.reshape(batch, n_kv * wp, head_dim)

    out = _paged_call(
        functools.partial(
            _verify_kernel,
            span=span,
            block_size=block_size,
            n_kv=n_kv,
            group=group,
            window=window,
            wp=wp,
            scale=head_dim**-0.5,
        ),
        qg,
        k_pool,
        v_pool,
        block_tables,
        positions,
        span=span,
        last_block=lambda pos, b: lax.div(
            pos[b] + window - 1 + block_size, block_size
        )
        - 1,
        name="paged_verify",
    )
    out = out.reshape(batch, n_kv, wp, head_dim)[:, :, :rows]
    out = out.reshape(batch, n_kv, window, group, head_dim)
    return out.transpose(0, 2, 1, 3, 4).reshape(
        batch, window, n_heads, head_dim
    )
