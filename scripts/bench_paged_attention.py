#!/usr/bin/env python
"""Micro-bench: paged-attention kernels, jnp reference vs Pallas.

Times the two decode-hot ops (single-token decode, K-step verify)
under both backends across a sweep of (batch, context, block_size)
points, on whatever backend is live — compiled Mosaic on TPU,
interpret mode on CPU CI (where the Pallas numbers are *informational*:
interpret mode measures correctness plumbing, not kernel speed; the
speedup bar applies on metal).

Output (``--out``): JSON with one record per sweep point carrying
``decode_us`` / ``verify_us`` per backend and the pallas/jnp speedup
ratios, flushed atomically **after every sweep point** so a budget
kill never loses completed measurements.  Honors
``DLROVER_TPU_BENCH_BUDGET_S`` (stops sweeping, never mid-point).

``--autotune`` additionally runs the shape-keyed tuner
(``ops/autotune.py``) on each sweep point's decode/verify shape before
timing, so the pallas numbers reflect the tuned config and the tuning
events land on the timeline (``kernel_autotune`` spans).

``--tables`` times the bare decode kernel instead, at the tables the
benchmark's serving cells run (``TABLES``: Trinity-Large's full and
window layers, deepseek-llm-7b's, Falcon-H1-34B's) with a quarter, a
half and all of every lane's table live, and prints microseconds a
call, microseconds a LIVE page and the GB/s of the K and V rows the
lanes hold — the record ``PERF.md`` quotes.  A time is a device time
only on a chip; in interpret mode the rows say nothing about speed.

``--chunk`` times the bare prefill-chunk kernel at Trinity-Large's two
geometries (``CHUNKS``: a full layer's 32 k keys at three positions, a
window layer's view with the window full) and prints milliseconds a
call, the share of the bf16 peak on the keys the mask admits, and
``chunk_key_blocks``' count of computed, unmasked and skipped steps.

``--selected`` times the bare selected-keys prefill kernel
(``sparse_prefill``) at Keye-VL-2.0's widths (``SELECTED_WIDTHS``: a
2048-row chunk that ends at 4096 / 8192 / 12288 / 16384 cached
positions, each row reading its top 2048 of random scores) and prints
milliseconds a call and microseconds a 262 144 logits of the key
blocks it computes.

Wired into ``bench.py`` as the ``extras.paged_kernels`` leg.
"""

import argparse
import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

BUDGET_ENV = "DLROVER_TPU_BENCH_BUDGET_S"

#: (batch, context, block_size) sweep — ≥3 context lengths
DEFAULT_SWEEP = (
    (4, 64, 8),
    (4, 128, 8),
    (8, 256, 8),
    (8, 256, 16),
)
VERIFY_WINDOW = 4

#: name -> (lanes, heads, kv_heads, table entries, a window layer's
#: ``first``): the decode tables of the benchmark's serving cells, 16
#: tokens a block and heads of 128 in bfloat16
TABLES = {
    "trinity_full": (16, 48, 8, 2048, False),
    "trinity_window": (16, 48, 8, 385, True),
    "deepseek7b": (16, 32, 32, 64, False),
    "falcon_h1": (32, 20, 4, 64, False),
}
LIVE_SHARES = (0.25, 0.5, 1.0)

#: kernel name -> (window, ring blocks or None for the full table's 32 k
#: keys, the chunk's first positions): a 2048-row chunk of Trinity-Large
#: (48 / 8 heads of 128, bfloat16, 16 tokens a block)
CHUNKS = {
    "paged_prefill_full": (None, None, (0, 8192, 24576)),
    "paged_prefill_window": (4096, 385, (8192,)),
}

#: cached positions a 2048-row chunk of Keye-VL-2.0 (32 / 4 heads of
#: 128, bfloat16, top 2048) ends at: the four static widths its prefill
#: program picks from at 16 384 positions a lane
SELECTED_WIDTHS = (4096, 8192, 12288, 16384)

#: held positions of DeepSeek-V3.2's decode row (32 lanes, 128 heads,
#: blocks of 16, a table of 512 entries, top 2048 of a 512-wide latent
#: and a 64-wide rotated key, bfloat16), and of the sweep that set
#: ``ops/paged_attention.LATENT_STREAM_WIDTH`` (the table as wide as
#: what a lane holds)
LATENT_HELD = (2048, 4096, 8192)
LATENT_SWEEP = (2048, 4096, 6144, 8192, 12288, 16384, 32768)


#: the decode step's index scores (``--index-decode``): lanes, index
#: heads, their width, table entries, top-k and the held positions
#: swept, at DeepSeek-V3.2's cell (prompts of median 2048 and half an
#: answer of median 3072: ~4 k held) and at Keye-VL-2.0's (3-15 k)
INDEX_DECODE = {
    "deepseek-v32": dict(lanes=32, heads=64, dim=128, entries=512,
                         topk=2048, held=(2048, 4096, 8192)),
    "keye-vl2": dict(lanes=16, heads=16, dim=64, entries=1024,
                     topk=2048, held=(4096, 9216, 15360)),
}


def _time_call(call, reps: int) -> float:
    """Best-of-reps wall microseconds for an already-warm callable."""
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        call()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def _chained_us(kernel, q, rest, reps: int) -> float:
    """Microseconds a call of ``kernel(q, *rest)``: ``reps`` calls
    chained inside ONE program (each call's queries depend on the one
    before), compiled outside the clock."""
    import jax
    from jax import lax

    def chained(q, *rest):
        def body(_, q):
            return q + (kernel(q, *rest) * 1e-3).astype(q.dtype)

        return lax.fori_loop(0, reps, body, q)

    fn = jax.jit(chained)
    fn(q, *rest).block_until_ready()
    return _time_call(lambda: fn(q, *rest).block_until_ready(), 3) / reps


def _make_point(batch, context, block_size, *, heads=4, kv_heads=2, head_dim=8,
                seed=0):
    """Concrete arrays for one sweep point: a pool with every lane's
    prefix at ``context`` tokens (plus one ragged short lane, the mixed
    batch the early-exit path exists for)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    max_blocks = -(-context // block_size)
    num_blocks = batch * max_blocks + 1  # + null block 0
    q = jnp.asarray(
        rng.standard_normal((batch, heads, head_dim)), jnp.float32
    )
    qv = jnp.asarray(
        rng.standard_normal((batch, VERIFY_WINDOW, heads, head_dim)),
        jnp.float32,
    )
    k_pool = jnp.asarray(
        rng.standard_normal((num_blocks, block_size, kv_heads, head_dim)),
        jnp.float32,
    )
    v_pool = jnp.asarray(
        rng.standard_normal((num_blocks, block_size, kv_heads, head_dim)),
        jnp.float32,
    )
    tables = jnp.asarray(
        1 + np.arange(batch * max_blocks).reshape(batch, max_blocks),
        jnp.int32,
    )
    seq_lens = np.full((batch,), context, np.int64)
    seq_lens[-1] = max(context // 4, 1)  # one short lane in the mix
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    positions = jnp.maximum(seq_lens - VERIFY_WINDOW, 0)
    return dict(
        q=q, qv=qv, k_pool=k_pool, v_pool=v_pool, tables=tables,
        seq_lens=seq_lens, positions=positions,
    )


def _bench_point(point, reps: int, autotune: bool):
    """Time decode + verify under both backends for one sweep point."""
    import jax

    from dlrover_tpu.ops import autotune as at
    from dlrover_tpu.ops import paged_attention as pa

    a = _make_point(*point)
    shape_kw = dict(
        group=a["q"].shape[1] // a["k_pool"].shape[2],
        head_dim=a["q"].shape[2],
        block_size=a["k_pool"].shape[1],
        max_blocks=a["tables"].shape[1],
        dtype=a["q"].dtype,
    )

    def decode_fn(backend, config=None):
        if backend == "pallas" and config is not None:
            from dlrover_tpu.ops.paged_kernels import paged_decode_kernel

            fn = jax.jit(functools.partial(paged_decode_kernel, config=config))
        else:
            fn = jax.jit(
                functools.partial(pa.paged_decode_attention, backend=backend)
            )

        def call():
            fn(
                a["q"], a["k_pool"], a["v_pool"], a["tables"], a["seq_lens"]
            ).block_until_ready()

        return call

    def verify_fn(backend, config=None):
        if backend == "pallas" and config is not None:
            from dlrover_tpu.ops.paged_kernels import paged_verify_kernel

            fn = jax.jit(functools.partial(paged_verify_kernel, config=config))
        else:
            fn = jax.jit(
                functools.partial(pa.paged_verify_attention, backend=backend)
            )

        def call():
            fn(
                a["qv"], a["k_pool"], a["v_pool"], a["tables"], a["positions"]
            ).block_until_ready()

        return call

    rec = {
        "batch": point[0],
        "context": point[1],
        "block_size": point[2],
        "verify_window": VERIFY_WINDOW,
    }
    if autotune:
        for kernel, make in (("decode", decode_fn), ("verify", verify_fn)):
            kw = dict(shape_kw)
            if kernel == "verify":
                kw["window"] = VERIFY_WINDOW
            best, report = at.tune_kernel(
                kernel,
                lambda cfg, make=make: make("pallas", cfg),
                at.candidates(kernel, **kw),
                key=at.shape_key(kernel, **kw),
                reps=reps,
            )
            rec[f"{kernel}_tuned_config"] = best
            rec[f"{kernel}_tuned_report"] = report
    for kernel, make in (("decode", decode_fn), ("verify", verify_fn)):
        for backend in ("jnp", "pallas"):
            call = make(backend)
            call()  # warmup: compile outside the clock
            rec[f"{kernel}_{backend}_us"] = round(_time_call(call, reps), 3)
        rec[f"{kernel}_speedup"] = round(
            rec[f"{kernel}_jnp_us"] / max(rec[f"{kernel}_pallas_us"], 1e-9), 4
        )
    return rec


def run_sweep(sweep=DEFAULT_SWEEP, reps: int = 5, autotune: bool = False,
              flush_fn=None, budget_s=None):
    """Bench every sweep point, calling ``flush_fn(payload)`` after each
    (the per-point flush tier-1 smoke-tests).  Stops early — between
    points, never mid-point — when the wall budget runs low."""
    import jax

    if budget_s is None:
        raw = os.getenv(BUDGET_ENV, "")
        budget_s = float(raw) if raw else None
    t0 = time.monotonic()
    payload = {
        "bench": "paged_attention",
        "backend": jax.default_backend(),
        "interpret": _interpret(),
        "points": [],
        "skipped_points": 0,
        "complete": False,
    }
    for i, point in enumerate(sweep):
        if budget_s is not None and (time.monotonic() - t0) > budget_s * 0.8:
            payload["skipped_points"] = len(sweep) - i
            break
        payload["points"].append(_bench_point(point, reps, autotune))
        if flush_fn is not None:
            flush_fn(payload)
    payload["complete"] = payload["skipped_points"] == 0
    payload["elapsed_s"] = round(time.monotonic() - t0, 3)
    if payload["points"]:
        payload["decode_speedup_best"] = max(
            p["decode_speedup"] for p in payload["points"]
        )
        payload["verify_speedup_best"] = max(
            p["verify_speedup"] for p in payload["points"]
        )
    if flush_fn is not None:
        flush_fn(payload)
    return payload


def _table_case(lanes, heads, kv_heads, entries, window, live, *,
                block_size=16, head_dim=128, dtype=None, seed=0):
    """A pool whose pages lie scattered (a shuffled free list), every
    lane holding ``live`` of its table and the null block behind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dtype = dtype or jnp.bfloat16
    rng = np.random.default_rng(seed)
    held = max(1, int(round(entries * live)))
    num_blocks = lanes * entries + 1
    tables = np.zeros((lanes, entries), np.int32)
    tables[:, :held] = 1 + rng.permutation(lanes * entries)[
        : lanes * held
    ].reshape(lanes, held)
    shape = (num_blocks, block_size, kv_heads, head_dim)
    pool = jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)
    return dict(
        q=jnp.asarray(
            rng.standard_normal((lanes, heads, head_dim), np.float32), dtype
        ),
        k_pool=pool, v_pool=pool[::-1],
        tables=jnp.asarray(tables),
        seq_lens=jnp.full((lanes,), held * block_size - 3, jnp.int32),
        # a window's edge: inside the first block, before the length
        first=jnp.full((lanes,), block_size - 4, jnp.int32)
        if window else None,
        held=held,
    )


def bench_tables(names=None, shares=LIVE_SHARES, spans=(None,), reps=20,
                 dims=None):
    """Rows of the bare decode kernel over ``TABLES``: ``reps`` calls
    chained inside ONE program (each call's queries depend on the last
    call's output), so a row is the kernel's time on the device and not
    a dispatch's.  ``spans``: pages a group to force (``None``: what
    ``ops/autotune.py`` resolves).  ``dims``: ``block_size`` /
    ``head_dim`` / ``dtype`` of a rehearsal."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import autotune
    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.paged_kernels import paged_decode_kernel

    rows = []
    for name in names or list(TABLES):
        lanes, heads, kv_heads, entries, window = TABLES[name]
        for live in shares:
            a = _table_case(
                lanes, heads, kv_heads, entries, window, live, **(dims or {})
            )
            block_size, _, head_dim = a["k_pool"].shape[1:]
            for span in spans:
                config = autotune.get_config(
                    "decode", group=heads // kv_heads, head_dim=head_dim,
                    block_size=block_size, max_blocks=entries,
                    dtype=a["q"].dtype,
                )
                if span is not None:
                    config = dict(config, kv_span=span)

                def kernel(q, k, v, tables, lens, first, config=config,
                           kernel_name="paged_window_decode" if window
                           else "paged_decode"):
                    return paged_decode_kernel(
                        q, k, v, tables, lens, config=config,
                        first=first, name=kernel_name,
                    )

                args = (a["q"], a["k_pool"], a["v_pool"], a["tables"],
                        a["seq_lens"], a["first"])
                us = _chained_us(kernel, args[0], args[1:], reps)
                # the same call against the dense reference, on the
                # device the row was timed on
                single = (a["q"], a["k_pool"], a["v_pool"], a["tables"],
                          a["seq_lens"])
                diff = jnp.max(jnp.abs(
                    paged_decode_kernel(
                        *single, config=config, first=a["first"]
                    ).astype(jnp.float32)
                    - pa.paged_decode_attention(
                        *single, backend="jnp", first=a["first"]
                    ).astype(jnp.float32)
                ))
                tokens = lanes * int(a["seq_lens"][0])
                row_bytes = 2 * kv_heads * head_dim * jnp.dtype(
                    a["q"].dtype
                ).itemsize
                rows.append({
                    "table": name, "entries": entries, "lanes": lanes,
                    "live_share": live, "live_pages": lanes * a["held"],
                    "kv_span": config["kv_span"],
                    "us_a_call": round(us, 2),
                    "us_a_live_page": round(us / (lanes * a["held"]), 4),
                    "gb_per_s": round(tokens * row_bytes / us / 1e3, 2),
                    "max_abs_diff_vs_jnp": float(diff),
                })
                print(json.dumps(rows[-1]), flush=True)
    return rows


def bench_chunk(names=None, reps=20, blocks=(None,), dims=None):
    """Rows of the bare chunk kernel over ``CHUNKS``, ``reps`` calls
    chained inside ONE program as :func:`bench_tables` does.
    ``blocks``: ``(block_q, block_k)`` pairs to force (``None``: the
    kernel's own).  ``dims``: ``rows`` / ``heads`` / ``kv_heads`` /
    ``head_dim`` / ``keys`` / ``dtype`` of a rehearsal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.trinity import _key_view_blocks
    from dlrover_tpu.observability.profiler import peak_flops_for_kind
    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.paged_kernels import (
        chunk_key_blocks, chunk_prefill_kernel,
    )

    dims = {
        **dict(rows=2048, heads=48, kv_heads=8, head_dim=128, keys=32768,
               block_size=16, dtype=jnp.bfloat16),
        **(dims or {}),
    }
    c, heads, n_kv, d = (
        dims[n] for n in ("rows", "heads", "kv_heads", "head_dim")
    )
    bs, dtype = dims["block_size"], dims["dtype"]
    try:  # a share of a peak is a chip's number: none off one
        peak = peak_flops_for_kind(jax.devices()[0].device_kind)
    except LookupError:
        peak = None
    out_rows = []
    for name in names or list(CHUNKS):
        window, ring, starts = CHUNKS[name]
        t = dims["keys"] if ring is None else _key_view_blocks(ring, bs) * bs
        k = jax.random.normal(jax.random.PRNGKey(1), (n_kv, t, d), dtype)
        v = k[:, ::-1]
        q = jax.random.normal(jax.random.PRNGKey(2), (c, heads, d), dtype)
        for start in starts:
            # a ring's view begins at the block of the window's edge
            key0 = 0 if ring is None else max(start - window + 1, 0) // bs * bs
            pos = start + np.arange(c)
            admitted = int(
                np.minimum(pos + 1, window or pos + 1).sum()
            )
            for pair in blocks:
                kw = {} if pair is None else dict(
                    block_q=pair[0], block_k=pair[1]
                )
                kernel = functools.partial(
                    chunk_prefill_kernel, window=window, name=name, **kw
                )

                args = (q, k, v, jnp.int32(start), jnp.int32(key0))
                ms = _chained_us(kernel, q, args[1:], reps) / 1e3
                # the chunk's first and last rows against the dense
                # form, on the device the row was timed on
                got = kernel(*args).astype(jnp.float32)
                n = min(128, c)
                diff = max(
                    float(jnp.max(jnp.abs(
                        got[lo:lo + n] - pa.paged_chunk_attention(
                            q[lo:lo + n], k, v, jnp.int32(start + lo),
                            jnp.int32(key0), window, "jnp",
                        ).astype(jnp.float32)
                    )))
                    for lo in (0, c - n)
                )
                computed, unmasked, skipped = chunk_key_blocks(
                    start, key0, c, t, window, **kw
                )
                out_rows.append({
                    "kernel": name, "start": start, "key0": key0, "keys": t,
                    "blocks": pair, "ms_a_call": round(ms, 4),
                    "peak_pct_admitted": peak and round(
                        100 * 4 * heads * d * admitted / (ms / 1e3) / peak, 2
                    ),
                    "steps_computed": computed, "steps_unmasked": unmasked,
                    "steps_skipped": skipped,
                    "max_abs_diff_vs_jnp": diff,
                })
                print(json.dumps(out_rows[-1]), flush=True)
    return out_rows


def bench_selected(widths=SELECTED_WIDTHS, reps=20, blocks=(None,), dims=None):
    """Rows of the bare selected-keys prefill kernel, ``reps`` calls
    chained inside ONE program as :func:`bench_chunk` does, the chunk
    the last ``rows`` positions of each width.  ``blocks`` and ``dims``
    (``rows`` / ``heads`` / ``kv_heads`` / ``head_dim`` / ``topk`` /
    ``dtype``) as there."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.paged_kernels import (
        SELECTED_BLOCK_K, SELECTED_BLOCK_Q, selected_prefill_kernel,
    )

    dims = {
        **dict(rows=2048, heads=32, kv_heads=4, head_dim=128, topk=2048,
               dtype=jnp.bfloat16),
        **(dims or {}),
    }
    c, heads, n_kv, d = (
        dims[n] for n in ("rows", "heads", "kv_heads", "head_dim")
    )
    out_rows = []
    for t in widths:
        start = t - c
        k = jax.random.normal(jax.random.PRNGKey(1), (t, n_kv, d), dims["dtype"])
        v = k[::-1]
        q = jax.random.normal(jax.random.PRNGKey(2), (c, heads, d), dims["dtype"])
        scores = jax.random.normal(jax.random.PRNGKey(3), (c, t))
        causal = jnp.arange(t)[None] <= start + jnp.arange(c)[:, None]
        taken = pa.exact_topk_mask(
            jnp.where(causal, scores, -jnp.inf), min(dims["topk"], t)
        )
        for pair in blocks:
            block_q, block_k = pair or (SELECTED_BLOCK_Q, SELECTED_BLOCK_K)
            kernel = functools.partial(
                selected_prefill_kernel, block_q=block_q, block_k=block_k
            )
            bq, bk = min(block_q, c), min(block_k, t)
            # the key blocks up to each query block's last row: the rest
            # the kernel neither fetches nor computes
            logits = heads * bq * bk * sum(
                (start + (i + 1) * bq - 1) // bk + 1 for i in range(c // bq)
            )

            args = (q, k, v, taken, jnp.int32(start), jnp.int32(t))
            ms = _chained_us(kernel, q, args[1:], reps) / 1e3
            # the chunk's first and last rows against the XLA form, on
            # the device the row was timed on
            got = kernel(*args).astype(jnp.float32)
            n = min(128, c)
            diff = max(
                float(jnp.max(jnp.abs(
                    got[lo:lo + n] - pa.selected_prefill_attention(
                        q[lo:lo + n], k, v, taken[lo:lo + n],
                        jnp.int32(start + lo), jnp.int32(t), backend="jnp",
                    ).astype(jnp.float32)
                )))
                for lo in (0, c - n)
            )
            out_rows.append({
                "kernel": "sparse_prefill", "keys": t, "start": start,
                "blocks": (bq, bk), "ms_a_call": round(ms, 4),
                "us_a_262144_logits": round(ms * 1e3 * 262144 / logits, 3),
                "max_abs_diff_vs_jnp": diff,
            })
            print(json.dumps(out_rows[-1]), flush=True)
    return out_rows


def _latent_case(lanes, heads, entries, held, dims, seed=0):
    """The two leaves with their blocks scattered, every lane holding
    ``held`` positions less 3 of a table of ``entries`` and picking its
    top ``topk`` of random scores."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import paged_attention as pa

    bs, dc, dr, width, topk, dtype = (
        dims[n] for n in ("block_size", "rank", "rope", "minor", "topk",
                          "dtype")
    )
    rng = np.random.default_rng(seed)
    blocks = held // bs
    tables = np.zeros((lanes, entries), np.int32)
    tables[:, :blocks] = 1 + rng.permutation(lanes * blocks).reshape(
        lanes, blocks
    )
    n = lanes * blocks + 1
    key = jax.random.PRNGKey(seed)
    lens = jnp.full((lanes,), held - 3, jnp.int32)
    scores = jnp.where(
        jnp.arange(entries * bs)[None] < lens[:, None],
        jax.random.normal(key, (lanes, entries * bs)), -jnp.inf,
    )
    n_sel = min(topk, entries * bs)
    tables = jnp.asarray(tables)
    rows, taken = pa.exact_topk_rows(scores, n_sel, tables, with_mask=True)
    return dict(
        q_c=jax.random.normal(key, (lanes, heads, dc), dtype),
        q_pe=jax.random.normal(key, (lanes, heads, dr), dtype),
        c=jax.random.normal(key, (n, bs, dc), dtype),
        pe=jax.random.normal(key, (n, bs * dr // width, width), dtype),
        tables=tables, lens=lens, scores=scores, rows=rows, taken=taken,
        n_sel=n_sel,
    )


def bench_latent(held=LATENT_HELD, entries=512, reps=20, dims=None):
    """Rows of DeepSeek-V3.2's bare decode attention, ``reps`` calls
    chained inside ONE program as :func:`bench_tables` does: the
    GATHERED fetch (two gathers of the picked rows, the ``where``, the
    kernel over the buffer: ``latent_rows_decode_attention``) against
    the STREAMED kernel (``mla_stream_decode_kernel``) on the same
    leaves, tables and selection, each with its largest difference from
    the jnp form.  ``entries``: the table's width (``None``: as wide as
    what a lane holds — the sweep that sets ``LATENT_STREAM_WIDTH``).
    ``dims``: a rehearsal's sizes."""
    import jax.numpy as jnp

    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.paged_kernels import mla_stream_decode_kernel

    dims = {
        **dict(lanes=32, heads=128, block_size=16, rank=512, rope=64,
               minor=128, topk=2048, dtype=jnp.bfloat16, scale=0.1352),
        **(dims or {}),
    }
    out_rows = []
    for positions in held:
        width = entries or positions // dims["block_size"]
        a = _latent_case(dims["lanes"], dims["heads"], width, positions, dims)
        scale, n_sel = dims["scale"], a["n_sel"]

        def gathered(q_c, q_pe, c, pe, rows, lens, backend="pallas"):
            # the rows hang on the queries (by a zero no compiler can
            # see): a chained call gathers anew, as a decode step does,
            # where a gather of the loop's constants would be made once
            rows = rows + (q_c[0, 0, 0] > 1e30).astype(rows.dtype)
            return pa.latent_rows_decode_attention(
                q_c, q_pe, c.reshape(-1, c.shape[-1]),
                pe.reshape(-1, pe.shape[-1]), rows,
                jnp.minimum(lens, n_sel), scale, backend,
            )

        rest = (a["q_pe"], a["c"], a["pe"], a["rows"], a["lens"])
        row = {
            "kernel": "mla_sparse_decode", "held": positions,
            "heads": dims["heads"],
            "table": width * dims["block_size"], "topk": n_sel,
            "gathered_us": round(
                _chained_us(gathered, a["q_c"], rest, reps), 1
            ),
        }
        ref = gathered(a["q_c"], *rest, backend="jnp").astype(jnp.float32)
        row["gathered_max_abs_diff_vs_jnp"] = float(jnp.max(jnp.abs(
            gathered(a["q_c"], *rest).astype(jnp.float32) - ref
        )))
        kernel = functools.partial(mla_stream_decode_kernel, scale=scale)
        rest = (a["q_pe"], a["c"], a["pe"], a["tables"], a["lens"],
                a["taken"])
        us = _chained_us(kernel, a["q_c"], rest, reps)
        got = kernel(a["q_c"], *rest).astype(jnp.float32)
        out_rows.append({
            **row, "streamed_us": round(us, 1),
            # the same sum in another order of rows
            "streamed_max_abs_diff_vs_gathered_jnp": float(
                jnp.max(jnp.abs(got - ref))
            ),
            # the streamed jnp form gathers a lane's whole table,
            # ``[lanes, T, rank]``: up to the cell's 8192 positions
            "streamed_max_abs_diff_vs_jnp": float(jnp.max(jnp.abs(
                got - pa.latent_decode_attention(
                    a["q_c"], a["q_pe"], a["c"], a["pe"], a["tables"],
                    a["lens"], pa.LatentSelection(a["taken"], None), scale,
                    backend="jnp",
                ).astype(jnp.float32)
            ))) if width * dims["block_size"] <= 8192 else None,
        })
        print(json.dumps(out_rows[-1]), flush=True)
    return out_rows


def bench_index_decode(names=None, spans=(None,), reps=20, block_size=16,
                       dims=None):
    """Rows of the decode step's bare index scores, ``reps`` calls
    chained inside ONE program: the GATHERED form (every entry of every
    lane's table gathered from the flat leaf, relaid to keys, ``[B, Hi,
    T]`` float32 scores, the heads' weighted sum: what a decode step
    ran before PR 58) against the STREAMED kernel
    (``index_decode_scores_kernel``) on the same keys in rows of 128
    lanes, every lane holding ``held`` positions less 3 of scattered
    blocks: each form's largest difference from the same sum in
    float32 at the highest precision over the largest score (``*_err``;
    on the chip the gathered form's second product rounds its float32
    operands to bfloat16), and in how many positions ``exact_topk_mask``
    picks otherwise from the streamed scores than from the gathered
    ones and from the exact ones.  ``spans``: table entries a group to force.  ``dims``: a
    rehearsal's cases in :data:`INDEX_DECODE`'s place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.paged_kernels import (
        index_decode_scores_kernel,
        index_decode_span,
    )

    def chained_us(form, qi, rest):
        # a call's queries hang on the scores of the one before
        def kernel(qi, *rest):
            out = form(qi, *rest)
            return jnp.max(jnp.where(jnp.isfinite(out), out, 0.0)).astype(
                qi.dtype
            )[None, None, None]

        return _chained_us(kernel, qi, rest, reps)

    out_rows = []
    for name, case in (dims or INDEX_DECODE).items():
        if names and name not in names:
            continue
        lanes, heads, dim, entries, topk = (
            case[n] for n in ("lanes", "heads", "dim", "entries", "topk")
        )
        minor = max(128, dim)
        for held in case["held"]:
            rng = np.random.default_rng(held)
            blocks = held // block_size
            tables = np.zeros((lanes, entries), np.int32)
            tables[:, :blocks] = 1 + rng.permutation(lanes * blocks).reshape(
                lanes, blocks
            )
            tables = jnp.asarray(tables)
            key = jax.random.PRNGKey(held)
            n = lanes * blocks + 1
            flat = jax.random.normal(
                key, (n, block_size * dim), jnp.bfloat16
            )
            rows = flat.reshape(n, -1, minor)
            qi = jax.random.normal(key, (lanes, heads, dim), jnp.bfloat16)
            w = jax.random.normal(key, (lanes, heads), jnp.float32)
            lens = jnp.full((lanes,), held - 3, jnp.int32)

            def gathered(qi, w, leaf, tables, lens):
                # the tables hang on the queries (by a zero no compiler
                # can see): a chained call gathers anew, as a decode
                # step does
                tables = tables + (qi[0, 0, 0] > 1e30).astype(tables.dtype)
                return pa.decode_index_scores(
                    qi, w, pa.gather_index_keys(leaf, tables, dim, "jnp"),
                    lens,
                )

            want = gathered(qi, w, flat, tables, lens)
            with jax.default_matmul_precision("highest"):
                exact = gathered(
                    qi.astype(jnp.float32), w, flat.astype(jnp.float32),
                    tables, lens,
                )
            finite = jnp.isfinite(want)
            size = jnp.max(jnp.where(finite, jnp.abs(exact), 0.0))

            def err(got):
                return float(jnp.max(
                    jnp.where(finite, jnp.abs(got - exact), 0.0)
                ) / size)

            row = {
                "kernel": "index_decode_scores", "case": name, "held": held,
                "table": entries * block_size,
                "gathered_us": round(
                    chained_us(gathered, qi, (w, flat, tables, lens)), 1
                ),
                "gathered_rows_leaf_us": round(
                    chained_us(gathered, qi, (w, rows, tables, lens)), 1
                ),
                "gathered_err": err(want),
            }
            n_sel = min(topk, entries * block_size)
            for span in spans:
                kernel = functools.partial(
                    index_decode_scores_kernel, span=span
                )
                got = kernel(qi, w, rows, tables, lens)
                out_rows.append({
                    **row,
                    "span": span or index_decode_span(qi, rows, entries),
                    "streamed_us": round(
                        chained_us(kernel, qi, (w, rows, tables, lens)), 1
                    ),
                    "same_finite": bool(
                        jnp.all(jnp.isfinite(got) == finite)
                    ),
                    "streamed_err": err(got),
                    "topk_differ": int(jnp.sum(
                        pa.exact_topk_mask(got, n_sel)
                        != pa.exact_topk_mask(want, n_sel)
                    )),
                    "topk_differ_from_exact": int(jnp.sum(
                        pa.exact_topk_mask(got, n_sel)
                        != pa.exact_topk_mask(exact, n_sel)
                    )),
                })
                print(json.dumps(out_rows[-1]), flush=True)
    return out_rows


def bench_selection(lanes=32, positions=8192, topk=2048, reps=20):
    """Microseconds a call of the three exact selections at a decode
    step's ``[lanes, positions]`` scores: the sort that carries each
    position's pool row (``exact_topk_rows`` with its mask), the sort
    that carries nothing and the counting search (``exact_topk_mask``)
    — and that the three pick the same."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dlrover_tpu.ops import paged_attention as pa

    scores = jax.random.normal(jax.random.PRNGKey(0), (lanes, positions))
    tables = jnp.arange(lanes * positions // 16, dtype=jnp.int32).reshape(
        lanes, -1
    )
    def sort_alone(s):  # the k-th value of a sort that carries nothing
        kth = -lax.sort(-s, dimension=1)[:, topk - 1:topk]
        room = topk - jnp.sum(s > kth, -1, keepdims=True)
        return (s > kth) | ((s == kth) & (jnp.cumsum(s == kth, -1) <= room))

    forms = {
        "sort_with_rows": lambda s: pa.exact_topk_rows(
            s, topk, tables, with_mask=True
        )[1],
        "sort_alone": sort_alone,
        "counting_search": lambda s: pa.exact_topk_mask(s, topk),
    }
    want = forms["sort_with_rows"](scores)
    row = {"selection": [lanes, positions], "topk": topk}
    for name, form in forms.items():
        row[f"{name}_us"] = round(_chained_us(
            lambda s, form=form: jnp.where(form(s), 1.0, 0.0), scores, (),
            reps,
        ), 1)
        row[f"{name}_same"] = bool(jnp.all(form(scores) == want))
    print(json.dumps(row), flush=True)
    return [row]


def _interpret() -> bool:
    from dlrover_tpu.ops.pallas_utils import use_interpret

    return use_interpret()


def _flush(out_file: str, payload) -> None:
    tmp = out_file + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, out_file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="bench_paged_attention.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="run the shape-keyed tuner per sweep point before timing",
    )
    ap.add_argument(
        "--tables", nargs="*", default=None, metavar="NAME",
        help="time the bare decode kernel at the serving cells' tables "
        f"({', '.join(TABLES)}; none named: all) instead of the sweep",
    )
    ap.add_argument(
        "--live", default=",".join(str(x) for x in LIVE_SHARES),
        help="shares of a lane's table that are live, comma-separated",
    )
    ap.add_argument(
        "--spans", default="",
        help="pages a group to force, comma-separated (default: autotune's)",
    )
    ap.add_argument(
        "--chunk", nargs="*", default=None, metavar="NAME",
        help="time the bare prefill-chunk kernel at Trinity-Large's "
        f"geometries ({', '.join(CHUNKS)}; none named: both)",
    )
    ap.add_argument(
        "--blocks", default="",
        help="with --chunk or --selected: block shapes to force, e.g. "
        "512x1024,256x1024 (default: the kernel's own)",
    )
    ap.add_argument(
        "--selected", action="store_true",
        help="time the bare selected-keys prefill kernel at Keye-VL-2.0's "
        f"widths ({', '.join(str(w) for w in SELECTED_WIDTHS)} keys)",
    )
    ap.add_argument(
        "--latent", action="store_true",
        help="time DeepSeek-V3.2's bare decode attention, the gathered "
        "fetch against the streamed kernel, at lanes holding "
        f"{', '.join(str(h) for h in LATENT_HELD)} positions of a "
        "512-entry table, and the three exact selections at [32, 8192]",
    )
    ap.add_argument(
        "--heads", type=int, default=128,
        help="with --latent or --latent-sweep: the heads a lane "
        "(DeepSeek-V3.2's 128; Kimi Linear's latent layers have 32)",
    )
    ap.add_argument(
        "--latent-sweep", action="store_true",
        help="the same two fetches with the table as wide as what a lane "
        f"holds ({', '.join(str(h) for h in LATENT_SWEEP)} positions): "
        "the crossover that set LATENT_STREAM_WIDTH",
    )
    ap.add_argument(
        "--index-decode", nargs="*", default=None, metavar="NAME",
        help="time the decode step's bare index scores, the gathered form "
        "against the streamed kernel, at the serving cells' shapes "
        f"({', '.join(INDEX_DECODE)}; none named: both); --spans forces "
        "the table entries a group",
    )
    args = ap.parse_args(argv)
    blocks = [
        tuple(int(n) for n in b.split("x"))
        for b in args.blocks.split(",") if b
    ] or (None,)

    if args.index_decode is not None:
        import jax

        rows = bench_index_decode(
            args.index_decode or None,
            spans=[int(x) for x in args.spans.split(",") if x] or (None,),
            reps=max(args.reps, 20),
        )
        _flush(args.out, {
            "bench": "index_decode_scores", "rows": rows,
            "backend": jax.default_backend(), "interpret": _interpret(),
            "device_kind": jax.devices()[0].device_kind,
        })
        print(f"wrote {args.out} ({len(rows)} rows)")
        return 0

    if args.latent or args.latent_sweep:
        import jax

        rows, dims = [], dict(heads=args.heads)
        if args.latent:
            rows += bench_latent(reps=max(args.reps, 20), dims=dims)
            rows += bench_selection(reps=max(args.reps, 20))
        if args.latent_sweep:
            rows += bench_latent(
                LATENT_SWEEP, entries=None, reps=max(args.reps, 20),
                dims=dims,
            )
        _flush(args.out, {
            "bench": "mla_sparse_decode", "rows": rows,
            "backend": jax.default_backend(), "interpret": _interpret(),
            "device_kind": jax.devices()[0].device_kind,
        })
        print(f"wrote {args.out} ({len(rows)} rows)")
        return 0

    if args.selected:
        import jax

        rows = bench_selected(reps=max(args.reps, 20), blocks=blocks)
        _flush(args.out, {
            "bench": "sparse_prefill", "rows": rows,
            "backend": jax.default_backend(), "interpret": _interpret(),
            "device_kind": jax.devices()[0].device_kind,
        })
        print(f"wrote {args.out} ({len(rows)} rows)")
        return 0

    if args.chunk is not None:
        import jax

        rows = bench_chunk(
            args.chunk or None, reps=max(args.reps, 20), blocks=blocks,
        )
        _flush(args.out, {
            "bench": "paged_prefill_chunk", "rows": rows,
            "backend": jax.default_backend(), "interpret": _interpret(),
            "device_kind": jax.devices()[0].device_kind,
        })
        print(f"wrote {args.out} ({len(rows)} rows)")
        return 0

    if args.tables is not None:
        import jax

        rows = bench_tables(
            args.tables or None,
            shares=[float(x) for x in args.live.split(",")],
            spans=[int(x) for x in args.spans.split(",") if x] or (None,),
        )
        _flush(args.out, {
            "bench": "paged_decode_tables", "rows": rows,
            "backend": jax.default_backend(), "interpret": _interpret(),
            "device_kind": jax.devices()[0].device_kind,
        })
        print(f"wrote {args.out} ({len(rows)} rows)")
        return 0

    payload = run_sweep(
        reps=args.reps,
        autotune=args.autotune,
        flush_fn=lambda p: _flush(args.out, p),
    )
    print(json.dumps({k: v for k, v in payload.items() if k != "points"}))
    print(f"wrote {args.out} ({len(payload['points'])} points)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
