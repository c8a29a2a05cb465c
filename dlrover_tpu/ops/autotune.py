"""Shape-keyed autotuner for the paged-attention Pallas kernels.

The kernels in ``ops/paged_kernels.py`` have two tunables per shape:
``q_rows`` (padded query rows per KV head — the q-block) and
``kv_span`` (pool pages a group — the kv-block: the pages the decode
kernel fetches into one buffer and scores with one matmul, ``ceil(held
blocks / kv_span)`` groups a lane; for verify the pages of a grid step,
whose KV extent is ``ceil(max_blocks / kv_span)``).  Which pair wins
depends on the device generation and the shape, so the choice is data,
not code:

- **Candidates** are derived from ``round_block_to_tile`` (PR 3's
  tile-legality helper), so every swept config is a legal Mosaic tile
  — the tuner never times a config that would fail to lower on TPU.
- **Timing** happens only when explicitly invoked (the
  ``scripts/bench_paged_attention.py`` micro-bench, or any caller of
  :func:`tune_kernel`), on the live backend, minimum-of-``reps`` wall
  time per candidate.  Tuning never runs inside a jit trace — the
  dispatcher only ever *looks up* a config, so the scheduler's
  compile-once invariant is untouched.
- **Cache**: winners land in a JSON table keyed by
  ``(kernel, shape-bucket, dtype, device-kind)`` at
  ``$DLROVER_TPU_AUTOTUNE_CACHE`` (default: a git-ignored file inside
  the checkout, next to the compile cache — nothing outside the
  checkout steers tile sizes).  Lookup order is winner cache ->
  checked-in ``ops/autotune_defaults.json`` (the deterministic table
  CPU CI resolves against) -> shape heuristic.  The device bucket of a
  TPU is its real ``device_kind`` (``"TPU v5 lite"`` ->
  ``tpu-v5-lite``), so a row tuned on a chip is found on that chip.
- Every tuning event is recorded on the timeline as a
  ``kernel_autotune`` span (labels ``kernel`` / ``best_config`` /
  ``candidates`` / ``best_us``, schema-linted) and publishes the
  winner's time as the ``dlrover_tpu_paged_kernel_us`` gauge
  (labels ``kernel`` / ``backend``).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

CACHE_ENV = "DLROVER_TPU_AUTOTUNE_CACHE"
_DEFAULTS_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "autotune_defaults.json"
)

#: in-process memo so the dispatcher's trace-time lookups are O(1)
_MEMO: Dict[str, Dict[str, Any]] = {}


def _cache_path() -> str:
    from dlrover_tpu.common.jax_env import CHECKOUT_CACHE_ROOT

    return os.getenv(CACHE_ENV, "").strip() or os.path.join(
        CHECKOUT_CACHE_ROOT, "paged_autotune.json"
    )


def _device_kind() -> str:
    """Device bucket for cache keys: TPUs key by their real kind (tile
    economics differ per generation); everything else runs the kernels
    in interpret mode and shares one bucket."""
    from dlrover_tpu.ops.pallas_utils import use_interpret

    if use_interpret():
        return "interpret"
    return jax.devices()[0].device_kind.replace(" ", "-").lower()


def _pow2_bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def shape_key(
    kernel: str,
    *,
    group: int,
    head_dim: int,
    block_size: int,
    max_blocks: int,
    dtype,
    window: int = 1,
    device_kind: Optional[str] = None,
) -> str:
    """Stable cache key.  ``max_blocks`` is pow2-bucketed (grid length
    only shifts the stream count, not the tile choice); everything that
    changes tile legality or arithmetic intensity keys exactly."""
    kind = device_kind if device_kind is not None else _device_kind()
    return "|".join(
        (
            kernel,
            f"g{group}",
            f"d{head_dim}",
            f"bs{block_size}",
            f"mb{_pow2_bucket(max_blocks)}",
            f"w{window}",
            np.dtype(dtype).name,
            kind,
        )
    )


def _load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            loaded = json.load(f)
        return loaded if isinstance(loaded, dict) else {}
    except (OSError, ValueError):
        return {}


def _heuristic(
    kernel: str,
    *,
    group: int,
    head_dim: int,
    block_size: int,
    max_blocks: int,
    dtype,
    window: int = 1,
) -> Dict[str, Any]:
    """Untuned fallback.  No per-head row padding either way: the
    kernels run every head in one matmul and pad the TOTAL row count
    to a sublane tile themselves.  Interpret mode streams one page per
    step.  Compiled, ``decode`` fetches its own pages a GROUP of
    ``kv_span`` at a time for the blocks a lane holds, and a group has
    a price of its own (~0.6 us on a v5e beside ~0.07 us a page:
    PERF.md, PR 45): the widest legal power of two up to 32 that still
    leaves a full table four groups, so that a short table's lanes are
    not one group of mostly stale columns — the kernel's wrapper then
    bounds the group by the fast memory a page's bytes leave it
    (``paged_decode_kernel``).  ``verify`` streams its pages as
    pipeline operands a grid step: the widest legal span up to 4 (16
    where a table holds 256 blocks or more), amortizing grid overhead."""
    from dlrover_tpu.ops.pallas_utils import use_interpret

    q_rows = group * (window if kernel == "verify" else 1)
    if use_interpret():
        return {"q_rows": q_rows, "kv_span": 1}
    if kernel == "decode":
        spans = tuple(c for c in (2, 4, 8, 16, 32) if 4 * c <= max_blocks)
    else:
        spans = (2, 4) + ((8, 16) if max_blocks >= 256 else ())
    span = 1
    for cand in spans:
        if cand <= max_blocks and _span_is_legal(
            cand, block_size, max_blocks, dtype
        ):
            span = cand
    return {"q_rows": q_rows, "kv_span": span}


def _span_is_legal(
    span: int, block_size: int, max_blocks: int, dtype
) -> bool:
    """A span is legal iff the kv rows it streams per step survive
    ``round_block_to_tile`` unchanged — i.e. they already sit on a
    Mosaic tile boundary for this dtype."""
    from dlrover_tpu.accelerate.module_replace import round_block_to_tile

    total = max_blocks * block_size
    kv_rows = min(span * block_size, total)
    return round_block_to_tile(kv_rows, total, dtype) == kv_rows


def candidates(
    kernel: str,
    *,
    group: int,
    head_dim: int,
    block_size: int,
    max_blocks: int,
    dtype,
    window: int = 1,
) -> List[Dict[str, Any]]:
    """Legal (q_rows, kv_span) sweep for one shape, smallest first."""
    from dlrover_tpu.ops.paged_kernels import sublane_tile

    rows = group * (window if kernel == "verify" else 1)
    tile = sublane_tile(dtype)
    row_opts = sorted({rows, ((rows + tile - 1) // tile) * tile})
    # decode's groups reach 32 pages (its heuristic's widest)
    span_opts = [
        s
        for s in (1, 2, 4, 8) + ((16, 32) if kernel == "decode" else ())
        if s <= max_blocks and _span_is_legal(s, block_size, max_blocks, dtype)
    ] or [1]
    return [
        {"q_rows": r, "kv_span": s} for r in row_opts for s in span_opts
    ]


def get_config(
    kernel: str,
    *,
    group: int,
    head_dim: int,
    block_size: int,
    max_blocks: int,
    dtype,
    window: int = 1,
) -> Dict[str, Any]:
    """Trace-time config lookup (never times anything): in-process memo
    -> user cache JSON -> checked-in defaults -> heuristic."""
    key = shape_key(
        kernel,
        group=group,
        head_dim=head_dim,
        block_size=block_size,
        max_blocks=max_blocks,
        dtype=dtype,
        window=window,
    )
    hit = _MEMO.get(key)
    if hit is not None:
        return hit
    cfg = _load_json(_cache_path()).get(key)
    if not isinstance(cfg, dict):
        cfg = _load_json(_DEFAULTS_FILE).get(key)
    if not isinstance(cfg, dict):
        cfg = _heuristic(
            kernel,
            group=group,
            head_dim=head_dim,
            block_size=block_size,
            max_blocks=max_blocks,
            dtype=dtype,
            window=window,
        )
    cfg = {"q_rows": int(cfg["q_rows"]), "kv_span": int(cfg["kv_span"])}
    _MEMO[key] = cfg
    return cfg


def clear_memo() -> None:
    """Drop the in-process lookup memo (tests; after cache writes)."""
    _MEMO.clear()


def _save_winner(key: str, config: Dict[str, Any], best_us: float) -> str:
    path = _cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    table = _load_json(path)
    table[key] = dict(config, best_us=round(best_us, 3))
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return path


def tune_kernel(
    kernel: str,
    run_fn: Callable[[Dict[str, Any]], Callable[[], Any]],
    cands: List[Dict[str, Any]],
    *,
    key: str,
    reps: int = 3,
    backend: str = "pallas",
    save: bool = True,
) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """Time every candidate and persist + publish the winner.

    ``run_fn(config)`` returns a zero-arg callable that executes the
    kernel once, *blocking until the result is ready* (the callable is
    invoked once for warmup/compile before timing).  A candidate that
    fails to compile or run is skipped, and its report row carries the
    reason under ``error``; no candidate surviving is fatal.  Returns
    ``(best_config, report)`` where the report lists per-candidate
    microseconds.
    """
    from dlrover_tpu.observability.events import get_event_logger
    from dlrover_tpu.observability.metrics import get_registry

    start_wall = time.time()
    t_begin = time.perf_counter()
    report: List[Dict[str, Any]] = []
    best: Optional[Dict[str, Any]] = None
    best_us = float("inf")
    for config in cands:
        try:
            call = run_fn(config)
            call()  # warmup: compile + first run outside the clock
            elapsed_us = float("inf")
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                call()
                elapsed_us = min(
                    elapsed_us, (time.perf_counter() - t0) * 1e6
                )
        except Exception as exc:  # illegal tile / OOM: skip, don't die
            report.append(dict(config, error=f"{type(exc).__name__}: {exc}"))
            continue
        report.append(dict(config, us=round(elapsed_us, 3)))
        if elapsed_us < best_us:
            best_us = elapsed_us
            best = config
    if best is None:
        raise RuntimeError(
            f"autotune[{kernel}]: no candidate ran (tried {len(cands)}): "
            + "; ".join(str(row.get("error")) for row in report)
        )
    if save:
        _save_winner(key, best, best_us)
        _MEMO[key] = dict(best)
    get_event_logger().complete(
        "kernel_autotune",
        start_wall,
        time.perf_counter() - t_begin,
        kernel=kernel,
        best_config=json.dumps(best, sort_keys=True),
        candidates=len(cands),
        best_us=round(best_us, 3),
    )
    get_registry().set_gauge(
        "dlrover_tpu_paged_kernel_us",
        best_us,
        labels={"kernel": kernel, "backend": backend},
    )
    return dict(best), report
