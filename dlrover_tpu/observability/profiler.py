"""Profiling utilities: the peak-FLOPs table + XLA trace capture.

Reference parity: the xpu_timer kernel-timing role
(``atorch/atorch/utils/prof.py:38`` took its FLOPs census by
monkey-patching torch.nn.functional).  JAX needs no symbol
interposition: ``jax.profiler`` captures device traces
(SURVEY.md §5.1 TPU equivalent), and the trainer's live attribution
takes its FLOPs from ``Trainer._flops_fn_from`` and its peak from
:func:`device_peak_flops`.
"""

import contextlib
import os
import threading
from typing import Optional, Tuple

import jax

from dlrover_tpu.common.log import default_logger as logger

#: Per-device-kind peak bf16 FLOP/s (per chip).  ONE table behind
#: every MFU number in the repo — ``bench_mfu``'s candidate scoring
#: and the observatory's per-node
#: ``dlrover_tpu_node_mfu`` gauge all route through
#: :func:`peak_flops_for_kind` so the bench and the live job can never
#: disagree about what "peak" means.  Matching is by substring on the
#: lowercased ``device_kind`` string, FIRST match wins — order the
#: specific patterns (v5 lite) before the generic ones (v5).
PEAK_FLOPS_BY_KIND: Tuple[Tuple[str, float], ...] = (
    ("v6", 918e12),     # Trillium / v6e
    ("v5 lite", 197e12),
    ("v5lite", 197e12),
    ("v5e", 197e12),
    ("v5", 459e12),     # v5p
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

PEAK_FLOPS_ENV = "DLROVER_TPU_PEAK_FLOPS"


def peak_flops_for_kind(kind: str) -> float:
    """Peak bf16 FLOP/s for a ``device_kind`` string.  A kind the table
    does not know RAISES ``LookupError``: an MFU against a guessed peak
    is a wrong number, not a degraded one (set
    ``DLROVER_TPU_PEAK_FLOPS`` for a chip the table has no row for)."""
    lowered = str(kind or "").lower()
    for pattern, peak in PEAK_FLOPS_BY_KIND:
        if pattern in lowered:
            return peak
    raise LookupError(
        f"unknown device kind {kind!r}: no peak-FLOPs table entry; "
        f"set {PEAK_FLOPS_ENV} to the chip's real bf16 peak"
    )


def device_peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of ONE attached chip: the
    ``DLROVER_TPU_PEAK_FLOPS`` override when set (a malformed value
    raises), else the table entry for ``jax.devices()[0].device_kind``
    — raising for a kind the table does not know or when no backend
    can be reached."""
    raw = os.getenv(PEAK_FLOPS_ENV, "")
    if raw:
        return float(raw)
    if device is None:
        device = jax.devices()[0]
    return peak_flops_for_kind(getattr(device, "device_kind", ""))


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA device trace viewable in tensorboard/xprof
    (the libtpu-level replacement for CUDA-event interposition)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        logger.info("trace written to %s", log_dir)


#: the live trace server (jax keeps it alive only while a reference
#: exists — the old API returned it to callers who all dropped it on
#: the floor, so "nothing ever stops it" was really "anything GCing
#: it stops it at an arbitrary moment")
_profiler_server = None
_profiler_server_lock = threading.Lock()


def start_profiler_server(port: int = 9999) -> Optional[object]:
    """On-demand profiling endpoint (``jax.profiler`` trace server).

    Idempotent: a second call returns the already-running server.
    The module holds the reference (jax stops the server when the
    object is collected), so the lifetime is explicit —
    :func:`stop_profiler_server` ends it."""
    global _profiler_server
    with _profiler_server_lock:
        if _profiler_server is not None:
            return _profiler_server
        try:
            _profiler_server = jax.profiler.start_server(port)
        except Exception as e:  # noqa: BLE001
            logger.warning("profiler server failed: %s", e)
            return None
        return _profiler_server


def stop_profiler_server():
    """Stop the trace server started by :func:`start_profiler_server`
    (no-op when none is running)."""
    global _profiler_server
    with _profiler_server_lock:
        server, _profiler_server = _profiler_server, None
    if server is None:
        return
    stop = getattr(server, "stop", None)
    try:
        if callable(stop):
            stop()
        # else: dropping the last reference stops it (jax contract)
    except Exception as e:  # noqa: BLE001
        logger.warning("profiler server stop failed: %s", e)
    logger.info("profiler server stopped")
