"""Profiling utilities: FLOPs census + XLA trace capture.

Reference parity: ``AProfiler`` (``atorch/atorch/utils/prof.py:38`` —
FLOPs/MACs census by monkey-patching torch.nn.functional) and the
xpu_timer kernel-timing role.  JAX gives both analytically: the
compiled computation's cost analysis reports exact FLOPs/bytes, and
``jax.profiler`` captures device traces for tensorboard — no symbol
interposition needed (SURVEY.md §5.1 TPU equivalent).
"""

import contextlib
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, Optional, Tuple

import jax

from dlrover_tpu.common.log import default_logger as logger

#: Per-device-kind peak bf16 FLOP/s (per chip).  ONE table behind
#: every MFU number in the repo — ``AProfiler.mfu``, ``bench_mfu``'s
#: candidate scoring, and the observatory's per-node
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


class AProfiler:
    """FLOPs/memory census of a jitted function + step timing.

    ``registry`` must expose ``observe_duration`` (the
    ``MetricsRegistry`` contract).  A registry without it is rejected
    at CONSTRUCTION — ``step()`` used to discover the mismatch only
    when it tried to record, which silently lost every sample until
    then."""

    #: step-time window (ring — the old list paid O(n) ``pop(0)``)
    STEP_WINDOW = 1024

    def __init__(self, registry=None):
        if registry is not None and not callable(
            getattr(registry, "observe_duration", None)
        ):
            raise TypeError(
                "AProfiler registry must provide observe_duration() "
                f"(got {type(registry).__name__}); pass a "
                "MetricsRegistry or None"
            )
        self._registry = registry
        self._step_times = deque(maxlen=self.STEP_WINDOW)

    def cost_analysis(self, fn: Callable, *args, **kwargs) -> Dict:
        """Exact compiled-cost census (replaces the reference's
        monkey-patched per-op accounting)."""
        lowered = jax.jit(fn).lower(*args, **kwargs)
        compiled = lowered.compile()
        costs = compiled.cost_analysis()
        if isinstance(costs, list):  # old jax returns [dict]
            costs = costs[0] if costs else {}
        result = {
            "flops": float(costs.get("flops", 0.0)),
            "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
        }
        try:
            mem = compiled.memory_analysis()
            result["output_bytes"] = float(
                getattr(mem, "output_size_in_bytes", 0)
            )
            result["temp_bytes"] = float(
                getattr(mem, "temp_size_in_bytes", 0)
            )
        except Exception:  # noqa: BLE001
            pass
        return result

    def model_flops_per_token(self, num_params: int) -> float:
        """The 6N rule of thumb for transformer training FLOPs."""
        return 6.0 * num_params

    @contextlib.contextmanager
    def step(self, name: str = "train_step"):
        start = time.perf_counter()
        try:
            yield
        finally:
            # a raising step still took its time — drop the sample
            # and the window under-reports exactly the bad steps
            elapsed = time.perf_counter() - start
            self._step_times.append(elapsed)
            if self._registry is not None:
                self._registry.observe_duration(name, elapsed)

    def mean_step_time(self) -> float:
        if not self._step_times:
            return 0.0
        return sum(self._step_times) / len(self._step_times)

    def mfu(self, flops_per_step: float,
            peak_flops: Optional[float] = None) -> float:
        """Model FLOPs utilization vs peak.  ``peak_flops`` defaults
        to the attached chip's table entry
        (:func:`device_peak_flops`: ``DLROVER_TPU_PEAK_FLOPS``
        override → ``device_kind`` table → loud v5e fallback) — the
        hard-coded ``197e12`` default used to make every non-v5e
        number silently wrong."""
        t = self.mean_step_time()
        if t <= 0:
            return 0.0
        if peak_flops is None:
            peak_flops = device_peak_flops()
        return flops_per_step / t / peak_flops


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
