"""Training metrics registry + native Prometheus exporter control.

Reference parity: xpu_timer's bvar/Prometheus export
(``atorch/dev/xpu_timer``, port 28888+rank).  Training processes write
counters/gauges through ``MetricsRegistry`` (atomic file rewrite);
the C++ daemon (``native/metrics_exporter/exporter.cc``) serves them
as Prometheus text on 28888+rank.
"""

import re
import os
import subprocess
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.native_build import needs_rebuild, write_stamp

BASE_PORT = 28888  # xpu_timer's port convention


def log_bounds(base: float, growth: float, count: int) -> Tuple[float, ...]:
    """Geometric (log-spaced) histogram bucket upper bounds:
    ``base * growth**i`` for ``i in range(count)``.  Log buckets give
    constant RELATIVE resolution — the right shape for latencies and
    sizes, whose interesting range spans decades."""
    return tuple(base * growth ** i for i in range(count))


#: default latency buckets: 100 µs .. ~210 s, ×2 per bucket (22
#: buckets + the implicit +Inf).  A control-plane RPC lands in the
#: low-millisecond buckets when healthy and walks up the ladder as the
#: master saturates — exactly the drift the p99 gauges key on.
LATENCY_BOUNDS = log_bounds(1e-4, 2.0, 22)
#: default size buckets: 64 B .. ~1 GB, ×4 per bucket (13 buckets +
#: +Inf) — request/response payloads and flush batches.
SIZE_BOUNDS = log_bounds(64.0, 4.0, 13)


class Histogram:
    """One log-bucketed histogram series: cumulative bucket counts +
    sum + count, rendered in the classic Prometheus text format
    (``<name>_bucket{le=...}`` / ``<name>_sum`` / ``<name>_count``).
    NOT thread-safe on its own — the owning registry's lock guards
    every observe/render."""

    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(self, bounds: Tuple[float, ...] = LATENCY_BOUNDS):
        self.bounds = tuple(sorted(bounds))
        # one count per finite bound + the +Inf overflow bucket;
        # NON-cumulative internally (one increment per observe),
        # accumulated at render time
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float):
        value = float(value)
        self.sum += value
        self.count += 1
        # linear scan: bounds are ~20 entries and the loop is cheaper
        # than bisect's call overhead at that size
        for i, bound in enumerate(self.bounds):
            if value <= bound:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def quantile(self, q: float) -> float:
        """Upper-bound estimate of the ``q`` quantile (0..1) from the
        bucket counts: the smallest bucket bound whose cumulative
        count reaches ``q * count``.  Observations past the last
        finite bound report that bound — an under-estimate, loudly
        conservative rather than invented."""
        if self.count <= 0:
            return 0.0
        target = q * self.count
        cum = 0
        for i, bound in enumerate(self.bounds):
            cum += self.counts[i]
            if cum >= target:
                return bound
        return self.bounds[-1] if self.bounds else 0.0

    @staticmethod
    def _fmt_le(bound: float) -> str:
        return f"{bound:.9g}"

    def render_lines(
        self, name: str, inner_labels: str, stamp: str = ""
    ) -> List[str]:
        """The exposition lines for this series.  ``inner_labels`` is
        the pre-rendered ``k="v"`` list (may be empty); ``le`` is
        appended last so the caller's label escaping is reused."""
        lines = []
        cum = 0
        for i, bound in enumerate(self.bounds):
            cum += self.counts[i]
            le = f'le="{self._fmt_le(bound)}"'
            inner = f"{inner_labels},{le}" if inner_labels else le
            lines.append(f"{name}_bucket{{{inner}}} {cum}{stamp}")
        le = 'le="+Inf"'
        inner = f"{inner_labels},{le}" if inner_labels else le
        lines.append(f"{name}_bucket{{{inner}}} {self.count}{stamp}")
        suffix = f"{{{inner_labels}}}" if inner_labels else ""
        lines.append(f"{name}_sum{suffix} {self.sum:.9g}{stamp}")
        lines.append(f"{name}_count{suffix} {self.count}{stamp}")
        return lines

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(
    _REPO_ROOT, "native", "metrics_exporter", "exporter.cc"
)
_BIN_DIR = os.path.join(_REPO_ROOT, "native", "metrics_exporter", "build")
_BIN = os.path.join(_BIN_DIR, "metrics_exporter")


class MetricsRegistry:
    """Process-local metric store flushed to the exporter file."""

    def __init__(self, path: str = "", flush_interval: float = 5.0,
                 rank: Optional[int] = None):
        """``rank``: when set, every metric carries a ``rank`` label —
        the per-rank series the reference's per-rank bvar exporters
        provide (aggregation then happens in PromQL, not here)."""
        self._path = path or os.path.join(
            tempfile.gettempdir(),
            f"dlrover_tpu_metrics_{os.getpid()}.prom",
        )
        self._metrics: Dict[str, float] = {}
        #: (name, rendered-inner-labels) -> Histogram — kept separate
        #: from the scalar map because one logical series renders as
        #: many exposition lines
        self._histograms: Dict[Tuple[str, str], Histogram] = {}
        self._lock = threading.Lock()
        self._flush_interval = flush_interval
        self._last_flush = 0.0
        self._rank = rank

    @property
    def path(self) -> str:
        return self._path

    @staticmethod
    def _escape_label(value) -> str:
        """Prometheus text-format label escaping (backslash, quote,
        newline) — an unescaped quote in a value would corrupt the
        whole exposition line."""
        return (
            str(value)
            .replace("\\", "\\\\")
            .replace('"', '\\"')
            .replace("\n", "\\n")
        )

    _NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")

    def _inner_labels(self, labels: Optional[Dict] = None) -> str:
        """The rendered ``k="v"`` label list (no braces; "" when no
        labels survive the merge)."""
        merged = dict(labels or {})
        if self._rank is not None:
            merged.setdefault("rank", self._rank)
        if not merged:
            return ""
        return ",".join(
            f'{self._NAME_RE.sub("_", str(k))}='
            f'"{self._escape_label(v)}"'
            for k, v in sorted(merged.items())
        )

    def _key(self, name: str, labels: Optional[Dict] = None) -> str:
        name = self._NAME_RE.sub("_", name)
        inner = self._inner_labels(labels)
        if not inner:
            return name
        return f"{name}{{{inner}}}"

    def set_gauge(self, name: str, value: float, labels=None):
        with self._lock:
            self._metrics[self._key(name, labels)] = float(value)
        self._maybe_flush()

    def inc_counter(self, name: str, value: float = 1.0, labels=None):
        key = self._key(name, labels)
        with self._lock:
            self._metrics[key] = self._metrics.get(key, 0.0) + value
        self._maybe_flush()

    def observe_duration(self, name: str, seconds: float, labels=None):
        """Simple duration tracking: _sum/_count pair."""
        self.inc_counter(name + "_seconds_sum", seconds, labels)
        self.inc_counter(name + "_count", 1.0, labels)

    def observe_histogram(self, name: str, value: float, labels=None,
                          bounds: Optional[Tuple[float, ...]] = None):
        """Record one observation into a log-bucketed histogram
        series (created on first observe; ``bounds`` only applies
        then — a series' bucket layout is immutable).  Rendered as
        classic Prometheus ``_bucket``/``_sum``/``_count`` lines by
        ``render_text()``/``flush()``."""
        name = self._NAME_RE.sub("_", name)
        with self._lock:
            key = (name, self._inner_labels(labels))
            hist = self._histograms.get(key)
            if hist is None:
                hist = self._histograms[key] = Histogram(
                    bounds if bounds is not None else LATENCY_BOUNDS
                )
            hist.observe(value)
        self._maybe_flush()

    def histogram(self, name: str, labels=None) -> Optional[Histogram]:
        """The live ``Histogram`` for a series (None before its first
        observe) — quantile reads for the self-telemetry snapshot and
        the fleet bench.  The returned object is shared; treat it as
        read-only."""
        with self._lock:
            return self._histograms.get(
                (self._NAME_RE.sub("_", name),
                 self._inner_labels(labels))
            )

    def histogram_series(self, name: str) -> Dict[str, Histogram]:
        """Every label-set of one histogram name, keyed by the
        rendered inner-label string (reader for per-kind sweeps)."""
        name = self._NAME_RE.sub("_", name)
        with self._lock:
            return {
                inner: hist
                for (n, inner), hist in self._histograms.items()
                if n == name
            }

    def retire_series(self, labels: Dict) -> int:
        """Drop every series — scalar gauges/counters AND histogram
        series — carrying ALL of the given label pairs, and return
        how many were dropped.  A dead or drained serving replica's
        ``dlrover_tpu_serving_*{replica=...}`` gauges would otherwise
        keep their last values on ``/metrics`` forever, reading as a
        live-but-frozen replica; retiring the series makes the death
        visible as absence."""
        pairs = {
            f'{self._NAME_RE.sub("_", str(k))}='
            f'"{self._escape_label(v)}"'
            for k, v in labels.items()
        }
        if not pairs:
            return 0
        dropped = 0
        with self._lock:
            for key in list(self._metrics):
                if "{" not in key:
                    continue
                inner = key[key.index("{") + 1:key.rindex("}")]
                if pairs <= set(inner.split(",")):
                    del self._metrics[key]
                    dropped += 1
            for hkey in list(self._histograms):
                if pairs <= set(hkey[1].split(",")):
                    del self._histograms[hkey]
                    dropped += 1
        self._maybe_flush()
        return dropped

    def _histogram_lines(self, stamp: str = "") -> list:
        """Caller holds the lock."""
        lines = []
        for (name, inner) in sorted(self._histograms):
            lines.extend(
                self._histograms[(name, inner)].render_lines(
                    name, inner, stamp
                )
            )
        return lines

    def render_text(self) -> str:
        """The current metrics as Prometheus exposition text for the
        master's plain-HTTP ``/metrics`` endpoint.  NO trailing
        timestamp: the classic text format demands int64
        *milliseconds* there, and the seconds-float stamp ``flush``
        writes (which the C++ exporter strips before serving, using
        it only for staleness eviction) would make a real Prometheus
        scrape land every sample at ~epoch — served samples must
        carry the scrape time instead."""
        with self._lock:
            lines = [
                f"{k} {v:.9g}"
                for k, v in sorted(self._metrics.items())
            ]
            lines.extend(self._histogram_lines())
        return "\n".join(lines) + "\n"

    def _maybe_flush(self):
        now = time.time()
        if now - self._last_flush >= self._flush_interval:
            self.flush()

    def flush(self):
        with self._lock:
            now = time.time()
            # trailing unix timestamp (Prometheus text format allows
            # it) is what lets the exporter evict STALE series — a
            # crashed writer's last file would otherwise be served as
            # live forever
            lines = [
                f"{k} {v:.9g} {now:.3f}"
                for k, v in sorted(self._metrics.items())
            ]
            lines.extend(self._histogram_lines(f" {now:.3f}"))
            self._last_flush = now
        tmp = self._path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write("\n".join(lines) + "\n")
            os.replace(tmp, self._path)
        except OSError as e:
            logger.warning("metrics flush failed: %s", e)


_default_registry: Optional[MetricsRegistry] = None
_default_registry_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """Process-wide default registry (the Trainer installs its own as
    the default when it starts, so library counters land in the same
    exporter file)."""
    global _default_registry
    with _default_registry_lock:
        if _default_registry is None:
            _default_registry = MetricsRegistry()
        return _default_registry


def set_default_registry(registry: MetricsRegistry):
    global _default_registry
    with _default_registry_lock:
        _default_registry = registry


def record_ckpt_io(kind: str, nbytes: int, seconds: float):
    """Export one checkpoint data-plane measurement as gauges
    (``dlrover_tpu_ckpt_io_gbps{kind=...}`` / ``_bytes{kind=...}``).
    ``kind``: drain | restore | persist | prealloc.  Never raises —
    metrics must not break a save."""
    try:
        reg = get_registry()
        gbps = nbytes / 1e9 / max(seconds, 1e-9)
        reg.set_gauge(
            "dlrover_tpu_ckpt_io_gbps", gbps, labels={"kind": kind}
        )
        reg.set_gauge(
            "dlrover_tpu_ckpt_io_bytes",
            float(nbytes),
            labels={"kind": kind},
        )
    except Exception as e:  # noqa: BLE001
        logger.warning("ckpt io metric export failed: %s", e)


def record_input_io(stage: str, nbytes: int, seconds: float):
    """Export one input data-plane measurement as gauges
    (``dlrover_tpu_input_gbps{stage=...}`` / ``_bytes{stage=...}``).
    ``stage``: ``host_fetch`` (what the consumer waited on) |
    ``read_batch`` (the loader producer pool's raw fetch bandwidth —
    distinct so stacking ``host_prefetch`` over an already-pipelined
    loader doesn't fold two measurements into one series) | ``h2d``.
    Never raises — metrics must not break the input pipeline."""
    try:
        reg = get_registry()
        gbps = nbytes / 1e9 / max(seconds, 1e-9)
        reg.set_gauge(
            "dlrover_tpu_input_gbps", gbps, labels={"stage": stage}
        )
        reg.set_gauge(
            "dlrover_tpu_input_bytes",
            float(nbytes),
            labels={"stage": stage},
        )
    except Exception as e:  # noqa: BLE001
        logger.warning("input io metric export failed: %s", e)


def record_serving(
    replica: str,
    tokens_per_s=None,
    queue_depth=None,
    kv_blocks_used=None,
    p99_latency_s=None,
    kv_utilization=None,
    preemptions=None,
    prefix_hit_rate=None,
    accepted_tokens_per_step=None,
):
    """Export one serving-plane snapshot as gauges
    (``dlrover_tpu_serving_*{replica=...}``): generation throughput,
    dispatch/admission queue depth, paged-KV pool occupancy, the
    dispatcher-side end-to-end p99, plus the incremental-allocation
    vitals — filled-cache utilization, cumulative preemptions, the
    shared-block prefix hit rate and the multi-token decode
    accept-per-window mean — the numbers the serving pane in
    ``scripts/top.py`` and ``bench_serving.py`` key on.  ``None``
    fields are skipped (replicas know their pool, only the dispatcher
    knows fleet latency).  Never raises — metrics must not break the
    serving loop."""
    try:
        reg = get_registry()
        labels = {"replica": replica}
        if tokens_per_s is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_tokens_per_s",
                float(tokens_per_s),
                labels=labels,
            )
        if queue_depth is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_queue_depth",
                float(queue_depth),
                labels=labels,
            )
        if kv_blocks_used is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_kv_blocks_used",
                float(kv_blocks_used),
                labels=labels,
            )
        if p99_latency_s is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_p99_latency",
                float(p99_latency_s),
                labels=labels,
            )
        if kv_utilization is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_kv_utilization",
                float(kv_utilization),
                labels=labels,
            )
        if preemptions is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_preemptions",
                float(preemptions),
                labels=labels,
            )
        if prefix_hit_rate is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_prefix_hit_rate",
                float(prefix_hit_rate),
                labels=labels,
            )
        if accepted_tokens_per_step is not None:
            reg.set_gauge(
                "dlrover_tpu_serving_accepted_tokens_per_step",
                float(accepted_tokens_per_step),
                labels=labels,
            )
    except Exception as e:  # noqa: BLE001
        logger.warning("serving metric export failed: %s", e)


def record_serving_latency(
    replica: str,
    ttft_s=None,
    tbt_p99_s=None,
    e2e_s=None,
    queue_wait_s=None,
):
    """Observe one completed request's SLO latencies into the
    per-replica log-bucketed histograms
    (``dlrover_tpu_serving_{ttft,tbt,e2e,queue_wait}_seconds``),
    rendered as classic ``_bucket``/``_sum``/``_count`` exposition —
    the quantile source for ``/status`` and the SLO-straggler
    derivation.  ``tbt_p99_s`` observations are the request-level
    per-token-gap p99 (one sample per request, not per token — the
    series is a distribution over requests).  Never raises."""
    try:
        reg = get_registry()
        labels = {"replica": replica}
        if ttft_s is not None:
            reg.observe_histogram(
                "dlrover_tpu_serving_ttft_seconds",
                float(ttft_s), labels=labels,
            )
        if tbt_p99_s is not None:
            reg.observe_histogram(
                "dlrover_tpu_serving_tbt_seconds",
                float(tbt_p99_s), labels=labels,
            )
        if e2e_s is not None:
            reg.observe_histogram(
                "dlrover_tpu_serving_e2e_seconds",
                float(e2e_s), labels=labels,
            )
        if queue_wait_s is not None:
            reg.observe_histogram(
                "dlrover_tpu_serving_queue_wait_seconds",
                float(queue_wait_s), labels=labels,
            )
    except Exception as e:  # noqa: BLE001
        logger.warning("serving latency export failed: %s", e)


def record_offload_io(nbytes: int, seconds: float, buffered: bool):
    """Export one host-offload chunk-stream measurement as gauges
    (``dlrover_tpu_offload_gbps{buffered=...}`` / ``_bytes``): the
    optimizer-state host<->device traffic of one streamed update.
    ``buffered`` distinguishes the rolling double-buffered DMA window
    from the serial (kill-switched) stream so a regression in the
    overlap shows up as a ratio between the two series.  Never raises
    — metrics must not break a train step."""
    try:
        reg = get_registry()
        gbps = nbytes / 1e9 / max(seconds, 1e-9)
        labels = {"buffered": "1" if buffered else "0"}
        reg.set_gauge(
            "dlrover_tpu_offload_gbps", gbps, labels=labels
        )
        reg.set_gauge(
            "dlrover_tpu_offload_bytes", float(nbytes), labels=labels
        )
    except Exception as e:  # noqa: BLE001
        logger.warning("offload io metric export failed: %s", e)


def record_reshard_io(from_world: int, to_world: int, nbytes: int,
                      seconds: float):
    """Export one elastic-reshard restore measurement as gauges
    (``dlrover_tpu_reshard_gbps`` / ``_bytes``, labeled with the world
    transition) plus a ``dlrover_tpu_reshard_total`` counter: the
    overlap-range bytes that reassembled this rank's new slices from a
    different-world checkpoint.  Never raises — metrics must not break
    a restore."""
    try:
        reg = get_registry()
        labels = {
            "from_world": str(int(from_world)),
            "to_world": str(int(to_world)),
        }
        reg.set_gauge(
            "dlrover_tpu_reshard_gbps",
            nbytes / 1e9 / max(seconds, 1e-9),
            labels=labels,
        )
        reg.set_gauge(
            "dlrover_tpu_reshard_bytes", float(nbytes), labels=labels
        )
        reg.inc_counter("dlrover_tpu_reshard_total")
    except Exception as e:  # noqa: BLE001
        logger.warning("reshard metric export failed: %s", e)


def record_datastore_flush(rows: int, seconds: float):
    """One write-behind flush batch landed: its commit latency feeds
    the ``dlrover_tpu_datastore_flush_seconds`` histogram and the
    batch size the ``dlrover_tpu_datastore_flush_rows`` histogram —
    the tail of this distribution is the journal's durability lag
    under load.  Never raises — telemetry must not break a flush."""
    try:
        reg = get_registry()
        reg.observe_histogram(
            "dlrover_tpu_datastore_flush_seconds", seconds
        )
        reg.observe_histogram(
            "dlrover_tpu_datastore_flush_rows", float(rows),
            bounds=SIZE_BOUNDS,
        )
    except Exception as e:  # noqa: BLE001
        logger.warning("datastore flush metric export failed: %s", e)


def record_dropped_reports(n: int = 1):
    """Count fire-and-forget reports dropped by the client-side
    ``ReportBuffer`` overflow cap during a master outage
    (``dlrover_tpu_control_dropped_reports``).  A nonzero rate means
    the outage outlived the buffer — telemetry from that window is
    gone (training state is unaffected; reports are advisory).  Never
    raises."""
    try:
        get_registry().inc_counter(
            "dlrover_tpu_control_dropped_reports", float(n)
        )
    except Exception as e:  # noqa: BLE001
        logger.warning("dropped-report metric export failed: %s", e)


#: windowed meter behind ``dlrover_tpu_control_rps``: the master's
#: servicer calls ``record_control_rpc`` per RPC; the rate gauge is
#: recomputed at most once per window so the metric itself cannot
#: become control-plane load
_CONTROL_RPS_WINDOW_S = 5.0
_control_rpc_lock = threading.Lock()
_control_rpc_window_start = 0.0
_control_rpc_window_count = 0


def record_control_rpc(n: int = 1):
    """Count one (or ``n``) master control-plane RPCs; exports the
    windowed rate as ``dlrover_tpu_control_rps`` and the lifetime tally
    as ``dlrover_tpu_control_rpc_total``.  Never raises."""
    global _control_rpc_window_start, _control_rpc_window_count
    try:
        reg = get_registry()
        reg.inc_counter("dlrover_tpu_control_rpc_total", float(n))
        now = time.monotonic()
        with _control_rpc_lock:
            if not _control_rpc_window_start:
                _control_rpc_window_start = now
            _control_rpc_window_count += n
            elapsed = now - _control_rpc_window_start
            if elapsed < _CONTROL_RPS_WINDOW_S:
                return
            rps = _control_rpc_window_count / elapsed
            _control_rpc_window_start = now
            _control_rpc_window_count = 0
        reg.set_gauge("dlrover_tpu_control_rps", rps)
    except Exception as e:  # noqa: BLE001
        logger.warning("control rpc metric export failed: %s", e)


class MetricsExporter:
    """Builds (once) and supervises the native exporter daemon.

    ``extra_files``: additional per-rank metric files to merge into
    this exporter's exposition (node-level aggregation: rank 0 serves
    every local rank).  ``stale_secs``: series whose trailing flush
    timestamp is older than this are evicted (0 = never)."""

    def __init__(self, registry: MetricsRegistry, rank: int = 0,
                 port: Optional[int] = None,
                 extra_files: Optional[list] = None,
                 stale_secs: float = 600.0):
        self._registry = registry
        self._port = port if port is not None else BASE_PORT + rank
        self._extra_files = list(extra_files or [])
        self._stale_secs = stale_secs
        self._proc: Optional[subprocess.Popen] = None

    @property
    def port(self) -> int:
        return self._port

    @staticmethod
    def build() -> str:
        os.makedirs(_BIN_DIR, exist_ok=True)
        if needs_rebuild(_BIN, _SRC):
            cmd = ["g++", "-O2", "-std=c++17", "-o", _BIN, _SRC]
            logger.info("building metrics exporter: %s", " ".join(cmd))
            subprocess.run(cmd, check=True, capture_output=True)
            write_stamp(_BIN, _SRC)
        return _BIN

    def start(self):
        binary = self.build()
        self._registry.flush()
        self._proc = subprocess.Popen(  # noqa: S603
            [
                binary,
                str(self._port),
                str(self._stale_secs),
                self._registry.path,
                *self._extra_files,
            ],
            stderr=subprocess.DEVNULL,
        )
        logger.info(
            "metrics exporter on :%d (%d files)",
            self._port, 1 + len(self._extra_files),
        )

    def stop(self):
        if self._proc is not None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
            self._proc = None
