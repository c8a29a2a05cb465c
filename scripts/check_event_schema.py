"""Lint every timeline emit site against the declared event schema.

The goodput ledger is only trustworthy if emit sites use the CLOSED
phase vocabulary (``observability/events.py`` ``PHASES`` /
``INSTANT_EVENTS``): a typo'd phase name would still be written, still
render in the trace — and silently fall out of the declared loss
buckets.  This lint walks the repo's Python with ``ast`` and checks
every call to an event-logger method (``span`` / ``begin`` / ``end`` /
``complete`` / ``instant`` on a receiver whose expression mentions
``event``):

- the phase/name argument is a STRING LITERAL (no computed names — the
  vocabulary must be greppable) drawn from the declared sets;
- the labels ``REQUIRED_SPAN_LABELS`` demands for that phase are
  passed as keyword arguments at span-opening sites (``span`` /
  ``begin`` / ``complete``);
- a phase listed in ``OPTIONAL_SPAN_LABELS`` has a CLOSED label set:
  its sites may pass the required and the optional labels, no others
  (``serve_step``'s host-time partition: a typo'd ``admit_ms`` would
  silently drop out of the metric that reads it);
- a ``startup`` span's ``stage`` is a string literal of
  ``STARTUP_STAGES``: the set-up readers sum the stages by name, and a
  typo'd stage would fall into no part of the split;
- ``leaf()`` — the profiler-only annotation of a per-iteration phase —
  names a declared leaf (``LEAF_ANNOTATIONS``) or a declared phase;
- ``named_scope()`` — the name ``jax.named_scope`` puts on the device
  operations of a step program — is a string literal of
  ``DEVICE_SCOPES``: the trace readers attribute device time by these
  names, and a typo'd scope would fall into "unscoped".

Usage: ``python scripts/check_event_schema.py [paths...]``
(default: the package, scripts/, tests/ and bench*.py).  Exit 1 on any
violation; ``tests/test_event_schema_lint.py`` runs it in tier-1.
"""

import ast
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dlrover_tpu.observability.events import (  # noqa: E402
    DEVICE_SCOPES,
    INSTANT_EVENTS,
    LEAF_ANNOTATIONS,
    OPTIONAL_SPAN_LABELS,
    PHASES,
    REQUIRED_INSTANT_LABELS,
    REQUIRED_SPAN_LABELS,
    STARTUP_STAGES,
)

EMIT_METHODS = {"span", "begin", "end", "complete", "instant", "leaf"}
#: positional parameters of ``complete()`` that are not labels
_COMPLETE_PARAMS = {"phase", "start_wall", "duration_s"}
#: methods that OPEN a span and must carry the phase's required labels
OPENING_METHODS = {"span", "begin", "complete"}

#: The closed vocabulary of ``dlrover_tpu_``-prefixed metric names the
#: package may emit (``set_gauge`` / ``inc_counter`` literal first
#: args inside ``dlrover_tpu/``).  Dashboards and alerts key on these
#: — a typo'd name would silently export an orphan series.  Names
#: outside the prefix (tests, user metrics) are not policed.
DECLARED_METRICS = {
    # goodput ledger (observability/events.py TimelineAggregator)
    "dlrover_tpu_goodput",
    "dlrover_tpu_goodput_loss_seconds",
    "dlrover_tpu_timeline_useful_seconds",
    "dlrover_tpu_timeline_wall_seconds",
    # checkpoint data plane (observability/metrics.py record_ckpt_io)
    "dlrover_tpu_ckpt_io_gbps",
    "dlrover_tpu_ckpt_io_bytes",
    "dlrover_tpu_ckpt_skipped_snapshots",
    # a CheckpointEngine.close() that gave up waiting for a stuck
    # snapshot drain and deliberately leaked its shm/lock/queue
    # handles (engine.close; DLROVER_TPU_CKPT_CLOSE_TIMEOUT_S)
    "dlrover_tpu_ckpt_drain_stuck",
    # SIGTERM flush hook could not be installed (non-main-thread
    # embedder); the atexit fallback flush is active instead
    "dlrover_tpu_ckpt_sigterm_fallback",
    # elastic-reshard restore data plane (record_reshard_io): the
    # overlap-range bytes reassembling a rank's new slices from a
    # different-world checkpoint
    "dlrover_tpu_reshard_gbps",
    "dlrover_tpu_reshard_bytes",
    "dlrover_tpu_reshard_total",
    # input data plane (record_input_io)
    "dlrover_tpu_input_gbps",
    "dlrover_tpu_input_bytes",
    # host-offload optimizer-state chunk stream (record_offload_io)
    "dlrover_tpu_offload_gbps",
    "dlrover_tpu_offload_bytes",
    # control plane (record_control_rpc; master servicer RPC meter)
    "dlrover_tpu_control_rps",
    "dlrover_tpu_control_rpc_total",
    # client-side ReportBuffer overflow drops during a master outage
    # (record_dropped_reports)
    "dlrover_tpu_control_dropped_reports",
    # the observatory's per-node derivations (observability/health.py
    # HealthEngine.refresh_gauges): health code 1/0.5/0.4/0 and the
    # step-time-over-median straggler score
    "dlrover_tpu_node_health",
    "dlrover_tpu_straggler_score",
    # the live attribution profiler's per-node derivations
    # (HealthEngine over step_profile spans): model-FLOPs utilization
    # and the five-bucket device-time shares
    # (compute/collective/copy/infeed/idle)
    "dlrover_tpu_node_mfu",
    "dlrover_tpu_device_share",
    # the Brain autonomy loop (master/auto_scaler.BrainAutoScaler):
    # decisions and execution outcomes by action, failing decision
    # cycles (both scaler generations count here), and the world size
    # the Brain last planned against
    "dlrover_tpu_autoscale_decisions",
    "dlrover_tpu_autoscale_executions",
    "dlrover_tpu_autoscale_errors",
    "dlrover_tpu_autoscale_world",
    # the master's control-plane SELF-telemetry
    # (observability/self_telemetry.py):
    # per-RPC-kind latency + request/response-size histograms
    "dlrover_tpu_master_rpc_latency_seconds",
    "dlrover_tpu_master_rpc_request_bytes",
    "dlrover_tpu_master_rpc_response_bytes",
    # pool vitals: in-flight RPCs (each holds a gRPC worker),
    # busy/pool occupancy pair, parked long-polls, and long-polls
    # degraded to immediate answers at the parked-wait cap
    "dlrover_tpu_master_inflight_rpcs",
    "dlrover_tpu_master_busy_workers",
    "dlrover_tpu_master_worker_pool_size",
    "dlrover_tpu_master_parked_waits",
    "dlrover_tpu_master_rejected_waits",
    # per-job control-plane state growth (kv | rdzv/* | tasks |
    # timeline row counts)
    "dlrover_tpu_master_state_rows",
    # write-behind datastore health (record_datastore_flush +
    # MasterSelfTelemetry.refresh_gauges): flush latency/batch-size
    # histograms, live queue depth, journal lag (rows enqueued minus
    # rows flushed = claimed durability a crash would lose)
    "dlrover_tpu_datastore_flush_seconds",
    "dlrover_tpu_datastore_flush_rows",
    "dlrover_tpu_datastore_queue_depth",
    "dlrover_tpu_journal_lag_rows",
    # compacted control-plane snapshot vitals (failover.py health):
    # age bounds the journal tail a failover replays
    "dlrover_tpu_snapshot_age_seconds",
    "dlrover_tpu_snapshot_duration_seconds",
    # the inference plane (observability/metrics.py record_serving):
    # per-replica generation throughput, dispatch/admission queue
    # depth, paged-KV block-pool occupancy and the dispatcher-side
    # end-to-end p99 — the serving pane in scripts/top.py and
    # bench_serving.py key on exactly these four
    "dlrover_tpu_serving_tokens_per_s",
    "dlrover_tpu_serving_queue_depth",
    "dlrover_tpu_serving_kv_blocks_used",
    "dlrover_tpu_serving_p99_latency",
    # incremental-allocation serving vitals (ISSUE 15): filled-cache
    # share of pool capacity (what reservation admission caps and
    # incremental admission pushes toward 1.0), cumulative
    # pool-pressure preemptions, shared-block prefix hit rate, and
    # the multi-token decode accept-per-window mean (the dispatch
    # amortization actually achieved)
    "dlrover_tpu_serving_kv_utilization",
    "dlrover_tpu_serving_preemptions",
    "dlrover_tpu_serving_prefix_hit_rate",
    "dlrover_tpu_serving_accepted_tokens_per_step",
    # per-request SLO histograms (ISSUE 16, record_serving_latency):
    # dispatcher-side
    # time-to-first-token, request-level time-between-tokens p99,
    # end-to-end latency, and scheduler queue wait — rendered as
    # _bucket/_sum/_count families on /metrics
    "dlrover_tpu_serving_ttft_seconds",
    "dlrover_tpu_serving_tbt_seconds",
    "dlrover_tpu_serving_e2e_seconds",
    "dlrover_tpu_serving_queue_wait_seconds",
    # per-replica health verdict gauge (ServingHealthEngine):
    # 1 ok .. 0.1 dead_air, mirroring dlrover_tpu_node_health
    "dlrover_tpu_serving_health",
    # paged-attention kernel autotuner (ops/autotune.py): the winning
    # candidate's best-of-reps wall time for one (kernel, shape) key,
    # labeled {kernel, backend} — each sample pairs with a
    # kernel_autotune span on the timeline
    "dlrover_tpu_paged_kernel_us",
    # the RLHF flywheel (ISSUE 20, rl/flywheel.py): the policy
    # generation last published, the trainer stall one in-place
    # publish charged (pairs with a weight_publish span), the
    # serve->train trajectory stream rate, and how many trajectories
    # the staleness policy refused
    "dlrover_tpu_flywheel_generation",
    "dlrover_tpu_flywheel_publish_stall_s",
    "dlrover_tpu_flywheel_trajectories_per_s",
    "dlrover_tpu_flywheel_staleness_dropped",
}
METRIC_METHODS = {
    "set_gauge",
    "inc_counter",
    "observe_duration",
    "observe_histogram",
}
_METRIC_PREFIX = "dlrover_tpu_"


def _default_paths():
    paths = [
        os.path.join(REPO, "dlrover_tpu"),
        os.path.join(REPO, "scripts"),
        os.path.join(REPO, "tests"),
    ]
    paths.extend(glob.glob(os.path.join(REPO, "bench*.py")))
    return paths


def _python_files(paths):
    for path in paths:
        if os.path.isfile(path):
            yield path
        else:
            for root, _dirs, files in os.walk(path):
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)


def _is_event_receiver(func: ast.Attribute) -> bool:
    """True when the call receiver looks like an event logger —
    ``self._events``, ``events``, ``EVENTS``, ``get_event_logger()``;
    this is the repo-wide naming convention the lint enforces
    alongside the schema."""
    try:
        receiver = ast.unparse(func.value)
    except Exception:  # noqa: BLE001 - very old nodes
        return False
    return "event" in receiver.lower()


def _literal_phase(call: ast.Call):
    """The phase argument if it is a string literal; (found, value)."""
    if call.args:
        arg = call.args[0]
    else:
        arg = next(
            (
                kw.value
                for kw in call.keywords
                if kw.arg in ("phase", "name")
            ),
            None,
        )
    if arg is None:
        return False, None
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return True, arg.value
    return True, None  # present but not a literal


def check_file(path: str):
    violations = []
    try:
        tree = ast.parse(open(path).read(), filename=path)
    except SyntaxError as e:
        return [f"{path}: syntax error: {e}"]
    in_package = (
        os.path.relpath(path, REPO).startswith("dlrover_tpu")
    )
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        if (
            in_package
            and func.attr in METRIC_METHODS
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and node.args[0].value.startswith(_METRIC_PREFIX)
            and node.args[0].value not in DECLARED_METRICS
        ):
            violations.append(
                f"{os.path.relpath(path, REPO)}:{node.lineno}: "
                f"{func.attr}({node.args[0].value!r}) is not a "
                "declared dlrover_tpu_ metric (add it to "
                "DECLARED_METRICS or fix the typo)"
            )
            continue
        if func.attr == "named_scope":
            _, scope = _literal_phase(node)
            if scope not in DEVICE_SCOPES:
                violations.append(
                    f"{os.path.relpath(path, REPO)}:{node.lineno}: "
                    f"named_scope({scope!r}) is not a string literal "
                    f"of DEVICE_SCOPES ({sorted(DEVICE_SCOPES)})"
                )
            continue
        if func.attr not in EMIT_METHODS:
            continue
        if not _is_event_receiver(func):
            continue
        where = f"{os.path.relpath(path, REPO)}:{node.lineno}"
        method = func.attr
        found, phase = _literal_phase(node)
        if not found:
            violations.append(
                f"{where}: {method}() without a phase argument"
            )
            continue
        if phase is None:
            violations.append(
                f"{where}: {method}() phase must be a string "
                "literal from the declared schema, not an expression"
            )
            continue
        if method == "instant":
            declared, kind = INSTANT_EVENTS, "instant event"
        elif method == "leaf":
            declared = LEAF_ANNOTATIONS | set(PHASES)
            kind = "leaf annotation or phase"
        else:
            declared, kind = set(PHASES), "phase"
        if phase not in declared:
            violations.append(
                f"{where}: {method}({phase!r}) is not a declared "
                f"{kind} (declared: {sorted(declared)})"
            )
            continue
        if method == "instant":
            kwargs = {kw.arg for kw in node.keywords if kw.arg}
            has_splat = any(
                kw.arg is None for kw in node.keywords
            )
            missing = [
                lab
                for lab in REQUIRED_INSTANT_LABELS.get(phase, ())
                if lab not in kwargs
            ]
            if missing and not has_splat:
                violations.append(
                    f"{where}: instant({phase!r}) missing required "
                    f"label(s) {missing}"
                )
        if method in OPENING_METHODS:
            kwargs = {kw.arg for kw in node.keywords if kw.arg}
            has_splat = any(
                kw.arg is None for kw in node.keywords
            )
            missing = [
                lab
                for lab in REQUIRED_SPAN_LABELS.get(phase, ())
                if lab not in kwargs
            ]
            if missing and not has_splat:
                violations.append(
                    f"{where}: {method}({phase!r}) missing required "
                    f"label(s) {missing}"
                )
            if phase in OPTIONAL_SPAN_LABELS:
                allowed = (
                    set(REQUIRED_SPAN_LABELS.get(phase, ()))
                    | set(OPTIONAL_SPAN_LABELS[phase])
                    | _COMPLETE_PARAMS
                )
                unknown = sorted(kwargs - allowed)
                if unknown:
                    violations.append(
                        f"{where}: {method}({phase!r}) passes "
                        f"undeclared label(s) {unknown} (the phase's "
                        "label set is closed: OPTIONAL_SPAN_LABELS)"
                    )
            if phase == "startup" and not has_splat:
                stage = next(
                    (kw.value for kw in node.keywords if kw.arg == "stage"),
                    None,
                )
                if stage is not None and not (
                    isinstance(stage, ast.Constant)
                    and stage.value in STARTUP_STAGES
                ):
                    violations.append(
                        f"{where}: {method}('startup') stage must be a "
                        "string literal of STARTUP_STAGES "
                        f"({sorted(STARTUP_STAGES)})"
                    )
            # retry-storm visibility: a control_wait span opened as a
            # retry pause must carry the attempt ordinal, or storms
            # collapse into indistinguishable blips on the timeline
            if (
                phase == "control_wait"
                and not has_splat
                and "retries" not in kwargs
            ):
                kind_kw = next(
                    (
                        kw.value
                        for kw in node.keywords
                        if kw.arg == "kind"
                    ),
                    None,
                )
                if (
                    isinstance(kind_kw, ast.Constant)
                    and kind_kw.value == "retry"
                ):
                    violations.append(
                        f"{where}: {method}('control_wait') with "
                        "kind='retry' missing the 'retries' label"
                    )
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    paths = argv or _default_paths()
    violations = []
    n_files = 0
    for path in _python_files(paths):
        n_files += 1
        violations.extend(check_file(path))
    for v in violations:
        print(v)
    print(
        f"event_schema_violations={len(violations)} "
        f"files_checked={n_files}"
    )
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
