"""Goodput harness: measure useful-training-time ratio under worker
kills.

The reference's headline claim is goodput — 69% -> 95% on GLM-65B with
fault tolerance (``README.md:56-58``) and the chaosblade kill-a-pod
runbook (``docs/tech_report/fault_tolerance_exps.md:27-80``).  This
harness reproduces that experiment at CI scale: launch a 2-process
elastic run (``dlrover_tpu.run``), inject a MIX of faults at
configured training steps — hard SIGKILLs and GRACEFUL preemptions
(a fake GCE metadata endpoint flips to TERMINATE, the agent's
PreemptionWatcher flushes the shm snapshot to storage and reports,
then the worker is SIGTERMed like the dying VM would be) — and
measure

- ``goodput``            = final_step x steady-state step time / wall
                           clock from first to last completed step
                           (restart + re-init + re-warmup overhead is
                           the loss)
- ``recovery_latency_s`` = per kill, wall time from the SIGKILL to the
                           next completed step of the new incarnation
- step continuity: every incarnation's first step must be exactly one
  past a step that was flash-checkpointed (RPO 0 with per-step
  blocking snapshots) — a gap or regression fails the run.

Run standalone (prints one JSON line) or via ``run_goodput()`` from
``bench.py``.  CPU-only by design: the metric exercises the control
plane (agent restart, rendezvous, shm restore), not the chip.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _read_progress(path):
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    return out


class _FakeMetadata:
    """Local stand-in for the GCE metadata server: answers the two
    endpoints the PreemptionWatcher polls; the harness flips it to
    TERMINATE to inject a graceful preemption."""

    def __init__(self):
        import http.server
        import threading

        self.event = "NONE"
        harness = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 - stdlib contract
                if self.path.endswith("maintenance-event"):
                    body = harness.event
                elif self.path.endswith("preempted"):
                    body = "FALSE"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                payload = body.encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *a):  # quiet
                pass

        self._srv = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler
        )
        self.base = f"http://127.0.0.1:{self._srv.server_port}/"
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True
        )
        self._thread.start()

    def close(self):
        self._srv.shutdown()
        self._srv.server_close()


def run_goodput(
    target_steps: int = 3200,
    faults=(
        (500, "sigkill"),
        (1050, "preempt"),
        (1600, "sigkill"),
        (2150, "preempt"),
        (2700, "sigkill"),
    ),
    step_sleep: float = 0.1,
    timeout: float = 1500.0,
) -> dict:
    """Run the fault-and-recover experiment; returns the metrics dict.

    Defaults inject FIVE faults ~55-60 s of useful work apart — three
    hard SIGKILLs and two watcher-driven graceful preemptions (fake
    metadata endpoint -> PreemptionWatcher -> storage flush -> SIGTERM)
    — so the MEASURED goodput covers both fault kinds at a spacing
    comparable to the reference's ">=95% under preemptions" claim
    (ref: docs/tech_report/fault_tolerance_exps.md:27-80, chaosblade
    kill + preemption mix).

    Raises RuntimeError on harness failure (launcher died, steps not
    reached, step continuity broken, graceful path not engaged).
    """
    workdir = tempfile.mkdtemp(prefix="dlrover_goodput_")
    progress = os.path.join(workdir, "progress.jsonl")
    events_file = os.path.join(workdir, "events.jsonl")
    metadata = _FakeMetadata()
    env = dict(
        os.environ,
        GOODPUT_TARGET_STEPS=str(target_steps),
        GOODPUT_STEP_SLEEP=str(step_sleep),
        GOODPUT_PROGRESS_FILE=progress,
        GOODPUT_CKPT_DIR=os.path.join(workdir, "ckpt"),
        DLROVER_TPU_SOCKET_DIR=os.path.join(workdir, "socks"),
        # unified timeline: launcher/agent/workers all append here;
        # the goodput ledger below is computed FROM it instead of
        # re-deriving timings
        DLROVER_TPU_EVENTS_FILE=events_file,
        # the agent's REAL preemption watcher polls the fake endpoint
        DLROVER_TPU_METADATA_BASE=metadata.base,
        DLROVER_TPU_PREEMPTION_POLL="0.3",
        JAX_PLATFORMS="cpu",
        # persist even sub-second compiles: the toy model's jits are
        # below the default 1.0s persistence threshold, which would
        # make the compile cache a silent no-op for this workload
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=REPO,
        # one device per proc: a test conftest's 8-virtual-device
        # XLA_FLAGS would leak in and slow every worker down
        XLA_FLAGS="",
    )
    log_path = os.path.join(workdir, "launcher.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.run",
                "--nnodes=1", "--nproc_per_node=2",
                "--monitor_interval=0.3",
                "--stop_timeout=2",
                f"--max_restarts={len(faults) + 2}",
                # the three restart-latency levers, all on by default
                # in the harness because they ARE the product defaults
                # for preemption-heavy TPU fleets:
                # - persistent XLA cache (recompile is avoidable; the
                #   launcher's default: one fixed directory)
                # - prefork zygote (reimport is avoidable)
                # - short failure grace (survivors of a peer kill are
                #   wedged in collectives; SIGTERM buys nothing)
                "--prefork",
                "--failure_stop_timeout=0.5",
                os.path.join(REPO, "scripts", "goodput_train.py"),
            ],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=workdir,
        )

    kills = []  # (kill_time, last_step_seen, inc_at_kill, kind)
    pending = [(int(s), str(k)) for s, k in faults]
    deadline = time.time() + timeout
    try:
        while proc.poll() is None:
            if time.time() > deadline:
                raise RuntimeError("goodput harness timed out")
            lines = _read_progress(progress)
            if lines and pending:
                max_step = max(e["step"] for e in lines)
                max_inc = max(e["inc"] for e in lines)
                # arm the next fault only after the previous fault's
                # restart has been observed (a new incarnation logged
                # progress) — otherwise a fast loop can blow through
                # several thresholds inside one monitor interval
                restart_seen = (
                    not kills or max_inc > kills[-1][2]
                )
                if max_step >= pending[0][0] and restart_seen:
                    _step, kind = pending.pop(0)
                    # fault the most recent rank-1 worker
                    rank1 = [e for e in lines if e["rank"] == 1]
                    victim = (rank1 or lines)[-1]["pid"]
                    if kind == "preempt":
                        # graceful path: metadata flips, the agent's
                        # watcher flushes + reports (<=0.3s poll) —
                        # and then the host DIES anyway (that is what
                        # a preemption is; a SIGTERM alone would be
                        # swallowed by the worker's flush handler and
                        # the worker would keep running)
                        metadata.event = (
                            "TERMINATE_ON_HOST_MAINTENANCE"
                        )
                        time.sleep(1.0)  # watcher poll + flush window
                    try:
                        os.kill(victim, signal.SIGKILL)
                    except ProcessLookupError:
                        pass
                    kills.append(
                        (time.time(), max_step, max_inc, kind)
                    )
                    if kind == "preempt":
                        # clear the event once delivered so the NEXT
                        # preemption is a distinct edge
                        metadata.event = "NONE"
            time.sleep(0.1)
    finally:
        metadata.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    lines = _read_progress(progress)
    if proc.returncode != 0:
        tail = open(log_path).read()[-800:]
        raise RuntimeError(
            f"launcher exited {proc.returncode}; log tail:\n{tail}"
        )
    if not lines or max(e["step"] for e in lines) < target_steps:
        raise RuntimeError("target steps never reached")

    # continuity: an incarnation's first step is one past a snapshot.
    # Rollback (re-executed steps) is measured here too: with per-step
    # snapshots it is 0, but at a realistic checkpoint cadence the
    # work re-done after restore is goodput loss the projection must
    # charge (ADVICE-r3: recovery latency alone overstates goodput).
    by_inc = {}
    for e in lines:
        if e["rank"] != 0:
            continue
        by_inc.setdefault(e["inc"], []).append(e)
    prev_last = None
    rollback_steps = []
    for inc in sorted(by_inc):
        entries = sorted(by_inc[inc], key=lambda e: e["step"])
        first = entries[0]["step"]
        if prev_last is not None and first > prev_last + 1:
            raise RuntimeError(
                f"step gap across restart: {prev_last} -> {first}"
            )
        if prev_last is not None:
            rollback_steps.append(max(0, prev_last + 1 - first))
        steps = [e["step"] for e in entries]
        if steps != list(range(steps[0], steps[-1] + 1)):
            raise RuntimeError(f"non-contiguous steps in inc {inc}")
        prev_last = entries[-1]["step"]

    # steady-state step time: median dt between consecutive rank-0
    # steps within one incarnation (excludes restart gaps)
    dts = []
    for entries in by_inc.values():
        entries = sorted(entries, key=lambda e: e["step"])
        for a, b in zip(entries, entries[1:]):
            dts.append(b["t"] - a["t"])
    dts.sort()
    if not dts:
        raise RuntimeError("not enough progress samples")
    step_time = dts[len(dts) // 2]

    rank0 = sorted(
        (e for e in lines if e["rank"] == 0), key=lambda e: e["t"]
    )
    wall = rank0[-1]["t"] - rank0[0]["t"]
    useful = (target_steps - rank0[0]["step"]) * step_time
    goodput = min(useful / wall, 1.0) if wall > 0 else 0.0

    recoveries = []  # (kind, seconds)
    for kill_t, _, inc_at_kill, kind in kills:
        # recovery = fault -> first completed step of a NEW incarnation
        # (the old rank-0 keeps logging until the agent tears it down)
        after = [
            e
            for e in lines
            if e["t"] > kill_t and e["inc"] > inc_at_kill
        ]
        if after:
            recoveries.append(
                (kind, min(e["t"] for e in after) - kill_t)
            )

    if len(recoveries) != len(kills):
        # an unmeasured fault must fail the harness, not inflate the
        # numbers (mean of fewer recoveries -> silently optimistic)
        raise RuntimeError(
            f"{len(kills)} faults but only {len(recoveries)} measured "
            "recoveries"
        )
    # the graceful path must have ENGAGED (watcher saw the event and
    # flushed) — otherwise the preempt faults were just slow SIGTERMs
    n_preempt = sum(1 for *_x, kind in kills if kind == "preempt")
    if n_preempt:
        log_text = open(log_path).read()
        engaged = log_text.count("maintenance event")
        if engaged < n_preempt:
            raise RuntimeError(
                f"{n_preempt} preemptions injected but the watcher "
                f"logged only {engaged} maintenance events"
            )
    # zero-kill baseline run: no faults -> no recovery loss (1.0 is
    # then exact, not an artifact of an empty mean)
    mean_rec = (
        sum(r for _, r in recoveries) / len(recoveries)
        if recoveries
        else 0.0
    )
    # Secondary PROJECTION onto the reference experiment's (roughly
    # hourly) fault rate: each fault costs measured recovery latency
    # PLUS measured rollback (steps re-executed after restore x step
    # time) out of every 3600s of work.  The measured goodput above is
    # the headline; this contextualizes it against the reference's
    # ">=95% with hourly preemptions".
    mean_rollback_s = (
        sum(rollback_steps) / len(rollback_steps) * step_time
        if rollback_steps
        else 0.0
    )
    fault_cost = mean_rec + mean_rollback_s
    goodput_hourly = 3600.0 / (3600.0 + fault_cost)

    # goodput LEDGER from the event timeline: every lost second named
    # (restart/rendezvous/compile/checkpoint/...), losses summing
    # exactly to wall − useful.  The measured goodput above stays the
    # headline; the ledger says WHERE its complement went.
    from dlrover_tpu.observability.events import (
        compute_ledger,
        pair_spans,
        read_events,
    )

    timeline = read_events(events_file)
    ledger = compute_ledger(timeline)
    # restart-critical-path visibility: per-leg span totals and the
    # MEASURED concurrency between the restore prefetch and the AOT
    # compile (sum of per-process interval intersections) — the
    # overlap the restart_path scheduler is supposed to buy
    leg_ivs = {}
    for iv in pair_spans(timeline):
        if iv["phase"] in (
            "restore_prefetch", "aot_compile", "finish_restore",
            "rendezvous_wait", "restart_path",
        ):
            leg_ivs.setdefault(iv["phase"], []).append(iv)
    by_proc = {}
    for phase in ("restore_prefetch", "aot_compile"):
        for iv in leg_ivs.get(phase, []):
            by_proc.setdefault((iv["node"], iv["pid"]), {})[
                phase
            ] = iv
    overlap_s = 0.0
    for d in by_proc.values():
        if len(d) == 2:
            a, b = d["restore_prefetch"], d["aot_compile"]
            overlap_s += max(
                0.0,
                min(a["end"], b["end"]) - max(a["start"], b["start"]),
            )
    restart_path = {
        "span_counts": {k: len(v) for k, v in leg_ivs.items()},
        "measured_overlap_s": round(overlap_s, 4),
    }
    for phase in ("restore_prefetch", "aot_compile"):
        restart_path[f"{phase}_s"] = round(
            sum(
                iv["end"] - iv["start"]
                for iv in leg_ivs.get(phase, [])
            ),
            4,
        )
    return {
        "restart_path": restart_path,
        "ledger": ledger,
        "loss_breakdown": ledger.get("loss_breakdown", {}),
        "events_file": events_file,
        "timeline_events": len(timeline),
        "goodput": round(goodput, 4),
        "goodput_hourly_preemptions": round(goodput_hourly, 4),
        "steps": target_steps,
        "kills": len(kills),
        "restarts_observed": len(by_inc) - 1,
        "step_time_s": round(step_time, 4),
        "wall_s": round(wall, 2),
        "recovery_latency_s": [
            {"kind": k, "s": round(r, 2)} for k, r in recoveries
        ],
        "mean_recovery_s": round(mean_rec, 2),
        "rollback_steps": rollback_steps,
        "mean_rollback_s": round(mean_rollback_s, 3),
    }


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description="goodput harness")
    parser.add_argument(
        "--out",
        default="BENCH_OUT.json",
        help="write the full result JSON here as well as stdout (the "
        "driver's stdout tail capture can truncate; a file cannot)",
    )
    parser.add_argument(
        "--trace_out",
        default="BENCH_TRACE.json",
        help="write the merged timeline as a Perfetto-loadable "
        "chrome-trace JSON here ('' = skip)",
    )
    args = parser.parse_args(argv)

    if args.out:
        # early stub: a harness timeout mid-run leaves a parseable
        # artifact, not an absent file
        try:
            with open(args.out, "w") as f:
                json.dump(
                    {
                        "metric": "goodput_under_kills",
                        "value": None,
                        "extras": {"status": "running"},
                    },
                    f,
                )
        except OSError:
            pass
    result = run_goodput()
    if args.trace_out:
        from dlrover_tpu.observability.events import (
            export_chrome_trace,
            read_events,
        )

        export_chrome_trace(
            read_events(result["events_file"]), args.trace_out
        )
        result["trace_file"] = os.path.abspath(args.trace_out)
    payload = {
        "metric": "goodput_under_kills",
        # headline: the MEASURED goodput at ~60s kill spacing
        # (the hourly-rate projection, now charged with
        # measured rollback too, stays in extras)
        "value": result["goodput"],
        "unit": "fraction",
        "vs_baseline": round(result["goodput"] / 0.95, 3),
        # the artifact contract: goodput + the per-phase attribution
        # of its complement, top-level
        "goodput": result["goodput"],
        "loss_breakdown": result["loss_breakdown"],
        "extras": result,
    }
    print(json.dumps(payload), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
