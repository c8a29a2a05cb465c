"""Observatory closed-loop scenario: inject one straggler + one hang,
assert the master names both — node and problem — within a bounded
number of reporting intervals.

This is the acceptance harness for the job observatory
(``observability/health.py`` + the derived-signal diagnosis
operators): a real ``LocalJobMaster`` serves over real gRPC, and N
simulated nodes run the REAL agent reporting path — each node's
worker loop sleeps its per-step duration and emits ``step`` spans
through a real ``EventLogger``, a real ``TimelineReporter`` tails the
JSONL and ships deltas, a real ``HeartbeatReporter`` keeps the agent
heartbeat up.  Faults:

- the **straggler** node's step sleep is multiplied by
  ``straggler_factor`` (the sleep-fault form of a degraded chip /
  ``rpc delay`` slowdown) — its spans keep flowing, just slower;
- the **hung** node stops emitting spans entirely after
  ``hang_after`` steps while its heartbeats continue — the
  wedged-in-a-collective posture the SpeedMonitor cannot attribute
  (the global step keeps advancing on the healthy ranks).

The harness polls the ``JobStatusRequest`` snapshot and records, in
units of the reporting interval, how long each verdict took:
``straggler_intervals`` (from scenario start) and ``hang_intervals``
(from the hang onset).  It also asserts the diagnosis conclusions
(``DiagnosisManager`` on top of the engine) name the same nodes with
the right problems.  JSON ``--out`` artifact; honors
``DLROVER_TPU_BENCH_BUDGET_S``.

Usage::

    python scripts/bench_observatory.py [--nodes 4] [--interval 0.5]
        [--detect-within 3] [--out OUT.json]
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import BenchBudget, flush_partial as _flush  # noqa: E402


def run_scenario(
    nodes: int = 4,
    straggler_node: int = 2,
    hung_node: int = 3,
    step_s: float = 0.04,
    straggler_factor: float = 3.0,
    interval: float = 0.5,
    hang_after: int = 6,
    detect_within: int = 3,
    timeout_s: float = 60.0,
    probe=None,
    profile: bool = True,
) -> dict:
    """One closed-loop run; returns the metrics dict.  ``probe``,
    when given, is called with the live master's address after
    detection (the tier-1 smoke drives ``scripts/top.py`` through
    it).  Raises RuntimeError only on harness failure — a missed
    detection is a RESULT (``detected=False``).

    With ``profile=True`` (the default) the ATTRIBUTION leg runs too:
    every node emits periodic ``step_profile`` spans — the straggler
    with a copy-dominant share (the offload-problem signature), the
    healthy ranks compute-dominant — and each node runs a simulated
    agent monitor poll so the master's diagnosis-triggered ``capture``
    directive is delivered, answered with a ``ProfileReport``, and
    lands in the Brain ``profiles`` table.  ``profile=False`` pins
    the pre-profiling observatory surface (no ``profiles`` key, no
    attribution fields)."""
    import dlrover_tpu.master.datastore as ds_mod
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.monitor import (
        HeartbeatReporter,
        TimelineReporter,
    )
    from dlrover_tpu.common.env import get_free_port
    from dlrover_tpu.observability.events import (
        EventLogger,
        anchored_now,
    )

    workdir = tempfile.mkdtemp(prefix="dlrover_observatory_")
    job = "observatory-bench"
    # scenario-scale knobs, applied only around master construction:
    # watchdog 2 intervals of total span silence, diagnosis sweep
    # every half interval so a verdict never waits a full minute
    overrides = {
        "DLROVER_TPU_JOB_NAME": job,
        "DLROVER_TPU_HANG_WATCHDOG_S": str(2.0 * interval),
        "DLROVER_TPU_DIAGNOSIS_INTERVAL_S": str(interval / 2.0),
        "DLROVER_TPU_STRAGGLER_RATIO": "1.5",
        "DLROVER_TPU_PROFILE": "1" if profile else "0",
    }
    if profile:
        # a Brain db so the deep-capture summary row is DURABLE (the
        # acceptance bar: the capture lands in the db, not just in
        # master memory)
        overrides["DLROVER_TPU_BRAIN_DB"] = os.path.join(
            workdir, "brain.db"
        )
    saved = {k: os.environ.get(k) for k in overrides}
    saved_store = ds_mod._default_store
    if profile:
        ds_mod._default_store = None
    os.environ.update(overrides)
    try:
        from dlrover_tpu.master.master import LocalJobMaster

        master = LocalJobMaster(get_free_port(), node_num=nodes)
        master.prepare()
    except BaseException:
        # construction failed: the swapped-out datastore global must
        # not leak into the caller's process
        if profile:
            store = ds_mod._default_store
            if store is not None and store is not saved_store:
                store.close()
            ds_mod._default_store = saved_store
        raise
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    stop = threading.Event()
    hang_onset = [0.0]
    clients, reporters, threads = [], [], []
    #: node -> number of capture directives the simulated agent
    #: received (the delivered-once assertion)
    captures_delivered = {}

    def _profile_shares(n: int):
        """Synthetic attribution: the straggler looks like an offload
        problem (copy-dominant), everyone else MXU-bound."""
        if n == straggler_node:
            return dict(
                share_compute=0.30, share_collective=0.10,
                share_copy=0.45, share_infeed=0.05,
                share_idle=0.10, tflops=30.0, mfu=0.11,
            )
        return dict(
            share_compute=0.70, share_collective=0.15,
            share_copy=0.05, share_infeed=0.05,
            share_idle=0.05, tflops=90.0, mfu=0.38,
        )

    def node_worker(n: int, events: EventLogger):
        step = 0
        while not stop.is_set():
            if n == hung_node and step >= hang_after:
                if not hang_onset[0]:
                    hang_onset[0] = time.monotonic()
                time.sleep(0.02)  # wedged: alive, emitting nothing
                continue
            dur = step_s * (
                straggler_factor if n == straggler_node else 1.0
            )
            t0_mono = time.monotonic()
            t0_wall = anchored_now(t0_mono)
            time.sleep(dur)  # the simulated device work (sleep fault)
            step += 1
            events.complete(
                "step",
                t0_wall,
                time.monotonic() - t0_mono,
                step=step,
            )
            if profile and step % 3 == 0:
                # the continuous attribution leg: one step_profile
                # span per few steps, the way the trainer's
                # background worker emits them
                shares = _profile_shares(n)
                events.complete(
                    "step_profile",
                    t0_wall,
                    time.monotonic() - t0_mono,
                    step=step,
                    share_compute=shares["share_compute"],
                    share_collective=shares["share_collective"],
                    share_copy=shares["share_copy"],
                    share_infeed=shares["share_infeed"],
                    share_idle=shares["share_idle"],
                    tflops=shares["tflops"],
                    mfu=shares["mfu"],
                )

    def agent_poll(n: int, client: MasterClient):
        """The simulated agent's monitor-pacing poll: the capture
        directive rides it (zero extra RPCs) and is answered with a
        ProfileReport + an artifact file, like the real agent."""
        last = 0
        while not stop.is_set():
            try:
                last = client.num_nodes_waiting(
                    wait_timeout=interval / 2.0, last_num=last
                )
            except (ConnectionError, OSError):
                time.sleep(interval / 2.0)
                continue
            directive = client.take_node_action()
            if directive is None:
                continue
            action, reason, cid = directive
            if action != "capture":
                continue
            captures_delivered[n] = captures_delivered.get(n, 0) + 1
            artifact = os.path.join(
                workdir, f"capture_{n}_{cid}.json"
            )
            summary = {
                "reason": reason,
                "capture_id": cid,
                "node": n,
                "workers_signalled": 1,
                "profiles_collected": 0 if n == hung_node else 1,
                "stack_dumps": 1,
                "profiles": [],
            }
            try:
                with open(artifact, "w") as f:
                    json.dump(
                        dict(
                            summary,
                            stacks={
                                f"stacks_{n}.txt":
                                    "Thread 0x1 (most recent call "
                                    "first): wedged in collective"
                            },
                        ),
                        f,
                    )
            except OSError:
                artifact = ""
            try:
                client.report_profile(
                    node_rank=n,
                    reason=reason,
                    capture_id=cid,
                    summary=summary,
                    artifact=artifact,
                )
            except (ConnectionError, OSError):
                pass

    try:
        for n in range(nodes):
            client = MasterClient(master.addr, node_id=n)
            clients.append(client)
            path = os.path.join(workdir, f"events_{n}.jsonl")
            events = EventLogger(
                path=path, job=job, node=n, rank=0, incarnation=0
            )
            # ship at half the reporting interval: the detection
            # bound is watchdog (2 intervals) + ship delay + poll —
            # a full-interval ship cadence would eat the whole margin
            shipper = TimelineReporter(
                path, client=client, interval=interval / 2.0
            )
            heart = HeartbeatReporter(
                client=client, interval=interval / 2.0
            )
            shipper.start()
            heart.start()
            reporters.extend([shipper, heart])
            t = threading.Thread(
                target=node_worker,
                args=(n, events),
                name=f"sim-node-{n}",
                daemon=True,
            )
            t.start()
            threads.append(t)
            if profile:
                t = threading.Thread(
                    target=agent_poll,
                    args=(n, client),
                    name=f"sim-agent-{n}",
                    daemon=True,
                )
                t.start()
                threads.append(t)

        poller = MasterClient(master.addr, node_id=nodes)
        clients.append(poller)
        t_start = time.monotonic()
        deadline = t_start + timeout_s
        straggler_detected_at = 0.0
        hang_detected_at = 0.0
        hang_concluded_at = 0.0
        capture_landed_at = 0.0
        conclusion_hits = {}
        snapshot = {}
        while time.monotonic() < deadline:
            status = poller.get_job_status() or {}
            snapshot = status
            health = status.get("health") or {}
            now = time.monotonic()
            if (
                not straggler_detected_at
                and straggler_node in (health.get("stragglers") or [])
            ):
                straggler_detected_at = now
            if (
                not hang_detected_at
                and hung_node in (health.get("hangs") or [])
            ):
                hang_detected_at = now
            for c in status.get("conclusions") or []:
                conclusion_hits.setdefault(
                    (c.get("problem"), c.get("node_rank")), c
                )
            if (
                not hang_concluded_at
                and ("hang", hung_node) in conclusion_hits
            ):
                hang_concluded_at = now
            if profile and not capture_landed_at:
                entry = (status.get("profiles") or {}).get(
                    hung_node
                ) or (status.get("profiles") or {}).get(
                    str(hung_node)
                )
                if entry and entry.get("summary") is not None:
                    capture_landed_at = now
            core_done = (
                straggler_detected_at
                and hang_detected_at
                and ("straggler", straggler_node) in conclusion_hits
                and ("hang", hung_node) in conclusion_hits
            )
            if core_done and (not profile or capture_landed_at):
                break
            time.sleep(interval / 4.0)

        if probe is not None:
            probe(master.addr)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)
        for r in reporters:
            r.stop()
        for c in clients:
            c.close()
        master.stop()
        # the durable half of the capture acceptance: the summary
        # row must be in the Brain profiles table (read before the
        # scenario store is torn down and the global restored)
        profile_rows = []
        if profile:
            store = ds_mod._default_store
            try:
                if store is not None:
                    profile_rows = store.profiles(job)
            except Exception:  # noqa: BLE001 - harness robustness
                profile_rows = []
            finally:
                if store is not None and store is not saved_store:
                    store.close()
                ds_mod._default_store = saved_store

    nodes_snap = {
        n.get("node"): n
        for n in (snapshot.get("health") or {}).get("nodes") or []
    }
    straggler_intervals = (
        (straggler_detected_at - t_start) / interval
        if straggler_detected_at
        else None
    )
    hang_intervals = (
        (hang_detected_at - hang_onset[0]) / interval
        if hang_detected_at and hang_onset[0]
        else None
    )
    detected = bool(
        straggler_intervals is not None
        and hang_intervals is not None
        and ("straggler", straggler_node) in conclusion_hits
        and ("hang", hung_node) in conclusion_hits
    )
    # false-positive audit: which OTHER nodes ended up flagged
    false_stragglers = [
        n
        for n in (snapshot.get("health") or {}).get("stragglers", [])
        if n != straggler_node
    ]
    # ----- the attribution leg's verdicts -----
    attribution = None
    if profile:
        straggler_cause = conclusion_hits.get(
            ("straggler", straggler_node), {}
        ).get("cause", "")
        straggler_snap = nodes_snap.get(straggler_node, {})
        capture_intervals = (
            round(
                (capture_landed_at - hang_concluded_at) / interval, 2
            )
            if capture_landed_at and hang_concluded_at
            else None
        )
        attribution = {
            # the slowed rank's conclusion must NAME its dominant
            # device-time category ("copy 45%" = offload problem)
            "straggler_cause": straggler_cause,
            "straggler_cause_names_category": (
                "copy" in straggler_cause
            ),
            "straggler_dominant": straggler_snap.get("dominant"),
            "straggler_mfu": straggler_snap.get("mfu"),
            # deep capture of the hung rank: delivered exactly once,
            # landed in /status and the Brain db within the bound
            "captures_delivered": dict(captures_delivered),
            "capture_delivered_once": (
                captures_delivered.get(hung_node, 0) == 1
            ),
            "capture_intervals": capture_intervals,
            "capture_in_db": any(
                r.get("node") == hung_node for r in profile_rows
            ),
            "db_profile_rows": len(profile_rows),
        }
        detected = bool(
            detected
            and attribution["straggler_cause_names_category"]
            and attribution["capture_in_db"]
            and capture_intervals is not None
            and capture_intervals <= detect_within
        )
    return {
        "nodes": nodes,
        "straggler_node": straggler_node,
        "hung_node": hung_node,
        "interval_s": interval,
        "detect_within": detect_within,
        "detected": detected,
        "straggler_intervals": (
            round(straggler_intervals, 2)
            if straggler_intervals is not None
            else None
        ),
        "hang_intervals": (
            round(hang_intervals, 2)
            if hang_intervals is not None
            else None
        ),
        "within_bound": bool(
            detected
            and hang_intervals is not None
            and hang_intervals <= detect_within
        ),
        "false_stragglers": false_stragglers,
        "straggler_score": (
            nodes_snap.get(straggler_node, {}).get("straggler_score")
        ),
        "conclusions": sorted(
            f"{p}@{r}" for p, r in conclusion_hits
        ),
        "node_statuses": {
            n: s.get("status") for n, s in nodes_snap.items()
        },
        "profile": profile,
        "attribution": attribution,
        "workdir": workdir,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="observatory straggler+hang detection scenario"
    )
    parser.add_argument("--nodes", type=int, default=4)
    parser.add_argument("--interval", type=float, default=0.5)
    parser.add_argument("--step_s", type=float, default=0.04)
    parser.add_argument("--straggler_factor", type=float, default=3.0)
    parser.add_argument("--detect-within", type=int, default=3,
                        dest="detect_within")
    parser.add_argument("--timeout", type=float, default=60.0)
    parser.add_argument(
        "--no-profile", action="store_false", dest="profile",
        help="skip the attribution leg (step_profile spans + "
        "diagnosis-triggered deep capture) — the pre-profiling "
        "observatory scenario exactly",
    )
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    budget = BenchBudget()
    timeout = budget.cap_timeout(args.timeout, reserve_s=10.0)

    payload = {
        "metric": "observatory_hang_detect_intervals",
        "value": None,
        "unit": "reporting intervals",
        "vs_baseline": None,
        "extras": {"bench_budget_s": budget.total},
    }
    try:
        result = run_scenario(
            nodes=args.nodes,
            interval=args.interval,
            step_s=args.step_s,
            straggler_factor=args.straggler_factor,
            detect_within=args.detect_within,
            timeout_s=timeout,
            profile=args.profile,
        )
    except RuntimeError as e:
        payload["extras"]["error"] = str(e)
        if args.out:
            _flush(args.out, payload)
        print(json.dumps(payload, indent=2))
        return 1
    payload["value"] = result.get("hang_intervals")
    payload["extras"]["scenario"] = result
    if args.out:
        _flush(args.out, payload)
    print(json.dumps(payload, indent=2))
    return 0 if result["detected"] else 1


if __name__ == "__main__":
    sys.exit(main())
