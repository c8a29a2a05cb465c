"""Micro-benchmark + fleet simulator for the control plane.

Spins the REAL gRPC master servicer on localhost (KV store,
rendezvous managers, task manager — the same components
``LocalJobMaster`` wires) and drives it with N simulated agents whose
waits are long-polls: one RPC parks on the master's condition and
returns the moment the state changes.

Reported:

- ``idle`` — N agents wait 5 s (budget-scaled) on a key that is never
  set: total RPC count (client AND server side) and RPC/s.
- ``wakeup`` — the key is set mid-wait: per-agent latency from ``kv
  set`` to waiter return, p50/p99.
- ``throughput`` — N agents hammer ``kv get`` for ~1 s:
  ``control_rps``, the sustained master RPC rate.

The FLEET SIMULATOR leg (``--fleet N``) is the ROADMAP item-2 proof:
a sweep of 64..N simulated agents (threads with real ``MasterClient``
channels) drives realistic traffic — heartbeats, KV set/get,
rendezvous waiting-count long-polls, shard task get/ack, timeline
batches — against ONE real master whose self-telemetry
(``observability/self_telemetry.py``) is then read back to report
**p50/p99 per RPC kind vs N** plus the achieved RPC/s, and to locate
the **saturation knee** (the largest N whose p99 stays within
``KNEE_RATIO`` of the smallest N's).  ``--overload`` additionally
runs a synthetic overload: a shrunken worker pool
(``DLROVER_TPU_MASTER_WORKERS``) under parked long-polls must yield a
``master_overload`` conclusion + instant within 3 derivation
intervals — the MasterHealth acceptance loop, closed.

Usage::

    python scripts/bench_control_plane.py [--agents 8] [--wait_s 5]
                                          [--fleet 256] [--overload]
                                          [--out OUT.json]

Honors ``DLROVER_TPU_BENCH_BUDGET_S`` (scales the wait window, agent
count and fleet sweep down) and flushes the payload-so-far to
``--out`` after every phase (and after every fleet N — a 512-agent
leg dying at the harness timeout must not lose the 64/128/256
points).
"""

import argparse
import json
import os
import statistics
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# ONE definition of the budget/flush semantics across all benches
from bench import BenchBudget, flush_partial as _flush  # noqa: E402

from dlrover_tpu.agent.master_client import MasterClient  # noqa: E402
from dlrover_tpu.common.constants import RendezvousName  # noqa: E402
from dlrover_tpu.common.env import get_free_port  # noqa: E402
from dlrover_tpu.master.kv_store import KVStoreService  # noqa: E402
from dlrover_tpu.master.rendezvous import (  # noqa: E402
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.servicer import (  # noqa: E402
    MasterServicer,
    create_master_service,
)
from dlrover_tpu.master.shard.task_manager import TaskManager  # noqa: E402


def start_master():
    """The real servicer over real gRPC on a free localhost port;
    returns (addr, servicer, server, kv_store)."""
    kv = KVStoreService()
    servicer = MasterServicer(
        task_manager=TaskManager(),
        rdzv_managers={
            RendezvousName.ELASTIC_TRAINING:
                ElasticTrainingRendezvousManager(),
            RendezvousName.NETWORK_CHECK:
                NetworkCheckRendezvousManager(),
        },
        kv_store=kv,
    )
    port = get_free_port()
    server = create_master_service(port, servicer)
    server.start()
    return f"127.0.0.1:{port}", servicer, server, kv


def _run_waiters(addr, n_agents, key, wait_s):
    """N agents waiting on ``key``; returns (clients, results) where
    results[i] is the waiter's return wall time or None on timeout."""
    clients = [
        MasterClient(addr, node_id=i, timeout=wait_s + 15.0)
        for i in range(n_agents)
    ]
    results = [None] * n_agents

    def _wait(i):
        try:
            clients[i].kv_store_wait(key, timeout=wait_s)
            results[i] = time.perf_counter()
        except TimeoutError:
            results[i] = None

    threads = [
        threading.Thread(target=_wait, args=(i,), daemon=True)
        for i in range(n_agents)
    ]
    for t in threads:
        t.start()
    return clients, results, threads


def bench_idle_wait(addr, servicer, n_agents, wait_s) -> dict:
    """The acceptance workload: an idle ``wait_s`` KV wait on a key
    nobody sets.  Counts every RPC the waiters issue."""
    server_before = servicer.rpc_count
    key = f"bench/idle/{os.getpid()}"
    clients, _results, threads = _run_waiters(
        addr, n_agents, key, wait_s
    )
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    client_rpcs = sum(c.rpc_count for c in clients)
    for c in clients:
        c.close()
    return {
        "agents": n_agents,
        "wait_s": wait_s,
        "client_rpcs": client_rpcs,
        "server_rpcs": servicer.rpc_count - server_before,
        "rpcs_per_waiter": round(client_rpcs / max(n_agents, 1), 2),
        "rps": round(client_rpcs / max(elapsed, 1e-9), 2),
    }


def bench_wakeup(addr, kv, n_agents, wait_s) -> dict:
    """Latency from ``kv set`` to waiter return, p50/p99 over the
    agent fleet."""
    key = f"bench/wake/{os.getpid()}"
    clients, results, threads = _run_waiters(
        addr, n_agents, key, wait_s + 10.0
    )
    time.sleep(min(0.5, wait_s / 4))  # everyone parked
    t_set = time.perf_counter()
    kv.set(key, b"wake")
    for t in threads:
        t.join()
    for c in clients:
        c.close()
    lat_ms = sorted(
        (r - t_set) * 1e3 for r in results if r is not None
    )
    if not lat_ms:
        return {"error": "no waiter woke"}
    return {
        "agents": n_agents,
        "wakeup_p50_ms": round(statistics.median(lat_ms), 2),
        "wakeup_p99_ms": round(
            lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))], 2
        ),
        "wakeup_max_ms": round(lat_ms[-1], 2),
    }


def bench_throughput(addr, kv, n_agents, duration_s: float = 1.0) -> dict:
    """Sustained ``kv get`` RPC rate over N concurrent agents — the
    master's control-plane ceiling on this host."""
    kv.set("bench/throughput", b"x")
    clients = [
        MasterClient(addr, node_id=i) for i in range(n_agents)
    ]
    stop = time.perf_counter() + duration_s

    def _hammer(i):
        while time.perf_counter() < stop:
            clients[i].kv_store_get("bench/throughput")

    threads = [
        threading.Thread(target=_hammer, args=(i,), daemon=True)
        for i in range(n_agents)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    rpcs = sum(c.rpc_count for c in clients)
    for c in clients:
        c.close()
    return {
        "agents": n_agents,
        "rpcs": rpcs,
        "control_rps": round(rpcs / max(elapsed, 1e-9), 2),
    }


# --------------------------------------------------------------------------
# fleet simulator (ROADMAP item 2: prove the 256-512 agent fan-in)
# --------------------------------------------------------------------------

#: the knee heuristic: the largest N whose overall p99 stays within
#: this ratio of the smallest N's p99 (past it the master is past its
#: comfortable fan-in on this host)
KNEE_RATIO = 3.0


def _fleet_master(workers: int = 0):
    """One real master with SELF-TELEMETRY on a fresh registry (per-N
    histograms must not mix across sweep points); returns
    ``(addr, servicer, server, telemetry, registry)``."""
    import tempfile

    from dlrover_tpu.common.env import master_workers
    from dlrover_tpu.observability.events import TimelineAggregator
    from dlrover_tpu.observability.metrics import MetricsRegistry
    from dlrover_tpu.observability.self_telemetry import (
        MasterSelfTelemetry,
    )

    registry = MetricsRegistry(
        path=os.path.join(
            tempfile.gettempdir(),
            f"fleet_metrics_{os.getpid()}_{time.monotonic_ns()}.prom",
        )
    )
    kv = KVStoreService()
    task_manager = TaskManager()
    rdzv_managers = {
        RendezvousName.ELASTIC_TRAINING:
            ElasticTrainingRendezvousManager(),
        RendezvousName.NETWORK_CHECK:
            NetworkCheckRendezvousManager(),
    }
    aggregator = TimelineAggregator(job="fleet", registry=registry)
    telemetry = MasterSelfTelemetry(
        registry=registry,
        pool_size=workers or master_workers(),
    )
    telemetry.attach(
        kv_store=kv,
        rdzv_managers=rdzv_managers,
        task_manager=task_manager,
        timeline_aggregator=aggregator,
    )
    # the servicer's parked-wait cap reads the env at construction;
    # an explicit shrunken pool must shrink the cap WITH it (cap >
    # pool would let every worker park and starve mutations — the
    # exact condition the half-the-pool invariant prevents)
    prev_workers = os.environ.get("DLROVER_TPU_MASTER_WORKERS")
    if workers:
        os.environ["DLROVER_TPU_MASTER_WORKERS"] = str(workers)
    try:
        servicer = MasterServicer(
            task_manager=task_manager,
            rdzv_managers=rdzv_managers,
            kv_store=kv,
            timeline_aggregator=aggregator,
            telemetry=telemetry,
        )
        port = get_free_port()
        server = create_master_service(
            port, servicer, max_workers=workers
        )
    finally:
        if workers:
            if prev_workers is None:
                os.environ.pop("DLROVER_TPU_MASTER_WORKERS", None)
            else:
                os.environ["DLROVER_TPU_MASTER_WORKERS"] = (
                    prev_workers
                )
    server.start()
    return f"127.0.0.1:{port}", servicer, server, telemetry, registry


FLEET_DATASET = "fleet_shards"


#: an agent gives up after this many OWN errors (fleet-wide errors
#: are reported but must not kill other agents — a sweep point that
#: silently sheds agents would misplace the knee)
AGENT_MAX_ERRORS = 8


def _agent_loop(client, idx: int, stop, period_s: float,
                errors: list):
    """One simulated agent's steady-state conversation per period:
    heartbeat, own-KV set/get, a 2-span timeline batch, one shard
    task get+ack, and a waiting-count LONG-POLL (which parks a master
    worker for the rest of the period — exactly the item-2 hazard the
    occupancy gauges must surface).  The long-poll doubles as the
    pacing sleep; a rejected (immediate-answer) poll falls back to a
    local wait so a saturated master is not hammered in a busy
    loop."""
    step = 0
    own_errors = 0
    while not stop.is_set():
        t0 = time.monotonic()
        try:
            client.report_heartbeat()
            client.kv_store_set(
                f"fleet/{idx}", str(step).encode()
            )
            client.kv_store_get(f"fleet/{idx}")
            now = time.time()
            client.report_timeline_events(
                [
                    {
                        "name": "step",
                        "ph": "X",
                        "wall": now - 0.05,
                        "dur": 0.05,
                        "node": idx,
                        "labels": {"step": step},
                    },
                    {
                        "name": "data_stall",
                        "ph": "X",
                        "wall": now - 0.06,
                        "dur": 0.01,
                        "node": idx,
                        "labels": {"stage": "host_fetch"},
                    },
                ]
            )
            task = client.get_task(FLEET_DATASET)
            if task is not None and task.task_id >= 0:
                client.report_task_result(
                    FLEET_DATASET, task.task_id
                )
            remaining = period_s - (time.monotonic() - t0)
            if remaining > 0.01:
                # parks a pool worker until the timeout — the
                # realistic idle-agent monitor poll
                client.num_nodes_waiting(
                    wait_timeout=remaining, last_num=0
                )
            step += 1
        except Exception as e:  # noqa: BLE001 - one agent must not kill the run
            errors.append(repr(e))
            own_errors += 1
            if own_errors > AGENT_MAX_ERRORS:
                # bail on THIS agent only: the cap must be per-agent
                # or fleet-wide error #9 would start silently
                # shedding agents while the point still reports the
                # nominal N
                return
        # pacing floor even when the long-poll answered immediately
        # (parked-wait cap reached): no busy-looping on a saturated
        # master
        elapsed = time.monotonic() - t0
        if elapsed < period_s:
            stop.wait(period_s - elapsed)


def run_fleet_point(
    n_agents: int,
    duration_s: float = 4.0,
    period_s: float = 0.5,
    workers: int = 0,
) -> dict:
    """One sweep point: N agents at steady state against one fresh
    master; per-RPC-kind p50/p99 read back from the master's OWN
    latency histograms."""
    addr, servicer, server, telemetry, registry = _fleet_master(
        workers
    )
    stop = threading.Event()
    errors: list = []
    clients = []
    threads = []
    try:
        seed = MasterClient(addr, node_id=0)
        clients.append(seed)
        seed.report_dataset_shard_params(
            dataset_name=FLEET_DATASET,
            dataset_size=2_000_000,
            batch_size=1,
            num_minibatches_per_shard=50,
        )
        for i in range(n_agents):
            client = MasterClient(addr, node_id=i, timeout=30.0)
            clients.append(client)
            t = threading.Thread(
                target=_agent_loop,
                args=(client, i, stop, period_s, errors),
                daemon=True,
            )
            threads.append(t)
            t.start()
        # measure the steady window only (thread spin-up excluded)
        time.sleep(min(1.0, duration_s / 4))
        rpc0 = servicer.rpc_count
        t0 = time.monotonic()
        time.sleep(duration_s)
        window = time.monotonic() - t0
        rpcs = servicer.rpc_count - rpc0
        snapshot = telemetry.snapshot()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        for c in clients:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        server.stop(grace=0.5)
    pool = snapshot["pool"]
    # the knee signal: worst p99 over the kinds that SHOULD be fast.
    # Parked long-polls report their wait window as latency by
    # design — folding them in would hide saturation behind the
    # pacing period.  ONE definition of the wait-capable set
    # (self_telemetry.WAIT_KINDS), shared with the deriver's p99.
    from dlrover_tpu.observability.self_telemetry import WAIT_KINDS

    fast_p99 = 0.0
    for kind, stats in snapshot["rpc"].items():
        if kind in WAIT_KINDS:
            continue
        fast_p99 = max(fast_p99, stats.get("p99_ms") or 0.0)
    rps = rpcs / max(window, 1e-9)
    return {
        "agents": n_agents,
        "duration_s": round(window, 3),
        "rps": round(rps, 1),
        "rps_per_agent": round(rps / max(n_agents, 1), 2),
        "rpc": snapshot["rpc"],
        "p99_ms": round(fast_p99, 3),
        "window_p99_ms": snapshot["rpc_p99_window_ms"],
        "pool": pool,
        "state_rows": snapshot["state_rows"],
        "agent_errors": len(errors),
        "error_sample": errors[:3],
    }


def find_knee(points: list) -> dict:
    """The saturation knee over a sweep: the largest N that is still
    healthy on BOTH axes — fast-kind p99 within ``KNEE_RATIO`` of the
    smallest N's (floored at 5 ms so scheduler noise on a near-idle
    master cannot fake a knee) AND per-agent throughput holding at
    least half the smallest N's (a master can saturate by slowing
    every answer OR by starving the offered load; CPU CI shows the
    second shape first).  ``saturated=False`` when the whole sweep
    stayed healthy — the knee is past the largest N tried."""
    if not points:
        return {}
    baseline_p99 = max(points[0].get("p99_ms") or 0.0, 5.0)
    baseline_rpa = points[0].get("rps_per_agent") or 0.0
    knee = points[0]["agents"]
    saturated = False
    reason = None
    for pt in points:
        p99_ok = (
            (pt.get("p99_ms") or 0.0) <= KNEE_RATIO * baseline_p99
        )
        rpa_ok = (
            baseline_rpa <= 0
            or (pt.get("rps_per_agent") or 0.0)
            >= 0.5 * baseline_rpa
        )
        if p99_ok and rpa_ok:
            knee = pt["agents"]
        else:
            saturated = True
            reason = "p99" if not p99_ok else "throughput"
            break
    return {
        "baseline_p99_ms": round(baseline_p99, 3),
        "baseline_rps_per_agent": round(baseline_rpa, 2),
        "knee_agents": knee,
        "saturated": saturated,
        "saturated_by": reason,
        "knee_ratio": KNEE_RATIO,
    }


def run_fleet(
    ns,
    duration_s: float = 4.0,
    period_s: float = 0.5,
    workers: int = 0,
    checkpoint=None,
) -> dict:
    """The sweep: one fresh master + fleet per N, partial results
    handed to ``checkpoint`` after EVERY point (the per-N flush rule
    — a 512-agent leg hitting the budget must not lose the smaller
    points)."""
    result = {
        "points": [],
        "duration_s": duration_s,
        "period_s": period_s,
        "cpu_count": os.cpu_count(),
    }
    for n in ns:
        result["points"].append(
            run_fleet_point(
                n, duration_s=duration_s, period_s=period_s,
                workers=workers,
            )
        )
        result["knee"] = find_knee(result["points"])
        if checkpoint is not None:
            checkpoint(result)
    return result


def run_overload(
    n_agents: int = 8,
    workers: int = 2,
    interval_s: float = 0.5,
    sustain: int = 2,
    timeout_intervals: float = 8.0,
    longpoll_s: float = 2.0,
) -> dict:
    """The synthetic overload: a SHRUNKEN pool under parked
    long-polls must drive the MasterHealth deriver to a
    ``master_overload`` conclusion + instant within 3 derivation
    intervals (the acceptance bar; ``detect_intervals`` reports the
    measured value)."""
    import tempfile

    from dlrover_tpu.master.diagnosis import (
        DiagnosisManager,
        MasterOverloadOperator,
    )
    from dlrover_tpu.observability.events import (
        EventLogger,
        read_events,
        set_default_event_logger,
    )
    from dlrover_tpu.observability.health import MasterHealth

    events_file = os.path.join(
        tempfile.gettempdir(),
        f"overload_events_{os.getpid()}_{time.monotonic_ns()}.jsonl",
    )
    prev_workers = os.environ.get("DLROVER_TPU_MASTER_WORKERS")
    os.environ["DLROVER_TPU_MASTER_WORKERS"] = str(workers)
    # restore whatever logger the embedding process had installed (a
    # bench harness's own file), not None — clobbering it would send
    # the rest of the process's instants to a fresh env-derived file
    from dlrover_tpu.observability import events as _events_mod

    prev_logger = _events_mod._default_logger
    set_default_event_logger(EventLogger(path=events_file))
    stop = threading.Event()
    clients, threads = [], []
    manager = None
    try:
        addr, servicer, server, telemetry, _reg = _fleet_master(
            workers
        )
        health = MasterHealth(telemetry, sustain=sustain)
        manager = DiagnosisManager(
            operators=[MasterOverloadOperator(health)],
            interval=interval_s,
        )

        def _park(i):
            client = MasterClient(addr, node_id=i, timeout=30.0)
            clients.append(client)
            while not stop.is_set():
                try:
                    client.num_nodes_waiting(
                        wait_timeout=longpoll_s, last_num=0
                    )
                except Exception:  # noqa: BLE001
                    stop.wait(0.2)

        for i in range(n_agents):
            t = threading.Thread(
                target=_park, args=(i,), daemon=True
            )
            threads.append(t)
            t.start()
        time.sleep(interval_s)  # saturation established
        t0 = time.monotonic()
        manager.start()
        deadline = t0 + timeout_intervals * interval_s
        detected = None
        while time.monotonic() < deadline:
            hits = [
                c
                for c in manager.recent_conclusions()
                if str(c.get("problem", "")).startswith(
                    "master_overload"
                )
            ]
            if hits:
                detected = time.monotonic() - t0
                break
            time.sleep(interval_s / 5)
        instants = [
            e
            for e in read_events(events_file)
            if e.get("name") == "master_overload"
        ]
        out = {
            "agents": n_agents,
            "workers": workers,
            "interval_s": interval_s,
            "sustain": sustain,
            "detected": detected is not None,
            "detect_intervals": (
                round(detected / interval_s, 2)
                if detected is not None
                else None
            ),
            "reasons": sorted(
                {
                    (e.get("labels") or {}).get("reason", "?")
                    for e in instants
                }
            ),
            "instants": len(instants),
            "occupancy": telemetry.occupancy(),
        }
    finally:
        stop.set()
        if manager is not None:
            manager.stop()
        for t in threads:
            t.join(timeout=5.0)
        for c in clients:
            try:
                c.close()
            except Exception:  # noqa: BLE001
                pass
        try:
            server.stop(grace=0.5)
        except Exception:  # noqa: BLE001
            pass
        set_default_event_logger(prev_logger)
        if prev_workers is None:
            os.environ.pop("DLROVER_TPU_MASTER_WORKERS", None)
        else:
            os.environ["DLROVER_TPU_MASTER_WORKERS"] = prev_workers
        try:
            os.unlink(events_file)
        except OSError:
            pass
    return out


def run_all(n_agents: int = 8, wait_s: float = 5.0,
            out_path: str = "", payload: dict = None) -> dict:
    """All phases; shared with ``bench.py`` extras and the tier-1
    smoke test."""
    addr, servicer, server, kv = start_master()
    result = {
        "agents": n_agents,
        "wait_s": wait_s,
        "cpu_count": os.cpu_count(),
    }

    def _checkpoint():
        if payload is not None:
            payload["extras"]["control_plane"] = result
            _flush(out_path, payload)

    try:
        result["idle"] = bench_idle_wait(
            addr, servicer, n_agents, wait_s
        )
        _checkpoint()
        result["wakeup"] = bench_wakeup(addr, kv, n_agents, wait_s)
        _checkpoint()
        result["throughput"] = bench_throughput(addr, kv, n_agents)
        result["control_rps"] = result["throughput"]["control_rps"]
        _checkpoint()
    finally:
        server.stop(grace=0.5)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="control-plane micro-benchmark + fleet simulator"
    )
    parser.add_argument("--agents", type=int, default=8)
    parser.add_argument("--wait_s", type=float, default=5.0)
    parser.add_argument(
        "--fleet", type=int, default=0,
        help="fleet-simulator sweep up to N agents (0 = skip); "
        "sweeps 64,128,256,512 capped at N",
    )
    parser.add_argument(
        "--fleet_duration_s", type=float, default=4.0,
        help="steady-state window per sweep point",
    )
    parser.add_argument(
        "--fleet_workers", type=int, default=0,
        help="master gRPC pool for the fleet leg "
        "(0 = $DLROVER_TPU_MASTER_WORKERS or 64)",
    )
    parser.add_argument(
        "--overload", action="store_true",
        help="also run the shrunken-pool synthetic overload "
        "(master_overload conclusion within 3 intervals)",
    )
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    budget = BenchBudget()
    n_agents, wait_s = args.agents, args.wait_s
    if budget.tight(60):
        # shed the wait window first (it dominates wall time), then
        # the fleet size
        wait_s = min(wait_s, 2.0)
    if budget.tight(20):
        n_agents, wait_s = min(n_agents, 2), min(wait_s, 1.0)

    payload = {
        "metric": "control_rps",
        "value": None,
        "unit": "rpc/s",
        "vs_baseline": None,
        "extras": {"bench_budget_s": budget.total},
    }
    result = run_all(n_agents, wait_s, args.out, payload)
    payload["value"] = result.get("control_rps")
    payload["extras"]["control_plane"] = result
    if args.out:
        _flush(args.out, payload)
    if args.fleet:
        ns = [n for n in (64, 128, 256, 512) if n <= args.fleet]
        if not ns:
            ns = [args.fleet]
        duration = args.fleet_duration_s
        if budget.tight(120):
            # shed the biggest points first — the smaller ones still
            # locate the knee on a throttled host
            ns = ns[:2] or ns
            duration = min(duration, 2.0)

        def _checkpoint(partial):
            payload["extras"]["fleet"] = partial
            if args.out:
                _flush(args.out, payload)

        fleet = run_fleet(
            ns,
            duration_s=duration,
            workers=args.fleet_workers,
            checkpoint=_checkpoint,
        )
        payload["extras"]["fleet"] = fleet
        if args.out:
            _flush(args.out, payload)
    if args.overload:
        payload["extras"]["overload"] = run_overload()
        if args.out:
            _flush(args.out, payload)
    print(json.dumps(payload, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
