"""``top`` for a running job: a refreshing terminal dashboard over the
master observatory.

Reads the ``JobStatusRequest`` snapshot (gRPC, ``--master_addr`` /
``$DLROVER_TPU_MASTER_ADDR``) or the plain-HTTP ``/status`` endpoint
(``--status_url`` when the master was started with ``--status_port``)
and renders per-node health — step counter, step-time and rate EWMAs,
data-stall share, straggler score, restarts/faults, the hang-watchdog
verdict — plus the live goodput ledger and the newest diagnosis
conclusions.  Refreshes every ``--interval`` seconds until ^C.

``--snapshot`` fetches ONCE and prints the raw JSON (written to
``--out`` too when given) — the CI/scripting mode; the tier-1 smoke
test asserts this JSON names the same nodes the RPC snapshot does.

Usage::

    python scripts/top.py --master_addr 127.0.0.1:50051
    python scripts/top.py --status_url http://master:8081/status
    python scripts/top.py --master_addr ... --snapshot --out status.json
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_STATUS_GLYPH = {
    "healthy": "ok",
    "straggler": "SLOW",
    "data_stalled": "STALL",
    "hung": "HUNG",
}


def fetch_status(master_addr: str = "", status_url: str = "",
                 conclusions: int = 16):
    """One snapshot dict (or None when the observatory is off)."""
    if status_url:
        import urllib.request

        with urllib.request.urlopen(status_url, timeout=10) as resp:
            data = json.loads(resp.read().decode())
        return data or None
    from dlrover_tpu.common import messages as msg
    from dlrover_tpu.common.comm import MasterChannel

    chan = MasterChannel(master_addr, timeout=10.0)
    try:
        res = chan.get(
            msg.JobStatusRequest(conclusions=conclusions)
        )
    finally:
        chan.close()
    if res is None or not getattr(res, "available", False):
        return None
    return res.status


def _fmt_share(shares: dict) -> str:
    if not shares:
        return "-"
    return ",".join(
        f"{stage}:{share:.0%}" for stage, share in sorted(
            shares.items(), key=lambda kv: -kv[1]
        )
    )


def _fmt_why(node: dict) -> str:
    """The attribution column: dominant device-time category + MFU
    from the live profiler's step_profile spans ("-" until the
    continuous leg has produced one for this node)."""
    dominant = node.get("dominant") or {}
    if not dominant:
        return "-"
    why = f"{dominant.get('category', '?')}:{dominant.get('share', 0.0):.0%}"
    mfu = node.get("mfu") or 0.0
    if mfu:
        why += f" mfu:{mfu:.2f}"
    return why


def render(status: dict) -> str:
    """The dashboard frame as a string (separated from the fetch loop
    so tests can assert on it without a tty)."""
    health = status.get("health") or {}
    ledger = status.get("ledger") or {}
    speed = status.get("speed") or {}
    lines = []
    lines.append(
        f"job {health.get('job', '?')}"
        f" · goodput {ledger.get('goodput', 0.0):.3f}"
        f" (useful {ledger.get('useful_s', 0.0):.1f}s"
        f" / wall {ledger.get('wall_s', 0.0):.1f}s)"
        f" · global step {speed.get('global_step', '-')}"
        f" · median step {health.get('median_step_time_s', 0.0):.3f}s"
    )
    loss = ledger.get("loss_breakdown") or {}
    if loss:
        top_loss = sorted(
            loss.items(), key=lambda kv: -kv[1]
        )[:4]
        lines.append(
            "loss: " + "  ".join(
                f"{phase}={sec:.1f}s" for phase, sec in top_loss
            )
        )
    lines.append("")
    header = (
        f"{'node':>4} {'state':>6} {'step':>8} {'t/step':>8} "
        f"{'rate':>7} {'straggle':>8} {'stall':>14} "
        f"{'why':>18} "
        f"{'rst':>3} {'flt':>3} {'inc':>3} {'silent':>7}"
    )
    lines.append(header)
    lines.append("-" * len(header))
    for n in health.get("nodes") or []:
        age = n.get("last_event_age_s")
        lines.append(
            f"{n.get('node', '?'):>4} "
            f"{_STATUS_GLYPH.get(n.get('status'), '?'):>6} "
            f"{n.get('step', -1):>8} "
            f"{n.get('step_time_s', 0.0):>8.3f} "
            f"{n.get('step_rate', 0.0):>7.2f} "
            f"{n.get('straggler_score', 0.0):>7.2f}x "
            f"{_fmt_share(n.get('stall_share') or {}):>14} "
            f"{_fmt_why(n):>18} "
            f"{n.get('restarts', 0):>3} "
            f"{n.get('faults', 0):>3} "
            f"{n.get('inc', 0):>3} "
            f"{(f'{age:.0f}s' if age is not None else '-'):>7}"
        )
    master = status.get("master") or {}
    if master:
        # the control plane's own vitals (absent when the master
        # predates self-telemetry)
        pool = master.get("pool") or {}
        ds = master.get("datastore") or {}
        jrn = master.get("journal") or {}
        line = (
            f"master: pool {pool.get('busy', 0)}/"
            f"{pool.get('size', '?')} busy"
            f" ({pool.get('parked_waits', 0)} parked,"
            f" {pool.get('rejected_waits', 0)} rejected)"
            f" · rpc p99(window)"
            f" {master.get('rpc_p99_window_ms', 0.0):.1f}ms"
        )
        if ds:
            line += (
                f" · wb queue {ds.get('queue_depth', 0)}/"
                f"{ds.get('queue_cap', '?')}"
                f" lag {ds.get('lag_rows', 0)} rows"
            )
        if jrn.get("snapshot_age_s") is not None:
            line += f" · snapshot {jrn['snapshot_age_s']:.0f}s ago"
        lines.append("")
        lines.append(line)
        rpc = master.get("rpc") or {}
        if rpc:
            top_rpc = sorted(
                rpc.items(),
                key=lambda kv: -(kv[1].get("p99_ms") or 0.0),
            )[:4]
            lines.append(
                "rpc (worst p99): " + "  ".join(
                    f"{kind}"
                    f" p50={stats.get('p50_ms', 0.0):g}ms"
                    f" p99={stats.get('p99_ms', 0.0):g}ms"
                    f" n={stats.get('count', 0)}"
                    for kind, stats in top_rpc
                )
            )
        rows = master.get("state_rows") or {}
        if rows:
            lines.append(
                "state rows: " + "  ".join(
                    f"{kind}={n}"
                    for kind, n in sorted(rows.items())
                )
            )
    profiles = status.get("profiles") or {}
    if profiles:
        lines.append("")
        lines.append("deep captures (newest per node):")
        for key in sorted(profiles, key=lambda k: str(k)):
            p = profiles[key] or {}
            t = time.strftime(
                "%H:%M:%S", time.localtime(p.get("t", 0))
            )
            summary = p.get("summary")
            if summary is None:
                detail = "in flight"
            else:
                detail = (
                    f"{summary.get('profiles_collected', 0)} "
                    f"profiles, "
                    f"{summary.get('stack_dumps', 0)} stack dumps"
                )
            lines.append(
                f"  {t} node {p.get('node', key):>3} "
                f"{p.get('reason', '?'):<12} {detail}"
                + (
                    f" -> {p.get('artifact')}"
                    if p.get("artifact")
                    else ""
                )
            )
    serving = status.get("serving") or {}
    if serving:
        # the inference plane (rl/generation_service.ServingEngine
        # status + record_serving gauges): per-replica throughput /
        # queue / KV occupancy, fleet p50/p99
        lines.append("")
        lines.append(
            f"serving: queue {serving.get('queue_depth', 0)}"
            f" · completed {serving.get('completed', 0)}"
            f" · p50 {serving.get('p50_latency_s', 0.0):.3f}s"
            f" · p99 {serving.get('p99_latency_s', 0.0):.3f}s"
            f" · weights v{serving.get('version', 0)}"
        )
        slo = serving.get("slo") or {}
        if slo:
            # the SLO histogram quantiles (ISSUE 16): what the
            # dispatcher-side TTFT/TBT/e2e/queue-wait histograms say
            slo_line = (
                f"slo: ttft p99 {slo.get('ttft_p99_s', 0.0):.3f}s"
                f" · tbt p99 {slo.get('tbt_p99_s', 0.0):.4f}s"
                f" · e2e p99 {slo.get('e2e_p99_s', 0.0):.3f}s"
                f" · queue p99 {slo.get('queue_wait_p99_s', 0.0):.3f}s"
            )
            if "fleet_prefix_hit_rate" in slo:
                # fleet-wide shared-prefix hit rate (ISSUE 17): what
                # affinity routing is actually buying across replicas
                slo_line += (
                    " · fleet hit "
                    f"{100.0 * slo['fleet_prefix_hit_rate']:.1f}%"
                )
            lines.append(slo_line)
        health = serving.get("health") or {}
        why_by_idx = {
            h.get("replica"): h
            for h in (health.get("replicas") or [])
        }
        reps = serving.get("replicas") or []
        if reps:
            # kvutil/preempt/hit% are the incremental-allocation
            # vitals (ISSUE 15): filled-cache share, pool-pressure
            # preemptions, shared-prefix block hit rate; the `why`
            # column (ISSUE 16, only when the serving observatory is
            # on) is the health verdict that explains a sick row
            # the role column (ISSUE 17) only appears under fleet
            # mode, where prefill workers and decode replicas are
            # judged against different peer pools
            has_roles = any("role" in r for r in reps)
            hdr = f"{'repl':>4} "
            if has_roles:
                hdr += f"{'role':>8} "
            hdr += (
                f"{'state':>8} {'inflight':>8} "
                f"{'tok/s':>8} {'queue':>6} {'kvblk':>6} "
                f"{'kvutil':>6} {'preempt':>7} {'hit%':>6}"
            )
            if why_by_idx:
                hdr += f"  {'why':<28}"
            lines.append(hdr)
            lines.append("-" * len(hdr))
            for r in reps:
                state = (
                    "ok" if r.get("alive")
                    else ("drained" if r.get("drained") else "DEAD")
                )
                row = f"{r.get('idx', '?'):>4} "
                if has_roles:
                    row += f"{r.get('role', 'decode'):>8} "
                row += (
                    f"{state:>8} "
                    f"{r.get('outstanding', 0):>8} "
                    f"{r.get('tokens_per_s', 0.0):>8.1f} "
                    f"{r.get('queue_depth', 0):>6} "
                    f"{r.get('kv_blocks_used', 0):>6} "
                    f"{r.get('kv_utilization', 0.0):>6.2f} "
                    f"{r.get('preemptions', 0):>7} "
                    f"{100.0 * r.get('prefix_hit_rate', 0.0):>5.1f}%"
                )
                if why_by_idx:
                    h = why_by_idx.get(r.get("idx")) or {}
                    row += f"  {h.get('why', ''):<28}"
                lines.append(row)
    conclusions = status.get("conclusions") or []
    if conclusions:
        lines.append("")
        lines.append("recent diagnosis conclusions (newest last):")
        for c in conclusions[-8:]:
            t = time.strftime(
                "%H:%M:%S", time.localtime(c.get("t", 0))
            )
            lines.append(
                f"  {t} node {c.get('node_rank', -1):>3} "
                f"{c.get('problem', '?'):<12} -> "
                f"{c.get('action', 'none'):<16} {c.get('cause', '')}"
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="live observatory dashboard for a running job"
    )
    parser.add_argument(
        "--master_addr",
        default=os.getenv("DLROVER_TPU_MASTER_ADDR", ""),
        help="master gRPC address (host:port); default "
        "$DLROVER_TPU_MASTER_ADDR",
    )
    parser.add_argument(
        "--status_url", default="",
        help="plain-HTTP /status URL (alternative to --master_addr "
        "when the master runs with --status_port)",
    )
    parser.add_argument("--interval", type=float, default=2.0)
    parser.add_argument(
        "--conclusions", type=int, default=16,
        help="how many recent diagnosis conclusions to fetch",
    )
    parser.add_argument(
        "--snapshot", action="store_true",
        help="fetch once, print the raw JSON, exit (CI mode)",
    )
    parser.add_argument(
        "--out", default="",
        help="also write the snapshot JSON here (with --snapshot)",
    )
    args = parser.parse_args(argv)
    if not args.master_addr and not args.status_url:
        parser.error(
            "need --master_addr (or $DLROVER_TPU_MASTER_ADDR) "
            "or --status_url"
        )

    if args.snapshot:
        status = fetch_status(
            args.master_addr, args.status_url, args.conclusions
        )
        payload = status if status is not None else {
            "available": False
        }
        text = json.dumps(payload, indent=2, default=str)
        if args.out:
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                f.write(text + "\n")
            os.replace(tmp, args.out)
        print(text)
        return 0 if status is not None else 1

    try:
        while True:
            try:
                status = fetch_status(
                    args.master_addr,
                    args.status_url,
                    args.conclusions,
                )
            except (ConnectionError, OSError) as e:
                frame = f"(master unreachable: {e})"
            else:
                if status is None:
                    frame = (
                        "(observatory unavailable — the master "
                        "predates it)"
                    )
                else:
                    frame = render(status)
            # ANSI clear + home: a refreshing frame, not a scroll
            sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
            sys.stdout.flush()
            time.sleep(max(args.interval, 0.2))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
