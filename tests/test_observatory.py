"""The job observatory: streaming health derivation, derived-signal
diagnosis, the JobStatusRequest/HTTP surfaces, the closed-loop
straggler+hang scenario, and what a master wires with no setting
at all."""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.comm import MasterChannel
from dlrover_tpu.common.env import get_free_port
from dlrover_tpu.master.diagnosis import (
    DataStallOperator,
    DiagnosisManager,
    HangWatchdogOperator,
    StragglerOperator,
)
from dlrover_tpu.observability.health import (
    STATUS_HUNG,
    STATUS_STRAGGLER,
    HealthEngine,
)
from dlrover_tpu.observability.metrics import MetricsRegistry


def _step_events(node, count, dur, t0=None, pid=1, inc=0, start=1):
    """Synthesized ``step`` X records the way the trainer emits them."""
    t0 = time.time() - count * dur if t0 is None else t0
    out = []
    for i in range(count):
        out.append(
            {
                "name": "step",
                "ph": "X",
                "wall": t0 + i * dur,
                "mono": i * dur,
                "dur": dur,
                "job": "j",
                "node": node,
                "rank": 0,
                "inc": inc,
                "pid": pid,
                "labels": {"step": start + i},
            }
        )
    return out


class TestHealthEngine:
    def test_step_ewma_and_straggler_score(self):
        engine = HealthEngine(job="j", straggler_ratio=1.5)
        for node in range(3):
            engine.observe_events(node, _step_events(node, 6, 0.1))
        engine.observe_events(3, _step_events(3, 6, 0.31))
        stragglers = engine.stragglers()
        assert [n for n, _ in stragglers] == [3]
        assert stragglers[0][1] == pytest.approx(3.1, rel=0.05)
        snap = engine.snapshot()
        assert snap["stragglers"] == [3]
        by_node = {n["node"]: n for n in snap["nodes"]}
        assert by_node[3]["status"] == STATUS_STRAGGLER
        assert by_node[0]["status"] == "healthy"
        assert by_node[0]["step_time_s"] == pytest.approx(0.1, rel=0.01)
        assert by_node[0]["step"] == 6
        # a healthy node's score hovers at 1x, never flagged
        assert by_node[0]["straggler_score"] == pytest.approx(1.0, rel=0.05)

    def test_straggler_needs_min_steps(self):
        engine = HealthEngine(job="j", straggler_ratio=1.5)
        for node in range(2):
            engine.observe_events(node, _step_events(node, 6, 0.1))
        # two slow steps are not a verdict (cold start, one GC pause)
        engine.observe_events(2, _step_events(2, 2, 0.5))
        assert engine.stragglers() == []

    def test_hang_watchdog_flags_silent_node(self):
        engine = HealthEngine(job="j", hang_watchdog_s=0.15)
        engine.observe_events(0, _step_events(0, 3, 0.01))
        engine.observe_events(1, _step_events(1, 3, 0.01))
        time.sleep(0.2)
        # node 1 keeps emitting, node 0 goes silent
        engine.observe_events(1, _step_events(1, 1, 0.01, start=4))
        suspects = engine.hang_suspects()
        assert [n for n, _ in suspects] == [0]
        assert suspects[0][1] >= 0.15
        snap = engine.snapshot()
        assert snap["hangs"] == [0]
        by_node = {n["node"]: n for n in snap["nodes"]}
        assert by_node[0]["status"] == STATUS_HUNG
        assert by_node[0]["health"] == 0.0

    def test_hang_watchdog_never_arms_for_silent_from_birth(self):
        engine = HealthEngine(job="j", hang_watchdog_s=0.05)
        engine.observe_heartbeat(0, time.time())
        time.sleep(0.1)
        # heartbeats alone never arm the span watchdog: a job that
        # emits no timeline at all must not be branded hung
        assert engine.hang_suspects() == []

    def test_hang_watchdog_suppressed_by_open_span(self):
        """A node attributably busy (open B of a long compile) is not
        hung — the ledger already charges that time."""
        engine = HealthEngine(job="j", hang_watchdog_s=0.1)
        now = time.time()
        engine.observe_events(
            0,
            [
                {
                    "name": "compile",
                    "ph": "B",
                    "wall": now,
                    "mono": 1.0,
                    "node": 0,
                    "pid": 7,
                    "sid": 1,
                }
            ],
        )
        time.sleep(0.15)
        assert engine.hang_suspects() == []
        # the E closes the span: silence past the watchdog now counts
        engine.observe_events(
            0,
            [
                {
                    "name": "compile",
                    "ph": "E",
                    "wall": now + 0.1,
                    "mono": 1.1,
                    "node": 0,
                    "pid": 7,
                    "sid": 1,
                }
            ],
        )
        time.sleep(0.15)
        assert [n for n, _ in engine.hang_suspects()] == [0]

    def test_orphaned_open_span_cannot_disarm_forever(self):
        """A B whose E never arrives (crashed writer, dropped batch)
        buys its phase a bounded grace window, not immunity — and an
        incarnation bump (the restart replaced the processes) clears
        the dead generation's open spans immediately."""
        engine = HealthEngine(job="j", hang_watchdog_s=0.03)
        now = time.time()
        b_rec = {
            "name": "checkpoint_restore", "ph": "B", "wall": now,
            "mono": 1.0, "node": 0, "pid": 7, "sid": 1, "inc": 0,
        }
        engine.observe_events(0, [b_rec])
        time.sleep(0.05)
        assert engine.hang_suspects() == []  # inside the grace
        time.sleep(
            0.03 * HealthEngine.OPEN_SPAN_GRACE_WINDOWS + 0.1
        )
        assert [n for n, _ in engine.hang_suspects()] == [0]
        # incarnation bump wipes open spans without waiting out grace
        # (the probe is an instant — a B would itself open a span)
        engine2 = HealthEngine(job="j", hang_watchdog_s=0.03)
        engine2.observe_events(0, [dict(b_rec)])
        engine2.observe_events(
            0, [dict(b_rec, inc=1, name="worker_kill", ph="i")]
        )
        time.sleep(0.05)
        assert [n for n, _ in engine2.hang_suspects()] == [0]

    def test_hang_watchdog_yields_to_dead_node_detection(self):
        """A node whose agent ALSO stopped heartbeating is dead, not
        hung — the job manager's heartbeat monitor owns that case."""
        engine = HealthEngine(job="j", hang_watchdog_s=0.05)
        engine.HEARTBEAT_FRESH_S = 0.1
        engine.observe_events(0, _step_events(0, 2, 0.01))
        engine.observe_heartbeat(0, time.time())
        time.sleep(0.2)  # both spans AND heartbeats stale
        assert engine.hang_suspects() == []

    def test_stall_share_by_stage(self):
        engine = HealthEngine(job="j", window_s=10.0)
        now = time.time()
        events = []
        for i in range(5):
            events.append(
                {
                    "name": "data_stall",
                    "ph": "X",
                    "wall": now - 5 + i,
                    "mono": float(i),
                    "dur": 0.8,
                    "node": 0,
                    "pid": 1,
                    "labels": {"stage": "host_fetch"},
                }
            )
        events.append(
            {
                "name": "data_stall",
                "ph": "X",
                "wall": now - 1,
                "mono": 9.0,
                "dur": 0.1,
                "node": 0,
                "pid": 1,
                "labels": {"stage": "h2d"},
            }
        )
        engine.observe_events(0, events)
        shares = engine.stall_shares()
        assert 0 in shares
        assert shares[0]["host_fetch"] > shares[0]["h2d"]
        assert 0 < shares[0]["host_fetch"] <= 1.0

    def test_restart_and_fault_counts(self):
        engine = HealthEngine(job="j")
        now = time.time()
        engine.observe_events(
            2,
            [
                {"name": "restart", "ph": "B", "wall": now,
                 "mono": 0.0, "node": 2, "pid": 1, "sid": 1},
                {"name": "fault_injected", "ph": "i", "wall": now,
                 "mono": 0.1, "node": 2, "pid": 1,
                 "labels": {"kind": "kill", "target": "agent"}},
            ],
        )
        engine.observe_fault(2, "NODE_ERROR")
        by_node = {
            n["node"]: n for n in engine.snapshot()["nodes"]
        }
        assert by_node[2]["restarts"] == 1
        assert by_node[2]["faults"] == 2

    def test_gauges_exported(self):
        registry = MetricsRegistry(flush_interval=1e9)
        engine = HealthEngine(
            job="j", registry=registry, straggler_ratio=1.5
        )
        for node in range(2):
            engine.observe_events(node, _step_events(node, 5, 0.1))
        engine.observe_events(2, _step_events(2, 5, 0.4))
        engine.refresh_gauges()
        text = registry.render_text()
        assert 'dlrover_tpu_node_health{node="2"} 0.5' in text
        assert 'dlrover_tpu_straggler_score{node="2"}' in text
        assert 'dlrover_tpu_node_health{node="0"} 1' in text


class _ListOperatorEngine:
    """Minimal HealthEngine facade for operator unit tests."""

    straggler_ratio = 1.5
    hang_watchdog_s = 10.0

    def __init__(self, stragglers=(), hangs=(), stalls=None):
        self._stragglers = list(stragglers)
        self._hangs = list(hangs)
        self._stalls = stalls or {}

    def stragglers(self):
        return self._stragglers

    def hang_suspects(self):
        return self._hangs

    def stall_shares(self):
        return self._stalls


class TestDerivedOperators:
    def test_straggler_operator(self):
        op = StragglerOperator(_ListOperatorEngine(
            stragglers=[(3, 2.4)]
        ))
        out = op.infer(None)
        assert len(out) == 1
        assert out[0].problem == "straggler"
        assert out[0].node_rank == 3
        assert out[0].action == "none"
        assert "x2.40" in out[0].cause

    def test_hang_operator(self):
        op = HangWatchdogOperator(
            _ListOperatorEngine(hangs=[(1, 42.0)])
        )
        out = op.infer(None)
        assert out[0].problem == "hang"
        assert out[0].node_rank == 1
        assert out[0].action == "restart_process"

    def test_data_stall_operator_threshold(self):
        op = DataStallOperator(
            _ListOperatorEngine(
                stalls={0: {"host_fetch": 0.6}, 1: {"h2d": 0.1}}
            ),
            share_threshold=0.3,
        )
        out = op.infer(None)
        assert [c.node_rank for c in out] == [0]
        assert out[0].problem == "data_stall"
        assert "host_fetch" in out[0].cause

    def test_manager_records_conclusions(self, tmp_path, monkeypatch):
        """Fresh conclusions land on the timeline (``diagnosis``
        instant) and in the Brain node_events table, and stay
        readable via recent_conclusions without being consumed.  The
        manager reads an injected clock: the cooldown is over when the
        test says so, not when a loaded machine gets round to it."""
        from dlrover_tpu.master import diagnosis
        from dlrover_tpu.master.datastore import BrainDatastore
        from dlrover_tpu.observability.events import (
            EventLogger,
            read_events,
            set_default_event_logger,
        )

        class Clock:
            """``time`` as ``master/diagnosis.py`` sees it."""

            now = time.time()

            def time(self):
                return self.now

        clock = Clock()
        monkeypatch.setattr(diagnosis, "time", clock)
        events_file = str(tmp_path / "events.jsonl")
        store = BrainDatastore(str(tmp_path / "brain.db"))
        set_default_event_logger(EventLogger(path=events_file))
        try:
            engine = _ListOperatorEngine(stragglers=[(2, 3.0)])
            mgr = DiagnosisManager(
                operators=[StragglerOperator(engine)],
                health_engine=engine,
                datastore=store,
                job="jx",
                conclusion_cooldown=0.2,
            )
            fresh = mgr.diagnose()
            assert len(fresh) == 1
            recs = read_events(events_file)
            diag = [r for r in recs if r["name"] == "diagnosis"]
            assert len(diag) == 1
            assert diag[0]["labels"]["problem"] == "straggler"
            assert diag[0]["labels"]["node_rank"] == 2
            rows = store.node_events("jx")
            assert len(rows) == 1
            assert rows[0]["event_type"] == "diagnosis"
            detail = json.loads(rows[0]["detail"])
            assert detail["problem"] == "straggler"
            # snapshot view is not consumed by take_conclusions
            assert len(mgr.recent_conclusions()) == 1
            assert len(mgr.take_conclusions()) == 1
            assert len(mgr.recent_conclusions()) == 1
            # cooldown: the same verdict does not re-fire...
            clock.now += 0.15
            assert mgr.diagnose() == []
            clock.now += 0.1
            # ...until the cooldown elapses
            assert len(mgr.diagnose()) == 1
        finally:
            set_default_event_logger(None)
            store.close()


@pytest.fixture
def observatory_master(monkeypatch):
    monkeypatch.setenv("DLROVER_TPU_STATUS_PORT", "0")
    from dlrover_tpu.master.master import LocalJobMaster

    m = LocalJobMaster(get_free_port(), node_num=2)
    m.prepare()
    yield m
    m.stop()


class TestStatusSurfaces:
    def test_job_status_rpc_and_http(self, observatory_master):
        m = observatory_master
        chan = MasterChannel(m.addr, node_id=0)
        try:
            chan.report(
                msg.TimelineEventsReport(
                    events=_step_events(0, 4, 0.05)
                )
            )
            chan.report(msg.HeartBeat(timestamp=time.time()))
            res = chan.get(msg.JobStatusRequest())
            assert res.available
            health = res.status["health"]
            assert [n["node"] for n in health["nodes"]] == [0]
            assert res.status["epoch"]["incarnation"] == m.incarnation
            assert "ledger" in res.status
            # the HTTP surface serves the same snapshot + metrics
            port = m.status_server.port
            js = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status", timeout=10
                ).read().decode()
            )
            assert [
                n["node"] for n in js["health"]["nodes"]
            ] == [0]
            text = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ).read().decode()
            assert "dlrover_tpu_node_health" in text
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/nope", timeout=10
                )
        finally:
            chan.close()

    def test_client_helper(self, observatory_master):
        from dlrover_tpu.agent.master_client import MasterClient

        client = MasterClient(observatory_master.addr, node_id=0)
        try:
            client.report_heartbeat()
            status = client.get_job_status()
            assert status is not None
            assert "health" in status
        finally:
            client.close()


class TestMasterWiring:
    def test_master_always_builds_the_observatory(self, monkeypatch):
        """No setting asked for: the master has a health engine that
        the timeline aggregator taps, the derived-signal operators
        beside the whole-job stagnation rule, and the status snapshot
        on the RPC — the HTTP port alone waits for
        ``DLROVER_TPU_STATUS_PORT``."""
        monkeypatch.delenv("DLROVER_TPU_STATUS_PORT", raising=False)
        from dlrover_tpu.master.diagnosis import (
            DataStallOperator,
            HangOperator,
            HangWatchdogOperator,
            MasterOverloadOperator,
            StragglerOperator,
        )
        from dlrover_tpu.master.master import LocalJobMaster

        m = LocalJobMaster(get_free_port(), node_num=1)
        try:
            assert m.health_engine is not None
            assert m.timeline_aggregator._health is m.health_engine
            assert m.master_health is not None
            kinds = {
                type(o) for o in m.diagnosis_manager.chain._operators
            }
            assert {
                StragglerOperator, DataStallOperator,
                HangWatchdogOperator, MasterOverloadOperator,
                HangOperator,
            } <= kinds
            m.prepare()
            assert m.status_server is None  # no port was asked for
            chan = MasterChannel(m.addr, node_id=0)
            try:
                res = chan.get(msg.JobStatusRequest())
                assert res.available is True
                assert {"health", "epoch", "master"} <= set(
                    res.status
                )
            finally:
                chan.close()
        finally:
            m.stop()


@pytest.mark.timeout(180)
def test_scenario_names_straggler_and_hang(tmp_path):
    """The acceptance loop: one slowed rank + one hung rank; the
    JobStatusRequest snapshot and the diagnosis conclusions name the
    right nodes with the right problems within the interval bound,
    and ``scripts/top.py --snapshot --out`` emits the same JSON."""
    from scripts.bench_observatory import run_scenario
    from scripts.top import main as top_main, render

    out_file = str(tmp_path / "top.json")
    probe_result = {}

    def probe(addr):
        rc = top_main(
            ["--master_addr", addr, "--snapshot", "--out", out_file]
        )
        probe_result["rc"] = rc

    result = run_scenario(
        nodes=4,
        straggler_node=2,
        hung_node=3,
        step_s=0.04,
        straggler_factor=3.0,
        interval=0.4,
        detect_within=3,
        timeout_s=60.0,
        probe=probe,
    )
    assert result["detected"], result
    assert result["within_bound"], result
    assert result["straggler_intervals"] is not None
    assert result["hang_intervals"] <= 3, result
    assert "straggler@2" in result["conclusions"]
    assert "hang@3" in result["conclusions"]
    assert result["node_statuses"][2] == "straggler"
    assert result["node_statuses"][3] == "hung"
    # the straggler never false-flags as hung: it still emits spans
    assert "hang@2" not in result["conclusions"]
    # top.py saw the same live master
    assert probe_result["rc"] == 0
    top_snapshot = json.loads(open(out_file).read())
    health = top_snapshot["health"]
    assert 2 in health["stragglers"]
    assert 3 in health["hangs"]
    problems = {
        (c["problem"], c["node_rank"])
        for c in top_snapshot.get("conclusions", [])
    }
    assert ("straggler", 2) in problems
    assert ("hang", 3) in problems
    # and the dashboard renders the same verdicts
    frame = render(top_snapshot)
    assert "HUNG" in frame and "SLOW" in frame


def test_top_render_smoke():
    from scripts.top import render

    status = {
        "health": {
            "job": "j",
            "median_step_time_s": 0.1,
            "nodes": [
                {
                    "node": 0, "status": "healthy", "step": 10,
                    "step_time_s": 0.1, "step_rate": 10.0,
                    "straggler_score": 1.0, "stall_share": {},
                    "restarts": 0, "faults": 0, "inc": 0,
                    "last_event_age_s": 0.5,
                },
                {
                    "node": 1, "status": "hung", "step": 4,
                    "step_time_s": 0.1, "step_rate": 0.0,
                    "straggler_score": 0.0,
                    "stall_share": {"host_fetch": 0.4},
                    "restarts": 1, "faults": 2, "inc": 1,
                    "last_event_age_s": 33.0,
                },
            ],
        },
        "ledger": {
            "goodput": 0.91, "useful_s": 9.1, "wall_s": 10.0,
            "loss_breakdown": {"restart": 0.5, "unattributed": 0.4},
        },
        "speed": {"global_step": 10},
        "conclusions": [
            {
                "t": time.time(), "problem": "hang",
                "action": "restart_process", "node_rank": 1,
                "cause": "no timeline event for 33s",
            }
        ],
    }
    frame = render(status)
    assert "goodput 0.910" in frame
    assert "HUNG" in frame
    assert "host_fetch:40%" in frame
    assert "restart_process" in frame
