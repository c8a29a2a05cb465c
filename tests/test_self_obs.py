"""Control-plane self-telemetry (ISSUE 13): histogram metric type,
servicer self-instrumentation, journal/datastore health, the
MasterHealth overload deriver, the metric surface of a master with
and of a servicer without a collector, and the fleet-bench smoke."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

from dlrover_tpu.common import messages as msg  # noqa: E402
from dlrover_tpu.common.comm import MasterChannel  # noqa: E402
from dlrover_tpu.common.env import get_free_port  # noqa: E402
from dlrover_tpu.observability.metrics import (  # noqa: E402
    SIZE_BOUNDS,
    Histogram,
    MetricsRegistry,
    log_bounds,
)


# --------------------------------------------------------------------------
# histogram bucket math + text-format rendering
# --------------------------------------------------------------------------


class TestHistogram:
    def test_log_bounds_geometric(self):
        bounds = log_bounds(0.001, 2.0, 4)
        assert bounds == (0.001, 0.002, 0.004, 0.008)

    def test_bucket_assignment_and_cumulative_counts(self):
        hist = Histogram(bounds=(0.1, 1.0, 10.0))
        for value in (0.05, 0.1, 0.5, 5.0, 100.0):
            hist.observe(value)
        # non-cumulative internals: (<=0.1)=2, (<=1.0)=1, (<=10)=1,
        # +Inf=1
        assert hist.counts == [2, 1, 1, 1]
        assert hist.count == 5
        assert hist.sum == pytest.approx(105.65)

    def test_quantile_upper_bound_estimate(self):
        hist = Histogram(bounds=(0.001, 0.01, 0.1, 1.0))
        for _ in range(99):
            hist.observe(0.005)  # lands in the 0.01 bucket
        hist.observe(0.5)  # the 1.0 bucket
        assert hist.quantile(0.5) == 0.01
        assert hist.quantile(0.99) == 0.01
        assert hist.quantile(1.0) == 1.0
        # past the last finite bound: conservative, never invented
        tail = Histogram(bounds=(0.1,))
        tail.observe(99.0)
        assert tail.quantile(0.99) == 0.1

    def test_empty_quantile_is_zero(self):
        assert Histogram().quantile(0.99) == 0.0

    def test_registry_renders_prometheus_text(self):
        reg = MetricsRegistry(path="/tmp/_unused_self_obs.prom")
        reg.observe_histogram(
            "my_latency_seconds", 0.005,
            labels={"kind": "Get"}, bounds=(0.001, 0.01, 0.1),
        )
        reg.observe_histogram(
            "my_latency_seconds", 0.05,
            labels={"kind": "Get"},
        )
        text = reg.render_text()
        # cumulative _bucket lines with le appended to the labels
        assert (
            'my_latency_seconds_bucket{kind="Get",le="0.001"} 0'
            in text
        )
        assert (
            'my_latency_seconds_bucket{kind="Get",le="0.01"} 1'
            in text
        )
        assert (
            'my_latency_seconds_bucket{kind="Get",le="0.1"} 2'
            in text
        )
        assert (
            'my_latency_seconds_bucket{kind="Get",le="+Inf"} 2'
            in text
        )
        assert 'my_latency_seconds_sum{kind="Get"} 0.055' in text
        assert 'my_latency_seconds_count{kind="Get"} 2' in text

    def test_registry_renders_unlabeled_histogram(self):
        reg = MetricsRegistry(path="/tmp/_unused_self_obs2.prom")
        reg.observe_histogram("h", 1.0, bounds=(2.0,))
        text = reg.render_text()
        assert 'h_bucket{le="2"} 1' in text
        assert 'h_bucket{le="+Inf"} 1' in text
        assert "h_sum 1" in text
        assert "h_count 1" in text

    def test_bounds_immutable_after_first_observe(self):
        reg = MetricsRegistry(path="/tmp/_unused_self_obs3.prom")
        reg.observe_histogram("h2", 1.0, bounds=(2.0,))
        reg.observe_histogram("h2", 1.0, bounds=(99.0, 100.0))
        hist = reg.histogram("h2")
        assert hist.bounds == (2.0,)
        assert hist.count == 2

    def test_flush_includes_histograms_with_stamp(self, tmp_path):
        path = str(tmp_path / "m.prom")
        reg = MetricsRegistry(path=path)
        reg.observe_histogram("h3", 0.5, bounds=(1.0,))
        reg.flush()
        content = open(path).read()
        line = next(
            ln for ln in content.splitlines()
            if ln.startswith('h3_bucket{le="1"}')
        )
        # value + trailing flush timestamp (staleness eviction)
        assert len(line.split()) == 3

    def test_size_bounds_cover_payloads(self):
        assert SIZE_BOUNDS[0] == 64.0
        assert SIZE_BOUNDS[-1] >= 1e9


# --------------------------------------------------------------------------
# servicer self-instrumentation
# --------------------------------------------------------------------------


def _make_servicer(telemetry=None):
    from dlrover_tpu.common.constants import RendezvousName
    from dlrover_tpu.master.kv_store import KVStoreService
    from dlrover_tpu.master.rendezvous import (
        ElasticTrainingRendezvousManager,
    )
    from dlrover_tpu.master.servicer import MasterServicer
    from dlrover_tpu.master.shard.task_manager import TaskManager

    kv = KVStoreService()
    servicer = MasterServicer(
        task_manager=TaskManager(),
        rdzv_managers={
            RendezvousName.ELASTIC_TRAINING:
                ElasticTrainingRendezvousManager(),
        },
        kv_store=kv,
        telemetry=telemetry,
    )
    return servicer, kv


def _envelope(message):
    return msg.Envelope(
        node_id=0,
        node_type="worker",
        data=msg.serialize_message(message),
    )


class TestServicerTelemetry:
    def _telemetry(self, tmp_path, pool=8):
        from dlrover_tpu.observability.self_telemetry import (
            MasterSelfTelemetry,
        )

        registry = MetricsRegistry(path=str(tmp_path / "m.prom"))
        return MasterSelfTelemetry(
            registry=registry, pool_size=pool
        ), registry

    def test_rpc_kinds_latency_and_sizes(self, tmp_path):
        tel, reg = self._telemetry(tmp_path)
        servicer, kv = _make_servicer(tel)
        servicer.report(
            _envelope(msg.KeyValuePair(key="a", value=b"x" * 100))
        )
        servicer.get(_envelope(msg.KeyValuePair(key="a")))
        stats = tel.rpc_stats()
        assert set(stats) == {"KeyValuePair"}
        assert stats["KeyValuePair"]["count"] == 2
        assert stats["KeyValuePair"]["p99_ms"] >= 0
        # request AND response sizes landed
        req = reg.histogram(
            "dlrover_tpu_master_rpc_request_bytes",
            labels={"kind": "KeyValuePair"},
        )
        resp = reg.histogram(
            "dlrover_tpu_master_rpc_response_bytes",
            labels={"kind": "KeyValuePair"},
        )
        assert req is not None and req.count == 2
        assert resp is not None and resp.count == 2
        assert req.sum > 100  # the 100-byte value rode the request

    def test_inflight_returns_to_zero_even_on_handler_error(
        self, tmp_path
    ):
        tel, _reg = self._telemetry(tmp_path)
        servicer, _kv = _make_servicer(tel)
        # a report whose handler raises still answers (BoolResponse
        # success=False) and must release the in-flight slot
        servicer._task_manager = None
        res = servicer.report(
            _envelope(
                msg.DatasetShardParams(dataset_name="x",
                                       dataset_size=1)
            )
        )
        assert res.success is False
        assert tel.occupancy() == 0.0

    def test_parked_and_rejected_waits(self, tmp_path):
        tel, _reg = self._telemetry(tmp_path)
        servicer, kv = _make_servicer(tel)
        seen = {}

        def _park():
            servicer.get(
                _envelope(
                    msg.KVWaitRequest(key="nope", wait_timeout=1.0)
                )
            )

        t = threading.Thread(target=_park, daemon=True)
        t.start()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            with tel._lock:
                seen["parked"] = tel._parked
            if seen["parked"] == 1:
                break
            time.sleep(0.01)
        assert seen["parked"] == 1
        # exhaust the slots: the next wait degrades + counts
        for _ in range(servicer.max_parked_waits):
            servicer._wait_slots.acquire(blocking=False)
        servicer.get(
            _envelope(msg.KVWaitRequest(key="k", wait_timeout=5.0))
        )
        assert tel.rejected_waits == 1
        kv.set("nope", b"wake")
        t.join(timeout=5.0)
        with tel._lock:
            assert tel._parked == 0

    def test_wait_kinds_excluded_from_window_p99(self, tmp_path):
        """A parked long-poll's latency is its wait window — folding
        it into the deriver's p99 would trip a permanent spurious
        rpc_p99 overload on a healthy idle fleet."""
        tel, _reg = self._telemetry(tmp_path)
        for _ in range(10):
            tel.rpc_begin()
            tel.rpc_end("KVWaitRequest", 5.0, 10, 10)
            tel.rpc_begin()
            tel.rpc_end("WaitingNodeNumRequest", 30.0, 10, 10)
            tel.rpc_begin()
            tel.rpc_end("HeartBeat", 0.001, 10, 10)
        assert tel.window_p99() < 0.5
        # the wait kinds still keep their per-kind histograms
        assert tel.rpc_stats()["KVWaitRequest"]["count"] == 10

    def test_window_p99_needs_min_samples(self, tmp_path):
        """Below MIN_P99_SAMPLES the p99 reads 0.0: with a handful
        of points ``int(n*0.99)`` is the maximum, and one isolated
        outlier on a near-idle master must not sustain a spurious
        overload verdict."""
        tel, _reg = self._telemetry(tmp_path)
        for _ in range(tel.MIN_P99_SAMPLES - 1):
            tel.rpc_begin()
            tel.rpc_end("HeartBeat", 2.0, 1, 1)
        assert tel.window_p99() == 0.0
        tel.rpc_begin()
        tel.rpc_end("HeartBeat", 2.0, 1, 1)
        assert tel.window_p99() == 2.0

    def test_fenced_report_skips_deserialization(self, tmp_path):
        """Fence FIRST: a stale client whose payload no longer
        unpickles must still get its typed StaleEpoch (telemetry
        labels it as such), not a deserialization crash."""
        tel, _reg = self._telemetry(tmp_path)
        servicer, _kv = _make_servicer(tel)
        servicer.job_epoch = 3
        envelope = msg.Envelope(
            node_id=0,
            node_type="worker",
            data=b"\x80\x05NOT-A-PICKLE",
            job_epoch=1,
        )
        res = servicer.report(envelope)
        assert isinstance(res, msg.StaleEpoch)
        assert res.job_epoch == 3
        assert tel.rpc_stats()["StaleEpoch"]["count"] == 1
        assert tel.occupancy() == 0.0

    def test_master_section_in_job_status(self, tmp_path):
        from dlrover_tpu.observability.health import HealthEngine

        tel, _reg = self._telemetry(tmp_path)
        servicer, _kv = _make_servicer(tel)
        servicer._health_engine = HealthEngine(job="t")
        res = servicer._job_status(msg.JobStatusRequest())
        master = res.status["master"]
        assert master["pool"]["size"] == 8
        assert "rpc" in master and "state_rows" in master

    def test_workers_env_sizes_pool_and_parked_cap(
        self, monkeypatch
    ):
        monkeypatch.setenv("DLROVER_TPU_MASTER_WORKERS", "10")
        servicer, _kv = _make_servicer()
        assert servicer.max_parked_waits == 5
        from dlrover_tpu.common.env import master_workers

        assert master_workers() == 10


# --------------------------------------------------------------------------
# journal & datastore health
# --------------------------------------------------------------------------


class TestDatastoreHealth:
    def test_journal_lag_under_stalled_flusher(self, tmp_path):
        """A stalled flusher must surface as queue depth + journal
        lag (rows enqueued minus rows flushed) — the 'claimed
        durability a crash would lose' number."""
        from dlrover_tpu.master.datastore import BrainDatastore
        from dlrover_tpu.observability.self_telemetry import (
            MasterSelfTelemetry,
        )

        store = BrainDatastore(str(tmp_path / "b.db"))
        release = threading.Event()
        real_write = store._write_batch
        store._write_batch = (
            lambda batch: (release.wait(10.0), real_write(batch))
        )
        try:
            for i in range(5):
                store.record_speed("j", 2, float(i))
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                if store.health()["lag_rows"] >= 5:
                    break
                time.sleep(0.01)
            health = store.health()
            assert health["lag_rows"] >= 5
            assert health["queue_cap"] == store.MAX_PENDING
            assert health["flusher_alive"] is True
            # the gauge surface mirrors it
            registry = MetricsRegistry(
                path=str(tmp_path / "m.prom")
            )
            tel = MasterSelfTelemetry(registry=registry, pool_size=4)
            tel.attach(datastore=store)
            tel.refresh_gauges()
            text = registry.render_text()
            assert "dlrover_tpu_journal_lag_rows 5" in text
            assert "dlrover_tpu_datastore_queue_depth" in text
        finally:
            release.set()
            store.close()
        # drained on close: lag returns to zero
        assert store.health()["lag_rows"] == 0

    def test_every_flush_lands_in_the_histograms(
        self, tmp_path, monkeypatch
    ):
        """One observation a flushed batch in
        ``dlrover_tpu_datastore_flush_seconds`` (the commit latency)
        and in ``..._flush_rows`` (its size): two drained bursts are
        two batches, their rows summed."""
        from dlrover_tpu.observability import metrics as m
        from dlrover_tpu.master.datastore import BrainDatastore

        registry = MetricsRegistry(path=str(tmp_path / "m.prom"))
        monkeypatch.setattr(m, "_default_registry", registry)
        store = BrainDatastore(str(tmp_path / "b.db"))
        for i in range(3):
            store.record_speed("j", 2, float(i))
        store._drain()
        for i in range(4):
            store.record_speed("j", 2, float(i))
        store.close()
        lines = registry.render_text().splitlines()

        def value(name):
            (line,) = [ln for ln in lines if ln.startswith(name + " ")]
            return float(line.split()[-1])

        batches = value("dlrover_tpu_datastore_flush_seconds_count")
        assert batches >= 2
        assert value("dlrover_tpu_datastore_flush_rows_count") == batches
        assert value("dlrover_tpu_datastore_flush_rows_sum") == 7.0

    def test_snapshot_health_from_journal(self, tmp_path):
        from dlrover_tpu.master.datastore import BrainDatastore
        from dlrover_tpu.master.failover import ControlPlaneJournal
        from dlrover_tpu.master.kv_store import KVStoreService

        store = BrainDatastore(str(tmp_path / "b.db"))
        kv = KVStoreService()
        journal = ControlPlaneJournal(
            store, "j", kv_store=kv, snapshot_interval_s=3600
        )
        try:
            assert journal.health()["snapshot_age_s"] is None
            journal.snapshot_now()
            health = journal.health()
            assert health["snapshot_age_s"] is not None
            assert health["snapshot_age_s"] < 5.0
            assert health["snapshot_duration_s"] >= 0.0
        finally:
            store.close()


# --------------------------------------------------------------------------
# MasterHealth deriver: streak / cooldown table
# --------------------------------------------------------------------------


class _FakeTelemetry:
    def __init__(self):
        self.p99 = 0.0
        self.ds = {}
        self.occ = 0.0
        self.rejected_waits = 0

    def window_p99(self):
        return self.p99

    def datastore_health(self):
        return self.ds

    def occupancy(self):
        return self.occ


class TestMasterHealthDeriver:
    def _health(self, tel, **kw):
        from dlrover_tpu.observability.health import MasterHealth

        kw.setdefault("sustain", 2)
        kw.setdefault("cooldown_s", 0.3)
        kw.setdefault("p99_s", 0.5)
        return MasterHealth(tel, **kw)

    def test_streak_then_fire_then_cooldown(self):
        tel = _FakeTelemetry()
        mh = self._health(tel)
        tel.p99 = 1.0  # breached
        assert mh.evaluate() == []  # streak 1 < sustain 2
        fired = mh.evaluate()
        assert [v["reason"] for v in fired] == ["rpc_p99"]
        assert fired[0]["value"] == 1.0
        assert fired[0]["threshold"] == 0.5
        assert fired[0]["streak"] == 2
        # cooldown: still breached, but no re-fire (and the streak
        # was consumed by acting)
        assert mh.evaluate() == []
        assert mh.evaluate() == []
        time.sleep(0.35)
        # past cooldown the sustained breach re-fires
        assert [v["reason"] for v in mh.evaluate()] == ["rpc_p99"]

    def test_recovery_resets_streak(self):
        tel = _FakeTelemetry()
        mh = self._health(tel)
        tel.p99 = 1.0
        assert mh.evaluate() == []
        tel.p99 = 0.0  # recovered: streak cleared
        assert mh.evaluate() == []
        tel.p99 = 1.0  # breach must re-sustain from scratch
        assert mh.evaluate() == []
        assert len(mh.evaluate()) == 1

    def test_queue_lag_and_rejects_reasons(self):
        tel = _FakeTelemetry()
        mh = self._health(tel)
        tel.ds = {
            "queue_cap": 100,
            "queue_depth": 90,
            "lag_rows": 9000,
        }
        tel.rejected_waits = 3
        mh.evaluate()
        tel.rejected_waits = 6  # +3 this interval
        reasons = {v["reason"] for v in mh.evaluate()}
        assert reasons == {
            "queue_depth", "journal_lag", "parked_rejects",
        }

    def test_pool_saturation_reason(self):
        tel = _FakeTelemetry()
        mh = self._health(tel)
        tel.occ = 0.95
        mh.evaluate()
        assert [v["reason"] for v in mh.evaluate()] == [
            "pool_saturated"
        ]

    def test_fire_emits_master_overload_instant(self, tmp_path):
        from dlrover_tpu.observability.events import (
            EventLogger,
            read_events,
            set_default_event_logger,
        )

        events_file = str(tmp_path / "e.jsonl")
        set_default_event_logger(EventLogger(path=events_file))
        try:
            tel = _FakeTelemetry()
            mh = self._health(tel, sustain=1)
            tel.p99 = 2.0
            assert len(mh.evaluate()) == 1
            recs = [
                e for e in read_events(events_file)
                if e["name"] == "master_overload"
            ]
            assert len(recs) == 1
            labels = recs[0]["labels"]
            assert labels["reason"] == "rpc_p99"
            assert labels["value"] == 2.0
            assert labels["threshold"] == 0.5
        finally:
            set_default_event_logger(None)

    def test_operator_turns_verdicts_into_conclusions(self):
        from dlrover_tpu.master.diagnosis import (
            DiagnosisManager,
            MasterOverloadOperator,
        )

        tel = _FakeTelemetry()
        mh = self._health(tel, sustain=1)
        tel.p99 = 2.0
        mgr = DiagnosisManager(
            operators=[MasterOverloadOperator(mh)], interval=3600
        )
        fresh = mgr.diagnose()
        assert len(fresh) == 1
        # per-reason problem key: a later journal_lag breach must not
        # be swallowed by the manager's (problem, node, action)
        # cooldown dedupe because rpc_p99 fired first
        assert fresh[0].problem == "master_overload:rpc_p99"
        assert fresh[0].action == "none"
        assert "rpc_p99" in fresh[0].cause


# --------------------------------------------------------------------------
# the metric surface with and without a collector
# --------------------------------------------------------------------------

SELF_OBS_PREFIXES = (
    "dlrover_tpu_master_",
    "dlrover_tpu_datastore_",
    "dlrover_tpu_journal_",
    "dlrover_tpu_snapshot_",
)


class TestSelfObsSurface:
    def test_servicer_without_collector_records_nothing(
        self, monkeypatch, tmp_path
    ):
        """A servicer built without a collector (a bench's bare
        master, a unit test) serves the same RPCs and leaves not ONE
        self-telemetry series behind; the master always builds one
        (next test)."""
        from dlrover_tpu.observability import metrics as m

        registry = MetricsRegistry(path=str(tmp_path / "m.prom"))
        monkeypatch.setattr(m, "_default_registry", registry)
        servicer, kv = _make_servicer()
        assert servicer.report(
            _envelope(msg.KeyValuePair(key="a", value=b"1"))
        ).success
        assert servicer.get(
            _envelope(msg.KeyValuePair(key="a"))
        ).value == b"1"
        # a parked wait too: the cap is kept without the gauges
        out = servicer.get(
            _envelope(msg.KVWaitRequest(key="nope", wait_timeout=0.05))
        )
        assert out.value == b""
        res = servicer.get(_envelope(msg.JobStatusRequest()))
        assert res.available is False  # no health engine either
        assert servicer.rpc_count == 4
        text = registry.render_text()
        offenders = [
            line
            for line in text.splitlines()
            if line.startswith(SELF_OBS_PREFIXES)
        ]
        assert offenders == []

    def test_surface_present_on(self, monkeypatch, tmp_path):
        from dlrover_tpu.observability import metrics as m
        from dlrover_tpu.master.master import LocalJobMaster

        registry = MetricsRegistry(path=str(tmp_path / "m.prom"))
        monkeypatch.setattr(m, "_default_registry", registry)
        master = LocalJobMaster(get_free_port(), node_num=1)
        assert master.master_telemetry is not None
        master.prepare()
        chan = MasterChannel(master.addr, node_id=0)
        try:
            chan.report(msg.HeartBeat(timestamp=time.time()))
            res = chan.get(msg.JobStatusRequest())
            assert "master" in res.status
            assert res.status["master"]["rpc"]["HeartBeat"][
                "count"
            ] == 1
        finally:
            chan.close()
            master.stop()
        master.master_telemetry.refresh_gauges()
        text = registry.render_text()
        assert (
            "dlrover_tpu_master_rpc_latency_seconds_bucket" in text
        )
        assert "dlrover_tpu_master_worker_pool_size" in text


# --------------------------------------------------------------------------
# status server: concurrent scrape
# --------------------------------------------------------------------------


def test_concurrent_scrape_not_blocked_by_slow_handler(tmp_path):
    """A slow /status consumer must not block a concurrent /metrics
    scrape (threaded server, one handler thread per request)."""
    from dlrover_tpu.observability.status_server import StatusServer

    registry = MetricsRegistry(path=str(tmp_path / "m.prom"))
    registry.set_gauge("scrape_probe", 1.0)
    entered = threading.Event()

    def _slow_snapshot():
        entered.set()
        time.sleep(1.5)
        return {"slow": True}

    server = StatusServer(
        0, registry=registry, snapshot_fn=_slow_snapshot,
        host="127.0.0.1",
    )
    server.start()
    try:
        port = server.port
        slow = threading.Thread(
            target=urllib.request.urlopen,
            args=(f"http://127.0.0.1:{port}/status",),
            kwargs={"timeout": 10},
            daemon=True,
        )
        slow.start()
        assert entered.wait(5.0)  # the slow handler is IN its sleep
        t0 = time.monotonic()
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
        elapsed = time.monotonic() - t0
        assert "scrape_probe 1" in text
        assert elapsed < 1.0  # did not queue behind the slow scrape
        slow.join(timeout=10.0)
    finally:
        server.stop()


# --------------------------------------------------------------------------
# top.py master pane
# --------------------------------------------------------------------------


def test_top_renders_master_pane():
    import top

    frame = top.render(
        {
            "health": {"job": "j", "nodes": []},
            "master": {
                "pool": {
                    "size": 64,
                    "busy": 7,
                    "parked_waits": 5,
                    "rejected_waits": 2,
                    "occupancy": 0.1094,
                },
                "rpc": {
                    "HeartBeat": {
                        "count": 10, "p50_ms": 0.1, "p99_ms": 0.4,
                    },
                    "KVWaitRequest": {
                        "count": 3, "p50_ms": 400.0,
                        "p99_ms": 900.0,
                    },
                },
                "rpc_p99_window_ms": 1.5,
                "state_rows": {"kv": 12, "tasks": 400},
                "datastore": {
                    "queue_depth": 9, "queue_cap": 10000,
                    "lag_rows": 9,
                },
                "journal": {"snapshot_age_s": 12.0},
            },
        }
    )
    assert "master: pool 7/64 busy (5 parked, 2 rejected)" in frame
    assert "wb queue 9/10000 lag 9 rows" in frame
    assert "snapshot 12s ago" in frame
    assert "KVWaitRequest p50=400ms p99=900ms n=3" in frame
    assert "state rows: kv=12  tasks=400" in frame
    # pre-self-obs master (no section): the pane is simply absent
    frame2 = top.render({"health": {"job": "j", "nodes": []}})
    assert "master: pool" not in frame2


# --------------------------------------------------------------------------
# schema lint: histogram metric names + master_overload labels
# --------------------------------------------------------------------------

LINT = os.path.join(REPO, "scripts", "check_event_schema.py")


def _run_lint(*args):
    return subprocess.run(
        [sys.executable, LINT, *args],
        capture_output=True,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=120,
    )


def test_lint_catches_undeclared_histogram_metric():
    """``observe_histogram`` is policed like set_gauge/inc_counter:
    the self-obs names are declared, a near-miss typo is not."""
    probe = os.path.join(
        REPO, "dlrover_tpu", "_lint_probe3_delete_me.py"
    )
    with open(probe, "w") as f:
        f.write(
            "def f(reg):\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_master_rpc_latency_seconds', 1.0)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_datastore_flush_seconds', 1.0)\n"
            "    reg.set_gauge('dlrover_tpu_journal_lag_rows', 1)\n"
            "    reg.observe_histogram("
            "'dlrover_tpu_master_rpc_latency_second', 1.0)\n"
        )
    try:
        proc = _run_lint(probe)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "event_schema_violations=1" in proc.stdout, (
            proc.stdout
        )
        assert (
            "dlrover_tpu_master_rpc_latency_second" in proc.stdout
        )
    finally:
        os.unlink(probe)


def test_lint_enforces_master_overload_labels(tmp_path):
    """An overload verdict without the breached signal and the
    numbers is unactionable — reason/value/threshold are REQUIRED."""
    bad = tmp_path / "bad_overload.py"
    bad.write_text(
        "events = None\n"
        "def f(events):\n"
        "    events.instant('master_overload', reason='rpc_p99')\n"
        "    events.instant('master_overload', reason='rpc_p99',\n"
        "                   value=1.0, threshold=0.5)\n"
    )
    proc = _run_lint(str(bad))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=1" in proc.stdout, proc.stdout
    assert (
        "missing required label(s) ['value', 'threshold']"
        in proc.stdout
    )


# --------------------------------------------------------------------------
# fleet bench smoke (tier-1, budget-scaled)
# --------------------------------------------------------------------------


@pytest.mark.timeout(120)
def test_fleet_bench_smoke_small_n():
    """The fleet simulator at tiny N: real gRPC master, real agent
    traffic, per-RPC-kind p50/p99 read back from the master's OWN
    histograms, knee fields present, partial checkpoint per point."""
    from bench_control_plane import find_knee, run_fleet

    seen = []
    result = run_fleet(
        [4, 8],
        duration_s=1.2,
        period_s=0.3,
        checkpoint=lambda partial: seen.append(
            len(partial["points"])
        ),
    )
    assert seen == [1, 2]  # per-N checkpoint (the early-flush rule)
    assert [p["agents"] for p in result["points"]] == [4, 8]
    for pt in result["points"]:
        assert pt["agent_errors"] == 0, pt["error_sample"]
        assert pt["rps"] > 0
        kinds = set(pt["rpc"])
        assert {
            "HeartBeat",
            "KeyValuePair",
            "TimelineEventsReport",
            "TaskRequest",
            "WaitingNodeNumRequest",
        } <= kinds
        for stats in pt["rpc"].values():
            assert stats["count"] > 0
            assert stats["p99_ms"] >= stats["p50_ms"] >= 0
        assert pt["pool"]["size"] > 0
        assert pt["state_rows"]["kv"] >= pt["agents"]
    knee = result["knee"]
    assert knee["knee_agents"] in (4, 8)
    assert "saturated" in knee
    # the heuristic itself, on a synthetic saturated sweep
    synthetic = find_knee(
        [
            {"agents": 4, "p99_ms": 4.0},
            {"agents": 8, "p99_ms": 6.0},
            {"agents": 16, "p99_ms": 400.0},
        ]
    )
    assert synthetic["knee_agents"] == 8
    assert synthetic["saturated"] is True


@pytest.mark.timeout(120)
def test_fleet_overload_names_master_within_three_intervals():
    """The acceptance loop: a shrunken pool under parked long-polls
    yields a master_overload conclusion + instant within ~3
    derivation intervals (0.5 slack absorbs CI scheduler noise; the
    bench records the exact figure)."""
    from bench_control_plane import run_overload

    out = run_overload(
        n_agents=6, workers=2, interval_s=0.5, sustain=2
    )
    assert out["detected"], out
    assert out["detect_intervals"] <= 3.5, out
    assert out["instants"] >= 1
    assert "parked_rejects" in out["reasons"] or out["reasons"]
