"""Live device-time attribution + the diagnosis-triggered deep
capture arm: the peak-FLOPs table, the category bucketing, the
background attribution worker, the HealthEngine's per-node
mfu/device-share derivations, conclusions citing the dominant
category, the CaptureCoordinator lifecycle (cooldown, directive
piggyback, failover re-arm), the end-to-end capture path against a
real LocalJobMaster, the Trainer continuous leg, the overhead bound,
and the ``DLROVER_TPU_PROFILE=0`` kill-switch pins."""

import json
import os
import sys
import time

import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.observability.attribution import (
    AttributionWorker,
    bucket_category,
    bucket_shares,
    dominant_category,
    trace_flops_per_step,
)
from dlrover_tpu.observability.events import (
    EventLogger,
    read_events,
    set_default_event_logger,
)
from dlrover_tpu.observability.health import HealthEngine
from dlrover_tpu.observability.metrics import MetricsRegistry
from dlrover_tpu.observability.profiler import (
    device_peak_flops,
    peak_flops_for_kind,
)
from dlrover_tpu.observability.trace import OpAggregate, TraceReport


class TestPeakFlopsTable:
    def test_known_kinds(self):
        assert peak_flops_for_kind("TPU v5 lite") == 197e12
        assert peak_flops_for_kind("TPU v5e") == 197e12
        assert peak_flops_for_kind("TPU v5") == 459e12
        assert peak_flops_for_kind("TPU v4") == 275e12
        assert peak_flops_for_kind("TPU v3") == 123e12
        assert peak_flops_for_kind("TPU v6e") == 918e12

    def test_unknown_kind_is_refused(self, monkeypatch):
        with pytest.raises(LookupError, match="weird accelerator"):
            peak_flops_for_kind("weird accelerator")
        # no override, CPU device kind: no guessed peak either
        monkeypatch.delenv("DLROVER_TPU_PEAK_FLOPS", raising=False)
        with pytest.raises(LookupError):
            device_peak_flops()

    def test_env_override_wins(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_PEAK_FLOPS", "123.5e12")
        assert device_peak_flops() == 123.5e12
        monkeypatch.setenv("DLROVER_TPU_PEAK_FLOPS", "not-a-number")
        with pytest.raises(ValueError):  # malformed: refused, not skipped
            device_peak_flops()

    def test_bench_mfu_uses_the_same_table(self, monkeypatch):
        import bench_mfu

        monkeypatch.setenv("DLROVER_TPU_PEAK_FLOPS", "42e12")

        class FakeDev:
            device_kind = "TPU v4"

        peak, kind = bench_mfu._chip_peak_flops(FakeDev())
        assert peak == 42e12  # the shared function's env override
        monkeypatch.delenv("DLROVER_TPU_PEAK_FLOPS")
        peak, kind = bench_mfu._chip_peak_flops(FakeDev())
        assert peak == 275e12
        assert "v4" in kind


def _report(
    by_category=None, total=0.0, steps=2, mean_step_us=0.0,
    flops=0.0,
):
    r = TraceReport(
        total_device_us=total,
        step_count=steps,
        mean_step_us=mean_step_us,
        by_category=dict(by_category or {}),
    )
    if flops:
        r.top_ops = [
            OpAggregate(
                key="k", category="convolution fusion",
                time_us=total, flops=flops,
            )
        ]
    return r


class TestBucketShares:
    def test_bucket_category(self):
        assert bucket_category("convolution fusion") == "compute"
        assert bucket_category("loop fusion") == "compute"
        assert bucket_category("all-reduce") == "collective"
        assert bucket_category("all-gather-start") == "collective"
        assert bucket_category("copy-done") == "copy"
        assert bucket_category("data formatting") == "copy"
        assert bucket_category("infeed") == "infeed"

    def test_shares_sum_to_one_with_idle(self):
        # 800us busy inside a 2x500us step window -> 20% idle
        r = _report(
            by_category={
                "convolution fusion": 500.0,
                "all-reduce": 200.0,
                "copy-done": 100.0,
            },
            total=800.0,
            steps=2,
            mean_step_us=500.0,
        )
        shares = bucket_shares(r)
        assert shares["idle"] == pytest.approx(0.2, abs=1e-3)
        assert shares["compute"] == pytest.approx(0.5, abs=1e-3)
        assert shares["collective"] == pytest.approx(0.2, abs=1e-3)
        assert shares["copy"] == pytest.approx(0.1, abs=1e-3)
        assert sum(shares.values()) == pytest.approx(1.0, abs=1e-2)
        assert dominant_category(shares)[0] == "compute"

    def test_no_step_window_normalizes_over_device_time(self):
        r = _report(
            by_category={"copy-done": 300.0, "fusion": 100.0},
            total=400.0,
            steps=0,
            mean_step_us=0.0,
        )
        shares = bucket_shares(r)
        assert shares["idle"] == 0.0
        assert shares["copy"] == pytest.approx(0.75, abs=1e-3)
        assert dominant_category(shares) == ("copy", 0.75)

    def test_empty_report(self):
        shares = bucket_shares(_report())
        assert all(v == 0.0 for v in shares.values())
        assert dominant_category(shares) is None

    def test_trace_flops_fallback(self):
        r = _report(total=100.0, steps=2, flops=2e12)
        assert trace_flops_per_step(r) == pytest.approx(1e12)


class TestAttributionWorker:
    def _run(self, tmp_path, monkeypatch, report, mode="profile",
             flops_fn=None, artifact_dir=""):
        events_file = str(tmp_path / "events.jsonl")
        set_default_event_logger(
            EventLogger(path=events_file, job="j", node=5, rank=0)
        )
        trace_dir = str(tmp_path / "tracedir")
        os.makedirs(trace_dir, exist_ok=True)
        monkeypatch.setattr(
            "dlrover_tpu.observability.trace.parse_trace",
            lambda path: report,
        )
        try:
            worker = AttributionWorker(flops_fn=flops_fn)
            worker.submit(
                trace_dir, step=7, start_wall=time.time(),
                duration_s=0.5, steps=1, mode=mode,
                artifact_dir=artifact_dir,
            )
            worker.close()
        finally:
            set_default_event_logger(None)
        return read_events(events_file), worker

    def test_emits_step_profile_span(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_PEAK_FLOPS", "1e12")
        report = _report(
            by_category={"copy-done": 600.0, "fusion": 200.0},
            total=800.0, steps=1, mean_step_us=1000.0, flops=4e8,
        )
        recs, worker = self._run(tmp_path, monkeypatch, report)
        spans = [r for r in recs if r["name"] == "step_profile"]
        assert len(spans) == 1
        labels = spans[0]["labels"]
        assert labels["step"] == 7
        assert labels["share_copy"] == pytest.approx(0.6, abs=1e-3)
        assert labels["share_idle"] == pytest.approx(0.2, abs=1e-3)
        # step time comes from the trace window (1000us), flops from
        # the trace ops: 4e8 / 1e-3s = 4e11 FLOP/s = 0.4 TFLOP/s
        assert labels["tflops"] == pytest.approx(0.4, abs=0.01)
        # mfu against peak 1e12 x device_count
        import jax

        assert labels["mfu"] == pytest.approx(
            0.4 / jax.device_count(), abs=0.01
        )
        assert worker.last_profile["shares"]["copy"] == pytest.approx(
            0.6, abs=1e-3
        )
        # the trace dir was cleaned up
        assert not os.path.exists(str(tmp_path / "tracedir"))

    def test_cost_analysis_flops_win(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_PEAK_FLOPS", "1e12")
        report = _report(
            by_category={"fusion": 100.0}, total=100.0,
            steps=1, mean_step_us=1000.0, flops=1.0,
        )
        recs, _w = self._run(
            tmp_path, monkeypatch, report, flops_fn=lambda: 8e8
        )
        labels = [
            r for r in recs if r["name"] == "step_profile"
        ][0]["labels"]
        assert labels["tflops"] == pytest.approx(0.8, abs=0.01)

    def test_capture_mode_writes_artifact(self, tmp_path, monkeypatch):
        report = _report(
            by_category={"fusion": 100.0}, total=100.0,
            steps=1, mean_step_us=200.0,
        )
        adir = str(tmp_path / "captures")
        self._run(
            tmp_path, monkeypatch, report, mode="capture",
            artifact_dir=adir,
        )
        files = os.listdir(adir)
        assert len(files) == 1 and files[0].startswith("profile_")
        payload = json.loads(open(os.path.join(adir, files[0])).read())
        assert payload["step"] == 7
        assert "shares" in payload and "summary" in payload


def _profile_span(node, step, shares, mfu=0.2, tflops=10.0,
                  wall=None):
    labels = {"step": step, "mfu": mfu, "tflops": tflops}
    for cat, v in shares.items():
        labels[f"share_{cat}"] = v
    return {
        "name": "step_profile",
        "ph": "X",
        "wall": wall if wall is not None else time.time(),
        "mono": float(step),
        "dur": 0.1,
        "node": node,
        "rank": 0,
        "pid": 1,
        "labels": labels,
    }


class TestHealthAttribution:
    def test_snapshot_and_accessor(self):
        engine = HealthEngine(job="j")
        engine.observe_events(
            0,
            [
                _profile_span(
                    0, 4,
                    {"compute": 0.7, "collective": 0.1,
                     "copy": 0.1, "infeed": 0.05, "idle": 0.05},
                    mfu=0.35,
                )
            ],
        )
        engine.observe_events(
            1,
            [
                _profile_span(
                    1, 4,
                    {"compute": 0.3, "collective": 0.1,
                     "copy": 0.5, "infeed": 0.0, "idle": 0.1},
                    mfu=0.12,
                )
            ],
        )
        snap = {n["node"]: n for n in engine.snapshot()["nodes"]}
        assert snap[0]["mfu"] == 0.35
        assert snap[0]["dominant"]["category"] == "compute"
        assert snap[1]["dominant"] == {
            "category": "copy", "share": 0.5
        }
        att = engine.attribution()
        assert att[1] == ("copy", 0.5)
        assert att[0][0] == "compute"

    def test_stale_profile_does_not_regress(self):
        engine = HealthEngine(job="j")
        now = time.time()
        engine.observe_events(
            0, [_profile_span(0, 8, {"copy": 0.9}, wall=now)]
        )
        # an OLDER span arriving late (rotated-file tail) is ignored
        engine.observe_events(
            0,
            [_profile_span(0, 2, {"compute": 0.9}, wall=now - 50)],
        )
        assert engine.attribution()[0][0] == "copy"

    def test_gauges_only_with_profiles(self):
        registry = MetricsRegistry(flush_interval=1e9)
        engine = HealthEngine(job="j", registry=registry)
        engine.observe_events(
            0,
            [
                {
                    "name": "step", "ph": "X", "wall": time.time(),
                    "mono": 1.0, "dur": 0.1, "node": 0, "pid": 1,
                    "labels": {"step": 1},
                }
            ],
        )
        engine.refresh_gauges()
        text = registry.render_text()
        # profiler off: EXACTLY the pre-profiling series set
        assert "dlrover_tpu_node_mfu" not in text
        assert "dlrover_tpu_device_share" not in text
        engine.observe_events(
            0,
            [_profile_span(0, 2, {"compute": 0.8, "copy": 0.2},
                           mfu=0.31)],
        )
        engine.refresh_gauges()
        text = registry.render_text()
        assert 'dlrover_tpu_node_mfu{node="0"} 0.31' in text
        assert (
            'dlrover_tpu_device_share{category="compute",node="0"} '
            "0.8" in text
        )

    def test_snapshot_without_profiles_has_no_attribution_keys(self):
        engine = HealthEngine(job="j")
        engine.observe_events(
            0,
            [
                {
                    "name": "step", "ph": "X", "wall": time.time(),
                    "mono": 1.0, "dur": 0.1, "node": 0, "pid": 1,
                    "labels": {"step": 1},
                }
            ],
        )
        node = engine.snapshot()["nodes"][0]
        assert "mfu" not in node
        assert "device_share" not in node
        assert "dominant" not in node


class TestConclusionsCiteCategory:
    class _Engine:
        straggler_ratio = 1.5

        def __init__(self, att):
            self._att = att

        def stragglers(self):
            return [(3, 2.5)]

        def stall_shares(self):
            return {3: {"host_fetch": 0.6}}

        def attribution(self):
            return self._att

    def test_straggler_cause_names_dominant(self):
        from dlrover_tpu.master.diagnosis import StragglerOperator

        op = StragglerOperator(self._Engine({3: ("copy", 0.42)}))
        out = op.infer(None)
        assert "dominant device time: copy 42%" in out[0].cause

    def test_data_stall_cause_names_dominant(self):
        from dlrover_tpu.master.diagnosis import DataStallOperator

        op = DataStallOperator(self._Engine({3: ("infeed", 0.5)}))
        out = op.infer(None)
        assert "dominant device time: infeed 50%" in out[0].cause

    def test_engine_without_attribution_still_works(self):
        from dlrover_tpu.master.diagnosis import StragglerOperator

        class Bare:
            straggler_ratio = 1.5

            def stragglers(self):
                return [(1, 3.0)]

        out = StragglerOperator(Bare()).infer(None)
        assert out[0].problem == "straggler"
        assert "dominant" not in out[0].cause


class TestCaptureCoordinator:
    def test_request_delivery_and_cooldown(self):
        from dlrover_tpu.master.capture import CaptureCoordinator

        c = CaptureCoordinator(job="j", cooldown_s=0.3)
        cid = c.request(2, reason="hang")
        assert cid == 1
        # in-flight + cooldown: repeat conclusions are throttled
        assert c.request(2, reason="hang") is None
        directive = c.directives.take(2)
        assert directive == ("capture", "hang", 1)
        # consumed: nothing further rides the poll
        assert c.directives.take(2) is None
        # still throttled until the cooldown elapses (the request
        # consumed the window even though no result came back)
        assert c.request(2, reason="hang") is None
        time.sleep(0.35)
        assert c.request(2, reason="hang") == 2

    def test_result_recorded_and_durable(self, tmp_path):
        from dlrover_tpu.master.capture import CaptureCoordinator
        from dlrover_tpu.master.datastore import BrainDatastore

        store = BrainDatastore(str(tmp_path / "brain.db"))
        try:
            c = CaptureCoordinator(
                job="jx", datastore=store, cooldown_s=60.0
            )
            cid = c.request(1, reason="straggler")
            # in-flight shows as a pending entry on the surface
            assert c.latest()[1]["summary"] is None
            c.record_result(
                1,
                summary={"stack_dumps": 2},
                artifact="/tmp/a.json",
                capture_id=cid,
            )
            latest = c.latest()[1]
            assert latest["summary"] == {"stack_dumps": 2}
            assert latest["reason"] == "straggler"
            rows = store.profiles("jx")
            assert len(rows) == 1
            assert rows[0]["node"] == 1
            assert rows[0]["summary"] == {"stack_dumps": 2}
            assert rows[0]["artifact"] == "/tmp/a.json"
        finally:
            store.close()

    def test_journal_roundtrip_through_control_plane(self, tmp_path):
        """The `capture` component rides the real PR-7 journal: a
        second master incarnation recovering from the same Brain db
        re-arms the in-flight directive and keeps cooldown anchors."""
        from dlrover_tpu.master.capture import CaptureCoordinator
        from dlrover_tpu.master.datastore import BrainDatastore
        from dlrover_tpu.master.failover import ControlPlaneJournal

        store = BrainDatastore(str(tmp_path / "brain.db"))
        try:
            c1 = CaptureCoordinator(
                job="jj", datastore=store, cooldown_s=600.0
            )
            j1 = ControlPlaneJournal(store, "jj", capture=c1)
            j1.attach()
            cid = c1.request(2, reason="hang")
            assert cid is not None
            j1.detach()
            # incarnation 2: fresh coordinator, replay from the db
            c2 = CaptureCoordinator(
                job="jj", datastore=store, cooldown_s=600.0
            )
            j2 = ControlPlaneJournal(store, "jj", capture=c2)
            j2.recover()
            assert c2.directives.take(2) == ("capture", "hang", cid)
            assert c2.request(2, reason="hang") is None  # cooldown
        finally:
            store.close()

    def test_failover_rearms_in_flight(self):
        from dlrover_tpu.master.capture import CaptureCoordinator

        c1 = CaptureCoordinator(job="j", cooldown_s=60.0)
        cid = c1.request(4, reason="hang")
        state = c1.export_state()
        # the new incarnation: directives died with the old memory
        c2 = CaptureCoordinator(job="j", cooldown_s=60.0)
        c2.restore_state(state)
        assert c2.directives.take(4) == ("capture", "hang", cid)
        # cooldown anchor survived: no duplicate capture
        assert c2.request(4, reason="hang") is None
        # and the result still lands under the SAME id
        c2.record_result(4, summary={"ok": 1})
        assert c2.latest()[4]["id"] == cid


class TestWorkerCaptureHandler:
    def test_signal_sets_flag_and_dumps_stacks(self, tmp_path):
        import signal

        from dlrover_tpu.trainer.capture import (
            STACK_FILE_PREFIX,
            install_capture_handler,
            reset_capture,
            take_capture_request,
        )

        reset_capture()
        try:
            assert install_capture_handler(str(tmp_path)) is True
            assert take_capture_request() is False
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.time() + 5.0
            while time.time() < deadline:
                if take_capture_request():
                    break
                time.sleep(0.01)
            else:
                pytest.fail("capture flag never set")
            stack_path = os.path.join(
                str(tmp_path),
                f"{STACK_FILE_PREFIX}{os.getpid()}.txt",
            )
            # poll until the dump settles: an early read catches it
            # mid-write.  The contract asserted is "an all-thread
            # stack dump was written" — NOT that this test's frame is
            # in it: faulthandler caps the dump at ~100 threads, and
            # after thread-leaking suite neighbours the main thread
            # can legitimately fall past the cap.
            marker = "(most recent call first)"
            deadline = time.time() + 10.0
            text = ""
            while time.time() < deadline:
                try:
                    text = open(stack_path).read()
                except OSError:
                    text = ""
                if marker in text and "File " in text:
                    break
                time.sleep(0.05)
            assert marker in text, text[-2000:]
            assert "File " in text
        finally:
            reset_capture()


class TestAgentCaptureExecutor:
    """The real agent-side capture leg, against a fake client: worker
    artifacts + stack dumps are collected, one combined artifact is
    written, and the ProfileReport carries the digest."""

    def _agent(self, tmp_path):
        from dlrover_tpu.agent.training import (
            ElasticLaunchConfig,
            ElasticTrainingAgent,
        )

        class FakeClient:
            addr = "127.0.0.1:1"

            def __init__(self):
                self.profiles = []

            def report_profile(self, **kw):
                self.profiles.append(kw)
                return True

        client = FakeClient()
        agent = ElasticTrainingAgent(
            ElasticLaunchConfig(node_rank=5),
            entrypoint=["true"],
            client=client,
            start_ckpt_saver=False,
        )
        return agent, client

    def test_execute_capture_no_workers(self, tmp_path, monkeypatch):
        base = tmp_path / "captures"
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_DIR", str(base))
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_TIMEOUT_S", "0.5")
        agent, client = self._agent(tmp_path)
        # the agent namespaces the shared base by node rank
        cdir = base / "node_5"
        # pre-existing worker artifacts (as if the SIGUSR2'd workers
        # wrote them): one profile + one stack dump
        os.makedirs(cdir, exist_ok=True)
        # written BEFORE t0 -> must be ignored (stale capture)
        with open(cdir / "profile_999_1.json", "w") as f:
            json.dump({"pid": 999, "step": 1, "shares": {}}, f)
        stale = cdir / "stacks_999.txt"
        stale.write_text("old dump")
        old = time.time() - 3600
        os.utime(cdir / "profile_999_1.json", (old, old))
        os.utime(stale, (old, old))
        summary = agent._execute_capture("hang", 7)
        assert summary["capture_id"] == 7
        assert summary["workers_signalled"] == 0
        assert summary["profiles_collected"] == 0
        assert summary["stack_dumps"] == 0
        assert len(client.profiles) == 1
        report = client.profiles[0]
        assert report["node_rank"] == 5
        assert report["reason"] == "hang"
        assert report["capture_id"] == 7
        artifact = report["artifact"]
        assert os.path.exists(artifact)
        payload = json.loads(open(artifact).read())
        assert payload["node"] == 5

    def test_execute_capture_collects_artifacts(
        self, tmp_path, monkeypatch
    ):
        import subprocess
        import sys as _sys

        base = tmp_path / "captures"
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_DIR", str(base))
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_TIMEOUT_S", "5")
        agent, client = self._agent(tmp_path)
        cdir = base / "node_5"
        os.makedirs(cdir, exist_ok=True)
        # one live "worker" that writes its profile when signalled
        # (the trainer-side flow, distilled)
        script = (
            "import json, os, signal, sys, time\n"
            f"cdir = {str(cdir)!r}\n"
            "def h(s, f):\n"
            "    with open(os.path.join(cdir, "
            "'profile_%d_3.json' % os.getpid()), 'w') as fp:\n"
            "        json.dump({'pid': os.getpid(), 'step': 3, "
            "'shares': {'copy': 0.5}, 'mfu': 0.2, "
            "'summary': {'top_ops': []}}, fp)\n"
            "    open(os.path.join(cdir, "
            "'stacks_%d.txt' % os.getpid()), 'w')"
            ".write('Thread dump')\n"
            "signal.signal(signal.SIGUSR2, h)\n"
            # the armed marker: without it the agent refuses to
            # signal (default SIGUSR2 disposition kills a process)
            "open(os.path.join(cdir, 'armed_%d' % os.getpid()), "
            "'w').close()\n"
            "open(os.path.join(cdir, 'ready_%d' % os.getpid()), "
            "'w').close()\n"
            "time.sleep(30)\n"
        )
        proc = subprocess.Popen(
            [_sys.executable, "-c", script],
            stderr=subprocess.PIPE,
        )
        try:
            deadline = time.time() + 10
            while time.time() < deadline and not os.path.exists(
                cdir / f"ready_{proc.pid}"
            ):
                time.sleep(0.05)
            assert os.path.exists(cdir / f"ready_{proc.pid}")
            agent._procs = [proc]
            summary = agent._execute_capture("straggler", 9)
        finally:
            rc = proc.poll()
            proc.kill()
            proc.wait()
        debug = (summary, rc, os.listdir(cdir),
                 proc.stderr.read().decode()[-500:])
        assert summary["profiles_collected"] == 1, debug
        assert summary["stack_dumps"] == 1
        assert summary["workers_unarmed"] == 0
        assert summary["profiles"][0]["shares"] == {"copy": 0.5}
        assert summary["profile_summary"] == {"top_ops": []}
        payload = json.loads(
            open(client.profiles[0]["artifact"]).read()
        )
        assert "Thread dump" in str(payload["stacks"])

    def test_unarmed_worker_is_never_signalled(
        self, tmp_path, monkeypatch
    ):
        """A worker that never installed the capture handler (any
        non-Trainer entrypoint) must NOT get SIGUSR2 — the default
        disposition would kill it, turning the diagnostic into the
        fault it was investigating."""
        import subprocess
        import sys as _sys

        base = tmp_path / "captures"
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_DIR", str(base))
        monkeypatch.setenv("DLROVER_TPU_CAPTURE_TIMEOUT_S", "0.5")
        agent, client = self._agent(tmp_path)
        proc = subprocess.Popen(
            [_sys.executable, "-c", "import time; time.sleep(30)"]
        )
        try:
            agent._procs = [proc]
            summary = agent._execute_capture("hang", 11)
            time.sleep(0.3)
            assert proc.poll() is None, (
                "unarmed worker was killed by the capture signal"
            )
        finally:
            proc.kill()
            proc.wait()
        assert summary["workers_signalled"] == 0
        assert summary["workers_unarmed"] == 1
        # the capture still reports (stack-less): the verdict surface
        # shows the capture happened and why it has no dumps
        assert client.profiles[0]["summary"]["workers_unarmed"] == 1


@pytest.mark.timeout(120)
class TestDeepCaptureE2E:
    """Satellite: real LocalJobMaster + a simulated node — the
    hang-watchdog conclusion triggers ONE capture directive, the
    (simulated) agent answers with an artifact + ProfileReport, the
    row lands in the Brain profiles table, and /status + top.py
    --snapshot expose it."""

    def test_hang_to_capture_path(self, tmp_path, monkeypatch):
        import dlrover_tpu.master.datastore as ds_mod
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.env import get_free_port
        from dlrover_tpu.master.master import LocalJobMaster

        monkeypatch.setenv("DLROVER_TPU_PROFILE", "1")
        monkeypatch.setenv("DLROVER_TPU_HANG_WATCHDOG_S", "0.2")
        monkeypatch.setenv("DLROVER_TPU_JOB_NAME", "capture-e2e")
        monkeypatch.setenv(
            "DLROVER_TPU_BRAIN_DB", str(tmp_path / "brain.db")
        )
        monkeypatch.setattr(ds_mod, "_default_store", None)
        master = LocalJobMaster(get_free_port(), node_num=1)
        master.prepare()
        store = ds_mod._default_store
        client = MasterClient(master.addr, node_id=0)
        try:
            now = time.time()
            client._channel.report(
                msg.TimelineEventsReport(
                    events=[
                        {
                            "name": "step", "ph": "X",
                            "wall": now - 0.5 + 0.1 * i,
                            "mono": 0.1 * i, "dur": 0.05,
                            "node": 0, "pid": 1,
                            "labels": {"step": i + 1},
                        }
                        for i in range(4)
                    ]
                )
            )
            client.report_heartbeat()
            time.sleep(0.3)  # past the watchdog, heartbeat fresh
            client.report_heartbeat()
            fresh = master.diagnosis_manager.diagnose()
            assert any(
                c.problem == "hang" and c.node_rank == 0
                for c in fresh
            ), fresh
            # the directive rides the ordinary monitor poll
            client.num_nodes_waiting()
            directive = client.take_node_action()
            assert directive is not None
            action, reason, cid = directive
            assert action == "capture" and reason == "hang"
            # delivered ONCE: repeat sweeps + polls produce nothing
            master.diagnosis_manager.diagnose()
            client.num_nodes_waiting()
            assert client.take_node_action() is None
            # the simulated agent answers with artifact + report
            artifact = str(tmp_path / f"capture_0_{cid}.json")
            summary = {
                "reason": reason,
                "capture_id": cid,
                "stack_dumps": 1,
                "profiles_collected": 0,
            }
            with open(artifact, "w") as f:
                json.dump(dict(summary, stacks={"s": "wedged"}), f)
            assert client.report_profile(
                node_rank=0, reason=reason, capture_id=cid,
                summary=summary, artifact=artifact,
            )
            # exposed on the status RPC...
            status = client.get_job_status()
            entry = status["profiles"][0]
            assert entry["summary"]["stack_dumps"] == 1
            assert entry["artifact"] == artifact
            # ...durable in the Brain profiles table...
            rows = store.profiles("capture-e2e")
            assert len(rows) == 1
            assert rows[0]["node"] == 0
            assert rows[0]["reason"] == "hang"
            # ...and visible through top.py --snapshot + render
            from scripts.top import main as top_main, render

            out_file = str(tmp_path / "top.json")
            rc = top_main(
                [
                    "--master_addr", master.addr,
                    "--snapshot", "--out", out_file,
                ]
            )
            assert rc == 0
            snap = json.loads(open(out_file).read())
            profiles = snap["profiles"]
            key = 0 if 0 in profiles else "0"
            assert profiles[key]["reason"] == "hang"
            frame = render(snap)
            assert "deep captures" in frame
            assert "hang" in frame
        finally:
            client.close()
            master.stop()
            if store is not None:
                store.close()
            ds_mod._default_store = None


class TestProfileKillSwitch:
    def test_profile_off_reproduces_today(self, tmp_path, monkeypatch):
        """DLROVER_TPU_PROFILE=0: no coordinator, no profiles key on
        the status surface, no directives on the wire, and reports
        from stale agents are refused."""
        import dlrover_tpu.master.datastore as ds_mod
        from dlrover_tpu.common import messages as msg
        from dlrover_tpu.common.comm import MasterChannel
        from dlrover_tpu.common.env import get_free_port
        from dlrover_tpu.master.master import LocalJobMaster

        monkeypatch.setenv("DLROVER_TPU_PROFILE", "0")
        monkeypatch.setattr(ds_mod, "_default_store", None)
        master = LocalJobMaster(get_free_port(), node_num=1)
        assert master.capture_coordinator is None
        assert master.diagnosis_manager._capture is None
        master.prepare()
        chan = MasterChannel(master.addr, node_id=0)
        try:
            res = chan.get(msg.WaitingNodeNumRequest())
            assert getattr(res, "action", "") == ""
            status = chan.get(msg.JobStatusRequest())
            assert status.available
            assert "profiles" not in status.status
            ack = chan.report(msg.ProfileReport(node_rank=0))
            assert ack is False
        finally:
            chan.close()
            master.stop()

    def test_trainer_env_gating(self, monkeypatch):
        from dlrover_tpu.common.env import (
            profile_enabled,
            profile_every_n_steps,
        )

        monkeypatch.setenv(
            "DLROVER_TPU_PROFILE_EVERY_N_STEPS", "50"
        )
        assert profile_every_n_steps() == 50
        monkeypatch.setenv("DLROVER_TPU_PROFILE", "0")
        assert profile_enabled() is False
        monkeypatch.delenv("DLROVER_TPU_PROFILE")
        assert profile_enabled() is True
        monkeypatch.delenv("DLROVER_TPU_PROFILE_EVERY_N_STEPS")
        assert profile_every_n_steps() == 0  # continuous leg off


class TestTrainerContinuousLeg:
    """The real Trainer loop: DLROVER_TPU_PROFILE_EVERY_N_STEPS=3
    opens one-step windows, the background worker parses them and
    emits step_profile spans to the node's events file."""

    def _run(self, tmp_path, monkeypatch, profile_env):
        import numpy as np
        import optax

        from dlrover_tpu.accelerate import (
            auto_accelerate,
            load_strategy,
        )
        from dlrover_tpu.models.llama import (
            LlamaConfig,
            init_params,
            loss_fn,
            param_logical_axes,
        )
        from dlrover_tpu.trainer.trainer import (
            Trainer,
            TrainingArgs,
        )

        os.environ["DLROVER_TPU_SOCKET_DIR"] = str(
            tmp_path / "socks_attr"
        )
        for key, value in profile_env.items():
            monkeypatch.setenv(key, value)
        fake = TraceReport(
            total_device_us=900.0,
            step_count=1,
            mean_step_us=1000.0,
            by_category={
                "convolution fusion": 600.0,
                "copy-done": 300.0,
            },
        )
        monkeypatch.setattr(
            "dlrover_tpu.observability.trace.parse_trace",
            lambda path: fake,
        )
        cfg = LlamaConfig.tiny(remat="none")
        result = auto_accelerate(
            loss_fn=lambda p, b: loss_fn(p, b, cfg),
            optimizer=optax.adamw(1e-3),
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
            load_strategy=load_strategy(
                {"data": 8, "remat": "none"}
            ),
        )
        tokens = np.ones((8, 17), dtype=np.int32)

        def data_iter():
            for _ in range(64):
                yield {"tokens": tokens}

        events_file = str(tmp_path / "events.jsonl")
        set_default_event_logger(
            EventLogger(path=events_file, job="j", node=0, rank=0)
        )
        try:
            trainer = Trainer(
                result,
                TrainingArgs(
                    max_steps=7,
                    checkpoint_dir=str(tmp_path / "ckpt"),
                    save_memory_interval=100,
                    save_storage_interval=100,
                    log_interval=100,
                ),
                data_iter,
            )
            summary = trainer.train()
        finally:
            set_default_event_logger(None)
            from dlrover_tpu.trainer.capture import reset_capture

            reset_capture()
        assert summary["final_step"] == 7
        return read_events(events_file)

    def test_emits_step_profile_spans(self, tmp_path, monkeypatch):
        recs = self._run(
            tmp_path,
            monkeypatch,
            {"DLROVER_TPU_PROFILE_EVERY_N_STEPS": "3"},
        )
        spans = [r for r in recs if r["name"] == "step_profile"]
        # max_steps 7, every 3 -> windows opened before steps 4, 7
        assert len(spans) == 2
        labels = spans[0]["labels"]
        assert labels["share_compute"] == pytest.approx(
            0.6, abs=0.05
        )
        assert labels["share_copy"] == pytest.approx(0.3, abs=0.05)
        assert labels["mode"] == "profile"
        assert {"share_collective", "share_infeed", "share_idle",
                "tflops", "mfu"} <= set(labels)

    def test_profile_zero_emits_nothing(self, tmp_path, monkeypatch):
        recs = self._run(
            tmp_path,
            monkeypatch,
            {
                "DLROVER_TPU_PROFILE_EVERY_N_STEPS": "3",
                "DLROVER_TPU_PROFILE": "0",
            },
        )
        assert [
            r for r in recs if r["name"] == "step_profile"
        ] == []


@pytest.mark.timeout(120)
def test_profiling_overhead_under_two_percent():
    """The always-on claim, pinned: with the continuous leg active,
    the steps it does NOT trace run within 2% of the profiler-off
    step time (the background parse must never steal the training
    thread).  The traced step's own cost and the amortized number
    are bench artifacts (``extras.profiling_*``), not CI bars — on
    CPU CI the trace capture itself dwarfs the 20 ms step."""
    from bench import measure_profiling_overhead

    # six rounds of an off and an on leg: the median ratio forgets the
    # round a neighbouring worker's burst of load fell into
    result = measure_profiling_overhead(steps=120, every=10)
    assert result["profiling_overhead"] < 0.02, result
