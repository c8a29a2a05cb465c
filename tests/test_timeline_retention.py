"""Timeline growth bounds: size-based rotation of the agent-side
JSONL events file and the age/row-cap retention sweep for the Brain
``timeline_events`` table.  Both are generous by default and
configurable."""

import os
import time

from dlrover_tpu.master.datastore import BrainDatastore
from dlrover_tpu.observability.events import EventLogger, read_events


def _fill(events: EventLogger, n: int):
    for i in range(n):
        events.instant("job_start", idx=i, pad="x" * 64)


class TestEventsFileRotation:
    def test_rotates_past_the_size_cap(self, tmp_path, monkeypatch):
        # ~8 KB cap; each record is ~200 bytes
        monkeypatch.setenv("DLROVER_TPU_EVENTS_MAX_MB", "0.008")
        path = str(tmp_path / "events.jsonl")
        events = EventLogger(path=path, job="j", node=0, rank=0,
                             incarnation=0)
        # several check windows past the cap, plus one post-rotation
        # event so the live file exists again
        _fill(events, 3 * EventLogger.ROTATE_CHECK_EVERY)
        events.instant("job_end", marker=True)
        events.close()
        assert os.path.exists(path + ".1"), "no rotation happened"
        # the live file restarted small; the backup holds the history
        assert os.path.getsize(path) < os.path.getsize(path + ".1")
        # both files are intact JSONL (rotation never tears a line)
        live = read_events(path)
        backup = read_events(path + ".1")
        assert live and backup
        total = len(live) + len(backup)
        # only the live+backup window is retained (older bytes of a
        # multi-rotation run are dropped by design)
        assert total <= 3 * EventLogger.ROTATE_CHECK_EVERY + 1

    def test_second_writer_follows_a_rotation(self, tmp_path,
                                              monkeypatch):
        """Two processes' loggers share one events file.  When one of
        them rotates it, the other — whose descriptor now points at
        the backup — follows to the new file at its next size check
        instead of rotating the fresh file over the history."""
        # cap below one check window of bytes: one window, one
        # rotation
        monkeypatch.setenv("DLROVER_TPU_EVENTS_MAX_MB", "0.02")
        path = str(tmp_path / "events.jsonl")
        first = EventLogger(path=path, job="j", node=0, rank=0,
                            incarnation=0)
        second = EventLogger(path=path, job="j", node=0, rank=1,
                             incarnation=0)
        second.instant("job_start", who="second")  # opens its fd
        _fill(first, EventLogger.ROTATE_CHECK_EVERY)  # rotates
        first.instant("job_end", who="first")  # recreates the file
        assert os.path.exists(path + ".1")
        backup_size = os.path.getsize(path + ".1")
        # the second writer's next window still lands in the backup
        # (its fd), then its size check sees the path moved on
        _fill(second, EventLogger.ROTATE_CHECK_EVERY - 1)
        second.instant("job_end", who="second")
        first.close()
        second.close()
        assert os.path.getsize(path + ".1") >= backup_size
        live = read_events(path)
        assert [e["labels"]["who"] for e in live] == [
            "first", "second",
        ]
        # nothing was rotated away: every line of both writers is in
        # one of the two files
        total = len(live) + len(read_events(path + ".1"))
        assert total == 2 * EventLogger.ROTATE_CHECK_EVERY + 2

    def test_zero_cap_disables_rotation(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_EVENTS_MAX_MB", "0")
        path = str(tmp_path / "events.jsonl")
        events = EventLogger(path=path, job="j", node=0, rank=0,
                             incarnation=0)
        _fill(events, 2 * EventLogger.ROTATE_CHECK_EVERY)
        events.close()
        assert not os.path.exists(path + ".1")

    def test_reporter_follows_a_rotation(self, tmp_path, monkeypatch):
        """The agent's TimelineReporter treats the recreated file as
        a truncation and keeps shipping post-rotation events."""
        from dlrover_tpu.agent.monitor import TimelineReporter

        # cap > one check window of bytes: at most ONE rotation per
        # size check, so the backup always holds the unshipped tail
        # (a double rotation between ticks is documented-lossy)
        monkeypatch.setenv("DLROVER_TPU_EVENTS_MAX_MB", "0.02")

        shipped = []

        class FakeClient:
            def report_timeline_events(self, events):
                shipped.extend(events)
                return True

        path = str(tmp_path / "events.jsonl")
        events = EventLogger(path=path, job="j", node=0, rank=0,
                             incarnation=0)
        reporter = TimelineReporter(path, client=FakeClient(),
                                    interval=3600)
        _fill(events, 40)
        reporter._tick()
        before = len(shipped)
        assert before == 40
        # force exactly one rotation, then one event in the fresh file
        extra = EventLogger.ROTATE_CHECK_EVERY
        _fill(events, extra)
        events.instant("job_end", marker=True)
        events.close()
        # tick 1 drains the rotated backup's unshipped tail, tick 2
        # reads the fresh live file — NOTHING between the last
        # shipped offset and the rotation point may be lost
        reporter._tick()
        reporter._tick()
        assert any(
            e["name"] == "job_end" for e in shipped[before:]
        ), "post-rotation events were not shipped"
        assert len(shipped) == before + extra + 1, (
            "rotation lost events: "
            f"{len(shipped)} != {before + extra + 1}"
        )


class TestBrainTimelineRetention:
    def _mk_events(self, n, t0=None):
        t0 = time.time() if t0 is None else t0
        return [
            {
                "name": "step",
                "ph": "X",
                "wall": t0 + i * 0.001,
                "mono": i * 0.001,
                "dur": 0.001,
                "node": 0,
                "rank": 0,
                "inc": 0,
                "pid": 1,
                "labels": {"step": i},
            }
            for i in range(n)
        ]

    def test_row_cap_keeps_newest(self, tmp_path):
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            store.record_timeline_events("j", self._mk_events(30))
            store.sweep_timeline("j", max_age_s=0, max_rows=10)
            rows = store.timeline_events("j")
            assert len(rows) == 10
            # the newest rows won (highest step labels survive)
            steps = sorted(r["labels"]["step"] for r in rows)
            assert steps == list(range(20, 30))
        finally:
            store.close()

    def test_age_bound(self, tmp_path):
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            store.record_timeline_events("j", self._mk_events(5))
            time.sleep(0.05)
            store.sweep_timeline("j", max_age_s=0.01, max_rows=0)
            assert store.timeline_events("j") == []
        finally:
            store.close()

    def test_sweep_is_job_scoped(self, tmp_path):
        """A shared multi-job Brain: one job's sweep must never
        touch a neighbour's rows."""
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            store.record_timeline_events("a", self._mk_events(20))
            store.record_timeline_events("b", self._mk_events(20))
            store.sweep_timeline("a", max_age_s=0, max_rows=5)
            assert len(store.timeline_events("a")) == 5
            assert len(store.timeline_events("b")) == 20
        finally:
            store.close()

    def test_generous_defaults_keep_everything(self, tmp_path):
        """The default knobs (7 days / 500k rows) must not sweep a
        normal job's fresh rows."""
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            store.record_timeline_events("j", self._mk_events(50))
            store.sweep_timeline("j")
            assert len(store.timeline_events("j")) == 50
        finally:
            store.close()

    def test_aggregator_triggers_throttled_sweep(self, tmp_path,
                                                 monkeypatch):
        from dlrover_tpu.observability.events import (
            TimelineAggregator,
        )

        monkeypatch.setenv("DLROVER_TPU_TIMELINE_MAX_ROWS", "10")
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            agg = TimelineAggregator(job="j", datastore=store)
            agg.add_events(0, self._mk_events(30))
            # the throttle keeps the sweep off the hot path; arm it
            agg._last_retention_sweep = (
                time.monotonic() - 2 * agg.RETENTION_SWEEP_S
            )
            agg.add_events(0, self._mk_events(5))
            assert len(store.timeline_events("j")) == 10
        finally:
            store.close()

    def test_sweep_stays_off_the_hot_path_between_intervals(
        self, tmp_path, monkeypatch
    ):
        """One sweep per ``RETENTION_SWEEP_S``: a fresh aggregator's
        first batches are not swept, and right after a sweep a burst
        past the cap stays until the interval has passed again."""
        from dlrover_tpu.observability.events import (
            TimelineAggregator,
        )

        monkeypatch.setenv("DLROVER_TPU_TIMELINE_MAX_ROWS", "10")
        store = BrainDatastore(str(tmp_path / "b.db"))
        try:
            agg = TimelineAggregator(job="j", datastore=store)
            agg.add_events(0, self._mk_events(30))
            assert len(store.timeline_events("j")) == 30
            agg._last_retention_sweep = (
                time.monotonic() - 2 * agg.RETENTION_SWEEP_S
            )
            agg.add_events(0, self._mk_events(5))
            assert len(store.timeline_events("j")) == 10
            agg.add_events(0, self._mk_events(5))
            assert len(store.timeline_events("j")) == 15
        finally:
            store.close()
