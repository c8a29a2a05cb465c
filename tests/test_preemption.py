"""TPU-VM preemption watcher: event edge detection, idle resets,
metadata-unavailable quiescence, agent callback wiring, and the
end-to-end graceful drain (notice → flush → master fencing →
survivor wake-up)."""

import time

from dlrover_tpu.agent.preemption import PreemptionWatcher


class TestPreemptionWatcher:
    def test_fires_once_per_event(self):
        values = iter(
            ["NONE", "TERMINATE_ON_HOST_MAINTENANCE",
             "TERMINATE_ON_HOST_MAINTENANCE", "NONE",
             "MIGRATE_ON_HOST_MAINTENANCE"]
        )
        events = []
        w = PreemptionWatcher(fetcher=lambda: next(values))
        w.on_preemption(events.append)
        results = [w.check_once() for _ in range(5)]
        assert events == [
            "TERMINATE_ON_HOST_MAINTENANCE",
            "MIGRATE_ON_HOST_MAINTENANCE",
        ]
        assert results[1] == "TERMINATE_ON_HOST_MAINTENANCE"
        assert results[2] is None  # same event, not re-fired

    def test_event_refires_after_idle_reset(self):
        values = iter(["TRUE", "NONE", "TRUE"])
        events = []
        w = PreemptionWatcher(fetcher=lambda: next(values))
        w.on_preemption(events.append)
        for _ in range(3):
            w.check_once()
        assert events == ["TRUE", "TRUE"]

    def test_unreachable_metadata_is_quiet(self):
        w = PreemptionWatcher(fetcher=lambda: None)
        w.on_preemption(lambda e: (_ for _ in ()).throw(AssertionError))
        assert w.check_once() is None
        assert w.unavailable

    def test_callback_error_does_not_break_watcher(self):
        values = iter(["TRUE", "NONE", "TRUE"])
        hits = []
        w = PreemptionWatcher(fetcher=lambda: next(values))

        def bad(_e):
            raise RuntimeError("boom")

        w.on_preemption(bad)
        w.on_preemption(hits.append)
        for _ in range(3):
            w.check_once()
        assert hits == ["TRUE", "TRUE"]


def _bare_agent(calls):
    from dlrover_tpu.agent import training as tr

    agent = tr.ElasticTrainingAgent.__new__(tr.ElasticTrainingAgent)
    agent._procs = []
    agent._preempted = False
    agent._save_ckpt_to_storage = lambda reason: calls["flush"].append(
        reason
    )
    agent._try_report_failure = (
        lambda msg, level: calls["report"].append((msg, level))
    )
    return agent


def test_agent_preemption_drains_flushes_and_fences():
    """The agent's _on_preemption callback drains the workers,
    flushes the shm checkpoint, and reports node_preempted so the
    master fences the node immediately."""
    calls = {"flush": [], "report": []}
    agent = _bare_agent(calls)
    agent._on_preemption("TERMINATE_ON_HOST_MAINTENANCE")
    assert calls["flush"] == ["preemption:TERMINATE_ON_HOST_MAINTENANCE"]
    assert calls["report"][0][1] == "node_preempted"
    assert agent._preempted


class _StubSaver:
    """Stands in for the agent-side AsyncCheckpointSaver: records the
    emergency flush and answers the drain's common-step poll."""

    def __init__(self):
        self.flushes = []
        self._step = 11

    def max_common_step(self):
        return self._step

    def save_shm_to_storage(self, reason=""):
        self.flushes.append(reason)
        return True


class _FakeProc:
    def __init__(self, rc=None):
        self._rc = rc
        self.signals = []

    def poll(self):
        return self._rc

    def send_signal(self, sig):
        self.signals.append(sig)


def test_agent_drain_signals_live_workers_and_returns_on_fresh_step(
    monkeypatch,
):
    """The drain asks every LIVE worker (and no exited one) to
    snapshot at its next step boundary, then returns the moment a
    common step newer than the one it started from lands in shm —
    not at the end of the grace."""
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.drain import DRAIN_SIGNAL

    monkeypatch.setenv("DLROVER_TPU_PREEMPT_DRAIN_GRACE_S", "20")
    calls = {"flush": [], "report": []}
    agent = _bare_agent(calls)
    live = [_FakeProc(), _FakeProc()]
    dead = _FakeProc(rc=0)
    agent._procs = [live[0], dead, live[1]]
    stub = _StubSaver()
    polls = []

    def _common_step():
        # the fresh snapshot lands once both workers were signalled
        polls.append(len(live[0].signals) + len(live[1].signals))
        return 12 if len(polls) > 2 else 11

    stub.max_common_step = _common_step
    monkeypatch.setattr(AsyncCheckpointSaver, "_instance", stub)
    t0 = time.monotonic()
    agent._drain_worker_snapshots("preemption:TRUE")
    assert time.monotonic() - t0 < 5.0  # far inside the 20 s grace
    assert [p.signals for p in live] == [[DRAIN_SIGNAL]] * 2
    assert dead.signals == []
    assert polls[0] == 0  # the baseline was read before any signal


def test_preemption_drain_end_to_end(monkeypatch):
    """Notice → shm flush → master notified → the SURVIVING agent
    observes the membership change within one monitor interval, and
    the next round completes WITHOUT the fenced node."""
    from dlrover_tpu.agent import training as tr
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.common.env import get_free_port
    from dlrover_tpu.master.master import LocalJobMaster

    monkeypatch.setenv("DLROVER_TPU_FENCE_TTL_S", "30")
    port = get_free_port()
    master = LocalJobMaster(port, node_num=2)
    master.prepare()
    survivor = MasterClient(master.addr, node_id=0)
    dying = MasterClient(master.addr, node_id=1)
    try:
        # both nodes form the live world (round completes instantly
        # at max_nodes); a short window so the post-fence shrink
        # round also completes inside the test
        survivor.report_rdzv_params(1, 2, 0.4, 1)
        survivor.join_rendezvous(0, 1)
        dying.join_rendezvous(1, 1)
        _rnd, _g, world = survivor.wait_comm_world(
            "elastic-training", 0, timeout=10
        )
        assert set(world) == {0, 1}
        assert survivor.num_nodes_waiting() == 0

        # the preemption notice fires the REAL agent callback chain
        calls = {"flush": [], "report": []}
        agent = tr.ElasticTrainingAgent.__new__(
            tr.ElasticTrainingAgent
        )
        agent._procs = []
        agent._preempted = False
        agent._client = dying
        agent._restart_count = 0
        stub = _StubSaver()
        monkeypatch.setattr(AsyncCheckpointSaver, "_instance", stub)
        watcher = PreemptionWatcher(
            fetcher=lambda: "TERMINATE_ON_HOST_MAINTENANCE"
        )
        watcher.on_preemption(agent._on_preemption)
        t0 = time.monotonic()
        assert watcher.check_once() == "TERMINATE_ON_HOST_MAINTENANCE"
        # shm flushed before the pod dies
        assert stub.flushes and "preemption" in stub.flushes[0]
        # the survivor's waiting-count poll signals the membership
        # change immediately (pending-remesh fencing) — well within
        # one monitor interval of the notice
        waiting = survivor.num_nodes_waiting()
        assert waiting > 0
        assert time.monotonic() - t0 < 5.0  # one monitor interval

        # the survivor re-joins; the shrunken round completes without
        # the fenced node once the waiting window lapses
        survivor.join_rendezvous(0, 1)
        deadline = time.time() + 10
        world = {}
        while time.time() < deadline:
            _rnd, _g, world = survivor.get_comm_world(
                "elastic-training", 0
            )
            if world:
                break
            time.sleep(0.1)
        assert set(world) == {0}
    finally:
        survivor.close()
        dying.close()
        master.stop()
