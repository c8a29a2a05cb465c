"""Master failover: durable control-plane journaling/replay, epoch
fencing, bounded reconnection — the "master crash is not a job crash"
subsystem (``master/failover.py``, ``common/fault_injection.py``).

Every replay test drives the REAL component pair: mutate a live
instance with the journal attached, then recover a FRESH instance from
the sqlite Brain and assert the two states are identical.  The
in-process master-restart test at the bottom goes end to end over real
gRPC: kill the serving master mid-``kv_store_wait``, start a new
incarnation on the same port + Brain db, and assert the parked waiter
re-parks and completes.
"""

import os
import threading
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient, ReportBuffer
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.comm import MasterChannel, StaleEpochError
from dlrover_tpu.common.constants import NodeType, RendezvousName
from dlrover_tpu.common.env import get_free_port
from dlrover_tpu.common.fault_injection import (
    FaultInjectedError,
    FaultInjector,
    FaultPlan,
    reset_fault_injector,
)
from dlrover_tpu.common.messages import serialize_message
from dlrover_tpu.master.datastore import BrainDatastore
from dlrover_tpu.master.failover import ControlPlaneJournal
from dlrover_tpu.master.job_manager import LocalJobManager
from dlrover_tpu.master.kv_store import KVStoreService
from dlrover_tpu.master.master import LocalJobMaster
from dlrover_tpu.master.rendezvous import (
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.shard.task_manager import TaskManager


@pytest.fixture()
def store(tmp_path):
    ds = BrainDatastore(str(tmp_path / "brain.db"))
    yield ds
    ds.close()


def _journal_to(store, component="kv", job="job-f"):
    """A component journal callback writing straight to the store."""
    return lambda op, args: store.journal_append(
        job, component, op, args
    )


# --------------------------------------------------------------------------
# component journal/replay round-trips
# --------------------------------------------------------------------------


class TestKVReplay:
    def test_journal_replay_identical(self, store):
        kv = KVStoreService()
        kv.set_journal(_journal_to(store))
        kv.set("a", b"1")
        kv.add("counter", 5)
        kv.add("counter", 2)
        kv.set("b", b"\x00binary\xff")
        kv.delete("a")

        fresh = KVStoreService()
        for _seq, _c, op, args in store.journal_entries("job-f"):
            fresh.apply_journal_op(op, args)
        assert fresh.export_state() == kv.export_state()
        assert fresh.get("counter") == b"7"
        assert fresh.get("a") == b""

    def test_add_journals_result_idempotent(self, store):
        """``add`` journals the RESULT as a set — replaying an entry
        the snapshot already contains cannot double-count."""
        kv = KVStoreService()
        kv.set_journal(_journal_to(store))
        kv.add("n", 3)
        entries = store.journal_entries("job-f")
        fresh = KVStoreService()
        fresh.restore_state(kv.export_state())  # snapshot includes it
        for _seq, _c, op, args in entries:  # ...and so does the journal
            fresh.apply_journal_op(op, args)
        assert fresh.get("n") == b"3"

    def test_snapshot_restore(self):
        kv = KVStoreService()
        kv.set("x", b"val")
        fresh = KVStoreService()
        fresh.restore_state(kv.export_state())
        assert fresh.get("x") == b"val"


class TestRendezvousReplay:
    def test_pending_round_resumes_with_members(self, store):
        mgr = ElasticTrainingRendezvousManager()
        mgr.set_journal(_journal_to(store, "rdzv/elastic-training"))
        mgr.update_rdzv_params(3, 3, 60.0, 1)
        mgr.join_rendezvous(0, 8)
        mgr.join_rendezvous(1, 8)

        fresh = ElasticTrainingRendezvousManager()
        for _seq, _c, op, args in store.journal_entries("job-f"):
            fresh.restore_state(args)
        # same pending round, same joined members: the third join on
        # the new incarnation completes the SAME world
        assert fresh.get_rdzv_round() == mgr.get_rdzv_round()
        fresh.join_rendezvous(2, 8)
        rnd, _g, world = fresh.get_comm_world(0)
        assert world == {0: 8, 1: 8, 2: 8}
        assert rnd == 1

    def test_completed_round_identical_world(self):
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(2, 2, 60.0, 1)
        mgr.join_rendezvous(0, 4)
        mgr.join_rendezvous(1, 4)
        rnd, group, world = mgr.get_comm_world(0)
        assert world

        fresh = ElasticTrainingRendezvousManager()
        fresh.restore_state(mgr.export_state())
        assert fresh.get_comm_world(0) == (rnd, group, world)
        assert fresh.state_version == mgr.state_version

    def test_restore_rearms_waiting_window(self):
        """A pending round must not complete instantly off a stale
        pre-crash ``lastcall`` timestamp: the window restarts NOW."""
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(1, 4, 30.0, 1)
        mgr.join_rendezvous(0, 1)
        state = mgr.export_state()
        state["lastcall"] = time.time() - 3600.0  # ancient
        fresh = ElasticTrainingRendezvousManager()
        fresh.restore_state(state)
        _rnd, _g, world = fresh.get_comm_world(0)
        assert world == {}  # window re-armed, not expired


class TestTaskManagerReplay:
    def _params(self, name="ds"):
        return msg.DatasetShardParams(
            dataset_name=name,
            dataset_size=40,
            batch_size=10,
            num_epochs=1,
            num_minibatches_per_shard=1,
        )

    def test_unacked_lease_requeued_on_replay(self, store):
        tm = TaskManager()
        tm.set_journal(_journal_to(store, "tasks"))
        tm.new_dataset(self._params())
        leased = tm.get_task(node_id=0, dataset_name="ds")
        assert not leased.is_empty

        fresh = TaskManager()
        for _seq, _c, op, args in store.journal_entries("job-f"):
            fresh.apply_journal_op(op, args)
        # the unacked lease is back in todo: the same shard dispatches
        # again on the new incarnation (timeout-requeue semantics)
        again = fresh.get_task(node_id=1, dataset_name="ds")
        assert (again.shard.start, again.shard.end) == (
            leased.shard.start, leased.shard.end,
        )

    def test_acked_lease_not_redispatched(self, store):
        tm = TaskManager()
        tm.set_journal(_journal_to(store, "tasks"))
        tm.new_dataset(self._params())
        done = tm.get_task(node_id=0, dataset_name="ds")
        tm.report_task_status("ds", done.task_id, success=True)

        fresh = TaskManager()
        for _seq, _c, op, args in store.journal_entries("job-f"):
            fresh.apply_journal_op(op, args)
        nxt = fresh.get_task(node_id=0, dataset_name="ds")
        assert (nxt.shard.start, nxt.shard.end) != (
            done.shard.start, done.shard.end,
        )

    def test_dispatch_journals_deltas_not_full_state(self, store):
        """Steady-state journal traffic is O(1) per ack — NOT the full
        dataset checkpoint per dispatch (that was O(shards²) per epoch
        through the write-behind queue, under the TaskManager lock).
        Full-state records appear only at creation + splitter refill;
        a plain dispatch journals nothing; a successful ack journals a
        compact ``done`` delta — and replay still converges to the
        same remaining-shard state."""
        import json

        tm = TaskManager()
        tm.set_journal(_journal_to(store, "tasks"))
        tm.new_dataset(self._params())  # 4 shards of 10
        for _ in range(3):
            t = tm.get_task(node_id=0, dataset_name="ds")
            tm.report_task_status("ds", t.task_id, success=True)

        entries = store.journal_entries("job-f")
        ops = [op for _s, _c, op, _a in entries]
        # creation + one refill full record, then one delta per ack
        assert ops.count("dataset") == 2
        assert ops.count("done") == 3
        # deltas are compact: no record grows with the shard count
        for _s, _c, op, args in entries:
            if op == "done":
                assert set(args) == {"name", "shard", "epoch", "step"}
                assert len(json.dumps(args)) < 200

        fresh = TaskManager()
        for _seq, _c, op, args in entries:
            fresh.apply_journal_op(op, args)
        last = fresh.get_task(node_id=1, dataset_name="ds")
        # exactly the one un-acked shard remains
        assert (last.shard.start, last.shard.end) == (30, 40)
        fresh.report_task_status("ds", last.task_id, success=True)
        assert fresh.finished()

    def test_snapshot_roundtrip(self):
        import json

        tm = TaskManager()
        tm.new_dataset(self._params())
        tm.get_task(node_id=0, dataset_name="ds")
        fresh = TaskManager()
        fresh.restore_state(tm.export_state())
        # same shards in the same order, same splitter position; the
        # task-id counter may advance on restore (ids only need to
        # stay unique and monotonic, never to collide with pre-crash
        # leases)
        a = json.loads(tm.export_state()["datasets"]["ds"]["ckpt"])
        b = json.loads(
            fresh.export_state()["datasets"]["ds"]["ckpt"]
        )
        assert b["todo"] == a["todo"]
        assert b["splitter"] == a["splitter"]
        assert b["task_id"] >= a["task_id"]


class TestJobManagerReplay:
    def test_node_table_roundtrip(self, store):
        jm = LocalJobManager(2)
        jm.set_journal(_journal_to(store, "nodes"))
        jm.start()
        jm.update_node_address(NodeType.WORKER, 0, "10.0.0.1:5")
        jm.collect_node_heartbeat(NodeType.WORKER, 0, time.time())

        fresh = LocalJobManager(2)
        for _seq, _c, op, args in store.journal_entries("job-f"):
            fresh.apply_journal_op(op, args)
        fresh.start()  # restored rows must survive start()
        node = fresh.get_node(0)
        assert node is not None
        assert node.host_addr == "10.0.0.1:5"
        assert fresh.nodes_version >= 1

    def test_snapshot_roundtrip(self):
        jm = LocalJobManager(2)
        jm.start()
        jm.update_node_address(NodeType.WORKER, 1, "10.0.0.2:6")
        fresh = LocalJobManager(2)
        fresh.restore_state(jm.export_state())
        fresh.start()
        assert (
            fresh.get_node(1).host_addr
            == "10.0.0.2:6"
        )


# --------------------------------------------------------------------------
# ControlPlaneJournal end to end over the Brain datastore
# --------------------------------------------------------------------------


def _build_components():
    return {
        "kv": KVStoreService(),
        "rdzv": {"et": ElasticTrainingRendezvousManager()},
        "tasks": TaskManager(),
        "nodes": LocalJobManager(2),
    }


def _journal_for(store, c, **kw):
    return ControlPlaneJournal(
        store,
        "job-f",
        kv_store=c["kv"],
        rdzv_managers=c["rdzv"],
        task_manager=c["tasks"],
        job_manager=c["nodes"],
        **kw,
    )


class TestControlPlaneJournal:
    def _mutate(self, c):
        c["kv"].set("barrier/1", b"ok")
        c["kv"].add("count", 2)
        c["rdzv"]["et"].update_rdzv_params(2, 2, 60.0, 1)
        c["rdzv"]["et"].join_rendezvous(0, 1)
        c["nodes"].start()
        c["nodes"].update_node_address(NodeType.WORKER, 0, "h:1")

    def _assert_recovered(self, a, b):
        assert b["kv"].export_state() == a["kv"].export_state()
        assert (
            b["rdzv"]["et"].export_state()["waiting"]
            == a["rdzv"]["et"].export_state()["waiting"]
        )
        assert (
            b["nodes"].get_node(0).host_addr == "h:1"
        )

    def test_journal_only_recovery(self, store):
        live = _build_components()
        journal = _journal_for(store, live)
        journal.attach()
        self._mutate(live)

        fresh = _build_components()
        stats = _journal_for(store, fresh).recover()
        assert stats["replayed"] > 0
        assert stats["snapshot_seq"] == 0
        self._assert_recovered(live, fresh)

    def test_snapshot_plus_journal_recovery(self, store):
        live = _build_components()
        journal = _journal_for(store, live)
        journal.attach()
        self._mutate(live)
        journal.snapshot_now()
        # post-snapshot mutations ride the journal tail
        live["kv"].set("late", b"tail")

        fresh = _build_components()
        stats = _journal_for(store, fresh).recover()
        assert stats["snapshot_seq"] > 0
        self._assert_recovered(live, fresh)
        assert fresh["kv"].get("late") == b"tail"

    def test_snapshot_prunes_journal(self, store):
        live = _build_components()
        journal = _journal_for(store, live)
        journal.attach()
        self._mutate(live)
        seq = store.journal_seq("job-f")
        journal.snapshot_now()
        entries = store.journal_entries("job-f")
        assert all(s > seq for s, *_rest in entries)

    def test_stop_takes_final_snapshot(self, store):
        live = _build_components()
        journal = _journal_for(store, live, snapshot_interval_s=3600)
        journal.attach()
        journal.start()
        live["kv"].set("k", b"v")
        journal.stop()
        snapshot, seq = store.load_control_snapshot("job-f")
        assert seq > 0
        assert snapshot["components"]["kv"]["kv"]

    def test_unknown_component_skipped(self, store):
        store.journal_append("job-f", "martian", "state", {"x": 1})
        fresh = _build_components()
        _journal_for(store, fresh).recover()  # must not raise

    def test_replay_not_rejournaled(self, store):
        live = _build_components()
        journal = _journal_for(store, live)
        journal.attach()
        live["kv"].set("k", b"v")
        before = store.journal_seq("job-f")
        fresh = _build_components()
        _journal_for(store, fresh).recover()
        assert store.journal_seq("job-f") == before


class TestControlMeta:
    def test_incarnation_monotonic_same_epoch(self, store):
        assert store.bump_incarnation("j") == (1, 1)
        assert store.bump_incarnation("j") == (1, 2)
        assert store.get_control_meta("j") == (1, 2)

    def test_job_epoch_bump_drops_generation_state(self, store):
        store.bump_incarnation("j")
        store.journal_append("j", "kv", "set", {"key": "a"})
        epoch = store.bump_job_epoch("j")
        assert epoch == 2
        assert store.journal_entries("j") == []
        assert store.load_control_snapshot("j") == (None, 0)
        # incarnations keep counting under the new epoch
        assert store.bump_incarnation("j") == (2, 1)

    def test_unregistered_job_defaults(self, store):
        assert store.get_control_meta("never") == (1, 0)


# --------------------------------------------------------------------------
# epoch fencing: servicer + channel
# --------------------------------------------------------------------------


def _servicer(job_epoch=3, incarnation=2):
    return MasterServicer(
        kv_store=KVStoreService(),
        rdzv_managers={
            RendezvousName.ELASTIC_TRAINING:
                ElasticTrainingRendezvousManager(),
        },
        job_epoch=job_epoch,
        incarnation=incarnation,
    )


def _envelope(message, job_epoch=-1):
    return msg.Envelope(
        node_id=0,
        node_type=NodeType.WORKER,
        data=serialize_message(message),
        job_epoch=job_epoch,
    )


class TestServicerFencing:
    def test_stale_epoch_fenced_with_typed_answer(self):
        servicer = _servicer(job_epoch=3, incarnation=2)
        out = servicer.get(
            _envelope(msg.KeyValuePair(key="k"), job_epoch=1)
        )
        assert isinstance(out, msg.StaleEpoch)
        assert (out.job_epoch, out.incarnation) == (3, 2)

    def test_report_fenced_too(self):
        servicer = _servicer(job_epoch=3)
        out = servicer.report(
            _envelope(msg.HeartBeat(timestamp=1.0), job_epoch=1)
        )
        assert isinstance(out, msg.StaleEpoch)

    def test_matching_epoch_dispatched(self):
        servicer = _servicer(job_epoch=3)
        out = servicer.get(
            _envelope(msg.KeyValuePair(key="k"), job_epoch=3)
        )
        assert not isinstance(out, msg.StaleEpoch)

    def test_legacy_client_never_fenced(self):
        """-1 = has not learned the pair yet (an old client, or one
        before its first refresh): dispatched, never fenced."""
        servicer = _servicer(job_epoch=3)
        out = servicer.get(_envelope(msg.KeyValuePair(key="k")))
        assert not isinstance(out, msg.StaleEpoch)

    def test_epoch_request_answered_even_when_stale(self):
        servicer = _servicer(job_epoch=3, incarnation=7)
        out = servicer.get(
            _envelope(msg.ControlEpochRequest(), job_epoch=1)
        )
        assert isinstance(out, msg.ControlEpoch)
        assert (out.job_epoch, out.incarnation) == (3, 7)

    def test_fenced_report_is_not_applied(self):
        """Fencing comes before dispatch: a stale client's KV write
        gets the typed answer and leaves no trace; the same write at
        the master's own epoch lands."""
        servicer = _servicer(job_epoch=3)
        write = msg.KeyValuePair(key="coord", value=b"old-world")
        out = servicer.report(_envelope(write, job_epoch=1))
        assert isinstance(out, msg.StaleEpoch)
        assert not servicer._kv_store.get("coord")
        out = servicer.report(_envelope(write, job_epoch=3))
        assert not isinstance(out, msg.StaleEpoch)
        assert servicer._kv_store.get("coord") == b"old-world"


class TestChannelEpochHandling:
    def _channel(self):
        # nothing listens on the address: these tests never touch the
        # wire (they drive _roundtrip with a fake rpc callable)
        return MasterChannel(
            f"127.0.0.1:{get_free_port()}", timeout=1.0
        )

    def test_stale_answer_adopts_and_reissues(self):
        chan = self._channel()
        changes = []
        chan.on_epoch_change = lambda e, i: changes.append((e, i))
        answers = [
            serialize_message(msg.StaleEpoch(job_epoch=4, incarnation=9)),
            serialize_message(msg.KeyValuePair(key="k", value=b"v")),
        ]

        def fake_rpc(payload, timeout):
            return answers.pop(0)

        chan._get = fake_rpc
        out = chan._roundtrip(
            "get", msg.KeyValuePair(key="k"), timeout=1.0
        )
        assert out.value == b"v"
        assert (chan.job_epoch, chan.master_incarnation) == (4, 9)
        assert changes == [(4, 9)]

    def test_endless_fencing_bounded(self):
        chan = self._channel()
        stale = serialize_message(
            msg.StaleEpoch(job_epoch=4, incarnation=9)
        )
        chan._get = lambda p, timeout: stale
        with pytest.raises(StaleEpochError):
            chan._roundtrip(
                "get", msg.KeyValuePair(key="k"), timeout=1.0
            )

    def test_fencing_reissues_a_bounded_number_of_times(self):
        """A fenced call adopts the answer's pair and re-issues
        transparently — ``MAX_EPOCH_REFRESHES`` wire calls in all
        against a master that keeps fencing, the change callback once
        (the pair changed once), then the typed error."""
        chan = self._channel()
        changes = []
        chan.on_epoch_change = lambda e, i: changes.append((e, i))
        calls = []

        def fake_rpc(payload, timeout):
            calls.append(1)
            return serialize_message(
                msg.StaleEpoch(job_epoch=4, incarnation=9)
            )

        chan._get = fake_rpc
        with pytest.raises(StaleEpochError):
            chan._roundtrip(
                "get", msg.KeyValuePair(key="k"), timeout=1.0
            )
        assert len(calls) == MasterChannel.MAX_EPOCH_REFRESHES
        assert changes == [(4, 9)]
        assert (chan.job_epoch, chan.master_incarnation) == (4, 9)

    def test_envelope_always_carries_the_fencing_pair(self):
        """Every envelope carries ``(job_epoch, master_incarnation)``
        as last learned: -1/-1 before the first refresh (never
        fenced), then whatever a refresh or a fenced answer taught."""
        import pickle

        chan = self._channel()
        env = pickle.loads(chan._wrap(msg.HeartBeat(timestamp=1.0)))
        assert (env.job_epoch, env.master_incarnation) == (-1, -1)
        chan.job_epoch, chan.master_incarnation = 5, 3
        env = pickle.loads(chan._wrap(msg.HeartBeat(timestamp=1.0)))
        assert (env.job_epoch, env.master_incarnation) == (5, 3)
        chan._adopt(msg.StaleEpoch(job_epoch=6, incarnation=1))
        env = pickle.loads(chan._wrap(msg.HeartBeat(timestamp=1.0)))
        assert (env.job_epoch, env.master_incarnation) == (6, 1)


class TestChannelRetryShape:
    def test_backoff_is_jittered_exponential_and_clamped(self):
        """The pause before attempt n+1 is ``0.1 * 2^(n-1)`` s jittered
        to [0.5, 1.5)x, capped at 5 s before the jitter and never past
        what is left of the deadline — no fixed schedule a fleet could
        fall into lockstep on."""
        chan = MasterChannel(f"127.0.0.1:{get_free_port()}")
        for attempt in range(1, 10):
            base = min(0.1 * 2 ** (attempt - 1), 5.0)
            draws = [chan._backoff(attempt, 1e9) for _ in range(50)]
            assert all(0.5 * base <= d < 1.5 * base for d in draws)
            assert len(set(draws)) > 1  # jittered, not a constant
        assert chan._backoff(9, 0.25) == 0.25
        assert chan._backoff(1, 0.0) == 0.0
        chan.close()

    def test_failover_deadline_bounds_retries(self, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_MASTER_RECONNECT_DEADLINE_S", "1.5"
        )
        chan = MasterChannel(
            f"127.0.0.1:{get_free_port()}", timeout=0.2
        )
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            chan.get(msg.KeyValuePair(key="k"), timeout=0.2)
        elapsed = time.monotonic() - t0
        assert elapsed < 10.0  # bounded by the deadline, not 120 s
        assert chan.rpc_count > 2  # the deadline bounds it, no count
        assert chan.retry_count >= 2

    def test_epoch_probe_deadline_bounded(self):
        """``refresh_epoch(deadline_s=...)`` caps its OWN retry loop:
        a quick probe from inside another call's retry loop (or from
        ``_survive_outage`` / the chaos MTTR probe) must not run the
        full 120 s reconnect deadline on top of the caller's."""
        chan = MasterChannel(
            f"127.0.0.1:{get_free_port()}", timeout=0.2
        )
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            chan.refresh_epoch(timeout=0.2, deadline_s=1.0)
        assert time.monotonic() - t0 < 6.0

    def test_concurrent_reconnect_resolves_fresh_stubs(
        self, monkeypatch
    ):
        """Channels are shared across threads: a ``_reconnect`` by one
        thread swaps the stubs under the others.  Every attempt must
        re-resolve from the CURRENT stub, or a thread whose captured
        callable points at the closed channel retries "Cannot invoke
        RPC on closed channel!" for the rest of the deadline (the
        chaos harness caught exactly this — 60 s of dead retries per
        master kill)."""
        monkeypatch.setenv(
            "DLROVER_TPU_MASTER_RECONNECT_DEADLINE_S", "10"
        )
        chan = MasterChannel(
            f"127.0.0.1:{get_free_port()}", timeout=0.2
        )
        fails = {"n": 0}

        def flaky(payload, timeout):
            fails["n"] += 1
            if fails["n"] < 3:
                raise ValueError(
                    "Cannot invoke RPC on closed channel!"
                )
            return serialize_message(
                msg.KeyValuePair(key="k", value=b"v")
            )

        # a concurrent _reconnect would rebuild real stubs; pin every
        # rebuild back to the fake so the retry loop exercises only
        # the re-resolution path
        monkeypatch.setattr(
            type(chan), "_build_channel",
            lambda self: setattr(self, "_get", flaky)
            or setattr(self, "_report", flaky),
        )
        chan._get = flaky
        chan._reconnect()  # another thread swapped the stubs
        out = chan.get(msg.KeyValuePair(key="k"), timeout=0.2)
        assert out.value == b"v"

    def test_close_aborts_inflight_retries(self):
        """``close()`` flags the retry loop: a deliberately-closed
        channel raises promptly instead of burning the reconnect
        deadline."""
        chan = MasterChannel(
            f"127.0.0.1:{get_free_port()}", timeout=0.2
        )
        chan.close()
        t0 = time.monotonic()
        with pytest.raises(ConnectionError, match="closed locally"):
            chan.get(msg.KeyValuePair(key="k"), timeout=0.2)
        assert time.monotonic() - t0 < 5.0

    def test_backoff_jittered_exponential_capped(self):
        chan = MasterChannel(f"127.0.0.1:{get_free_port()}")
        base, cap = chan.BACKOFF_BASE_S, chan.BACKOFF_CAP_S
        for attempt in range(1, 12):
            d = chan._backoff(attempt, remaining=100.0)
            ceiling = min(base * 2 ** (attempt - 1), cap)
            assert 0.0 <= d <= ceiling * 1.5
        # never exceeds the remaining deadline
        assert chan._backoff(10, remaining=0.05) <= 0.05


# --------------------------------------------------------------------------
# satellite: bounded ReportBuffer
# --------------------------------------------------------------------------


class _DeadChannel:
    def __init__(self):
        self.sent = []
        self.down = True

    def report(self, message):
        if self.down:
            raise ConnectionError("master gone")
        self.sent.append(message)
        return True


class _DeadClient:
    def __init__(self):
        self._channel = _DeadChannel()


class TestClientReassertGuards:
    """Re-assertion is only valid WITHIN one job generation."""

    def _client(self):
        return MasterClient(
            f"127.0.0.1:{get_free_port()}", node_id=0
        )

    def test_job_epoch_change_drops_session_state(self):
        """A straggler of a retired generation that learns the new
        job epoch must DROP its session state, not inject the dead
        job's KV keys / datasets / joins into the new one."""
        client = self._client()
        try:
            client._own_kv["g/1/0"] = b"dead-job-grad"
            client._own_datasets["ds"] = msg.DatasetShardParams(
                dataset_name="ds"
            )
            client._pending_join["et"] = (0, 1)
            client._last_job_epoch = 1
            client._on_epoch_change(2, 3)  # new generation
            assert client._own_kv == {}
            assert client._own_datasets == {}
            assert client._pending_join == {}
            # nothing was sent anywhere
            assert client._channel.rpc_count == 0
        finally:
            client.close()

    def test_first_learn_incarnation_one_skips_reassert(self):
        """First epoch learn against a never-restarted master
        (incarnation 1): nothing was lost, so nothing is re-asserted
        — and a straggler that never learned the OLD epoch can't
        tell a fresh generation apart, so re-asserting would be the
        stale-state injection again.  Caches stay for a later real
        restart of this generation."""
        client = self._client()
        try:
            client._own_kv["k"] = b"kept"
            client._on_epoch_change(2, 1)
            assert client._channel.rpc_count == 0
            assert client._own_kv == {"k": b"kept"}
            # a subsequent RESTART of this generation re-asserts:
            # same epoch, incarnation bumped -> the guard passes
            # (pinned end-to-end by TestInProcessMasterRestart)
            assert client._last_job_epoch == 2
        finally:
            client.close()


class TestReportBufferBound:
    def test_overflow_drops_oldest(self):
        client = _DeadClient()
        buf = ReportBuffer(
            client, max_items=2, auto_flush=False, max_pending=4
        )
        for i in range(10):
            buf.add(msg.GlobalStep(step=i))
        assert buf.pending <= 4
        assert buf.dropped == 6
        client._channel.down = False
        assert buf.flush()
        steps = [s.step for s in client._channel.sent[0].items]
        assert steps == [6, 7, 8, 9]  # the NEWEST survived

    def test_requeue_respects_bound(self):
        client = _DeadClient()
        buf = ReportBuffer(
            client, max_items=100, auto_flush=False, max_pending=3
        )
        for i in range(3):
            buf.add(msg.GlobalStep(step=i))
        buf.flush()  # transport fails -> front re-queue
        buf.add(msg.GlobalStep(step=3))
        assert buf.pending <= 3
        assert buf.dropped >= 1

    def test_no_drop_below_bound(self):
        client = _DeadClient()
        client._channel.down = False
        buf = ReportBuffer(
            client, max_items=100, auto_flush=False, max_pending=50
        )
        for i in range(20):
            buf.add(msg.GlobalStep(step=i))
        assert buf.dropped == 0


# --------------------------------------------------------------------------
# fault-injection plan mechanics
# --------------------------------------------------------------------------


class TestFaultPlan:
    def test_from_json_and_validation(self):
        plan = FaultPlan.from_json(
            '{"seed": 7, "faults": ['
            '{"kind": "kill", "target": "master",'
            ' "phase": "mid_rendezvous"},'
            '{"kind": "rpc", "target": "KVWaitRequest",'
            ' "op": "drop", "count": 2}]}'
        )
        assert plan.seed == 7
        assert len(plan.faults) == 2
        with pytest.raises(ValueError):
            FaultPlan.from_json(
                '{"faults": [{"kind": "kill", "phase": "nope"}]}'
            )

    def test_env_roundtrip(self, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_FAULT_PLAN",
            '{"seed": 1, "faults": [{"kind": "rpc", "op": "dup"}]}',
        )
        reset_fault_injector()
        try:
            from dlrover_tpu.common.fault_injection import (
                get_fault_injector,
            )

            inj = get_fault_injector()
            assert inj is not None
            assert inj.on_rpc("Anything") == "dup"
        finally:
            reset_fault_injector()

    def test_malformed_env_ignored(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_FAULT_PLAN", "{broken")
        reset_fault_injector()
        try:
            from dlrover_tpu.common.fault_injection import (
                get_fault_injector,
            )

            assert get_fault_injector() is None
        finally:
            reset_fault_injector()

    def test_rpc_drop_after_count(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_EVENTS_FILE", str(tmp_path / "ev.jsonl")
        )
        plan = FaultPlan.from_json(
            '{"faults": [{"kind": "rpc", "target": "TaskRequest",'
            ' "op": "drop", "after": 1, "count": 1}]}'
        )
        inj = FaultInjector(plan, role="agent")
        assert inj.on_rpc("TaskRequest") == ""  # skipped (after=1)
        with pytest.raises(FaultInjectedError):
            inj.on_rpc("TaskRequest")
        assert inj.on_rpc("TaskRequest") == ""  # count exhausted
        assert inj.on_rpc("HeartBeat") == ""  # name filter

    def test_rpc_delay(self, tmp_path, monkeypatch):
        monkeypatch.setenv(
            "DLROVER_TPU_EVENTS_FILE", str(tmp_path / "ev.jsonl")
        )
        plan = FaultPlan.from_json(
            '{"faults": [{"kind": "rpc", "op": "delay",'
            ' "delay_s": 0.1}]}'
        )
        inj = FaultInjector(plan, role="agent")
        t0 = time.monotonic()
        inj.on_rpc("HeartBeat")
        assert time.monotonic() - t0 >= 0.1

    def test_seeded_probability_deterministic(self):
        def fired(seed):
            plan = FaultPlan.from_json(
                '{"seed": %d, "faults": [{"kind": "rpc",'
                ' "op": "dup", "prob": 0.5, "count": -1}]}' % seed
            )
            inj = FaultInjector(plan, role="agent")
            return [inj.on_rpc("X") == "dup" for _ in range(32)]

        assert fired(3) == fired(3)
        assert fired(3) != fired(4)

    def test_kill_role_filter_no_kill(self):
        """A master-targeted kill must NOT fire in an agent role (if
        filtering were broken this test would die with the process)."""
        plan = FaultPlan.from_json(
            '{"faults": [{"kind": "kill", "target": "master",'
            ' "phase": "mid_rendezvous"}]}'
        )
        inj = FaultInjector(plan, role="agent")
        inj.maybe_crash("mid_rendezvous")  # alive == pass
        inj.maybe_crash("mid_long_poll")


# --------------------------------------------------------------------------
# in-process master restart: parked waiter re-parks on the new
# incarnation, replayed KV answers pre-crash sets
# --------------------------------------------------------------------------


class TestInProcessMasterRestart:
    @pytest.fixture()
    def brain_env(self, tmp_path, monkeypatch):
        import dlrover_tpu.master.datastore as ds_mod

        db = str(tmp_path / "brain.db")
        monkeypatch.setenv("DLROVER_TPU_BRAIN_DB", db)
        monkeypatch.setattr(ds_mod, "_default_store", None)
        yield db
        store = ds_mod._default_store
        if store is not None:
            store.close()
        ds_mod._default_store = None

    def test_kv_wait_survives_master_restart(self, brain_env):
        port = get_free_port()
        m1 = LocalJobMaster(port, node_num=1)
        m1.prepare()
        assert (m1.job_epoch, m1.incarnation) == (1, 1)
        client = MasterClient(f"127.0.0.1:{port}", node_id=0)
        try:
            client.kv_store_set("pre", b"persisted")
            got = []
            waiter = threading.Thread(
                target=lambda: got.append(
                    client.kv_store_wait("answer", timeout=30.0)
                ),
                daemon=True,
            )
            waiter.start()
            time.sleep(0.4)  # parked on incarnation 1
            m1.stop()

            m2 = LocalJobMaster(port, node_num=1)
            m2.prepare()
            try:
                assert (m2.job_epoch, m2.incarnation) == (1, 2)
                # journal replay restored the pre-crash set
                assert m2.kv_store.get("pre") == b"persisted"
                m2.kv_store.set("answer", b"42")
                waiter.join(timeout=30.0)
                assert got == [b"42"]
                # the re-issued wait refreshed the fencing pair
                assert client._channel.master_incarnation == 2
            finally:
                m2.stop()
        finally:
            client.close()

    def test_job_end_retires_state_next_run_starts_fresh(
        self, brain_env
    ):
        """A JOB-terminal stop (request_stop passes a JobExitReason)
        must retire the durable control-plane state: a later run under
        the same Brain db + job name starts with a BUMPED epoch and
        empty components — not the finished job's exhausted datasets
        and stale KV keys (which would fence nothing and silently end
        the new job at step 0)."""
        port = get_free_port()
        m1 = LocalJobMaster(port, node_num=1)
        m1.prepare()
        m1.kv_store.set("stale", b"old-run")
        m1.task_manager.new_dataset(
            msg.DatasetShardParams(
                dataset_name="ds",
                dataset_size=10,
                batch_size=10,
                num_epochs=1,
                num_minibatches_per_shard=1,
            )
        )
        m1.request_stop(True, "Succeeded")  # job ENDED

        m2 = LocalJobMaster(port, node_num=1)
        m2.prepare()
        try:
            # new generation: epoch bumped (stragglers fenced),
            # nothing replayed
            assert m2.job_epoch == 2
            assert m2.incarnation == 1
            assert m2.kv_store.get("stale") == b""
            assert not m2.task_manager.training_started()
        finally:
            m2.stop()  # bare stop: master-only, state kept

    def test_bare_stop_keeps_state_for_handover(self, brain_env):
        """A reasonless stop() is a master-only shutdown: the final
        snapshot stays, the next incarnation resumes the job."""
        port = get_free_port()
        m1 = LocalJobMaster(port, node_num=1)
        m1.prepare()
        m1.kv_store.set("keep", b"live-job")
        m1.stop()
        m2 = LocalJobMaster(port, node_num=1)
        m2.prepare()
        try:
            assert (m2.job_epoch, m2.incarnation) == (1, 2)
            assert m2.kv_store.get("keep") == b"live-job"
        finally:
            m2.stop()


# --------------------------------------------------------------------------
# satellite: SIGKILL between journal enqueue and write-behind flush —
# replay tolerates the torn tail (truncate to last complete record)
# --------------------------------------------------------------------------


class TestTornJournalTail:
    CHILD = """
import os, sys, time
sys.path.insert(0, {repo!r})
from dlrover_tpu.master.datastore import BrainDatastore

ds = BrainDatastore({db!r})
# batch 1: becomes durable (the fault plan skips the first flush)
for i in range(3):
    ds.journal_append("j", "kv", "set", {{"key": f"a{{i}}"}})
assert len(ds.journal_entries("j")) == 3  # drains = flush happened
# batch 2: enqueued; the NEXT flush SIGKILLs the process between
# dequeue and sqlite write (the maybe_crash hook in _write_batch)
for i in range(3):
    ds.journal_append("j", "kv", "set", {{"key": f"b{{i}}"}})
time.sleep(10)  # the flusher's kill lands first
"""

    def test_sigkill_between_enqueue_and_flush(self, tmp_path):
        import json
        import subprocess
        import sys

        db = str(tmp_path / "brain.db")
        repo = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
        child = tmp_path / "child.py"
        child.write_text(self.CHILD.format(repo=repo, db=db))
        env = dict(
            os.environ,
            DLROVER_TPU_FAULT_ROLE="master",
            DLROVER_TPU_FAULT_PLAN=json.dumps({
                "faults": [{
                    "kind": "kill", "target": "master",
                    "phase": "mid_report_flush", "after": 1,
                }],
            }),
            JAX_PLATFORMS="cpu",
        )
        proc = subprocess.run(
            [sys.executable, str(child)],
            env=env, capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == -9, (
            f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
        )

        # recovery: the durable prefix survives, the killed batch is
        # the crash-lost linger window
        ds = BrainDatastore(db)
        try:
            entries = ds.journal_entries("j")
            assert [e[3]["key"] for e in entries] == [
                "a0", "a1", "a2",
            ]
            top = entries[-1][0]

            # a torn tail ROW (the crash interrupted sqlite mid-write
            # or the args column is garbage): replay truncates to the
            # last complete record and NEVER raises — even for valid
            # rows behind the tear
            with ds._lock:
                ds._conn.execute(
                    "INSERT INTO control_journal VALUES "
                    "(?,?,?,?,?,?)",
                    ("j", top + 1, "kv", "set", '{"key": "to', 0.0),
                )
                ds._conn.execute(
                    "INSERT INTO control_journal VALUES "
                    "(?,?,?,?,?,?)",
                    ("j", top + 2, "kv", "set",
                     '{"key": "after-tear"}', 0.0),
                )
                ds._conn.commit()
            entries = ds.journal_entries("j")
            assert [e[3]["key"] for e in entries] == [
                "a0", "a1", "a2",
            ]

            # a full recover over the torn journal must not crash and
            # must install the pre-tear state
            kv = KVStoreService()
            journal = ControlPlaneJournal(ds, "j", kv_store=kv)
            stats = journal.recover()
            assert stats["replayed"] == 3

            # new appends continue past the torn row's seq (MAX(seq)
            # includes it — sequences never collide)
            seq = ds.journal_append("j", "kv", "set", {"key": "new"})
            assert seq > top + 2
        finally:
            ds.close()
