"""Control-plane fast-path tests: long-poll waits, coalesced delta
reporting (``BatchedReport`` / ``NotModified``), the write-behind
datastore, the buffered ``recv_line``, and wire-pickle parity — over
the real gRPC master where it matters (same strategy as
``test_master.py``)."""

import dataclasses
import os
import pickle
import socket
import sqlite3
import threading
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient, ReportBuffer
from dlrover_tpu.common import messages as msg
from dlrover_tpu.common.comm import MasterChannel
from dlrover_tpu.common.constants import (
    NodeType,
    RendezvousName,
    TrainingLoopStatus,
)
from dlrover_tpu.common.env import get_free_port
from dlrover_tpu.common.netio import recv_exact, recv_line
from dlrover_tpu.master.datastore import BrainDatastore
from dlrover_tpu.master.kv_store import KVStoreService
from dlrover_tpu.master.master import LocalJobMaster


@pytest.fixture
def master():
    port = get_free_port()
    m = LocalJobMaster(port, node_num=2)
    m.prepare()
    yield m
    m.stop()


@pytest.fixture
def channel(master):
    chan = MasterChannel(master.addr, node_id=0, node_type=NodeType.WORKER)
    yield chan
    chan.close()


# --------------------------------------------------------------------------
# satellite: buffered recv_line
# --------------------------------------------------------------------------


class _FakeConn:
    """Socket stand-in honoring MSG_PEEK, counting recv syscalls."""

    def __init__(self, data: bytes):
        self.buf = data
        self.recv_calls = 0

    def recv(self, n, flags=0):
        self.recv_calls += 1
        chunk = self.buf[:n]
        if not (flags & socket.MSG_PEEK):
            self.buf = self.buf[len(chunk):]
        return chunk


class TestRecvLine:
    def test_buffered_not_byte_per_syscall(self):
        conn = _FakeConn(b"PUT key 5\nhello")
        assert recv_line(conn) == "PUT key 5"
        # one MSG_PEEK + one consuming recv — NOT one per byte
        assert conn.recv_calls == 2
        # wire semantics: nothing past the newline was consumed
        assert conn.buf == b"hello"

    def test_slow_dribble_socket_pair(self):
        a, b = socket.socketpair()
        payload = b"hello world\nBODY!"

        def _dribble():
            for i in range(len(payload)):
                a.sendall(payload[i:i + 1])
                time.sleep(0.002)

        t = threading.Thread(target=_dribble, daemon=True)
        t.start()
        try:
            assert recv_line(b) == "hello world"
            # the bytes after the line are intact for recv_exact
            assert recv_exact(b, 5) == b"BODY!"
        finally:
            t.join()
            a.close()
            b.close()

    def test_peer_close_mid_line(self):
        a, b = socket.socketpair()
        a.sendall(b"no newline")
        a.close()
        with pytest.raises(ConnectionError):
            recv_line(b)
        b.close()


# --------------------------------------------------------------------------
# satellite: pinned pickle protocol + whole-surface round trip
# --------------------------------------------------------------------------


def _all_message_types():
    out = []
    stack = [msg.Message]
    while stack:
        cls = stack.pop()
        for sub in cls.__subclasses__():
            stack.append(sub)
            out.append(sub)
    return out


class TestWireSerialization:
    def test_protocol_pinned_to_highest(self):
        raw = msg.serialize_message(msg.HeartBeat(timestamp=1.0))
        # pickle's PROTO opcode: byte 0 is \x80, byte 1 the version
        assert raw[0] == 0x80
        assert raw[1] == pickle.HIGHEST_PROTOCOL
        assert msg.WIRE_PICKLE_PROTOCOL == pickle.HIGHEST_PROTOCOL

    def test_every_message_type_round_trips(self):
        types = _all_message_types()
        assert len(types) > 40  # the whole protocol surface
        for cls in types:
            instance = cls()
            back = msg.deserialize_message(msg.serialize_message(instance))
            assert type(back) is cls
            if dataclasses.is_dataclass(cls):
                assert back == instance

    def test_batched_report_round_trips_nested(self):
        batch = msg.BatchedReport(
            items=[
                msg.HeartBeat(timestamp=1.5),
                msg.GlobalStep(step=7, timestamp=2.0),
                msg.KeyValuePair(key="k", value=b"v"),
                msg.TimelineEventsReport(
                    events=[{"name": "step", "ph": "X", "wall": 1.0}]
                ),
            ]
        )
        back = msg.deserialize_message(msg.serialize_message(batch))
        assert back == batch
        assert [type(i) for i in back.items] == [
            msg.HeartBeat,
            msg.GlobalStep,
            msg.KeyValuePair,
            msg.TimelineEventsReport,
        ]


# --------------------------------------------------------------------------
# satellite: condition-based KV wait (the long-poll primitive)
# --------------------------------------------------------------------------


class TestKVStoreCondition:
    def test_wait_wakes_on_set(self):
        kv = KVStoreService()
        t = threading.Timer(0.2, kv.set, args=("k", b"v"))
        t.start()
        t0 = time.monotonic()
        assert kv.wait("k", timeout=5.0) == b"v"
        elapsed = time.monotonic() - t0
        # event-driven: well under the old 50 ms busy-poll granularity
        # plus scheduling noise; nowhere near the 5 s timeout
        assert 0.15 < elapsed < 1.0
        t.join()

    def test_wait_timeout_returns_none(self):
        kv = KVStoreService()
        t0 = time.monotonic()
        assert kv.wait("missing", timeout=0.2) is None
        assert time.monotonic() - t0 < 1.0

    def test_wait_wakes_on_add(self):
        kv = KVStoreService()
        threading.Timer(0.1, kv.add, args=("ctr", 2)).start()
        assert kv.wait("ctr", timeout=5.0) == b"2"


# --------------------------------------------------------------------------
# tentpole: long-poll over the real gRPC master
# --------------------------------------------------------------------------


class TestLongPollKV:
    def test_idle_wait_rpc_bound(self, master):
        """THE acceptance bound, asserted directly: an idle 5 s KV
        wait under long-poll costs <= 2 RPCs (vs 25 at the 0.2 s
        reference poll)."""
        client = MasterClient(master.addr, node_id=0)
        before = client.rpc_count
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            client.kv_store_wait("never-set", timeout=5.0)
        elapsed = time.monotonic() - t0
        assert elapsed >= 4.5  # it really waited
        assert client.rpc_count - before <= 2
        client.close()

    def test_longpoll_wakes_fast(self, master):
        """The waiter returns within one flush interval of ``kv set``
        — not one poll interval (0.2 s) later."""
        client = MasterClient(master.addr, node_id=0)
        setter = MasterClient(master.addr, node_id=1)
        t_set = [0.0]

        def _set():
            time.sleep(0.5)
            t_set[0] = time.monotonic()
            setter.kv_store_set("wake-key", b"addr:123")

        t = threading.Thread(target=_set, daemon=True)
        t.start()
        value = client.kv_store_wait("wake-key", timeout=10.0)
        woke = time.monotonic()
        t.join()
        assert value == b"addr:123"
        assert woke - t_set[0] < 0.15
        client.close()
        setter.close()

    def test_wait_for_master_parks_on_channel_ready(self, monkeypatch):
        """The launcher's wait for a master that is not up yet parks
        on grpc's channel-ready future: no TCP connect probe from
        Python, and it returns within a reconnect backoff of the
        server opening — False once the timeout passes unanswered."""
        from dlrover_tpu.common.comm import wait_channel_ready

        probes = []
        real_connect = socket.create_connection

        def _counting_connect(*a, **k):
            probes.append(a)
            return real_connect(*a, **k)

        monkeypatch.setattr(
            socket, "create_connection", _counting_connect
        )
        port = get_free_port()
        addr = f"127.0.0.1:{port}"
        assert wait_channel_ready(addr, timeout=0.3) is False
        m = LocalJobMaster(port, node_num=1)
        threading.Timer(0.4, m.prepare).start()
        try:
            t0 = time.monotonic()
            assert wait_channel_ready(addr, timeout=20.0) is True
            assert time.monotonic() - t0 >= 0.3  # it did wait
        finally:
            m.stop()
        assert probes == []

    def test_saturated_master_is_repolled_at_the_paced_rate(
        self, master
    ):
        """A master past its parked-wait cap answers a long-poll at
        once; the client then re-issues at the 10 Hz pace
        (``_pace_longpoll``), not in a hot RPC spin."""
        slots = master._servicer._wait_slots
        taken = 0
        while slots.acquire(blocking=False):
            taken += 1
        assert taken == master._servicer.max_parked_waits
        client = MasterClient(master.addr, node_id=0)
        setter = MasterClient(master.addr, node_id=1)
        before = client.rpc_count
        got = []
        waiter = threading.Thread(
            target=lambda: got.append(
                client.kv_store_wait("late-key", timeout=30.0)
            ),
            daemon=True,
        )
        try:
            t0 = time.monotonic()
            waiter.start()
            time.sleep(1.0)
            rpcs = client.rpc_count - before
            elapsed = time.monotonic() - t0
            setter.kv_store_set("late-key", b"v")
            waiter.join(timeout=10.0)
        finally:
            for _ in range(taken):
                slots.release()
        assert got == [b"v"]  # the immediate answers still deliver
        # the pace guarantees the ceiling (one RPC a 0.1 s backoff, as
        # long as the sleep really took); how FEW a loaded host's
        # scheduler lets through says nothing about a hot spin
        assert rpcs <= elapsed / 0.1 + 2, (rpcs, elapsed)
        client.close()
        setter.close()


class TestLongPollRendezvous:
    def test_comm_world_longpoll_wakes_on_completion(self, master):
        c0 = MasterClient(master.addr, node_id=0)
        c1 = MasterClient(master.addr, node_id=1)
        assert c0._channel.report(
            msg.RendezvousParams(
                min_nodes=2, max_nodes=2, waiting_timeout=60
            )
        )
        assert c0.join_rendezvous(0, 1) >= 0
        result = {}

        def _wait():
            result["world"] = c0.wait_comm_world(
                RendezvousName.ELASTIC_TRAINING, 0, timeout=10.0
            )

        waiter = threading.Thread(target=_wait, daemon=True)
        waiter.start()
        time.sleep(0.3)  # c0 is parked on the master
        t_join = time.monotonic()
        assert c1.join_rendezvous(1, 1) >= 0  # completes at max_nodes
        waiter.join(timeout=5.0)
        woke = time.monotonic()
        assert not waiter.is_alive()
        rnd, _group, world = result["world"]
        assert world == {0: 1, 1: 1}
        assert rnd >= 1
        # the parked RPC returned on the completion notify, not a poll
        assert woke - t_join < 1.0
        c0.close()
        c1.close()

    def test_comm_world_longpoll_few_rpcs(self, master):
        c0 = MasterClient(master.addr, node_id=0)
        c1 = MasterClient(master.addr, node_id=1)
        c0._channel.report(
            msg.RendezvousParams(
                min_nodes=2, max_nodes=2, waiting_timeout=60
            )
        )
        c0.join_rendezvous(0, 1)
        before = c0.rpc_count
        threading.Timer(1.0, c1.join_rendezvous, args=(1, 1)).start()
        _rnd, _g, world = c0.wait_comm_world(
            RendezvousName.ELASTIC_TRAINING, 0, timeout=10.0
        )
        assert world
        # one parked RPC covered the whole 1 s wait (2 allows a
        # chunk-boundary race)
        assert c0.rpc_count - before <= 2
        c0.close()
        c1.close()


class TestLongPollTasksAndStatus:
    def test_training_status_longpoll(self, master):
        client = MasterClient(master.addr, node_id=0)

        def _register():
            time.sleep(0.3)
            client2 = MasterClient(master.addr, node_id=1)
            client2.report_dataset_shard_params(
                dataset_name="lp_ds", dataset_size=100, batch_size=10
            )
            client2.close()

        threading.Thread(target=_register, daemon=True).start()
        t0 = time.monotonic()
        status = client.get_training_status(wait_timeout=10.0)
        elapsed = time.monotonic() - t0
        assert status == TrainingLoopStatus.START
        assert elapsed < 5.0  # woke on the dataset notify, not timeout
        client.close()

    def test_task_wait_longpoll_wakes_on_requeue(self, master):
        c0 = MasterClient(master.addr, node_id=0)
        c1 = MasterClient(master.addr, node_id=1)
        # one single-shard dataset: c0 takes the only task, c1 would WAIT
        c0.report_dataset_shard_params(
            dataset_name="wait_ds",
            dataset_size=100,
            batch_size=10,
            num_minibatches_per_shard=10,
        )
        task0 = c0.get_task("wait_ds")
        assert task0.task_type == msg.TaskType.TRAINING
        assert c1.get_task("wait_ds").task_type == msg.TaskType.WAIT

        def _fail_task():
            time.sleep(0.3)  # c1 is parked; failure requeues the shard
            c0.report_task_result(
                "wait_ds", task0.task_id, err_message="boom"
            )

        threading.Thread(target=_fail_task, daemon=True).start()
        t0 = time.monotonic()
        task1 = c1.get_task("wait_ds", wait_timeout=10.0)
        elapsed = time.monotonic() - t0
        assert task1.task_type == msg.TaskType.TRAINING
        assert elapsed < 5.0
        c0.close()
        c1.close()


class TestRollingUpgradeCompat:
    def test_old_client_pickles_without_new_fields(self, master, channel):
        """Unpickle restores ``__dict__``, not dataclass defaults: a
        pre-fast-path client's requests arrive WITHOUT wait_timeout/
        version/last_num and must still be served."""
        old_style = [
            msg.TaskRequest(dataset_name="nope"),
            msg.RunningNodesRequest(),
            msg.WaitingNodeNumRequest(),
            msg.TrainingStatusRequest(),
            msg.CommWorldRequest(node_id=0),
        ]
        for request in old_style:
            for field in (
                "wait_timeout", "version", "last_num"
            ):
                request.__dict__.pop(field, None)
            res = channel.get(request)
            assert res is not None, f"{type(request).__name__} unanswered"


class TestParkedWaiterCap:
    def test_saturated_wait_degrades_to_immediate_answer(self):
        """Past the parked-wait cap (half the pool) the master
        answers a long-poll immediately instead of parking another
        pool thread — mutation RPCs can always find a worker."""
        from dlrover_tpu.master.servicer import MasterServicer

        servicer = MasterServicer(kv_store=KVStoreService())
        # exhaust every wait slot (cap follows the configured pool)
        for _ in range(servicer.max_parked_waits):
            assert servicer._wait_slots.acquire(blocking=False)
        envelope = msg.Envelope(
            node_id=0,
            node_type=NodeType.WORKER,
            data=msg.serialize_message(
                msg.KVWaitRequest(key="k", wait_timeout=10.0)
            ),
        )
        t0 = time.monotonic()
        res = servicer.get(envelope)
        elapsed = time.monotonic() - t0
        assert isinstance(res, msg.KeyValuePair) and res.value == b""
        assert elapsed < 0.5  # did NOT park for the 10 s wait
        # a freed slot restores parking
        servicer._wait_slots.release()
        t0 = time.monotonic()
        servicer.get(
            msg.Envelope(
                node_id=0,
                node_type=NodeType.WORKER,
                data=msg.serialize_message(
                    msg.KVWaitRequest(key="k", wait_timeout=0.3)
                ),
            )
        )
        assert time.monotonic() - t0 >= 0.25  # parked again


# --------------------------------------------------------------------------
# tentpole: delta protocol (NotModified) over the real master
# --------------------------------------------------------------------------


class TestDeltaProtocol:
    def test_running_nodes_not_modified_then_change(
        self, master, channel
    ):
        assert channel.report(msg.HeartBeat(timestamp=time.time()))
        first = channel.get(msg.RunningNodesRequest())
        assert isinstance(first, msg.RunningNodes)
        assert len(first.nodes) == 1
        # unchanged: the version'd re-request ships NO node table
        again = channel.get(msg.RunningNodesRequest(version=first.version))
        assert isinstance(again, msg.NotModified)
        assert again.version == first.version
        # a world change invalidates: a second node heartbeats
        chan2 = MasterChannel(
            master.addr, node_id=1, node_type=NodeType.WORKER
        )
        assert chan2.report(msg.HeartBeat(timestamp=time.time()))
        fresh = channel.get(msg.RunningNodesRequest(version=first.version))
        assert isinstance(fresh, msg.RunningNodes)
        assert len(fresh.nodes) == 2
        assert fresh.version != first.version
        chan2.close()

    def test_client_cache_stays_correct_after_change(self, master):
        c0 = MasterClient(master.addr, node_id=0)
        assert c0._channel.report(msg.HeartBeat(timestamp=time.time()))
        assert len(c0.get_running_nodes()) == 1
        before = c0.rpc_count
        assert len(c0.get_running_nodes()) == 1  # NotModified + cache
        assert c0.rpc_count - before == 1
        c1 = MasterClient(master.addr, node_id=1)
        assert c1._channel.report(msg.HeartBeat(timestamp=time.time()))
        # the change MUST invalidate the cache
        assert len(c0.get_running_nodes()) == 2
        c0.close()
        c1.close()

    def test_comm_world_not_modified(self, master, channel):
        assert channel.report(
            msg.RendezvousParams(
                min_nodes=1, max_nodes=1, waiting_timeout=60
            )
        )
        state = channel.get(
            msg.JoinRendezvousRequest(node_rank=0, local_world_size=1)
        )
        assert state.round >= 0
        world = channel.get(msg.CommWorldRequest(node_id=0))
        assert isinstance(world, msg.CommWorld) and world.world
        again = channel.get(
            msg.CommWorldRequest(node_id=0, version=world.version)
        )
        assert isinstance(again, msg.NotModified)
        # a new join clears the world: no NotModified against the old
        # version
        channel.get(
            msg.JoinRendezvousRequest(node_rank=0, local_world_size=1)
        )
        fresh = channel.get(
            msg.CommWorldRequest(node_id=0, version=world.version)
        )
        assert isinstance(fresh, msg.CommWorld)


# --------------------------------------------------------------------------
# tentpole: coalesced delta reporting (ReportBuffer / BatchedReport)
# --------------------------------------------------------------------------


class _FakeChannel:
    def __init__(self):
        self.sent = []
        self.down = False

    def report(self, message):
        if self.down:
            raise ConnectionError("master unreachable")
        self.sent.append(message)
        return True


class _FakeClient:
    def __init__(self):
        self._channel = _FakeChannel()


class TestReportBuffer:
    def test_one_envelope_order_preserved(self):
        client = _FakeClient()
        buf = ReportBuffer(client, max_items=64, auto_flush=False)
        for i in range(5):
            buf.add(msg.GlobalStep(step=i))
        buf.add(msg.HeartBeat(timestamp=9.0))
        assert client._channel.sent == []  # nothing shipped yet
        assert buf.flush()
        assert len(client._channel.sent) == 1
        batch = client._channel.sent[0]
        assert isinstance(batch, msg.BatchedReport)
        assert [s.step for s in batch.items[:5]] == [0, 1, 2, 3, 4]
        assert isinstance(batch.items[5], msg.HeartBeat)

    def test_size_threshold_flushes_inline(self):
        client = _FakeClient()
        buf = ReportBuffer(client, max_items=3, auto_flush=False)
        buf.add(msg.GlobalStep(step=0))
        buf.add(msg.GlobalStep(step=1))
        assert client._channel.sent == []
        buf.add(msg.GlobalStep(step=2))  # trips max_items
        assert len(client._channel.sent) == 1
        assert len(client._channel.sent[0].items) == 3

    def test_transport_failure_requeues_front_no_loss(self):
        client = _FakeClient()
        buf = ReportBuffer(client, auto_flush=False)
        client._channel.down = True
        buf.add(msg.GlobalStep(step=0))
        buf.add(msg.GlobalStep(step=1))
        assert not buf.flush()
        assert buf.pending == 2  # re-queued, not lost
        buf.add(msg.GlobalStep(step=2))
        client._channel.down = False
        assert buf.flush()
        steps = [s.step for s in client._channel.sent[0].items]
        assert steps == [0, 1, 2]  # order survived the outage

    def test_close_flushes_pending(self):
        """Flush-on-shutdown: the agent's exit path must not lose
        buffered reports (kill-one-agent coverage)."""
        client = _FakeClient()
        buf = ReportBuffer(client, max_age_s=30.0)  # age never trips
        buf.add(msg.GlobalStep(step=42))
        buf.close()
        assert len(client._channel.sent) == 1
        assert client._channel.sent[0].items[0].step == 42

    def test_add_owns_delivery_and_the_age_flusher_ships_it(self):
        """``add`` never sends on its own below the size threshold and
        acks True even with the master down (the buffer owes the
        report from there); the age flusher then ships it as ONE
        enveloped batch once the master is back."""
        client = _FakeClient()
        client._channel.down = True
        buf = ReportBuffer(client, max_age_s=0.05)
        try:
            assert buf.add(msg.HeartBeat(timestamp=1.0)) is True
            time.sleep(0.2)  # age flushes fail and re-queue
            assert buf.pending == 1 and client._channel.sent == []
            client._channel.down = False
            deadline = time.monotonic() + 5.0
            while not client._channel.sent:
                assert time.monotonic() < deadline
                time.sleep(0.02)
        finally:
            buf.close()
        assert len(client._channel.sent) == 1
        batch = client._channel.sent[0]
        assert isinstance(batch, msg.BatchedReport)
        assert isinstance(batch.items[0], msg.HeartBeat)
        assert buf.pending == 0

    def test_batched_report_against_real_master(self, master):
        """End to end: one BatchedReport applies every item in order
        (last KV write wins) and feeds the speed monitor."""
        client = MasterClient(master.addr, node_id=0)
        buf = ReportBuffer(client, auto_flush=False)
        buf.add(msg.KeyValuePair(key="coord", value=b"first"))
        buf.add(msg.HeartBeat(timestamp=time.time()))
        buf.add(msg.GlobalStep(step=3, timestamp=time.time()))
        buf.add(msg.KeyValuePair(key="coord", value=b"second"))
        before = client.rpc_count
        assert buf.flush()
        assert client.rpc_count - before == 1  # ONE wire RPC
        assert client.kv_store_get("coord") == b"second"
        assert len(client.get_running_nodes()) == 1  # heartbeat landed
        client.close()


# --------------------------------------------------------------------------
# tentpole: write-behind datastore
# --------------------------------------------------------------------------


class TestWriteBehindDatastore:
    def test_close_drains_zero_rows_lost(self, tmp_path):
        db = str(tmp_path / "brain.db")
        store = BrainDatastore(db)
        n = 500
        for i in range(n):
            store.record_speed("job", i % 7 + 1, float(i))
        store.close()  # fsync'd drain
        conn = sqlite3.connect(db)
        count = conn.execute(
            "SELECT COUNT(*) FROM speed_samples"
        ).fetchone()[0]
        conn.close()
        assert count == n

    def test_read_your_writes_before_any_flush_interval(self, tmp_path):
        store = BrainDatastore(str(tmp_path / "b.db"))
        store.record_speed("job", 4, 100.0)
        store.record_node_event("job", "n0", "oom", "detail")
        # immediate read: the drain barrier makes the queue invisible
        assert store.speed_history("job") == {4: 100.0}
        events = store.node_events("job")
        assert len(events) == 1 and events[0]["event_type"] == "oom"
        store.close()

    def test_timeline_batch_lands_as_one_executemany(self, tmp_path):
        store = BrainDatastore(str(tmp_path / "b.db"))
        events = [
            {"name": "step", "ph": "X", "wall": float(i), "dur": 0.1}
            for i in range(100)
        ]
        store.record_timeline_events("job", events)
        assert len(store.timeline_events("job")) == 100
        store.close()

    def test_measurement_commits_beside_the_queue(self, tmp_path):
        """The one synchronous recorder: a strategy measurement is
        visible to a SECOND connection the moment the call returns,
        no drain — a neighbour master reads this file directly —
        while the high-rate row recorded before it rides the
        flusher's queue and is there after the drain."""
        db = str(tmp_path / "beside.db")
        store = BrainDatastore(db)
        assert store._flusher.is_alive()
        store.record_speed("job", 2, 50.0)
        assert store.health()["enqueued_rows"] == 1
        store.record_measurement("wl", {"fsdp": 2}, 0.25, job="job")
        conn = sqlite3.connect(db)  # independent reader, no drain
        count = conn.execute(
            "SELECT COUNT(*) FROM strategy_measurements"
        ).fetchone()[0]
        assert count == 1
        store._drain()
        assert store.health()["lag_rows"] == 0
        count = conn.execute(
            "SELECT COUNT(*) FROM speed_samples"
        ).fetchone()[0]
        conn.close()
        assert count == 1
        store.close()

    def test_async_buffers_between_commits(self, tmp_path):
        """The write-behind queue genuinely batches — an independent
        reader does NOT see an enqueued row
        before the linger, while the owning store (drain) does."""
        db = str(tmp_path / "async.db")
        store = BrainDatastore(db)
        # stall the flusher wake-up by writing exactly once
        store.record_speed("job", 2, 50.0)
        conn = sqlite3.connect(db)
        early = conn.execute(
            "SELECT COUNT(*) FROM speed_samples"
        ).fetchone()[0]
        conn.close()
        assert store.speed_history("job") == {2: 50.0}  # drained read
        # the independent pre-linger read may or may not have caught
        # the commit (timing); what MUST hold is owner visibility and
        # zero loss after close
        assert early in (0, 1)
        store.close()


# --------------------------------------------------------------------------
# satellite: bench smoke (tiny N, 2 s budget) — the bench cannot rot
# --------------------------------------------------------------------------


class TestBenchControlPlaneSmoke:
    def test_run_all_tiny(self, monkeypatch):
        import sys

        repo = os.path.dirname(os.path.dirname(__file__))
        sys.path.insert(0, os.path.join(repo, "scripts"))
        monkeypatch.setenv("DLROVER_TPU_BENCH_BUDGET_S", "2")
        from bench_control_plane import run_all

        result = run_all(n_agents=2, wait_s=1.0)
        idle = result["idle"]
        assert idle["client_rpcs"] > 0
        assert "wakeup_p50_ms" in result["wakeup"]
        assert result["control_rps"] > 0
        # the acceptance bound, at smoke scale: an idle wait costs a
        # parked RPC or two a waiter, not one per poll interval
        assert idle["rpcs_per_waiter"] <= 2
