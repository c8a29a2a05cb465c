"""Replica exchange, hang detection, loss-spike capture, numeric
drift checks."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.replica import (
    ReplicaManager,
    ReplicaService,
    fetch_replica,
    push_replica,
)
from dlrover_tpu.trainer.fault_tolerance import (
    HangDetector,
    LossSpikeCapture,
    NumericChecker,
    pytree_digest,
)


class TestReplicaService:
    def test_put_get_over_tcp(self):
        svc = ReplicaService(host="127.0.0.1")
        svc.start()
        try:
            addr = f"127.0.0.1:{svc.port}"
            payload = b"x" * (1 << 20) + b"shard-data"
            assert push_replica(addr, 3, payload)
            assert fetch_replica(addr, 3) == payload
            assert fetch_replica(addr, 9) is None
        finally:
            svc.stop()

    def test_manager_backup_and_restore(self):
        services = {
            r: ReplicaService(host="127.0.0.1") for r in range(3)
        }
        for svc in services.values():
            svc.start()
        peers = {
            r: f"127.0.0.1:{svc.port}" for r, svc in services.items()
        }
        try:
            mgr0 = ReplicaManager(0, services[0], lambda: peers)
            payload = b"node0-shard-step42"
            assert mgr0.backup(payload) == 1  # landed on node 1
            # node 0 relaunches with empty shm: new manager, new svc
            fresh = ReplicaService(host="127.0.0.1")
            fresh.start()
            try:
                mgr0b = ReplicaManager(0, fresh, lambda: peers)
                assert mgr0b.restore() == payload
            finally:
                fresh.stop()
        finally:
            for svc in services.values():
                svc.stop()


class TestHangDetector:
    def test_fires_on_stall(self):
        fired = []
        det = HangDetector(
            timeout=0.2, check_interval=0.05,
            on_hang=lambda: fired.append(1),
        )
        det.report_step(1)
        det.start()
        time.sleep(0.6)
        det.stop()
        assert fired and det.hang_detected

    def test_progress_prevents_firing(self):
        fired = []
        det = HangDetector(
            timeout=0.5, check_interval=0.05,
            on_hang=lambda: fired.append(1),
        )
        det.start()
        for s in range(10):
            det.report_step(s)
            time.sleep(0.03)
        det.stop()
        assert not fired


class TestLossSpike:
    def test_detects_spike(self, tmp_path):
        cap = LossSpikeCapture(
            str(tmp_path), spike_factor=3.0, min_history=20
        )
        rng = np.random.default_rng(0)
        for step in range(30):
            assert not cap.observe(step, 2.0 + rng.normal(0, 0.01))
        assert cap.observe(30, 10.0, batch={"x": jnp.ones((2, 2))})
        assert (tmp_path / "spikes.jsonl").exists()
        assert (tmp_path / "spike_30.npz").exists()


class TestNumericChecker:
    def test_digest_stability(self):
        tree = {"a": jnp.arange(8.0), "b": jnp.ones((2, 2))}
        same = {"a": jnp.arange(8.0), "b": jnp.ones((2, 2))}
        assert pytree_digest(tree) == pytree_digest(same)
        diff = {"a": jnp.arange(8.0) + 1e-3, "b": jnp.ones((2, 2))}
        assert pytree_digest(tree) != pytree_digest(diff)

    def test_compare_trees(self):
        checker = NumericChecker(rtol=1e-4)
        a = {"w": jnp.ones((4,))}
        assert checker.compare_trees("exact", a, {"w": jnp.ones((4,))})
        assert not checker.compare_trees(
            "drift", a, {"w": jnp.ones((4,)) * 1.1}
        )
        assert checker.records[-1]["max_rel_err"] > 0.05


# --------------------------------------------------------------------------
# master failover integration: kill+restart the master mid-rendezvous
# and mid-kv_store_wait; the same two-agent coordinated run must
# complete with byte-identical final state vs the no-fault run
# --------------------------------------------------------------------------

import threading

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.common.env import get_free_port
from dlrover_tpu.master.master import LocalJobMaster

STEPS = 4


def _toy_train(addr, rank, gates, done, params_reported):
    """Deterministic 2-rank 'training': per step each rank publishes a
    gradient to the master KV store and waits (long-poll) for the
    peer's, then both apply the identical mean update.  The ONLY
    nondeterminism possible is a lost/duplicated coordination message
    — exactly what master failover must never cause.  Rank 1 joins
    only once rank 0 has told the master that the round takes two
    nodes: a join that overtook that report completed a round of one
    (the default) on a loaded machine, in the no-fault run too."""
    client = MasterClient(addr, node_id=rank)
    try:
        if rank == 0:
            client.report_rdzv_params(2, 2, 60, 1)
            params_reported.set()
        assert params_reported.wait(timeout=60)
        if gates and ("join", rank) in gates:
            gates[("join", rank)].wait(timeout=60)
        client.join_rendezvous(rank, 1)
        _rnd, _grp, world = client.wait_comm_world(
            RendezvousName.ELASTIC_TRAINING, rank, timeout=60.0
        )
        assert rank in world and len(world) == 2, world
        state = np.full(8, 0.125, np.float64)
        for s in range(STEPS):
            grad = np.sin(state * (s + 1) * (rank + 1))
            if gates and ("set", s, rank) in gates:
                gates[("set", s, rank)].wait(timeout=60)
            client.kv_store_set(f"g/{s}/{rank}", grad.tobytes())
            other = client.kv_store_wait(
                f"g/{s}/{1 - rank}", timeout=60.0
            )
            peer = np.frombuffer(other, np.float64)
            state = state + 0.5 * (grad + peer)
        if done is not None:
            done[rank] = state.tobytes()
    finally:
        client.close()


class TestMasterKillMidJob:
    @pytest.fixture()
    def brain_env(self, tmp_path, monkeypatch):
        import dlrover_tpu.master.datastore as ds_mod

        monkeypatch.setenv(
            "DLROVER_TPU_BRAIN_DB", str(tmp_path / "brain.db")
        )
        monkeypatch.setattr(ds_mod, "_default_store", None)
        yield
        store = ds_mod._default_store
        if store is not None:
            store.close()
        ds_mod._default_store = None

    @staticmethod
    def _crash(master):
        """Simulate a crash: the gRPC server vanishes NOW — no final
        snapshot, no graceful drain (``stop()`` would compact the
        journal, which a SIGKILL never does)."""
        if master.control_journal is not None:
            master.control_journal.detach()
            master.control_journal._stopped.set()
        master._server.stop(grace=0)

    def _run_job(self, port, fault=None):
        """Run the 2-agent job; ``fault(master) -> master`` is invoked
        mid-run to kill/replace the master.  Returns both ranks' final
        state bytes."""
        master = LocalJobMaster(port, node_num=2)
        master.prepare()
        addr = f"127.0.0.1:{port}"
        gates = fault.gates if fault else {}
        done = {}
        params_reported = threading.Event()
        threads = [
            threading.Thread(
                target=_toy_train,
                args=(addr, rank, gates, done, params_reported),
                daemon=True,
            )
            for rank in (0, 1)
        ]
        try:
            for t in threads:
                t.start()
            if fault:
                master = fault.run(master)
            for t in threads:
                t.join(timeout=120.0)
            assert not any(t.is_alive() for t in threads), (
                "agents wedged (reconnect/re-park failed)"
            )
        finally:
            master.stop()
        assert set(done) == {0, 1}
        return done

    def test_kill_master_mid_rendezvous_byte_identical(
        self, brain_env, tmp_path, monkeypatch
    ):
        """Rank 0 joins and parks; the master dies before rank 1 ever
        joins; the restarted master must resume the SAME round (or the
        re-asserted join must heal it) and the run's final state must
        match the no-fault run bit for bit."""
        reference = self._run_job(get_free_port())

        test = self

        class Fault:
            def __init__(self):
                # rank 1 joins only after the replacement master is up
                self.gates = {("join", 1): threading.Event()}

            def run(self, master):
                port = master._port
                # rank 0 has joined once its node is in the waiting set
                from dlrover_tpu.common.constants import (
                    RendezvousName as RN,
                )

                rdzv = master.rdzv_managers[RN.ELASTIC_TRAINING]
                deadline = time.time() + 30
                while time.time() < deadline:
                    if rdzv._waiting_nodes:
                        break
                    time.sleep(0.02)
                assert rdzv._waiting_nodes, "rank 0 never joined"
                test._crash(master)
                m2 = LocalJobMaster(port, node_num=2)
                m2.prepare()
                assert m2.incarnation == 2
                self.gates[("join", 1)].set()
                return m2

        # fresh Brain for the fault run (the fixture db already holds
        # the reference run's journal under the same job name)
        import dlrover_tpu.master.datastore as ds_mod

        store = ds_mod._default_store
        if store is not None:
            store.close()
        ds_mod._default_store = None
        monkeypatch.setenv(
            "DLROVER_TPU_BRAIN_DB", str(tmp_path / "brain2.db")
        )

        faulted = self._run_job(get_free_port(), Fault())
        assert faulted[0] == reference[0]
        assert faulted[1] == reference[1]

    def test_kill_master_mid_kv_wait_byte_identical(
        self, brain_env, tmp_path, monkeypatch
    ):
        """Rank 0 publishes its step-2 gradient and parks waiting for
        rank 1's; the master dies mid-wait; rank 1 publishes only to
        the NEW incarnation.  Both sides must heal (replay or client
        re-assert) and the final state must be byte-identical."""
        reference = self._run_job(get_free_port())

        test = self

        class Fault:
            def __init__(self):
                self.gates = {("set", 2, 1): threading.Event()}

            def run(self, master):
                port = master._port
                # rank 0 parked: its step-2 key is set, rank 1's isn't
                deadline = time.time() + 30
                while time.time() < deadline:
                    if master.kv_store.get("g/2/0"):
                        break
                    time.sleep(0.02)
                assert master.kv_store.get("g/2/0"), (
                    "rank 0 never reached step 2"
                )
                time.sleep(0.3)  # let its kv wait park
                test._crash(master)
                m2 = LocalJobMaster(port, node_num=2)
                m2.prepare()
                assert m2.incarnation == 2
                self.gates[("set", 2, 1)].set()
                return m2

        import dlrover_tpu.master.datastore as ds_mod

        store = ds_mod._default_store
        if store is not None:
            store.close()
        ds_mod._default_store = None
        monkeypatch.setenv(
            "DLROVER_TPU_BRAIN_DB", str(tmp_path / "brain2.db")
        )

        faulted = self._run_job(get_free_port(), Fault())
        assert faulted[0] == reference[0]
        assert faulted[1] == reference[1]

    def test_master_gone_for_good_mid_kv_wait_raises_at_deadline(
        self, monkeypatch
    ):
        """A master that dies mid-wait and never comes back: the wait
        outlives the channel's own reconnect deadline (it keeps
        waiting for a replacement) and gives up with ConnectionError
        only when the CALLER's timeout runs out — bounded, not at a
        fixed attempt count and not forever."""
        monkeypatch.setenv(
            "DLROVER_TPU_MASTER_RECONNECT_DEADLINE_S", "1.0"
        )
        port = get_free_port()
        master = LocalJobMaster(port, node_num=1)
        master.prepare()
        client = MasterClient(f"127.0.0.1:{port}", node_id=0)
        errs = []

        def _wait():
            try:
                client.kv_store_wait("never/set", timeout=4.0)
            except (ConnectionError, TimeoutError) as e:
                errs.append(e)

        t = threading.Thread(target=_wait, daemon=True)
        t0 = time.monotonic()
        t.start()
        time.sleep(0.4)  # parked on the live master
        try:
            master._server.stop(grace=0)
            t.join(timeout=30.0)
            elapsed = time.monotonic() - t0
            assert errs and isinstance(errs[0], ConnectionError)
            # past the 1 s reconnect deadline, out by the 4 s timeout
            assert 3.0 <= elapsed < 15.0, elapsed
            assert client._channel.retry_count >= 1
        finally:
            client.close()
            master.stop()
