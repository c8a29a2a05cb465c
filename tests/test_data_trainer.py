"""Data loaders (shm ring, elastic tuned loader, device prefetch) and
the high-level Trainer loop with flash-checkpoint resume."""

import json
import multiprocessing as mp
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accelerate import auto_accelerate, load_strategy
from dlrover_tpu.data import (
    ElasticDataLoader,
    ShmBatchWriter,
    ShmDataLoader,
    device_prefetch,
)
from dlrover_tpu.data.shm_dataloader import BatchSpec
from dlrover_tpu.models.llama import (
    LlamaConfig,
    init_params,
    loss_fn,
    param_logical_axes,
)
from dlrover_tpu.parallel.mesh import destroy_parallel_mesh
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs


# the producer does not import jax: it touches only the shm module
_PRODUCER_SCRIPT = """
import sys
sys.path.insert(0, {repo!r})
import numpy as np
from dlrover_tpu.data.shm_dataloader import ShmBatchWriter

writer = ShmBatchWriter({name!r})  # attaches to the consumer's ring
for i in range({n}):
    writer.put(
        {{
            "x": np.full((4, 8), i, dtype=np.float32),
            "y": np.arange(4, dtype=np.int64) + i,
        }}
    )
writer.close()
"""


class TestShmDataLoader:
    def test_cross_process_batches(self):
        import subprocess
        import sys

        name = f"t{os.getpid()}"
        repo = os.path.dirname(os.path.dirname(__file__))
        spec = BatchSpec(
            {"x": ((4, 8), "float32"), "y": ((4,), "int64")}
        )
        loader = ShmDataLoader(name, spec, num_slots=2, timeout=60)
        proc = subprocess.Popen(
            [
                sys.executable,
                "-c",
                _PRODUCER_SCRIPT.format(repo=repo, name=name, n=5),
            ],
            env=dict(os.environ),
        )
        batches = list(loader)
        proc.wait(timeout=30)
        loader.close()
        assert len(batches) == 5
        for i, b in enumerate(batches):
            np.testing.assert_array_equal(b["x"], np.full((4, 8), i))
            np.testing.assert_array_equal(
                b["y"], np.arange(4, dtype=np.int64) + i
            )


class TestElasticDataLoader:
    def test_batch_size_tuning(self, tmp_path):
        config = tmp_path / "paral.json"
        config.write_text(
            json.dumps({"dataloader": {"batch_size": 8}})
        )
        loader = ElasticDataLoader(
            dataset_size=64,
            batch_size=4,
            read_batch=lambda idx: idx,
            config_file=str(config),
            shuffle=False,
        )
        assert loader.batch_size == 8  # tuned at init
        batches = list(loader)
        assert all(len(b) == 8 for b in batches)

    def test_resume_mid_epoch(self):
        loader = ElasticDataLoader(
            dataset_size=32,
            batch_size=4,
            read_batch=lambda idx: idx,
            config_file="/nonexistent",
            shuffle=False,
        )
        it = iter(loader)
        first = next(it)
        state = loader.state_dict()
        loader2 = ElasticDataLoader(
            dataset_size=32,
            batch_size=4,
            read_batch=lambda idx: idx,
            config_file="/nonexistent",
            shuffle=False,
        )
        loader2.load_state_dict(state)
        resumed = next(iter(loader2))
        assert set(first) | set(resumed) <= set(range(32))
        assert not (set(first) & set(resumed))  # no repeats


class TestPrefetch:
    def test_order_preserved(self):
        data = [{"x": np.full((2,), i)} for i in range(6)]
        out = list(device_prefetch(iter(data), size=3))
        assert len(out) == 6
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b["x"]), i)


class TestTrainer:
    def _build(self, tmp_path, max_steps, socket_dir,
               snapshot_mode="auto", sparse_tables=None, strategy=None,
               **extra_args):
        os.environ["DLROVER_TPU_SOCKET_DIR"] = socket_dir
        cfg = LlamaConfig.tiny(remat="none")
        result = auto_accelerate(
            loss_fn=lambda p, b: loss_fn(p, b, cfg),
            optimizer=optax.adamw(1e-3),
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
            load_strategy=load_strategy(
                dict(strategy or {"data": 8}, remat="none")
            ),
        )
        tokens = np.ones((8, 17), dtype=np.int32)

        def data_iter():
            for _ in range(max(4, max_steps)):
                yield {"tokens": tokens}

        args = TrainingArgs(
            max_steps=max_steps,
            checkpoint_dir=str(tmp_path / "ckpt"),
            save_memory_interval=2,
            save_storage_interval=4,
            log_interval=100,
            micro_batch_size=8,
            snapshot_mode=snapshot_mode,
            sparse_tables=sparse_tables,
            **extra_args,
        )
        return Trainer(result, args, data_iter)

    def test_train_and_resume(self, tmp_path):
        sock = str(tmp_path / "socks")
        trainer = Trainer.__new__(Trainer)  # noqa: F841 (appease lint)
        t1 = self._build(tmp_path, max_steps=6, socket_dir=sock)
        summary = t1.train()
        assert summary["final_step"] == 6

        # a fresh trainer resumes from the persisted/shm checkpoint
        t2 = self._build(tmp_path, max_steps=8, socket_dir=sock)
        start = t2._init_or_restore_state()
        assert start >= 4  # at least the last storage save

    def test_staged_snapshot_mode_resumes(self, tmp_path):
        """The bounded-device-memory snapshot path (the one compiled
        copy, landing in host memory where the backend has it)
        produces checkpoints a fresh trainer restores from (round-2
        advisor: the full-copy snapshot is a 2x HBM transient; staged
        is the near-capacity alternative)."""
        sock = str(tmp_path / "socks2")
        t1 = self._build(
            tmp_path, max_steps=4, socket_dir=sock,
            snapshot_mode="staged",
        )
        summary = t1.train()
        assert summary["final_step"] == 4
        t2 = self._build(tmp_path, max_steps=6, socket_dir=sock)
        start = t2._init_or_restore_state()
        assert start >= 4

    @pytest.mark.parametrize("mode", ["staged", "copy"])
    def test_skipped_snapshot_costs_no_device_work(
        self, tmp_path, monkeypatch, mode
    ):
        """While the previous snapshot is still draining the next one
        is skipped BEFORE the one snapshot program is built or run,
        whatever the mode: on a v5e the late check stalled every
        skipped step 3 s and ran "copy" mode out of HBM."""
        t = self._build(
            tmp_path, max_steps=2, socket_dir=str(tmp_path / "socks5"),
            snapshot_mode=mode,
        )
        t._init_or_restore_state()
        monkeypatch.setattr(
            t._engine, "snapshot_slot_free", lambda step: False
        )

        def boom(*_a, **_k):
            raise AssertionError("device work for a skipped snapshot")

        monkeypatch.setattr(t, "_snapshot_program", boom)
        t._snap_fn = boom
        t._maybe_checkpoint(2)

    def test_staged_snapshot_survives_the_donating_step(
        self, tmp_path, monkeypatch
    ):
        """The snapshot is the state AT its step, bit for bit, although
        the next train step donates and overwrites the state's buffers
        before the drain has read a byte."""
        t = self._build(
            tmp_path, max_steps=4, socket_dir=str(tmp_path / "socks6"),
            snapshot_mode="staged",
        )
        t._init_or_restore_state()
        batch = {"tokens": jnp.ones((8, 17), jnp.int32)}
        t.state, _ = t._fns.train_step(t.state, batch)
        want = jax.device_get(t.state)
        held = []
        drain = t._engine.save_to_memory
        monkeypatch.setattr(
            t._engine, "save_to_memory",
            lambda step, snap, **kw: held.append((step, snap, kw)),
        )
        t._maybe_checkpoint(2)
        for _ in range(2):  # the state's buffers are donated, twice
            t.state, _ = t._fns.train_step(t.state, batch)
        jax.block_until_ready(t.state)
        (step, snap, kw), = held
        assert drain(step, snap, **dict(kw, blocking=True))
        got_step, arrays = t._engine._shm_handler.load_state(copy=True)
        assert got_step == 2
        flat, _ = jax.tree_util.tree_flatten_with_path(want)
        assert len(flat) == len(arrays)
        for path, leaf in flat:
            got = arrays[jax.tree_util.keystr(path)]
            assert got.dtype == leaf.dtype and got.shape == leaf.shape
            assert got.tobytes() == np.asarray(leaf).tobytes(), path
        # the trained state has moved on: the test compared a snapshot
        moved = jax.device_get(t.state)["params"]["embed"]
        assert moved.tobytes() != np.asarray(
            want["params"]["embed"]
        ).tobytes()
        t._engine.close()

    def test_snapshot_program_compiles_once(self, tmp_path):
        """Three snapshots, ONE compile of ONE program: built at the
        first snapshot and called as it is after."""
        import jax.monitoring

        compiled = []

        def listener(event, _secs, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiled.append(kw.get("fun_name"))

        t = self._build(
            tmp_path, max_steps=6, socket_dir=str(tmp_path / "socks7"),
            snapshot_mode="staged",
        )
        t._init_or_restore_state()
        batch = {"tokens": jnp.ones((8, 17), jnp.int32)}
        programs = []
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            for step in range(1, 7):
                t.state, _ = t._fns.train_step(t.state, batch)
                if step % 2 == 0:
                    t._maybe_checkpoint(step)
                    programs.append(t._snap_fn)
                    assert t._engine.wait_for_snapshot(timeout=60)
        finally:
            jax.monitoring.unregister_event_duration_listener(listener)
        assert t._engine.skipped_snapshots == 0
        assert isinstance(programs[0], jax.stages.Compiled)
        assert all(fn is programs[0] for fn in programs)
        assert [n for n in compiled if n and "snapshot_copy" in n] == [
            "jit(snapshot_copy)"
        ]
        t._engine.close()

    @pytest.mark.parametrize(
        "mode,probe,kind",
        [
            ("staged", True, "pinned_host"),
            ("staged", False, None),
            ("copy", True, None),
        ],
    )
    def test_snapshot_lands_where_the_mode_and_the_backend_say(
        self, tmp_path, monkeypatch, mode, probe, kind
    ):
        """``staged`` asks for each leaf's OWN sharding in
        ``pinned_host`` memory where the backend has it in-program;
        where it has not (this CPU), and in ``copy`` mode, for the
        device's own.  Read off the requested shardings: nothing is
        lowered."""
        from dlrover_tpu.common import jax_env

        t = self._build(
            tmp_path, max_steps=2, socket_dir=str(tmp_path / "socks8"),
            snapshot_mode=mode, strategy={"data": 2, "fsdp": 4},
        )
        t._init_or_restore_state()
        monkeypatch.setattr(jax_env, "pinned_host_works", lambda: probe)
        asked = t._snapshot_shardings()
        leaves = jax.tree_util.tree_leaves(t.state)
        placed = jax.tree_util.tree_leaves(asked)
        assert len(placed) == len(leaves)
        for leaf, sharding in zip(leaves, placed):
            if kind is None:
                assert sharding == leaf.sharding
            else:
                assert sharding.memory_kind == kind
                assert sharding == leaf.sharding.with_memory_kind(kind)
        assert t._snap_memory_kind == (kind or "device")
        t._engine.close()

    @pytest.mark.parametrize("sharded", [False, True])
    def test_host_leaf_hands_the_drain_bytes_and_keeps_none(self, sharded):
        """What the drain gets for a leaf of a recycled host tree:
        shape, dtype, and on ``np.asarray`` the bytes — read through a
        throwaway handle, so no transfer is launched up front
        (``copy_to_host_async`` is not offered) and no host copy is
        left on the array the trainer keeps."""
        from jax.sharding import NamedSharding, PartitionSpec

        from dlrover_tpu.trainer.trainer import _HostLeaf

        mesh = jax.make_mesh((8,), ("x",))
        sharding = NamedSharding(
            mesh, PartitionSpec("x") if sharded else PartitionSpec()
        ).with_memory_kind("pinned_host")
        want = np.arange(64 * 3, dtype=np.float32).reshape(64, 3)
        array = jax.device_put(want, sharding)
        assert array.sharding.memory_kind == "pinned_host"
        leaf = _HostLeaf(array)
        assert leaf.shape == (64, 3) and leaf.dtype == np.float32
        assert not hasattr(leaf, "copy_to_host_async")
        got = np.asarray(leaf)
        assert got.tobytes() == want.tobytes()
        assert array._npy_value is None

    def test_sharded_state_snapshots_and_restores_through_staged(
        self, tmp_path
    ):
        """A state sharded over the mesh (fsdp 4 x data 2) takes the
        same program, each leaf under its own sharding, and a fresh
        trainer restores it bit for bit."""
        sock = str(tmp_path / "socks9")
        mesh = {"data": 2, "fsdp": 4}
        t1 = self._build(
            tmp_path, max_steps=4, socket_dir=sock,
            snapshot_mode="staged", strategy=mesh,
        )
        assert t1.train()["final_step"] == 4
        assert any(
            not leaf.sharding.is_fully_replicated
            for leaf in jax.tree_util.tree_leaves(t1.state)
        )
        t2 = self._build(
            tmp_path, max_steps=6, socket_dir=sock,
            snapshot_mode="staged", strategy=mesh,
        )
        assert t2._init_or_restore_state() == 4
        for a, b in zip(
            jax.tree_util.tree_leaves(jax.device_get(t1.state)),
            jax.tree_util.tree_leaves(jax.device_get(t2.state)),
        ):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert t2._engine.skipped_snapshots == 0

    def test_replay_recorder_wired(self, tmp_path):
        """With replay_dir set, the Trainer ring-logs every batch and
        digests the state on the configured cadence."""
        import json

        sock = str(tmp_path / "socks4")
        t = self._build(
            tmp_path, max_steps=4, socket_dir=sock,
            replay_dir=str(tmp_path / "replay"),
            replay_digest_interval=2,
        )
        t.train()
        rank_dir = tmp_path / "replay" / "rank00000"
        batches = [
            f.name for f in rank_dir.iterdir()
            if f.name.startswith("batch-")
        ]
        assert len(batches) == 4
        entries = [
            json.loads(x)
            for x in (rank_dir / "journal.jsonl").read_text().splitlines()
        ]
        digests = [e for e in entries if "state_digest" in e]
        assert {e["step"] for e in digests} == {2, 4}

    def test_sparse_tables_save_and_restore_with_dense(self, tmp_path):
        """Host-side KvTable embeddings checkpoint at the storage tier
        alongside the dense state and restore on resume (reference
        role: tfplus saver integration)."""
        from dlrover_tpu.sparse.kv_table import KvTable

        sock = str(tmp_path / "socks3")
        table = KvTable(dim=4)
        keys = np.arange(10, dtype=np.int64)
        table.scatter(keys, np.full((10, 4), 7.0, np.float32))
        t1 = self._build(
            tmp_path, max_steps=4, socket_dir=sock,
            sparse_tables={"emb": table},
        )
        summary = t1.train()
        assert summary["final_step"] == 4

        fresh = KvTable(dim=4)
        t2 = self._build(
            tmp_path, max_steps=6, socket_dir=sock,
            sparse_tables={"emb": fresh},
        )
        start = t2._init_or_restore_state()
        assert start >= 4
        got = fresh.gather(keys, insert_missing=False)
        np.testing.assert_allclose(got, 7.0)
        table.close()
        fresh.close()
