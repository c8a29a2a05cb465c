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
               snapshot_mode="auto", sparse_tables=None, **extra_args):
        os.environ["DLROVER_TPU_SOCKET_DIR"] = socket_dir
        cfg = LlamaConfig.tiny(remat="none")
        result = auto_accelerate(
            loss_fn=lambda p, b: loss_fn(p, b, cfg),
            optimizer=optax.adamw(1e-3),
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
            load_strategy=load_strategy({"data": 8, "remat": "none"}),
        )
        tokens = np.ones((8, 17), dtype=np.int32)

        def data_iter():
            for _ in range(4):
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
        """The bounded-memory (leaf-wise device->host) snapshot path
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
        is skipped BEFORE the state is pulled to the host (staged) or
        copied on the device (copy): on a v5e the late check stalled
        every skipped step 3 s and ran "copy" mode out of HBM."""
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

        monkeypatch.setattr(t, "_staged_device_get", boom)
        t._snap_fn = boom
        t._maybe_checkpoint(2)

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
