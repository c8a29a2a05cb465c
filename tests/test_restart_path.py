"""Overlapped restart critical path (trainer/restart_path.py +
CheckpointEngine.start_prefetch/finish_restore + TrainStepFns.aot_compile).

The contracts under test:

- the overlapped restore is BYTE-IDENTICAL to the serial ``load`` —
  from shm (zero-copy staging) and from a leaf-streamed storage shard;
- ANY prefetch/compile failure, at launch or on the leg's thread,
  yields the serial order (clean fallback, never a corrupt state);
- the two legs genuinely run concurrently: their timeline spans'
  mono-anchored intervals intersect;
- the AOT-compiled train step computes exactly what the lazy jit does.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.agent.ckpt_shm import (
    SharedMemoryHandler,
    TruncatedShardError,
    stream_shard_leaves,
)
from dlrover_tpu.observability.events import (
    EventLogger,
    pair_spans,
    read_events,
    set_default_event_logger,
)
from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine
from dlrover_tpu.trainer.restart_path import RestartCoordinator


def make_state(scale=1.0):
    return {
        "params": {
            "w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
            * scale,
            "b": jnp.full((16,), 0.5, jnp.bfloat16),
        },
        "mu": np.full((8, 8), 0.25, np.float32) * scale,
        "step": np.int64(3),
    }


def assert_bytes_equal(a, b):
    fa = {
        jax.tree_util.keystr(p): v
        for p, v in jax.tree_util.tree_leaves_with_path(a)
    }
    fb = {
        jax.tree_util.keystr(p): v
        for p, v in jax.tree_util.tree_leaves_with_path(b)
    }
    assert set(fa) == set(fb)
    for k in sorted(fa):
        assert (
            np.asarray(fa[k]).tobytes() == np.asarray(fb[k]).tobytes()
        ), k


def _engine(ckpt_dir, name):
    return CheckpointEngine(
        checkpoint_dir=ckpt_dir, process_rank=0, process_count=1,
        local_shard_num=1, name=name,
    )


class TestStreamShardLeaves:
    def test_leaves_stream_in_file_order(self, tmp_ckpt_dir):
        handler = SharedMemoryHandler(0, name="stream1", host=True)
        try:
            state = {
                "a": np.arange(10, dtype=np.float32),
                "b": np.full((4, 4), 7.0, np.float64),
            }
            handler.save_state(5, state)
            from dlrover_tpu.common.storage import PosixDiskStorage

            path = os.path.join(tmp_ckpt_dir, "s.drckpt")
            assert handler.dump_to_file(
                path, PosixDiskStorage()
            ) is not None
            items = list(stream_shard_leaves(path))
            assert items[0][0] == "meta" and items[0][1] == 5
            leaves = [(k, v) for kind, k, v in items[1:]]
            assert [k for k, _ in leaves] == ["['a']", "['b']"]
            np.testing.assert_array_equal(leaves[0][1], state["a"])
            np.testing.assert_array_equal(leaves[1][1], state["b"])
        finally:
            handler.close(unlink=True)

    def test_truncated_file_raises(self, tmp_ckpt_dir):
        handler = SharedMemoryHandler(0, name="stream2", host=True)
        try:
            handler.save_state(
                6, {"a": np.ones(1000, np.float64)}
            )
            from dlrover_tpu.common.storage import PosixDiskStorage

            path = os.path.join(tmp_ckpt_dir, "t.drckpt")
            handler.dump_to_file(path, PosixDiskStorage())
            data = open(path, "rb").read()
            with open(path, "wb") as f:
                f.write(data[: len(data) - 512])
            with pytest.raises(TruncatedShardError):
                for _ in stream_shard_leaves(path):
                    pass
            # the tolerant reader still maps truncation to "absent"
            from dlrover_tpu.agent.ckpt_shm import read_shard_file

            step, arrays = read_shard_file(path)
            assert step == -1 and arrays == {}
        finally:
            handler.close(unlink=True)


class TestEngineOverlapRestore:
    def test_shm_overlap_matches_serial_bytes(self, tmp_ckpt_dir):
        eng = _engine(tmp_ckpt_dir, "ov1")
        try:
            state = make_state()
            host = jax.device_get(state)
            assert eng.save_to_memory(3, host)
            prefetch = eng.start_prefetch()
            step_o, overlap = eng.finish_restore(
                prefetch, target=state
            )
            step_s, serial = eng.load(target=state)
            assert step_o == step_s == 3
            assert_bytes_equal(overlap, serial)
            # restored jax leaves keep their shardings
            assert isinstance(overlap["params"]["w"], jax.Array)
            assert overlap["params"]["b"].dtype == jnp.bfloat16
        finally:
            eng.close()

    def test_storage_overlap_streams_leaves(self, tmp_ckpt_dir):
        eng = _engine(tmp_ckpt_dir, "ov2")
        try:
            state = make_state(scale=2.0)
            assert eng.save_to_storage(9, jax.device_get(state))
            assert eng.wait_for_persist(9, timeout=60)
            # shm gone (relaunched node): only the committed storage
            # step remains — the prefetch must stage it leaf-streamed
            eng._shm_handler.mark_invalid()
            prefetch = eng.start_prefetch()
            step_o, overlap = eng.finish_restore(
                prefetch, target=state
            )
            assert step_o == 9
            eng._shm_handler.mark_invalid()
            step_s, serial = eng.load(target=state)
            assert step_s == 9
            assert_bytes_equal(overlap, serial)
        finally:
            eng.close()

    def test_no_target_matches_serial(self, tmp_ckpt_dir):
        eng = _engine(tmp_ckpt_dir, "ov3")
        try:
            host = jax.device_get(make_state())
            assert eng.save_to_memory(3, host)
            prefetch = eng.start_prefetch()
            step_o, overlap = eng.finish_restore(prefetch)
            step_s, serial = eng.load()
            assert step_o == step_s == 3
            assert set(overlap) == set(serial)
            for k in overlap:
                assert (
                    np.asarray(overlap[k]).tobytes()
                    == np.asarray(serial[k]).tobytes()
                )
                # standalone copies, not live shm views (serial
                # parity: the next snapshot must not mutate them)
                assert overlap[k].base is None or not isinstance(
                    overlap[k].base, memoryview
                )
        finally:
            eng.close()

    def test_prefetch_thread_failure_falls_back_serial(
        self, tmp_ckpt_dir, monkeypatch
    ):
        eng = _engine(tmp_ckpt_dir, "ov4")
        try:
            state = make_state()
            host = jax.device_get(state)
            assert eng.save_to_memory(3, host)

            def boom():
                raise RuntimeError("prefetch thread died")

            monkeypatch.setattr(
                eng._shm_handler, "steps_available", boom
            )
            prefetch = eng.start_prefetch()
            prefetch.join()
            assert prefetch.error is not None
            monkeypatch.undo()  # serial path reads the real handler
            step, restored = eng.finish_restore(
                prefetch, target=state
            )
            assert step == 3
            step_s, serial = eng.load(target=state)
            assert_bytes_equal(restored, serial)
        finally:
            eng.close()

    def test_consensus_divergence_falls_back_serial(
        self, tmp_ckpt_dir
    ):
        """Consensus picks a step the prefetch did NOT stage (a peer
        lacks our newest shm snapshot): finish_restore must restore
        the agreed older step through the serial path."""
        eng = _engine(tmp_ckpt_dir, "ov5")
        try:
            committed = make_state(scale=1.0)
            newer = make_state(scale=9.0)
            assert eng.save_to_storage(1, jax.device_get(committed))
            assert eng.wait_for_persist(1, timeout=60)
            assert eng.save_to_memory(2, jax.device_get(newer))
            from dlrover_tpu.trainer.checkpoint.engine import (
                _newest_common_step,
            )

            eng._step_sync_fn = lambda avail: _newest_common_step(
                [avail, [1, 1, 1]]
            )
            prefetch = eng.start_prefetch()
            step, restored = eng.finish_restore(
                prefetch, target=newer
            )
            assert step == 1
            np.testing.assert_array_equal(
                np.asarray(restored["params"]["w"]),
                np.asarray(committed["params"]["w"]),
            )
        finally:
            eng.close()


class TestRestartCoordinator:
    def _events(self, tmp_path):
        p = str(tmp_path / "events.jsonl")
        log = EventLogger(path=p, job="rp")
        set_default_event_logger(log)
        return p, log

    def teardown_method(self, method):
        set_default_event_logger(None)

    def test_legs_overlap_on_timeline(self, tmp_ckpt_dir, tmp_path):
        """The tentpole claim: restore prefetch and AOT compile run
        CONCURRENTLY — their spans' mono-anchored intervals
        intersect, under the restart_path parent."""
        p, log = self._events(tmp_path)
        eng = _engine(tmp_ckpt_dir, "co1")
        try:
            state = make_state()
            assert eng.save_to_memory(3, jax.device_get(state))

            def slow_compile():
                time.sleep(0.2)
                return "compiled-artifact"

            coord = RestartCoordinator(eng, events=log)
            coord.start(compile_fn=slow_compile)
            step, restored = coord.finish_restore(target=state)
            assert step == 3
            fn = coord.resolve_train_step(fallback="lazy")
            assert fn == "compiled-artifact"
            ivs = pair_spans(read_events(p))
            by_phase = {}
            for iv in ivs:
                by_phase.setdefault(iv["phase"], []).append(iv)
            assert "restore_prefetch" in by_phase
            assert "aot_compile" in by_phase
            assert "restart_path" in by_phase
            assert "finish_restore" in by_phase
            pre = by_phase["restore_prefetch"][0]
            aot = by_phase["aot_compile"][0]
            lo = max(pre["start"], aot["start"])
            hi = min(pre["end"], aot["end"])
            assert lo < hi, (pre, aot)  # intervals intersect
            # the parent covers both legs
            parent = by_phase["restart_path"][0]
            assert parent["start"] <= lo + 1e-6
            assert parent["end"] >= max(pre["end"], aot["end"]) - 1e-6
        finally:
            eng.close()

    def test_launch_failure_yields_serial_order(
        self, tmp_ckpt_dir, tmp_path, monkeypatch
    ):
        """A leg that cannot even be LAUNCHED (not one that dies on
        its thread): the coordinator gives up the overlap for this
        restart — no compile leg behind the failed prefetch, the lazy
        step, a serial ``load`` with byte-identical state, and a
        timeline without leg spans whose parent span is closed."""
        p, log = self._events(tmp_path)
        eng = _engine(tmp_ckpt_dir, "co2")
        try:
            state = make_state()
            assert eng.save_to_memory(3, jax.device_get(state))

            def no_thread(**_kw):
                raise RuntimeError("can't start new thread")

            monkeypatch.setattr(eng, "start_prefetch", no_thread)
            called = []
            coord = RestartCoordinator(eng, events=log)
            coord.start(
                compile_fn=lambda: called.append(1) or "artifact"
            )
            assert coord.overlap is False
            assert (
                coord.resolve_train_step(fallback="lazy") == "lazy"
            )
            assert not called  # no background compile was launched
            step, restored = coord.finish_restore(target=state)
            assert step == 3
            step_s, serial = eng.load(target=state)
            assert_bytes_equal(restored, serial)
            ivs = pair_spans(read_events(p))
            phases = {iv["phase"] for iv in ivs}
            assert "restore_prefetch" not in phases
            assert "aot_compile" not in phases
            assert "finish_restore" not in phases
            assert "restart_path" in phases  # opened, and closed
        finally:
            eng.close()

    def test_compile_leg_failure_falls_back(
        self, tmp_ckpt_dir, tmp_path
    ):
        _p, log = self._events(tmp_path)
        eng = _engine(tmp_ckpt_dir, "co3")
        try:
            state = make_state()
            assert eng.save_to_memory(3, jax.device_get(state))

            def broken_compile():
                raise RuntimeError("XLA exploded")

            coord = RestartCoordinator(eng, events=log)
            coord.start(compile_fn=broken_compile)
            assert (
                coord.resolve_train_step(fallback="lazy") == "lazy"
            )
            step, restored = coord.finish_restore(target=state)
            assert step == 3  # restore leg unaffected
        finally:
            eng.close()

    def test_coordinator_without_engine(self, tmp_path):
        _p, log = self._events(tmp_path)
        coord = RestartCoordinator(None, events=log)
        coord.start(compile_fn=lambda: "artifact")
        assert coord.finish_restore(target=None) == (-1, None)
        assert coord.resolve_train_step() == "artifact"


class TestAotCompileParity:
    def test_aot_equals_lazy_jit(self):
        """TrainStepFns.aot_compile: the AOT executable and the lazy
        jit produce identical states and metrics from the same
        inputs."""
        import optax

        from dlrover_tpu.parallel.mesh import (
            AxisName,
            create_parallel_mesh,
        )
        from dlrover_tpu.parallel.sharding import default_rules
        from dlrover_tpu.parallel.train_step import build_train_step

        mesh_ctx = create_parallel_mesh([(AxisName.DATA, -1)])
        rules = default_rules()

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        fns = build_train_step(
            loss_fn,
            optax.adam(1e-2),
            lambda rng: {
                "w": jax.random.normal(rng, (16, 4), jnp.float32)
            },
            {"w": (None, None)},
            mesh_ctx,
            rules,
        )
        assert fns.state_shape is not None
        batch = {"x": jnp.ones((8, 16)), "y": jnp.zeros((8, 4))}
        compiled = fns.aot_compile(batch)
        s1, m1 = compiled(
            fns.init_state(jax.random.PRNGKey(0)), batch
        )
        s2, m2 = fns.train_step(
            fns.init_state(jax.random.PRNGKey(0)), batch
        )
        assert float(m1["loss"]) == float(m2["loss"])
        np.testing.assert_array_equal(
            np.asarray(s1["params"]["w"]),
            np.asarray(s2["params"]["w"]),
        )
