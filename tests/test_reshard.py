"""Elastic mesh resharding: device-count-agnostic shard format,
overlap-range resharded restore, shm layout gating.

The headline pin is the 8→4→8 round-trip: a simulated 8-host job
checkpoints an axis-0-sharded optimizer state, "loses" half its
hosts, reshard-restores onto 4, trains one (simulated) step, saves,
grows back to 8, and ends with optimizer state BITWISE-identical to
an uninterrupted run.  Old-format (headerless) shards must still
restore on an unchanged world, and a grown world's new rank reads its
rows out of the old ranks' files.
"""

import json
import os

import numpy as np
import pytest

from dlrover_tpu.agent.ckpt_shm import SharedMemoryHandler
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.trainer.checkpoint import reshard as R
from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine
from dlrover_tpu.trainer.checkpoint.reshard import (
    LeafLayout,
    ReshardError,
    axis0_layouts,
    iter_copy_runs,
    plan_reshard,
    read_shard_header,
    replicated_layouts,
    scan_checkpoint_shards,
    stream_resharded_leaves,
)


def _materialize(src: np.ndarray, src_box, dst_box, runs):
    """Apply copy runs byte-for-byte and return the dst block."""
    dst = np.zeros(dst_box[1], dtype=src.dtype)
    src_flat = src.reshape(-1).view(np.uint8)
    dst_flat = dst.reshape(-1).view(np.uint8)
    for s_off, d_off, nb in runs:
        dst_flat[d_off : d_off + nb] = src_flat[s_off : s_off + nb]
    return dst


class TestCopyRuns:
    def test_replicated_is_one_run(self):
        runs = list(
            iter_copy_runs((0, 0), (4, 6), (0, 0), (4, 6), 4)
        )
        assert runs == [(0, 0, 4 * 6 * 4)]

    def test_scalar_leaf(self):
        assert list(iter_copy_runs((), (), (), (), 8)) == [(0, 0, 8)]

    def test_partial_inner_dim_runs_per_row(self):
        # src holds cols 0..4, dst wants cols 2..6: per-row 2-byte runs
        runs = list(
            iter_copy_runs((0, 0), (4, 4), (0, 2), (4, 4), 1)
        )
        assert runs == [(2 + 4 * r, 4 * r, 2) for r in range(4)]

    def test_axis0_reshard_bytes_exact(self):
        g = np.arange(24 * 5, dtype=np.float32).reshape(24, 5)
        # dst rank1-of-4 (rows 6..12) from src rank2/3-of-8
        got = np.zeros((6, 5), np.float32)
        got_u8 = got.reshape(-1).view(np.uint8)
        for sr in range(8):
            src = g[sr * 3 : (sr + 1) * 3]
            for s_off, d_off, nb in iter_copy_runs(
                (sr * 3, 0), (3, 5), (6, 0), (6, 5), 4
            ):
                got_u8[d_off : d_off + nb] = (
                    src.reshape(-1).view(np.uint8)[s_off : s_off + nb]
                )
        np.testing.assert_array_equal(got, g[6:12])

    def test_3d_odd_split(self):
        g = np.arange(7 * 3 * 2, dtype=np.int16).reshape(7, 3, 2)
        src_box = ((2, 0, 0), (3, 3, 2))  # rows 2..5
        dst_box = ((4, 0, 0), (3, 3, 2))  # rows 4..7
        runs = list(
            iter_copy_runs(
                src_box[0], src_box[1], dst_box[0], dst_box[1], 2
            )
        )
        out = _materialize(g[2:5], src_box, dst_box, runs)
        np.testing.assert_array_equal(out[:1], g[4:5])


class TestLayouts:
    def test_layout_validation(self):
        with pytest.raises(ValueError):
            LeafLayout((4,), (2,), (3,))  # block exceeds global
        with pytest.raises(ValueError):
            LeafLayout((4, 4), (0,), (4,))  # rank mismatch

    def test_replicated_and_axis0(self):
        tree = {"w": np.zeros((8, 2)), "b": np.zeros(())}
        rep = replicated_layouts(tree)
        assert rep["['w']"]["start"] == [0, 0]
        ax = axis0_layouts(tree, rank=3, world=4)
        assert ax["['w']"]["global_shape"] == [32, 2]
        assert ax["['w']"]["start"] == [24, 0]
        # scalars stay replicated
        assert ax["['b']"]["global_shape"] == []


class TestDeriveLayouts:
    def test_sharded_array_yields_block_layout(self):
        """A non-replicated jax.Array must produce a real block
        layout — regression: tuples of slice objects are unhashable
        before Python 3.12, and the old dedup silently degraded
        EVERY sharded leaf to None (reshard disabled)."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from dlrover_tpu.trainer.checkpoint.reshard import (
            derive_layouts,
        )

        devices = jax.devices()
        if len(devices) < 2:
            pytest.skip("needs >1 device (conftest forces 8)")
        mesh = Mesh(np.array(devices), ("d",))
        sharding = NamedSharding(mesh, PartitionSpec("d"))
        arr = jax.device_put(
            np.arange(len(devices) * 4, dtype=np.float32), sharding
        )
        rep = jax.device_put(
            np.ones((3,), np.float32),
            NamedSharding(mesh, PartitionSpec()),
        )
        layouts = derive_layouts({"w": arr, "b": rep})
        assert layouts is not None, (
            "sharded leaf degraded to None — reshard disabled"
        )
        # single process owns every shard: the union block is the
        # full leaf
        assert layouts["['w']"]["global_shape"] == [
            len(devices) * 4
        ]
        assert layouts["['w']"]["start"] == [0]
        assert layouts["['b']"]["shape"] == [3]


def _opt_state(rows: int, cols: int):
    """An optimizer-shaped global state: fp32 params, fp32 momentum,
    fp64 second moment, a replicated int32 step counter."""
    rng = np.random.default_rng(7)
    return {
        "p": rng.standard_normal((rows, cols)).astype(np.float32),
        "m": rng.standard_normal((rows, cols)).astype(np.float32),
        "v": np.abs(rng.standard_normal((rows, cols))).astype(
            np.float64
        ),
        "step": np.int32(100),
    }


def _rank_tree(g, rank, world):
    per = g["p"].shape[0] // world
    return {
        "p": g["p"][rank * per : (rank + 1) * per],
        "m": g["m"][rank * per : (rank + 1) * per],
        "v": g["v"][rank * per : (rank + 1) * per],
        "step": g["step"],
    }


def _rank_layouts(tree, rank, world):
    lay = axis0_layouts(
        {k: v for k, v in tree.items() if k != "step"}, rank, world
    )
    lay.update(replicated_layouts({"step": tree["step"]}))
    return lay


def _engines(ckpt_dir, world, name, **kw):
    """Simulated hosts: one engine per rank; rank 0 hosts the saver
    serving every shard's lock/meta endpoints, so build it first."""
    return [
        CheckpointEngine(
            checkpoint_dir=ckpt_dir,
            process_rank=r,
            process_count=world,
            local_shard_num=world,
            name=name,
            step_sync_fn=lambda avail: max(avail),
            **kw,
        )
        for r in range(world)
    ]


def _save_world(engines, g, step, world):
    """Every rank snapshots its slice; rank 0 triggers the persist."""
    for r, eng in enumerate(engines):
        tree = _rank_tree(g, r, world)
        lay = _rank_layouts(tree, r, world)
        if r == 0:
            continue
        assert eng.save_to_memory(step, tree, layouts=lay)
    tree0 = _rank_tree(g, 0, world)
    assert engines[0].save_to_storage(
        step, tree0, layouts=_rank_layouts(tree0, 0, world)
    )
    assert engines[0].wait_for_persist(step, timeout=120)


def _close_all(engines):
    for eng in engines[1:]:
        eng.close()
    engines[0].close()


def _restore_world(ckpt_dir, world, name, g_like):
    """Each new rank reshard-restores its slice; returns the
    reassembled global state."""
    engines = _engines(ckpt_dir, world, name)
    rows = g_like["p"].shape[0]
    per = rows // world
    out = {
        "p": np.zeros_like(g_like["p"]),
        "m": np.zeros_like(g_like["m"]),
        "v": np.zeros_like(g_like["v"]),
        "step": None,
    }
    steps = set()
    try:
        for r, eng in enumerate(engines):
            target = {
                "p": np.zeros((per,) + g_like["p"].shape[1:],
                              g_like["p"].dtype),
                "m": np.zeros((per,) + g_like["m"].shape[1:],
                              g_like["m"].dtype),
                "v": np.zeros((per,) + g_like["v"].shape[1:],
                              g_like["v"].dtype),
                "step": np.int32(0),
            }
            lay = _rank_layouts(target, r, world)
            got, arrays = eng.load(layouts=lay)
            steps.add(got)
            for k in ("p", "m", "v"):
                out[k][r * per : (r + 1) * per] = arrays[f"['{k}']"]
            out["step"] = arrays["['step']"]
    finally:
        _close_all(engines)
    assert len(steps) == 1, steps
    return steps.pop(), out


@pytest.mark.usefixtures("tmp_ckpt_dir")
class TestReshardRoundTrip:
    def test_8_to_4_to_8_bitwise(self, tmp_ckpt_dir):
        """The acceptance pin: shrink to half the hosts mid-run, grow
        back, and end bitwise-identical to the uninterrupted run."""
        g0 = _opt_state(rows=32, cols=6)

        # ---- world 8 trains to step 5 and checkpoints
        engines = _engines(tmp_ckpt_dir, 8, "rt_w8")
        try:
            _save_world(engines, g0, step=5, world=8)
        finally:
            _close_all(engines)

        # ---- shrink: 4 survivors reshard-restore
        step, g1 = _restore_world(tmp_ckpt_dir, 4, "rt_w4a", g0)
        assert step == 5
        for k in ("p", "m", "v"):
            np.testing.assert_array_equal(g1[k], g0[k])
        assert int(g1["step"]) == 100

        # ---- world 4 "trains" one deterministic step and saves —
        # the SAME update an uninterrupted 8-host run would apply
        g2 = {
            "p": g1["p"] - 0.01 * g1["m"],
            "m": 0.9 * g1["m"],
            "v": 0.99 * g1["v"],
            "step": np.int32(int(g1["step"]) + 1),
        }
        engines = _engines(tmp_ckpt_dir, 4, "rt_w4b")
        try:
            _save_world(engines, g2, step=6, world=4)
        finally:
            _close_all(engines)

        # ---- grow back: 8 ranks reshard-restore the 4-way shards
        step, g3 = _restore_world(tmp_ckpt_dir, 8, "rt_w8b", g2)
        assert step == 6
        uninterrupted = {
            "p": g0["p"] - 0.01 * g0["m"],
            "m": (0.9 * g0["m"]).astype(np.float32),
            "v": 0.99 * g0["v"],
        }
        for k in ("p", "m", "v"):
            assert g3[k].dtype == uninterrupted[k].dtype
            np.testing.assert_array_equal(g3[k], uninterrupted[k])
        assert int(g3["step"]) == 101

    def test_old_format_restores_on_unchanged_world(
        self, tmp_ckpt_dir
    ):
        """Headerless (pre-layout) shards keep restoring when the
        world has not changed — with and without requested layouts."""
        g = _opt_state(rows=8, cols=4)
        engines = _engines(tmp_ckpt_dir, 2, "old_w2")
        try:
            for r, eng in enumerate(engines):
                tree = _rank_tree(g, r, 2)
                if r == 0:
                    continue
                assert eng.save_to_memory(3, tree)  # NO layouts
            assert engines[0].save_to_storage(3, _rank_tree(g, 0, 2))
            assert engines[0].wait_for_persist(3, timeout=120)
        finally:
            _close_all(engines)
        # header really is old-format
        info = read_shard_header(
            os.path.join(
                tmp_ckpt_dir, "checkpoint-3", "shard_0.drckpt"
            )
        )
        assert info.layouts is None

        engines = _engines(tmp_ckpt_dir, 2, "old_w2r")
        try:
            # legacy call (no layouts)
            got, arrays = engines[1].load()
            assert got == 3
            np.testing.assert_array_equal(
                arrays["['p']"], _rank_tree(g, 1, 2)["p"]
            )
            # layout-aware call on the SAME world: the legacy shape
            # check admits the headerless shard
            tree0 = _rank_tree(g, 0, 2)
            got, arrays = engines[0].load(
                layouts=_rank_layouts(tree0, 0, 2)
            )
            assert got == 3
            np.testing.assert_array_equal(arrays["['p']"], tree0["p"])
        finally:
            _close_all(engines)

    def test_grown_world_restores_through_overlap_range_leg(
        self, tmp_ckpt_dir
    ):
        """A world grown 2 -> 4: rank 2 has no ``shard_2`` file.  A
        restore that names its layouts reads its rows out of old
        rank 1's file (rows 4..6 of 8 lie inside rows 4..8); one that
        names none has only the per-rank file to go by and refuses
        rather than hand back another rank's rows."""
        g = _opt_state(rows=8, cols=4)
        engines = _engines(tmp_ckpt_dir, 2, "ks_w2")
        try:
            _save_world(engines, g, step=4, world=2)
        finally:
            _close_all(engines)

        # one process per node: rank 2 hosts its own saver endpoints
        eng = CheckpointEngine(
            checkpoint_dir=tmp_ckpt_dir, process_rank=2,
            process_count=4, local_shard_num=1, node_rank=2,
            name="ks_w4_2",
            step_sync_fn=lambda avail: max(avail),
        )
        try:
            with pytest.raises(RuntimeError, match="unavailable"):
                eng.load()
            per = 2
            target = {
                "p": np.zeros((per, 4), np.float32),
                "m": np.zeros((per, 4), np.float32),
                "v": np.zeros((per, 4), np.float64),
                "step": np.int32(0),
            }
            got, arrays = eng.load(
                layouts=_rank_layouts(target, 2, 4)
            )
            assert got == 4
            for k in ("p", "m", "v"):
                np.testing.assert_array_equal(
                    arrays[f"['{k}']"], g[k][4:6]
                )
        finally:
            eng.close()


class TestShmLayoutGating:
    def test_stale_world_shm_excluded(self, tmp_ckpt_dir):
        """A surviving segment holding the OLD world's slices must
        not serve a NEW world's restore: the layout gate excludes
        it (bytes valid, placement wrong)."""
        eng = CheckpointEngine(
            checkpoint_dir=tmp_ckpt_dir, process_rank=0,
            process_count=1, local_shard_num=1, name="gate1",
        )
        try:
            tree = {"w": np.arange(8, dtype=np.float32)}
            old_lay = axis0_layouts(tree, 0, 8)  # saved on world 8
            assert eng.save_to_memory(2, tree, layouts=old_lay)
            new_lay = axis0_layouts(tree, 0, 4)  # restore wants w4
            assert eng._usable_shm_steps(new_lay) == []
            assert eng._usable_shm_steps(old_lay) == [2]
            # no layouts requested: today's behavior, step visible
            assert eng._usable_shm_steps(None) == [2]
        finally:
            eng.close()

    def test_headerless_shm_admitted_by_shape(self, tmp_ckpt_dir):
        eng = CheckpointEngine(
            checkpoint_dir=tmp_ckpt_dir, process_rank=0,
            process_count=1, local_shard_num=1, name="gate2",
        )
        try:
            tree = {"w": np.arange(8, dtype=np.float32)}
            assert eng.save_to_memory(2, tree)  # legacy: no layouts
            same = replicated_layouts(tree)
            assert eng._usable_shm_steps(same) == [2]
            bigger = axis0_layouts(
                {"w": np.zeros(16, np.float32)}, 0, 2
            )
            assert eng._usable_shm_steps(bigger) == []
        finally:
            eng.close()


class TestShardHeaders:
    def test_emergency_flush_carries_layouts(self, tmp_ckpt_dir):
        """The crash-flush path (shm slot -> dump_to_file) persists
        the layout header — a preemption flush is reshardable."""
        handler = SharedMemoryHandler(0, name="hdr1", host=True)
        try:
            tree = {"w": np.arange(6, dtype=np.float32)}
            lay = axis0_layouts(tree, 1, 4)
            handler.save_state(9, tree, layouts=lay)
            path = os.path.join(tmp_ckpt_dir, "shard_1.drckpt")
            assert handler.dump_to_file(
                path, PosixDiskStorage()
            ) is not None
            info = read_shard_header(path)
            assert info.step == 9
            assert info.layouts is not None
            assert info.layouts["['w']"].start == (6,)
            assert info.layouts["['w']"].global_shape == (24,)
        finally:
            handler.close(unlink=True)

    def test_coverage_error_names_leaf(self, tmp_ckpt_dir):
        g = np.arange(16, dtype=np.float32)
        handler = SharedMemoryHandler(0, name="hdr2", host=True)
        try:
            tree = {"w": g[:8]}
            handler.save_state(1, tree, layouts=axis0_layouts(
                tree, 0, 2
            ))
            handler.dump_to_file(
                os.path.join(tmp_ckpt_dir, "shard_0.drckpt"),
                PosixDiskStorage(),
            )
        finally:
            handler.close(unlink=True)
        # shard_1 (rows 8..16) missing: rank 1 of 2 is uncovered
        want = axis0_layouts({"w": g[8:]}, 1, 2)
        with pytest.raises(ReshardError, match="\\['w'\\]"):
            for _ in stream_resharded_leaves(tmp_ckpt_dir, want):
                pass

    def test_mixed_steps_rejected(self, tmp_ckpt_dir):
        for r, step in ((0, 1), (1, 2)):
            handler = SharedMemoryHandler(
                r, name=f"hdr3_{r}", host=True
            )
            try:
                tree = {"w": np.zeros(4, np.float32)}
                handler.save_state(
                    step, tree, layouts=axis0_layouts(tree, r, 2)
                )
                handler.dump_to_file(
                    os.path.join(
                        tmp_ckpt_dir, f"shard_{r}.drckpt"
                    ),
                    PosixDiskStorage(),
                )
            finally:
                handler.close(unlink=True)
        shards = scan_checkpoint_shards(tmp_ckpt_dir)
        with pytest.raises(ReshardError, match="mixed steps"):
            plan_reshard(
                shards,
                axis0_layouts({"w": np.zeros(4, np.float32)}, 0, 2),
            )


class TestReshardSpan:
    def test_reshard_span_labels(self, tmp_ckpt_dir, tmp_path,
                                 monkeypatch):
        """The reshard leg emits a ``reshard`` span with the world
        transition + bytes + throughput (schema-enforced labels)."""
        from dlrover_tpu.observability import events as ev

        events_file = tmp_path / "events.jsonl"
        monkeypatch.setenv(
            ev.EVENTS_FILE_ENV, str(events_file)
        )
        ev.set_default_event_logger(None)  # re-read the env
        try:
            g = _opt_state(rows=8, cols=4)
            engines = _engines(tmp_ckpt_dir, 2, "span_w2")
            try:
                _save_world(engines, g, step=2, world=2)
            finally:
                _close_all(engines)
            step, _ = _restore_world(
                tmp_ckpt_dir, 4, "span_w4", g
            )
            assert step == 2
        finally:
            ev.set_default_event_logger(None)
        records = [
            json.loads(line)
            for line in events_file.read_text().splitlines()
        ]
        spans = [r for r in records if r.get("name") == "reshard"]
        assert spans, records
        for s in spans:
            labels = s["labels"]
            assert labels["from_world"] == 2
            assert labels["to_world"] == 4
            assert labels["bytes"] > 0
            assert "throughput_gbps" in labels
