"""The zero-stall input plane: zero-copy shm batch ring (RPC-free
steady state, torn-slot safety, timeout-vs-close), pipelined
ElasticDataLoader (byte-identical serial fallback, live num_workers,
checkpoint watermark), pipelined device prefetch with staged
data_stall labels, overlapped shard-task RPC, and the elastic sampler
across a world-size change."""

import json
import os
import threading
import time

import numpy as np
import pytest

from dlrover_tpu.common.messages import DataShard, Task, TaskType
from dlrover_tpu.data import ElasticDataLoader, ShmSlotTimeout
from dlrover_tpu.data.shm_dataloader import (
    SLOT_WRITING,
    BatchSpec,
    ShmBatchWriter,
    ShmDataLoader,
)
from dlrover_tpu.trainer.elastic.sampler import (
    ElasticDistributedSampler,
)

SPEC = BatchSpec({"x": ((4, 8), "float32"), "y": ((4,), "int64")})


def _mk_batch(i: int):
    return {
        "x": np.full((4, 8), i, dtype=np.float32),
        "y": np.arange(4, dtype=np.int64) + i,
    }


def _count_meta_rpcs(ring) -> list:
    """Wrap the ring's SharedDict proxy so every call is recorded."""
    calls = []
    orig = ring.meta._call

    def counting(method, *args, **kwargs):
        calls.append(method)
        return orig(method, *args, **kwargs)

    ring.meta._call = counting
    return calls


class TestShmRing:
    def test_steady_state_is_rpc_free(self, tmp_path):
        """put/next_batch touch only the shm header — zero SharedDict
        RPCs once attached (the old design polled an RPC per 2 ms)."""
        name = f"rpcfree{os.getpid()}"
        loader = ShmDataLoader(name, SPEC, num_slots=2, timeout=30)
        writer = ShmBatchWriter(name)
        loader_calls = _count_meta_rpcs(loader._ring)
        writer_calls = _count_meta_rpcs(writer._ring)
        try:
            for i in range(6):
                assert writer.put(_mk_batch(i), timeout=30)
                batch = loader.next_batch()
                np.testing.assert_array_equal(
                    batch["x"], np.full((4, 8), i)
                )
            assert loader_calls == []
            assert writer_calls == []
        finally:
            writer.close()
            loader.close()

    def test_zero_copy_views_roundtrip(self):
        """copy=False batches are views over the segment and carry the
        same bytes; the slot recycles on the next call."""
        name = f"views{os.getpid()}"
        loader = ShmDataLoader(name, SPEC, num_slots=2, timeout=30)
        writer = ShmBatchWriter(name)
        try:
            writer.put(_mk_batch(3))
            batch = loader.next_batch(copy=False)
            assert not batch["x"].flags.owndata  # a view, not a copy
            np.testing.assert_array_equal(
                batch["x"], np.full((4, 8), 3)
            )
            loader.release_slot()
            writer.put(_mk_batch(4))
            batch = loader.next_batch(copy=True)
            assert batch["y"].base is None or batch["y"].flags.owndata
            np.testing.assert_array_equal(
                batch["y"], np.arange(4, dtype=np.int64) + 4
            )
        finally:
            writer.close()
            loader.close()

    def test_legacy_path_byte_identical(self):
        """zero_copy=False (the pre-rewrite tobytes/frombuffer path)
        produces the same batches as the zero-copy plane."""
        results = {}
        for zero_copy in (True, False):
            name = f"legacy{int(zero_copy)}{os.getpid()}"
            loader = ShmDataLoader(
                name, SPEC, num_slots=2, timeout=30,
                zero_copy=zero_copy,
            )
            writer = ShmBatchWriter(name, zero_copy=zero_copy)
            try:
                out = []
                for i in range(3):
                    writer.put(_mk_batch(i))
                    out.append(loader.next_batch())
                results[zero_copy] = out
            finally:
                writer.close()
                loader.close()
        for a, b in zip(results[True], results[False]):
            assert a["x"].tobytes() == b["x"].tobytes()
            assert a["y"].tobytes() == b["y"].tobytes()

    def test_timeout_raises_not_none(self):
        """A slot that never fills raises ShmSlotTimeout — a slow
        producer must not look like a clean end of stream."""
        name = f"tmo{os.getpid()}"
        loader = ShmDataLoader(name, SPEC, num_slots=2, timeout=0.2)
        try:
            with pytest.raises(ShmSlotTimeout):
                loader.next_batch()
        finally:
            loader.close()

    def test_clean_close_yields_none(self):
        name = f"eos{os.getpid()}"
        loader = ShmDataLoader(name, SPEC, num_slots=2, timeout=30)
        writer = ShmBatchWriter(name)
        writer.put(_mk_batch(0))
        writer.close()
        try:
            # the batch published before close is still delivered,
            # then the stream ends cleanly
            batch = loader.next_batch()
            assert batch is not None
            assert loader.next_batch() is None
        finally:
            loader.close()

    def test_producer_crash_mid_slot_never_reads_torn_batch(self):
        """A producer that dies between WRITING and FULL leaves the
        slot torn; the consumer times out loudly instead of reading a
        half-written batch."""
        name = f"torn{os.getpid()}"
        loader = ShmDataLoader(name, SPEC, num_slots=2, timeout=0.3)
        writer = ShmBatchWriter(name)
        try:
            # simulate the crash: state WRITING, payload half-written,
            # no FULL flip, no close
            ring = writer._ring
            ring.set_slot_state(0, SLOT_WRITING)
            ring.slot_views(0)["x"][:2] = 7.0
            with pytest.raises(ShmSlotTimeout):
                loader.next_batch()
        finally:
            writer._ring.close()
            loader.close()


class _SourcePool:
    """Deterministic, thread-safe read_batch with call accounting."""

    def __init__(self, dataset_size: int, width: int = 8):
        rng = np.random.default_rng(0)
        self.data = rng.standard_normal(
            (dataset_size, width)
        ).astype(np.float32)
        self.calls = []
        self.threads = []
        self._lock = threading.Lock()

    def __call__(self, indices: np.ndarray):
        with self._lock:
            self.calls.append(np.array(indices))
            self.threads.append(threading.current_thread().name)
        return {"x": self.data[indices], "idx": np.array(indices)}


class TestElasticDataLoaderPipeline:
    def _loader(self, pool, **kwargs):
        kwargs.setdefault("dataset_size", len(pool.data))
        kwargs.setdefault("batch_size", 4)
        kwargs.setdefault("config_file", "/nonexistent")
        kwargs.setdefault("shuffle", True)
        return ElasticDataLoader(read_batch=pool, **kwargs)

    def test_pipelined_byte_identical_to_serial(self):
        """Same sampler seed: the producer pool yields the exact
        batch sequence a serial read of the sampler's draws gives,
        byte for byte — including with a multi-worker pool."""
        pool = _SourcePool(64)
        serial = [
            pool(indices)
            for indices, _ in self._loader(pool)._index_batches()
        ]
        for workers in (1, 3):
            out = list(
                self._loader(
                    pool, num_workers=workers, prefetch_depth=3,
                )
            )
            assert len(out) == len(serial)
            for a, b in zip(serial, out):
                assert a["x"].tobytes() == b["x"].tobytes()
                assert a["idx"].tobytes() == b["idx"].tobytes()

    def test_read_batch_runs_on_the_producer_pool(self):
        """Every ``read_batch`` call runs on an ``input-fetch`` pool
        thread, never on the consumer's, and the pool has read ahead
        by the time the first batch is handed out; the yield order is
        the sampler's draw order all the same."""
        pool = _SourcePool(32)
        loader = self._loader(pool, num_workers=2, prefetch_depth=3)
        it = iter(loader)
        first = next(it)
        time.sleep(0.1)
        assert len(pool.calls) > 1  # read-ahead past the yielded one
        batches = [first] + list(it)
        assert len(batches) == 8
        assert pool.threads and all(
            t.startswith("input-fetch") for t in pool.threads
        )
        draws = [
            idx for idx, _ in self._loader(pool)._index_batches()
        ]
        for want, batch in zip(draws, batches):
            np.testing.assert_array_equal(want, batch["idx"])

    def test_num_workers_tuned_from_config(self, tmp_path):
        config = tmp_path / "paral.json"
        config.write_text(
            json.dumps(
                {"dataloader": {"batch_size": 8, "num_workers": 3}}
            )
        )
        pool = _SourcePool(64)
        loader = self._loader(pool, config_file=str(config))
        assert loader.batch_size == 8
        assert loader.num_workers == 3

    def test_mid_epoch_state_ignores_readahead(self):
        """state_dict reflects the last YIELDED batch even while the
        producer pool has read ahead — resume must not skip the
        prefetched-but-unconsumed batches."""
        pool = _SourcePool(64)
        loader = self._loader(
            pool, num_workers=2, prefetch_depth=4
        )
        it = iter(loader)
        consumed = [next(it), next(it)]
        # give the pool time to read well ahead of the consumer
        time.sleep(0.1)
        state = loader.state_dict()
        it.close()

        pool2 = _SourcePool(64)
        resumed = self._loader(pool2, num_workers=2)
        resumed.load_state_dict(state)
        rest = list(resumed)

        full = [b["idx"] for b in list(self._loader(_SourcePool(64)))]
        got = [b["idx"] for b in consumed + rest]
        assert len(got) == len(full)
        for a, b in zip(full, got):
            np.testing.assert_array_equal(a, b)


class TestDevicePrefetch:
    def test_pipelined_order_preserved(self):
        from dlrover_tpu.data import device_prefetch

        data = [{"x": np.full((2,), i)} for i in range(6)]
        out = list(device_prefetch(iter(data), size=3, pipelined=True))
        assert len(out) == 6
        for i, b in enumerate(out):
            np.testing.assert_array_equal(np.asarray(b["x"]), i)

    def test_stall_spans_carry_stage_labels(self, tmp_path):
        from dlrover_tpu.data import device_prefetch
        from dlrover_tpu.observability.events import (
            EventLogger,
            read_events,
            set_default_event_logger,
        )

        events_file = tmp_path / "events.jsonl"
        set_default_event_logger(EventLogger(path=str(events_file)))
        try:

            def slow_iter():
                for i in range(3):
                    time.sleep(0.03)
                    yield {"x": np.full((2,), i)}

            list(
                device_prefetch(
                    slow_iter(), size=1, stall_threshold_s=0.01,
                    pipelined=True,
                )
            )
        finally:
            set_default_event_logger(None)
        stalls = [
            e for e in read_events(str(events_file))
            if e["name"] == "data_stall"
        ]
        assert stalls, "slow host fetch must emit data_stall spans"
        for e in stalls:
            assert e["labels"]["stage"] in ("host_fetch", "h2d")
        assert any(
            e["labels"]["stage"] == "host_fetch" for e in stalls
        )


class _StubMasterClient:
    """Serves a scripted task list with RPC accounting."""

    def __init__(self, n_shards: int, delay_s: float = 0.0):
        self._tasks = [
            Task(
                task_id=i,
                task_type=TaskType.TRAINING,
                shard=DataShard(name="d", start=i * 4, end=(i + 1) * 4),
            )
            for i in range(n_shards)
        ]
        self._i = 0
        self._delay = delay_s
        self.get_task_threads = []
        self._lock = threading.Lock()

    def get_task(self, dataset_name: str) -> Task:
        self.get_task_threads.append(
            threading.current_thread().name
        )
        if self._delay:
            time.sleep(self._delay)
        with self._lock:
            i, self._i = self._i, self._i + 1
        if i < len(self._tasks):
            return self._tasks[i]
        return Task()  # empty: dataset exhausted

    def report_task_result(self, *a, **k):
        return True


class TestShardTaskPrefetch:
    def test_shards_complete_and_in_order(self):
        from dlrover_tpu.trainer.sharding import ShardingClient

        stub = _StubMasterClient(5)
        client = ShardingClient("d", batch_size=4, client=stub)
        shards = list(client.iter_shards())
        assert [s.start for s in shards] == [0, 4, 8, 12, 16]
        # the prefetcher issued RPCs off the consumer thread
        assert any(
            "shard-prefetch" in t for t in stub.get_task_threads
        )

    def test_prefetch_overlaps_consumption(self):
        """The 2nd shard's RPC runs while the 1st is
        being 'consumed' — the consumer never waits the full RPC
        latency again after the first fetch."""
        from dlrover_tpu.trainer.sharding import ShardingClient

        delay = 0.15
        stub = _StubMasterClient(3, delay_s=delay)
        client = ShardingClient("d", batch_size=4, client=stub)
        assert client.fetch_shard() is not None  # pays the first RPC
        time.sleep(delay * 1.5)  # "consume" the shard
        t0 = time.monotonic()
        assert client.fetch_shard() is not None
        assert time.monotonic() - t0 < delay / 2

    def test_only_the_first_rpc_is_on_the_consumer_thread(self):
        """Nothing is prefetched before the first ``fetch_shard``: that
        RPC runs on the caller's thread; every later one — the one
        that finds the dataset exhausted included — comes from the
        prefetcher, one RPC a task and none twice."""
        from dlrover_tpu.trainer.sharding import ShardingClient

        stub = _StubMasterClient(2)
        client = ShardingClient("d", batch_size=4, client=stub)
        shards = list(client.iter_shards())
        assert [s.start for s in shards] == [0, 4]
        me = threading.current_thread().name
        assert stub.get_task_threads[0] == me
        assert len(stub.get_task_threads) == 3
        assert all(
            "shard-prefetch" in t for t in stub.get_task_threads[1:]
        )


class TestTaskManagerShutdown:
    def test_stop_interrupts_watcher_promptly(self):
        from dlrover_tpu.master.shard.task_manager import TaskManager

        mgr = TaskManager(check_interval=30.0)
        mgr.start()
        assert mgr._watcher.is_alive()
        t0 = time.monotonic()
        mgr.stop()
        mgr._watcher.join(timeout=2.0)
        assert not mgr._watcher.is_alive()
        # far below the 30 s poll interval the old sleep() pinned
        assert time.monotonic() - t0 < 2.0


class TestSamplerWorldResize:
    def test_mid_epoch_resize_no_double_consume(self):
        """drop_last=False pads the index list to a multiple of the
        replica count; resuming mid-epoch under a NEW world size must
        consume each remaining index exactly once — the padded
        duplicates must not be re-consumed on top of their originals."""
        size = 10
        # phase 1: 3 replicas, consume 2 rounds (6 samples, aligned
        # for both the old stride 3 and the new stride 2)
        old = [
            ElasticDistributedSampler(
                size, num_replicas=3, rank=r, shuffle=True,
                drop_last=False,
            )
            for r in range(3)
        ]
        consumed = []
        iters = [iter(s) for s in old]
        for _ in range(2):
            for it in iters:
                consumed.append(next(it))
        state = old[0].state_dict()
        assert state["completed_num"] == 6

        # phase 2: resume on 2 replicas
        new = [
            ElasticDistributedSampler(
                size, num_replicas=2, rank=r, shuffle=True,
                drop_last=False,
            )
            for r in range(2)
        ]
        for s in new:
            s.load_state_dict(state)
        rest = []
        for s in new:
            rest.extend(s)

        got = sorted(consumed + rest)
        # every sample exactly once: the old world's total was padded
        # to 12, the new world's to 10 — the pad entries fall away and
        # no index is consumed twice
        assert got == sorted(range(size))

    def test_resize_preserving_padding_consumes_pad_once(self):
        """When the new world still pads (10 -> 4 replicas after 4
        consumed on 2), the pad duplicates appear exactly as often as
        the padded index list prescribes — never more."""
        size = 10
        old = [
            ElasticDistributedSampler(
                size, num_replicas=2, rank=r, shuffle=False,
                drop_last=False,
            )
            for r in range(2)
        ]
        consumed = []
        iters = [iter(s) for s in old]
        for _ in range(2):
            for it in iters:
                consumed.append(next(it))
        state = old[0].state_dict()
        assert state["completed_num"] == 4

        new = [
            ElasticDistributedSampler(
                size, num_replicas=4, rank=r, shuffle=False,
                drop_last=False,
            )
            for r in range(4)
        ]
        for s in new:
            s.load_state_dict(state)
        rest = []
        for s in new:
            rest.extend(s)
        got = sorted(consumed + rest)
        # the new world pads 10 -> 12 by repeating indices 0 and 1;
        # 0 and 1 were already consumed in phase 1, so they appear
        # exactly twice, everything else exactly once
        expected = sorted(list(range(size)) + [0, 1])
        assert got == expected


class TestBenchInputSmoke:
    def test_run_all_tiny(self, tmp_path, monkeypatch):
        import sys

        repo = os.path.dirname(os.path.dirname(__file__))
        sys.path.insert(0, os.path.join(repo, "scripts"))
        from bench_input import run_all

        result = run_all(batch_mb=1, batches=2, slots=2)
        for mode in ("serial", "zero_copy", "pipelined"):
            assert result[mode]["batches_s"] > 0
            assert result[mode]["gbps"] > 0
        assert "pipelined_vs_serial" in result
