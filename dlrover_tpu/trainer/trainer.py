"""The high-level training loop: accelerate + flash ckpt + elasticity.

Reference parity: ``AtorchTrainer``
(``atorch/atorch/trainer/atorch_trainer.py:136`` — HF-Trainer-shaped
loop over auto_accelerate artifacts) and ``FlashCkptTrainer``
(``dlrover/trainer/torch/flash_checkpoint/hf_trainer.py``) which
replaces the save path with the async shm engine.

One object wires the whole stack: sharded train step (auto_accelerate
or explicit strategy), flash-checkpoint engine (memory every
``save_memory_interval`` steps, storage every
``save_storage_interval`` — the reference's two-tier cadence), elastic
progress reporting, hang detection, loss-spike capture, and metrics.
"""

import json
import os
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Callable, Iterable, Optional

import jax

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.observability.events import (
    anchored_now,
    get_event_logger,
)
from dlrover_tpu.trainer.elastic.context import (
    init_distributed,
)
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
from dlrover_tpu.trainer.fault_tolerance import (
    HangDetector,
    LossSpikeCapture,
    default_hang_action,
)


def _batch_tokens(batch) -> int:
    """Elements of the batch's first leaf: batch x sequence for a
    batch of token ids."""
    leaves = jax.tree_util.tree_leaves(batch)
    return int(leaves[0].size) if leaves else 0


class _HostLeaf:
    """One leaf of a staged snapshot as the drain sees it: shape, dtype
    and — when the drain asks, one leaf at a time — its bytes.  The
    pinned host tree outlives its drain (the next snapshot recycles
    it) and a ``jax.Array`` keeps every host copy it hands out, so the
    bytes are read through a throwaway handle on the same buffers: the
    copy dies with the handle, and a snapshot's peak host memory stays
    at the tree plus the drain's two leaves."""

    def __init__(self, array):
        self._array = array
        self.shape = array.shape
        self.dtype = array.dtype

    def __array__(self, dtype=None, copy=None):
        import numpy as np

        array = self._array
        handle = jax.make_array_from_single_device_arrays(
            array.shape,
            array.sharding,
            [shard.data for shard in array.addressable_shards],
        )
        return np.asarray(handle, dtype=dtype)


def _in_thread(name: str, fn) -> Future:
    """``fn()`` on a daemon thread of its own; what it returns or
    raises is the Future's."""
    done = Future()

    def run():
        try:
            done.set_result(fn())
        except BaseException as e:  # noqa: BLE001 - result() raises it
            done.set_exception(e)

    threading.Thread(target=run, name=name, daemon=True).start()
    return done


def _snapshot_shardings(state, to_host: bool):
    """Where the snapshot program puts each leaf of ``state``: the
    leaf's own sharding — in ``pinned_host`` memory when ``to_host``
    (no device memory is spent, and every chip of a sharded state
    copies its own shards), else as it is."""

    def place(leaf):
        sharding = getattr(leaf, "sharding", None)
        if to_host and sharding is not None:
            return sharding.with_memory_kind("pinned_host")
        return sharding

    return jax.tree_util.tree_map(place, state)


def _compile_snapshot_copy(state, shardings, recycled):
    """THE snapshot program, compiled: a copy of the ``state`` tree
    (arrays, or their shapes with shardings) onto ``shardings``, with
    ``recycled`` — the previous snapshot's host tree, or ``None`` —
    donated, so that the copy lands in the same buffers."""

    def snapshot_copy(state, recycled):
        del recycled  # its buffers are the outputs'
        return jax.tree_util.tree_map(jax.numpy.copy, state)

    return (
        jax.jit(
            snapshot_copy,
            out_shardings=shardings,
            donate_argnums=1,
            keep_unused=True,
        )
        .lower(state, recycled)
        .compile()
    )


@dataclass
class TrainingArgs:
    max_steps: int
    checkpoint_dir: str = ""
    save_memory_interval: int = 10  # steps between shm snapshots
    save_storage_interval: int = 100  # steps between persisted ckpts
    log_interval: int = 10
    global_batch_size: int = 0
    micro_batch_size: int = 0
    hang_timeout: float = 1800.0
    # periodic in-train evaluation cadence (steps; 0 = disabled).
    # Requires eval_iter_fn at Trainer construction.
    eval_interval: int = 0
    # max batches per evaluation pass (0 = drain the eval iterator)
    eval_max_batches: int = 0
    capture_loss_spikes: bool = False
    spike_dir: str = ""
    metrics_port: int = 0  # 0 = no exporter daemon
    # snapshot buffering: ONE compiled copy of the state either way.
    # "auto" picks "copy" (the copy stays on the device, non-blocking
    # drain — transient 2x state HBM) when it fits, "staged" (the copy
    # lands in pinned host memory, no extra HBM, but the step blocks
    # for the transfer) near HBM capacity
    snapshot_mode: str = "auto"
    # host-side sparse embedding tables ({name: KvTable-like}) saved
    # alongside the dense state at every storage-tier step via
    # SparseCheckpointManager full+delta chains, restored on resume
    sparse_tables: Optional[dict] = None
    # deterministic-replay flight recorder (trainer/replay.py):
    # batches ring-logged every step, state digests every
    # replay_digest_interval steps (a digest forces a device sync —
    # keep the interval coarse in production)
    replay_dir: str = ""
    replay_digest_interval: int = 50
    # resident op profiler (xpu_timer analog: measurement for the
    # WHOLE job, ref atorch/dev/xpu_timer/common/manager.h:201): every
    # trace_interval steps, trace trace_steps real training steps,
    # parse the chrome trace (observability/trace.py), export category
    # shares + top GEMM clusters to the metrics registry, and drop the
    # census JSON at trace_drop_file — where the agent's
    # ChipMetricsCollector ships it to the master's diagnosis chain
    # (GemmRegressionOperator).  0 = off.
    trace_interval: int = 0
    trace_steps: int = 2
    trace_drop_file: str = ""
    extra: dict = field(default_factory=dict)


class Trainer:
    def __init__(
        self,
        accelerate_result,
        args: TrainingArgs,
        data_iter_fn: Callable[[], Iterable],
        rng_seed: int = 0,
        eval_iter_fn: Optional[Callable[[], Iterable]] = None,
        callbacks=None,
        lr_schedule: Optional[Callable[[int], float]] = None,
    ):
        """``accelerate_result``: an ``AccelerateResult`` (from
        ``auto_accelerate``); ``data_iter_fn()`` returns a fresh batch
        iterator yielding host pytrees matching the batch sharding.

        ``eval_iter_fn`` enables ``evaluate()`` and the periodic
        in-train cadence (``args.eval_interval``).  ``callbacks`` is a
        list of :class:`~dlrover_tpu.trainer.callbacks.TrainerCallback`.
        ``lr_schedule`` (the optax schedule the optimizer was built
        with — see ``optimizers/schedules.get_scheduler``) lets the
        trainer log/export the current LR; the schedule POSITION lives
        in the optimizer state, so resume needs no extra wiring."""
        from dlrover_tpu.trainer.callbacks import CallbackList

        self._ctx = init_distributed()
        self._result = accelerate_result
        self._fns = accelerate_result.fns
        self._args = args
        self._data_iter_fn = data_iter_fn
        self._eval_iter_fn = eval_iter_fn
        self._callbacks = CallbackList(callbacks)
        self._lr_schedule = lr_schedule
        self._rng_seed = rng_seed

        self.state = None
        self.progress = ElasticTrainer(
            global_batch_size=args.global_batch_size
            or args.micro_batch_size * self._ctx.world_size,
            micro_batch_size=args.micro_batch_size or 1,
            world_size=self._ctx.world_size,
            rank=self._ctx.rank,
        )
        self._engine = None
        self._restart_coord = None
        self._world_changed = False
        #: per-leaf global layouts of this rank's state slices
        #: (derived from the live shardings after init_state)
        self._layouts = None
        if args.checkpoint_dir:
            from dlrover_tpu.trainer.checkpoint.engine import (
                CheckpointEngine,
            )
            from dlrover_tpu.trainer.restart_path import (
                RestartCoordinator,
            )

            self._engine = CheckpointEngine(
                checkpoint_dir=args.checkpoint_dir,
                process_rank=self._ctx.rank,
                process_count=self._ctx.world_size,
                node_rank=self._ctx.node_rank,
                local_shard_num=int(
                    os.getenv("DLROVER_TPU_LOCAL_PROCESS_COUNT", "1")
                ),
            )
            # restart critical path: kick the restore byte prefetch
            # NOW, so it streams while init_state traces+compiles in
            # _init_or_restore_state; any prefetch failure falls
            # back to the serial load.
            # After a WORLD CHANGE the target layouts are unknowable
            # until init_state shards the new state — the blind
            # prefetch would stage the OLD world's shard, so the
            # restore runs the serial reshard-aware load instead.
            prev_world = int(
                os.getenv("DLROVER_TPU_PREV_WORLD", "0") or 0
            )
            self._world_changed = (
                prev_world > 0
                and prev_world != self._ctx.world_size
            )
            if not self._world_changed:
                self._restart_coord = RestartCoordinator(self._engine)
                self._restart_coord.start()
            # graceful-drain protocol: the agent's SIGUSR1 flips
            # snapshot-every-step mode (trainer/drain.py)
            from dlrover_tpu.trainer.drain import install_drain_handler

            install_drain_handler()
        self._sparse_mgr = None
        if args.sparse_tables and args.checkpoint_dir:
            from dlrover_tpu.sparse.checkpoint import (
                SparseCheckpointManager,
            )

            # one chain per process: sparse tables are host-local
            self._sparse_mgr = SparseCheckpointManager(
                os.path.join(
                    args.checkpoint_dir,
                    f"sparse-rank{self._ctx.rank:05d}",
                )
            )
        self._replay = None
        if args.replay_dir:
            from dlrover_tpu.trainer.replay import ReplayRecorder

            self._replay = ReplayRecorder(
                os.path.join(
                    args.replay_dir, f"rank{self._ctx.rank:05d}"
                )
            )
        self._hang = HangDetector(
            timeout=args.hang_timeout, on_hang=default_hang_action
        )
        self._spikes = (
            LossSpikeCapture(
                args.spike_dir
                or os.path.join(args.checkpoint_dir or "/tmp", "spikes")
            )
            if args.capture_loss_spikes
            else None
        )
        self._snap_fn = None
        self._snap_prepared = False
        self._shm_ready = None  # Future of the shm slots' preallocation
        self._snap_shardings = None
        self._snap_memory_kind = "device"
        # a staged snapshot's pinned host tree (first a Future of its
        # allocation): recycled by the next snapshot, never re-allocated
        self._snap_host = None
        self._snapshot_mode = (
            None if args.snapshot_mode == "auto" else args.snapshot_mode
        )
        # timeline: one ``step`` span per completed step (from
        # _consume_metrics) and one ``snapshot_pull`` per snapshot's
        # synchronous leg; both are no-ops without an events file
        self._events = get_event_logger()
        self._step_span_from = None  # perf_counter of the last step done
        # the ``startup`` stage ``first_step``, open from the first
        # step's dispatch (its program's resolution and compile before
        # it) to its completion in _consume_metrics
        self._first_step_sid = None
        # the remat policy the step runs under, once the first batch's
        # shape has resolved it: a label of every ``step`` span
        self._remat = None
        # live attribution profiler (observability/attribution.py):
        # the continuous leg traces ONE step every
        # DLROVER_TPU_PROFILE_EVERY_N_STEPS (default 0 = off, zero
        # overhead) and a background thread emits the step_profile
        # span; the SIGUSR2 capture handler arms the deep-capture arm
        # (agent directive → N-step trace + faulthandler stack dump).
        # DLROVER_TPU_PROFILE=0 disables both exactly.
        from dlrover_tpu.common.env import (
            profile_enabled,
            profile_every_n_steps,
        )

        self._profile_on = profile_enabled()
        self._profile_every = (
            profile_every_n_steps() if self._profile_on else 0
        )
        self._attribution = None
        if self._profile_on:
            from dlrover_tpu.trainer.capture import (
                install_capture_handler,
            )

            install_capture_handler()
        self._registry = None
        self._exporter = None
        if args.metrics_port:
            from dlrover_tpu.observability.metrics import (
                MetricsExporter,
                MetricsRegistry,
                set_default_registry,
            )
            from dlrover_tpu.trainer.callbacks import MetricsCallback

            # rank label keeps this rank's series distinct when a
            # node-level exporter merges every rank's metric file
            self._registry = MetricsRegistry(rank=self._ctx.rank)
            set_default_registry(self._registry)
            self._exporter = MetricsExporter(
                self._registry,
                rank=self._ctx.rank,
                port=args.metrics_port + self._ctx.rank,
            )
            self._callbacks.callbacks.append(
                MetricsCallback(self._registry)
            )

    # ------------------------------------------------------------ resume
    def _init_or_restore_state(self):
        self.state = self._fns.init_state(
            jax.random.PRNGKey(self._rng_seed)
        )
        start_step = 0
        if self._engine is not None:
            from dlrover_tpu.trainer.checkpoint.reshard import (
                derive_layouts,
            )

            self._layouts = derive_layouts(self.state)
            # restore straight onto the initialized state's shardings;
            # the coordinator consumes the bytes the __init__-time
            # prefetch staged while init_state compiled (falls back to
            # the serial engine.load on any overlap failure)
            if self._restart_coord is not None:
                # the derived layouts supersede the blind prefetch's:
                # if what it staged turns out to be another world's
                # placement, the finish falls into the reshard leg
                step, restored = self._restart_coord.finish_restore(
                    target=self.state, layouts=self._layouts
                )
                # one restart, one prefetch: a later re-init must read
                # FRESH availability (training may have snapshotted
                # past the staged step), i.e. the serial load below
                self._restart_coord = None
            else:
                # serial, layout-aware: after a world change this is
                # the reshard leg — each leaf reassembled from
                # whichever old-world shards cover its new slices
                step, restored = self._engine.load(
                    target=self.state, layouts=self._layouts
                )
            if step >= 0 and restored is not None:
                self.state = restored
                start_step = step
                logger.info("resumed training from step %d", step)
                if self._sparse_mgr is not None:
                    # dense step wins: load the sparse chain at-or-
                    # before it so embeddings never run AHEAD of the
                    # dense weights
                    s = self._sparse_mgr.restore(
                        self._args.sparse_tables, step=step
                    )
                    if s is not None:
                        logger.info(
                            "restored sparse tables at step %d", s
                        )
                    else:
                        logger.warning(
                            "dense state resumed at step %d but NO "
                            "sparse save exists at-or-before it — "
                            "embedding tables keep their current "
                            "(likely freshly-initialized) contents",
                            step,
                        )
        self.progress.global_step = start_step
        if self._engine is not None:
            self._prepare_snapshots()
        return start_step

    # ------------------------------------------------------------- save
    def _resolve_snapshot_mode(self) -> str:
        """"copy" when a second on-device state fits comfortably,
        "staged" otherwise (round-2 advisor: the full jnp.copy is a 2x
        HBM transient — fatal near capacity; the staged path, whose
        copy lands in host memory, trades step blocking for bounded
        device memory)."""
        mode = self._args.snapshot_mode
        if mode != "auto":
            return mode
        from dlrover_tpu.accelerate.analyser import device_memory_bytes

        def per_device_bytes(leaf):
            """What ONE device actually holds: full size when the leaf
            is replicated (dp-only state!), its shard when sharded —
            dividing the global size by device count would claim a
            replicated 10 GB state costs 1.25 GB/device and pick
            "copy" exactly where it OOMs."""
            try:
                by_device = {}
                for s in leaf.addressable_shards:
                    by_device[s.device] = (
                        by_device.get(s.device, 0) + s.data.nbytes
                    )
                if by_device:
                    return max(by_device.values())
            except Exception:  # noqa: BLE001
                pass
            return leaf.size * leaf.dtype.itemsize

        state_bytes = sum(
            per_device_bytes(leaf)
            for leaf in jax.tree_util.tree_leaves(self.state)
        )
        # a copy is safe when state + its copy stay under ~80% of HBM
        fits = 2 * state_bytes <= 0.8 * device_memory_bytes()
        return "copy" if fits else "staged"

    def _snapshot_shardings(self):
        """Each leaf's own sharding, in ``pinned_host`` memory for a
        ``staged`` snapshot and in device memory for ``copy``.  A
        backend without in-program ``pinned_host`` (the CPU tests)
        degrades to the device's own, so the SAME program runs."""
        from dlrover_tpu.common.jax_env import pinned_host_works

        to_host = self._snapshot_mode == "staged" and pinned_host_works()
        self._snap_memory_kind = "pinned_host" if to_host else "device"
        return _snapshot_shardings(self.state, to_host)

    def _prepare_snapshots(self):
        """Resolve the snapshot mode and where the snapshot lands."""
        if self._snap_prepared:
            return
        self._snap_prepared = True
        if self._snapshot_mode is None:
            self._snapshot_mode = self._resolve_snapshot_mode()
            logger.info("snapshot mode: %s", self._snapshot_mode)
        self._snap_shardings = self._snapshot_shardings()

    def _make_room_for_snapshots(self):
        """For a snapshot that lands in pinned host memory, start making
        room for it, once, on two helper threads: one allocates the
        pinned host tree (page-locking 8 GB takes a v5e's host ~12 s,
        which must not be the first snapshot's stall), the other faults
        in the two shm slots (the first drains, slower from pinned
        leaves than from numpy, must end well before the next snapshot,
        or it is skipped).  Both stall whatever else the process does
        on the host meanwhile (program loads took 10 s longer beside
        them): so they start when the first step is in flight — every
        program is loaded, the training thread mostly waits for the
        device.  Every later snapshot recycles the tree."""
        if (
            self._snap_memory_kind != "pinned_host"
            or self._snap_host is not None
        ):
            return
        shapes = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
            self.state,
        )
        zeros = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda s: jax.numpy.zeros(s.shape, s.dtype), shapes
            ),
            out_shardings=self._snap_shardings,
        )
        self._snap_host = _in_thread(
            "snapshot-host-room", lambda: jax.block_until_ready(zeros())
        )
        self._shm_ready = _in_thread(
            "snapshot-shm-room",
            lambda: self._engine.preallocate_like(shapes),
        )

    def _snapshot_program(self):
        """ONE compiled copy of the state tree, whatever the mode
        (built once, compiled once, ahead of the first pull's span):
        the next train step donates and overwrites ``self.state``'s
        buffers, the copy stays.  Its second argument is the previous
        snapshot's host tree, donated: the copy lands in the same
        buffers (``None`` where the copy stays on the device and dies
        with its drain).  Kept in the persistent compile cache however
        short its compile, so a restarted worker loads it."""
        self._prepare_snapshots()  # (a state set by hand: do it now)
        self._make_room_for_snapshots()
        if isinstance(self._snap_host, Future):
            self._snap_host = self._snap_host.result()
        if self._snap_fn is None:
            from dlrover_tpu.common.jax_env import kept_in_compile_cache

            if self._shm_ready is not None:
                self._shm_ready.result()
            with kept_in_compile_cache():
                self._snap_fn = _compile_snapshot_copy(
                    self.state, self._snap_shardings, self._snap_host
                )
        return self._snap_fn

    def _pull_snapshot(self, step: int):
        """The SYNCHRONOUS leg of a snapshot, on the training thread:
        one run of the snapshot program, named from inside (a
        ``snapshot_pull`` span, and the same name on an open profiler
        trace).  Returns the tree for the engine's drain — the
        asynchronous ``checkpoint_save`` that follows.  The caller has
        seen the slot free: the previous drain has ended, and its host
        tree may be recycled."""
        snap_fn = self._snapshot_program()
        if self._snapshot_mode == "staged":
            # the pull's copies run in series behind the step just
            # dispatched in any case: wait for it HERE, so that the
            # span — which the ledger charges as loss — holds the
            # transfer alone and the step keeps its own compute time
            jax.block_until_ready(self.state)
        pull_t0 = time.monotonic()
        with self._events.leaf("snapshot_pull"):
            # the previous snapshot's drain has ended (the slot is
            # free): its host tree is donated, the copy lands there
            snap = snap_fn(self.state, self._snap_host)
            if self._snapshot_mode == "staged":
                # the host copy is whole when the span ends: the drain
                # reads host memory, and the next step's gap pays
                # nothing for this snapshot ("copy" returns at the
                # dispatch, its transfer hides beside the next steps)
                jax.block_until_ready(snap)
        if self._events.enabled:
            pull_s = max(time.monotonic() - pull_t0, 1e-9)
            nbytes = sum(
                int(leaf.nbytes)
                for leaf in jax.tree_util.tree_leaves(snap)
            )
            self._events.complete(
                "snapshot_pull",
                anchored_now(pull_t0),
                pull_s,
                step=step,
                bytes=nbytes,
                throughput_gbps=round(nbytes / pull_s / 1e9, 3),
                mode=self._snapshot_mode,
                memory_kind=self._snap_memory_kind,
            )
        if self._snap_memory_kind == "pinned_host":
            self._snap_host = snap
            snap = jax.tree_util.tree_map(_HostLeaf, snap)
        return snap

    def _maybe_checkpoint(self, step: int):
        if self._engine is None:
            return
        self._make_room_for_snapshots()  # a step is in flight
        from dlrover_tpu.trainer.drain import drain_requested

        draining = drain_requested()
        to_storage = step % self._args.save_storage_interval == 0
        to_memory = (
            step % self._args.save_memory_interval == 0
            # drain mode (agent SIGUSR1: the node — or a peer — is
            # about to die): snapshot EVERY step so the agent's flush
            # persists the last step the whole world completed, not
            # the last periodic snapshot
            or draining
        )
        if not (to_storage or to_memory):
            return
        if not self._engine.snapshot_slot_free(step):
            return  # previous drain still running: skip, at no cost
        snap = self._pull_snapshot(step)
        if to_storage:
            self._engine.save_to_storage(
                step, snap, blocking=False, layouts=self._layouts
            )
            if self._sparse_mgr is not None:
                # export inline (version cut), write in background —
                # the step blocks only for the touched-row memcpy
                self._sparse_mgr.save(
                    step, self._args.sparse_tables, blocking=False
                )
        else:
            # drain mode blocks: the agent is about to flush shm, and
            # an un-drained async snapshot would hand it a torn buffer
            self._engine.save_to_memory(
                step, snap, blocking=draining,
                layouts=self._layouts,
            )
        self._callbacks.on_save(step, storage=to_storage)

    def _consume_metrics(self, step: int, metrics, batch) -> float:
        loss = float(metrics["loss"])  # syncs on step completion
        if self._first_step_sid is not None:
            self._events.end("startup", self._first_step_sid)
            self._first_step_sid = None
        now = time.perf_counter()
        prev_done = self._last_done
        dt = now - prev_done
        self._last_done = now
        if self._events.enabled:
            # step-done to step-done, as the ledger defines ``step``.
            # The first completion has no step-done before it (its gap
            # holds the compile), and a gap that a closed trace window
            # or an eval reset is not one step's: neither is a span.
            if self._step_span_from == prev_done:
                self._events.complete(
                    "step",
                    anchored_now() - dt,
                    dt,
                    step=step,
                    tokens=_batch_tokens(batch),
                    **({"remat": self._remat} if self._remat else {}),
                )
            self._step_span_from = now
        if self._spikes is not None:
            self._spikes.observe(step, loss, batch)
        record = {"loss": loss, "step_time_s": dt}
        if "grad_norm" in metrics:
            record["grad_norm"] = float(metrics["grad_norm"])
        if self._lr_schedule is not None:
            # optax evaluates step_size_fn(count) BEFORE incrementing:
            # the Nth update applied schedule(N-1)
            record["lr"] = float(self._lr_schedule(step - 1))
        self._callbacks.on_step_end(step, record)
        if step % self._args.log_interval == 0:
            logger.info(
                "step %d loss %.4f (%.3fs/step)", step, loss, dt
            )
        return dt

    def _process_trace(self, trace_dir: str, step: int):
        """Resident-profiler post-processing: parse the captured
        window, mirror op-time series onto the metrics registry (the
        C++ exporter's surface), and drop the census JSON where the
        agent's ChipMetricsCollector ships it into the master's
        diagnosis chain (GemmRegressionOperator)."""
        import shutil

        from dlrover_tpu.observability.trace import parse_trace

        try:
            report = parse_trace(trace_dir)
        except Exception as e:  # noqa: BLE001 - observability only
            logger.warning("op trace parse failed: %s", e)
            return
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        self.last_op_report = report
        if not report.total_device_us:
            return  # no device op tracks (CPU backend)
        if self._registry is not None:
            report.export_to_registry(self._registry)
        summary = report.summary(top_k=5)
        drop = self._args.trace_drop_file
        if drop:
            payload = dict(summary, step=step)
            tmp = f"{drop}.tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump(payload, f)
                os.replace(tmp, drop)  # atomic vs collector reads
            except OSError as e:
                logger.warning("op census drop failed: %s", e)
        top = summary["gemm_clusters"][:1]
        logger.info(
            "op profile @step %d: device %.0fus/step, top gemm %s",
            step,
            report.mean_step_us,
            top[0]["key"] if top else "n/a",
        )

    # ------------------------------------------- attribution profiler
    def _take_capture_request(self) -> bool:
        """A pending agent deep-capture request (SIGUSR2), consumed."""
        if not self._profile_on:
            return False
        from dlrover_tpu.trainer.capture import take_capture_request

        return take_capture_request()

    #: cost-analysis FLOPs require a second lower+compile of the
    #: train step (jax's call cache does not serve explicit
    #: ``.lower().compile()``); past this state size the duplicate
    #: compile is only worth it when a persistent compilation cache
    def _resolve_remat(self, batch):
        """Before the first step, now that the batch's shape is known:
        what the model's scanned block keeps for its backward, resolved
        from the compiled step's memory where nobody named it
        (``TrainStepFns.resolve_remat``), and ONE ``remat_plan`` record
        of what runs.  A restart resolves again, from the same shapes
        to the same rung."""
        plan = self._fns.resolve_remat(batch)
        if plan is None:
            return
        self._remat = plan.policy
        logger.info("remat plan: %s", plan.labels())
        self._events.instant("remat_plan", **plan.labels())

    #: can answer it — otherwise the trace-summed fallback carries
    #: the number
    COST_ANALYSIS_MAX_STATE_BYTES = 2 << 30

    def _flops_fn_from(self, batch):
        """Lazy cost-analysis FLOPs for the attribution worker: the
        jitted step lowered from shape specs (no live arrays held by
        the background thread).  None when the step exposes no
        ``lower`` (multi-jit offload steps) or when the recompile
        would be expensive (big state, no persistent compile cache)
        — the worker then uses trace-summed op FLOPs."""
        train_step = self._fns.train_step
        if not hasattr(train_step, "lower"):
            return None
        try:
            spec = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jax.ShapeDtypeStruct(
                    tuple(x.shape), x.dtype
                ),
                t,
            )
            state_spec = spec(self.state)
            batch_spec = spec(batch)
            state_bytes = sum(
                s.size * s.dtype.itemsize
                for s in jax.tree_util.tree_leaves(state_spec)
            )
        except Exception:  # noqa: BLE001 - exotic leaves
            return None
        if state_bytes > self.COST_ANALYSIS_MAX_STATE_BYTES and (
            not os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ):
            logger.info(
                "attribution FLOPs: skipping the cost-analysis "
                "recompile (%.1f GB state, no compilation cache); "
                "using trace-summed op FLOPs",
                state_bytes / 1e9,
            )
            return None

        def flops():
            compiled = train_step.lower(
                state_spec, batch_spec
            ).compile()
            costs = compiled.cost_analysis()
            if isinstance(costs, list):
                costs = costs[0] if costs else {}
            return float(costs.get("flops", 0.0))

        return flops

    def _submit_profile(
        self, trace_dir, step, start_wall, dur_s, steps, mode, batch
    ):
        """Hand one captured window to the background attribution
        worker (parse + step_profile span off the training thread)."""
        from dlrover_tpu.common.env import capture_dir

        if self._attribution is None:
            from dlrover_tpu.observability.attribution import (
                AttributionWorker,
            )

            self._attribution = AttributionWorker(
                flops_fn=self._flops_fn_from(batch)
            )
        self._attribution.submit(
            trace_dir,
            step,
            start_wall,
            dur_s,
            steps=steps,
            mode=mode,
            artifact_dir=capture_dir() if mode == "capture" else "",
        )

    # ------------------------------------------------------------- eval
    def evaluate(self, eval_iter_fn=None, max_batches: int = 0):
        """One evaluation pass: mean forward loss over the eval
        iterator under the training shardings (reference
        ``AtorchTrainer.evaluate``/``evaluation_loop``
        ``atorch_trainer.py:1742,1857`` — redesigned as a jitted
        forward-only step; no gather-to-rank-0, the loss is already a
        replicated scalar).  Returns the metrics dict and fires
        ``on_eval``."""
        it_fn = eval_iter_fn or self._eval_iter_fn
        if it_fn is None:
            raise ValueError(
                "evaluate() needs eval_iter_fn (ctor or argument)"
            )
        if self._fns.eval_step is None:
            raise ValueError(
                "the accelerate artifacts carry no eval_step "
                "(rebuilt with an older build_train_step?)"
            )
        if self.state is None:
            self._init_or_restore_state()
        max_batches = max_batches or self._args.eval_max_batches
        batch_sharding = self._fns.batch_sharding
        t0 = time.perf_counter()
        total, count = 0.0, 0
        # one-deep pipeline, same as train: batch N+1 dispatches while
        # N's loss materializes
        pending = None
        for batch in it_fn():
            if max_batches and count >= max_batches:
                break
            device_batch = jax.device_put(batch, batch_sharding)
            metrics = self._fns.eval_step(self.state, device_batch)
            if pending is not None:
                total += float(pending["loss"])
            pending = metrics
            count += 1
        if pending is not None:
            total += float(pending["loss"])
        if count == 0:
            raise ValueError("eval iterator yielded no batches")
        result = {
            "eval_loss": total / count,
            "eval_batches": count,
            "eval_time_s": round(time.perf_counter() - t0, 3),
        }
        step = int(self.progress.global_step)
        logger.info(
            "eval @ step %d: loss %.4f (%d batches, %.2fs)",
            step, result["eval_loss"], count, result["eval_time_s"],
        )
        self._callbacks.on_eval(step, result)
        return result

    # ------------------------------------------------------------- train
    def train(self):
        from dlrover_tpu.data.prefetch import device_prefetch

        with self._events.span("startup", stage="state"):
            start_step = self._init_or_restore_state()
        if self._exporter is not None:
            self._exporter.start()
        self._hang.start()
        self._callbacks.on_train_begin(start_step)
        batch_sharding = self._fns.batch_sharding
        step = start_step
        step_times = []
        eval_every = (
            self._args.eval_interval
            if self._eval_iter_fn is not None
            else 0
        )
        try:
            # metrics are read to host with a ONE-STEP delay: forcing
            # float(loss) right after dispatch would block on the device
            # result every step and serialize the async dispatch
            # pipeline (round-1 advisor finding); by the time step N+1
            # is dispatched, step N's metrics are already materialized.
            # Step time is measured completion-to-completion inside
            # _consume_metrics (float(loss) syncs on the device result)
            # — dispatch latency alone would be ~ms regardless of the
            # real step duration.
            pending = None  # (step, metrics, batch)
            self._last_done = time.perf_counter()
            trace_every = self._args.trace_interval
            tracing_left = 0
            trace_dir_cur = None
            # window bookkeeping for the attribution legs: what kind
            # of window is open ("census" = the inline resident
            # profiler, "profile" = the continuous attribution leg,
            # "capture" = an agent deep-capture), how many steps it
            # spans, and when it opened (for the step_profile span)
            trace_mode = None
            trace_window_steps = 0
            trace_t0_mono = 0.0
            trace_t0_wall = 0.0
            while step < self._args.max_steps:
                # pipelined input plane: host fetch of batch k+1 runs
                # on a background thread while batch k stages h2d and
                # batch k-1 computes; batches arrive device-resident,
                # with `size` transfers in flight
                epoch_iter = device_prefetch(
                    self._data_iter_fn(),
                    size=2,
                    sharding=batch_sharding,
                    pipelined=True,
                )
                for batch in epoch_iter:
                    if step >= self._args.max_steps:
                        break
                    if step == start_step:
                        # the stage that holds the step program's
                        # ``compile`` records: a ``step`` span exists
                        # only from the second step on
                        self._first_step_sid = self._events.begin(
                            "startup", stage="first_step"
                        )
                        self._resolve_remat(batch)
                    open_mode = None
                    if tracing_left == 0:
                        # priority: a deep-capture request beats the
                        # periodic cadences (the diagnosis chain is
                        # waiting on it); the census leg keeps its
                        # historical precedence over the continuous
                        # attribution leg on a shared step
                        if self._take_capture_request():
                            open_mode = "capture"
                        elif (
                            trace_every > 0
                            and step != start_step
                            and step % trace_every == 0
                        ):
                            open_mode = "census"
                        elif (
                            self._profile_every > 0
                            and step != start_step
                            and step % self._profile_every == 0
                        ):
                            open_mode = "profile"
                    if open_mode is not None:
                        # trace the NEXT window of REAL steps (not
                        # replayed extras — an out-of-band capture
                        # would advance the optimizer off the
                        # training trajectory).  Settle the pipelined
                        # metrics first so the window holds only
                        # whole steps.
                        import tempfile

                        from dlrover_tpu.common.env import (
                            capture_steps,
                        )
                        if pending is not None:
                            step_times.append(
                                self._consume_metrics(*pending)
                            )
                            pending = None
                        trace_dir_cur = tempfile.mkdtemp(
                            prefix="dlrover_optrace_"
                        )
                        jax.profiler.start_trace(trace_dir_cur)
                        trace_mode = open_mode
                        if open_mode == "census":
                            tracing_left = max(
                                1, self._args.trace_steps
                            )
                        elif open_mode == "capture":
                            tracing_left = capture_steps()
                        else:  # the lightweight continuous leg
                            tracing_left = 1
                        trace_window_steps = tracing_left
                        trace_t0_mono = time.monotonic()
                        trace_t0_wall = anchored_now(trace_t0_mono)
                    if self._replay is not None:
                        # `batch` is already device-resident; the
                        # recorder's np.asarray pulls it back — replay
                        # is an opt-in debug mode, correctness over
                        # overlap
                        self._replay.record(step + 1, batch)
                    # step boundaries on an open profiler trace (the
                    # dispatch only: the loss is read a step later)
                    with jax.profiler.StepTraceAnnotation(
                        "train", step_num=step + 1
                    ):
                        self.state, metrics = self._fns.train_step(
                            self.state, batch
                        )
                    step += 1
                    if (
                        self._replay is not None
                        # interval <= 0 = batches only, no digests
                        # (a digest forces a device sync)
                        and self._args.replay_digest_interval > 0
                        and step % self._args.replay_digest_interval
                        == 0
                    ):
                        self._replay.commit(step, self.state)
                    # no span here: _consume_metrics times the step
                    # where its loss reaches the host
                    self.progress.advance()
                    self._hang.report_step(step)
                    if pending is not None:
                        step_times.append(
                            self._consume_metrics(*pending)
                        )
                    pending = (step, metrics, batch)
                    if tracing_left > 0:
                        tracing_left -= 1
                        if tracing_left == 0:
                            # close the window on a step boundary:
                            # consume forces completion of every
                            # traced step before stop_trace
                            step_times.append(
                                self._consume_metrics(*pending)
                            )
                            pending = None
                            jax.profiler.stop_trace()
                            if trace_mode == "census":
                                # historical inline path: census to
                                # registry + diagnosis drop file
                                self._process_trace(
                                    trace_dir_cur, step
                                )
                            else:
                                # attribution legs parse on the
                                # BACKGROUND worker — the next step
                                # dispatches immediately
                                self._submit_profile(
                                    trace_dir_cur,
                                    step,
                                    trace_t0_wall,
                                    time.monotonic() - trace_t0_mono,
                                    trace_window_steps,
                                    trace_mode,
                                    batch,
                                )
                            trace_dir_cur = None
                            trace_mode = None
                            self._last_done = time.perf_counter()
                    self._maybe_checkpoint(step)
                    if eval_every and step % eval_every == 0:
                        # settle the pipelined metrics first so the
                        # eval pause is not booked as a step time
                        # (a trace window closing on this step may
                        # already have consumed them)
                        if pending is not None:
                            step_times.append(
                                self._consume_metrics(*pending)
                            )
                            pending = None
                        self.evaluate()
                        self._last_done = time.perf_counter()
                else:
                    continue
                break
            if pending is not None:
                step_times.append(self._consume_metrics(*pending))
        finally:
            if trace_dir_cur is not None and tracing_left > 0:
                # training ended mid-window: close it or the NEXT
                # start_trace (this process or a later test) dies
                # with "profile already started"
                try:
                    jax.profiler.stop_trace()
                    self._process_trace(trace_dir_cur, step)
                except Exception as e:  # noqa: BLE001
                    logger.warning("trace close failed: %s", e)
            if self._attribution is not None:
                # drain in-flight attribution parses so the final
                # step_profile span lands before the timeline ships
                self._attribution.close(timeout=10.0)
            self._hang.stop()
            if self._exporter is not None:
                self._exporter.stop()
            if self._engine is not None:
                # final snapshot + persist, blocking, through the same
                # snapshot program: a leaf-wise host copy of the state
                # BESIDE a staged snapshot's host tree does not fit a
                # host that the job's snapshots already fill.  An async
                # drain from the last in-loop snapshot may still be
                # running — join it first or the save slot is busy and
                # the persist never comes.
                self._engine.wait_for_snapshot(timeout=600)
                if self._engine.snapshot_slot_free(step):
                    saved = self._engine.save_to_storage(
                        step, self._pull_snapshot(step)
                    )
                    # the last snapshot is in shm: give the host tree
                    # back before the persist takes its share of memory
                    self._snap_host = None
                    if saved:
                        self._engine.wait_for_persist(step, timeout=600)
                if self._sparse_mgr is not None:
                    # join in-flight async writes FIRST: the final step
                    # may equal the last interval step, and two writers
                    # on one step dir would race the commit rename
                    self._sparse_mgr.wait_for_writes()
                    self._sparse_mgr.save(step, self._args.sparse_tables)
                self._engine.close()
        summary = {
            "final_step": step,
            "mean_step_time": (
                sum(step_times) / len(step_times) if step_times else 0.0
            ),
        }
        self._callbacks.on_train_end(summary)
        return summary
