"""Restart critical-path scheduler: overlap the three recovery legs.

Restart-to-first-step latency IS goodput loss under preemption, and
the post-restart sequence — backend init → rendezvous join →
checkpoint restore → train-step compile → first step — historically
ran strictly serially even though its expensive legs use DISJOINT
resources:

- **restore** moves bytes (shm/storage → host RAM → device);
- **compile** burns CPU inside XLA (or hits the persistent
  ``JAX_COMPILATION_CACHE_DIR``);
- **rendezvous** is pure coordination wait.

This module sequences them so the restart costs
``max(restore, compile, rendezvous)`` instead of their sum:

1. :meth:`RestartCoordinator.start` kicks the restore **byte
   prefetch** (``CheckpointEngine.start_prefetch`` — shm attach +
   leaf-streamed storage read into host RAM, no jax) and the
   **background AOT compile** (``TrainStepFns.aot_compile`` or any
   ``compile_fn``) on threads aligned by a start barrier, the moment
   the worker knows its config.
2. :meth:`finish_restore` runs the cross-rank step consensus and
   pipelines per-leaf ``device_put`` against the staged bytes
   (``CheckpointEngine.finish_restore``).
3. :meth:`resolve_train_step` hands the first step the compiled
   artifact instead of a cold trace.

Degradation contract: ANY leg failing (at launch or on its thread)
yields the serial order with byte-identical restored state.  The legs
emit ``restart_path`` child spans
(``restore_prefetch`` / ``aot_compile`` / ``rendezvous_wait`` /
``finish_restore``) on the PR-1 timeline, so the goodput ledger shows
the measured overlap; ``scripts/bench_restart.py`` reports the
overlapped MTTR from the same machinery.
"""

import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.observability.events import (
    anchored_now,
    get_event_logger,
)


def _gate_for(barrier: Optional[threading.Barrier]):
    """Start-alignment gate: both legs begin together so their spans
    measure real concurrency.  Best-effort — a broken/timed-out
    barrier must never block a leg."""
    if barrier is None:
        return None

    def gate():
        try:
            barrier.wait(timeout=5.0)
        except threading.BrokenBarrierError:
            pass

    return gate


class _CompileLeg:
    """The background AOT-compile thread.  Failure is recorded, never
    raised into the restart path — the first step falls back to the
    lazily-tracing ``train_step``."""

    def __init__(self, fn: Callable, gate=None, events=None):
        self._fn = fn
        self._gate = gate
        self._events = events or get_event_logger()
        self.result = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="restart-aot-compile", daemon=True
        )
        self._thread.start()

    def _run(self):
        if self._gate is not None:
            self._gate()
        t0_mono = time.monotonic()
        t0_wall = anchored_now(t0_mono)
        try:
            self.result = self._fn()
        except Exception as e:  # noqa: BLE001 - degrade, never corrupt
            self.error = e
            logger.warning(
                "background AOT compile failed: %s (first step will "
                "trace lazily)", e,
            )
        finally:
            self._events.complete(
                "aot_compile",
                t0_wall,
                time.monotonic() - t0_mono,
                ok=self.error is None,
            )
            self._done.set()

    def wait(self, timeout: Optional[float] = None):
        self._done.wait(timeout)
        return self.result if self.error is None else None


class RestartCoordinator:
    """Sequences one restart's recovery legs; see the module doc.

    Typical worker bootstrap::

        engine = CheckpointEngine(...)
        coord = RestartCoordinator(engine)
        with coord.rendezvous_wait():
            init_distributed()          # / mesh creation
        fns = build_train_step(...)
        coord.start(compile_fn=lambda: fns.aot_compile(batch_spec))
        step, state = coord.finish_restore(target=state)
        train_step = coord.resolve_train_step(fallback=fns.train_step)

    ``start`` may also run BEFORE the mesh exists when only the
    prefetch leg is wanted (``compile_fn=None``) — the byte stream
    then overlaps the rendezvous itself.
    """

    def __init__(self, engine=None, events=None):
        self._engine = engine
        self._events = events or get_event_logger()
        #: cleared when a leg fails to launch: the serial order follows
        self.overlap = True
        self._prefetch = None
        self._compile_leg: Optional[_CompileLeg] = None
        self._path_sid = -1
        self._pending = set()
        self._started = False

    # ------------------------------------------------------------ legs
    def start(self, compile_fn: Optional[Callable] = None,
              checkpoint_dir: Optional[str] = None,
              layouts=None) -> "RestartCoordinator":
        """Launch the overlappable legs.  Safe to call once; a second
        ``start`` only adds a compile leg if none ran yet (the worker
        may start the prefetch pre-mesh and the compile post-mesh).

        ``layouts`` ({keypath: global-layout dict},
        ``trainer/checkpoint/reshard.py``) makes the restore byte
        prefetch reshard-aware: after a world change it streams
        whichever shard files cover this rank's NEW slices — the
        reshard-copy leg then rides the same overlap window as the
        AOT compile and the rendezvous, so elastic MTTR stays
        ≈ max(reshard, compile)."""
        if not self.overlap:
            return self
        legs = []
        if self._engine is not None and self._prefetch is None:
            legs.append("prefetch")
        if compile_fn is not None and self._compile_leg is None:
            legs.append("compile")
        if not legs:
            return self
        if not self._started:
            self._started = True
            self._path_sid = self._events.begin("restart_path")
        barrier = (
            threading.Barrier(len(legs)) if len(legs) > 1 else None
        )
        try:
            if "prefetch" in legs:
                self._pending.add("restore")
                self._prefetch = self._engine.start_prefetch(
                    checkpoint_dir=checkpoint_dir,
                    start_gate=_gate_for(barrier),
                    layouts=layouts,
                )
            if "compile" in legs:
                self._pending.add("compile")
                self._compile_leg = _CompileLeg(
                    compile_fn, gate=_gate_for(barrier),
                    events=self._events,
                )
        except Exception as e:  # noqa: BLE001 - overlap is an optimization
            logger.warning(
                "restart overlap launch failed: %s (serial path)", e
            )
            self.overlap = False
        return self

    @contextmanager
    def rendezvous_wait(self):
        """Wrap the device-world wait (``jax.distributed`` init / mesh
        barrier) so the ledger sees the coordination leg of this
        restart."""
        with self._events.span("rendezvous_wait"):
            yield

    # --------------------------------------------------------- resolve
    def finish_restore(self, target=None,
                       checkpoint_dir: Optional[str] = None,
                       layouts=None):
        """Consensus + staged-bytes application; serial ``load`` when
        the prefetch was never started or any leg failed.  Returns
        ``(step, state)`` like ``CheckpointEngine.load``.  ``layouts``
        supersedes what ``start`` passed — a caller that only learns
        its target slices after the prefetch launched (the Trainer
        derives them from the initialized state) still gets the
        layout-aware reshard fallback."""
        try:
            if self._engine is None:
                return -1, None
            if not self.overlap or self._prefetch is None:
                return self._engine.load(
                    target=target, checkpoint_dir=checkpoint_dir,
                    layouts=layouts,
                )
            return self._engine.finish_restore(
                self._prefetch, target=target,
                checkpoint_dir=checkpoint_dir, layouts=layouts,
            )
        finally:
            self._resolved("restore")

    def resolve_train_step(self, fallback: Optional[Callable] = None,
                           timeout: float = 600.0):
        """The compiled train step when the AOT leg delivered, else
        ``fallback`` (the lazily-tracing jit).  Waits for an in-flight
        compile — the first step should block on the artifact, not
        start a redundant cold trace."""
        try:
            if self._compile_leg is None:
                return fallback
            compiled = self._compile_leg.wait(timeout)
            return compiled if compiled is not None else fallback
        finally:
            self._resolved("compile")

    def _resolved(self, leg: str):
        self._pending.discard(leg)
        if self._started and not self._pending:
            self._started = False
            self._events.end("restart_path", sid=self._path_sid)

    def close(self):
        """End the parent span early (abandoned restart path)."""
        self._pending.clear()
        if self._started:
            self._started = False
            self._events.end("restart_path", sid=self._path_sid)
