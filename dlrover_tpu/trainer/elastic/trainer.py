"""Step-level elasticity: global-batch-invariant accumulation + progress.

Reference parity: ``dlrover/trainer/torch/elastic/trainer.py:181``
(``ElasticTrainer``) and ``GradientState:53`` — gradient accumulation is
re-derived from the *current* world size so the effective global batch
stays constant as nodes join/leave; the step counter is reported to the
master's SpeedMonitor.

JAX redesign: instead of wrapping an optimizer object, the trainer
exposes ``num_micro_steps`` (for a ``lax.scan`` micro-batch loop — the
idiomatic XLA way to accumulate) and ``accumulate_gradients`` for an
eager loop.  Progress reporting goes straight to the master over gRPC
from rank 0 and to a step file the agent's TrainingMonitor watches.
"""

import json
import os
import time
from typing import Callable, Optional

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.observability.events import (
    anchored_now,
    get_event_logger,
)
from dlrover_tpu.trainer.elastic.context import (
    process_count,
    process_rank,
)

DEFAULT_STEP_FILE = "/tmp/dlrover_tpu_global_step.json"


class ElasticTrainer:
    def __init__(
        self,
        global_batch_size: int,
        micro_batch_size: int,
        world_size: Optional[int] = None,
        rank: Optional[int] = None,
        step_file: str = "",
        report_interval: float = 15.0,
        master_client=None,
    ):
        self.global_batch_size = global_batch_size
        self.micro_batch_size = micro_batch_size
        self.world_size = world_size or process_count()
        self.rank = rank if rank is not None else process_rank()
        per_step = self.micro_batch_size * self.world_size
        if global_batch_size % per_step != 0:
            logger.warning(
                "global batch %d not divisible by micro*world %d; "
                "rounding accumulation up",
                global_batch_size,
                per_step,
            )
        self.num_micro_steps = max(
            1, (global_batch_size + per_step - 1) // per_step
        )
        self.global_step = 0
        self._step_file = step_file or os.getenv(
            "DLROVER_TPU_STEP_FILE", DEFAULT_STEP_FILE
        )
        self._report_interval = report_interval
        self._last_report = 0.0
        self._client = master_client
        # timeline: each step_done closes a `step` span back to the
        # previous one — the useful-time side of the goodput ledger
        self._events = get_event_logger()
        self._step_mark = None  # (wall, mono) of the last step_done

    # ------------------------------------------------------------ progress
    def _master_client(self):
        if self._client is None and os.getenv(NodeEnv.MASTER_ADDR):
            from dlrover_tpu.agent.master_client import MasterClient

            self._client = MasterClient.singleton_instance()
        return self._client

    def step_done(self, steps: int = 1):
        """Advance the global step and close a ``step`` span back to
        the previous call; rank 0 reports progress."""
        self.advance(steps)
        if self._events.enabled:
            now_m = time.monotonic()
            now_w = anchored_now(now_m)
            if self._step_mark is not None:
                dur = now_m - self._step_mark[1]
                self._events.complete(
                    "step", now_w - dur, dur, step=self.global_step
                )
            self._step_mark = (now_w, now_m)

    def advance(self, steps: int = 1):
        """Advance the global step; rank 0 reports progress.  No
        ``step`` span: for a loop that times its own steps
        (``trainer/trainer.py`` — completion to completion, where the
        loss reaches the host; a call here, right after the dispatch,
        would time dispatch to dispatch)."""
        self.global_step += steps
        if self.rank != 0:
            return
        now = time.time()
        if now - self._last_report < self._report_interval:
            return
        self._last_report = now
        try:
            with open(self._step_file, "w") as f:
                json.dump(
                    {"step": self.global_step, "timestamp": now}, f
                )
        except OSError:
            pass
        client = self._master_client()
        if client is not None:
            try:
                client.report_global_step(self.global_step, now)
            except ConnectionError:
                pass

    # -------------------------------------------------------- accumulation
    def accumulate_gradients(
        self,
        grad_fn: Callable,
        params,
        micro_batches,
    ):
        """Eager accumulation over ``micro_batches`` (an iterable of
        pytrees); returns (mean_loss, mean_grads).  Prefer a
        ``lax.scan`` inside jit for the hot path — see
        ``dlrover_tpu.parallel.train_step``."""
        import jax

        total_loss = None
        total_grads = None
        count = 0
        for batch in micro_batches:
            loss, grads = grad_fn(params, batch)
            if total_grads is None:
                total_loss, total_grads = loss, grads
            else:
                total_loss = total_loss + loss
                total_grads = jax.tree_util.tree_map(
                    lambda a, b: a + b, total_grads, grads
                )
            count += 1
        scale = 1.0 / max(count, 1)
        mean_grads = jax.tree_util.tree_map(
            lambda g: g * scale, total_grads
        )
        return total_loss * scale, mean_grads

    def state_dict(self) -> dict:
        return {"global_step": self.global_step}

    def load_state_dict(self, state: dict):
        self.global_step = int(state.get("global_step", 0))
