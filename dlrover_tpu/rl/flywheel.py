"""The zero-copy RLHF flywheel (ISSUE 20).

Closes the train -> rollout -> train loop with zero
serialize/deserialize hops on either leg:

- **In-place weight publish** — every K optimizer steps the trainer
  commits its policy params (and, in draft mode, the small drafter
  trained alongside it) straight into the double-buffered shm
  snapshot segment serving replicas already adopt from
  (``ServingEngine.sync_weights``).  The publish of generation g+1
  overlaps training while replicas still read generation g; the
  generation side-segment (``agent/ckpt_shm``) makes replica probes
  one atomic-width load, and a publisher killed mid-save never bumps
  it — no replica ever observes a torn snapshot.  The trainer's
  stall is bounded by one chunk-parallel memcpy, not a pickle hop.

- **Trajectory streaming** — every completed rollout (prompt +
  sampled tail + per-token logprobs + the policy generation that
  sampled it) flows back to the trainer through the same shm-ring
  substrate the serving transport rides, arriving as a ready
  training sample.  Exactly-once by req-id dedup (an optional journal
  survives consumer restarts), and — sampling being
  (seed, position)-pure — a replayed round is bitwise-identical.
  Stale trajectories (generation lag beyond
  ``DLROVER_TPU_FLYWHEEL_MAX_LAG``) are dropped or importance-tagged
  per ``DLROVER_TPU_FLYWHEEL_STALENESS``.

- **Device arbitration** lives in
  ``master/flywheel_operator.FlywheelOperator`` (the Brain side);
  this module only exposes the plane gauges it consumes.
"""

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from dlrover_tpu.common.env import (
    flywheel_max_lag,
    flywheel_publish_every,
    flywheel_staleness_policy,
)
from dlrover_tpu.common.log import default_logger as logger

#: trajectory-ring payload schema; bump on ANY layout change (the
#: serving rings carry their own independent RING_SCHEMA_VERSION)
TRAJ_SCHEMA_VERSION = 1


def _traj_spec(max_total: int):
    from dlrover_tpu.data.shm_dataloader import BatchSpec

    return BatchSpec(
        {
            # req_id, prompt_len, total_len, new_tokens, generation
            # (the policy generation whose weights sampled the tail),
            # seed, schema_version, finish_code
            "meta": ((8,), "<i8"),
            # [prompt | sampled tail], zero-padded
            "tokens": ((max_total,), "<i4"),
            # per sampled token: log p(token | prefix) under the
            # sampling policy (NaN where capture missed a position)
            "logprobs": ((max_total,), "<f4"),
        }
    )


@dataclass
class Trajectory:
    """One completed rollout as a ready training sample."""

    req_id: int
    tokens: np.ndarray  # [prompt | tail], int32
    prompt_len: int
    new_tokens: int
    logprobs: np.ndarray  # len == new_tokens, float32 (NaN = unknown)
    generation: int  # the policy generation that sampled the tail
    seed: int = 0
    finish_code: int = 0
    stale: bool = False  # tagged by the "tag" staleness policy
    lag: int = 0  # generations behind the newest publish at arrival


@dataclass
class FlywheelStats:
    published: int = 0
    last_stall_s: float = 0.0
    publish_bytes: int = 0
    streamed: int = 0
    duplicates: int = 0
    staleness_dropped: int = 0
    staleness_tagged: int = 0


class TrajectorySink:
    """Exactly-once, staleness-policed intake for streamed
    trajectories.

    Dedup is by req-id: the serving plane can answer a request twice
    across a drain/crash race, and a chaos-killed consumer may replay
    ring slots after restart — the second copy must never become a
    second gradient.  An optional append-only journal records every
    accepted req-id so a RESTARTED consumer (same journal path)
    resumes the dedup set instead of double-training."""

    def __init__(self, policy: Optional[str] = None,
                 max_lag: Optional[int] = None,
                 journal_path: Optional[str] = None):
        self.policy = policy or flywheel_staleness_policy()
        self.max_lag = (
            flywheel_max_lag() if max_lag is None else int(max_lag)
        )
        self._seen: set = set()
        self._journal_path = journal_path or ""
        self._journal_fd = None
        self.stats = FlywheelStats()
        if self._journal_path:
            if os.path.exists(self._journal_path):
                with open(self._journal_path) as f:
                    for line in f:
                        line = line.strip()
                        if line:
                            self._seen.add(int(line))
            self._journal_fd = os.open(
                self._journal_path,
                os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                0o644,
            )

    def accept(self, traj: Trajectory,
               current_generation: int) -> Optional[Trajectory]:
        """One trajectory through dedup + staleness; returns it
        (possibly tagged) or None when refused."""
        if traj.req_id in self._seen:
            self.stats.duplicates += 1
            return None
        traj.lag = max(int(current_generation) - traj.generation, 0)
        if traj.lag > self.max_lag:
            if self.policy == "drop":
                self.stats.staleness_dropped += 1
                # a dropped trajectory is still CONSUMED exactly once
                self._mark(traj.req_id)
                return None
            traj.stale = True
            self.stats.staleness_tagged += 1
        self._mark(traj.req_id)
        self.stats.streamed += 1
        return traj

    def _mark(self, req_id: int):
        self._seen.add(req_id)
        if self._journal_fd is not None:
            # O_APPEND + one write: atomic on POSIX, crash-safe line
            os.write(self._journal_fd, f"{req_id}\n".encode())

    def close(self):
        if self._journal_fd is not None:
            os.close(self._journal_fd)
            self._journal_fd = None


def _tree_nbytes(tree) -> int:
    import jax

    return int(
        sum(
            np.asarray(x).nbytes
            for x in jax.tree_util.tree_leaves(tree)
        )
    )


class FlywheelCoordinator:
    """The trainer-side hub of the flywheel: paced in-place weight
    publishes out, streamed trajectories in.

    The trajectory stream is an shm ring (the PR-4 substrate): the
    producer side (``offer_result`` — typically the thread collecting
    ``ServingEngine.result``) and the consumer side (``drain`` — the
    training loop) may live in different processes; both ends attach
    by the coordinator's name."""

    def __init__(
        self,
        engine,
        max_total: int,
        name: Optional[str] = None,
        publish_every: Optional[int] = None,
        staleness: Optional[str] = None,
        max_lag: Optional[int] = None,
        ring_slots: int = 64,
        journal_path: Optional[str] = None,
        create: bool = True,
    ):
        from dlrover_tpu.observability.events import get_event_logger
        from dlrover_tpu.rl.generation_service import _Ring

        self.engine = engine
        self.publish_every = int(
            flywheel_publish_every()
            if publish_every is None else publish_every
        )
        self._max_total = int(max_total)
        self._name = name or f"flywheel-{os.getpid()}"
        self._events = get_event_logger()
        self.sink = TrajectorySink(
            policy=staleness, max_lag=max_lag,
            journal_path=journal_path,
        )
        self.stats = self.sink.stats
        self.generation = 0
        self._ring = _Ring(
            f"{self._name}-traj",
            spec=_traj_spec(self._max_total),
            num_slots=int(ring_slots),
            create=create,
        )
        self._owns_ring = bool(create)
        self._round = 0
        self._window_t0 = time.monotonic()
        self._window_n = 0
        self._closed = False

    # ------------------------------------------------- weight publish
    def publish(self, params, draft_params=None,
                step: Optional[int] = None) -> float:
        """One in-place publish of the policy (+ drafter) into the
        serving plane's snapshot segment.  Returns the stall charged
        to the trainer (the save_state wall time — one chunk-parallel
        memcpy into the inactive slot; replicas keep reading the
        other slot throughout)."""
        from dlrover_tpu.observability.metrics import get_registry

        nbytes = _tree_nbytes(params)
        if draft_params is not None:
            nbytes += _tree_nbytes(draft_params)
        t0 = time.time()
        stall = self.engine.sync_weights(
            params, draft_params=draft_params
        ) if draft_params is not None else self.engine.sync_weights(
            params
        )
        self.generation = int(self.engine._version)
        self.stats.published += 1
        self.stats.last_stall_s = stall
        self.stats.publish_bytes = nbytes
        self._events.complete(
            "weight_publish",
            t0,
            stall,
            generation=self.generation,
            bytes=nbytes,
            stall_s=round(stall, 6),
            step=(-1 if step is None else int(step)),
        )
        reg = get_registry()
        reg.set_gauge(
            "dlrover_tpu_flywheel_generation", self.generation
        )
        reg.set_gauge(
            "dlrover_tpu_flywheel_publish_stall_s", stall
        )
        return stall

    def maybe_publish(self, step: int, params, draft_params=None):
        """Pace-gated publish: every ``publish_every`` steps (and on
        step 0, so replicas never serve the init template once
        training has params).  Returns the stall or None."""
        if int(step) % self.publish_every != 0:
            return None
        return self.publish(params, draft_params=draft_params,
                            step=step)

    # ---------------------------------------------- trajectory stream
    def offer_result(self, req_id: int, prompt, result: Dict,
                     seed: int = 0, timeout: float = 5.0) -> bool:
        """Producer side: pack one completed ``ServingEngine.result``
        payload onto the trajectory ring.  Returns False only when
        the ring stayed full for ``timeout`` (the consumer is gone or
        wedged — the caller decides whether to retry or drop)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tokens = np.asarray(result["tokens"], np.int32).reshape(-1)
        new_tokens = int(result.get("new_tokens", 0))
        total = int(tokens.size)
        buf = np.zeros((self._max_total,), np.int32)
        buf[:total] = tokens[: self._max_total]
        lp_buf = np.full((self._max_total,), np.nan, np.float32)
        lp = np.asarray(
            result.get("logprobs", ()), np.float32
        ).reshape(-1)
        lp_buf[: min(lp.size, self._max_total)] = (
            lp[: self._max_total]
        )
        finish = 1 if result.get("finish_reason") == "eos" else 0
        msg = {
            "meta": np.asarray(
                [int(req_id), int(prompt.size), total, new_tokens,
                 int(result.get("version", -1)), int(seed),
                 TRAJ_SCHEMA_VERSION, finish],
                np.int64,
            ),
            "tokens": buf,
            "logprobs": lp_buf,
        }
        return self._ring.try_put(msg, timeout=timeout)

    def drain(self, max_n: int = 0) -> List[Trajectory]:
        """Consumer side: pull every queued trajectory through the
        sink (dedup + staleness) and return the accepted ones as
        ready training samples."""
        out: List[Trajectory] = []
        while not max_n or len(out) < max_n:
            msg = self._ring.try_get()
            if msg is None:
                break
            meta = msg["meta"]
            if int(meta[6]) != TRAJ_SCHEMA_VERSION:
                raise RuntimeError(
                    f"trajectory payload schema v{int(meta[6])} != "
                    f"reader schema v{TRAJ_SCHEMA_VERSION}"
                )
            total = int(meta[2])
            new_tokens = int(meta[3])
            traj = Trajectory(
                req_id=int(meta[0]),
                tokens=msg["tokens"][:total].copy(),
                prompt_len=int(meta[1]),
                new_tokens=new_tokens,
                logprobs=msg["logprobs"][:new_tokens].copy(),
                generation=int(meta[4]),
                seed=int(meta[5]),
                finish_code=int(meta[7]),
            )
            accepted = self.sink.accept(traj, self.generation)
            if accepted is None:
                continue
            self._events.complete(
                "trajectory",
                time.time(),
                0.0,
                req_id=accepted.req_id,
                generation=accepted.generation,
                tokens=accepted.new_tokens,
            )
            out.append(accepted)
        if out:
            self._window_n += len(out)
            now = time.monotonic()
            if now - self._window_t0 >= 1.0:
                from dlrover_tpu.observability.metrics import (
                    get_registry,
                )

                get_registry().set_gauge(
                    "dlrover_tpu_flywheel_trajectories_per_s",
                    self._window_n / (now - self._window_t0),
                )
                get_registry().set_gauge(
                    "dlrover_tpu_flywheel_staleness_dropped",
                    self.stats.staleness_dropped,
                )
                self._window_n = 0
                self._window_t0 = now
        return out

    # -------------------------------------------------- round harness
    def run_round(self, prompts, max_new: Optional[int] = None,
                  seed: int = 0, timeout: Optional[float] = None,
                  ) -> List[Trajectory]:
        """One whole rollout round: submit every prompt, collect
        every result as it completes, stream each through the ring
        and return the accepted trajectories.  The round is bracketed
        by a ``rollout_round`` span carrying the scoreboard."""
        self._round += 1
        t0 = time.time()
        dropped0 = self.stats.staleness_dropped
        ids = {}
        for i, row in enumerate(prompts):
            s = int(seed) + i * 1000003
            rid = self.engine.submit(row, max_new=max_new, seed=s)
            ids[rid] = (row, s)
        for rid, (row, s) in ids.items():
            res = self.engine.result(rid, timeout=timeout)
            self.offer_result(rid, row, res, seed=s)
        out = self.drain()
        self._events.complete(
            "rollout_round",
            t0,
            time.time() - t0,
            round=self._round,
            trajectories=len(out),
            staleness_dropped=(
                self.stats.staleness_dropped - dropped0
            ),
        )
        return out

    def close(self):
        if self._closed:
            return
        self._closed = True
        self.sink.close()
        try:
            self._ring.close(unlink=self._owns_ring)
        except Exception as e:  # noqa: BLE001 - already unlinked
            logger.warning("flywheel ring close failed: %s", e)
