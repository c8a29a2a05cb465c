"""Environment helpers for node/process identity.

Reference parity: ``dlrover/python/common/env_utils.py``.
"""

import os

from dlrover_tpu.common.constants import NodeEnv


def _get_int(name: str, default: int = 0) -> int:
    value = os.getenv(name, "")
    try:
        return int(value)
    except (TypeError, ValueError):
        return default


def get_node_id() -> int:
    return _get_int(NodeEnv.NODE_ID, 0)


def get_node_rank() -> int:
    return _get_int(NodeEnv.NODE_RANK, get_node_id())


def get_node_num() -> int:
    return _get_int(NodeEnv.NODE_NUM, 1)


def get_node_type() -> str:
    return os.getenv(NodeEnv.NODE_TYPE, "worker")


def get_process_rank() -> int:
    return _get_int(NodeEnv.PROCESS_RANK, 0)


def get_process_count() -> int:
    return _get_int(NodeEnv.PROCESS_COUNT, 1)


def get_local_rank() -> int:
    return _get_int(NodeEnv.LOCAL_RANK, 0)


def get_local_process_count() -> int:
    return _get_int(NodeEnv.LOCAL_PROCESS_COUNT, 1)


def get_master_addr() -> str:
    return os.getenv(NodeEnv.MASTER_ADDR, "")


def get_job_name() -> str:
    return os.getenv(NodeEnv.JOB_NAME, "local-job")


def get_restart_count() -> int:
    return _get_int(NodeEnv.RESTART_COUNT, 0)


EVENTS_MAX_MB_ENV = "DLROVER_TPU_EVENTS_MAX_MB"
TIMELINE_MAX_AGE_ENV = "DLROVER_TPU_TIMELINE_MAX_AGE_S"
TIMELINE_MAX_ROWS_ENV = "DLROVER_TPU_TIMELINE_MAX_ROWS"


def env_float(name: str, default: float) -> float:
    """Float env knob with a default (malformed values fall back) —
    the one parser behind every tunable threshold."""
    try:
        return float(os.getenv(name, "") or default)
    except ValueError:
        return default


def events_max_bytes() -> int:
    """Size-based rotation threshold for the agent-side JSONL events
    file (0 = never rotate).  Generous default: a week-long job at
    control-plane event rates stays far below it."""
    return int(env_float(EVENTS_MAX_MB_ENV, 256.0) * 1024 * 1024)


def timeline_max_age_s() -> float:
    """Brain ``timeline_events`` retention age (rows older than this
    are swept; 0 = age-unbounded)."""
    return env_float(TIMELINE_MAX_AGE_ENV, 7 * 24 * 3600.0)


def timeline_max_rows() -> int:
    """Brain ``timeline_events`` per-job row cap (newest rows win;
    0 = row-unbounded)."""
    return int(env_float(TIMELINE_MAX_ROWS_ENV, 500_000))


CKPT_CLOSE_TIMEOUT_ENV = "DLROVER_TPU_CKPT_CLOSE_TIMEOUT_S"
PREEMPT_DRAIN_GRACE_ENV = "DLROVER_TPU_PREEMPT_DRAIN_GRACE_S"


def ckpt_close_timeout_s() -> float:
    """How long ``CheckpointEngine.close()`` waits for an in-flight
    snapshot drain before deliberately LEAKING the shm/lock/queue
    handles (closing under a live drain would corrupt the persist —
    the leak is the safe outcome, now observable via the
    ``dlrover_tpu_ckpt_drain_stuck`` counter)."""
    return env_float(CKPT_CLOSE_TIMEOUT_ENV, 300.0)


def preempt_drain_grace_s() -> float:
    """How long the agent waits, after asking workers to drain
    (SIGUSR1 -> snapshot-every-step), for a fresh common step to land
    in shm before flushing to storage.  Bounded by the preemption
    notice lead (~60 s on GCE) and the pod's SIGTERM grace."""
    return env_float(PREEMPT_DRAIN_GRACE_ENV, 5.0)


GEN_TIMEOUT_ENV = "DLROVER_TPU_GEN_TIMEOUT_S"
GEN_CLOSE_TIMEOUT_ENV = "DLROVER_TPU_GEN_CLOSE_TIMEOUT_S"
GEN_BUCKETS_ENV = "DLROVER_TPU_GEN_BUCKETS"
SERVING_DRAIN_ENV = "DLROVER_TPU_SERVING_DRAIN_S"


def gen_timeout_s() -> float:
    """Per-request response timeout of the cross-process serving
    engine (``ServingEngine.result``)."""
    return env_float(GEN_TIMEOUT_ENV, 600.0)


def gen_close_timeout_s() -> float:
    """How long generation-engine ``close()`` waits for the worker's
    stop handshake / process exit before killing it (the
    ``DLROVER_TPU_CKPT_CLOSE_TIMEOUT_S`` pattern)."""
    return env_float(GEN_CLOSE_TIMEOUT_ENV, 30.0)


def gen_buckets() -> tuple:
    """Prompt-length buckets for the generation backends: prompts pad
    up to the smallest bucket >= their length, so
    ``JitSamplerBackend`` / ``KVCacheBackend`` compile once per
    (batch, BUCKET) instead of once per distinct ``[B, P]``.  Causal
    masking makes the padded result identical to the exact-shape one
    at any temperature (the batch dim — which shapes the sampler's
    noise — is never padded).  Unset/empty = exact shapes (today's
    behavior)."""
    raw = os.getenv(GEN_BUCKETS_ENV, "")
    out = []
    for part in raw.replace(";", ",").split(","):
        part = part.strip()
        if part:
            try:
                out.append(int(part))
            except ValueError:
                continue  # junk entries are ignored, not fatal
    return tuple(sorted(set(b for b in out if b > 0)))


def serving_drain_grace_s() -> float:
    """How long a draining serving replica keeps stepping to flush
    responses before handing unfinished sequences back to the
    dispatcher (SIGUSR1/SIGTERM drain protocol)."""
    return env_float(SERVING_DRAIN_ENV, 2.0)


FLEET_IMBALANCE_ENV = "DLROVER_TPU_FLEET_IMBALANCE_CAP"
FLEET_INTERACTIVE_SLOTS_ENV = "DLROVER_TPU_FLEET_INTERACTIVE_SLOTS"
FLEET_PREFILL_WORKERS_ENV = "DLROVER_TPU_FLEET_PREFILL_WORKERS"
FLEET_SHIP_SLOTS_ENV = "DLROVER_TPU_FLEET_SHIP_SLOTS"
FLEET_MIN_SHIP_PROMPT_ENV = "DLROVER_TPU_FLEET_MIN_SHIP_PROMPT"


def fleet_imbalance_cap() -> int:
    """Affinity routing's load-imbalance cap: an affinity-preferred
    replica is eligible only while its outstanding count stays within
    this many requests of the least-loaded live replica — affinity may
    bias placement but never starve a replica (>= 1)."""
    return max(1, int(env_float(FLEET_IMBALANCE_ENV, 4)))


def fleet_interactive_slots() -> int:
    """Reserved decode-slot quota for the interactive SLO class: batch
    admission leaves at least this many of ``max_slots`` free for
    interactive lanes (clamped to ``max_slots - 1`` at use so batch
    can always make progress; 0 = no reservation)."""
    return max(0, int(env_float(FLEET_INTERACTIVE_SLOTS_ENV, 2)))


def fleet_prefill_workers() -> int:
    """How many replicas the dispatcher designates as PREFILL workers
    (disaggregated prefill/decode).  They fill KV blocks and ship them
    over shm to decode replicas; 0 (the default) keeps every replica
    unified.  Clamped so at least one decode replica remains."""
    return max(0, int(env_float(FLEET_PREFILL_WORKERS_ENV, 0)))


def fleet_ship_slots() -> int:
    """Slots in the dispatcher-owned shm ship arena (concurrent
    in-flight prefill->decode block transfers; >= 1)."""
    return max(1, int(env_float(FLEET_SHIP_SLOTS_ENV, 8)))


def fleet_min_ship_prompt() -> int:
    """Minimum prompt length (tokens) for a request to take the
    disaggregated prefill->ship->decode path; shorter prompts go
    straight to a decode replica (prefilling them locally costs less
    than a block ship).  0 = ship everything."""
    return max(0, int(env_float(FLEET_MIN_SHIP_PROMPT_ENV, 0)))


KV_GROW_BLOCKS_ENV = "DLROVER_TPU_KV_GROW_BLOCKS"
KV_ADMIT_WATERMARK_ENV = "DLROVER_TPU_KV_ADMIT_WATERMARK"
DECODE_STEPS_ENV = "DLROVER_TPU_DECODE_STEPS"


def kv_grow_blocks() -> int:
    """Decode-time growth quantum: how many blocks an admitted
    sequence reserves as headroom beyond its prompt, and the chunk its
    block table grows by when decode crosses a block boundary (>= 1 —
    the first decode position can sit past the prompt's last block)."""
    return max(1, int(env_float(KV_GROW_BLOCKS_ENV, 2)))


def kv_admit_watermark() -> float:
    """Watermark admission: a new sequence is admitted only if,
    after its initial allocation, at least this
    FRACTION of the usable pool stays free as growth headroom for the
    sequences already running.  0 = admit whenever the initial
    allocation fits (maximum admission, maximum preemption churn).
    The first sequence always admits regardless (progress)."""
    return min(max(env_float(KV_ADMIT_WATERMARK_ENV, 0.1), 0.0), 0.9)


def decode_steps() -> int:
    """Multi-token decode: K decode steps fused into ONE compiled
    scheduler iteration (K-greedy self-drafting + one batched verify
    forward; ``rl/scheduler.py``).  ``DLROVER_TPU_DECODE_STEPS=1``
    (the default) is exactly the PR-13 one-token-per-dispatch loop."""
    return max(1, int(env_float(DECODE_STEPS_ENV, 1)))


PROFILE_ENV = "DLROVER_TPU_PROFILE"
PROFILE_EVERY_ENV = "DLROVER_TPU_PROFILE_EVERY_N_STEPS"
CAPTURE_STEPS_ENV = "DLROVER_TPU_CAPTURE_STEPS"
CAPTURE_COOLDOWN_ENV = "DLROVER_TPU_CAPTURE_COOLDOWN_S"
CAPTURE_TIMEOUT_ENV = "DLROVER_TPU_CAPTURE_TIMEOUT_S"
CAPTURE_DIR_ENV = "DLROVER_TPU_CAPTURE_DIR"


def profile_enabled() -> bool:
    """Kill-switch for the live attribution profiler: the continuous
    ``step_profile`` leg in the trainer, the per-node MFU /
    device-share derivations + gauges in the ``HealthEngine``, the
    master's ``CaptureCoordinator`` (diagnosis-triggered deep
    captures riding the directive piggyback), the worker-side capture
    signal handler, and the Brain ``profiles`` surface.
    ``DLROVER_TPU_PROFILE=0`` reproduces today's paths exactly: no
    ``step_profile`` spans, no mfu/device-share gauges, no ``capture``
    directives on the wire (pinned by tests).  Default: enabled —
    though the continuous leg additionally needs
    ``DLROVER_TPU_PROFILE_EVERY_N_STEPS`` > 0 (default 0 = off, zero
    per-step overhead)."""
    return os.getenv(PROFILE_ENV, "1").lower() not in (
        "0", "false", "off",
    )


def profile_every_n_steps() -> int:
    """Continuous-leg cadence: every N steps the trainer captures a
    one-step ``jax.profiler`` trace and emits a ``step_profile`` span
    (0 = off; the default, so the always-on claim costs nothing until
    an operator opts in)."""
    return max(int(env_float(PROFILE_EVERY_ENV, 0.0)), 0)


def capture_steps() -> int:
    """How many consecutive steps a deep capture traces."""
    return max(int(env_float(CAPTURE_STEPS_ENV, 3.0)), 1)


def capture_cooldown_s() -> float:
    """Per-node throttle on diagnosis-triggered deep captures: the
    hang-watchdog / sustained-straggler conclusions auto-trigger at
    most ONE capture of a node per this window."""
    return env_float(CAPTURE_COOLDOWN_ENV, 600.0)


def capture_timeout_s() -> float:
    """How long the agent waits for its workers' profile artifacts
    after the capture signal before shipping what it has (a hung
    worker never answers — its stack dump is the artifact)."""
    return env_float(CAPTURE_TIMEOUT_ENV, 15.0)


def capture_dir() -> str:
    """Where capture artifacts (stack dumps, trace summaries) land:
    ``DLROVER_TPU_CAPTURE_DIR``, else a ``captures/`` dir next to the
    node's events file, else "" (no capture surface)."""
    d = os.getenv(CAPTURE_DIR_ENV, "")
    if d:
        return d
    events_file = os.getenv("DLROVER_TPU_EVENTS_FILE", "")
    if events_file:
        return os.path.join(
            os.path.dirname(os.path.abspath(events_file)), "captures"
        )
    return ""


BRAIN_ENV = "DLROVER_TPU_BRAIN"
BRAIN_INTERVAL_ENV = "DLROVER_TPU_BRAIN_INTERVAL_S"
BRAIN_COOLDOWN_ENV = "DLROVER_TPU_BRAIN_COOLDOWN_S"
BRAIN_SUSTAIN_ENV = "DLROVER_TPU_BRAIN_SUSTAIN"


def brain_enabled() -> bool:
    """Kill-switch for the autonomy loop: the observatory-fed Brain
    (``master/resource_optimizer.ObservatoryBrainOptimizer`` +
    ``master/auto_scaler.BrainAutoScaler`` + the planned-action
    executor in ``master/brain.py``), its node directives riding the
    ``WaitingNodeNum`` response, its journal component, and the
    ``scale_decision``/``scale_execute`` telemetry.
    ``DLROVER_TPU_BRAIN=0`` reproduces the seed auto-scaler exactly:
    ``AllreduceAutoScaler`` polling the ``SpeedMonitor`` with
    ``Scaler.scale(plan)`` as its only actuator, no directives on the
    wire, nothing journaled.  Default: enabled."""
    return os.getenv(BRAIN_ENV, "1").lower() not in (
        "0", "false", "off",
    )


def brain_interval_s() -> float:
    """Cadence of the Brain decision cycle."""
    return env_float(BRAIN_INTERVAL_ENV, 30.0)


def brain_cooldown_s() -> float:
    """Minimum quiet time after an executed decision before the next
    same-direction decision; opposite-direction decisions wait twice
    this (hysteresis)."""
    return env_float(BRAIN_COOLDOWN_ENV, 120.0)


def brain_sustain_cycles() -> int:
    """Consecutive decision cycles a signal (straggler verdict, hang
    verdict, chronic stall share) must persist before the Brain acts
    on it — one noisy snapshot is not a verdict."""
    return max(int(env_float(BRAIN_SUSTAIN_ENV, 2.0)), 1)


MASTER_WORKERS_ENV = "DLROVER_TPU_MASTER_WORKERS"


def master_workers() -> int:
    """gRPC thread-pool size of the master server
    (``DLROVER_TPU_MASTER_WORKERS``).  Each PARKED long-poll holds a
    pool thread for its whole wait, so the ceiling bounds the fleet a
    single master can serve — it must be raisable without a code
    change, and the occupancy gauge
    (``dlrover_tpu_master_busy_workers`` over
    ``dlrover_tpu_master_worker_pool_size``) is derived from this
    same value so the two can never disagree."""
    return max(int(env_float(MASTER_WORKERS_ENV, 64.0)), 1)


RECONNECT_DEADLINE_ENV = "DLROVER_TPU_MASTER_RECONNECT_DEADLINE_S"
SNAPSHOT_INTERVAL_ENV = "DLROVER_TPU_CONTROL_SNAPSHOT_INTERVAL_S"


def master_reconnect_deadline_s() -> float:
    """Total time a client keeps retrying/reconnecting across a
    master outage before giving up."""
    try:
        return float(os.getenv(RECONNECT_DEADLINE_ENV, "120"))
    except ValueError:
        return 120.0


def control_snapshot_interval_s() -> float:
    """Cadence of the master's compacted control-plane snapshot
    (journal entries at or below the snapshot seq are pruned)."""
    try:
        return float(os.getenv(SNAPSHOT_INTERVAL_ENV, "20"))
    except ValueError:
        return 20.0


FLYWHEEL_STALENESS_ENV = "DLROVER_TPU_FLYWHEEL_STALENESS"
FLYWHEEL_MAX_LAG_ENV = "DLROVER_TPU_FLYWHEEL_MAX_LAG"
FLYWHEEL_PUBLISH_EVERY_ENV = "DLROVER_TPU_FLYWHEEL_PUBLISH_EVERY"
FLYWHEEL_LEND_QUEUE_ENV = "DLROVER_TPU_FLYWHEEL_LEND_QUEUE"
FLYWHEEL_RECLAIM_QUEUE_ENV = "DLROVER_TPU_FLYWHEEL_RECLAIM_QUEUE"
FLYWHEEL_MIN_TRAIN_ENV = "DLROVER_TPU_FLYWHEEL_MIN_TRAIN_WORLD"


def flywheel_staleness_policy() -> str:
    """What happens to a trajectory whose generation lags the current
    published weights by more than ``flywheel_max_lag()``: ``drop``
    (the default — off-policy beyond the lag bound is discarded and
    counted in ``dlrover_tpu_flywheel_staleness_dropped``) or ``tag``
    (kept, with the lag recorded so the learner can importance-weight
    it)."""
    val = os.getenv(FLYWHEEL_STALENESS_ENV, "drop").lower()
    return val if val in ("drop", "tag") else "drop"


def flywheel_max_lag() -> int:
    """Maximum generations a trajectory may lag the published weights
    before the staleness policy applies (>= 0; 0 = only on-policy
    trajectories pass untouched)."""
    return max(0, int(env_float(FLYWHEEL_MAX_LAG_ENV, 1)))


def flywheel_publish_every() -> int:
    """K: the trainer publishes policy (and draft) weights into the
    shm snapshot segment every K optimizer steps (>= 1)."""
    return max(1, int(env_float(FLYWHEEL_PUBLISH_EVERY_ENV, 4)))


def flywheel_lend_queue_depth() -> float:
    """Rollout-bound threshold: sustained serving queue depth (per
    live replica) at or above this marks the round rollout-bound and
    eligible for a train->serve chip lend."""
    return env_float(FLYWHEEL_LEND_QUEUE_ENV, 4.0)


def flywheel_reclaim_queue_depth() -> float:
    """Learner-bound threshold: sustained serving queue depth (per
    live replica) at or below this, with a lend outstanding, triggers
    the reclaim (drain a replica, rank rejoins the mesh)."""
    return env_float(FLYWHEEL_RECLAIM_QUEUE_ENV, 0.5)


def flywheel_min_train_world() -> int:
    """Floor on the trainer world size during arbitration: the
    FlywheelOperator never lends a chip that would shrink the mesh
    below this (>= 1)."""
    return max(1, int(env_float(FLYWHEEL_MIN_TRAIN_ENV, 1)))


def get_free_port(host: str = "127.0.0.1") -> int:
    import socket

    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
        sock.bind((host, 0))
        return sock.getsockname()[1]
