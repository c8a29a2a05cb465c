"""Chaos-injection harness: kill the control plane, measure recovery.

Plays the role of DLRover's ElasticJob controller for a local job: it
owns the master subprocess (``python -m dlrover_tpu.master.main`` with
a durable ``--brain_db``), launches the training job against it
(``python -m dlrover_tpu.run --master_addr=...`` running the goodput
worker), and SUPERVISES the master — whenever the master process dies,
the harness restarts it on the same port with the same Brain db, the
way the controller recreates a failed master pod and agents simply
reattach (PAPER.md §1).  Master recovery (journal+snapshot replay,
incarnation bump, agents re-parking their long-polls) is the product
under test; this script only measures it.

Fault plans (``--plan``):

- ``none``                  — no faults; the goodput baseline leg.
- ``master-kill-storm``     — ``--kills`` timer-driven SIGKILLs of the
  master, evenly spaced across the step budget.
- ``master-kill-rendezvous``/``master-kill-longpoll``/
  ``master-kill-flush`` — a SEEDED one-kill fault plan pinned to the
  named phase hook (``DLROVER_TPU_FAULT_PLAN`` +
  ``DLROVER_TPU_FAULT_ROLE=master``): the master SIGKILLs itself at
  ``mid_rendezvous`` / ``mid_long_poll`` / ``mid_report_flush``, which
  reproduces "the master dies mid-X" deterministically instead of by
  racing a timer against the serve loop.  The plan rides only the
  FIRST incarnation — a restarted master is a fresh pod; the
  controller does not re-inject the chaos.
- ``agent-kill``            — SIGKILL the rank-1 worker once mid-run
  (the PR-3 worker-restart path, for storm mixes).
- ``rpc-chaos``             — seeded drop/delay/duplicate of agent
  RPCs at the ``MasterChannel`` boundary; no kills.  The job must
  complete anyway (retries + idempotent masters absorb it).

Reported per run (JSON ``--out`` artifact, wired into ``bench.py``
``extras.failover``):

- ``master_kills`` / ``master_restarts`` and per-kill ``mttr_s`` —
  wall time from master death to the NEW incarnation answering a
  ``ControlEpochRequest`` (replay is complete before the server
  opens, so "answers the epoch probe" == "serving the resumed job").
- ``goodput`` — final step x steady-state step time / wall clock, the
  same definition ``bench_goodput`` uses.
- ``stall_max_s`` — the longest gap between consecutive completed
  steps; under master failover a master kill should barely dent this
  (steps don't go through the master at steady state).
- ``job_survived`` — the job MUST survive the storm: a dead job is a
  harness-level failure.

Honors ``DLROVER_TPU_BENCH_BUDGET_S`` (scales the step budget down).

Usage::

    python scripts/chaos.py --plan master-kill-storm [--kills 2]
                            [--steps 60] [--seed 7] [--out OUT.json]
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import BenchBudget, flush_partial as _flush  # noqa: E402

from dlrover_tpu.common.comm import (  # noqa: E402
    MasterChannel,
    wait_channel_ready,
)
from dlrover_tpu.common.env import get_free_port  # noqa: E402

PLANS = (
    "none",
    "master-kill-storm",
    "master-kill-rendezvous",
    "master-kill-longpoll",
    "master-kill-flush",
    "agent-kill",
    "rpc-chaos",
    # SIGTERM-with-grace waves against one of two agent pods (unlike
    # the SIGKILL plans): the dying agent drains its workers to a
    # fresh snapshot, flushes, fences itself at the master, and the
    # SURVIVOR re-meshes onto the shrunken world without a restart-
    # from-scratch; the pod is re-created after a delay and the world
    # grows back.  Run twice by main() — DLROVER_TPU_BRAIN=1 vs the
    # static seed auto-scaler (=0) — to produce the Brain-vs-static
    # goodput/MTTR artifact.
    "preempt-storm",
    # sleep-fault one pod of three MID-RUN (a chip degrades under the
    # job): the coupled world runs at the slow rank's speed.  With
    # the Brain on, the master's straggler derivation names the node,
    # the Brain issues ONE planned drain_replace — cooperative drain
    # directive → fence → survivors re-mesh and reshard-restore — and
    # the job finishes at full speed on the shrunken world.  Brain
    # off, nobody acts and the job limps to the target.  Run twice by
    # main() to produce the Brain-vs-static goodput artifact.
    "slow-node",
)

#: phase hook each plan pins its master kill to
_PHASE_FOR_PLAN = {
    "master-kill-rendezvous": "mid_rendezvous",
    "master-kill-longpoll": "mid_long_poll",
    "master-kill-flush": "mid_report_flush",
}


def build_fault_plan(plan: str, seed: int) -> str:
    """The ``DLROVER_TPU_FAULT_PLAN`` JSON for plan-driven faults
    ("" = the plan is timer-driven or fault-free)."""
    phase = _PHASE_FOR_PLAN.get(plan)
    if phase is not None:
        return json.dumps({
            "seed": seed,
            "faults": [{
                "kind": "kill", "target": "master",
                "phase": phase, "count": 1,
            }],
        })
    if plan == "rpc-chaos":
        return json.dumps({
            "seed": seed,
            "faults": [
                {"kind": "rpc", "op": "drop", "prob": 0.05,
                 "count": -1},
                {"kind": "rpc", "op": "delay", "prob": 0.05,
                 "delay_s": 0.05, "count": -1},
                {"kind": "rpc", "op": "dup", "prob": 0.05,
                 "count": -1},
            ],
        })
    return ""


def _read_progress(path):
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    return out


class MasterSupervisor:
    """Owns the master subprocess: spawn, death detection, restart on
    the same port + Brain db, per-restart MTTR."""

    def __init__(self, workdir: str, fault_plan: str = "",
                 job_name: str = "chaos", extra_env: dict = None):
        self.port = get_free_port()
        self.addr = f"127.0.0.1:{self.port}"
        self._workdir = workdir
        self._brain_db = os.path.join(workdir, "brain.db")
        self._log_path = os.path.join(workdir, "master.log")
        self._fault_plan = fault_plan
        self._job_name = job_name
        #: master-side knob overrides (Brain cadence, straggler ratio
        #: ... the slow-node plan tightens them to chaos timescales)
        self._extra_env = dict(extra_env or {})
        self._proc = None
        self.incarnations = 0
        self.mttr_s = []

    def _spawn(self, with_plan: bool):
        env = dict(
            os.environ,
            PYTHONPATH=REPO,
            DLROVER_TPU_BRAIN_DB=self._brain_db,
            DLROVER_TPU_EVENTS_FILE=os.path.join(
                self._workdir, "events.jsonl"
            ),
            # compact often: a chaos run is short, and the recovery
            # cost bound (snapshot + linger of journal) is the point
            DLROVER_TPU_CONTROL_SNAPSHOT_INTERVAL_S="5",
            DLROVER_TPU_FAULT_ROLE="master",
        )
        env.update(self._extra_env)
        if with_plan and self._fault_plan:
            env["DLROVER_TPU_FAULT_PLAN"] = self._fault_plan
        else:
            env.pop("DLROVER_TPU_FAULT_PLAN", None)
        log = open(self._log_path, "a")
        self._proc = subprocess.Popen(  # noqa: S603
            [
                sys.executable, "-m", "dlrover_tpu.master.main",
                "--platform", "local",
                "--port", str(self.port),
                "--node_num", "1",
                "--job_name", self._job_name,
            ],
            stdout=log, stderr=subprocess.STDOUT, env=env,
            cwd=self._workdir,
        )
        log.close()
        self.incarnations += 1

    def _probe_ready(self, timeout: float) -> bool:
        """Serving == the NEW incarnation answers an epoch probe
        (recovery replays before the gRPC server opens, so this is
        also 'the resumed job state is installed')."""
        if not wait_channel_ready(self.addr, timeout=timeout):
            return False
        chan = MasterChannel(self.addr)
        try:
            chan.refresh_epoch(timeout=5.0, deadline_s=5.0)
            return True
        except ConnectionError:
            return False
        finally:
            chan.close()

    def start(self, timeout: float = 30.0) -> bool:
        self._spawn(with_plan=True)
        return self._probe_ready(timeout)

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def kill(self):
        if self.alive():
            try:
                os.kill(self._proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass

    def restart(self, timeout: float = 30.0) -> bool:
        """Controller behavior: recreate the dead master pod.  The
        fault plan is NOT re-injected.  Records MTTR from the moment
        the death was observed."""
        t_dead = time.perf_counter()
        if self._proc is not None:
            self._proc.wait()
        self._spawn(with_plan=False)
        ok = self._probe_ready(timeout)
        if ok:
            self.mttr_s.append(
                round(time.perf_counter() - t_dead, 3)
            )
        return ok

    def stop(self):
        if self._proc is not None and self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait()

    def log_tail(self, n: int = 800) -> str:
        try:
            return open(self._log_path).read()[-n:]
        except OSError:
            return ""


class NodePod:
    """One simulated elastic pod: a ``dlrover_tpu.run`` launcher (the
    per-node agent) pinned to a node_rank against a shared master."""

    def __init__(self, workdir: str, node_rank: int, master_addr: str,
                 env: dict, max_nodes: int = 2):
        self.node_rank = node_rank
        self._workdir = workdir
        self._addr = master_addr
        self._env = dict(env)
        self._max_nodes = max_nodes
        self._log_path = os.path.join(
            workdir, f"pod{node_rank}.log"
        )
        self.proc = None
        self.launches = 0

    def launch(self):
        log = open(self._log_path, "a")
        env = dict(self._env, DLROVER_TPU_NODE_RANK=str(self.node_rank))
        # per-pod socket namespace: on a real cluster every node has
        # its own /tmp — two simulated pods sharing one socket dir
        # would collide on the agent's ckpt factory queue
        env["DLROVER_TPU_SOCKET_DIR"] = os.path.join(
            self._workdir, f"socks{self.node_rank}"
        )
        self.proc = subprocess.Popen(  # noqa: S603
            [
                sys.executable, "-m", "dlrover_tpu.run",
                f"--nnodes=1:{self._max_nodes}",
                "--nproc_per_node=1",
                f"--node_rank={self.node_rank}",
                f"--master_addr={self._addr}",
                "--monitor_interval=0.3",
                "--stop_timeout=2",
                "--failure_stop_timeout=0.5",
                "--max_restarts=6",
                "--rdzv_timeout=60",
                # a lone survivor must complete its shrunken round in
                # seconds; joiners still get the full 60 s above
                "--rdzv_waiting_timeout=1.5",
                os.path.join(REPO, "scripts", "goodput_train.py"),
            ],
            stdout=log, stderr=subprocess.STDOUT, env=env,
            cwd=self._workdir,
        )
        log.close()
        self.launches += 1

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def sigterm(self):
        if self.alive():
            try:
                self.proc.terminate()
            except ProcessLookupError:
                pass

    def wait_dead(self, grace: float) -> bool:
        """SIGTERM grace, then SIGKILL — the kubelet's contract."""
        try:
            self.proc.wait(timeout=grace)
            return True
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
            return False

    def stop(self):
        if self.alive():
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def log_tail(self, n: int = 1200) -> str:
        try:
            return open(self._log_path).read()[-n:]
        except OSError:
            return ""


def run_preempt_storm(
    steps: int = 60,
    waves: int = 2,
    step_sleep: float = 0.08,
    save_every: int = 5,
    term_grace: float = 10.0,
    relaunch_delay: float = 12.0,
    timeout: float = 300.0,
    brain: bool = True,
) -> dict:
    """SIGTERM-with-grace preemption waves against pod 1 of a 2-pod
    job.  The dying pod drains + fences and the survivor re-meshes
    within a monitor interval — training continues on the shrunken
    world THROUGH the ``relaunch_delay`` outage (the realistic gap
    before the scheduler re-creates the pod).  Per-wave MTTR =
    SIGTERM → first step BEYOND the pre-death watermark, logged AFTER
    the pod actually died.

    ``brain``: the autonomy comparison is the Brain vs the static
    seed auto-scaler — ``DLROVER_TPU_BRAIN`` rides both the master
    and the job."""
    workdir = tempfile.mkdtemp(prefix="dlrover_preempt_")
    progress = os.path.join(workdir, "progress.jsonl")
    supervisor = MasterSupervisor(
        workdir, fault_plan="", job_name="preempt",
        extra_env={"DLROVER_TPU_BRAIN": "1" if brain else "0"},
    )
    if not supervisor.start():
        raise RuntimeError(
            "master never came up: " + supervisor.log_tail()
        )
    env = dict(
        os.environ,
        GOODPUT_TARGET_STEPS=str(steps),
        GOODPUT_STEP_SLEEP=str(step_sleep),
        GOODPUT_SAVE_EVERY=str(save_every),
        GOODPUT_PROGRESS_FILE=progress,
        GOODPUT_CKPT_DIR=os.path.join(workdir, "ckpt"),
        DLROVER_TPU_SOCKET_DIR=os.path.join(workdir, "socks"),
        DLROVER_TPU_EVENTS_FILE=os.path.join(
            workdir, "events.jsonl"
        ),
        DLROVER_TPU_BRAIN="1" if brain else "0",
        DLROVER_TPU_PREEMPT_DRAIN_GRACE_S="2.0",
        DLROVER_TPU_EMERGENCY_COMMIT_TIMEOUT_S="3.0",
        DLROVER_TPU_FENCE_TTL_S="8.0",
        JAX_PLATFORMS="cpu",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=REPO,
        XLA_FLAGS="",
    )
    pods = [
        NodePod(workdir, 0, supervisor.addr, env),
        NodePod(workdir, 1, supervisor.addr, env),
    ]
    t_start = time.perf_counter()
    t_start_wall = time.time()
    for pod in pods:
        pod.launch()

    # +2 keeps the marks OFF the save_every cadence: a wave landing
    # exactly on a periodic snapshot step would hide the replay cost
    # the graceful drain exists to remove
    wave_marks = [
        max(3, int(steps * (i + 1) / (waves + 1)) + 2)
        for i in range(waves)
    ]
    recoveries = []  # per wave: seconds from SIGTERM to NEW progress
    replayed = []  # per wave: steps re-run after the restore
    wave = None  # in-flight wave state
    deadline = time.time() + timeout
    try:
        while any(p.alive() for p in pods):
            if time.time() > deadline:
                raise RuntimeError(
                    "preempt storm timed out; pod0 tail:\n"
                    + pods[0].log_tail() + "\npod1 tail:\n"
                    + pods[1].log_tail()
                )
            lines = _read_progress(progress)
            max_step = max((e["step"] for e in lines), default=0)
            now = time.perf_counter()
            if (
                wave is None
                and wave_marks
                and max_step >= wave_marks[0]
                and pods[1].alive()
            ):
                wave_marks.pop(0)
                pods[1].sigterm()
                wave = {
                    "t0": now,
                    "t0_wall": time.time(),
                    "before": max_step,
                    "relaunch_at": None,
                    "recovered": False,
                }
            if wave is not None:
                if wave["relaunch_at"] is None and (
                    not pods[1].alive()
                    or now - wave["t0"] > term_grace
                ):
                    pods[1].wait_dead(grace=1.0)
                    wave["relaunch_at"] = now + relaunch_delay
                    # the interruption point: the watermark when the
                    # pod actually died (the drained pod keeps
                    # stepping through its grace — those steps are
                    # training, not recovery)
                    wave["t_dead_wall"] = time.time()
                    wave["before"] = max(
                        (e["step"] for e in lines), default=0
                    )
                if (
                    wave["relaunch_at"] is not None
                    and wave["relaunch_at"] > 0
                    and now >= wave["relaunch_at"]
                ):
                    if max_step < steps:
                        pods[1].launch()  # the re-created pod
                    wave["relaunch_at"] = -1.0
                if not wave["recovered"] and (
                    wave.get("t_dead_wall") is not None
                ):
                    post = [
                        e["step"]
                        for e in lines
                        if e["t"] > wave["t_dead_wall"]
                    ]
                    if post and max(post) > wave["before"]:
                        # the job stepped PAST the preemption point:
                        # recovery complete; replay depth = how far
                        # below the preemption step the resumed
                        # counter dipped
                        wave["recovered"] = True
                        recoveries.append(
                            round(now - wave["t0"], 3)
                        )
                        replayed.append(
                            max(wave["before"] - min(post), 0)
                        )
                if wave["recovered"] and wave["relaunch_at"] == -1.0:
                    wave = None
            time.sleep(0.05)
    finally:
        for pod in pods:
            pod.stop()
        supervisor.stop()
    wall_s = time.perf_counter() - t_start

    lines = _read_progress(progress)
    final_step = max((e["step"] for e in lines), default=0)
    rank0 = sorted(
        (e for e in lines if e["rank"] == 0),
        key=lambda e: e["step"],
    )
    deltas = sorted(
        b["t"] - a["t"]
        for a, b in zip(rank0, rank0[1:])
        if b["step"] == a["step"] + 1 and b["t"] > a["t"]
    )
    steady_s = deltas[len(deltas) // 2] if deltas else step_sleep
    # goodput measures TRAINING: launch → the target step landing.
    # The re-created pod's post-completion rejoin (it comes back,
    # restores, finds the job already done, exits) is scheduler
    # housekeeping, not training wall time.
    done_t = [e["t"] for e in lines if e["step"] >= steps]
    train_wall_s = (
        min(done_t) - t_start_wall if done_t else wall_s
    )
    goodput = (
        min(1.0, final_step * steady_s / train_wall_s)
        if train_wall_s
        else 0.0
    )
    return {
        "plan": "preempt-storm",
        "brain": brain,
        "steps": final_step,
        "target_steps": steps,
        "save_every": save_every,
        "waves": waves - len(wave_marks),
        "wall_s": round(wall_s, 2),
        "train_wall_s": round(train_wall_s, 2),
        "goodput": round(goodput, 4),
        "steady_step_s": round(steady_s, 4),
        "recovery_s": recoveries,
        "recovery_mean_s": round(
            sum(recoveries) / len(recoveries), 3
        ) if recoveries else None,
        "steps_replayed": replayed,
        "job_survived": final_step >= steps,
        "workdir": workdir,
    }


def run_slow_node(
    steps: int = 60,
    pods: int = 3,
    slow_node: int = 2,
    slow_factor: float = 5.0,
    slow_after: int = 0,
    step_sleep: float = 0.25,
    save_every: int = 5,
    brain: bool = True,
    timeout: float = 300.0,
    seed: int = 7,
) -> dict:
    """Sleep-fault one pod of ``pods`` mid-run: from step
    ``slow_after`` (default ~1/3 of the target) its simulated device
    work takes ``slow_factor`` times longer, and the per-step
    collective drags the WHOLE job down to its speed.

    With ``brain=True`` the closed loop must rescue the job: the
    observatory's step-time derivations brand the node a straggler,
    the Brain issues one hysteresis-guarded ``drain_replace``, the
    node drains (fresh snapshot, flush, fence) and exits with the
    preemption code, and the survivors re-mesh + reshard-restore and
    finish at full speed — the pool has no spare capacity, so the
    shrunken world is the planned outcome.  ``brain=False`` is the
    static job: nobody acts, every remaining step pays the slow tax.

    Goodput uses the HEALTHY steady step time (median pre-onset
    inter-step delta — identical across legs) so a leg that merely
    runs slowly cannot look "efficient at the degraded speed"."""
    workdir = tempfile.mkdtemp(prefix="dlrover_slownode_")
    progress = os.path.join(workdir, "progress.jsonl")
    slow_after = slow_after or max(int(steps * 0.25), 4)
    brain_flag = "1" if brain else "0"
    supervisor = MasterSupervisor(
        workdir, fault_plan="", job_name="slownode",
        extra_env={
            "DLROVER_TPU_BRAIN": brain_flag,
            # chaos timescales: decide every 0.5s, cool down 5s,
            # 2-cycle sustain against CPU-CI step-time noise; factor
            # 5 degradation clears ratio 2.0 with >2x margin
            "DLROVER_TPU_BRAIN_INTERVAL_S": "0.5",
            "DLROVER_TPU_BRAIN_COOLDOWN_S": "5",
            "DLROVER_TPU_BRAIN_SUSTAIN": "2",
            "DLROVER_TPU_STRAGGLER_RATIO": "2.0",
        },
    )
    if not supervisor.start():
        raise RuntimeError(
            "master never came up: " + supervisor.log_tail()
        )
    env = dict(
        os.environ,
        GOODPUT_TARGET_STEPS=str(steps),
        GOODPUT_STEP_SLEEP=str(step_sleep),
        GOODPUT_SAVE_EVERY=str(save_every),
        GOODPUT_PROGRESS_FILE=progress,
        GOODPUT_CKPT_DIR=os.path.join(workdir, "ckpt"),
        DLROVER_TPU_BRAIN=brain_flag,
        DLROVER_TPU_TIMELINE_REPORT_S="1.0",
        DLROVER_TPU_PREEMPT_DRAIN_GRACE_S="2.0",
        DLROVER_TPU_EMERGENCY_COMMIT_TIMEOUT_S="3.0",
        DLROVER_TPU_FENCE_TTL_S="8.0",
        JAX_PLATFORMS="cpu",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=REPO,
        XLA_FLAGS="",
    )
    del seed  # the fault is deterministic (step-count onset)
    pod_list = []
    for rank in range(pods):
        pod_env = dict(
            env,
            DLROVER_TPU_EVENTS_FILE=os.path.join(
                workdir, f"events_pod{rank}.jsonl"
            ),
        )
        if rank == slow_node:
            pod_env["GOODPUT_SLOW_AFTER"] = str(slow_after)
            pod_env["GOODPUT_SLOW_FACTOR"] = str(slow_factor)
        pod_list.append(
            NodePod(
                workdir, rank, supervisor.addr, pod_env,
                max_nodes=pods,
            )
        )
    t_start_wall = time.time()
    t_start = time.perf_counter()
    for pod in pod_list:
        pod.launch()

    slow_dead_wall = None
    slow_rc = None
    deadline = time.time() + timeout
    try:
        while any(p.alive() for p in pod_list):
            if time.time() > deadline:
                raise RuntimeError(
                    "slow-node run timed out; pod0 tail:\n"
                    + pod_list[0].log_tail()
                    + f"\npod{slow_node} tail:\n"
                    + pod_list[slow_node].log_tail()
                )
            if not supervisor.alive():
                raise RuntimeError(
                    "master died during slow-node run: "
                    + supervisor.log_tail()
                )
            if (
                slow_dead_wall is None
                and not pod_list[slow_node].alive()
            ):
                slow_dead_wall = time.time()
                slow_rc = pod_list[slow_node].proc.returncode
            time.sleep(0.05)
    finally:
        for pod in pod_list:
            pod.stop()
        supervisor.stop()
    wall_s = time.perf_counter() - t_start

    lines = _read_progress(progress)
    final_step = max((e["step"] for e in lines), default=0)
    rank0 = sorted(
        (e for e in lines if e["rank"] == 0),
        key=lambda e: e["step"],
    )
    healthy_deltas = sorted(
        b["t"] - a["t"]
        for a, b in zip(rank0, rank0[1:])
        if b["step"] == a["step"] + 1
        and b["t"] > a["t"]
        and b["step"] < slow_after
    )
    steady_s = (
        healthy_deltas[len(healthy_deltas) // 2]
        if healthy_deltas
        else step_sleep
    )
    onset = [e["t"] for e in lines if e["step"] >= slow_after]
    onset_wall = min(onset) if onset else None
    done_t = [e["t"] for e in lines if e["step"] >= steps]
    train_wall_s = (
        min(done_t) - t_start_wall if done_t else wall_s
    )
    goodput = (
        min(1.0, final_step * steady_s / train_wall_s)
        if train_wall_s
        else 0.0
    )
    from dlrover_tpu.agent.training import AgentExitCode

    drained = slow_rc == AgentExitCode.NODE_PREEMPTED
    return {
        "plan": "slow-node",
        "brain": brain,
        "steps": final_step,
        "target_steps": steps,
        "slow_node": slow_node,
        "slow_after": slow_after,
        "slow_factor": slow_factor,
        "wall_s": round(wall_s, 2),
        "train_wall_s": round(train_wall_s, 2),
        "goodput": round(goodput, 4),
        "steady_step_s": round(steady_s, 4),
        "slow_node_drained": drained,
        "slow_node_rc": slow_rc,
        "time_to_drain_s": (
            round(slow_dead_wall - onset_wall, 2)
            if drained and onset_wall and slow_dead_wall
            else None
        ),
        "job_survived": final_step >= steps,
        "workdir": workdir,
    }


def run_plan(
    plan: str = "master-kill-storm",
    steps: int = 60,
    kills: int = 2,
    seed: int = 7,
    step_sleep: float = 0.08,
    timeout: float = 300.0,
    nproc: int = 2,
) -> dict:
    """One chaos run; returns the metrics dict.  Raises RuntimeError
    on harness failure, a job that did not survive its plan
    included."""
    if plan not in PLANS:
        raise ValueError(f"unknown plan {plan!r} (have: {PLANS})")
    workdir = tempfile.mkdtemp(prefix="dlrover_chaos_")
    progress = os.path.join(workdir, "progress.jsonl")
    fault_plan = build_fault_plan(plan, seed)
    master_plan = fault_plan if plan.startswith("master-") else ""
    agent_plan = fault_plan if plan == "rpc-chaos" else ""

    supervisor = MasterSupervisor(workdir, fault_plan=master_plan)
    if not supervisor.start():
        raise RuntimeError(
            "master never came up: " + supervisor.log_tail()
        )

    env = dict(
        os.environ,
        GOODPUT_TARGET_STEPS=str(steps),
        GOODPUT_STEP_SLEEP=str(step_sleep),
        GOODPUT_PROGRESS_FILE=progress,
        GOODPUT_CKPT_DIR=os.path.join(workdir, "ckpt"),
        DLROVER_TPU_SOCKET_DIR=os.path.join(workdir, "socks"),
        DLROVER_TPU_EVENTS_FILE=os.path.join(
            workdir, "events.jsonl"
        ),
        JAX_PLATFORMS="cpu",
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        PYTHONPATH=REPO,
        XLA_FLAGS="",
    )
    if agent_plan:
        env["DLROVER_TPU_FAULT_PLAN"] = agent_plan
        env["DLROVER_TPU_FAULT_ROLE"] = "agent"
    else:
        env.pop("DLROVER_TPU_FAULT_PLAN", None)
    log_path = os.path.join(workdir, "launcher.log")
    t_start = time.perf_counter()
    with open(log_path, "w") as log:
        launcher = subprocess.Popen(  # noqa: S603
            [
                sys.executable, "-m", "dlrover_tpu.run",
                "--nnodes=1", f"--nproc_per_node={nproc}",
                f"--master_addr={supervisor.addr}",
                "--monitor_interval=0.3",
                "--stop_timeout=2",
                "--max_restarts=4",
                "--failure_stop_timeout=0.5",
                os.path.join(REPO, "scripts", "goodput_train.py"),
            ],
            stdout=log, stderr=subprocess.STDOUT, env=env,
            cwd=workdir,
        )

    # timer-driven kill thresholds, evenly spaced inside the run
    storm = []
    if plan == "master-kill-storm":
        storm = [
            max(1, int(steps * (i + 1) / (kills + 1)))
            for i in range(kills)
        ]
    agent_kill_at = max(2, steps // 3) if plan == "agent-kill" else None

    master_kills = 0
    deadline = time.time() + timeout
    job_survived = True
    try:
        while launcher.poll() is None:
            if time.time() > deadline:
                raise RuntimeError(
                    "chaos run timed out; launcher log tail:\n"
                    + open(log_path).read()[-800:]
                )
            lines = _read_progress(progress)
            max_step = (
                max(e["step"] for e in lines) if lines else 0
            )
            if storm and max_step >= storm[0] and supervisor.alive():
                storm.pop(0)
                supervisor.kill()
                master_kills += 1
            if (
                agent_kill_at is not None
                and max_step >= agent_kill_at
            ):
                agent_kill_at = None
                rank1 = [e for e in lines if e["rank"] == 1]
                victim = (rank1 or lines)[-1]["pid"]
                try:
                    os.kill(victim, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            if not supervisor.alive():
                # plan-driven suicides are kills the timer didn't do
                if not storm and plan in _PHASE_FOR_PLAN and (
                    master_kills == 0
                ):
                    master_kills += 1
                if not supervisor.restart():
                    raise RuntimeError(
                        "restarted master never became ready: "
                        + supervisor.log_tail()
                    )
            time.sleep(0.05)
    finally:
        supervisor.stop()
        if launcher.poll() is None:
            launcher.kill()
            launcher.wait()
    wall_s = time.perf_counter() - t_start

    lines = _read_progress(progress)
    final_step = max((e["step"] for e in lines), default=0)
    if launcher.returncode != 0 or final_step < steps:
        job_survived = False
    if job_survived is False and plan != "none":
        # the job MUST survive the storm — this is the acceptance
        # bar, so a dead job is a harness-level failure
        raise RuntimeError(
            f"job did not survive plan {plan!r} "
            f"(rc={launcher.returncode}, step {final_step}/{steps}); "
            "launcher log tail:\n" + open(log_path).read()[-1200:]
        )

    # goodput: final step x steady step time / wall (bench_goodput's
    # definition); steady time = median inter-step delta on rank 0
    rank0 = sorted(
        (e for e in lines if e["rank"] == 0),
        key=lambda e: e["step"],
    )
    deltas = sorted(
        b["t"] - a["t"]
        for a, b in zip(rank0, rank0[1:])
        if b["step"] == a["step"] + 1 and b["t"] > a["t"]
    )
    steady_s = deltas[len(deltas) // 2] if deltas else step_sleep
    # the stall is the longest TIME gap between ANY two consecutive
    # progress entries — a restart replays from the checkpoint, so
    # the step counter repeats/regresses across exactly the gap we
    # must not exclude (the steady median above keeps the
    # step-continuity filter: it wants true inter-step deltas)
    rank0_by_t = sorted(
        (e for e in lines if e["rank"] == 0), key=lambda e: e["t"]
    )
    stall_max_s = max(
        (
            b["t"] - a["t"]
            for a, b in zip(rank0_by_t, rank0_by_t[1:])
        ),
        default=0.0,
    )
    goodput = (
        min(1.0, final_step * steady_s / wall_s) if wall_s else 0.0
    )
    return {
        "plan": plan,
        "seed": seed,
        "steps": final_step,
        "target_steps": steps,
        "wall_s": round(wall_s, 2),
        "goodput": round(goodput, 4),
        "steady_step_s": round(steady_s, 4),
        "stall_max_s": round(stall_max_s, 3),
        "master_kills": master_kills,
        "master_restarts": supervisor.incarnations - 1,
        "mttr_s": supervisor.mttr_s,
        "mttr_mean_s": round(
            sum(supervisor.mttr_s) / len(supervisor.mttr_s), 3
        ) if supervisor.mttr_s else None,
        "mttr_max_s": max(supervisor.mttr_s, default=None),
        "job_survived": job_survived,
        "launcher_rc": launcher.returncode,
        "workdir": workdir,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="chaos-injection harness"
    )
    parser.add_argument("--plan", default="master-kill-storm",
                        choices=PLANS)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--kills", type=int, default=2)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--step_sleep", type=float, default=0.08)
    parser.add_argument("--timeout", type=float, default=300.0)
    parser.add_argument("--waves", type=int, default=2,
                        help="preempt-storm: SIGTERM waves")
    parser.add_argument("--save_every", type=int, default=5,
                        help="preempt-storm: shm snapshot cadence "
                        "(steps) — the periodic-RPO the graceful "
                        "drain beats")
    parser.add_argument("--brain-only", action="store_true",
                        help="slow-node / preempt-storm: run only "
                        "the Brain-on leg")
    parser.add_argument("--static-only", action="store_true",
                        help="slow-node / preempt-storm: run only "
                        "the Brain-off leg")
    parser.add_argument("--slow_factor", type=float, default=5.0,
                        help="slow-node: sleep-fault multiplier")
    parser.add_argument("--pods", type=int, default=3,
                        help="slow-node: pod count (the straggler "
                        "median needs >= 3)")
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    budget = BenchBudget()
    steps = args.steps
    if budget.tight(120):
        steps = min(steps, 30)
    if budget.tight(45):
        # slow-node keeps a higher floor: the Brain leg pays a fixed
        # detect+re-mesh cost, and the comparison needs enough
        # post-onset steps for the steady-state win to dominate it
        steps = min(steps, 20 if args.plan == "slow-node" else 12)

    payload = {
        "metric": "chaos_mttr_mean_s",
        "value": None,
        "unit": "s",
        "vs_baseline": None,
        "extras": {"bench_budget_s": budget.total},
    }

    if args.plan == "slow-node":
        payload["metric"] = "slow_node_goodput_gain"
        legs = (
            [True] if args.brain_only
            else [False] if args.static_only
            else [True, False]
        )
        timeout = budget.cap_timeout(args.timeout)
        # the slow leg must dominate scheduler noise: steps slower
        # than teardown, degradation >> the straggler ratio
        storm_sleep = max(args.step_sleep, 0.25)
        try:
            for brain in legs:
                leg = run_slow_node(
                    steps=steps,
                    pods=args.pods,
                    slow_node=args.pods - 1,
                    slow_factor=args.slow_factor,
                    step_sleep=storm_sleep,
                    brain=brain,
                    timeout=timeout,
                    seed=args.seed,
                )
                payload["extras"]["brain" if brain else "static"] = leg
                if args.out:
                    _flush(args.out, payload)
        except RuntimeError as e:
            payload["extras"]["error"] = str(e)
            if args.out:
                _flush(args.out, payload)
            print(json.dumps(payload, indent=2))
            return 1
        on = payload["extras"].get("brain")
        off = payload["extras"].get("static")
        if on and off:
            payload["value"] = round(
                on["goodput"] - off["goodput"], 4
            )
        if args.out:
            _flush(args.out, payload)
        print(json.dumps(payload, indent=2))
        survived = all(
            payload["extras"].get(k, {}).get("job_survived", False)
            for k in ("brain", "static")
            if k in payload["extras"]
        )
        return 0 if survived else 1

    if args.plan == "preempt-storm":
        payload["metric"] = "preempt_recovery_mean_s"
        legs = (
            [True] if args.brain_only
            else [False] if args.static_only
            else [True, False]
        )
        timeout = budget.cap_timeout(args.timeout)
        # a storm needs steps SLOWER than pod teardown, or the job
        # races to completion between the SIGTERM and the first
        # missed collective and the wave measures nothing
        storm_sleep = max(args.step_sleep, 0.25)
        try:
            for brain in legs:
                leg = run_preempt_storm(
                    steps=steps,
                    waves=args.waves,
                    step_sleep=storm_sleep,
                    save_every=args.save_every,
                    timeout=timeout,
                    brain=brain,
                )
                payload["extras"]["brain" if brain else "static"] = leg
                if args.out:
                    _flush(args.out, payload)
        except RuntimeError as e:
            payload["extras"]["error"] = str(e)
            if args.out:
                _flush(args.out, payload)
            print(json.dumps(payload, indent=2))
            return 1
        re_leg = payload["extras"].get("brain")
        rs_leg = payload["extras"].get("static")
        if re_leg:
            payload["value"] = re_leg["recovery_mean_s"]
        if re_leg and rs_leg:
            payload["extras"]["goodput_gain"] = round(
                re_leg["goodput"] - rs_leg["goodput"], 4
            )
            payload["extras"]["mttr_ratio"] = round(
                (re_leg["recovery_mean_s"] or 0.0)
                / max(rs_leg["recovery_mean_s"] or 1e-9, 1e-9),
                3,
            )
        if args.out:
            _flush(args.out, payload)
        print(json.dumps(payload, indent=2))
        survived = all(
            payload["extras"].get(k, {}).get("job_survived", False)
            for k in ("brain", "static")
            if k in payload["extras"]
        )
        return 0 if survived else 1

    try:
        result = run_plan(
            plan=args.plan,
            steps=steps,
            kills=args.kills,
            seed=args.seed,
            step_sleep=args.step_sleep,
            timeout=budget.cap_timeout(args.timeout),
        )
    except RuntimeError as e:
        payload["extras"]["error"] = str(e)
        if args.out:
            _flush(args.out, payload)
        print(json.dumps(payload, indent=2))
        return 1
    payload["value"] = result.get("mttr_mean_s")
    payload["extras"]["chaos"] = result
    if args.out:
        _flush(args.out, payload)
    print(json.dumps(payload, indent=2))
    return 0 if result["job_survived"] else 1


if __name__ == "__main__":
    sys.exit(main())
