"""Runners of the ``train`` and ``resume`` traffic kinds: the elastic
launcher on the benchmark's worker script, watched from outside.

The launcher call, the wait for a whole shm snapshot followed by a
SIGKILL of the worker, and the checks of losses, restore step and cache
hits are copied from ``chip_smoke.py``'s ``_launch_example`` /
``phase_train`` / ``_check_losses`` / ``_worker_reports`` (PR 21, proven
on the chip there); the smoke stays the program's, this copy is the
yardstick's.
"""

import hashlib
import math
import os
import random
import signal
import sys
import time

import metrics as M
from harness import (
    BENCH, CellFailed, attach_trace, require, shm_names, tail,
)

POLL_S = 0.1


def launch(cell, seed, trace, expect_platform, sandbox, env, stop_after_s):
    """Start ``python -m dlrover_tpu.run <launcher args>
    benchmarks/worker_train.py <worker args>``; returns (Popen, paths)."""
    t = cell["traffic"]
    run_dir = sandbox.run_dir
    paths = dict(
        events=os.path.join(run_dir, "events.jsonl"),
        worker=os.path.join(run_dir, "worker.jsonl"),
        # the program names the job's shm segments after a hash of this
        # directory: with the runner's process id in it, the names are
        # this run's alone, and the same across the job's restarts
        ckpt=os.path.join(run_dir, f"ckpt-{os.getpid()}"),
        stop=os.path.join(run_dir, "stop"),
        trace=os.path.join(run_dir, "trace"),
        log=os.path.join(run_dir, "launcher.log"),
    )
    mesh = t.get("mesh", {})
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run",
        *t["launcher"],
        f"--events_file={paths['events']}",
        os.path.join(BENCH, "worker_train.py"),
        "--config", cell["config_path"],
        "--run_dir", run_dir,
        "--ckpt_dir", paths["ckpt"],
        "--expect_platform", expect_platform,
        "--batch", str(t["batch"]),
        "--seq", str(t["seq"]),
        "--lr", str(t["lr"]),
        "--seed", str(seed),
        "--save_memory_interval", str(t["save_memory_interval"]),
        "--devices", str(mesh.get("devices", cell["chips"])),
        "--fsdp", str(mesh.get("fsdp", 0)),
        "--tensor", str(mesh.get("tensor", 0)),
        "--stop_after_s", str(stop_after_s),
        "--reference", str(int(t.get("reference", True))),
    ]
    if trace:
        tr = t["trace"]
        cmd += [
            "--trace_dir", paths["trace"],
            "--trace_after_step", str(tr["after_step"]),
            "--trace_phase", str(tr["phase"]),
            "--trace_steps", str(tr["steps"]),
        ]
    paths["shm_token"] = "_d" + hashlib.sha1(
        os.path.abspath(paths["ckpt"]).encode()
    ).hexdigest()[:8] + "_"  # trainer/checkpoint/engine.py's rule
    sandbox.own_shm(paths["shm_token"])
    return sandbox.popen(cmd, env, "launcher.log"), paths


class Watch:
    """The run as seen from outside: the worker's rows and the program's
    timeline, re-read on every poll."""

    def __init__(self, proc, paths, deadline):
        self.proc, self.paths, self.deadline = proc, paths, deadline

    def rows(self, kind=None, inc=None):
        return [
            r for r in M.read_jsonl(self.paths["worker"])
            if (kind is None or r["kind"] == kind)
            and (inc is None or r["inc"] == inc)
        ]

    def spans(self):
        return M.read_spans(self.paths["events"])

    def until(self, what, cond):
        """Poll until ``cond()`` is truthy; the launcher exiting, a fatal
        row or the deadline first is a failed run."""
        while True:
            got = cond()
            if got:
                return got
            fatal = self.rows("fatal")
            require(not fatal, f"worker refused to run: {fatal[:1]}")
            if self.proc.poll() is not None:
                raise CellFailed(
                    f"launcher exited {self.proc.returncode} before "
                    f"{what}:\n{tail(self.paths['log'])}"
                )
            require(
                time.time() < self.deadline, f"timed out waiting for {what}"
            )
            time.sleep(POLL_S)

    def saves(self):
        """Whole shm snapshots of the worker so far (the agent's own
        ``checkpoint_save`` is its flush to storage)."""
        pids = {r["pid"] for r in self.rows("begin")}
        return [
            s for s in M.named(self.spans(), "checkpoint_save")
            if s["pid"] in pids
        ]

    def finish(self, timeout=300):
        """Drop the stop file, wait for the launcher's exit 0."""
        with open(self.paths["stop"], "w"):
            pass
        try:
            rc = self.proc.wait(timeout=timeout)
        except Exception:
            raise CellFailed(
                f"launcher still running {timeout} s after the stop "
                f"file:\n{tail(self.paths['log'])}"
            )
        require(
            rc == 0, f"launcher exited {rc}:\n{tail(self.paths['log'])}"
        )


def start(cell, seed, seconds, trace, expect_platform, sandbox, env):
    """Launch the job and wait out its set-up, which ends — and the
    window opens — when the traffic's ``setup_snapshots``-th snapshot is
    whole in shm (the first ones drain into cold pages for seconds).
    Returns (Watch, time the window opened)."""
    t = cell["traffic"]
    proc, paths = launch(
        cell, seed, trace, expect_platform, sandbox, env,
        stop_after_s=t["setup_timeout_s"] + seconds + 120,
    )
    watch = Watch(proc, paths, time.time() + t["setup_timeout_s"])
    n = t["setup_snapshots"]
    saves = watch.until(
        f"shm snapshot {n}",
        lambda: (lambda s: s if len(s) >= n else None)(watch.saves()),
    )
    require(
        shm_names(paths["shm_token"]),
        "a snapshot is whole in shm under no name the runner expects "
        f"(*{paths['shm_token']}*): the run could not remove it",
    )
    t_open = saves[n - 1]["end"]
    watch.deadline = t_open + seconds + 120
    return watch, t_open


def check_losses(rows, vocab_size, band):
    """Every logged loss finite and within ``band`` of ln(vocab): the
    tokens are uniformly random, so there is nothing to learn.  Returns
    the number of rows that fail."""
    bad = 0
    for r in rows:
        ok = math.isfinite(r["loss"]) and (
            abs(r["loss"] - math.log(vocab_size)) < band
        )
        bad += not ok
    return bad


def check_reference(watch, steps, traffic, notes):
    """Before any update, on the seeded weights and the first batch, the
    worker scored every token with the program's forward and with the
    plain float32 reference: the largest difference of one token's
    logprob is held to ``logprob_tol`` (a mean hides a lower precision:
    ``tolerance_probe.py``).  The first step's logged loss — the step
    program's own, fused loss path — is held to the reference's mean."""
    ref = watch.rows("reference")
    first = [r for r in steps if r["step"] == 1 and r["inc"] == 0]
    if not ref or not first:
        notes.append("no reference row or no step 1")
        return False
    diff = abs(ref[0]["loss"] - first[0]["loss"])
    notes.append(
        f"program vs float32 reference on the first batch: max |diff| of "
        f"a token's logprob {ref[0]['max_token_diff']:.4f} (tolerance "
        f"{traffic['logprob_tol']}); first logged loss "
        f"{first[0]['loss']:.5f} vs {ref[0]['loss']:.5f} (diff {diff:.5f},"
        f" tolerance {traffic['reference_tol']})"
    )
    return (
        ref[0]["max_token_diff"] <= traffic["logprob_tol"]
        and diff <= traffic["reference_tol"]
    )


def collect(watch, cell, seconds, t_open, trace, notes):
    """What both kinds report: rows, spans, window, device, memory, the
    reduced trace."""
    t = cell["traffic"]
    steps = watch.rows("step")
    devices = {r["inc"]: r for r in watch.rows("device")}
    ends = watch.rows("end")
    ctx = {
        "rows": steps,
        "spans": watch.spans(),
        "window": (t_open, t_open + seconds),
        "device_report": devices.get(0),
        "device_rows": devices,
        "memory_peak_bytes": max(
            (r["memory_peak_bytes"] for r in ends
             if r.get("memory_peak_bytes")), default=None,
        ),
        "tokens_per_step": t["batch"] * t["seq"],
        "notes": notes,
        "end_to_end": {},
    }
    if trace:
        attach_trace(ctx, watch.paths["trace"])
    return ctx


def run_train(cell, seed, seconds, trace, expect_platform, sandbox, env):
    """Steady steps with flash-checkpoint snapshots to shm."""
    t = cell["traffic"]
    watch, t_open = start(
        cell, seed, seconds, trace, expect_platform, sandbox, env
    )
    watch.until("the end of the window",
                lambda: time.time() >= t_open + seconds + 0.5)
    if trace:
        watch.until("the profiler window", lambda: watch.rows("trace"))
    watch.finish()

    notes = []
    ctx = collect(watch, cell, seconds, t_open, trace, notes)
    snap = t["save_memory_interval"]
    inside = M.in_window(ctx["rows"], ctx["window"])
    ctx["end_to_end"]["train_tokens_per_s"] = M.whole_cycle_tokens_per_s(
        ctx["rows"], ctx["window"], snap, ctx["tokens_per_step"]
    )
    ctx["why_missing"] = (
        f"the window holds {len(inside)} steps and fewer than two "
        f"snapshot-bearing ones (every {snap})"
    )
    bad = check_losses(
        ctx["rows"], cell["config"]["vocab_size"], t["loss_band"]
    )
    ref_ok = (
        check_reference(watch, ctx["rows"], t, notes)
        if t.get("reference", True) else True
    )
    incs = {r["inc"] for r in ctx["rows"]}
    ctx.update(
        attempted=len(inside),
        failed=check_losses(
            inside, cell["config"]["vocab_size"], t["loss_band"]
        ),
        correct=bad == 0 and ref_ok and incs == {0} and len(inside) > 0,
    )
    return ctx


def run_resume(cell, seed, seconds, trace, expect_platform, sandbox, env):
    """The train job, SIGKILLed a seed-drawn number of steps into the
    window; the agent restarts the worker, which restores from shm."""
    t = cell["traffic"]
    snap = t["save_memory_interval"]
    watch, t_open = start(
        cell, seed, seconds, trace, expect_platform, sandbox, env
    )
    lo, hi = t["kill_after_steps"]
    kill_after = random.Random(seed).randint(lo, hi)
    at_open = max(r["step"] for r in watch.rows("step", inc=0))
    watch.until(
        f"step {at_open + kill_after}",
        lambda: watch.rows("step", inc=0)[-1]["step"]
        >= at_open + kill_after,
    )
    victim = watch.rows("begin", inc=0)[0]["pid"]
    t_kill = time.time()
    os.kill(victim, signal.SIGKILL)
    # not until(): a resume that has not completed when the window ends
    # is a failed attempt, not a run that cannot be reported
    while (
        time.time() < t_open + seconds + 0.5
        and watch.proc.poll() is None
    ):
        time.sleep(POLL_S)
    resumed = bool(watch.rows("step", inc=1))
    if trace and resumed:
        watch.until("the profiler window",
                    lambda: watch.rows("trace", inc=1))
    watch.finish()

    notes = [f"SIGKILL {kill_after} steps into the window"]
    ctx = collect(watch, cell, seconds, t_open, trace, notes)
    agent = M.named(ctx["spans"], "restart")
    parts = M.resume_partition(
        t_kill, ctx["spans"], ctx["rows"],
        agent_pid=agent[0]["pid"] if agent else -1,
    )
    ctx["resume"] = parts
    ctx["t_kill"] = t_kill
    in_time = (
        "resume_s" in parts and t_kill + parts["resume_s"] <= t_open + seconds
    )
    ctx["end_to_end"]["resume_s"] = parts.get("resume_s")
    ctx["why_missing"] = "incarnation 1 never completed a step"
    restores = [
        int(s["labels"]["step"])
        for s in M.named(ctx["spans"], "checkpoint_restore", inc=1)
    ]
    cold, warm = ctx["device_rows"].get(0), ctx["device_rows"].get(1)
    checks = {
        "one restore at a snapshot step": (
            len(restores) == 1 and restores[0] >= snap
            and restores[0] % snap == 0
        ),
        "first step of incarnation 1 is restored + 1": (
            warm is not None and restores
            and int(warm["step"]) == restores[0] + 1
        ),
        "incarnation 1 compiled from the cache": (
            cold is not None and warm is not None
            and warm["cache_hits"] > 0
            and warm["cache_hits"]
            >= cold["cache_hits"] + cold["cache_misses"]
        ),
        "losses inside the band": check_losses(
            ctx["rows"], cell["config"]["vocab_size"], t["loss_band"]
        ) == 0,
        "resumed inside the window": in_time,
    }
    notes.extend(f"FAILED: {k}" for k, ok in checks.items() if not ok)
    ctx.update(
        attempted=1,
        failed=0 if in_time else 1,
        correct=all(checks.values()),
    )
    return ctx
