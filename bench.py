"""Headline bench: flash-checkpoint blocking save time.

Measures the wall-clock a training step is blocked while snapshotting a
GPT-2-xl-class (~1.5B param) train state from device HBM into host
shared memory (the async agent persists it off the hot path) — the
reference's headline Flash Checkpoint number: Megatron-LM GPT save
blocked 151-242 s synchronously, 0.5 s with DLRover Flash Checkpoint
(``docs/blogs/megatron_flash_checkpoint.md:157-160``, BASELINE.md).

The engine snapshots asynchronously: ``save_to_memory(blocking=False)``
launches every device->host transfer and drains into shm on a
background thread, so the training loop is blocked only for the
dispatch.  The bench mutates the state between saves so every snapshot
pays the REAL device->host transfer (a jax.Array caches its host copy;
saving an unchanged state would measure that cache, not the machine).

Prints ONE JSON line:
``{"metric": ..., "value": seconds, "unit": "s", "vs_baseline": ...}``
where ``vs_baseline`` = reference_0.5s / ours (>1 == less blocking than
the reference's published time).

On non-TPU backends (CI) the state is scaled down; the recorded run is
on one real chip.  ``d2h_gbps`` in extras records the measured
device->host link so drain numbers can be normalized.

Robustness (post BENCH_r05 rc=124): a ``DLROVER_TPU_BENCH_BUDGET_S``
wall-clock budget scales phases down instead of dying at the harness
timeout, and the payload-so-far is flushed to ``--out`` after every
phase — a kill can truncate the run but never lose it.  The parallel
data plane's same-host comparison lands in ``extras.drain_gbps`` vs
``extras.drain_serial_gbps`` (``DLROVER_TPU_CKPT_COPY_WORKERS=1``).
"""

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_BLOCKING_S = 0.5  # reference flash-ckpt save blocking time

BUDGET_ENV = "DLROVER_TPU_BENCH_BUDGET_S"


class BenchBudget:
    """Wall-clock budget for the whole bench run (``BUDGET_ENV``).

    BENCH_r05 died at the harness timeout (rc=124) and lost the ENTIRE
    run because results were only written at the end.  Two defenses:
    callers flush partial payloads after every phase (``flush_partial``)
    and consult the budget to scale down state sizes / snapshot counts
    or skip later phases instead of running into the hard kill."""

    def __init__(self):
        raw = os.getenv(BUDGET_ENV, "")
        try:
            self.total = float(raw) if raw else None
        except ValueError:
            self.total = None
        self._t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def remaining(self):
        """Seconds left, or None when no budget is configured."""
        if self.total is None:
            return None
        return max(self.total - self.elapsed(), 0.0)

    def tight(self, need_s: float) -> bool:
        """True when under budget pressure for a phase needing
        ``need_s`` (no budget configured == never tight)."""
        r = self.remaining()
        return r is not None and r < need_s

    def cap_timeout(self, default_s: float, reserve_s: float = 60.0):
        """Subprocess timeout capped so the parent keeps ``reserve_s``
        to flush results even if the child runs long."""
        r = self.remaining()
        if r is None:
            return default_s
        return max(min(default_s, r - reserve_s), 1.0)


def snapshot_plan(budget: "BenchBudget", on_tpu: bool):
    """(n_params, chunk_elems) for the drain-snapshot phase, scaled
    by the wall-clock budget on EVERY backend.

    BENCH_r05 hit rc=124 *after* the subprocess phases were budget-
    capped because this phase's 500 MB state was only scaled on TPU
    — in the throttled CI container (~0.1 GB/s memcpy) each
    snapshot/restore leg of the un-scaled CPU state ran 15-18 s, and
    the ~8 legs blew straight through the budget.  Budget pressure
    now shrinks the state on CPU too; the recorded ``state_gb`` keeps
    rounds comparable."""
    if on_tpu:
        # PINNED at 0.5 GB bf16 across rounds (VERDICT-r4 weak #5);
        # budget pressure overrides the pin — a scaled-down result
        # beats a lost one
        n_params = 250_000_000
        if budget.tight(600):
            n_params = 100_000_000
        if budget.tight(240):
            n_params = 50_000_000
    else:
        n_params = 50_000_000
        if budget.tight(600):
            n_params = 20_000_000
        if budget.tight(240):
            n_params = 5_000_000
    chunk = min(25_000_000, n_params)
    n_params = max(n_params // chunk, 1) * chunk
    return n_params, chunk


def flush_partial(out_path: str, payload: dict):
    """Atomically write the payload-so-far to ``--out`` — a later
    timeout can no longer lose the phases that already completed."""
    if not out_path:
        return
    try:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, out_path)
    except OSError:
        pass


def _read_result_file(path: str, stdout: str):
    """Child result: the ``--out`` artifact first (immune to pipe
    truncation), stdout JSON-line parse as the fallback."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        import bench_mfu

        return bench_mfu._parse_json_line(stdout)


def _run_train_bench(budget: "BenchBudget" = None) -> dict:
    """Run bench_mfu.py in a subprocess (its model must release HBM
    before the checkpoint bench allocates the 3 GB state) and return its
    result dict: tokens_per_sec, mfu, hfu, config, chip, ..."""
    if os.getenv("DLROVER_BENCH_SKIP_MFU"):
        return {"skipped": True}
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_mfu.py"
    )
    out_file = os.path.join(
        tempfile.mkdtemp(prefix="dlrover_bench_mfu_"), "out.json"
    )
    # bench_mfu worst case: 300s backend probe + 5 candidates x 900s
    # each — give it headroom, don't kill a legitimate OOM-fallback
    # chain mid-run; under a wall-clock budget, cap it so the ckpt
    # phases (the headline) still get their share
    timeout_s = 5400
    if budget is not None:
        timeout_s = budget.cap_timeout(5400, reserve_s=300)
    try:
        proc = subprocess.run(
            [sys.executable, script, "--out", out_file],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
        parsed = _read_result_file(out_file, proc.stdout)
        if parsed is not None and parsed.get("value") is not None:
            out = dict(parsed.get("extras", {}))
            out["vs_mfu_bar_0.40"] = parsed.get("vs_baseline")
            return out
        if parsed is not None:  # the child died mid-run (early stub)
            return {
                "error": f"incomplete run (rc={proc.returncode})",
                "partial": parsed.get("extras"),
                "stderr_tail": proc.stderr[-500:],
            }
        return {
            "error": f"no JSON output (rc={proc.returncode})",
            "stderr_tail": proc.stderr[-500:],
        }
    except subprocess.TimeoutExpired as e:
        # the killed child may have flushed a stub/partial artifact —
        # exactly what the timeout defense exists to preserve
        return {"error": str(e), "partial": _partial_extras(out_file)}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def _partial_extras(out_file: str):
    parsed = _read_result_file(out_file, "")
    return parsed.get("extras") if parsed else None


def _run_goodput_bench(budget: "BenchBudget" = None) -> dict:
    """Run bench_goodput.py in a subprocess (it spawns its own elastic
    launcher on CPU) and return its extras dict."""
    if os.getenv("DLROVER_BENCH_SKIP_GOODPUT"):
        return {"skipped": True}
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_goodput.py"
    )
    workdir = tempfile.mkdtemp(prefix="dlrover_bench_goodput_")
    out_file = os.path.join(workdir, "out.json")
    timeout_s = 900
    if budget is not None:
        timeout_s = budget.cap_timeout(900, reserve_s=240)
    try:
        proc = subprocess.run(
            [
                sys.executable, script,
                "--out", out_file,
                "--trace_out", os.path.join(workdir, "trace.json"),
            ],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
        parsed = _read_result_file(out_file, proc.stdout)
        if parsed is not None and parsed.get("value") is not None:
            return dict(parsed.get("extras", {}))
        if parsed is not None:  # the child died mid-run (early stub)
            return {
                "error": f"incomplete run (rc={proc.returncode})",
                "partial": parsed.get("extras"),
                "stderr_tail": proc.stderr[-500:],
            }
        return {
            "error": f"no JSON output (rc={proc.returncode})",
            "stderr_tail": proc.stderr[-500:],
        }
    except subprocess.TimeoutExpired as e:
        return {"error": str(e), "partial": _partial_extras(out_file)}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def _run_restart_bench(budget: "BenchBudget" = None) -> dict:
    """Run scripts/bench_restart.py in a subprocess (it builds its own
    model + engine; isolation keeps its compile/restore work off this
    process's backend) and return its payload: restart_overlap_s
    beside the single-leg baselines on the same host."""
    if os.getenv("DLROVER_BENCH_SKIP_RESTART"):
        return {"skipped": True}
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_restart.py",
    )
    out_file = os.path.join(
        tempfile.mkdtemp(prefix="dlrover_bench_restart_"), "out.json"
    )
    timeout_s = 600
    if budget is not None:
        timeout_s = budget.cap_timeout(600, reserve_s=120)
    try:
        proc = subprocess.run(
            [sys.executable, script, "--out", out_file],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
        parsed = _read_result_file(out_file, proc.stdout)
        if parsed is not None:
            return parsed
        return {
            "error": f"no JSON output (rc={proc.returncode})",
            "stderr_tail": proc.stderr[-500:],
        }
    except subprocess.TimeoutExpired as e:
        return {"error": str(e), "partial": _partial_extras(out_file)}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def _host_memcpy_gbps(nbytes: int = 256 * 1024 * 1024) -> float:
    """This machine's single-threaded memcpy bandwidth — the floor
    under every host-side number (shm_read, drain memcpy legs).  The
    recorded env measures ~0.1 GB/s (heavily throttled container);
    a real TPU-VM host does 5-20 GB/s, so divide accordingly."""
    import numpy as np

    src = np.ones(nbytes, dtype=np.uint8)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm: fault dst pages outside the timing
    t0 = time.perf_counter()
    np.copyto(dst, src)
    return nbytes / 1e9 / max(time.perf_counter() - t0, 1e-9)


def _host_fault_gbps(nbytes: int = 512 * 1024 * 1024) -> float:
    """First-touch (page-fault-dominated) copy bandwidth: what a COLD
    multi-GB buffer copy actually runs at in this container (measured
    ~0.17 GB/s vs 7.7 GB/s resident) — the dominant term in
    ``shm_read_s``, which allocates a fresh private buffer per load.
    The hot restore path (``load(target=...)``) is zero-copy and never
    pays this."""
    import numpy as np

    src = np.ones(nbytes, dtype=np.uint8)
    t0 = time.perf_counter()
    dst = np.empty_like(src)
    np.copyto(dst, src)  # dst pages fault inside the timing
    return nbytes / 1e9 / max(time.perf_counter() - t0, 1e-9)


def _shm_drain_micro(nbytes: int) -> dict:
    """Host-only shm drain throughput, parallel vs serial.

    Saves a synthetic NumPy state through the REAL
    ``SharedMemoryHandler.save_state`` path twice: once with the
    configured worker pool (``drain_gbps``) and once pinned to
    ``DLROVER_TPU_CKPT_COPY_WORKERS=1`` (``drain_serial_gbps``, the
    byte-identical pre-parallel code path) — the apples-to-apples
    same-host comparison the acceptance bar wants.  Host-side only so
    the number measures the memcpy data plane, not the device link.
    The state construction and timed-drain loop live in
    ``scripts/bench_ckpt_io.py`` — ONE definition of the measurement.
    """
    from dlrover_tpu.agent.ckpt_shm import SharedMemoryHandler
    from dlrover_tpu.common.parallel_io import (
        CHUNK_MB_ENV,
        COPY_WORKERS_ENV,
    )

    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from bench_ckpt_io import synthetic_state, timed_drain_gbps

    state = synthetic_state(nbytes)
    total = sum(a.nbytes for a in state.values())
    out = {"drain_micro_state_mb": round(total / 1e6, 1)}
    prev_workers = os.environ.get(COPY_WORKERS_ENV)
    prev_chunk = os.environ.get(CHUNK_MB_ENV)
    if prev_chunk is None:
        # 16 MB chunks keep every worker fed even at the
        # budget-scaled 64 MB state size
        os.environ[CHUNK_MB_ENV] = "16"
    try:
        for tag, workers in (
            ("drain_gbps", prev_workers),
            ("drain_serial_gbps", "1"),
        ):
            if workers is None:
                os.environ.pop(COPY_WORKERS_ENV, None)
            else:
                os.environ[COPY_WORKERS_ENV] = str(workers)
            handler = SharedMemoryHandler(0, name=f"benchio_{tag}",
                                          host=True)
            try:
                out[tag] = timed_drain_gbps(handler, state, total)
            finally:
                handler.close(unlink=True)
    finally:
        for env, prev in (
            (COPY_WORKERS_ENV, prev_workers),
            (CHUNK_MB_ENV, prev_chunk),
        ):
            if prev is None:
                os.environ.pop(env, None)
            else:
                os.environ[env] = prev
    if out.get("drain_serial_gbps"):
        out["drain_speedup"] = round(
            out["drain_gbps"] / out["drain_serial_gbps"], 2
        )
    return out


def _input_micro(batch_mb: int, batches: int) -> dict:
    """Input-plane throughput, pipelined zero-copy vs the legacy
    serial ring path, same host (``scripts/bench_input.py`` owns the
    measurement — ONE definition)."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from bench_input import run_all

    result = run_all(batch_mb, batches, slots=4)
    out = {"input_batch_mb": batch_mb}
    out["input_gbps"] = result["pipelined"]["gbps"]
    out["input_serial_gbps"] = result["serial"]["gbps"]
    if "pipelined_vs_serial" in result:
        out["input_speedup"] = result["pipelined_vs_serial"]
    return out


def _control_micro(n_agents: int, wait_s: float) -> dict:
    """Control-plane long-poll waits over the real gRPC master,
    same host (``scripts/bench_control_plane.py`` owns the
    measurement — ONE definition)."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from bench_control_plane import run_all

    result = run_all(n_agents, wait_s)
    out = {"control_bench": result}
    if "control_rps" in result:
        out["control_rps"] = result["control_rps"]
    return out


def _fleet_bench(budget: "BenchBudget", out_path: str,
                 payload: dict) -> dict:
    """Fleet-scale saturation leg (``scripts/bench_control_plane.py``
    owns the simulator — ONE definition): 64..256 (512 when the
    budget allows) simulated agents against one real self-telemetry
    master, p50/p99 per RPC kind vs N + the saturation knee, plus the
    shrunken-pool synthetic overload.  The partial payload is flushed
    after EVERY sweep point — a 512-agent leg that hits the budget
    must not lose the 64/128/256 points (the BENCH_r05 early-flush
    rule)."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from bench_control_plane import run_fleet, run_overload

    tightish = budget.tight(420)
    ns = [64, 128, 256]
    if not tightish and not budget.tight(600):
        ns.append(512)
    if budget.tight(240):
        ns = [64, 128]
    duration = 2.5 if tightish else 4.0

    def _checkpoint(partial):
        payload["extras"]["fleet"] = partial
        flush_partial(out_path, payload)

    fleet = run_fleet(ns, duration_s=duration,
                      checkpoint=_checkpoint)
    try:
        fleet["overload"] = run_overload()
    except Exception as e:  # noqa: BLE001 - the sweep points stand alone
        fleet["overload_error"] = str(e)
    return {"fleet": fleet}


def measure_profiling_overhead(
    steps: int = 60, every: int = 15, step_sleep: float = 0.02
) -> dict:
    """Continuous-attribution-leg overhead: steady step time with
    ``DLROVER_TPU_PROFILE_EVERY_N_STEPS`` effectively on vs off.

    Mirrors the trainer's mechanics exactly — every ``every`` steps a
    one-step ``jax.profiler`` window opens and the parse runs on the
    background :class:`AttributionWorker` — in interleaved rounds of
    one off leg and one on leg of ``every`` steps each, the order
    swapped from round to round so container drift cancels (the
    bench_restart trick).  Two numbers:

    - ``profiling_overhead`` — the MEDIAN OVER ROUNDS of a round's
      median STEADY (non-traced) step time over its median off step
      time, minus 1: what profiling costs the steps it does not
      touch.  A neighbour's burst of load lands in one round and
      moves one ratio, not the pooled median of a whole side.  This
      is the tier-1 < 2% assertion: the background parse must not
      steal the training thread.
    - ``profiling_amortized_overhead`` — mean-over-all-steps ratio,
      including the traced steps' trace start/stop cost.  On CPU CI
      with ~20 ms steps this is dominated by the capture itself and
      NOT held to the 2% bar; on real hardware (seconds-long steps,
      N ≥ 100) it converges to the steady number.

    Shared with ``tests/test_profiling.py`` — ONE definition of the
    measurement."""
    import statistics
    import tempfile as _tempfile

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.observability.attribution import (
        AttributionWorker,
    )

    f = jax.jit(lambda x: x * 1.0001 + 1.0)
    x = jnp.ones((256, 256))
    for _ in range(3):  # warm the jit
        x = f(x)
    jax.block_until_ready(x)

    worker = AttributionWorker()

    def leg(n: int, profile_every: int):
        """``(steady, traced)`` step times of ``n`` steps."""
        nonlocal x
        steady, traced_times = [], []
        for count in range(1, n + 1):
            traced = profile_every > 0 and count % profile_every == 0
            t0 = time.perf_counter()
            trace_dir = None
            if traced:
                trace_dir = _tempfile.mkdtemp(
                    prefix="dlrover_profovh_"
                )
                jax.profiler.start_trace(trace_dir)
            y = f(x)
            jax.block_until_ready(y)
            time.sleep(step_sleep)
            x = y
            if trace_dir is not None:
                jax.profiler.stop_trace()
                worker.submit(
                    trace_dir,
                    count,
                    time.time(),
                    time.perf_counter() - t0,
                    steps=1,
                    mode="profile",
                )
            (traced_times if traced else steady).append(
                time.perf_counter() - t0
            )
        return steady, traced_times

    # an ON leg holds exactly one traced step: its last
    rounds = max(steps // (2 * every), 2)
    ratios, off_times, on_steady, on_traced = [], [], [], []
    for r in range(rounds):  # A/B, B/A, ...: drift cancels
        legs = {}
        for profile_every in ((0, every) if r % 2 == 0 else (every, 0)):
            legs[profile_every] = leg(every, profile_every)
        (off, _), (on, traced_times) = legs[0], legs[every]
        ratios.append(statistics.median(on) / statistics.median(off) - 1.0)
        off_times += off
        on_steady += on
        on_traced += traced_times
    worker.close()
    overhead = statistics.median(ratios)
    med_off = statistics.median(off_times)
    med_on = statistics.median(on_steady)
    on_all = on_steady + on_traced
    amortized = (
        (sum(on_all) / len(on_all)) / med_off - 1.0
        if med_off > 0 and on_all
        else 0.0
    )
    return {
        "profiling_overhead": round(overhead, 4),
        "profiling_amortized_overhead": round(amortized, 4),
        "profiling_steady_step_s": round(med_on, 5),
        "profiling_off_step_s": round(med_off, 5),
        "profiling_traced_step_s": round(
            statistics.median(on_traced), 5
        ) if on_traced else None,
        "profiling_every": every,
        "profiling_steps": 2 * rounds * every,
    }


def _brain_loop_bench(budget: "BenchBudget" = None) -> dict:
    """The closed autonomy loop's acceptance artifact: Brain-on vs
    Brain-off goodput under the slow-node sleep fault, plus — when
    the budget allows — the preempt-storm comparison (the Brain vs
    the static seed auto-scaler).  ``scripts/chaos.py`` owns both
    scenarios — ONE definition."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from chaos import run_preempt_storm, run_slow_node

    tightish = budget is not None and budget.tight(300)
    steps = 20 if tightish else 30
    on = run_slow_node(steps=steps, brain=True, timeout=240.0)
    off = run_slow_node(steps=steps, brain=False, timeout=240.0)
    brain_loop = {
        "slow_node": {
            "brain": on,
            "static": off,
            "goodput_gain": round(
                on["goodput"] - off["goodput"], 4
            ),
        }
    }
    out = {
        "brain_loop": brain_loop,
        "brain_slow_node_goodput_gain": brain_loop["slow_node"][
            "goodput_gain"
        ],
    }
    # the storm legs are the most expensive chaos in the suite; only
    # a roomy budget runs them here (chaos.py --plan preempt-storm
    # produces the same artifact standalone)
    if budget is None or not budget.tight(700):
        # storm steps must be SLOWER than pod teardown (chaos.py
        # main() applies the same floor) or the job races to the
        # target between the SIGTERM and the first missed collective
        p_on = run_preempt_storm(
            steps=30, step_sleep=0.25, brain=True, timeout=240.0,
        )
        p_off = run_preempt_storm(
            steps=30, step_sleep=0.25, brain=False, timeout=240.0,
        )
        brain_loop["preempt_storm"] = {
            "brain": p_on,
            "static": p_off,
            "goodput_gain": round(
                p_on["goodput"] - p_off["goodput"], 4
            ),
        }
        out["brain_preempt_goodput_gain"] = brain_loop[
            "preempt_storm"
        ]["goodput_gain"]
    return out


def _failover_bench(budget: "BenchBudget" = None) -> dict:
    """Master-kill-storm vs fault-free goodput + per-kill master MTTR
    (``scripts/chaos.py`` owns the orchestration — ONE definition).
    A real master subprocess + a real 2-proc launcher job per leg."""
    sys.path.insert(
        0,
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"
        ),
    )
    from chaos import run_plan

    tightish = budget is not None and budget.tight(300)
    steps = 20 if tightish else 40
    out = {}
    clean = run_plan(
        plan="none", steps=steps, step_sleep=0.05, timeout=180.0
    )
    storm = run_plan(
        plan="master-kill-storm", steps=steps, kills=2,
        step_sleep=0.05, timeout=240.0,
    )
    out["failover"] = {"clean": clean, "storm": storm}
    out["failover_mttr_mean_s"] = storm.get("mttr_mean_s")
    if clean.get("goodput"):
        out["failover_goodput_ratio"] = round(
            storm["goodput"] / clean["goodput"], 3
        )
    return out


def _run_paged_kernels_bench(budget: "BenchBudget" = None) -> dict:
    """Run scripts/bench_paged_attention.py in a subprocess: decode +
    verify timings under both paged-attention backends (jnp gather
    reference vs streamed Pallas kernels) across ≥3 context lengths,
    with the pallas/jnp speedup ratio as the headline.  Informational
    on CPU CI (interpret mode measures plumbing, not kernels); the
    ≥1x bar applies on TPU."""
    if os.getenv("DLROVER_BENCH_SKIP_SERVING"):
        return {"skipped": True}
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_paged_attention.py",
    )
    out_file = os.path.join(
        tempfile.mkdtemp(prefix="dlrover_bench_paged_"), "out.json"
    )
    timeout_s = 300
    if budget is not None:
        timeout_s = budget.cap_timeout(300, reserve_s=90)
    env = dict(os.environ)
    env[BUDGET_ENV] = str(int(max(30, timeout_s - 30)))
    try:
        proc = subprocess.run(
            [sys.executable, script, "--out", out_file, "--reps", "3"],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
        parsed = _read_result_file(out_file, proc.stdout)
        if parsed is not None:
            out = {
                k: v for k, v in parsed.items() if k != "points"
            }
            out["n_points"] = len(parsed.get("points", []))
            # per-point summary: context -> (decode, verify) speedups
            out["speedups"] = {
                f"b{p['batch']}_c{p['context']}_bs{p['block_size']}": [
                    p.get("decode_speedup"),
                    p.get("verify_speedup"),
                ]
                for p in parsed.get("points", [])
            }
            return out
        return {
            "error": f"no JSON output (rc={proc.returncode})",
            "stderr_tail": proc.stderr[-500:],
        }
    except subprocess.TimeoutExpired as e:
        # the killed child flushed a partial payload per sweep point
        # (run_sweep calls flush_fn after each point, not at the end)
        return {"error": str(e), "partial": _read_result_file(out_file, "")}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def _run_flywheel_bench(budget: "BenchBudget" = None) -> dict:
    """Run scripts/bench_flywheel.py in a subprocess: the zero-copy
    RLHF loop — in-place publish stall vs the pickle hop (and vs the
    training step), streamed rollout rounds with exactly-once
    trajectory accounting, Brain-arbitrated device lending vs the
    static split, and the replica+publisher chaos kill."""
    if os.getenv("DLROVER_BENCH_SKIP_SERVING"):
        return {"skipped": True}
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "scripts", "bench_flywheel.py",
    )
    out_file = os.path.join(
        tempfile.mkdtemp(prefix="dlrover_bench_flywheel_"),
        "out.json",
    )
    timeout_s = 600
    env = dict(os.environ)
    if budget is not None:
        timeout_s = budget.cap_timeout(600, reserve_s=120)
        # the child scales request counts / skips late legs from the
        # budget env; hand it the time actually left for this leg
        env[BUDGET_ENV] = str(int(max(30, timeout_s - 60)))
    cmd = [sys.executable, script, "--out", out_file]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout_s,
            env=env,
        )
        parsed = _read_result_file(out_file, proc.stdout)
        if parsed is not None:
            out = dict(parsed.get("extras", {}))
            out["publish_speedup_vs_pickle_hop"] = parsed.get("value")
            if proc.returncode != 0:
                out["error"] = f"incomplete run (rc={proc.returncode})"
                out["stderr_tail"] = proc.stderr[-500:]
            return out
        return {
            "error": f"no JSON output (rc={proc.returncode})",
            "stderr_tail": proc.stderr[-500:],
        }
    except subprocess.TimeoutExpired as e:
        return {"error": str(e), "partial": _partial_extras(out_file)}
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)}


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description="headline bench")
    parser.add_argument(
        "--out",
        default="BENCH_OUT.json",
        help="write the result JSON here as well as stdout (the "
        "driver's stdout tail capture can truncate; a file cannot)",
    )
    args = parser.parse_args(argv)
    budget = BenchBudget()

    payload = {
        "metric": "flash_ckpt_blocking_save_s",
        "value": None,
        "unit": "s",
        "vs_baseline": None,
        "extras": {
            "baseline_blocking_s": BASELINE_BLOCKING_S,
            "bench_budget_s": budget.total,
        },
    }
    extras = payload["extras"]

    # training throughput first, in its own process (frees HBM on exit)
    if budget.tight(240):
        train_bench = {"skipped": "budget"}
    else:
        train_bench = _run_train_bench(budget)
    extras["train"] = train_bench
    flush_partial(args.out, payload)
    if budget.tight(180):
        goodput_bench = {"skipped": "budget"}
    else:
        goodput_bench = _run_goodput_bench(budget)
    extras["goodput"] = goodput_bench
    flush_partial(args.out, payload)
    # restart critical path: overlapped MTTR on this host
    # (trainer/restart_path.py; scripts/bench_restart.py)
    if budget.tight(150):
        restart_bench = {"skipped": "budget"}
    else:
        restart_bench = _run_restart_bench(budget)
    extras["restart"] = restart_bench
    if isinstance(restart_bench.get("restart_overlap_s"), (int, float)):
        extras["restart_overlap_s"] = restart_bench["restart_overlap_s"]
    flush_partial(args.out, payload)
    # probe sizes shrink under pressure: in the throttled container
    # even the 768 MB of probe buffers costs double-digit seconds
    probe_mb = 32 if budget.tight(120) else 256
    memcpy_gbps = _host_memcpy_gbps(probe_mb * 1024 * 1024)
    fault_gbps = _host_fault_gbps(2 * probe_mb * 1024 * 1024)
    extras["host_memcpy_gbps"] = round(memcpy_gbps, 3)
    extras["host_fault_gbps"] = round(fault_gbps, 3)
    flush_partial(args.out, payload)

    # the parallel-vs-serial drain comparison runs EARLY and host-only:
    # even a budget kill later in the run leaves drain_gbps on disk.
    # Guarded: a diagnostic failure (tiny /dev/shm, etc.) must not
    # abort the headline phases.  Under hard budget pressure the
    # micro phases are skipped outright — the ckpt headline (below)
    # outranks the comparisons.
    if budget.tight(60):
        extras["micro_phases"] = "skipped_budget"
    else:
        drain_state_mb = 64 if budget.tight(300) else 256
        try:
            extras.update(
                _shm_drain_micro(drain_state_mb * 1024 * 1024)
            )
        except Exception as e:  # noqa: BLE001
            extras["drain_micro_error"] = str(e)
        flush_partial(args.out, payload)

        # input-plane comparison, host-only and early for the same
        # reason
        try:
            extras.update(
                _input_micro(
                    batch_mb=16 if budget.tight(300) else 64,
                    batches=4 if budget.tight(300) else 8,
                )
            )
        except Exception as e:  # noqa: BLE001
            extras["input_micro_error"] = str(e)
        flush_partial(args.out, payload)

        # control-plane comparison, host-only and early for the same
        # reason (real gRPC master + simulated agents on localhost)
        try:
            extras.update(
                _control_micro(
                    n_agents=4 if budget.tight(300) else 8,
                    wait_s=2.0 if budget.tight(300) else 5.0,
                )
            )
        except Exception as e:  # noqa: BLE001
            extras["control_micro_error"] = str(e)
        flush_partial(args.out, payload)

        # fleet-scale saturation leg: p50/p99 per RPC kind vs N
        # against one self-telemetry master + the shrunken-pool
        # overload proof (flushes per sweep point internally)
        try:
            extras.update(_fleet_bench(budget, args.out, payload))
        except Exception as e:  # noqa: BLE001
            extras["fleet_bench_error"] = str(e)
        flush_partial(args.out, payload)

        # master-failover leg: goodput under a master-kill storm vs
        # fault-free, plus master MTTR (scripts/chaos.py)
        try:
            extras.update(_failover_bench(budget))
        except Exception as e:  # noqa: BLE001
            extras["failover_bench_error"] = str(e)
        flush_partial(args.out, payload)

        # paged-attention kernel micro-bench: decode + verify, jnp
        # gather reference vs streamed Pallas kernels, ≥3 context
        # lengths; speedup ratio informational on CPU CI
        # (scripts/bench_paged_attention.py)
        if budget.tight(120):
            extras["paged_kernels"] = {"skipped": "budget"}
        else:
            extras["paged_kernels"] = _run_paged_kernels_bench(budget)
        flush_partial(args.out, payload)

        # RLHF flywheel: in-place publish stall vs the pickle hop,
        # streamed rollout rounds, Brain device lending and the
        # replica+publisher chaos kill
        # (scripts/bench_flywheel.py owns the scenario)
        if budget.tight(240):
            extras["flywheel"] = {"skipped": "budget"}
        else:
            extras["flywheel"] = _run_flywheel_bench(budget)
        flush_partial(args.out, payload)

        # continuous attribution leg's overhead: steady step time
        # with the one-step profile window on vs off (the < 2%
        # always-on claim, pinned by the tier-1 smoke)
        try:
            tightish = budget.tight(300)
            extras.update(
                measure_profiling_overhead(
                    steps=40 if tightish else 60,
                    every=10 if tightish else 15,
                )
            )
        except Exception as e:  # noqa: BLE001
            extras["profiling_overhead_error"] = str(e)
        flush_partial(args.out, payload)

        # observatory leg: injected straggler + hang must be named
        # within the interval bound (scripts/bench_observatory.py
        # owns the scenario — ONE definition)
        try:
            sys.path.insert(
                0,
                os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "scripts",
                ),
            )
            from bench_observatory import run_scenario

            scenario = run_scenario(interval=0.4, timeout_s=45.0)
            extras["observatory"] = scenario
            extras["observatory_hang_detect_intervals"] = (
                scenario.get("hang_intervals")
            )
        except Exception as e:  # noqa: BLE001
            extras["observatory_bench_error"] = str(e)
        flush_partial(args.out, payload)

        # autonomy-loop leg: the Brain job must beat the static job
        # on goodput under the slow-node fault (scripts/chaos.py
        # owns the scenario)
        try:
            extras.update(_brain_loop_bench(budget))
        except Exception as e:  # noqa: BLE001
            extras["brain_loop_bench_error"] = str(e)
    flush_partial(args.out, payload)

    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"
    # PINNED state size (VERDICT-r4 weak #5: the auto-sized state made
    # the blocking-save headline incomparable across rounds — 1.7ms at
    # 0.45GB, 6.2ms at 1.45GB).  0.5 GB bf16 on TPU, small on CPU CI;
    # the d2h probe is kept for normalization only.  Sizing lives in
    # snapshot_plan: the budget scales the state on EVERY backend
    # (the unscaled CPU state was the BENCH_r05 rc=124 residual).
    d2h_probe_gbps = None
    if on_tpu:
        probe = jax.device_put(
            jnp.ones((16, 1024, 1024), jnp.float32)  # 64 MB
        )
        jax.block_until_ready(probe)
        import numpy as _np

        t0 = time.perf_counter()
        host = _np.asarray(probe)
        d2h_probe_gbps = host.nbytes / 1e9 / max(
            time.perf_counter() - t0, 1e-9
        )
        extras["d2h_probe_gbps"] = round(d2h_probe_gbps, 4)
    n_params, chunk = snapshot_plan(budget, on_tpu)
    n_chunks = n_params // chunk
    extras["state_scaled_for_budget"] = bool(
        n_params < (250_000_000 if on_tpu else 50_000_000)
    )

    key = jax.random.PRNGKey(0)
    state = {
        f"layer_{i}": jax.device_put(
            jax.random.normal(
                jax.random.fold_in(key, i), (chunk,), dtype=jnp.bfloat16
            )
        )
        for i in range(n_chunks)
    }
    jax.block_until_ready(state)

    # stand-in for an optimizer step: mutates every leaf so the next
    # snapshot cannot reuse any cached host copy
    update = jax.jit(lambda s: jax.tree_util.tree_map(lambda x: x + 1, s))

    sock_dir = tempfile.mkdtemp(prefix="dlrover_bench_socks_")
    os.environ["DLROVER_TPU_SOCKET_DIR"] = sock_dir
    ckpt_dir = tempfile.mkdtemp(prefix="dlrover_bench_ckpt_")

    from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine

    engine = CheckpointEngine(
        checkpoint_dir=ckpt_dir, process_rank=0, process_count=1,
        local_shard_num=1,
    )

    gb = n_params * 2 / 1e9
    extras["state_gb"] = round(gb, 2)
    extras["backend"] = jax.default_backend()

    # pre-create + fault in the shm segment off the hot path (init-time)
    t_prealloc0 = time.perf_counter()
    engine.preallocate_like(state)
    prealloc_s = time.perf_counter() - t_prealloc0
    extras["prealloc_s"] = round(prealloc_s, 2)
    extras["prealloc_gbps"] = round(
        2 * gb / max(prealloc_s, 1e-9), 3
    )  # double-buffered: prealloc touches 2x the state

    # first save: with the segment pre-faulted this is transfer-bound,
    # not allocation-bound, and it does not block the loop
    t_first0 = time.perf_counter()
    assert engine.save_to_memory(0, state, blocking=False)
    first_block_s = time.perf_counter() - t_first0
    engine.wait_for_snapshot()
    first_total_s = time.perf_counter() - t_first0
    extras["first_save_block_s"] = round(first_block_s, 4)
    extras["first_save_total_s"] = round(first_total_s, 2)
    flush_partial(args.out, payload)

    blocked, drains = [], []
    steps = (1,) if budget.tight(4 * first_total_s + 120) else (1, 2)
    for step in steps:
        state = update(state)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        ok = engine.save_to_memory(step, state, blocking=False)
        blocked.append(time.perf_counter() - t0)
        assert ok
        engine.wait_for_snapshot()
        drains.append(time.perf_counter() - t0)
    blocking = min(blocked)
    drain_s = min(drains)
    payload["value"] = round(blocking, 4)
    payload["vs_baseline"] = round(BASELINE_BLOCKING_S / blocking, 2)
    extras["snapshot_drain_s"] = round(drain_s, 2)
    extras["d2h_gbps"] = round(gb / drain_s, 3)
    flush_partial(args.out, payload)

    # async persistence completes off the hot path
    state = update(state)
    jax.block_until_ready(state)
    t_persist0 = time.perf_counter()
    engine.save_to_storage(4, state, blocking=False)
    engine.wait_for_snapshot()
    persisted = engine.wait_for_persist(
        4, timeout=budget.cap_timeout(600)
    )
    persist_s = time.perf_counter() - t_persist0
    extras["async_persist_s"] = round(persist_s, 2)
    extras["persisted"] = bool(persisted)
    extras["persist_gbps"] = round(gb / max(persist_s, 1e-9), 3)
    flush_partial(args.out, payload)

    # restore after "restart": zero-copy shm views batched onto the
    # live state's device shardings (includes host->device transfer)
    t0 = time.perf_counter()
    step, host_arrays = engine.load()
    shm_read_s = time.perf_counter() - t0
    assert step == 4 and host_arrays is not None
    extras["shm_read_s"] = round(shm_read_s, 4)
    extras["shm_read_gbps"] = round(gb / max(shm_read_s, 1e-9), 3)
    t0 = time.perf_counter()
    step, restored = engine.load(target=state)
    restore_device_s = time.perf_counter() - t0
    assert step == 4 and restored is not None
    extras["restore_to_device_s"] = round(restore_device_s, 2)
    flush_partial(args.out, payload)
    # restore-side blocking headline (VERDICT-r4 #9): time from
    # "restart decided" to the FIRST step completing on the restored
    # state — shm read + H2D restore + one training step
    t0 = time.perf_counter()
    _step, rerestored = engine.load(target=state)
    first = update(rerestored)
    jax.block_until_ready(first)
    time_to_first_step_s = time.perf_counter() - t0
    extras["time_to_first_step_s"] = round(time_to_first_step_s, 2)
    extras["bench_elapsed_s"] = round(budget.elapsed(), 1)

    engine.close()

    print(json.dumps(payload), flush=True)
    flush_partial(args.out, payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
