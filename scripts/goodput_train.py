"""Worker script for the goodput harness (``bench_goodput.py``).

A tiny data-parallel train loop under ``dlrover_tpu.run``: every step
is flash-checkpointed to shared memory (blocking, so RPO = 0 steps)
and appended to a progress file the harness tails.  On restart after a
kill the engine's consensus restore resumes from the last snapshot —
the harness asserts step continuity across incarnations.

Reference role: the chaosblade fault-tolerance experiments
(``docs/tech_report/fault_tolerance_exps.md:27-80``) — kill a worker,
training resumes from the checkpoint without losing the job.
"""

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from dlrover_tpu.trainer.elastic import init_distributed

ctx = init_distributed()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

from dlrover_tpu.data.prefetch import device_prefetch  # noqa: E402
from dlrover_tpu.observability.events import (  # noqa: E402
    anchored_now,
    get_event_logger,
)
from dlrover_tpu.parallel.mesh import AxisName, create_parallel_mesh  # noqa: E402
from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine  # noqa: E402

EVENTS = get_event_logger()

TARGET = int(os.environ["GOODPUT_TARGET_STEPS"])
STEP_SLEEP = float(os.environ.get("GOODPUT_STEP_SLEEP", "0.05"))
PROGRESS = os.environ["GOODPUT_PROGRESS_FILE"]
CKPT_DIR = os.environ["GOODPUT_CKPT_DIR"]
# shm snapshot cadence (steps).  1 = every step (RPO 0, the classic
# harness).  The preempt-storm harness runs >1 so the graceful-drain
# win is measurable: with drain, survivors resume from the step the
# preemption interrupted; without, they replay up to SAVE_EVERY-1
# steps per wave.
SAVE_EVERY = max(int(os.environ.get("GOODPUT_SAVE_EVERY", "1")), 1)
# sleep-fault (the chaos slow-node plan): from step SLOW_AFTER on,
# this process's simulated device work takes SLOW_FACTOR times longer
# — a degraded chip appearing MID-RUN.  The whole coupled world runs
# at the slow rank's speed until the Brain drains it (or, Brain off,
# until the job limps to the target).  0 = healthy (default).
SLOW_AFTER = int(os.environ.get("GOODPUT_SLOW_AFTER", "0"))
SLOW_FACTOR = max(float(os.environ.get("GOODPUT_SLOW_FACTOR", "1")), 1.0)


def log_progress(step: int) -> None:
    line = json.dumps(
        {
            "pid": os.getpid(),
            "rank": ctx.rank,
            "inc": ctx.restart_count,
            "step": step,
            "t": time.time(),
        }
    )
    with open(PROGRESS, "a") as f:
        f.write(line + "\n")


def main() -> int:
    from dlrover_tpu.trainer.drain import (
        drain_requested,
        install_drain_handler,
    )
    from dlrover_tpu.trainer.restart_path import RestartCoordinator

    install_drain_handler()

    create_parallel_mesh([(AxisName.DATA, -1)])
    optimizer = optax.adam(1e-2)
    params = {"w": jnp.eye(32), "b": jnp.zeros((32,))}
    state = {
        "params": params,
        "opt_state": optimizer.init(params),
        # a committed int32 array (not a weak python int) so the AOT
        # executable's input avals match both the fresh and the
        # checkpoint-restored state
        "step": jnp.zeros((), jnp.int32),
    }

    engine = CheckpointEngine(
        checkpoint_dir=CKPT_DIR,
        process_rank=ctx.rank,
        process_count=ctx.world_size,
        node_rank=ctx.node_rank,
        local_shard_num=int(
            os.getenv("DLROVER_TPU_LOCAL_PROCESS_COUNT", "1")
        ),
    )

    def loss_fn(params, x):
        h = jnp.tanh(x @ params["w"] + params["b"])
        return jnp.mean(h * h)

    @jax.jit
    def train_step(state, x):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], x)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        return {
            "params": optax.apply_updates(state["params"], updates),
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }, loss

    # overlapped restart critical path: restore byte prefetch and the
    # train-step AOT compile (or its persistent-cache hit) run
    # concurrently; the serial order follows any leg failure
    # (trainer/restart_path.py)
    host_state = jax.device_get(state)
    x_spec = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    state_spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state
    )

    def aot_compile():
        return train_step.lower(state_spec, x_spec).compile()

    # device-count-agnostic layouts: the goodput state is replicated
    # (pure data parallel), so every shard covers every leaf — a job
    # that shrinks or grows reshard-restores from ANY old shard file
    from dlrover_tpu.trainer.checkpoint.reshard import (
        replicated_layouts,
    )

    layouts = replicated_layouts(host_state)
    coord = RestartCoordinator(engine)
    coord.start(compile_fn=aot_compile, layouts=layouts)
    ck_step, restored = coord.finish_restore(target=host_state)
    if ck_step >= 0:
        state = restored
        print(
            f"[goodput rank {ctx.rank} inc {ctx.restart_count}] "
            f"resumed from step {ck_step}",
            flush=True,
        )
    compiled_step = coord.resolve_train_step(fallback=None)

    distributed = ctx.master_addr and ctx.world_size > 1
    on_cpu = jax.default_backend() == "cpu"
    barrier_seq = [0]

    def step_barrier():
        """Couple the ranks like a real data-parallel grad allreduce
        does: when a peer dies, the survivors stall here until the
        agent tears them down and restarts the group — that stalled
        time is exactly the goodput loss being measured.  On CPU
        worlds XLA has no multiprocess computations, so the coupling
        runs over the coordination service instead (same blocking
        semantics, no device collective)."""
        if not distributed:
            return
        if on_cpu:
            from dlrover_tpu.trainer.elastic.context import (
                control_plane_barrier,
            )

            barrier_seq[0] += 1
            control_plane_barrier(f"goodput_step_{barrier_seq[0]}")
        else:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("goodput_step")

    # the first step waits on the AOT artifact, not a cold trace; a
    # shape/aval mismatch at call time falls back to the lazy jit
    step_fn = compiled_step if compiled_step is not None else train_step

    step = int(state["step"])

    def batch_stream(start: int):
        """Deterministic per-step host batches: a restart resuming at
        step k regenerates exactly the batches the dead incarnation
        would have consumed."""
        i = start
        while True:
            rng = np.random.default_rng((ctx.rank << 20) + i)
            yield rng.standard_normal((16, 32)).astype(np.float32)
            i += 1

    # pipelined input plane: the host fetch of batch k+1 overlaps the
    # device staging of batch k and the compute of step k-1
    batches = iter(
        device_prefetch(batch_stream(step), size=2, pipelined=True)
    )

    first_step = True
    while step < TARGET:
        step_barrier()
        x = next(batches)
        t0_mono = time.monotonic()
        t0_wall = anchored_now(t0_mono)
        if first_step:
            # this incarnation's warmup: the AOT hand-off (or the
            # fallback trace+compile / cache hit) is restart overhead
            # the ledger must see, not useful step time
            with EVENTS.span("compile"):
                try:
                    state, loss = step_fn(state, x)
                except Exception:
                    if step_fn is train_step:
                        raise
                    step_fn = train_step
                    state, loss = step_fn(state, x)
                jax.block_until_ready(state)
        else:
            state, loss = step_fn(state, x)
            jax.block_until_ready(state)
        # simulated per-step device work (slowed past the sleep-fault
        # onset — the step span's dur carries the degradation to the
        # master's health derivations)
        slowed = SLOW_AFTER and step >= SLOW_AFTER
        time.sleep(STEP_SLEEP * (SLOW_FACTOR if slowed else 1.0))
        step += 1
        if not first_step:
            EVENTS.complete(
                "step", t0_wall, time.monotonic() - t0_mono, step=step
            )
        first_step = False
        # blocking memory snapshot at the configured cadence; drain
        # mode (agent SIGUSR1 before a preemption/re-mesh) snapshots
        # EVERY step so the flush persists the freshest coupled step
        if step % SAVE_EVERY == 0 or drain_requested():
            engine.save_to_memory(
                step, jax.device_get(state), layouts=layouts
            )
            engine.wait_for_snapshot()
        log_progress(step)

    engine.close()
    print(f"[goodput rank {ctx.rank}] done at step {step}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
