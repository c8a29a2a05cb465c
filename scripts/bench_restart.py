"""Micro-benchmark: the restart critical path (MTTR).

Measures "restart decided" → "first step completed on the restored
state" through the real ``RestartCoordinator``: ``start`` runs the
restore byte prefetch and the AOT compile concurrently, the
rendezvous wait rides under them, ``finish_restore`` pipelines
per-leaf ``device_put`` against the staged bytes, and the first step
waits on the compiled artifact.

Every round pays a ``--rendezvous_s`` coordination wait (default
0.5 s — the goodput harness's measured worker-side
rendezvous+backend-init leg): it is the third leg of the real
critical path, a free overlap window for the other two legs.
``--rendezvous_s 0`` measures the pure two-leg overlap.

Each round gets a FRESH jit function (a new executable cache entry —
no cross-round compile reuse) and a fresh engine namespace (no shm
reuse); all restore the same committed shard.  Single-leg baselines
(``restore_only_s``, ``compile_only_s``) bound the ideal:
``max(legs) <= overlap <= sum(legs)``.

Honors ``DLROVER_TPU_BENCH_BUDGET_S`` (scales the state down and
drops to one round), flushes the payload-so-far to ``--out`` after
every phase, and prints one JSON line.

Usage::

    python scripts/bench_restart.py [--state_mb 64] [--rounds 2]
        [--out OUT.json]
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# ONE definition of the budget/flush semantics across all benches: a
# fix there (e.g. PR 2's rc=124 partial-flush defense) must not have
# to be re-applied here
from bench import BenchBudget, flush_partial as _flush  # noqa: E402


def build_workload(state_mb: int, depth: int = 4):
    """A scan-over-layers MLP: enough XLA work that compile is a real
    restart leg, with a params tree sized to ``state_mb`` so the byte
    stream is the other real leg (the 7B-class shape: restore and
    compile are both seconds; a tiny batch keeps the step itself from
    diluting the MTTR measurement)."""
    import jax
    import jax.numpy as jnp

    hidden = max(int((state_mb * 1024 * 1024 / 4 / depth) ** 0.5), 32)

    def init_state(rng):
        return {
            "layers": jax.random.normal(
                rng, (depth, hidden, hidden), jnp.float32
            )
            * 0.01,
            "step": jnp.zeros((), jnp.int32),
        }

    def loss_fn(params, x):
        def body(h, w):
            return jnp.tanh(h @ w), None

        h, _ = jax.lax.scan(body, x, params["layers"])
        return jnp.mean(h * h)

    def make_step():
        # a FRESH function object per mode: its own executable cache
        # entry, so neither mode rides the other's compile
        def _step(state, x):
            loss, grads = jax.value_and_grad(loss_fn)(
                {"layers": state["layers"]}, x
            )
            return {
                "layers": state["layers"] - 0.01 * grads["layers"],
                "step": state["step"] + 1,
            }, loss

        return jax.jit(_step)

    batch_shape = (2, hidden)
    return init_state, make_step, batch_shape, hidden


def measure_reshard(root_dir: str, state_mb: int = 64,
                    old_world: int = 8, new_world: int = 4,
                    lost_steps: int = 50, step_probe: int = 3) -> dict:
    """Elastic-MTTR comparison on simulated hosts.

    Commits one ``old_world``-way axis-0-sharded checkpoint (layout
    headers on every shard), then measures two recoveries to the same
    training progress:

    - **reshard**: every ``new_world`` rank reassembles its NEW slice
      from the old shards' overlapping byte ranges
      (``CheckpointEngine.load(layouts=...)``); MTTR = the slowest
      rank (ranks run concurrently in production — measuring each
      serially and taking the max is the conservative bound).
    - **full restart**: the pre-reshard reality — the checkpoint is
      unreadable on the new world, so recovery = re-running the
      ``lost_steps`` of training it held, at the workload's measured
      steady step time.
    """
    import tempfile as _tf

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.trainer.checkpoint.reshard import axis0_layouts

    ckpt_dir = _tf.mkdtemp(prefix="dlrover_benchrs_reshard_")
    rows = max(old_world * 64, 256)
    cols = max(
        int(state_mb * 1024 * 1024 / 4 / rows), 64
    )
    global_w = np.random.default_rng(0).standard_normal(
        (rows, cols)
    ).astype(np.float32)
    per = rows // old_world
    step = 7

    # ---- commit the old-world checkpoint (8 engines, one saver)
    engines = []
    for r in range(old_world):
        engines.append(
            CheckpointEngine(
                checkpoint_dir=ckpt_dir, process_rank=r,
                process_count=old_world, local_shard_num=old_world,
                name="brs_old",
            )
        )
    t0 = time.perf_counter()
    for r, eng in enumerate(engines):
        local = {"w": global_w[r * per : (r + 1) * per]}
        lay = axis0_layouts(local, r, old_world)
        if r == 0:
            continue  # rank 0 persists last so every shard is in shm
        assert eng.save_to_memory(step, local, layouts=lay)
    local0 = {"w": global_w[:per]}
    assert engines[0].save_to_storage(
        step, local0, layouts=axis0_layouts(local0, 0, old_world)
    )
    assert engines[0].wait_for_persist(step, timeout=300)
    commit_s = time.perf_counter() - t0
    for eng in engines:
        eng.close()

    # ---- reshard restore onto the new world
    new_per = rows // new_world
    sync = lambda avail: max(avail)  # noqa: E731 - simulated hosts
    restore_times = []
    new_engines = []
    for r in range(new_world):
        new_engines.append(
            CheckpointEngine(
                checkpoint_dir=ckpt_dir, process_rank=r,
                process_count=new_world, local_shard_num=new_world,
                name="brs_new", step_sync_fn=sync,
            )
        )
    moved_bytes = 0
    for r, eng in enumerate(new_engines):
        target = {
            "w": np.zeros((new_per, cols), np.float32)
        }
        lay = axis0_layouts(target, r, new_world)
        t0 = time.perf_counter()
        got, restored = eng.load(target=target, layouts=lay)
        restore_times.append(time.perf_counter() - t0)
        assert got == step, got
        np.testing.assert_array_equal(
            np.asarray(restored["w"]),
            global_w[r * new_per : (r + 1) * new_per],
        )
        moved_bytes += restored["w"].nbytes
    for eng in new_engines:
        eng.close()

    # ---- the restart-from-scratch comparator: re-run the lost steps
    init_state, make_step, batch_shape, _hidden = build_workload(
        max(state_mb // 2, 16), depth=2
    )
    wstate = init_state(jax.random.PRNGKey(1))
    step_fn = make_step()
    batch = jnp.ones(batch_shape, jnp.float32)
    wstate, _ = step_fn(wstate, batch)  # compile outside the probe
    jax.block_until_ready(wstate)
    t0 = time.perf_counter()
    for _ in range(step_probe):
        wstate, _ = step_fn(wstate, batch)
    jax.block_until_ready(wstate)
    step_s = (time.perf_counter() - t0) / step_probe

    reshard_mttr = max(restore_times)
    full_restart_mttr = lost_steps * step_s
    return {
        "old_world": old_world,
        "new_world": new_world,
        "state_mb": round(global_w.nbytes / 1e6, 1),
        "commit_s": round(commit_s, 4),
        "restore_s_per_rank": [round(t, 4) for t in restore_times],
        "reshard_mttr_s": round(reshard_mttr, 4),
        "lost_steps": lost_steps,
        "steady_step_s": round(step_s, 5),
        "full_restart_mttr_s": round(full_restart_mttr, 4),
        "reshard_bytes": moved_bytes,
        "speedup_vs_full_restart": round(
            full_restart_mttr / max(reshard_mttr, 1e-9), 2
        ),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="restart critical-path MTTR"
    )
    parser.add_argument("--state_mb", type=int, default=192)
    parser.add_argument("--depth", type=int, default=4)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--rendezvous_s", type=float, default=0.5)
    parser.add_argument("--out", default="")
    args = parser.parse_args(argv)

    budget = BenchBudget()
    state_mb, rounds = args.state_mb, args.rounds
    if budget.tight(300):
        # keep a REAL byte leg even when scaled down: below ~100 MB
        # the restore is milliseconds and the measurement degenerates
        # into pure fixed overhead (one full round is well under a
        # minute at this size)
        state_mb = min(state_mb, 96)
        rounds = min(rounds, 2)

    os.environ.setdefault(
        "DLROVER_TPU_SOCKET_DIR",
        tempfile.mkdtemp(prefix="dlrover_benchrs_socks_"),
    )
    ckpt_dir = tempfile.mkdtemp(prefix="dlrover_benchrs_ckpt_")

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.trainer.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.trainer.restart_path import RestartCoordinator

    init_state, make_step, batch_shape, hidden = build_workload(
        state_mb, args.depth
    )
    state = init_state(jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    state_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(state)
    )

    payload = {
        "metric": "restart_mttr_s",
        "value": None,
        "unit": "s",
        "state_mb": round(state_bytes / 1e6, 1),
        "hidden": hidden,
        "depth": args.depth,
        "rounds": rounds,
        "rendezvous_s": args.rendezvous_s,
        "backend": jax.default_backend(),
        "bench_budget_s": budget.total,
    }

    # commit the checkpoint once; every measured restore reads THIS
    # shard from storage (the relaunched-node path — shm is gone)
    seed_engine = CheckpointEngine(
        checkpoint_dir=ckpt_dir, process_rank=0, process_count=1,
        local_shard_num=1, name="br_seed",
    )
    host_state = jax.device_get(state)
    assert seed_engine.save_to_storage(7, host_state)
    assert seed_engine.wait_for_persist(7, timeout=300)
    seed_engine.close()
    _flush(args.out, payload)

    batch = jnp.ones(batch_shape, jnp.float32)

    def measure(tag: str) -> float:
        engine = CheckpointEngine(
            checkpoint_dir=ckpt_dir, process_rank=0,
            process_count=1, local_shard_num=1, name=tag,
        )
        step_fn = make_step()

        def aot():
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                state,
            )
            return step_fn.lower(
                specs,
                jax.ShapeDtypeStruct(batch_shape, jnp.float32),
            ).compile()

        t0 = time.perf_counter()
        coord = RestartCoordinator(engine)
        coord.start(compile_fn=aot)
        if args.rendezvous_s > 0:
            # the coordination wait: the worker blocks on the device
            # world assembling — a free window for the launched legs
            with coord.rendezvous_wait():
                time.sleep(args.rendezvous_s)
        got, restored = coord.finish_restore(target=state)
        assert got == 7, got
        fn = coord.resolve_train_step(fallback=step_fn)
        out_state, _loss = fn(restored, batch)
        jax.block_until_ready(out_state)
        elapsed = time.perf_counter() - t0
        engine.close()
        return elapsed

    # single-leg baselines bound the ideal: max(legs) is the floor
    # the overlapped path aims at, their sum what a failed leg costs
    t0 = time.perf_counter()
    probe_engine = CheckpointEngine(
        checkpoint_dir=ckpt_dir, process_rank=0, process_count=1,
        local_shard_num=1, name="br_probe",
    )
    _s, _r = probe_engine.load(target=state)
    payload["restore_only_s"] = round(time.perf_counter() - t0, 4)
    probe_engine.close()
    probe_step = make_step()
    t0 = time.perf_counter()
    probe_step.lower(
        jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state
        ),
        jax.ShapeDtypeStruct(batch_shape, jnp.float32),
    ).compile()
    payload["compile_only_s"] = round(time.perf_counter() - t0, 4)
    _flush(args.out, payload)

    overlapped = []
    for r in range(rounds):
        if budget.tight(30):
            payload["rounds_completed"] = r
            break
        overlapped.append(measure(f"br_o{r}"))
        _flush(args.out, dict(payload, overlap_runs=overlapped))

    # ---- reshard leg: elastic world change vs restart-from-scratch
    if not budget.tight(45):
        try:
            payload["reshard"] = measure_reshard(
                ckpt_dir, state_mb=max(state_mb // 2, 32),
                lost_steps=50, step_probe=3,
            )
            payload["reshard_mttr_s"] = payload["reshard"][
                "reshard_mttr_s"
            ]
            payload["full_restart_mttr_s"] = payload["reshard"][
                "full_restart_mttr_s"
            ]
        except Exception as e:  # noqa: BLE001 - leg must not kill bench
            payload["reshard"] = {"error": str(e)}
        _flush(args.out, payload)

    if overlapped:
        payload["restart_overlap_s"] = round(min(overlapped), 4)
        payload["value"] = payload["restart_overlap_s"]
        payload["overlap_runs"] = [round(s, 4) for s in overlapped]
        ideal = max(
            payload["restore_only_s"], payload["compile_only_s"]
        )
        payload["ideal_max_leg_s"] = round(ideal, 4)

    print(json.dumps(payload), flush=True)
    _flush(args.out, payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
