"""The staged snapshot's device->host pull, timed bare on the chip.

A state of the training cell's shapes (``mistral-7b-v0.1`` at depth 2:
fp32 masters + two ``agd`` moments, 8.38 GB in 38 leaves) is pulled to
the host several ways in ONE process, each three times after a warm-up,
with a donating program rewriting the state between pulls as a train
step would (so nothing is served from a cached host copy):

- ``leafwise``: ``tree_map(np.asarray, state)``, one blocking
  out-of-program transfer after another into fresh numpy memory;
- ``async_then_wait``: every leaf's ``copy_to_host_async`` first, then
  the same reads;
- ``device_put``: ``jax.device_put`` of the tree onto ``pinned_host``
  shardings, out of program;
- ``compiled``: ONE jitted copy whose ``out_shardings`` are each leaf's
  own sharding in ``pinned_host`` memory, the previous host tree
  dropped before the call;
- ``compiled_donated``: the same with the previous host tree donated.

Then the drain's side: reading the ``pinned_host`` leaves back as numpy
(serially, and through ``SharedMemoryHandler.save_state`` as the engine
does), against ``save_state`` of a numpy tree.  JSON to stdout and to
``chiprun_out/snapshot_pull.json``.

``--tiny`` runs the same control flow at toy widths (a CPU rehearsal:
its times mean nothing and are labelled with the platform).
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def cell_config(tiny: bool, **overrides):
    """The training cell's model (``mistral-7b-v0.1`` at depth 2), or
    the tiny one of the CPU rehearsal."""
    from dlrover_tpu.models import llama

    if tiny:
        return llama.LlamaConfig.tiny(**overrides)
    return llama.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        mlp_dim=14336, max_seq_len=2048, **overrides,
    )


def _state(tiny: bool):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import llama
    from dlrover_tpu.optimizers import agd

    cfg = cell_config(tiny)

    @jax.jit
    def init(rng):
        params = llama.init_params(rng, cfg)
        return {
            "params": params,
            "opt_state": agd(3e-5).init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    return init(jax.random.PRNGKey(0))


def _nbytes(tree) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--out", default="chiprun_out/snapshot_pull.json")
    args = p.parse_args()

    import jax

    from dlrover_tpu.agent.ckpt_shm import SharedMemoryHandler
    from dlrover_tpu.common import jax_env

    out = {"device": jax_env.device_report(), "reps": args.reps}
    dev = jax.devices()[0]
    state = [_state(args.tiny)]
    jax.block_until_ready(state[0])
    total = _nbytes(state[0])
    out["bytes"] = total
    out["leaves"] = len(jax.tree_util.tree_leaves(state[0]))

    bump = jax.jit(
        lambda s: jax.tree_util.tree_map(lambda x: x + 1, s),
        donate_argnums=0,
    )

    def fresh():
        """What a donating train step leaves: new buffers, no cached
        host copy."""
        state.append(bump(state.pop()))
        jax.block_until_ready(state[0])

    def gbps(seconds):
        return round(total / seconds / 1e9, 3)

    def timed(name, fn):
        rows = []
        for i in range(args.reps + 1):
            fresh()
            t0 = time.perf_counter()
            got = fn()
            dt = time.perf_counter() - t0
            del got
            if i:  # the first is the warm-up
                rows.append(round(dt, 4))
        out[name] = {"seconds": rows, "gbps": [gbps(s) for s in rows]}
        print(name, out[name], flush=True)

    # ---- out of program
    timed(
        "leafwise",
        lambda: jax.tree_util.tree_map(np.asarray, state[0]),
    )

    def async_then_wait():
        for leaf in jax.tree_util.tree_leaves(state[0]):
            leaf.copy_to_host_async()
        return jax.tree_util.tree_map(np.asarray, state[0])

    timed("async_then_wait", async_then_wait)

    host = jax.tree_util.tree_map(
        lambda x: x.sharding.with_memory_kind(
            "pinned_host" if jax_env.pinned_host_works() else "device"
        ),
        state[0],
    )
    out["memory_kind"] = jax.tree_util.tree_leaves(host)[0].memory_kind

    timed(
        "device_put",
        lambda: jax.block_until_ready(jax.device_put(state[0], host)),
    )

    # ---- in program
    def copy(s):
        return jax.tree_util.tree_map(jax.numpy.copy, s)

    plain = jax.jit(copy, out_shardings=host)
    t0 = time.perf_counter()
    compiled = plain.lower(state[0]).compile()
    out["compile_s"] = round(time.perf_counter() - t0, 3)
    mem = compiled.memory_analysis()
    out["memory_analysis"] = {
        k: int(getattr(mem, k))
        for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "host_output_size_in_bytes",
            "host_temp_size_in_bytes",
        )
        if hasattr(mem, k)
    }
    before = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    timed("compiled", lambda: jax.block_until_ready(plain(state[0])))
    after = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    out["device_peak_bytes"] = {"before": before, "after": after}

    donated = jax.jit(
        lambda s, old: copy(s),
        out_shardings=host, donate_argnums=1, keep_unused=True,
    )
    held = [jax.block_until_ready(plain(state[0]))]

    def compiled_donated():
        held.append(
            jax.block_until_ready(donated(state[0], held.pop()))
        )
        return None

    timed("compiled_donated", compiled_donated)

    # ---- the same bytes?
    fresh()
    want = [jax.tree_util.tree_map(np.asarray, state[0])]
    snap = jax.block_until_ready(donated(state[0], held.pop()))
    fresh()  # the state's buffers are rewritten; the snapshot stays
    t0 = time.perf_counter()
    got = jax.tree_util.tree_map(np.asarray, snap)
    out["host_read_serial"] = {
        "seconds": round(time.perf_counter() - t0, 4),
        "gbps": gbps(time.perf_counter() - t0),
    }
    out["bit_for_bit"] = all(
        a.tobytes() == b.tobytes()
        for a, b in zip(
            jax.tree_util.tree_leaves(want[0]),
            jax.tree_util.tree_leaves(got),
        )
    )
    print("bit_for_bit", out["bit_for_bit"], out["host_read_serial"],
          flush=True)
    del got, snap

    # ---- the drain: save_state fed pinned_host leaves, and numpy ones
    os.environ.setdefault(
        "DLROVER_TPU_SOCKET_DIR", tempfile.mkdtemp(prefix="snapbench")
    )
    handler = SharedMemoryHandler(0, name="snapbench", host=True)
    handler.preallocate(total)
    try:
        trees = {
            "drain_numpy": lambda: want[0],
            "drain_pinned": lambda: jax.block_until_ready(
                plain(state[0])
            ),
        }
        for name, tree_fn in trees.items():
            rows = []
            for step in range(args.reps + 2):
                fresh()
                tree = tree_fn()  # a new tree: no cached host value
                t0 = time.perf_counter()
                handler.save_state(step, tree)
                dt = time.perf_counter() - t0
                del tree
                if step >= 2:  # both slots' pages warm
                    rows.append(round(dt, 4))
            out[name] = {"seconds": rows, "gbps": [gbps(s) for s in rows]}
            print(name, out[name], flush=True)
            want[0] = None  # 8.4 GB of host memory back
        _step, arrays = handler.load_state(copy=False)
        flat, _ = jax.tree_util.tree_flatten_with_path(state[0])
        last = jax.tree_util.tree_map(np.asarray, state[0])
        out["shm_bit_for_bit"] = all(
            arrays[jax.tree_util.keystr(path)].tobytes()
            == np.asarray(leaf).tobytes()
            for (path, _), leaf in zip(
                flat, jax.tree_util.tree_leaves(last)
            )
        )
        del arrays
    finally:
        handler.close(unlink=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
