"""One staged snapshot of the training cell's state, checked on the chip.

The real ``Trainer`` on ``mistral-7b-v0.1`` at depth 2 (8.38 GB of fp32
masters and ``agd`` moments: ``snapshot_mode`` ``auto`` resolves to
``staged``), driven step by step in ONE process:

1. a few steps, a snapshot (``_maybe_checkpoint``), and the device's
   state at that step read out leaf-wise as the reference (a 128-bit
   digest a leaf: a second 8 GB host copy does not fit beside the two
   shm slots and the host tree on a 40 GiB machine);
2. more steps, which donate and overwrite the state while the drain
   runs, their losses logged: the uninterrupted run;
3. the snapshot's leaves read back from shm have the reference's
   shapes, dtypes and digests: the same bytes;
4. the device state and the host tree are dropped, the state is
   rebuilt from the shm leaves alone, and the same batches give the
   same losses;
5. two more snapshots: every ``snapshot_pull`` of the events file has a
   ``checkpoint_save`` of the same step and bytes, none was skipped,
   the snapshot program compiled once, no host copy stays cached on the
   recycled host tree, and the process's peak memory is reported.

Last stdout line: one JSON object, ``ok`` true or false; also written to
``chiprun_out/staged_snapshot.json``.  ``--tiny`` rehearses the control
flow on the CPU (no ``pinned_host`` there: the copy stays on the
device).
"""

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

from bench_snapshot_pull import cell_config  # noqa: E402


def _memory_mb():
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS", "VmHWM")):
                key, value = line.split(":")
                out[key] = int(value.split()[0]) // 1024
    out["cgroup"] = _cgroup_mb()
    return out


def _cgroup_mb():
    for path in (
        "/sys/fs/cgroup/memory.current",
        "/sys/fs/cgroup/memory/memory.usage_in_bytes",
    ):
        try:
            with open(path) as f:
                return int(f.read()) // 2**20
        except OSError:
            continue
    return None


def _wait_for_room(before, timeout_s=90.0):
    """Until the machine's memory in use has fallen by most of a host
    tree (the runtime unpins it in its own time), or the time is up."""
    deadline = time.monotonic() + timeout_s
    now = _memory_mb()
    while time.monotonic() < deadline:
        now = _memory_mb()
        if (
            now.get("cgroup") is None
            or before.get("cgroup") is None
            or now["cgroup"] < before["cgroup"] - 6000
        ):
            break
        time.sleep(0.5)
    return now


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="chiprun_out/staged_snapshot.json")
    args = p.parse_args()

    work = tempfile.mkdtemp(prefix="snapck")
    os.environ["DLROVER_TPU_SOCKET_DIR"] = tempfile.mkdtemp(prefix="sk")
    events_path = os.path.join(work, "events.jsonl")
    os.environ["DLROVER_TPU_EVENTS_FILE"] = events_path

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.accelerate import auto_accelerate
    from dlrover_tpu.common import jax_env
    from dlrover_tpu.models import llama
    from dlrover_tpu.observability.events import read_events
    from dlrover_tpu.optimizers import agd
    from dlrover_tpu.trainer.trainer import (
        Trainer,
        TrainingArgs,
        _HostLeaf,
    )

    cfg = cell_config(args.tiny, **({"remat": "none"} if args.tiny else {}))
    batch_shape = (8, 17) if args.tiny else (2, 2049)
    result = auto_accelerate(
        loss_fn=lambda prm, b: llama.loss_fn(prm, b, cfg),
        optimizer=agd(3e-5),
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        param_axes=llama.param_logical_axes(cfg),
        devices=jax.devices() if args.tiny else jax.devices()[:1],
    )
    trainer = Trainer(
        result,
        TrainingArgs(
            max_steps=10**9,
            checkpoint_dir=os.path.join(work, "ckpt"),
            save_memory_interval=4,
            save_storage_interval=10**9,
            micro_batch_size=batch_shape[0],
            snapshot_mode="staged" if args.tiny else "auto",
        ),
        lambda: iter(()),
        rng_seed=args.seed,
    )
    out = {"device": jax_env.device_report(), "memory_mb": {}}
    out["memory_mb"]["start"] = _memory_mb()
    trainer._init_or_restore_state()
    engine = trainer._engine
    out["mode"] = trainer._snapshot_mode
    out["memory_kind"] = trainer._snap_memory_kind

    def batch(step):
        rng = np.random.default_rng([args.seed, step])
        return {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, batch_shape, np.int32)
            )
        }

    def run(first, last):
        losses = []
        for step in range(first, last + 1):
            trainer.state, metrics = trainer._fns.train_step(
                trainer.state, batch(step)
            )
            losses.append(float(metrics["loss"]))
        return losses

    def digest(array):
        array = np.ascontiguousarray(array)
        return (
            array.shape,
            str(array.dtype),
            hashlib.blake2b(array.data, digest_size=16).hexdigest(),
        )

    def note(stage):
        out["memory_mb"][stage] = _memory_mb()
        print(stage, out["memory_mb"][stage], file=sys.stderr, flush=True)

    # 1. steps, the device's state at the snapshot's step, the snapshot
    run(1, 4)
    note("after_4_steps")
    flat, treedef = jax.tree_util.tree_flatten_with_path(trainer.state)
    keys = [jax.tree_util.keystr(path) for path, _ in flat]
    shardings = [leaf.sharding for _, leaf in flat]
    # (read through throwaway handles: no host copy stays cached)
    want = {
        key: digest(np.asarray(_HostLeaf(leaf)))
        for key, (_, leaf) in zip(keys, flat)
    }
    out["leaves"] = len(flat)
    out["bytes"] = sum(int(leaf.nbytes) for _, leaf in flat)
    del flat
    note("after_reference_digests")
    trainer._maybe_checkpoint(4)
    # 2. the uninterrupted run goes on beside the drain
    uninterrupted = run(5, 8)
    drained = engine.wait_for_snapshot(timeout=600)
    note("after_first_drain")
    # 3. shm against the device, bit for bit
    got_step, arrays = engine._shm_handler.load_state(copy=False)
    out["snapshot_step"] = got_step
    out["shm_equals_device_bit_for_bit"] = (
        bool(drained)
        and sorted(arrays) == sorted(keys)
        and all(digest(arrays[key]) == want[key] for key in keys)
    )
    note("after_shm_digests")
    # 4. a state rebuilt from shm alone continues as the run did (as a
    # restarted worker it starts without a host tree: the uploads'
    # staging does not fit beside the tree and both shm slots)
    trainer._snap_host = trainer._snap_fn = None
    trainer._snap_prepared = False
    trainer.state = None
    if out["memory_kind"] == "pinned_host":
        out["memory_mb"]["host_tree_given_back"] = _wait_for_room(
            before=out["memory_mb"]["after_first_drain"]
        )
    trainer.state = jax.tree_util.tree_unflatten(
        treedef,
        [
            # one leaf at a time: the host has no room for 8 GB of
            # staged uploads beside the host tree and both shm slots
            jax.block_until_ready(jax.device_put(arrays[key], sharding))
            for key, sharding in zip(keys, shardings)
        ],
    )
    del arrays
    note("after_rebuild")
    trainer._prepare_snapshots()
    resumed = run(5, 8)
    out["losses_uninterrupted"] = uninterrupted
    out["losses_resumed"] = resumed
    out["resumed_equals_uninterrupted"] = resumed == uninterrupted
    # 5. two more snapshots through the same program and buffers
    trainer._maybe_checkpoint(8)
    engine.wait_for_snapshot(timeout=600)
    program = trainer._snap_fn
    run(9, 12)
    trainer._maybe_checkpoint(12)
    engine.wait_for_snapshot(timeout=600)
    out["one_program"] = trainer._snap_fn is program
    held = jax.tree_util.tree_leaves(trainer._snap_host)
    out["host_tree_leaves"] = len(held)
    out["host_copies_cached_on_host_tree"] = sum(
        leaf._npy_value is not None for leaf in held
    )
    out["skipped_snapshots"] = engine.skipped_snapshots
    out["memory_mb"]["end"] = _memory_mb()
    events = read_events(events_path)
    pulls = [e for e in events if e["name"] == "snapshot_pull"]
    saves = [
        e for e in events
        if e["name"] == "checkpoint_save" and e["ph"] == "X"
    ]
    out["pulls"] = [
        dict(e["labels"], seconds=round(e["dur"], 4)) for e in pulls
    ]
    out["drains"] = [
        dict(e["labels"], seconds=round(e["dur"], 4)) for e in saves
    ]
    out["every_pull_has_its_drain"] = [
        (e["labels"]["step"], e["labels"]["bytes"]) for e in pulls
    ] == [(e["labels"]["step"], e["labels"]["bytes"]) for e in saves]
    engine.close()
    out["ok"] = bool(
        out["shm_equals_device_bit_for_bit"]
        and out["resumed_equals_uninterrupted"]
        and out["one_program"]
        and out["every_pull_has_its_drain"]
        and len(pulls) == 3
        and out["skipped_snapshots"] == 0
        and out["host_copies_cached_on_host_tree"] == 0
        and (args.tiny or out["memory_kind"] == "pinned_host")
    )
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
