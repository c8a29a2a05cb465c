#!/usr/bin/env python3
"""Drive both hot paths once on a real TPU through the entry points users call.

    python chip_smoke.py [--seed N]          # one chip: kernels, train, serve
    python chip_smoke.py --four-chips        # only the sharded train step
                                             # and its one-chip comparison

This process never initialises a JAX backend (a parent that touched JAX
would hold the chip its children need).  Every phase runs in a child
process, strictly one after another, each fully exited — with every
process it started — before the next begins.  A phase that fails, a
child that reports any platform but the expected one, or a Pallas
kernel that ran interpreted makes the script exit nonzero without the
final line; nothing is caught and forgiven.

Phases (model: the widths of ``LlamaConfig.llama2_7b`` — dim 4096,
32 heads x 128, MLP 11008, vocab 32000 — bf16 compute, random weights
and tokens from ``--seed``; only the depth is cut):

- ``kernels``: every Pallas kernel on the two paths, compiled for the
  chip and compared with its jnp reference there.  The int8
  quant / fused-Adam kernels are not on these paths (the train phase
  runs the example's ``agd`` optimizer with fp32 moments) and are
  covered by ``tests/test_tpu_compile.py`` only.
- ``train``: ``python -m dlrover_tpu.run --nnodes=1 --nproc_per_node=1
  examples/llama_pretrain.py --preset llama2_7b --layers 2 --batch 2
  --seq 2048`` with flash attention: steps, one memory snapshot into
  shm, SIGKILL of the worker, agent restart, restore from shm at the
  snapshot step, further steps, launcher exit 0, and the second
  incarnation's compile served from the persistent cache.
  Depth 2, batch 2: the step compiled for a described v5e needs 7.45 GiB
  of arguments (667 M params as fp32 masters + two fp32 moments,
  donated) + 5.25 GiB of temporaries = 12.7 GiB of the chip's 16 GB;
  batch 4 needs 14.2 GiB and leaves no room for the snapshot staging.
- ``serve``: a ``ServingEngine`` with one replica subprocess at the same
  widths, depth 4 (1.07 B params: a 4.3 GiB fp32 template + 2.1 GiB of
  bf16 KV pool, 8.6 GiB transient while published weights are adopted),
  answering 8 requests (prompts 128-512 tokens, 32 new tokens,
  temperature 0) through the Pallas paged backend, then — after that
  replica has exited — the same requests through the jnp backend.
- ``--four-chips``: the same launcher command, one process driving four
  chips on an fsdp=2 x tensor=2 mesh, against the one-chip run of the
  same seed.  No other phase runs under this option.

stdout: one JSON line per phase (phase, seconds, compile seconds, what
was asserted), then as the LAST line exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The expected platform and the sizes are arguments of the functions
below (the tests pass ``"cpu"`` and tiny sizes); the command line has
no switch for them and no environment variable is read for them.
"""

import argparse
import json
import math
import multiprocessing
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: bf16 rounds at 2^-8 relative: kernel outputs and gradients must
#: agree with the jnp reference to this share of the reference's
#: largest magnitude
KERNEL_TOL = 3e-2
#: per-token logprob agreement between the Pallas and the jnp paged
#: backends over each request's common token prefix (bf16 logits)
LOGPROB_TOL = 0.1
#: per-step loss agreement, four chips vs one (bf16 compute, different
#: reduction orders under fsdp x tensor sharding)
LOSS_TOL = 5e-2
#: the tokens are uniformly random, so there is nothing to learn: at a
#: learning rate that keeps a cold-started (no warm-up) optimizer stable
#: every step's loss stays within this band of ln(vocab)
LOSS_BAND = 1.0
TRAIN_LR = "3e-5"

REAL_SIZES = {
    "kernels": dict(
        batch=2, seq=2048, heads=32, head_dim=128, gqa_kv_heads=8,
        norm_rows=4096, dim=4096,
        lanes=16, block_size=16, max_blocks=64, num_blocks=2048,
        paged_kv_heads=(8, 32), window=4,
    ),
    "train": dict(
        model_args=["--preset", "llama2_7b", "--layers", "2"],
        vocab_size=32000, batch=2, seq=2048,
        # the SIGKILL follows the first WHOLE snapshot, and the worker
        # must still be training then: the 8 GB drain into cold shm
        # pages took 5-10 s on the chip machines seen, ~60 steps of
        # 0.164 s; 125 steps remain after the step-25 snapshot
        steps=150, snapshot_every=25,
    ),
    "serve": dict(
        model=dict(preset="llama2_7b", n_layers=4, max_seq_len=1024),
        requests=8, prompt_min=128, prompt_max=512, max_new=32,
        max_slots=8, block_size=16, num_blocks=2048, max_seq_len=1024,
        prefill_chunk=128,
    ),
    "four": dict(
        model_args=["--preset", "llama2_7b", "--layers", "2"],
        vocab_size=32000, batch=2, seq=2048, steps=4,
    ),
}

PHASE_TIMEOUT_S = {"kernels": 400, "train": 700, "serve": 800, "four": 1100}
#: the whole script, compilation included, stays inside this
TOTAL_TIMEOUT_S = 1150


class PhaseFailed(Exception):
    pass


def _require(cond, what):
    if not cond:
        raise PhaseFailed(what)


def _require_device(device, expect_platform):
    _require(
        device["platform"] == expect_platform,
        f"ran on {device['platform']!r} ({device['device_kind']!r}), "
        f"expected {expect_platform!r} — no fallback device",
    )


def _named(path, name):
    """The timeline events called ``name`` in the JSONL file at ``path``
    (the repo's reader skips a SIGKILLed writer's torn last line)."""
    from dlrover_tpu.observability.events import read_events

    return [e for e in read_events(path) if e["name"] == name]


# ---------------------------------------------------------------- kernels


def phase_kernels(sizes, seed, expect_platform, workdir):
    """Each Pallas kernel of the two paths vs its jnp reference, in this
    (chip-owning) process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.common.jax_env import device_report
    from dlrover_tpu.models.llama import dot_product_attention
    from dlrover_tpu.ops import fused
    from dlrover_tpu.ops import paged_attention as pa
    from dlrover_tpu.ops.flash_attention import flash_attention
    from dlrover_tpu.ops.pallas_utils import use_interpret

    device = device_report()
    _require_device(device, expect_platform)
    on_tpu = expect_platform == "tpu"
    _require(
        use_interpret() == (not on_tpu),
        "Pallas interpret mode must be off on the chip (and on off it)",
    )
    rng = np.random.default_rng(seed)
    checks = []
    compile_s = 0.0

    def normal(shape, dtype=jnp.bfloat16):
        return jnp.asarray(
            rng.standard_normal(shape, dtype=np.float32), dtype
        )

    def check(name, fn, ref_fn, args):
        nonlocal compile_s
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        dt_compile = time.perf_counter() - t0
        compile_s += dt_compile
        if on_tpu:
            _require(
                "tpu_custom_call" in compiled.as_text(),
                f"{name}: no Mosaic kernel in the compiled program",
            )
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        ref = jax.block_until_ready(jax.jit(ref_fn)(*args))
        worst = 0.0
        for o, r in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)
        ):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            _require(o.shape == r.shape, f"{name}: shape {o.shape}")
            _require(np.isfinite(o).all(), f"{name}: non-finite output")
            worst = max(
                worst,
                float(np.abs(o - r).max() / max(np.abs(r).max(), 1e-6)),
            )
        _require(
            worst <= KERNEL_TOL,
            f"{name}: rel err {worst:.4f} > {KERNEL_TOL}",
        )
        checks.append(
            dict(
                kernel=name,
                rel_err=round(worst, 5),
                compile_s=round(dt_compile, 3),
                run_s=round(run_s, 4),
            )
        )

    # flash attention, forward + backward, MHA and GQA
    b, s, h, d = (sizes[k] for k in ("batch", "seq", "heads", "head_dim"))
    for kv in (h, sizes["gqa_kv_heads"]):
        q, cot = normal((b, s, h, d)), normal((b, s, h, d))
        k, v = normal((b, s, kv, d)), normal((b, s, kv, d))

        def fwd_bwd(attn, q, k, v, cot):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v), q, k, v)
            return (out,) + vjp(cot)

        check(
            f"flash_fwd_bwd_kv{kv}",
            lambda *a: fwd_bwd(flash_attention, *a),
            lambda *a: fwd_bwd(dot_product_attention, *a),
            (q, k, v, cot),
        )

    # fused RMSNorm: Pallas forward, saved-rstd backward
    x = normal((sizes["norm_rows"], sizes["dim"]))
    w = 1.0 + 0.1 * normal((sizes["dim"],), jnp.float32)
    cot = normal((sizes["norm_rows"], sizes["dim"]))

    def rms_fwd_bwd(norm, x, w, cot):
        out, vjp = jax.vjp(norm, x, w)
        return (out,) + vjp(cot)

    check(
        "rms_norm_fwd_bwd",
        lambda *a: rms_fwd_bwd(
            lambda x, w: fused.rms_norm(x, w, 1e-5), *a
        ),
        lambda *a: rms_fwd_bwd(
            lambda x, w: fused._rms_plain(x, w, 1e-5)[0], *a
        ),
        (x, w, cot),
    )

    # paged decode + K-step verify against the jnp (gather) backend
    lanes, bs, mb, nb, win = (
        sizes[k]
        for k in ("lanes", "block_size", "max_blocks", "num_blocks",
                  "window")
    )
    for kv in sizes["paged_kv_heads"]:
        k_pool, v_pool = normal((nb, bs, kv, d)), normal((nb, bs, kv, d))
        tables = jnp.asarray(
            1 + rng.permutation(nb - 1)[: lanes * mb].reshape(lanes, mb),
            jnp.int32,
        )
        # ragged lanes: an empty one, a one-token one, a full table
        lens = rng.integers(1, mb * bs - win, size=lanes)
        lens[:3] = (0, 1, mb * bs - win)
        seq_lens = jnp.asarray(lens, jnp.int32)
        check(
            f"paged_decode_kv{kv}",
            lambda *a: pa.paged_decode_attention(*a, backend="pallas"),
            lambda *a: pa.paged_decode_attention(*a, backend="jnp"),
            (normal((lanes, h, d)), k_pool, v_pool, tables, seq_lens),
        )
        check(
            f"paged_verify_w{win}_kv{kv}",
            lambda *a: pa.paged_verify_attention(*a, backend="pallas"),
            lambda *a: pa.paged_verify_attention(*a, backend="jnp"),
            (normal((lanes, win, h, d)), k_pool, v_pool, tables,
             seq_lens),
        )

    return dict(
        device=device,
        compile_s=round(compile_s, 3),
        asserted=(
            f"{len(checks)} Pallas kernels compiled"
            + (" to Mosaic" if on_tpu else " (interpret)")
            + f", each within {KERNEL_TOL} of its jnp reference"
        ),
        checks=checks,
    )


# ------------------------------------------------------------------ train


def _launch_example(workdir, tag, example_args, env_extra=None):
    """Start ``python -m dlrover_tpu.run ... examples/llama_pretrain.py``
    in its own directory; returns (Popen, paths)."""
    run_dir = os.path.join(workdir, tag)
    os.makedirs(run_dir)
    paths = dict(
        events=os.path.join(run_dir, "events.jsonl"),
        ckpt=os.path.join(run_dir, "ckpt"),
        log=os.path.join(run_dir, "launcher.log"),
        socks=tempfile.mkdtemp(prefix="cs-"),  # AF_UNIX paths are short
    )
    env = dict(os.environ, DLROVER_TPU_SOCKET_DIR=paths["socks"])
    env.pop("DLROVER_TPU_EVENTS_FILE", None)
    env.update(env_extra or {})
    cmd = [
        sys.executable, "-m", "dlrover_tpu.run",
        "--nnodes=1", "--nproc_per_node=1",
        "--monitor_interval=1", "--max_restarts=2",
        "--failure_stop_timeout=1",
        f"--events_file={paths['events']}",
        os.path.join(REPO, "examples", "llama_pretrain.py"),
        *example_args,
        "--lr", TRAIN_LR, "--curves",
        "--save_storage_interval", "100000",
        "--ckpt_dir", paths["ckpt"],
    ]
    with open(paths["log"], "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT
        )
    return proc, paths


def _finish_launch(proc, paths, timeout):
    try:
        rc = proc.wait(timeout=timeout)
    finally:
        shutil.rmtree(paths["socks"], ignore_errors=True)
    if rc != 0:
        with open(paths["log"]) as f:
            sys.stderr.write(f.read()[-6000:])
    _require(rc == 0, f"launcher exited {rc} (log: {paths['log']})")


def _curves(paths):
    path = os.path.join(paths["ckpt"], "curves", "train_log.jsonl")
    rows = []
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row.get("kind") == "train":
                rows.append(row)
    return rows


def _check_losses(rows, vocab_size):
    _require(rows, "no training step was logged")
    for row in rows:
        _require(
            math.isfinite(row["loss"]),
            f"step {row['step']}: loss {row['loss']}",
        )
        _require(
            abs(row["loss"] - math.log(vocab_size)) < LOSS_BAND,
            f"step {row['step']}: loss {row['loss']:.3f} left the "
            f"+-{LOSS_BAND} band of ln({vocab_size}) = "
            f"{math.log(vocab_size):.3f}",
        )


def _worker_reports(events_path, expect_platform):
    reports = {
        int(e["inc"]): e["labels"]
        for e in _named(events_path, "device_report")
    }
    for labels in reports.values():
        _require_device(labels, expect_platform)
    return reports


def phase_train(sizes, seed, expect_platform, workdir):
    """The elastic launcher on the example: steps, shm snapshot, SIGKILL,
    restart, restore from shm, more steps, warm compile."""
    steps, snap = sizes["steps"], sizes["snapshot_every"]
    proc, paths = _launch_example(
        workdir,
        "train",
        [
            *sizes["model_args"],
            "--batch", str(sizes["batch"]), "--seq", str(sizes["seq"]),
            "--steps", str(steps), "--seed", str(seed),
            "--save_memory_interval", str(snap),
            "--devices", "1",  # this is the one-chip phase
        ],
    )
    # wait for the first snapshot (step `snap`) to be whole in shm, then
    # SIGKILL the worker that wrote it, mid-training
    killed_pid = None
    deadline = time.monotonic() + PHASE_TIMEOUT_S["train"] - 60
    while killed_pid is None:
        _require(
            proc.poll() is None,
            f"launcher exited {proc.returncode} before the snapshot",
        )
        _require(
            time.monotonic() < deadline, "no shm snapshot in time"
        )
        saves = _named(paths["events"], "checkpoint_save")
        if saves:
            _require(
                int(saves[0]["labels"]["step"]) == snap,
                f"first snapshot at step {saves[0]['labels']['step']}",
            )
            killed_pid = int(saves[0]["pid"])
        else:
            time.sleep(0.1)
    os.kill(killed_pid, signal.SIGKILL)
    _finish_launch(proc, paths, PHASE_TIMEOUT_S["train"] - 60)

    events = paths["events"]
    reports = _worker_reports(events, expect_platform)
    _require(
        set(reports) == {0, 1},
        f"expected device reports of incarnations 0 and 1, got "
        f"{sorted(reports)}",
    )
    _require(
        _named(events, "restart"), "the agent never restarted the worker"
    )
    restores = [
        int(e["labels"]["step"])
        for e in _named(events, "checkpoint_restore")
        if int(e["inc"]) == 1
    ]
    # the newest snapshot that was whole in shm when the kill landed:
    # the first one, or a later one the worker finished meanwhile
    _require(
        len(restores) == 1
        and snap <= restores[0] < steps
        and restores[0] % snap == 0,
        f"incarnation 1 restored at {restores}, expected one snapshot "
        f"step (a multiple of {snap} below {steps})",
    )
    restored = restores[0]
    _require(
        int(reports[1]["step"]) == restored + 1,
        f"incarnation 1 resumed at step {reports[1]['step']} after "
        f"restoring step {restored}",
    )
    rows = _curves(paths)
    _check_losses(rows, sizes["vocab_size"])
    _require(
        rows[-1]["step"] == steps, f"last step {rows[-1]['step']}"
    )
    cold, warm = reports[0], reports[1]
    # everything incarnation 0 compiled (or itself loaded), incarnation
    # 1 loaded from the persistent cache
    _require(
        warm["cache_hits"] > 0
        and warm["cache_hits"] >= cold["cache_hits"] + cold["cache_misses"],
        f"second compile was not a cache hit: cold {cold}, warm {warm}",
    )
    shutil.rmtree(paths["ckpt"], ignore_errors=True)
    device = {k: cold[k] for k in ("platform", "device_kind", "device_count")}
    return dict(
        device=device,
        compile_s=cold["compile_s"],
        warm_compile_s=warm["compile_s"],
        asserted=(
            f"{len(rows)} steps logged, every loss finite and within "
            f"{LOSS_BAND} of ln({sizes['vocab_size']}) (first "
            f"{rows[0]['loss']:.3f}); snapshot "
            f"at step {snap} in shm, worker {killed_pid} SIGKILLed "
            f"mid-training, incarnation 1 restored step {restored} from "
            f"shm and ran steps {restored + 1}..{steps}; "
            f"launcher exit 0; cold compile {cold['cache_misses']} cache "
            f"misses, warm compile {warm['cache_hits']} hits / "
            f"{warm['cache_misses']} misses"
        ),
        losses=[round(r["loss"], 4) for r in rows[:: max(len(rows) // 12, 1)]],
        first_step_s=dict(
            cold=round(cold["first_step_s"], 3),
            warm=round(warm["first_step_s"], 3),
        ),
        step_s=round(rows[-1]["step_time_s"], 4),
    )


# ------------------------------------------------------------------ serve


def _model_kwargs(model):
    """``LlamaConfig`` keyword arguments (JSON-able: they ride in the
    replica's spec) from a sizes entry: explicit widths, or every width
    of ``LlamaConfig.<preset>`` with the depth and context overridden."""
    from dlrover_tpu.models.llama import LlamaConfig

    model = dict(model)
    preset = model.pop("preset", None)
    if preset:
        cfg = getattr(LlamaConfig, preset)()
        model = dict(
            {
                k: getattr(cfg, k)
                for k in ("vocab_size", "dim", "n_heads", "n_kv_heads",
                          "mlp_dim")
            },
            **model,
        )
    return model


def _seeded_weights(model, seed):
    """The policy's parameters as HOST arrays made from ``seed`` (the
    serving parent publishes numpy, never device arrays): the tree of
    ``models.llama.init_params`` with its fan-in scaling."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**dict(model, dtype=jnp.bfloat16))
    shapes = jax.eval_shape(
        lambda key: init_params(key, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    rng = np.random.default_rng(seed)

    def leaf(path, spec):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return np.ones(spec.shape, np.float32)
        fan_in = spec.shape[0 if "lm_head" in name else 1]
        out = rng.standard_normal(spec.shape, dtype=np.float32)
        out *= fan_in**-0.5
        return out

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _serve_leg(
    sizes, model, backend, weights, prompts, events_path, expect_platform
):
    """One ServingEngine lifetime under one paged backend; the replica
    has exited when this returns."""
    from dlrover_tpu.common.jax_env import backend_initialized
    from dlrover_tpu.rl.generation_service import ServingEngine

    # the replica inherits this (phase-private) process's environment
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = backend
    os.environ["DLROVER_TPU_EVENTS_FILE"] = events_path
    t0 = time.perf_counter()
    engine = ServingEngine(
        "dlrover_tpu.rl.generation_service:tiny_llama_factory",
        max_new_tokens=sizes["max_new"],
        temperature=0.0,
        factory_kwargs=dict(model, dtype="bfloat16"),
        name=f"smoke-{backend}-{os.getpid()}",
        num_replicas=1,
        max_slots=sizes["max_slots"],
        block_size=sizes["block_size"],
        num_blocks=sizes["num_blocks"],
        max_seq_len=sizes["max_seq_len"],
        prefill_chunk=sizes["prefill_chunk"],
        start_timeout=600.0,
        capture_logprobs=True,
    )
    try:
        ready_s = time.perf_counter() - t0
        engine.sync_weights(weights)
        t0 = time.perf_counter()
        ids = [
            engine.submit(p, max_new=sizes["max_new"], seed=i)
            for i, p in enumerate(prompts)
        ]
        results = [engine.result(i, timeout=600.0) for i in ids]
        serve_s = time.perf_counter() - t0
        completed = engine.status()["completed"]
    finally:
        engine.close()
    _require(
        not backend_initialized(),
        "the serving parent initialised a JAX backend",
    )
    _require(
        len(set(ids)) == len(prompts) and completed == len(prompts),
        f"{completed} completions for {len(prompts)} requests",
    )
    events = events_path
    spans = [e["labels"]["req_id"] for e in _named(events, "serve_request")]
    _require(
        sorted(spans) == sorted(ids),
        f"serve_request spans {sorted(spans)} != submitted {sorted(ids)}",
    )
    for p, r in zip(prompts, results):
        _require(
            r["new_tokens"] == sizes["max_new"]
            and r["tokens"].size == p.size + sizes["max_new"]
            and (r["tokens"][: p.size] == p).all(),
            f"request answered with {r['new_tokens']} new tokens",
        )
        _require(r["version"] == 1, "answered with unpublished weights")
        _require(
            r["logprobs"].size == sizes["max_new"]
            and all(math.isfinite(x) and x <= 0 for x in r["logprobs"]),
            "logprobs missing or not finite",
        )
    reports = [e["labels"] for e in _named(events, "device_report")]
    _require(len(reports) == 2, f"{len(reports)} replica device reports")
    ready, drained = reports
    for labels in reports:
        _require_device(labels, expect_platform)
    _require(
        ready["kernel_backend"] == backend,
        f"replica traced the {ready['kernel_backend']!r} paged backend",
    )
    if backend == "pallas":
        _require(
            bool(ready["interpret"]) == (expect_platform != "tpu"),
            f"replica interpret mode = {ready['interpret']}",
        )
    counts = json.loads(drained["compile_counts"])
    _require(counts["decode"] == 1, f"compile counts {counts}")
    return dict(
        device={
            k: ready[k] for k in ("platform", "device_kind", "device_count")
        },
        ready_s=round(ready_s, 2),
        serve_s=round(serve_s, 2),
        compile_counts=counts,
        results=results,
    )


def phase_serve(sizes, seed, expect_platform, workdir):
    """ServingEngine + one replica subprocess: Pallas paged backend,
    then (after that replica exited) the jnp backend on the same
    requests and weights."""
    import numpy as np

    socks = tempfile.mkdtemp(prefix="cs-")
    os.environ["DLROVER_TPU_SOCKET_DIR"] = socks
    try:
        model = _model_kwargs(sizes["model"])
        weights = _seeded_weights(model, seed)
        rng = np.random.default_rng(seed + 1)
        prompts = [
            rng.integers(
                0, model["vocab_size"],
                size=int(rng.integers(
                    sizes["prompt_min"], sizes["prompt_max"] + 1
                )),
            ).astype(np.int32)
            for _ in range(sizes["requests"])
        ]
        legs = {
            backend: _serve_leg(
                sizes, model, backend, weights, prompts,
                os.path.join(workdir, f"serve-{backend}.jsonl"),
                expect_platform,
            )
            for backend in ("pallas", "jnp")
        }
    finally:
        shutil.rmtree(socks, ignore_errors=True)
    same = total = 0
    worst = 0.0
    for a, b in zip(legs["pallas"]["results"], legs["jnp"]["results"]):
        ta, tb = (r["tokens"][-sizes["max_new"]:] for r in (a, b))
        agree = ta == tb
        same += int(agree.sum())
        total += agree.size
        common = agree.size if agree.all() else int(agree.argmin())
        if common:
            worst = max(
                worst,
                float(
                    np.abs(
                        a["logprobs"][:common] - b["logprobs"][:common]
                    ).max()
                ),
            )
    _require(
        worst <= LOGPROB_TOL,
        f"pallas vs jnp logprobs differ by {worst:.4f} > {LOGPROB_TOL}",
    )
    pallas = legs["pallas"]
    return dict(
        device=pallas["device"],
        compile_s=pallas["ready_s"],
        asserted=(
            f"{len(prompts)} requests each completed exactly once with "
            f"{sizes['max_new']} new tokens under both paged backends; "
            f"replica traced pallas (compiled: "
            f"{expect_platform == 'tpu'}), decode compiled once; "
            f"pallas vs jnp logprobs within {LOGPROB_TOL} over the "
            f"common prefixes"
        ),
        max_logprob_diff=round(worst, 5),
        identical_token_share=round(same / total, 4),
        replica_ready_s=dict(
            pallas=pallas["ready_s"], jnp=legs["jnp"]["ready_s"]
        ),
        serve_s=dict(pallas=pallas["serve_s"], jnp=legs["jnp"]["serve_s"]),
        compile_counts=pallas["compile_counts"],
    )


# ------------------------------------------------------------- four chips


def phase_four(sizes, seed, expect_platform, workdir):
    """The sharded train step on four chips (fsdp=2 x tensor=2, one
    process) against the one-chip run of the same seed."""
    common = [
        *sizes["model_args"],
        "--batch", str(sizes["batch"]), "--seq", str(sizes["seq"]),
        "--steps", str(sizes["steps"]), "--seed", str(seed),
        "--save_memory_interval", "100000",
    ]
    runs = {}
    for tag, extra in (
        ("one", ["--devices", "1"]),
        ("four", ["--devices", "4", "--fsdp", "2", "--tensor", "2"]),
    ):
        proc, paths = _launch_example(workdir, tag, common + extra)
        _finish_launch(proc, paths, PHASE_TIMEOUT_S["four"] / 2 - 30)
        report = _worker_reports(paths["events"], expect_platform)[0]
        rows = _curves(paths)
        _check_losses(rows, sizes["vocab_size"])
        shutil.rmtree(paths["ckpt"], ignore_errors=True)
        runs[tag] = dict(
            report=report,
            losses=[r["loss"] for r in rows],
            step_s=round(rows[-1]["step_time_s"], 4),
        )
    one, four = runs["one"], runs["four"]
    _require(
        four["report"]["device_count"] == 4
        and four["report"]["mesh_devices"] == 4,
        f"four-chip run saw {four['report']['device_count']} devices",
    )
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    _require(
        len(diffs) == sizes["steps"] and max(diffs) <= LOSS_TOL,
        f"losses differ by {max(diffs):.4f} > {LOSS_TOL}: "
        f"{one['losses']} vs {four['losses']}",
    )
    report = four["report"]
    state, shard = report["state_bytes"], report["state_shard_bytes"]
    _require(
        shard <= 0.3 * state,
        f"state shard {shard} B is not ~1/4 of {state} B",
    )
    in_use = json.loads(report["device_bytes_in_use"])
    if expect_platform == "tpu":
        # what the devices really hold: every device about its quarter
        # of the state (plus a batch and scratch), none of them all of it
        _require(
            all(isinstance(x, int) for x in in_use)
            and max(in_use) <= 0.5 * state
            and min(in_use) >= 0.5 * shard,
            f"per-device bytes in use {in_use} vs state {state}",
        )
    collectives = json.loads(report["collectives"])
    _require(
        sum(collectives.values()) > 0,
        "the compiled sharded step holds no collective",
    )
    return dict(
        device={
            k: report[k] for k in ("platform", "device_kind", "device_count")
        },
        compile_s=report["compile_s"],
        asserted=(
            f"fsdp=2 x tensor=2 on 4 devices vs 1 device, same seed: "
            f"{len(diffs)} per-step losses within {LOSS_TOL} (max diff "
            f"{max(diffs):.4f}); state shard {shard} B of {state} B per "
            f"device; compiled step holds collectives"
        ),
        losses=dict(
            one=[round(x, 4) for x in one["losses"]],
            four=[round(x, 4) for x in four["losses"]],
        ),
        device_bytes_in_use=in_use,
        state_bytes=state,
        state_shard_bytes=shard,
        collectives=collectives,
        one_chip_compile_s=one["report"]["compile_s"],
        last_step_s=dict(one=one["step_s"], four=four["step_s"]),
    )


# ------------------------------------------------------------ the driver

ONE_CHIP_PHASES = (
    ("kernels", phase_kernels),
    ("train", phase_train),
    ("serve", phase_serve),
)
FOUR_CHIP_PHASES = (("four", phase_four),)


def _phase_child(fn, kwargs, out_path):
    """Entry point of a phase's process: own session (so the parent can
    stop everything the phase started), the repo on the import path,
    the result as a JSON file."""
    os.setsid()
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    os.chdir(REPO)
    from dlrover_tpu.common.jax_env import export_compile_cache

    # this phase and everything it starts compile into the checkout's
    # one cache directory (or the one the environment names)
    export_compile_cache(os.environ)
    result = fn(**kwargs)
    with open(out_path, "w") as f:
        json.dump(result, f)


def _run_phase(name, fn, sizes, seed, expect_platform, workdir, deadline):
    out_path = os.path.join(workdir, f"{name}.json")
    ctx = multiprocessing.get_context("spawn")
    child = ctx.Process(
        target=_phase_child,
        args=(
            fn,
            dict(
                sizes=sizes, seed=seed, expect_platform=expect_platform,
                workdir=workdir,
            ),
            out_path,
        ),
        name=f"chip-smoke-{name}",
    )
    t0 = time.perf_counter()
    child.start()
    try:
        child.join(
            max(min(PHASE_TIMEOUT_S.get(name, 600),
                    deadline - time.monotonic()), 1.0)
        )
        timed_out = child.is_alive()
    finally:
        # the phase's whole session: nothing it started outlives it
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        child.join(30)
    if timed_out:
        raise PhaseFailed(f"phase {name} timed out")
    if child.exitcode != 0:
        raise PhaseFailed(f"phase {name} exited {child.exitcode}")
    with open(out_path) as f:
        result = json.load(f)
    _require_device(result["device"], expect_platform)
    return dict(
        phase=name,
        seconds=round(time.perf_counter() - t0, 2),
        **result,
    )


def run(expect_platform, sizes, seed=0, phases=ONE_CHIP_PHASES, out=None):
    """Run ``phases`` one after another; print one JSON line each and
    the final ok line.  Returns the process exit code."""
    from dlrover_tpu.common.jax_env import backend_initialized

    out = out or sys.stdout
    held_before = backend_initialized()  # a test's process may; we never
    workdir = os.path.join(REPO, ".cache", "chip_smoke")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    device = None
    deadline = time.monotonic() + TOTAL_TIMEOUT_S
    for name, fn in phases:
        try:
            line = _run_phase(
                name, fn, sizes.get(name), seed, expect_platform, workdir,
                deadline,
            )
        except PhaseFailed as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
            return 1
        device = line["device"]
        print(json.dumps(line), file=out, flush=True)
    if backend_initialized() and not held_before:
        print(
            "chip_smoke: FAILED: the parent initialised a JAX backend",
            file=sys.stderr,
        )
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device["platform"],
                    "kind": device["device_kind"],
                    "count": device["device_count"],
                },
            }
        ),
        file=out,
        flush=True,
    )
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run ONLY the four-chip sharded train step and its "
        "one-chip comparison",
    )
    args = parser.parse_args(argv)
    return run(
        "tpu",
        REAL_SIZES,
        seed=args.seed,
        phases=FOUR_CHIP_PHASES if args.four_chips else ONE_CHIP_PHASES,
    )


if __name__ == "__main__":
    sys.exit(main())
