"""The benchmark's training worker: user code of the framework, started
by ``python -m dlrover_tpu.run`` like any other.

After the pretraining example under ``examples/`` (auto_accelerate +
Trainer + flash checkpoint + elasticity, ``agd`` optimizer, uniformly
random tokens).  What differs, and why it lives here and not in the
example: the model's widths come from a configuration FILE of the
benchmark, and the model itself from the family module that file names;
the step times, the device, the memory peak, the reference loss and the
profiler window are taken by a callback of the benchmark's own and
written to
``<run_dir>/worker.jsonl``; and the worker stops when the runner drops
``<run_dir>/stop`` (or at ``--stop_after_s``, so that an orphan ends).
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import numpy as np

import harness
import metrics


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--run_dir", required=True)
    p.add_argument("--ckpt_dir", required=True)
    p.add_argument("--expect_platform", required=True)
    p.add_argument("--batch", type=int, required=True)
    p.add_argument("--seq", type=int, required=True)
    p.add_argument("--lr", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--save_memory_interval", type=int, required=True)
    p.add_argument("--devices", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=0)
    p.add_argument("--tensor", type=int, default=0)
    p.add_argument("--stop_after_s", type=float, required=True)
    p.add_argument("--reference", type=int, default=1)
    p.add_argument("--trace_dir", default="")
    p.add_argument("--trace_after_step", type=int, default=0)
    p.add_argument("--trace_phase", type=int, default=0)
    p.add_argument("--trace_steps", type=int, default=5)
    return p.parse_args()


class Rows:
    def __init__(self, run_dir, inc):
        self._path = os.path.join(run_dir, "worker.jsonl")
        self._inc = inc

    def write(self, kind, **fields):
        metrics.append_jsonl(
            self._path,
            dict(kind=kind, inc=self._inc, pid=os.getpid(), **fields),
        )


def bench_callback(
    args, cfg, fam, forward, rows, meter, device, training_args, first_batch
):
    import jax
    import jax.numpy as jnp

    import onchip
    from dlrover_tpu.trainer.callbacks import TrainerCallback

    stop_file = os.path.join(args.run_dir, "stop")
    deadline = time.time() + args.stop_after_s
    snap = args.save_memory_interval

    class Bench(TrainerCallback):
        trainer = None
        reported = False
        stopping = False
        trace_stop_at = 0  # >0 while the profiler is open
        traced = False
        start_step = 0

        def on_train_begin(self, start_step):
            self.start_step = start_step
            rows.write("begin", start_step=start_step, t=time.time())
            if args.reference and start_step == 0:
                self._reference(self.trainer.state["params"])

        def _reference(self, params):
            """The first batch on the seeded weights, before any update:
            per-token logprobs of the program's forward (its compute
            type, its attention) against the plain float32 reference's,
            and the reference's mean loss for the runner to hold the
            first step's logged loss against."""
            def program(p, t):
                logp = jax.nn.log_softmax(forward(p, t[:, :-1]), -1)
                return jnp.take_along_axis(logp, t[:, 1:, None], -1)[..., 0]

            t0 = time.time()
            ref = jax.jit(
                lambda p, t: fam.token_logprobs(p, t, cfg)
            )(params, first_batch)
            got = jax.jit(program)(params, first_batch)
            rows.write(
                "reference",
                loss=float(-jnp.mean(ref)),
                max_token_diff=float(jnp.max(jnp.abs(got - ref))),
                seconds=time.time() - t0,
            )

        def on_step_end(self, step, metrics):
            now = time.time()
            rows.write("step", step=step, t=now, loss=metrics["loss"])
            if not self.reported:
                self.reported = True
                rows.write(
                    "device",
                    step=step,
                    first_step_s=metrics["step_time_s"],
                    **device,
                    **meter.snapshot(),
                )
            self._trace(step)
            if not self.stopping and (
                now > deadline or os.path.exists(stop_file)
            ):
                # the loop has already dispatched step + 1; it ends there
                self.stopping = True
                training_args.max_steps = step

        def _trace(self, step):
            if not args.trace_dir or self.traced:
                return
            if self.trace_stop_at:
                if step >= self.trace_stop_at:
                    jax.profiler.stop_trace()
                    self.traced = True
                    rows.write(
                        "trace", dir=args.trace_dir, t1=time.time(),
                        stop_step=step,
                    )
                return
            if (
                step >= max(args.trace_after_step, self.start_step + 10)
                and step % snap == args.trace_phase
            ):
                onchip.start_trace(args.trace_dir)
                self.trace_stop_at = step + args.trace_steps

        def on_train_end(self, summary):
            if self.trace_stop_at and not self.traced:
                jax.profiler.stop_trace()
            rows.write(
                "end", t=time.time(), final_step=summary["final_step"],
                memory_peak_bytes=onchip.memory_peak_bytes(),
            )

    return Bench()


def main():
    args = parse_args()
    with open(args.config) as f:
        cfg = json.load(f)

    from dlrover_tpu.common.jax_env import CompileMeter, device_report
    from dlrover_tpu.trainer.elastic import init_distributed

    meter = CompileMeter()  # before the first compile of this process
    ctx = init_distributed()
    rows = Rows(args.run_dir, ctx.restart_count)

    import jax

    device = device_report()
    if (
        device["platform"] != args.expect_platform
        or device["device_count"] < args.devices
    ):
        rows.write("fatal", what="wrong device", **device)
        sys.exit(3)

    from dlrover_tpu.accelerate import auto_accelerate, load_strategy
    from dlrover_tpu.optimizers import agd
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    fam = harness.family(cfg)
    parts = fam.train_parts(cfg, args.seq)
    devices = jax.devices()[: args.devices]
    strategy = None
    if args.fsdp or args.tensor:
        fsdp, tensor = args.fsdp or 1, args.tensor or 1
        strategy = load_strategy(
            {
                "data": len(devices) // (fsdp * tensor),
                "fsdp": fsdp,
                "tensor": tensor,
            }
        )
    result = auto_accelerate(
        loss_fn=parts["loss_fn"],
        optimizer=agd(args.lr),
        init_params_fn=parts["init_params_fn"],
        param_axes=parts["param_axes"],
        load_strategy=strategy,
        devices=devices,
    )
    print(
        f"strategy: {result.strategy.describe()} | "
        f"params: {result.profile.num_params:,}",
        flush=True,
    )

    # any --seed up to a little over 2**31 seeds both streams; a
    # restarted worker draws fresh batches (replaying the ones the
    # restored model trained on would score as memorised text)
    seed = args.seed % (2**31 - 1)

    def batches(restart_count):
        rng = np.random.default_rng([seed, restart_count])
        while True:
            yield {
                "tokens": rng.integers(
                    0, cfg["vocab_size"],
                    size=(args.batch, args.seq + 1),
                    dtype=np.int32,
                )
            }

    training_args = TrainingArgs(
        max_steps=10**9,  # the callback ends the run
        checkpoint_dir=args.ckpt_dir,
        save_memory_interval=args.save_memory_interval,
        save_storage_interval=10**9,
        log_interval=50,
        micro_batch_size=args.batch,
    )
    callback = bench_callback(
        args, cfg, fam, parts["forward"], rows, meter, device, training_args,
        next(batches(0))["tokens"],
    )
    trainer = Trainer(
        result,
        training_args,
        lambda: batches(ctx.restart_count),
        rng_seed=seed,
        callbacks=[callback],
    )
    callback.trainer = trainer
    summary = trainer.train()
    print(f"done: {summary}", flush=True)


if __name__ == "__main__":
    main()
