"""Llama pretraining with the full stack: auto_accelerate + Trainer +
flash checkpoint + elasticity.

Run elastic on one host (8 virtual devices for CI; real chips on TPU —
one worker process drives every chip of its host):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m dlrover_tpu.run --nnodes=1 --nproc_per_node=1 \
        examples/llama_pretrain.py --steps 50

The strategy engine picks the mesh (DP for small configs, FSDP/TP as
the model grows); pass --fsdp/--tensor to pin one.  ``--preset
llama2_7b --layers N`` runs the published 7B widths at a depth that
fits the chip (what ``chip_smoke.py`` drives).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument(
        "--preset", default="", choices=("", "llama2_7b"),
        help="take every width from LlamaConfig.<preset> (dim, heads, "
        "mlp, vocab); --layers still cuts the depth",
    )
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument(
        "--seed", type=int, default=0,
        help="seeds the weights and the token stream",
    )
    p.add_argument("--save_memory_interval", type=int, default=10)
    p.add_argument("--save_storage_interval", type=int, default=25)
    p.add_argument(
        "--curves", action="store_true",
        help="append every step's loss/step time to "
        "<ckpt_dir>/curves/train_log.jsonl (implied by --eval_interval)",
    )
    p.add_argument("--fsdp", type=int, default=0)
    p.add_argument("--tensor", type=int, default=0)
    p.add_argument(
        "--devices", type=int, default=0,
        help="build the mesh over the first N devices only (0 = all)",
    )
    p.add_argument(
        "--eval_interval", type=int, default=0,
        help="evaluate on a held-out set every N steps (0 = off); "
        "curves land in <ckpt_dir>/curves/train_log.jsonl",
    )
    p.add_argument(
        "--ckpt_dir", default="/tmp/dlrover_tpu_llama_ckpt"
    )
    return p.parse_args()


def device_report_callback(meter, fns, batch_shape, mesh_devices):
    """After this incarnation's first step (the one that compiles),
    put on the timeline which device the worker really runs on and
    what the compile cost: persistent-cache hits and misses and the
    seconds spent in backend compiles.  A restarted worker that finds
    the cache warm shows hits here and a short first step.  On a
    multi-device mesh the record also carries each device's bytes in
    use (is the state really sharded?) and the collectives the
    compiled step holds."""
    import json

    import jax

    from dlrover_tpu.common.jax_env import device_report
    from dlrover_tpu.observability.events import get_event_logger
    from dlrover_tpu.trainer.callbacks import TrainerCallback

    def collectives():
        state_shape = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sh
            ),
            fns.state_shape,
            fns.state_shardings,
        )
        text = fns.train_step.lower(
            state_shape, batch_shape
        ).compile().as_text()
        return {
            op: text.count(f" {op}(")
            for op in (
                "all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute",
            )
        }

    def state_bytes():
        """(whole train state, one device's shard of it) in bytes,
        from the shardings the step was compiled with."""
        total = shard = 0
        for s, sh in zip(
            jax.tree_util.tree_leaves(fns.state_shape),
            jax.tree_util.tree_leaves(fns.state_shardings),
        ):
            total += int(np.prod(s.shape)) * s.dtype.itemsize
            shard += (
                int(np.prod(sh.shard_shape(s.shape))) * s.dtype.itemsize
            )
        return total, shard

    class DeviceReport(TrainerCallback):
        done = False

        def on_step_end(self, step, metrics):
            if self.done:
                return
            self.done = True
            device = device_report()
            compiles = meter.snapshot()
            extra = {}
            if mesh_devices > 1:
                extra["state_bytes"], extra["state_shard_bytes"] = (
                    state_bytes()
                )
                extra["device_bytes_in_use"] = json.dumps(
                    [
                        (d.memory_stats() or {}).get("bytes_in_use")
                        for d in jax.local_devices()
                    ]
                )
                extra["collectives"] = json.dumps(collectives())
            get_event_logger().instant(
                "device_report",
                platform=device["platform"],
                device_kind=device["device_kind"],
                device_count=device["device_count"],
                mesh_devices=mesh_devices,
                step=step,
                first_step_s=metrics["step_time_s"],
                cache_hits=compiles["cache_hits"],
                cache_misses=compiles["cache_misses"],
                compile_s=compiles["compile_s"],
                **extra,
            )

    return DeviceReport()


def main():
    args = parse_args()

    from dlrover_tpu.common.jax_env import install_compile_meter
    from dlrover_tpu.trainer.elastic import init_distributed

    ctx = init_distributed()
    # the process's one meter, which ``init_distributed`` installed
    # before the first compile of this process
    meter = install_compile_meter()

    import jax

    from dlrover_tpu.accelerate import auto_accelerate, load_strategy
    from dlrover_tpu.models.llama import (
        LlamaConfig,
        init_params,
        loss_fn,
        param_logical_axes,
    )
    from dlrover_tpu.optimizers import agd
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    if args.preset:
        cfg = getattr(LlamaConfig, args.preset)(
            n_layers=args.layers, max_seq_len=args.seq
        )
    else:
        cfg = LlamaConfig(
            vocab_size=4096,
            dim=args.dim,
            n_layers=args.layers,
            n_heads=args.heads,
            n_kv_heads=max(args.heads // 2, 1),
            mlp_dim=args.dim * 3,
            max_seq_len=args.seq,
        )
    devices = jax.devices()[: args.devices or None]
    strategy = None
    if args.fsdp or args.tensor:
        n = len(devices)
        fsdp = args.fsdp or 1
        tensor = args.tensor or 1
        strategy = load_strategy(
            {
                "data": n // (fsdp * tensor),
                "fsdp": fsdp,
                "tensor": tensor,
            }
        )
    result = auto_accelerate(
        loss_fn=lambda p, b: loss_fn(p, b, cfg),
        optimizer=agd(args.lr),
        init_params_fn=lambda rng: init_params(rng, cfg),
        param_axes=param_logical_axes(cfg),
        load_strategy=strategy,
        devices=devices,
    )
    print(
        f"strategy: {result.strategy.describe()} | "
        f"params: {result.profile.num_params:,}",
        flush=True,
    )

    # a restarted worker draws fresh batches: replaying the ones the
    # restored model already trained on would score as memorised text
    rng = np.random.default_rng([args.seed, ctx.restart_count])

    def data_iter():
        while True:
            yield {
                "tokens": rng.integers(
                    0, cfg.vocab_size,
                    size=(args.batch, args.seq + 1),
                    dtype=np.int32,
                )
            }

    def eval_iter():
        # fixed held-out set (seeded separately from training data)
        eval_rng = np.random.default_rng(12345)
        for _ in range(4):
            yield {
                "tokens": eval_rng.integers(
                    0, cfg.vocab_size,
                    size=(args.batch, args.seq + 1),
                    dtype=np.int32,
                )
            }

    callbacks = [
        device_report_callback(
            meter,
            result.fns,
            {
                "tokens": jax.ShapeDtypeStruct(
                    (args.batch, args.seq + 1),
                    np.int32,
                    sharding=result.fns.batch_sharding,
                )
            },
            len(devices),
        )
    ]
    if (
        (args.eval_interval or args.curves)
        and args.ckpt_dir
        and ctx.rank == 0
    ):
        # rank-0 only: every rank appending to one shared jsonl would
        # interleave duplicate records (see callbacks.py docstring)
        from dlrover_tpu.trainer.callbacks import JsonlLoggerCallback

        callbacks.append(
            JsonlLoggerCallback(
                os.path.join(args.ckpt_dir, "curves")
            )
        )
    trainer = Trainer(
        result,
        TrainingArgs(
            max_steps=args.steps,
            checkpoint_dir=args.ckpt_dir,
            save_memory_interval=args.save_memory_interval,
            save_storage_interval=args.save_storage_interval,
            log_interval=10,
            micro_batch_size=args.batch,
            eval_interval=args.eval_interval,
        ),
        data_iter,
        rng_seed=args.seed,
        eval_iter_fn=eval_iter,
        callbacks=callbacks,
    )
    summary = trainer.train()
    if args.eval_interval:
        if summary["final_step"] % args.eval_interval == 0:
            # the in-train cadence already evaluated at the final step
            print("final eval: covered by in-train cadence", flush=True)
        else:
            final_eval = trainer.evaluate()
            print(f"final eval: {final_eval}", flush=True)
    print(f"done: {summary}", flush=True)


if __name__ == "__main__":
    main()
