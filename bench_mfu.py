"""Training-throughput bench: tokens/sec + MFU of the flagship llama.

The reference's headline story is goodput on large LLM training
(`README.md:56-58`: 95% goodput on GLM-65B); goodput is only meaningful
relative to a healthy training rate, so this bench measures the raw
model-step throughput of the framework's own train path — the jitted
sharded train step produced by ``build_train_step`` (the
``auto_accelerate`` artifact), flash attention and remat on, bf16
matmuls with fp32 accumulation, donated buffers.

Method: pick the largest candidate config that fits the chip (OOM falls
back to the next size), run warmup then ~10 timed steps
completion-to-completion, report

- ``tokens_per_sec``  — batch*seq / mean step wall-clock
- ``mfu``             — model FLOPs (6N per token + causal attention
                        term 6*L*d*S per token) / step time / chip peak
- ``hfu``             — hardware FLOPs from the compiled step's XLA
                        cost analysis / step time / chip peak (null
                        when the census undercounts — XLA prices a
                        lax.scan body once, not per trip)

Timing is differential — two chained runs of different step counts,
each ended by ``block_until_ready`` on the last step's metrics; the
slope cancels the fixed dispatch overhead.

Prints ONE JSON line standalone; ``bench.py`` runs it as a subprocess
and merges the result into its extras.  ``vs_baseline`` is mfu/0.40 —
0.40 MFU being the well-tuned-LLM-training bar the reference's GPU
numbers represent (the reference publishes goodput, not MFU, so parity
is "reference-class utilization").
"""

import os
import argparse
import json
import sys
import time


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        print(f"ignoring malformed {name}", file=sys.stderr)
        return default


def _parse_json_line(stdout: str):
    """Last parseable JSON object line of ``stdout``, or None (a stray
    '{'-prefixed log line must not mask a valid result)."""
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _chip_peak_flops(device) -> tuple:
    """(peak bf16 FLOP/s, kind string) for the attached chip — ONE
    table (``observability/profiler.py``) shared with the live
    per-node MFU gauge, so the bench and the running job can never
    disagree about what "peak" means.  A kind the table does not know
    raises (CPU runs name a peak through ``DLROVER_TPU_PEAK_FLOPS``)."""
    from dlrover_tpu.observability.profiler import device_peak_flops

    kind = str(getattr(device, "device_kind", "")).lower()
    return device_peak_flops(device), kind


def _candidates(on_tpu: bool):
    """(name, cfg_kwargs, batch, seq, steps) from largest to smallest."""
    if not on_tpu:
        return [
            (
                "tiny-ci",
                dict(
                    vocab_size=256, dim=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                    remat="dots",
                ),
                4, 128, 3,
            )
        ]
    # head_dim 128 throughout (dim/heads): the MXU's lane width — a
    # 64-wide head leaves half the systolic array idle in attention.
    # Entries: (name, cfg kwargs, batch, seq, steps[, optimizer]);
    # optimizer "int8" = the framework's quantized-moment AdamW
    # (1 byte/param/moment) — what lets ~1B-param configs fit a 16 GB
    # chip with fp32 master weights.
    # ce_chunk_rows=4096: measured best fused-CE chunk on v5e (fewer
    # scan trips over the lm head; 0.5154 vs 0.5129 MFU at 512)
    common = dict(
        vocab_size=32000, max_seq_len=2048, remat="dots",
        ce_chunk_rows=4096,
    )
    return [
        # headline candidates: best throughput config first
        ("llama-0.6b",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=8, mlp_dim=5504), 8, 2048, 10),
        ("llama-0.3b",
         dict(common, dim=1024, n_heads=8, n_kv_heads=8,
              n_layers=12, mlp_dim=2816), 8, 2048, 10),
        ("llama-0.3b-remat",
         dict(common, dim=1024, n_heads=8, n_kv_heads=8,
              n_layers=12, mlp_dim=2816, remat="full"), 4, 2048, 10),
        # scale proofs (run separately, attached to extras): ~1B-param
        # configs that fit 16 GB HBM via the framework's int8-moment
        # optimizer + full remat; the small CE chunk trades the 0.5%
        # throughput of 4096 for ~1 GB of fit headroom
        ("llama-1.4b-int8opt",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=24, mlp_dim=5504, remat="full",
              ce_chunk_rows=512),
         8, 2048, 10, "int8"),
        ("llama-0.9b-int8opt",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=16, mlp_dim=5504, remat="full",
              ce_chunk_rows=512),
         8, 2048, 10, "int8"),
        # host-offload proof: ~1.75B params on one 16 GB chip — bf16
        # compute params in HBM, fp32 master+moments in the TPU host's
        # RAM as pinned_host chunks (optimizers/host_offload.py; ref
        # adam_offload.py).  fp32 resident state alone (28 GB) would
        # be ~2x HBM.  Measured r4: 5.0 s/step, MFU 0.19 — the
        # op_time report attributes ~59% of device time to the 24
        # B/param/step chunk DMA at ~14 GB/s (PCIe-bound, as the
        # reference's offload is); the proof is FITTING, not speed.
        ("llama-1.8b-offload",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=32, mlp_dim=5504, remat="full",
              ce_chunk_rows=512),
         8, 2048, 6, "offload"),
        # same model, int8-quantized offloaded moments: halves the
        # PCIe stream the fp32 proof is bound by (~24 -> ~13
        # B/param/step).  Measured r4: 3.69 s/step, MFU 0.255 (vs
        # 5.04 / 0.187 fp32; copy share 59% -> 34%)
        ("llama-1.8b-offload8",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=32, mlp_dim=5504, remat="full",
              ce_chunk_rows=512),
         8, 2048, 6, "offload_int8"),
        # micro-accumulated offload: 4 microbatches of 8 per stream
        # update (effective batch 32).  The runtime executes program
        # ops strictly serially (measured r5: a straight-line
        # [matmuls + host copies] program shows ZERO overlap), so the
        # honest offload throughput lever is amortizing the chunk
        # stream over more tokens — the same economics as the
        # reference's grad-accumulated large-model recipes.  Sync
        # (non-delayed) mode: the delayed schedule's extra grads
        # buffer (+3.6 GB) does not fit at 1.8B alongside the bf16
        # accumulator.
        ("llama-1.8b-offload-m3",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=32, mlp_dim=5504, remat="full",
              ce_chunk_rows=256),
         24, 2048, 4, "offload_m3"),
        ("llama-1.8b-offload8-m3",
         dict(common, dim=2048, n_heads=16, n_kv_heads=16,
              n_layers=32, mlp_dim=5504, remat="full",
              ce_chunk_rows=256),
         24, 2048, 4, "offload_int8_m3"),
        # the 3B ceiling proof (VERDICT-r4 #2): ~3.0B params on ONE
        # 16 GB chip.  A single backward's full dW tree cannot
        # coexist with the bf16 params at this scale (measured: needs
        # ~19 GB), so the step runs the GROUPED two-pass backward
        # (build_grouped_offload_step): one dW-half at a time, group
        # A's grads staged to host between passes, int8-moment host
        # stream for the optimizer state.  The proof is FITTING +
        # loss decreasing; throughput is secondary (two forwards per
        # step by construction).
        ("llama-3b-offload8-g2",
         dict(common, dim=2560, n_heads=20, n_kv_heads=20,
              n_layers=36, mlp_dim=6912, remat="full",
              ce_chunk_rows=128),
         12, 2048, 3, "offload_int8_g2"),
        # same 3B model with the SOLVER-chosen group split
        # (accelerate.solver.solve_offload_groups): smallest N whose
        # balanced per-layer split fits the chip, embed/lm-head
        # weight charged to the first/last groups — the grouped
        # backward's group-count knob closed-loop instead of
        # hand-tuned
        ("llama-3b-offload8-gs",
         dict(common, dim=2560, n_heads=20, n_kv_heads=20,
              n_layers=36, mlp_dim=6912, remat="full",
              ce_chunk_rows=128),
         12, 2048, 3, "offload_int8_gs"),
    ]


def _llama_layer_param_counts(cfg):
    """(per-layer stacked params, embed params, lm-head params) —
    the solver's per-layer footprint input, computed analytically
    from the config (init_params' exact shapes)."""
    d, hd = cfg.dim, cfg.head_dim
    per_layer = (
        2 * d  # attn_norm + mlp_norm
        + d * cfg.n_heads * hd  # wq
        + 2 * d * cfg.n_kv_heads * hd  # wk, wv
        + cfg.n_heads * hd * d  # wo
        + 3 * d * cfg.mlp_dim  # w_gate, w_up, w_down
    )
    return per_layer, cfg.vocab_size * d, d * cfg.vocab_size


def _grouped_boundaries(cfg, suffix, batch, seq):
    """Layer split for a ``_gN``/``_gs`` candidate.  ``_g2`` keeps
    the original midpoint split (the proven-to-fit 3B config);
    larger N balances per-layer weight; ``_gs`` asks the solver for
    BOTH the group count and the split."""
    from dlrover_tpu.accelerate.analyser import ModelProfile
    from dlrover_tpu.accelerate.solver import (
        balanced_boundaries,
        solve_offload_groups,
    )

    per_layer, embed, head = _llama_layer_param_counts(cfg)
    if suffix == "2":
        return (cfg.n_layers // 2,), None
    if suffix != "s":
        return (
            balanced_boundaries(
                [per_layer] * cfg.n_layers, int(suffix),
                embed_params=embed, head_params=head,
            ),
            None,
        )
    n_params = per_layer * cfg.n_layers + embed + head
    # full (remat=none) activation footprint per sample; the solver
    # applies the remat policy's retained fraction itself
    act_per_sample = cfg.n_layers * seq * cfg.dim * 2 * 16
    profile = ModelProfile(
        num_params=n_params,
        param_bytes=4 * n_params,
        largest_leaf=0,
        leaf_count=12,
        activation_bytes_per_sample=act_per_sample,
        num_layers=cfg.n_layers,
    )
    plan = solve_offload_groups(
        profile,
        batch_per_replica=batch,
        remat=cfg.remat if cfg.remat in ("none", "dots", "full")
        else "full",
        layer_params=[per_layer] * cfg.n_layers,
        embed_params=embed,
        head_params=head,
    )
    print(f"solver group plan: {plan.describe()}", file=sys.stderr)
    return plan.boundaries, plan.describe()


def _run_candidate(
    name, cfg_kwargs, batch, seq, steps, optimizer="adamw"
) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.llama import (
        LlamaConfig,
        count_params,
        init_params,
        loss_fn,
        param_logical_axes,
    )
    from dlrover_tpu.parallel.mesh import (
        AxisName,
        create_parallel_mesh,
        destroy_parallel_mesh,
    )
    from dlrover_tpu.parallel.sharding import default_rules
    from dlrover_tpu.parallel.train_step import build_train_step

    cfg = LlamaConfig(**cfg_kwargs)
    destroy_parallel_mesh()
    group_plan = None
    if optimizer.startswith("offload"):
        # host-offload path: single-chip by design (no mesh — on pods
        # the state shards over fsdp instead); bf16 params in HBM,
        # fp32 master (+ fp32 or int8 moments) in host DRAM, streamed
        # chunk updates
        from dlrover_tpu.optimizers.host_offload import (
            HostOffloadAdamW,
            build_offloaded_train_step,
        )

        group_suffix = None
        if "_g" in optimizer:
            tail = optimizer.rsplit("_g", 1)[1]
            if tail == "s" or tail.isdigit():
                group_suffix = tail
        if group_suffix is not None:
            from dlrover_tpu.models.llama import (
                init_ngrouped_params,
                loss_fn_ngrouped,
            )
            from dlrover_tpu.optimizers.host_offload import (
                build_grouped_offload_step,
            )

            boundaries, group_plan = _grouped_boundaries(
                cfg, group_suffix, batch, seq
            )
            init_fns = init_ngrouped_params(
                jax.random.PRNGKey(0), cfg, boundaries
            )
            opt_kw = dict(
                learning_rate=3e-4,
                moments="int8" if "int8" in optimizer else "fp32",
                chunk_elems=_env_int(
                    "BENCH_OFFLOAD_CHUNK", 16 * 1024 * 1024
                ),
            )
            init_state_fn, offload_step = (
                build_grouped_offload_step(
                    lambda *args: loss_fn_ngrouped(
                        args[:-1], args[-1], cfg
                    ),
                    init_fns=init_fns,
                    optimizers=[
                        HostOffloadAdamW(**opt_kw) for _ in init_fns
                    ],
                )
            )
            state = init_state_fn(None)
            jax.block_until_ready(tuple(s.params for s in state))
            n_params = sum(count_params(s.params) for s in state)

            class _GroupedFns:
                train_step = staticmethod(offload_step)
                batch_sharding = None

            fns = _GroupedFns()
        else:
            micro = (
                int(optimizer.rsplit("_m", 1)[1])
                if "_m" in optimizer
                else 1
            )
            init_state_fn, offload_step = build_offloaded_train_step(
                lambda p, b: loss_fn(p, b, cfg),
                lambda rng: init_params(rng, cfg),
                HostOffloadAdamW(
                    learning_rate=3e-4,
                    moments=(
                        "int8" if "int8" in optimizer else "fp32"
                    ),
                    # 32M-elem chunks bound the fused step's
                    # in-flight fp32 transient (window * ~5 chunk
                    # buffers); 64M chunks at window 2 still exceeded
                    # HBM at 1.8B.  Accumulated configs shave the
                    # last few hundred MB with 16M-elem chunks.
                    chunk_elems=_env_int(
                        "BENCH_OFFLOAD_CHUNK",
                        (16 if "_m" in optimizer else 32)
                        * 1024 * 1024,
                    ),
                ),
                # accumulated configs pair the micro-grad program
                # with the CHUNKED per-program update stream: the
                # one-program fused form must co-reserve the
                # accumulator, per-micro grads and both param
                # generations and exceeds HBM at 1.8B (measured)
                mode="chunked" if micro > 1 else "auto",
                micro_steps=micro,
            )
            state = init_state_fn(jax.random.PRNGKey(0))
            jax.block_until_ready(state.params)
            n_params = count_params(state.params)

            class _OffloadFns:
                train_step = staticmethod(offload_step)
                batch_sharding = None

            fns = _OffloadFns()
    else:
        ctx = create_parallel_mesh(
            [(AxisName.DATA, len(jax.devices()))],
            devices=jax.devices(),
        )
        rules = default_rules(fsdp=False)
        if optimizer == "int8":
            from dlrover_tpu.optimizers import quantized_moments

            opt = quantized_moments(3e-4)
        else:
            opt = optax.adamw(3e-4)
        fns = build_train_step(
            loss_fn=lambda p, b: loss_fn(p, b, cfg),
            optimizer=opt,
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
            mesh_ctx=ctx,
            rules=rules,
        )
        state = fns.init_state(jax.random.PRNGKey(0))
        jax.block_until_ready(state)
        n_params = count_params(state["params"])

    tokens = jax.device_put(
        jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0,
            cfg.vocab_size, dtype=jnp.int32,
        ),
        fns.batch_sharding,
    )
    batch_dict = {"tokens": tokens}

    # exact hardware cost of the compiled step, before any execution.
    # The offload candidate's step is a multi-jit Python function (no
    # .lower) — its census is legitimately unavailable, not a
    # failure; the result carries an EXPLICIT census marker either
    # way so trajectory tooling can tell "no data" from "no copies"
    hw_flops_per_step = 0.0
    census = "unavailable"
    if not optimizer.startswith("offload"):
        try:
            compiled = fns.train_step.lower(
                state, batch_dict
            ).compile()
            costs = compiled.cost_analysis()
            if isinstance(costs, list):
                costs = costs[0] if costs else {}
            hw_flops_per_step = float(costs.get("flops", 0.0))
            if hw_flops_per_step > 0:
                census = "ok"
        except Exception:  # noqa: BLE001
            pass

    # the state lives in a single-slot holder so run_chain can DROP
    # the entry reference before stepping: a caller-held name would
    # pin the entry params tree (3.5 GB at 1.8B) for the whole chain
    # — exactly the margin that OOMs the accumulated offload proofs
    holder = [state]
    del state

    def run_chain(n):
        """Dispatch n steps back-to-back, then wait for the last one
        (``block_until_ready`` on its metrics: a data dependency on the
        whole chain).
        The state is passed as a consumed temporary (slot.pop() IN the
        call): a loop variable would pin each step's entry params for
        the duration of the call — the offload steps rely on the old
        params freeing the moment backward completes."""
        t0 = time.perf_counter()
        m = None
        for _ in range(n):
            new_st, m = fns.train_step(holder.pop(), batch_dict)
            holder.append(new_st)
            # drop the name NOW: keeping it bound through the next
            # call would pin the previous state (params and all)
            # for that call's entire dispatch — at 3B that margin
            # is the difference between fitting and OOM
            del new_st
        jax.block_until_ready(m)
        return time.perf_counter() - t0, float(m["loss"])

    t_compile0 = time.perf_counter()
    warmup_t, _ = run_chain(2)  # first call compiles
    warmup_s = time.perf_counter() - t_compile0

    # differential timing: two chain lengths share the same fixed
    # dispatch overhead; the slope is the pure step time
    n_short = 2
    n_long = n_short + steps
    t_short, _ = run_chain(n_short)
    t_long, loss = run_chain(n_long)
    state = holder.pop()
    step_s = max((t_long - t_short) / (n_long - n_short), 1e-9)

    tokens_per_step = batch * seq
    # model FLOPs: 6N per token + causal attention 12*L*d*S/2 per token
    model_flops_per_token = (
        6.0 * n_params + 6.0 * cfg.n_layers * cfg.dim * seq
    )
    model_flops_per_step = model_flops_per_token * tokens_per_step
    peak, chip = _chip_peak_flops(jax.devices()[0])
    peak_total = peak * len(jax.devices())

    # runtime per-op timing (xpu_timer analog): trace 2 steps, report
    # time shares by HLO category + GEMM clusters by shape.  Gated off
    # on CPU (no device op tracks) and by BENCH_OP_TRACE=0.
    op_time = None
    if (
        jax.default_backend() == "tpu"
        and os.environ.get("BENCH_OP_TRACE", "1") != "0"
    ):
        try:
            from dlrover_tpu.observability.trace import (
                capture_op_profile,
            )

            report = capture_op_profile(
                fns.train_step, state, batch_dict, steps=2, warmup=0
            )
            if report.total_device_us:
                op_time = report.summary(top_k=5)
        except Exception as e:  # noqa: BLE001 - observability only
            print(f"op trace capture failed: {e}", file=sys.stderr)

    destroy_parallel_mesh()
    return {
        "config": name,
        "params_m": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "steps_timed": steps,
        "step_time_s": round(step_s, 4),
        "tokens_per_sec": round(tokens_per_step / step_s, 1),
        # XLA's cost analysis counts a lax.scan body ONCE (trip count
        # is opaque to it), so it undercounts the layer stack; report
        # hfu only when the census plausibly covers the model flops.
        # "census" says WHY hfu may be null: "unavailable" = the step
        # never went through .lower() (multi-jit offload step) or
        # cost analysis failed — no data, not zero copies.
        "mfu": round(model_flops_per_step / step_s / peak_total, 4),
        "hfu": round(hw_flops_per_step / step_s / peak_total, 4)
        if hw_flops_per_step > model_flops_per_step
        else None,
        "census": census,
        "group_plan": group_plan,
        "model_tflops_per_step": round(model_flops_per_step / 1e12, 2),
        "hw_tflops_per_step": round(hw_flops_per_step / 1e12, 2),
        "warmup_s": round(warmup_s, 1),
        "final_loss": round(loss, 4),
        "chip": chip,
        "peak_tflops": round(peak / 1e12, 1),
        "optimizer": optimizer,
        "backend": jax.default_backend(),
        "op_time": op_time,
    }


def run_offload_dma_compare(on_tpu: bool) -> dict:
    """Serial vs double-buffered offload DMA on the chunk-streamed
    update path: the same synthetic offloaded step timed with the
    rolling prefetch window ON (default) and OFF
    (``DLROVER_TPU_OFFLOAD_BUFFERED=0`` — the one-shot legacy
    pipeline), each with its census ``copy`` share from the runtime
    op trace.  On backends without device op tracks (CPU CI) the
    share is legitimately unavailable and marked explicitly."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.optimizers.host_offload import (
        HostOffloadAdamW,
        build_offloaded_train_step,
    )

    n = (64 if on_tpu else 2) * 1024 * 1024
    target = jnp.float32(1.0)

    def loss_fn(params, batch):
        pred = params["w"].astype(jnp.float32) * batch["x"]
        return jnp.mean((pred - target) ** 2)

    init_state, train_step = build_offloaded_train_step(
        loss_fn,
        lambda rng: {
            "w": jax.random.normal(rng, (n,), jnp.float32)
        },
        HostOffloadAdamW(
            learning_rate=1e-3, backend="numpy",
            chunk_elems=max(n // 8, 1),
        ),
        mode="chunked",
    )
    batch = {"x": jnp.ones((n,), jnp.float32)}

    def copy_share(state):
        if not on_tpu or os.environ.get("BENCH_OP_TRACE", "1") == "0":
            return None
        try:
            from dlrover_tpu.observability.trace import (
                capture_op_profile,
            )

            report = capture_op_profile(
                train_step, state, batch, steps=2, warmup=0
            )
            if not report.total_device_us:
                return None
            return round(
                sum(
                    us
                    for cat, us in report.by_category.items()
                    if "copy" in cat.lower()
                )
                / report.total_device_us,
                4,
            )
        except Exception as e:  # noqa: BLE001 - observability only
            print(f"offload dma trace failed: {e}", file=sys.stderr)
            return None

    prev = os.environ.get("DLROVER_TPU_OFFLOAD_BUFFERED")
    out = {"elems": n, "census": "unavailable"}
    try:
        for tag, env_val in (("buffered", "1"), ("serial", "0")):
            os.environ["DLROVER_TPU_OFFLOAD_BUFFERED"] = env_val
            state = init_state(jax.random.PRNGKey(0))
            state, _m = train_step(state, batch)  # compile + warm
            jax.block_until_ready(state.params)
            steps = 3
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = train_step(state, batch)
            float(m["loss"])  # completion barrier
            out[f"{tag}_step_s"] = round(
                (time.perf_counter() - t0) / steps, 4
            )
            share = copy_share(state)
            out[f"{tag}_copy_share"] = share
            if share is not None:
                out["census"] = "ok"
            del state
    finally:
        if prev is None:
            os.environ.pop("DLROVER_TPU_OFFLOAD_BUFFERED", None)
        else:
            os.environ["DLROVER_TPU_OFFLOAD_BUFFERED"] = prev
    if out.get("serial_step_s"):
        out["dma_speedup"] = round(
            out["serial_step_s"] / max(out["buffered_step_s"], 1e-9),
            3,
        )
    return out


WARMSTART_ENV = "DLROVER_TPU_BENCH_WARMSTART"


def _read_json_file(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _candidate_runner():
    """Child-process launcher with the warm-start plumbing: every
    candidate child shares ONE persistent ``JAX_COMPILATION_CACHE_DIR``
    (second-and-later incarnations load, not compile — production
    restart behavior) and, when available, is FORKED from a zygote
    with the jax/model import chain pre-warmed
    (``agent/zygote.py``; the fork re-applies the cache-dir env to
    ``jax.config``).  ``DLROVER_TPU_BENCH_WARMSTART=0`` kills both
    and restores plain cold subprocess spawns.

    Returns ``(run_child, close, info)``; ``run_child(extra_argv,
    timeout) -> (result_dict | None, err_tail)``."""
    import itertools
    import subprocess
    import tempfile

    script = os.path.abspath(__file__)
    warm = os.environ.get(WARMSTART_ENV, "1") != "0"
    workdir = tempfile.mkdtemp(prefix="dlrover_bench_mfu_run_")
    env = dict(os.environ)
    info = {"enabled": warm, "zygote_forks": 0}
    pool = None
    if warm:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from dlrover_tpu.common.jax_env import export_compile_cache

        cache_dir = export_compile_cache(env)
        env.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0"
        )
        info["compilation_cache_dir"] = cache_dir
        try:
            from dlrover_tpu.agent.zygote import ZygotePool

            pool = ZygotePool(
                name=f"bench_mfu_{os.getpid()}",
                preload=(
                    "jax",
                    "jax.numpy",
                    "optax",
                    "dlrover_tpu.models.llama",
                    "dlrover_tpu.optimizers.host_offload",
                ),
            )
            pool.start(env=env, wait=False)
        except Exception as e:  # noqa: BLE001 - warm start optional
            print(f"bench_mfu: no zygote ({e})", file=sys.stderr)
            pool = None

    counter = itertools.count()

    def run_child(extra_argv, timeout):
        out_file = os.path.join(
            workdir, f"child_{next(counter)}.json"
        )
        argv = [
            sys.executable, script, *extra_argv,
            "--child-out", out_file,
        ]
        if pool is not None and pool.alive:
            from dlrover_tpu.agent.zygote import ZygoteHandle

            handle = pool.spawn(argv, env)
            if isinstance(handle, ZygoteHandle):
                info["zygote_forks"] += 1
            try:
                handle.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                handle.kill()
                return None, f"timeout after {timeout}s"
            result = _read_json_file(out_file)
            if result is not None:
                return result, ""
            return None, f"rc={handle.returncode}"
        try:
            proc = subprocess.run(
                argv,
                capture_output=True,
                text=True,
                timeout=timeout,
                env=env,
            )
        except subprocess.TimeoutExpired:
            # same contract as the zygote path: a hung candidate
            # falls back to the next one, it must not abort the run
            return (
                _read_json_file(out_file),
                f"timeout after {timeout}s",
            )
        result = _read_json_file(out_file)
        if result is None:
            result = _parse_json_line(proc.stdout)
        return result, proc.stderr[-400:]

    def close():
        import shutil

        if pool is not None:
            pool.close()
        # child JSON outputs + the per-run compilation cache live
        # under workdir; an externally supplied
        # JAX_COMPILATION_CACHE_DIR is outside it and survives
        shutil.rmtree(workdir, ignore_errors=True)

    return run_child, close, info


def run_mfu() -> dict:
    """Try candidates largest-first, each in its own subprocess: a
    failed (OOM) attempt's device allocations are only reliably
    reclaimed by process exit."""
    import os
    import subprocess

    # probe the backend WITHOUT initializing jax in this process: on a
    # TPU VM libtpu is process-exclusive, so grabbing the device here
    # would starve every candidate child
    probe = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax; print(jax.default_backend())",
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    on_tpu = probe.stdout.strip().endswith("tpu")
    cands = _candidates(on_tpu)
    run_child, close_runner, warm_info = _candidate_runner()
    tpu_flag = "1" if on_tpu else "0"

    def run_one(idx, timeout=1500):
        # the 3B proof pays a long init + compile before its first
        # step — hence the generous default
        return run_child(
            ["--candidate", str(idx), "--on-tpu", tpu_flag], timeout
        )

    try:
        last_err = "no candidates"
        headline = None
        headline_idx = None
        for idx, cand in enumerate(cands):
            if len(cand) > 5:  # scale proofs run after the headline
                continue
            result, err = run_one(idx)
            if result is not None:
                headline = result
                headline_idx = idx
                break
            last_err = err
            print(
                f"bench_mfu: candidate {cand[0]} failed, falling back",
                file=sys.stderr,
            )
        if headline is None:
            raise RuntimeError(f"all candidates failed: {last_err}")
        headline["warm_start"] = warm_info
        # second incarnation of the SAME candidate: with the shared
        # compilation cache + zygote imports warm, its warmup_s is
        # what a production restart pays (compile excluded) — the
        # cold/warm pair quantifies the warm-start win.  On CPU CI
        # the rerun is opt-in (DLROVER_TPU_BENCH_WARM_RERUN=1).
        if warm_info["enabled"] and (
            on_tpu
            or os.environ.get("DLROVER_TPU_BENCH_WARM_RERUN") == "1"
        ):
            result2, _err2 = run_one(headline_idx)
            if result2 is not None:
                headline["warm_restart"] = {
                    "cold_warmup_s": headline.get("warmup_s"),
                    "warm_warmup_s": result2.get("warmup_s"),
                    "step_time_s": result2.get("step_time_s"),
                }
        # serial vs double-buffered offload DMA stream (+ census copy
        # share per mode) — the tentpole comparison, small enough to
        # run on every backend
        cmp_result, cmp_err = run_child(
            ["--offload-compare", "--on-tpu", tpu_flag], 900
        )
        headline["offload_dma"] = (
            cmp_result
            if cmp_result is not None
            else {"error": cmp_err}
        )
        if on_tpu:
            # attach the scale proofs: the largest int8-moment config
            # that fits, PLUS the host-offload config (different
            # mechanism — both are part of the single-chip scale
            # story)
            proofs = []
            seen_opts = set()
            for idx, cand in enumerate(cands):
                if len(cand) <= 5:
                    continue
                opt_kind = cand[5]
                if opt_kind in seen_opts:
                    continue  # first (largest) success per mechanism
                result, _err = run_one(idx)
                if result is not None:
                    proofs.append(result)
                    seen_opts.add(opt_kind)
            if proofs:
                headline["scale_proof"] = proofs[0]
                headline["scale_proofs"] = proofs
    finally:
        close_runner()
    return headline


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--candidate", type=int, default=None)
    parser.add_argument("--on-tpu", type=int, default=None)
    parser.add_argument(
        "--offload-compare",
        action="store_true",
        help="child mode: serial vs double-buffered offload DMA",
    )
    parser.add_argument(
        "--child-out",
        default=None,
        help="child mode: also write the result JSON here (zygote-"
        "forked children have no captured stdout pipe)",
    )
    parser.add_argument(
        "--out",
        default="BENCH_OUT.json",
        help="write the result JSON here as well as stdout (parent "
        "mode only; the driver's stdout tail capture can truncate, "
        "a file cannot)",
    )
    args = parser.parse_args()
    import os

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    def _finish_child(result) -> int:
        print(json.dumps(result), flush=True)
        if args.child_out:
            try:
                with open(args.child_out, "w") as f:
                    json.dump(result, f)
            except OSError:
                pass
        return 0

    if args.candidate is not None or args.offload_compare:
        # child mode: run exactly one probe in this process; the
        # candidate list comes from the PARENT's backend decision so
        # both sides index the same list even if this child's backend
        # resolution differs
        if args.on_tpu is not None:
            on_tpu = bool(args.on_tpu)
        else:
            import jax

            on_tpu = jax.default_backend() == "tpu"
        if args.offload_compare:
            return _finish_child(run_offload_dma_compare(on_tpu))
        cands = _candidates(on_tpu)
        return _finish_child(_run_candidate(*cands[args.candidate]))

    if args.out:
        # early stub: a harness timeout mid-run leaves a parseable
        # artifact naming the phase that died, not an absent file
        try:
            with open(args.out, "w") as f:
                json.dump(
                    {
                        "metric": "train_mfu",
                        "value": None,
                        "extras": {"status": "running"},
                    },
                    f,
                )
        except OSError:
            pass
    result = run_mfu()
    payload = {
        "metric": "train_mfu",
        "value": result["mfu"],
        "unit": "fraction_of_peak",
        "vs_baseline": round(result["mfu"] / 0.40, 3),
        "extras": result,
    }
    print(json.dumps(payload), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
