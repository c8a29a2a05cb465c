"""``auto_accelerate`` — one call from model to sharded train step.

Reference parity: ``atorch/atorch/auto/accelerate.py:406``
(``auto_accelerate(model, optim_func, dataset, loss_func, ...)`` →
namedtuple of transformed artifacts).  The TPU pipeline: analyse
(abstract shapes) → generate candidate meshes → optionally dry-run →
build the winning sharded train step.  Semi-auto: pass
``load_strategy=Strategy(...)`` to skip the search, exactly like the
reference's ``load_strategy`` path.
"""

from dataclasses import dataclass
from typing import Callable, Optional

import jax

from dlrover_tpu.accelerate.analyser import analyse_model
from dlrover_tpu.accelerate.strategy import Strategy, generate_candidates
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.observability.events import get_event_logger
from dlrover_tpu.parallel.mesh import create_parallel_mesh
from dlrover_tpu.parallel.sharding import default_rules
from dlrover_tpu.parallel.train_step import TrainStepFns, build_train_step


@dataclass
class AccelerateResult:
    fns: TrainStepFns
    strategy: Strategy
    mesh_ctx: object
    rules: object
    profile: object
    timings: dict
    # measurement-calibrated planner (None without a dry run): already
    # fitted on this run's timings — ``planner.plan(n_devices=256)``
    # ranks candidates at a larger target scale (profile small, plan
    # big; accelerate/dim_planner.py)
    planner: object = None


def _build_for_strategy(
    strategy: Strategy,
    loss_fn,
    optimizer,
    init_params_fn,
    param_axes,
    devices,
):
    mesh_ctx = create_parallel_mesh(
        strategy.mesh_dims(), devices=devices
    )
    if strategy.pipe > 1:
        mesh_ctx.pipeline_microbatches = (
            strategy.pipe_microbatches or 2 * strategy.pipe
        )
    rules = default_rules(**strategy.rule_flags())
    fns = build_train_step(
        loss_fn=loss_fn,
        optimizer=optimizer,
        init_params_fn=init_params_fn,
        param_axes=param_axes,
        mesh_ctx=mesh_ctx,
        rules=rules,
        num_micro_steps=strategy.num_micro_steps,
        remat=strategy.remat,
    )
    return fns, mesh_ctx, rules


def auto_accelerate(
    loss_fn: Callable,
    optimizer,
    init_params_fn: Callable,
    param_axes,
    sample_batch_fn: Optional[Callable] = None,
    devices=None,
    load_strategy: Optional[Strategy] = None,
    dry_run: bool = False,
    long_context: bool = False,
    moe: bool = False,
    batch_per_replica: int = 1,
    seq_len: int = 2048,
    global_batch: Optional[int] = None,
    tune_space: Optional[dict] = None,
    tune_budget: int = 6,
) -> AccelerateResult:
    """Args mirror ``build_train_step`` plus search knobs.

    ``batch_per_replica``/``seq_len`` describe the actual workload —
    the candidate cost model and the gradient-accumulation (micro
    step) search evaluate at these values, so passing the real numbers
    is what makes the ranking workload-aware.

    ``sample_batch_fn(batch_sharding) -> batch`` enables the timed dry
    run; without it (or with dry_run=False) the top-ranked memory-fit
    candidate wins directly.

    ``global_batch``: the user's actual (global) batch size.  The
    batch dim shards over data x fsdp, so candidates whose
    data x fsdp does not divide it are unusable — they are filtered
    out rather than discovered as a device_put error at the first
    step.

    ``tune_space`` (dry-run mode only): Strategy-field value lists,
    e.g. ``{"num_micro_steps": [1, 2, 4], "remat": ["dots", "full"]}``
    — after the mesh race picks a winner, Bayesian optimization
    (``bayes_search.tune_strategy``) spends ``tune_budget`` extra
    timed builds searching the tunables inside it.
    """
    # a training worker's ``startup`` stage between the backend's
    # initialisation and the train state (observability/events.py
    # ``STARTUP_STAGES``)
    events = get_event_logger()
    stage = events.begin("startup", stage="accelerate")
    if devices is None:
        devices = jax.devices()
    profile = analyse_model(init_params_fn, optimizer)
    timings = {}

    planner = None
    if load_strategy is not None:
        strategy = load_strategy
        # elastic re-mesh: a pinned strategy sized for the PREVIOUS
        # world is structurally illegal after a membership change
        # (its mesh product no longer matches the device count) —
        # re-solve the factorization for the new world instead of
        # failing at mesh creation.  The agent exports
        # DLROVER_TPU_PREV_WORLD across restarts; a same-size restart
        # keeps the pinned strategy untouched.
        from dlrover_tpu.accelerate.solver import (
            resolve_for_world,
            strategy_device_count,
        )

        if strategy_device_count(strategy) != len(devices):
            plan = resolve_for_world(
                profile,
                len(devices),
                batch_per_replica,
                seq_len,
                prior=strategy,
                long_context=long_context,
                global_batch=global_batch,
            )
            strategy = plan.strategy
    else:
        candidates = generate_candidates(
            profile,
            len(devices),
            long_context=long_context,
            moe=moe,
            batch_per_replica=batch_per_replica,
            seq_len=seq_len,
            global_batch=global_batch,
        )
        if not candidates:
            raise RuntimeError(
                f"no strategy fits: {profile.num_params} params on "
                f"{len(devices)} devices"
            )
        if dry_run and sample_batch_fn is not None:
            def build(s):
                fns, _, _ = _build_for_strategy(
                    s, loss_fn, optimizer, init_params_fn,
                    param_axes, devices,
                )
                state = fns.init_state(jax.random.PRNGKey(0))
                batch = sample_batch_fn(fns.batch_sharding)
                return fns.train_step, state, batch

            from dlrover_tpu.accelerate.search import successive_halving

            strategy, timings = successive_halving(build, candidates)
            if strategy is None:
                strategy = candidates[0]
            elif tune_space:
                # BO over the winner's tunables (micro steps, remat,
                # pipe microbatches, ...) — the knobs no analytic
                # model predicts
                from dlrover_tpu.accelerate.bayes_search import (
                    tune_strategy,
                )

                strategy, tune_hist = tune_strategy(
                    build, strategy, tune_space, budget=tune_budget
                )
                timings["bayes_tune"] = tune_hist
            # calibrate the per-term cost model on what was measured:
            # result.planner.plan(n) ranks candidates at target scale
            from dlrover_tpu.accelerate.dim_planner import (
                CalibratedPlanner,
            )

            by_desc = {c.describe(): c for c in candidates}
            measured = [
                (by_desc[d], t[-1])
                for d, t in timings.items()
                if d in by_desc and t and t[-1] is not None
            ]
            # same constant rank basis as candidate generation: with
            # a known global batch, per-device tokens = global/n
            rank_bpr = (
                global_batch / len(devices)
                if global_batch is not None
                else batch_per_replica
            )
            planner = CalibratedPlanner(
                profile,
                batch_per_replica=rank_bpr,
                seq_len=seq_len,
            )
            planner.calibrate(measured)
        else:
            strategy = candidates[0]

    logger.info(
        "auto_accelerate: %s params -> strategy %s",
        profile.num_params,
        strategy.describe(),
    )
    fns, mesh_ctx, rules = _build_for_strategy(
        strategy, loss_fn, optimizer, init_params_fn, param_axes, devices
    )
    events.end("startup", stage, params=int(profile.num_params))
    return AccelerateResult(
        fns=fns,
        strategy=strategy,
        mesh_ctx=mesh_ctx,
        rules=rules,
        profile=profile,
        timings=timings,
        planner=planner,
    )
