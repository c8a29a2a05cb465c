"""Jitted sharded train step — what ``auto_accelerate`` returns.

Reference parity: the *output* of atorch's ``auto_accelerate``
(``auto/accelerate.py:406``) — a transformed (model, optim, dataloader)
triple ready to step.  Here the equivalent artifact is a single jitted
function: params/optimizer state sharded per the rule table (GSPMD
inserts the ZeRO gather/scatter and TP collectives), gradient
accumulation as a ``lax.scan`` over microbatches (global batch
invariance under elasticity — reference ``ElasticTrainer``), buffers
donated so optimizer update is in-place in HBM.
"""

from dataclasses import dataclass
from functools import cache
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from dlrover_tpu.parallel import remat as rematlib
from dlrover_tpu.parallel.mesh import AxisName, MeshContext
from dlrover_tpu.parallel.sharding import (
    BATCH,
    LogicalAxisRules,
    logical_sharding,
    param_sharding_with_fsdp,
    rules_scope,
    shard_pytree,
)


@dataclass
class TrainStepFns:
    """The compiled artifacts handed back to the user."""

    train_step: Callable  # (state, batch) -> (state, metrics)
    init_state: Callable  # (rng) -> sharded TrainState pytree
    state_shardings: Any
    batch_sharding: Any
    # forward-only loss under the SAME shardings (no donation: eval
    # must not consume the train state's buffers); None on artifacts
    # built before eval existed
    eval_step: Optional[Callable] = None  # (state, batch) -> metrics
    # eval_shape of the train state (ShapeDtypeStructs) — what the AOT
    # path lowers against; None on artifacts built before AOT existed
    state_shape: Any = None
    # the strategy's remat policy (parallel/remat.py): a named one is
    # what ``train_step`` is traced under; ``auto`` is traced under
    # ``full`` until ``resolve_remat`` has put the resolved rung's step
    # in its place
    remat: str = rematlib.AUTO
    # what ran, once ``resolve_remat`` has looked (None: not yet, or a
    # model without a block the ladder applies to)
    remat_plan: Optional[rematlib.RematPlan] = None
    # rung -> the jitted step traced under it, and what the model
    # reported to each trace; None on artifacts built by hand
    _step_under: Optional[Callable] = None
    _remat_reports: Optional[dict] = None

    def aot_compile(self, sample_batch):
        """AOT-compile the train step from shape specs alone:
        ``jit(...).lower(state_specs, batch_specs).compile()``.

        Needs NO live state and NO data — only the mesh — so it can
        run on a background thread the moment the mesh exists,
        concurrently with the restore byte stream (the restart
        critical path, ``trainer/restart_path.py``).  A warm
        ``JAX_COMPILATION_CACHE_DIR`` turns this into a cache load;
        cold, it is the full XLA compile that would otherwise
        serialize in front of the first step.

        ``sample_batch``: a pytree of arrays OR ShapeDtypeStructs
        giving the batch layout.  Returns the compiled executable —
        call it exactly like ``train_step`` (same shardings, same
        donation); inputs with other shapes must go through the
        retracing ``train_step`` instead.
        """
        if self.state_shape is None:
            raise ValueError(
                "artifacts built before the AOT path existed "
                "(rebuild with build_train_step)"
            )
        return self.train_step.lower(
            self.state_shape, _shape_specs(sample_batch)
        ).compile()

    def resolve_remat(self, sample_batch, limit_bytes=None):
        """Decide, where nobody named it, what the model's scanned
        block keeps for its backward — from the compiled step's own
        memory, now that the batch's shape is known — and put that
        step in ``train_step``'s place.  Returns the
        :class:`~dlrover_tpu.parallel.remat.RematPlan` (also kept as
        ``remat_plan``), or None for a model that checkpoints no block
        through the ladder.

        Lowered from shape specs (no live array is held): the step is
        compiled under the richest rung and, while its
        ``memory_analysis`` exceeds ``limit_bytes`` (default: the
        device's ``bytes_limit``, 16 GiB where it reports none) less
        ``remat.RESERVE_BYTES``, under the next one down; the last
        rung, ``full``, is what an unresolved step runs anyway.  The
        executable that passed serves the shapes it was compiled for,
        so nothing compiles twice; a restart resolves again from the
        same shapes and, through the persistent compile cache, gets the
        same rung.  A policy NAMED by
        the model's config or by the strategy is not tried against
        anything: the plan only records it."""
        if self.remat_plan is not None:
            return self.remat_plan
        if self._step_under is None or self.state_shape is None:
            return None
        specs = (self.state_shape, _shape_specs(sample_batch))
        rung = self.remat
        if rung == rematlib.AUTO:
            # the richest rung's trace doubles as the question whether
            # the model takes a rung at all: one that names its own
            # policy (or checkpoints no block) does not look, and the
            # traced step is then the program it would run anyway
            rung = rematlib.RICHEST
            self.train_step = self._step_under(rung)
        self.train_step.trace(*specs)  # the jit's own first trace
        seen = self._remat_reports[rung]
        if seen is None:
            return None
        step_bytes = limit = None
        tried = []
        if seen["source"] == "resolved":
            if limit_bytes is None:
                from dlrover_tpu.accelerate.analyser import (
                    device_memory_bytes,
                )

                limit_bytes = device_memory_bytes()
            limit = int(limit_bytes)
            trial = []  # the last rung's executable alone stays loaded

            def bytes_under(rung):
                trial.clear()
                step = self._step_under(rung)
                try:
                    compiled = step.lower(*specs).compile()
                except jax.errors.JaxRuntimeError as e:
                    # the TPU's compiler and loader refuse a program
                    # that cannot fit: the rung is too rich, no fault
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        raise
                    return None
                trial.append(_ResolvedStep(step, compiled, specs[1]))
                return rematlib.compiled_step_bytes(compiled)

            rung, step_bytes, tried = rematlib.resolve_rung(
                bytes_under, limit
            )
            # the rung taken is the last one tried
            self.train_step = (
                trial.pop() if trial else self._step_under(rung)
            )
            seen = self._remat_reports[rung]
        self.remat_plan = rematlib.RematPlan(
            step_bytes=step_bytes,
            limit_bytes=limit,
            rungs_tried=len(tried),
            **seen,
        )
        return self.remat_plan


def _shape_specs(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), tree
    )


class _ResolvedStep:
    """The step under the resolved remat rung: the executable that
    passed the memory trial runs the batches of the shape it was
    compiled for (``Compiled`` takes no other), the rung's jit any
    other shape and every ``lower``."""

    def __init__(self, jitted, compiled, batch_specs):
        self._jitted = jitted
        self._compiled = compiled
        self._batch_specs = jax.tree_util.tree_leaves(batch_specs)

    def __call__(self, state, batch):
        leaves = jax.tree_util.tree_leaves(batch)
        if len(leaves) == len(self._batch_specs) and all(
            x.shape == s.shape and x.dtype == s.dtype
            for x, s in zip(leaves, self._batch_specs)
        ):
            return self._compiled(state, batch)
        return self._jitted(state, batch)

    def __getattr__(self, name):  # lower, trace, eval_shape, ...
        return getattr(self._jitted, name)


def make_train_state(params, optimizer):
    return {
        "step": jnp.zeros((), dtype=jnp.int32),
        "params": params,
        "opt_state": optimizer.init(params),
    }


def build_train_step(
    loss_fn: Callable,  # (params, batch) -> scalar loss
    optimizer,  # optax.GradientTransformation
    init_params_fn: Callable,  # (rng) -> params pytree
    param_axes,  # logical-axes pytree matching params
    mesh_ctx: MeshContext,
    rules: LogicalAxisRules,
    num_micro_steps: int = 1,
    batch_logical_axes=(BATCH,),
    remat: str = rematlib.AUTO,
) -> TrainStepFns:
    """``remat``: the strategy's policy for the model's scanned block
    (``parallel/remat.py``); ``auto`` leaves it to
    ``TrainStepFns.resolve_remat``."""
    if remat != rematlib.AUTO and remat not in rematlib.POLICIES:
        raise ValueError(
            f"unknown remat policy {remat!r}: one of "
            f"{sorted(rematlib.POLICIES)} or {rematlib.AUTO!r}"
        )
    mesh = mesh_ctx.mesh
    # publish the rule table so in-model activation constraints
    # (apply_sharding_constraint via _current_rules) match param shardings
    mesh_ctx.rules = rules

    def _init_state(rng):
        params = init_params_fn(rng)
        return make_train_state(params, optimizer)

    state_shape = jax.eval_shape(
        _init_state, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )

    _is_axes_leaf = lambda x: isinstance(x, (tuple, type(None)))  # noqa: E731
    if rules.uses_axis(AxisName.FSDP):
        # ZeRO-3 strategy: params whose logical axes don't map onto the
        # fsdp axis still shard over it on their largest divisible dim
        # (shape-aware placement — every param shards, the all-gather
        # rides the biggest dim)
        param_shardings = jax.tree_util.tree_map(
            lambda axes, leaf: param_sharding_with_fsdp(
                mesh, rules, axes, leaf.shape
            ),
            param_axes,
            state_shape["params"],
            is_leaf=_is_axes_leaf,
        )
    else:
        param_shardings = jax.tree_util.tree_map(
            lambda axes: logical_sharding(mesh, rules, axes),
            param_axes,
            is_leaf=_is_axes_leaf,
        )
    batch_sharding = logical_sharding(mesh, rules, batch_logical_axes)
    replicated = logical_sharding(mesh, rules, ())

    def _opt_state_shardings(params_shape):
        """Optimizer state inherits params' shardings structurally:
        optax moment trees mirror the params pytree (match by tree
        structure, NOT by leaf shape — distinct params often share a
        shape, e.g. llama wq/wo, but have transposed layouts); scalar
        leaves (counts) replicate."""
        opt_shape = jax.eval_shape(optimizer.init, params_shape)
        params_def = jax.tree_util.tree_structure(params_shape)

        def is_params_like(sub):
            try:
                return (
                    jax.tree_util.tree_structure(sub) == params_def
                )
            except Exception:  # noqa: BLE001
                return False

        def pick(sub):
            return param_shardings if is_params_like(sub) else replicated

        return jax.tree_util.tree_map(
            pick, opt_shape, is_leaf=is_params_like
        )

    state_shardings = {
        "step": replicated,
        "params": param_shardings,
        "opt_state": _opt_state_shardings(state_shape["params"]),
    }

    init_state = jax.jit(_init_state, out_shardings=state_shardings)

    # scope policy -> what the model did with it, written by each trace
    remat_reports = {}

    def _loss_and_grad(remat_scope, params, batch):
        # rules and the remat policy bound at trace time: the model's
        # activation constraints and its checkpoint resolve against
        # THIS build even if another strategy is built before this
        # step is first called
        with rules_scope(rules), rematlib.scope(*remat_scope) as seen:
            out = jax.value_and_grad(loss_fn)(params, batch)
        remat_reports[remat_scope[0]] = seen.applied
        return out

    def _step(remat_scope, state, batch):
        params = state["params"]
        if num_micro_steps > 1:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    (num_micro_steps, x.shape[0] // num_micro_steps)
                    + x.shape[1:]
                ),
                batch,
            )

            def accum(carry, mb):
                loss_sum, grad_sum = carry
                loss, grads = _loss_and_grad(remat_scope, params, mb)
                grad_sum = jax.tree_util.tree_map(
                    jnp.add, grad_sum, grads
                )
                return (loss_sum + loss, grad_sum), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, dtype=jnp.float32), params
            )
            (loss_sum, grad_sum), _ = jax.lax.scan(
                accum, (jnp.zeros((), jnp.float32), zeros), micro
            )
            scale = 1.0 / num_micro_steps
            loss = loss_sum * scale
            grads = jax.tree_util.tree_map(
                lambda g: g * scale, grad_sum
            )
        else:
            loss, grads = _loss_and_grad(remat_scope, params, batch)
        # a device scope (observability/events.py DEVICE_SCOPES): the
        # model's parts are named where the loss is written
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state["opt_state"], params
            )
            new_params = optax.apply_updates(params, updates)
            grad_norm = optax.global_norm(grads)
        new_state = {
            "step": state["step"] + 1,
            "params": new_params,
            "opt_state": new_opt_state,
        }
        return new_state, {"loss": loss, "grad_norm": grad_norm}

    @cache
    def _step_under(policy, source="resolved"):
        """The jitted step whose loss is traced under ``policy``: a
        rung that ``resolve_remat`` tries, the ``strategy``'s named
        one, or None where nobody has decided."""

        def _train_step(state, batch):
            return _step((policy, source), state, batch)

        return jax.jit(
            _train_step,
            in_shardings=(state_shardings, batch_sharding),
            out_shardings=(state_shardings, replicated),
            donate_argnums=(0,),
        )

    train_step = _step_under(
        None if remat == rematlib.AUTO else remat, "strategy"
    )

    def _eval_step(state, batch):
        with rules_scope(rules):
            loss = loss_fn(state["params"], batch)
        return {"loss": loss}

    # no donation: evaluation reads the live train state and must not
    # invalidate its buffers mid-run
    eval_step = jax.jit(
        _eval_step,
        in_shardings=(state_shardings, batch_sharding),
        out_shardings=replicated,
    )
    return TrainStepFns(
        train_step=train_step,
        init_state=init_state,
        state_shardings=state_shardings,
        batch_sharding=batch_sharding,
        eval_step=eval_step,
        state_shape=state_shape,
        remat=remat,
        _step_under=_step_under,
        _remat_reports=remat_reports,
    )
