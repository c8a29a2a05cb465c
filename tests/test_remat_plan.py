"""What the scanned block keeps for its backward (ISSUE 43): the ladder
of ``parallel/remat.py``, who may name a policy, how an unnamed one is
resolved from the compiled step's memory, and the record of what ran.
CPU, tiny sizes: counts, jaxprs and bytes — no time is read here."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accelerate import auto_accelerate, load_strategy
from dlrover_tpu.models import llama
from dlrover_tpu.observability import events as ev
from dlrover_tpu.parallel import remat
from dlrover_tpu.parallel.mesh import destroy_parallel_mesh

RUNGS = tuple(remat.LADDER)  # poorest first
GiB = 1 << 30


# ------------------------------------------------------------ the ladder


def test_ladder_grows_rung_by_rung_and_ends_without_a_checkpoint():
    assert RUNGS == ("full", "flash", "qkv", "matmuls", "none")
    kept = [remat.LADDER[r] for r in RUNGS[:-1]]
    assert kept[0] == ()
    for poorer, richer in zip(kept, kept[1:]):
        assert set(poorer) < set(richer)
    assert remat.LADDER["none"] is None
    assert remat.POLICIES == set(RUNGS) | {"dots"}


def _grads(policy):
    cfg = llama.LlamaConfig.tiny(remat=policy, dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size
        )
    }
    return jax.jit(jax.grad(lambda p: llama.loss_fn(p, batch, cfg)))(params)


@pytest.fixture(scope="module")
def grads_of_none():
    return _grads("none")


@pytest.mark.parametrize("policy", RUNGS[:-1] + ("dots",))
def test_gradients_under_every_policy_equal_none(policy, grads_of_none):
    """A kept value IS the value its replay would have recomputed.  On
    the CPU backend in float32 that reads bit for bit: the replay runs
    the same instructions on the same inputs."""
    got = _grads(policy)
    for a, b in zip(
        jax.tree_util.tree_leaves(got),
        jax.tree_util.tree_leaves(grads_of_none),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _checkpoints(jaxpr) -> int:
    """``jax.checkpoint`` regions in a jaxpr, nested ones included."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("checkpoint", "remat2"):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _checkpoints(sub)
    return found


def _block_checkpoints(cfg) -> int:
    """Checkpoint regions of the layer stack alone (the fused
    cross-entropy has one of its own, whatever the block does)."""
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.ones((2, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(
            lambda p: jnp.sum(
                llama.forward_hidden(p, tokens, cfg).astype(jnp.float32)
            )
        )
    )(params)
    return _checkpoints(jaxpr.jaxpr)


@pytest.mark.parametrize("policy", sorted(remat.POLICIES))
def test_named_config_policy_is_what_the_block_runs_under(policy):
    cfg = llama.LlamaConfig.tiny(remat=policy)
    assert _block_checkpoints(cfg) == (0 if policy == "none" else 1)


def test_unnamed_and_unresolved_is_todays_full():
    assert llama.LlamaConfig.tiny().remat == remat.AUTO
    assert remat.select(remat.AUTO) == ("full", "default")
    assert _block_checkpoints(llama.LlamaConfig.tiny()) == 1


def test_unknown_policy_is_refused_by_name():
    with pytest.raises(ValueError, match="unknown remat policy 'fulll'"):
        _block_checkpoints(llama.LlamaConfig.tiny(remat="fulll"))


# ---------------------------------------------------------- the resolver

#: a step's bytes under each rung, as a compiled step would read
BYTES = {"full": 10 * GiB, "flash": 11 * GiB, "qkv": 12 * GiB,
         "matmuls": 13 * GiB, "none": 14 * GiB}


@pytest.mark.parametrize(
    "limit_gib, want, tried",
    [
        (16.0, "none", 1),      # everything fits: the richest
        (14.5, "none", 1),      # exactly limit - reserve
        (14.4, "matmuls", 2),   # steps down as the limit falls
        (13.0, "qkv", 3),
        (12.0, "flash", 4),
        (11.0, "full", 5),
        (1.0, "full", 5),       # nothing fits: today's program
    ],
)
def test_resolver_picks_the_richest_rung_that_fits(limit_gib, want, tried):
    asked = []

    def step_bytes(rung):
        asked.append(rung)
        return BYTES[rung]

    rung, size, order = remat.resolve_rung(
        step_bytes, int(limit_gib * GiB), reserve_bytes=GiB // 2
    )
    assert (rung, size, len(order)) == (want, BYTES[want], tried)
    # richest first, one trial a rung, none after the one that fits
    assert order == asked == list(reversed(RUNGS))[:tried]


def test_resolver_steps_past_a_rung_the_compiler_refused():
    refused = {"none", "matmuls"}
    rung, size, tried = remat.resolve_rung(
        lambda r: None if r in refused else BYTES[r], 64 * GiB
    )
    assert (rung, size, tried) == ("qkv", BYTES["qkv"], ["none", "matmuls", "qkv"])
    # even "full" refused: it is still what runs (and raises there)
    assert remat.resolve_rung(lambda r: None, 64 * GiB)[:2] == ("full", None)


def test_reserve_is_one_constant():
    assert remat.RESERVE_BYTES == 512 << 20
    rung, _, _ = remat.resolve_rung(BYTES.get, 14 * GiB + remat.RESERVE_BYTES)
    assert rung == "none"
    rung, _, _ = remat.resolve_rung(
        BYTES.get, 14 * GiB + remat.RESERVE_BYTES - 1
    )
    assert rung == "matmuls"


# ------------------------------------------------- the step obeys the plan


def _accelerate(cfg, strategy, devices=8):
    return auto_accelerate(
        # the plain cross-entropy: the fused one checkpoints its logits,
        # whatever the block does
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg, fused_ce=False),
        optimizer=optax.adamw(1e-3),
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        param_axes=llama.param_logical_axes(cfg),
        load_strategy=load_strategy(strategy),
        devices=jax.devices()[:devices],
    )


BATCH = {"tokens": jax.ShapeDtypeStruct((8, 17), jnp.int32)}


def _step_checkpoints(fns) -> int:
    """Checkpoint regions of the step as it would run."""
    traced = fns.train_step.trace(fns.state_shape, BATCH)
    return _checkpoints(traced.jaxpr.jaxpr)


@pytest.fixture
def mesh_cleanup():
    yield
    destroy_parallel_mesh()


@pytest.mark.parametrize(
    "cfg_remat, strategy_remat, policy, source, block_checkpoints",
    [
        # Strategy.remat is wired: "none" leaves no checkpoint of the block
        (remat.AUTO, "none", "none", "strategy", 0),
        (remat.AUTO, "full", "full", "strategy", 1),
        (remat.AUTO, "qkv", "qkv", "strategy", 1),
        (remat.AUTO, "dots", "dots", "strategy", 1),
        # the model's own word wins over the strategy's
        ("none", "full", "none", "config", 0),
        ("full", "none", "full", "config", 1),
        ("matmuls", remat.AUTO, "matmuls", "config", 1),
    ],
)
def test_a_named_policy_is_obeyed(
    cfg_remat, strategy_remat, policy, source, block_checkpoints,
    mesh_cleanup,
):
    cfg = llama.LlamaConfig.tiny(remat=cfg_remat)
    result = _accelerate(cfg, {"data": 8, "remat": strategy_remat})
    assert result.strategy.remat == strategy_remat
    plan = result.fns.resolve_remat(BATCH)
    assert (plan.policy, plan.source) == (policy, source)
    assert (plan.step_bytes, plan.limit_bytes, plan.rungs_tried) == (
        None, None, 0)  # nothing was tried against anything
    assert plan.layers == cfg.n_layers
    assert _step_checkpoints(result.fns) == block_checkpoints


def test_unnamed_step_runs_full_until_resolved(mesh_cleanup):
    result = _accelerate(llama.LlamaConfig.tiny(), {"data": 8})
    assert result.strategy.remat == remat.AUTO
    assert result.fns.remat_plan is None
    assert _step_checkpoints(result.fns) == 1


@pytest.mark.parametrize(
    "mesh, limit, policy, tried",
    [
        # no device limit on the CPU: the 16 GiB default
        ({"data": 8}, None, "none", 1),
        ({"data": 8}, 0, "full", 5),      # nothing fits
        # sharded shapes: the same resolution, on one device's bytes
        ({"data": 2, "fsdp": 2, "tensor": 2}, None, "none", 1),
        ({"data": 2, "fsdp": 2, "tensor": 2}, 0, "full", 5),
    ],
)
def test_unnamed_step_is_resolved_from_its_compiled_memory(
    mesh, limit, policy, tried, mesh_cleanup
):
    from dlrover_tpu.accelerate.analyser import device_memory_bytes

    cfg = llama.LlamaConfig.tiny()
    fns = _accelerate(cfg, mesh).fns
    plan = fns.resolve_remat(BATCH, limit_bytes=limit)
    assert (plan.policy, plan.source, plan.rungs_tried) == (
        policy, "resolved", tried)
    assert plan.limit_bytes == (
        device_memory_bytes() if limit is None else limit
    ) and device_memory_bytes() == 16 * GiB
    assert plan.step_bytes > 0
    if policy == "none":
        assert plan.step_bytes <= plan.limit_bytes - remat.RESERVE_BYTES
        assert plan.kept_bytes_per_layer is None
    else:
        # the layer's input alone: [8, 16, 64] in bfloat16
        assert plan.kept_bytes_per_layer == 8 * 16 * 64 * 2
    assert _step_checkpoints(fns) == (0 if policy == "none" else 1)
    assert fns.resolve_remat(BATCH) is plan  # decided once
    # the executable that passed is the one that runs, and other shapes
    # still go through the rung's jit
    state = fns.init_state(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        np.ones((8, 17), np.int32), fns.batch_sharding
    )
    state, metrics = fns.train_step(state, {"tokens": tokens})
    assert np.isfinite(float(metrics["loss"]))
    shorter = jax.device_put(np.ones((8, 9), np.int32), fns.batch_sharding)
    state, metrics = fns.train_step(state, {"tokens": shorter})
    assert np.isfinite(float(metrics["loss"]))


def test_a_model_without_a_ladder_block_has_no_plan(mesh_cleanup):
    w = jnp.ones((8, 4))
    fns = auto_accelerate(
        loss_fn=lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
        optimizer=optax.sgd(0.1),
        init_params_fn=lambda rng: {"w": w},
        param_axes={"w": (None, None)},
        load_strategy=load_strategy({"data": 8}),
    ).fns
    batch = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
    assert fns.resolve_remat(batch) is None and fns.remat_plan is None
    state = fns.init_state(jax.random.PRNGKey(0))
    x = jax.device_put(np.ones((8, 8), np.float32), fns.batch_sharding)
    _, metrics = fns.train_step(state, {"x": x})
    assert float(metrics["loss"]) == 64.0


@pytest.mark.parametrize("rung", RUNGS[:-1])
def test_kept_bytes_follow_the_rungs_names(rung, mesh_cleanup):
    """``kept_bytes_per_layer`` is the layer's input plus the named
    values of the rung, in logical bytes, as the trace saw them (dense
    attention here: no log-sum-exp)."""
    cfg = llama.LlamaConfig.tiny()
    fns = _accelerate(cfg, {"data": 8, "remat": rung}).fns
    x = 8 * 16 * cfg.dim * 2
    kv = 8 * 16 * cfg.n_kv_heads * cfg.head_dim * 2
    mlp = 8 * 16 * cfg.mlp_dim * 2
    want = {
        "full": x,
        "flash": 2 * x,
        "qkv": 3 * x + 2 * kv,
        "matmuls": 4 * x + 2 * kv + 2 * mlp,
    }[rung]
    assert fns.resolve_remat(BATCH).kept_bytes_per_layer == want


def test_flash_kernel_names_its_residuals():
    """The attention kernel's forward names its output and log-sum-exp
    inside its ``custom_vjp``, so a rung that keeps them does not run
    ``_flash_fwd`` again in the backward."""
    from dlrover_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q):
        block = remat.checkpointed(
            lambda q: flash_attention(q, q, q), "flash"
        )
        return jnp.sum(block(q))

    with remat.scope("flash", "strategy") as seen:
        text = str(jax.make_jaxpr(jax.grad(loss))(q))
    assert seen.sizes == {
        remat.ATTN_OUT: q.size * 4,
        remat.ATTN_LSE: 1 * 2 * 128 * 4,
    }
    for name in (remat.ATTN_OUT, remat.ATTN_LSE):
        assert f"name={name}" in text
    # one forward kernel, none replayed (two under "full")
    assert text.count("_flash_fwd") == 1
    with_full = str(
        jax.make_jaxpr(
            jax.grad(
                lambda q: jnp.sum(
                    jax.checkpoint(lambda q: flash_attention(q, q, q))(q)
                )
            )
        )(q)
    )
    assert with_full.count("_flash_fwd") == 2


# ----------------------------------------------- the record of what ran


@pytest.fixture
def trained(tmp_path, request):
    """Five steps of a tiny trainer with an events file."""
    from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

    cfg_remat, strategy = request.param
    os.environ["DLROVER_TPU_SOCKET_DIR"] = str(tmp_path / "socks")
    path = tmp_path / "events.jsonl"
    ev.set_default_event_logger(ev.EventLogger(path=str(path)))
    try:
        cfg = llama.LlamaConfig.tiny(remat=cfg_remat)
        result = _accelerate(cfg, strategy)
        tokens = np.ones((8, 17), dtype=np.int32)
        trainer = Trainer(
            result,
            TrainingArgs(max_steps=5, log_interval=100, micro_batch_size=8),
            lambda: ({"tokens": tokens} for _ in range(8)),
        )
        trainer.train()
    finally:
        ev.set_default_event_logger(None)
        destroy_parallel_mesh()
    return result, ev.read_events(str(path))


@pytest.mark.parametrize(
    "trained, policy, source",
    [
        ((remat.AUTO, {"data": 8}), "none", "resolved"),
        ((remat.AUTO, {"data": 8, "remat": "flash"}), "flash", "strategy"),
        (("dots", {"data": 8}), "dots", "config"),
    ],
    indirect=["trained"],
)
def test_one_remat_plan_record_and_a_label_on_every_step(
    trained, policy, source
):
    result, events = trained
    plans = [e for e in events if e["name"] == "remat_plan"]
    assert len(plans) == 1 and plans[0]["ph"] == "i"
    labels = plans[0]["labels"]
    assert set(labels) == {
        "policy", "source", "layers", "kept_bytes_per_layer",
        "step_bytes", "limit_bytes", "rungs_tried",
    }
    assert (labels["policy"], labels["source"]) == (policy, source)
    assert labels == result.fns.remat_plan.labels()
    if source == "resolved":
        assert labels["step_bytes"] <= (
            labels["limit_bytes"] - remat.RESERVE_BYTES
        )
    steps = [e for e in events if e["name"] == "step"]
    assert len(steps) == 4  # the first completion has no span
    assert {e["labels"]["remat"] for e in steps} == {policy}
    # the record comes before the first step's span
    assert events.index(plans[0]) < events.index(steps[0])


def test_remat_plan_is_a_declared_instant_event():
    assert "remat_plan" in ev.INSTANT_EVENTS
