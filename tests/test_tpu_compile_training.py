"""The training side compiled for a described TPU: the sharded train
step, the serving copy, the snapshot programs and the cells' remat
rung.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    BF16,
    S,
    _compile_for_metal,
    _copy_case,
    _train_state_shapes,
    one_chip,
    topo,
)


def test_sharded_train_step_compiles_for_four_chips(topo, monkeypatch):
    """The ``--four-chips`` program: loss + grad of the llama block at
    7B widths on an fsdp=2 x tensor=2 mesh, flash attention and the
    fused norm per shard (GSPMD cannot partition a Mosaic kernel)."""
    from dlrover_tpu.accelerate import auto_accelerate, load_strategy
    from dlrover_tpu.models.llama import (
        LlamaConfig,
        init_params,
        loss_fn,
        param_logical_axes,
    )
    from dlrover_tpu.optimizers import agd
    from dlrover_tpu.parallel.mesh import destroy_parallel_mesh

    # the program takes its flash decision from the backend it runs on;
    # here that is the CPU, so name the choice
    monkeypatch.setenv("DLROVER_TPU_FLASH_ATTENTION", "1")
    cfg = LlamaConfig.llama2_7b(n_layers=1, max_seq_len=S)
    try:
        result = auto_accelerate(
            loss_fn=lambda p, b: loss_fn(p, b, cfg),
            optimizer=agd(3e-4),
            init_params_fn=lambda rng: init_params(rng, cfg),
            param_axes=param_logical_axes(cfg),
            load_strategy=load_strategy(
                {"data": 1, "fsdp": 2, "tensor": 2}
            ),
            devices=list(topo.devices),
        )
        fns = result.fns
        state = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sh
            ),
            fns.state_shape,
            fns.state_shardings,
        )
        batch = {
            "tokens": jax.ShapeDtypeStruct(
                (2, S + 1), jnp.int32, sharding=fns.batch_sharding
            )
        }
        text = fns.train_step.lower(state, batch).compile().as_text()
    finally:
        destroy_parallel_mesh()  # the global mesh other tests see
    assert "tpu_custom_call" in text
    assert "all-gather" in text or "all-reduce" in text


@pytest.mark.parametrize(
    "cell,out_bytes",
    [
        ("deepseek7b-rollout-c16", 3_701_473_792),
        ("falconh1-34b-rollout-c32", 6 * 5120 * 3584 * 2),
    ],
)
def test_the_serving_copy_is_one_program_without_temporaries(
    cell, out_bytes, one_chip
):
    """A replica's serving copy is written by ONE compiled program: at
    the cell's size it returns the copy's bytes (C: 3.70 GB, every
    matrix in bfloat16 with ``wqkv`` ``[5, 4096, 12288]``; F: the fused
    leaf alone, 0.22 GB) and holds nothing beside them — the casts'
    intermediates live in the outputs' own allocation — so an adoption
    peaks at the template plus the copy."""
    from dlrover_tpu.models import llama

    work = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        _copy_case(cell),
    )
    compiled = llama._cast_and_fuse.lower(work, jnp.dtype(BF16)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == out_bytes
    assert mem.temp_size_in_bytes == 0
    assert mem.alias_size_in_bytes == 0  # nothing donated


@pytest.mark.parametrize("mode", ["staged", "copy"])
def test_the_snapshot_program_spends_no_device_memory(mode, one_chip):
    """The trainer's ONE snapshot program at the training cell's size.
    ``staged``: every byte of the copy is a host output (8.38 GB in
    ``pinned_host`` memory), the recycled host tree is aliased to it
    whole, and the device holds NOTHING beside the state it reads: no
    relayout copy of any leaf (a leaf is up to 0.52 GB; 5.43 GiB of the
    chip belong to the step's temporaries).  ``copy``: the same
    program with device outputs, a second state and no temporaries."""
    from dlrover_tpu.trainer import trainer

    def spec(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            tree,
        )

    shapes = _train_state_shapes()
    state = spec(shapes, one_chip)
    state_bytes = sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(shapes)
    )
    assert state_bytes == 8_380_465_160
    to_host = mode == "staged"
    shardings = trainer._snapshot_shardings(state, to_host)
    recycled = None
    if to_host:
        assert {
            s.memory_kind for s in jax.tree_util.tree_leaves(shardings)
        } == {"pinned_host"}
        recycled = spec(shapes, one_chip.with_memory_kind("pinned_host"))
    compiled = trainer._compile_snapshot_copy(state, shardings, recycled)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.6 * 2**30  # the issue's line
    assert mem.temp_size_in_bytes == 0           # what the compiler gives
    padded = 8_380_466_176  # each leaf rounded up to its tiling
    if to_host:
        assert mem.host_output_size_in_bytes == padded
        assert mem.host_alias_size_in_bytes == padded
        assert mem.output_size_in_bytes < 4096  # the tuple's pointers
        assert mem.host_temp_size_in_bytes == 0
        # one asynchronous copy a leaf, straight from the argument
        assert compiled.as_text().count("copy-start(") == 38
    else:
        assert mem.output_size_in_bytes >= padded
        assert mem.host_output_size_in_bytes == 0


V5E_BYTES_LIMIT = 16_909_336_064  # memory_stats()["bytes_limit"] of a v5e


@pytest.mark.parametrize(
    "limit, policy, tried, replays_flash",
    [
        # the training cell on its chip: everything the backward reads
        # fits beside 7.8 GiB of state, so nothing of the block is replayed
        (V5E_BYTES_LIMIT, "none", 1, 0),
        # half a GiB less and the richest rung is out: the named set stays
        (V5E_BYTES_LIMIT - (512 << 20), "matmuls", 2, 0),
    ],
)
def test_training_cells_remat_rung_is_resolved_from_compiled_memory(
    limit, policy, tried, replays_flash, topo, monkeypatch
):
    """``TrainStepFns.resolve_remat`` at the training cell's shapes
    (``mistral-7b-v0.1`` at depth 2, batch 2 x 2048, ``agd``), the step
    compiled for the described chip under the ladder's rungs: which rung
    the compiled bytes admit, and that a kept attention output takes the
    ``_flash_fwd`` replay off the backward."""
    from dlrover_tpu.accelerate import auto_accelerate
    from dlrover_tpu.models import llama
    from dlrover_tpu.optimizers import agd
    from dlrover_tpu.parallel import remat
    from dlrover_tpu.parallel.mesh import destroy_parallel_mesh

    monkeypatch.setenv("DLROVER_TPU_FLASH_ATTENTION", "1")
    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=2, n_heads=32, n_kv_heads=8,
        mlp_dim=14336, max_seq_len=2048,
    )
    try:
        fns = auto_accelerate(
            loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
            optimizer=agd(3e-5),
            init_params_fn=lambda rng: llama.init_params(rng, cfg),
            param_axes=llama.param_logical_axes(cfg),
            devices=[topo.devices[0]],
        ).fns
        batch = {"tokens": jax.ShapeDtypeStruct((2, 2049), jnp.int32)}
        plan = fns.resolve_remat(batch, limit_bytes=limit)
    finally:
        destroy_parallel_mesh()
    assert (plan.policy, plan.source, plan.rungs_tried) == (
        policy, "resolved", tried)
    assert plan.layers == 2
    assert 14.2e9 < plan.step_bytes <= limit - remat.RESERVE_BYTES
    if policy == "matmuls":
        # input 32 MiB + out 32 + lse 0.5 + q 32 + k, v 8 + 8 + the
        # residual 32 + gate, up 2 x 112 MiB
        assert plan.kept_bytes_per_layer == 386_400_256
    text = fns.train_step._compiled.as_text()
    assert text.count("jit(_flash_fwd)/pallas_call") == 1
    assert text.count(
        "rematted_computation/attn/jit(_flash_fwd)"
    ) == replays_flash
    assert "jit(_train_step)/" in text
