"""Strategy-driven kernel selection — the module-replace analog.

Reference parity: ``atorch/atorch/auto/opt_lib/
module_replace_optimization.py:179`` (swaps a model's attention modules
for flash-attention implementations as an optimization pass).  On TPU
there are no modules to rewrite: the model's ``forward`` takes a
pluggable ``attention_fn``, and this pass picks the kernel that matches
the active strategy:

- sequence axis > 1  -> ring attention (``lax.ppermute`` KV rotation)
  under ``shard_map``, seq-sharded end to end;
- TPU backend        -> the Pallas flash-attention kernel;
- otherwise          -> the dense reference kernel (XLA fuses it well
  enough on CPU CI, and Pallas interpret mode would be slower).

``dlrover_tpu.models.llama.forward`` resolves its default attention
through :func:`select_attention` at trace time, so a train step built
by ``auto_accelerate`` automatically runs the right kernel with no user
plumbing (the same invisibility the reference achieves with module
surgery).
"""

import os
from functools import partial
from typing import Optional

import jax

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel.mesh import AxisName, MeshContext
from dlrover_tpu.parallel.sharding import (
    BATCH,
    HEADS,
    KV_HEADS,
    SEQ,
    LogicalAxisRules,
    filter_spec_for_mesh,
)

# test/override hook: "auto" | "1" (force flash) | "0" (force dense)
FLASH_ENV = "DLROVER_TPU_FLASH_ATTENTION"
# test/override hook: "auto" | "ring" | "ulysses"
SP_KERNEL_ENV = "DLROVER_TPU_SP_KERNEL"
# solver-chosen flash tiles, "block_q,block_kv" (empty = measured
# defaults); accelerate.solve_joint_plan emits the pair
FLASH_BLOCKS_ENV = "DLROVER_TPU_FLASH_BLOCKS"


def _tile_multiple(dtype) -> int:
    """Smallest legal sublane tile for the flash kernel's seq-blocked
    dimension on TPU (Mosaic min tiles: fp32 (8,128), bf16/fp16
    (16,128), 1-byte types (32,128))."""
    import numpy as np

    dt = np.dtype(dtype) if not hasattr(dtype, "itemsize") else dtype
    if dt.itemsize >= 4:
        return 8
    if dt.itemsize == 2:
        return 16
    return 32


def round_block_to_tile(block: int, local_seq: int, dtype) -> int:
    """Clamp a solver/env flash-block override to the LOCAL sequence,
    rounding DOWN to the largest supported tile multiple that fits.

    A bare ``min(block, local_seq)`` can hand the Pallas kernel a
    non-tile-aligned block (e.g. override 256 against a local seq of
    100 → 100, not a multiple of the (8|16|32, 128) Mosaic tile) and
    fail at kernel build.  When the local sequence is itself below one
    tile, the kernel's internal ``min(block, s)`` + bounds masks
    handle the padding — return the local seq unchanged."""
    tile = _tile_multiple(dtype)
    b = min(int(block), int(local_seq))
    if local_seq < tile:
        return b
    return max(b - b % tile, tile)


def _flash_enabled(flash: Optional[bool]) -> bool:
    if flash is not None:
        return flash
    env = os.getenv(FLASH_ENV, "auto").lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return jax.default_backend() == "tpu"


def sp_kernel_choice(
    seq_size: int, n_heads: int, n_kv_heads: int
) -> str:
    """Which sequence-parallel attention form to run: "ulysses" when
    both head counts divide the seq axis (one all-to-all exchanging
    seq<->head beats n ring hops on ICI — reference ships both as
    selectable optimizations, ``sequence_parallel_optimization.py:9``),
    "ring" otherwise (works for any head count, overlaps compute with
    the ppermute rotation)."""
    env = os.getenv(SP_KERNEL_ENV, "auto").lower()
    if env in ("ring", "ulysses"):
        return env
    if n_heads % seq_size == 0 and n_kv_heads % seq_size == 0:
        return "ulysses"
    return "ring"


def select_attention(
    mesh_ctx: Optional[MeshContext],
    rules: Optional[LogicalAxisRules],
    flash: Optional[bool] = None,
):
    """Return the attention kernel for the current strategy.

    The returned callable has the model kernel signature
    ``(q[B,S,H,D], k[B,S,KV,D], v, causal=True) -> [B,S,H,D]``.
    """
    import importlib

    # the package re-exports the function under the same name as the
    # module, so attribute-style imports resolve to the function
    _fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
    _llama = importlib.import_module("dlrover_tpu.models.llama")

    use_flash = _flash_enabled(flash)
    inner = (
        _fa.flash_attention if use_flash
        else _llama.dot_product_attention
    )
    if use_flash:
        # tile override: apply a solver-chosen flash tile without
        # touching model code
        blocks = os.getenv(FLASH_BLOCKS_ENV, "")
        if blocks:
            try:
                bq, bk = (int(x) for x in blocks.split(","))
                if bq <= 0 or bk <= 0:
                    raise ValueError("blocks must be positive")
                # clamp to the LOCAL sequence at call time: under a
                # seq-sharded mesh the kernel sees seq/s.seq, and a
                # well-formed override sized for the global seq would
                # otherwise fail at kernel build (ADVICE-r4).  The
                # clamp point is the first place local shapes exist;
                # the clamped block additionally rounds DOWN to the
                # largest supported Mosaic tile multiple — a bare min
                # (override 256, local seq 100 → 100) is not a legal
                # tile and dies at kernel build.
                base = inner

                def inner(q, k, v, *a, _base=base, _bq=bq, _bk=bk,
                          **kw):
                    lbq = round_block_to_tile(
                        _bq, q.shape[1], q.dtype
                    )
                    lbk = round_block_to_tile(
                        _bk, k.shape[1], k.dtype
                    )
                    if (lbq, lbk) != (_bq, _bk):
                        reason = (
                            "exceeds local seq"
                            if _bq > q.shape[1] or _bk > k.shape[1]
                            else "is not a Mosaic tile multiple"
                        )
                        logger.warning(
                            "%s=%r %s (local q=%d k=%d); adjusted "
                            "to tile-aligned %d,%d",
                            FLASH_BLOCKS_ENV, blocks, reason,
                            q.shape[1], k.shape[1], lbq, lbk,
                        )
                    return _base(
                        q, k, v, *a, block_q=lbq, block_k=lbk, **kw
                    )
            except ValueError:
                logger.warning(
                    "ignoring malformed %s=%r",
                    FLASH_BLOCKS_ENV, blocks,
                )

    seq_size = (
        mesh_ctx.axis_size(AxisName.SEQUENCE) if mesh_ctx else 1
    )
    if rules is None:
        return inner
    if seq_size > 1:
        return _sp_under_shard_map(mesh_ctx, rules, inner, use_flash)
    if use_flash and mesh_ctx is not None and mesh_ctx.mesh.size > 1:
        # GSPMD cannot partition a Mosaic kernel: run flash per shard
        # (batch over the data axes, heads over the tensor axis)
        return _flash_under_shard_map(mesh_ctx, rules, inner)
    return inner


def _ambient_mesh(mesh):
    """The mesh a nested ``shard_map`` must be built on: inside another
    manual region (the pipe executor's partial-manual shard_map) that
    is the AMBIENT abstract mesh — passing the concrete mesh trips
    "context mesh should match" because pipe is already Manual."""
    cur = jax.sharding.get_abstract_mesh()
    if any("Manual" in str(t) for t in cur.axis_types):
        return cur
    return mesh


def shard_mapped(fn, mesh_ctx: MeshContext, in_specs, out_specs):
    """``fn`` run per shard of ``mesh_ctx``'s mesh — how a Pallas
    kernel (which GSPMD cannot partition) joins a sharded program."""
    from dlrover_tpu.parallel.sharding import shard_map_compat

    return shard_map_compat(
        fn,
        mesh=_ambient_mesh(mesh_ctx.mesh),
        in_specs=in_specs,
        out_specs=out_specs,
    )


def _attention_specs(mesh_ctx: MeshContext, rules: LogicalAxisRules):
    """(q spec, k/v spec) of the ``[B, S, heads, D]`` attention inputs
    under the activation rule table."""
    return tuple(
        filter_spec_for_mesh(
            rules.spec((BATCH, SEQ, heads, None)), mesh_ctx.mesh
        )
        for heads in (HEADS, KV_HEADS)
    )


def _flash_under_shard_map(mesh_ctx: MeshContext,
                           rules: LogicalAxisRules,
                           inner_attention):
    q_spec, kv_spec = _attention_specs(mesh_ctx, rules)

    def attention(q, k, v, causal: bool = True):
        return shard_mapped(
            partial(inner_attention, causal=causal),
            mesh_ctx,
            in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec,
        )(q, k, v)

    return attention


def select_layer_executor(mesh_ctx: Optional[MeshContext]):
    """How the model's stacked layer dim is executed: a plain
    ``lax.scan`` normally; the GPipe shard_map pipeline when the
    strategy runs pipe > 1 (reference
    ``pipeline_parallel_optimization.py:56`` — PiPPy graph-split; the
    TPU-native form is SPMD microbatch ppermute,
    ``dlrover_tpu.parallel.pipeline``).

    Executor signature: ``(block, layers, x, *extras) -> x`` where
    ``block(layer_params, x, *extras) -> x`` is one layer and
    ``layers`` is the stacked param pytree (leading dim = layer)."""
    pipe_size = (
        mesh_ctx.axis_size(AxisName.PIPELINE) if mesh_ctx else 1
    )
    if pipe_size <= 1:
        return _scan_layers
    return _pipeline_executor(mesh_ctx)


def _scan_layers(block, layers, x, *extras):
    import jax

    def body(h, lp):
        return block(lp, h, *extras), None

    h, _ = jax.lax.scan(body, x, layers)
    return h


def _pipeline_executor(mesh_ctx: MeshContext):
    """GPipe over the "pipe" mesh axis: layers sharded into stages,
    activations microbatched and rotated stage-to-stage with ppermute.
    Partial-manual shard_map — only "pipe" is manual, every other mesh
    axis stays auto so GSPMD keeps inserting the dp/fsdp/tp collectives
    inside the stage body."""
    import jax
    from jax.sharding import PartitionSpec as P

    from dlrover_tpu.parallel.pipeline import (
        merge_microbatches,
        pipeline_spmd,
        split_microbatches,
    )

    mesh = mesh_ctx.mesh
    n_stages = mesh_ctx.axis_size(AxisName.PIPELINE)
    num_mb = mesh_ctx.pipeline_microbatches or 2 * n_stages
    logger.info(
        "module_replace: GPipe executor, %d stages x %d microbatches",
        n_stages, num_mb,
    )

    def execute(block, layers, x, *extras):
        import jax.numpy as jnp

        # f32 at the shard_map boundary: the VJP psums the replicated
        # input's cotangent over the manual pipe axis, and a bf16 psum
        # under partial-manual shard_map crashes XLA CPU (same
        # float-normalization bug as pipeline_spmd's broadcast)
        x_dtype = x.dtype
        upcast = x_dtype in (jnp.bfloat16, jnp.float16)

        def run(layers_local, x_local, *extras_local):
            x_local = x_local.astype(x_dtype)

            def stage_fn(stage_layers, x_mb):
                def body(h, lp):
                    return block(lp, h, *extras_local), None

                h, _ = jax.lax.scan(body, x_mb, stage_layers)
                return h

            mbs = split_microbatches(x_local, num_mb)
            out = pipeline_spmd(
                stage_fn, layers_local, mbs,
                axis_name=AxisName.PIPELINE,
            )
            return merge_microbatches(out)

        layer_specs = jax.tree_util.tree_map(
            lambda _: P(AxisName.PIPELINE), layers
        )
        rep = P()
        extras_specs = tuple(rep for _ in extras)
        x_in = x.astype(jnp.float32) if upcast else x
        extras_in = tuple(
            e.astype(jnp.float32)
            if e.dtype in (jnp.bfloat16, jnp.float16)
            else e
            for e in extras
        )
        from dlrover_tpu.parallel.sharding import shard_map_compat

        return shard_map_compat(
            run,
            mesh=mesh,
            in_specs=(layer_specs, rep) + extras_specs,
            out_specs=rep,
            manual_axes={AxisName.PIPELINE},
        )(layers, x_in, *extras_in)

    return execute


def _sp_under_shard_map(mesh_ctx: MeshContext,
                        rules: LogicalAxisRules,
                        inner_attention,
                        use_flash: bool = True):
    """Sequence-parallel attention over the seq mesh axis, wrapped in
    shard_map with specs matching the activation rule table (so it
    composes with the surrounding GSPMD program).

    The SP form is picked per call site from the traced head counts
    (:func:`sp_kernel_choice`): Ulysses all-to-all when heads divide
    the axis, ring otherwise.  Ulysses runs ``inner_attention`` (the
    Pallas flash kernel on TPU) on the gathered sequence; the ring's
    per-block kernel is flash via ``flash_attention_lse``."""
    from dlrover_tpu.parallel.collectives import (
        ring_attention,
        ulysses_attention,
    )

    seq_size = mesh_ctx.axis_size(AxisName.SEQUENCE)
    q_spec, kv_spec = _attention_specs(mesh_ctx, rules)

    # inside the manual region the heads dim is already tensor-sharded
    # (HEADS/KV_HEADS -> tensor axis): Ulysses' all_to_all must divide
    # the LOCAL head count, not the global one
    tp = mesh_ctx.axis_size(AxisName.TENSOR)

    def _tp_split(logical) -> int:
        target = rules.mesh_axes(logical)
        flat = target if isinstance(target, tuple) else (target,)
        return tp if AxisName.TENSOR in flat else 1

    h_split = _tp_split(HEADS)
    kv_split = _tp_split(KV_HEADS)

    def attention(q, k, v, causal: bool = True):
        choice = sp_kernel_choice(
            seq_size, q.shape[2] // h_split, k.shape[2] // kv_split
        )
        logger.info(
            "module_replace: %s attention over %d-way seq axis "
            "(q spec %s)", choice, seq_size, q_spec,
        )
        if choice == "ulysses":
            fn = partial(
                ulysses_attention,
                axis_name=AxisName.SEQUENCE,
                inner_attention=inner_attention,
                causal=causal,
            )
        else:
            # a tile override carried by the inner partial must reach
            # the ring's per-block kernel too — seq-sharded strategies
            # are exactly where the solver sizes tiles for the LOCAL
            # sequence
            tile_kwargs = getattr(inner_attention, "keywords", {})
            fn = partial(
                ring_attention,
                axis_name=AxisName.SEQUENCE,
                causal=causal,
                use_flash=use_flash,
                block_q=tile_kwargs.get("block_q"),
                block_k=tile_kwargs.get("block_k"),
            )
        return shard_mapped(
            fn,
            mesh_ctx,
            in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec,
        )(q, k, v)

    return attention
