"""Logical-axis sharding rules — the strategy engine's output format.

Reference parity: the role of atorch's opt_lib transforms
(``zero_optimization.py:115,240`` ZeRO/FSDP,
``tensor_parallel_optimization.py:23`` TP module replacement,
``mixed_parallel_optimization.py:57``): deciding *how each tensor is
laid out across the cluster*.  In the reference that is a module
rewrite + process-group plumbing; on TPU it is a table mapping
**logical array axes** ("embed", "heads", "mlp", ...) to **mesh axes**,
compiled by GSPMD into collectives.  Strategies differ only in the
table:

- DDP        -> params replicated, batch over ("data","fsdp")
- ZeRO-3/FSDP-> params sharded on "fsdp" along their largest dim
- TP         -> Megatron-style: qkv/mlp-in column, proj/mlp-out row
- SP/EP      -> sequence/expert dims on "seq"/"expert"

so "auto_accelerate" becomes: pick a rule table, shard_pytree, jit.
"""

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.parallel.mesh import AxisName

def shard_map_compat(fn, mesh, in_specs, out_specs,
                     manual_axes=None, check=False):
    """``jax.shard_map`` with this repo's defaults (no vma check).
    ``manual_axes``: the mesh axes the body handles manually (None =
    all of them)."""
    import jax

    kw = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check,
    )
    if manual_axes is not None:
        kw["axis_names"] = set(manual_axes)
    return jax.shard_map(fn, **kw)


# logical axis vocabulary used by model definitions
BATCH = "batch"
SEQ = "seq_len"
EMBED = "embed"
MLP = "mlp"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
VOCAB = "vocab"
EXPERT = "expert"
LAYERS = "layers"


class LogicalAxisRules:
    """Ordered mapping logical-axis -> mesh axis (or tuple of axes).

    First match wins; unlisted logical axes are replicated (None).
    """

    def __init__(self, rules: Sequence[Tuple[str, Optional[object]]]):
        self._rules: List[Tuple[str, Optional[object]]] = list(rules)

    def mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        for name, axes in self._rules:
            if name == logical:
                return axes
        return None

    def spec(self, logical_axes: Sequence[Optional[str]]):
        """PartitionSpec from a tuple of logical axis names."""
        from jax.sharding import PartitionSpec

        used = set()
        entries = []
        for ax in logical_axes:
            target = self.mesh_axes(ax)
            # a mesh axis may appear at most once in a spec
            if target is None:
                entries.append(None)
                continue
            flat = target if isinstance(target, tuple) else (target,)
            if any(a in used for a in flat):
                entries.append(None)
                continue
            used.update(flat)
            entries.append(target)
        return PartitionSpec(*entries)

    def extend(self, extra: Sequence[Tuple[str, Optional[object]]]):
        return LogicalAxisRules(list(extra) + self._rules)

    def uses_axis(self, mesh_axis: str,
                  exclude: Sequence[str] = (BATCH,)) -> bool:
        """True when some rule (outside ``exclude``) targets
        ``mesh_axis`` — i.e. the strategy actively shards params over
        it (BATCH is excluded by default: it always carries the data
        axes for activations regardless of the param strategy)."""
        for name, axes in self._rules:
            if name in exclude:
                continue
            flat = axes if isinstance(axes, tuple) else (axes,)
            if mesh_axis in flat:
                return True
        return False


def default_rules(
    fsdp: bool = True,
    tensor_parallel: bool = False,
    sequence_parallel: bool = False,
    expert_parallel: bool = False,
    pipeline: bool = False,
) -> LogicalAxisRules:
    """The canonical rule tables (strategy selection in one place)."""
    rules: List[Tuple[str, Optional[object]]] = [
        # batch is always sharded over every data-flavored axis
        (BATCH, (AxisName.DATA, AxisName.FSDP)),
    ]
    if pipeline:
        # stacked layer dim becomes the stage dim; the layer executor
        # (module_replace.select_layer_executor) runs the GPipe
        # shard_map over it
        rules.append((LAYERS, AxisName.PIPELINE))
    if sequence_parallel:
        rules.append((SEQ, AxisName.SEQUENCE))
    if tensor_parallel:
        rules += [
            (HEADS, AxisName.TENSOR),
            (KV_HEADS, AxisName.TENSOR),
            (MLP, AxisName.TENSOR),
            (VOCAB, AxisName.TENSOR),
        ]
    if expert_parallel:
        rules.append((EXPERT, AxisName.EXPERT))
    if fsdp:
        # ZeRO-3: shard the big parameter dim over the fsdp axis
        rules.append((EMBED, AxisName.FSDP))
    return LogicalAxisRules(rules)


_scope = threading.local()


@contextlib.contextmanager
def rules_scope(rules: "LogicalAxisRules"):
    """Bind the active rule table for the duration of a trace.

    ``build_train_step`` wraps its loss invocation in this scope so the
    activation constraints a model emits are resolved against the same
    table that sharded its params — captured at trace time, immune to
    later builds mutating shared context (two train steps built against
    different strategies each bake in their own rules)."""
    stack = getattr(_scope, "stack", None)
    if stack is None:
        stack = _scope.stack = []
    stack.append(rules)
    try:
        yield rules
    finally:
        stack.pop()


def active_rules() -> Optional["LogicalAxisRules"]:
    stack = getattr(_scope, "stack", None)
    return stack[-1] if stack else None


def filter_spec_for_mesh(spec, mesh):
    """Drop spec entries referencing axes the mesh doesn't have (a rule
    table is strategy-global; the mesh picks which axes exist)."""
    from jax.sharding import PartitionSpec

    mesh_axes = set(mesh.axis_names)
    entries = []
    for e in spec:
        flat = e if isinstance(e, tuple) else (e,)
        if e is None or all(a in mesh_axes for a in flat):
            entries.append(e)
        else:
            present = tuple(a for a in flat if a in mesh_axes)
            entries.append(
                present if len(present) > 1
                else (present[0] if present else None)
            )
    return PartitionSpec(*entries)


def logical_sharding(mesh, rules: LogicalAxisRules, logical_axes):
    from jax.sharding import NamedSharding

    return NamedSharding(
        mesh, filter_spec_for_mesh(rules.spec(logical_axes), mesh)
    )


def param_sharding_with_fsdp(
    mesh,
    rules: LogicalAxisRules,
    logical_axes,
    shape,
    fsdp_axis: str = AxisName.FSDP,
):
    """Parameter sharding with shape-aware ZeRO-3 placement.

    The rule table maps logical axes to mesh axes; on top of that, the
    fsdp axis is placed on the param's LARGEST still-unsharded,
    divisible dim (reference ``zero_optimization.py:240`` FSDP shards
    the flattened param; the GSPMD dual is choosing the dim so every
    parameter — not only those carrying a designated logical axis —
    shards over fsdp, and the all-gather rides the biggest dim).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    spec = filter_spec_for_mesh(rules.spec(logical_axes), mesh)
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsdp_size = mesh_axes.get(fsdp_axis, 1)
    if fsdp_size <= 1:
        return NamedSharding(mesh, spec)
    used = set()
    for e in spec:
        for a in e if isinstance(e, tuple) else (e,):
            if a is not None:
                used.add(a)
    if fsdp_axis in used:
        return NamedSharding(mesh, spec)
    # candidate dims: unsharded, divisible by the fsdp size; biggest wins
    candidates = [
        (dim_size, i)
        for i, (dim_size, e) in enumerate(zip(shape, spec))
        if e is None and dim_size % fsdp_size == 0 and dim_size > 1
    ]
    if not candidates:
        return NamedSharding(mesh, spec)
    _, dim = max(candidates)
    entries = list(spec)
    entries[dim] = fsdp_axis
    return NamedSharding(mesh, PartitionSpec(*entries))


def shard_pytree(pytree, axes_pytree, mesh, rules: LogicalAxisRules):
    """Produce a NamedSharding pytree from a logical-axes pytree with
    the same structure (the model exports the latter)."""
    import jax

    return jax.tree_util.tree_map(
        lambda axes: logical_sharding(mesh, rules, axes),
        axes_pytree,
        is_leaf=lambda x: isinstance(x, (tuple, type(None))),
    )


def apply_sharding_constraint(x, logical_axes, rules: LogicalAxisRules):
    """In-graph activation-sharding constraint; a no-op when no global
    mesh is set (eager debugging / single device).

    Inside a partial-manual ``shard_map`` region (the GPipe layer
    executor runs the stage body with the "pipe" axis manual) the
    constraint must be expressed against the ambient abstract mesh —
    a NamedSharding over the outer all-Auto mesh trips a mesh-type
    mismatch — with the manual axes dropped from the spec (the array
    is already per-device along them)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.parallel.mesh import get_mesh_context

    ctx = get_mesh_context()
    if ctx is None:
        return x
    spec = filter_spec_for_mesh(rules.spec(logical_axes), ctx.mesh)
    try:
        amesh = jax.sharding.get_abstract_mesh()
        manual = {
            name
            for name, t in zip(amesh.axis_names, amesh.axis_types)
            if "Manual" in str(t)
        }
    except Exception:  # noqa: BLE001
        amesh, manual = None, set()
    if manual:
        entries = []
        for e in spec:
            flat = e if isinstance(e, tuple) else (e,)
            keep = tuple(
                a for a in flat if a is not None and a not in manual
            )
            entries.append(
                keep if len(keep) > 1 else (keep[0] if keep else None)
            )
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(amesh, PartitionSpec(*entries))
        )
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec)
    )
