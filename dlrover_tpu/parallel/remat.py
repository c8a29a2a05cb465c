"""What the scanned block's forward keeps for its backward.

``jax.checkpoint(block)`` keeps a layer's input alone and replays the
whole layer in the backward pass; with memory to spare that replay is
time spent for nothing.  The policies here form a LADDER by bytes kept
a layer, each a ``jax.checkpoint`` policy over values the model names
(``keep``), ordered by replay time saved a byte:

======== ==================================================== ==========
rung     kept beside the layer's input                         replayed
======== ==================================================== ==========
full     nothing                                               the layer
flash    the attention kernel's output and log-sum-exp         all but the
                                                               attention
qkv      + q and k (after RoPE) and v                          + no q/k/v
                                                               products
matmuls  + the residual after ``wo``, the ``gate`` and ``up``  norms and
         products                                              ``silu * up``
none     everything the backward reads (no checkpoint)         nothing
======== ==================================================== ==========

``dots`` (``checkpoint_dots_with_no_batch_dims``) stays what it was and
is not a rung: it is only ever named.

Who decides: a model config or a :class:`~dlrover_tpu.accelerate.
strategy.Strategy` that NAMES a policy gets it; where both say
``auto`` the rung is resolved from the compiled step's own memory
(:func:`resolve_rung`, driven by ``TrainStepFns.resolve_remat`` at the
trainer's first batch), and a step that nobody resolved runs ``full``.
The step is traced inside :func:`scope`, which is how the choice
reaches the model and how the model says what it did with it.
"""

import contextlib
import threading
from dataclasses import asdict, dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

#: "nobody named a policy": the default of ``LlamaConfig.remat`` and of
#: ``Strategy.remat``, distinguishable from a named ``"full"``
AUTO = "auto"

ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"
ATTN_Q = "attn_q"
ATTN_K = "attn_k"
ATTN_V = "attn_v"
ATTN_RESID = "attn_resid"
MLP_GATE = "mlp_gate"
MLP_UP = "mlp_up"

_FLASH = (ATTN_OUT, ATTN_LSE)
_QKV = _FLASH + (ATTN_Q, ATTN_K, ATTN_V)
_MATMULS = _QKV + (ATTN_RESID, MLP_GATE, MLP_UP)

#: rung -> the names it keeps (None: no checkpoint), poorest first
LADDER: Dict[str, Optional[Tuple[str, ...]]] = {
    "full": (),
    "flash": _FLASH,
    "qkv": _QKV,
    "matmuls": _MATMULS,
    "none": None,
}
RICHEST = "none"
POLICIES = frozenset(LADDER) | {"dots"}

#: Device memory the resolver leaves beside the compiled step.  The
#: step's ``memory_analysis`` counts the one program; next to it the
#: trainer holds two prefetched batches and a step's metrics (KBs), and
#: a staged snapshot's outputs are ``pinned_host`` and take no HBM —
#: so this is room for the allocator's fragmentation and for an open
#: profiler window, not for a second program (PERF.md section 4).
RESERVE_BYTES = 512 << 20


def checkpointed(block: Callable, policy: str) -> Callable:
    """``block`` under ``policy`` (a rung of the ladder or ``dots``)."""
    if policy not in POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}: one of {sorted(POLICIES)}"
        )
    if policy == "dots":
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        )
    names = LADDER[policy]
    if names is None:
        return block
    if not names:
        return jax.checkpoint(block)
    return jax.checkpoint(
        block,
        policy=jax.checkpoint_policies.save_only_these_names(*names),
    )


# ------------------------------------------------------- trace-time scope


class _Scope:
    """One trace of the step: the policy it was given from outside the
    model, and what the model reports back."""

    def __init__(self, policy: Optional[str], source: str):
        self.policy = policy
        self.source = source
        self.sizes: Dict[str, int] = {}
        # (policy, source, layers, input_bytes) once a model has
        # checkpointed its block under this scope
        self._reported = None

    @property
    def applied(self) -> Optional[dict]:
        """What the model did, read once the trace is over (the
        attention kernel names its residuals only while the backward
        is built, after the model's forward has returned)."""
        if self._reported is None:
            return None
        policy, source, layers, input_bytes = self._reported
        names = LADDER.get(policy)
        return {
            "policy": policy,
            "source": source,
            "layers": layers,
            # what a named set keeps, in logical (unsharded) bytes;
            # under "none" / "dots" what is kept is autodiff's and the
            # compiler's, not a set this module names
            "kept_bytes_per_layer": (
                None if names is None
                else input_bytes + sum(self.sizes.get(n, 0) for n in names)
            ),
        }


_local = threading.local()


@contextlib.contextmanager
def scope(policy: Optional[str], source: str = "strategy"):
    """Trace the loss under ``policy`` and say whose it is (``source``):
    the ``strategy``'s named one, or a rung that ``resolve_remat`` is
    trying (``resolved``); ``None`` where nobody has decided."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    sc = _Scope(policy, source)
    stack.append(sc)
    try:
        yield sc
    finally:
        stack.pop()


def _active() -> Optional[_Scope]:
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def keep(x, name: str):
    """Name ``x`` for the ladder's policies (an identity otherwise)."""
    sc = _active()
    if sc is not None:
        sc.sizes[name] = x.size * x.dtype.itemsize
    return checkpoint_name(x, name)


def select(named: str) -> Tuple[str, str]:
    """The policy a model applies at trace time and where it came from:
    its config's own name, else the strategy's, else the rung being
    resolved, else ``full``."""
    if named != AUTO:
        return named, "config"
    sc = _active()
    if sc is None or sc.policy is None:
        return "full", "default"
    return sc.policy, sc.source


def report(policy: str, source: str, layers: int, input_bytes: int):
    """The model tells the scope what it checkpointed: read by
    ``resolve_remat`` for the ``remat_plan`` record."""
    sc = _active()
    if sc is not None:
        sc._reported = (policy, source, layers, input_bytes)


# ------------------------------------------------------------- resolver


@dataclass(frozen=True)
class RematPlan:
    """What ran, for the ``remat_plan`` record of the events file."""

    policy: str
    source: str  # config | strategy | resolved | default
    layers: int
    kept_bytes_per_layer: Optional[int]
    step_bytes: Optional[int]
    limit_bytes: Optional[int]
    rungs_tried: int

    def labels(self) -> dict:
        return asdict(self)


def resolve_rung(
    step_bytes: Callable[[str], Optional[int]],
    limit_bytes: int,
    reserve_bytes: int = RESERVE_BYTES,
) -> Tuple[str, Optional[int], List[str]]:
    """The richest rung whose step fits ``limit_bytes`` less the
    reserve: ``step_bytes(rung)`` is the compiled step's bytes under
    that rung, ``None`` where the compiler itself refused it.  Tried
    from ``none`` down; ``full``, today's program, is taken whatever
    it reads.  Returns ``(rung, its bytes, the rungs tried)``."""
    tried: List[str] = []
    room = limit_bytes - reserve_bytes
    for rung in reversed(LADDER):
        tried.append(rung)
        size = step_bytes(rung)
        if rung == "full" or (size is not None and size <= room):
            return rung, size, tried
    raise AssertionError("unreachable: the ladder ends at 'full'")


def compiled_step_bytes(compiled) -> int:
    """A compiled step's device bytes: arguments + outputs − aliased
    (donated arguments are their outputs) + temporaries."""
    m = compiled.memory_analysis()
    return int(
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.temp_size_in_bytes
    )
