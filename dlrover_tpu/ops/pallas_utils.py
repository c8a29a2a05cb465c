"""Shared plumbing for the repo's Pallas/Mosaic kernel families.

Every kernel family (``ops/flash_attention.py`` dense flash,
``ops/paged_kernels.py`` paged decode/verify, ``ops/fused.py`` RMSNorm
and ``ops/quantization.py`` int8 quant / fused Adam) compiles to
Mosaic on TPU and runs in Pallas *interpret mode* everywhere else, so
CPU CI exercises the exact same kernel bodies the TPU runs — just
slowly.  This module is the ONE place that decides which; no kernel
file keeps a copy of the policy.

Env contract (one env for all kernels):

- ``DLROVER_TPU_PALLAS_INTERPRET=1|true|on``  -> force interpret mode
  off-TPU (how CPU CI reaches the paged kernels).  On a TPU backend
  this RAISES: an interpreted kernel on the chip would make a broken
  chip run look fine.
- ``DLROVER_TPU_PALLAS_INTERPRET=0|false|off`` -> force compiled mode
  without asking JAX for its backend; on a non-TPU host Mosaic will
  refuse to lower and the call fails loudly.  This is also the switch
  a compile-for-a-described-TPU rehearsal sets (the CPU is the default
  backend there, but the lowering targets the described chip).
- unset -> interpret exactly when the default JAX backend is not TPU.
"""

from __future__ import annotations

import os

import jax

INTERPRET_ENV = "DLROVER_TPU_PALLAS_INTERPRET"

_TRUE = ("1", "true", "on", "yes")
_FALSE = ("0", "false", "off", "no")


def use_interpret() -> bool:
    """Should Pallas kernels run in interpret mode on this host?

    Read at trace time (the value is baked into each compiled
    executable), so flipping the env between jits takes effect on the
    next trace, not retroactively.
    """
    raw = os.getenv(INTERPRET_ENV, "").strip().lower()
    if raw in _FALSE:
        return False
    on_tpu = jax.default_backend() == "tpu"
    if raw in _TRUE and on_tpu:
        raise RuntimeError(
            f"{INTERPRET_ENV}={raw!r} on a TPU backend: Pallas kernels "
            "never run interpreted on the chip"
        )
    return raw in _TRUE or not on_tpu


def named_kernel(name: str, call):
    """``call`` — the function a ``pl.pallas_call`` returns — under a
    stable name in the device trace.

    Pallas stages its kernel through an anonymous wrapper, so the
    kernel's event on the trace's ``XLA Ops`` line is the compiler's
    ``closed_call.N``, which no reduction can pick out.  XLA inlines a
    nested ``jit`` and gives the CALL's name to the root of what it
    inlined; with the custom call as that root (``call`` must be the
    whole body — no slicing or reshaping after it in here) the event
    reads ``<name>.N`` from run to run.  Pass the same ``name`` to
    ``pl.pallas_call`` so the Mosaic kernel carries it too.  Nothing
    about what the kernel computes changes."""

    def kernel(*args):
        return call(*args)

    kernel.__name__ = kernel.__qualname__ = name
    return jax.jit(kernel)
