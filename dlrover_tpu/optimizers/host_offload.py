"""Host-offloaded AdamW: optimizer state lives in HOST memory.

Reference parity: ``atorch/atorch/optimizers/adam_offload.py`` (309
LoC: fp32 master params + Adam moments on the host, bucket-wise
grad D2H / param H2D around a CPU AVX update).  A v5e chip has 16 GB
HBM; fp32 AdamW costs 16 bytes/param of resident state (master + two
moments) + 2 bytes of bf16 compute params — host-resident state is
the standard lever past ~1B params/chip when int8 moments are not
enough.

TPU redesign (single-chip scale lever; on pods the same state is
SHARDED over the fsdp axis instead — ``parallel/train_step.py``):

- device holds only **bf16 compute params**; fp32 master params and
  fp32 moments live in HOST memory (host DRAM, no HBM).
- backward runs as one jit (bf16 params -> bf16 grads).
- the update streams CHUNKS of (master, mu, nu, grad) through the
  chip: H2D in, fused Adam math on device, bf16 param chunk + updated
  fp32 chunks out.  Chunking bounds the HBM transient to
  ``6 * chunk_bytes`` regardless of leaf size (the reference's bucket
  loop, same reason).

Two storage backends for the host state:

- ``pinned_host`` (default on TPU): chunks are jax arrays with
  ``memory_kind="pinned_host"`` — resident in the **TPU host's** RAM
  and DMA'd over its PCIe by XLA-compiled transfer programs, with
  donation recycling the host buffers.  This is the XLA-memories
  redesign of the reference's cudaMemcpy bucket loop.
- ``numpy`` (default on CPU/tests): plain in-process numpy buffers,
  updated in place, with a sliding in-flight window overlapping
  transfers and compute.

Either way the state checkpoints through the flash-ckpt engine:
leaves are ``device_get``-able (numpy ones already are).

``moments="int8"`` additionally stores the offloaded moments
blockwise-quantized (the host-offload dual of
``optimizers.quantized_moments``): the per-step stream drops from
~24 to ~12 bytes/param — the offload proof is PCIe-bound (~59% of
device time in chunk DMA), so halving the traffic is the single
biggest lever.  ``nu`` stores sqrt(nu) exactly like the resident int8
optimizer (dynamic-range rationale in ``optimizers/low_bit.py``).
"""

import functools
import os
from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.jax_env import pinned_host_works
from dlrover_tpu.common.log import default_logger as logger

# 64M elements = 256 MB per fp32 chunk buffer; the update transient is
# ~6 buffers (3 in, 3 out) plus the resident bf16 params and grads
DEFAULT_CHUNK_ELEMS = 64 * 1024 * 1024

#: kill-switch: ``=0`` restores the pre-DMA-pipeline behavior exactly
#: (one-shot first-window prefetch instead of the rolling
#: double-buffered window)
OFFLOAD_BUFFERED_ENV = "DLROVER_TPU_OFFLOAD_BUFFERED"
#: kill-switch for the quantized optimizer-state TRANSFERS (fp32
#: moments moved across the host boundary as int8+scales): ``=0``
#: forces fp32 wire format, ``=1`` forces int8, unset = int8 only
#: where a real PCIe boundary exists (TPU backend)
OFFLOAD_QUANT_ENV = "DLROVER_TPU_OFFLOAD_QUANT"


def _buffered_enabled() -> bool:
    return os.getenv(OFFLOAD_BUFFERED_ENV, "1") != "0"


class OffloadState(NamedTuple):
    """Train state for the offloaded path.  ``params`` is the bf16
    device tree the forward consumes.  Host-state layout by
    configuration:

    - numpy + fp32 moments: master/mu/nu mirror the params tree with
      whole-leaf numpy arrays (updated in place);
    - pinned_host + fp32: per-leaf LISTS of host-memory chunk arrays;
    - int8 moments (either backend): master as above, mu/nu as
      per-leaf LISTS of ``(int8_payload, block_scales)`` tuples, one
      per chunk (payload padded to the quant block).
    """

    step: int
    params: Dict  # bf16, device
    master: Dict  # fp32, host
    mu: Dict      # fp32, host
    nu: Dict      # fp32, host


def _adamw_chunk_math(master, mu, nu, grad, bc1, bc2,
                      *, lr, b1, b2, eps, wd):
    """THE AdamW update over one fp32 chunk — the single source of
    the math for both storage backends (a fix applied to one must not
    silently miss the other).  ``wd`` may be a traced scalar (the
    fused delayed schedule gates decay off on its no-op first step);
    a static 0 still skips the term entirely."""
    g = grad.astype(jnp.float32)
    mu = b1 * mu + (1.0 - b1) * g
    nu = b2 * nu + (1.0 - b2) * g * g
    update = (mu / bc1) / (jnp.sqrt(nu / bc2) + eps)
    if not isinstance(wd, (int, float)) or wd:
        update = update + wd * master
    master = master - lr * update
    return master, mu, nu, master.astype(jnp.bfloat16)


# int8 moment quantization block — the SAME block the resident int8
# optimizer quantizes over (a retune there must not silently diverge)
from dlrover_tpu.ops.quantization import BLOCK as _QBLOCK  # noqa: E402


def _deq_chunk(q, scales, n):
    """int8 [padded] + per-1024-block scales -> fp32 [n]."""
    x = q.astype(jnp.float32).reshape(-1, _QBLOCK) * scales[:, None]
    return x.reshape(-1)[:n]


def _np_quant_chunk(x: np.ndarray):
    """Host-side mirror of :func:`_quant_chunk` (same block layout,
    same absmax/127 scales) for the quantized TRANSFER path: fp32
    moments that stay fp32 in host storage are quantized on the host
    right before the H2D dispatch, so only int8+scales cross the
    boundary."""
    x = np.asarray(x, dtype=np.float32)
    n = x.shape[0]
    pad = (-n) % _QBLOCK
    if pad:
        x = np.pad(x, (0, pad))
    blocks = x.reshape(-1, _QBLOCK)
    scales = np.maximum(
        np.max(np.abs(blocks), axis=1) / 127.0, 1e-12
    ).astype(np.float32)
    q = np.clip(
        np.round(blocks / scales[:, None]), -127, 127
    ).astype(np.int8)
    return q.reshape(-1), scales


def _np_deq_chunk(q: np.ndarray, scales: np.ndarray, n: int):
    """Host-side mirror of :func:`_deq_chunk` for the D2H writeback."""
    x = (
        np.asarray(q, np.float32).reshape(-1, _QBLOCK)
        * np.asarray(scales, np.float32)[:, None]
    )
    return x.reshape(-1)[:n]


def _quant_chunk(x):
    """fp32 [n] -> (int8 [padded], per-block scales).  Plain jnp: the
    op is memory-bound and lives inside the chunk jit, so XLA fuses it
    into the same pass as the update math."""
    n = x.shape[0]
    pad = (-n) % _QBLOCK
    if pad:
        x = jnp.pad(x, (0, pad))
    blocks = x.reshape(-1, _QBLOCK)
    scales = jnp.maximum(
        jnp.max(jnp.abs(blocks), axis=1) / 127.0, 1e-12
    )
    q = jnp.clip(
        jnp.round(blocks / scales[:, None]), -127, 127
    ).astype(jnp.int8)
    return q.reshape(-1), scales


def _adamw_chunk_math_q(master, mu_q, mu_s, nu_q, nu_s, grad,
                        bc1, bc2, *, lr, b1, b2, eps, wd):
    """AdamW over one chunk with int8-quantized moments: dequant ->
    THE shared math -> requant, all inside one jit pass.  nu is
    stored as sqrt(nu) (see optimizers/low_bit.py for the
    dynamic-range rationale); squaring it reconstructs the value the
    shared update consumes."""
    n = master.shape[0]
    mu = _deq_chunk(mu_q, mu_s, n)
    nu_root = _deq_chunk(nu_q, nu_s, n)
    master, mu, nu, p_bf16 = _adamw_chunk_math(
        master, mu, nu_root * nu_root, grad, bc1, bc2,
        lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
    )
    mu_q2, mu_s2 = _quant_chunk(mu)
    nu_q2, nu_s2 = _quant_chunk(jnp.sqrt(nu))
    return master, mu_q2, mu_s2, nu_q2, nu_s2, p_bf16


@functools.partial(
    jax.jit,
    static_argnames=("lr", "b1", "b2", "eps", "wd"),
    donate_argnums=(0, 1, 2),
)
def _chunk_update(master, mu, nu, grad, bc1, bc2,
                  *, lr, b1, b2, eps, wd):
    """numpy-backend entry: plain device in/out chunks."""
    return _adamw_chunk_math(
        master, mu, nu, grad, bc1, bc2,
        lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
    )


@functools.partial(
    jax.jit,
    static_argnames=("lr", "b1", "b2", "eps", "wd"),
    donate_argnums=(0, 1, 2, 3, 4),
)
def _chunk_update_q(master, mu_q, mu_s, nu_q, nu_s, grad, bc1, bc2,
                    *, lr, b1, b2, eps, wd):
    """numpy-backend entry, int8 moments."""
    return _adamw_chunk_math_q(
        master, mu_q, mu_s, nu_q, nu_s, grad, bc1, bc2,
        lr=lr, b1=b1, b2=b2, eps=eps, wd=wd,
    )


class _RollingPrefetch:
    """Double-buffered H2D stream over the chunk sequence.

    The one-shot prefetch only hid the FIRST ``window`` chunks' H2D
    under the backward; every later chunk's transfer was dispatched
    immediately before its own compute, serializing copy against math.
    This object keeps a rolling window: consuming chunk ``k``
    (:meth:`get`) dispatches the H2D of chunk ``k + window``, so the
    transfer of the next chunks always overlaps the in-flight chunks'
    update math — the out-of-program form of the fused path's
    barrier-windowed copy pipeline.  Per-chunk host buffers are READ
    ONLY ahead of their own writeback (each chunk is written exactly
    once, strictly after its own compute), so early staging can never
    observe a torn update."""

    def __init__(self, opt, leaves_m, leaves_mu, leaves_nu,
                 quant: bool):
        self._opt = opt
        self._m = leaves_m
        self._mu = leaves_mu
        self._nu = leaves_nu
        self._quant = quant
        self._entries: Dict = {}
        self._order = []
        for li, m in enumerate(leaves_m):
            for j, sl in enumerate(opt._chunk_slices(m.size)):
                self._order.append((li, j, sl))
        self._cursor = 0
        for _ in range(opt.window):
            self._dispatch_next()

    def _dispatch_next(self):
        if self._cursor >= len(self._order):
            return
        li, j, sl = self._order[self._cursor]
        self._cursor += 1
        self._entries[(li, j)] = self._opt._stage_chunk(
            self._m, self._mu, self._nu, li, j, sl,
            quant=self._quant,
        )

    def get(self, key):
        """Consume one chunk's staged inputs and refill the window."""
        entry = self._entries.pop(key, None)
        self._dispatch_next()
        return entry

    def __len__(self):
        return len(self._entries)


class _OneShotPrefetch(dict):
    """Legacy first-window prefetch dict.  Carries the staging-time
    quant flag so ``_apply_numpy`` unpacks the staged tuples with the
    arity they were built with, even if ``DLROVER_TPU_OFFLOAD_QUANT``
    flips between ``start_prefetch`` and ``apply_gradients``."""

    def __init__(self, quant: bool):
        super().__init__()
        self._quant = quant


class HostOffloadAdamW:
    """AdamW whose fp32 state never resides in HBM.

    Not an optax transformation on purpose: optax updates live inside
    one jit over device state, which is exactly what offload must
    avoid.  Use with :func:`build_offloaded_train_step` or drive
    ``init``/``apply_gradients`` directly.
    """

    def __init__(
        self,
        learning_rate: float = 1e-3,
        b1: float = 0.9,
        b2: float = 0.999,
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        chunk_elems: int = DEFAULT_CHUNK_ELEMS,
        max_in_flight: int = 2,
        backend: str = "auto",
        moments: str = "fp32",
    ):
        self.lr = learning_rate
        self.b1 = b1
        self.b2 = b2
        self.eps = eps
        self.wd = weight_decay
        self.chunk = int(chunk_elems)
        self.window = max(1, int(max_in_flight))
        if moments not in ("fp32", "int8"):
            raise ValueError(f"unknown moments dtype {moments!r}")
        self.moments = moments
        if backend == "auto":
            backend = (
                "pinned_host"
                if jax.default_backend() == "tpu"
                else "numpy"
            )
        if backend not in ("numpy", "pinned_host"):
            raise ValueError(f"unknown offload backend {backend!r}")
        self.backend = backend

    # ------------------------------------------- pinned_host helpers
    def _shardings(self):
        from jax.sharding import SingleDeviceSharding

        dev = SingleDeviceSharding(jax.devices()[0])
        if pinned_host_works():
            host = dev.with_memory_kind("pinned_host")
        else:
            host = dev
        return dev, host

    def _pinned_update_fn(self):
        """Chunk update compiled with host-memory in/out shardings;
        donation recycles the TPU-host buffers so steady state
        allocates nothing.

        The grad arrives as the WHOLE flat leaf plus a traced offset
        and is sliced INSIDE the program: slicing outside would
        materialize a second full copy of the grads as in-flight
        slice buffers (measured: the difference between the 1.8B
        accumulated proof fitting and OOMing)."""
        if getattr(self, "_pinned_fn", None) is not None:
            return self._pinned_fn
        from jax import lax

        dev, host = self._shardings()
        hyper = dict(
            lr=self.lr, b1=self.b1, b2=self.b2, eps=self.eps,
            wd=self.wd,
        )

        if self.moments == "int8":

            def body(master, mu_q, mu_s, nu_q, nu_s, grad_leaf, off,
                     bc1, bc2):
                # flatten + slice IN-program: an eager reshape outside
                # would materialize a full second copy of the grads
                # at dispatch time (jit specializes per leaf shape —
                # a handful of executables, not one per chunk)
                grad = lax.dynamic_slice(
                    grad_leaf.reshape(-1), (off,),
                    (master.shape[0],),
                )
                outs = _adamw_chunk_math_q(
                    jax.device_put(master, dev),
                    jax.device_put(mu_q, dev),
                    jax.device_put(mu_s, dev),
                    jax.device_put(nu_q, dev),
                    jax.device_put(nu_s, dev),
                    grad, bc1, bc2, **hyper,
                )
                return tuple(
                    jax.device_put(o, host) for o in outs[:5]
                ) + (outs[5],)

            self._pinned_fn = jax.jit(
                body,
                in_shardings=(host,) * 5 + (dev, None, None, None),
                out_shardings=(host,) * 5 + (dev,),
                donate_argnums=(0, 1, 2, 3, 4),
            )
        else:

            def body(master, mu, nu, grad_leaf, off, bc1, bc2):
                grad = lax.dynamic_slice(
                    grad_leaf.reshape(-1), (off,),
                    (master.shape[0],),
                )
                # host->HBM in, shared AdamW math, HBM->host out
                m_d, mu_d, nu_d, p_bf16 = _adamw_chunk_math(
                    jax.device_put(master, dev),
                    jax.device_put(mu, dev),
                    jax.device_put(nu, dev),
                    grad, bc1, bc2, **hyper,
                )
                return (
                    jax.device_put(m_d, host),
                    jax.device_put(mu_d, host),
                    jax.device_put(nu_d, host),
                    p_bf16,
                )

            self._pinned_fn = jax.jit(
                body,
                in_shardings=(host, host, host, dev, None, None,
                              None),
                out_shardings=(host, host, host, dev),
                donate_argnums=(0, 1, 2),
            )
        return self._pinned_fn

    @staticmethod
    def _q_padded(n: int) -> int:
        return ((n + _QBLOCK - 1) // _QBLOCK) * _QBLOCK

    def _chunk_slices(self, n: int):
        return [
            slice(lo, min(lo + self.chunk, n))
            for lo in range(0, n, self.chunk)
        ]

    # ----------------------------------------------------------- init
    def init(self, params) -> OffloadState:
        """``params``: any pytree of arrays (host or device).  Master
        copies and moments materialize on the host; the returned
        ``params`` tree is bf16 on device."""
        if self.backend == "pinned_host":
            return self._init_pinned(params)
        return self._init_numpy(params)

    def _init_pinned(self, params) -> OffloadState:
        _, host = self._shardings()
        leaves, treedef = jax.tree_util.tree_flatten(params)
        master, mu, nu, bf16 = [], [], [], []
        for leaf in leaves:
            arr = jnp.asarray(leaf)
            flat = arr.reshape(-1).astype(jnp.float32)
            m_chunks, mu_chunks, nu_chunks = [], [], []
            for sl in self._chunk_slices(flat.shape[0]):
                chunk = flat[sl]
                m_chunks.append(jax.device_put(chunk, host))
                # mu and nu get DISTINCT zero buffers: device_put of
                # the same array can return an aliased buffer, and
                # aliased leaves break donation in the fused step
                if self.moments == "int8":
                    padded = self._q_padded(chunk.shape[0])

                    def zq():
                        return jax.device_put(
                            jnp.zeros((padded,), jnp.int8), host
                        )

                    def zs():
                        return jax.device_put(
                            jnp.zeros(
                                (padded // _QBLOCK,), jnp.float32
                            ),
                            host,
                        )

                    mu_chunks.append((zq(), zs()))
                    nu_chunks.append((zq(), zs()))
                else:
                    mu_chunks.append(
                        jax.device_put(
                            jnp.zeros(chunk.shape, jnp.float32),
                            host,
                        )
                    )
                    nu_chunks.append(
                        jax.device_put(
                            jnp.zeros(chunk.shape, jnp.float32),
                            host,
                        )
                    )
            master.append(m_chunks)
            mu.append(mu_chunks)
            nu.append(nu_chunks)
            bf16.append(arr.astype(jnp.bfloat16))
            del arr, flat  # the fp32 device copy must not linger
        unf = jax.tree_util.tree_unflatten
        return OffloadState(
            step=0,
            params=unf(treedef, bf16),
            master=unf(treedef, master),
            mu=unf(treedef, mu),
            nu=unf(treedef, nu),
        )

    def _init_numpy(self, params) -> OffloadState:
        # np.array (not asarray/ascontiguousarray): a jax Array's
        # zero-copy numpy view is READ-ONLY, and the writeback path
        # updates reshape(-1) views of these buffers in place — they
        # must be owned, contiguous, writable host memory
        master = jax.tree_util.tree_map(
            lambda p: np.array(p, dtype=np.float32, order="C"),
            params,
        )
        if self.moments == "int8":
            def zq_chunks(p):
                out = []
                for sl in self._chunk_slices(p.size):
                    padded = self._q_padded(sl.stop - sl.start)
                    out.append(
                        (
                            np.zeros((padded,), np.int8),
                            np.zeros(
                                (padded // _QBLOCK,), np.float32
                            ),
                        )
                    )
                return out

            mu = jax.tree_util.tree_map(zq_chunks, master)
            nu = jax.tree_util.tree_map(zq_chunks, master)
        else:
            mu = jax.tree_util.tree_map(
                lambda p: np.zeros(p.shape, np.float32), master
            )
            nu = jax.tree_util.tree_map(
                lambda p: np.zeros(p.shape, np.float32), master
            )
        bf16 = jax.tree_util.tree_map(
            lambda p: jnp.asarray(p, dtype=jnp.bfloat16), master
        )
        return OffloadState(
            step=0, params=bf16, master=master, mu=mu, nu=nu
        )

    # --------------------------------------------------------- update
    def _transfer_quant(self) -> bool:
        """Whether fp32 moments cross the host boundary quantized
        (int8 payload + per-block scales — ~4x less moment traffic
        each way).  Host STORAGE stays fp32 (checkpoint format
        unchanged); only the wire format changes, which is why this
        is a per-step decision, not an init-time one.  Defaults on
        only where a real transfer link exists (TPU backend);
        ``DLROVER_TPU_OFFLOAD_QUANT=0/1`` overrides."""
        if self.moments != "fp32" or self.backend != "numpy":
            return False  # int8 moments already transfer quantized
        raw = os.getenv(OFFLOAD_QUANT_ENV, "")
        if raw == "0":
            return False
        if raw == "1":
            return True
        return jax.default_backend() == "tpu"

    def _stage_chunk(self, leaves_m, leaves_mu, leaves_nu,
                     li: int, j: int, sl: slice, quant: bool = False):
        """Dispatch the async H2D of ONE chunk's host state; returns
        the device-input tuple the chunk jit consumes.  With
        ``quant`` (fp32 moments, quantized transfers) the moments are
        blockwise-quantized host-side first — nu as sqrt(nu), the
        same wire convention as the int8-moment storage format — so
        the H2D carries 1 byte/elem instead of 4."""
        flat_m = leaves_m[li].reshape(-1)
        if self.moments == "int8":
            mu_q, mu_s = leaves_mu[li][j]
            nu_q, nu_s = leaves_nu[li][j]
            return (
                jnp.asarray(flat_m[sl]),
                jnp.asarray(mu_q), jnp.asarray(mu_s),
                jnp.asarray(nu_q), jnp.asarray(nu_s),
            )
        flat_mu = leaves_mu[li].reshape(-1)
        flat_nu = leaves_nu[li].reshape(-1)
        if quant:
            mu_q, mu_s = _np_quant_chunk(flat_mu[sl])
            nu_q, nu_s = _np_quant_chunk(np.sqrt(flat_nu[sl]))
            return (
                jnp.asarray(flat_m[sl]),
                jnp.asarray(mu_q), jnp.asarray(mu_s),
                jnp.asarray(nu_q), jnp.asarray(nu_s),
            )
        return (
            jnp.asarray(flat_m[sl]),
            jnp.asarray(flat_mu[sl]),
            jnp.asarray(flat_nu[sl]),
        )

    @staticmethod
    def _emit_stream_span(
        duration_s: float, nbytes: int, buffered: bool,
    ):
        """One ``offload_copy`` span per chunk-streamed update: the
        host<->device optimizer-state traffic with its measured
        throughput, tagged ``buffered`` so the double-buffered and
        serial pipelines stay distinguishable in the timeline (and in
        the ``dlrover_tpu_offload_gbps`` gauge).  Must be called at
        stream end: the span start is reconstructed as anchored "now"
        minus ``duration_s`` so it sits on the same clock as B/E
        records."""
        try:
            from dlrover_tpu.common.parallel_io import throughput_gbps
            from dlrover_tpu.observability.events import (
                anchored_now,
                get_event_logger,
            )
            from dlrover_tpu.observability.metrics import (
                record_offload_io,
            )

            events = get_event_logger()
            events.complete(
                "offload_copy",
                anchored_now() - max(duration_s, 0.0),
                duration_s,
                bytes=int(nbytes),
                throughput_gbps=throughput_gbps(nbytes, duration_s),
                buffered=bool(buffered),
            )
            record_offload_io(nbytes, duration_s, buffered)
        except Exception:  # noqa: BLE001 - observability only
            pass

    def start_prefetch(self, state: OffloadState):
        """Start the H2D stream of host state (numpy backend).
        Called BEFORE backward so the first window's transfers
        overlap the compute; the returned object feeds
        :meth:`apply_gradients`.

        Default: a :class:`_RollingPrefetch` — the window REFILLS as
        chunks are consumed, so every chunk's H2D (not just the first
        window's) overlaps the previous chunks' update math.
        ``DLROVER_TPU_OFFLOAD_BUFFERED=0`` restores the legacy
        one-shot first-window dict exactly.  The pinned_host backend
        overlaps via :func:`build_fused_offload_step` instead
        (out-of-program ``device_put`` dispatch overhead makes
        per-chunk prefetch a loss there)."""
        if self.backend != "numpy":
            return None
        leaves_m, treedef = jax.tree_util.tree_flatten(state.master)
        leaves_mu = treedef.flatten_up_to(state.mu)
        leaves_nu = treedef.flatten_up_to(state.nu)
        quant = self._transfer_quant()
        if _buffered_enabled():
            return _RollingPrefetch(
                self, leaves_m, leaves_mu, leaves_nu, quant
            )
        prefetched = _OneShotPrefetch(quant)
        budget = self.window
        for li, m in enumerate(leaves_m):
            for j, sl in enumerate(self._chunk_slices(m.size)):
                if budget <= 0:
                    return prefetched
                prefetched[(li, j)] = self._stage_chunk(
                    leaves_m, leaves_mu, leaves_nu, li, j, sl,
                    quant=quant,
                )
                budget -= 1
        return prefetched

    def apply_gradients(
        self, state: OffloadState, grads, prefetched=None
    ) -> OffloadState:
        """One AdamW step.  ``grads``: device pytree matching
        ``state.params``.  Streams chunks through the chip; host
        buffers are recycled (donation on pinned_host, in-place numpy
        otherwise).  ``prefetched``: optional chunk window from
        :meth:`start_prefetch`."""
        if self.backend == "pinned_host":
            return self._apply_pinned(state, grads)
        return self._apply_numpy(state, grads, prefetched)

    def _apply_pinned(
        self, state: OffloadState, grads
    ) -> OffloadState:
        step = state.step + 1
        bc1 = jnp.float32(1.0 - self.b1**step)
        bc2 = jnp.float32(1.0 - self.b2**step)
        fn = self._pinned_update_fn()
        leaves_m, treedef = jax.tree_util.tree_flatten(
            state.master, is_leaf=lambda x: isinstance(x, list)
        )
        leaves_mu = treedef.flatten_up_to(state.mu)
        leaves_nu = treedef.flatten_up_to(state.nu)
        leaves_p = treedef.flatten_up_to(state.params)
        leaves_g = treedef.flatten_up_to(grads)
        new_m, new_mu, new_nu, new_p = [], [], [], []
        for li, m_chunks in enumerate(leaves_m):
            shape = leaves_p[li].shape
            flat_g = leaves_g[li]  # flattened INSIDE the chunk jit
            slices = self._chunk_slices(flat_g.size)
            ms, mus, nus, ps = [], [], [], []
            for j, sl in enumerate(slices):
                off = jnp.int32(sl.start)
                if self.moments == "int8":
                    mu_q, mu_s = leaves_mu[li][j]
                    nu_q, nu_s = leaves_nu[li][j]
                    (m_h, mu_q2, mu_s2, nu_q2, nu_s2, p_d) = fn(
                        m_chunks[j], mu_q, mu_s, nu_q, nu_s,
                        flat_g, off, bc1, bc2,
                    )
                    mus.append((mu_q2, mu_s2))
                    nus.append((nu_q2, nu_s2))
                else:
                    m_h, mu_h, nu_h, p_d = fn(
                        m_chunks[j],
                        leaves_mu[li][j],
                        leaves_nu[li][j],
                        flat_g,
                        off,
                        bc1,
                        bc2,
                    )
                    mus.append(mu_h)
                    nus.append(nu_h)
                ms.append(m_h)
                ps.append(p_d)
            new_m.append(ms)
            new_mu.append(mus)
            new_nu.append(nus)
            flat_p = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
            new_p.append(flat_p.reshape(shape))
        unf = jax.tree_util.tree_unflatten
        return OffloadState(
            step=step,
            params=unf(treedef, new_p),
            master=unf(treedef, new_m),
            mu=unf(treedef, new_mu),
            nu=unf(treedef, new_nu),
        )

    def _apply_numpy(
        self, state: OffloadState, grads, prefetched=None
    ) -> OffloadState:
        import time as _time

        prefetched = prefetched or {}
        step = state.step + 1
        bc1 = jnp.float32(1.0 - self.b1**step)
        bc2 = jnp.float32(1.0 - self.b2**step)

        leaves_m, treedef = jax.tree_util.tree_flatten(state.master)
        leaves_mu = treedef.flatten_up_to(state.mu)
        leaves_nu = treedef.flatten_up_to(state.nu)
        leaves_g = treedef.flatten_up_to(grads)

        new_param_chunks: Dict[int, list] = {}
        in_flight = []  # (leaf_idx, chunk_slice, chunk_idx, results)

        int8 = self.moments == "int8"
        # unpack staged chunks with the arity they were staged with:
        # the prefetch window pins the quant flag at start_prefetch
        # time, so an env flip between the two calls cannot mismatch
        # the in-flight tuples
        tq = getattr(prefetched, "_quant", None)
        if tq is None:
            tq = self._transfer_quant()
        buffered = isinstance(prefetched, _RollingPrefetch)
        hyper = dict(
            lr=self.lr, b1=self.b1, b2=self.b2, eps=self.eps,
            wd=self.wd,
        )
        t0 = _time.perf_counter()
        stream_bytes = 0

        def drain_one():
            li, sl, j, res = in_flight.pop(0)
            if int8:
                m_d, mu_q, mu_s, nu_q, nu_s, p_d = res
                np.copyto(
                    leaves_m[li].reshape(-1)[sl], np.asarray(m_d)
                )
                qb, sb = leaves_mu[li][j]
                np.copyto(qb, np.asarray(mu_q))
                np.copyto(sb, np.asarray(mu_s))
                qb, sb = leaves_nu[li][j]
                np.copyto(qb, np.asarray(nu_q))
                np.copyto(sb, np.asarray(nu_s))
            elif tq:
                # quantized wire, fp32 storage: dequantize back into
                # the SAME fp32 host buffers (nu travels as sqrt(nu),
                # the int8-moment wire convention)
                m_d, mu_q, mu_s, nu_q, nu_s, p_d = res
                np.copyto(
                    leaves_m[li].reshape(-1)[sl], np.asarray(m_d)
                )
                n = sl.stop - sl.start
                np.copyto(
                    leaves_mu[li].reshape(-1)[sl],
                    _np_deq_chunk(
                        np.asarray(mu_q), np.asarray(mu_s), n
                    ),
                )
                nu_root = _np_deq_chunk(
                    np.asarray(nu_q), np.asarray(nu_s), n
                )
                np.copyto(
                    leaves_nu[li].reshape(-1)[sl], nu_root * nu_root
                )
            else:
                m_d, mu_d, nu_d, p_d = res
                # d2h writebacks into the SAME host buffers
                np.copyto(
                    leaves_m[li].reshape(-1)[sl], np.asarray(m_d)
                )
                np.copyto(
                    leaves_mu[li].reshape(-1)[sl], np.asarray(mu_d)
                )
                np.copyto(
                    leaves_nu[li].reshape(-1)[sl], np.asarray(nu_d)
                )
            new_param_chunks.setdefault(li, []).append(p_d)

        for li in range(len(leaves_m)):
            flat_m = leaves_m[li].reshape(-1)
            flat_g = leaves_g[li].reshape(-1)
            n = flat_m.shape[0]
            for j, sl in enumerate(self._chunk_slices(n)):
                pre = prefetched.get((li, j))
                if pre is None:
                    pre = self._stage_chunk(
                        leaves_m, leaves_mu, leaves_nu, li, j, sl,
                        quant=tq,
                    )
                if int8 or tq:
                    res = _chunk_update_q(
                        *pre, flat_g[sl], bc1, bc2, **hyper
                    )
                else:
                    res = _chunk_update(
                        *pre, flat_g[sl], bc1, bc2, **hyper
                    )
                elems = sl.stop - sl.start
                # master fp32 both ways + moments (fp32 or int8 +
                # fp32 scales) both ways — the chunk-stream traffic
                # the span reports
                if int8 or tq:
                    padded = self._q_padded(elems)
                    stream_bytes += 2 * (
                        4 * elems
                        + 2 * (padded + 4 * (padded // _QBLOCK))
                    )
                else:
                    stream_bytes += 2 * (4 * elems + 2 * 4 * elems)
                in_flight.append((li, sl, j, res))
                # bounded window: older chunks' HBM buffers are freed
                # by the writeback before new ones are dispatched
                while len(in_flight) > self.window:
                    drain_one()
        while in_flight:
            drain_one()
        self._emit_stream_span(
            _time.perf_counter() - t0, stream_bytes, buffered,
        )

        new_params = []
        for li, m in enumerate(leaves_m):
            chunks = new_param_chunks[li]
            flat = (
                chunks[0]
                if len(chunks) == 1
                else jnp.concatenate(chunks)
            )
            new_params.append(flat.reshape(m.shape))
        return OffloadState(
            step=step,
            params=jax.tree_util.tree_unflatten(
                treedef, new_params
            ),
            master=state.master,
            mu=state.mu,
            nu=state.nu,
        )


def make_accumulated_grads_fn(loss_fn, micro_steps: int):
    """(params, batch) -> (mean loss, mean grads) over ``micro_steps``
    microbatches (batch leading dim splits evenly).  The stream update
    is the expensive part of an offloaded step (~6-12 B/param over
    PCIe each way), so amortizing it over K microbatches is the
    offload-native throughput lever — accumulation happens in bf16
    (an fp32 accumulator would cost 4 B/param of the HBM the offload
    exists to free)."""
    micro_steps = max(1, int(micro_steps))

    def grads_of(params, batch):
        if micro_steps <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        split = jax.tree_util.tree_map(
            lambda x: x.reshape(
                (micro_steps, x.shape[0] // micro_steps)
                + x.shape[1:]
            ),
            batch,
        )
        loss_sum = jnp.float32(0.0)
        acc = None
        inv = 1.0 / micro_steps
        for k in range(micro_steps):
            mb = jax.tree_util.tree_map(lambda x: x[k], split)
            loss_k, g = jax.value_and_grad(loss_fn)(params, mb)
            loss_sum = loss_sum + loss_k
            if acc is None:
                acc = jax.tree_util.tree_map(
                    lambda a: (a * inv).astype(a.dtype), g
                )
            else:
                acc = jax.tree_util.tree_map(
                    lambda s, a: (s + a * inv).astype(s.dtype),
                    acc, g,
                )
        return loss_sum * inv, acc

    return grads_of


class FusedOffloadState(NamedTuple):
    """Train state for the FUSED offload path.  ``master``/``mu``/
    ``nu`` use the SAME chunked host layout as the pinned_host
    backend (per-leaf lists of host chunk arrays; int8 moments as
    ``(payload, scales)`` tuples) — chunking is what lets the fused
    program bound its HBM transient.  ``grads`` holds the previous
    step's gradients in delayed mode (``None`` in synchronous
    mode)."""

    step: jnp.ndarray  # int32 scalar, device
    params: Dict       # bf16, device
    master: Dict       # fp32 chunk lists, host memory kind
    mu: Dict           # fp32 chunks or (int8 payload, scales), host
    nu: Dict
    grads: Optional[Dict]  # bf16, device (delayed mode only)


def build_fused_offload_step(
    loss_fn,
    init_params_fn,
    optimizer: Optional[HostOffloadAdamW] = None,
    delayed: bool = True,
    window: int = 2,
    micro_steps: int = 1,
):
    """Host-offloaded train step as ONE jit program — the TPU-native
    overlap design.

    The reference overlaps its CPU-offloaded Adam with backward by
    registering per-module inner optimizers on grad hooks
    (``ref: atorch/atorch/optimizers/adam_offload.py:52-70``).  The
    XLA equivalent is to put the whole update INSIDE the train-step
    program with host-memory-kind shardings: the compiler turns each
    host transfer into an async copy-start / copy-done pair and
    overlaps it with the backward matmuls in the SAME program.
    Measured on v5e: out-of-program ``device_put`` transfers run at
    only 2.5-6 GB/s (per-dispatch overhead) while in-program copies
    stream at ~11 GB/s — fusing is what makes the DMA both fast and
    hidden.

    Memory discipline: left alone, XLA hoists EVERY chunk's H2D copy
    to the front of the program (measured: a 1.8B fused step demands
    32.8 GB of 15.75 GB HBM).  The update therefore streams the SAME
    chunked host layout the pinned backend uses, with a sliding
    window enforced by ``lax.optimization_barrier``: chunk ``i``'s
    host inputs are gated on chunk ``i-window``'s host OUTPUTS, so at
    most ``window`` chunks of fp32 state are in flight on device at
    once — the in-program form of the reference's bucket loop.

    Two scheduling modes:

    - ``delayed=True`` (default): backward runs on the CURRENT
      params while the update applies the PREVIOUS step's gradients
      to produce the next params — the two are data-independent, so
      every host copy (H2D in, D2H out) and the update math itself
      overlap the backward.  This is the delayed-parameter-update
      schedule of ZeRO-Offload (gradients are applied one step after
      they were computed).  Step 1 is a TRUE no-op: it has no
      previous gradients, weight decay is gated off (it would move
      every param before any real gradient) and bias correction
      counts real moment updates — the trajectory equals the
      synchronous one run on the shifted grad sequence, exactly.
    - ``delayed=False``: backward first, update after (exact
      synchronous AdamW).  H2D copies still hoist into the backward;
      the D2H tail is exposed but chunk-pipelined.

    Returns ``(init_state, train_step)``; ``train_step`` jit-compiles
    on first call (shardings are captured from the state built by
    ``init_state``).
    """
    from jax import lax

    opt = optimizer or HostOffloadAdamW()
    int8 = opt.moments == "int8"
    dev, host = opt._shardings()
    # env override for on-chip tuning: the window trades HBM
    # transient (~window * 5 * chunk_bytes) against copy/compute
    # pipelining depth
    env_window = os.getenv("DLROVER_TPU_OFFLOAD_WINDOW")
    if env_window:
        try:
            window = int(env_window)
        except ValueError:
            logger.warning(
                "ignoring malformed DLROVER_TPU_OFFLOAD_WINDOW=%r",
                env_window,
            )
    window = max(1, int(window))
    micro_steps = max(1, int(micro_steps))
    hyper = dict(
        lr=opt.lr, b1=opt.b1, b2=opt.b2, eps=opt.eps, wd=opt.wd
    )
    # when the backend has no second memory space (_shardings
    # degraded host to dev), in-program device_put is an unlowerable
    # no-op (CPU has no annotate_device_placement) — elide it
    two_spaces = host is not dev

    def _in(x):
        return jax.device_put(x, dev) if two_spaces else x

    def _out(x):
        return jax.device_put(x, host) if two_spaces else x

    def init_state(rng) -> FusedOffloadState:
        params = init_params_fn(rng)
        base = opt._init_pinned(params)  # chunked host layout
        del params
        grads = (
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16),
                base.params,
            )
            if delayed
            else None
        )
        return FusedOffloadState(
            step=jnp.zeros((), jnp.int32),
            params=base.params,
            master=base.master,
            mu=base.mu,
            nu=base.nu,
            grads=grads,
        )

    def _apply(params, grads, master, mu, nu, step, wd):
        """Traced chunk-streamed update: barrier-windowed H2D, the
        shared AdamW math, D2H.  ``step`` is the bias-correction step
        (the number of REAL moment updates so far); ``wd`` may be a
        traced scalar (delayed mode gates decay off at step 1)."""
        hyper_t = dict(hyper, wd=wd)
        stepf = step.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(jnp.float32(opt.b1), stepf)
        bc2 = 1.0 - jnp.power(jnp.float32(opt.b2), stepf)
        is_list = lambda x: isinstance(x, list)  # noqa: E731
        leaves_m, treedef = jax.tree_util.tree_flatten(
            master, is_leaf=is_list
        )
        leaves_mu = treedef.flatten_up_to(mu)
        leaves_nu = treedef.flatten_up_to(nu)
        leaves_g = treedef.flatten_up_to(grads)
        leaves_p = treedef.flatten_up_to(params)
        tokens = []  # chunk host outputs, in stream order
        new_p, new_m, new_mu, new_nu = [], [], [], []
        for li, m_chunks in enumerate(leaves_m):
            flat_g = leaves_g[li].reshape(-1)
            shape = leaves_p[li].shape
            slices = opt._chunk_slices(flat_g.shape[0])
            ms, mus, nus, ps = [], [], [], []
            for j, sl in enumerate(slices):
                if int8:
                    mu_q, mu_s = leaves_mu[li][j]
                    nu_q, nu_s = leaves_nu[li][j]
                    ins = (m_chunks[j], mu_q, mu_s, nu_q, nu_s)
                else:
                    ins = (
                        m_chunks[j],
                        leaves_mu[li][j],
                        leaves_nu[li][j],
                    )
                if len(tokens) >= window:
                    # gate this chunk's H2D on the D2H completion of
                    # the chunk `window` positions back: bounds the
                    # in-flight fp32 transient to ~window chunks
                    gated = lax.optimization_barrier(
                        ins + (tokens[len(tokens) - window],)
                    )
                    ins = gated[:-1]
                g = flat_g[sl]
                if int8:
                    (m2, mu_q2, mu_s2, nu_q2, nu_s2, pb) = (
                        _adamw_chunk_math_q(
                            _in(ins[0]), _in(ins[1]), _in(ins[2]),
                            _in(ins[3]), _in(ins[4]),
                            g, bc1, bc2, **hyper_t,
                        )
                    )
                    m2h = _out(m2)
                    mus.append((_out(mu_q2), _out(mu_s2)))
                    nus.append((_out(nu_q2), _out(nu_s2)))
                else:
                    m2, mu2, nu2, pb = _adamw_chunk_math(
                        _in(ins[0]), _in(ins[1]), _in(ins[2]),
                        g, bc1, bc2, **hyper_t,
                    )
                    m2h = _out(m2)
                    mus.append(_out(mu2))
                    nus.append(_out(nu2))
                ms.append(m2h)
                tokens.append(m2h)
                ps.append(pb)
            new_m.append(ms)
            new_mu.append(mus)
            new_nu.append(nus)
            flat_p = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
            new_p.append(flat_p.reshape(shape))
        unf = jax.tree_util.tree_unflatten
        return (
            unf(treedef, new_p),
            unf(treedef, new_m),
            unf(treedef, new_mu),
            unf(treedef, new_nu),
        )

    _grads_of = make_accumulated_grads_fn(loss_fn, micro_steps)

    def step_fn(state: FusedOffloadState, batch):
        step = state.step + 1
        loss, grads = _grads_of(state.params, batch)
        # delayed: backward ran on the CURRENT params while the
        # update applies the PREVIOUS grads and only feeds the NEXT
        # step — the two are data-independent, so copies and update
        # math ride under the backward (ZeRO-Offload delayed
        # parameter update).  sync: this step's grads apply now.
        applied = state.grads if delayed else grads
        if delayed:
            # step 1 has no previous gradients, so its update must be
            # a TRUE no-op: weight decay is gated off (a bare
            # bias-corrected decay would move every param before any
            # real gradient), and bias correction counts REAL moment
            # updates (step t applies the grads computed at t-1, the
            # (t-1)-th update) — the delayed trajectory is exactly the
            # synchronous one run on the shifted grad sequence.
            upd_step = jnp.maximum(step - 1, 1)
            wd_t = (
                jnp.float32(opt.wd)
                * (step > 1).astype(jnp.float32)
                if opt.wd
                else opt.wd
            )
        else:
            upd_step, wd_t = step, opt.wd
        new_p, new_m, new_mu, new_nu = _apply(
            state.params, applied, state.master, state.mu,
            state.nu, upd_step, wd_t,
        )
        new_state = FusedOffloadState(
            step, new_p, new_m, new_mu, new_nu,
            grads if delayed else None,
        )
        return new_state, {"loss": loss}

    cache: Dict[object, object] = {}

    def train_step(state: FusedOffloadState, batch):
        jitted = cache.get("jit")
        if jitted is None:
            state_sh = jax.tree_util.tree_map(
                lambda a: a.sharding, state
            )
            jitted = jax.jit(
                step_fn,
                in_shardings=(state_sh, None),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )
            cache["jit"] = jitted
        # "k=v,k=v" -> per-program XLA overrides (scheduler tuning
        # for the copy/compute overlap without touching global
        # LIBTPU_INIT_ARGS).  AOT executables are shape-specialized,
        # so they cache PER BATCH SHAPE — a different eval/tail batch
        # must retrace, not crash
        opts = os.getenv("DLROVER_TPU_OFFLOAD_XLA_OPTS", "")
        if not opts:
            return jitted(state, batch)
        shape_key = tuple(
            (tuple(x.shape), str(x.dtype))
            for x in jax.tree_util.tree_leaves(batch)
        )
        fn = cache.get(shape_key)
        if fn is None:
            kv = dict(
                item.split("=", 1)
                for item in opts.split(",")
                if "=" in item
            )
            fn = jitted.lower(state, batch).compile(
                compiler_options=kv
            )
            cache[shape_key] = fn
        return fn(state, batch)

    return init_state, train_step


def _release_params(state: OffloadState) -> OffloadState:
    """Swap the bf16 params tree for ShapeDtypeStructs once backward
    has consumed it: the update stream only needs SHAPES, and the
    swap drops the last in-step reference so the runtime frees the
    old params the moment the backward finishes executing — without
    it, old params + grads + the new params chunks coexist, which is
    the OOM margin at 3B.  Callers must pass the state as a consumed
    temporary (``step(holder.pop(), batch)``)."""
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        state.params,
    )
    return state._replace(params=shapes)


def build_offloaded_train_step(
    loss_fn,
    init_params_fn,
    optimizer: Optional[HostOffloadAdamW] = None,
    mode: str = "auto",
    micro_steps: int = 1,
    window: int = 2,
):
    """Single-chip train step with host-resident optimizer state.

    ``mode`` selects the update scheduling:

    - ``"auto"`` (default): ``"fused_delayed"`` when the backend is
      ``pinned_host`` (TPU), else ``"chunked"``.
    - ``"fused_delayed"`` / ``"fused"``: one-program update via
      :func:`build_fused_offload_step` (overlapped; ``fused`` is the
      exact-synchronous variant).
    - any mode composes with ``micro_steps`` gradient accumulation
      (``make_accumulated_grads_fn``) — the chunked mode is what the
      accumulated 1.8B proofs use: per-chunk update programs keep
      peak HBM far below the one-program fused form.
    - ``"chunked"``: the streaming
      :meth:`HostOffloadAdamW.apply_gradients` path, with the numpy
      backend prefetching its first chunk window before backward.

    Returns ``(init_state, train_step)`` where ``train_step(state,
    batch) -> (state, metrics)``.
    """
    opt = optimizer or HostOffloadAdamW()
    if mode == "auto":
        mode = (
            "fused_delayed"
            if opt.backend == "pinned_host"
            else "chunked"
        )
    if mode in ("fused", "fused_delayed"):
        return build_fused_offload_step(
            loss_fn, init_params_fn, opt,
            delayed=(mode == "fused_delayed"),
            micro_steps=micro_steps,
            window=window,
        )
    if mode != "chunked":
        raise ValueError(f"unknown offload mode {mode!r}")

    def init_state(rng) -> OffloadState:
        params = init_params_fn(rng)
        state = opt.init(params)
        del params
        return state

    if micro_steps <= 1:
        grad_fn = jax.jit(
            lambda params, batch: jax.value_and_grad(loss_fn)(
                params, batch
            )
        )

        def train_step(state: OffloadState, batch):
            # dispatch the H2D prefetch of the first chunk window
            # BEFORE backward so the transfers ride under the compute
            prefetched = opt.start_prefetch(state)
            loss, grads = grad_fn(state.params, batch)
            state = _release_params(state)
            new_state = opt.apply_gradients(
                state, grads, prefetched=prefetched
            )
            return new_state, {"loss": loss}

        return init_state, train_step

    # accumulated chunked path: one PROGRAM per microbatch, NOT one
    # K-micro program — the fused accumulation program must co-reserve
    # the accumulator, the per-micro grads and the backward residuals
    # and exceeds a 16 GB chip at 1.8B (measured).  The accumulator is
    # DONATED into each micro's backward program so the grad add is an
    # epilogue on the aliased buffer: peak stays at the r4-proven
    # (params + one grads tree + residuals), not + a separate acc.
    inv = 1.0 / micro_steps
    scaled_vag = jax.value_and_grad(
        lambda p, b: loss_fn(p, b) * inv
    )
    first_grad = jax.jit(scaled_vag)

    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def _grad_into(params, mb, acc, loss_sum):
        loss_k, g = scaled_vag(params, mb)
        return (
            loss_sum + loss_k,
            jax.tree_util.tree_map(
                lambda s, a: (s + a).astype(s.dtype), acc, g
            ),
        )

    pending: Dict[str, object] = {}

    def train_step(state: OffloadState, batch):
        # NOTE the state is CONSUMED (donation semantics): pass it as
        # a temporary — `state, m = train_step(state, batch)` keeps
        # the caller's binding alive through the whole dispatch and
        # pins the old params tree (6 GB at 3B) into the chunk-stream
        # window.  See _release_params.
        # completion barrier on the PREVIOUS step: async dispatch
        # otherwise pipelines steps, and at 1.8B two in-flight steps'
        # buffers exceed HBM (runtime OOM) — a one-element readback
        # of the previous step's assembled params serializes steps
        prev = pending.pop("probe", None)
        if prev is not None:
            float(prev)
        prefetched = opt.start_prefetch(state)
        split = jax.tree_util.tree_map(
            lambda x: x.reshape(
                (micro_steps, x.shape[0] // micro_steps)
                + x.shape[1:]
            ),
            batch,
        )
        mb0 = jax.tree_util.tree_map(lambda x: x[0], split)
        loss_sum, acc = first_grad(state.params, mb0)
        for k in range(1, micro_steps):
            mb = jax.tree_util.tree_map(lambda x: x[k], split)
            loss_sum, acc = _grad_into(
                state.params, mb, acc, loss_sum
            )
        state = _release_params(state)
        new_state = opt.apply_gradients(
            state, acc, prefetched=prefetched
        )
        # the LAST-dispatched leaf: its completion implies the whole
        # stream's on this serially-executing runtime
        last = jax.tree_util.tree_leaves(new_state.params)[-1]
        pending["probe"] = (
            last.reshape(-1)[-1].astype(jnp.float32)
        )
        return new_state, {"loss": loss_sum}

    return init_state, train_step


def build_grouped_offload_step(
    loss_grouped,
    init_a_fn=None,
    init_b_fn=None,
    optimizer_a: Optional[HostOffloadAdamW] = None,
    optimizer_b: Optional[HostOffloadAdamW] = None,
    *,
    init_fns: Optional[Sequence] = None,
    optimizers: Optional[Sequence] = None,
):
    """Offloaded train step with N param groups and one backward
    pass per group — the ceiling lever past ~2B params on a 16 GB
    chip, where a single backward's full dW tree cannot coexist with
    the bf16 params (measured: 3.0B needs ~19 GB).  More groups
    shrink the peak further: the largest resident dW tree is one
    group's, so N is the knob that trades backward passes for HBM
    headroom (``accelerate.solver.solve_offload_groups`` picks the
    smallest N that fits from the model's per-layer footprint).

    Semantics are EXACT single-step AdamW: every group's gradients
    are evaluated at the step-start params (groups ``0..N-2``'s
    gradients are staged to host memory while later backwards and
    the last group's update run, then brought back in reverse
    order) — not block-coordinate descent.

    Two calling conventions:

    - legacy two-group (positional, unchanged):
      ``build_grouped_offload_step(loss, init_a, init_b, opt_a,
      opt_b)`` with ``loss(params_a, params_b, batch)``;
    - N-group: ``build_grouped_offload_step(loss, init_fns=[...],
      optimizers=[...])`` with ``loss(*group_params, batch)``.

    ``init_fns[i]()`` builds group i's params tree lazily so each
    group's fp32 source frees before the next materializes.  Returns
    ``(init_state, train_step)`` with ``train_step(state, batch) ->
    (state, metrics)`` over a tuple of per-group states, CONSUMED
    like the chunked step (pass it as a temporary).
    """
    if init_fns is None:
        init_fns = [
            fn for fn in (init_a_fn, init_b_fn) if fn is not None
        ]
        optimizers = [optimizer_a, optimizer_b][: len(init_fns)]
    init_fns = list(init_fns)
    n_groups = len(init_fns)
    if n_groups < 1:
        raise ValueError("need at least one param group")
    if optimizers is None:
        optimizers = [None] * n_groups
    opts = [o or HostOffloadAdamW() for o in optimizers]
    if len(opts) != n_groups:
        raise ValueError(
            f"{len(opts)} optimizers for {n_groups} groups"
        )
    dev, host = opts[0]._shardings()

    vags = [
        jax.jit(jax.value_and_grad(loss_grouped, argnums=i))
        for i in range(n_groups)
    ]
    # host staging round-trip for the early groups' grads (identity
    # programs with host output/input layouts; on CPU test meshes
    # host==dev and these are no-ops)
    stage_out = jax.jit(lambda g: g, out_shardings=host)
    stage_in = jax.jit(lambda g: g, out_shardings=dev)
    two_spaces = host is not dev
    host_scalar = jax.jit(
        lambda l: jax.device_put(l, dev).reshape(-1)[0].astype(
            jnp.float32
        ),
        out_shardings=dev,
    )

    def _barrier(value):
        """Force completion of everything dispatched so far: at 3B
        the phases' OUTPUT buffers are allocated at dispatch on this
        runtime, so letting every phase enqueue at once demands
        every phase's outputs simultaneously (~16 GB of outputs
        alone).  Only needed where a second memory space exists —
        the CPU test mesh runs phases eagerly anyway."""
        if two_spaces and value is not None:
            float(value)

    def _last_leaf_probe(params):
        return (
            jax.tree_util.tree_leaves(params)[-1]
            .reshape(-1)[-1]
            .astype(jnp.float32)
        )

    def init_state(rng=None):
        del rng  # group inits carry their own keys
        return tuple(
            opts[i].init(init_fns[i]()) for i in range(n_groups)
        )

    pending: Dict[str, object] = {}

    debug = os.getenv("DLROVER_TPU_GROUPED_DEBUG", "") == "1"

    def _dbg(msg):
        if debug:
            import time as _time

            mem = ""
            try:
                stats = jax.local_devices()[0].memory_stats()
                mem = (
                    f" hbm={stats.get('bytes_in_use', 0) / 1e9:.2f}G"
                    f" peak={stats.get('peak_bytes_in_use', 0) / 1e9:.2f}G"
                )
            except Exception:  # noqa: BLE001
                pass
            print(
                f"[grouped {_time.strftime('%H:%M:%S')}] {msg}{mem}",
                flush=True,
            )

    def train_step(state, batch):
        states = list(state)
        del state
        prev = pending.pop("probe", None)
        if prev is not None:
            float(prev)  # serialize steps (HBM cannot hold two)
        _dbg("step start")
        step_params = [s.params for s in states]
        loss = None
        staged = []
        # passes 1..N-1: early groups' grads at step-start params ->
        # host staging (one dW tree resident at a time)
        for i in range(n_groups - 1):
            loss_i, g = vags[i](*step_params, batch)
            if loss is None:
                loss = loss_i
            _barrier(loss_i)
            _dbg(f"vag_{i} done")
            g = stage_out(g)
            _barrier(
                host_scalar(jax.tree_util.tree_leaves(g)[0])
                if two_spaces
                else None
            )
            staged.append(g)
        # final pass: last group's grads at the SAME step-start
        # params, updated immediately (no staging round-trip).  The
        # rolling H2D window starts AFTER the backward barrier: at
        # the 3B HBM edge the backward's residuals + dW leave no
        # margin for early-staged chunks, and the chunk stream still
        # pipelines copy against update math within the window.
        last = n_groups - 1
        loss_last, g_last = vags[last](*step_params, batch)
        if loss is None:
            loss = loss_last
        _barrier(loss_last)
        _dbg(f"vag_{last} done")
        pre_last = opts[last].start_prefetch(states[last])
        del step_params  # step-start refs live on in `states`
        # rebinding FIRST matters: inlining _release_params in the
        # call would keep the name bound to the original state (real
        # params pinned) for the whole dispatch
        states[last] = _release_params(states[last])
        states[last] = opts[last].apply_gradients(
            states[last], g_last, prefetched=pre_last
        )
        del g_last, pre_last
        # bring the staged grads back and update in reverse order;
        # between updates, force the LAST-dispatched leaf: programs
        # execute in dispatch order on this runtime, so its
        # completion implies the whole stream's (the first leaf
        # would only cover the head of the stream)
        for i in range(n_groups - 2, -1, -1):
            _barrier(
                _last_leaf_probe(states[i + 1].params)
                if two_spaces
                else None
            )
            _dbg(f"apply_{i + 1} done")
            g = stage_in(staged[i])
            staged[i] = None
            # rolling window for this group's chunk stream: its H2D
            # overlaps the previous group's still-draining update
            pre = opts[i].start_prefetch(states[i])
            states[i] = _release_params(states[i])
            states[i] = opts[i].apply_gradients(
                states[i], g, prefetched=pre
            )
            del g, pre
        _dbg("apply_0 dispatched")
        pending["probe"] = _last_leaf_probe(states[0].params)
        return tuple(states), {"loss": loss}

    return init_state, train_step
