"""Process-level JAX facts the launchers need WITHOUT touching a device.

A TPU chip belongs to one process at a time, so every process in this
repo that spawns chip-using children (the elastic launcher and agent,
the zygote, the serving parents, ``chip_smoke.py``) must stay off the
backend itself.  The helpers here let such a parent decide what it has
to from the environment alone:

- where the persistent XLA compile cache lives
  (:func:`compile_cache_dir` / :func:`export_compile_cache`), and which
  short compiles it keeps all the same (:func:`kept_in_compile_cache`);
- which platform its children will get (:func:`platform_from_env`);
- whether anything in this process initialised a backend after all
  (:func:`backend_initialized` — the guard the zygote and the tests
  use).

Two are for the process that OWNS the chip:
:func:`pinned_host_works`, whether a compiled program can place arrays
in ``pinned_host`` memory (the host-offloaded optimizer's state, the
trainer's staged snapshot), and :func:`install_compile_meter`, the
process's one :class:`CompileMeter`: what it traced, lowered and
compiled or was handed by the persistent cache, counted and, with an
events file, written to the timeline a program and stage.
"""

import contextlib
import os
import re
import sys
import threading
from typing import Dict, MutableMapping, Optional

from dlrover_tpu.observability.events import anchored_now

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Root of everything the program caches on disk: a fixed, git-ignored
#: directory inside the checkout.  The path is part of the compile
#: cache's key, so it is never built from ``mkdtemp``, a pid or a time;
#: and nothing outside what git would commit steers the program.
CHECKOUT_CACHE_ROOT = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".cache",
)


def compile_cache_dir() -> str:
    """The one persistent XLA compile cache directory of this checkout:
    ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
    ``<checkout>/.cache/jax_compile`` — the same path on every call and
    from every entry point."""
    return os.environ.get(COMPILE_CACHE_ENV, "").strip() or os.path.join(
        CHECKOUT_CACHE_ROOT, "jax_compile"
    )


def export_compile_cache(
    env: MutableMapping[str, str], override: str = ""
) -> str:
    """Point a child's environment at the compile cache.  A directory
    the environment already names wins and nothing else is set; else
    ``override`` (the launcher's ``--compile_cache_dir``) or the fixed
    in-checkout path is exported.  JAX reads the variable at import, so
    the child needs no code of its own.  Returns the directory."""
    path = env.get(COMPILE_CACHE_ENV, "").strip()
    if not path:
        path = override or compile_cache_dir()
        os.makedirs(path, exist_ok=True)
        env[COMPILE_CACHE_ENV] = path
    return path


@contextlib.contextmanager
def kept_in_compile_cache():
    """Whatever compiles inside is written to the persistent cache
    however short its compile: JAX keeps nothing that compiled in under
    a second, which is right for a one-off eager op and wrong for a
    program that EVERY process of a kind compiles at its start.  The
    process's threshold is put back on the way out."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    usual = getattr(jax.config, name)
    jax.config.update(name, 0.0)
    try:
        yield
    finally:
        jax.config.update(name, usual)


_PINNED_HOST_PROBED: Optional[bool] = None


def pinned_host_works() -> bool:
    """Whether this backend supports the ``pinned_host`` memory kind
    (TPU yes; the CPU test mesh no).  Probed once.  Off-TPU a failed
    probe downgrades host shardings to plain device shardings so the
    SAME code path runs — with identical math — where no second memory
    space exists.  On a TPU the probe failing is an error and RAISES:
    "offloaded" state silently left in HBM is not an offload.
    Initialises the backend: only a process that owns the chip calls
    this."""
    global _PINNED_HOST_PROBED
    if _PINNED_HOST_PROBED is None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        from dlrover_tpu.common.log import default_logger as logger

        try:
            dev = SingleDeviceSharding(jax.devices()[0])
            host = dev.with_memory_kind("pinned_host")
            x = jax.device_put(jnp.zeros((8,)), host)
            # the users move between memory spaces INSIDE jit
            # (annotate_device_placement) — CPU accepts the plain
            # device_put above but cannot lower the in-program form,
            # so the probe must exercise it
            fn = jax.jit(
                lambda a: jax.device_put(
                    jax.device_put(a, dev) + 1.0, host
                ),
                in_shardings=host,
                out_shardings=host,
            )
            jax.block_until_ready(fn(x))
            _PINNED_HOST_PROBED = True
        except Exception:  # noqa: BLE001 - any failure means "no"
            if jax.default_backend() == "tpu":
                raise
            _PINNED_HOST_PROBED = False
            logger.info(
                "pinned_host memory kind unavailable; host shardings "
                "fall back to device memory"
            )
    return _PINNED_HOST_PROBED


def platform_from_env() -> str:
    """The platform JAX will pick in a process started with this
    process's environment, read off ``JAX_PLATFORMS`` alone — first
    entry, lowercased; ``""`` when unset (JAX then takes the best
    backend installed: the TPU on a chip machine)."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()


def device_report() -> Dict[str, object]:
    """The device as JAX reports it, for a ``device_report`` event or a
    result line.  Initialises the backend: only a process that owns the
    chip calls this."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


class CompileMeter:
    """Counts this process's persistent-compile-cache hits and misses
    and sums the seconds spent in backend compiles, off JAX's own
    monitoring events.  Create it before the first compile; touches no
    device.

    Given an enabled event logger it also writes one ``compile`` record
    a program and stage onto the timeline (``ph: "X"``, on the logger's
    anchored clock: the end is the callback's instant, the start that
    less JAX's duration): ``trace`` (to a jaxpr) and ``lower`` (to a
    module) — of the many small functions JAX traces inside a
    program's own trace or lowering only the outer record is written,
    which covers them — and ``backend_compile``, with
    ``cache``: ``hit`` where the persistent cache handed the executable
    over (the record is then as long as the retrieval), ``miss`` where
    it was compiled and written, ``none`` where it was compiled and the
    cache not asked or the entry not kept — JAX keeps nothing that
    compiled in under a second, so such a program compiles at EVERY
    start.  The cache's own events carry no program's name: what a
    thread heard since its last backend compile is that compile's.
    Nothing fires unless something compiles."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"
    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _STAGES = {_TRACE: "trace", _LOWER: "lower", _COMPILE: "backend_compile"}

    def __init__(self, events=None):
        import jax.monitoring

        self._counts = {self._HIT: 0, self._MISS: 0}
        self._compile_s = 0.0
        self._events = (
            events if events is not None and events.enabled else None
        )
        # per thread: traces and lowerings open (JAX announces the
        # start of either as a scalar), and the cache's verdict on the
        # compile under way
        self._thread = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        if self._events is not None:
            jax.monitoring.register_scalar_listener(self._on_scalar)

    def _on_event(self, event: str, **_kwargs):
        if event in self._counts:
            self._counts[event] += 1
            self._thread.cache = "hit" if event == self._HIT else "miss"

    def _on_scalar(self, event: str, _value, **_kwargs):
        if event in (self._TRACE, self._LOWER):
            self._thread.open = getattr(self._thread, "open", 0) + 1

    def _on_duration(self, event: str, duration_secs: float, **kwargs):
        if event == self._COMPILE:
            self._compile_s += duration_secs
        stage = self._STAGES.get(event)
        if stage is None or self._events is None:
            return
        labels = {}
        if stage != "backend_compile":
            self._thread.open = max(getattr(self._thread, "open", 1) - 1, 0)
            if self._thread.open:
                return  # inside another program's trace or lowering
        else:
            labels["cache"] = getattr(self._thread, "cache", "none")
            self._thread.cache = "none"
        # ``jit(step)`` and ``step`` are one program: the trace names
        # the function, the later stages the module made from it
        program = str(kwargs.get("fun_name", ""))
        wrapped = re.fullmatch(r"\w+\((.*)\)", program)
        self._events.complete(
            "compile",
            anchored_now() - duration_secs,
            duration_secs,
            program=wrapped.group(1) if wrapped else program,
            stage=stage,
            **labels,
        )

    def snapshot(self) -> Dict[str, float]:
        return {
            "cache_hits": self._counts[self._HIT],
            "cache_misses": self._counts[self._MISS],
            "compile_s": round(self._compile_s, 3),
        }


_COMPILE_METER: Optional[CompileMeter] = None


def install_compile_meter(events=None) -> CompileMeter:
    """This process's one meter, made by the first call — which a
    chip-owning process makes before its first compile (the top of a
    serving replica's loop, ``init_distributed``), with the logger its
    ``compile`` records go to.  Later calls return the same meter."""
    global _COMPILE_METER
    if _COMPILE_METER is None:
        _COMPILE_METER = CompileMeter(events=events)
    return _COMPILE_METER


def backend_initialized() -> bool:
    """Has THIS process initialised any JAX backend?  False when jax
    was never imported.  A parent that answers True holds the chip and
    its chip-using children will fail or hang."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())
