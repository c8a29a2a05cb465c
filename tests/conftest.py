"""Test harness configuration.

Tests run on an 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``), mirroring the reference's
strategy of never needing real multi-node hardware in CI (SURVEY.md §4).

The suite never needs a chip: this process is held to the CPU through
``jax.config`` and every child a test spawns through ``JAX_PLATFORMS``
(parents in this repo never ask JAX for a backend on a child's behalf —
a child takes its platform from the environment it is given).
"""

import contextlib
import faulthandler
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import uuid

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# MFU needs the chip's peak; an unknown device kind (the CPU) raises, so
# CPU tests name a peak through the override that exists
os.environ.setdefault("DLROVER_TPU_PEAK_FLOPS", "197e12")

_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()

# ONE compile cache a run: this process, its sibling xdist workers (the
# controller hands every worker the run's uid) and every child a test
# starts (``benchmarks/harness.py`` ``child_env`` and
# ``common/jax_env.export_compile_cache`` keep a directory the
# environment names) compile a program once between them.  The directory
# is made new for the run and removed at its end: no run reads what
# another tree wrote.
_RUN_UID = os.environ.get("PYTEST_XDIST_TESTRUNUID") or uuid.uuid4().hex


def _run_cache_dir(uid):
    return os.path.join(
        tempfile.gettempdir(), f"dlrover_tpu_tier1_jax_cache_{uid}"
    )


os.environ["JAX_COMPILATION_CACHE_DIR"] = _run_cache_dir(_RUN_UID)
os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the test process keeps every compile, however short or small: what a
# case traces, the next scheduler of the same configuration traces again
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

#: seconds a case may take when it names no ``timeout`` mark: six times
#: the longest case on record
DEFAULT_TIMEOUT_S = 600.0


@contextlib.contextmanager
def time_limit(seconds, what="the test"):
    """Fail the caller, with every thread's stack on stderr, when the
    block outlasts ``seconds``: a hang is one red case, not the whole
    run cut at its limit.  ``SIGALRM`` reaches only the main thread, so
    elsewhere the block runs unlimited."""
    if (
        threading.current_thread() is not threading.main_thread()
        or not hasattr(signal, "setitimer")
    ):
        yield
        return

    def expired(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        pytest.fail(f"{what} ran past its {seconds:g} s limit")

    usual = signal.signal(signal.SIGALRM, expired)
    began = time.monotonic()
    outer_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        # an enclosing limit (the hook's, around a case that sets its
        # own) goes on counting from where it was
        if outer_left:
            outer_left = max(outer_left - (time.monotonic() - began), 1e-3)
        signal.setitimer(signal.ITIMER_REAL, outer_left)
        signal.signal(signal.SIGALRM, usual)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    mark = item.get_closest_marker("timeout")
    seconds = float(mark.args[0]) if mark and mark.args else DEFAULT_TIMEOUT_S
    with time_limit(seconds, item.nodeid):
        yield


@pytest.fixture(autouse=True)
def _isolated_socket_dir(tmp_path, monkeypatch):
    """Each test gets its own unix-socket namespace so parallel/repeated
    runs don't collide on /tmp paths."""
    monkeypatch.setenv("DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks"))
    yield


@pytest.fixture
def tmp_ckpt_dir():
    with tempfile.TemporaryDirectory(prefix="dlrover_tpu_ckpt_") as d:
        yield d


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): the case fails, with a stack, once it has run "
        f"this long (default {DEFAULT_TIMEOUT_S:g})",
    )
    # the xdist controller names the run, so that its workers share the
    # compile cache it removes at the end
    if (
        not hasattr(config, "workerinput")
        and getattr(config.option, "testrunuid", _RUN_UID) is None
    ):
        config.option.testrunuid = _RUN_UID
    config.addinivalue_line(
        "markers",
        "heavy: multi-process / subprocess e2e test, scheduled after the "
        "unit tests so fast feedback comes first",
    )


def pytest_unconfigure(config):
    if not hasattr(config, "workerinput"):  # the controller, or alone
        uid = getattr(config.option, "testrunuid", None) or _RUN_UID
        for path in {_run_cache_dir(uid), _run_cache_dir(_RUN_UID)}:
            shutil.rmtree(path, ignore_errors=True)


def pytest_collection_modifyitems(config, items):
    # Stable partition: everything keeps its collection order, but tests
    # marked `heavy` (engine sessions, bench subprocesses) run after the
    # unit tests, so an interrupted run still covers the cheap majority.
    items.sort(key=lambda item: 1 if item.get_closest_marker("heavy") else 0)


@pytest.fixture(autouse=True)
def _suite_clean_mesh():
    """Suite-wide: drop the global mesh context after every test —
    un-jitted model code reads it at trace time, so a mesh leaked by
    one module silently reroutes another module's kernels."""
    yield
    from dlrover_tpu.parallel.mesh import destroy_parallel_mesh

    destroy_parallel_mesh()
