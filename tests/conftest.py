"""Test harness configuration.

Tests run on an 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``), mirroring the reference's
strategy of never needing real multi-node hardware in CI (SURVEY.md §4).

The suite never needs a chip: this process is held to the CPU through
``jax.config`` and every child a test spawns through ``JAX_PLATFORMS``
(parents in this repo never ask JAX for a backend on a child's behalf —
a child takes its platform from the environment it is given).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# MFU needs the chip's peak; an unknown device kind (the CPU) raises, so
# CPU tests name a peak through the override that exists
os.environ.setdefault("DLROVER_TPU_PEAK_FLOPS", "197e12")

_FLAG = "--xla_force_host_platform_device_count=8"
_existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _existing:
    os.environ["XLA_FLAGS"] = (_existing + " " + _FLAG).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile  # noqa: E402

import pytest  # noqa: E402


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
    # the timeout marks are advisory (no pytest-timeout in the image);
    # register them so the suite runs warning-clean
    config.addinivalue_line(
        "markers", "timeout(seconds): advisory per-test time budget"
    )
    config.addinivalue_line(
        "markers",
        "heavy: multi-process / subprocess e2e test, scheduled after the "
        "unit tests so fast feedback comes first",
    )


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
