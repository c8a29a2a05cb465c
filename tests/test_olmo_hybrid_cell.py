"""``family_olmo_hybrid``'s cell in tier-1: the cases of
``benchmarks/tests/test_olmo_hybrid_cpu.py`` (which a benchmark PR keeps
beside the harness, outside tier-1), run from here as they stand — the
tiny configuration through ``harness.run_cell`` end to end on the CPU
(the engine's replica over a pool of three layers' state and one layer's
pages, ``correct`` decided by the family's plain reference in a child
process), the configuration file against the catalog's cut, the counts,
the byte function and the metric files the real cell is listed on.  A
program PR that renames what the family imports, or moves a label a
metric reads, fails here and not on the chip.

Numbers read here are counts and differences on the CPU, never a device
metric.
"""

import importlib.util
import os

import pytest
import rehearsal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.heavy

_spec = importlib.util.spec_from_file_location(
    "benchmarks_test_olmo_hybrid_cpu",
    os.path.join(REPO, "benchmarks", "tests", "test_olmo_hybrid_cpu.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)


@pytest.fixture(autouse=True)
def _cells_run_in_their_own_process(monkeypatch):
    monkeypatch.setattr(
        _cases.harness, "run_cell", rehearsal.run_cell
    )


# the module's fixture and every case of it, collected under this file
globals().update({
    name: value for name, value in vars(_cases).items()
    if name.startswith("test_") or name == "data_root"
})
