"""``family_lfm2_moe``'s cell in tier-1: the cases of
``benchmarks/tests/test_lfm2_moe_cpu.py`` (which a benchmark PR keeps
beside the harness, outside tier-1), run from here as they stand — the
tiny configuration through ``harness.run_cell`` end to end on the CPU on
ONE seed (the engine's replica over a pool of three layers' conv tails
and two layers' pages in rows of two KV heads, ``correct`` decided by the
family's plain reference forced onto the served experts, in a child
process), the configuration file against the catalog's cut, the counts,
the byte function and the metric files the real cell is listed on.  A
program PR that renames what the family imports, or moves a label a
metric reads, fails here and not on the chip.

Numbers read here are counts and differences on the CPU, never a device
metric.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.heavy

_spec = importlib.util.spec_from_file_location(
    "benchmarks_test_lfm2_moe_cpu",
    os.path.join(REPO, "benchmarks", "tests", "test_lfm2_moe_cpu.py"),
)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)


def _run_cell_in_a_process_of_its_own(workload, seed, seconds, trace,
                                      expect_platform, data_root):
    """``harness.run_cell`` as the command line is one process: the cell
    checks that the engine's parent never touched the JAX backend, which
    a test process that ran other files has."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'benchmarks')!r})\n"
        "import harness\n"
        f"line = harness.run_cell({workload!r}, {seed}, {seconds}, {trace}, "
        f"expect_platform={expect_platform!r}, data_root={data_root!r})\n"
        "print(json.dumps(line))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(autouse=True)
def _cells_run_in_their_own_process(monkeypatch):
    monkeypatch.setattr(
        _cases.harness, "run_cell", _run_cell_in_a_process_of_its_own
    )


# the module's fixture and every case of it, collected under this file
globals().update({
    name: value for name, value in vars(_cases).items()
    if name.startswith("test_") or name == "data_root"
})
