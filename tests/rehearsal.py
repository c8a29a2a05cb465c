"""A benchmark cell rehearsed from tier-1: ``harness.run_cell`` in a
process of its own, as the command line is one — the cell checks that
the engine's parent never touched the JAX backend, which a test process
that ran other files has.

Every rehearsal's children (the harness's process, the engine's replica,
the reference) take the run's one compile cache from the environment
(``tests/conftest.py``) and keep every compile in it, however short:
of a replica's backend compiles at the tiny sizes all but the three
step programs take under the second below which JAX keeps nothing (17.7
of 18.7 s in ``trinity-rollout``'s replica, by its ``compile`` records),
so without the two thresholds a file's second child compiled them all
again.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

KEEP_EVERY_COMPILE = {
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
}


def run_cell(workload, seed, seconds, trace, expect_platform, data_root):
    """``harness.run_cell``'s result line, from a new process."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {BENCH!r})\n"
        "import harness\n"
        f"line = harness.run_cell({workload!r}, {seed}, {seconds}, {trace}, "
        f"expect_platform={expect_platform!r}, data_root={data_root!r})\n"
        "print(json.dumps(line))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, **KEEP_EVERY_COMPILE),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
