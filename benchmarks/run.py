#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the LAST line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run).  Without a TPU, with fewer chips than the
cell asks for, or without the program beside it, it prints no result and
exits nonzero: there is no switch for a rehearsal here (the tests call
``harness.run_cell(..., expect_platform="cpu")`` on tiny data files).
"""

import time

T_START = time.time()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    import harness

    # whatever the run's children print goes to stderr: the result is the
    # LAST line of standard output
    stdout = os.dup(1)
    os.dup2(2, 1)
    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, args.trace,
            expect_platform="tpu", t_start=T_START,
        )
    except harness.CellFailed as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        sys.stdout.flush()
        os.dup2(stdout, 1)
        os.close(stdout)
    for note in line.pop("notes"):
        print(f"benchmark: {note}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
