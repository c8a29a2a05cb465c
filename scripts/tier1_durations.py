#!/usr/bin/env python
"""Where a tier-1 run's seconds went, from its junit file.

    python scripts/tier1_durations.py /tmp/_t1.xml [--wall SECONDS]

Prints test-seconds by file (the unit ``--dist loadfile`` hands a
worker, so the largest file bounds the run from below), the 40 longest
cases, the sum, and sum / 6 (six workers, perfect packing) against the
wall.  ``docs/testing.md`` says how the numbers are used.
"""

import argparse
import collections
import xml.etree.ElementTree as ET

WORKERS = 6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("junit")
    ap.add_argument("--wall", type=float, help="the run's wall seconds "
                    "(default: the testsuite's own `time`)")
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args()

    root = ET.parse(args.junit).getroot()
    suite = root if root.tag == "testsuite" else root.find("testsuite")
    by_file = collections.defaultdict(lambda: [0, 0.0])
    cases = []
    for case in root.iter("testcase"):
        # classname is "tests.test_x.TestClass": the file is its first
        # component that starts with "test_"
        parts = case.get("classname", "").split(".")
        file = next((p for p in parts if p.startswith("test_")), parts[-1])
        seconds = float(case.get("time", 0.0))
        by_file[file][0] += 1
        by_file[file][1] += seconds
        cases.append((seconds, f"{file}::{'.'.join(parts[parts.index(file) + 1:] + [case.get('name')])}"))

    total = sum(s for s, _ in cases)
    wall = args.wall if args.wall is not None else float(suite.get("time", 0.0))
    print(f"{'seconds':>9} {'cases':>6}  file")
    for file, (n, s) in sorted(by_file.items(), key=lambda kv: -kv[1][1]):
        print(f"{s:9.1f} {n:6d}  {file}.py")
    print(f"\nthe {args.top} longest cases")
    for s, name in sorted(cases, reverse=True)[:args.top]:
        print(f"{s:9.1f}  {name}")
    print(f"\n{len(cases)} cases, {total:.0f} test-seconds; "
          f"/ {WORKERS} workers = {total / WORKERS:.0f} s packed perfectly; "
          f"wall {wall:.0f} s; longest file "
          f"{max(s for _, s in by_file.values()):.0f} s")


if __name__ == "__main__":
    main()
