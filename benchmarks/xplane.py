"""The reduction from a profiler trace (``.xplane.pb``) to numbers.

Reads the file with ``jax.profiler.ProfileData`` (JAX alone, no backend
is initialised).  A trace holds planes; a TPU chip is a plane named
``/device:TPU:<n>`` whose line ``XLA Ops`` carries one event per executed
HLO operation (a Pallas kernel is one such event), with a start and a
duration in nanoseconds on the device's clock.

    python benchmarks/xplane.py <trace dir or .xplane.pb>   # look by hand
"""

import glob
import json
import os
import re
import sys

DEVICE_PLANE = r"^/device:TPU:\d+$"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory
    (or the path itself when it is a file)."""
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(
        glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        ),
        key=os.path.getmtime,
    )
    return found[-1] if found else None


def load(path):
    """A trace file; ``.gz`` (the recorded trace of the tests) is
    unpacked in memory."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def op_name(event_name):
    """An ``XLA Ops`` event is named by its whole HLO text, ``%_flash_fwd.18
    = (bf16[...]) custom-call(...)``: the operation's own name is what
    stands before `` = ``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_family(name):
    """``fusion.123`` -> ``fusion``: the name an operation keeps from run
    to run (the number is the compiler's)."""
    return re.sub(r"(\.\d+)+$", "", op_name(name)) or name


def device_ops(profile, plane_pattern=DEVICE_PLANE, op_line=OP_LINE):
    """``{plane name: [(start_ns, end_ns, op name), ...]}`` of the
    operations that ran on each device, sorted by start (an enclosing
    operation before what it encloses)."""
    out = {}
    for plane in profile.planes:
        if not re.match(plane_pattern, plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != op_line:
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                ops.append(
                    (start, start + float(ev.duration_ns), op_name(ev.name))
                )
        if ops:
            out[plane.name] = sorted(ops, key=lambda o: (o[0], -o[1]))
    return out


def union_s(intervals):
    """Seconds covered by the union of ``(start_ns, end_ns, ...)``."""
    busy, cur0, cur1 = 0.0, None, None
    for iv in sorted(intervals, key=lambda o: (o[0], o[1])):
        s, e = iv[0], iv[1]
        if cur1 is None or s > cur1:
            if cur1 is not None:
                busy += cur1 - cur0
            cur0, cur1 = s, e
        else:
            cur1 = max(cur1, e)
    if cur1 is not None:
        busy += cur1 - cur0
    return busy / 1e9


def self_times(ops):
    """``[(self_ns, name), ...]``: each operation's time less that of the
    operations nested in it.  A ``while`` (the scan over layers) is an
    event that encloses its body's events; summing durations would count
    the body twice."""
    own = [e - s for s, e, _ in ops]
    stack = []  # indices of the operations that enclose the current one
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(max(t, 0.0), ops[i][2]) for i, t in enumerate(own)]


def module_times(profile, pattern, plane_pattern=DEVICE_PLANE,
                 module_line="XLA Modules"):
    """Device seconds of each run of the compiled programs whose name
    (``jit__decode_lp(<fingerprint>)``) matches ``pattern``, over all
    chips."""
    out = []
    for plane in profile.planes:
        if not re.match(plane_pattern, plane.name):
            continue
        for line in plane.lines:
            if line.name == module_line:
                out.extend(
                    float(ev.duration_ns) / 1e9 for ev in line.events
                    if re.search(pattern, ev.name)
                )
    return out


def host_activity(profile, t0_ns, t1_ns, plane_pattern=r"^/host:"):
    """What the host spent most of [t0, t1] in: the name of the host
    event with the longest overlap (host planes only)."""
    best, best_overlap = "host: nothing recorded", 0.0
    for plane in profile.planes:
        if not re.match(plane_pattern, plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                overlap = min(e, t1_ns) - max(s, t0_ns)
                if overlap > best_overlap:
                    best, best_overlap = ev.name, overlap
    return best


def reduce(profile, kernel_pattern=None, plane_pattern=DEVICE_PLANE,
           op_line=OP_LINE, top=10):
    """The numbers the benchmark takes from one trace.

    - ``window_s``: first start to last end of any device operation,
      the same for every chip;
    - ``busy_s``: union of the operation intervals, averaged over the
      chips that ran anything;
    - ``device_ops``: the ``top`` operation families by summed SELF time
      on the device (seconds, mean over chips);
    - ``idle_gaps``: the ``top`` longest gaps between operations on the
      busiest chip, each named by what the host was in meanwhile and the
      operation that ended the gap;
    - ``kernel_s``: summed self time of the operations whose name matches
      ``kernel_pattern`` (mean over chips), when a pattern is given.

    None when no device plane holds an operation.
    """
    planes = device_ops(profile, plane_pattern, op_line)
    if not planes:
        return None
    n = len(planes)
    t0 = min(ops[0][0] for ops in planes.values())
    t1 = max(max(e for _, e, _ in ops) for ops in planes.values())
    by_family, kernel_ns = {}, 0.0
    for ops in planes.values():
        for own, name in self_times(ops):
            fam = op_family(name)
            by_family[fam] = by_family.get(fam, 0.0) + own
            if kernel_pattern and re.search(kernel_pattern, name):
                kernel_ns += own
    busiest = max(planes.values(), key=union_s)
    gaps, cursor = [], busiest[0][0]
    for s, e, name in busiest:
        if s > cursor:
            gaps.append((s - cursor, cursor, s, name))
        cursor = max(cursor, e)
    gaps.sort(reverse=True)
    return {
        "chips": n,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(union_s(ops) for ops in planes.values()) / n,
        "device_ops": [
            [fam, ns / 1e9 / n]
            for fam, ns in sorted(
                by_family.items(), key=lambda kv: -kv[1]
            )[:top]
        ],
        "idle_gaps": [
            [
                f"{host_activity(profile, g0, g1)[:60]} -> {op_family(name)}",
                dur / 1e9,
            ]
            for dur, g0, g1, name in gaps[:top]
        ],
        "kernel_s": kernel_ns / 1e9 / n if kernel_pattern else None,
    }


def describe(profile, limit=12):
    """Planes, lines and the longest events of each line, with their
    stats: what to read before writing a pattern against a trace."""
    out = []
    for plane in profile.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            totals = {}
            for ev in events:
                d = totals.setdefault(ev.name, [0, 0.0, None])
                d[0] += 1
                d[1] += float(ev.duration_ns)
                if d[2] is None:
                    d[2] = {k: str(v)[:160] for k, v in ev.stats}
            top = sorted(totals.items(), key=lambda kv: -kv[1][1])[:limit]
            lines.append(
                {
                    "line": line.name,
                    "events": len(events),
                    "top": [
                        {"name": k, "n": v[0], "ms": v[1] / 1e6,
                         "stats": v[2]}
                        for k, v in top
                    ],
                }
            )
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    path = find_xplane(sys.argv[1])
    prof = load(path)
    print(json.dumps(describe(prof), indent=1))
    print(json.dumps(reduce(prof), indent=1))
