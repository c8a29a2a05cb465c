"""Readers of what the program says about itself from INSIDE its hot
loops (ISSUE 24): the labels the serving scheduler writes onto each
``serve_step`` record — the iteration's host time by leaf phase, the
lanes at work — and the ``sched.*`` annotations it puts on the host
plane of the profiler's trace, on the device operations' own clock.

Like ``readers.py``: each takes one number from ``ctx`` or returns None
when there is nothing to read — a program that writes no such label or
annotation (the parent of the PR that added them) leaves the metric out
of the line, and nothing here raises for it.
"""

import re
import statistics

import metrics as M


def _window_spans(ctx, phase):
    t0, t1 = ctx["window"]
    return [
        s for s in M.named(ctx["spans"], phase) if t0 <= s["start"] <= t1
    ]


def label_median(ctx, phase, label, scale=1.0):
    """Median of one numeric label over the spans of ``phase`` that
    start in the window and carry it."""
    values = [
        s["labels"][label] for s in _window_spans(ctx, phase)
        if label in s["labels"]
    ]
    return scale * statistics.median(values) if values else None


def label_ratio(ctx, phase, num, den, scale=100.0):
    """Summed label ``num`` over summed label ``den``, over the spans of
    ``phase`` that start in the window and carry both."""
    both = [
        s["labels"] for s in _window_spans(ctx, phase)
        if num in s["labels"] and den in s["labels"]
    ]
    total = sum(labels[den] for labels in both)
    if total <= 0:
        return None
    return scale * sum(labels[num] for labels in both) / total


def merged(intervals):
    """Sorted, disjoint ``[start, end]`` lists covering the union of
    ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(a, b):
    """Length covered by both of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(ops):
    """``[start, end]`` of every gap between the operations of one chip
    (``xplane.device_ops`` order), first start to last end."""
    gaps, cursor = [], ops[0][0]
    for s, e, _ in ops:
        if s > cursor:
            gaps.append([cursor, s])
        cursor = max(cursor, e)
    return gaps


def host_events(profile, pattern, plane_pattern=r"^/host:"):
    """``(start_ns, end_ns)`` of the host-plane events whose name matches
    ``pattern``."""
    out = []
    for plane in profile.planes:
        if not re.match(plane_pattern, plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if re.search(pattern, ev.name):
                    start = float(ev.start_ns)
                    out.append((start, start + float(ev.duration_ns)))
    return out


def idle_named(ctx, pattern):
    """Share of the busiest chip's idle time in the traced window that
    lies inside a host annotation of the program's matching ``pattern``
    (``jax.profiler.TraceAnnotation`` events on the host plane: the same
    file, the same clock as the device operations).  What is left is
    idle time the program has no name for."""
    import xplane

    prof = ctx.get("trace_profile")
    if prof is None:
        return None
    planes = xplane.device_ops(prof)
    named = merged(host_events(prof, pattern))
    if not planes or not named:
        return None
    gaps = idle_gaps(max(planes.values(), key=xplane.union_s))
    idle = sum(e - s for s, e in gaps)
    return 100.0 * overlap(gaps, named) / idle if idle > 0 else None
