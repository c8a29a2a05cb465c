"""Readers of the metrics a learned sparse attention adds, beside the
readers that are there (which this file leaves as they are).

Like ``readers.py``: a reader returns None when there is nothing to
read — no trace, a program without the scope, the kernel or the label
(the parent of the PR that added them) — and the harness leaves the
metric out of the line; nothing here raises for it.
"""

import re

import flops
import readers_scopes
from harness import family
from readers_spans import _window_spans


def scope_share(ctx, pattern, more_parts):
    """``readers_scopes.scope_share`` over a partition whose closed set
    of parts also holds ``more_parts``: a scope the program enters
    INSIDE one of ``readers_scopes.PARTS`` (``attn/indexer``), so that
    the readers of that set go on counting its time under the outer
    part while this one reads the inner.  Computed once a run."""
    key = "scope_partition+" + ",".join(more_parts)
    if key not in ctx:
        parts = readers_scopes.PARTS
        readers_scopes.PARTS = tuple(parts) + tuple(more_parts)
        try:
            ctx[key] = readers_scopes._partition(ctx)
        finally:
            readers_scopes.PARTS = parts
    if ctx[key] is None:
        return None
    shares = [
        pct for name, pct in ctx[key]["by_key"].items()
        if re.search(pattern, name)
    ]
    return sum(shares) if shares else None


def fewest_lanes(lanes, steps):
    """The lowest mean of ``steps`` consecutive entries of ``lanes``
    (all of them where there are fewer): what ANY run of that many
    decode steps had at least, whichever of them the trace caught."""
    steps = max(1, min(int(steps), len(lanes)))
    total = low = sum(lanes[:steps])
    for gone, new in zip(lanes, lanes[steps:]):
        total += new - gone
        low = min(low, total)
    return low / steps


def kernel_bandwidth_share_lanes(ctx, pattern, bytes_fn):
    """``readers_roofline.kernel_bandwidth_share`` for a kernel that
    skips the lanes that do not decode: ``bytes_fn(cfg, lanes)`` of the
    cell's family a call, times the device operations whose name
    matches ``pattern``, over their summed duration and the device's
    ``hbm_bytes_per_s``, in percent.  The trace is a few seconds
    somewhere inside the window and the records say nothing of where,
    so ``lanes`` is a floor: the kernel runs once a layer and decode
    step, the trace therefore holds ``calls / layers`` consecutive
    steps, and of all runs of that many ``serve_step`` records of the
    window that decoded the one with the fewest ``lanes_decode`` is
    taken (:func:`fewest_lanes`) — never a row the kernel did not
    move, so the share can only read low."""
    import xplane

    prof = ctx.get("trace_profile")
    if prof is None:
        return None
    cell = ctx["cell"]
    count = getattr(family(cell["config"]), bytes_fn, None)
    lanes = [
        s["labels"]["lanes_decode"]
        for s in sorted(
            _window_spans(ctx, "serve_step"), key=lambda s: s["start"]
        )
        if s["labels"].get("lanes_decode", 0) > 0
    ]
    durations = [
        (end - start) / 1e9
        for ops in xplane.device_ops(prof).values()
        for start, end, name in ops
        if re.search(pattern, name)
    ]
    if count is None or not lanes or not durations or sum(durations) <= 0:
        return None
    peak = flops.peak_for(
        cell["peaks"], ctx["device_report"]["device_kind"]
    )["hbm_bytes_per_s"]
    steps = len(durations) // cell["config"]["num_hidden_layers"]
    moved = count(cell["config"], fewest_lanes(lanes, steps)) * len(durations)
    return 100.0 * moved / sum(durations) / peak
