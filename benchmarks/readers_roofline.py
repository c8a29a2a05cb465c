"""Readers of a kernel's share of its memory roofline: the bytes the
algorithm has to move for one call — a function of the configuration's
family module, counted from the dtypes the program holds — times the
calls of the kernel in the traced window, over their summed time on the
device, over the chip's published memory bandwidth (``peaks.json``).

Like ``readers.py``: a reader returns None when there is nothing to
read — no trace, no operation of that name (the parent of the PR that
added the kernel), a family without the byte function — and the harness
leaves the metric out of the line; nothing here raises for it.  A
reading over 100 means the byte function counts too much or the
pattern misses part of the kernel's time: it is reported as it is,
never clipped.
"""

import re

import flops
from harness import family


def kernel_bandwidth_share(ctx, pattern, bytes_fn, lanes_key):
    """``bytes_fn(cfg, lanes)`` of the cell's family (``lanes``: the
    traffic file's ``lanes_key``) a call, times the device operations
    whose name matches ``pattern``, over their summed duration and the
    device's ``hbm_bytes_per_s``, in percent."""
    import xplane

    prof = ctx.get("trace_profile")
    if prof is None:
        return None
    cell = ctx["cell"]
    count = getattr(family(cell["config"]), bytes_fn, None)
    if count is None:
        return None
    durations = [
        (end - start) / 1e9
        for ops in xplane.device_ops(prof).values()
        for start, end, name in ops
        if re.search(pattern, name)
    ]
    if not durations or sum(durations) <= 0:
        return None
    peak = flops.peak_for(
        cell["peaks"], ctx["device_report"]["device_kind"]
    )["hbm_bytes_per_s"]
    moved = count(cell["config"], cell["traffic"][lanes_key]) * len(durations)
    return 100.0 * moved / sum(durations) / peak
