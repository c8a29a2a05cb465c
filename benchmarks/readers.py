"""Readers of the per-layer metrics: each takes one number from what a
traced run left behind (``ctx``: step rows, spans, the window, request
rows, the reduced trace) or returns None when there is nothing to read —
the harness then leaves the metric out of the line.  A metric's
``layer_metrics/<name>.json`` names one of these with its arguments; a
later PR that needs another reader adds a module of its own beside this
one and names that.
"""

import statistics

import flops
import metrics as M
from harness import family


def _traffic(ctx):
    return ctx["cell"]["traffic"]


def _scaled(value, scale):
    return None if value is None else value * scale


def plain_step(ctx, scale=1.0):
    """Median completion gap of consecutive plain steps in the window."""
    return _scaled(
        M.plain_step_s(
            ctx["rows"], ctx["window"], _traffic(ctx)["save_memory_interval"]
        ),
        scale,
    )


def snapshot_stall(ctx, scale=1.0):
    return _scaled(
        M.snapshot_stall_s(
            ctx["rows"], ctx["window"], _traffic(ctx)["save_memory_interval"]
        ),
        scale,
    )


def step_mfu(ctx):
    """Required FLOPs of the plain step over its median time and the
    chip's published bf16 peak: a utilization from ``step.ms``, not a
    kernel's roofline share."""
    step_s = plain_step(ctx)
    if step_s is None:
        return None
    cell = ctx["cell"]
    peak = flops.peak_for(cell["peaks"], ctx["device_report"]["device_kind"])
    cfg, seq = cell["config"], _traffic(ctx)["seq"]
    return flops.mfu_pct(
        family(cfg).train_flops_per_token(cfg, seq),
        ctx["tokens_per_step"], step_s, peak["bf16_flops_per_s"],
        chips=cell["chips"],
    )


def resume_part(ctx, part, scale=1.0):
    return _scaled(ctx["resume"].get(part), scale)


def device_row(ctx, inc, field, scale=1.0):
    """A field of the worker's own device row (``CompileMeter`` counts
    and seconds, first step) for one incarnation."""
    row = ctx["device_rows"].get(inc)
    return None if row is None else _scaled(row.get(field), scale)


def _window_spans(ctx, phase):
    t0, t1 = ctx["window"]
    return [
        s for s in M.named(ctx["spans"], phase) if t0 <= s["start"] <= t1
    ]


def span_median(ctx, phase, scale=1.0):
    """Median length of the spans of one phase that start in the
    window."""
    spans = _window_spans(ctx, phase)
    if not spans:
        return None
    return scale * statistics.median(s["end"] - s["start"] for s in spans)


def span_share(ctx, part, whole, scale=100.0):
    """Summed length of ``part`` spans over that of ``whole`` spans, both
    starting in the window."""
    num = sum(s["end"] - s["start"] for s in _window_spans(ctx, part))
    den = sum(s["end"] - s["start"] for s in _window_spans(ctx, whole))
    return scale * num / den if den > 0 else None


def engine_overhead(ctx, scale=1e3):
    """Median per request completed in the window of (client completion
    - client submit) - the replica's ``serve_request`` span.  That span
    starts at the wall time the dispatcher stamped at ``submit`` and ends
    when the scheduler finishes the request (``rl/scheduler.py``
    ``_finish``), so it INCLUDES ``queue_wait``; what is left is the way
    back: result ring, dispatcher thread, the client's wake-up."""
    span = {
        int(s["labels"]["req_id"]): s["end"] - s["start"]
        for s in M.named(ctx["spans"], "serve_request")
    }
    over = [
        (r["done"] - r["submit"]) - span[r["req_id"]]
        for r in M.completed_in(ctx["requests"], ctx["window"])
        if r["req_id"] in span
    ]
    return scale * statistics.median(over) if over else None


def tpot_percentile(ctx, q):
    """The ``q``-th percentile over the requests completed in the window
    of (client completion - submit) / new tokens, in ms: time per output
    token as the rollout caller feels it, its own prefill and the other
    lanes' prefill chunks included."""
    tpot = M.tpot_ms(ctx["requests"], ctx["window"])
    return M.percentile(tpot, q) if tpot else None


def trace_idle(ctx):
    """1 - union of device-operation intervals / traced window."""
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def trace_kernel_share(ctx, pattern):
    """Device time of the operations whose name matches ``pattern`` over
    the device's busy time, from the trace."""
    import xplane

    prof = ctx.get("trace_profile")
    if prof is None:
        return None
    tr = xplane.reduce(prof, kernel_pattern=pattern)
    if not tr or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]


def trace_module_median(ctx, pattern, scale=1.0):
    """Median device time of one run of the compiled program whose name
    matches ``pattern`` (the trace's ``XLA Modules`` line)."""
    import xplane

    prof = ctx.get("trace_profile")
    times = xplane.module_times(prof, pattern) if prof is not None else []
    return scale * statistics.median(times) if times else None
