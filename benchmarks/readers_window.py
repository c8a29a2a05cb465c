"""Readers of the metrics that layers of two kinds of attention and a
share of a layer's experts add, beside the readers that are there
(which this file leaves as they are).

Each is a share of a roofline: what the kernel HAS to move or compute,
from the program's own labels through the family's byte and operation
functions, over the kernel's summed time on the device in the replica's
trace, over the chip's published peak (``peaks.json``).  The trace is a
few seconds somewhere inside the window and the records say nothing of
where, so what was moved is a FLOOR: the kernel's calls in the trace
say how many consecutive steps (or chunks) it holds, and of all runs of
that many records of the window the one that asks for least is taken
(``lowest_run``) — a share can only read low, never over what the
kernel did.

Like ``readers.py``: a reader returns None when there is nothing to
read — no trace, a program without the kernel or the label (the parent
of the PR that added them), a family without the function — and the
harness leaves the metric out of the line; nothing here raises for it.
"""

import re

import flops
from harness import family
from readers_spans import _window_spans


def lowest_run(values, n):
    """The lowest sum of ``n`` consecutive entries of ``values`` (of
    all of them where there are fewer)."""
    n = max(1, min(int(n), len(values)))
    total = low = sum(values[:n])
    for gone, new in zip(values, values[n:]):
        total += new - gone
        low = min(low, total)
    return low


def _kernel_seconds(ctx, pattern, inside=None):
    """Durations of the device operations whose name matches
    ``pattern`` — with ``inside``, of those that start within a run of a
    compiled program whose name matches it (the trace's ``XLA Modules``
    line: ``jit__decode_lp(...)``)."""
    import xplane

    prof = ctx.get("trace_profile")
    if prof is None:
        return []
    runs = {}
    if inside is not None:
        for plane in prof.planes:
            runs[plane.name] = [
                (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                for line in plane.lines if line.name == "XLA Modules"
                for ev in line.events if re.search(inside, ev.name)
            ]
    return [
        (end - start) / 1e9
        for plane, ops in xplane.device_ops(prof).items()
        for start, end, name in ops
        if re.search(pattern, name) and (
            inside is None or any(a <= start < b for a, b in runs[plane])
        )
    ]


def _records(ctx, phase, labels):
    """The label dicts of the window's ``phase`` records that carry
    every one of ``labels``, in order of start."""
    return [
        s["labels"]
        for s in sorted(_window_spans(ctx, phase), key=lambda s: s["start"])
        if all(name in s["labels"] for name in labels)
    ]


def _peak(ctx, key):
    return flops.peak_for(
        ctx["cell"]["peaks"], ctx["device_report"]["device_kind"]
    )[key]


def decode_bandwidth_share(ctx, pattern, bytes_fn, rows_label, kind):
    """A decode attention kernel's share of the memory roofline:
    ``bytes_fn(cfg, rows, lanes)`` of the cell's family a decode step —
    ``rows`` the step's ``rows_label`` (``kv_rows_window`` /
    ``kv_rows_full``: the token rows its lanes had to read, summed over
    the layers of the ``kind``), ``lanes`` its ``lanes_decode`` — over
    the kernel's calls in the trace, ``layers_of_kind(cfg)[kind]`` a
    step, over their summed time and ``hbm_bytes_per_s``, in percent."""
    cfg = ctx["cell"]["config"]
    fam = family(cfg)
    count = getattr(fam, bytes_fn, None)
    kinds = getattr(fam, "layers_of_kind", None)
    seconds = _kernel_seconds(ctx, pattern)
    steps = [
        r for r in _records(ctx, "serve_step", (rows_label, "lanes_decode"))
        if r["lanes_decode"] > 0
    ]
    if count is None or kinds is None or not steps or sum(seconds) <= 0:
        return None
    moved = lowest_run(
        [count(cfg, r[rows_label], r["lanes_decode"]) for r in steps],
        len(seconds) // max(kinds(cfg)[kind], 1),
    )
    return 100.0 * moved / sum(seconds) / _peak(ctx, "hbm_bytes_per_s")


def prefill_mxu_share(ctx, pattern, flops_fn):
    """The chunk attention kernel's share of the chip's bf16 peak:
    ``flops_fn(cfg, rows, kv_len)`` of the cell's family a chunk (the
    ``prefill`` span's labels: its real rows and the position behind
    its last) over the kernel's calls in the trace, one a layer and
    chunk, over their summed time and ``bf16_flops_per_s``, in
    percent.  Padded rows are computed and not counted."""
    cfg = ctx["cell"]["config"]
    count = getattr(family(cfg), flops_fn, None)
    seconds = _kernel_seconds(ctx, pattern)
    chunks = _records(ctx, "prefill", ("rows", "kv_len"))
    if count is None or not chunks or sum(seconds) <= 0:
        return None
    done = lowest_run(
        [count(cfg, r["rows"], r["kv_len"]) for r in chunks],
        len(seconds) // cfg["num_hidden_layers"],
    )
    return 100.0 * done / sum(seconds) / _peak(ctx, "bf16_flops_per_s")


def expert_bandwidth_share_decode(ctx, pattern, program, bytes_fn):
    """The routed experts' kernel's share of the memory roofline IN
    DECODE, where a step's few rows make it read an expert's three
    matrices for a row or two: ``bytes_fn(cfg)`` of the cell's family an
    expert hit, times the step's ``experts_hit`` label (distinct held
    experts with a row, the mean over the expert layers) and the expert
    layers, over the kernel's calls INSIDE the runs of the compiled
    program whose name matches ``program`` (a prefill chunk's calls,
    tens of rows an expert, are not in it), over their summed time and
    ``hbm_bytes_per_s``, in percent."""
    cfg = ctx["cell"]["config"]
    count = getattr(family(cfg), bytes_fn, None)
    seconds = _kernel_seconds(ctx, pattern, inside=program)
    steps = _records(ctx, "serve_step", ("experts_hit",))
    layers = cfg["num_hidden_layers"] - cfg.get("num_dense_layers", 0)
    if count is None or not steps or sum(seconds) <= 0 or layers <= 0:
        return None
    moved = lowest_run(
        [r["experts_hit"] * layers * count(cfg) for r in steps],
        len(seconds) // layers,
    )
    return 100.0 * moved / sum(seconds) / _peak(ctx, "hbm_bytes_per_s")
