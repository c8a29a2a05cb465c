"""Readers of a run's SET-UP, from inside the program (ISSUE 55): the
``startup`` stages a chip-owning process writes from its start to its
first step (``observability/events.py`` ``STARTUP_STAGES``) and the
``compile`` records its ``common/jax_env.CompileMeter`` writes a program
and stage (``trace`` | ``lower`` | ``backend_compile``, the last with
``cache``: hit | miss | none).

The run's start is ``window[0] - setup_s`` (the harness sets both
before the readers run); "before the window" means a record that ENDS
at or before ``window[0]``, "in the window" one that STARTS inside it.
Seconds of records are seconds of wall clock: overlapping and nested
records count once (a program traced inside another's lowering, a
``compile`` record inside the ``serve_step`` that holds it).

Like ``readers.py``: each takes one number from ``ctx`` or returns None
when there is nothing to read — a program that writes no ``startup``
stage and no ``compile`` record (the parent of the PR that added them)
leaves the metric out of the line, and nothing here raises for it.
"""

import metrics as M
from readers_spans import merged, overlap


def _run_start(ctx):
    return ctx["window"][0] - ctx["end_to_end"]["setup_s"]


def _pairs(spans):
    return [(s["start"], s["end"]) for s in spans]


def _length(intervals):
    return float(sum(e - s for s, e in merged(intervals)))


def _before(ctx, spans):
    t0 = ctx["window"][0]
    return [s for s in spans if s["end"] <= t0]


def _stages(ctx, stages):
    """``startup`` spans of the first incarnation with one of
    ``stages``."""
    return [
        s for s in M.named(ctx["spans"], "startup", inc=0)
        if s["labels"].get("stage") in stages
    ]


def _compiles(ctx, stages=None, cache=None):
    return [
        s for s in M.named(ctx["spans"], "compile")
        if "stage" in s["labels"]  # CompileMeter's, not a hand-made span
        and (stages is None or s["labels"]["stage"] in stages)
        and (cache is None or s["labels"].get("cache") in cache)
    ]


def stage_seconds(ctx, stages, phases=()):
    """Summed length of the first incarnation's ``startup`` spans with
    one of ``stages``, plus the spans of ``phases`` (``weight_cast``),
    all before the window.  None without such a stage."""
    found = _before(ctx, _stages(ctx, stages))
    if not found:
        return None
    for phase in phases:
        found += _before(ctx, M.named(ctx["spans"], phase))
    return sum(s["end"] - s["start"] for s in found)


def compile_seconds(ctx, stages, cache=None, where="before"):
    """Wall clock under the ``compile`` records of ``stages`` (and, of a
    ``backend_compile``, of one of ``cache``): before the window, or —
    ``where="window"`` — of the records that start inside it, which name
    a recompile there.  None where the program writes no such records;
    0.0 where it does and none matches."""
    if not _compiles(ctx):
        return None
    found = _compiles(ctx, stages, cache)
    if where == "window":
        t0, t1 = ctx["window"]
        found = [s for s in found if t0 <= s["start"] <= t1]
    else:
        found = _before(ctx, found)
    return _length(_pairs(found))


def compile_count(ctx, stages, cache):
    """How many ``compile`` records of ``stages`` and ``cache`` end
    before the window."""
    if not _compiles(ctx):
        return None
    return len(_before(ctx, _compiles(ctx, stages, cache)))


def stepping_seconds(ctx, phases, stages=()):
    """Wall clock before the window under the spans of ``phases``
    (``serve_step``; ``step``) and the ``startup`` stages ``stages``
    (``first_step``), less what the ``compile`` records inside them
    cover: warm-up, ramp and first snapshot cycles — what the traffic
    file dictates plus each program's load and first run."""
    if not _compiles(ctx):
        return None
    found = _stages(ctx, stages)
    for phase in phases:
        found += M.named(ctx["spans"], phase)
    steps = merged(_pairs(_before(ctx, found)))
    if not steps:
        return None
    compiles = merged(_pairs(_before(ctx, _compiles(ctx))))
    return _length(steps) - overlap(steps, compiles)


def unnamed_seconds(ctx):
    """The part of [run start, window start] that NO span and no
    ``compile`` record of the events file covers: the coverage witness
    (the benchmark's own imports and cell loading, the launcher and
    agent before the spawn, a script's imports between two stages,
    anything missed).  None where no process wrote a ``startup``
    stage."""
    if not M.named(ctx["spans"], "startup"):
        return None
    lo, hi = _run_start(ctx), ctx["window"][0]
    covered = [
        (max(s["start"], lo), min(s["end"], hi))
        for s in ctx["spans"] if s["end"] > lo and s["start"] < hi
    ]
    return (hi - lo) - _length(covered)
