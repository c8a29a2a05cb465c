"""The benchmark's arithmetic: rows and spans in, numbers out.

Pure functions over plain lists and dicts (no JAX, no program import),
so the tests under ``benchmarks/tests`` check them on synthetic rows in
milliseconds.  The inputs are

- *step rows*: ``{"step", "t", "loss", "inc"}`` written by the
  benchmark's own callback in ``worker_train.py`` (``t`` = the worker's
  ``time.time()`` when the step's loss had reached the host);
- *spans*: closed intervals ``{"phase", "start", "end", "pid", "inc",
  "labels"}`` on the wall clock, paired here from the program's
  timeline file (``observability/events.py`` JSONL records);
- *request rows*: ``{"submit", "done", "new_tokens", ...}`` on the
  load generator's wall clock.
"""

import json
import math
import os
import statistics


def read_jsonl(path):
    """Rows of a JSONL file; a writer killed mid-line leaves a torn last
    line, which is skipped."""
    rows = []
    if not os.path.exists(path):
        return rows
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict):
                rows.append(row)
    return rows


def append_jsonl(path, row):
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")


def pair_spans(events):
    """Timeline records -> closed intervals on the WALL clock.

    After ``observability/events.pair_spans`` (the program's reader,
    which the benchmark does not import: the yardstick may not move with
    the program): ``X`` records carry start and duration; ``B``/``E``
    pair by ``(pid, sid)`` and take their length from the writer's
    monotonic clock.  A ``B`` whose writer died is dropped — nothing the
    benchmark reads is a half-open span.  Instants (``ph: "i"``) come
    back as zero-length intervals.
    """
    spans, open_b = [], {}
    for e in sorted(events, key=lambda e: e.get("mono", 0.0)):
        ph = e.get("ph")
        base = {
            "phase": e.get("name", ""),
            "pid": e.get("pid", 0),
            "rank": e.get("rank", -1),
            "inc": int(e.get("inc", 0)),
            "labels": dict(e.get("labels") or {}),
        }
        if ph == "X":
            start = float(e["wall"])
            spans.append(
                dict(base, start=start, end=start + float(e.get("dur", 0.0)))
            )
        elif ph == "i":
            start = float(e["wall"])
            spans.append(dict(base, start=start, end=start))
        elif ph == "B":
            open_b[(e.get("pid"), e.get("sid"))] = (e, base)
        elif ph == "E":
            hit = open_b.pop((e.get("pid"), e.get("sid")), None)
            if hit is None:
                continue
            b, base = hit
            base["labels"].update(e.get("labels") or {})
            start = float(b["wall"])
            dur = max(float(e["mono"]) - float(b["mono"]), 0.0)
            spans.append(dict(base, start=start, end=start + dur))
    spans.sort(key=lambda s: (s["start"], s["end"]))
    return spans


def read_spans(path):
    return pair_spans([e for e in read_jsonl(path) if "name" in e])


def named(spans, phase, inc=None):
    return [
        s for s in spans
        if s["phase"] == phase and (inc is None or s["inc"] == inc)
    ]


def percentile(values, q):
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default), over ALL the values given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


# ------------------------------------------------------------------ train


def step_gaps(rows, inc=0):
    """``{step: t(step) - t(step - 1)}`` over consecutive step rows of
    one incarnation."""
    by_step = {r["step"]: r["t"] for r in rows if r.get("inc", 0) == inc}
    return {
        s: t - by_step[s - 1] for s, t in by_step.items() if s - 1 in by_step
    }


def in_window(rows, window, inc=0):
    t0, t1 = window
    return [
        r for r in rows if r.get("inc", 0) == inc and t0 <= r["t"] <= t1
    ]


def whole_cycle_tokens_per_s(rows, window, snap_every, tokens_per_step):
    """Tokens of the steps between the completion of the first and of
    the last snapshot-bearing step inside the window, over the wall time
    between those two completions.  Whole snapshot cycles only: the rate
    does not jump with whether the window happens to hold 3 or 4 stalls,
    and every stall between the two ends is inside it.  None when the
    window holds fewer than two snapshot-bearing steps."""
    snaps = [r for r in in_window(rows, window) if r["step"] % snap_every == 0]
    if len(snaps) < 2:
        return None
    first, last = snaps[0], snaps[-1]
    return (
        (last["step"] - first["step"]) * tokens_per_step
        / (last["t"] - first["t"])
    )


def plain_step_s(rows, window, snap_every):
    """Median gap between the completions of consecutive plain steps in
    the window.  A snapshot-bearing step's gap holds the stall, and the
    step after it is dispatched behind the stall: both are left out."""
    gaps = step_gaps(rows)
    inside = {r["step"] for r in in_window(rows, window)}
    plain = [
        g for s, g in gaps.items()
        if s in inside and s - 1 in inside and s % snap_every not in (0, 1)
    ]
    return statistics.median(plain) if plain else None


def snapshot_stall_s(rows, window, snap_every):
    """Median over the window's snapshot-bearing steps of (that step's
    completion gap - the window's median plain-step gap)."""
    plain = plain_step_s(rows, window, snap_every)
    gaps = step_gaps(rows)
    inside = {r["step"] for r in in_window(rows, window)}
    stalls = [
        g - plain for s, g in gaps.items()
        if s in inside and s % snap_every == 0
    ] if plain is not None else []
    return statistics.median(stalls) if stalls else None


# ----------------------------------------------------------------- resume


def resume_partition(t_kill, spans, rows, agent_pid):
    """The wall time from the SIGKILL to incarnation 1's first completed
    step, and its consecutive parts.  Keys (seconds; a part that cannot
    be read is absent):

    - ``resume_s``: kill -> ``t`` of the first step row of incarnation 1
    - ``detect_s``: kill -> start of the agent's ``restart`` span
    - ``restart_s``: ``restart`` start -> first timeline record of
      incarnation 1 that is not the agent's own (flush of the shm
      snapshot to storage, stop, spawn, the worker's imports)
    - ``restore_s``: the ``checkpoint_restore`` span of incarnation 1
    - ``first_step_s``: end of that restore -> first step row
    """
    out = {}
    first = [r for r in rows if r.get("inc", 0) == 1]
    if first:
        out["resume_s"] = min(r["t"] for r in first) - t_kill
    restarts = [
        s for s in named(spans, "restart") if s["start"] >= t_kill - 1.0
    ]
    if restarts:
        r0 = restarts[0]["start"]
        out["detect_s"] = r0 - t_kill
        worker = [
            s for s in spans
            if s["inc"] == 1 and s["pid"] != agent_pid and s["start"] >= r0
        ]
        if worker:
            out["restart_s"] = min(s["start"] for s in worker) - r0
    restores = named(spans, "checkpoint_restore", inc=1)
    if restores:
        out["restore_s"] = restores[0]["end"] - restores[0]["start"]
        if first:
            out["first_step_s"] = (
                min(r["t"] for r in first) - restores[0]["end"]
            )
    return out


# ---------------------------------------------------------------- rollout


def rollout_tokens_per_s(requests, window):
    """New tokens delivered inside the window over its length.  A
    request that straddles an edge of the window is credited the share
    of its [submit, done] interval that lies inside (the caller sees no
    token before ``done``; its tokens were made all along that
    interval), so the rate covers all the work of the window and does
    not swing with which requests happen to end just after it."""
    t0, t1 = window
    tokens = 0.0
    for r in requests:
        overlap = min(r["done"], t1) - max(r["submit"], t0)
        if overlap > 0:
            tokens += r["new_tokens"] * overlap / (r["done"] - r["submit"])
    return tokens / (t1 - t0)


def completed_in(requests, window):
    t0, t1 = window
    return [r for r in requests if t0 <= r["done"] <= t1]


def tpot_ms(requests, window):
    """Per request completed inside the window: (done - submit) / new
    tokens, in ms — prefill, queueing and the other lanes' prefill
    chunks included."""
    return [
        1e3 * (r["done"] - r["submit"]) / r["new_tokens"]
        for r in completed_in(requests, window)
    ]
