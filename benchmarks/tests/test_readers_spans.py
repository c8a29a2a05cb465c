"""The readers of the program's own labels and annotations
(``readers_spans.py``) on small hand-made contexts and on the recorded
trace: each gives the number a reading by hand gives, and None — never
an error — where the program wrote nothing to read."""

import json
import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness
import readers_spans as R
import xplane

TRACE = os.path.join(HERE, "data", "train_5steps.xplane.pb.gz")
NEW = [
    "sched.admit_ms", "sched.dispatch_ms", "sched.commit_ms",
    "sched.occupancy_pct", "device.serve_idle_named_pct",
    "kernel.paged_share_pct", "ckpt.pull_ms", "ckpt.drain_ms",
    "step.span_ms",
]


def span(start, dur, **labels):
    return {"phase": "serve_step", "start": start, "end": start + dur,
            "pid": 1, "inc": 0, "labels": labels}


def ctx_of(spans, window=(10.0, 20.0)):
    return {"spans": spans, "window": window}


def test_label_median_over_the_spans_that_start_in_the_window():
    ctx = ctx_of([
        span(9.0, 0.1, admit_ms=50.0),            # before the window
        span(10.5, 0.1, admit_ms=0.2),
        span(11.0, 0.1, admit_ms=0.4),
        span(12.0, 0.1, admit_ms=0.9),
        span(13.0, 0.1),                          # SERVE_OBS=0: no label
        span(21.0, 0.1, admit_ms=70.0),           # after
        dict(span(14.0, 0.1, admit_ms=9.0), phase="decode"),
    ])
    assert R.label_median(ctx, "serve_step", "admit_ms") == 0.4
    assert R.label_median(ctx, "serve_step", "admit_ms", scale=2.0) == 0.8


@pytest.mark.parametrize("spans", [
    [],
    [span(11.0, 0.1, tokens=3)],                  # the parent's record
    [span(30.0, 0.1, admit_ms=1.0)],              # outside the window
])
def test_label_readers_give_none_with_nothing_to_read(spans):
    ctx = ctx_of(spans)
    assert R.label_median(ctx, "serve_step", "admit_ms") is None
    assert R.label_ratio(
        ctx, "serve_step", "lanes_decode", "slots"
    ) is None


def test_label_ratio_is_a_ratio_of_sums():
    ctx = ctx_of([
        span(11.0, 0.1, lanes_decode=16, slots=16),
        span(12.0, 0.1, lanes_decode=12, slots=16),
        span(13.0, 0.1, lanes_decode=0, slots=16),
        span(14.0, 0.1, slots=16),                # no numerator: skipped
    ])
    assert R.label_ratio(
        ctx, "serve_step", "lanes_decode", "slots"
    ) == pytest.approx(100.0 * 28 / 48)


def test_interval_arithmetic():
    assert R.merged([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert R.overlap([[0, 3], [5, 8]], [[2, 6], [7, 20]]) == 1 + 1 + 1
    assert R.overlap([[0, 3]], []) == 0
    ops = [(0, 4, "a"), (1, 2, "inner"), (6, 7, "b"), (7, 9, "c"),
           (12, 13, "d")]
    assert R.idle_gaps(ops) == [[4, 6], [9, 12]]


def fake_profile(host_events, ops):
    def events(rows):
        return [NS(name=n, start_ns=s, duration_ns=e - s) for s, e, n in rows]

    return NS(planes=[
        NS(name="/device:TPU:0",
           lines=[NS(name="XLA Ops", events=events(ops))]),
        NS(name="/host:CPU",
           lines=[NS(name="python3", events=events(host_events))]),
        NS(name="/host:metadata", lines=[]),
    ])


def test_idle_named_is_the_share_of_idle_time_under_an_annotation():
    ops = [(0, 100, "%jit_decode.1 = x"), (140, 200, "%jit_decode.2 = x"),
           (260, 300, "%jit_decode.3 = x")]          # idle: 40 + 60
    host = [
        (90, 105, "sched.wait"),                     # 5 of gap one
        (105, 125, "sched.commit"),                  # 20
        (125, 130, "np.asarray(jax.Array)"),         # not the program's
        (130, 150, "sched.dispatch"),                # 10
        (200, 230, "sched.admit"),                   # 30 of gap two
        (205, 210, "sched.admit"),                   # nested: once
    ]
    ctx = {"trace_profile": fake_profile(host, ops)}
    assert R.idle_named(ctx, r"^sched\.") == pytest.approx(65.0)
    assert R.idle_named(ctx, r"^sched\.(wait)$") == pytest.approx(5.0)


def test_idle_named_gives_none_with_nothing_to_read():
    ops = [(0, 100, "%a = x"), (140, 200, "%b = x")]
    assert R.idle_named({}, r"^sched\.") is None
    assert R.idle_named({"trace_profile": None}, r"^sched\.") is None
    # no annotation of the program's (the parent), no device plane (CPU),
    # no idle time at all
    no_ann = fake_profile([(0, 300, "PjitFunction(step)")], ops)
    assert R.idle_named({"trace_profile": no_ann}, r"^sched\.") is None
    no_dev = fake_profile([(0, 300, "sched.wait")], [])
    assert R.idle_named({"trace_profile": no_dev}, r"^sched\.") is None
    busy = fake_profile([(0, 300, "sched.wait")], [(0, 100, "%a = x")])
    assert R.idle_named({"trace_profile": busy}, r"^sched\.") is None


def test_idle_named_on_the_recorded_trace():
    """PR 23's trace of cell A: the program had no annotations then, so
    there is nothing to read; the host events JAX itself recorded there
    are read on the device's clock all the same."""
    ctx = {"trace_profile": xplane.load(TRACE)}
    assert R.idle_named(ctx, r"^sched\.") is None
    share = R.idle_named(ctx, r"^PjitFunction\(_train_step\)$")
    assert 0.0 <= share <= 100.0
    gaps = R.idle_gaps(xplane.device_ops(ctx["trace_profile"])
                       ["/device:TPU:0"])
    assert sum(e - s for s, e in gaps) / 1e9 == pytest.approx(
        0.801974431 - 0.80190495, rel=1e-6
    )


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_data_beside_the_old_ones(name):
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert len(entry["workloads"]) == 1
    cell = harness.load_cell(entry["workloads"][0])
    loaded = next(m for m in cell["per_layer"] if m["name"] == name)
    assert callable(harness.resolve(loaded["reader"]))
    assert loaded["reads"]
    reported = {m["name"] for m in cell["end_to_end"]}
    assert entry["moves"] in reported
    # appended: the accepted entries still come first, in their order
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW
