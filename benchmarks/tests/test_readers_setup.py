"""The readers of a run's set-up (``readers_setup.py``) on hand-made
records: each gives the number a reading by hand gives — nested and
overlapping records counted once, the window's edge kept — and None,
never an error, where the program wrote no ``startup`` stage and no
``compile`` record (the parent of the PR that added them)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import harness
import readers
import readers_setup as R

with open(os.path.join(harness.REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
ROLLOUT = [
    w["name"] for w in BENCHMARK["workloads"] if "rollout" in w["traffic"]
]
TRAIN = [w["name"] for w in BENCHMARK["workloads"] if w["name"] not in ROLLOUT]
#: the metrics of ISSUE 55, as its table has them: name -> (moves, cells)
SETUP = {
    **{
        name: ("setup_s", TRAIN + ROLLOUT)
        for name in (
            "setup.process_s", "setup.backend_init_s", "setup.weights_s",
            "setup.trace_lower_s", "setup.compile_s", "setup.cache_load_s",
            "setup.cache_misses", "setup.stepping_s", "setup.unnamed_s",
        )
    },
    "serve.compile_in_window_s": ("rollout_tokens_per_s", ROLLOUT),
    "step.compile_in_window_s": ("train_tokens_per_s", TRAIN),
    "engine.reply_ms": ("rollout_tokens_per_s", ROLLOUT),
}

WINDOW = (100.0, 151.0)
SETUP_S = 60.0  # the run started at 40.0


def span(phase, start, dur, inc=0, pid=7, **labels):
    return {"phase": phase, "start": start, "end": start + dur,
            "pid": pid, "rank": 0, "inc": inc, "labels": labels}


def stage(name, start, dur, **kw):
    return span("startup", start, dur, stage=name, **kw)


def compiled(stage_, start, dur, program="_decode", **kw):
    return span("compile", start, dur, program=program, stage=stage_, **kw)


def ctx_of(spans):
    return {"spans": spans, "window": WINDOW,
            "end_to_end": {"setup_s": SETUP_S}}


#: a replica's start: stages one after the other, a warm-up whose first
#: step holds a program's three records, a recompile inside the window
REPLICA = [
    stage("process", 41.0, 2.0),
    stage("imports", 43.0, 3.0),
    stage("backend_init", 46.0, 10.0, device_kind="TPU v5 lite"),
    stage("factory", 56.0, 0.5),
    stage("pool", 56.5, 1.5, pool_bytes=1 << 30),
    stage("weights", 58.0, 4.0, bytes=7 << 30),
    span("weight_cast", 62.0, 2.0, bytes_in=1, bytes_out=1, leaves_cast=1),
    compiled("trace", 62.1, 0.2, program="_cast_and_fuse"),
    compiled("lower", 62.3, 0.1, program="_cast_and_fuse"),
    compiled("backend_compile", 62.4, 0.5, program="_cast_and_fuse",
             cache="hit"),
    span("device_report", 64.0, 0.0, platform="tpu"),
    # the warm-up's first iteration: 20 s, 12 of them the program's way
    # to an executable, an inner function's trace nested in the lowering
    span("serve_step", 65.0, 20.0, tokens=8, new_tokens=0),
    span("prefill", 65.0, 19.0, tokens=8),  # inside the step: counted once
    compiled("trace", 65.5, 3.0),
    compiled("lower", 68.5, 4.0),
    compiled("trace", 69.0, 1.0, program="_kernel"),  # nested
    compiled("backend_compile", 72.5, 5.0, cache="miss"),
    span("serve_step", 85.0, 10.0, tokens=0, new_tokens=16),
    compiled("trace", 85.5, 0.5, program="_sample"),
    compiled("lower", 86.0, 0.25, program="_sample"),
    compiled("backend_compile", 86.25, 0.75, program="_sample",
             cache="none"),
    span("serve_step", 99.5, 1.0, tokens=0, new_tokens=16),  # straddles
    # in the window
    span("serve_step", 120.0, 3.0, tokens=0, new_tokens=16),
    compiled("trace", 120.5, 0.5, program="_decode"),
    compiled("backend_compile", 121.0, 1.5, program="_decode", cache="miss"),
    span("reply", 123.0, 0.004, req_id=1, per_token_bytes=0),
    span("reply", 124.0, 0.090, req_id=2, per_token_bytes=1 << 20),
    span("reply", 125.0, 0.100, req_id=3, per_token_bytes=1 << 20),
    span("reply", 90.0, 9.5, req_id=0, per_token_bytes=0),  # warm-up's
]


@pytest.mark.parametrize(
    "reader, args, expected",
    [
        (R.stage_seconds, dict(stages=["process", "imports", "factory"]),
         5.5),
        (R.stage_seconds, dict(stages=["backend_init"]), 10.0),
        (R.stage_seconds,
         dict(stages=["weights", "pool", "accelerate", "state"],
              phases=["weight_cast"]), 7.5),
        # 62.1-62.4, 65.5-72.5 (the nested trace once), 85.5-86.25
        (R.compile_seconds, dict(stages=["trace", "lower"]), 8.05),
        (R.compile_seconds,
         dict(stages=["backend_compile"], cache=["miss", "none"]), 5.75),
        (R.compile_seconds, dict(stages=["backend_compile"], cache=["hit"]),
         0.5),
        (R.compile_count, dict(stages=["backend_compile"], cache=["miss"]),
         1),
        # 65-85 and 85-95 (the step that straddles the edge is the
        # window's) less 65.5-77.5 and 85.5-87.0
        (R.stepping_seconds, dict(phases=["serve_step", "step"],
                                  stages=["first_step"]), 16.5),
        # 40-41 and 64-65: what nothing covers (the warm-up's long
        # reply fills 95-99.5, the straddling step counts up to the edge)
        (R.unnamed_seconds, dict(), 1.0 + 1.0),
        (R.compile_seconds,
         dict(stages=["trace", "lower", "backend_compile"], where="window"),
         2.0),
        (readers.span_median, dict(phase="reply", scale=1000.0), 90.0),
    ],
    ids=lambda v: getattr(v, "__name__", None),
)
def test_a_replicas_split_reads_as_by_hand(reader, args, expected):
    assert reader(ctx_of(REPLICA), **args) == pytest.approx(expected)


#: a training worker: no factory, pool or weights; the first step is a
#: stage, steps follow; the agent's and a restarted worker's records are
#: not the first incarnation's
WORKER = [
    span("rendezvous", 40.5, 0.5, pid=3, inc=0),  # the agent's
    stage("process", 42.0, 1.0),
    stage("imports", 43.0, 2.0),
    stage("backend_init", 45.0, 12.0),
    stage("accelerate", 58.0, 1.0, params=7 << 30),
    stage("state", 59.0, 6.0),
    stage("first_step", 66.0, 14.0),
    compiled("trace", 66.0, 2.0, program="_train_step"),
    compiled("lower", 68.0, 1.0, program="_train_step"),
    compiled("backend_compile", 69.0, 9.0, program="_train_step",
             cache="hit"),
    span("step", 80.0, 1.0, step=2),
    span("step", 81.0, 1.0, step=3),
    span("snapshot_pull", 81.5, 0.25, step=3),  # inside a step: once
    span("step", 99.0, 2.0, step=21),  # ends in the window
    stage("process", 130.0, 1.0, inc=1),
    stage("backend_init", 131.0, 9.0, inc=1),
]


@pytest.mark.parametrize(
    "reader, args, expected",
    [
        (R.stage_seconds, dict(stages=["process", "imports", "factory"]),
         3.0),
        (R.stage_seconds, dict(stages=["backend_init"]), 12.0),
        (R.stage_seconds,
         dict(stages=["weights", "pool", "accelerate", "state"],
              phases=["weight_cast"]), 7.0),
        (R.compile_seconds, dict(stages=["backend_compile"], cache=["hit"]),
         9.0),
        (R.compile_count, dict(stages=["backend_compile"], cache=["miss"]),
         0),
        (R.stepping_seconds, dict(phases=["serve_step", "step"],
                                  stages=["first_step"]), 2.0 + 2.0),
        # 40-40.5, 41-42, 57-58, 65-66, 82-99
        (R.unnamed_seconds, dict(), 0.5 + 1.0 + 1.0 + 1.0 + 17.0),
        (R.compile_seconds,
         dict(stages=["trace", "lower", "backend_compile"], where="window"),
         0.0),
    ],
    ids=lambda v: getattr(v, "__name__", None),
)
def test_a_workers_split_reads_as_by_hand(reader, args, expected):
    assert reader(ctx_of(WORKER), **args) == pytest.approx(expected)


#: what the parent of the PR writes: spans, none of the new ones (a
#: hand-made ``compile`` span without a stage is not the meter's)
PARENT = [
    span("weight_cast", 62.0, 2.0, bytes_in=1, bytes_out=1, leaves_cast=1),
    span("serve_step", 65.0, 20.0, tokens=8, new_tokens=0),
    span("compile", 70.0, 5.0),
    span("serve_step", 120.0, 3.0, tokens=0, new_tokens=16),
]


@pytest.mark.parametrize("records", [PARENT, []], ids=["parent", "empty"])
@pytest.mark.parametrize("name", sorted(SETUP))
def test_records_without_the_phases_read_nothing(name, records):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        metric = json.load(f)
    reader = harness.resolve(metric["reader"])
    assert reader(ctx_of(list(records)), **metric.get("args", {})) is None


@pytest.mark.parametrize("name", sorted(SETUP))
def test_the_metric_is_listed_as_the_issue_lists_it(name):
    """Appended after what was there, each with an explicit list of
    cells, the layer's file beside the others."""
    moves, cells = SETUP[name]
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    entry = BENCHMARK["per_layer"][names.index(name)]
    assert names.index(name) >= len(names) - len(SETUP)
    assert entry["moves"] == moves and entry["better"] == "lower"
    assert sorted(entry["workloads"]) == sorted(cells)
    assert entry["source"] == (
        "program_counter" if name == "setup.cache_misses" else "program_span"
    )
    for cell in cells:
        listed = [
            m["name"] for m in harness.load_cell(cell)["per_layer"]
        ]
        assert name in listed
