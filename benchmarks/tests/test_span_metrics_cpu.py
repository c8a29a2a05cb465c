"""The tiny CPU cells report the metrics that read the program's own
spans and labels (ISSUE 24) in a ``--trace 1`` run.

The data root is made here: ``tests/tiny``'s ``BENCHMARK.json`` with the
nine entries that the repository's ``BENCHMARK.json`` appended, each
pointed at the tiny cell of its kind — so the tiny tree's own file, and
the tests that pin what it reports, stay as they are.  Times are CPU
times: they show that the program writes what the readers read, and are
never a device metric.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness

TINY = os.path.join(BENCH, "tests", "tiny")
TINY_CELL = {
    "mistral7b-train-snap50": "tiny-train",
    "deepseek7b-rollout-c16": "tiny-rollout",
}
SPAN_METRICS = {
    "tiny-train": {"ckpt.pull_ms", "ckpt.drain_ms", "step.span_ms"},
    "tiny-rollout": {
        "sched.admit_ms", "sched.dispatch_ms", "sched.commit_ms",
        "sched.occupancy_pct",
    },
}


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        tiny = json.load(f)
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        full = json.load(f)
    have = {m["name"] for m in tiny["per_layer"]}
    files = os.path.join(TINY, tiny["paths"][0])
    tiny["paths"] = [files]  # absolute: the data stay where they are
    for c in tiny["configs"]:
        c["file"] = os.path.join(TINY, c["file"])
    for m in full["per_layer"]:
        if m["name"] not in have:
            tiny["per_layer"].append(
                dict(m, workloads=[TINY_CELL[w] for w in m["workloads"]])
            )
    root = tmp_path_factory.mktemp("tiny_spans")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(tiny, f)
    return str(root)


def run(workload, data_root, seconds=4.0):
    line = harness.run_cell(
        workload, 2**31 + 79, seconds, True, expect_platform="cpu",
        data_root=data_root,
    )
    assert line["correct"], line["notes"]
    return {k: v["value"] for k, v in line["metrics"].items()}


def test_train_kind_reports_its_spans(data_root):
    got = run("tiny-train", data_root)
    assert SPAN_METRICS["tiny-train"] <= set(got), sorted(got)
    # the span the trainer writes and the benchmark's own callback time
    # the same instants: where the step's loss reaches the host
    assert got["step.span_ms"] == pytest.approx(got["step.ms"], rel=0.25)
    assert 0 < got["ckpt.pull_ms"]
    assert 0 < got["ckpt.drain_ms"]
    # no device plane on the CPU: the trace readers stay silent
    assert "kernel.flash_share_pct" not in got


def test_rollout_kind_reports_the_scheduler_partition(data_root):
    got = run("tiny-rollout", data_root)
    assert SPAN_METRICS["tiny-rollout"] <= set(got), sorted(got)
    assert all(got[k] >= 0 for k in SPAN_METRICS["tiny-rollout"])
    assert 0 < got["sched.occupancy_pct"] <= 100
    # a host partition of a step is no longer than the step
    assert (
        got["sched.admit_ms"] + got["sched.dispatch_ms"]
        + got["sched.commit_ms"]
    ) < got["sched.decode_step_ms"] * 2
    for name in ("device.serve_idle_named_pct", "kernel.paged_share_pct"):
        assert name not in got
