"""``family_trinity``'s cell rehearsed end to end on the CPU: the tiny
configuration and traffic file of ``benchmarks/tests/tiny/data/``
appended to a copy of the tiny ``BENCHMARK.json`` (entries only, as the
real cell is appended to the real one), through the same
``harness.run_cell`` the command line calls — the engine's replica over
a pool of two kinds of blocks, ``sample.npz``'s ``served_experts`` and
the float32 reference forced onto the served routing and given the same
share, in a child process — once as it is, ``correct`` true by both
numbers, and once with ONE PLANTED FAULT on the served side (a released
window block read: ``tests/tiny/data/family_trinity_stale.py``),
``correct`` false.

Numbers read here are counts and differences on the CPU, never a device
metric.
"""

import json
import os

import pytest
import rehearsal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

TINY = os.path.join(BENCH, "tests", "tiny")
CELLS = {"trinity-rollout": "tiny-trinity",
         "trinity-rollout-stale": "tiny-trinity-stale"}
pytestmark = pytest.mark.heavy


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    """The tiny benchmark with two configurations and two cells
    appended, and the cells' names on the lists of the metrics they
    report."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = [os.path.join(TINY, "data")]
    for c in bench["configs"]:
        c["file"] = os.path.join(TINY, c["file"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    for cell, config in CELLS.items():
        bench["configs"].append(dict(
            bench["configs"][0], name=config,
            file=os.path.join(TINY, "data", "configs", config + ".json"),
        ))
        bench["workloads"].append(dict(
            name=cell, config=config, traffic="tiny-rollout-trinity",
            chips=1, why="rehearsal",
        ))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "tiny-rollout" in m.get("workloads", []):
                m["workloads"].append(cell)
    for m in real["per_layer"]:  # those that read labels, not a trace
        if m["name"].startswith(("moe.", "kv.")):
            bench["per_layer"].append(dict(m, workloads=list(CELLS)))
    root = tmp_path_factory.mktemp("bm")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(root)


def run(data_root, cell, trace, seed):
    return rehearsal.run_cell(cell, seed, 4.0, trace, "cpu", data_root)


def test_the_cell_is_correct_by_both_numbers(data_root):
    line = run(data_root, "trinity-rollout", 0, 2**31 + 78)
    assert line["correct"], "\n".join(line["notes"])
    assert line["failed"] == 0 and line["attempted"] > 10
    assert set(line["metrics"]) == {"rollout_tokens_per_s", "setup_s"}
    compared = line["compared"]
    assert list(compared) == ["logprob_max_abs_diff", "routing_slack_max"]
    for c in compared.values():
        assert 0 <= c["value"] <= c["limit"]
    note = next(n for n in line["notes"] if "float32 reference" in n)
    assert "forced onto the served routing" in note


def test_a_traced_run_reads_the_share_and_the_window_from_the_records(
    data_root
):
    line = run(data_root, "trinity-rollout", 1, 2**31 + 79)
    assert line["correct"], "\n".join(line["notes"])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # 2 of 8 experts held, 2 a token: about a quarter of the assignments
    assert 5 < got["moe.local_rows_pct"] < 60
    assert 0 < got["moe.experts_hit_pct"] <= 100
    # prompts pass window + chunk: blocks come back behind live windows
    assert 0 < got["kv.window_released_pct"] < 100
    assert "rollout_tokens_per_s" not in got
    # a CPU trace has no device plane: the device readers find nothing
    assert not [k for k in got if k.startswith(("kernel.", "serve."))]


def test_a_released_window_block_that_is_read_turns_correct_false(data_root):
    line = run(data_root, "trinity-rollout-stale", 0, 2**31 + 78)
    assert not line["correct"]
    assert line["failed"] == 0  # every reply whole: only the numbers say it
    assert "FAILED: served logprobs match the reference" in line["notes"]
    compared = line["compared"]
    assert compared["logprob_max_abs_diff"]["value"] > (
        compared["logprob_max_abs_diff"]["limit"]
    )
