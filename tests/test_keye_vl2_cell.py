"""``family_keye_vl2``'s cell rehearsed end to end on the CPU: the tiny
configuration and traffic file of ``benchmarks/tests/tiny/data/``
appended to a copy of the tiny ``BENCHMARK.json`` (entries only, as the
real cell is appended to the real one), through the same
``harness.run_cell`` the command line calls — the engine's replica, the
result ring with its per-position ring beside it, ``sample.npz``'s
``served_experts``, and the float32 reference forced onto the served
routing in a child process.

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
CELL = "keye-rollout"
pytestmark = pytest.mark.heavy


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    """The tiny benchmark with one configuration and one cell appended,
    and the cell's name on the lists of the metrics it reports."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = [os.path.join(TINY, "data")]
    for c in bench["configs"]:
        c["file"] = os.path.join(TINY, c["file"])
    bench["configs"].append(dict(
        bench["configs"][0], name="tiny-keye-vl2",
        file=os.path.join(TINY, "data", "configs", "tiny-keye-vl2.json"),
    ))
    bench["workloads"].append(dict(
        name=CELL, config="tiny-keye-vl2",
        traffic="tiny-rollout-keye-vl2", chips=1, why="rehearsal",
    ))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-rollout" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    for m in real["per_layer"]:  # those that read labels, not a trace
        if m["name"].startswith(("moe.", "kv.selected")):
            bench["per_layer"].append(dict(m, workloads=[CELL]))
    root = tmp_path_factory.mktemp("bm")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(root)


def run(data_root, trace, seed):
    return rehearsal.run_cell(CELL, seed, 4.0, trace, "cpu", data_root)


def test_the_cell_is_correct_by_both_numbers(data_root):
    line = run(data_root, 0, 2**31 + 78)
    assert line["correct"], "\n".join(line["notes"])
    assert line["failed"] == 0 and line["attempted"] > 10
    assert set(line["metrics"]) == {"rollout_tokens_per_s", "setup_s"}
    compared = line["compared"]
    assert list(compared) == ["logprob_max_abs_diff", "routing_slack_max"]
    for c in compared.values():
        assert 0 <= c["value"] <= c["limit"]
    note = next(n for n in line["notes"] if "float32 reference" in n)
    assert "forced onto the served routing" in note


def test_a_traced_run_reads_the_expert_load_from_the_records(data_root):
    line = run(data_root, 1, 2**31 + 79)
    assert line["correct"], "\n".join(line["notes"])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # 8 experts, 2 a token, at most 4 lanes: some experts idle a step
    assert 0 < got["moe.experts_hit_pct"] <= 100
    assert got["moe.rows_max_over_mean"] >= 1
    # an indexer's ``sel_rows`` over its ``cached_rows``: the longer
    # lanes read a part of what they have cached
    assert 10 < got["kv.selected_share_pct"] <= 100
    assert "rollout_tokens_per_s" not in got
