"""``family_deepseek_v32``'s cell rehearsed end to end on the CPU: the
tiny configuration and traffic file of ``benchmarks/tests/tiny/data/``
appended to a copy of the tiny ``BENCHMARK.json`` (entries only, as the
real cell is appended to the real one), through the same
``harness.run_cell`` the command line calls — the engine's replica over
a pool without ``k`` and ``v``, ``sample.npz``'s ``served_experts`` and
``served_selection``, and the float32 reference (multi-head form)
forced onto the served side's experts and picked keys and given the same
share, in a child process.  Once as it is, traced: ``correct`` by both
numbers, and the metrics that read the program's labels.  And once each
with ONE PLANTED FAULT on the served side
(``tests/tiny/data/family_deepseek_v32_faulty.py``): the indexer
bypassed, which only the selection's slack can see, and the weights
rounded through int8, the precision below the configuration's —
``correct`` false.

Numbers read here are counts and differences on the CPU, never a device
metric.
"""

import json
import os
import shutil

import pytest
import rehearsal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")

TINY = os.path.join(BENCH, "tests", "tiny")
CELL = "deepseek-v32-rollout"
CELLS = {CELL: "tiny-deepseek-v32",
         CELL + "-indexer": "tiny-deepseek-v32-indexer",
         CELL + "-int8": "tiny-deepseek-v32-int8"}
TRAFFIC = "tiny-rollout-deepseek-v32"
#: the int8 cell's traffic: the same file with 16 requests sampled for
#: the reference where the file has 4.  WHICH requests a run samples
#: follows which complete inside its 4 s wall window, and the planted
#: int8 fault moves the selection's slack over its limit in about one
#: request of three: on a FIXED list of 48 requests (the stream's 4-51,
#: no window) the parent's program (PR 53) and PR 54's serve the same
#: tokens, picks and experts in all 48 and read the same slack request
#: by request, 0.0000-0.2705, over 0.1 in 16 — so a sample of 4 shows it
#: in 4 runs of 5 whatever the program (9 of 11 and 7 of 12 runs read
#: so), and a sample of 16 misses it once in ~700 (``PERF.md`` section
#: 6, PR 54).  Limits, traffic and window are the file's own.
SAMPLED = {CELL + "-int8": 16}
pytestmark = pytest.mark.heavy


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    """The tiny benchmark with three configurations and three cells
    appended, and the cells' names on the lists of the metrics they
    report."""
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    root = tmp_path_factory.mktemp("bm")
    # a copy of the tiny tree's data files, and a traffic file more for
    # each cell of ``SAMPLED`` beside them
    data = os.path.join(root, "data")
    shutil.copytree(os.path.join(TINY, "data"), data)
    with open(os.path.join(data, "traffic", TRAFFIC + ".json")) as f:
        traffic = json.load(f)
    bench["paths"] = [data]
    for c in bench["configs"]:
        c["file"] = os.path.join(TINY, c["file"])
    for cell, config in CELLS.items():
        bench["configs"].append(dict(
            bench["configs"][0], name=config,
            file=os.path.join(TINY, "data", "configs", config + ".json"),
        ))
        name = TRAFFIC
        if cell in SAMPLED:
            name = f"{TRAFFIC}-sample{SAMPLED[cell]}"
            with open(os.path.join(data, "traffic", name + ".json"), "w") as f:
                json.dump(dict(traffic, reference_sample=SAMPLED[cell]), f)
        bench["workloads"].append(dict(
            name=cell, config=config, traffic=name, chips=1, why="rehearsal",
        ))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "tiny-rollout" in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    for m in real["per_layer"]:  # those that read labels, not a trace
        if m["name"].startswith(("moe.", "kv.selected", "engine.reply_c")):
            bench["per_layer"].append(dict(m, workloads=list(CELLS)))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(root)


def run(data_root, cell, trace):
    return rehearsal.run_cell(cell, 2**31 + 78, 4.0, trace, "cpu", data_root)


def test_the_cell_is_correct_and_reads_its_labels(data_root):
    line = run(data_root, CELL, 1)
    assert line["correct"], "\n".join(line["notes"])
    assert line["failed"] == 0 and line["attempted"] > 10
    compared = line["compared"]
    assert list(compared) == ["logprob_max_abs_diff", "routing_slack_max"]
    for c in compared.values():
        assert 0 <= c["value"] <= c["limit"]
    note = next(n for n in line["notes"] if "float32 reference" in n)
    assert "forced onto the served routing" in note
    # the reference follows the served side's picked keys too: its own
    # float32 selection read 0.2-0.9 here, one flipped key of 32
    assert compared["logprob_max_abs_diff"]["value"] < 0.06
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # prompts of 8-100 against an index_topk of 32: most lane-steps
    # read a part of what they have cached
    assert 10 < got["kv.selected_share_pct"] < 100
    # 2 of 8 experts held, 2 a token from ONE group of 4: about a
    # quarter of the assignments, and some held expert idle a step
    assert 5 < got["moe.local_rows_pct"] < 60
    assert 0 < got["moe.experts_hit_pct"] <= 100
    assert "rollout_tokens_per_s" not in got
    # the rows of every reply in the window left in one copy of their
    # own length (``copied_bytes`` over ``per_token_bytes``)
    assert got["engine.reply_copied_pct"] == 100
    # a CPU trace has no device plane: the device readers find nothing
    assert not [k for k in got if k.startswith(("kernel.", "serve."))]


@pytest.mark.parametrize("fault,seen_by", [
    ("indexer", "routing_slack_max"),
    ("int8", "routing_slack_max"),
])
def test_a_planted_fault_turns_correct_false(data_root, fault, seen_by):
    """The indexer bypassed: the reference is forced onto the newest 32
    keys too, so the logprobs agree, and the picks lie far below the
    keys left out under the reference's own index scores.  Weights
    through int8, the precision below the configuration's: the served
    index scores move, and with them the picks — over 16 sampled
    requests (``SAMPLED``), since the fault shows in about one of
    three."""
    line = run(data_root, f"{CELL}-{fault}", 0)
    assert not line["correct"]
    assert line["failed"] == 0  # every reply whole: only the numbers say it
    assert "FAILED: served logprobs match the reference" in line["notes"]
    compared = line["compared"]
    assert compared[seen_by]["value"] > compared[seen_by]["limit"]
    if fault == "indexer":
        assert compared["logprob_max_abs_diff"]["value"] < (
            compared["logprob_max_abs_diff"]["limit"]
        )
