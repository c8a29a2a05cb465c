"""Every cell kind rehearsed end to end on the CPU at a tiny size, through
the same ``harness.run_cell`` the command line calls: ``expect_platform``
and the directory of data files are its arguments.  The data live under
``tests/tiny``: a ``BENCHMARK.json``, one configuration, four traffic
files — and nothing else, which is the proof that a new cell (the
four-chip cell of PERF.md's Open questions among them: ``chips: 4`` and
an fsdp=2 x tensor=2 mesh as data) needs data files only.

Times printed here are CPU times: they show that the arithmetic runs, and
are never a device metric.
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness

TINY = os.path.join(BENCH, "tests", "tiny")


def run(workload, trace, seconds=4.0, seed=2**31 + 77):
    return harness.run_cell(
        workload, seed, seconds, trace, expect_platform="cpu", data_root=TINY
    )


def values(line):
    return {k: v["value"] for k, v in line["metrics"].items()}


@pytest.mark.parametrize("workload,devices", [
    ("tiny-train", 1),
    ("tiny-train-fsdp2tp2", 4),  # four virtual devices, data files only
    ("rehearsal-train", 1),  # a family only its configuration names
])
def test_train_kind(workload, devices):
    line = run(workload, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    got = values(line)
    assert set(got) == {"train_tokens_per_s", "setup_s"}
    assert got["train_tokens_per_s"] > 0 and got["setup_s"] > 0
    assert any("float32 reference" in n for n in line["notes"])


def test_train_kind_traced_reports_its_layers_only():
    line = run("tiny-train", trace=1)
    assert line["correct"]
    got = values(line)
    # a CPU trace has no device plane: the device readers find nothing
    # and their metrics are left out, never guessed
    assert set(got) == {"step.ms", "step.mfu_pct", "ckpt.stall_ms"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_resume_kind():
    line = run("tiny-resume", trace=0, seconds=12.0)
    assert line["correct"], line["notes"]
    assert (line["attempted"], line["failed"]) == (1, 0)
    got = values(line)
    assert set(got) == {"resume_s", "setup_s"}
    assert 0 < got["resume_s"] < 12.0


def test_resume_kind_traced_partitions_the_resume():
    line = run("tiny-resume", trace=1, seconds=12.0)
    assert line["correct"], line["notes"]
    got = values(line)
    assert set(got) == {
        "agent.detect_s", "agent.restart_s", "ckpt.restore_s",
        "step.warm_compile_s",
    }
    assert all(v >= 0 for v in got.values())


def test_a_resume_outside_the_window_is_a_failed_attempt():
    line = run("tiny-resume", trace=0, seconds=1.0)
    assert not line["correct"]
    assert (line["attempted"], line["failed"]) == (1, 1)


@pytest.mark.parametrize("workload,trace,names", [
    ("tiny-rollout", 0, {"rollout_tokens_per_s", "setup_s"}),
    ("tiny-rollout", 1, {"engine.overhead_ms", "sched.decode_step_ms",
                         "sched.prefill_share_pct", "sched.tpot_p95_ms"}),
    # a family only its configuration names, weights of its own seeding:
    # served and checked through the family, or the logprobs disagree
    ("rehearsal-rollout", 0, {"rollout_tokens_per_s", "setup_s"}),
])
def test_rollout_kind(workload, trace, names):
    line = run(workload, trace=trace)
    assert line["correct"], line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 10
    assert set(values(line)) == names
    assert all(v > 0 for v in values(line).values())
    assert any("float32 reference" in n for n in line["notes"])
    # serving idle share: absent on the CPU, never estimated
    assert "busy_s" not in line["device"]


def test_a_configuration_without_a_family_fails_by_name(tmp_path):
    with pytest.raises(harness.CellFailed, match="'family'"):
        harness.family({"hidden_size": 64})
    # ... and so does its cell, before anything is started
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(TINY, "data", "configs", "tiny.json")) as f:
        config = json.load(f)
    del config["family"]
    bench["paths"] = [os.path.join(TINY, "data")]
    bench["configs"][0]["file"] = str(tmp_path / "nofamily.json")
    with open(bench["configs"][0]["file"], "w") as f:
        json.dump(config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with pytest.raises(harness.CellFailed, match="'family'"):
        harness.run_cell("tiny-train", 1, 1.0, 0, expect_platform="cpu",
                         data_root=str(tmp_path))


LOADS_EVERY_CELL = """
import json, sys
sys.path.insert(0, {bench!r})
import harness
for root in (harness.REPO, {tiny!r}):
    with open(root + "/BENCHMARK.json") as f:
        cells = json.load(f)["workloads"]
    for w in cells:
        harness.load_cell(w["name"], root)
assert "jax" not in sys.modules and "dlrover_tpu" not in sys.modules
"""


def test_loading_a_cell_imports_neither_jax_nor_the_program():
    """The harness's own process resolves a cell's family: seconds of
    imports there would be set-up time of every run."""
    proc = subprocess.run(
        [sys.executable, "-c", LOADS_EVERY_CELL.format(bench=BENCH, tiny=TINY)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_only_family_modules_name_a_model():
    """The harness, runners, worker, factory, checks and readers name no
    model and import nothing of the program's models: the family module
    a configuration file names does."""
    import glob
    import re

    named = re.compile(
        r"dlrover_tpu\.models|llama|import reference|from reference"
    )
    for path in sorted(glob.glob(os.path.join(BENCH, "*.py"))):
        name = os.path.basename(path)
        if name.startswith("family_") or name == "reference.py":
            continue
        with open(path) as f:
            hits = [ln for ln in f if named.search(ln)]
        assert not hits, (name, hits)


def test_the_command_line_refuses_to_measure_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral7b-train-snap50", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "measured on 'tpu' only" in proc.stderr


def test_every_cell_of_the_benchmark_loads_from_data():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])  # its family resolves
        assert cell["traffic"]["kind"] in harness.RUNNERS
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            # a per-layer metric moves an end-to-end metric of ITS cell
            assert m["moves"] in reported and m["moves"] in e2e
            assert callable(harness.resolve(m["reader"]))
