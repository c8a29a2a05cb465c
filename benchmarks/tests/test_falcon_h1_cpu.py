"""The hybrid family rehearsed on the CPU: a tiny configuration of
``family_falcon_h1`` runs the ``rollout`` runner end to end — the
serving engine, a replica, the scheduler with the per-lane state in its
pool, the Pallas kernels in interpret mode — with ``correct`` decided
against the family's plain reference, and the roofline reader is checked
on a recorded profile.

The data root is made here (as ``test_span_metrics_cpu.py`` makes its
own): the tiny tree's traffic and peak files, one configuration file
of this family beside them, and a ``BENCHMARK.json`` with one cell and
the three per-layer metrics the repository's file lists for the hybrid
cell.  Times are CPU times, never a device metric.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness
import readers_roofline
import xplane

TINY = os.path.join(BENCH, "tests", "tiny")
CELL = "falconh1-tiny-rollout"
NEW_METRICS = (
    "kernel.ssm_share_pct", "kernel.ssm_update_bw_pct",
    "sched.prefill_chunk_ms",
)


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        full = json.load(f)
    files = os.path.join(TINY, "data")
    bench = {
        "command": full["command"],
        "paths": [files],
        "run_seconds": 3,
        "configs": [{
            "name": "tiny-falcon-h1", "source": "none", "reduced": [],
            "file": os.path.join(files, "configs", "tiny-falcon-h1.json"),
            "why": "rehearsal of the hybrid family",
        }],
        "workloads": [{
            "name": CELL, "config": "tiny-falcon-h1",
            "traffic": "tiny-rollout", "chips": 1,
            "why": "the rollout kind on a model with per-lane state",
        }],
        "end_to_end": [
            dict(m, workloads=[CELL]) for m in full["end_to_end"]
            if m["name"] in ("rollout_tokens_per_s", "setup_s")
        ],
        "per_layer": [
            dict(m, workloads=[CELL]) for m in full["per_layer"]
            if m["name"] in NEW_METRICS + ("sched.decode_step_ms",)
        ],
    }
    assert len(bench["per_layer"]) == 4
    root = tmp_path_factory.mktemp("tiny_falcon_h1")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


@pytest.mark.parametrize("trace,names", [
    (0, {"rollout_tokens_per_s", "setup_s"}),
    # no device plane on the CPU: the two trace readers stay silent
    (1, {"sched.decode_step_ms", "sched.prefill_chunk_ms"}),
])
def test_rollout_kind_on_the_hybrid_family(data_root, trace, names):
    line = harness.run_cell(
        CELL, 2**31 + 83, 4.0, trace, expect_platform="cpu",
        data_root=data_root,
    )
    assert line["correct"], line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 5
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == names
    assert all(v > 0 for v in got.values())
    assert any("float32 reference" in n for n in line["notes"])


def test_train_parts_names_the_missing_path():
    import family_falcon_h1

    with pytest.raises(harness.CellFailed, match="no training path"):
        family_falcon_h1.train_parts({}, 16)


def test_ssm_update_bytes_counts_state_twice_and_operands_once():
    import family_falcon_h1 as fam

    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "falcon-h1-34b-instruct.json")
    )
    one = fam.ssm_update_bytes(cfg, 1)
    # a lane: 32 heads x 128 x 256 float32 read and written, and
    # x, y (32 x 128 each), B, C (2 x 256 each), dt (32) once
    assert one == 2 * 32 * 128 * 256 * 4 + (2 * 4096 + 2 * 512 + 32) * 4
    assert fam.ssm_update_bytes(cfg, 32) == 32 * one


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (
            name, start_ns, duration_ns,
        )


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _ctx(profile, lanes=32):
    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "falcon-h1-34b-instruct.json")
    )
    return {
        "trace_profile": profile,
        "device_report": {"device_kind": "TPU v5 lite"},
        "cell": {
            "config": cfg,
            "traffic": {"max_slots": lanes},
            "peaks": harness.load_json(os.path.join(BENCH, "peaks.json")),
        },
    }


ARGS = dict(
    pattern="^ssm_decode_update", bytes_fn="ssm_update_bytes",
    lanes_key="max_slots",
)


def test_roofline_reader_on_a_synthetic_device_plane():
    import family_falcon_h1 as fam

    cfg = _ctx(None)["cell"]["config"]
    call = fam.ssm_update_bytes(cfg, 32)
    at_peak_ns = call / 819e9 * 1e9
    events = [
        # two calls at twice the least time the chip could take: 50 %
        _Event("%ssm_decode_update.12 = (f32[32,32,128]) custom-call()",
               1000.0, 2 * at_peak_ns),
        _Event("%ssm_decode_update.12 = (f32[32,32,128]) custom-call()",
               9e6, 2 * at_peak_ns),
        _Event("%fusion.3 = bf16[32,5120] fusion()", 5e6, 1e6),
        _Event("%jit_ssm_decode_update_.12 = f32[1] get-tuple-element()",
               8e6, 1e3),  # not the kernel: the name does not start so
    ]
    profile = _Profile([
        _Plane("/device:TPU:0", [_Line(xplane.OP_LINE, events)]),
        _Plane("/host:CPU", [_Line("python", [
            _Event("ssm_decode_update", 0.0, 1e9),
        ])]),
    ])
    got = readers_roofline.kernel_bandwidth_share(_ctx(profile), **ARGS)
    assert got == pytest.approx(50.0, rel=1e-6)


def test_roofline_reader_is_silent_where_there_is_nothing_to_read():
    recorded = xplane.load(
        os.path.join(BENCH, "tests", "data", "train_5steps.xplane.pb.gz")
    )
    # a recorded trace of a program that has no such kernel (the
    # parent's), no trace at all, and a family without the function
    assert readers_roofline.kernel_bandwidth_share(
        _ctx(recorded), **ARGS
    ) is None
    assert readers_roofline.kernel_bandwidth_share(_ctx(None), **ARGS) is None
    dense = _ctx(recorded)
    dense["cell"]["config"] = {"family": "family_dense"}
    assert readers_roofline.kernel_bandwidth_share(
        dense, **dict(ARGS, pattern=".")
    ) is None
