"""The Olmo-Hybrid family rehearsed on the CPU: a tiny configuration of
``family_olmo_hybrid`` runs the ``rollout`` runner end to end — the
serving engine, a replica, the scheduler with the per-lane state of the
linear layers and the pages of the full layer in its pool, the Pallas
kernels in interpret mode — with ``correct`` decided against the
family's plain reference; the byte function and the new metric files
are checked beside it.

The data root is made here (as ``test_falcon_h1_cpu.py`` makes its own):
the tiny tree's traffic and peak files, one configuration file of this
family beside them, and a ``BENCHMARK.json`` with one cell and the
per-layer metrics the repository's file lists for the cell.  Times are
CPU times, never a device metric.
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
CELL = "olmo-hybrid-tiny-rollout"
REAL_CELL = "olmo-hybrid-rollout-c64"
NEW_METRICS = (
    "attn.linear_share_pct", "kernel.gdn_share_pct",
    "kernel.gdn_update_bw_pct", "kernel.gdn_prefill_share_pct",
    "kv.state_share_pct",
)


def _bench():
    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    full = _bench()
    files = os.path.join(TINY, "data")
    bench = {
        "command": full["command"],
        "paths": [files],
        "run_seconds": 3,
        "configs": [{
            "name": "tiny-olmo-hybrid", "source": "none", "reduced": [],
            "file": os.path.join(files, "configs", "tiny-olmo-hybrid.json"),
            "why": "rehearsal of the linear-attention hybrid family",
        }],
        "workloads": [{
            "name": CELL, "config": "tiny-olmo-hybrid",
            "traffic": "tiny-rollout", "chips": 1,
            "why": "the rollout kind on a model whose state lives in "
                   "some layers and whose pages in the others",
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
    assert len(bench["per_layer"]) == 6
    root = tmp_path_factory.mktemp("tiny_olmo_hybrid")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


@pytest.mark.parametrize("trace,names", [
    (0, {"rollout_tokens_per_s", "setup_s"}),
    # no device plane on the CPU: the four trace readers stay silent,
    # the counter of the scheduler's records does not
    (1, {"sched.decode_step_ms", "kv.state_share_pct"}),
])
def test_rollout_kind_on_the_linear_attention_family(data_root, trace, names):
    line = harness.run_cell(
        CELL, 2**31 + 149, 4.0, trace, expect_platform="cpu",
        data_root=data_root,
    )
    assert line["correct"], line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 5
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == names
    assert all(v > 0 for v in got.values())
    if trace:
        # the slabs of 3 linear layers against the blocks live over 1
        # full layer: a share, and neither none nor all of the cache
        assert 0 < got["kv.state_share_pct"] < 100
    assert any("float32 reference" in n for n in line["notes"])


def test_train_parts_names_the_missing_path():
    import family_olmo_hybrid

    with pytest.raises(harness.CellFailed, match="no training path"):
        family_olmo_hybrid.train_parts({}, 16)


def test_model_kwargs_fail_by_name_without_the_model(monkeypatch):
    """What the parent of the PR that added the model does with the
    cell: it fails at once, by the family's own message, before any
    replica is started."""
    import importlib.util

    import family_olmo_hybrid

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(
        harness.CellFailed, match="no dlrover_tpu.models.olmo_hybrid"
    ):
        family_olmo_hybrid.model_kwargs({}, 16)


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")
    )
    published = dict(cfg, **cfg["published"])
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    assert len(published["layer_types"]) == 32
    # depth only: three whole periods of the published pattern
    assert cfg["num_hidden_layers"] == 12 == len(cfg["layer_types"])
    assert cfg["layer_types"] == published["layer_types"][:12]
    assert cfg["layer_types"].count("full_attention") == 3
    for key in (
        "norm_placement", "qk_norm", "positions", "gdn_form",
        "state_dtype", "state_layout", "weights", "depth_effect",
        "depth_choice",
    ):
        assert cfg["assumed"][key], key


def test_counts_of_the_published_layers():
    import family_olmo_hybrid as fam

    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")
    )
    # 9 x 215.57 M + 3 x 185.81 M + 2 x 385.35 M
    assert round(fam.total_params(cfg) / 1e6, 1) == 3268.3
    linear, small = fam._layer_params(cfg, "linear_attention")
    assert round((linear + small) / 1e6, 2) == 215.57
    full, small = fam._layer_params(cfg, "full_attention")
    assert round((full + small) / 1e6, 2) == 185.81


def test_gdn_update_bytes_counts_state_twice_and_operands_once():
    import family_olmo_hybrid as fam

    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")
    )
    one = fam.gdn_update_bytes(cfg, 1)
    # a lane: 30 heads x 96 x 192 float32 read and written, and q, k
    # (30 x 96 each), v, o (30 x 192 each), alpha, beta (30 each) once
    assert one == 2 * 30 * 96 * 192 * 4 + (2 * 2880 + 2 * 5760 + 60) * 4
    assert fam.gdn_update_bytes(cfg, 64) == 64 * one
    assert round(64 * 2 * 30 * 96 * 192 * 4 / 1e6, 1) == 283.1


def test_the_cell_is_listed_where_its_metrics_are_read():
    bench = _bench()
    lists = {
        m["name"]: m.get("workloads", []) for m in bench["per_layer"]
    }
    for name in NEW_METRICS:
        assert lists[name] == [REAL_CELL], name
        spec = harness.load_json(
            os.path.join(BENCH, "layer_metrics", name + ".json")
        )
        assert callable(harness.resolve(spec["reader"])), name
    for name in (
        "attn.full_share_pct", "kernel.full_decode_share_pct",
        "sched.prefill_chunk_ms", "serve.unscoped_share_pct",
    ):
        assert lists[name][-1] == REAL_CELL, name
    # the model's decode kernel is the full layers', by its name
    assert REAL_CELL not in lists["kernel.paged_share_pct"]
    cell = harness.load_cell(REAL_CELL)
    t = cell["traffic"]
    assert (t["max_slots"], t["num_blocks"], t["prefill_chunk"]) == (
        64, 6848, 256
    )
    assert t["prompt_len"]["max"] + t["max_new"]["max"] <= t["max_seq_len"]


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


def test_the_roofline_metric_reads_the_kernel_by_its_name():
    import family_olmo_hybrid as fam

    cell = harness.load_cell(REAL_CELL)
    spec = harness.load_json(
        os.path.join(BENCH, "layer_metrics", "kernel.gdn_update_bw_pct.json")
    )
    call = fam.gdn_update_bytes(cell["config"], 64)
    at_peak_ns = call / 819e9 * 1e9
    events = [
        # two calls at 1.25 x the least time the chip could take: 80 %
        _Event("%gdn_decode_update.7 = (f32[64,15,384]) custom-call()",
               1000.0, 1.25 * at_peak_ns),
        _Event("%gdn_decode_update.7 = (f32[64,15,384]) custom-call()",
               9e6, 1.25 * at_peak_ns),
        _Event("%fusion.3 = bf16[64,3840] fusion()", 5e6, 1e6),
    ]
    ctx = {
        "trace_profile": _Profile([
            _Plane("/device:TPU:0", [_Line(xplane.OP_LINE, events)]),
        ]),
        "device_report": {"device_kind": "TPU v5 lite"},
        "cell": cell,
    }
    got = readers_roofline.kernel_bandwidth_share(ctx, **spec["args"])
    assert got == pytest.approx(80.0, rel=1e-6)
    ctx["trace_profile"] = None
    assert readers_roofline.kernel_bandwidth_share(
        ctx, **spec["args"]
    ) is None
