"""The Kimi Linear family rehearsed on the CPU: a tiny configuration of
``family_kimi_linear`` runs the ``rollout`` runner end to end — the
serving engine, a replica, the scheduler with the per-lane state of the
KDA layers and the latent pages of the MLA layer in its pool, the Pallas
kernels in interpret mode — with ``correct`` decided against the
family's plain reference FORCED onto the served experts; the
configuration file, the counts and the metric files the real cell is
listed on are checked beside it.

The data root is made here (as ``test_olmo_hybrid_cpu.py`` makes its
own).  Times are CPU times, never a device metric.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness
import readers_hybrid
import readers_roofline
import xplane

TINY = os.path.join(BENCH, "tests", "tiny")
CELL = "kimi-linear-tiny-rollout"
REAL_CELL = "kimi-linear-rollout-c128-reason8k"
NEW_METRICS = (
    "kernel.kda_share_pct", "kernel.kda_update_bw_pct",
    "kernel.kda_prefill_share_pct", "kernel.mla_full_decode_roofline_pct",
    "kernel.mla_causal_prefill_mxu_pct", "attn.kda_share_pct",
    "kv.slab_share_pct", "serve.kda_unscoped_share_pct",
    "sched.kda_prefill_chunk_ms",
)
COUNTERS = (
    "sched.decode_step_ms", "kv.slab_share_pct", "moe.experts_hit_pct",
    "moe.local_rows_pct", "sched.kda_prefill_chunk_ms",
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
            "name": "tiny-kimi-linear", "source": "none", "reduced": [],
            "file": os.path.join(files, "configs", "tiny-kimi-linear.json"),
            "why": "rehearsal of the KDA + latent-attention family",
        }],
        "workloads": [{
            "name": CELL, "config": "tiny-kimi-linear",
            "traffic": "tiny-rollout-kimi-linear", "chips": 1,
            "why": "the rollout kind on a model whose state lives in "
                   "some layers and whose latent rows in the others",
        }],
        "end_to_end": [
            dict(m, workloads=[CELL]) for m in full["end_to_end"]
            if m["name"] in ("rollout_tokens_per_s", "setup_s")
        ],
        "per_layer": [
            dict(m, workloads=[CELL]) for m in full["per_layer"]
            if m["name"] in set(NEW_METRICS + COUNTERS)
        ],
    }
    assert len(bench["per_layer"]) == len(set(NEW_METRICS + COUNTERS))
    root = tmp_path_factory.mktemp("tiny_kimi_linear")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


def test_rollout_kind_on_the_kda_and_latent_family(data_root):
    """ONE seed, traced: no device plane on the CPU, so the trace
    readers stay silent and the counters of the scheduler's records do
    not."""
    line = harness.run_cell(
        CELL, 2**31 + 157, 4.0, 1, expect_platform="cpu",
        data_root=data_root,
    )
    assert line["correct"], line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 3
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(COUNTERS)
    assert all(v > 0 for v in got.values())
    # the slabs of 3 KDA layers against the blocks live over 1 MLA
    # layer: a share, and neither none nor all of the cache
    assert 0 < got["kv.slab_share_pct"] < 100
    assert set(line["compared"]) == {
        "logprob_max_abs_diff", "routing_slack_max"
    }
    assert any("forced onto the served routing" in n for n in line["notes"])


def test_train_parts_names_the_missing_path():
    import family_kimi_linear

    with pytest.raises(harness.CellFailed, match="no training path"):
        family_kimi_linear.train_parts({}, 16)


def test_model_kwargs_fail_by_name_without_the_model(monkeypatch):
    """What the parent of the PR that added the model does with the
    cell's configuration: it fails at once, by the family's own message,
    before any replica is started."""
    import importlib.util

    import family_kimi_linear

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(
        harness.CellFailed, match="no dlrover_tpu.models.kimi_linear"
    ):
        family_kimi_linear.model_kwargs({}, 16)


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "kimi-linear-48b-a3b.json")
    )
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    published = dict(cfg, **cfg["published"])
    la, pla = cfg["linear_attn_config"], published["linear_attn_config"]
    assert published["num_hidden_layers"] == 27
    assert len(pla["kda_layers"]) == 20 and len(pla["full_attn_layers"]) == 7
    # depth: layers 1-12 as published, three whole periods
    assert cfg["num_hidden_layers"] == 12
    assert la["kda_layers"] == [i for i in pla["kda_layers"] if i <= 12]
    assert la["full_attn_layers"] == [4, 8, 12]
    assert {k: v for k, v in la.items() if not k.endswith("_layers")} == {
        k: v for k, v in pla.items() if not k.endswith("_layers")
    }
    # the share: 16 of 256 experts, an eighth of the vocabulary
    dep = cfg["deployment"]
    assert cfg["num_experts"] * dep["chips_sharing_a_layer"] == 256
    assert cfg["vocab_size"] * dep["vocabulary_shares"] == 163840
    assert dep["share"] == 0
    # no width differs from the published one
    for key, value in (
        ("hidden_size", 2304), ("intermediate_size", 9216),
        ("moe_intermediate_size", 1024), ("kv_lora_rank", 512),
        ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
        ("v_head_dim", 128), ("num_experts_per_token", 8),
        ("num_attention_heads", 32),
    ):
        assert cfg[key] == value, key
    assert (la["num_heads"], la["head_dim"]) == (32, 128)
    for key in (
        "layers", "block", "kda_form", "mla_form", "router", "cached_rows",
        "state_dtype", "weights", "served_arrays", "routing_slack",
        "depth_effect", "depth_choice",
    ):
        assert cfg["assumed"][key], key


def test_the_cell_is_listed_where_its_metrics_are_read():
    bench = _bench()
    lists = {
        m["name"]: m.get("workloads", []) for m in bench["per_layer"]
    }
    # membership only: the next cell is appended to these lists too
    for name in NEW_METRICS:
        assert REAL_CELL in lists[name], name
        spec = harness.load_json(
            os.path.join(BENCH, "layer_metrics", name + ".json")
        )
        assert callable(harness.resolve(spec["reader"])), name
    for name in (
        "attn.latent_share_pct", "kernel.mla_decode_share_pct",
        "kernel.mla_prefill_share_pct", "kernel.moe_expert_bw_pct",
        "moe.local_rows_pct", "sched.decode_step_ms", "setup.compile_s",
    ):
        assert REAL_CELL in lists[name], name
    # V's roofline readers count a trace's steps over every layer: this
    # model's are read through readers_hybrid.of_kind instead
    assert REAL_CELL not in lists["kernel.mla_decode_roofline_pct"]
    assert REAL_CELL not in lists["kernel.mla_prefill_mxu_pct"]
    cell = harness.load_cell(REAL_CELL)
    t = cell["traffic"]
    assert (t["max_slots"], t["num_blocks"], t["prefill_chunk"]) == (
        128, 72832, 512
    )
    assert t["clients"] == t["strata"] == t["warmup"]["requests"] == 128
    assert t["prompt_len"]["max"] + t["max_new"]["max"] <= t["max_seq_len"]
    assert t["routing_slack_max"] > 0 and t["logprob_tol"] > 0


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
    import family_kimi_linear as fam

    cell = harness.load_cell(REAL_CELL)
    spec = harness.load_json(
        os.path.join(BENCH, "layer_metrics", "kernel.kda_update_bw_pct.json")
    )
    call = fam.kda_update_bytes(cell["config"], 128)
    at_peak_ns = call / 819e9 * 1e9
    events = [
        # two calls at 1.25 x the least time the chip could take: 80 %
        _Event("%kda_decode_update.7 = (f32[128,32,128]) custom-call()",
               1000.0, 1.25 * at_peak_ns),
        _Event("%kda_decode_update.7 = (f32[128,32,128]) custom-call()",
               9e6, 1.25 * at_peak_ns),
        _Event("%fusion.3 = bf16[128,2304] fusion()", 5e6, 1e6),
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


def test_a_reader_of_one_kind_counts_that_kinds_layers():
    """``readers_hybrid.of_kind`` hands the accepted reader the run with
    ``num_hidden_layers`` read as the MLA layers' 3; a family without
    ``layers_of_kind`` (a parent's) reads nothing."""
    cell = harness.load_cell(REAL_CELL)
    seen = {}

    def reader(ctx, tag):
        seen[tag] = ctx["cell"]["config"]["num_hidden_layers"]
        return 7.0

    harness.readers_probe = reader  # resolvable as "harness:readers_probe"
    try:
        got = readers_hybrid.of_kind(
            {"cell": cell}, "harness:readers_probe", "mla", tag="mla"
        )
        assert got == 7.0 and seen == {"mla": 3}
        assert cell["config"]["num_hidden_layers"] == 12  # untouched
        assert readers_hybrid.of_kind(
            {"cell": cell}, "harness:readers_probe", "window", tag="none"
        ) is None
    finally:
        del harness.readers_probe
