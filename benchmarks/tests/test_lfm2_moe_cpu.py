"""The LFM2 mixture-of-experts family rehearsed on the CPU: a tiny
configuration of ``family_lfm2_moe`` runs the ``rollout`` runner end to
end — the serving engine, a replica, the scheduler with the conv tails of
three layers and the pages of two (rows of two KV heads) in its pool,
the Pallas kernels in interpret mode — with ``correct`` decided against
the family's plain reference FORCED onto the served experts; the
configuration file, the counts, the byte function and the metric files
the real cell is listed on are checked beside it.

The data root is made here (as ``test_kimi_linear_cpu.py`` makes its
own).  Times are CPU times, never a device metric.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness
import readers_experts
import readers_window
import xplane

TINY = os.path.join(BENCH, "tests", "tiny")
CELL = "lfm2-moe-tiny-rollout"
REAL_CELL = "lfm2-24b-rollout-c256-reason4k"
NEW_METRICS = (
    "attn.conv_share_pct", "attn.full64_share_pct",
    "kernel.kv64_decode_share_pct", "kernel.kv64_decode_bw_pct",
    "kernel.kv64_prefill_share_pct", "moe.rows_per_hit_expert",
    "kv.tail_share_pct", "sched.conv_prefill_chunk_ms",
)
COUNTERS = (
    "sched.decode_step_ms", "kv.tail_share_pct", "moe.experts_hit_pct",
    "moe.local_rows_pct", "moe.rows_per_hit_expert",
    "sched.conv_prefill_chunk_ms",
)
SEEDS = (2**31 + 211,)


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
            "name": "tiny-lfm2-moe", "source": "none", "reduced": [],
            "file": os.path.join(files, "configs", "tiny-lfm2-moe.json"),
            "why": "rehearsal of the gated short-convolution family",
        }],
        "workloads": [{
            "name": CELL, "config": "tiny-lfm2-moe",
            "traffic": "tiny-rollout-lfm2-moe", "chips": 1,
            "why": "the rollout kind on a model whose only lane state is "
                   "a conv tail and whose pages hold two heads a row",
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
    root = tmp_path_factory.mktemp("tiny_lfm2_moe")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(root)


@pytest.mark.parametrize("seed", SEEDS)
def test_rollout_kind_on_the_short_convolution_family(data_root, seed):
    """ONE seed, traced: no device plane on the CPU, so the trace readers
    stay silent and the counters of the scheduler's records do not."""
    line = harness.run_cell(
        CELL, seed, 4.0, 1, expect_platform="cpu", data_root=data_root,
    )
    assert line["correct"], line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 3
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == set(COUNTERS)
    assert all(v > 0 for v in got.values())
    # three layers' tails of 128 float32 a lane against the blocks live
    # over two layers: a share, and neither none nor all of the cache
    assert 0 < got["kv.tail_share_pct"] < 100
    # every expert is held: every assignment is computed here
    assert got["moe.local_rows_pct"] == 100.0
    assert 1.0 <= got["moe.rows_per_hit_expert"] <= 4.0  # 4 lanes x 2 / 8
    assert set(line["compared"]) == {
        "logprob_max_abs_diff", "routing_slack_max"
    }
    assert any("forced onto the served routing" in n for n in line["notes"])


def test_train_parts_names_the_missing_path():
    import family_lfm2_moe

    with pytest.raises(harness.CellFailed, match="no training path"):
        family_lfm2_moe.train_parts({}, 16)


def test_model_kwargs_fail_by_name_without_the_model(monkeypatch):
    """What the parent of the PR that added the model does with the
    cell's configuration: it fails at once, by the family's own message,
    before any replica is started."""
    import importlib.util

    import family_lfm2_moe

    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(
        harness.CellFailed, match="no dlrover_tpu.models.lfm2_moe"
    ):
        family_lfm2_moe.model_kwargs({}, 16)


def test_the_configuration_is_the_catalogs_but_for_the_cut():
    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")
    )
    assert sorted(cfg["published"]) == sorted(cfg["reduced"]) == [
        "layer_types", "num_hidden_layers"
    ]
    published = dict(cfg, **cfg["published"])
    period = ["conv", "conv", "full_attention", "conv"]
    assert published["num_hidden_layers"] == 40
    assert published["layer_types"] == period * 10
    # depth: layers 0-7 as published, two whole periods, both dense layers
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == published["layer_types"][:8] == period * 2
    assert cfg["num_dense_layers"] == 2 and cfg["num_expert_layers"] == 6
    dep = cfg["deployment"]
    assert (dep["chips_sharing_a_layer"], dep["share"]) == (1, 0)
    assert dep["vocabulary_shares"] == 1
    # no width differs from the published one
    for key, value in (
        ("hidden_size", 2048), ("intermediate_size", 11776),
        ("moe_intermediate_size", 1536), ("num_attention_heads", 32),
        ("num_key_value_heads", 8), ("num_experts", 64),
        ("num_experts_per_tok", 4), ("conv_L_cache", 3),
        ("vocab_size", 65536), ("norm_eps", 1e-5),
    ):
        assert cfg[key] == value, key
    assert cfg["rope_parameters"] == {
        "rope_theta": 1000000, "rope_type": "default"
    }
    for key in (
        "layers", "block", "tied_head", "conv_form", "attention_form",
        "router", "state_dtype", "cached_rows", "weights", "served_arrays",
        "routing_slack", "depth_effect", "depth_choice",
    ):
        assert cfg["assumed"][key], key


def test_the_counts_are_the_issues():
    import family_lfm2_moe as fam

    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")
    )
    assert fam.total_params(cfg) == 4_025_293_440
    assert fam.total_params(dict(cfg, **cfg["published"])) == 23_843_661_440
    assert fam.layers_of_kind(cfg) == {
        "conv": 6, "full": 2, "dense": 2, "expert": 6
    }
    assert sum(fam._conv_params(cfg)) == 16_783_360
    assert sum(fam._attn_params(cfg)) == 10_485_888
    assert fam.expert_bytes(cfg) == 2 * 9_437_184
    dep = cfg["deployment"]
    assert fam.cache_bytes_per_token_layer(cfg) == (
        dep["cache_bytes_per_token_layer"]
    ) == 2048
    assert fam.lane_state_bytes_per_layer(cfg) == (
        dep["lane_state_bytes_per_layer"]
    ) == 16384
    # a token's four active experts, the router, the operators, the head
    assert fam.matmul_params(cfg) == (
        6 * 4 * 2048 * 2048 + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
        + 2 * 3 * 2048 * 11776 + 6 * (2048 * 64 + 4 * 9_437_184)
        + 2048 * 65536
    )


def test_full_decode_bytes_counts_every_row_once_and_the_queries_at_64():
    import family_lfm2_moe as fam

    cfg = harness.load_json(
        os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")
    )
    # rows are summed over the 2 attention layers already; a lane's q
    # and o are 32 heads x 64 x 2 B each, a layer
    assert fam.full_decode_bytes(cfg, 1000, 0) == 1000 * 2048
    assert fam.full_decode_bytes(cfg, 0, 256) == 2 * 256 * 2 * 32 * 64 * 2
    assert fam.full_decode_bytes(cfg, 1000, 256) == (
        fam.full_decode_bytes(cfg, 1000, 0)
        + fam.full_decode_bytes(cfg, 0, 256)
    )


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
        "moe.experts_hit_pct", "moe.rows_max_over_mean",
        "moe.local_rows_pct", "kernel.moe_expert_share_pct",
        "kernel.moe_expert_bw_pct", "kernel.paged_share_pct",
        "sched.decode_step_ms", "serve.attn_share_pct", "setup.compile_s",
    ):
        assert REAL_CELL in lists[name], name
    # pinned by the accepted olmo-hybrid test: this cell reads them
    # through metrics of its own
    for name in (
        "attn.full_share_pct", "kv.state_share_pct",
        "sched.prefill_chunk_ms", "kernel.full_decode_share_pct",
    ):
        assert REAL_CELL not in lists[name], name
    cell = harness.load_cell(REAL_CELL)
    t = cell["traffic"]
    assert (t["max_slots"], t["num_blocks"], t["prefill_chunk"]) == (
        256, 72832, 512
    )
    assert t["clients"] == t["strata"] == t["warmup"]["requests"] == 256
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


def _ctx(cell, records, events):
    return {
        "trace_profile": _Profile([
            _Plane("/device:TPU:0", [_Line(xplane.OP_LINE, events)]),
        ]),
        "device_report": {"device_kind": "TPU v5 lite"},
        "cell": cell,
        "window": (0.0, 100.0),
        "events": [
            {"name": "serve_step", "ph": "X", "ts": 1.0 + i, "dur": 0.01,
             "labels": labels}
            for i, labels in enumerate(records)
        ],
    }


def test_the_roofline_metric_reads_the_kernel_by_its_name(monkeypatch):
    import family_lfm2_moe as fam

    cell = harness.load_cell(REAL_CELL)
    spec = harness.load_json(os.path.join(
        BENCH, "layer_metrics", "kernel.kv64_decode_bw_pct.json"
    ))
    records = [
        {"kv_rows_full": 2 * 256 * 1500, "lanes_decode": 256},
        {"kv_rows_full": 2 * 256 * 1501, "lanes_decode": 256},
    ]
    step = fam.full_decode_bytes(cell["config"], 2 * 256 * 1500, 256)
    at_peak_ns = step / 819e9 * 1e9
    # one step's two calls (a layer each) at 1.25 x the least time the
    # chip could take for the step that asks for fewest bytes: 80 %
    events = [
        _Event("%paged_full_decode.3 = (bf16[256,32,128]) custom-call()",
               1000.0, 0.625 * at_peak_ns),
        _Event("%paged_full_decode.4 = (bf16[256,32,128]) custom-call()",
               9e6, 0.625 * at_peak_ns),
        _Event("%fusion.3 = bf16[256,2048] fusion()", 5e6, 1e6),
    ]
    monkeypatch.setattr(
        readers_window, "_records",
        lambda ctx, phase, labels: [
            r for r in records if all(n in r for n in labels)
        ],
    )
    ctx = _ctx(cell, records, events)
    got = readers_window.decode_bandwidth_share(ctx, **spec["args"])
    assert got == pytest.approx(80.0, rel=1e-6)
    ctx["trace_profile"] = None
    assert readers_window.decode_bandwidth_share(ctx, **spec["args"]) is None


def test_rows_per_hit_expert_divides_by_the_expert_layers(monkeypatch):
    cell = harness.load_cell(REAL_CELL)
    records = [
        {"expert_rows_local": 6 * 1024, "experts_hit": 64.0},
        {"expert_rows_local": 6 * 1000, "experts_hit": 62.5},
        {"lanes_decode": 3},  # a parent's record: no labels
    ]
    monkeypatch.setattr(
        readers_experts, "_window_spans",
        lambda ctx, phase: [{"labels": r} for r in records],
    )
    got = readers_experts.rows_per_hit_expert({"cell": cell})
    assert got == pytest.approx((1024 + 1000) / (64 + 62.5))
    monkeypatch.setattr(
        readers_experts, "_window_spans",
        lambda ctx, phase: [{"labels": records[2]}],
    )
    assert readers_experts.rows_per_hit_expert({"cell": cell}) is None
