"""The readers a learned sparse attention adds (``readers_sparse.py``)
on hand-made contexts: the roofline share of the selected-rows decode
kernel takes a FLOOR of the lanes that decoded — the fewest any run of
as many consecutive steps as the trace holds had — so it can only read
low; and, like every reader, None where there is nothing to read."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import family_keye_vl2 as fam
import harness
import readers_sparse as R
import xplane

CFG = harness.load_json(
    os.path.join(BENCH, "configs", "keye-vl-2.0-30b-a3b.json")
)
LAYERS = CFG["num_hidden_layers"]
ARGS = dict(pattern="^sparse_paged_decode", bytes_fn="sparse_decode_bytes")


def step(start, lanes):
    return {"phase": "serve_step", "start": start, "end": start + 0.01,
            "pid": 1, "inc": 0, "labels": {"lanes_decode": lanes}}


def profile(calls, each_ns):
    events = [
        NS(name="%sparse_paged_decode.13 = bf16[16,32,128] custom-call()",
           start_ns=1e3 + i * 2 * each_ns, duration_ns=each_ns)
        for i in range(calls)
    ] + [NS(name="%fusion.332 = s32[32768] fusion()", start_ns=0.0,
            duration_ns=5e5)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name=xplane.OP_LINE, events=events)])
    ])


def ctx_of(prof, spans, config=CFG):
    return {
        "trace_profile": prof,
        "spans": spans,
        "window": (10.0, 20.0),
        "device_report": {"device_kind": "TPU v5 lite"},
        "cell": {
            "config": config,
            "peaks": harness.load_json(os.path.join(BENCH, "peaks.json")),
        },
    }


@pytest.mark.parametrize("lanes, steps, want", [
    ([16, 16, 15, 14, 16, 16], 2, 14.5),
    ([16, 16, 15, 14, 16, 16], 1, 14),
    ([16, 16, 15, 14, 16, 16], 6, 15.5),
    ([4, 2, 6], 9, 4.0),  # fewer records than steps: all of them
    ([3], 0, 3.0),  # a trace of less than one whole step
])
def test_fewest_lanes_is_the_lowest_mean_of_a_run(lanes, steps, want):
    assert R.fewest_lanes(lanes, steps) == want


def test_the_share_counts_the_fewest_lanes_a_traced_run_can_have_had():
    # two decode steps in the trace (2 x LAYERS calls), each call at
    # twice the time 14.5 lanes' bytes take at the peak: 50 %
    at_peak_ns = fam.sparse_decode_bytes(CFG, 14.5) / 819e9 * 1e9
    spans = [
        step(9.0, 1),  # before the window
        step(11.0, 16), step(11.1, 16), step(11.2, 15), step(11.3, 14),
        step(11.35, 0),  # a step that only prefilled
        step(11.4, 16), step(11.5, 16),
        step(21.0, 1),  # after
    ]
    got = R.kernel_bandwidth_share_lanes(
        ctx_of(profile(2 * LAYERS, 2 * at_peak_ns), spans), **ARGS
    )
    assert got == pytest.approx(50.0, rel=1e-6)
    # the records out of order read the same
    got = R.kernel_bandwidth_share_lanes(
        ctx_of(profile(2 * LAYERS, 2 * at_peak_ns), spans[::-1]), **ARGS
    )
    assert got == pytest.approx(50.0, rel=1e-6)


def test_the_share_never_reads_above_what_every_lane_would_give():
    spans = [step(11.0 + i / 100, 13 + i % 4) for i in range(50)]
    at_peak_ns = fam.sparse_decode_bytes(CFG, 16) / 819e9 * 1e9
    got = R.kernel_bandwidth_share_lanes(
        ctx_of(profile(10 * LAYERS, at_peak_ns), spans), **ARGS
    )
    assert 13 / 16 * 100 <= got < 100


@pytest.mark.parametrize("case", ["no trace", "no kernel", "no records",
                                  "no byte function"])
def test_the_share_is_silent_where_there_is_nothing_to_read(case):
    spans = [step(11.0, 16)]
    prof = profile(LAYERS, 1e5)
    ctx = {
        "no trace": ctx_of(None, spans),
        "no kernel": ctx_of(profile(0, 1e5), spans),
        "no records": ctx_of(prof, []),
        "no byte function": ctx_of(prof, spans, {"family": "family_dense"}),
    }[case]
    assert R.kernel_bandwidth_share_lanes(ctx, **ARGS) is None
