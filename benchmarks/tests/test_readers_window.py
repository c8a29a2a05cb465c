"""The readers that layers of two kinds of attention and a share of a
layer's experts add (``readers_window.py``) on hand-made contexts: each
roofline share takes a FLOOR of what the kernel had to move or compute
— the run of as many consecutive records as the trace holds calls for
that asks for least — so it can only read low; the experts' share in
decode counts the kernel's calls inside the decode program's runs
alone; and, like every reader, None where there is nothing to read.
``family_trinity``'s byte and operation functions are checked by hand
beside them."""

import os
import sys
from types import SimpleNamespace as NS

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import family_trinity as fam
import harness
import readers_window as R
import xplane

CFG = harness.load_json(
    os.path.join(BENCH, "configs", "trinity-large-preview.json")
)
ROW = 2 * 8 * 128 * 2  # a token's K and V, every KV head, bfloat16
QO = 2 * 48 * 128 * 2


def span(phase, start, **labels):
    return {"phase": phase, "start": start, "end": start + 0.01, "pid": 1,
            "inc": 0, "labels": labels}


def op(name, start_ns, dur_ns):
    return NS(name=f"%{name}.7 = bf16[16,48,128] custom-call()",
              start_ns=float(start_ns), duration_ns=float(dur_ns))


def profile(ops, modules=()):
    return NS(planes=[NS(name="/device:TPU:0", lines=[
        NS(name=xplane.OP_LINE, events=list(ops)),
        NS(name="XLA Modules", events=[
            NS(name=name, start_ns=float(a), duration_ns=float(b - a))
            for name, a, b in modules
        ]),
    ])])


def ctx_of(prof, spans, config=CFG):
    return {
        "trace_profile": prof, "spans": spans, "window": (10.0, 20.0),
        "device_report": {"device_kind": "TPU v5 lite"},
        "cell": {
            "config": config,
            "peaks": harness.load_json(os.path.join(BENCH, "peaks.json")),
        },
    }


def test_the_counts_are_the_cut():
    assert fam.layers_of_kind(CFG) == {"window": 4, "full": 1}
    assert fam.total_params(CFG) == 4_321_903_872  # 4321.9 M: 8.64 GB of bfloat16
    # 0.5 of an expert a token and expert layer under a flat router
    assert fam.matmul_params(CFG) == (
        5 * 3072 * 128 * (3 * 48 + 2 * 8) + 3 * 3072 * 12288
        + 4 * (3072 * 256 + 28311552 + 28311552 // 2) + 3072 * 25024
    )
    assert fam.expert_bytes(CFG) == 3 * 3072 * 3072 * 2
    assert fam.window_decode_bytes(CFG, 1000, 3) == 1000 * ROW + 12 * QO
    assert fam.full_decode_bytes(CFG, 1000, 3) == 1000 * ROW + 3 * QO
    # a chunk of 3 rows ending at position 6 (window far): 4 + 5 + 6
    # keys on every one of the 5 layers
    assert fam.prefill_attention_flops(CFG, 3, 6) == 4 * 48 * 128 * 5 * 15
    # 2048 rows ending at 10000: the full layer reads t + 1 keys a row,
    # a window layer 4096 (every row is past the window)
    full = sum(range(7953, 10001))
    assert fam.prefill_attention_flops(CFG, 2048, 10000) == (
        4 * 48 * 128 * (full + 4 * 2048 * 4096)
    )
    # a chunk that crosses the window's length
    assert fam.prefill_attention_flops(CFG, 2048, 5000) == 4 * 48 * 128 * (
        sum(range(2953, 5001))
        + 4 * (sum(range(2953, 4097)) + (5000 - 4096) * 4096)
    )


def test_a_decode_kernels_share_takes_the_cheapest_run_of_steps():
    # 8 calls of the window kernel: 2 consecutive steps of 4 layers
    prof = profile(op("paged_window_decode", i * 2e6, 1e6) for i in range(8))
    rows = [40000, 30000, 10000, 20000, 50000]
    spans = [
        span("serve_step", 11 + i, kv_rows_window=r, lanes_decode=16)
        for i, r in enumerate(rows)
    ] + [span("serve_step", 17, kv_rows_window=0, lanes_decode=0)]
    got = R.decode_bandwidth_share(
        ctx_of(prof, spans), "^paged_window_decode", "window_decode_bytes",
        "kv_rows_window", "window",
    )
    moved = (10000 + 20000) * ROW + 2 * 16 * 4 * QO
    assert got == pytest.approx(100 * moved / 8e-3 / 819e9)
    full = R.decode_bandwidth_share(
        ctx_of(prof, spans), "^paged_full_decode", "full_decode_bytes",
        "kv_rows_full", "full",
    )
    assert full is None  # no such operation, no such label


def test_the_chunk_kernels_share_counts_real_rows_and_seen_keys():
    prof = profile(
        op("paged_prefill_window" if i % 5 else "paged_prefill_full",
           i * 2e6, 1e6)
        for i in range(10)
    )
    chunks = [(2048, 2048), (2048, 4096), (500, 4596), (2048, 20000)]
    spans = [
        span("prefill", 11 + i, rows=r, kv_len=k)
        for i, (r, k) in enumerate(chunks)
    ]
    got = R.prefill_mxu_share(
        ctx_of(prof, spans), "^paged_prefill", "prefill_attention_flops"
    )
    cheapest = min(
        fam.prefill_attention_flops(CFG, *a)
        + fam.prefill_attention_flops(CFG, *b)
        for a, b in zip(chunks, chunks[1:])
    )
    assert got == pytest.approx(100 * cheapest / 10e-3 / 197e12)


def test_the_experts_share_in_decode_leaves_the_chunks_calls_out():
    ops = [op("moe_expert_ffn", 100 + i * 10, 5) for i in range(8)]  # decode
    ops += [op("moe_expert_ffn", 2000 + i * 100, 80) for i in range(4)]
    prof = profile(ops, [
        ("jit__decode_lp(123)", 90, 140), ("jit__decode_lp(123)", 140, 200),
        ("jit__prefill(77)", 1900, 2500),
    ])
    spans = [
        span("serve_step", 11 + i, experts_hit=h, experts=32)
        for i, h in enumerate([2.0, 1.25, 0.5, 3.0])
    ]
    got = R.expert_bandwidth_share_decode(
        ctx_of(prof, spans), "^moe_expert_ffn", "decode", "expert_bytes"
    )
    moved = (1.25 + 0.5) * 4 * fam.expert_bytes(CFG)
    assert got == pytest.approx(100 * moved / (8 * 5e-9) / 819e9)


@pytest.mark.parametrize("reader,args", [
    (R.decode_bandwidth_share,
     ("^paged_window_decode", "window_decode_bytes", "kv_rows_window",
      "window")),
    (R.prefill_mxu_share, ("^paged_prefill", "prefill_attention_flops")),
    (R.expert_bandwidth_share_decode,
     ("^moe_expert_ffn", "decode", "expert_bytes")),
])
def test_nothing_to_read_reads_none(reader, args):
    labelled = [
        span("serve_step", 12, kv_rows_window=5, lanes_decode=1,
             experts_hit=1.0),
        span("prefill", 12, rows=4, kv_len=9),
    ]
    empty = profile([op("fusion", 0, 10)])
    named = profile([op("paged_window_decode", 0, 10),
                     op("paged_prefill_full", 20, 10),
                     op("moe_expert_ffn", 40, 10)],
                    [("jit__decode_lp(1)", 0, 100)])
    dense = harness.load_json(os.path.join(BENCH, "configs",
                                           "deepseek-llm-7b.json"))
    for ctx in (
        ctx_of(None, labelled),  # no trace
        ctx_of(empty, labelled),  # the parent: no such kernel
        ctx_of(named, []),  # the parent: no such label
        ctx_of(named, labelled, dense),  # a family without the function
    ):
        assert reader(ctx, *args) is None
