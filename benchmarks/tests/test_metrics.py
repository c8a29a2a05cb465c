"""The benchmark's arithmetic on synthetic rows and spans (CPU, ms).

    python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flops
import metrics as M
import xplane


def train_rows(step_s=0.2, stall_s=3.0, snap=50, steps=400, t0=1000.0):
    """Step rows of a job whose snapshot-bearing steps each stall."""
    rows, t = [], t0
    for s in range(1, steps + 1):
        t += step_s + (stall_s if s % snap == 0 else 0.0)
        rows.append({"step": s, "t": t, "loss": 10.4, "inc": 0})
    return rows


def test_whole_cycle_rate_ignores_partial_cycles():
    rows = train_rows()
    cycle_s = 50 * 0.2 + 3.0
    want = 50 * 4096 / cycle_s
    # windows that hold 3 snapshots and 3-and-a-bit cycles give one rate
    for start, length in ((1005.0, 40.0), (1009.0, 47.0), (1001.0, 51.0)):
        got = M.whole_cycle_tokens_per_s(
            rows, (start, start + length), 50, 4096
        )
        assert got == pytest.approx(want, rel=1e-9)


def test_whole_cycle_rate_needs_two_snapshots():
    rows = train_rows()
    assert M.whole_cycle_tokens_per_s(rows, (1000.0, 1012.0), 50, 4096) is None


def test_plain_step_and_stall():
    rows = train_rows(step_s=0.18, stall_s=3.3)
    window = (1000.0, 1060.0)
    assert M.plain_step_s(rows, window, 50) == pytest.approx(0.18)
    assert M.snapshot_stall_s(rows, window, 50) == pytest.approx(3.3)


def test_other_incarnations_do_not_enter_the_gaps():
    rows = train_rows(steps=120) + [
        {"step": 101, "t": 5000.0, "loss": 10.4, "inc": 1}
    ]
    assert 101 in M.step_gaps(rows, inc=0)
    assert M.step_gaps(rows, inc=1) == {}


def test_percentile_matches_linear_interpolation():
    xs = [float(i) for i in range(1, 101)]
    assert M.percentile(xs, 95) == pytest.approx(95.05)
    assert M.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        M.percentile([], 95)


def test_pair_spans_and_resume_partition():
    agent, w0, w1 = 10, 20, 30
    events = [
        {"name": "checkpoint_save", "ph": "X", "wall": 90.0, "dur": 1.0,
         "mono": 1.0, "pid": w0, "inc": 0, "labels": {"step": 100}},
        # the agent's restart: B at 106, E at 130 (monotonic 16 -> 40)
        {"name": "restart", "ph": "B", "wall": 106.0, "mono": 16.0,
         "pid": agent, "sid": 1, "inc": 1},
        {"name": "checkpoint_save", "ph": "X", "wall": 106.5, "dur": 7.0,
         "mono": 24.0, "pid": agent, "inc": 1},
        {"name": "restart", "ph": "E", "wall": 130.0, "mono": 40.0,
         "pid": agent, "sid": 1, "inc": 1},
        {"name": "rendezvous", "ph": "i", "wall": 124.0, "mono": 34.0,
         "pid": w1, "inc": 1},
        {"name": "checkpoint_restore", "ph": "X", "wall": 131.0, "dur": 4.0,
         "mono": 45.0, "pid": w1, "inc": 1, "labels": {"step": 100}},
        # a span whose writer died is dropped, not guessed
        {"name": "step", "ph": "B", "wall": 99.0, "mono": 9.0, "pid": w0,
         "sid": 7, "inc": 0},
    ]
    spans = M.pair_spans(events)
    assert [s["phase"] for s in spans if s["phase"] == "step"] == []
    restart = M.named(spans, "restart")[0]
    assert restart["end"] - restart["start"] == pytest.approx(24.0)
    rows = [
        {"step": 100, "t": 99.0, "loss": 10.4, "inc": 0},
        {"step": 101, "t": 138.0, "loss": 10.4, "inc": 1},
        {"step": 102, "t": 138.2, "loss": 10.4, "inc": 1},
    ]
    parts = M.resume_partition(100.0, spans, rows, agent_pid=agent)
    assert parts["resume_s"] == pytest.approx(38.0)
    assert parts["detect_s"] == pytest.approx(6.0)
    assert parts["restart_s"] == pytest.approx(18.0)
    assert parts["restore_s"] == pytest.approx(4.0)
    assert parts["first_step_s"] == pytest.approx(3.0)
    # the named parts and what lies between them add up to the whole
    between = 131.0 - 124.0
    assert (
        parts["detect_s"] + parts["restart_s"] + between
        + parts["restore_s"] + parts["first_step_s"]
    ) == pytest.approx(parts["resume_s"])


def test_resume_partition_without_a_resume():
    parts = M.resume_partition(100.0, [], [], agent_pid=1)
    assert parts == {}


def test_rollout_rate_credits_straddlers_by_overlap():
    reqs = [
        {"submit": 0.0, "done": 10.0, "new_tokens": 100},   # inside
        {"submit": -5.0, "done": 5.0, "new_tokens": 100},   # half before
        {"submit": 45.0, "done": 55.0, "new_tokens": 100},  # half after
        {"submit": 60.0, "done": 70.0, "new_tokens": 100},  # outside
    ]
    assert M.rollout_tokens_per_s(reqs, (0.0, 50.0)) == pytest.approx(4.0)
    assert len(M.completed_in(reqs, (0.0, 50.0))) == 2
    assert M.tpot_ms(reqs, (0.0, 50.0)) == pytest.approx([100.0, 100.0])


def test_flops_of_the_two_configurations():
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "mistral-7b-v0.1.json")) as f:
        mistral = json.load(f)
    with open(os.path.join(here, "configs", "deepseek-llm-7b.json")) as f:
        deepseek = json.load(f)
    assert flops.layer_matmul_params(mistral) == 218_103_808
    assert flops.total_params(mistral) == 698_372_096  # auto_accelerate's count
    assert flops.layer_matmul_params(deepseek) == 202_375_168
    per_token = flops.train_flops_per_token(mistral, 2048)
    assert per_token == pytest.approx(6 * 567_279_616 + 3 * 2 * 16_777_216)
    # a step that took exactly the required FLOPs / peak is 100 %
    step_s = per_token * 4096 / 197e12
    assert flops.mfu_pct(per_token, 4096, step_s, 197e12) == pytest.approx(100)
    # the same step on four chips' peak is a quarter of it
    assert flops.mfu_pct(per_token, 4096, step_s, 197e12, chips=4) == (
        pytest.approx(25)
    )
    with pytest.raises(LookupError):
        flops.peak_for({"TPU v5 lite": {}}, "TPU v9")


def test_union_of_intervals():
    assert xplane.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)


# ---------------------------------------------------------------- traffic


def _traffic():
    import json
    import os

    import harness

    with open(os.path.join(harness.BENCH, "traffic", "rollout-c16.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [13, 2**31 + 77])
def test_every_pass_of_the_stream_covers_the_distribution(seed):
    import rollout_cell as R

    t = _traffic()
    n = t["strata"]
    stream = R.RequestStream(t, seed, vocab_size=1000)
    reqs = [stream.take() for _ in range(20 * n)]  # never runs out
    assert [r["idx"] for r in reqs] == list(range(20 * n))
    edges = [
        R.stratum_length(t["max_new"], max(i / n, 1e-9)) for i in range(n)
    ] + [t["max_new"]["max"]]
    for k in range(0, len(reqs), n):
        answers = sorted(r["max_new"] for r in reqs[k:k + n])
        # one answer length from each slice of the clipped lognormal
        assert all(lo <= a <= hi for a, lo, hi in
                   zip(answers, edges, edges[1:]))
    assert all(
        t["prompt_len"]["min"] <= r["prompt"].size <= t["prompt_len"]["max"]
        and r["prompt"].size + r["max_new"] <= t["max_seq_len"]
        for r in reqs
    )


def test_seeds_draw_their_own_lengths_and_the_same_amount_of_work():
    import rollout_cell as R

    t, n = _traffic(), 160
    runs = []
    for seed in (13, 14, 2**31 + 77):
        stream = R.RequestStream(t, seed, vocab_size=1000)
        runs.append([stream.take() for _ in range(n)])
    shapes = [[(r["prompt"].size, r["max_new"]) for r in run] for run in runs]
    assert shapes[0] != shapes[1] != shapes[2]
    for total in (
        [sum(p for p, _ in s) for s in shapes],
        [sum(a for _, a in s) for s in shapes],
    ):
        assert max(total) / min(total) < 1.02
    again = R.RequestStream(t, 13, vocab_size=1000)
    assert all(
        (a["prompt"] == b["prompt"]).all() and a["max_new"] == b["max_new"]
        for a, b in zip(runs[0], (again.take() for _ in range(n)))
    )


def test_a_run_removes_its_own_shm_segments_and_no_others(tmp_path):
    import os

    import harness

    box = harness.Sandbox(str(tmp_path / "run"))
    token = f"bm-{os.getpid()}"
    box.own_shm(token)
    mine = f"/dev/shm/dlrover_tpu_ckpt_{token}_0"
    other = f"/dev/shm/dlrover_tpu_ckpt_someone-else-{os.getpid()}_0"
    try:
        for path in (mine, other):  # both appear while the run is on
            with open(path, "w"):
                pass
        box.close()
        assert not os.path.exists(mine) and os.path.exists(other)
    finally:
        for path in (mine, other):
            if os.path.exists(path):
                os.unlink(path)
