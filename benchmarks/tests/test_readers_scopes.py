"""The readers of the device's time by scope, on a recorded trace and on
a hand-made one.

``data/train_scopes.xplane.pb.gz`` is the profiler's own file from cell
A's first traced run WITH the device scopes in the program (PR 38, a TPU
v5e): plain steps of the Mistral depth-2 train step, gzipped.  The
numbers asserted are what that run's result line carried.
``data/train_5steps.xplane.pb.gz`` (PR 23) is the same program before
the scopes: its operations carry paths and none of them a scope.
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import readers_scopes as S
import xplane

SCOPED = os.path.join(HERE, "data", "train_scopes.xplane.pb.gz")
UNSCOPED = os.path.join(HERE, "data", "train_5steps.xplane.pb.gz")


def _ctx(path):
    return {"trace_profile": xplane.load(path), "trace_path": path}


@pytest.fixture(scope="module")
def recorded():
    return _ctx(SCOPED)


def _pattern(metric):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        entry = json.load(f)
    assert entry["reader"] == "readers_scopes:scope_share"
    return entry["args"]["pattern"]


# ------------------------------------------------------ what a path says


@pytest.mark.parametrize("path,key", [
    ("jit(_train_step)/jvp(attn)/dot_general:", ("-", "attn", "fwd")),
    ("jit(_train_step)/transpose(jvp(mlp))/dot_general:",
     ("-", "mlp", "bwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/mul:", ("-", "mlp", "recompute")),
    ("jit(_train_step)/transpose(jvp(head_loss))/while/body/checkpoint/"
     "rematted_computation/dot_general:", ("-", "head_loss", "recompute")),
    ("jit(_train_step)/optimizer/add:", ("-", "optimizer", "fwd")),
    ("jit(_train_step)/transpose(jvp())/while/body/squeeze:",
     ("-", "-", "bwd")),
    ("", ("-", "-", "fwd")),
    # a function's name is no scope, whatever it is called
    ("jit(_prefill)/prefill/while/body/attn/dot_general:",
     ("prefill", "attn", "fwd")),
    ("jit(decode)/jit(sample)/add:", ("-", "-", "fwd")),
    ("jit(_decode_lp)/decode/sample/pjit(head)/argmax:",
     ("decode", "sample", "fwd")),
    ("jit(_decode_lp)/decode/while/body/dynamic_slice:",
     ("decode", "-", "fwd")),
    # one compiled program, two roles: the first names the operation's
    ("jit(_window)/verify/head/dot_general:", ("verify", "head", "fwd")),
    # the innermost part is the operation's own
    ("jit(f)/decode/attn/ssm/mul:", ("decode", "ssm", "fwd")),
    ("jit(f)/decode/attention/mul:", ("decode", "-", "fwd")),
])
def test_a_paths_role_part_and_direction(path, key):
    assert S.classify(path) == key


# --------------------------------------------------- the recorded trace


def test_the_files_own_record_of_each_operations_path():
    paths = S.op_paths(SCOPED)["/device:TPU:0"]
    assert len(paths) > 500
    name, path = next(
        (n, p) for n, p in paths.items() if n.startswith("%_flash_bwd")
    )
    assert path.startswith("jit(_train_step)/transpose(jvp(")
    assert S.classify(path)[1] == "attn"
    # an event's name in the profile is the key of its metadata's record
    profile = xplane.load(SCOPED)
    names = {
        ev.name for plane in profile.planes
        if plane.name == "/device:TPU:0"
        for line in plane.lines if line.name == xplane.OP_LINE
        for ev in line.events
    }
    assert names and names <= set(paths)


def test_the_recorded_steps_partition(recorded):
    parts = S.partition(recorded)
    assert parts["total"] == pytest.approx(100.0, abs=1e-6)
    assert parts["busy_s"] == pytest.approx(0.746181073, rel=1e-9)
    for margin in ("by_part", "by_role", "by_direction"):
        assert sum(parts[margin].values()) == pytest.approx(
            parts["total"], abs=1e-9
        )
    assert parts["by_role"] == {"-": pytest.approx(100.0)}
    assert parts["by_part"] == {
        "mlp": pytest.approx(37.5865, abs=1e-3),
        "optimizer": pytest.approx(19.0056, abs=1e-3),
        "head_loss": pytest.approx(17.5823, abs=1e-3),
        "attn": pytest.approx(16.9521, abs=1e-3),
        "-": pytest.approx(6.6833, abs=1e-3),
        "embed": pytest.approx(2.1901, abs=1e-3),
    }
    assert parts["by_direction"] == {
        "bwd": pytest.approx(44.9759, abs=1e-3),
        "fwd": pytest.approx(40.4263, abs=1e-3),
        "recompute": pytest.approx(14.5978, abs=1e-3),
    }
    # the optimizer has no backward, the embedding no replay
    assert "-/optimizer/bwd" not in parts["by_key"]
    assert "-/embed/recompute" not in parts["by_key"]
    assert S.partition(recorded) is parts  # once a run


def test_the_layer_scans_while_is_not_counted_twice(recorded):
    """The scans' ``while`` events enclose their bodies: by their whole
    durations the operations would sum to far more than the busy time."""
    ops = xplane.device_ops(recorded["trace_profile"])["/device:TPU:0"]
    whole = sum(end - start for start, end, _ in ops)
    busy = 1e9 * xplane.union_s(ops)
    assert whole > 1.3 * busy
    assert S.partition(recorded)["total"] <= 100.0 + 1e-6


@pytest.mark.parametrize("metric,value", [
    ("step.attn_share_pct", 16.9521),
    ("step.mlp_share_pct", 37.5865),
    ("step.head_loss_share_pct", 17.5823),
    ("step.optimizer_share_pct", 19.0056),
    ("step.recompute_share_pct", 14.5978),
    ("step.unscoped_share_pct", 6.6833),
    # a train step has no serving role: nothing under one
    ("serve.prefill_share_pct", 0),
    ("serve.prefill_head_share_pct", 0),
    ("serve.attn_share_pct", 0),
    ("serve.mlp_share_pct", 0),
    ("serve.head_share_pct", 0),
])
def test_each_metrics_cut_of_the_recorded_partition(recorded, metric, value):
    got = S.scope_share(recorded, _pattern(metric))
    assert got == pytest.approx(value, abs=1e-3)


def test_a_trace_without_scopes_reads_none():
    """The parent's trace: every operation has its path, none a scope."""
    ctx = _ctx(UNSCOPED)
    paths = S.op_paths(UNSCOPED)["/device:TPU:0"]
    assert sum(1 for p in paths.values() if p) > 300
    assert S.partition(ctx) is None
    assert S.scope_share(ctx, _pattern("step.mlp_share_pct")) is None
    assert S.scope_share(ctx, _pattern("step.unscoped_share_pct")) is None


def test_a_stray_scoped_operation_is_no_scoped_program(tmp_path):
    """What a parent's run showed on the chip (PR 38): its first-token
    sampler came out of the compile cache as the change had compiled it,
    ``prefill/sample`` on its paths, 0.05 % of busy time."""
    ops = {
        "%fusion.1 = x": ("jit(_decode_lp)/while/body/dot_general:", 0, 900),
        "%fusion.2 = y": ("jit(_sample_one_lp)/prefill/sample/reduce:",
                          900, 1000),
    }
    path = tmp_path / "stray.xplane.pb"
    path.write_bytes(_xspace(
        "/device:TPU:0", {name: p for name, (p, _, _) in ops.items()}
    ))
    line = _Line(xplane.OP_LINE, [
        _Event(name, start, end) for name, (_, start, end) in ops.items()
    ])
    ctx = {"trace_profile": _Profile([_Plane("/device:TPU:0", [line])]),
           "trace_path": str(path)}
    assert S.partition(ctx) is None
    assert S.scope_share(ctx, _pattern("serve.unscoped_share_pct")) is None


def test_no_trace_reads_none():
    assert S.scope_share({"trace_profile": None}, "/mlp/") is None
    assert S.scope_share({}, "/mlp/") is None


# ------------------------------------------------- a hand-made trace


def _varint(n):
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _xspace(plane_name, paths, by_ref=()):
    """An ``XSpace`` of one plane whose event metadata carry ``tf_op``:
    as a string, or (``by_ref``) as a reference to a stat metadata's
    name, the two forms the profiler writes."""
    stat_meta = {1: "tf_op", 2: "hlo_category"}
    events = []
    for i, (name, path) in enumerate(paths.items(), start=1):
        if name in by_ref:
            ref = 100 + i
            stat_meta[ref] = path
            stat = _field(1, 1) + _field(7, ref)
        else:
            stat = _field(1, 1) + _field(5, path)
        other = _field(1, 2) + _field(5, "fusion")
        events.append(_entry(i, (
            _field(1, i) + _field(2, name) + _field(5, other)
            + _field(5, stat)
        )))
    plane = _field(2, plane_name)
    for event in events:
        plane += _field(4, event)
    for key, name in stat_meta.items():
        plane += _field(5, _entry(key, _field(1, key) + _field(2, name)))
    return _field(1, plane)


class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


#: one decode step and one prefill chunk of a serving loop: name ->
#: (path, start, end) in ns.  Each program's ``while`` encloses its body.
SERVING = {
    "%fusion.1 = embed": ("jit(_decode_lp)/decode/embed/gather:", 0, 10),
    "%while.2 = scan": ("jit(_decode_lp)/decode/while:", 10, 210),
    "%fusion.3 = qkv": (
        "jit(_decode_lp)/decode/while/body/attn/dot_general:", 10, 60),
    "%paged_decode.4 = call": (
        "jit(_decode_lp)/decode/while/body/attn/pallas_call:", 60, 100),
    "%fusion.5 = mlp": (
        "jit(_decode_lp)/decode/while/body/mlp/dot_general:", 100, 200),
    "%fusion.6 = slice": (
        "jit(_decode_lp)/decode/while/body/dynamic_slice:", 200, 210),
    "%fusion.7 = head": ("jit(_decode_lp)/decode/head/dot_general:", 210, 290),
    "%fusion.8 = sample": ("jit(_decode_lp)/decode/sample/argmax:", 290, 300),
    # 100 ns idle, then the chunk
    "%while.9 = scan": ("jit(_prefill)/prefill/while:", 400, 540),
    "%fusion.10 = qkv": (
        "jit(_prefill)/prefill/while/body/attn/dot_general:", 400, 440),
    "%fusion.11 = mlp": (
        "jit(_prefill)/prefill/while/body/mlp/dot_general:", 440, 540),
    "%fusion.12 = head": ("jit(_prefill)/prefill/head/dot_general:", 540, 590),
    "%copy.13 = copy": ("", 590, 600),
}


@pytest.fixture
def serving(tmp_path):
    path = tmp_path / "serving.xplane.pb"
    path.write_bytes(_xspace(
        "/device:TPU:0", {name: p for name, (p, _, _) in SERVING.items()},
        by_ref={"%fusion.5 = mlp", "%fusion.12 = head"},
    ))
    ops = _Line(xplane.OP_LINE, [
        _Event(name, start, end) for name, (_, start, end) in SERVING.items()
    ])
    steps = _Line("Steps", [_Event("0", 0, 600)])  # not the operations'
    host = _Plane("/host:CPU", [_Line(xplane.OP_LINE, [_Event("x", 0, 9)])])
    return {
        "trace_profile": _Profile([_Plane("/device:TPU:0", [steps, ops]),
                                   host]),
        "trace_path": str(path),
    }


def test_both_forms_of_the_stat_are_read(serving):
    paths = S.op_paths(serving["trace_path"])["/device:TPU:0"]
    assert paths == {name: p for name, (p, _, _) in SERVING.items()}


def test_a_serving_loops_partition(serving):
    parts = S.partition(serving)
    # busy 500 ns of a 600 ns window; every ns of it under one key
    assert parts["busy_s"] == pytest.approx(500e-9)
    assert parts["total"] == pytest.approx(100.0)
    assert parts["by_key"] == {
        "decode/mlp/fwd": pytest.approx(20.0),
        "prefill/mlp/fwd": pytest.approx(20.0),
        "decode/attn/fwd": pytest.approx(18.0),
        "decode/head/fwd": pytest.approx(16.0),
        "prefill/head/fwd": pytest.approx(10.0),
        "prefill/attn/fwd": pytest.approx(8.0),
        "decode/embed/fwd": pytest.approx(2.0),
        "decode/sample/fwd": pytest.approx(2.0),
        "decode/-/fwd": pytest.approx(2.0),  # the scan's own slice
        "-/-/fwd": pytest.approx(2.0),  # the copy without a path
    }
    assert parts["by_role"] == {
        "decode": pytest.approx(60.0), "prefill": pytest.approx(38.0),
        "-": pytest.approx(2.0),
    }


@pytest.mark.parametrize("metric,value", [
    ("serve.prefill_share_pct", 38.0),
    ("serve.prefill_head_share_pct", 10.0),
    ("serve.attn_share_pct", 18.0),
    ("serve.mlp_share_pct", 20.0),
    ("serve.head_share_pct", 16.0),
    ("serve.unscoped_share_pct", 4.0),
])
def test_each_serving_metrics_cut(serving, metric, value):
    assert S.scope_share(serving, _pattern(metric)) == pytest.approx(value)


def test_the_twelve_metrics_are_cuts_of_one_partition():
    """Step: the five parts, ``embed`` and unscoped are the whole.
    Serving: the roles are the whole, and so are the parts."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entries = [
            m for m in json.load(f)["per_layer"]
            if re.match(r"(step|serve)\..*_share_pct$", m["name"])
        ]
    assert len(entries) == 12
    assert {m["source"] for m in entries} == {"device_trace"}
    keys = [
        f"{role}/{part}/{direction}"
        for role in S.ROLES + (S.NONE,) for part in S.PARTS + (S.NONE,)
        for direction in ("fwd", "bwd", "recompute")
    ]
    hits = {
        m["name"]: {k for k in keys if re.search(_pattern(m["name"]), k)}
        for m in entries
    }
    step_parts = [
        "step.attn_share_pct", "step.mlp_share_pct",
        "step.head_loss_share_pct", "step.optimizer_share_pct",
        "step.unscoped_share_pct",
    ]
    for i, a in enumerate(step_parts):
        for b in step_parts[i + 1:]:
            assert not hits[a] & hits[b], (a, b)
    assert hits["serve.prefill_head_share_pct"] < hits[
        "serve.prefill_share_pct"]
    for name in ("serve.attn_share_pct", "serve.mlp_share_pct",
                 "serve.head_share_pct"):
        assert not hits[name] & hits["serve.prefill_share_pct"]
    assert all(k.endswith("/recompute")
               for k in hits["step.recompute_share_pct"])
