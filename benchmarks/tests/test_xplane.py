"""The trace reducer on a recorded trace and on hand-made intervals.

``data/train_5steps.xplane.pb.gz`` is the profiler's own file from cell A's
first traced run on a TPU v5e (PR 23): five plain steps of the Mistral
depth-2 train step, gzipped.  The numbers asserted are what reading that
file by hand gave.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import xplane

TRACE = os.path.join(HERE, "data", "train_5steps.xplane.pb.gz")


@pytest.fixture(scope="module")
def profile():
    return xplane.load(TRACE)


def test_device_plane_and_op_names(profile):
    planes = xplane.device_ops(profile)
    assert list(planes) == ["/device:TPU:0"]
    names = {name for _, _, name in planes["/device:TPU:0"]}
    # an event's name is its whole HLO text; the reducer keeps the op name
    assert all(" = " not in n and not n.startswith("%") for n in names)
    assert any(n.startswith("_flash_fwd.") for n in names)
    assert any(n.startswith("_flash_bwd.") for n in names)


def test_busy_idle_and_flash_share_of_the_recorded_trace(profile):
    r = xplane.reduce(profile, kernel_pattern="^_flash_(fwd|bwd)")
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.801974431, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.80190495, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # four kernels (fwd, remat fwd, two bwd) x 2 layers x ~5 steps
    assert r["kernel_s"] == pytest.approx(0.057757628, rel=1e-6)
    share = 100 * r["kernel_s"] / r["busy_s"]
    assert share == pytest.approx(7.2025, abs=1e-3)


def test_top_operations_are_self_times(profile):
    r = xplane.reduce(profile)
    families = dict(r["device_ops"])
    assert len(r["device_ops"]) == 10
    # the scans over layers are `while` events that enclose their bodies:
    # counted by self time they vanish from the top, and the families'
    # times sum to no more than the busy time
    assert "while" not in families
    assert sum(families.values()) <= r["busy_s"]
    assert list(families)[0] == "fusion"
    assert families["_flash_fwd"] == pytest.approx(0.029940428, rel=1e-6)
    assert len(r["idle_gaps"]) == 10
    assert all(" -> " in name and dur < 1e-4 for name, dur in r["idle_gaps"])


def test_self_times_of_nested_operations():
    ops = sorted(
        [(0, 100, "while.1"), (10, 40, "fusion.1"), (40, 90, "while.2"),
         (50, 60, "_flash_fwd.3"), (120, 130, "copy.4")],
        key=lambda o: (o[0], -o[1]),
    )
    own = {name: t for t, name in xplane.self_times(ops)}
    assert own == {
        "while.1": 20, "fusion.1": 30, "while.2": 40, "_flash_fwd.3": 10,
        "copy.4": 10,
    }
    assert xplane.union_s(ops) == pytest.approx(110e-9)


def test_a_trace_without_device_operations_reduces_to_nothing():
    class Empty:
        planes = []

    assert xplane.reduce(Empty()) is None


def test_op_family():
    assert xplane.op_family("%fusion.123 = f32[2] fusion(...)") == "fusion"
    assert xplane.op_family("_flash_bwd.22") == "_flash_bwd"
    assert xplane.op_family("custom-call.7") == "custom-call"
