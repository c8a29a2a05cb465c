"""The program's phases on the profiler's clock (ISSUE 24).

Contracts pinned here, all on the CPU:

- ``EventLogger.leaf`` is ONLY a profiler annotation (no JSONL line),
  ``span`` / ``begin``-``end`` annotate as well as write, and both are
  no-ops in a process that never imported JAX;
- the serving scheduler partitions each iteration's host time into the
  ``sched.*`` leaves and writes the sums, with the lane counts, as
  labels on the ``serve_step`` record it already wrote — on every
  record, and on nothing else the iteration writes;
- ``trainer/trainer.py`` emits one ``step`` span per completed step
  (completion to completion) and one ``snapshot_pull`` span per
  snapshot's synchronous leg, which the goodput ledger charges as loss;
- a ``jax.profiler`` window opened around a few scheduler steps and a
  few trainer steps holds host events under all of those names.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.accelerate import auto_accelerate  # noqa: E402
from dlrover_tpu.accelerate.strategy import load_strategy  # noqa: E402
from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.observability import events as ev  # noqa: E402
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    ContinuousBatchingScheduler,
    SchedulerConfig,
)
from dlrover_tpu.trainer.callbacks import TrainerCallback  # noqa: E402
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs  # noqa: E402

CFG = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, remat="none", dtype=jnp.float32,
)
PARAMS = llama.init_params(jax.random.PRNGKey(0), CFG)
SLOTS = 4
PARTS = ("admit_ms", "dispatch_ms", "wait_ms", "commit_ms", "other_ms")
LEAVES = ("sched.admit", "sched.dispatch", "sched.wait", "sched.commit")


def _scheduler(events_path, monkeypatch, decode_steps="1"):
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", decode_steps)
    sch = ContinuousBatchingScheduler(
        CFG,
        SchedulerConfig(
            max_slots=SLOTS, block_size=4, num_blocks=64, max_seq_len=64,
            prefill_chunk=8, temperature=0.0, max_new_default=12,
        ),
        events=ev.EventLogger(path=str(events_path), job="hot-loop"),
        replica="r-test",
    )
    sch.sync_weights(PARAMS)
    return sch


def _submit(sch, n=6, seed=7):
    rng = np.random.default_rng(seed)
    for i in range(n):
        sch.submit(
            rng.integers(0, 97, (int(rng.integers(5, 20)),)).astype(
                np.int32
            ),
            max_new=12, seed=100 + i,
        )


def _serve_steps(path):
    return [e for e in ev.read_events(str(path)) if e["name"] == "serve_step"]


# ------------------------------------------------------------ scheduler


@pytest.mark.parametrize("decode_steps", ["1", "3"])
def test_serve_step_labels_partition_the_span(
    tmp_path, monkeypatch, decode_steps
):
    """admit + dispatch + wait + commit + other == the span's own
    duration, on the single-token and on the K-step window path."""
    path = tmp_path / "events.jsonl"
    sch = _scheduler(path, monkeypatch, decode_steps=decode_steps)
    _submit(sch)
    assert len(list(sch.run())) == 6
    steps = _serve_steps(path)
    assert len(steps) > 10
    for e in steps:
        labels = e["labels"]
        assert all(labels[k] >= 0 for k in PARTS[:4]), labels
        # labels are rounded to 1e-4 ms each
        assert sum(labels[k] for k in PARTS) == pytest.approx(
            1e3 * e["dur"], abs=1e-3
        ), labels
    # every phase did something somewhere in the run
    for k in PARTS[:4]:
        assert sum(e["labels"][k] for e in steps) > 0, k


def test_lane_counts_are_consistent_with_the_slots(tmp_path, monkeypatch):
    path = tmp_path / "events.jsonl"
    sch = _scheduler(path, monkeypatch)
    _submit(sch, n=7)
    list(sch.run())
    steps = _serve_steps(path)
    for e in steps:
        labels = e["labels"]
        assert labels["slots"] == SLOTS
        assert 0 <= labels["lanes_decode"] <= SLOTS
        assert 0 <= labels["lanes_prefill"] <= SLOTS
        # the run-ahead loop's census rides on the same record
        assert 0 <= labels["lanes_ahead"] <= labels["lanes_decode"]
        assert labels["overrun_tokens"] == 0  # no EOS in this traffic
        assert (labels["tokens"] > 0) == (labels["lanes_prefill"] > 0)
    # one token a decoding lane (K = 1), committed an iteration after
    # its dispatch: ``new_tokens`` counts the tokens an iteration
    # COMMITS, ``lanes_decode`` the lanes it dispatches
    assert sum(e["labels"]["new_tokens"] for e in steps) == sum(
        e["labels"]["lanes_decode"] for e in steps
    )
    # the loop ran ahead: every lane of every decode step but the very
    # first was dispatched before its previous token had been read
    assert sum(e["labels"]["lanes_ahead"] for e in steps) == sum(
        e["labels"]["lanes_decode"] for e in steps
    )
    assert sch.stats()["ahead_steps"] == sum(
        e["labels"]["lanes_ahead"] > 0 for e in steps
    )
    # seven requests on four slots: the batch was full at some point
    assert max(e["labels"]["lanes_decode"] for e in steps) == SLOTS


def test_every_serve_step_record_has_the_closed_label_set(
    tmp_path, monkeypatch
):
    """One ``serve_step`` record an iteration that did work, each with
    exactly the required labels and the optional ones of
    ``OPTIONAL_SPAN_LABELS`` (what ``benchmarks/`` reads: the five
    parts, the lane counts, the state counters), and the iteration's
    own ``prefill`` / ``decode`` records carry none of them."""
    path = tmp_path / "events.jsonl"
    sch = _scheduler(path, monkeypatch)
    _submit(sch)
    list(sch.run())
    steps = _serve_steps(path)
    assert len(steps) == sch.iterations
    want = {"tokens", "new_tokens", "throughput_tps"} | set(
        ev.OPTIONAL_SPAN_LABELS[ev.PHASE_SERVE_STEP]
    )
    for e in steps:
        assert set(e["labels"]) == want, e
        assert e["labels"]["state_bytes"] == 0  # keys and values only
    for e in ev.read_events(str(path)):
        if e["name"] in ("prefill", "decode"):
            assert not set(e["labels"]) & set(PARTS), e


# ----------------------------------------------------------- the logger


def test_leaf_and_span_are_no_ops_without_jax(tmp_path):
    """In a process that never imported JAX (events.py loaded by path:
    the package's ``__init__`` would import it) a leaf is the shared
    null context, a span still writes its two lines, and JAX stays
    unimported."""
    path = tmp_path / "events.jsonl"
    code = textwrap.dedent(
        f"""
        import importlib.util, json, sys
        spec = importlib.util.spec_from_file_location(
            "ev", {os.path.join(REPO, "dlrover_tpu", "observability",
                                "events.py")!r})
        ev = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ev)
        log = ev.EventLogger(path={str(path)!r})
        assert log.leaf("sched.commit") is ev._NO_ANNOTATION
        with log.leaf("sched.commit"):
            pass
        with log.span("rendezvous"):
            pass
        off = ev.EventLogger(path="")
        with off.leaf("sched.admit"), off.span("rendezvous"):
            pass
        assert "jax" not in sys.modules, "the logger imported JAX"
        print(json.dumps([e["ph"] for e in ev.read_events({str(path)!r})]))
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == '["B", "E"]'


def test_leaf_writes_no_line_and_span_still_pairs(tmp_path):
    path = tmp_path / "events.jsonl"
    log = ev.EventLogger(path=str(path))
    with log.leaf("sched.dispatch"):
        with log.span("rendezvous"):
            pass
    sid = log.begin("compile")
    log.end("compile", sid)
    log.end("compile")  # an end without a begin stays harmless
    got = [(e["name"], e["ph"]) for e in ev.read_events(str(path))]
    assert got == [
        ("rendezvous", "B"), ("rendezvous", "E"),
        ("compile", "B"), ("compile", "E"), ("compile", "E"),
    ]
    # every annotation a span entered was left again
    assert log._annotated[(threading.get_ident(), "compile")] == []
    assert log._annotated[(threading.get_ident(), "rendezvous")] == []


def test_snapshot_pull_outranks_step_in_the_ledger():
    """A pull lies inside a step-done-to-step-done span: the ledger
    charges it as loss, the asynchronous drain that follows as
    nothing."""
    assert ev.PHASES.index("snapshot_pull") < ev.PHASES.index("step")
    assert ev.PHASES.index("step") < ev.PHASES.index("checkpoint_save")

    def x(name, start, dur, **labels):
        return {"name": name, "ph": "X", "wall": start, "dur": dur,
                "mono": start, "pid": 1, "labels": labels}

    ledger = ev.compute_ledger([
        x("step", 100.0, 1.0, step=1),
        x("step", 101.0, 4.0, step=2),
        x("snapshot_pull", 102.0, 3.0, step=2, bytes=1,
          throughput_gbps=1.0, mode="staged", memory_kind="pinned_host"),
        x("checkpoint_save", 105.0, 0.5, step=2, bytes=1,
          throughput_gbps=1.0),
        x("step", 105.0, 1.0, step=3),
    ])
    assert ledger["wall_s"] == pytest.approx(6.0)
    assert ledger["useful_s"] == pytest.approx(3.0)
    assert ledger["loss_breakdown"]["snapshot_pull"] == pytest.approx(3.0)
    assert ledger["loss_breakdown"].get("checkpoint_save", 0.0) == 0.0


# ----------------------------------------- one profiled run of both loops


def _build_trainer(tmp_path, snapshot_mode):
    os.environ["DLROVER_TPU_SOCKET_DIR"] = str(tmp_path / "socks")
    cfg = llama.LlamaConfig.tiny(remat="none")
    result = auto_accelerate(
        loss_fn=lambda p, b: llama.loss_fn(p, b, cfg),
        optimizer=optax.adamw(1e-3),
        init_params_fn=lambda rng: llama.init_params(rng, cfg),
        param_axes=llama.param_logical_axes(cfg),
        load_strategy=load_strategy({"data": 8, "remat": "none"}),
    )
    tokens = np.ones((8, 17), dtype=np.int32)

    def data_iter():
        for _ in range(8):
            yield {"tokens": tokens}

    class Unhurried(TrainerCallback):
        """A tiny step takes milliseconds, a drain's thread tens of
        them: leave each snapshot's drain two slow steps to end in, or
        the next snapshot is skipped as the slot is busy."""

        def on_step_end(self, step, metrics):
            time.sleep(0.1)

    args = TrainingArgs(
        max_steps=6, checkpoint_dir=str(tmp_path / "ckpt"),
        save_memory_interval=2, save_storage_interval=100,
        log_interval=100, micro_batch_size=8, snapshot_mode=snapshot_mode,
    )
    return Trainer(result, args, data_iter, callbacks=[Unhurried()])


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A few scheduler steps and six trainer steps (a staged snapshot
    every second one) inside ONE ``jax.profiler`` window, the Python
    tracer off as the benchmark opens it.  Gives the host events' names
    and the timeline's records."""
    from jax.profiler import ProfileData

    tmp_path = tmp_path_factory.mktemp("profiled")
    path = tmp_path / "events.jsonl"
    mp = pytest.MonkeyPatch()
    ev.set_default_event_logger(ev.EventLogger(path=str(path)))
    try:
        sch = _scheduler(tmp_path / "sched.jsonl", mp)
        _submit(sch, n=3)
        trainer = _build_trainer(tmp_path, "staged")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        trace_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for _ in range(12):
                sch.step()
            summary = trainer.train()
        finally:
            jax.profiler.stop_trace()
        skipped = trainer._engine.skipped_snapshots
        # the copy leg, on the trained state, outside the window
        trainer._engine = None
        copy = _build_trainer(tmp_path / "copy", "copy")
        copy._init_or_restore_state()
        copy._maybe_checkpoint(2)
        copy._engine.wait_for_snapshot(timeout=60)
        copy._engine.close()
    finally:
        ev.set_default_event_logger(None)
        mp.undo()
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    assert len(found) == 1
    names = {}
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names[e.name] = names.get(e.name, 0) + 1
    return {
        "summary": summary,
        "host_events": names,
        "events": ev.read_events(str(path)),
        "skipped_snapshots": skipped,
    }


@pytest.mark.parametrize("name", LEAVES + ("snapshot_pull", "train"))
def test_profiler_window_holds_the_phase(profiled, name):
    """The leaves, the pull and the step markers are host events of the
    window — and the spans the scheduler reports after the fact
    (``serve_step``, ``decode``, ``prefill``), which would enclose the
    leaves and win every idle gap, are not."""
    assert profiled["host_events"].get(name, 0) >= 3, sorted(
        n for n in profiled["host_events"] if "." in n or "_" in n
    )[:40]
    for enclosing in ("serve_step", "decode", "prefill"):
        assert enclosing not in profiled["host_events"]


def test_trainer_emits_one_step_span_a_step(profiled):
    """Six steps: the first completion has no step-done before it, the
    other five are one span each, completion to completion."""
    assert profiled["summary"]["final_step"] == 6
    steps = [e for e in profiled["events"] if e["name"] == "step"]
    assert [e["labels"]["step"] for e in steps] == [2, 3, 4, 5, 6]
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in steps)
    assert all(e["labels"]["tokens"] == 8 * 17 for e in steps)
    # completion to completion: each span ends where the next starts
    for a, b in zip(steps, steps[1:]):
        assert a["wall"] + a["dur"] == pytest.approx(b["wall"], abs=5e-3)


def test_ledger_over_the_trainers_events_has_useful_time(profiled):
    ledger = ev.compute_ledger(profiled["events"])
    assert ledger["useful_s"] > 0
    assert ledger["loss_breakdown"]["snapshot_pull"] > 0
    assert sum(ledger["loss_breakdown"].values()) == pytest.approx(
        ledger["wall_s"] - ledger["useful_s"]
    )


@pytest.mark.parametrize("mode,steps", [
    # the job's final blocking save is a pull of its own, at step 6
    ("staged", [2, 4, 6, 6]),
    ("copy", [2]),
])
def test_snapshot_pull_span_per_snapshot(profiled, mode, steps):
    pulls = [
        e for e in profiled["events"]
        if e["name"] == "snapshot_pull" and e["labels"]["mode"] == mode
    ]
    assert [e["labels"]["step"] for e in pulls] == steps
    for e in pulls:
        labels = e["labels"]
        assert e["ph"] == "X" and e["dur"] > 0
        assert labels["bytes"] > 0
        assert labels["throughput_gbps"] == pytest.approx(
            labels["bytes"] / e["dur"] / 1e9, rel=0.05, abs=2e-3
        )
        # where the one program put the copy: this backend has no
        # in-program pinned_host, so both modes stay on the device
        assert labels["memory_kind"] == "device"
    # ... and a drain behind each staged pull, off the training thread
    if mode == "staged":
        drains = [
            e["labels"]["step"] for e in profiled["events"]
            if e["name"] == "checkpoint_save" and e["ph"] in ("X", "B")
        ]
        assert set(steps) <= set(drains)


def test_every_pull_has_its_drain_and_none_is_skipped(profiled):
    """A skipped snapshot is a different result, not a faster one: the
    trainer's run skipped none, and every ``snapshot_pull`` is followed
    by ONE ``checkpoint_save`` of the same step with the same
    ``bytes``."""
    assert profiled["skipped_snapshots"] == 0
    spans = sorted(
        (
            e for e in profiled["events"]
            if e["ph"] == "X"
            and e["name"] in ("snapshot_pull", "checkpoint_save")
        ),
        key=lambda e: e["wall"],
    )
    pulls = [e for e in spans if e["name"] == "snapshot_pull"]
    assert [
        (e["labels"]["mode"], e["labels"]["step"]) for e in pulls
    ] == [
        ("staged", 2), ("staged", 4), ("staged", 6),
        ("staged", 6),  # the job's final blocking save
        ("copy", 2),
    ]
    drains = [
        e for e in spans
        if e["name"] == "checkpoint_save"
        # (the saver's shm -> storage write is a checkpoint_save too)
        and e["labels"].get("stage") != "persist"
    ]
    for pull, until in zip(pulls, pulls[1:] + [None]):
        mine = [
            d for d in drains
            if d["wall"] >= pull["wall"]
            and (until is None or d["wall"] < until["wall"])
        ]
        # between two pulls ONE drain, the first pull's
        assert len(mine) == 1, (pull, mine)
        for d in mine:
            assert d["labels"]["step"] == pull["labels"]["step"]
            assert d["labels"]["bytes"] == pull["labels"]["bytes"]


# -------------------------------------------------------------- the lint


def _lint(path):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_event_schema.py"), str(path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def test_lint_closes_leaf_names_and_serve_step_labels(tmp_path):
    bad = tmp_path / "bad_leaf.py"
    bad.write_text(
        "def f(events, name):\n"
        "    events.leaf('sched.admit')\n"          # fine
        "    events.leaf('snapshot_pull')\n"        # a phase: fine
        "    events.leaf('sched.sample')\n"         # undeclared leaf
        "    events.leaf(name)\n"                   # not a literal
        "    events.complete('serve_step', 0.0, 1.0, tokens=1,\n"
        "                    new_tokens=1, throughput_tps=1.0,\n"
        "                    admit_ms=0.1, lanes_decode=1)\n"  # fine
        "    events.complete('serve_step', 0.0, 1.0, tokens=1,\n"
        "                    new_tokens=1, throughput_tps=1.0,\n"
        "                    admitt_ms=0.1)\n"      # typo'd label
    )
    proc = _lint(bad)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=3" in proc.stdout, proc.stdout
    assert "leaf('sched.sample') is not a declared leaf" in proc.stdout
    assert "undeclared label(s) ['admitt_ms']" in proc.stdout


def test_lint_enforces_snapshot_pull_labels(tmp_path):
    bad = tmp_path / "bad_pull.py"
    bad.write_text(
        "def f(events):\n"
        "    events.complete('snapshot_pull', 0.0, 1.0, step=1,\n"
        "                    bytes=1, throughput_gbps=1.0,\n"
        "                    memory_kind='device')\n"
        "    events.complete('snapshot_pull', 0.0, 1.0, step=1,\n"
        "                    bytes=1, throughput_gbps=1.0,\n"
        "                    mode='staged')\n"
        "    events.complete('snapshot_pull', 0.0, 1.0, step=1,\n"
        "                    bytes=1, throughput_gbps=1.0,\n"
        "                    mode='staged', memory_kind='pinned_host')\n"
    )
    proc = _lint(bad)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "event_schema_violations=2" in proc.stdout, proc.stdout
    assert "missing required label(s) ['mode']" in proc.stdout
    assert "missing required label(s) ['memory_kind']" in proc.stdout
