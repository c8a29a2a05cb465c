"""A chip-owning process's start from inside (ISSUE 55): the ``startup``
stages of a serving replica and of a training worker, the ``compile``
records ``common/jax_env.CompileMeter`` writes a program and stage, and
the ``reply`` span of a finished request — on tiny models, each process
of a kind started twice on one compile cache, so that the second reads
what the first wrote."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common.jax_env import CompileMeter, install_compile_meter
from dlrover_tpu.observability import events as ev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVE_CFG_KW = dict(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=64, remat="none", dtype="float32",
)
REPLICA_STAGES = [
    "process", "imports", "backend_init", "factory", "pool", "weights",
]
WORKER_STAGES = [
    "process", "imports", "backend_init", "accelerate", "state",
    "first_step",
]


def _spans(events, phase):
    return [s for s in ev.pair_spans(events) if s["phase"] == phase]


def _compiles(events, **labels):
    return [
        s for s in _spans(events, "compile")
        if all(s["labels"].get(k) == v for k, v in labels.items())
    ]


def _inside(inner, outer, slack=1e-3):
    return (
        outer["start"] - slack <= inner["start"]
        and inner["end"] <= outer["end"] + slack
    )


def _check_stages(stages, expected):
    """Each stage once, in the order given, none overlapping another,
    ``process`` first."""
    assert [s["labels"]["stage"] for s in stages] == expected
    assert len({s["pid"] for s in stages}) == 1
    for before, after in zip(stages, stages[1:]):
        assert before["end"] <= after["start"] + 1e-3, (before, after)
    assert stages[0]["start"] < stages[1]["start"]


# ------------------------------------------------------- serving replica


def _serve_once(events_path, cache_dir, socks):
    """A one-replica engine, three requests, closed; the replica's
    records."""
    from dlrover_tpu.rl.generation_service import ServingEngine

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DLROVER_TPU_EVENTS_FILE", str(events_path))
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
        mp.setenv("DLROVER_TPU_SOCKET_DIR", str(socks))
        # JAX keeps what took a second to compile: on a loaded machine a
        # tiny program does, in one process and not in the other.  With
        # the threshold out of reach only ``kept_in_compile_cache`` keeps
        mp.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "600")
        eng = ServingEngine(
            factory="dlrover_tpu.rl.generation_service:tiny_llama_factory",
            factory_kwargs=SERVE_CFG_KW,
            max_new_tokens=4,
            temperature=0.0,
            name=f"startup-test-{os.getpid()}",
            num_replicas=1,
            max_slots=4,
            block_size=4,
            num_blocks=40,
            max_seq_len=48,
            prefill_chunk=8,
        )
        try:
            ids = [
                eng.submit(
                    (np.arange(3 + i, dtype=np.int32) * 7) % 50, seed=i
                )
                for i in range(3)
            ]
            for rid in ids:
                eng.result(rid, timeout=120)
        finally:
            eng.close()
    return ids, ev.read_events(str(events_path))


@pytest.fixture(scope="module")
def replicas(tmp_path_factory):
    """(request ids, records) of two replicas started one after the
    other on ONE compile cache directory."""
    root = tmp_path_factory.mktemp("startup")
    socks = tmp_path_factory.mktemp("sk")
    return [
        _serve_once(root / f"events{i}.jsonl", root / "cache", socks)
        for i in range(2)
    ]


@pytest.mark.heavy
@pytest.mark.parametrize("run", [0, 1], ids=["cold", "warm"])
def test_a_replica_writes_each_startup_stage_once_in_order(replicas, run):
    _, events = replicas[run]
    stages = _spans(events, "startup")
    _check_stages(stages, REPLICA_STAGES)
    by_stage = {s["labels"]["stage"]: s["labels"] for s in stages}
    assert by_stage["backend_init"]["device_kind"] == "cpu"
    assert by_stage["pool"]["pool_bytes"] > 0
    assert by_stage["weights"]["bytes"] > 0
    # READY — the ``device_report`` instant — follows the last stage and
    # the resident copy
    ready = min(
        e["wall"] for e in events
        if e["name"] == "device_report" and "kernel_backend" in e["labels"]
    )
    cast = _spans(events, "weight_cast")[0]
    assert stages[-1]["end"] <= cast["start"] + 1e-3
    assert cast["end"] <= ready + 1e-3


@pytest.mark.heavy
@pytest.mark.parametrize("run", [0, 1], ids=["cold", "warm"])
def test_compile_records_name_the_scheduler_programs_and_every_stage(
    replicas, run
):
    """The serving programs' first dispatch is the ``prefill`` /
    ``decode`` span that encloses their records; every record before
    READY lies inside a ``startup`` stage, the ``weight_cast`` span or a
    ``serve_step``, and none precedes the process's start."""
    _, events = replicas[run]
    for program in ("_prefill_last", "_decode"):
        stages = [s["labels"]["stage"] for s in _compiles(
            events, program=program
        )]
        assert stages == ["trace", "lower", "backend_compile"], program
    steps = _spans(events, "serve_step")
    for program, phase in (("_prefill_last", "prefill"), ("_decode", "decode")):
        first = _spans(events, phase)[0]
        for rec in _compiles(events, program=program):
            assert _inside(rec, first), (program, rec, first)
            assert _inside(rec, steps[0])
    holders = (
        _spans(events, "startup") + _spans(events, "weight_cast") + steps
    )
    born = _spans(events, "startup")[0]["start"]
    for rec in _spans(events, "compile"):
        assert rec["start"] >= born
        assert any(_inside(rec, h) for h in holders), rec
        assert set(rec["labels"]) - {"cache"} == {"program", "stage"}
        assert ("cache" in rec["labels"]) == (
            rec["labels"]["stage"] == "backend_compile"
        )


@pytest.mark.heavy
def test_the_second_process_is_handed_what_the_first_wrote(replicas):
    """``_cast_and_fuse`` compiles in well under JAX's second and is
    kept all the same (``kept_in_compile_cache``): a miss in the first
    process, a hit in the second, which misses nothing."""
    (_, cold), (_, warm) = replicas
    assert [
        s["labels"]["cache"]
        for s in _compiles(cold, program="_cast_and_fuse",
                           stage="backend_compile")
    ] == ["miss"]
    assert [
        s["labels"]["cache"]
        for s in _compiles(warm, program="_cast_and_fuse",
                           stage="backend_compile")
    ] == ["hit"]
    assert not _compiles(warm, stage="backend_compile", cache="miss")
    assert not _compiles(cold, stage="backend_compile", cache="hit")


@pytest.mark.heavy
def test_a_program_under_the_threshold_reads_none_in_both(replicas):
    """JAX keeps nothing that compiled in under a second: such a program
    compiles at EVERY start, and both processes' records say so."""
    (_, cold), (_, warm) = replicas
    backend = _compiles(cold, stage="backend_compile")
    kept = {s["labels"]["program"] for s in backend
            if s["labels"]["cache"] != "none"}
    quick = {
        s["labels"]["program"] for s in backend
        if s["end"] - s["start"] < 0.2
    } - kept  # a name is several programs where its shapes differ
    assert quick
    again = [
        s for s in _compiles(warm, stage="backend_compile")
        if s["labels"]["program"] in quick
    ]
    assert {s["labels"]["program"] for s in again} == quick
    assert {s["labels"]["cache"] for s in again} == {"none"}


@pytest.mark.heavy
@pytest.mark.parametrize("run", [0, 1], ids=["cold", "warm"])
def test_a_finished_request_writes_one_reply_span(replicas, run):
    ids, events = replicas[run]
    replies = _spans(events, "reply")
    assert sorted(s["labels"]["req_id"] for s in replies) == sorted(ids)
    assert {s["labels"]["per_token_bytes"] for s in replies} == {0}
    # a model without per-position rows: nothing copied for them
    assert {s["labels"]["copied_bytes"] for s in replies} == {0}
    assert all(s["end"] > s["start"] for s in replies)
    done = {
        s["labels"]["req_id"]: s["end"]
        for s in _spans(events, "serve_request")
    }
    for s in replies:  # after the scheduler finished the request
        assert s["start"] >= done[s["labels"]["req_id"]] - 1e-3


# ------------------------------------------------------- training worker

_TRAIN_IN_A_FRESH_PROCESS = """
from dlrover_tpu.trainer.elastic import init_distributed
ctx = init_distributed()
import jax, numpy as np, optax
from dlrover_tpu.accelerate import auto_accelerate, load_strategy
from dlrover_tpu.models import llama
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

cfg = llama.LlamaConfig.tiny(remat="none")
result = auto_accelerate(
    loss_fn=lambda p, b: llama.loss_fn(p, b, cfg, fused_ce=False),
    optimizer=optax.adamw(1e-3),
    init_params_fn=lambda rng: llama.init_params(rng, cfg),
    param_axes=llama.param_logical_axes(cfg),
    load_strategy=load_strategy({"data": 2}),
    devices=jax.devices()[:2],
)

def batches():  # the fifth batch has another shape
    for i in range(6):
        yield {"tokens": np.ones((4, 17 if i < 4 else 9), dtype=np.int32)}

Trainer(
    result,
    TrainingArgs(max_steps=6, log_interval=100, micro_batch_size=4),
    batches,
).train()
"""


@pytest.fixture(scope="module")
def worker_events(tmp_path_factory):
    root = tmp_path_factory.mktemp("worker")
    path = root / "events.jsonl"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        JAX_COMPILATION_CACHE_DIR=str(root / "cache"),
        DLROVER_TPU_EVENTS_FILE=str(path),
        PYTHONPATH=REPO,
    )
    subprocess.run(
        [sys.executable, "-c", _TRAIN_IN_A_FRESH_PROCESS], env=env,
        capture_output=True, text=True, timeout=300, check=True,
    )
    return ev.read_events(str(path))


@pytest.mark.heavy
def test_a_worker_writes_its_stages_in_order(worker_events):
    stages = _spans(worker_events, "startup")
    _check_stages(stages, WORKER_STAGES)
    by_stage = {s["labels"]["stage"]: s["labels"] for s in stages}
    assert by_stage["backend_init"]["device_kind"] == "cpu"
    assert by_stage["accelerate"]["params"] > 0
    # a ``step`` span exists only from the second step on
    steps = _spans(worker_events, "step")
    assert [s["labels"]["step"] for s in steps] == [2, 3, 4, 5, 6]
    assert stages[-1]["end"] <= steps[0]["end"]


@pytest.mark.heavy
def test_first_step_encloses_the_step_programs_records(worker_events):
    first_step = _spans(worker_events, "startup")[-1]
    records = _compiles(worker_events, program="_train_step")
    inside = [r for r in records if _inside(r, first_step)]
    assert {r["labels"]["stage"] for r in inside} == {
        "trace", "lower", "backend_compile",
    }
    state = _spans(worker_events, "startup")[-2]
    assert any(
        _inside(r, state)
        for r in _compiles(worker_events, program="_init_state")
    )


@pytest.mark.heavy
def test_a_batch_of_another_shape_compiles_after_first_step(worker_events):
    """What a steady window must not hold: a record whose start lies
    after the first step names the recompile, here the step program for
    the second batch shape, inside the ``step`` span that paid for it
    (the fourth's: a step is dispatched before the one before it is
    read)."""
    first_step = _spans(worker_events, "startup")[-1]
    late = [
        r for r in _compiles(worker_events, program="_train_step")
        if r["start"] >= first_step["end"]
    ]
    assert [r["labels"]["stage"] for r in late] == [
        "trace", "lower", "backend_compile",
    ]
    fourth = next(
        s for s in _spans(worker_events, "step") if s["labels"]["step"] == 4
    )
    assert all(_inside(r, fourth) for r in late)


# -------------------------------------------------------------- the meter


@pytest.fixture
def metered():
    """``metered(logger)``: a meter of the test's own, its listeners
    taken off JAX again on the way out (the process's installed meter
    stays)."""
    from jax._src import monitoring

    made = []

    def make(logger):
        made.append(CompileMeter(events=logger))
        return made[-1]

    yield make
    for meter in made:
        monitoring.unregister_event_listener(meter._on_event)
        monitoring.unregister_event_duration_listener(meter._on_duration)
        if meter._events is not None:
            monitoring.unregister_scalar_listener(meter._on_scalar)


def _fresh_program():
    """A jitted function no other test has compiled."""
    return jax.jit(lambda x: jnp.tanh(x) * 3.0 + jnp.clip(x, 0.0, 1.0))


def test_a_disabled_logger_installs_the_meter_and_writes_nothing(
    tmp_path, monkeypatch, metered
):
    monkeypatch.delenv(ev.EVENTS_FILE_ENV, raising=False)
    monkeypatch.chdir(tmp_path)
    logger = ev.EventLogger()
    assert not logger.enabled
    meter = metered(logger)
    before = meter.snapshot()
    _fresh_program()(jnp.ones((3, 5))).block_until_ready()
    assert set(meter.snapshot()) == {"cache_hits", "cache_misses", "compile_s"}
    assert meter.snapshot()["compile_s"] >= before["compile_s"]
    assert meter._events is None
    assert os.listdir(tmp_path) == []


def test_the_process_has_one_installed_meter():
    assert install_compile_meter() is install_compile_meter()


def test_records_lie_on_the_anchored_clock_and_nested_traces_once(
    tmp_path, metered
):
    """A function traced inside another's trace is covered by the outer
    record; ``jit(f)`` and ``f`` are one program; end = the callback's
    instant on the logger's clock, start = end - JAX's duration."""
    logger = ev.EventLogger(path=str(tmp_path / "events.jsonl"))
    metered(logger)

    @jax.jit
    def inner_fn(x):
        return jnp.where(x > 0, x, 0.0) * 2

    def outer_fn(x):
        return inner_fn(x) + jnp.clip(x, 0, 1)

    t0 = ev.anchored_now()
    jax.jit(outer_fn)(jnp.ones((7, 3))).block_until_ready()
    t1 = ev.anchored_now()
    records = [
        s for s in _spans(ev.read_events(logger.path), "compile")
        if s["labels"]["program"] in ("outer_fn", "inner_fn")
    ]
    assert [
        (s["labels"]["program"], s["labels"]["stage"]) for s in records
    ] == [
        ("outer_fn", "trace"), ("outer_fn", "lower"),
        ("outer_fn", "backend_compile"),
    ]
    assert records[-1]["labels"]["cache"] in ("hit", "miss", "none")
    for before, after in zip(records, records[1:]):
        assert before["end"] <= after["start"] + 1e-3
    assert t0 <= records[0]["start"] and records[-1]["end"] <= t1


def test_the_cache_verdict_is_the_compiling_threads_own(tmp_path, metered):
    """What a thread heard from the cache since its last backend compile
    is that compile's: a verdict does not leak to another thread's
    record, nor to the same thread's next."""
    logger = ev.EventLogger(path=str(tmp_path / "events.jsonl"))
    meter = metered(logger)
    meter._on_event(meter._HIT)  # this thread: a hit under way
    heard = []

    def other():
        meter._on_duration(meter._COMPILE, 0.25, fun_name="jit(theirs)")
        heard.append(True)

    worker = threading.Thread(target=other)
    worker.start()
    worker.join()
    meter._on_duration(meter._COMPILE, 0.5, fun_name="jit(ours)")
    meter._on_duration(meter._COMPILE, 0.5, fun_name="jit(ours)")
    got = [
        (s["labels"]["program"], s["labels"]["cache"],
         round(s["end"] - s["start"], 3))
        for s in _spans(ev.read_events(logger.path), "compile")
    ]
    assert heard and sorted(got) == [
        ("ours", "hit", 0.5), ("ours", "none", 0.5), ("theirs", "none", 0.25),
    ]
    assert meter.snapshot()["cache_hits"] == 1


def test_process_start_is_the_kernels_and_precedes_now():
    born = ev.process_start_wall()
    assert born is not None
    age = ev.anchored_now() - born
    # this interpreter has been up for a while, and not since the epoch
    assert 0.0 < age < 24 * 3600.0
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    assert ticks > 0


def test_a_forked_childs_start_is_its_fork(tmp_path):
    """A process forked from a long-lived parent (a zygote's child)
    counts its ``process`` stage from the fork, not from the parent's
    start."""
    code = (
        "import os, sys, time, json\n"
        "from dlrover_tpu.observability import events as ev\n"
        "time.sleep(1.2)\n"
        "parent = ev.anchored_now() - ev.process_start_wall()\n"
        "r, w = os.pipe()\n"
        "pid = os.fork()\n"
        "if pid == 0:\n"
        "    age = ev.anchored_now() - ev.process_start_wall()\n"
        "    os.write(w, json.dumps(age).encode())\n"
        "    os._exit(0)\n"
        "os.waitpid(pid, 0)\n"
        "print(json.dumps([parent, json.loads(os.read(r, 100))]))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=dict(os.environ, PYTHONPATH=REPO),
        capture_output=True, text=True, timeout=60, check=True,
    )
    parent_age, child_age = json.loads(out.stdout.strip().splitlines()[-1])
    assert parent_age >= 1.2
    assert child_age < parent_age - 1.0
