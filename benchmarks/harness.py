"""The cell runner: everything it runs is named by data.

``run_cell(workload, seed, seconds, trace)`` looks the workload up in
``BENCHMARK.json`` (configuration, traffic mix, chips), reads
``configs/<config>.json`` and ``traffic/<traffic>.json`` beside this
file, hands them to the runner of the traffic file's ``kind``
(``runners`` below), and reduces what the run left behind to the metrics
``BENCHMARK.json`` lists for that cell: end-to-end metrics without a
trace, per-layer metrics — one ``layer_metrics/<name>.json`` each, naming
its reader — with one.  A later PR adds a cell, a configuration, a
traffic mix, a per-layer metric or a model family (the module a
configuration file names under ``family``) by adding files and
``BENCHMARK.json`` entries; nothing here names one.

Every number a run's ``correct`` held to a limit (a ``rollout`` cell:
the largest difference of a served token's logprob from the family's
float32 reference against the traffic file's ``logprob_tol``; a
``train`` cell: that of the first batch and the first loss's) rides
last in the result line, under ``compared``, beside its limit.  Where a
``rollout`` cell's family provides ``token_logprobs_forced`` (a model
with a router: ``family_dense.py``, point 4), the program's replies
carry ``result["per_token"]``, the reference is forced onto those
choices (``sample.npz``: ``served_<name>``), and a second number rides
there: the largest slack of a served choice under the reference's own
scores against the traffic file's ``routing_slack_max``.

The process that calls this never initialises a JAX backend: a parent
that touched JAX would hold the chip its children need.  The platform
expected (``"tpu"`` from the command line, ``"cpu"`` from the tests) and
the directory of data files are ARGUMENTS; no switch or environment
variable selects a rehearsal.
"""

import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _path in (REPO, BENCH):  # the program, and the modules beside this
    if _path not in sys.path:
        sys.path.insert(0, _path)

#: kind of a traffic file -> "module:function" of its runner
RUNNERS = {
    "train": "train_cell:run_train",
    "resume": "train_cell:run_resume",
    "rollout": "rollout_cell:run_rollout",
}


class CellFailed(Exception):
    """The run cannot be reported: no result line, exit code 1."""


def require(cond, what):
    if not cond:
        raise CellFailed(what)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(spec):
    """``"module:function"`` (a module beside this file) -> callable."""
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def load_cell(workload, data_root=REPO):
    """Everything ``BENCHMARK.json`` and the data files say about one
    cell.  ``data_root`` holds ``BENCHMARK.json``; the data files lie
    under its first ``paths`` entry."""
    bench = load_json(os.path.join(data_root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    require(workload in cells, f"no workload {workload!r} in BENCHMARK.json")
    entry = cells[workload]
    files = os.path.join(data_root, bench["paths"][0])
    if files not in sys.path:  # a data root's own family modules
        sys.path.append(files)
    config_entry = next(
        c for c in bench["configs"] if c["name"] == entry["config"]
    )
    config_path = os.path.join(data_root, config_entry["file"])
    config = load_json(config_path)
    family(config)  # a configuration that names none fails here, by name

    def listed(metric):
        return workload in metric.get("workloads", [workload])

    def shared(rel):
        # the data root's own file, else the benchmark's (the tests'
        # tiny tree reuses the readers and names its own peak)
        own = os.path.join(files, rel)
        return load_json(own if os.path.exists(own) else
                         os.path.join(BENCH, rel))

    per_layer = [
        dict(m, **shared(os.path.join("layer_metrics", m["name"] + ".json")))
        for m in bench["per_layer"] if listed(m)
    ]
    return {
        "name": workload,
        "files": files,
        "chips": int(entry["chips"]),
        "config": config,
        "config_path": config_path,
        "traffic": load_json(
            os.path.join(files, "traffic", entry["traffic"] + ".json")
        ),
        "end_to_end": [m for m in bench["end_to_end"] if listed(m)],
        "per_layer": per_layer,
        "peaks": shared("peaks.json"),
    }


def family(cfg):
    """The module a configuration file names under ``family``: its
    architecture's model keywords, worker and serving parts, plain
    reference and operation counts (``family_dense.py`` says what a
    family provides).  A configuration that names none is an error,
    never the dense block by default."""
    require(
        isinstance(cfg.get("family"), str),
        "the configuration file has no key 'family': it names the "
        "module beside the harness that holds its architecture",
    )
    return importlib.import_module(cfg["family"])


def child_env(expect_platform, chips):
    """The environment of everything a run starts.  The compile cache is
    ONE fixed directory inside the checkout (``$JAX_COMPILATION_CACHE_DIR``
    when the machine sets it); the program's own default is the same
    path, and it takes whatever this exports."""
    env = dict(os.environ)
    env.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(REPO, ".cache", "jax_compile"),
    )
    os.makedirs(env["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    env.pop("DLROVER_TPU_EVENTS_FILE", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if expect_platform == "cpu":  # the tests' rehearsal
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={max(chips, 1)}"
        )
    else:
        held = env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
        require(
            held in ("", expect_platform),
            f"JAX is held to {held!r} here: a cell is measured on "
            f"{expect_platform!r} only",
        )
    return env


def shm_names(token):
    """Names under /dev/shm that carry ``token``.  /dev/shm is shared
    ground outside the checkout: a run looks there only for names made
    from a token of its own."""
    try:
        return sorted(n for n in os.listdir("/dev/shm") if token in n)
    except OSError:
        return []


def children_of(pid):
    """Live child processes of ``pid`` (from /proc)."""
    out = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[1]) == pid and fields[0] != "Z":
            out.append(int(entry))
    return out


def serving_replicas_of(pid):
    """Children of ``pid`` that are serving replicas of the program."""
    out = []
    for child in children_of(pid):
        try:
            with open(f"/proc/{child}/cmdline", "rb") as f:
                cmdline = f.read()
        except OSError:
            continue
        if b"dlrover_tpu.rl.generation_service" in cmdline:
            out.append(child)
    return out


class Sandbox:
    """What one run may leave behind, removed on the way out whatever
    happened: its process group(s), its socket directory, and ITS shm
    segments (an 8 GB segment left behind breaks the next run) — those
    whose names carry a token the runner registered with ``own_shm``,
    and no others: a concurrent run's, a test's or a real job's
    segments are not this run's to touch."""

    def __init__(self, run_dir):
        self.run_dir = run_dir
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir)
        import tempfile

        self.socks = tempfile.mkdtemp(prefix="bm-")  # AF_UNIX paths are short
        self._shm_tokens = []
        self._groups = []

    def own_shm(self, token):
        """Every /dev/shm name that carries ``token`` is this run's: the
        token holds this process's id, or is the program's hash of a
        path that does, so no other run makes it."""
        require(len(token) >= 6, f"shm token {token!r} is too short")
        self._shm_tokens.append(token)

    def popen(self, cmd, env, log_name):
        log = open(os.path.join(self.run_dir, log_name), "w")
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        log.close()
        self._groups.append(proc)
        return proc

    def close(self):
        for proc in self._groups:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        for pid in serving_replicas_of(os.getpid()):  # engine.close failed
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        shutil.rmtree(self.socks, ignore_errors=True)
        for token in self._shm_tokens:
            for name in shm_names(token):
                try:
                    os.unlink(os.path.join("/dev/shm", name))
                except OSError:
                    pass


def tail(path, n=6000):
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def attach_trace(ctx, trace_dir):
    """The profiler's file under ``trace_dir`` and its reduction, for the
    device readers and the result line; both None without a file."""
    import xplane

    path = xplane.find_xplane(trace_dir)
    ctx["trace_profile"] = xplane.load(path) if path else None
    ctx["trace"] = xplane.reduce(ctx["trace_profile"]) if path else None


def device_object(report, memory_peak_bytes, expect_platform, chips):
    """The result line's ``device`` from a worker's or replica's own
    report.  Another platform or fewer devices than the cell asks for is
    a failed run, not a fallback."""
    require(report is not None, "no process reported its device")
    require(
        report["platform"] == expect_platform,
        f"ran on {report['platform']!r} ({report['device_kind']!r}), "
        f"expected {expect_platform!r}",
    )
    require(
        int(report["device_count"]) >= chips,
        f"{report['device_count']} device(s) for a {chips}-chip cell",
    )
    return {
        "platform": report["platform"],
        "kind": report["device_kind"],
        "count": int(report["device_count"]),
        "memory_peak_bytes": memory_peak_bytes,
    }


def run_cell(workload, seed, seconds, trace, expect_platform="tpu",
             data_root=REPO, t_start=None):
    """Run one cell once; returns the result line as a dict.

    ``t_start``: wall time at which the calling process started (set-up
    is counted from there)."""
    t_start = time.time() if t_start is None else t_start
    cell = load_cell(workload, data_root)
    runner = resolve(RUNNERS[cell["traffic"]["kind"]])
    workdir = os.path.join(REPO, ".cache", "benchmarks")
    os.makedirs(workdir, exist_ok=True)
    sandbox = Sandbox(os.path.join(workdir, workload))
    try:
        env = child_env(expect_platform, cell["chips"])
        env["DLROVER_TPU_SOCKET_DIR"] = sandbox.socks
        env["PYTHONPATH"] = cell["files"] + os.pathsep + env["PYTHONPATH"]
        ctx = runner(
            cell, int(seed), float(seconds), bool(trace), expect_platform,
            sandbox, env,
        )
    finally:
        sandbox.close()
    ctx["end_to_end"]["setup_s"] = ctx["window"][0] - t_start
    ctx["cell"] = cell
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = resolve(m["reader"])(ctx, **m.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            value = ctx["end_to_end"].get(m["name"])
            require(
                value is not None,
                f"end-to-end metric {m['name']} could not be taken: "
                + ctx.get("why_missing", "the window held too little"),
            )
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = device_object(
        ctx["device_report"], ctx.get("memory_peak_bytes"),
        expect_platform, cell["chips"],
    )
    line = {
        "correct": bool(ctx["correct"]),
        "attempted": int(ctx["attempted"]),
        "failed": int(ctx["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        reduced = ctx.get("trace")
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
    line["notes"] = ctx.get("notes", [])
    # each number the run's ``correct`` compared, beside its limit
    line["compared"] = ctx.get("compared", {})
    return line
