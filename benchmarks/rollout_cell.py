"""Runner of the ``rollout`` traffic kind: one ``ServingEngine`` with one
replica subprocess, driven by a closed loop of clients.

The engine call and the per-request checks are copied from
``chip_smoke.py``'s ``_serve_leg`` (PR 21, proven on the chip there).
This process is the serving PARENT: it never initialises a JAX backend;
the replica it spawns owns the chip, and the reference check runs in a
child of its own after the replica has exited.

**What a reply may carry for the reference check (the contract a
program with a router is built to; ISSUE 36).**  Beside ``tokens`` and
``logprobs`` a reply's ``result`` MAY hold ``per_token``: a dict ``name
-> array`` whose first axis is the request's positions
(``result["tokens"].size``, prompt and answer).  Row ``j`` is what the
program decided while it COMPUTED position ``j`` — for a router, the
experts it sent that position to, ``[positions, layers, k]`` — by the
prefill program for a prompt's positions as by the decode program for
the answer's; a position never computed, such as the last new token,
holds -1 in an integer array and NaN in a float one.  The harness asks
the engine for nothing new: a program that has such arrays returns them
whenever logprobs are captured.  ``reference_check`` writes each name
to ``sample.npz`` as ``served_<name>`` ``[n, max_seq_len, ...]``, padded
the same way, and a family with ``token_logprobs_forced``
(``family_dense.py``, point 4) is forced onto them.  A reply without
the key adds nothing: ``sample.npz`` is then the file it always was.
"""

import contextlib
import json
import math
import os
import random
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

import metrics as M
from harness import BENCH, attach_trace, family, require, tail


def stratum_length(spec, u):
    """The clipped lognormal's length at quantile ``u`` (0 < u < 1)."""
    x = math.exp(
        math.log(spec["median"])
        + spec["sigma"] * statistics.NormalDist().inv_cdf(u)
    )
    return int(min(max(round(x), spec["min"]), spec["max"]))


class RequestStream:
    """The run's requests, drawn from ``seed`` pass by pass for as long
    as they are taken.  A pass is ``strata`` requests: for prompt length
    and for answer length, one draw from each of ``strata`` equal slices
    of the traffic file's clipped lognormal, the two paired and ordered
    at random.  So every seed draws its own lengths, and every stretch
    of any seed's stream holds the whole distribution: what the seed
    changes is which length within a slice, the pairing and the order —
    not how much work a window holds.  Prompt tokens are uniform."""

    def __init__(self, traffic, seed, vocab_size):
        self._t, self._vocab = traffic, vocab_size
        self._rng, self._np = random.Random(seed), np.random.default_rng(seed)
        self._ready, self._taken, self._lock = [], 0, threading.Lock()

    def _lengths(self, spec):
        n = self._t["strata"]
        out = [
            stratum_length(spec, (i + max(self._rng.random(), 1e-9)) / n)
            for i in range(n)
        ]
        self._rng.shuffle(out)
        return out

    def _request(self, prompt_len, max_new):
        idx, self._taken = self._taken, self._taken + 1
        return dict(
            idx=idx, max_new=max_new,
            prompt=self._np.integers(
                0, self._vocab, size=prompt_len
            ).astype(np.int32),
        )

    def take(self):
        with self._lock:
            if not self._ready:
                self._ready = list(zip(
                    self._lengths(self._t["prompt_len"]),
                    self._lengths(self._t["max_new"]),
                ))
            return self._request(*self._ready.pop())

    def take_warmup(self):
        """A request of the traffic file's fixed warm-up shape: set-up
        does the same work whatever the seed."""
        with self._lock:
            w = self._t["warmup"]
            return self._request(w["prompt_len"], w["max_new"])


@contextlib.contextmanager
def environment(env):
    """The engine builds its replica's environment from ``os.environ``."""
    old = dict(os.environ)
    os.environ.clear()
    os.environ.update(env)
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(old)


class ClosedLoop:
    """``clients`` threads, each submitting its next request when its
    last one completes; all times on this process's wall clock."""

    def __init__(self, engine, take, clients, limit=None):
        """``take()`` gives the next request.  ``limit``: submit that
        many and end (the warm-up); None: until ``stop``."""
        self._engine, self._take_next = engine, take
        self._left, self._lock = limit, threading.Lock()
        self._stop = threading.Event()
        self.done, self.errors = [], []
        self._threads = [
            threading.Thread(target=self._client, name=f"client-{i}")
            for i in range(clients)
        ]

    def _take(self):
        with self._lock:
            if self._left is not None:
                if self._left == 0:
                    return None
                self._left -= 1
        return self._take_next()

    def _client(self):
        while not self._stop.is_set():
            req = self._take()
            if req is None:
                return
            try:
                t0 = time.time()
                rid = self._engine.submit(
                    req["prompt"], max_new=req["max_new"], seed=req["idx"]
                )
                res = self._engine.result(rid, timeout=300.0)
                t1 = time.time()
            except Exception as e:  # a failed request is counted, not hidden
                self.errors.append(f"request {req['idx']}: {e!r}")
                continue
            row = dict(
                req, req_id=rid, submit=t0, done=t1, result=res,
                new_tokens=int(res["new_tokens"]),
            )
            with self._lock:
                self.done.append(row)

    def start(self):
        for t in self._threads:
            t.start()

    def stop(self, timeout=120.0, drain=False):
        """No new submissions (``drain``: none after the ``limit``-th);
        every request in flight is awaited."""
        if not drain:
            self._stop.set()
        for t in self._threads:
            t.join(timeout)
        require(
            not any(t.is_alive() for t in self._threads),
            "a client was still waiting for its reply",
        )


def check_request(row):
    """One reply: the prompt echoed, exactly ``max_new`` new tokens,
    as many finite logprobs <= 0."""
    r, p, n = row["result"], row["prompt"], row["max_new"]
    return (
        r["new_tokens"] == n
        and r["tokens"].size == p.size + n
        and bool((r["tokens"][: p.size] == p).all())
        and r["logprobs"].size == n
        and all(math.isfinite(x) and x <= 0 for x in r["logprobs"])
    )


def write_sample(path, sample, width):
    """``sample.npz`` of the served requests ``sample``, every array
    padded to ``width`` positions: ``reference_check.py``'s input.  Each
    array a reply holds under ``per_token`` rides as ``served_<name>``
    (this module's docstring); -1 or NaN pads it, as it marks a position
    the program never computed."""
    tokens = np.zeros((len(sample), width), np.int32)
    logprobs = np.full((len(sample), width), np.nan, np.float32)
    served = {}
    for i, r in enumerate(sample):
        res = r["result"]
        tokens[i, : res["tokens"].size] = res["tokens"]
        logprobs[i, : r["max_new"]] = res["logprobs"]
        for name, rows in res.get("per_token", {}).items():
            rows = np.asarray(rows)
            require(
                rows.dtype.kind in "if"
                and rows.shape[:1] == (res["tokens"].size,),
                f"per_token[{name!r}] of request {r['idx']}: {rows.dtype} "
                f"{rows.shape} is not one int or float row a position "
                f"({res['tokens'].size})",
            )
            if name not in served:
                served[name] = np.full(
                    (len(sample), width) + rows.shape[1:],
                    -1 if rows.dtype.kind == "i" else np.nan, rows.dtype,
                )
            served[name][i, : rows.shape[0]] = rows
    np.savez(
        path, tokens=tokens, logprobs=logprobs,
        prompt_len=np.array([r["prompt"].size for r in sample]),
        new_tokens=np.array([r["max_new"] for r in sample]),
        **{f"served_{name}": a for name, a in served.items()},
    )


def reference_check(cell, seed, rows, sandbox, env, expect_platform, notes,
                    compared):
    """The plain reference over a seeded sample of the served requests,
    in a child process (this one must stay off the backend); each number
    it holds to a limit of the traffic file goes into ``compared``: the
    largest difference of an answer token's logprob (``logprob_tol``)
    and, where the family forces the reference onto the served side's
    routing, the largest slack of a served choice under the reference's
    own scores (``routing_slack_max``; no default)."""
    t = cell["traffic"]
    sample = random.Random(seed).sample(
        sorted(rows, key=lambda r: r["idx"]), min(t["reference_sample"],
                                                  len(rows))
    )
    path = os.path.join(sandbox.run_dir, "sample.npz")
    out = os.path.join(sandbox.run_dir, "reference.json")
    write_sample(path, sample, t["max_seq_len"])
    proc = sandbox.popen(
        [sys.executable, os.path.join(BENCH, "reference_check.py"),
         cell["config_path"], str(seed), path, out, expect_platform],
        env, "reference.log",
    )
    try:
        rc = proc.wait(timeout=t["reference_timeout_s"])
    except subprocess.TimeoutExpired:
        rc = "timeout"
    if rc != 0:
        notes.append(
            f"reference check exited {rc}: "
            + tail(os.path.join(sandbox.run_dir, "reference.log"), 600)
        )
        return False
    got = M.read_jsonl(out)[0]
    note = (
        f"served logprobs vs float32 reference over {got['compared']} "
        f"tokens of {len(sample)} requests: max |diff| "
        f"{got['max_abs_diff']:.4f} (tolerance {t['logprob_tol']})"
    )
    compared["logprob_max_abs_diff"] = {
        "value": got["max_abs_diff"], "limit": t["logprob_tol"]
    }
    ok = got["max_abs_diff"] <= t["logprob_tol"]
    if "max_routing_slack" in got:  # the family forced the reference
        limit = t.get("routing_slack_max")
        note += (
            f"; the reference forced onto the served routing at "
            f"{got['routed_positions']} positions, "
            f"{got['positions_off_own_topk']} of them off its own top-k: "
            f"max slack {got['max_routing_slack']:.4f} "
            + ("(the traffic file has no key 'routing_slack_max')"
               if limit is None else f"(limit {limit})")
        )
        compared["routing_slack_max"] = {
            "value": got["max_routing_slack"], "limit": limit
        }
        ok = ok and limit is not None and got["max_routing_slack"] <= limit
    notes.append(note)
    return ok


def run_rollout(cell, seed, seconds, trace, expect_platform, sandbox, env):
    from dlrover_tpu.rl.generation_service import ServingEngine

    t, cfg = cell["traffic"], cell["config"]
    run_dir = sandbox.run_dir
    events = os.path.join(run_dir, "events.jsonl")
    env = dict(
        env,
        DLROVER_TPU_EVENTS_FILE=events,
        DLROVER_TPU_PAGED_KERNEL=t["paged_kernel"],
    )
    stream = RequestStream(t, seed, cfg["vocab_size"])
    notes, t_run = [], time.time()
    name = f"bm-{os.getpid()}"
    sandbox.own_shm(name)  # the engine's segments and rings carry it
    with environment(env):
        engine = ServingEngine(
            "benchmarks.serve_factory:factory",
            max_new_tokens=t["max_new"]["max"],
            temperature=t["temperature"],
            factory_kwargs=dict(
                family(cfg).model_kwargs(cfg, t["max_seq_len"]),
                dtype="bfloat16",
                bench=dict(
                    config=cfg, seed=seed, run_dir=run_dir,
                    trace_s=t["trace_s"],
                ),
            ),
            name=name,
            num_replicas=1,
            max_slots=t["max_slots"],
            block_size=t["block_size"],
            num_blocks=t["num_blocks"],
            max_seq_len=t["max_seq_len"],
            prefill_chunk=t["prefill_chunk"],
            start_timeout=t["setup_timeout_s"],
            capture_logprobs=True,
        )
        try:
            # warm-up: every program the traffic uses compiles (or loads
            # from the cache) here; their shapes do not depend on a
            # request's lengths, so one fixed shape a lane warms them all
            t_up = time.time()
            warmup = ClosedLoop(
                engine, stream.take_warmup, t["clients"],
                limit=t["warmup"]["requests"],
            )
            warmup.start()
            warmup.stop(timeout=t["setup_timeout_s"], drain=True)
            require(not warmup.errors, f"warm-up failed: {warmup.errors}")
            # ramp: the closed loop runs into its steady state before the
            # window opens, so the window starts with every lane at work
            loop = ClosedLoop(engine, stream.take, t["clients"])
            notes.append(
                f"set-up: engine up in {t_up - t_run:.1f} s, warm-up "
                f"{time.time() - t_up:.1f} s, ramp {t['ramp_s']} s"
            )
            loop.start()
            time.sleep(t["ramp_s"])
            t_open = time.time()
            if trace:
                time.sleep(seconds / 2)
                with open(os.path.join(run_dir, "trace_go"), "w"):
                    pass
            time.sleep(max(t_open + seconds - time.time(), 0))
            loop.stop()
            with open(os.path.join(run_dir, "stop"), "w"):
                pass
            deadline = time.time() + 30
            while not [
                r for r in M.read_jsonl(os.path.join(run_dir,
                                                     "replica.jsonl"))
                if r["kind"] == "memory"
            ]:
                require(time.time() < deadline, "no memory row")
                time.sleep(0.05)
        finally:
            engine.close()
    from dlrover_tpu.common.jax_env import backend_initialized

    window = (t_open, t_open + seconds)
    rows = warmup.done + loop.done
    spans = M.read_spans(events)
    replica = M.read_jsonl(os.path.join(run_dir, "replica.jsonl"))
    reports = [s["labels"] for s in M.named(spans, "device_report")]
    require(reports, "the replica reported no device")
    inside = M.completed_in(loop.done, window)
    bad = [r["idx"] for r in rows if not check_request(r)]
    served = sorted(
        int(s["labels"]["req_id"]) for s in M.named(spans, "serve_request")
    )
    counts = next(
        (json.loads(r["compile_counts"]) for r in reports
         if "compile_counts" in r), {},
    )
    compared = {}
    checks = {
        "the parent stayed off the JAX backend": not backend_initialized(),
        "every reply whole": not bad,
        "no request failed": not loop.errors,
        "each request served exactly once": (
            served == sorted(r["req_id"] for r in rows)
        ),
        "decode compiled once": counts.get("decode") == 1,
        "paged backend as asked": (
            reports[0].get("kernel_backend") == t["paged_kernel"]
        ),
        "served logprobs match the reference": reference_check(
            cell, seed, inside or rows, sandbox, env, expect_platform, notes,
            compared,
        ),
    }
    notes.extend(f"FAILED: {k}" for k, ok in checks.items() if not ok)
    notes.extend(loop.errors[:3])
    steps = [
        s["end"] - s["start"] for s in M.named(spans, "serve_step")
        if window[0] <= s["start"] <= window[1]
    ]
    notes.append(
        f"window: {len(inside)} requests completed with "
        f"{sum(r['new_tokens'] for r in inside)} new tokens after prompts "
        f"of {sum(r['prompt'].size for r in inside)}; {len(steps)} "
        f"scheduler steps took {sum(steps):.2f} s"
    )
    ctx = {
        "requests": [
            {k: r[k] for k in ("idx", "req_id", "submit", "done",
                               "new_tokens")}
            for r in loop.done
        ],
        "spans": spans,
        "window": window,
        "device_report": reports[0],
        "memory_peak_bytes": next(
            (r["memory_peak_bytes"] for r in replica
             if r["kind"] == "memory"), None,
        ),
        "notes": notes,
        "attempted": len(inside) + len(loop.errors),
        "failed": len(loop.errors)
        + sum(1 for r in inside if r["idx"] in bad),
        "correct": all(checks.values()),
        "compared": compared,
        "end_to_end": {
            "rollout_tokens_per_s": M.rollout_tokens_per_s(
                loop.done, window
            ),
        },
        "why_missing": "no request completed inside the window",
    }
    if trace:
        attach_trace(ctx, os.path.join(run_dir, "trace"))
    return ctx
