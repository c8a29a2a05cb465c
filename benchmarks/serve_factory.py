"""The serving replica's model factory for the ``rollout`` cells — user
code of the serving plane, named in the engine's spec like any factory.

The model (``forward_fn``, ``cfg``) is the program's own, through the
``serving_parts`` of the family module the configuration file names, at
the widths the benchmark gives it.  Two things are the benchmark's: the
weights — made ON THE DEVICE, in one jitted call, from the run's seed
by the family's ``seeded_params``, so the parent neither generates
5.8 GB on the host nor publishes them through shm — and a side thread
that does what only the process holding the chip can do: read its
memory peak and, in a traced run, open a ``jax.profiler`` window when
the runner asks.
"""

import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def _side_thread(run_dir, trace_s):
    import metrics
    import onchip

    rows = os.path.join(run_dir, "replica.jsonl")
    traced = False
    while True:
        time.sleep(0.05)
        if (
            not traced
            and os.path.exists(os.path.join(run_dir, "trace_go"))
        ):
            traced = True
            t0 = time.time()
            onchip.start_trace(os.path.join(run_dir, "trace"))
            time.sleep(trace_s)
            onchip.jax.profiler.stop_trace()
            metrics.append_jsonl(
                rows, dict(kind="trace", t0=t0, t1=time.time())
            )
        if os.path.exists(os.path.join(run_dir, "stop")):
            metrics.append_jsonl(
                rows,
                dict(
                    kind="memory",
                    memory_peak_bytes=onchip.memory_peak_bytes(),
                ),
            )
            return


def factory(bench, **model_kwargs):
    import harness

    fam = harness.family(bench["config"])
    parts = fam.serving_parts(**model_kwargs)
    parts["params_template_fn"] = lambda: fam.seeded_params(
        bench["config"], bench["seed"]
    )
    threading.Thread(
        target=_side_thread,
        args=(bench["run_dir"], float(bench.get("trace_s", 3.0))),
        name="bench-side",
        daemon=True,
    ).start()
    return parts
