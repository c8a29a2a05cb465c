"""What only the process that holds the chip can do, shared by the
benchmark's two pieces of code that run inside such a process (the
training worker's callback and the serving replica's side thread)."""

import jax


def memory_peak_bytes():
    """Peak bytes in use on the fullest local device, or None where the
    backend does not report it."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    return max((p for p in peaks if p is not None), default=None)


def start_trace(trace_dir):
    """Open a ``jax.profiler`` window without the Python tracer (it slows
    the host that the window is there to observe)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
