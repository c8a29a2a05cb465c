"""Unit tests for ``observability/profiler.py``: the trace-server
lifecycle (the peak-FLOPs table is covered in ``test_profiling.py``)."""

from dlrover_tpu.observability import profiler as prof
from dlrover_tpu.observability.profiler import (
    start_profiler_server,
    stop_profiler_server,
)


class TestProfilerServer:
    def test_lifecycle_idempotent_start_and_stop(self, monkeypatch):
        stopped = []

        class FakeServer:
            def stop(self):
                stopped.append(True)

        calls = []

        def fake_start(port):
            calls.append(port)
            return FakeServer()

        import jax

        monkeypatch.setattr(jax.profiler, "start_server", fake_start)
        stop_profiler_server()  # clean slate
        s1 = start_profiler_server(9911)
        s2 = start_profiler_server(9911)
        assert s1 is s2  # second start returns the running server
        assert calls == [9911]
        stop_profiler_server()
        assert stopped == [True]
        stop_profiler_server()  # no-op, no double stop
        assert stopped == [True]
        # a fresh start after stop builds a new server
        s3 = start_profiler_server(9912)
        assert s3 is not None and s3 is not s1
        stop_profiler_server()

    def test_start_failure_returns_none(self, monkeypatch):
        import jax

        def boom(port):
            raise RuntimeError("no profiler here")

        monkeypatch.setattr(jax.profiler, "start_server", boom)
        stop_profiler_server()
        assert start_profiler_server(9913) is None
        stop_profiler_server()

    def test_module_holds_the_reference(self, monkeypatch):
        """The server object must be owned by the module, not the
        caller — jax stops the server when the object is collected,
        so a dropped return value used to stop it at GC whim."""
        import jax

        class FakeServer:
            pass

        monkeypatch.setattr(
            jax.profiler, "start_server", lambda port: FakeServer()
        )
        stop_profiler_server()
        start_profiler_server(9914)
        assert prof._profiler_server is not None
        stop_profiler_server()
        assert prof._profiler_server is None
