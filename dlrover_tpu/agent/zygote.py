"""Pre-fork worker zygote: restart workers without re-paying imports.

Restart-to-first-step latency IS goodput loss under preemption, and on
a 1-core TPU-VM the dominant fixed cost of a fresh worker is the
Python/jax import chain (~3-4 s) that a restart repays on every
incarnation.  The reference stack restarts workers through torchelastic
``subprocess`` spawn and eats that cost each time
(``dlrover/python/elastic_agent/torch/training.py:582`` restart path);
here the agent instead keeps a **zygote** process alive — started once,
with the heavy modules pre-imported but NO jax backend initialized —
and forks each worker incarnation from it.  A fork inherits the warm
``sys.modules``, so a restarted worker is compute-ready in the time it
takes to initialize the backend and re-join the coordinator.

Safety rules baked in:

- the zygote NEVER touches ``jax.devices()``/arrays — a live backend
  (TPU client, threadpools) does not survive ``fork``; import-only is
  fork-safe.
- the zygote is single-threaded (reaping is polled between socket
  requests, no SIGCHLD handler, no reaper thread), so a forked child
  cannot inherit a lock held by a background thread.
- env vars that jax captures at import time (``JAX_PLATFORMS``,
  compilation-cache settings) are re-applied to ``jax.config`` in the
  child when the spawn env disagrees with the zygote's import-time
  value.

The agent talks to the zygote over a length-prefixed pickled unix
socket (the repo's standard local IPC frame, ``common/multi_process``);
``ZygotePool`` exposes Popen-shaped handles so the agent's monitor loop
is oblivious to how a worker was spawned, and falls back to plain
``subprocess`` spawn whenever the zygote is unavailable.
"""

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import (
    _recv_msg,
    _send_msg,
    _socket_path,
)

# modules worth pre-importing: the jax stack plus this framework's
# worker-side entry surface (all read env at call time, not import time)
DEFAULT_PRELOAD = (
    "jax",
    "jax.numpy",
    "optax",
    "dlrover_tpu.trainer.elastic",
)

# jax reads these env vars once at import; a forked child whose spawn
# env differs must push the new value into jax.config explicitly
_JAX_ENV_CONFIG = {
    "JAX_PLATFORMS": "jax_platforms",
    "JAX_COMPILATION_CACHE_DIR": "jax_compilation_cache_dir",
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": (
        "jax_persistent_cache_min_compile_time_secs"
    ),
}


def _exit_code(status: int) -> int:
    """waitpid status -> Popen-style returncode (negative signal)."""
    if os.WIFSIGNALED(status):
        return -os.WTERMSIG(status)
    if os.WIFEXITED(status):
        return os.WEXITSTATUS(status)
    return 1


def exit_record_dir(sock_path: str) -> str:
    return sock_path + ".exits"


def _record_exit(exit_dir: str, pid: int, code: int):
    """Atomically record a child's own exit code: the fallback truth
    source when the zygote (and its waitpid bookkeeping) is gone.  A
    signal-killed child writes nothing — absence means abnormal."""
    try:
        tmp = os.path.join(exit_dir, f".{pid}.tmp")
        with open(tmp, "w") as f:
            f.write(str(code))
        os.rename(tmp, os.path.join(exit_dir, str(pid)))
    except OSError:
        pass


def read_exit_record(exit_dir: str, pid: int) -> Optional[int]:
    try:
        with open(os.path.join(exit_dir, str(pid))) as f:
            return int(f.read().strip())
    except (OSError, ValueError):
        return None


def _fixup_jax_config(spawn_env: Dict[str, str]):
    """Align jax.config with the CHILD's env for import-time-captured
    settings (no-op when jax is not preloaded)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    for env_key, cfg_key in _JAX_ENV_CONFIG.items():
        if env_key not in spawn_env:
            continue
        value: object = spawn_env[env_key]
        if cfg_key == "jax_persistent_cache_min_compile_time_secs":
            try:
                value = float(value)  # config is numeric
            except ValueError:
                continue
        # not best effort: a fork left on the zygote's platform or
        # cache directory would run on the wrong device or never hit
        jax.config.update(cfg_key, value)


def _run_child(argv: Sequence[str], env: Dict[str, str]) -> int:
    """Become the worker: runs in the forked child, never returns to
    the server loop (caller os._exit()s with the return value)."""
    import runpy

    os.environ.clear()
    os.environ.update(env)
    _fixup_jax_config(env)
    # the zygote ignores nothing special, but inherited dispositions
    # must not leak into trainers that install their own handlers
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    signal.signal(signal.SIGINT, signal.SIG_DFL)
    if argv and argv[0] == "-m":
        sys.argv = list(argv[1:])
        target, mode = argv[1], "module"
    else:
        sys.argv = list(argv)
        target, mode = argv[0], "path"
    try:
        if mode == "module":
            runpy.run_module(
                target, run_name="__main__", alter_sys=True
            )
        else:
            runpy.run_path(target, run_name="__main__")
        return 0
    except SystemExit as e:
        code = e.code
        if code is None:
            return 0
        return code if isinstance(code, int) else 1
    except BaseException:  # noqa: BLE001 - worker crash surface
        import traceback

        traceback.print_exc()
        return 1


class ZygoteServer:
    """Single-threaded fork server (run via ``python -m
    dlrover_tpu.agent.zygote``)."""

    def __init__(self, sock_name: str, preload: Sequence[str]):
        self._path = _socket_path(sock_name)
        if os.path.exists(self._path):
            os.unlink(self._path)
        self._listener = socket.socket(
            socket.AF_UNIX, socket.SOCK_STREAM
        )
        self._listener.bind(self._path)
        self._listener.listen(4)
        self._listener.settimeout(0.2)
        self._exit_codes: Dict[int, int] = {}
        self._live: set = set()
        self._conn: Optional[socket.socket] = None
        # children record their OWN exit code here (exit_record_dir):
        # if the zygote dies, the agent can still distinguish a clean
        # worker completion from a crash instead of failing the rank
        self._exit_dir = exit_record_dir(self._path)
        os.makedirs(self._exit_dir, exist_ok=True)
        for stale in os.listdir(self._exit_dir):
            try:
                os.unlink(os.path.join(self._exit_dir, stale))
            except OSError:
                pass
        self._preload(preload)

    def _preload(self, modules: Sequence[str]):
        import importlib

        t0 = time.time()
        for mod in modules:
            try:
                importlib.import_module(mod)
            except Exception as e:  # noqa: BLE001
                print(
                    f"zygote: preload {mod} failed: {e}",
                    file=sys.stderr,
                    flush=True,
                )
        # a live backend would not survive fork (and on a TPU the
        # zygote would hold the chip its workers need) — refuse to serve
        from dlrover_tpu.common.jax_env import backend_initialized

        if backend_initialized():
            raise RuntimeError(
                "zygote preload initialized a jax backend; "
                "remove the offending preload module"
            )
        print(
            f"zygote: ready ({len(modules)} modules in "
            f"{time.time() - t0:.1f}s)",
            file=sys.stderr,
            flush=True,
        )

    def _reap(self):
        while True:
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                return
            if pid == 0:
                return
            self._live.discard(pid)
            self._exit_codes[pid] = _exit_code(status)

    def _spawn(self, argv: Sequence[str], env: Dict[str, str]) -> int:
        pid = os.fork()
        if pid != 0:
            # the kernel recycles pids: stale exit state recorded for a
            # PREVIOUS child under this pid would make poll report the
            # old exit code for the live worker
            self._exit_codes.pop(pid, None)
            try:
                os.unlink(os.path.join(self._exit_dir, str(pid)))
            except OSError:
                pass
        if pid == 0:
            code = 1
            try:
                # drop BOTH server fds: a worker holding the accepted
                # agent connection would keep it from seeing EOF after
                # a zygote crash (poll RPCs would block to timeout)
                self._listener.close()
                if self._conn is not None:
                    self._conn.close()
                code = _run_child(argv, env)
            finally:
                code = code if isinstance(code, int) else 1
                _record_exit(self._exit_dir, os.getpid(), code)
                os._exit(code)
        self._live.add(pid)
        return pid

    def _handle(self, req) -> Tuple:
        cmd = req.get("cmd")
        if cmd == "spawn":
            # the entrypoint always starts with a python executable;
            # the fork IS the interpreter, so drop it
            argv = list(req["argv"])
            if argv and os.path.basename(argv[0]).startswith("python"):
                argv = argv[1:]
            return ("ok", self._spawn(argv, req["env"]))
        if cmd == "poll":
            self._reap()
            return ("ok", self._exit_codes.get(req["pid"]))
        if cmd == "ping":
            return ("ok", os.getpid())
        if cmd == "shutdown":
            return ("bye", None)
        return ("err", f"unknown cmd {cmd!r}")

    def serve_forever(self):
        try:
            while True:
                self._reap()
                if self._conn is None:
                    try:
                        self._conn, _ = self._listener.accept()
                        self._conn.settimeout(0.2)
                    except socket.timeout:
                        continue
                try:
                    req = _recv_msg(self._conn)
                except socket.timeout:
                    continue
                except (ConnectionError, EOFError, OSError):
                    self._conn.close()
                    self._conn = None
                    continue
                resp = self._handle(req)
                try:
                    _send_msg(self._conn, resp)
                except OSError:
                    self._conn.close()
                    self._conn = None
                if resp[0] == "bye":
                    return
        finally:
            if self._conn is not None:
                self._conn.close()
            self._listener.close()
            try:
                os.unlink(self._path)
            except OSError:
                pass


class ZygoteHandle:
    """Popen-shaped handle for a zygote-forked worker."""

    def __init__(self, pid: int, pool: "ZygotePool"):
        self.pid = pid
        self._pool = pool
        self.returncode: Optional[int] = None

    def poll(self) -> Optional[int]:
        if self.returncode is not None:
            return self.returncode
        try:
            self.returncode = self._pool._rpc(
                {"cmd": "poll", "pid": self.pid}
            )
        except (ConnectionError, OSError):
            # zygote gone: its children were reparented to init and
            # keep running.  The child's own exit record is consulted
            # FIRST: after a clean exit the kernel may recycle the pid
            # for an unrelated process, and a liveness probe alone
            # would then report the dead rank as running forever
            # (ADVICE-r4).  A signal death writes no record; only then
            # does the probe decide alive vs ORPHAN_EXIT.
            recorded = read_exit_record(
                self._pool.exit_dir, self.pid
            )
            if recorded is not None:
                self.returncode = recorded
            else:
                try:
                    os.kill(self.pid, 0)
                except ProcessLookupError:
                    self.returncode = ZygotePool.ORPHAN_EXIT
        return self.returncode

    def wait(self, timeout: Optional[float] = None) -> int:
        deadline = None if timeout is None else time.time() + timeout
        while True:
            rc = self.poll()
            if rc is not None:
                return rc
            if deadline is not None and time.time() > deadline:
                raise subprocess.TimeoutExpired(
                    f"zygote-worker-{self.pid}", timeout
                )
            time.sleep(0.05)

    def send_signal(self, sig: int):
        if self.poll() is None:
            try:
                os.kill(self.pid, sig)
            except ProcessLookupError:
                pass

    def terminate(self):
        self.send_signal(signal.SIGTERM)

    def kill(self):
        self.send_signal(signal.SIGKILL)


class ZygotePool:
    """Agent-side client; spawns workers through the fork server.

    ``spawn`` transparently falls back to ``subprocess.Popen`` when the
    zygote is missing or broken — worker startup must never fail
    because the LATENCY optimization did.
    """

    # sentinel returncode when the zygote died and took the exit
    # status with it (nonzero -> the agent treats the worker as failed)
    ORPHAN_EXIT = -257

    def __init__(
        self,
        name: str = "zygote",
        preload: Sequence[str] = DEFAULT_PRELOAD,
        start_timeout: float = 120.0,
    ):
        self._sock_name = name
        self._preload = tuple(preload)
        self._start_timeout = start_timeout
        self._proc: Optional[subprocess.Popen] = None
        self._sock: Optional[socket.socket] = None

    @property
    def exit_dir(self) -> str:
        return exit_record_dir(_socket_path(self._sock_name))

    # ----------------------------------------------------------- server
    def start(
        self, env: Optional[Dict[str, str]] = None, wait: bool = False
    ) -> bool:
        """Launch the fork server with the agent's worker base env.

        Non-blocking by default: preload takes seconds and the FIRST
        worker launch shouldn't wait on it — ``spawn`` quietly falls
        back to plain Popen until the zygote answers.  ``wait=True``
        blocks until ready (tests)."""
        env = dict(env or os.environ)
        # the server must import dlrover_tpu regardless of how the
        # caller made it importable (sys.path edits don't inherit)
        import dlrover_tpu

        pkg_root = os.path.dirname(
            os.path.dirname(os.path.abspath(dlrover_tpu.__file__))
        )
        parts = env.get("PYTHONPATH", "").split(os.pathsep)
        if pkg_root not in parts:
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in [pkg_root, *parts] if p
            )
        self._proc = subprocess.Popen(  # noqa: S603
            [
                sys.executable,
                "-m",
                "dlrover_tpu.agent.zygote",
                "--socket",
                self._sock_name,
                "--preload",
                ",".join(self._preload),
            ],
            env=env,
        )
        if not wait:
            return True
        deadline = time.time() + self._start_timeout
        while time.time() < deadline:
            if self._proc.poll() is not None:
                logger.warning(
                    "zygote exited %s during startup",
                    self._proc.returncode,
                )
                return False
            try:
                if self._rpc({"cmd": "ping"}):
                    return True
            except (ConnectionError, OSError):
                time.sleep(0.2)
        logger.warning("zygote did not come up; using plain spawn")
        self.close()
        return False

    def _connect(self) -> socket.socket:
        if self._sock is None:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(10.0)
            s.connect(_socket_path(self._sock_name))
            self._sock = s
        return self._sock

    def _rpc(self, req):
        try:
            sock = self._connect()
            _send_msg(sock, req)
            status, result = _recv_msg(sock)
        except (ConnectionError, OSError, socket.timeout):
            if self._sock is not None:
                self._sock.close()
                self._sock = None
            raise ConnectionError("zygote unreachable")
        if status == "err":
            raise RuntimeError(result)
        return result

    @property
    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    # ----------------------------------------------------------- spawn
    def spawn(self, argv: List[str], env: Dict[str, str]):
        """Fork a worker (zygote) or Popen it (fallback); returns a
        Popen-shaped handle either way."""
        if self.alive:
            try:
                pid = self._rpc(
                    {"cmd": "spawn", "argv": argv, "env": env}
                )
                return ZygoteHandle(pid, self)
            except ConnectionError:
                # normal during the preload window right after start()
                logger.info("zygote not ready; plain spawn")
            except RuntimeError as e:
                logger.warning(
                    "zygote spawn failed (%s); plain spawn", e
                )
        return subprocess.Popen(argv, env=env)  # noqa: S603

    def close(self):
        if self._sock is not None:
            try:
                _send_msg(self._sock, {"cmd": "shutdown"})
                _recv_msg(self._sock)
            except (ConnectionError, OSError, socket.timeout, EOFError):
                pass
            self._sock.close()
            self._sock = None
        if self._proc is not None:
            if self._proc.poll() is None:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
                    self._proc.wait()
            self._proc = None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="dlrover-tpu-zygote")
    parser.add_argument("--socket", required=True)
    parser.add_argument(
        "--preload", default=",".join(DEFAULT_PRELOAD)
    )
    args = parser.parse_args(argv)
    preload = [m for m in args.preload.split(",") if m]
    server = ZygoteServer(args.socket, preload)
    server.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
