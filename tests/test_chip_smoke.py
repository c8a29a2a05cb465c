"""``chip_smoke.py`` rehearsed on the CPU at tiny sizes, and its refusals.

The script's contract is checked on the chip by the driver; what the
CPU suite can pin is that the whole of it runs end to end through the
same entry points (launcher, example, ServingEngine, kernels in
interpret mode), that a wrong device or a failing phase can never
produce the final ``"ok": true`` line, and that no parent process in
it touches a JAX backend.
"""

import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from dlrover_tpu.common import jax_env  # noqa: E402

TINY_MODEL_ARGS = ["--dim", "64", "--layers", "2", "--heads", "4"]
TINY_SIZES = {
    "kernels": dict(
        batch=1, seq=128, heads=4, head_dim=8, gqa_kv_heads=2,
        norm_rows=16, dim=128,
        lanes=4, block_size=8, max_blocks=4, num_blocks=32,
        paged_kv_heads=(2,), window=3,
    ),
    "train": dict(
        model_args=TINY_MODEL_ARGS, vocab_size=4096, batch=4, seq=128,
        # the worker must still be training when the SIGKILL lands
        steps=150, snapshot_every=25,
    ),
    "serve": dict(
        model=dict(
            vocab_size=256, dim=64, n_heads=4, n_kv_heads=2, mlp_dim=128,
            n_layers=2, max_seq_len=64,
        ),
        requests=3, prompt_min=8, prompt_max=24, max_new=6,
        max_slots=4, block_size=8, num_blocks=64, max_seq_len=64,
        prefill_chunk=16,
    ),
    "four": dict(
        model_args=TINY_MODEL_ARGS, vocab_size=4096, batch=4, seq=64,
        steps=3,
    ),
}


def _lines(buf):
    return [json.loads(x) for x in buf.getvalue().splitlines() if x]


def boom(sizes, seed, expect_platform, workdir):
    raise RuntimeError("a phase that raises")


def liar(sizes, seed, expect_platform, workdir):
    """A phase whose child claims it ran on the chip's neighbour."""
    return dict(
        device=dict(platform="cpu", device_kind="cpu", device_count=1),
        compile_s=0.0,
        asserted="nothing",
    )


@pytest.mark.heavy
def test_whole_script_on_cpu_at_tiny_sizes(monkeypatch):
    """Rehearsal 1 of the chip run: every one-chip phase, end to end."""
    monkeypatch.delenv("DLROVER_TPU_SOCKET_DIR", raising=False)
    out = io.StringIO()
    rc = chip_smoke.run("cpu", TINY_SIZES, seed=3, out=out)
    lines = _lines(out)
    assert rc == 0, out.getvalue()
    assert [x.get("phase") for x in lines[:-1]] == [
        "kernels", "train", "serve",
    ]
    for line in lines[:-1]:
        assert line["device"]["platform"] == "cpu"
        assert line["seconds"] > 0 and "compile_s" in line
        assert line["asserted"]
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }


@pytest.mark.heavy
def test_four_chip_phase_on_four_virtual_devices(monkeypatch):
    """Rehearsal 2: the sharded step on four virtual CPU devices."""
    monkeypatch.delenv("DLROVER_TPU_SOCKET_DIR", raising=False)
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
    )
    out = io.StringIO()
    rc = chip_smoke.run(
        "cpu", TINY_SIZES, seed=3, phases=chip_smoke.FOUR_CHIP_PHASES,
        out=out,
    )
    lines = _lines(out)
    assert rc == 0, out.getvalue()
    assert [x.get("phase") for x in lines[:-1]] == ["four"]
    assert lines[0]["state_shard_bytes"] * 3 < lines[0]["state_bytes"]
    assert sum(lines[0]["collectives"].values()) > 0
    assert lines[-1]["device"]["count"] == 4


def test_cpu_child_is_refused_when_the_chip_is_expected():
    """What the driver's sandbox run must show: no accelerator, nonzero
    exit, no result line."""
    out = io.StringIO()
    rc = chip_smoke.run(
        "tpu", TINY_SIZES, phases=chip_smoke.ONE_CHIP_PHASES[:1], out=out
    )
    assert rc != 0
    assert '"ok"' not in out.getvalue()


def test_child_result_naming_another_platform_is_refused():
    out = io.StringIO()
    rc = chip_smoke.run("tpu", {}, phases=(("liar", liar),), out=out)
    assert rc != 0
    assert out.getvalue() == ""


def test_raising_phase_fails_the_script():
    out = io.StringIO()
    rc = chip_smoke.run("cpu", {}, phases=(("boom", boom),), out=out)
    assert rc != 0
    assert out.getvalue() == ""


def test_command_line_refuses_the_cpu():
    """``python chip_smoke.py`` as the driver runs it in the sandbox:
    JAX finds no accelerator -> nonzero, no ``"ok": true``."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_script_process_holds_no_backend():
    """A fresh interpreter that runs a phase through ``run`` still has
    no initialised backend afterwards."""
    import subprocess

    code = (
        "import io, sys; sys.path[:0] = [%r, %r]\n"
        "import chip_smoke\n"
        "from dlrover_tpu.common.jax_env import backend_initialized\n"
        "from test_chip_smoke import TINY_SIZES\n"
        "rc = chip_smoke.run('cpu', TINY_SIZES, "
        "phases=chip_smoke.ONE_CHIP_PHASES[:1], out=io.StringIO())\n"
        "assert rc == 0, rc\n"
        "assert not backend_initialized()\n"
        "print('clean')\n" % (REPO, os.path.join(REPO, "tests"))
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "clean" in proc.stdout


class TestCompileCacheHelper:
    def test_environment_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv(jax_env.COMPILE_CACHE_ENV, str(tmp_path))
        assert jax_env.compile_cache_dir() == str(tmp_path)
        env = {jax_env.COMPILE_CACHE_ENV: str(tmp_path)}
        assert jax_env.export_compile_cache(env, "/elsewhere") == str(
            tmp_path
        )
        assert env == {jax_env.COMPILE_CACHE_ENV: str(tmp_path)}

    def test_fixed_in_checkout_path_when_unset(self, monkeypatch):
        monkeypatch.delenv(jax_env.COMPILE_CACHE_ENV, raising=False)
        first, second = jax_env.compile_cache_dir(), jax_env.compile_cache_dir()
        assert first == second == os.path.join(
            REPO, ".cache", "jax_compile"
        )
        env = {}
        assert jax_env.export_compile_cache(env) == first
        assert env[jax_env.COMPILE_CACHE_ENV] == first

    def test_launcher_default_is_the_same_path(self, monkeypatch):
        from dlrover_tpu.trainer import elastic_run

        monkeypatch.delenv(jax_env.COMPILE_CACHE_ENV, raising=False)
        args = elastic_run.parse_args(["train.py"])
        assert args.compile_cache_dir == jax_env.compile_cache_dir()


class TestLauncherRefusesMoreWorkersThanChips:
    """``--nproc_per_node`` the host's chips cannot serve fails at
    launch with a message; it never reaches a worker that would hang."""

    def _probe(self, monkeypatch, stdout):
        import subprocess

        from dlrover_tpu.trainer import elastic_run

        calls = []

        def fake_run(cmd, **kwargs):
            calls.append(cmd)
            return subprocess.CompletedProcess(cmd, 0, stdout, "")

        monkeypatch.setattr(elastic_run.subprocess, "run", fake_run)
        return elastic_run, calls

    def test_two_workers_on_a_one_chip_host(self, monkeypatch):
        elastic_run, calls = self._probe(monkeypatch, "tpu 1\n")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        with pytest.raises(SystemExit, match="--nproc_per_node=1"):
            elastic_run.run(
                elastic_run.parse_args(["--nproc_per_node=2", "t.py"])
            )
        assert len(calls) == 1  # probed in a subprocess, not in-process

    def test_cpu_hosts_and_single_workers_are_not_probed(
        self, monkeypatch
    ):
        elastic_run, calls = self._probe(monkeypatch, "tpu 1\n")
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        elastic_run._check_nproc_fits_host(8)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        elastic_run._check_nproc_fits_host(1)
        assert calls == []


@pytest.mark.heavy
def test_serving_parent_has_no_backend_after_generate():
    """``ServingEngine``'s parent never initialises a JAX backend: a
    fresh interpreter builds an engine, generates, and is still clean."""
    import subprocess

    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from dlrover_tpu.common.jax_env import backend_initialized\n"
        "from dlrover_tpu.rl.generation_service import ServingEngine\n"
        "eng = ServingEngine(\n"
        "    'dlrover_tpu.rl.generation_service:tiny_llama_factory', 4,\n"
        "    temperature=0.0, num_replicas=1, max_slots=2, block_size=8,\n"
        "    num_blocks=32, max_seq_len=32, prefill_chunk=8,\n"
        "    factory_kwargs=dict(vocab_size=64, dim=32, n_layers=1,\n"
        "        n_heads=2, n_kv_heads=1, mlp_dim=64, max_seq_len=32,\n"
        "        dtype='float32'))\n"
        "try:\n"
        "    out = eng.generate(np.arange(12, dtype=np.int32).reshape(2, 6))\n"
        "finally:\n"
        "    eng.close()\n"
        "assert out.shape == (2, 10), out.shape\n"
        "assert not backend_initialized()\n"
        "print('clean')\n" % REPO
    )
    import tempfile

    # AF_UNIX paths are short: pytest's tmp_path can overflow them
    with tempfile.TemporaryDirectory(prefix="cs-") as socks:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, cwd=REPO,
            env=dict(
                os.environ, JAX_PLATFORMS="cpu",
                DLROVER_TPU_SOCKET_DIR=socks,
            ),
        )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "clean" in proc.stdout
