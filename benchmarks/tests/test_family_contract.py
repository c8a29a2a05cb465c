"""The contract between the program and the benchmark, per configuration
of ``BENCHMARK.json``, on the CPU in seconds: the family the file names
resolves; its ``model_kwargs`` build the program's model object; at a
tiny override of the sizes the program's forward and the family's plain
reference agree per token on the family's seeded weights; and the
family's ``total_params`` is the size of that parameter tree.  A program
PR that renames what a family imports fails here, not on the chip.

Each check runs in an interpreter of its own: it initialises a JAX
backend, and the cell rehearsals of this directory, which may share a
pytest process with it, check that THEIR process never does.

(ISSUE 26 asked for this under ``tests/``, where tier-1 runs it; a PR of
the benchmark's kind adds no file outside ``benchmarks/``, so it is kept
here and PERF.md section 7 lists the move.)
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness

with open(os.path.join(harness.REPO, "BENCHMARK.json")) as _f:
    CONFIGS = json.load(_f)["configs"]

#: sizes that keep a family's shape (heads per KV head, gated MLP, untied
#: head) and fit a CPU test; every other key is the configuration's own
TINY = {
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "vocab_size": 384, "num_attention_heads": 8,
}
SEQ = 24
#: float32 weights, float32 compute on both sides; what differs is the
#: order of the sums (the program scans over layers and fuses its loss)
TOL = 2e-4


def tiny_cfg(name):
    entry = next(c for c in CONFIGS if c["name"] == name)
    with open(os.path.join(harness.REPO, entry["file"])) as f:
        cfg = json.load(f)
    ratio = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return dict(cfg, **TINY, num_key_value_heads=max(8 // ratio, 1))


@pytest.mark.parametrize("config", [c["name"] for c in CONFIGS])
@pytest.mark.parametrize("check", [
    "the_family_resolves_and_provides_its_parts",
    "program_and_reference_agree_per_token",
])
def test_contract(check, config):
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), check, config],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]


def the_family_resolves_and_provides_its_parts(cfg):
    fam = harness.family(cfg)
    for name in (
        "model_kwargs", "train_parts", "serving_parts", "seeded_params",
        "token_logprobs", "matmul_params", "total_params",
        "train_flops_per_token",
    ):
        assert callable(getattr(fam, name)), name
    kwargs = fam.model_kwargs(cfg, SEQ)
    assert json.loads(json.dumps(kwargs)) == kwargs  # rides through JSON
    parts = fam.train_parts(cfg, SEQ)
    assert {"model", "init_params_fn", "loss_fn", "param_axes",
            "forward"} <= set(parts)
    served = fam.serving_parts(**kwargs, dtype="bfloat16")  # as a rollout cell
    assert {"forward_fn", "params_template_fn", "cfg"} <= set(served)
    assert served["cfg"] == parts["model"]


def program_and_reference_agree_per_token(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = harness.family(cfg)
    parts = fam.train_parts(cfg, SEQ)
    params = fam.seeded_params(cfg, 2**31 + 5)
    # the reference's tree IS the program's: same leaves, same shapes
    template = jax.eval_shape(parts["init_params_fn"], jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(
        lambda a: a.shape, params
    ) == jax.tree_util.tree_map(lambda a: a.shape, template)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == fam.total_params(cfg)
    assert fam.matmul_params(cfg) < n
    assert fam.train_flops_per_token(cfg, SEQ) > 6 * fam.matmul_params(cfg)

    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], size=(2, SEQ + 1), dtype=np.int32
    )
    served = fam.serving_parts(**fam.model_kwargs(cfg, SEQ), dtype="float32")
    got = jax.nn.log_softmax(
        served["forward_fn"](params, tokens[:, :-1]).astype(jnp.float32), -1
    )
    got = jnp.take_along_axis(got, tokens[:, 1:, None], -1)[..., 0]
    ref = fam.token_logprobs(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - ref))) < TOL
    # the step program's own loss path against the reference's mean
    loss = parts["loss_fn"](params, {"tokens": tokens})
    loss = loss[0] if isinstance(loss, tuple) else loss
    assert abs(float(loss) + float(jnp.mean(ref))) < 5e-3  # bf16 compute


if __name__ == "__main__":
    globals()[sys.argv[1]](tiny_cfg(sys.argv[2]))
