"""The contract between the program and the benchmark, per configuration
of ``BENCHMARK.json``, on the CPU in seconds: the family the file names
resolves and provides its parts; its ``model_kwargs`` build the
program's model object; at a tiny override of the sizes the program's
forward and the family's plain reference agree per token on the
family's seeded weights; and the family's counts are the size of that
parameter tree.  A program PR that renames what a family imports fails
here, not on the chip.

**An appended configuration passes without an edit here** (ISSUE 35;
the cases are those of ``tests/test_benchmark_family_contract.py``, its
tier-1 twin): the tiny sizes of a family are DATA — the numbers of the
configuration under ``tests/tiny/data/configs/`` that names the same
family, which a ``model_config`` PR adds for its CPU rehearsal anyway —
and a family without a training path (``train_parts`` raises
``CellFailed``) skips the training cases instead of failing them.

**A family that forces its reference** (``token_logprobs_forced``,
``family_dense.py`` point 4; ISSUE 36) is held to that function's shape
contract at the tiny sizes, on filler choices built from its
configuration's ``assumed.served_arrays``; a family without it skips
the case.  The tiny tree's own configurations of such families (the
sparse rehearsal, whose program parts do not exist yet) are held to the
same case, so that it runs before a benchmark configuration has one.

Each check runs in an interpreter of its own: it initialises a JAX
backend, and the cell rehearsals of this directory, which may share a
pytest process with it, check that THEIR process never does.
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

TINY_CONFIGS = os.path.join(BENCH, "tests", "tiny", "data", "configs")
sys.path.append(os.path.dirname(TINY_CONFIGS))  # the rehearsal families
SEQ = 24
#: float32 weights, float32 compute on both sides; what differs is the
#: order of the sums (the program scans over layers, chunks its scan)
TOL = 2e-4
SKIPPED = 77  # a check's exit code for "this family has no such path"
CHECKS = [
    "the_family_resolves_and_provides_its_parts",
    "train_parts_build_the_serving_model",
    "the_counts_are_the_tree",
    "program_and_reference_agree_per_token",
    "the_training_loss_is_the_reference_mean",
    "a_forced_reference_returns_two_arrays_of_one_shape",
]


def tiny_sizes(family):
    """The numbers of the tiny tree's ONE configuration of ``family``."""
    named = {}
    for name in sorted(os.listdir(TINY_CONFIGS)):
        with open(os.path.join(TINY_CONFIGS, name)) as f:
            tiny = json.load(f)
        if tiny.get("family") == family:
            named[name] = tiny
    if len(named) != 1:
        # a second file of the family would change, unseen, the sizes at
        # which every configuration of that family is held to its contract
        raise SystemExit(
            f"{sorted(named)} under {TINY_CONFIGS} name the family "
            f"{family!r}: its tiny sizes are the numbers of exactly one file"
        )
    (tiny,) = named.values()
    return {
        k: v for k, v in tiny.items()
        if isinstance(v, (int, float)) and not isinstance(v, bool)
    }


def tiny_cfg(name):
    """The configuration's own file with every SIZE replaced by the
    tiny tree's (a size: a whole number that both files hold); each
    multiplier, eps and theta stays the configuration's own, and so
    does its ratio of query to key/value heads."""
    entry = next(c for c in CONFIGS if c["name"] == name)
    with open(os.path.join(harness.REPO, entry["file"])) as f:
        cfg = json.load(f)
    sizes = {
        k: v for k, v in tiny_sizes(cfg["family"]).items()
        if isinstance(v, int) and isinstance(cfg.get(k), int)
    }
    if {"num_attention_heads", "num_key_value_heads"} <= set(sizes):
        heads = sizes["num_key_value_heads"] * (
            cfg["num_attention_heads"] // cfg["num_key_value_heads"]
        )
        if "head_dim" in sizes or sizes["hidden_size"] % heads == 0:
            sizes["num_attention_heads"] = heads
    return dict(cfg, **sizes)


def run_check(check, config):
    """``config``: a name in ``BENCHMARK.json``, or the path of a tiny
    configuration, which is taken as it is."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), check, config],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    if proc.returncode == SKIPPED:
        pytest.skip(proc.stderr.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-3000:]


@pytest.mark.parametrize("config", [c["name"] for c in CONFIGS])
@pytest.mark.parametrize("check", CHECKS)
def test_contract(check, config):
    run_check(check, config)


def forcing_tiny_configs():
    """The tiny tree's configurations whose family forces (importing a
    family imports neither JAX nor the program)."""
    return [
        name for name in sorted(os.listdir(TINY_CONFIGS))
        if hasattr(
            harness.family(harness.load_json(os.path.join(TINY_CONFIGS, name))),
            "token_logprobs_forced",
        )
    ]


@pytest.mark.parametrize("config", forcing_tiny_configs())
def test_a_forcing_family_of_the_tiny_tree(config):
    run_check(
        "a_forced_reference_returns_two_arrays_of_one_shape",
        os.path.join(TINY_CONFIGS, config),
    )


def train_parts_or_skip(fam, cfg):
    try:
        return fam.train_parts(cfg, SEQ)
    except harness.CellFailed as e:
        print(f"{cfg['family']} has no training path: {e}", file=sys.stderr)
        sys.exit(SKIPPED)


def seeded_batch(cfg):
    import numpy as np

    fam = harness.family(cfg)
    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], size=(2, SEQ + 1), dtype=np.int32
    )
    return fam, fam.seeded_params(cfg, 2**31 + 5), tokens


def the_family_resolves_and_provides_its_parts(cfg):
    fam = harness.family(cfg)
    for name in (
        "model_kwargs", "train_parts", "serving_parts", "seeded_params",
        "token_logprobs", "matmul_params", "total_params",
    ):
        assert callable(getattr(fam, name)), name
    kwargs = fam.model_kwargs(cfg, SEQ)
    assert json.loads(json.dumps(kwargs)) == kwargs  # rides through JSON
    served = fam.serving_parts(**kwargs, dtype="bfloat16")  # as a rollout cell
    assert {"forward_fn", "params_template_fn", "cfg"} <= set(served)
    # the scheduler's side of the contract: K/V geometry as attributes
    for attr in ("n_layers", "n_kv_heads", "head_dim", "dtype"):
        assert hasattr(served["cfg"], attr), attr
    assert served["cfg"].n_layers == cfg["num_hidden_layers"]


def train_parts_build_the_serving_model(cfg):
    fam = harness.family(cfg)
    parts = train_parts_or_skip(fam, cfg)
    assert {"model", "init_params_fn", "loss_fn", "param_axes",
            "forward"} <= set(parts)
    assert callable(fam.train_flops_per_token)
    served = fam.serving_parts(**fam.model_kwargs(cfg, SEQ),
                               dtype="bfloat16")
    assert served["cfg"] == parts["model"]


def the_counts_are_the_tree(cfg):
    import jax

    fam, params, _ = seeded_batch(cfg)
    served = fam.serving_parts(**fam.model_kwargs(cfg, SEQ), dtype="float32")
    # the reference's tree IS the program's: same leaves, same shapes
    template = jax.eval_shape(served["params_template_fn"])
    assert jax.tree_util.tree_map(
        lambda a: a.shape, params
    ) == jax.tree_util.tree_map(lambda a: a.shape, template)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == fam.total_params(cfg)
    assert 0 < fam.matmul_params(cfg) < n


def program_and_reference_agree_per_token(cfg):
    import jax
    import jax.numpy as jnp

    fam, params, tokens = seeded_batch(cfg)
    served = fam.serving_parts(**fam.model_kwargs(cfg, SEQ), dtype="float32")
    with jax.default_matmul_precision("highest"):
        got = jax.nn.log_softmax(
            served["forward_fn"](params, tokens[:, :-1]).astype(jnp.float32),
            -1,
        )
    got = jnp.take_along_axis(got, tokens[:, 1:, None], -1)[..., 0]
    ref = fam.token_logprobs(params, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


def the_training_loss_is_the_reference_mean(cfg):
    import jax.numpy as jnp

    fam, params, tokens = seeded_batch(cfg)
    parts = train_parts_or_skip(fam, cfg)
    ref = fam.token_logprobs(params, tokens, cfg)
    loss = parts["loss_fn"](params, {"tokens": tokens})
    loss = loss[0] if isinstance(loss, tuple) else loss
    assert abs(float(loss) + float(jnp.mean(ref))) < 5e-3  # bf16 compute
    assert fam.train_flops_per_token(cfg, SEQ) > 6 * fam.matmul_params(cfg)


def a_forced_reference_returns_two_arrays_of_one_shape(cfg):
    import jax.numpy as jnp
    import numpy as np

    fam = harness.family(cfg)
    if not hasattr(fam, "token_logprobs_forced"):
        print(f"{cfg['family']} makes no choice to force", file=sys.stderr)
        sys.exit(SKIPPED)
    tokens = np.random.default_rng(7).integers(
        0, cfg["vocab_size"], size=(2, SEQ + 1), dtype=np.int32
    )
    params = fam.seeded_params(cfg, 2**31 + 5)
    # filler: every choice 0.  Whatever the family makes of that (here a
    # duplicate, so an infinite slack), the two arrays have their shape
    served = {
        name: np.zeros(
            tokens.shape + tuple(cfg[key] for key in spec["per_position"]),
            spec["dtype"],
        )
        for name, spec in cfg["assumed"]["served_arrays"].items()
    }
    logprobs, slack = fam.token_logprobs_forced(params, tokens, cfg, served)
    for got in (logprobs, slack):
        assert got.shape == (2, SEQ) and got.dtype == jnp.float32, got
    assert bool(jnp.all(jnp.isfinite(logprobs)))
    assert fam.token_logprobs(params, tokens, cfg).shape == (2, SEQ)


if __name__ == "__main__":
    globals()[sys.argv[1]](
        harness.load_json(sys.argv[2]) if sys.argv[2].endswith(".json")
        else tiny_cfg(sys.argv[2])
    )
