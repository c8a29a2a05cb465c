"""How far reduced precision moves what the ``correct`` checks compare:
run by hand when a tolerance is set, not by a cell.

    python3 benchmarks/tolerance_probe.py <config.json> <seed> <batch> <seq>

The plain float32 reference scores one seeded batch on seeded weights,
then again with every weight matrix rounded through bfloat16, float8
(e4m3) and int8 (one scale per tensor) — the arithmetic stays float32,
so what is printed is the effect of the rounding alone, the same on any
platform.  Per rounding: the shift of the mean loss (what the ``train``
kind's ``reference_tol`` is held against) and the largest shift of one
token's logprob (what ``logprob_tol`` is held against).  A tolerance is
sound if the precision the configuration states stays under it and the
next lower one does not.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def rounders():
    import jax.numpy as jnp

    def through(dtype):
        return lambda w: w.astype(dtype).astype(jnp.float32)

    def int8(w):
        scale = jnp.max(jnp.abs(w)) / 127.0
        return jnp.round(w / scale).clip(-127, 127) * scale

    return {
        "bfloat16": through(jnp.bfloat16),
        "float8_e4m3fn": through(jnp.float8_e4m3fn),
        "int8_per_tensor": int8,
    }


def main(config_path, seed, batch, seq):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness

    with open(config_path) as f:
        cfg = json.load(f)
    reference = harness.family(cfg)
    params = reference.seeded_params(cfg, int(seed))
    tokens = np.random.default_rng(int(seed)).integers(
        0, cfg["vocab_size"], size=(int(batch), int(seq) + 1), dtype=np.int32
    )
    score = jax.jit(lambda p, t: reference.token_logprobs(p, t, cfg))
    exact = score(params, tokens)
    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "loss": float(-jnp.mean(exact)),
    }), flush=True)
    for name, rounder in rounders().items():
        rounded = jax.tree_util.tree_map(
            lambda w: rounder(w) if w.ndim >= 2 and w.shape[-1] > 1 else w,
            params,
        )
        got = score(rounded, tokens)
        print(json.dumps({
            "weights": name,
            "mean_loss_shift": float(jnp.abs(jnp.mean(got) - jnp.mean(exact))),
            "max_token_logprob_shift": float(jnp.max(jnp.abs(got - exact))),
        }), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:5])
