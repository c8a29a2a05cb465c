"""Child process of a ``rollout`` run, started after the engine has
closed (the chip is free): the per-token logprobs that the plain
float32 reference of the configuration's family gives for sampled
requests' prompt + answer, on the same seeded weights, against the
logprobs the serving plane captured while sampling.

    python reference_check.py <config.json> <seed> <sample.npz> <out.json> <platform>

``sample.npz``: ``tokens`` [n, L] (right-padded with 0), ``prompt_len``
[n], ``new_tokens`` [n], ``logprobs`` [n, max new] (NaN-padded).
Prefill and then decoding through the paged cache must agree with one
full causal forward; logprobs are compared, not tokens (with random
weights the largest logit changes on rounding).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(config_path, seed, sample_path, out_path, platform):
    import jax
    import numpy as np

    import harness

    device = jax.devices()[0]
    if device.platform != platform:
        raise SystemExit(f"reference ran on {device.platform!r}")
    with open(config_path) as f:
        cfg = json.load(f)
    sample = np.load(sample_path)
    fam = harness.family(cfg)
    params = fam.seeded_params(cfg, int(seed))
    ref = np.asarray(
        jax.jit(lambda p, t: fam.token_logprobs(p, t, cfg))(
            params, sample["tokens"]
        )
    )
    worst, compared = 0.0, 0
    for i in range(sample["tokens"].shape[0]):
        p, n = int(sample["prompt_len"][i]), int(sample["new_tokens"][i])
        # ref[i, j] scores token j + 1: the answer is tokens p .. p+n-1
        diff = np.abs(ref[i, p - 1:p - 1 + n] - sample["logprobs"][i, :n])
        worst = max(worst, float(diff.max()))
        compared += n
    with open(out_path, "w") as f:
        json.dump({"max_abs_diff": worst, "compared": compared}, f)


if __name__ == "__main__":
    main(*sys.argv[1:6])
