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
weights the largest logit changes on rounding).  A difference that is
not finite (a NaN on either side) reads infinite, never less.

A router's top-k is a choice of the same kind, made INSIDE the model: a
sound bfloat16 run and the float32 reference send some positions to
other experts, and the difference carries through attention to every
later token.  So where the configuration's family provides
``token_logprobs_forced`` (``family_dense.py``, point 4), ``sample.npz``
must also hold what the served side decided, ``served_<name>``
``[n, L, ...]`` (``rollout_cell.py``'s docstring): the reference takes
those choices in place of its own, EVERY answer token is compared as
before, and ``<out.json>`` gains ``max_routing_slack`` — how far, under
the reference's own float32 scores, the worst choice taken lies below
the best one left out, over every computed position, prompt included —
with ``routed_positions`` and ``positions_off_own_topk`` for the note.
A family without the function is scored as it always was: two keys.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def worst_difference(ref, sample):
    """The largest |reference - served| over the sample's answer tokens,
    and their count (numpy only; ``ref`` [n, L-1])."""
    import numpy as np

    worst, compared = 0.0, 0
    for i in range(sample["tokens"].shape[0]):
        p, n = int(sample["prompt_len"][i]), int(sample["new_tokens"][i])
        # ref[i, j] scores token j + 1: the answer is tokens p .. p+n-1
        diff = np.abs(ref[i, p - 1:p - 1 + n] - sample["logprobs"][i, :n])
        # ``max(worst, nan)`` would keep ``worst``: a NaN must not pass
        diff = np.where(np.isfinite(diff), diff, np.float32(np.inf))
        worst = max(worst, float(diff.max()))
        compared += n
    return worst, compared


def routing_slack(slack, ref, sample):
    """-> (the largest ``slack`` over every position a request computed,
    their count, how many of them read over 0): numpy only.  ``slack``
    [n, L-1] float32 as ``ref``; row ``j`` is position ``j``'s, and a
    request of ``p + n`` tokens computed positions ``0 .. p + n - 2``.  A
    value that is not finite reads infinite."""
    import numpy as np

    if slack.shape != ref.shape or slack.dtype != np.float32:
        raise SystemExit(
            f"token_logprobs_forced: slack is {slack.dtype} {slack.shape}, "
            f"the logprobs are float32 {ref.shape}: one float32 a position"
        )
    worst, positions, off = 0.0, 0, 0
    for i in range(sample["tokens"].shape[0]):
        m = int(sample["prompt_len"][i]) + int(sample["new_tokens"][i]) - 1
        row = np.where(np.isfinite(slack[i, :m]), slack[i, :m],
                       np.float32(np.inf))
        worst = max(worst, float(row.max()))
        positions, off = positions + m, off + int((row > 0).sum())
    return worst, positions, off


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
    result = {}
    if hasattr(fam, "token_logprobs_forced"):
        served = {
            name[len("served_"):]: sample[name]
            for name in sample.files if name.startswith("served_")
        }
        if not served:
            raise SystemExit(
                f"{cfg['family']} has token_logprobs_forced and "
                f"{sample_path} holds no served_* array: the replies "
                "carried no per_token, so nothing says which choices the "
                "served side made"
            )
        ref, slack = (np.asarray(a) for a in jax.jit(
            lambda p, t, s: fam.token_logprobs_forced(p, t, cfg, s)
        )(params, sample["tokens"], served))
        result = dict(zip(
            ("max_routing_slack", "routed_positions",
             "positions_off_own_topk"),
            routing_slack(slack, ref, sample),
        ))
    else:
        ref = np.asarray(
            jax.jit(lambda p, t: fam.token_logprobs(p, t, cfg))(
                params, sample["tokens"]
            )
        )
    worst, compared = worst_difference(ref, sample)
    with open(out_path, "w") as f:
        json.dump({"max_abs_diff": worst, "compared": compared, **result}, f)


if __name__ == "__main__":
    main(*sys.argv[1:6])
