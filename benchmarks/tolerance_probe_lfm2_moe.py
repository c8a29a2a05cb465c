"""``tolerance_probe_kimi_linear.py``'s steps for a configuration of
``family_lfm2_moe`` — gated short-convolution layers that keep a two-row
tail a lane, grouped-query attention layers whose 64-wide heads lie two
a row of the page pool, sigmoid-routed experts all held (run by hand on
the chip when the cell's ``logprob_tol`` and ``routing_slack_max`` are
set, not by a cell):

    python3 benchmarks/tolerance_probe_lfm2_moe.py <config.json> \
        <traffic.json> <seed,seed,..> [lanes] [answer] [fault,fault,..]

The program's own serving path — the family's ``serving_parts`` step
programs over a pool made by ``rl/kv_cache`` (the conv layers' tails, the
attention layers' pages in rows of two heads), the traffic file's block
size, chunk and backend, ``lanes`` lanes side by side (the pool is sized
for them, not for the cell's 256), driven here token by token with the
tokens given (lane ``i`` prefills ``i + 1`` whole chunks and a LAST chunk
of 2-5 tokens, then paged decode) — is scored as a cell's check scores
it: the float32 reference FORCED onto the experts the served side chose;
the largest difference of one answer token's logprob and the largest
routing slack over every computed position
(``family.forced_readings``).  Every number is a MAXIMUM over the tokens
read, so a fault's reading over ``answer`` tokens is a floor of what it
reads over a cell's thousands.  One JSON line a reading: ``sound``
first, then one fault each of those asked for (all by default; a sound
pair of limits has every control over at least one of them), seed by
seed:

- ``int8_weights``: every weight matrix the served side multiplies with
  rounded through int8 (one scale per tensor): the precision below the
  configuration's;
- ``tail_zeroed``: lane 0's conv tails zeroed before its last prefill
  chunk, 2-5 tokens before its answer;
- ``tail_other_lane``: after prefill, lane ``i`` holds lane ``i + 1``'s
  conv tails;
- ``taps_newest_first``: the three taps applied in the reverse order;
- ``gate_b_dropped``: the convolution's input is ``X``, not ``B * X``;
- ``gate_c_dropped``: the convolution's output goes ungated to ``W_out``;
- ``k_norm_dropped``: the per-head RMSNorm of ``k`` is left out;
- ``rotation_dropped``: nothing is rotated (the angle is 0 everywhere);
- ``lanes_exchanged``: after prefill, the first 16 blocks (256 tokens)
  of lane ``i``'s keys and values are lane ``i + 1``'s in every
  attention layer;
- ``row_halves_exchanged``: after prefill, the two 64-wide halves of
  every 128-lane row of ``k`` and ``v`` are exchanged — KV heads ``2p``
  and ``2p + 1`` trade places: the fault the two-heads-a-row layout can
  have;
- ``bias_dropped``: the served router selects without its bias;
- ``renorm_dropped``: the routed experts' weights are the scores
  themselves, not divided by their sum;
- ``held_expert_dropped``: the first held expert's term is missing in
  every expert layer.

The faults of a trace are patched into the program's modules HERE, for
the reading's own trace; nothing of them is in the program.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tolerance_probe_deepseek_v32 import faulty_weights  # noqa: E402
from tolerance_probe_kimi_linear import step_programs  # noqa: E402

FAULTS = (
    "int8_weights", "tail_zeroed", "tail_other_lane", "taps_newest_first",
    "gate_b_dropped", "gate_c_dropped", "k_norm_dropped",
    "rotation_dropped", "lanes_exchanged", "row_halves_exchanged",
    "bias_dropped", "renorm_dropped", "held_expert_dropped",
)
#: faults that change what a step program TRACES (patched modules)
PATCHED = (
    "gate_b_dropped", "gate_c_dropped", "k_norm_dropped",
    "rotation_dropped", "renorm_dropped",
)
WEIGHTS = (
    "bias_dropped", "held_expert_dropped", "int8_weights",
    "taps_newest_first",
)
#: blocks of a lane's prompt that ``lanes_exchanged`` exchanges
EXCHANGED_BLOCKS = 16


class patched:
    """The program's modules with one fault in them, for one trace."""

    def __init__(self, fault, model):
        """``model``: the module of the served model's step programs
        (the family's, found through its config object: nothing here
        names a model)."""
        self.fault, self.model, self.saved = fault, model, []

    def _set(self, module, name, fn):
        self.saved.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def __enter__(self):
        import jax
        import jax.numpy as jnp

        fault, model = self.fault, self.model
        if fault in ("gate_b_dropped", "gate_c_dropped"):

            def faulty_inputs(h, lp, cfg):
                # the program's ``_conv_inputs`` without one gate
                p = jnp.matmul(
                    h, lp["w_in"].astype(cfg.dtype),
                    preferred_element_type=jnp.float32,
                )
                b, c, x = jnp.split(p, 3, axis=-1)
                if fault == "gate_b_dropped":
                    return x, c
                return b * x, jnp.ones_like(c)

            self._set(model, "_conv_inputs", faulty_inputs)
        elif fault == "k_norm_dropped":
            import itertools

            norm, calls = model._head_norm, itertools.count()

            def q_alone(x, weight, eps):
                # a layer norms q, then k: every second call is k's
                return x if next(calls) % 2 else norm(x, weight, eps)

            self._set(model, "_head_norm", q_alone)
        elif fault == "rotation_dropped":
            tables = model._rope_tables

            def angle_zero(theta, dim, positions):
                cos, sin = tables(theta, dim, positions)
                return jnp.ones_like(cos), jnp.zeros_like(sin)

            self._set(model, "_rope_tables", angle_zero)
        elif fault == "renorm_dropped":
            route = model._route

            def scores_as_weights(x, lp, cfg, renorm_eps=0.0):
                h, ids, _ = route(x, lp, cfg, renorm_eps)
                xf = x.astype(jnp.float32)
                hf = xf * jax.lax.rsqrt(
                    jnp.mean(xf * xf, -1, keepdims=True) + cfg.rms_norm_eps
                ) * lp["mlp_norm"]
                s = jax.nn.sigmoid(jnp.matmul(
                    hf, lp["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                ))
                return h, ids, (
                    jnp.take_along_axis(s, ids, -1) * cfg.route_scale
                )

            self._set(model, "_route", scores_as_weights)
        return self

    def __exit__(self, *exc):
        for module, name, fn in reversed(self.saved):
            setattr(module, name, fn)


def altered(params, fault, cfg):
    """The seeded tree with ``fault`` in its weights
    (``tolerance_probe_deepseek_v32.faulty_weights``, and the taps'
    order)."""
    if fault != "taps_newest_first":
        return faulty_weights(params, fault, cfg)
    return dict(params, layers=tuple(
        dict(lp, conv_w=lp["conv_w"][::-1]) if "conv_w" in lp else lp
        for lp in params["layers"]
    ))


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       answer, fault, cfg):
    """-> (logprobs ``[lanes, answer]`` of each lane's answer tokens, the
    experts every computed position chose ``{"experts": [lanes, total,
    expert layers, k]}``, -1 where a position was never computed), as
    the paged programs compute them.  A fault of the cache hits lane 0
    before its last chunk, or every lane after prefill."""
    import functools
    import importlib

    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    model = importlib.import_module(type(parts["cfg"]).__module__)
    traced = fault if fault in PATCHED else None
    key = (traced, id(parts))
    lanes, total = tokens.shape
    chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
    mb = -(-traffic["max_seq_len"] // bs)
    num_blocks = lanes * mb + 1
    with patched(traced, model):
        if key not in programs:
            programs[key] = step_programs(parts)
        prefill, decode = (
            functools.partial(f, params) for f in programs[key]
        )
        pool = init_block_pool(paged_cache_config(
            parts["cfg"], num_blocks, bs, lanes, chunk
        ))
        tables = np.zeros((lanes, mb), np.int32)
        for i in range(lanes):  # lane i owns blocks 1 + i * mb ...
            tables[i] = 1 + i * mb + np.arange(mb)
        out = np.zeros((lanes, answer), np.float32)
        chose = {
            name: np.full(
                (lanes, total) + tuple(cfg[k] for k in spec["per_position"]),
                -1, spec["dtype"],
            )
            for name, spec in cfg["assumed"]["served_arrays"].items()
        }
        for i in range(lanes):
            p = int(prompt_lens[i])
            starts = list(range(0, p, chunk))
            for start in starts:
                if i == 0 and start == starts[-1] and fault == "tail_zeroed":
                    pool = dict(pool, conv=pool["conv"].at[:, 0].set(0.0))
                real = min(chunk, p - start)
                piece = np.zeros((1, chunk), np.int32)
                piece[0, :real] = tokens[i, start:start + real]
                pool, lp, rows = prefill(
                    pool, piece, tables[i], np.int32(start), np.int32(i),
                    np.int32(real), np.int32(tokens[i, start + real]),
                )
                for name, a in rows.items():
                    chose[name][i, start:start + real] = np.asarray(a)[:real]
            out[i, 0] = float(lp)
        if fault == "tail_other_lane":
            pool = dict(pool, conv=jnp.roll(pool["conv"], -1, axis=1))
        elif fault == "lanes_exchanged":
            mine = np.concatenate(
                [tables[i, :EXCHANGED_BLOCKS] for i in range(lanes)]
            )
            theirs = np.concatenate([
                tables[(i + 1) % lanes, :EXCHANGED_BLOCKS]
                for i in range(lanes)
            ])
            pool = dict(pool, **{
                n: pool[n].at[:, mine].set(pool[n][:, theirs])
                for n in ("k", "v")
            })
        elif fault == "row_halves_exchanged":
            half = pool["k"].shape[-1] // 2
            pool = dict(pool, **{
                n: jnp.concatenate(
                    [pool[n][..., half:], pool[n][..., :half]], -1
                )
                for n in ("k", "v")
            })
        active = np.ones((lanes,), bool)
        for j in range(answer - 1):
            toks = np.zeros((lanes,), np.int32)
            pos = np.zeros((lanes,), np.int32)
            nxt = np.zeros((lanes,), np.int32)
            for i in range(lanes):
                at = int(prompt_lens[i]) + j
                toks[i], pos[i], nxt[i] = (
                    tokens[i, at], at, tokens[i, at + 1]
                )
            pool, lps, rows = decode(pool, toks, tables, pos, active, nxt)
            out[:, j + 1] = np.asarray(lps)[:lanes]
            for name, a in rows.items():
                a = np.asarray(a)
                for i in range(lanes):
                    chose[name][i, pos[i]] = a[i]
        del pool
    return out, chose


def main(config_path, traffic_path, seeds, lanes=4, answer=256, faults=""):
    import jax
    import numpy as np

    import harness

    lanes, answer = int(lanes), int(answer)
    faults = [f for f in faults.split(",") if f] or list(FAULTS)
    if faults == ["none"]:
        faults = []
    unknown = sorted(set(faults) - set(FAULTS))
    if unknown:
        raise SystemExit(f"no such fault: {unknown}; there are {FAULTS}")
    cfg = harness.load_json(config_path)
    traffic = harness.load_json(traffic_path)
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = traffic["paged_kernel"]
    fam = harness.family(cfg)
    chunk = traffic["prefill_chunk"]
    parts = fam.serving_parts(
        **fam.model_kwargs(cfg, traffic["max_seq_len"]), dtype="bfloat16"
    )
    programs = {}
    score = jax.jit(lambda p, t, s: fam.forced_readings(p, t, cfg, s))
    for seed in (int(x) for x in seeds.split(",")):
        rng = np.random.default_rng(seed)
        # lane i prefills i + 1 whole chunks and a last one of 2-5
        # tokens: the chunk edge lies inside the reach of the taps, and
        # lane 0's last boundary, where ``tail_zeroed`` strikes, a few
        # tokens before its answer
        prompt_lens = [
            chunk * (i + 1) + int(rng.integers(2, 6)) for i in range(lanes)
        ]
        total = max(prompt_lens) + answer
        tokens = rng.integers(
            0, cfg["vocab_size"], size=(lanes, total), dtype=np.int32
        )
        print(json.dumps({
            "platform": jax.devices()[0].platform, "seed": seed,
            "prompt_lens": prompt_lens, "answer": answer,
        }), flush=True)
        served = {}
        params = fam.seeded_params(cfg, seed)
        # the fault that spends the seed's tree comes last
        for fault in [None] + sorted(faults, key="int8_weights".__eq__):
            faulty = params
            if fault in WEIGHTS:
                faulty = altered(params, fault, cfg)
            if fault == "int8_weights":
                params = None
            served[fault or "sound"] = serve_given_tokens(
                parts, programs, parts["serving_params_fn"](faulty), traffic,
                tokens, prompt_lens, answer, fault, cfg,
            )
            del faulty
        # the served tree goes before the reference's comes
        params = None
        params = fam.seeded_params(cfg, seed)
        for name, (got, chose) in served.items():
            ref, routed = (
                np.asarray(a) for a in score(params, tokens, chose)
            )
            diff, worst, off = 0.0, 0.0, 0
            for i, p in enumerate(prompt_lens):
                d = np.abs(ref[i, p - 1:p - 1 + answer] - got[i])
                diff = max(
                    diff, float(np.where(np.isfinite(d), d, np.inf).max())
                )
                row = routed[i, :p + answer - 1]
                row = np.where(np.isfinite(row), row, np.float32(np.inf))
                worst, off = max(worst, float(row.max())), off + int(
                    (row > 0).sum()
                )
            print(json.dumps({
                "seed": seed,
                "served": name,
                "logprob_max_abs_diff": diff,
                "max_routing_slack": worst,
                "positions_off_own_topk": off,
                "answer_tokens": int(lanes * answer),
            }), flush=True)
        del params, served


if __name__ == "__main__":
    main(*sys.argv[1:7])
