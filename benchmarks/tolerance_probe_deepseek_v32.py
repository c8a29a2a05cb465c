"""``tolerance_probe_keye_vl2.py``'s steps for a configuration of
``family_deepseek_v32`` — latent attention over a cache of one
compressed row a token, a learned top-k indexer and a share of a
layer's group-routed experts (run by hand on the chip when the cell's
``logprob_tol``, ``routing_slack_max`` and the configuration's
``assumed.selection_slack_weight`` are set, not by a cell):

    python3 benchmarks/tolerance_probe_deepseek_v32.py <config.json> \
        <traffic.json> <seed,seed,..> [lanes] [answer] [fault,fault,..]

The program's own serving path — the family's ``serving_parts`` step
programs over a pool made by ``rl/kv_cache`` (no ``k``, no ``v``), the
traffic file's block size, chunk and backend, ``lanes`` lanes side by
side (the pool is sized for them, not for the cell's 32), driven here
token by token with the tokens given (prefill in chunks, then paged
decode; every lane's prompt is past ``index_topk`` before its first
answer token, so the selection is real) — is scored as a cell's check
scores it: the float32 reference FORCED onto the experts the served
side chose and onto the keys its indexer picked; the largest difference
of one answer token's logprob, the largest routing slack and the
largest selection slack over every computed position, the two slacks
APART (``family.forced_readings``; a cell compares the larger of the
first and ``assumed.selection_slack_weight`` times the second with
``routing_slack_max``).  Every number is a MAXIMUM over the tokens
read, so a fault's reading over ``answer`` tokens is a floor of what it
reads over a cell's ~4500: a fault over a limit here is over it there;
what a SOUND run reads at the cell's lengths is read in the cell.  One
JSON line a reading: ``sound`` first, then one fault each of those
asked for (all by default; a sound set of limits has every control
over at least one of them), seed by seed:

- ``int8_weights``: every weight matrix the served side multiplies with
  rounded through int8 (one scale per tensor): the precision below the
  configuration's;
- ``scaling_dropped``: the routed experts' weights sum to 1, not to
  ``routed_scaling_factor`` (every held expert's ``w_down`` divided by
  it on the served side: the same sum, no other program);
- ``held_expert_dropped``: the first held expert's term is missing in
  every expert layer (its ``w_down`` zeroed on the served side);
- ``bias_dropped``: the served router selects without its bias;
- ``indexer_bypassed``: every query reads the NEWEST ``index_topk``
  rows, whatever the indexer scores;
- ``ik_previous_layer``: layer ``l``'s index queries are scored against
  layer ``l - 1``'s index keys (layer 0 against its own);
- ``ckv_lanes_exchanged``: after prefill, the first 64 blocks (1024
  tokens) of lane ``i``'s latents and rotated keys are lane ``i + 1``'s
  in every layer — a selected row read through another lane's table;
- ``kpe_zeroed``: after prefill, the rotated shared key of every cached
  row is zero (the rotated 64 of the cached row's 576);
- ``yarn_scale_dropped``: the scores' scale is ``192 ** -0.5`` without
  ``(0.1 ln 40 + 1) ** 2``;
- ``group_limit_dropped``: the served router takes the top-8 of all 256
  experts, no group left out.

The faults of a trace are patched into the program's modules HERE, for
the reading's own trace; nothing of them is in the program.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tolerance_probe_trinity import altered_weights  # noqa: E402

FAULTS = (
    "int8_weights", "scaling_dropped", "held_expert_dropped",
    "bias_dropped", "indexer_bypassed", "ik_previous_layer",
    "ckv_lanes_exchanged", "kpe_zeroed", "yarn_scale_dropped",
    "group_limit_dropped",
)
#: faults that change what a step program TRACES (patched modules)
PATCHED = ("indexer_bypassed", "ik_previous_layer")
#: faults of the served model's keywords: other programs
KEYWORDS = {
    "yarn_scale_dropped": dict(rope_mscale_all_dim=0.0),
    "group_limit_dropped": dict(n_group=1, topk_group=1),
}
WEIGHTS = (
    "bias_dropped", "held_expert_dropped", "int8_weights", "scaling_dropped"
)
#: blocks of a lane's prompt that ``ckv_lanes_exchanged`` exchanges
EXCHANGED_BLOCKS = 64


def step_programs(parts):
    """The family's two step programs, each returning the logprob of
    the token(s) that follow and what every row chose (``experts``,
    ``selection``), compiled once a trace (the weights are an
    argument)."""
    import functools

    import jax
    import jax.numpy as jnp

    def logprob(logits, token):
        return jax.nn.log_softmax(logits.astype(jnp.float32), -1)[token]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, pool, chunk_tokens, table, start, real, nxt):
        logits, pool, rows = parts["paged_prefill_fn"](
            params, chunk_tokens, pool, table, start
        )
        return pool, logprob(logits[0, real - 1], nxt), rows

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pool, toks, tables, positions, active, nxt):
        logits, pool, rows = parts["paged_decode_fn"](
            params, toks, pool, tables, positions, active
        )
        return pool, jax.vmap(logprob)(logits, nxt), rows

    return prefill, decode


def faulty_weights(params, fault, cfg):
    """The seeded tree with ``fault`` in its weights: a tree of its own
    that shares every leaf the fault leaves alone — but for
    ``int8_weights``, which alters every matrix in place and spends the
    tree given (two whole trees do not fit the chip)."""
    import jax.numpy as jnp

    if fault == "int8_weights":
        return altered_weights(params, fault)
    factor = cfg["routed_scaling_factor"]

    def layer(lp):
        if "router" not in lp:
            return lp
        if fault == "bias_dropped":
            return dict(lp, router_bias=jnp.zeros_like(lp["router_bias"]))
        w = lp["w_down"]
        if fault == "scaling_dropped":
            return dict(
                lp, w_down=(w.astype(jnp.float32) / factor).astype(w.dtype)
            )
        assert fault == "held_expert_dropped", fault
        return dict(lp, w_down=w.at[0].set(0))

    return dict(params, layers=tuple(layer(lp) for lp in params["layers"]))


class patched:
    """The program's modules with one fault in them, for one trace."""

    def __init__(self, fault, num_blocks):
        self.fault, self.num_blocks, self.saved = fault, num_blocks, []

    def _set(self, module, name, fn):
        self.saved.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def __enter__(self):
        import jax.numpy as jnp

        from dlrover_tpu.ops import paged_attention as pa

        if self.fault == "indexer_bypassed":
            # a position's score is its position: the top-k is the newest

            def decode_newest(qi, w, keys, seq_lens):
                at = jnp.arange(keys.shape[1], dtype=jnp.float32)[None]
                return jnp.where(at < seq_lens[:, None], at, -jnp.inf)

            def prefill_newest(qi, w, keys, start_pos, backend=None):
                at = jnp.arange(keys.shape[0], dtype=jnp.float32)[None]
                rows = (start_pos + jnp.arange(qi.shape[0]))[:, None]
                return jnp.where(at <= rows, at, -jnp.inf)

            self._set(pa, "decode_index_scores", decode_newest)
            self._set(pa, "prefill_index_scores", prefill_newest)
        elif self.fault == "ik_previous_layer":
            gather, nb = pa.gather_index_keys, self.num_blocks

            def previous(ik_pool, tables, width):
                return gather(
                    ik_pool, jnp.where(tables >= nb, tables - nb, tables),
                    width,
                )

            self._set(pa, "gather_index_keys", previous)
        return self

    def __exit__(self, *exc):
        for module, name, fn in reversed(self.saved):
            setattr(module, name, fn)


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       answer, fault, cfg):
    """-> (logprobs ``[lanes, answer]`` of each lane's answer tokens,
    what every computed position chose ``{"experts": [lanes, total,
    expert layers, k], "selection": [lanes, total, layers, words]}``,
    -1 where a position was never computed), as the paged programs
    compute them."""
    import functools

    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    traced = fault if fault in PATCHED else None
    key = (traced, id(parts))
    if key not in programs:
        programs[key] = step_programs(parts)
    lanes, total = tokens.shape
    chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
    mb = -(-traffic["max_seq_len"] // bs)
    num_blocks = lanes * mb + 1
    with patched(traced, num_blocks):
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
            for start in range(0, p, chunk):
                real = min(chunk, p - start)
                piece = np.zeros((1, chunk), np.int32)
                piece[0, :real] = tokens[i, start:start + real]
                pool, lp, rows = prefill(
                    pool, piece, tables[i], np.int32(start), np.int32(real),
                    np.int32(tokens[i, start + real]),
                )
                for name, a in rows.items():
                    chose[name][i, start:start + real] = np.asarray(a)[:real]
            out[i, 0] = float(lp)
        if fault == "ckv_lanes_exchanged":
            mine = np.concatenate(
                [tables[i, :EXCHANGED_BLOCKS] for i in range(lanes)]
            )
            theirs = np.concatenate([
                tables[(i + 1) % lanes, :EXCHANGED_BLOCKS]
                for i in range(lanes)
            ])
            pool = dict(pool, **{
                n: pool[n].at[:, mine].set(pool[n][:, theirs])
                for n in ("c", "kpe")
            })
        elif fault == "kpe_zeroed":
            pool = dict(pool, kpe=pool["kpe"] * 0)
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


def main(config_path, traffic_path, seeds, lanes=2, answer=256, faults=""):
    import jax
    import numpy as np

    import harness

    lanes, answer = int(lanes), int(answer)
    faults = [f for f in faults.split(",") if f] or list(FAULTS)
    unknown = sorted(set(faults) - set(FAULTS))
    if unknown:
        raise SystemExit(f"no such fault: {unknown}; there are {FAULTS}")
    cfg = harness.load_json(config_path)
    traffic = harness.load_json(traffic_path)
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = traffic["paged_kernel"]
    fam = harness.family(cfg)
    chunk = traffic["prefill_chunk"]
    kwargs = fam.model_kwargs(cfg, traffic["max_seq_len"])
    sound_parts = fam.serving_parts(**kwargs, dtype="bfloat16")
    other_parts = {
        f: fam.serving_parts(**dict(kwargs, **KEYWORDS[f]), dtype="bfloat16")
        for f in faults if f in KEYWORDS
    }
    programs = {}
    score = jax.jit(lambda p, t, s: fam.forced_readings(p, t, cfg, s))
    for seed in (int(x) for x in seeds.split(",")):
        rng = np.random.default_rng(seed)
        # every lane is past index_topk before its first answer token:
        # lane i prefills the chunks that hold index_topk, 2 i + 1 more
        # and a few tokens
        first = -(-cfg["index_topk"] // chunk)
        prompt_lens = [
            chunk * (first + 2 * i + 1)
            + int(rng.integers(3, max(chunk // 8, 4)))
            for i in range(lanes)
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
            parts = other_parts.get(fault, sound_parts)
            faulty = params
            if fault in WEIGHTS:
                faulty = faulty_weights(params, fault, cfg)
            if fault == "int8_weights":
                params = None
            served[fault or "sound"] = serve_given_tokens(
                parts, programs, parts["serving_params_fn"](faulty), traffic,
                tokens, prompt_lens, answer, fault, cfg,
            )
            del faulty
        # the served tree goes before the reference's comes: two whole
        # trees do not fit the chip
        params = None
        params = fam.seeded_params(cfg, seed)
        for name, (got, chose) in served.items():
            ref, routed, picked = (
                np.asarray(a) for a in score(params, tokens, chose)
            )
            diff, worst = 0.0, {"routing": [0.0, 0], "selection": [0.0, 0]}
            for i, p in enumerate(prompt_lens):
                d = np.abs(ref[i, p - 1:p - 1 + answer] - got[i])
                diff = max(
                    diff, float(np.where(np.isfinite(d), d, np.inf).max())
                )
                for kind, slack in (("routing", routed),
                                    ("selection", picked)):
                    row = slack[i, :p + answer - 1]
                    row = np.where(
                        np.isfinite(row), row, np.float32(np.inf)
                    )
                    worst[kind][0] = max(worst[kind][0], float(row.max()))
                    worst[kind][1] += int((row > 0).sum())
            print(json.dumps({
                "seed": seed,
                "served": name,
                "logprob_max_abs_diff": diff,
                "max_routing_slack": worst["routing"][0],
                "max_selection_slack": worst["selection"][0],
                "positions_off_own_topk": worst["routing"][1],
                "positions_off_own_selection": worst["selection"][1],
                "answer_tokens": int(lanes * answer),
            }), flush=True)
        del params, served


if __name__ == "__main__":
    main(*sys.argv[1:7])
