"""``tolerance_probe.py``'s steps for a configuration whose model keeps a
per-lane state beside its paged K/V (run by hand on the chip when the
``rollout`` kind's ``logprob_tol`` is set for such a cell, not by a
cell):

    python3 benchmarks/tolerance_probe_state.py <config.json> <traffic.json> <seed> [lanes] [answer]

Two kinds of reading, one JSON line each, all of them the largest
difference of one token's logprob from the family's float32 reference:

1. the reference itself with every weight matrix rounded through
   float8 (e4m3's 3 mantissa bits) and int8 (one scale per tensor),
   float32 arithmetic:
   what a precision below the configuration's does to a token (the
   weights are HELD in bfloat16, so that rounding is the baseline and
   not a step);
2. the program's own serving path — the family's ``serving_parts``
   step programs over a pool made by ``rl/kv_cache``, the traffic
   file's geometry and backend, driven here token by token with the
   tokens given (prefill in chunks, then paged decode, several lanes
   side by side) — sound, and with one fault injected:

   - ``state_zeroed``: a lane's recurrent state zeroed between its
     first and second prefill chunk;
   - ``conv_zeroed``: its convolution tail zeroed there;
   - ``advanced_in_prefill``: a decode step that treats the lane as
     active between those chunks (what a scheduler that forgot the
     ``active`` mask would do);
   - ``kv_page_swapped``: the first K/V page of two lanes exchanged
     after prefill.

A tolerance is sound if every sound reading stays under it, with room,
and every control reads over it.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FAULTS = (
    None, "state_zeroed", "conv_zeroed", "advanced_in_prefill",
    "kv_page_swapped",
)


def step_programs(parts):
    """The family's two step programs, each returning the logprob of
    the token(s) that follow, compiled once for every reading (the
    weights are an argument: closed over, 10 GB would be constants of
    the compiled program)."""
    import functools

    import jax
    import jax.numpy as jnp

    def logprob(logits, token):
        return jax.nn.log_softmax(logits.astype(jnp.float32), -1)[token]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, pool, chunk_tokens, table, start, lane, real, nxt):
        logits, pool = parts["paged_prefill_fn"](
            params, chunk_tokens, pool, table, start, lane, real
        )
        return pool, logprob(logits[0, real - 1], nxt)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pool, toks, tables, positions, active, nxt):
        logits, pool = parts["paged_decode_fn"](
            params, toks, pool, tables, positions, active
        )
        return pool, jax.vmap(logprob)(logits, nxt)

    return prefill, decode


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       fault):
    """Per-token logprobs ``[lanes, answer]`` of ``tokens[:, P:]`` as
    the paged programs compute them: lane ``i`` prefills
    ``tokens[i, :P_i]`` in chunks and then decodes ``answer - 1`` steps
    beside the others.  ``fault`` hits lane 0 (and lane 1's page)."""
    import functools

    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    prefill, decode = (functools.partial(f, params) for f in programs)
    lanes, total = tokens.shape
    chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
    mb = -(-traffic["max_seq_len"] // bs)
    pool = init_block_pool(paged_cache_config(
        parts["cfg"], traffic["num_blocks"], bs, traffic["max_slots"]
    ))
    slots = traffic["max_slots"]
    tables = np.zeros((slots, mb), np.int32)
    for i in range(lanes):  # lane i owns blocks 1 + i * mb ...
        tables[i] = 1 + i * mb + np.arange(mb)

    answer = total - int(max(prompt_lens))
    out = np.zeros((lanes, answer), np.float32)
    for i in range(lanes):
        p = int(prompt_lens[i])
        for n, start in enumerate(range(0, p, chunk)):
            if i == 0 and n == 1 and fault in (
                "state_zeroed", "conv_zeroed", "advanced_in_prefill"
            ):
                if fault == "advanced_in_prefill":
                    active = np.zeros((slots,), bool)
                    active[0] = True
                    toks = np.zeros((slots,), np.int32)
                    toks[0] = tokens[0, start]
                    pos = np.zeros((slots,), np.int32)
                    pos[0] = start
                    pool, _ = decode(
                        pool, toks, tables, pos, active,
                        np.zeros((slots,), np.int32),
                    )
                else:
                    leaf = "ssm" if fault == "state_zeroed" else "conv"
                    pool = dict(pool, **{leaf: pool[leaf].at[:, 0].set(0.0)})
            real = min(chunk, p - start)
            piece = np.zeros((1, chunk), np.int32)
            piece[0, :real] = tokens[i, start:start + real]
            pool, lp = prefill(
                pool, piece, tables[i], np.int32(start), np.int32(i),
                np.int32(real), np.int32(tokens[i, start + real]),
            )
        out[i, 0] = float(lp)
    if fault == "kv_page_swapped":
        tables[[0, 1], 0] = tables[[1, 0], 0]
    active = np.zeros((slots,), bool)
    active[:lanes] = True
    for j in range(answer - 1):
        toks = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        nxt = np.zeros((slots,), np.int32)
        for i in range(lanes):
            at = int(prompt_lens[i]) + j
            toks[i], pos[i], nxt[i] = tokens[i, at], at, tokens[i, at + 1]
        pool, lps = decode(pool, toks, tables, pos, active, nxt)
        out[:, j + 1] = np.asarray(lps)[:lanes]
    return out


def rounders():
    import jax
    import jax.numpy as jnp

    def int8(w):
        w = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w)) / 127.0
        return (jnp.round(w / scale).clip(-127, 127) * scale)

    return {
        # reduce_precision, not a pair of casts: the compiler may drop
        # a round trip through a narrower type as excess precision (on
        # the chip it did, and the reading was 0.0)
        "float8_e4m3": lambda w: jax.lax.reduce_precision(
            w, exponent_bits=4, mantissa_bits=3
        ),
        "int8_per_tensor": int8,
    }


def main(config_path, traffic_path, seed, lanes=4, answer=96):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness

    seed, lanes, answer = int(seed), int(lanes), int(answer)
    cfg = harness.load_json(config_path)
    traffic = harness.load_json(traffic_path)
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = traffic["paged_kernel"]
    fam = harness.family(cfg)
    params = fam.seeded_params(cfg, seed)
    rng = np.random.default_rng(seed)
    chunk = traffic["prefill_chunk"]
    # lane i prefills i + 1 whole chunks and a few tokens more (no
    # multiple of the chunk).  Lane 0's one chunk boundary, where the
    # faults strike, lies a few tokens before its answer: a scheduler
    # with such a fault would hit every boundary, and a cell's sample
    # holds prompts that end just past one
    prompt_lens = [
        chunk * (i + 1) + int(rng.integers(3, max(chunk // 8, 4)))
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

    parts = fam.serving_parts(
        **fam.model_kwargs(cfg, traffic["max_seq_len"]), dtype="bfloat16"
    )
    programs = step_programs(parts)
    served = {
        fault or "sound": serve_given_tokens(
            parts, programs, params, traffic, tokens, prompt_lens, fault
        )
        for fault in FAULTS
    }
    del parts, programs

    score = jax.jit(lambda p, t: fam.token_logprobs(p, t, cfg))

    def answers(ref):
        ref = np.asarray(ref)
        return np.stack([
            ref[i, p - 1:p - 1 + answer] for i, p in enumerate(prompt_lens)
        ])

    exact = answers(score(params, tokens))
    print(json.dumps({
        "reference_mean_logprob": float(exact.mean()),
        "reference_std_logprob": float(exact.std()),
    }), flush=True)
    for name, got in served.items():
        diff = np.abs(got - exact)
        print(json.dumps({
            "served": name,
            "max_token_logprob_diff": float(diff.max()),
            "faulted_lanes_max": float(diff[:2].max()),
            "other_lanes_max": float(diff[2:].max()) if lanes > 2 else None,
            "tokens": int(diff.size),
        }), flush=True)
    for name, rounder in rounders().items():
        # the tree again from the seed, rounded leaf by leaf in place:
        # two whole trees do not fit the chip
        del params
        params = fam.seeded_params(cfg, seed)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        del params
        for i, w in enumerate(leaves):
            if w.ndim >= 2 and w.dtype == jnp.bfloat16:
                leaves[i] = jax.jit(
                    lambda w: rounder(w).astype(w.dtype), donate_argnums=0
                )(w)
        del w
        params = jax.tree_util.tree_unflatten(treedef, leaves)
        del leaves
        got = answers(score(params, tokens))
        print(json.dumps({
            "weights": name,
            "mean_logprob_shift": float(abs(got.mean() - exact.mean())),
            "max_token_logprob_shift": float(np.abs(got - exact).max()),
        }), flush=True)

if __name__ == "__main__":
    main(*sys.argv[1:6])
