"""``tolerance_probe_state.py``'s steps for ``family_olmo_hybrid`` (run by
hand on the chip when the cell's ``logprob_tol`` is set, not by a cell):

    python3 benchmarks/tolerance_probe_olmo_hybrid.py <config.json> <traffic.json> <seed>[,<seed>...] [lanes] [answer]

One JSON line a reading, each the largest difference of one token's
logprob from the family's float32 reference:

1. the reference itself with every weight matrix rounded to int8 (one
   scale per tensor; the precision below the configuration's bfloat16:
   it MUST read over the limit) and through float8;
2. the program's own serving path — the family's ``serving_parts`` step
   programs over a pool made by ``rl/kv_cache``, the traffic file's
   geometry and backend, driven token by token with the tokens given —
   sound, and with one fault:

   - ``state_zeroed`` / ``conv_zeroed``: lane 0's recurrent state / its
     conv tail zeroed between its first and second prefill chunk (the
     boundary lies a few tokens before its answer);
   - ``advanced_in_prefill``: a decode step that treats the lane as
     active between those chunks;
   - ``kv_page_swapped``: the first K/V page of two lanes exchanged
     after prefill;
   - ``beta_not_doubled``, ``alpha_dropped`` (= 1), ``k_norm_dropped``
     (k not L2-normalised): the linear layer's form changed, in both
     step programs;
   - ``slab_at_other_rank``: the decode program reads and writes the
     first two linear layers' state slabs at each other's rank (the
     prefill filled them at their own).

Several seeds share every compiled program.  A tolerance is sound if
every sound reading stays under it, with room, and every control reads
over it on every seed.
"""

import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tolerance_probe_state import rounders, step_programs

STATE_LEAF, CONV_LEAF = "gdn", "conv"
#: faults of the pool or the schedule: the sound programs serve them
POOL_FAULTS = (
    None, "state_zeroed", "conv_zeroed", "advanced_in_prefill",
    "kv_page_swapped",
)
#: faults of the model's form: programs of their own
FORM_FAULTS = (
    "beta_not_doubled", "alpha_dropped", "k_norm_dropped",
    "slab_at_other_rank",
)


@contextlib.contextmanager
def form_fault(model, name):
    """``model`` — the module of the program that holds the family's
    step programs — with one line of the linear layer changed, while a
    step program is traced."""
    import jax.numpy as jnp

    gates, heads, ranks = model._gates, model._qkv_heads, model._ranks

    def beta_not_doubled(a, b, lp, cfg):
        alpha, beta = gates(a, b, lp, cfg)
        return alpha, beta / (2.0 if cfg.linear_allow_neg_eigval else 1.0)

    def alpha_dropped(a, b, lp, cfg):
        alpha, beta = gates(a, b, lp, cfg)
        return jnp.ones_like(alpha), beta

    def k_norm_dropped(qkv, cfg):
        q, _, v = heads(qkv, cfg)
        k = jnp.split(qkv, (cfg.key_dim, 2 * cfg.key_dim), axis=-1)[1]
        return q, k.reshape(q.shape), v

    def other_rank(cfg):
        out = ranks(cfg)
        first, second = [
            i for i, kind in enumerate(cfg.layer_types)
            if kind == model.LINEAR
        ][:2]
        out[first], out[second] = out[second], out[first]
        return out

    patch = {
        "beta_not_doubled": ("_gates", beta_not_doubled),
        "alpha_dropped": ("_gates", alpha_dropped),
        "k_norm_dropped": ("_qkv_heads", k_norm_dropped),
        "slab_at_other_rank": ("_ranks", other_rank),
    }[name]
    with mock.patch.object(model, *patch):
        yield


def faulted_programs(parts, name):
    """``step_programs`` traced under ``form_fault(name)``: the trace
    happens at the first call, so each program is wrapped to enter the
    fault around every call (a no-op once compiled)."""
    prefill, decode = step_programs(parts)
    model = sys.modules[parts["paged_prefill_fn"].func.__module__]

    def under(fn):
        def call(*args):
            with form_fault(model, name):
                return fn(*args)
        return call

    if name == "slab_at_other_rank":
        return prefill, under(decode)  # the prefill fills its own ranks
    return under(prefill), under(decode)


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       fault):
    """``tolerance_probe_state.serve_given_tokens`` for this family's
    leaves: per-token logprobs ``[lanes, answer]`` of ``tokens[:, P:]``
    as the paged programs compute them; ``fault`` hits lane 0 (and lane
    1's page)."""
    import functools

    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    prefill, decode = (functools.partial(f, params) for f in programs)
    lanes, total = tokens.shape
    chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
    mb = -(-traffic["max_seq_len"] // bs)
    slots = traffic["max_slots"]
    pool = init_block_pool(paged_cache_config(
        parts["cfg"], traffic["num_blocks"], bs, slots, chunk
    ))
    tables = np.zeros((slots, mb), np.int32)
    for i in range(lanes):  # lane i owns blocks 1 + i * mb ...
        tables[i] = 1 + i * mb + np.arange(mb)

    answer = total - int(max(prompt_lens))
    out = np.zeros((lanes, answer), np.float32)
    for i in range(lanes):
        p = int(prompt_lens[i])
        starts = list(range(0, p, chunk))
        for start in starts:
            # lane 0's LAST chunk boundary: a few tokens before its answer
            if i == 0 and start == starts[-1] and start and fault in (
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
                    leaf = STATE_LEAF if fault == "state_zeroed" else CONV_LEAF
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


def main(config_path, traffic_path, seeds, lanes=4, answer=96):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness

    lanes, answer = int(lanes), int(answer)
    cfg = harness.load_json(config_path)
    traffic = harness.load_json(traffic_path)
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = traffic["paged_kernel"]
    fam = harness.family(cfg)
    chunk = traffic["prefill_chunk"]
    parts = fam.serving_parts(
        **fam.model_kwargs(cfg, traffic["max_seq_len"]), dtype="bfloat16"
    )
    programs = {None: step_programs(parts)}
    programs.update(
        (name, faulted_programs(parts, name)) for name in FORM_FAULTS
    )
    score = jax.jit(lambda p, t: fam.token_logprobs(p, t, cfg))
    for seed in (int(s) for s in str(seeds).split(",")):
        rng = np.random.default_rng(seed)
        # lane i prefills i + 1 whole chunks and a few tokens more (no
        # multiple of the chunk): lane 0's one chunk boundary, where the
        # faults strike, lies a few tokens before its answer
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
        params = fam.seeded_params(cfg, seed)
        serving = parts["serving_params_fn"](params)
        served = {
            fault or "sound": serve_given_tokens(
                parts, programs[None], serving, traffic, tokens,
                prompt_lens, fault,
            )
            for fault in POOL_FAULTS
        }
        served.update(
            (fault, serve_given_tokens(
                parts, programs[fault], serving, traffic, tokens,
                prompt_lens, None,
            ))
            for fault in FORM_FAULTS
        )
        del serving

        def answers(ref):
            ref = np.asarray(ref)
            return np.stack([
                ref[i, p - 1:p - 1 + answer]
                for i, p in enumerate(prompt_lens)
            ])

        exact = answers(score(params, tokens))
        print(json.dumps({
            "seed": seed,
            "reference_mean_logprob": float(exact.mean()),
            "reference_std_logprob": float(exact.std()),
        }), flush=True)
        for name, got in served.items():
            diff = np.abs(got - exact)
            print(json.dumps({
                "seed": seed, "served": name,
                "max_token_logprob_diff": float(diff.max()),
                "faulted_lanes_max": float(diff[:2].max()),
                "other_lanes_max": (
                    float(diff[2:].max()) if lanes > 2 else None
                ),
                "tokens": int(diff.size),
            }), flush=True)
        for name, rounder in rounders().items():
            # the tree again from the seed, rounded leaf by leaf in
            # place: two whole trees are more than the chip need hold
            del params
            params = fam.seeded_params(cfg, seed)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            del params
            for i, w in enumerate(leaves):
                if w.ndim >= 2 and w.dtype == jnp.bfloat16:
                    leaves[i] = jax.jit(
                        lambda w: rounder(w).astype(w.dtype),
                        donate_argnums=0,
                    )(w)
            del w
            params = jax.tree_util.tree_unflatten(treedef, leaves)
            del leaves
            got = answers(score(params, tokens))
            print(json.dumps({
                "seed": seed, "weights": name,
                "mean_logprob_shift": float(abs(got.mean() - exact.mean())),
                "max_token_logprob_shift": float(np.abs(got - exact).max()),
            }), flush=True)
        del params


if __name__ == "__main__":
    main(*sys.argv[1:6])
