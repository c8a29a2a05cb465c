"""``tolerance_probe_state.py``'s steps for a configuration of
``family_keye_vl2`` — routed experts and a learned top-k indexer over a
paged index-key cache (run by hand on the chip when the cell's
``logprob_tol`` and ``routing_slack_max`` are set, not by a cell):

    python3 benchmarks/tolerance_probe_keye_vl2.py <config.json> <traffic.json> <seed> [lanes] [answer]

The program's own serving path — the family's ``serving_parts`` step
programs over a pool made by ``rl/kv_cache``, the traffic file's
geometry and backend, driven here token by token with the tokens given
(prefill in chunks, then paged decode, several lanes side by side) —
is scored as a cell's check scores it: the float32 reference FORCED
onto the experts the served side chose, the largest difference of one
answer token's logprob AND the largest routing slack over every
computed position.  One JSON line a reading: ``sound``, and one fault
each (a sound tolerance pair has every control over at least one of
its two limits):

- ``int8_weights``: every weight matrix the served side multiplies
  with rounded through int8 (one scale per tensor): the precision
  below the configuration's;
- ``indexer_bypassed``: the newest ``topk`` positions taken in place of
  the ``topk`` of largest index score (a sliding window);
- ``index_block_swapped`` / ``index_lane_swapped``: the index keys of
  one block / of every prompt block of lanes 0 and 1 exchanged after
  prefill (K and V stay);
- ``index_keys_of_previous_layer``: layer ``l``'s queries scored
  against layer ``l - 1``'s index keys — a selection carried across
  layers (the layer scan has no place to hand layer ``l``'s selection
  itself to layer ``l + 1`` without another program);
- ``router_perturbed``: normal(0, 0.5) added to the served router's
  logits; ``router_random``: its experts drawn at random;
- ``experts_exchanged``: the weights of experts 0 and 1 of every layer
  exchanged on the served side (its router untouched; the hidden states
  drift from the reference's, so its later choices read as slack too).

The indexer and router faults are patched into the program's modules
HERE, for the reading's own trace; nothing of them is in the program.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FAULTS = (
    None, "indexer_bypassed", "index_block_swapped", "index_lane_swapped",
    "index_keys_of_previous_layer", "router_perturbed", "router_random",
    "experts_exchanged", "int8_weights",
)
#: faults that change what a step program TRACES (patched modules)
PATCHED = (
    "indexer_bypassed", "index_keys_of_previous_layer", "router_perturbed",
    "router_random",
)


class patched:
    """The program's modules with one fault in them, for one trace."""

    def __init__(self, fault, cfg, model):
        """``model``: the module of the program's model (the family's
        ``serving_parts`` say which; nothing here names one)."""
        self.fault, self.cfg, self.model, self.saved = fault, cfg, model, []

    def _set(self, module, name, fn):
        self.saved.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def __enter__(self):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.ops import paged_attention as pa

        fault, cfg, model = self.fault, self.cfg, self.model
        if fault == "indexer_bypassed":
            def newest_rows(scores, k, tables):
                n = jnp.sum(jnp.isfinite(scores), -1, keepdims=True)
                pos = (jnp.maximum(n - k, 0) + jnp.arange(k)).astype(jnp.int32)
                bs = scores.shape[1] // tables.shape[1]
                blocks = jnp.take_along_axis(tables, pos // bs, axis=1)
                return blocks * bs + pos % bs

            def newest_mask(scores, k):
                visible = jnp.isfinite(scores)
                n = jnp.sum(visible, -1, keepdims=True)
                return visible & (jnp.arange(scores.shape[-1]) >= n - k)

            self._set(pa, "exact_topk_rows", newest_rows)
            self._set(pa, "exact_topk_mask", newest_mask)
        elif fault == "index_keys_of_previous_layer":
            layers = cfg["num_hidden_layers"]

            gather = pa.gather_index_keys

            def previous(ik_pool, tables, width):
                per_layer = ik_pool.shape[0] // layers
                return gather(
                    ik_pool,
                    jnp.where(tables >= per_layer, tables - per_layer, tables),
                    width,
                )

            self._set(pa, "gather_index_keys", previous)
        elif fault in ("router_perturbed", "router_random"):
            route = model._route
            k = cfg["num_experts_per_tok"]

            def faulty(x, lp, model_cfg):
                h, _, _ = route(x, lp, model_cfg)
                logits = jnp.matmul(
                    h.astype(jnp.float32), lp["router"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
                noise = jax.random.normal(
                    jax.random.PRNGKey(7), logits.shape, jnp.float32
                )
                scored = (
                    logits + 0.5 * noise if fault == "router_perturbed"
                    else noise
                )
                ids = jax.lax.top_k(scored, k)[1].astype(jnp.int32)
                taken = jnp.take_along_axis(logits, ids, -1)
                return h, ids, jax.nn.softmax(taken, -1)

            self._set(model, "_route", faulty)
        return self

    def __exit__(self, *exc):
        for module, name, fn in reversed(self.saved):
            setattr(module, name, fn)


def step_programs(parts):
    """The family's two step programs, each returning the logprob of
    the token(s) that follow and the experts every row was sent to,
    compiled once a trace (the weights are an argument)."""
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
        return pool, logprob(logits[0, real - 1], nxt), rows["experts"]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pool, toks, tables, positions, active, nxt):
        logits, pool, rows = parts["paged_decode_fn"](
            params, toks, pool, tables, positions, active
        )
        return pool, jax.vmap(logprob)(logits, nxt), rows["experts"]

    return prefill, decode


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       answer, fault, cfg):
    """-> (logprobs ``[lanes, answer]`` of each lane's answer tokens,
    experts ``[lanes, total, layers, k]`` with -1 where a position was
    never computed), as the paged programs compute them.  ``programs``
    keeps the step programs of every fault that traces the same."""
    import functools

    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    traced = fault if fault in PATCHED else None
    if traced not in programs:
        programs[traced] = step_programs(parts)
    with patched(traced, cfg, sys.modules[type(parts["cfg"]).__module__]):
        prefill, decode = (
            functools.partial(f, params) for f in programs[traced]
        )
        lanes, total = tokens.shape
        chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
        mb = -(-traffic["max_seq_len"] // bs)
        slots = traffic["max_slots"]
        pool = init_block_pool(paged_cache_config(
            parts["cfg"], traffic["num_blocks"], bs, slots
        ))
        tables = np.zeros((slots, mb), np.int32)
        for i in range(lanes):  # lane i owns blocks 1 + i * mb ...
            tables[i] = 1 + i * mb + np.arange(mb)
        out = np.zeros((lanes, answer), np.float32)
        experts = np.full(
            (lanes, total, cfg["num_hidden_layers"],
             cfg["num_experts_per_tok"]), -1, np.int32,
        )
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
                experts[i, start:start + real] = np.asarray(rows)[:real]
            out[i, 0] = float(lp)
        if fault in ("index_block_swapped", "index_lane_swapped"):
            n = 1 if fault == "index_block_swapped" else (
                int(min(prompt_lens[:2])) // bs
            )
            a, b = tables[0, :n], tables[1, :n]
            ik = pool["ik"]
            pool = dict(
                pool, ik=ik.at[:, a].set(ik[:, b]).at[:, b].set(ik[:, a])
            )
        active = np.zeros((slots,), bool)
        active[:lanes] = True
        for j in range(answer - 1):
            toks = np.zeros((slots,), np.int32)
            pos = np.zeros((slots,), np.int32)
            nxt = np.zeros((slots,), np.int32)
            for i in range(lanes):
                at = int(prompt_lens[i]) + j
                toks[i], pos[i], nxt[i] = (
                    tokens[i, at], at, tokens[i, at + 1]
                )
            pool, lps, rows = decode(pool, toks, tables, pos, active, nxt)
            out[:, j + 1] = np.asarray(lps)[:lanes]
            rows = np.asarray(rows)
            for i in range(lanes):
                experts[i, pos[i]] = rows[i]
    return out, experts


def altered_weights(params, fault):
    """The served tree with ``fault`` in its weights, leaf by leaf in
    place (two whole trees do not fit the chip)."""
    import jax
    import jax.numpy as jnp

    def int8(w):
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f)) / 127.0
        return (jnp.round(f / scale).clip(-127, 127) * scale).astype(w.dtype)

    def exchanged(w):  # [L, E, ...]: experts 0 and 1 of every layer
        return w.at[:, 0].set(w[:, 1]).at[:, 1].set(w[:, 0])

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    leaves = []
    for path, w in flat:
        name = path[-1].key
        if fault == "int8_weights" and w.ndim >= 2 and (
            w.dtype == jnp.bfloat16
        ):
            w = jax.jit(int8, donate_argnums=0)(w)
        elif fault == "experts_exchanged" and name in (
            "w_gate", "w_up", "w_down"
        ):
            w = jax.jit(exchanged, donate_argnums=0)(w)
        leaves.append(w)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def main(config_path, traffic_path, seed, lanes=4, answer=64):
    import jax
    import numpy as np

    import harness

    seed, lanes, answer = int(seed), int(lanes), int(answer)
    cfg = harness.load_json(config_path)
    traffic = harness.load_json(traffic_path)
    os.environ["DLROVER_TPU_PAGED_KERNEL"] = traffic["paged_kernel"]
    fam = harness.family(cfg)
    rng = np.random.default_rng(seed)
    chunk = traffic["prefill_chunk"]
    # lane i prefills i + 2 whole chunks and a few tokens more: every
    # lane holds more than ``topk`` tokens before its first answer token
    prompt_lens = [
        chunk * (i + 2) + int(rng.integers(3, max(chunk // 8, 4)))
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
    params = fam.seeded_params(cfg, seed)
    served, programs = {}, {}
    for fault in FAULTS:
        if fault in ("experts_exchanged", "int8_weights"):
            # each from the seed's own tree: the one before is spent
            del params
            params = altered_weights(fam.seeded_params(cfg, seed), fault)
        served[fault or "sound"] = serve_given_tokens(
            parts, programs, parts["serving_params_fn"](params), traffic,
            tokens, prompt_lens, answer, fault, cfg,
        )
    del params, parts, programs

    params = fam.seeded_params(cfg, seed)
    score = jax.jit(lambda p, t, s: fam.token_logprobs_forced(p, t, cfg, s))
    for name, (got, experts) in served.items():
        ref, slack = (
            np.asarray(a) for a in score(params, tokens, {"experts": experts})
        )
        diff, worst, off = 0.0, 0.0, 0
        for i, p in enumerate(prompt_lens):
            d = np.abs(ref[i, p - 1:p - 1 + answer] - got[i])
            diff = max(diff, float(np.where(np.isfinite(d), d, np.inf).max()))
            row = slack[i, :p + answer - 1]
            row = np.where(np.isfinite(row), row, np.float32(np.inf))
            worst, off = max(worst, float(row.max())), off + int((row > 0).sum())
        print(json.dumps({
            "served": name,
            "logprob_max_abs_diff": diff,
            "max_routing_slack": worst,
            "positions_off_own_topk": off,
            "answer_tokens": int(lanes * answer),
        }), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:6])
