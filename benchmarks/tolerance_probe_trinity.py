"""``tolerance_probe_keye_vl2.py``'s steps for a configuration of
``family_trinity`` — window and full attention layers over a cache of
two kinds of blocks, a gated attention output and a share of a layer's
sigmoid-routed experts (run by hand on the chip when the cell's
``logprob_tol`` and ``routing_slack_max`` are set, not by a cell):

    python3 benchmarks/tolerance_probe_trinity.py <config.json> <traffic.json> <seed> [lanes] [answer]

The program's own serving path — the family's ``serving_parts`` step
programs over a pool made by ``rl/kv_cache`` and the window layers'
blocks handed out by its ``WindowBlocks`` as the scheduler hands them
out, the traffic file's block size, chunk and backend, ``lanes`` lanes
side by side (the pools are sized for them, not for the cell's 16),
driven here token by token with the tokens given (prefill in chunks,
then paged decode) — is scored as a cell's check scores it: the float32
reference FORCED onto the experts the served side chose, the largest
difference of one answer token's logprob AND the largest routing slack
over every computed position.  One JSON line a reading: ``sound``, and
one fault each (a sound pair of limits has every control over at least
one of them):

- ``window_ignored``: the window layers read every cached key (the
  served side built with ``sliding_window = max_seq_len``);
- ``rope_on_full``: the full layer rotates q and k as a window layer
  does;
- ``gate_dropped``: the attention output goes to ``W_o`` without its
  sigmoid gate;
- ``window_block_stale`` / ``window_blocks_stale_16``: after prefill,
  one live block (16 tokens) / sixteen of them (256 tokens) of every
  lane's window hold ANOTHER lane's keys and values in all four window
  layers — what a lane reads when a block it has given back, by then
  re-issued, is still in its table;
- ``bias_dropped``: the served router selects without its bias;
- ``held_expert_dropped``: the first held expert's term is missing in
  every expert layer (its ``w_down`` zeroed on the served side);
- ``route_scale_dropped``: the routed experts' weights sum to 1, not to
  ``route_scale``;
- ``int8_weights``: every weight matrix the served side multiplies with
  rounded through int8 (one scale per tensor): the precision below the
  configuration's.

The attention faults are patched into the program's modules HERE, for
the reading's own trace; nothing of them is in the program.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from tolerance_probe_keye_vl2 import step_programs  # noqa: E402

FAULTS = (
    None, "window_ignored", "rope_on_full", "gate_dropped",
    "window_block_stale", "window_blocks_stale_16", "bias_dropped",
    "held_expert_dropped", "route_scale_dropped", "int8_weights",
)
#: faults that change what a step program TRACES (patched modules)
PATCHED = ("rope_on_full", "gate_dropped")
#: faults of the served model's keywords: other programs, other pools
KEYWORDS = {
    "window_ignored": lambda kw: dict(sliding_window=kw["max_seq_len"]),
    "route_scale_dropped": lambda kw: dict(route_scale=1.0),
}
WEIGHTS = ("bias_dropped", "held_expert_dropped", "int8_weights")
STALE = {"window_block_stale": 1, "window_blocks_stale_16": 16}


class patched:
    """The program's modules with one fault in them, for one trace."""

    def __init__(self, fault, model):
        self.fault, self.model, self.saved = fault, model, []

    def _set(self, module, name, fn):
        self.saved.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def __enter__(self):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.ops import paged_attention as pa

        model = self.model
        if self.fault == "gate_dropped":
            output = model._attn_output

            def ungated(x, attn, g, lp, cfg):
                return output(x, attn, jnp.full_like(g, 40.0), lp, cfg)

            self._set(model, "_attn_output", ungated)
        elif self.fault == "rope_on_full":
            theta = 1e4

            def rotate(x, positions):  # [..., S, D] by [S]
                half = x.shape[-1] // 2
                freqs = theta ** (
                    -jnp.arange(half, dtype=jnp.float32) / half
                )
                ang = positions.astype(jnp.float32)[:, None] * freqs
                cos, sin = jnp.cos(ang), jnp.sin(ang)
                x1, x2 = x[..., :half], x[..., half:]
                return jnp.concatenate(
                    [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
                ).astype(x.dtype)

            chunk, decode = pa.paged_chunk_attention, pa.paged_decode_attention

            def chunk_rotated(q, k, v, start, key0, window=None, *a, **kw):
                if window is None:
                    c, t = q.shape[0], k.shape[1]
                    q = jnp.swapaxes(rotate(
                        jnp.swapaxes(q, 0, 1), start + jnp.arange(c)
                    ), 0, 1)
                    k = rotate(k, key0 + jnp.arange(t))
                return chunk(q, k, v, start, key0, window, *a, **kw)

            def decode_rotated(q, k_pool, v_pool, tables, lens,
                               backend=None, first=None, **kw):
                if first is not None:
                    return decode(q, k_pool, v_pool, tables, lens, backend,
                                  first=first, **kw)
                # the full layer: its keys by position, rotated, dense
                k = pa.gather_sequence(k_pool, tables)  # [B, T, KV, D]
                v = pa.gather_sequence(v_pool, tables)
                b, t, nkv, d = k.shape
                k = jnp.moveaxis(
                    rotate(jnp.moveaxis(k, 1, 2), jnp.arange(t)), 2, 1
                )
                q = jax.vmap(lambda row, p: rotate(row[:, None], p[None])
                             )(q, lens - 1)[:, :, 0]
                s = jnp.einsum(
                    "bkgd,btkd->bkgt", q.reshape(b, nkv, -1, d), k,
                    preferred_element_type=jnp.float32,
                ) * d ** -0.5
                s = jnp.where(
                    (jnp.arange(t)[None] < lens[:, None])[:, None, None],
                    s, -1e30,
                )
                out = jnp.einsum(
                    "bkgt,btkd->bkgd", jax.nn.softmax(s, -1).astype(v.dtype),
                    v, preferred_element_type=jnp.float32,
                ).astype(v.dtype)
                return out.reshape(q.shape)

            self._set(pa, "paged_chunk_attention", chunk_rotated)
            self._set(pa, "paged_decode_attention", decode_rotated)
        return self

    def __exit__(self, *exc):
        for module, name, fn in reversed(self.saved):
            setattr(module, name, fn)


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       answer, fault, cfg):
    """-> (logprobs ``[lanes, answer]`` of each lane's answer tokens,
    experts ``[lanes, total, expert layers, k]`` with -1 where a
    position was never computed), as the paged programs compute them
    over the two kinds of blocks."""
    import functools

    import numpy as np

    from dlrover_tpu.rl.kv_cache import (
        BlockPool,
        init_block_pool,
        paged_cache_config,
    )

    traced = fault if fault in PATCHED else None
    key = (traced, id(parts))
    if key not in programs:
        programs[key] = step_programs(parts)
    with patched(traced, sys.modules[type(parts["cfg"]).__module__]):
        prefill, decode = (
            functools.partial(f, params) for f in programs[key]
        )
        lanes, total = tokens.shape
        chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
        mb = -(-traffic["max_seq_len"] // bs)
        window = parts["cfg"].sliding_window
        cache_cfg = paged_cache_config(
            parts["cfg"], lanes * mb + 1, bs, lanes, chunk
        )
        pool = init_block_pool(cache_cfg)
        rings = BlockPool(cache_cfg).window
        full = np.zeros((lanes, mb), np.int32)
        for i in range(lanes):  # lane i owns blocks 1 + i * mb ...
            full[i] = 1 + i * mb + np.arange(mb)

        def tables(lane, start, end):
            rings.advance(lane, start - window + 1, end)
            return np.concatenate(
                [full[lane], np.asarray(rings.table_row(lane), np.int32)]
            )

        out = np.zeros((lanes, answer), np.float32)
        experts = np.full(
            (lanes, total, cfg["num_expert_layers"],
             cfg["num_experts_per_tok"]), -1, np.int32,
        )
        for i in range(lanes):
            p = int(prompt_lens[i])
            for start in range(0, p, chunk):
                real = min(chunk, p - start)
                piece = np.zeros((1, chunk), np.int32)
                piece[0, :real] = tokens[i, start:start + real]
                pool, lp, rows = prefill(
                    pool, piece, tables(i, start, start + chunk),
                    np.int32(start), np.int32(real),
                    np.int32(tokens[i, start + real]),
                )
                experts[i, start:start + real] = np.asarray(rows)[:real]
            out[i, 0] = float(lp)
        if fault in STALE:
            # lane i's live blocks a quarter of the window behind its
            # newest hold lane i + 1's: a block given back and re-issued,
            # still named
            ring_len = rings.table_blocks
            behind = max(window // bs // 4, 1)
            mine, theirs = [], []
            for i in range(lanes):
                j = (i + 1) % lanes
                for n in range(min(STALE[fault], behind)):
                    a = int(prompt_lens[i]) // bs - behind - n
                    b = int(prompt_lens[j]) // bs - behind - n
                    mine.append(rings.table_row(i)[a % ring_len])
                    theirs.append(rings.table_row(j)[b % ring_len])
            assert 0 not in mine + theirs
            mine, theirs = np.asarray(mine), np.asarray(theirs)
            pool = dict(
                pool,
                wk=pool["wk"].at[:, mine].set(pool["wk"][:, theirs]),
                wv=pool["wv"].at[:, mine].set(pool["wv"][:, theirs]),
            )
        active = np.ones((lanes,), bool)
        for j in range(answer - 1):
            toks = np.zeros((lanes,), np.int32)
            pos = np.zeros((lanes,), np.int32)
            nxt = np.zeros((lanes,), np.int32)
            rows_t = np.zeros((lanes, full.shape[1] + rings.table_blocks),
                              np.int32)
            for i in range(lanes):
                at = int(prompt_lens[i]) + j
                toks[i], pos[i], nxt[i] = (
                    tokens[i, at], at, tokens[i, at + 1]
                )
                rows_t[i] = tables(i, at, at + 1)
            pool, lps, rows = decode(pool, toks, rows_t, pos, active, nxt)
            out[:, j + 1] = np.asarray(lps)[:lanes]
            rows = np.asarray(rows)
            for i in range(lanes):
                experts[i, pos[i]] = rows[i]
        del pool
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

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    del params
    leaves = []
    for path, w in flat:
        name = path[-1].key
        if fault == "int8_weights" and w.ndim >= 2 and (
            w.dtype == jnp.bfloat16
        ):
            w = jax.jit(int8, donate_argnums=0)(w)
        elif fault == "bias_dropped" and name == "router_bias":
            w = jnp.zeros_like(w)
        elif fault == "held_expert_dropped" and name == "w_down":
            w = jax.jit(lambda a: a.at[0].set(0), donate_argnums=0)(w)
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
    # lane is past the window before its first answer token
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

    kwargs = fam.model_kwargs(cfg, traffic["max_seq_len"])
    sound_parts = fam.serving_parts(**kwargs, dtype="bfloat16")
    params = fam.seeded_params(cfg, seed)
    served, programs = {}, {}
    for fault in FAULTS:
        parts = sound_parts
        if fault in KEYWORDS:
            parts = fam.serving_parts(
                **dict(kwargs, **KEYWORDS[fault](kwargs)), dtype="bfloat16"
            )
        if fault in WEIGHTS:
            # each from the seed's own tree: the one before is spent
            del params
            params = altered_weights(fam.seeded_params(cfg, seed), fault)
        served[fault or "sound"] = serve_given_tokens(
            parts, programs, parts["serving_params_fn"](params), traffic,
            tokens, prompt_lens, answer, fault, cfg,
        )
        if fault in WEIGHTS:
            del params
            params = fam.seeded_params(cfg, seed)
    del params, parts, sound_parts, programs

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
