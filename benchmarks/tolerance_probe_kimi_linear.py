"""``tolerance_probe_deepseek_v32.py``'s steps for a configuration of
``family_kimi_linear`` — Kimi Delta Attention layers that keep a
per-channel-gated state a lane, NoPE latent-attention layers that keep
one compressed row a token, and a share of a layer's sigmoid-routed
experts (run by hand on the chip when the cell's ``logprob_tol`` and
``routing_slack_max`` are set, not by a cell):

    python3 benchmarks/tolerance_probe_kimi_linear.py <config.json> \
        <traffic.json> <seed,seed,..> [lanes] [answer] [fault,fault,..]

The program's own serving path — the family's ``serving_parts`` step
programs over a pool made by ``rl/kv_cache`` (state slabs for the KDA
layers, latent leaves for the MLA layers, no ``k``, no ``v``), the
traffic file's block size, chunk and backend, ``lanes`` lanes side by
side (the pool is sized for them, not for the cell's 128), driven here
token by token with the tokens given (lane ``i`` prefills ``i + 2``
chunks and a few tokens, then paged decode) — is scored as a cell's
check scores it: the float32 reference FORCED onto the experts the
served side chose; the largest difference of one answer token's logprob
and the largest routing slack over every computed position
(``family.forced_readings``).  Every number is a MAXIMUM over the tokens
read, so a fault's reading over ``answer`` tokens is a floor of what it
reads over a cell's thousands.  One JSON line a reading: ``sound``
first, then one fault each of those asked for (all by default; a sound
pair of limits has every control over at least one of them), seed by
seed:

- ``int8_weights``: every weight matrix the served side multiplies with
  rounded through int8 (one scale per tensor): the precision below the
  configuration's;
- ``mean_decay``: a head's 128 decays replaced by their mean — the
  scalar gate of ``ops/gdn.py``;
- ``no_decay``: the decay dropped (``a`` = 1);
- ``beta_doubled``: ``beta = 2 sigmoid`` (Olmo-Hybrid's rule);
- ``gate_silu``: the output gate's sigmoid replaced by SiLU
  (GatedDeltaNet's rule);
- ``conv_zeroed``: lane 0's conv tail zeroed between its first and
  second prefill chunk;
- ``state_zeroed``: lane 0's recurrent state zeroed there;
- ``state_other_rank``: in decode, KDA layer ``j`` advances the slab of
  layer ``j + 1`` (the last one the first's);
- ``leaf_other_rank``: in decode, MLA layer ``j`` reads the latent rows
  of layer ``j - 1`` (the first its own);
- ``lanes_exchanged``: after prefill, the first 64 blocks (1024 tokens)
  of lane ``i``'s latents and shared keys are lane ``i + 1``'s in every
  MLA layer;
- ``kpe_zeroed``: after prefill, the shared ``pe`` part of every cached
  row is zero;
- ``kpe_rotated``: after prefill, the ``pe`` part of every cached row
  is rotated by its position (split-half pairs, theta 10000: what a
  model that did not set ``mla_use_nope`` would have cached);
- ``bias_dropped``: the served router selects without its bias;
- ``scaling_dropped``: the routed experts' weights sum to 1, not to
  ``routed_scaling_factor``;
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

FAULTS = (
    "int8_weights", "mean_decay", "no_decay", "beta_doubled", "gate_silu",
    "conv_zeroed", "state_zeroed", "state_other_rank", "leaf_other_rank",
    "lanes_exchanged", "kpe_zeroed", "kpe_rotated", "bias_dropped",
    "scaling_dropped", "held_expert_dropped",
)
#: faults that change what a step program TRACES (patched modules)
PATCHED = (
    "mean_decay", "no_decay", "beta_doubled", "gate_silu",
    "state_other_rank", "leaf_other_rank",
)
WEIGHTS = (
    "bias_dropped", "held_expert_dropped", "int8_weights", "scaling_dropped"
)
#: blocks of a lane's prompt that ``lanes_exchanged`` exchanges
EXCHANGED_BLOCKS = 64


def step_programs(parts):
    """The family's two step programs, each returning the logprob of the
    token(s) that follow and the experts every row chose, compiled once
    a trace (the weights are an argument)."""
    import functools

    import jax
    import jax.numpy as jnp

    def logprob(logits, token):
        return jax.nn.log_softmax(logits.astype(jnp.float32), -1)[token]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, pool, chunk_tokens, table, start, lane, real, nxt):
        logits, pool, rows = parts["paged_prefill_fn"](
            params, chunk_tokens, pool, table, start, lane, real
        )
        return pool, logprob(logits[0, real - 1], nxt), rows

    @functools.partial(jax.jit, donate_argnums=(1,))
    def decode(params, pool, toks, tables, positions, active, nxt):
        logits, pool, rows = parts["paged_decode_fn"](
            params, toks, pool, tables, positions, active
        )
        return pool, jax.vmap(logprob)(logits, nxt), rows

    return prefill, decode


class patched:
    """The program's modules with one fault in them, for one trace."""

    def __init__(self, fault, num_blocks, model):
        """``model``: the module of the served model's step programs
        (the family's, found through its config object: nothing here
        names a model)."""
        self.fault, self.num_blocks, self.saved = fault, num_blocks, []
        self.model = model

    def _set(self, module, name, fn):
        self.saved.append((module, name, getattr(module, name)))
        setattr(module, name, fn)

    def __enter__(self):
        import jax
        import jax.numpy as jnp

        from dlrover_tpu.ops import paged_attention as pa

        fault, model = self.fault, self.model
        if fault in ("mean_decay", "no_decay", "beta_doubled"):
            gates = model._kda_gates

            def faulty_gates(f, b, lp, cfg):
                alpha, beta = gates(f, b, lp, cfg)
                if fault == "beta_doubled":
                    return alpha, 2.0 * beta
                if fault == "no_decay":
                    return jnp.ones_like(alpha), beta
                mean = jnp.mean(alpha, -1, keepdims=True)
                return jnp.broadcast_to(mean, alpha.shape), beta

            self._set(model, "_kda_gates", faulty_gates)
        elif fault == "gate_silu":

            def silu_gated(x, o, g, lp, cfg):
                # the program's ``_kda_output`` with SiLU for sigmoid
                o = o * jax.lax.rsqrt(
                    jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps
                ) * lp["kda_norm"]
                y = o.reshape(g.shape) * jax.nn.silu(g)
                return x + model._proj(
                    y.astype(cfg.dtype), lp["wo"], cfg.dtype
                )

            self._set(model, "_kda_output", silu_gated)
        elif fault == "state_other_rank":
            update = model.kda_decode_update

            def next_slab(state, layer, *rest):
                return update(
                    state, (layer + 1) % state.shape[0], *rest
                )

            self._set(model, "kda_decode_update", next_slab)
        elif fault == "leaf_other_rank":
            attend, nb = pa.latent_decode_attention, self.num_blocks

            def previous(q_c, q_pe, c_leaf, pe_leaf, tables, *rest):
                return attend(
                    q_c, q_pe, c_leaf, pe_leaf,
                    jnp.where(tables >= nb, tables - nb, tables), *rest
                )

            self._set(pa, "latent_decode_attention", previous)
        return self

    def __exit__(self, *exc):
        for module, name, fn in reversed(self.saved):
            setattr(module, name, fn)


def _rotated(kpe, tables, block_size, dr, theta=10000.0):
    """The ``kpe`` leaf ``[layers, blocks, rows, 128]`` with the rows of
    the lanes' ``tables`` rotated by their positions (split-half
    pairs)."""
    import jax.numpy as jnp
    import numpy as np

    shape = kpe.shape
    rows = kpe.reshape(shape[0], shape[1], block_size, dr)
    half = dr // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    for table in tables:
        pos = (
            np.arange(table.size * block_size, dtype=np.float32)
        ).reshape(table.size, block_size, 1)
        cos, sin = jnp.cos(pos * freq), jnp.sin(pos * freq)
        mine = rows[:, table].astype(jnp.float32)
        x1, x2 = mine[..., :half], mine[..., half:]
        rows = rows.at[:, table].set(jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1
        ).astype(rows.dtype))
    return rows.reshape(shape)


def serve_given_tokens(parts, programs, params, traffic, tokens, prompt_lens,
                       answer, fault, cfg):
    """-> (logprobs ``[lanes, answer]`` of each lane's answer tokens, the
    experts every computed position chose ``{"experts": [lanes, total,
    expert layers, k]}``, -1 where a position was never computed), as
    the paged programs compute them.  A fault of the cache hits lane 0
    at its first chunk boundary, or every lane after prefill."""
    import functools
    import importlib

    import numpy as np

    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    model = importlib.import_module(type(parts["cfg"]).__module__)
    traced = fault if fault in PATCHED else None
    key = (traced, id(parts))
    lanes, total = tokens.shape
    chunk, bs = traffic["prefill_chunk"], traffic["block_size"]
    mb = -(-traffic["max_seq_len"] // bs)
    num_blocks = lanes * mb + 1
    with patched(traced, num_blocks, model):
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
            for n, start in enumerate(range(0, p, chunk)):
                if i == 0 and n == 1 and fault in (
                    "state_zeroed", "conv_zeroed"
                ):
                    leaf = "kda" if fault == "state_zeroed" else "conv"
                    pool = dict(pool, **{leaf: pool[leaf].at[:, 0].set(0.0)})
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
        if fault == "lanes_exchanged":
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
        elif fault == "kpe_rotated":
            pool = dict(pool, kpe=_rotated(
                pool["kpe"], tables, bs, cfg["qk_rope_head_dim"]
            ))
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
        # lane i prefills i + 2 whole chunks and a few tokens more: lane
        # 0's first chunk boundary, where the cache's faults strike, lies
        # a chunk and a few tokens before its answer
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
        served = {}
        params = fam.seeded_params(cfg, seed)
        # the fault that spends the seed's tree comes last
        for fault in [None] + sorted(faults, key="int8_weights".__eq__):
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
