"""The step programs carry the K/V pool through the layer scan and
address layer ``l``'s blocks at ``l * num_blocks``.

Each of the six serving programs, at tiny widths and depth 3 on the
CPU, against ITSELF with ``scan_layers_over_pool`` replaced by a plain
Python loop over layers on per-layer pools — no scan, no flat view, no
offset (``base`` 0, the table's ids as they stand).  Logits and every
pool block must agree bitwise; what the programs discard (an inactive
lane, a padded chunk tail, a window past the table) lands in its OWN
layer's null block and nowhere else.  An off-by-one in ``base`` writes
into the neighbouring layer, which a logits check at depth 2 can miss:
the pool starts full of noise, so "untouched" is bitwise too.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import falcon_h1, llama
from dlrover_tpu.ops import paged_attention as pa

DEPTH, BLOCKS, BS, LANES, CHUNK, WINDOW = 3, 12, 4, 3, 8, 3
# lane 0 sits mid-sequence, lane 1 is INACTIVE in the batched programs
# (its table names blocks it must not touch), lane 2's windows and
# chunks run up to and past the end of its table (16 positions)
TABLES = [[3, 5, 0, 0], [7, 8, 0, 0], [9, 2, 4, 6]]


def _loop_layers_over_pool(body, carry, xs, k_pool, v_pool, read_only=False):
    """``scan_layers_over_pool``'s contract, the plain way: layer
    ``l`` is handed ``k_pool[l]``, ``v_pool[l]`` alone."""
    ks, vs, ys = [], [], []
    for layer in range(k_pool.shape[0]):
        xs_l = jax.tree_util.tree_map(lambda a: a[layer], xs)
        kv = pa.LayerPool(
            k_pool[layer], v_pool[layer], jnp.int32(0), jnp.int32(layer)
        )
        if read_only:
            carry, ys_l = body(carry, xs_l, kv)
        else:
            carry, ys_l, kv = body(carry, xs_l, kv)
            ks.append(kv.k)
            vs.append(kv.v)
        ys.append(ys_l)
    ys = (
        None if ys[0] is None
        else jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    )
    if read_only:
        return carry, ys
    return carry, ys, jnp.stack(ks), jnp.stack(vs)


def _noise(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _llama_case(program):
    cfg = llama.LlamaConfig.tiny(
        n_layers=DEPTH, n_heads=4, n_kv_heads=2, dim=32, max_seq_len=32,
        dtype=jnp.float32,
    )
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    params = llama.init_params(keys[0], cfg)
    shape = (DEPTH, BLOCKS, BS, cfg.n_kv_heads, cfg.head_dim)
    pool = {
        "k": _noise(keys[1], shape, cfg.dtype),
        "v": _noise(keys[2], shape, cfg.dtype),
    }
    tables = jnp.array(TABLES, jnp.int32)
    active = jnp.array([True, False, True])
    if program == "prefill_chunk":
        # 8 positions from 12: the table ends at 16, so the chunk's
        # second half is a padded tail past it
        fn = partial(llama.paged_prefill_chunk, cfg=cfg)
        tokens = jax.random.randint(keys[3], (1, CHUNK), 0, cfg.vocab_size)
        args = (params, tokens, pool, tables[2], jnp.int32(12))
        named = {9, 2, 4, 6}
        written = {6}
    else:
        positions = jnp.array([5, 6, 14], jnp.int32)
        named = {3, 5, 9, 2, 4, 6}
        if program == "decode":
            fn = partial(llama.paged_decode_step, cfg=cfg)
            tokens = jax.random.randint(keys[3], (LANES,), 0, cfg.vocab_size)
            written = {5, 6}
        else:
            fn = partial(
                llama.paged_verify_step if program == "verify"
                else llama.paged_verify_write_step, cfg=cfg,
            )
            tokens = jax.random.randint(
                keys[3], (LANES, WINDOW), 0, cfg.vocab_size
            )
            # lane 0 writes 5..7 (block 5), lane 2 writes 14, 15
            # (block 6) and position 16, past the table: null block
            written = set() if program == "verify" else {5, 6}
        args = (params, tokens, pool, tables, positions, active)
    return fn, args, written, named


def _falcon_case(program):
    cfg = falcon_h1.FalconH1Config.tiny(
        num_hidden_layers=DEPTH, dtype=jnp.float32
    )
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    params = falcon_h1.init_params(keys[0], cfg)
    shape = (
        DEPTH, BLOCKS, BS, cfg.num_key_value_heads, cfg.head_dim
    )
    pool = {
        "k": _noise(keys[1], shape, cfg.dtype),
        "v": _noise(keys[2], shape, cfg.dtype),
    }
    for i, (leaf, (lshape, ldtype)) in enumerate(cfg.lane_state().items()):
        pool[leaf] = 0.1 * _noise(
            keys[3 + i], (DEPTH, LANES) + lshape, ldtype
        )
    tables = jnp.array(TABLES, jnp.int32)
    if program == "prefill_chunk":
        # five real tokens of eight, from 8: block 4 and one row of 6
        fn = partial(falcon_h1.paged_prefill_chunk, cfg=cfg)
        tokens = jax.random.randint(keys[5], (1, CHUNK), 0, cfg.vocab_size)
        args = (
            params, tokens, pool, tables[2], jnp.int32(8), jnp.int32(2),
            jnp.int32(5),
        )
        return fn, args, {4, 6}, {9, 2, 4, 6}
    fn = partial(falcon_h1.paged_decode_step, cfg=cfg)
    tokens = jax.random.randint(keys[5], (LANES,), 0, cfg.vocab_size)
    args = (
        params, tokens, pool, tables, jnp.array([5, 6, 14], jnp.int32),
        jnp.array([True, False, True]),
    )
    return fn, args, {5, 6}, {3, 5, 9, 2, 4, 6}


CASES = {
    "llama-decode": partial(_llama_case, "decode"),
    "llama-prefill_chunk": partial(_llama_case, "prefill_chunk"),
    "llama-verify": partial(_llama_case, "verify"),
    "llama-verify_write": partial(_llama_case, "verify_write"),
    "falcon_h1-decode": partial(_falcon_case, "decode"),
    "falcon_h1-prefill_chunk": partial(_falcon_case, "prefill_chunk"),
}


def _changed_blocks(before, after):
    """``{layer: {block ids whose bytes differ}}`` of one pool leaf."""
    diff = np.any(
        np.asarray(before) != np.asarray(after), axis=(2, 3, 4)
    )  # [L, N]
    return {
        layer: set(np.flatnonzero(diff[layer]).tolist())
        for layer in range(diff.shape[0])
    }


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_program_equals_a_plain_loop_over_per_layer_pools(
    case, backend, monkeypatch
):
    monkeypatch.setenv(pa.PAGED_KERNEL_ENV, backend)
    fn, args, written, named = CASES[case]()
    pool = args[2]
    out = jax.jit(fn)(*args)
    with monkeypatch.context() as m:
        m.setattr(pa, "scan_layers_over_pool", _loop_layers_over_pool)
        ref = jax.jit(fn)(*args)
    read_only = not isinstance(out, tuple)
    logits, ref_logits = (out, ref) if read_only else (out[0], ref[0])
    assert np.isfinite(np.asarray(logits)).all()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    if read_only:
        return
    new_pool, ref_pool = out[1], ref[1]
    assert sorted(new_pool) == sorted(pool)
    for leaf in pool:
        assert new_pool[leaf].shape == pool[leaf].shape
        np.testing.assert_array_equal(
            np.asarray(new_pool[leaf]), np.asarray(ref_pool[leaf]), leaf
        )
    for leaf in ("k", "v"):
        for layer, changed in _changed_blocks(
            pool[leaf], new_pool[leaf]
        ).items():
            # what the program discards went to block 0 of THIS layer's
            # own blocks; every real write is there; nothing else moved
            # — not the inactive lane's blocks, not a block no table
            # names, in no layer
            assert changed == written | {0}, (leaf, layer, changed)
            assert not (changed - {0}) - named
