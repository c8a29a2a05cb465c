"""Paged KV cache: block-pool accounting and the paged attention ops
(``rl/kv_cache.py`` + ``ops/paged_attention.py`` + the paged decode
path in ``models/llama.py``).

The correctness bar: a sequence decoded through scattered pool blocks
must produce EXACTLY the tokens the dense contiguous-cache path
produces (greedy, fp32) — block tables are an addressing scheme, not
an approximation."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models import llama  # noqa: E402
from dlrover_tpu.ops.paged_attention import (  # noqa: E402
    paged_decode_attention,
    paged_prefill_attention,
)
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    BlockPool,
    DoubleFreeError,
    OutOfBlocksError,
    PagedCacheConfig,
    init_block_pool,
    prefix_block_keys,
)

CACHE_CFG = PagedCacheConfig(
    n_layers=2, n_kv_heads=2, head_dim=8, num_blocks=9, block_size=4,
    dtype=jnp.float32,
)


class TestBlockPool:
    def test_null_block_reserved(self):
        pool = BlockPool(CACHE_CFG)
        assert pool.free_blocks == 8  # 9 minus the null block
        blocks = pool.allocate(0, 32)  # exactly the whole pool
        assert 0 not in blocks
        assert pool.free_blocks == 0

    def test_alloc_free_no_leak_under_churn(self):
        """Hundreds of mixed-size admissions/evictions must return
        the pool to exactly its initial state — a leaked block would
        eventually wedge admission forever."""
        pool = BlockPool(CACHE_CFG)
        rng = np.random.default_rng(0)
        live = {}
        for i in range(300):
            if live and (len(live) > 3 or rng.random() < 0.4):
                sid = rng.choice(list(live))
                pool.free(int(sid))
                del live[int(sid)]
            n_tokens = int(rng.integers(1, 13))
            if pool.can_allocate(n_tokens):
                pool.allocate(i + 1000, n_tokens)
                live[i + 1000] = n_tokens
        for sid in list(live):
            pool.free(sid)
        assert pool.used_blocks == 0
        assert pool.free_blocks == CACHE_CFG.usable_blocks
        assert pool.live_sequences == 0
        assert pool.alloc_count == pool.free_count > 0
        # freed-everything => no reserved slots => no fragmentation
        assert pool.internal_fragmentation() == 0.0

    def test_out_of_blocks_is_loud(self):
        pool = BlockPool(CACHE_CFG)
        pool.allocate(1, 30)
        assert not pool.can_allocate(8)
        with pytest.raises(OutOfBlocksError):
            pool.allocate(2, 8)

    def test_double_allocate_rejected(self):
        pool = BlockPool(CACHE_CFG)
        pool.allocate(7, 4)
        with pytest.raises(ValueError):
            pool.allocate(7, 4)

    def test_fragmentation_accounting(self):
        """Reserved-but-unfilled slots / reserved slots: a 1-token
        sequence holding one 4-slot block is 75% internal waste."""
        pool = BlockPool(CACHE_CFG)
        pool.allocate(1, 4)
        pool.note_filled(1, 1)
        assert pool.internal_fragmentation() == pytest.approx(0.75)
        pool.note_filled(1, 4)
        assert pool.internal_fragmentation() == 0.0

    def test_table_row_pads_with_null(self):
        pool = BlockPool(CACHE_CFG)
        blocks = pool.allocate(1, 6)  # 2 blocks
        row = pool.table_row(1, 5)
        assert row[:2] == blocks
        assert row[2:] == [0, 0, 0]
        with pytest.raises(ValueError):
            pool.table_row(1, 1)  # narrower than the allocation

    def test_extend_grows_and_raises_when_dry(self):
        """Incremental allocation: ``extend`` appends blocks to a
        live sequence's table and fails LOUDLY when the pool is dry
        (the scheduler's cue to preempt)."""
        pool = BlockPool(CACHE_CFG)
        pool.allocate(1, 4)  # 1 block
        assert pool.covered_tokens(1) == 4
        added = pool.extend(1, 2)
        assert len(added) == 2
        assert pool.covered_tokens(1) == 12
        assert pool.blocks_of(1)[1:] == added
        pool.allocate(2, 20)  # 5 blocks -> pool full (8 usable)
        with pytest.raises(OutOfBlocksError):
            pool.extend(1, 1)
        pool.free(2)
        pool.extend(1, 1)
        assert pool.covered_tokens(1) == 16


class TestDoubleFreeGuard:
    """Satellite: a block id landing on the free list twice must
    raise instead of corrupting the LIFO free list into handing one
    block to two sequences."""

    def test_aliased_block_raises_loudly(self):
        """Simulate the evict-racing-drain corruption: two sequences'
        tables alias one physical block; freeing both must raise on
        the second free, not silently double-list the block."""
        pool = BlockPool(CACHE_CFG)
        pool.allocate(1, 4)
        pool.allocate(2, 4)
        pool._seqs[2].blocks[0] = pool._seqs[1].blocks[0]
        pool.free(1)
        with pytest.raises(DoubleFreeError, match="freed twice"):
            pool.free(2)

    def test_shared_overrelease_raises(self):
        pool = BlockPool(CACHE_CFG)
        pool.allocate(1, 8)
        keys = prefix_block_keys(np.arange(4, dtype=np.int32), 4)
        assert pool.share_block(1, 0, keys[0])
        shared = pool.blocks_of(1)[0]
        pool.free(1)  # decref -> refcount 0, parked in the LRU
        with pytest.raises(DoubleFreeError):
            pool._release_block(shared)

    def test_evict_then_drain_requeue_is_clean(self):
        """The real-path regression (the race the guard exists for):
        preemption (evict) followed by a drain's free of the SAME
        requeued sequence after re-admission must free each block
        exactly once — churn through evict/realloc cycles and end
        with an intact pool."""
        pool = BlockPool(CACHE_CFG)
        pool.allocate(10, 12)
        pool.allocate(11, 8)
        pool.free(10)  # the evict leg
        pool.allocate(10, 12)  # drain-requeue re-admitted it
        pool.free(10)  # the drain leg frees the NEW allocation
        pool.free(11)
        assert pool.used_blocks == 0
        assert pool.free_blocks == CACHE_CFG.usable_blocks


class TestPrefixIndex:
    def test_block_keys_are_position_chained(self):
        """Key i hashes blocks 0..i: two prompts share key 1 only
        when BOTH their first two blocks match."""
        a = np.arange(8, dtype=np.int32)
        b = np.concatenate([np.arange(4), np.array([9, 9, 9, 9])])
        ka = prefix_block_keys(a, 4)
        kb = prefix_block_keys(b.astype(np.int32), 4)
        assert len(ka) == len(kb) == 2
        assert ka[0] == kb[0]
        assert ka[1] != kb[1]
        # a partial tail block produces no key
        assert len(prefix_block_keys(a[:7], 4)) == 1

    def test_share_acquire_refcount_lru_cycle(self):
        pool = BlockPool(CACHE_CFG)
        keys = prefix_block_keys(np.arange(8, dtype=np.int32), 4)
        pool.allocate(1, 8)
        assert pool.share_block(1, 0, keys[0])
        assert pool.share_block(1, 1, keys[1])
        assert not pool.share_block(1, 0, keys[0])  # already indexed
        shared = pool.blocks_of(1)
        # a second identical prompt maps the SAME physical blocks
        assert pool.peek_prefix(keys) == (2, 0)
        hit = pool.acquire_prefix(keys)
        assert hit == shared
        pool.allocate(2, 8, prefix_blocks=hit)
        assert pool.blocks_of(2) == shared
        assert pool.prefix_hits == 2
        # free both holders: blocks park in the LRU, content retained
        pool.free(1)
        pool.free(2)
        assert pool.live_sequences == 0
        assert pool.used_blocks == 0
        assert pool.cached_shared_blocks == 2
        n, in_lru = pool.peek_prefix(keys)
        assert (n, in_lru) == (2, 2)
        # a third request still hits straight from the cache
        hit = pool.acquire_prefix(keys)
        assert hit == shared
        pool.allocate(3, 8, prefix_blocks=hit)
        pool.free(3)

    def test_lru_eviction_is_refcount_gated(self):
        """Allocation pressure reclaims ONLY refcount-0 cached blocks
        (oldest first); blocks still held by a live sequence never
        move."""
        pool = BlockPool(CACHE_CFG)
        ka = prefix_block_keys(np.arange(4, dtype=np.int32), 4)
        kb = prefix_block_keys(
            np.arange(10, 14, dtype=np.int32), 4
        )
        pool.allocate(1, 4)
        pool.share_block(1, 0, ka[0])
        pool.allocate(2, 4)
        pool.share_block(2, 0, kb[0])
        pool.free(2)  # kb's block -> LRU
        assert pool.cached_shared_blocks == 1
        # exhaust the pool: 8 usable, 2 in use/cached -> take 6, then
        # one more must evict the LRU'd kb block, never seq 1's
        pool.allocate(3, 24)  # 6 blocks
        assert pool.free_blocks == 0
        pool.allocate(4, 4)  # forces the LRU eviction
        assert pool.cached_shared_blocks == 0
        assert pool.peek_prefix(kb) == (0, 0)  # evicted from index
        assert pool.peek_prefix(ka) == (1, 0)  # still live via seq 1
        pool.free(1)
        pool.free(3)
        pool.free(4)
        # seq 1's shared block survives as cache after its free
        assert pool.cached_shared_blocks == 1


class TestPagedAttentionOps:
    def _pool_with_seq(self, rng, t_real, nkv=2, d=8):
        """A pool whose blocks 1.. hold one sequence's first
        ``t_real`` positions, garbage elsewhere."""
        cfg = PagedCacheConfig(
            n_layers=1, n_kv_heads=nkv, head_dim=d, num_blocks=6,
            block_size=4, dtype=jnp.float32,
        )
        k_dense = jnp.asarray(
            rng.standard_normal((t_real, nkv, d)), jnp.float32
        )
        v_dense = jnp.asarray(
            rng.standard_normal((t_real, nkv, d)), jnp.float32
        )
        # garbage everywhere (incl. the null block) proves masking
        k_pool = jnp.asarray(
            rng.standard_normal((6, 4, nkv, d)) * 100, jnp.float32
        )
        v_pool = jnp.asarray(
            rng.standard_normal((6, 4, nkv, d)) * 100, jnp.float32
        )
        table = [1, 2, 3]
        for t in range(t_real):
            blk, off = table[t // 4], t % 4
            k_pool = k_pool.at[blk, off].set(k_dense[t])
            v_pool = v_pool.at[blk, off].set(v_dense[t])
        return k_pool, v_pool, k_dense, v_dense, jnp.asarray(
            table + [0], jnp.int32
        )

    def test_decode_matches_dense_attention(self):
        rng = np.random.default_rng(1)
        t_real, nh, nkv, d = 7, 4, 2, 8
        k_pool, v_pool, k_dense, v_dense, table = self._pool_with_seq(
            rng, t_real
        )
        q = jnp.asarray(
            rng.standard_normal((1, nh, d)), jnp.float32
        )
        out = paged_decode_attention(
            q, k_pool, v_pool, table[None],
            jnp.asarray([t_real], jnp.int32),
        )
        # dense reference over the same 7 positions
        ref = llama.dot_product_attention(
            q[:, None],  # [1, 1, H, D] single query
            k_dense[None],
            v_dense[None],
            causal=False,  # seq_lens mask plays causal's role here
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_prefill_causal_within_chunk(self):
        """Chunk queries at positions 4..6 see the cached prefix plus
        only their own causal prefix inside the chunk."""
        rng = np.random.default_rng(2)
        t_real, nh, nkv, d = 7, 4, 2, 8
        k_pool, v_pool, k_dense, v_dense, table = self._pool_with_seq(
            rng, t_real
        )
        q = jnp.asarray(
            rng.standard_normal((3, nh, d)), jnp.float32
        )  # positions 4, 5, 6
        out = paged_prefill_attention(
            q, k_pool, v_pool, table, jnp.int32(4)
        )
        for i, qpos in enumerate((4, 5, 6)):
            ref = paged_decode_attention(
                q[i][None], k_pool, v_pool, table[None],
                jnp.asarray([qpos + 1], jnp.int32),
            )[0]
            np.testing.assert_allclose(
                np.asarray(out[i]), np.asarray(ref),
                rtol=1e-5, atol=1e-5,
            )


class TestPagedDecodePath:
    def test_paged_equals_dense_decode(self):
        """End to end: chunked paged prefill + paged decode over
        scattered blocks produce EXACTLY the dense contiguous-cache
        greedy tokens (fp32)."""
        cfg = llama.LlamaConfig.tiny(
            vocab_size=97, dim=32, n_layers=2, n_heads=4,
            n_kv_heads=2, mlp_dim=64, remat="none",
            dtype=jnp.float32,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompt = jnp.array([[5, 9, 2, 7, 1]], jnp.int32)
        plen, max_new = prompt.shape[1], 6
        total = plen + max_new

        # dense reference
        cache = llama.init_kv_cache(cfg, 1, total)
        logits = None
        for t in range(plen):
            logits, cache = llama.decode_step(
                params, prompt[:, t], cache, jnp.int32(t), cfg
            )
        ref = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(plen, total):
            ref.append(int(tok[0]))
            if t == total - 1:
                break
            logits, cache = llama.decode_step(
                params, tok, cache, jnp.int32(t), cfg
            )
            tok = jnp.argmax(logits, -1).astype(jnp.int32)

        # paged path, chunk=2 (pads the last chunk)
        pcfg = PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, num_blocks=8, block_size=4,
            dtype=jnp.float32,
        )
        bpool = BlockPool(pcfg)
        bpool.allocate(0, total)
        table = jnp.asarray(bpool.table_row(0, 4), jnp.int32)
        pool = init_block_pool(pcfg)
        chunk_len, last_logits = 2, None
        for start in range(0, plen, chunk_len):
            chunk = prompt[:, start:start + chunk_len]
            pad = chunk_len - chunk.shape[1]
            if pad:
                chunk = jnp.pad(chunk, ((0, 0), (0, pad)))
            last_logits, pool = llama.paged_prefill_chunk(
                params, chunk, pool, table, jnp.int32(start), cfg
            )
        idx = (plen - 1) % chunk_len
        tok = jnp.argmax(last_logits[:, idx], -1).astype(jnp.int32)
        out = []
        for t in range(plen, total):
            out.append(int(tok[0]))
            if t == total - 1:
                break
            lg, pool = llama.paged_decode_step(
                params, tok, pool, table[None],
                jnp.array([t], jnp.int32), jnp.array([True]), cfg,
            )
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        assert out == ref

    def test_batched_prefill_matches_scan_cache(self):
        """``llama.prefill`` (one forward) fills the same cache the
        one-token-at-a-time ``decode_step`` scan fills (fp32)."""
        cfg = llama.LlamaConfig.tiny(
            vocab_size=97, dim=32, n_layers=2, n_heads=4,
            n_kv_heads=2, mlp_dim=64, remat="none",
            dtype=jnp.float32,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompt = jnp.array(
            [[5, 9, 2, 7], [11, 3, 8, 1]], jnp.int32
        )
        plen = prompt.shape[1]
        scan_cache = llama.init_kv_cache(cfg, 2, plen + 2)
        logits = None
        for t in range(plen):
            logits, scan_cache = llama.decode_step(
                params, prompt[:, t], scan_cache, jnp.int32(t), cfg
            )
        fast_cache = llama.init_kv_cache(cfg, 2, plen + 2)
        all_logits, fast_cache = llama.prefill(
            params, prompt, fast_cache, cfg
        )
        np.testing.assert_allclose(
            np.asarray(scan_cache["k"][:, :, :plen]),
            np.asarray(fast_cache["k"][:, :, :plen]),
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(logits),
            np.asarray(all_logits[:, -1]),
            rtol=2e-4, atol=2e-4,
        )


# ---------------------------------------------------------------------------
# layers of two kinds in one manager (ISSUE 44): the layers WITH a window
# keep their blocks under a second table a lane, a ring
# ---------------------------------------------------------------------------


class _WindowedModel:
    """A model config as ``paged_cache_config`` reads it: five layers,
    the fourth without a window."""

    n_layers, n_kv_heads, head_dim, dtype = 5, 2, 8, jnp.float32

    def __init__(self, windows=(32, 32, 32, None, 32)):
        self._windows = windows

    def layer_windows(self):
        return self._windows


def _windowed_cfg(**kw):
    from dlrover_tpu.rl.kv_cache import paged_cache_config

    args = dict(num_blocks=41, block_size=4, max_slots=3, prefill_chunk=10)
    args.update(kw)
    return paged_cache_config(_WindowedModel(), **args)


class TestWindowLayers:
    def test_the_count_is_sized_from_the_declaration(self):
        from dlrover_tpu.rl.kv_cache import window_table_blocks

        # the issue's geometry: 4096 / 2048 / 16 -> 385 a lane
        assert window_table_blocks(4096, 2048, 16) == 385
        cfg = _windowed_cfg()
        # window - 1 + chunk = 41 positions: 11 blocks of 4, and one
        # more because the span need not start at a block's first token
        assert cfg.window_table_blocks == 12
        assert cfg.window_blocks == 3 * 12 + 1
        assert (cfg.n_full_layers, cfg.n_window_layers) == (1, 4)
        pool = init_block_pool(cfg)
        assert pool["k"].shape == pool["v"].shape == (1, 41, 4, 2, 8)
        assert pool["wk"].shape == pool["wv"].shape == (4, 37, 4, 2, 8)

    def test_a_model_without_windows_gets_the_pool_it_had(self):
        from dlrover_tpu.rl.kv_cache import paged_cache_config

        class Plain(_WindowedModel):
            layer_windows = None

        for model in (Plain(), _WindowedModel((None,) * 5)):
            cfg = paged_cache_config(model, 41, 4, 3, 10)
            assert cfg.layer_windows == () and cfg.window_blocks == 0
            assert sorted(init_block_pool(cfg)) == ["k", "v"]
            assert init_block_pool(cfg)["k"].shape[0] == 5
            assert BlockPool(cfg).window is None
            assert "window_blocks_live" not in BlockPool(cfg).stats()

    @pytest.mark.parametrize("windows,chunk,why", [
        ((32, 32, 32, None), 10, "names 4 layers of 5"),
        ((32, 16, 32, None, 32), 10, "one window"),
        ((32, 32, 32, None, 32), 0, "the prefill chunk"),
        ((32,) * 5, 10, "at least one layer keeps every position"),
    ])
    def test_a_declaration_that_cannot_be_sized_is_refused(
        self, windows, chunk, why
    ):
        from dlrover_tpu.rl.kv_cache import paged_cache_config

        with pytest.raises(ValueError, match=why):
            paged_cache_config(_WindowedModel(windows), 41, 4, 3, chunk)

    def test_a_lane_never_exceeds_its_allotment(self):
        """A sequence walked through chunks and then token by token to
        200 positions holds at most its ring, whatever the boundaries,
        and its ring names each live block at ``b % ring``."""
        pool = BlockPool(_windowed_cfg())
        blocks, window, chunk, bs = pool.window, 32, 10, 4
        ring = blocks.table_blocks
        pos = 0
        while pos < 53:  # a prompt of 53 tokens in padded chunks of 10
            blocks.advance(7, pos - window + 1, pos + chunk)
            first, end = blocks.live_range(7)
            assert end - first <= ring
            assert first == max(pos - window + 1, 0) // bs
            assert end == -(-(pos + chunk) // bs)
            row = blocks.table_row(7)
            live = {row[b % ring] for b in range(first, end)}
            assert 0 not in live and len(live) == end - first
            assert sum(x != 0 for x in row) == end - first
            pos += chunk
        for pos in range(53, 200):
            blocks.advance(7, pos - window + 1, pos + 1)
            first, end = blocks.live_range(7)
            # the blocks a padded chunk took ahead stay until decode
            # has written them; past those a lane holds its window
            assert end - first <= (
                ring if pos < 60 else (window - 1) // bs + 2
            )
            assert blocks.live_blocks == end - first
        st = pool.stats()
        assert st["window_blocks_released"] == first
        assert st["window_blocks_allocated"] == end
        assert st["window_blocks_peak"] <= ring
        pool.free(7)
        assert pool.stats()["window_blocks_live"] == 0

    def test_a_released_block_goes_to_the_next_asker_and_is_never_named(
        self
    ):
        """What a lane gives back leaves its ring at once (a stale entry
        would read ANOTHER sequence's keys) and is re-issued first."""
        blocks = BlockPool(_windowed_cfg()).window
        blocks.advance(1, 0, 40)
        held = set(blocks.table_row(1)) - {0}
        blocks.advance(1, 17, 40)  # blocks 0-3 fall behind position 17
        kept = set(blocks.table_row(1)) - {0}
        gone = held - kept
        assert len(gone) == 4
        blocks.advance(2, 0, 16)
        assert set(blocks.table_row(2)) - {0} == gone
        assert not (set(blocks.table_row(1)) & gone)

    def test_the_pool_of_every_lanes_ring_cannot_run_dry(self):
        cfg = _windowed_cfg()
        blocks = BlockPool(cfg).window
        for seq in range(cfg.max_slots):
            blocks.advance(seq, 100 - 31, 100 + 10)
        assert blocks.live_blocks <= cfg.window_blocks - 1
        with pytest.raises(ValueError, match="allotment"):
            blocks.advance(0, 0, 200)

    def test_full_layers_accounting_is_unchanged(self):
        """The sequence's own table, watermark arithmetic and counters
        read as they do for a model without windows."""
        plain = BlockPool(CACHE_CFG)
        windowed = BlockPool(_windowed_cfg(num_blocks=CACHE_CFG.num_blocks))
        for pool in (plain, windowed):
            pool.allocate(1, 9, extra_blocks=1)
            pool.extend(1, 1)
            pool.note_filled(1, 9)
            pool.allocate(2, 4)
            pool.free(2)
        a, b = plain.stats(), windowed.stats()
        # (how many layers page is the model's: two here, five there)
        assert a.pop("paged_layers") == CACHE_CFG.n_layers
        assert b["paged_layers"] == 5 and b["state_layers"] == 0
        assert {k: b[k] for k in a} == a
        assert b["full_blocks_live"] == a["used_blocks"]
        assert plain.blocks_of(1) == windowed.blocks_of(1)


# ---------------------------------------------------------------------------
# one declaration of what a layer keeps (ISSUE 49): layers that keep
# lane state and no keys beside layers that keep pages and no state
# ---------------------------------------------------------------------------


class _HybridModel:
    """A model config as ``paged_cache_config`` reads it: four layers,
    the last an attention layer between recurrent ones."""

    n_layers, n_kv_heads, head_dim, dtype = 4, 3, 8, jnp.float32

    def __init__(self, keeps=("state", "state", "state", "pages"),
                 state=True, flat=False, windows=None):
        self._keeps, self._state, self.flat_pages = keeps, state, flat
        if windows is not None:
            self.layer_windows = lambda: windows

    def lane_state(self):
        if not self._state:
            return {}
        return {"conv": ((6,), jnp.float32), "s": ((2, 4, 8), jnp.float32)}

    def layer_keeps(self):
        return self._keeps


def _hybrid_cfg(model=None, **kw):
    from dlrover_tpu.rl.kv_cache import paged_cache_config

    args = dict(num_blocks=21, block_size=4, max_slots=5, prefill_chunk=8)
    args.update(kw)
    return paged_cache_config(model or _HybridModel(), **args)


class TestLayerKeeps:
    def test_each_pool_holds_the_layers_of_its_kind(self):
        from dlrover_tpu.rl.kv_cache import lane_state_nbytes

        cfg = _hybrid_cfg()
        assert (cfg.n_full_layers, cfg.n_paged_layers,
                cfg.n_state_layers) == (1, 1, 3)
        pool = init_block_pool(cfg)
        assert {k: v.shape for k, v in pool.items()} == {
            "k": (1, 21, 4, 3, 8), "v": (1, 21, 4, 3, 8),
            "conv": (3, 5, 6), "s": (3, 5, 2, 4, 8),
        }
        # bytes: one table for four layers would page 4x and hold a
        # state for the layer that has none
        assert pool["k"].nbytes == 21 * 4 * 3 * 8 * 4
        assert lane_state_nbytes(pool, cfg) == 3 * 5 * (6 + 64) * 4
        st = BlockPool(cfg).stats()
        assert (st["paged_layers"], st["state_layers"]) == (1, 3)

    def test_both_is_the_default_and_leaves_the_config_as_it_was(self):
        """Falcon-H1's case: every layer keeps state AND pages, declared
        or not."""
        said = _hybrid_cfg(_HybridModel(("both",) * 4))

        class Undeclared(_HybridModel):
            layer_keeps = None

        unsaid = _hybrid_cfg(Undeclared())
        assert said == unsaid and said.layer_keeps == ()
        assert (said.n_full_layers, said.n_state_layers) == (4, 4)
        pool = init_block_pool(said)
        assert pool["k"].shape[0] == 4 and pool["s"].shape[0] == 4
        # and a model of pages only says "pages" of every layer
        dense = _hybrid_cfg(_HybridModel(("pages",) * 4, state=False))
        assert dense.layer_keeps == () and dense.n_state_layers == 0
        assert sorted(init_block_pool(dense)) == ["k", "v"]
        st = BlockPool(dense).stats()
        assert (st["paged_layers"], st["state_layers"]) == (4, 0)

    def test_a_layer_may_keep_both_beside_layers_that_keep_one(self):
        cfg = _hybrid_cfg(_HybridModel(("state", "both", "pages", "state")))
        assert (cfg.n_full_layers, cfg.n_state_layers) == (2, 3)
        pool = init_block_pool(cfg)
        assert pool["k"].shape[0] == 2 and pool["conv"].shape[0] == 3

    def test_flat_pages_hold_the_same_bytes_side_by_side(self):
        """A KV head count the chip's tiling would pad (3; the model's
        30) lies ``[block_size * KV, D]``."""
        flat = init_block_pool(_hybrid_cfg(_HybridModel(flat=True)))
        tiled = init_block_pool(_hybrid_cfg())
        assert flat["k"].shape == flat["v"].shape == (1, 21, 4 * 3, 8)
        assert flat["k"].nbytes == tiled["k"].nbytes
        assert flat["s"].shape == tiled["s"].shape

    @pytest.mark.parametrize("keeps,state,windows,why", [
        (("state", "pages"), True, None, "names 2 layers of 4"),
        (("state", "keys", "pages", "pages"), True, None,
         "takes one of"),
        (("state", "state", "state", "pages"), False, None,
         "names layers that keep state"),
        (("pages",) * 4, True, None, "leaves no layer to keep the "
                                     "lane_state"),
        (("state",) * 4, True, None, "leaves no layer that keeps pages"),
        (("pages", "both", "pages", "pages"), False, None,
         "names layers that keep state"),
    ])
    def test_misuse_is_refused_by_name(self, keeps, state, windows, why):
        with pytest.raises(ValueError, match="layer_keeps\\(\\) " + why):
            _hybrid_cfg(_HybridModel(keeps, state, windows=windows))

    def test_it_cannot_disagree_with_the_windows(self):
        """A layer with a window keeps pages: a declaration that says so
        composes, one that says otherwise cannot be made (lane state
        beside windows is refused where the windows are read)."""
        agrees = _hybrid_cfg(_HybridModel(
            ("pages",) * 4, state=False, windows=(16, 16, 16, None),
        ))
        assert agrees.n_window_layers == 3 and agrees.n_full_layers == 1
        assert agrees.layer_keeps == ()
        with pytest.raises(ValueError, match="declares no lane_state"):
            _hybrid_cfg(_HybridModel(
                ("state", "pages", "pages", "pages"), state=True,
                windows=(16, 16, 16, None),
            ))

    def test_block_pool_hands_out_ids_and_knows_no_layer(self):
        """The allocator is the one it was: ids, tables, no notion of
        what a block holds."""
        pool = BlockPool(_hybrid_cfg())
        blocks = pool.allocate(0, 9)
        assert len(blocks) == 3 and 0 not in blocks
        assert pool.table_row(0, 6) == blocks + [0, 0, 0]
        pool.free(0)
        assert pool.used_blocks == 0


#: ``init_block_pool``'s shape tree at each serving cell's geometry, as
#: the parent of ISSUE 49 made it: the new declaration moves none (PR 58
#: laid K's index keys in rows of 128 lanes: the same bytes in the same
#: order, ``[5, 18240, 1024]`` before)
CELL_POOLS = {
    "deepseek7b-rollout-c16": {
        "k": ((5, 1152, 16, 32, 128), "bfloat16"),
        "v": ((5, 1152, 16, 32, 128), "bfloat16"),
    },
    "falconh1-34b-rollout-c32": {
        "conv": ((6, 32, 3, 5120), "float32"),
        "k": ((6, 2304, 16, 4, 128), "bfloat16"),
        "ssm": ((6, 32, 32, 128, 256), "float32"),
        "v": ((6, 2304, 16, 4, 128), "bfloat16"),
    },
    "keye-vl2-rollout-c16-ctx16k": {
        "ik": ((5, 18240, 8, 128), "bfloat16"),
        "k": ((5, 18240, 16, 4, 128), "bfloat16"),
        "v": ((5, 18240, 16, 4, 128), "bfloat16"),
    },
    "trinity-large-rollout-c16-ctx32k": {
        "k": ((1, 36416, 16, 8, 128), "bfloat16"),
        "v": ((1, 36416, 16, 8, 128), "bfloat16"),
        "wk": ((4, 6161, 16, 8, 128), "bfloat16"),
        "wv": ((4, 6161, 16, 8, 128), "bfloat16"),
    },
    # three layers page, nine hold state; 12.94 GB with the weights
    "olmo-hybrid-rollout-c64": {
        "conv": ((9, 64, 34560), "float32"),
        "gdn": ((9, 64, 15, 96, 384), "float32"),
        "k": ((3, 6848, 480, 128), "bfloat16"),
        "v": ((3, 6848, 480, 128), "bfloat16"),
    },
}


@pytest.mark.parametrize("cell", sorted(CELL_POOLS))
def test_the_pool_of_every_serving_cell_is_what_it_was(cell):
    """To the byte: the shape tree (nothing is allocated)."""
    from dlrover_tpu.rl.kv_cache import paged_cache_config

    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks",
    )
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import harness

    loaded = harness.load_cell(cell)
    t = loaded["traffic"]
    fam = harness.family(loaded["config"])
    parts = fam.serving_parts(
        **fam.model_kwargs(loaded["config"], t["max_seq_len"]),
        dtype="bfloat16",
    )
    cfg = paged_cache_config(
        parts["cfg"], t["num_blocks"], t["block_size"], t["max_slots"],
        t["prefill_chunk"],
    )
    pool = jax.eval_shape(lambda: init_block_pool(cfg))
    assert {
        k: (v.shape, str(v.dtype)) for k, v in pool.items()
    } == CELL_POOLS[cell]
    if cell == "olmo-hybrid-rollout-c64":
        nbytes = {
            k: int(np.prod(v.shape)) * v.dtype.itemsize
            for k, v in pool.items()
        }
        assert round(2 * nbytes["k"] / 1e9, 2) == 5.05
        assert round((nbytes["conv"] + nbytes["gdn"]) / 1e9, 2) == 1.35
        assert (cfg.n_paged_layers, cfg.n_state_layers) == (3, 9)
