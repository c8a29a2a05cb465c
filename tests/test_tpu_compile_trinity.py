"""Trinity-Large's step programs (a pool of two kinds of blocks)
compiled for a described TPU at its cell's geometry.
"""

import math
import re
from functools import partial

import jax
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    BF16,
    _compile_for_metal,
    _materialised,
    _scheduler_decode,
    _scheduler_prefill,
    one_chip,
    topo,
)


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_two_kinded_block_carries_both_pools_in_place(program, one_chip):
    """Trinity-Large's step programs at ``trinity-large-rollout-c16-
    ctx32k``'s geometry (the published widths at 1 dense + 4 expert
    layers, 32 of 256 experts held, an eighth of the vocabulary; 16
    lanes, tables of 2048 + 385 entries, chunk 2048): the full layer's
    pool ``[1, 36416, ...]`` AND the four window layers' ``[4, 6161,
    ...]`` — sized by the program, 4.0 GB together where one table for
    five layers would be 11.9 — are aliased to the outputs and never
    moved, no layer's ``[32, 3072, 3072]`` expert matrices and no fused
    projection are copied (the layers are unrolled over their own
    leaves), the temporaries stay small, and each kernel carries the
    name that tells window from full in a trace."""
    from dlrover_tpu.models import trinity
    from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    cfg = trinity.TrinityConfig(
        num_hidden_layers=5, num_dense_layers=1, held_experts=32,
        vocab_size=25024, max_seq_len=32768,
    )

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def seeded():  # as the benchmark seeds it: matrices in bfloat16
        tree = trinity.init_params(jax.random.PRNGKey(0), cfg)
        return trinity.serving_params(jax.tree_util.tree_map(
            lambda a: a.astype(BF16) if a.ndim >= 2 and a.shape[-1] != 256
            else a, tree,
        ), cfg)

    params = jax.tree_util.tree_map(spec, jax.eval_shape(seeded))
    cache = paged_cache_config(cfg, 36416, 16, 16, 2048)
    assert cache.window_table_blocks == 385
    pool = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: init_block_pool(cache))
    )
    assert pool["wk"].shape == (4, 16 * 385 + 1, 16, 8, 128)
    pool_bytes = sum(math.prod(a.shape) * 2 for a in pool.values())
    assert pool_bytes < 4.1e9
    width = 2048 + 385
    if program == "decode":
        fn, rest = _scheduler_decode(
            partial(trinity.paged_decode_step, cfg=cfg), 16, width, True
        )
    else:
        fn, rest = _scheduler_prefill(
            partial(trinity.paged_prefill_chunk, cfg=cfg), 16, False,
            program == "prefill_last", 2048, width, True,
        )
    tokens, *after = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in rest
    ]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(PAGED_KERNEL_ENV, "pallas")
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(
            params, tokens, pool, *after
        ).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= pool_bytes
    # a chunk holds its keys by position (a full layer's 32768: 134 MB
    # for K and V) and its rows' projections; a decode step next to none
    assert mem.temp_size_in_bytes < (
        64 if program == "decode" else 512
    ) * 2**20
    pools = {math.prod(a.shape) for a in pool.values()}
    layer = {math.prod(a.shape[1:]) for a in pool.values()}
    stack = 32 * 3072 * 3072
    fused = 3072 * (48 + 8 + 8 + 48) * 128
    moved = [
        line[:160] for elements, op, line in _materialised(text)
        if elements in pools | layer | {stack, fused}
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice|transpose)", line)
        # (a chunk's program prefetches a layer's fused projection into
        # fast memory, ``copy-start`` / ``copy-done``: no HBM buffer)
        and not re.match(r"(ROOT )?%copy-(start|done)", line)
    ]
    assert not moved, moved

    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("moe_expert_ffn")
    for kind in ("window", "full"):
        assert kernel(f"paged_{kind}_decode") == (program == "decode")
        assert kernel(f"paged_prefill_{kind}") == (program != "decode")
    assert "ragged-dot" not in text
