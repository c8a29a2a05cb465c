"""LFM2's block (short-convolution tails beside packed pages of 64-wide
heads) compiled for a described TPU: the token's write in place, the
step programs at the cell's geometry.
"""

import math
import os
import re
from functools import partial

import jax
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    BF16,
    _compile_for_metal,
    _kv64_case,
    _materialised,
    _scheduler_decode,
    _scheduler_prefill,
    one_chip,
    topo,
)


def test_a_token_of_64_wide_heads_is_written_into_rows_of_two_in_place(
        one_chip):
    """The write of a decode step's K and V at ``head_dim`` 64 is the
    plain scatter into the pool viewed as rows of two heads (a token's
    ``[8, 64]`` IS ``[4, 128]``): both pools aliased at their logical
    bytes, nothing pool-sized copied."""
    fn, shapes = _kv64_case("write")
    specs = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    compiled = jax.jit(fn, donate_argnums=(0, 1)).lower(*specs).compile()
    pool_bytes = 2 * math.prod(shapes[0][0]) * 2
    assert pool_bytes == 72832 * 16 * 2 * 2048  # 4.77 GB
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 2**20


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_short_conv_block_keeps_tails_and_packed_pages_in_place(
        program, one_chip):
    """LFM2-24B-A2B's step programs at ``lfm2-24b-rollout-c256-
    reason4k``'s geometry (the published widths at two whole periods, 6
    conv + 2 attention layers, both dense layers, 64 of 64 experts, the
    whole vocabulary behind a tied head; 256 lanes, 72 832 blocks of 16,
    tables of 256, chunk 512): the two attention layers' pages ``k``,
    ``v`` ``[2, 72832, 16 x 4, 128]`` — rows of two 64-wide KV heads, a
    block's rows side by side (``flat_pages``) —
    and the six conv layers' tails ``[6, 256, 4096]`` are aliased to the
    outputs at their LOGICAL bytes (4.77 GB of pages: a minor axis of 64
    would be padded to twice that) and never copied, sliced out whole or
    transposed in any program; the embedding is read as the head where
    it lies; the kernels that are there carry their names."""
    import json
    import sys

    from dlrover_tpu.models import lfm2_moe as model
    from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks",
    )
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import family_lfm2_moe as fam

    with open(os.path.join(bench, "configs", "lfm2-24b-a2b.json")) as f:
        hf = json.load(f)
    cfg = model.Lfm2MoeConfig(**fam.model_kwargs(hf, 4096))
    lanes, blocks, table, chunk = 256, 72832, 256, 512

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def seeded():  # as the benchmark seeds it: matrices in bfloat16
        tree = model.init_params(jax.random.PRNGKey(0), cfg)
        small = ("conv_w", "router")
        return model.serving_params({
            **{n: tree[n].astype(BF16) if tree[n].ndim == 2 else tree[n]
               for n in tree if n != "layers"},
            "layers": tuple(
                {n: w.astype(BF16) if w.ndim >= 2 and n not in small else w
                 for n, w in lp.items()}
                for lp in tree["layers"]
            ),
        }, cfg)

    params = jax.tree_util.tree_map(spec, jax.eval_shape(seeded))
    weights = sum(
        math.prod(a.shape) * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(params)
    )
    assert 8.04e9 < weights < 8.06e9
    cache = paged_cache_config(cfg, blocks, 16, lanes, chunk)
    assert (cache.n_full_layers, cache.n_state_layers) == (2, 6)
    pool = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: init_block_pool(cache))
    )
    assert pool["k"].shape == pool["v"].shape == (2, blocks, 16 * 4, 128)
    assert pool["conv"].shape == (6, lanes, 4096)
    pool_bytes = sum(
        math.prod(a.shape) * a.dtype.itemsize for a in pool.values()
    )
    assert pool_bytes == 2 * blocks * 16 * 2048 + 6 * lanes * 16384
    if program == "decode":
        fn, rest = _scheduler_decode(
            partial(model.paged_decode_step, cfg=cfg), lanes, table, True
        )
    else:
        fn, rest = _scheduler_prefill(
            partial(model.paged_prefill_chunk, cfg=cfg), lanes, True,
            program == "prefill_last", chunk, table, True,
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
    gib = 2**30
    print(
        f"lfm2 {program}: arguments {mem.argument_size_in_bytes / gib:.3f} "
        f"GiB, temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
        f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB"
    )
    # every pool aliased, at its logical bytes: a padded layout would
    # alias (and hold) more
    assert mem.alias_size_in_bytes == pool_bytes
    # weights + pool + the step's rows: what the replica holds resident
    assert mem.argument_size_in_bytes < 12.1 * gib
    # the chip's 15.75 GiB less arguments leave over 1 GiB free
    assert mem.temp_size_in_bytes < (
        0.6 if program == "decode" else 2.5
    ) * gib
    # no leaf of the pool and not the embedding (which is the head) is
    # copied, sliced out whole or transposed
    watched = {math.prod(a.shape) for a in pool.values()}
    watched |= {math.prod(a.shape[1:]) for n, a in pool.items() if n != "conv"}
    watched.add(65536 * 2048)
    if program == "decode":
        # a decode step shifts EVERY lane's tail of a layer: its own read
        watched.discard(math.prod(pool["conv"].shape[1:]))
    moved = [
        line[:160]
        for dtype in ("bf16", "f32")
        for elements, op, line in _materialised(text, dtype)
        if elements in watched
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice|transpose)", line)
        and not re.match(r"(ROOT )?%copy-(start|done)", line)
    ]
    assert not moved, moved
    targets = set(re.findall(r'custom_call_target="([^"]+)"', text))
    assert targets <= {
        "tpu_custom_call", "ConcatBitcast", "AssumeGatherIndicesInBound",
        "GatherScatterIndicesBitpacked", "AllocateBuffer",
    }, targets

    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("paged_full_decode") == (program == "decode")
    assert kernel("paged_prefill_full") == (program != "decode")
    assert kernel("moe_expert_ffn")
    # the expert layout's binary search alone loops: no page is written
    # and no lane's tail shifted in a loop
    loops = re.findall(r'while\(.*?op_name="([^"]*)"', text)
    assert all("searchsorted" in name for name in loops), loops
