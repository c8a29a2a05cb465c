"""Kimi Linear's block (KDA slabs beside latent pages) compiled for a
described TPU: the kernel's state in place, the step programs at the
cell's geometry.
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
    _kda_case,
    _materialised,
    _scheduler_decode,
    _scheduler_prefill,
    one_chip,
    topo,
)


def test_kda_state_is_updated_in_place_at_its_logical_bytes(one_chip):
    """Kimi Delta Attention's decode kernel addresses one slab of the
    stacked ``[KDA layers, lanes, 32, 128, 128]`` state through its
    index maps and aliases the buffer to its output: donated, nothing of
    the 2.42 GB is copied, and the slab's ON-DEVICE bytes are its
    logical bytes (``[128, 128]`` a head is whole tiles: unpacked)."""
    fn, shapes = _kda_case()
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes
        ]
    ).compile()
    mem = compiled.memory_analysis()
    state_bytes = 9 * 128 * 32 * 128 * 128 * 4
    small = 128 * (4 * 32 * 128 + 32 + 1) * 4 + 4
    assert state_bytes <= mem.argument_size_in_bytes < (
        state_bytes + 2 * small + 2**20
    )
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 32 * 2**20
    assert not [
        line for line in compiled.as_text().splitlines()
        if " copy(" in line and "f32[9,128,32,128,128]" in line
    ]


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_kimi_linear_block_keeps_slabs_and_latent_leaves_in_place(
        program, one_chip):
    """Kimi-Linear-48B-A3B's step programs at
    ``kimi-linear-rollout-c128-reason8k``'s geometry (the published
    widths at three whole periods, 9 KDA + 3 MLA layers, 16 of 256
    experts, 20480 rows of the vocabulary; 128 lanes, 72 832 blocks of
    16, tables of 512, chunk 512): the three MLA layers' latent leaves
    ``c [3, 72832, 16, 512]`` / ``kpe [3, 72832, 8, 128]`` and the nine
    KDA layers' slabs — conv tails ``[9, 128, 36864]``, states ``[9,
    128, 32, 128, 128]`` — are aliased to the outputs at their LOGICAL
    bytes (6.61 GB: 4.03 of pages, 2.59 of state) and never moved, each
    kernel carries its name, and no library routine is called (the WY
    systems are inverted by products).  The pool's on-device bytes are
    pinned as ``olmo-hybrid-rollout-c64``'s are."""
    import json

    from dlrover_tpu.models import kimi_linear as model
    from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    import sys

    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks",
    )
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import family_kimi_linear as fam

    with open(os.path.join(bench, "configs", "kimi-linear-48b-a3b.json")) as f:
        hf = json.load(f)
    cfg = model.KimiLinearConfig(**fam.model_kwargs(hf, 8192))
    lanes, blocks, table, chunk = 128, 72832, 512, 512

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
    assert 3.85e9 < weights < 3.88e9
    cache = paged_cache_config(cfg, blocks, 16, lanes, chunk)
    assert (cache.n_full_layers, cache.n_state_layers) == (3, 9)
    pool = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: init_block_pool(cache))
    )
    assert pool["c"].shape == (3, blocks, 16, 512)
    assert pool["kpe"].shape == (3, blocks, 8, 128)
    assert pool["conv"].shape == (9, lanes, 36864)
    assert pool["kda"].shape == (9, lanes, 32, 128, 128)
    pool_bytes = sum(
        math.prod(a.shape) * a.dtype.itemsize for a in pool.values()
    )
    assert pool_bytes == 3 * blocks * 16 * 1152 + 9 * lanes * 2244608
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
        f"kimi {program}: arguments {mem.argument_size_in_bytes / gib:.3f} "
        f"GiB, temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, "
        f"aliased {mem.alias_size_in_bytes / gib:.3f} GiB"
    )
    # every pool aliased, at its logical bytes
    assert mem.alias_size_in_bytes == pool_bytes
    # weights + pool + the step's rows: what the replica holds resident
    assert mem.argument_size_in_bytes < 9.9 * gib
    # the chip's 15.75 GiB less arguments leave 1 GiB and more free
    assert mem.temp_size_in_bytes < (
        0.6 if program == "decode" else 2.5
    ) * gib
    # no leaf of the pool is copied, sliced out whole or transposed —
    # but the conv tails in DECODE: at 128 lanes (one whole lane tile)
    # the compiler computes a step's ``[lanes, channels]`` rows
    # lanes-minor and relays the 170 MB slab in and out of the step
    # (``PERF.md`` section 7; Olmo-Hybrid's 64 lanes do not tempt it)
    pools = {math.prod(a.shape) for a in pool.values()}
    if program == "decode":
        pools.discard(math.prod(pool["conv"].shape))
    moved = [
        line[:160]
        for dtype in ("bf16", "f32")
        for elements, op, line in _materialised(text, dtype)
        if elements in pools
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice|transpose)", line)
        and not re.match(r"(ROOT )?%copy-(start|done)", line)
    ]
    assert not moved, moved
    targets = set(re.findall(r'custom_call_target="([^"]+)"', text))
    assert targets <= {
        "tpu_custom_call", "ConcatBitcast", "AssumeGatherIndicesInBound",
        "GatherScatterIndicesBitpacked", "AllocateBuffer",
    }, targets
    # the jitted pieces are inlined: no call is left
    assert not re.search(r" = [^\n=]*? call\(", text)

    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("kda_decode_update") == (program == "decode")
    assert kernel("mla_sparse_decode") == (program == "decode")
    assert kernel("mla_prefill") == (program != "decode")
    assert kernel("moe_expert_ffn")
    # the chunk scan alone loops (over a chunk's eight sub-chunks)
    loops = re.findall(r'while\(.*?op_name="([^"]*)"', text)
    if program != "decode":
        assert any("kda_scan" in name for name in loops), loops
