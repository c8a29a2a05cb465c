"""OLMo-Hybrid's block (gated delta-rule layers beside attention)
compiled for a described TPU: the kernel's state in place, the step
programs at the cell's geometry.
"""

import math
import re
from functools import partial

import jax
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    BF16,
    _compile_for_metal,
    _gdn_case,
    _materialised,
    _scheduler_decode,
    _scheduler_prefill,
    one_chip,
    topo,
)


def test_gdn_state_is_updated_in_place_and_holds_no_padding(one_chip):
    """The gated delta rule's decode kernel addresses one slab of the
    stacked ``[linear layers, lanes, 15, 96, 384]`` state through its
    index maps and aliases the buffer to its output: donated, nothing of
    the 1.27 GB is copied, and the slab's ON-DEVICE bytes are its
    logical bytes — as ``[.., 30, 96, 192]`` the chip's ``(8, 128)``
    tiling would hold a third more (192 padded to 256)."""
    fn, shapes = _gdn_case()
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes
        ]
    ).compile()
    mem = compiled.memory_analysis()
    state_bytes = 9 * 64 * 30 * 96 * 192 * 4
    small = 64 * (2 * 30 * 96 + 30 * 192 + 2 * 30 + 1) * 4 + 4
    # the arguments: the state at its logical size and the token's rows
    assert state_bytes <= mem.argument_size_in_bytes < (
        state_bytes + 2 * small + 2**20
    )
    assert mem.alias_size_in_bytes == state_bytes
    assert mem.temp_size_in_bytes < 16 * 2**20
    assert not [
        line for line in compiled.as_text().splitlines()
        if " copy(" in line and "f32[9,64,15,96,384]" in line
    ]


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_hybrid_linear_attention_block_keeps_every_pool_in_place(
        program, one_chip):
    """Olmo-Hybrid-7B's step programs at ``olmo-hybrid-rollout-c64``'s
    geometry (the published widths at three whole periods, 9 linear + 3
    full layers, the whole vocabulary; 64 lanes, 6848 blocks of 16,
    tables of 96, chunk 256): the three full layers' pages ``[3, 6848,
    480, 128]`` and the nine linear layers' slabs — the conv tails
    ``[9, 64, 34560]`` and the states ``[9, 64, 15, 96, 384]`` — are
    aliased to the outputs at their LOGICAL bytes (6.40 GB: no padding
    of 30 KV heads, of 192 columns or of 3 conv rows) and never moved,
    no fused projection is copied, nothing is written page by page in a
    loop, the temporaries stay small, and each kernel carries its name.
    A chunk reads its ONE lane's conv tail and state where they lie (no
    float32 slab of a layer's 64 lanes is sliced out first) and inverts
    its WY systems by products: no library routine is called.
    Arguments + temporaries (``assumed.depth_choice`` of the
    configuration file): decode 12.05 + 0.03 GiB, a chunk 11.07 + 0.06,
    the last chunk 12.05 + 0.06, of 15.75."""
    from dlrover_tpu.models import olmo_hybrid as model
    from dlrover_tpu.ops.paged_attention import PAGED_KERNEL_ENV
    from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config

    kinds = (model.LINEAR,) * 3 + (model.FULL,)
    cfg = model.OlmoHybridConfig(
        num_hidden_layers=12, layer_types=kinds * 3, max_seq_len=1536
    )

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def seeded():  # as the benchmark seeds it: matrices in bfloat16
        tree = model.init_params(jax.random.PRNGKey(0), cfg)
        return model.serving_params(jax.tree_util.tree_map(
            lambda a: a.astype(BF16) if a.ndim >= 2 and a.shape[0] != 4
            else a, tree,
        ), cfg)

    params = jax.tree_util.tree_map(spec, jax.eval_shape(seeded))
    cache = paged_cache_config(cfg, 6848, 16, 64, 256)
    assert (cache.n_full_layers, cache.n_state_layers) == (3, 9)
    pool = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: init_block_pool(cache))
    )
    assert pool["k"].shape == (3, 6848, 16 * 30, 128)
    assert pool["gdn"].shape == (9, 64, 15, 96, 384)
    pool_bytes = sum(
        math.prod(a.shape) * a.dtype.itemsize for a in pool.values()
    )
    assert 6.40e9 < pool_bytes < 6.41e9
    if program == "decode":
        fn, rest = _scheduler_decode(
            partial(model.paged_decode_step, cfg=cfg), 64, 96
        )
    else:
        fn, rest = _scheduler_prefill(
            partial(model.paged_prefill_chunk, cfg=cfg), 64, True,
            program == "prefill_last", 256, 96,
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
    # every pool aliased, at its logical bytes: a padded layout would
    # alias (and hold) more
    assert mem.alias_size_in_bytes == pool_bytes
    gib = 2**30
    assert mem.argument_size_in_bytes < (
        11.2 if program == "prefill_nohead" else 12.2
    ) * gib
    # a chunk's 58.9 (the last chunk's 59.7) MiB and a tenth: one more
    # lane-state slab of a layer (135 MiB) cannot hide under it
    assert mem.temp_size_in_bytes < (
        64 if program == "decode" else 66
    ) * 2**20
    pools = {math.prod(a.shape) for a in pool.values()}
    layer = {math.prod(a.shape[1:]) for a in pool.values()}
    fused = {3840 * 17340, 3840 * 3 * 3840}
    # the pages and the projections are bfloat16, the conv tails and
    # the states float32: a chunk of one lane must not move every
    # lane's slab in either.  A decode step shifts EVERY lane's conv
    # tail, so a layer's 64 tails (8.8 MB) are its own read
    sizes = {"bf16": pools | layer | fused, "f32": pools | layer}
    if program == "decode":
        sizes["f32"] = sizes["f32"] - {math.prod(pool["conv"].shape[1:])}
    moved = [
        line[:160]
        for dtype, watched in sizes.items()
        for elements, op, line in _materialised(text, dtype)
        if elements in watched
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice|transpose)", line)
        and not re.match(r"(ROOT )?%copy-(start|done)", line)
    ]
    assert not moved, moved
    # the kernels, views and hints; a solver or any other library
    # routine the compiler would call is a custom-call of another name
    targets = set(re.findall(r'custom_call_target="([^"]+)"', text))
    assert targets <= {
        "tpu_custom_call", "ConcatBitcast", "AssumeGatherIndicesInBound",
        "GatherScatterIndicesBitpacked",
    }, targets
    # a page write is a scatter of whole blocks: no loop over the lanes
    # or the chunk's rows (the chunk scan's own loop over its four
    # sub-chunks is the only kind there is)
    loops = re.findall(r'while\(.*?op_name="([^"]*)"', text)
    assert all("gdn_scan" in name for name in loops), loops
    assert (program == "decode") == (not loops)

    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("gdn_decode_update") == (program == "decode")
    assert kernel("paged_full_decode") == (program == "decode")
    assert kernel("paged_prefill_full") == (program != "decode")
