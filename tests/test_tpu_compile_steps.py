"""The dense, Falcon-H1 and Keye-VL-2.0 serving step programs at their
cells' geometry, compiled once each for a described TPU
(``tpu_compile_lib.compiled_step``) and read by every pin here.
"""

import math
import re

import jax
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    STEP_PROGRAMS,
    _MOVES,
    _compile_for_metal,
    _index_keys_are_read_in_place,
    _materialised,
    _ssm_case,
    compiled_step,
    one_chip,
    topo,
)


@pytest.mark.parametrize("program", sorted(STEP_PROGRAMS))
def test_step_program_carries_the_pool_in_place(program, compiled_step):
    """A serving step program compiled at its cell's geometry with the
    pool donated: the K/V pool rides in the layer scan's carry
    (``ops/paged_attention.scan_layers_over_pool``), so the program
    aliases it to its output, holds next to no temporaries and moves
    neither the pool nor one layer of it — scanned in and out it was
    sliced, copied and re-stacked every step (1.97 GiB of temporaries
    and 9 GB moved a step at C's geometry, PR 28)."""
    compiled, _, pool_shape, temp_limit = compiled_step(program)
    mem = compiled.memory_analysis()
    pool_elems = math.prod(pool_shape)
    if program != "llama-verify_w4":  # read-only: it returns no pool
        assert mem.alias_size_in_bytes >= 2 * pool_elems * 2  # k, v bf16
    assert mem.temp_size_in_bytes < temp_limit
    moved = [
        line.strip()[:160]
        for line in compiled.as_text().splitlines()
        for m in [_MOVES.search(line)]
        if m and math.prod(map(int, m.group(1).split(",")))
        in (pool_elems, pool_elems // pool_shape[0])
    ]
    assert not moved, moved


@pytest.mark.parametrize("program", [
    # a 2048-row chunk of 32 heads of 128 IS as many elements as ``wq``
    # [2048, 4096]: the pin by size cannot tell them apart there
    p for p in sorted(STEP_PROGRAMS) if not p.startswith("keye_vl2-prefill")
])
def test_step_program_reads_the_qkv_projection_in_place(
    program, compiled_step
):
    """The serving copy holds ``wq``, ``wk``, ``wv`` as one leaf
    ``wqkv``, and the compiled program reads a layer of it inside the
    matmul's fusion, like ``wo``: it writes no buffer the size of one
    layer's ``wq``, ``wk``, ``wv`` or ``wqkv``.  Held apart, each was
    cut out of the ``[L, D, D]`` stack into a buffer of its own
    (``constant_dynamic-slice_fusion``) and copied into another layout
    (``copy``) before its matmul, in every layer of every decode step
    and prefill chunk: 21 % of C's device time, 7 % of F's (ledger,
    PR 30).  The verify programs (64 rows) read the three in place
    before, too, and pass on either layout."""
    compiled, params, pool_shape, _ = compiled_step(program)
    _, heads, dim = params["layers"]["wo"].shape  # [L, heads * hd, D]
    kv = pool_shape[3] * pool_shape[4]  # kv_heads * hd
    sizes = {dim * heads, dim * kv, dim * (heads + 2 * kv)}
    buffers = _materialised(compiled.as_text())
    assert buffers, "the reader found no instruction at all"
    written = [b for b in buffers if b[0] in sizes]
    assert not written, written


@pytest.mark.parametrize("model", ["llama", "falcon_h1"])
def test_the_chunks_head_runs_only_where_it_is_read(model, compiled_step):
    """The scheduler reads ONE row of a prompt's chunks: the last
    token's.  The program of a chunk that is not the last computes
    nothing of the vocabulary's width — ``lm_head`` is not even an
    argument.  The last chunk's cuts that row from the model's ``[1,
    128, vocab]`` logits, and the compiler moves the cut before the
    product: it writes one float32 row of logits where the model's own
    form writes 128 (52 MB at C's vocabulary, 134 MB at F's), in the
    text and in ``memory_analysis()`` — the model needs no one-row
    form of its own."""
    whole, params, _, _ = compiled_step(f"{model}-prefill_chunk")
    nohead, _, _, _ = compiled_step(f"{model}-prefill_nohead")
    last, _, _, _ = compiled_step(f"{model}-prefill_last")
    dim, vocab = params["lm_head"].shape
    logits = 128 * vocab * 4

    def rows_of_logits(compiled):
        return {
            elements // vocab
            for elements, _, line in _materialised(compiled.as_text(), "f32")
            if elements % vocab == 0 and f"{vocab}]" in line
        }

    def written(compiled):
        mem = compiled.memory_analysis()
        return (
            mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes
        )

    assert 128 in rows_of_logits(whole)  # the reader reads
    assert written(whole) >= logits
    assert f"[{dim},{vocab}]" in whole.as_text()
    assert f"[{dim},{vocab}]" not in nohead.as_text()
    assert not rows_of_logits(nohead)
    assert f"[{dim},{vocab}]" in last.as_text()
    assert rows_of_logits(last) == {1}
    for program in (nohead, last):
        assert written(whole) - written(program) > 0.9 * logits


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_sparse_block_reads_its_experts_and_index_keys_in_place(
    program, compiled_step
):
    """The block with routed experts and an indexer, at its cell's
    geometry: the index-key leaf rides in the layer scan's carry beside
    K and V (aliased, never copied: stored a block's keys side by side
    in rows of 128 lanes, ``[L, blocks, 8, 128]`` — a 64-wide minor axis
    made every program copy the leaf in and out, 0.37 GB a call and as
    much a layer in a chunk); the decode step hands the leaf WHOLE to
    ``index_decode_scores``, which reads the lanes' own blocks, and
    gathers no ``[16, 16384, 64]`` of keys (33.5 MB a layer before PR
    58); no layer's ``[128, 2048, 768]`` expert stack is cut out of
    ``[5, 128, ...]`` (1.2 GB a layer before the experts were read at
    ``layer * 128`` of the flattened stacks); and the two kernels carry
    their names."""
    compiled, params, pool_shape, _ = compiled_step(f"keye_vl2-{program}")
    text = compiled.as_text()
    ik_elems = 5 * 18240 * 16 * 64
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (
        2 * math.prod(pool_shape) + ik_elems
    ) * 2
    stack = math.prod(params["layers"]["w_gate"].shape[1:])
    moved = [
        line for elements, op, line in _materialised(text)
        if elements in (ik_elems, ik_elems // 5, stack)
        # the leaf's in-place scatter is a fusion with its shape too:
        # a MOVE is named for what it does
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice)", line)
    ]
    assert not moved, moved
    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("moe_expert_ffn")
    assert kernel("sparse_paged_decode") == (program == "decode")
    assert kernel("sparse_prefill") == (program != "decode")
    assert kernel("index_scores") == (program != "decode")
    assert kernel("index_decode_scores") == (program == "decode")
    if program == "decode":
        _index_keys_are_read_in_place(
            text, ik_elems, r"16,(1024,8,128|16384,64|1024,1024)"
        )
    assert "ragged-dot" not in text


def test_ssm_state_is_updated_in_place(one_chip):
    """The decode recurrence's kernel addresses one layer of the
    stacked ``[layers, lanes, ...]`` state through its index maps and
    aliases the buffer to its output: donated, nothing of the 0.8 GB is
    copied and the program's temporaries stay far under one layer's
    slab (134 MB)."""
    fn, shapes = _ssm_case()
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes
        ]
    ).compile()
    mem = compiled.memory_analysis()
    state_bytes = 6 * 32 * 32 * 128 * 256 * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 32 * 2**20
    assert not [
        line for line in compiled.as_text().splitlines()
        if " copy(" in line and "f32[6,32,32,128,256]" in line
    ]
