"""DeepSeek-V3.2's latent block compiled for a described TPU: its step
programs at the cell's geometry (``tpu_compile_lib._latent_step_compiled``,
once each) and the latent decode kernel's two forms.
"""

import math
import re

import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    _compile_for_metal,
    _compiled_text,
    _index_keys_are_read_in_place,
    _kernel_operands,
    _latent_step_compiled,
    _materialised,
    _mla_decode_case,
    one_chip,
    topo,
)


@pytest.mark.parametrize("entries,streams", [(512, True), (2048, False)])
def test_latent_decode_streams_the_leaves_or_gathers_the_rows(
    entries, streams, one_chip
):
    """From the index scores on: at the cell's table (8192 positions,
    top 2048) the kernel is handed both leaves whole and nothing of
    ``[32, 2048, 512]`` is gathered; under a table of 32 k positions it
    is handed the gathered rows and neither leaf."""
    fn, shapes = _mla_decode_case(entries, "chosen")
    text = _compiled_text(fn, *shapes, sharding=one_chip)
    handed = _kernel_operands(text, "mla_sparse_decode")
    leaves = {7 * 18240 * 16 * 512, 7 * 18240 * 8 * 128}
    picked = 32 * 2048 * 512
    assert leaves <= set(handed) if streams else not leaves & set(handed)
    assert (picked in handed) != streams
    assert bool(re.search(r"bf16\[32,2048,512\]", text)) != streams
    assert not [
        line for elements, op, line in _materialised(text)
        if op == "copy" and elements in leaves
    ]


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_latent_block_carries_its_two_leaves_in_place(program, one_chip):
    """DeepSeek-V3.2's step programs at ``deepseek-v32-rollout-c32-
    reason8k``'s geometry (the published widths at 1 dense + 6 expert
    layers, 8 of 256 experts held, an eighth of the vocabulary; 32
    lanes, tables of 512 entries, chunk 512): the pool is the latents,
    the rotated shared keys and the index keys ALONE — no ``k``, no
    ``v`` — 1408 bytes a token and layer, every leaf aliased to the
    outputs and never moved (the rows' views ``[L * blocks * 16, 512]``
    and ``[L * blocks * 8, 128]`` are merges of leading axes), the
    index keys read in place by the decode step's
    ``index_decode_scores`` (no ``[32, 8192, 128]`` of them gathered:
    67 MB a layer before PR 58), no
    layer's ``[8, 7168, 2048]`` expert matrices copied, and each kernel
    under the name a trace tells it by."""
    compiled, pool = _latent_step_compiled(program, one_chip)
    assert set(pool) == {"c", "kpe", "ik"}
    assert pool["c"].shape == (7, 18240, 16, 512)
    assert pool["kpe"].shape == (7, 18240, 8, 128)
    assert pool["ik"].shape == (7, 18240, 16, 128)
    pool_bytes = sum(math.prod(a.shape) * 2 for a in pool.values())
    assert pool_bytes == 7 * 18240 * 16 * 1408
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= pool_bytes
    # a chunk holds the keys and values it decompressed (8192 positions
    # x 128 heads x (192 + 128): 671 MB) and its rows' projections; a
    # decode step every lane's index keys and picked rows a layer
    assert mem.temp_size_in_bytes < (
        768 if program == "decode" else 2048
    ) * 2**20
    pools = {math.prod(a.shape) for a in pool.values()}
    layer = {math.prod(a.shape[1:]) for a in pool.values()}
    stack = 8 * 7168 * 2048
    moved = [
        line[:160] for elements, op, line in _materialised(text)
        if elements in pools | layer | {stack}
        and re.match(r"(ROOT )?%(copy|dynamic-slice|slice|transpose)", line)
        and not re.match(r"(ROOT )?%copy-(start|done)", line)
    ]
    assert not moved, moved

    def kernel(name):  # an instruction of that name, not a path
        return re.search(rf"%{name}(\.\d+)* = ", text) is not None

    assert kernel("moe_expert_ffn")
    assert kernel("mla_sparse_decode") == (program == "decode")
    if program == "decode":
        # the kernel reads the lanes' own blocks: both leaves whole (and,
        # by the pin above, where they lie), no picked row gathered
        handed = _kernel_operands(text, "mla_sparse_decode")
        assert {math.prod(pool[n].shape) for n in ("c", "kpe")} <= set(handed)
        assert not re.search(r"bf16\[32,2048,(512|128)\]", text)
        _index_keys_are_read_in_place(
            text, math.prod(pool["ik"].shape),
            r"32,(512,16,128|8192,128|512,2048)",
        )
    assert kernel("mla_prefill") == (program != "decode")
    assert kernel("index_scores") == (program != "decode")
    assert kernel("index_decode_scores") == (program == "decode")
    assert "ragged-dot" not in text


# what the UNROLLED loop's programs read (the parent of PR 56, the same
# case compiled from its tree): kernels by name, bytes of arguments, of
# outputs aliased to them, and of temporaries — and the temporaries of
# the programs whose loop calls jitted pieces, which are this tree's.
# Since PR 58 the decode step scores its index keys in place (a kernel
# a layer more, 64.6 MB of temporaries less: every lane's table of keys
# is no longer gathered), and the index keys lie in rows (a chunk's
# temporaries 1.3-1.4 MB less)
_UNROLLED = {
    "decode": (
        {"rmsnorm_fwd": 23, "mla_sparse_decode": 7, "moe_expert_ffn": 6,
         "index_decode_scores": 7},
        11748319744, 2876375040, 102251008, 39235584,
    ),
    "prefill_nohead": (
        {"index_scores": 28, "mla_prefill": 28, "rmsnorm_fwd": 22,
         "moe_expert_ffn": 5},
        10723819008, 2876375040, 1214667264, 1212796416,
    ),
    "prefill_last": (
        {"index_scores": 28, "mla_prefill": 28, "rmsnorm_fwd": 23,
         "moe_expert_ffn": 6},
        11748243456, 2876375040, 1217268224, 1215494144,
    ),
}


# a chunk's paths under ``attn`` and neither ``latent`` nor ``indexer``,
# by what follows ``attn``: the unrolled loop's three (the width
# switch, its index, the gather of the sequence's rows) and what XLA
# itself makes inside the switch's piece — copies of a width's mask,
# the packing's window sum — which had NO path in the unrolled program
# (unscoped there, ``attn`` here)
_BARE_ATTN = {
    "clamp", "cond", "gather",
    "", "reshape", "reduce_window_sum", "broadcast_in_dim",
}


@pytest.mark.parametrize(
    "program", ["decode", "prefill_nohead", "prefill_last"]
)
def test_latent_pieces_are_inlined_into_the_unrolled_program(
    program, one_chip
):
    """The same three programs, whose layer loop calls jitted pieces —
    attention's, one a sub-scope, and an MLP a kind
    (``models/deepseek_v32.py``): the compiler inlines every call, and
    what it compiles is the unrolled loop's program by every count that
    does not hang on its scheduler — each kernel as often under its
    name, the same bytes of arguments, the pool's three leaves aliased
    to the outputs.  The temporaries are NOT the unrolled program's to
    the byte (the inliner's clones come in another order and the
    scheduler then decides otherwise): +1.5 MB in the decode step,
    -0.5 MB in a chunk at PR 56; pinned as read, so that a drift
    shows.  And a
    device trace still tells every operation's role and part: no
    scoped path without its role, and under ``attn`` without a
    sub-part what the unrolled loop had there — nothing in the decode
    step, the width switch in a chunk."""
    compiled, _ = _latent_step_compiled(program, one_chip)
    mem, text = compiled.memory_analysis(), compiled.as_text()
    kernels, argument, alias, unrolled_temp, temp = _UNROLLED[program]
    assert not re.search(r" = [^\n=]*? call\(", text)
    found = {}
    for name in re.findall(
        r"%([\w\-]+?)(?:\.\d+)* = [^\n]*custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', text
    ):
        found[name] = found.get(name, 0) + 1
    assert found == kernels
    # (``models/deepseek_v32.py`` ``_Leaves.walk`` says which side of a
    # call hands on which path)
    role = "decode" if program == "decode" else "prefill"
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    lost = {
        path for path in paths
        if re.search(r"\b(attn|mlp)\b", path)
        and not re.search(rf"\b{role}\b", path)
    }
    assert not lost, sorted(lost)[:5]
    bare = {
        re.sub(r"\bp?jit\([^()]*\)", "", path).rsplit("attn", 1)[1]
        .strip("/")
        for path in paths
        if re.search(r"\battn\b", path)
        and not re.search(r"\b(latent|indexer)\b", path)
    }
    assert bare == (set() if program == "decode" else _BARE_ATTN)
    assert mem.argument_size_in_bytes == argument
    assert mem.alias_size_in_bytes == alias
    assert mem.temp_size_in_bytes == temp, unrolled_temp
