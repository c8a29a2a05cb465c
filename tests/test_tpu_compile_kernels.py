"""Every Pallas kernel of the two hot paths compiled, bare, for a
described TPU: each case asserts a ``tpu_custom_call`` in the compiled
program (a kernel was really emitted, not the interpreter or a jnp
path), its name, its operands.  ``tpu_compile_lib.py`` has the cases and
says why the chip is described where it is.
"""

import re

import jax
import pytest

from tpu_compile_lib import (  # noqa: F401 - fixtures by name
    BF16,
    BLOCK,
    CASES,
    D,
    H,
    MAX_BLOCKS,
    NUM_BLOCKS,
    WINDOW,
    _case_text,
    _compile_for_metal,
    _compiled_text,
    _kv30_case,
    _paged_case,
    _pallas_calls,
    _sparse_prefill_case,
    one_chip,
    topo,
)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    text = _case_text(case, one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case,name", [
    ("paged_decode_kv32", "paged_decode"),
    ("paged_verify_w4_kv32", "paged_verify"),
    ("rms_norm_fwd_bwd", "rmsnorm_fwd"),
    ("ssm_decode_update", "ssm_decode_update"),
    ("gdn_decode_update", "gdn_decode_update"),
    ("kda_decode_update", "kda_decode_update"),
    ("paged_full_decode_kv64", "paged_full_decode"),
    ("paged_prefill_full_kv64", "paged_prefill_full"),
    ("paged_full_decode_kv30", "paged_full_decode"),
    ("paged_prefill_full_kv30", "paged_prefill_full"),
    ("sparse_prefill", "sparse_prefill"),
    ("mla_sparse_decode", "mla_sparse_decode"),
    ("mla_sparse_decode_rows_32k", "mla_sparse_decode"),
    ("mla_prefill", "mla_prefill"),
    ("index_scores", "index_scores"),
    ("index_decode_scores", "index_decode_scores"),
    ("index_decode_scores_keye", "index_decode_scores"),
    ("paged_window_decode", "paged_window_decode"),
    ("paged_full_decode_2048", "paged_full_decode"),
    ("paged_prefill_window", "paged_prefill_window"),
    ("paged_prefill_full", "paged_prefill_full"),
])
def test_serving_kernels_keep_their_names(case, name, one_chip):
    """A device trace names an operation by its HLO instruction: the
    serving path's kernels are ``<name>.N`` there (``pallas_utils.
    named_kernel``), not the ``closed_call.N`` Pallas's own wrapper
    leaves, so a reduction can pick them out (``^paged_``)."""
    text = _case_text(case, one_chip)
    calls = [
        line.strip() for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
    ]
    assert calls
    for line in calls:
        assert re.match(rf"(ROOT )?%{name}(\.\d+)* = ", line), line
    assert "closed_call" not in text


@pytest.mark.parametrize("case,checked", [
    ("mla_sparse_decode", False),  # the two leaves, streamed
    ("index_decode_scores", False),
    ("mla_sparse_decode_rows_32k", True),  # gathered rows: a BlockSpec a page
])
def test_streamed_kernels_carry_the_scaffolds_parameters(
    case, checked, one_chip
):
    """A kernel on ``_stream_lane_blocks`` is compiled under
    ``STREAM_PARAMS``: the Mosaic call's own config says the compiler's
    bounds checks are off (they are most of what a copy costs the scalar
    core, and the scaffold clamps what it addresses), and a kernel that
    is not on it keeps them — so the hand-written loop cannot come back
    unnoticed."""
    text = _case_text(case, one_chip)
    (call,) = [
        line for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
    ]
    assert ('"disable_bounds_checks":true' in call) != checked, case


@pytest.mark.parametrize("keys", [4096, 8192, 12288, 16384])
def test_sparse_prefill_steps_a_kv_heads_group_over_1024_keys(keys):
    """What ``sparse_prefill`` is at the cell's four widths: a grid of
    (KV heads, query blocks of 256 rows, key blocks of 1024) whose step
    takes a KV head's eight query heads at once — 2048 rows of queries
    against one key block and ONE ``[256, 1024]`` int8 tile of the
    selection — where a step was one head's 512 rows against 512 keys
    (32 x 4 x keys / 512 steps, each fetching and widening the tile
    its seven siblings also fetched)."""
    fn, shapes = _sparse_prefill_case(keys)
    jaxpr = jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes)
    )
    (call,) = _pallas_calls(jaxpr.jaxpr)
    mapping = call.params["grid_mapping"]
    assert mapping.grid == (4, 2048 // 256, keys // 1024)
    blocks = [
        tuple(b.block_size for b in m.block_shape)
        for m in mapping.block_mappings
    ]
    q_rows = (1, 8 * 256, D)
    assert blocks == [
        q_rows, (1, 1024, D), (1, 1024, D), (256, 1024), q_rows
    ]


@pytest.mark.parametrize("kernel", ["decode", "verify"])
@pytest.mark.parametrize("kv_heads", [8, 32])
def test_every_autotune_candidate_compiles(kernel, kv_heads, one_chip):
    """Mosaic accepts every (q_rows, kv_span) the tuner may sweep — and
    so whatever the heuristic can return — at the chip's shapes."""
    from dlrover_tpu.ops import autotune, paged_kernels

    window = WINDOW if kernel == "verify" else 1
    cands = autotune.candidates(
        kernel, group=H // kv_heads, head_dim=D, block_size=BLOCK,
        max_blocks=MAX_BLOCKS, dtype=BF16, window=window,
    )
    assert len(cands) >= 4
    run = {
        "decode": paged_kernels.paged_decode_kernel,
        "verify": paged_kernels.paged_verify_kernel,
    }[kernel]
    _, shapes = _paged_case(kernel, kv_heads)
    for config in cands:
        text = _compiled_text(
            lambda *a: run(*a, config=config), *shapes, sharding=one_chip
        )
        assert "tpu_custom_call" in text, config


def test_paged_pool_view_is_a_bitcast(one_chip):
    """The kernels view the pool as ``[N, bs*KV, D]``: that reshape must
    stay free on the chip's tiled layout — a copy would move the whole
    pool on every decode step."""
    fn, shapes = _paged_case("decode", 8)
    compiled = jax.jit(fn).lower(
        *[
            jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes
        ]
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert " copy(" not in compiled.as_text()


@pytest.mark.parametrize("case,pool", [
    ("paged_decode_kv8", (NUM_BLOCKS, BLOCK * 8, D)),
    ("paged_decode_kv32", (NUM_BLOCKS, BLOCK * 32, D)),
    ("paged_window_decode", (16 * 385 + 1, BLOCK * 8, D)),
    ("paged_full_decode_2048", (16 * 2048 + 1, BLOCK * 8, D)),
])
def test_decode_kernel_takes_the_pools_whole(case, pool, one_chip):
    """The decode kernel fetches its own pages: its operands are the
    table, the lengths, the queries and the two pools WHOLE (where they
    lie: no copy beside it), not a list of page operands that grows with
    the pages a step streams."""
    text = _case_text(case, one_chip)
    (call,) = [
        line for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
    ]
    call = call.split("backend_config")[0]  # the kernel's body is long
    operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
    assert len(operands.split(",")) == 5, call
    layouts = call.split("operand_layout_constraints=")[1]
    assert layouts.count("bf16[%d,%d,%d]" % pool) == 2, call
    assert " copy(" not in text


def test_a_flat_pool_of_30_kv_heads_reaches_the_kernel_whole(one_chip):
    """30 KV heads are no multiple of the chip's sublane tile: as ``[N,
    16, 30, 128]`` a pool is padded to 32 heads in memory and copied
    WHOLE into the ``[N, 16 x 30, 128]`` view the decode kernel takes
    (5.05 GB of pool, two copies of 2.35 GB a step: it does not even
    fit).  Held flat (``flat_pages``) the two pools are the kernel's
    operands as they lie; only the 30 query rows a lane are padded."""
    fn, shapes = _kv30_case("decode")
    text = _compiled_text(fn, *shapes, sharding=one_chip)
    (call,) = [
        line for line in text.splitlines()
        if "custom-call(" in line and "tpu_custom_call" in line
    ]
    layouts = call.split("backend_config")[0].split(
        "operand_layout_constraints="
    )[1]
    assert layouts.count("bf16[%d,%d,%d]" % (3 * 6848, 16 * 30, D)) == 2
    assert not [
        line[:120] for line in text.splitlines()
        if " copy(" in line and "bf16[20544," in line
    ]
