"""ISSUE 18: streamed Pallas paged-attention kernels.

Pins the tentpole's contracts on CPU CI (interpret mode runs the real
kernel bodies):

- pallas(interpret) vs jnp parity for decode AND the fused K-step
  verify, across dtypes, GQA group sizes, block sizes, ragged
  ``seq_lens`` including empty lanes, and poisoned table-overrun guard
  rows;
- empty lanes return EXACT zeros under both backends (the jnp
  reference used to softmax a fully-masked row into uniform weights
  over garbage);
- the ``DLROVER_TPU_PAGED_KERNEL`` dispatcher: ``jnp`` is
  byte-for-byte the reference, ``auto`` resolution, invalid values
  fail loudly;
- the scheduler churn story (admit/preempt/grow/resume/spec-decode)
  under the pallas backend: one compiled decode program and token
  tails identical to the jnp-backend run;
- the shape-keyed autotuner: tile-legal candidates, deterministic
  lookup, and a tune run that persists the winner, emits the
  ``kernel_autotune`` span with its required labels, and publishes the
  ``dlrover_tpu_paged_kernel_us`` gauge;
- the micro-bench harness flushes its artifact after every sweep point
  and honors the wall budget.
"""

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops import autotune, paged_kernels  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.ops.paged_kernels import (  # noqa: E402
    paged_decode_kernel,
    paged_verify_kernel,
    sublane_tile,
)
from dlrover_tpu.ops.pallas_utils import (  # noqa: E402
    INTERPRET_ENV,
    use_interpret,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POISON = 1e4  # guard-block contents: any leak is unmissable


def _case(group, block_size, dtype, seed=0, batch=4, kv=2, head_dim=8,
          max_blocks=4, window=3):
    """One parity scenario: normal K/V for in-use blocks, POISON in
    the null block and in every guard block that only unused
    (overrunning) table entries point at, ragged ``seq_lens``
    including an empty lane and a lane using the full table."""
    rng = np.random.default_rng(seed)
    heads = kv * group
    used = batch * max_blocks
    num_blocks = 1 + used + 1  # null + per-lane blocks + guard block
    k_pool = rng.standard_normal(
        (num_blocks, block_size, kv, head_dim)
    ).astype(np.float32)
    v_pool = rng.standard_normal(
        (num_blocks, block_size, kv, head_dim)
    ).astype(np.float32)
    k_pool[0] = POISON  # null block is garbage by design
    v_pool[0] = POISON
    k_pool[-1] = POISON  # the table-overrun guard block
    v_pool[-1] = POISON
    tables = (
        1 + np.arange(used).reshape(batch, max_blocks)
    ).astype(np.int32)
    seq_lens = np.array(
        [1, 0, block_size + block_size // 2, block_size * max_blocks],
        np.int32,
    )[:batch]
    q = rng.standard_normal((batch, heads, head_dim)).astype(np.float32)
    qv = rng.standard_normal(
        (batch, window, heads, head_dim)
    ).astype(np.float32)
    positions = np.maximum(seq_lens - window, 0).astype(np.int32)
    # every table entry past a lane's last resident block points at the
    # poison guard block: only masking (jnp) / fetching the blocks a
    # lane holds and no other (pallas) keeps it out of the output.  Verify's window K/V is resident by
    # contract, so "resident" covers max(seq_len, pos + window) tokens.
    for b in range(batch):
        covered = max(int(seq_lens[b]), int(positions[b]) + window)
        first_unused = -(-covered // block_size)
        tables[b, first_unused:] = num_blocks - 1
    c = dict(
        q=jnp.asarray(q, dtype), qv=jnp.asarray(qv, dtype),
        k_pool=jnp.asarray(k_pool, dtype),
        v_pool=jnp.asarray(v_pool, dtype),
        tables=jnp.asarray(tables), seq_lens=jnp.asarray(seq_lens),
        positions=jnp.asarray(positions),
    )
    return c


def _tol(dtype):
    # outputs are O(1); bf16 inputs round at ~2^-8 relative
    return 5e-5 if dtype == jnp.float32 else 6e-2


#: (dtype, block_size, group, head_dim, kv): the tiny sweep, plus the
#: shape the chip runs (head_dim 128, block_size 16, bf16 — GQA with 8
#: KV heads and MHA with 32, what ``chip_smoke.py`` and
#: ``tests/test_tpu_compile_*.py`` compile to Mosaic), small batch
PARITY_CASES = [
    pytest.param(dtype, bs, group, 8, 2,
                 id=f"g{group}-bs{bs}-{jnp.dtype(dtype).name}")
    for dtype in (jnp.float32, jnp.bfloat16)
    for bs in (8, 16)
    for group in (1, 2, 4)
] + [
    pytest.param(jnp.bfloat16, 16, 4, 128, 8, id="chip-gqa8-d128-bs16"),
    pytest.param(jnp.bfloat16, 16, 1, 128, 32, id="chip-mha32-d128-bs16"),
]


class TestDecodeParity:
    @pytest.mark.parametrize(
        "dtype,block_size,group,head_dim,kv", PARITY_CASES
    )
    def test_matches_jnp_reference(
        self, dtype, block_size, group, head_dim, kv
    ):
        c = _case(group, block_size, dtype, head_dim=head_dim, kv=kv)
        ref = pa.paged_decode_attention(
            c["q"], c["k_pool"], c["v_pool"], c["tables"],
            c["seq_lens"], backend="jnp",
        )
        out = paged_decode_kernel(
            c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seq_lens"]
        )
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=0,
        )
        # poison never leaked through masking or a fetch too many
        assert float(jnp.max(jnp.abs(out))) < POISON / 10

    @pytest.mark.parametrize(
        "config",
        [
            {"q_rows": 8, "kv_span": 1},
            {"q_rows": 8, "kv_span": 2},
            {"q_rows": 16, "kv_span": 4},
        ],
    )
    def test_tuned_configs_agree(self, config):
        """Every legal (q-block, kv-span) candidate computes the same
        attention — tuning can never change results."""
        c = _case(group=2, block_size=8, dtype=jnp.float32)
        ref = pa.paged_decode_attention(
            c["q"], c["k_pool"], c["v_pool"], c["tables"],
            c["seq_lens"], backend="jnp",
        )
        out = paged_decode_kernel(
            c["q"], c["k_pool"], c["v_pool"], c["tables"],
            c["seq_lens"], config=config,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-5, rtol=0
        )

    def test_empty_lane_exact_zeros_both_backends(self):
        """seq_lens == 0: the jnp reference used to return a uniform
        average of garbage V (softmax over an all-NEG_INF row); both
        backends must now return exact zeros."""
        c = _case(group=2, block_size=8, dtype=jnp.float32)
        assert int(c["seq_lens"][1]) == 0
        for backend in ("jnp", "pallas"):
            out = pa.paged_decode_attention(
                c["q"], c["k_pool"], c["v_pool"], c["tables"],
                c["seq_lens"], backend=backend,
            )
            assert bool(jnp.all(out[1] == 0.0)), backend
            # non-empty lanes are NOT zero (the fix is surgical)
            assert float(jnp.max(jnp.abs(out[0]))) > 0.0, backend


def _held_lengths(span, block_size, max_blocks):
    """Lengths a lane may hold around a group's edges: nothing, one
    token, one short of / exactly at / one past the first group's edge
    and the second's, and the whole table."""
    edge = span * block_size
    full = max_blocks * block_size
    lens = [0, 1, edge - 1, edge, edge + 1, 2 * edge, 2 * edge + 1, full]
    return [min(n, full) for n in lens]


class TestStreamedDecode:
    """ISSUE 45: the decode kernel fetches its own pages, ``kv_span``
    of them a group, for the blocks a lane holds."""

    @pytest.mark.parametrize("with_first", [False, True],
                             ids=["whole", "first"])
    @pytest.mark.parametrize("group", [6, 5, 1])
    @pytest.mark.parametrize("span", [1, 4, 16])
    def test_groups_of_held_pages_match_the_reference(
        self, span, group, with_first
    ):
        """Every length around a group's edge, an empty lane between
        two that hold something, a table whose width is no multiple of
        the group — with NaN in the null block and in every page a lane
        does not hold (the reference is given the same pool with zeros
        there: ``0 * NaN`` is NaN in its dense product)."""
        rng = np.random.default_rng(span * 10 + group)
        kv, d, bs = 2, 8, 4
        mb = 2 * span + 1  # the last group reaches past the table's end
        lens = _held_lengths(span, bs, mb)
        lens = lens[1:4] + [0] + lens[4:] + [0]  # empty lanes inside
        b = len(lens)
        n_blocks = 1 + b * mb
        k = rng.standard_normal((n_blocks, bs, kv, d)).astype(np.float32)
        v = rng.standard_normal((n_blocks, bs, kv, d)).astype(np.float32)
        tables = 1 + rng.permutation(b * mb).reshape(b, mb).astype(np.int32)
        dead = [0]
        for lane, n in enumerate(lens):
            held = -(-n // bs)
            dead.extend(tables[lane, held:])
            tables[lane, held:] = 0  # behind a lane: the null block
        k_nan, v_nan = k.copy(), v.copy()
        k_nan[dead] = np.nan
        v_nan[dead] = np.nan
        k[dead] = 0.0
        v[dead] = 0.0
        q = rng.standard_normal((b, kv * group, d)).astype(np.float32)
        first = (
            jnp.asarray([min(3, max(n - 1, 0)) for n in lens], jnp.int32)
            if with_first else None
        )
        lens = jnp.asarray(lens, jnp.int32)
        ref = pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), lens, backend="jnp", first=first,
        )
        out = paged_decode_kernel(
            jnp.asarray(q), jnp.asarray(k_nan), jnp.asarray(v_nan),
            jnp.asarray(tables), lens, first=first,
            config={"q_rows": group, "kv_span": span},
        )
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-6, rtol=0
        )
        for lane in np.flatnonzero(np.asarray(lens) == 0):
            assert not np.asarray(out[lane]).any()

    def test_the_kernels_time_cannot_follow_the_table(self):
        """A grid step is a lane, whatever the table's width: the
        kernel's program has no axis over the table's entries, and its
        operands are the two pools whole, not a page operand an entry
        of a step."""
        c = _case(group=2, block_size=8, dtype=jnp.float32, max_blocks=12)
        jaxpr = jax.make_jaxpr(
            lambda *a: paged_decode_kernel(
                *a, config={"q_rows": 2, "kv_span": 4}
            )
        )(c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seq_lens"])
        (call,) = [
            e for e in _all_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"
        ]
        assert call.params["grid_mapping"].grid == (c["q"].shape[0],)
        # tables, lengths, queries, K pool, V pool
        assert len(call.invars) == 5
        assert call.invars[3].aval.shape[0] == c["k_pool"].shape[0]


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


class TestVerifyParity:
    @pytest.mark.parametrize(
        "dtype,block_size,group,head_dim,kv", PARITY_CASES
    )
    def test_matches_jnp_reference(
        self, dtype, block_size, group, head_dim, kv
    ):
        c = _case(group, block_size, dtype, head_dim=head_dim, kv=kv)
        ref = pa.paged_verify_attention(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"], backend="jnp",
        )
        out = paged_verify_kernel(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"],
        )
        assert out.dtype == ref.dtype
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=0,
        )
        assert float(jnp.max(jnp.abs(out))) < POISON / 10

    @pytest.mark.parametrize("kv_span", [2, 4])
    def test_wide_spans_agree(self, kv_span):
        c = _case(group=2, block_size=8, dtype=jnp.float32)
        ref = pa.paged_verify_attention(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"], backend="jnp",
        )
        out = paged_verify_kernel(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"], config={"q_rows": 8, "kv_span": kv_span},
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-5, rtol=0
        )


class TestDispatcher:
    def test_jnp_killswitch_is_byte_for_byte(self, monkeypatch):
        """DLROVER_TPU_PAGED_KERNEL=jnp routes through the exact
        reference computation: bitwise-identical outputs."""
        monkeypatch.setenv(pa.PAGED_KERNEL_ENV, "jnp")
        assert pa.paged_kernel_backend() == "jnp"
        c = _case(group=2, block_size=8, dtype=jnp.float32)
        via_env = pa.paged_decode_attention(
            c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seq_lens"]
        )
        explicit = pa.paged_decode_attention(
            c["q"], c["k_pool"], c["v_pool"], c["tables"],
            c["seq_lens"], backend="jnp",
        )
        np.testing.assert_array_equal(
            np.asarray(via_env), np.asarray(explicit)
        )
        via_env_v = pa.paged_verify_attention(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"],
        )
        explicit_v = pa.paged_verify_attention(
            c["qv"], c["k_pool"], c["v_pool"], c["tables"],
            c["positions"], backend="jnp",
        )
        np.testing.assert_array_equal(
            np.asarray(via_env_v), np.asarray(explicit_v)
        )

    def test_pallas_env_routes_to_kernel(self, monkeypatch):
        monkeypatch.setenv(pa.PAGED_KERNEL_ENV, "pallas")
        assert pa.paged_kernel_backend() == "pallas"
        c = _case(group=2, block_size=8, dtype=jnp.float32)
        via_env = pa.paged_decode_attention(
            c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seq_lens"]
        )
        direct = paged_decode_kernel(
            c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seq_lens"]
        )
        np.testing.assert_array_equal(
            np.asarray(via_env), np.asarray(direct)
        )

    def test_auto_resolution_on_cpu(self, monkeypatch):
        """auto = jnp on a plain CPU host (interpret would only burn
        CI wall-clock), pallas once interpret mode is forced on."""
        monkeypatch.delenv(pa.PAGED_KERNEL_ENV, raising=False)
        monkeypatch.delenv(INTERPRET_ENV, raising=False)
        assert jax.default_backend() != "tpu"
        assert pa.paged_kernel_backend() == "jnp"
        monkeypatch.setenv(INTERPRET_ENV, "1")
        assert pa.paged_kernel_backend() == "pallas"

    def test_invalid_env_fails_loudly(self, monkeypatch):
        monkeypatch.setenv(pa.PAGED_KERNEL_ENV, "mosaic")
        with pytest.raises(ValueError, match="DLROVER_TPU_PAGED_KERNEL"):
            pa.paged_kernel_backend()


class TestInterpretEnv:
    def test_shared_env_overrides_both_ways(self, monkeypatch):
        monkeypatch.delenv(INTERPRET_ENV, raising=False)
        default = use_interpret()
        assert default == (jax.default_backend() != "tpu")
        monkeypatch.setenv(INTERPRET_ENV, "1")
        assert use_interpret() is True
        monkeypatch.setenv(INTERPRET_ENV, "off")
        assert use_interpret() is False

    @pytest.mark.parametrize(
        "module", ["flash_attention", "fused", "quantization",
                   "paged_kernels"],
    )
    def test_every_kernel_family_uses_the_one_policy(self, module):
        """No kernel file keeps its own copy of the interpret switch
        (``fused`` and ``quantization`` used to, and a compile-for-TPU
        rehearsal silently compiled the interpreter instead)."""
        import importlib

        from dlrover_tpu.ops import pallas_utils

        mod = importlib.import_module(f"dlrover_tpu.ops.{module}")
        assert mod.use_interpret is pallas_utils.use_interpret
        assert not hasattr(mod, "_use_interpret")

    def test_interpret_is_refused_on_a_tpu_backend(self, monkeypatch):
        from dlrover_tpu.ops import pallas_utils

        monkeypatch.setattr(
            pallas_utils.jax, "default_backend", lambda: "tpu"
        )
        monkeypatch.delenv(INTERPRET_ENV, raising=False)
        assert use_interpret() is False
        monkeypatch.setenv(INTERPRET_ENV, "1")
        with pytest.raises(RuntimeError, match="never run interpreted"):
            use_interpret()


class TestAutotune:
    def test_candidates_are_tile_legal(self):
        from dlrover_tpu.accelerate.module_replace import (
            round_block_to_tile,
        )

        for dtype in (jnp.float32, jnp.bfloat16):
            cands = autotune.candidates(
                "decode", group=2, head_dim=8, block_size=8,
                max_blocks=8, dtype=dtype,
            )
            assert cands
            total = 8 * 8
            for cand in cands:
                kv_rows = cand["kv_span"] * 8
                assert (
                    round_block_to_tile(kv_rows, total, dtype) == kv_rows
                ), cand
            # the tile-aligned q-block option is always in the sweep
            tile = sublane_tile(dtype)
            assert any(c["q_rows"] % tile == 0 for c in cands)

    def test_get_config_is_deterministic_and_cached(self, monkeypatch,
                                                    tmp_path):
        monkeypatch.setenv(
            autotune.CACHE_ENV, str(tmp_path / "absent.json")
        )
        autotune.clear_memo()
        kw = dict(
            group=2, head_dim=8, block_size=8, max_blocks=8,
            dtype=jnp.float32,
        )
        a = autotune.get_config("decode", **kw)
        b = autotune.get_config("decode", **kw)
        assert a == b
        # CPU CI resolves from the checked-in defaults table, so the
        # config can never depend on timing
        key = autotune.shape_key("decode", **kw)
        with open(
            os.path.join(
                REPO, "dlrover_tpu", "ops", "autotune_defaults.json"
            )
        ) as f:
            defaults = json.load(f)
        if key in defaults:
            assert a["kv_span"] == defaults[key]["kv_span"]
        autotune.clear_memo()

    def test_user_cache_beats_defaults(self, monkeypatch, tmp_path):
        kw = dict(
            group=2, head_dim=8, block_size=8, max_blocks=8,
            dtype=jnp.float32,
        )
        key = autotune.shape_key("decode", **kw)
        cache = tmp_path / "tuned.json"
        cache.write_text(json.dumps({key: {"q_rows": 16, "kv_span": 4}}))
        monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
        autotune.clear_memo()
        try:
            assert autotune.get_config("decode", **kw) == {
                "q_rows": 16,
                "kv_span": 4,
            }
        finally:
            autotune.clear_memo()

    def test_tune_kernel_persists_winner_and_instruments(
        self, monkeypatch, tmp_path
    ):
        from dlrover_tpu.observability import events as ev
        from dlrover_tpu.observability import metrics as mx

        cache = tmp_path / "cache.json"
        events_file = tmp_path / "events.jsonl"
        monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
        ev.set_default_event_logger(
            ev.EventLogger(path=str(events_file))
        )
        registry = mx.MetricsRegistry()
        mx.set_default_registry(registry)
        calls = []

        def run_fn(config):
            def call():
                calls.append(dict(config))
                if config["kv_span"] == 2:  # make candidate 2 "fast"
                    return
                import time

                time.sleep(0.002)

            return call

        try:
            best, report = autotune.tune_kernel(
                "decode",
                run_fn,
                [{"q_rows": 8, "kv_span": 1}, {"q_rows": 8, "kv_span": 2}],
                key="decode|test-key",
                reps=2,
            )
        finally:
            ev.set_default_event_logger(None)
            mx.set_default_registry(mx.MetricsRegistry())
            autotune.clear_memo()
        assert best == {"q_rows": 8, "kv_span": 2}
        assert len(report) == 2 and all("us" in r for r in report)
        # winner persisted in the shape-keyed JSON cache
        table = json.loads(cache.read_text())
        assert table["decode|test-key"]["kv_span"] == 2
        # timeline span with the full required label set
        recs = [
            json.loads(line)
            for line in events_file.read_text().splitlines()
        ]
        spans = [r for r in recs if r.get("name") == "kernel_autotune"]
        assert len(spans) == 1, recs
        labels = spans[0]["labels"]
        for lab in ("kernel", "best_config", "candidates", "best_us"):
            assert lab in labels, labels
        assert json.loads(labels["best_config"])["kv_span"] == 2
        # gauge published on the registry
        text = registry.render_text()
        assert "dlrover_tpu_paged_kernel_us" in text

    def test_tuned_cache_feeds_dispatch(self, monkeypatch, tmp_path):
        """End to end: a tuned winner written to the cache is what the
        kernel wrapper resolves (and computes the same attention)."""
        kw = dict(
            group=2, head_dim=8, block_size=8, max_blocks=4,
            dtype=jnp.float32,
        )
        key = autotune.shape_key("decode", **kw)
        cache = tmp_path / "cache.json"
        cache.write_text(json.dumps({key: {"q_rows": 8, "kv_span": 2}}))
        monkeypatch.setenv(autotune.CACHE_ENV, str(cache))
        autotune.clear_memo()
        try:
            c = _case(group=2, block_size=8, dtype=jnp.float32)
            assert autotune.get_config("decode", **kw)["kv_span"] == 2
            out = paged_decode_kernel(
                c["q"], c["k_pool"], c["v_pool"], c["tables"],
                c["seq_lens"],
            )
            ref = pa.paged_decode_attention(
                c["q"], c["k_pool"], c["v_pool"], c["tables"],
                c["seq_lens"], backend="jnp",
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=5e-5, rtol=0
            )
        finally:
            autotune.clear_memo()


@pytest.mark.heavy
class TestSchedulerChurnUnderPallas:
    def test_churn_spec_decode_matches_jnp_backend(self, monkeypatch):
        """The ISSUE-15 churn gauntlet (pool exhaustion -> grow ->
        preempt -> resume, K=3 speculative windows) re-run with the
        pallas backend: still ONE compiled decode program, real
        preemptions, zero leaked blocks, and token tails IDENTICAL to
        the jnp-backend run of the same workload."""
        from dlrover_tpu.models import llama
        from dlrover_tpu.rl.scheduler import (
            ContinuousBatchingScheduler,
            SchedulerConfig,
        )

        cfg = llama.LlamaConfig.tiny(
            vocab_size=97, dim=32, n_layers=2, n_heads=4,
            n_kv_heads=2, mlp_dim=64, remat="none", dtype=jnp.float32,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        prompts = [
            np.array([5, 9, 2], np.int32),
            np.array([11, 3, 7, 8, 1, 2, 9], np.int32),
            np.array([1, 2], np.int32),
            np.array([30, 31, 32, 33], np.int32),
        ]
        monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
        monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
        monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "3")

        def run(backend):
            monkeypatch.setenv(pa.PAGED_KERNEL_ENV, backend)
            sch = ContinuousBatchingScheduler(
                cfg,
                SchedulerConfig(
                    max_slots=4, block_size=4, num_blocks=9,
                    max_seq_len=64, prefill_chunk=3, temperature=0.0,
                ),
            )
            sch.sync_weights(params)
            ids = [
                sch.submit(p, max_new=12, seed=50 + i)
                for i, p in enumerate(prompts)
            ]
            res = {r.req_id: r for r in sch.run()}
            return sch, ids, res

        ref_sch, ref_ids, ref_res = run("jnp")
        sch, ids, res = run("pallas")

        assert sch.stats()["kernel_backend"] == "pallas"
        assert sch.compile_counts()["decode"] == 1
        assert sch.stats()["preemptions"] >= 1, sch.stats()
        assert sch.stats()["accepted_tokens"] > 0, sch.stats()
        assert sch.stats()["used_blocks"] == 0  # nothing leaked
        for rid, pid in zip(ref_ids, ids):
            np.testing.assert_array_equal(
                ref_res[rid].tokens, res[pid].tokens
            )


class TestBenchHarness:
    def _module(self):
        path = os.path.join(REPO, "scripts")
        if path not in sys.path:
            sys.path.insert(0, path)
        import bench_paged_attention as bpa

        return bpa

    def test_flushes_artifact_per_sweep_point(self):
        bpa = self._module()
        snapshots = []
        payload = bpa.run_sweep(
            sweep=((2, 16, 8), (2, 24, 8)),
            reps=1,
            flush_fn=lambda p: snapshots.append(
                json.loads(json.dumps(p))
            ),
        )
        # one flush after each sweep point + the final one
        assert len(snapshots) == 3
        assert len(snapshots[0]["points"]) == 1
        assert len(snapshots[1]["points"]) == 2
        assert payload["complete"] is True
        for point in payload["points"]:
            for field in (
                "decode_jnp_us", "decode_pallas_us", "decode_speedup",
                "verify_jnp_us", "verify_pallas_us", "verify_speedup",
            ):
                assert field in point, point
        assert payload["decode_speedup_best"] > 0

    def test_table_rows_name_the_price_of_a_live_page(self, monkeypatch):
        """``--tables``: a row a (table, live share, group size) with
        the time a call, a LIVE page and the GB/s of the rows the lanes
        hold, checked against the dense reference where it was timed."""
        bpa = self._module()
        monkeypatch.setitem(bpa.TABLES, "tiny", (2, 4, 2, 9, True))
        rows = bpa.bench_tables(
            ["tiny"], shares=(0.25, 1.0), spans=(None, 4), reps=2,
            dims=dict(block_size=4, head_dim=8, dtype=jnp.float32),
        )
        assert [(r["live_share"], r["kv_span"]) for r in rows] == [
            (0.25, 1), (0.25, 4), (1.0, 1), (1.0, 4),
        ]
        for row in rows:
            assert row["live_pages"] == 2 * max(1, round(9 * row["live_share"]))
            assert row["us_a_call"] > 0 and row["gb_per_s"] >= 0
            assert row["us_a_live_page"] == pytest.approx(
                row["us_a_call"] / row["live_pages"], rel=1e-2
            )
            assert row["max_abs_diff_vs_jnp"] < 5e-6

    def test_chunk_rows_say_how_often_the_mask_is_left_off(self, monkeypatch):
        """``--chunk``: a row a (kernel, position, block shape) with the
        time a call, the share of the peak on the keys the mask admits
        and the three counts of steps, checked against the dense form."""
        bpa = self._module()
        monkeypatch.setattr(bpa, "CHUNKS", {
            "tiny_full": (None, None, (0, 64)),
            "tiny_window": (24, 12, (64,)),
        })
        rows = bpa.bench_chunk(
            reps=2, blocks=(None, (8, 16)),
            dims=dict(rows=16, heads=4, kv_heads=2, head_dim=8, keys=96,
                      block_size=4, dtype=jnp.float32),
        )
        assert [(r["kernel"], r["start"], r["key0"], r["keys"]) for r in rows[::2]] == [
            ("tiny_full", 0, 0, 96), ("tiny_full", 64, 0, 96),
            ("tiny_window", 64, 40, 48),
        ]
        for row in rows:
            # no chip, no share of a chip's peak
            assert row["ms_a_call"] > 0 and row["peak_pct_admitted"] is None
            assert row["max_abs_diff_vs_jnp"] < 5e-6
            assert row["steps_unmasked"] <= row["steps_computed"]
        # one block of the kernel's own spans the tiny table: nothing to
        # leave the mask off; 8 x 16 at position 64 has whole blocks
        assert rows[2]["steps_unmasked"] == 0
        assert rows[3]["blocks"] == (8, 16) and rows[3]["steps_unmasked"] == 8

    def test_selected_rows_say_what_a_tile_of_logits_costs(self):
        """``--selected``: a row a (width, block shape) with the time a
        call and a 262 144 logits, the chunk the width's last rows and
        every row reading its top ``topk``, checked against the XLA
        form."""
        bpa = self._module()
        rows = bpa.bench_selected(
            widths=(32, 64), reps=2, blocks=(None, (8, 16)),
            dims=dict(rows=16, heads=4, kv_heads=2, head_dim=8, topk=12,
                      dtype=jnp.float32),
        )
        assert [(r["keys"], r["start"], r["blocks"]) for r in rows] == [
            (32, 16, (16, 32)), (32, 16, (8, 16)),
            (64, 48, (16, 64)), (64, 48, (8, 16)),
        ]
        # 8 x 16 at 32 keys: rows 16-23 read two key blocks, 24-31 two
        for row, logits in zip(rows, (16 * 32, 8 * 16 * 4, 16 * 64, 8 * 16 * 8)):
            assert row["kernel"] == "sparse_prefill"
            assert row["us_a_262144_logits"] == pytest.approx(
                row["ms_a_call"] * 1e3 * 262144 / (4 * logits), rel=1e-2,
            )
            assert row["max_abs_diff_vs_jnp"] < 5e-6

    @pytest.mark.parametrize("entries", [8, None])
    def test_latent_rows_set_the_two_fetches_side_by_side(self, entries):
        """``--latent`` / ``--latent-sweep``: a row a count of held
        positions with the time of the gathered fetch and of the
        streamed kernel on one selection, each against the jnp form; the
        sweep's table is as wide as what a lane holds."""
        bpa = self._module()
        rows = bpa.bench_latent(
            held=(64, 128), entries=entries, reps=2,
            dims=dict(lanes=3, heads=4, block_size=16, rank=32, rope=8,
                      minor=16, topk=40, dtype=jnp.float32, scale=0.25),
        )
        assert [(r["held"], r["table"]) for r in rows] == [
            (64, 128 if entries else 64), (128, 128),
        ]
        for row in rows:
            assert row["kernel"] == "mla_sparse_decode" and row["topk"] == 40
            assert row["heads"] == 4
            assert row["gathered_us"] > 0 and row["streamed_us"] > 0
            for name in ("gathered_max_abs_diff_vs_jnp",
                         "streamed_max_abs_diff_vs_gathered_jnp",
                         "streamed_max_abs_diff_vs_jnp"):
                assert row[name] < 5e-6

    def test_index_decode_rows_set_gathered_beside_streamed(self):
        """``--index-decode``: a row a case, a count of held positions
        and a span, the gathered form's time beside the streamed
        kernel's on the same keys, with what the two differ by."""
        bpa = self._module()
        rows = bpa.bench_index_decode(
            spans=(None, 2), reps=2,
            dims={"tiny": dict(lanes=3, heads=4, dim=16, entries=8,
                               topk=40, held=(64, 128))},
        )
        assert [(r["held"], r["span"]) for r in rows] == [
            (64, 8), (64, 2), (128, 8), (128, 2),
        ]
        for row in rows:
            assert row["kernel"] == "index_decode_scores"
            assert row["case"] == "tiny" and row["table"] == 128
            assert row["gathered_us"] > 0 and row["streamed_us"] > 0
            assert row["same_finite"] and not row["topk_differ"]
            assert not row["topk_differ_from_exact"]
            assert row["gathered_err"] < 1e-5 and row["streamed_err"] < 1e-5

    def test_selection_row_times_three_forms_of_one_choice(self):
        bpa = self._module()
        (row,) = bpa.bench_selection(lanes=3, positions=128, topk=40, reps=2)
        assert row["selection"] == [3, 128] and row["topk"] == 40
        for form in ("sort_with_rows", "sort_alone", "counting_search"):
            assert row[f"{form}_us"] > 0 and row[f"{form}_same"]

    def test_budget_stops_between_points(self):
        bpa = self._module()
        snapshots = []
        payload = bpa.run_sweep(
            sweep=((2, 16, 8), (2, 24, 8)),
            reps=1,
            budget_s=1e-9,
            flush_fn=lambda p: snapshots.append(
                json.loads(json.dumps(p))
            ),
        )
        assert payload["complete"] is False
        assert payload["skipped_points"] == 2
        assert snapshots  # the partial artifact still flushed


# ---------------------------------------------------------------------------
# ISSUE 44: decode over a table that starts at a window's edge, and the
# streamed attention of a long chunk
# ---------------------------------------------------------------------------


def _dense_chunk(q, k, v, start, key0, window):
    """[C, H, D] against keys [KV, T, D], float64 on the host."""
    c, nh, d = q.shape
    nkv, t, _ = k.shape
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros((c, nh, d))
    for i in range(c):
        p = start + i
        pos = key0 + np.arange(t)
        seen = pos <= p
        if window is not None:
            seen &= pos > p - window
        for h in range(nh):
            kh = h // (nh // nkv)
            s = (k[kh] @ q[i, h]) * d ** -0.5
            s = np.where(seen, s, -np.inf)
            w = np.exp(s - s.max())
            out[i, h] = (w / w.sum()) @ v[kh]
    return out


class TestWindowAndLongChunkKernels:
    def test_window_table_view_orders_a_ring_by_position(self):
        ring = jnp.asarray([[7, 8, 9, 0, 5, 6], [1, 2, 3, 4, 0, 0]], jnp.int32)
        view = pa.window_table_view(ring, jnp.asarray([4, 0]))
        assert view.tolist() == [[5, 6, 7, 8, 9, 0], [1, 2, 3, 4, 0, 0]]
        padded = pa.window_table_view(ring, jnp.asarray([4, 0]), 8)
        assert padded[:, 6:].tolist() == [[0, 0], [0, 0]]
        assert padded[:, :6].tolist() == view.tolist()

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_decode_masks_what_lies_before_the_first_position(
        self, backend
    ):
        """Positions of a lane's table before ``first`` carry POISON:
        the result is the attention over ``[first, seq_len)`` alone,
        under either backend, an empty lane exact zeros."""
        rng = np.random.default_rng(3)
        b, nkv, group, d, bs, mb = 4, 2, 3, 8, 4, 5
        n_blocks = 1 + b * mb
        k = rng.normal(size=(n_blocks, bs, nkv, d)).astype(np.float32)
        v = rng.normal(size=(n_blocks, bs, nkv, d)).astype(np.float32)
        q = rng.normal(size=(b, nkv * group, d)).astype(np.float32)
        tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
        lens = np.asarray([17, 20, 3, 0], np.int32)
        first = np.asarray([3, 0, 2, 0], np.int32)
        want = np.zeros((b, nkv * group, d))
        for i in range(b):
            if lens[i] == 0:
                continue
            rows = slice(first[i], lens[i])
            ki = k[tables[i]].reshape(-1, nkv, d)[rows].transpose(1, 0, 2)
            vi = v[tables[i]].reshape(-1, nkv, d)[rows].transpose(1, 0, 2)
            want[i] = _dense_chunk(
                q[i][None], ki, vi, lens[i] - first[i] - 1, 0, None
            )[0]
            k[tables[i]].reshape(-1, nkv, d)  # (views; poison below)
        for i in range(b):  # poison every masked cell
            flat_k = k[tables[i]].reshape(-1, nkv, d)
            flat_v = v[tables[i]].reshape(-1, nkv, d)
            flat_k[: first[i]] = POISON
            flat_v[: first[i]] = POISON
            flat_k[lens[i]:] = POISON
            flat_v[lens[i]:] = POISON
            k[tables[i]] = flat_k.reshape(mb, bs, nkv, d)
            v[tables[i]] = flat_v.reshape(mb, bs, nkv, d)
        got = pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lens), backend,
            first=jnp.asarray(first), name="paged_window_decode",
        )
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
        assert not np.asarray(got[3]).any()

    @pytest.mark.parametrize("window", [None, 10, 64])
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_chunk_attention_is_causal_and_windowed(self, backend, window):
        """A chunk of 32 queries at positions 40.. against 96 keys whose
        row 0 is position 8: the keys ``s <= t`` (and ``s > t - window``)
        and no others, whatever lies above and behind."""
        from dlrover_tpu.ops.paged_kernels import chunk_prefill_kernel

        rng = np.random.default_rng(5)
        c, nkv, group, d, t = 32, 2, 3, 8, 96
        q = rng.normal(size=(c, nkv * group, d)).astype(np.float32)
        k = rng.normal(size=(nkv, t, d)).astype(np.float32)
        v = rng.normal(size=(nkv, t, d)).astype(np.float32)
        start, key0 = 40, 8
        want = _dense_chunk(q, k, v, start, key0, window)
        if backend == "pallas":  # blocks smaller than the shapes
            got = chunk_prefill_kernel(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.int32(start), jnp.int32(key0), window=window,
                block_q=8, block_k=16,
            )
        else:
            got = pa.paged_chunk_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.int32(start), jnp.int32(key0), window, backend,
            )
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    @pytest.mark.parametrize("case,c,t,start,key0,window,both", [
        # blocks 0-2 whole, the chunk's own two cut by the diagonal
        ("diagonal", 32, 128, 72, 8, None, True),
        # ... and behind them the window's edge cuts one more
        ("window_edge", 32, 128, 72, 8, 40, True),
        # no block of 16 keys fits a window of 10: every one is cut
        ("narrow_window", 32, 128, 72, 8, 10, False),
        # a prompt's first chunk: nothing lies wholly below any row
        ("position_0", 16, 64, 0, 0, None, False),
        # a ring's view: key 0 is the block of the window's edge
        ("ring_view", 32, 112, 200, 160, 40, True),
    ])
    def test_chunk_kernel_masks_only_the_blocks_the_mask_cuts(
        self, case, c, t, start, key0, window, both
    ):
        """Blocks of 8 rows x 16 keys, several a query block: where
        every row reads a block whole the body without a mask runs,
        where the diagonal or the window's edge cuts it the masked one,
        and the result is the dense attention's either way."""
        from dlrover_tpu.ops.paged_kernels import (
            chunk_key_blocks, chunk_prefill_kernel,
        )

        rng = np.random.default_rng(11)
        nkv, group, d = 2, 3, 8
        q = rng.normal(size=(c, nkv * group, d)).astype(np.float32)
        k = rng.normal(size=(nkv, t, d)).astype(np.float32)
        v = rng.normal(size=(nkv, t, d)).astype(np.float32)
        computed, unmasked, skipped = chunk_key_blocks(
            start, key0, c, t, window, block_q=8, block_k=16
        )
        assert computed + skipped == (c // 8) * (t // 16)
        assert computed - unmasked > 0
        assert (unmasked > 0) == both, (computed, unmasked, skipped)
        got = chunk_prefill_kernel(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.int32(start), jnp.int32(key0), window=window,
            block_q=8, block_k=16,
        )
        want = _dense_chunk(q, k, v, start, key0, window)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    def test_chunk_key_blocks_count_what_the_dense_mask_shows(self):
        """Random chunks, views and windows: a block is computed where
        the dense mask has a one in it, unmasked where it is all ones,
        skipped where it is all zeros."""
        from dlrover_tpu.ops.paged_kernels import chunk_key_blocks

        rng = np.random.default_rng(17)
        seen_unmasked = 0
        for _ in range(200):
            bq, bk = (int(x) for x in rng.choice([4, 8, 16, 32], size=2))
            c, t = bq * int(rng.integers(1, 5)), bk * int(rng.integers(1, 9))
            key0 = int(rng.integers(0, 64))
            start = key0 + int(rng.integers(0, max(t - c, 0) + 1))
            window = (
                None if rng.random() < 0.4 else int(rng.integers(1, 2 * t))
            )
            q_pos = (start + np.arange(c))[:, None]
            k_pos = (key0 + np.arange(t))[None]
            mask = k_pos <= q_pos
            if window is not None:
                mask &= k_pos > q_pos - window
            tiles = mask.reshape(c // bq, bq, t // bk, bk)
            want = (
                int(tiles.any(axis=(1, 3)).sum()),
                int(tiles.all(axis=(1, 3)).sum()),
                int((~tiles.any(axis=(1, 3))).sum()),
            )
            got = chunk_key_blocks(start, key0, c, t, window, bq, bk)
            assert got == want, (start, key0, c, t, window, bq, bk)
            seen_unmasked += want[1]
        assert seen_unmasked > 100

    def test_chunk_kernel_takes_key_blocks_of_1024(self, monkeypatch):
        """Untold, a grid step reads ``CHUNK_KEY_BLOCK`` keys (a shorter
        table its own length), and at Trinity-Large's positions most
        computed steps run without a mask."""
        from dlrover_tpu.ops.paged_kernels import (
            CHUNK_KEY_BLOCK, chunk_key_blocks, chunk_prefill_kernel,
        )

        seen = []
        real = paged_kernels.pl.pallas_call

        def spy(kernel, **kw):
            spec = kw["grid_spec"]
            seen.append((spec.grid, spec.in_specs[1].block_shape))
            return real(kernel, **kw)

        monkeypatch.setattr(paged_kernels.pl, "pallas_call", spy)
        assert CHUNK_KEY_BLOCK == 1024
        for t, want in ((4096, 1024), (96, 96)):
            jax.eval_shape(
                lambda q, k, v: chunk_prefill_kernel(
                    q, k, v, jnp.int32(0), jnp.int32(0)
                ),
                jnp.zeros((32, 6, 8)), jnp.zeros((2, t, 8)),
                jnp.zeros((2, t, 8)),
            )
            assert seen[-1] == ((2, 1, t // want), (1, want, 8))
        # a full layer's chunk at position 8192 of 32 k keys: 9-10 blocks
        # a query block, one of them cut; a window layer's (window 4096,
        # its view from the block of the window's edge): 5, two cut
        assert chunk_key_blocks(8192, 0, 2048, 32768) == (38, 34, 90)
        assert chunk_key_blocks(8192, 4096, 2048, 7168, 4096) == (20, 12, 8)

    def test_a_wide_table_streams_more_pages_a_step(self, monkeypatch):
        """The untuned group on a compiled TPU grows with the table
        (thousands of 16-token pages a lane at 32 k tokens) up to 32
        pages and leaves a short table four groups; verify, still a
        pipeline operand a page, keeps the spans it had."""
        from dlrover_tpu.ops import pallas_utils

        monkeypatch.setattr(pallas_utils, "use_interpret", lambda: False)
        shape = dict(group=6, head_dim=128, block_size=16, dtype=jnp.bfloat16)

        def spans(kernel):
            return {
                mb: autotune._heuristic(kernel, max_blocks=mb, **shape)[
                    "kv_span"
                ]
                for mb in (8, 64, 128, 385, 2048)
            }

        assert spans("decode") == {8: 2, 64: 16, 128: 32, 385: 32, 2048: 32}
        assert spans("verify") == {8: 4, 64: 4, 128: 4, 385: 16, 2048: 16}

    def test_a_group_is_bounded_by_the_fast_memory(self, monkeypatch):
        """Whatever span is asked for, a group's four buffers stay
        under 1 MiB each: pages of 128 KB (32 KV heads of 128, bfloat16)
        come 8 a group, pages of 16 KB as many as were asked for."""
        seen = []
        real = paged_kernels.pl.pallas_call

        def spy(kernel, **kw):
            seen.append(kw["grid_spec"].scratch_shapes[0].shape)
            return real(kernel, **kw)

        monkeypatch.setattr(paged_kernels.pl, "pallas_call", spy)
        for kv, want in ((32, 8), (4, 32)):
            n_blocks, lanes, bs, d = 3, 2, 16, 128
            pool = jnp.zeros((n_blocks, bs, kv, d), jnp.bfloat16)
            jax.eval_shape(
                lambda q, k, v, t, n: paged_decode_kernel(
                    q, k, v, t, n, config={"q_rows": 1, "kv_span": 32}
                ),
                jnp.zeros((lanes, kv, d), jnp.bfloat16), pool, pool,
                jnp.zeros((lanes, 64), jnp.int32),
                jnp.zeros((lanes,), jnp.int32),
            )
            assert seen[-1] == (2, want, bs * kv, d)
