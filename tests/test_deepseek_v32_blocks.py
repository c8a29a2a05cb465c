"""DeepSeek-V3.2's step programs walk their layers in a Python loop
that calls ONE set of jitted pieces of attention (a sub-scope each) and
one jitted MLP a kind (dense, expert), made inside the step program's
own call (``models/deepseek_v32.py``): a program's trace and its lowered
module do not grow with the depth, and a jitted piece never outlives
the trace it was made for — a module patched between two traces (the
benchmark's planted faults, ``tests/test_trinity.py``) is honoured by
the second.

Tiny widths on the CPU; what is read are counts of functions, calls and
Python executions, never a time.
"""

import os
import re
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for _p in (BENCH, os.path.join(BENCH, "tests", "tiny", "data")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import family_deepseek_v32_faulty as faulty  # noqa: E402
import tiny_families as T  # noqa: E402

from dlrover_tpu.models import deepseek_v32 as M  # noqa: E402
from dlrover_tpu.ops import paged_attention as pa  # noqa: E402
from dlrover_tpu.rl.kv_cache import (  # noqa: E402
    init_block_pool,
    paged_cache_config,
)
from dlrover_tpu.rl.scheduler import (  # noqa: E402
    decode_program,
    prefill_programs,
)

LANES, BLOCK, MAX_BLOCKS, CHUNK = 4, 8, 16, 16


def lowered(program, layers):
    """The module text of one of the scheduler's three programs (as the
    cells run them: logprobs captured, the per-position rows returned)
    at the tiny widths, 1 dense + ``layers - 1`` expert layers."""
    cfg = M.DeepSeekV32Config.tiny(num_hidden_layers=layers)
    params = jax.eval_shape(lambda: M.serving_params(
        M.init_params(jax.random.PRNGKey(0), cfg), cfg
    ))
    pool = jax.eval_shape(lambda: init_block_pool(
        paged_cache_config(cfg, 40, BLOCK, LANES, MAX_BLOCKS)
    ))
    i32 = jnp.int32

    def spec(*shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype)

    chunk = (spec(1, CHUNK), spec(MAX_BLOCKS), spec(), spec(), spec())
    lanes = (spec(LANES), spec(LANES, 2, dtype=jnp.uint32))
    if program == "decode":
        fn = decode_program(
            partial(M.paged_decode_step, cfg=cfg), 1.0, True, MAX_BLOCKS, True
        )
        args = (spec(LANES), spec(LANES, MAX_BLOCKS + 2), lanes[1])
    else:
        prefill, last = prefill_programs(
            partial(M.paged_prefill_chunk, cfg=cfg), 1.0, True, False, True
        )
        fn = last if program == "prefill_last" else prefill
        args = lanes + chunk if program == "prefill_last" else chunk
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, pool, *args
    ).as_text()


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill_last"])
def test_a_block_kind_is_traced_and_lowered_once(program, monkeypatch):
    """At 1 dense + 2 and at 1 dense + 6 expert layers the module holds
    the SAME functions — one of each piece of attention, an ``mlp`` for
    the dense layer and one for the expert layers — and the deeper one
    adds a layer's ``call`` lines and their arguments, nothing else (the
    unrolled loop's text grew 2.1-2.3 x); and the Python of a piece runs
    once a kind, not once a layer.  (JAX 0.9 fires its
    ``jaxpr_trace_duration`` event for every CALL of a jitted function,
    a found trace included, so the event cannot count traces: the
    pieces' own helpers are counted instead.)"""
    runs = {"_queries": 0, "_indexer_inputs": 0, "_route": 0, "_mlp": 0}

    def counted(name):
        inner = getattr(M, name)

        def wrapper(*a, **kw):
            runs[name] += 1
            return inner(*a, **kw)

        monkeypatch.setattr(M, name, wrapper)

    for name in runs:
        counted(name)
    once = {"_queries": 1, "_indexer_inputs": 1, "_route": 1, "_mlp": 2}
    shallow = lowered(program, 3)
    assert runs == once
    runs.update(dict.fromkeys(runs, 0))
    deep = lowered(program, 7)
    assert runs == once

    def functions(text):
        return len(re.findall(r"^\s*func\.func", text, re.M))

    def calls(text, name):
        return len(re.findall(rf"\bcall @{name}\w*\(", text))

    assert functions(deep) == functions(shallow)
    pieces = ["write_rows", "index", "attend"]
    pieces += [] if program == "decode" else ["project"]
    for piece in pieces:
        assert calls(shallow, piece) == 3 and calls(deep, piece) == 7, piece
    # a chunk that is not its prompt's last drops the logits, and the
    # last layer's MLP with them
    dead = program == "prefill"
    assert calls(deep, "mlp") in (7 - dead, 7)
    assert len(deep) < 1.15 * len(shallow), (len(shallow), len(deep))


# ------------------------------- a patched module and the next trace


def _unpacked(words):
    bits = (words[..., None] >> np.arange(32)) & 1
    return bits.reshape(words.shape[:-1] + (-1,)).astype(bool)


def _serve_one(parts, params, prompt):
    sch = T.scheduler(parts, dict(
        max_slots=2, block_size=4, num_blocks=40, max_seq_len=64,
        prefill_chunk=12, temperature=1.0,
    ), params)
    sch.submit(prompt, max_new=6, seed=0)
    (result,) = sch.run()
    return _unpacked(np.asarray(result.per_token["selection"]))


@pytest.fixture(scope="module")
def sound():
    """The tiny family's parts and one request: ``(serve, picked,
    prompt length)`` — ``serve()`` by a NEW scheduler under the backend
    the environment names, ``picked(backend)`` the rows sound programs
    pick under it, served once a module.  When the last case is over and
    its patches are undone, the request is served once more under every
    backend a case used: the restored module picks the sound rows again
    (a piece traced for a patched module that outlived it shows here)."""
    parts = T.parts("deepseek_v32", 64)
    params = T.params("deepseek_v32", 2**31 + 56)
    plen = 42
    prompt = np.random.default_rng(5).integers(
        0, parts["cfg"].vocab_size, size=plen
    ).astype(np.int32)

    def serve():
        return _serve_one(parts, params, prompt)

    def under(backend):
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv(pa.PAGED_KERNEL_ENV, backend)
            return serve()

    sound_rows = {}

    def picked(backend):
        if backend not in sound_rows:
            sound_rows[backend] = under(backend)
        return sound_rows[backend]

    yield serve, picked, plen
    for backend, rows in sound_rows.items():
        assert (under(backend) == rows).all(), backend


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("patched", ["decode", "prefill", "gather"])
def test_a_patched_module_is_honoured_by_the_next_trace(
    patched, backend, sound, monkeypatch
):
    """In ONE process: a request served sound; then the planted fault of
    ``family_deepseek_v32_faulty`` assigned to
    ``ops.paged_attention.decode_index_scores`` (or
    ``prefill_index_scores``) and the same request served by a NEW
    scheduler, whose programs are traced anew.  The rows of the patched
    program pick the NEWEST ``index_topk`` positions, the other
    program's rows what they picked before: no piece traced before the
    patch was handed to a program traced after it.

    ``gather``: ``gather_index_keys`` wrapped as the limits' probes wrap
    it (``benchmarks/tolerance_probe_deepseek_v32.py``,
    ``ik_previous_layer``: the tables of the layer before), here for
    every lane's tables alone, so that the chunk program stays sound:
    the prompt's rows are what they were, and a layer past the first
    picks something else in the decode program's rows.  Under the
    ``pallas`` backend (interpret mode) the decode program gathers
    nothing and scores the leaf in place
    (``index_decode_scores_kernel``): an assigned ``decode_index_scores``
    replaces the kernel and a wrapped gather hands it the shifted
    tables, so both faults bite there as they do on the chip; and with
    the module restored the next scheduler picks the sound rows again
    (``sound``, once, after the last case)."""
    monkeypatch.setenv(pa.PAGED_KERNEL_ENV, backend)
    serve, picked_under, plen = sound
    topk = M.DeepSeekV32Config.tiny().index_topk
    picked = picked_under("jnp")
    rows = picked.shape[0] - 1  # the last new token computed no row
    if backend == "pallas":  # the sound choice does not hang on the backend
        assert (picked_under("pallas")[:rows] == picked[:rows]).all()
    newest = np.zeros_like(picked[:rows])
    for t in range(rows):
        newest[t, :, max(0, t + 1 - topk):t + 1] = True
    assert not (picked[topk:rows] == newest[topk:]).all()

    # what ``_newest`` assigns, restored when the test ends
    names = ("decode_index_scores", "prefill_index_scores",
             "gather_index_keys")
    sound_fns = {name: getattr(pa, name) for name in names}
    for name, fn in sound_fns.items():
        monkeypatch.setattr(pa, name, fn)
    if patched == "gather":
        gather, nb = pa.gather_index_keys, 40  # ``_serve_one``'s blocks

        def previous(ik_pool, tables, width):
            if tables.ndim == 1:  # the chunk's: one sequence's table
                return gather(ik_pool, tables, width)
            return gather(
                ik_pool, jnp.where(tables >= nb, tables - nb, tables), width
            )

        pa.gather_index_keys = previous
        served = serve()[:rows]
        assert (served[:plen] == picked[:plen]).all()
        # the first new token is the sound chunk's: the first layer, whose
        # tables are its own, picks for it what it picked
        assert (served[plen, 0] == picked[plen, 0]).all()
        assert not (served[plen:, 1:] == picked[plen:rows, 1:]).all()
    else:
        kept = "prefill" if patched == "decode" else "decode"
        keep = getattr(pa, f"{kept}_index_scores")
        faulty._newest(pa)
        setattr(pa, f"{kept}_index_scores", keep)
        served = serve()[:rows]
        # row j is what the program decided while it computed position
        # j: the prompt's rows are the chunk program's, the others
        # decode's
        mine = slice(plen, rows) if patched == "decode" else slice(0, plen)
        assert (served[mine] == newest[mine]).all()
        if patched == "decode":
            assert (served[:plen] == picked[:plen]).all()
    # the module is restored when the case ends, and the module-scoped
    # ``sound`` serves the request again after the last of them
