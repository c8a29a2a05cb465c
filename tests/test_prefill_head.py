"""The prefill chunk's head runs only where its output is read (ISSUE 41; first built for ISSUE 40).

A chunk's logits are read in one place: the row of the prompt's last
token, which seeds the first sampled token.  So

- the last chunk's program (``rl/scheduler.prefill_programs``) cuts
  that row from the model's ``[1, C, vocab]`` logits: the token and the
  logprob it returns are that row's, and the pool written is the same;
- the scheduler runs every chunk but a prompt's last through a program
  that returns the pool alone, and the last one through a program that
  also samples the first token: the token and its logprob are what the
  parent's three dispatches (chunk, ``logits[0, i]``, sample) gave;
- ``stats()`` counts chunks and heads, the ``serve_step`` record says
  whether its chunk ran the head.

Tiny llama and tiny Falcon-H1 (``lane_state()``: its chunk is told the
lane and the count of real tokens), float32, on the CPU.
"""

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import falcon_h1, llama
from dlrover_tpu.observability.events import EventLogger, read_events
from dlrover_tpu.rl.kv_cache import init_block_pool, paged_cache_config
from dlrover_tpu.rl.scheduler import (
    ContinuousBatchingScheduler,
    SchedulerConfig,
    prefill_programs,
)

CHUNK, BLOCK, MAX_SEQ = 8, 4, 64
LLAMA = llama.LlamaConfig.tiny(
    vocab_size=97, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
    mlp_dim=64, max_seq_len=MAX_SEQ, remat="none", dtype=jnp.float32,
)
DRAFT = llama.LlamaConfig.tiny(
    vocab_size=97, dim=16, n_layers=1, n_heads=2, n_kv_heads=2,
    mlp_dim=32, max_seq_len=MAX_SEQ, remat="none", dtype=jnp.float32,
)
FALCON = falcon_h1.FalconH1Config.tiny(
    max_seq_len=MAX_SEQ, dtype=jnp.float32
)


class Model:
    """A model as the scheduler is given it, and its chunk program with
    one signature for both: ``chunk(params, tokens, pool, table, start,
    real)`` (lane 0)."""

    def __init__(self, name):
        self.name = name
        if name == "llama":
            self.cfg = LLAMA
            self.params = llama.init_params(jax.random.PRNGKey(0), LLAMA)
            fn = partial(llama.paged_prefill_chunk, cfg=LLAMA)
            self.chunk = lambda p, t, pool, tab, start, real: fn(
                p, t, pool, tab, start
            )
            self.parts = {}
        else:
            self.cfg = FALCON
            self.params = falcon_h1.init_params(
                jax.random.PRNGKey(0), FALCON
            )
            fn = partial(falcon_h1.paged_prefill_chunk, cfg=FALCON)
            self.chunk = lambda p, t, pool, tab, start, real: fn(
                p, t, pool, tab, start, jnp.int32(0), real
            )
            self.parts = dict(
                paged_decode_fn=partial(
                    falcon_h1.paged_decode_step, cfg=FALCON
                ),
                paged_prefill_fn=fn,
                serving_params_fn=partial(
                    falcon_h1.serving_params, cfg=FALCON
                ),
            )
        self.fn, self.lane_state = fn, name != "llama"
        self.vocab = self.cfg.vocab_size

    def pool(self):
        return init_block_pool(
            paged_cache_config(self.cfg, 32, BLOCK, 2)
        )

    def chunks_of(self, prompt):
        """``(tokens [1, C], start, real)`` of every chunk of a prompt
        prefilled from position 0."""
        for start in range(0, len(prompt), CHUNK):
            part = prompt[start:start + CHUNK]
            yield (
                jnp.asarray(
                    np.pad(part, (0, CHUNK - len(part)))[None], jnp.int32
                ),
                jnp.int32(start), jnp.int32(len(part)),
            )

    def scheduler(self, events=None, **kw):
        sched = dict(
            max_slots=3, block_size=BLOCK, num_blocks=64,
            max_seq_len=MAX_SEQ, prefill_chunk=CHUNK, temperature=0.0,
        )
        sched.update(kw.pop("sched", {}))
        return ContinuousBatchingScheduler(
            self.cfg, SchedulerConfig(**sched), events=events,
            **self.parts, **kw,
        )


MODELS = ["llama", "falcon_h1"]


@functools.cache
def model_of(name):
    return Model(name)


@pytest.fixture(autouse=True)
def _exact_float32():
    with jax.default_matmul_precision("highest"):
        yield


TABLE = jnp.arange(1, MAX_SEQ // BLOCK + 1, dtype=jnp.int32)


def prompts_of(lengths, vocab, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


# ------------------------------------------------ the last chunk's program


@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize(
    "case,chunk_index,real",
    [("first", 0, CHUNK), ("middle", 1, 4), ("padded_last", 2, 5)],
)
def test_last_chunks_row_is_that_row_of_the_whole_head(
    name, case, chunk_index, real
):
    """A prompt of 2 chunks and 5 tokens: chunk 0 starts the lane from
    zero, chunk 1 continues at ``start > 0`` (and is told 4 of its
    tokens are real), chunk 2 is padded.  Run as a prompt's last chunk,
    each gives the argmax and the log-softmax of row ``real - 1`` of the
    model's own ``[1, C, vocab]`` logits, in the lanes' token vector
    too, and writes the pool the model writes."""
    model = model_of(name)
    (prompt,) = prompts_of([2 * CHUNK + 5], model.vocab)
    pool = model.pool()
    for i, (tokens, start, _) in enumerate(model.chunks_of(prompt)):
        if i == chunk_index:
            break
        _, pool = model.chunk(
            model.params, tokens, pool, TABLE, start, jnp.int32(CHUNK)
        )
    logits, after = model.chunk(
        model.params, tokens, pool, TABLE, start, jnp.int32(real)
    )
    assert logits.shape == (1, CHUNK, model.vocab)
    _, last = prefill_programs(model.fn, 0.0, True, model.lane_state)
    lane = 0  # ``Model.chunk``'s
    after_last, lanes, tok, lp = jax.jit(last)(
        model.params, pool, jnp.full((3,), -1, jnp.int32),
        jnp.zeros((3, 2), jnp.uint32), tokens, TABLE, start,
        jnp.int32(lane), jnp.int32(real),
    )
    row = logits[0, real - 1]
    assert int(tok) == int(jnp.argmax(row))
    assert lanes.tolist() == [int(tok), -1, -1]
    assert abs(float(lp) - float(jax.nn.log_softmax(row)[tok])) <= 1e-6
    for leaf in after:
        np.testing.assert_array_equal(after_last[leaf], after[leaf])


# --------------------------------------------------- through the scheduler


def parents_first_token(model, prompt):
    """The parent's rule at temperature 0: the chunk program's
    ``[1, C, vocab]`` logits, the row of the prompt's last token, its
    argmax and that token's log-softmax."""
    pool = model.pool()
    for tokens, start, real in model.chunks_of(prompt):
        logits, pool = model.chunk(
            model.params, tokens, pool, TABLE, start, real
        )
    row = logits[0, len(prompt) - 1 - int(start)]
    tok = int(jnp.argmax(row))
    return tok, float(jax.nn.log_softmax(row)[tok])


#: lengths: under a chunk, a whole chunk, padded and whole last chunks
LENGTHS = [3, CHUNK, CHUNK + 3, 2 * CHUNK, 2 * CHUNK + 5]
UNIFIED = {
    "unified-logprobs": dict(capture_logprobs=True),
    "unified-tokens_only": dict(capture_logprobs=False),
}
MODES = {
    # the llama programs are the scheduler's own; a model with lane
    # state is refused the prefill role, a K-step window and a draft
    "llama": dict(
        UNIFIED,
        prefill_role=dict(role="prefill"),
        **{
            "decode_k3-logprobs": dict(capture_logprobs=True, decode_k=3),
            "draft_mirror-logprobs": dict(
                capture_logprobs=True, decode_k=3, draft_cfg=DRAFT
            ),
        },
    ),
    # injected programs
    "falcon_h1": UNIFIED,
}


@pytest.mark.parametrize(
    "name,mode",
    [(name, mode) for name in MODELS for mode in sorted(MODES[name])],
)
def test_first_token_and_logprob_are_the_parents(
    name, mode, monkeypatch
):
    model = model_of(name)
    kw = dict(MODES[name][mode])
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", str(kw.pop("decode_k", 1)))
    sch = model.scheduler(**kw)
    if "draft_cfg" in kw:
        sch.sync_weights(
            model.params, llama.init_params(jax.random.PRNGKey(1), DRAFT)
        )
        assert sch.stats()["draft_active"] == 1
    else:
        sch.sync_weights(model.params)
    prompts = prompts_of(LENGTHS, model.vocab)
    ids = [sch.submit(p, max_new=4, seed=i) for i, p in enumerate(prompts)]
    if kw.get("role") == "prefill":
        for _ in range(40):
            sch.step()
        firsts = {s["req_id"]: (s["first_token"], None) for s in sch.shipped}
    else:
        firsts = {
            r.req_id: (
                int(r.tokens[len(prompts[ids.index(r.req_id)])]),
                float(r.logprobs[0]) if kw["capture_logprobs"] else None,
            )
            for r in sch.run()
        }
    assert sorted(firsts) == sorted(ids)
    for rid, prompt in zip(ids, prompts):
        tok, lp = parents_first_token(model, prompt)
        assert firsts[rid][0] == tok
        if firsts[rid][1] is not None:
            assert abs(firsts[rid][1] - lp) <= 1e-5
    st = sch.stats()
    assert st["prefill_heads"] == len(prompts)
    assert st["prefill_chunks"] == sum(
        math.ceil(len(p) / CHUNK) for p in prompts
    )
    counts = sch.compile_counts()
    assert counts["prefill"] == 1 and counts["sample"] == 1, counts
    if "draft_cfg" in kw:
        assert sch._draft_prefill_jit._cache_size() == 1


def test_a_prefix_hit_still_ends_in_a_last_chunk(monkeypatch):
    """A prompt whose first blocks are shared starts its prefill past
    them: fewer chunks, and the one that reaches the prompt's end is
    still its last — one head, the same first token."""
    model = model_of("llama")
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "1")
    sch = model.scheduler(capture_logprobs=True)
    sch.sync_weights(model.params)
    prompts = prompts_of([CHUNK + 3, 2 * CHUNK + 5], model.vocab)
    firsts = []
    for _ in range(2):
        ids = [sch.submit(p, max_new=2, seed=0) for p in prompts]
        done = {r.req_id: r for r in sch.run()}
        firsts.append([
            (int(done[i].tokens[len(p)]), float(done[i].logprobs[0]))
            for i, p in zip(ids, prompts)
        ])
    st = sch.stats()
    assert st["prefix_hits"] > 0
    assert st["prefill_heads"] == 2 * len(prompts)
    cold = sum(math.ceil(len(p) / CHUNK) for p in prompts)
    assert cold < st["prefill_chunks"] < 2 * cold
    for (tok, lp), again, prompt in zip(*firsts, prompts):
        assert tok == again[0] == parents_first_token(model, prompt)[0]
        assert abs(lp - again[1]) <= 1e-5


@pytest.mark.parametrize("name", MODELS)
def test_a_preempted_prompt_pays_its_head_again(
    name, monkeypatch, tmp_path
):
    """A starved pool: a sequence preempted while it decodes is
    re-admitted and re-prefilled, and its last chunk runs the head
    again.  The ``serve_step`` records carry the same count."""
    model = model_of(name)
    monkeypatch.setenv("DLROVER_TPU_KV_ADMIT_WATERMARK", "0")
    monkeypatch.setenv("DLROVER_TPU_KV_GROW_BLOCKS", "1")
    monkeypatch.setenv("DLROVER_TPU_DECODE_STEPS", "1")
    path = str(tmp_path / "events.jsonl")
    sch = model.scheduler(
        events=EventLogger(path=path),
        sched=dict(max_slots=3, num_blocks=13, max_seq_len=40),
    )
    sch.sync_weights(model.params)
    phases = []
    preempt = sch._preempt

    def spy(slot):
        phases.append(sch._slots[slot].phase)
        preempt(slot)

    monkeypatch.setattr(sch, "_preempt", spy)
    prompts = prompts_of([5, CHUNK + 2, 3, 2 * CHUNK], model.vocab)
    for i, p in enumerate(prompts):
        sch.submit(p, max_new=12, seed=i)
    assert len(sch.run()) == len(prompts)
    st = sch.stats()
    assert st["preemptions"] == len(phases) >= 1
    assert st["prefill_heads"] == len(prompts) + phases.count("decode")
    steps = [
        e["labels"] for e in read_events(path) if e["name"] == "serve_step"
    ]
    assert {s["prefill_heads"] for s in steps} == {0, 1}
    assert sum(s["prefill_heads"] for s in steps) == st["prefill_heads"]
    assert st["prefill_chunks"] == sum(1 for s in steps if s["tokens"])
