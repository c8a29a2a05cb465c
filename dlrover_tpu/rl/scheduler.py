"""Token-level (continuous-batching) generation scheduler.

Reference parity: Orca's iteration-level scheduling + vLLM's block
tables — the serving loop the reference system gets from its vLLM
backend.  A whole-batch backend (``rl/inference.py``) serves one
batch to completion before the next; here scheduling happens at TOKEN
granularity:

- the batch is ``max_slots`` fixed LANES, each holding (or not) one
  live sequence — an active-mask, never a shape change;
- ONE jitted decode program (``models.llama.paged_decode_step`` over
  the ``rl/kv_cache`` block pool) advances every active lane by one
  token per iteration; admissions and evictions mutate host-side
  arrays (block tables, positions, masks) only, so the program
  compiles exactly once and never retraces across arbitrary traffic;
- prompts prefill in fixed-size CHUNKS (one chunk per iteration,
  round-robin) interleaved with running decodes — a 10k-token prompt
  costs the running sequences a bounded slice per iteration instead
  of stalling them for its whole prefill;
- a sequence leaves its slot the moment it hits EOS or its token
  budget, and the freed slot admits the next queued prompt on the
  SAME iteration — mixed-length traffic never waits for the longest
  sequence in a batch (the dense-batch pathology this replaces).

Allocation is incremental (vLLM-style): admission reserves only the
prompt's blocks plus ``DLROVER_TPU_KV_GROW_BLOCKS`` headroom and is
gated by a free-pool watermark (``DLROVER_TPU_KV_ADMIT_WATERMARK``);
block tables grow on demand at decode time, and when the pool runs dry
the LOWEST-PRIORITY running sequence (fewest tokens generated,
youngest admission) is PREEMPTED — its blocks freed, the request
requeued at the queue head carrying its generated tail, so it
re-prefills and resumes deterministically (sampling is a pure
function of (seed, position), so the final tokens are identical —
pinned by test).  Full prompt blocks are content-hashed into the
pool's ref-counted shared-block index, so a repeated system prompt
maps the same physical blocks — unless the model declares per-lane
state (``lane_state()``), which cannot be shared by prefix.

Multi-token decode (``DLROVER_TPU_DECODE_STEPS=K``, default 1): one
fused compiled program runs K greedy self-drafting decode steps plus
ONE batched verify forward (``models.llama.paged_verify_step``) per
iteration, then accepts the longest draft prefix the verify pass
agrees with — at temperature 0 the emitted stream is exactly the K=1
loop's (each draft step IS the K=1 computation), at sampled
temperatures acceptance is rejection-style (every emitted token is
sampled from its true conditional).  Host dispatch drops by up to K×
on the CPU-bound path — the ``dispatches`` counter measures it.

One step ahead: the lanes' current tokens live ON THE DEVICE, a
``[max_slots]`` vector the decode program takes and returns, so step
n+1 is dispatched from step n's output before the host has read it.
An iteration enqueues its prefill chunk and its decode step and only
then reads and commits what the PREVIOUS iteration dispatched: the
commit loop, admission and the replica's ring traffic run beside the
program instead of between two programs.  What the host decides
without the tokens: positions, tables, and finish by length (a lane
whose in-flight token is its ``max_new``-th sits the next step out).
Finish by EOS is learnt one step late; that lane's extra step is
computed and discarded.  Where the loop may not run ahead it first
commits what is in flight — multi-token decode and a draft model,
the ``prefill`` role, a preemption, ``sync_weights``, ``drain`` —
decided by what the code can see, never by a switch.

Determinism: each request's tokens are sampled with
``fold_in(PRNGKey(seed), position)`` — a function of (seed, position)
only, independent of which slot/iteration served it.  The same
request produces the same tokens whether it ran alone, continuously
batched, after a drain-requeue or preemption-resume, or on a
different replica; tests pin tail parity against an unbatched
reference on exactly this property.
"""

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from dlrover_tpu.common.env import (
    decode_steps,
    fleet_interactive_slots,
    kv_admit_watermark,
    kv_grow_blocks,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.observability.events import EventLogger
from dlrover_tpu.rl.kv_cache import (
    BlockPool,
    OutOfBlocksError,
    extract_block_regions,
    init_block_pool,
    insert_block_regions,
    lane_state_nbytes,
    paged_cache_config,
    pool_can_ever_hold,
    prefix_block_keys,
    block_nbytes,
)

SLO_INTERACTIVE = "interactive"
SLO_BATCH = "batch"

FINISH_EOS = "eos"
FINISH_LENGTH = "length"


class _HostPhase:
    """One leaf phase of an iteration's host time: a profiler
    annotation for its duration (``EventLogger.leaf`` — on the device
    trace's clock when a ``jax.profiler`` window is open, a no-op
    otherwise) and a running sum that ``step`` writes onto the
    iteration's ``serve_step`` record.  Phases never nest."""

    __slots__ = ("_leaf", "total_s", "_t0", "_ann")

    def __init__(self, leaf: Callable):
        self._leaf = leaf
        self.total_s = 0.0

    def __enter__(self):
        self._ann = self._leaf()
        self._ann.__enter__()
        self._t0 = time.monotonic()

    def __exit__(self, *exc):
        self.total_s += time.monotonic() - self._t0
        self._ann.__exit__(*exc)


def _empty_tokens() -> np.ndarray:
    return np.zeros((0,), np.int32)


def _empty_logprobs() -> np.ndarray:
    return np.zeros((0,), np.float32)


def _never_computed(rows: np.ndarray):
    """What a per-position array holds where no step program computed
    the position: -1 in an integer array, NaN in a float one."""
    return -1 if rows.dtype.kind == "i" else np.nan


@dataclass
class GenRequest:
    """One generation request (prompt in, sampled tail out).

    ``resume_tokens`` carries a preempted sequence's generated tail:
    on re-admission the scheduler re-prefills prompt+tail and resumes
    sampling at the next position — (seed, position)-purity makes the
    continuation identical to the uninterrupted run."""

    req_id: int
    prompt: np.ndarray  # [P] int32
    max_new: int
    seed: int = 0
    submit_t: float = field(default_factory=time.monotonic)
    resume_tokens: np.ndarray = field(default_factory=_empty_tokens)
    # per-token logprobs of the resume tail (logprob capture mode;
    # same length as ``resume_tokens`` when known, NaN-padded when the
    # tail crossed a boundary that could not carry them)
    resume_logprobs: np.ndarray = field(
        default_factory=_empty_logprobs
    )
    # request-tracing state.  ``submit_wall`` is the wall-clock
    # anchor that rode the dispatcher→replica ring (0 = in-process
    # submit, fall back to this process's anchored clock); the rest
    # survive preemption so the serve_request span tells the request's
    # WHOLE life, not its last incarnation's
    submit_wall: float = 0.0
    preempts: int = 0
    hit_blocks: int = 0
    queue_wait_s: float = 0.0
    token_times: List[float] = field(default_factory=list)
    # serving lanes: the SLO class steers admission order, the
    # reserved-slot quota, and preemption rank; the tenant
    # key drives weighted fair-share within a class.  ``shipped`` is
    # the disaggregated-decode adoption payload (prefilled KV block
    # regions + the first sampled token) — consumed at admission,
    # never carried through a preempt/requeue (the resume path
    # re-prefills deterministically instead).
    slo_class: str = SLO_BATCH
    tenant: str = ""
    shipped: Optional[Dict] = None
    # how the dispatcher picked this replica (least_outstanding /
    # affinity / ship); "local" for in-process submits — stamped on
    # the serve_request span so routing decisions are auditable
    route: str = "local"


@dataclass
class GenResult:
    req_id: int
    tokens: np.ndarray  # [P + new] int32 (prompt verbatim + tail)
    finish_reason: str
    new_tokens: int
    latency_s: float
    stats: Dict = field(default_factory=dict)
    # per-generated-token actor logprobs (length == new_tokens) when
    # the scheduler runs with ``capture_logprobs``; empty otherwise —
    # the flywheel's streamed ``old_logp``, eliminating the trainer's
    # recompute forward over the rollout
    logprobs: np.ndarray = field(default_factory=_empty_logprobs)
    # what the model's step programs decided at every position they
    # COMPUTED (``per_token_outputs()`` of its config: a router's
    # experts), ``{name: [P + new, ...]}``, whenever logprobs are
    # captured: row ``j`` by the prefill program for a prompt position
    # as by the decode program for an answer's; a position never
    # computed here (the last new token; a shipped prefill's prompt)
    # holds -1 (NaN in a float array).  Empty for a model without.
    per_token: Dict[str, np.ndarray] = field(default_factory=dict)


@dataclass(frozen=True)
class SchedulerConfig:
    """Serving geometry: every field is a STATIC shape input of the
    compiled programs — change one and you get (exactly) one new
    compile, change traffic and you get none."""

    max_slots: int = 8  # decode lanes
    block_size: int = 16  # tokens per KV block
    num_blocks: int = 256  # pool size incl. the null block
    max_seq_len: int = 512  # longest prompt+tail a slot may hold
    prefill_chunk: int = 32  # prompt tokens prefilled per iteration
    max_new_default: int = 64
    temperature: float = 1.0
    eos_id: Optional[int] = None

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.block_size)


@dataclass
class _Slot:
    req: Optional[GenRequest] = None
    phase: str = "free"  # free | prefill | decode
    prefill_pos: int = 0
    prefill_tokens: np.ndarray = field(default_factory=_empty_tokens)
    prefill_len: int = 0  # prompt + resume-tail tokens to prefill
    prefix_keys: List[str] = field(default_factory=list)
    shared_upto: int = 0  # prompt blocks registered in the index
    admit_seq: int = 0  # monotonic admission order (victim policy)
    generated: List[int] = field(default_factory=list)
    logprobs: List[float] = field(default_factory=list)
    first_token_t: float = 0.0
    # tokens sampled for this lane on the device that the host has not
    # read yet (0-2: the first token, or a decode step, or both)
    ahead: int = 0
    # the model's per-position outputs where the result will carry
    # them, ``{name: [prompt + max_new, ...]}`` made untouched at
    # admission (``_lane_rows``): each commit writes its position's row
    # in place, and positions ``[0, rows_upto)`` are laid
    rows: Dict[str, np.ndarray] = field(default_factory=dict)
    rows_upto: int = 0


@dataclass
class _InFlight:
    """One dispatch whose samples the host has not read: a decode step
    (``toks`` / ``lps`` are ``[max_slots]``) or a prompt's first token
    (scalars).  ``lanes`` holds ``(slot, the _Slot it ran for, the
    lane's position after it)``: a slot that holds another ``_Slot``
    at commit left by EOS in between, and its sample is discarded."""

    lanes: List
    toks: object
    lps: object
    first: bool = False
    iteration: int = 0  # the ``step()`` that dispatched it
    # a decode step's per-position outputs, ``{name: [max_slots, ...]}``
    rows: Optional[Dict] = None


def sample_rows(logits, keys, sample_pos, temp: float):
    """logits [S, V]; keys [S, 2] request base keys; sample_pos [S]
    the OUTPUT position each token will occupy — the (seed,
    position)-only sampling contract."""
    import jax
    import jax.numpy as jnp

    if temp <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    folded = jax.vmap(jax.random.fold_in)(keys, sample_pos)
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l / temp)
    )(folded, logits).astype(jnp.int32)


def logprob_rows(logits, toks):
    """Actor logprob of each sampled token: log-softmax of the RAW
    fp32 logits (temperature-free — the trainer's ``token_logprobs``
    contract), gathered at the token."""
    import jax
    import jax.numpy as jnp

    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return jnp.take_along_axis(
        lp, toks[..., None].astype(jnp.int32), axis=-1
    )[..., 0]


def decode_program(decode_model, temp: float, capture_logprobs: bool,
                   max_blocks: int, per_token: bool = False):
    """The plain decode step as the scheduler jits it (the pool, the
    second argument, donated): ``(params, pool, tokens, lanes, keys)
    -> (pool, tokens'[, logprobs[, rows]])``.  ``per_token``: the model
    returns a third value, its per-position outputs ``{name: [S,
    ...]}``, which ride last where logprobs are captured.

    ``tokens`` is the lanes' current-token vector and ``tokens'`` the
    next one (an inactive lane keeps its entry), so a step is called
    with the previous step's OUTPUT and needs no value from the host.
    ``lanes`` is the host's one upload a step, int32 ``[S, max_blocks
    + 2]``: the block tables, then the positions, then the active
    mask.  ``keys`` ``[S, 2]`` changes only at admission."""
    import jax
    import jax.numpy as jnp

    def _step(params, pool, tokens, lanes, keys):
        tables = lanes[:, :max_blocks]
        positions = lanes[:, max_blocks]
        active = lanes[:, max_blocks + 1] != 0
        logits, pool, *rows = decode_model(
            params, tokens, pool, tables, positions, active
        )
        # device scopes (observability/events.py DEVICE_SCOPES): the
        # model's step program names its own role and parts; what this
        # module adds after it is the ``sample`` part of the same role
        with jax.named_scope("decode"), jax.named_scope("sample"):
            nxt = sample_rows(logits, keys, positions + 1, temp)
            return (
                pool, jnp.where(active, nxt, tokens), logits, nxt, rows
            )

    def _decode(params, pool, tokens, lanes, keys):
        pool, tokens, _, _, _ = _step(params, pool, tokens, lanes, keys)
        return pool, tokens

    def _decode_lp(params, pool, tokens, lanes, keys):
        pool, tokens, logits, nxt, rows = _step(
            params, pool, tokens, lanes, keys
        )
        with jax.named_scope("decode"), jax.named_scope("sample"):
            out = (pool, tokens, logprob_rows(logits, nxt))
            return out + tuple(rows) if per_token else out

    return _decode_lp if capture_logprobs else _decode


def prefill_programs(prefill_model, temp: float, capture_logprobs: bool,
                     lane_state: bool, per_token: bool = False):
    """The two programs a prompt's chunks go through, as the scheduler
    jits them (the pool, the second argument, donated).  A chunk's
    logits are read in ONE place: the row of the prompt's last token,
    which seeds the first sampled token.  So the head runs there only,
    for any ``prefill_model`` of the contract, injected ones included:

    - ``prefill(params, pool, chunk, table, start, lane, real) ->
      pool``: a chunk that is not its prompt's last.  The model's
      logits are dropped INSIDE the program, so the compiler removes
      the final norm and the ``lm_head`` matmul with them.
    - ``last(params, pool, tokens, keys, chunk, table, start, lane,
      real) -> (pool, tokens', tok[, logprob])``: the prompt's last
      chunk.  The row read is ``real - 1`` and the token's output
      position ``start + real``, both traced: one compiled program for
      every prompt length.  The row is cut from the model's ``[1, C,
      vocab]`` logits inside the program, as a masked sum over the
      chunk's rows: exact, and the compiler fuses it into the
      ``lm_head`` product, whose result is then ONE row — the other
      127 are never written (``tests/test_tpu_compile.py`` pins it; a
      ``dynamic_slice`` is left behind the whole product).  The first
      token is sampled by the (seed, position) rule and written into
      the lanes' token vector at ``lane`` on the device: the decode
      step dispatched next reads it there; the host reads the scalar a
      commit later.

    ``lane`` and ``real`` reach the model only where it keeps per-lane
    state (``lane_state``).  ``per_token``: the model returns a third
    value, its per-position outputs ``{name: [C, ...]}``; where
    logprobs are captured both programs return it last (``prefill``
    then ``(pool, rows)``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def _model(params, pool, chunk, table, start, lane, real):
        extra = (lane, real) if lane_state else ()
        return prefill_model(params, chunk, pool, table, start, *extra)

    keep_rows = per_token and capture_logprobs

    def _prefill(params, pool, *chunk):
        _, pool, *rows = _model(params, pool, *chunk)
        return (pool, rows[0]) if keep_rows else pool

    def _prefill_last(params, pool, tokens, keys, *chunk):
        start, lane, real = chunk[2:]
        logits, pool, *extra = _model(params, pool, *chunk)
        with jax.named_scope("prefill"), jax.named_scope("head"):
            # one row of [1, C, V], as a masked sum over C: it fuses
            # into the product, which then writes that row alone
            rows = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.sum(
                jnp.where(rows == real - 1, logits, 0.0), axis=1
            )
        with jax.named_scope("prefill"), jax.named_scope("sample"):
            tok = sample_rows(
                logits, keys[lane][None], (start + real)[None], temp
            )
            out = (pool, tokens.at[lane].set(tok[0]), tok[0])
            if capture_logprobs:
                out += (logprob_rows(logits, tok)[0],)
            return out + tuple(extra) if keep_rows else out

    return _prefill, _prefill_last


class ContinuousBatchingScheduler:
    """The token-level serving loop over a paged KV cache.

    What a model must provide (``models/llama.py``,
    ``models/falcon_h1.py``, ``models/keye_vl2.py``,
    ``models/trinity.py``, ``models/olmo_hybrid.py``,
    ``models/deepseek_v32.py``, ``models/kimi_linear.py`` and
    ``models/lfm2_moe.py`` do):

    - ``model_cfg``: the paged K/V geometry as attributes
      (``n_layers``, ``n_kv_heads``, ``head_dim``, ``dtype``) and,
      optionally, ``lane_state() -> {leaf: (shape, dtype)}`` — state a
      lane keeps per layer beside its pages (a recurrent state, a
      convolution's tail).  ``rl/kv_cache.paged_cache_config`` reads
      both; the scheduler owns the resulting pool, the state slabs
      indexed by lane, the K/V by :class:`BlockPool`'s tables.  Such a
      model MAY also say which layers keep which (``layer_keeps() ->
      ("pages" | "state" | "both", ...)``, ``models/olmo_hybrid.py``):
      the pool then holds ``k``, ``v`` for the layers that page and
      each slab for the layers that hold state, and nothing here
      changes — the refusals below and the ``(lane, real)`` contract
      are those of any model with lane state.  Also
      optionally ``paged_leaves() -> {leaf: (shape, dtype)}`` — what a
      TOKEN keeps per layer beside its K and V (an index key): a
      further leaf in the same blocks under the same tables, shared by
      prefix, shipped and freed with its block — and
      ``per_token_outputs() -> {name: (shape, dtype)}`` — what the
      step programs return for every row they compute beside the
      logits (a router's experts), as a third value ``{name: [rows,
      *shape]}``; while logprobs are captured it rides into
      ``GenResult.per_token``, and such a model takes no prefix hit
      (a shared block has no rows).  Either refuses a K-step window
      and a draft model at construction.  A model whose tokens keep NO
      per-head keys and values (``models/deepseek_v32.py``: one latent
      row a token serves every head) says ``pages_kv = False``: the
      pool then holds its ``paged_leaves()`` alone, ``n_kv_heads`` /
      ``head_dim`` are not read, the ``serve_step`` record carries
      ``cache_bytes`` (beside an indexer's ``sel_rows``,
      ``cached_rows`` and ``read_rows``), and the ``prefill`` role is
      refused by name
      beside the two above (a ship's regions are a K and a V).  Such a
      model MAY keep lane state too, with ``layer_keeps()`` saying
      which layers keep the slabs and which the latent rows
      (``models/kimi_linear.py``): the refusals of both kinds hold, the
      record carries ``state_layers`` / ``paged_layers`` and a
      ``cache_bytes`` of slabs plus live blocks.  And
      optionally
      ``layer_windows() -> (window | None, ...)``, one entry a layer
      (``models/trinity.py``): a layer WITH a window reads the keys ``t
      - window < s <= t`` only, so its blocks are its own kind — the
      pool then holds ``k``, ``v`` for the layers without one (under
      :class:`BlockPool`'s tables, unchanged) and ``wk``, ``wv`` for the
      others, ``max_slots`` rings of ``W`` blocks and a null block a
      layer, ``W`` sized by ``rl/kv_cache.window_table_blocks`` from the
      window, ``prefill_chunk`` and ``block_size`` and nothing else.
      A lane's SECOND table is that ring: before every chunk and
      every decode step the scheduler brings it up to the step
      (``_lane_tables`` -> ``WindowBlocks.advance``: blocks wholly
      behind the window given back, blocks up to the step's last write
      taken) and uploads it BEHIND the sequence's table, one row
      ``[max_blocks + W]`` a lane; the model's step programs split the
      row at ``ceil(model_cfg.max_seq_len / block_size)``, which must be
      the scheduler's.  Such a model takes no prefix hit (a shared block
      would need the window layers' blocks kept with its refcount) and
      refuses at construction, by name, a K-step window, a draft model
      and the ``prefill`` role (a ship carries the sequence's blocks
      only); preemption and resume re-prefill from token 0.
    - the step programs, the llama ones unless injected:
      ``paged_decode_fn(params, tokens, pool, tables, positions,
      active) -> (logits [S, V], pool)``, which must leave an inactive
      lane's state as it was, and ``paged_prefill_fn(params, chunk,
      pool, table, start) -> (logits [1, C, V], pool)``.  For a model
      WITH lane state the prefill program receives two more scalars,
      ``(..., start, lane, real)``: the lane whose slab it continues
      and how many tokens of the padded chunk are real — a recurrence
      must start from zero at ``start == 0``, carry from chunk to
      chunk of the same lane while other lanes decode in between, and
      stop at the last real token.  Injected programs are given the
      tree ``sync_weights`` was given.  A chunk's logits are read for
      ONE row, the prompt's last token's, so the scheduler jits the
      prefill program twice (:func:`prefill_programs`): a chunk that
      is not its prompt's last runs it with the logits dropped inside
      the program (the compiler removes the head), the last chunk with
      that row cut from them and the first token's sample behind it —
      a model provides nothing more for it.
    - optionally ``serving_params(tree) -> tree`` (``llama``'s unless
      ``serving_params_fn`` is injected): the copy the scheduler keeps
      resident and hands to every step program, made once an adoption
      (both models: by one jitted program, ``llama.serving_copy``;
      leaves that need no work stay the caller's arrays).  It may cast
      leaves to the compute dtype AND re-lay them out — both models
      hold ``wq``, ``wk``, ``wv`` as one leaf ``wqkv``, which a step
      program reads in place where it cut the three out of the layer
      stack and transposed them (``tests/test_tpu_compile.py`` pins the
      compiled programs) — so its leaves need not line up with the
      given tree's.  A model's step programs accept the training tree and
      its serving copy and give the same result on both; nothing but
      the scheduler's resident copy has the serving layout.
    - how a step program walks its layers: the pool it is handed and
      hands back is stacked, ``k``, ``v`` ``[L, num_blocks, block_size,
      KV, D]``, and DONATED.  Inside, the program CARRIES the pool
      through its layer loop, viewed ``[L * num_blocks, ...]``, and
      layer ``l`` adds ``l * num_blocks`` to every block id it writes
      or reads (its null block is block ``l * num_blocks``) —
      ``ops/paged_attention.scan_layers_over_pool`` does all of it.  A
      program NEVER passes a pool to ``lax.scan`` as a scanned input or
      takes it back as a stacked output: XLA then slices, copies and
      re-stacks the whole pool on every step, which was 14 of a 20 ms
      decode step at 1.5 GB (``tests/test_tpu_compile.py`` pins the
      six compiled programs; ``tests/test_pool_in_carry.py`` the
      offsets).

    A state that is no page cannot be reused by prefix, rolled back or
    shipped, so for a model with lane state the scheduler never takes
    a prefix hit and never shares a filled block (decided from the
    declaration alone), and refuses at construction ``decode_k > 1``,
    a draft model and the ``prefill`` role.  Preemption and resume
    re-prefill from token 0 and are sound."""

    def __init__(
        self,
        model_cfg,
        sched: Optional[SchedulerConfig] = None,
        paged_decode_fn: Optional[Callable] = None,
        paged_prefill_fn: Optional[Callable] = None,
        paged_verify_fn: Optional[Callable] = None,
        events=None,
        replica: str = "",
        role: str = "unified",
        capture_logprobs: bool = False,
        draft_cfg=None,
        draft_decode_fn: Optional[Callable] = None,
        draft_prefill_fn: Optional[Callable] = None,
        verify_write_fn: Optional[Callable] = None,
        serving_params_fn: Optional[Callable] = None,
    ):
        import jax
        import jax.numpy as jnp
        from functools import partial

        from dlrover_tpu.models import llama

        self._jax, self._jnp = jax, jnp
        self.cfg = model_cfg
        self.sched = sched or SchedulerConfig()
        s = self.sched
        if s.prefill_chunk < 1 or s.max_slots < 1:
            raise ValueError("prefill_chunk and max_slots must be >= 1")
        self._events = events
        # ``replica`` labels the serve_request spans with where the
        # request actually ran
        self.replica = replica
        self._last_prefill_req = -1
        # the partition of each iteration's host time (ISSUE 24): what
        # the host does around the compiled programs, by leaf phase.
        # ``wait`` is the blocking readback of what the PREVIOUS
        # iteration dispatched, while this iteration's programs are
        # already queued behind it — the device is busy, the host is
        # not the cause; what no phase covers is ``other``.
        self._ph_admit = _HostPhase(
            lambda: EventLogger.leaf("sched.admit")
        )
        self._ph_dispatch = _HostPhase(
            lambda: EventLogger.leaf("sched.dispatch")
        )
        self._ph_wait = _HostPhase(
            lambda: EventLogger.leaf("sched.wait")
        )
        self._ph_commit = _HostPhase(
            lambda: EventLogger.leaf("sched.commit")
        )
        self._lanes_decode = 0
        self._lanes_prefill = 0
        self._lanes_ahead = 0
        self._step_overrun = 0
        self._step_commits = 0
        self._params = None
        # what the step programs want resident (dtype, fused ``wqkv``)
        # is the llama programs' rule (``llama.serving_params``);
        # injected programs get the tree ``sync_weights`` was given,
        # unless their model brings its own rule (``serving_params_fn``)
        self._serving_params = serving_params_fn or (
            (lambda params: params)
            if paged_decode_fn or paged_prefill_fn or paged_verify_fn
            or verify_write_fn
            else partial(llama.serving_params, cfg=model_cfg)
        )
        self._serving_draft_params = (
            (lambda params: params)
            if draft_cfg is None or draft_decode_fn or draft_prefill_fn
            else partial(llama.serving_params, cfg=draft_cfg)
        )
        self._decode_model = paged_decode_fn or partial(
            llama.paged_decode_step, cfg=model_cfg
        )
        self._prefill_model = paged_prefill_fn or partial(
            llama.paged_prefill_chunk, cfg=model_cfg
        )
        self._verify_model = paged_verify_fn or partial(
            llama.paged_verify_step, cfg=model_cfg
        )
        # flywheel extensions, both off unless asked for.
        # ``capture_logprobs``: every sampled token also returns its
        # actor logprob (log-softmax of the RAW fp32 logits — the
        # trainer's ``token_logprobs`` semantics, so streamed tails
        # replace the old_logp recompute forward bit-for-bit).
        # ``draft_cfg``: a separate small DRAFT model runs the K-step
        # draft loop against its OWN pool while the policy verifies
        # (and writes its K/V) in one ``paged_verify_write_step``.
        self.capture_logprobs = bool(capture_logprobs)
        self._draft_cfg = draft_cfg
        self._draft_params = None
        self._draft_decode_model = (
            draft_decode_fn
            or (
                partial(llama.paged_decode_step, cfg=draft_cfg)
                if draft_cfg is not None else None
            )
        )
        self._draft_prefill_model = (
            draft_prefill_fn
            or (
                partial(llama.paged_prefill_chunk, cfg=draft_cfg)
                if draft_cfg is not None else None
            )
        )
        self._verify_write_model = (
            verify_write_fn
            or partial(llama.paged_verify_write_step, cfg=model_cfg)
        )

        # tuning values, read once at construction
        self.grow_blocks = kv_grow_blocks()
        self.admit_watermark = kv_admit_watermark()
        self.decode_k = decode_steps()
        # ``role``: "unified" (default) serves prefill+decode in place;
        # "prefill" stops at prefill completion and parks the filled
        # block regions + first token on ``self.shipped`` for the
        # worker loop to ship out.
        if role not in ("unified", "prefill"):
            raise ValueError(f"unknown scheduler role {role!r}")
        self.role = role
        self.interactive_slots = min(
            fleet_interactive_slots(), s.max_slots - 1
        )
        self.shipped: List[Dict] = []
        self.shipped_out = 0
        self.shipped_in = 0
        # separate-drafter speculative decode needs a K>1 window and a
        # lane that both prefills and decodes locally (a prefill-role
        # worker never drafts; shipped adoptions degrade draft quality
        # for that prompt, never correctness — emission is always the
        # policy's verify stream in draft mode)
        self.draft = (
            draft_cfg is not None
            and self.decode_k > 1
            and self.role == "unified"
        )
        # results of adoptions that finished on their first token when
        # no finished-list was threaded in (drained by step())
        self._adopt_finished: List[GenResult] = []

        cache_cfg = paged_cache_config(
            model_cfg, s.num_blocks, s.block_size, s.max_slots,
            s.prefill_chunk,
        )
        self.pool_cfg = cache_cfg
        # per-lane state beside the pages: no positions, no sharing, no
        # rollback, nothing to ship — what cannot be sound is refused
        # here, by name, and prefix reuse is off (``prefix_hits`` stays
        # 0, ``prefix_hits_skipped`` counts the admissions that would
        # have looked a prefix up)
        self.lane_state = bool(cache_cfg.lane_state)
        if self.lane_state:
            leaves = ", ".join(name for name, _, _ in cache_cfg.lane_state)
            for refused, why in (
                (self.decode_k > 1,
                 "multi-token decode (DLROVER_TPU_DECODE_STEPS > 1): a "
                 "rejected draft would have to roll the state back"),
                (draft_cfg is not None,
                 "a draft model: its verify step cannot roll the "
                 "policy's state back"),
                (role == "prefill",
                 "the prefill role: a shipped prefill carries K/V "
                 "blocks only, the state at the end of the prompt "
                 "would be lost"),
            ):
                if refused:
                    raise ValueError(
                        f"the model keeps per-lane state ({leaves}) "
                        f"beside its paged K/V and cannot be served "
                        f"with {why}"
                    )
        # what the model pages beside K and V (an index key a token)
        # rides in the same blocks: shared by prefix, shipped, freed
        # with them.  What its programs return a POSITION (a router's
        # experts) exists only for positions computed here: while
        # logprobs are captured a prefix hit is not taken — its
        # positions would have no rows — and counted like the lane
        # state's.  The K-step window and a draft model are refused by
        # name: neither ``paged_verify_step`` nor the draft loop has a
        # selection over index keys, and the window's rollback would
        # have to drop rows
        per_token = getattr(model_cfg, "per_token_outputs", None)
        self.per_token: Dict = dict(
            per_token() if per_token and self.capture_logprobs else {}
        )
        if cache_cfg.paged_leaves or per_token:
            leaves = ", ".join(cache_cfg.leaf_names) or "none"
            for refused, why in (
                (self.decode_k > 1,
                 "multi-token decode (DLROVER_TPU_DECODE_STEPS > 1): "
                 "the window's verify program reads K and V only"),
                (draft_cfg is not None,
                 "a draft model: its verify-and-write step reads and "
                 "writes K and V only"),
                # a model whose tokens keep no K / V at all: the ship
                # arena's slots are laid out as a K and a V region
                (role == "prefill" and not cache_cfg.pages_kv,
                 "the prefill role: a shipped prefill carries K and V "
                 "regions, and this model pages neither"),
            ):
                if refused:
                    raise ValueError(
                        f"the model pages more than K and V ({leaves}) "
                        f"or returns per-position outputs and cannot be "
                        f"served with {why}"
                    )
        # layers with a window keep their blocks under a second table a
        # lane (``WindowBlocks``): a block there is given back once the
        # window has passed it, so it can be neither shared by prefix
        # (a shared block would need the window layers' blocks kept
        # with its refcount) nor shipped, and a K-step window or a draft
        # model would read or write through the sequence's table alone
        self.window = cache_cfg.window
        if self.window is not None:
            for refused, why in (
                (self.decode_k > 1,
                 "multi-token decode (DLROVER_TPU_DECODE_STEPS > 1): the "
                 "window's verify program reads one table a lane"),
                (draft_cfg is not None,
                 "a draft model: its pool mirrors one table a lane"),
                (role == "prefill",
                 "the prefill role: a shipped prefill carries the "
                 "sequence's blocks, not the window layers'"),
                (getattr(model_cfg, "max_seq_len", s.max_seq_len)
                 != s.max_seq_len,
                 f"max_seq_len {s.max_seq_len}: the model's step "
                 f"programs split a lane's two tables at its own "
                 f"({getattr(model_cfg, 'max_seq_len', None)})"),
            ):
                if refused:
                    raise ValueError(
                        f"the model's layers with a window "
                        f"({self.window}) keep their blocks under a "
                        f"second table a lane and cannot be served with "
                        f"{why}"
                    )
        self.prefix_cache = (
            not self.lane_state and not self.per_token
            and self.window is None
        )
        self.prefix_hits_skipped = 0
        self.state_resets = 0
        self._step_state_resets = 0
        self.block_pool = BlockPool(cache_cfg)
        self._pool = init_block_pool(cache_cfg)
        self.state_bytes = lane_state_nbytes(self._pool, cache_cfg)
        # bytes one block id names over the layers that page: K and V
        # (a model with lane state pages nothing else), or the paged
        # leaves of a model that keeps neither
        self._block_bytes = block_nbytes(self._pool, cache_cfg.paged_names)
        # the draft pool mirrors the policy pool's GEOMETRY (same
        # block ids, tables, block size) with the DRAFT model's shapes
        # — one host-side allocator drives both
        self._draft_pool = None
        if self.draft:
            self._draft_pool = init_block_pool(
                paged_cache_config(
                    draft_cfg, s.num_blocks, s.block_size, s.max_slots
                )
            )

        # host mirrors of the fixed-shape device inputs
        S, MB = s.max_slots, s.max_blocks_per_seq
        self._tables = np.zeros((S, MB), np.int32)
        # each lane's ring over the window layers' blocks, uploaded
        # behind its table (no column for a model without windows)
        self._wtables = np.zeros(
            (S, cache_cfg.window_table_blocks), np.int32
        )
        self._positions = np.zeros((S,), np.int32)
        self._active = np.zeros((S,), bool)
        # the lanes' current tokens: ON THE DEVICE for the plain decode
        # loop (each step's output is the next step's input; prefill
        # and adoption write a lane's first token into it), on the host
        # for the multi-token window, which reads tokens back before
        # it knows the next positions anyway
        self._tokens_dev = jnp.zeros((S,), jnp.int32)
        self._next_token = np.zeros((S,), np.int32)
        # each lane's request key, ON THE DEVICE: made there at
        # admission (``_set_key``) and never read back — reading it
        # would wait for every program queued before it
        self._keys = jnp.zeros((S, 2), jnp.uint32)
        # dispatches whose samples the host has not read, oldest first
        self._inflight: List[_InFlight] = []
        # prefill chunks whose per-position rows are on their way to
        # the host, oldest first: ``(iteration, slot, its _Slot, first
        # position, rows, {name: device array})``
        self._chunk_rows: List = []
        # why this scheduler may never dispatch ahead of its commits
        # (None: it may): the K-step window's positions depend on how
        # many drafts were accepted.  (A prefill worker never decodes
        # and reads its one sample at once: ``_prefill_one``.)
        self._sync_cause: Optional[str] = (
            "multi_token" if self.decode_k > 1 else None
        )
        self._slots = [_Slot() for _ in range(S)]
        self._queue: List[GenRequest] = []
        # queued interactive requests, maintained at every queue
        # mutation: admission is per-step hot-loop work and a
        # saturated queue runs hundreds deep, so the common case
        # ("is anything interactive waiting?") must not scan it
        self._queued_interactive = 0
        # full-prompt block keys memoized per req_id: _admit probes
        # the blocked queue head every iteration, and SHA-1-hashing a
        # long system prompt 3x per step is hot-loop host work
        # (dropped at finish; preemption re-admits the same req_id)
        self._prompt_keys: Dict[int, List[str]] = {}
        self._next_req_id = 0
        self._prefill_rr = 0  # round-robin pointer over prefill slots
        self._admit_counter = 0
        self.draining = False

        # counters the serving gauges/bench read
        self.total_new_tokens = 0
        self.total_prefill_tokens = 0
        self.prefill_chunks = 0
        self.prefill_heads = 0
        self._step_prefill_heads = 0
        self.iterations = 0
        self.preemptions = 0
        self.grown_blocks = 0
        self.dispatches = 0  # jitted-program invocations (host cost)
        self.accepted_tokens = 0  # multi-token decode: tokens kept
        self.lane_windows = 0  # multi-token decode: (lane, window)s
        self._window_hit_blocks = 0  # prefix hits since last emit
        # the run-ahead loop's census: decode steps dispatched before
        # their lanes' previous tokens were read, decode steps that
        # first had to commit (by cause), lane-steps computed past an
        # EOS and discarded
        self.ahead_steps = 0
        self.sync_steps: Dict[str, int] = {}
        self.overrun_tokens = 0
        # a model with an indexer / a router: what a decode step's
        # lanes selected and how its rows fell on the experts (the
        # ``serve_step`` labels ``sel_rows``, ``cached_rows``,
        # ``read_rows``, ``index_bytes``,
        # ``experts_hit``, ``expert_rows_max``, ``expert_rows_mean``),
        # and their sums over the run
        self._index_row_bytes = sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for _, shape, dtype in cache_cfg.paged_leaves
        ) * cache_cfg.n_layers
        self._has_indexer = bool(
            getattr(model_cfg, "topk", None) and cache_cfg.paged_leaves
        )
        self._step_sel_rows = self._step_index_bytes = 0
        self._step_cached_rows = self._step_read_rows = 0
        self._step_experts: Dict = {}
        # a model with windows: the rows a decode step's kernels had to
        # read, by kind of layer (``serve_step`` labels
        # ``kv_rows_window`` / ``kv_rows_full``); every model: a chunk's
        # shape (``prefill`` span labels ``rows`` / ``kv_len``)
        self._step_kv_rows = [0, 0]
        # and a model whose layers divide between pages of K / V and
        # lane state (``layer_keeps()``): ``kv_rows_full`` alone, every
        # cached position of the lanes that decoded over the layers
        # that page
        self._counts_full_rows = bool(
            self.window is None and cache_cfg.pages_kv
            and cache_cfg.layer_keeps
        )
        self._step_chunk: Dict = {}
        self._window_counts = (0, 0)
        self.sel_rows = self.index_bytes = 0
        self.expert_totals = dict(
            steps=0, experts_hit=0.0, expert_rows_max=0,
            expert_rows_mean=0.0,
        )

        temp = float(s.temperature)

        _lp_rows = logprob_rows

        @jax.named_scope("verify")
        @jax.named_scope("sample")
        def _sample_grid(logits, keys, sample_pos):
            """logits [S, K, V]; sample_pos [S, K] — the K-window
            version of ``sample_rows`` (same contract per cell)."""
            if temp <= 0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            folded = jax.vmap(
                lambda k, ps: jax.vmap(
                    lambda p: jax.random.fold_in(k, p)
                )(ps)
            )(keys, sample_pos)
            return jax.vmap(
                jax.vmap(
                    lambda k, l: jax.random.categorical(k, l / temp)
                )
            )(folded, logits).astype(jnp.int32)

        K = self.decode_k

        def _decode_multi(params, pool, tokens, tables, positions,
                          active, keys):
            """K fused decode steps: greedy self-drafting (each draft
            step IS the K=1 computation, so at temp 0 drafts are the
            reference stream) + ONE batched verify forward whose
            real-rule samples gate acceptance.  Returns (pool, drafts
            [S, K], verify samples [S, K], leading-match count [S])."""
            drafts = []
            tok, pos = tokens, positions
            for _ in range(K):
                logits, pool = self._decode_model(
                    params, tok, pool, tables, pos, active
                )
                with jax.named_scope("decode"), jax.named_scope("sample"):
                    d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                drafts.append(d)
                tok, pos = d, pos + 1
            drafts = jnp.stack(drafts, axis=1)  # [S, K]
            # verify inputs: the window tokens actually occupying
            # positions p..p+K-1 (current token + first K-1 drafts) —
            # their K/V is already in the pool from the draft loop
            vin = jnp.concatenate(
                [tokens[:, None], drafts[:, :-1]], axis=1
            )
            vlogits = self._verify_model(
                params, vin, pool, tables, positions, active
            )  # [S, K, V]
            steps = jnp.arange(K, dtype=positions.dtype)
            ver = _sample_grid(
                vlogits, keys, positions[:, None] + 1 + steps[None]
            )
            eq = (ver == drafts).astype(jnp.int32)
            n_match = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)
            return pool, drafts, ver, n_match

        CAP = self.capture_logprobs

        def _decode_multi_lp(params, pool, tokens, tables, positions,
                             active, keys):
            """``_decode_multi`` + per-token logprobs: lp of each
            draft under its draft-step logits (the temp<=0 emission)
            and of each verify sample under the verify logits (the
            temp>0 emission)."""
            drafts, lps = [], []
            tok, pos = tokens, positions
            for _ in range(K):
                logits, pool = self._decode_model(
                    params, tok, pool, tables, pos, active
                )
                with jax.named_scope("decode"), jax.named_scope("sample"):
                    d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    lps.append(_lp_rows(logits, d))
                drafts.append(d)
                tok, pos = d, pos + 1
            drafts = jnp.stack(drafts, axis=1)  # [S, K]
            lp_drafts = jnp.stack(lps, axis=1)  # [S, K]
            vin = jnp.concatenate(
                [tokens[:, None], drafts[:, :-1]], axis=1
            )
            vlogits = self._verify_model(
                params, vin, pool, tables, positions, active
            )
            steps = jnp.arange(K, dtype=positions.dtype)
            ver = _sample_grid(
                vlogits, keys, positions[:, None] + 1 + steps[None]
            )
            with jax.named_scope("verify"), jax.named_scope("sample"):
                lp_ver = _lp_rows(vlogits, ver)
            eq = (ver == drafts).astype(jnp.int32)
            n_match = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)
            return pool, drafts, ver, n_match, lp_drafts, lp_ver

        def _decode_multi_draft(params, draft_params, pool, dpool,
                                tokens, tables, positions, active,
                                keys):
            """Separate-drafter window: the DRAFT model runs the
            K-step greedy draft loop against its OWN pool; the policy
            scores the window with ONE ``paged_verify_write_step``
            that also writes the policy K/V the drafter no longer
            produces.  Emission is ALWAYS the verify stream (``ver``
            is the policy's true conditioned sample at every
            temperature — the drafts only gate how far the window is
            trusted)."""
            drafts = []
            tok, pos = tokens, positions
            for _ in range(K):
                dlogits, dpool = self._draft_decode_model(
                    draft_params, tok, dpool, tables, pos, active
                )
                with jax.named_scope("decode"), jax.named_scope("sample"):
                    d = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                drafts.append(d)
                tok, pos = d, pos + 1
            drafts = jnp.stack(drafts, axis=1)  # [S, K]
            vin = jnp.concatenate(
                [tokens[:, None], drafts[:, :-1]], axis=1
            )
            vlogits, pool = self._verify_write_model(
                params, vin, pool, tables, positions, active
            )
            steps = jnp.arange(K, dtype=positions.dtype)
            ver = _sample_grid(
                vlogits, keys, positions[:, None] + 1 + steps[None]
            )
            with jax.named_scope("verify"), jax.named_scope("sample"):
                lp_ver = (
                    _lp_rows(vlogits, ver) if CAP
                    else jnp.zeros(ver.shape, jnp.float32)
                )
            eq = (ver == drafts).astype(jnp.int32)
            n_match = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)
            return pool, dpool, drafts, ver, n_match, lp_ver

        def _set_token(tokens, lane, tok):
            # an adopted prefill's first token arrives as a host value
            return tokens.at[lane].set(tok)

        def _set_key(keys, lane, seed):
            key = jax.random.key_data(jax.random.PRNGKey(seed))
            return keys.at[lane].set(key.reshape(-1)[:2])

        # the token vector (argument 2) is NOT donated: the host reads
        # step n's output after step n+1 has taken it as its input
        self._decode_jit = jax.jit(
            decode_program(
                self._decode_model, temp, CAP,
                s.max_blocks_per_seq + cache_cfg.window_table_blocks,
                per_token is not None,
            ),
            donate_argnums=(1,),
        )
        self._decode_multi_jit = (
            jax.jit(
                _decode_multi_lp if CAP else _decode_multi,
                donate_argnums=(1,),
            )
            if K > 1 else None
        )
        self._decode_multi_draft_jit = (
            jax.jit(_decode_multi_draft, donate_argnums=(2, 3))
            if self.draft else None
        )
        # a prompt's chunks: every chunk but the last runs a program
        # without a head, the last one a program with the head's one
        # row and the first token's sample (``prefill_programs``); the
        # draft mirror fills its pool and reads no logits at all
        self._draft_prefill_jit = (
            jax.jit(
                prefill_programs(
                    self._draft_prefill_model, temp, CAP, False
                )[0],
                donate_argnums=(1,),
            )
            if self.draft else None
        )
        prefill, prefill_last = prefill_programs(
            self._prefill_model, temp, CAP, self.lane_state,
            per_token is not None,
        )
        self._prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        self._prefill_last_jit = jax.jit(prefill_last, donate_argnums=(1,))
        self._set_token_jit = jax.jit(_set_token)
        self._set_key_jit = jax.jit(_set_key)

    # ------------------------------------------------------------- API
    def sync_weights(self, params, draft_params=None, generation=None):
        """Adopt the trainer's / publisher's current params (in-flight
        sequences continue on the new weights — the vLLM-backend
        weight-refresh semantics).  ``draft_params`` is the
        co-published DRAFT model (flywheel separate-drafter mode);
        until the first draft publish arrives the scheduler falls back
        to self-drafting.

        What is held where: the caller keeps ITS tree (the replica's
        float32 restore target, the trainer's live state); the
        scheduler keeps only the model's ``serving_params`` of it — a
        resident copy in the model's compute dtype and serving layout
        (``wq``, ``wk``, ``wv`` fused into ``wqkv``), made here once
        by the model's one jitted copy program (``llama.serving_copy``),
        so that no step program casts weights or cuts and transposes a
        projection again.  A leaf that already has that dtype and
        layout is the caller's array (a tree that is already a serving
        copy is a reference swap), and a scheduler built on injected
        step programs serves the tree it was given: the rule belongs
        to the model's programs.  The previous copy is dropped
        BEFORE the new one is made, so an adoption never holds two.
        One ``weight_cast`` span per call says what happened
        (``generation``: the published generation, where the caller
        adopts one).  A step in flight is committed first (its program
        holds the old copy alive); what finishes by that comes out of
        the next ``step()``."""
        self._commit_first("sync_weights", self._adopt_finished)
        t0 = time.monotonic()
        self._params = None
        self._params = self._serving_params(params)
        given, served = [params], [self._params]
        if draft_params is not None:
            self._draft_params = None
            self._draft_params = self._serving_draft_params(
                draft_params
            )
            given.append(draft_params)
            served.append(self._draft_params)
        # the casts are dispatched, not done: an adoption is rare, and
        # the span should hold the copy it reports
        self._jax.block_until_ready(served)
        if self._events is not None and self._events.enabled:
            from dlrover_tpu.observability.events import anchored_now

            flatten = self._jax.tree_util.tree_flatten_with_path
            paths_in, leaves_in = zip(*flatten(given)[0])
            paths_out, leaves_out = zip(*flatten(served)[0])
            # a serving copy may re-lay leaves out (``wqkv``), so the
            # two lists do not line up: a served leaf was copied where
            # it is none of the given arrays, and a given leaf was
            # fused where the served tree has none under its name
            given_ids = {id(x) for x in leaves_in}
            self._events.complete(
                "weight_cast",
                anchored_now(t0),
                max(time.monotonic() - t0, 1e-9),
                bytes_in=sum(int(x.nbytes) for x in leaves_in),
                bytes_out=sum(int(x.nbytes) for x in leaves_out),
                leaves_cast=sum(
                    id(x) not in given_ids for x in leaves_out
                ),
                leaves_fused=len(set(paths_in) - set(paths_out)),
                **(
                    {} if generation is None
                    else {"generation": int(generation)}
                ),
            )

    def submit(
        self,
        prompt,
        max_new: Optional[int] = None,
        seed: int = 0,
        req_id: Optional[int] = None,
        submit_wall: Optional[float] = None,
        slo_class: str = SLO_BATCH,
        tenant: str = "",
        shipped: Optional[Dict] = None,
        route: str = "local",
        resume_tokens: Optional[np.ndarray] = None,
        resume_logprobs: Optional[np.ndarray] = None,
    ) -> int:
        """Queue one prompt; returns the request id results carry.

        ``submit_wall`` is the submitter's wall-clock anchor (epoch
        seconds) when the request crossed a process boundary — the
        dispatcher stamps it onto the shm ring so the ``queue_wait``
        and ``serve_request`` spans start at the TRUE submit time,
        ring transit included.  ``slo_class``/``tenant`` steer the
        fleet admission lanes (any class other than "interactive"
        normalizes to "batch"); ``shipped`` carries a disaggregated
        prefill's KV block regions (``{"k", "v", "first_token"}``) —
        the request then admits straight into the decode phase.

        ``resume_tokens`` re-admits a partially-generated sequence
        that crossed a PROCESS boundary (a drained / killed replica's
        hand-back): the scheduler re-prefills prompt+tail, reusing
        the prompt's cached prefix blocks via ``peek_prefix``, and
        resumes sampling at the next position — (seed, position)-
        purity makes the continuation identical to the uninterrupted
        run instead of regenerating the tail from scratch.
        ``resume_logprobs`` optionally carries the tail's captured
        logprobs alongside."""
        if self.draining:
            raise RuntimeError(
                "scheduler is draining: submissions belong on "
                "another replica (the dispatcher requeues them)"
            )
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            # position-0 sampling would condition on pool garbage —
            # there is no (seed, position)-pure answer for it
            raise ValueError("prompt must hold at least one token")
        max_new = int(
            self.sched.max_new_default if max_new is None else max_new
        )
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if prompt.size + max_new > self.sched.max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"max_seq_len {self.sched.max_seq_len}"
            )
        if not pool_can_ever_hold(
            self.pool_cfg.num_blocks,
            self.pool_cfg.block_size,
            prompt.size + max_new,
        ):
            # a lone sequence must be able to run to its budget after
            # preempting everyone else; a worst case bigger than the
            # whole pool can't
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} needs "
                f"{self.pool_cfg.blocks_for(prompt.size + max_new)} "
                f"blocks > pool of {self.pool_cfg.usable_blocks}"
            )
        if req_id is None:
            req_id = self._next_req_id
        self._next_req_id = max(self._next_req_id, req_id) + 1
        if slo_class != SLO_INTERACTIVE:
            slo_class = SLO_BATCH
        resume = (
            np.asarray(resume_tokens, np.int32).reshape(-1)
            if resume_tokens is not None else _empty_tokens()
        )
        if resume.size >= max_new:
            raise ValueError(
                f"resume tail of {resume.size} token(s) already "
                f"meets max_new {max_new} — nothing left to generate"
            )
        if resume.size:
            rlp = (
                np.asarray(resume_logprobs, np.float32).reshape(-1)
                if resume_logprobs is not None else _empty_logprobs()
            )
            # a tail whose logprobs did not survive the boundary is
            # NaN-padded so consumers can tell "unknown" from 0.0
            if rlp.size < resume.size:
                rlp = np.concatenate(
                    [rlp,
                     np.full(resume.size - rlp.size, np.nan,
                             np.float32)]
                )
            rlp = rlp[: resume.size]
        else:
            rlp = _empty_logprobs()
        self._queue.append(
            GenRequest(req_id=req_id, prompt=prompt, max_new=max_new,
                       seed=int(seed),
                       submit_wall=float(submit_wall or 0.0),
                       resume_tokens=resume, resume_logprobs=rlp,
                       slo_class=slo_class, tenant=str(tenant),
                       # a shipped prefill predates the tail — resumes
                       # re-prefill deterministically instead
                       shipped=(
                           shipped
                           if not resume.size
                           # K/V blocks alone cannot seed a lane that
                           # also keeps a state: prefill here instead
                           and not self.lane_state else None
                       ),
                       route=str(route))
        )
        if slo_class == SLO_INTERACTIVE:
            self._queued_interactive += 1
        return req_id

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def active_count(self) -> int:
        return sum(1 for sl in self._slots if sl.req is not None)

    @property
    def idle(self) -> bool:
        """Nothing queued, nothing in a lane, and nothing dispatched
        or finished that a ``step()`` has yet to hand out."""
        return not (
            self._queue or self.active_count or self._inflight
            or self._adopt_finished
        )

    def settle(self, cause: str = "settle") -> List[GenResult]:
        """Commit every dispatch still in flight and return what has
        finished outside a ``step()``: for a caller that must see the
        lanes' ``generated`` whole, or will not call ``step()`` again
        (the replica's loop before it drains)."""
        self._commit_first(cause, self._adopt_finished)
        out, self._adopt_finished = self._adopt_finished, []
        return out

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program census: decode must stay at 1 across any
        admission/eviction/growth/preemption traffic (asserted by
        tier-1).  ``decode`` reports the ACTIVE decode program — the
        fused multi-token one when ``DLROVER_TPU_DECODE_STEPS>1``.
        ``prefill`` is the chunk program without a head (0 while every
        prompt has fit one chunk) and ``sample`` the last-chunk
        program: the head's one row and the first token's sample."""

        def n(f):
            return int(f._cache_size())

        if (
            self._decode_multi_draft_jit is not None
            and self._draft_params is not None
        ):
            active_decode = self._decode_multi_draft_jit
        elif self._decode_multi_jit is not None:
            active_decode = self._decode_multi_jit
        else:
            active_decode = self._decode_jit
        return {
            "decode": n(active_decode),
            "prefill": n(self._prefill_jit),
            "sample": n(self._prefill_last_jit),
        }

    def pool_report(self) -> Dict:
        """The device-side pool as it was made: ``pool`` — a JSON
        object ``{leaf: shape}`` — and ``pool_bytes``, for the replica's
        ``device_report``."""
        import json

        return {
            "pool": json.dumps(
                {name: list(a.shape) for name, a in self._pool.items()}
            ),
            "pool_bytes": int(sum(a.nbytes for a in self._pool.values())),
        }

    def stats(self) -> Dict:
        from dlrover_tpu.ops.paged_attention import paged_kernel_backend

        st = dict(self.block_pool.stats())
        st.update(
            kernel_backend=paged_kernel_backend(),
            queue_depth=self.queue_depth,
            active=self.active_count,
            iterations=self.iterations,
            total_new_tokens=self.total_new_tokens,
            total_prefill_tokens=self.total_prefill_tokens,
            # chunks dispatched, and how many of them ran the head (a
            # prompt's last: one a prompt prefilled, re-prefills too)
            prefill_chunks=self.prefill_chunks,
            prefill_heads=self.prefill_heads,
            preemptions=self.preemptions,
            grown_blocks=self.grown_blocks,
            dispatches=self.dispatches,
            decode_steps=self.decode_k,
            accepted_tokens=self.accepted_tokens,
            lane_windows=self.lane_windows,
            accepted_per_step=round(
                self.accepted_tokens / max(self.lane_windows, 1), 4
            ),
            draft_active=int(
                self.draft and self._draft_params is not None
            ),
            # per-lane state beside the pages (0 / 0 / 0 for a model
            # of keys and values only)
            state_bytes=self.state_bytes,
            state_resets=self.state_resets,
            prefix_hits_skipped=self.prefix_hits_skipped,
            # the run-ahead loop (``step``): how often it engaged
            ahead_steps=self.ahead_steps,
            sync_steps=dict(self.sync_steps),
            overrun_tokens=self.overrun_tokens,
        )
        if self._has_indexer:
            st.update(sel_rows=self.sel_rows, index_bytes=self.index_bytes)
        if self.expert_totals["steps"]:
            st.update(self.expert_totals)
        return st

    # ------------------------------------------------------ scheduling
    def _full_prompt_keys(self, req: GenRequest) -> List[str]:
        """Content keys for every FULL block of the request's
        original prompt (computed once per req_id; prompts are
        immutable, resume tails never register)."""
        keys = self._prompt_keys.get(req.req_id)
        if keys is None:
            bs = self.sched.block_size
            keys = prefix_block_keys(
                req.prompt[: (int(req.prompt.size) // bs) * bs], bs
            )
            self._prompt_keys[req.req_id] = keys
        return keys

    def _admissible(self, req: GenRequest):
        """Decide admission and size the initial allocation.  Returns
        ``None`` (keep queued — FIFO head-of-line) or a dict the
        admission path consumes."""
        cfgp = self.pool_cfg
        bs = cfgp.block_size
        prefill_tokens = (
            np.concatenate([req.prompt, req.resume_tokens])
            if req.resume_tokens.size else req.prompt
        )
        plen = int(prefill_tokens.size)
        total = int(req.prompt.size) + int(req.max_new)
        keys: List[str] = []
        peek = peek_lru = 0
        if self.prefix_cache and req.shipped is None:
            # only blocks fully inside the ORIGINAL prompt are ever
            # registered, and at least one token must remain to
            # prefill (its logits seed the first sampled token)
            max_hit = min(
                (plen - 1) // bs, int(req.prompt.size) // bs
            )
            if max_hit > 0:
                keys = self._full_prompt_keys(req)[:max_hit]
                peek, peek_lru = self.block_pool.peek_prefix(keys)
        headroom = min(
            self.grow_blocks,
            max(cfgp.blocks_for(total) - cfgp.blocks_for(plen), 0),
        )
        if self.role == "prefill":
            # a prefill worker never decodes: no growth headroom, so
            # more concurrent prefills pack into the same pool
            headroom = 0
        need = cfgp.blocks_for(plen) - peek + headroom
        watermark_blocks = int(
            np.ceil(self.admit_watermark * cfgp.usable_blocks)
        )
        # hits parked in the LRU are consumed BY the acquire — they
        # must not double-count as evictable capacity
        avail = self.block_pool.available_blocks - peek_lru
        if self.block_pool.live_sequences > 0 and (
            avail - need < watermark_blocks
        ):
            return None  # watermark: keep headroom for running lanes
        if avail < need:
            return None
        return {
            "prefill_tokens": prefill_tokens,
            "n_tokens": plen,
            "extra": headroom,
            "keys": keys,
            "peek_hits": peek,
        }

    def _pick_next_index(self) -> Optional[int]:
        """Which queued request admits next (SLO-class lanes):
        interactive before batch; while interactive work is in flight,
        batch admission is capped so ``interactive_slots`` decode
        slots stay reserved for the interactive lane (an idle
        interactive lane does NOT strand slots — batch fills every
        slot until the next interactive arrival, which admission then
        favors and which class-aware preemption can make room for);
        within a class the tenant with the fewest active slots wins
        (weighted fair share), FIFO breaking tenant ties."""
        if not self._queue:
            return None
        active_cls: Dict[str, int] = {}
        active_tenant: Dict = {}
        for sl in self._slots:
            if sl.req is None:
                continue
            c = sl.req.slo_class
            active_cls[c] = active_cls.get(c, 0) + 1
            k = (c, sl.req.tenant)
            active_tenant[k] = active_tenant.get(k, 0) + 1
        if self._queued_interactive > 0:
            # interactive first — the O(queue) scan only runs while
            # an interactive request is actually waiting (the counter
            # keeps the saturated-queue common case scan-free)
            idxs = [
                i for i, r in enumerate(self._queue)
                if r.slo_class == SLO_INTERACTIVE
            ]
            return min(
                idxs,
                key=lambda i: (
                    active_tenant.get(
                        (SLO_INTERACTIVE, self._queue[i].tenant), 0
                    ),
                    i,
                ),
            )
        # batch only from here: while interactive work is in flight,
        # keep ``interactive_slots`` decode slots reserved for it
        if (
            active_cls.get(SLO_INTERACTIVE, 0) > 0
            and active_cls.get(SLO_BATCH, 0)
            >= self.sched.max_slots - self.interactive_slots
        ):
            return None
        # everything queued is batch; arbitrate tenant fair share
        # over a bounded FIFO window so a hundreds-deep saturated
        # queue costs O(window), not O(queue), per admission
        window = min(len(self._queue), 32)
        return min(
            range(window),
            key=lambda i: (
                active_tenant.get(
                    (SLO_BATCH, self._queue[i].tenant), 0
                ),
                i,
            ),
        )

    def _admit(self, finished: Optional[List[GenResult]] = None):
        s = self.sched
        while self._queue and not self.draining:
            free = [
                i for i, sl in enumerate(self._slots)
                if sl.req is None
            ]
            if not free:
                return
            qi = self._pick_next_index()
            if qi is None:
                return  # lane caps leave nothing admissible
            req = self._queue[qi]
            plan = self._admissible(req)
            if plan is None:
                # the pool-blocked pick: later (smaller) requests
                # must not starve it forever
                return
            admit_t0 = time.monotonic()
            self._queue.pop(qi)
            if req.slo_class == SLO_INTERACTIVE:
                self._queued_interactive -= 1
            slot = free[0]
            if req.shipped is not None:
                self._adopt(slot, req, plan, admit_t0, finished)
                continue
            hit_ids = (
                self.block_pool.acquire_prefix(plan["keys"])
                if plan["keys"] else []
            )
            if not self.prefix_cache:
                # the index was never asked: a hit would skip tokens
                # whose state (or per-position rows) exists nowhere
                self.prefix_hits_skipped += 1
            self.block_pool.allocate(
                req.req_id,
                plan["n_tokens"],
                extra_blocks=plan["extra"],
                prefix_blocks=hit_ids,
            )
            row = self.block_pool.table_row(
                req.req_id, s.max_blocks_per_seq
            )
            self._tables[slot] = row
            self._positions[slot] = 0
            self._active[slot] = False  # decoding starts post-prefill
            self._set_key(slot, req.seed)
            n_hit = len(hit_ids)
            self._admit_counter += 1
            sl = _Slot(
                req=req,
                phase="prefill",
                prefill_tokens=plan["prefill_tokens"],
                prefill_len=int(plan["prefill_tokens"].size),
                prefix_keys=(
                    self._full_prompt_keys(req)
                    if self.prefix_cache else []
                ),
                shared_upto=n_hit,
                admit_seq=self._admit_counter,
            )
            # cached prefix blocks are already filled: prefill starts
            # past them
            sl.prefill_pos = n_hit * s.block_size
            self._lane_rows(sl, sl.prefill_pos)
            sl.generated = [int(t) for t in req.resume_tokens]
            if self.capture_logprobs and sl.generated:
                rlp = req.resume_logprobs
                sl.logprobs = [
                    float(rlp[i]) if i < rlp.size else float("nan")
                    for i in range(len(sl.generated))
                ]
            self._slots[slot] = sl
            self.block_pool.note_filled(req.req_id, sl.prefill_pos)
            self._window_hit_blocks += n_hit
            req.hit_blocks += n_hit
            self._trace_admit(req, admit_t0)

    def _set_key(self, slot: int, seed: int):
        # ``np.int64``: what ``PRNGKey`` makes of a Python int, so a
        # seed past 32 bits wraps as it does there
        self._keys = self._set_key_jit(
            self._keys, np.int32(slot), np.int64(seed)
        )

    def _adopt(self, slot: int, req: GenRequest, plan: Dict,
               admit_t0: float,
               finished: Optional[List[GenResult]]):
        """Admit a disaggregated prefill straight into DECODE: splice
        the shipped block regions into freshly allocated pool blocks,
        point the slot's table at them, and run a pure token loop from
        the first token the prefill worker already sampled.  The
        shipped tiles are bitwise the prefill worker's pool content,
        so decode over them equals decode over a local prefill (pinned
        by test); a later preemption drops nothing — the payload is
        consumed here and resume re-prefills deterministically."""
        s = self.sched
        payload, req.shipped = req.shipped, None
        plen = int(req.prompt.size)
        n_ship = self.pool_cfg.blocks_for(plen)
        self.block_pool.allocate(
            req.req_id, plan["n_tokens"], extra_blocks=plan["extra"]
        )
        ids = self.block_pool.blocks_of(req.req_id)[:n_ship]
        names = self.pool_cfg.paged_names
        self._pool = insert_block_regions(
            self._pool, ids, *(payload[name] for name in names),
            leaves=names,
        )
        self._tables[slot] = self.block_pool.table_row(
            req.req_id, s.max_blocks_per_seq
        )
        self._positions[slot] = plen
        self._active[slot] = True
        self._set_key(slot, req.seed)
        self._admit_counter += 1
        sl = _Slot(req=req, phase="decode", prefill_len=plen,
                   admit_seq=self._admit_counter)
        self._lane_rows(sl, plen)
        self._slots[slot] = sl
        self.block_pool.note_filled(req.req_id, plen)
        self.shipped_in += 1
        if self.prefix_cache:
            # shipped FULL prompt blocks are immutable content — index
            # them so later local prompts with the same prefix share
            keys = self._full_prompt_keys(req)
            for idx in range(min(len(keys), n_ship)):
                self.block_pool.share_block(
                    req.req_id, idx, keys[idx]
                )
        self._trace_admit(req, admit_t0)
        first = int(payload["first_token"])
        self._next_token[slot] = first
        self._tokens_dev = self._set_token_jit(
            self._tokens_dev, np.int32(slot), np.int32(first)
        )
        self._append_token(
            slot, first,
            self._adopt_finished if finished is None else finished,
        )

    def _trace_admit(self, req: GenRequest, admit_t0: float):
        """Close the request's queue phase: a fresh admission emits
        ``queue_wait`` (from the submit wall anchor) + ``admit``; a
        preempted request's re-admission emits ``resume`` with the
        restored tail size instead."""
        from dlrover_tpu.observability.events import anchored_now

        t1 = time.monotonic()
        end_wall = anchored_now(admit_t0)
        fresh = not (req.resume_tokens.size or req.preempts)
        if fresh:
            start_wall = (
                req.submit_wall if req.submit_wall > 0.0
                else anchored_now(req.submit_t)
            )
            req.queue_wait_s = max(end_wall - start_wall, 0.0)
        if self._events is None or not self._events.enabled:
            return
        if fresh:
            self._events.complete(
                "queue_wait",
                start_wall,
                max(end_wall - start_wall, 1e-9),
                req_id=req.req_id,
            )
            self._events.complete(
                "admit",
                end_wall,
                max(t1 - admit_t0, 1e-9),
                req_id=req.req_id,
            )
        else:
            self._events.complete(
                "resume",
                end_wall,
                max(t1 - admit_t0, 1e-9),
                req_id=req.req_id,
                resume_tokens=int(req.resume_tokens.size),
            )

    def _finish(self, slot: int, reason: str,
                finished: List[GenResult]):
        sl = self._slots[slot]
        req = sl.req
        now = time.monotonic()
        tokens = np.concatenate(
            [req.prompt, np.asarray(sl.generated, np.int32)]
        )
        gaps = [
            req.token_times[i + 1] - req.token_times[i]
            for i in range(len(req.token_times) - 1)
        ]
        stats = {
            "ttft_s": round(
                max(sl.first_token_t - req.submit_t, 0.0), 6
            ),
            "tbt_p99_s": round(
                float(np.percentile(gaps, 99)) if gaps else 0.0, 6
            ),
            "queue_wait_s": round(req.queue_wait_s, 6),
            "preempts": req.preempts,
            "prefix_hit_blocks": req.hit_blocks,
        }
        if self._events is not None and self._events.enabled:
            from dlrover_tpu.observability.events import anchored_now

            end_wall = anchored_now(now)
            start_wall = (
                req.submit_wall if req.submit_wall > 0.0
                else anchored_now(req.submit_t)
            )
            self._events.complete(
                "serve_request",
                start_wall,
                max(end_wall - start_wall, 1e-9),
                req_id=req.req_id,
                replica=self.replica,
                prompt_tokens=int(req.prompt.size),
                gen_tokens=len(sl.generated),
                ttft_s=stats["ttft_s"],
                tbt_p99_s=stats["tbt_p99_s"],
                preempts=req.preempts,
                prefix_hit_blocks=req.hit_blocks,
                route=req.route,
                slo_class=req.slo_class,
                finish_reason=reason,
            )
        finished.append(
            GenResult(
                req_id=req.req_id,
                tokens=tokens,
                finish_reason=reason,
                new_tokens=len(sl.generated),
                latency_s=now - req.submit_t,
                stats=stats,
                logprobs=(
                    np.asarray(sl.logprobs, np.float32)
                    if self.capture_logprobs else _empty_logprobs()
                ),
                per_token=self._hand_over_rows(sl, tokens.size),
            )
        )
        self.block_pool.free(req.req_id)
        self._prompt_keys.pop(req.req_id, None)
        # zero the table row: a freed block re-issued to another
        # sequence must never be gathered through this lane again
        self._tables[slot] = 0
        self._wtables[slot] = 0
        self._positions[slot] = 0
        self._active[slot] = False
        self._slots[slot] = _Slot()

    def _lane_rows(self, sl: _Slot, start: int):
        """Give ``sl`` its request's per-position arrays ``{name:
        [prompt + max_new, ...]}``: untouched memory (no fill, no page
        faulted in at admission) but for the positions before ``start``,
        which are never computed in this slot and read -1 / NaN."""
        n = int(sl.req.prompt.size) + int(sl.req.max_new)
        for name, (shape, dtype) in self.per_token.items():
            sl.rows[name] = np.empty((n,) + tuple(shape), dtype)
            sl.rows[name][:start] = _never_computed(sl.rows[name])
        sl.rows_upto = start

    def _hand_over_rows(self, sl: _Slot, n: int) -> Dict[str, np.ndarray]:
        """The finished request's per-position arrays ``{name: [n,
        ...]}``: the lane's own, as the commits laid them, -1 / NaN at
        the positions no step computed (the last)."""
        for rows in sl.rows.values():
            rows[sl.rows_upto:n] = _never_computed(rows)
        return {name: rows[:n] for name, rows in sl.rows.items()}

    def _land_chunk_rows(self, before: Optional[int]):
        """Write the per-position rows of the prefill chunks dispatched
        in iterations ``before`` the given one (all of them without)
        into their lanes' arrays, and let go of the device's: their
        copies were started at the dispatch, and any decode step read
        after this ran behind them."""
        n = sum(
            before is None or rec[0] < before for rec in self._chunk_rows
        )
        for _, slot, sl, start, count, rows in self._chunk_rows[:n]:
            if self._slots[slot] is not sl:
                continue  # the lane left (shipped, evicted) before
            for name, leaf in rows.items():
                with self._ph_wait:
                    host = np.asarray(leaf)
                with self._ph_commit:
                    sl.rows[name][start:start + count] = host[:count]
            sl.rows_upto = start + count
        del self._chunk_rows[:n]

    def _note_selection(self, cached: int):
        """One decode lane's share of the step's ``sel_rows`` /
        ``read_rows`` / ``index_bytes`` labels (a model with an indexer
        only)."""
        if not self._has_indexer:
            return
        sel = min(cached, int(self.cfg.topk))
        self._step_sel_rows += sel
        self._step_cached_rows += cached
        # what attention fetched to use them: the model says where it
        # reads more than it picks (a lane's whole blocks, streamed)
        reads = getattr(self.cfg, "decode_read_rows", None)
        self._step_read_rows += reads(
            cached,
            self.sched.max_blocks_per_seq * self.sched.block_size,
            self.sched.block_size,
        ) if reads else sel
        self._step_index_bytes += cached * self._index_row_bytes

    def _note_experts(self, rows: Dict[str, np.ndarray], slots: List[int]):
        """The expert load of one committed decode step, from the ids
        it returned ``[S, layers, k]``: distinct experts with a row
        (mean over layers), the fullest expert's rows (max over
        layers) and the mean rows an expert (of all of them)."""
        ids = rows.get("experts")
        n_experts = getattr(self.cfg, "num_experts", None)
        if ids is None or not n_experts or not slots:
            return
        ids = ids[slots]  # [lanes, layers, k]
        counts = np.stack([
            np.bincount(ids[:, layer].reshape(-1), minlength=n_experts)
            for layer in range(ids.shape[1])
        ])
        share = {}
        held = getattr(self.cfg, "held_experts", None)
        if held is not None:
            # this chip's share of the layer: the load is counted over
            # the experts that live here, beside every assignment made
            first = int(getattr(self.cfg, "first_expert", 0))
            share = dict(expert_rows=int(counts.sum()))
            counts, n_held = counts[:, first:first + held], int(held)
            share["expert_rows_local"] = int(counts.sum())
        else:
            n_held = int(n_experts)
        self._step_experts = dict(
            experts=n_held,
            experts_hit=round(float((counts > 0).sum(1).mean()), 3),
            expert_rows_max=int(counts.max()),
            expert_rows_mean=round(
                ids.shape[0] * ids.shape[2] / n_experts, 4
            ),
            **share,
        )
        for key in ("experts_hit", "expert_rows_max", "expert_rows_mean"):
            self.expert_totals[key] += self._step_experts[key]
        self.expert_totals["steps"] += 1

    def _preempt(self, slot: int):
        """Evict the sequence in ``slot`` (pool pressure): free its
        blocks and requeue it AT THE HEAD carrying its generated tail
        — on re-admission it re-prefills prompt+tail and resumes the
        identical (seed, position)-pure continuation."""
        sl = self._slots[slot]
        req = sl.req
        t0 = time.monotonic()
        n_blocks = len(self.block_pool.blocks_of(req.req_id))
        self.block_pool.free(req.req_id)
        resume = np.asarray(sl.generated, np.int32)
        self._queue.insert(
            0,
            GenRequest(
                req_id=req.req_id,
                prompt=req.prompt,
                max_new=req.max_new,
                seed=req.seed,
                submit_t=req.submit_t,
                resume_tokens=resume,
                resume_logprobs=np.asarray(sl.logprobs, np.float32),
                submit_wall=req.submit_wall,
                preempts=req.preempts + 1,
                hit_blocks=req.hit_blocks,
                queue_wait_s=req.queue_wait_s,
                token_times=req.token_times,
                slo_class=req.slo_class,
                tenant=req.tenant,
                route=req.route,
            ),
        )
        if req.slo_class == SLO_INTERACTIVE:
            self._queued_interactive += 1
        self._tables[slot] = 0
        self._wtables[slot] = 0
        self._positions[slot] = 0
        self._active[slot] = False
        self._slots[slot] = _Slot()
        self.preemptions += 1
        if self._events is not None and self._events.enabled:
            from dlrover_tpu.observability.events import anchored_now

            dur = max(time.monotonic() - t0, 1e-9)
            self._events.complete(
                "preempt",
                anchored_now(t0),
                dur,
                blocks_freed=n_blocks,
                tokens_generated=int(resume.size),
                req_id=req.req_id,
            )
        logger.info(
            "preempted seq %d (pool dry): freed %d block(s), "
            "requeued with %d generated token(s)",
            req.req_id, n_blocks, resume.size,
        )

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Lowest-priority live sequence, CLASS-AWARE first: every
        batch lane outranks every interactive lane as a victim (batch
        preempts before interactive, never the reverse at equal KV
        pressure — pinned by test); within a class fewest tokens
        generated, tie broken youngest-admission-first."""
        candidates = [
            i for i, sl in enumerate(self._slots)
            if sl.req is not None and i != exclude
        ]
        if not candidates:
            return None
        return min(
            candidates,
            key=lambda i: (
                0 if self._slots[i].req.slo_class
                != SLO_INTERACTIVE else 1,
                len(self._slots[i].generated),
                -self._slots[i].admit_seq,
            ),
        )

    def _ensure_blocks(self) -> bool:
        """Before a decode window, every decoding lane must own
        blocks covering its next K write positions — grow on demand,
        preempt the lowest-priority lane when the pool (free +
        evictable shared) runs dry.  Oldest lanes grow first
        so pressure lands on the youngest.

        Returns False, having preempted nobody, when the pool is dry
        while a dispatch is in flight: a victim's ``generated`` must
        be whole before it is requeued (and the commit may free the
        blocks that are short), so the caller commits and asks again."""
        cfgp = self.pool_cfg
        order = sorted(
            (
                i for i, sl in enumerate(self._slots)
                if sl.phase == "decode"
            ),
            key=lambda i: self._slots[i].admit_seq,
        )
        for slot in order:
            sl = self._slots[slot]
            if sl.req is None:
                continue  # preempted while an older lane grew
            req = sl.req
            if len(sl.generated) + sl.ahead >= req.max_new:
                continue  # its last token is in flight: no more writes
            total = int(req.prompt.size) + int(req.max_new)
            need_tokens = min(
                int(self._positions[slot]) + self.decode_k, total
            )
            while (
                self.block_pool.covered_tokens(req.req_id)
                < need_tokens
            ):
                owned = len(self.block_pool.blocks_of(req.req_id))
                short = cfgp.blocks_for(need_tokens) - owned
                want = min(
                    max(short, self.grow_blocks),
                    cfgp.blocks_for(total) - owned,
                )
                try:
                    self.block_pool.extend(req.req_id, want)
                    self.grown_blocks += want
                except OutOfBlocksError:
                    if self._inflight:
                        return False
                    victim = self._pick_victim(exclude=slot)
                    if victim is None:
                        raise OutOfBlocksError(
                            f"seq {req.req_id} cannot grow and no "
                            "victim remains — pool smaller than one "
                            "sequence's worst case"
                        ) from None
                    self._preempt(victim)
                    if self._slots[slot].req is None:
                        break  # defensive: we were the victim
            if self._slots[slot].req is not None:
                self._tables[slot] = self.block_pool.table_row(
                    req.req_id, self.sched.max_blocks_per_seq
                )
        return True

    def _append_token(self, slot: int, token: int,
                      finished: List[GenResult],
                      lp: Optional[float] = None) -> bool:
        """Append one sampled token; returns True when the sequence
        finished (EOS / budget) and left its slot."""
        sl = self._slots[slot]
        if not sl.generated:
            sl.first_token_t = time.monotonic()
        sl.generated.append(int(token))
        if self.capture_logprobs:
            sl.logprobs.append(
                float(lp) if lp is not None else float("nan")
            )
        # per-token timestamps fold into ONE tbt_p99_s label at
        # finish — the only per-token tracing cost
        sl.req.token_times.append(time.monotonic())
        self.total_new_tokens += 1
        eos = self.sched.eos_id
        if eos is not None and int(token) == int(eos):
            self._finish(slot, FINISH_EOS, finished)
            return True
        if len(sl.generated) >= sl.req.max_new:
            self._finish(slot, FINISH_LENGTH, finished)
            return True
        return False

    def _share_filled_blocks(self, slot: int):
        """Register prompt blocks the prefill has just completed into
        the shared index (full blocks are immutable from here on)."""
        sl = self._slots[slot]
        if not sl.prefix_keys:
            return
        bs = self.sched.block_size
        full_now = min(
            sl.prefill_pos // bs, len(sl.prefix_keys)
        )
        for idx in range(sl.shared_upto, full_now):
            self.block_pool.share_block(
                sl.req.req_id, idx, sl.prefix_keys[idx]
            )
        sl.shared_upto = max(sl.shared_upto, full_now)

    def _prefill_one(self, finished: List[GenResult]) -> int:
        """Run ONE prompt chunk (round-robin over prefilling slots);
        returns the number of prompt tokens processed."""
        s = self.sched
        slots = [
            i for i, sl in enumerate(self._slots)
            if sl.phase == "prefill"
        ]
        if not slots:
            return 0
        self._lanes_prefill = len(slots)
        slot = slots[self._prefill_rr % len(slots)]
        self._prefill_rr += 1
        sl = self._slots[slot]
        req = sl.req
        self._last_prefill_req = req.req_id
        plen = sl.prefill_len
        start = sl.prefill_pos
        # every upload is a value the host does not touch again: the
        # program reads it after this call has returned, and on the
        # CPU backend an upload may alias the numpy buffer it was
        # given (``_tables`` is mutated while the chunk is in flight)
        with self._ph_dispatch:
            chunk = sl.prefill_tokens[start:start + s.prefill_chunk]
            real = chunk.size
            if real < s.prefill_chunk:
                chunk = np.pad(chunk, (0, s.prefill_chunk - real))
            args = (
                np.array(chunk[None], np.int32),
                self._lane_tables(slot, start, start + s.prefill_chunk),
                np.int32(start),
                np.int32(slot),
                np.int32(real),
            )
            # the chunk's logits are read in one place, the row of the
            # prompt's last token: the head runs on the last chunk
            # only, for that row, and the first new token is sampled in
            # the same program into the lanes' token vector — its value
            # reaches the host with a later commit, the decode step
            # dispatched next reads it on the device
            last = start + real >= plen
            if last:
                self._pool, self._tokens_dev, tok, *lp = (
                    self._prefill_last_jit(
                        self._params, self._pool, self._tokens_dev,
                        self._keys, *args,
                    )
                )
            else:
                self._pool, *lp = self._as_tuple(self._prefill_jit(
                    self._params, self._pool, *args
                ))
            if self.per_token:
                # the chunk's rows: only their copy is started here, a
                # later commit lays them (``_land_chunk_rows``)
                rows = lp.pop()
                for leaf in rows.values():
                    leaf.copy_to_host_async()
                self._chunk_rows.append(
                    (self.iterations, slot, sl, start, real, rows)
                )
            self.dispatches += 1
            self.prefill_chunks += 1
            self._step_chunk = dict(rows=int(real), kv_len=int(start + real))
            self.prefill_heads += last
            self._step_prefill_heads += last
            if self.lane_state and start == 0:
                # the program starts this lane's state from zero
                self._step_state_resets += 1
                self.state_resets += 1
            if self.draft and self._draft_params is not None:
                # mirror the chunk into the DRAFT pool (same
                # table/blocks, draft shapes) so the drafter decodes
                # over a real prompt cache; a drafter adopted
                # mid-prefill just drafts worse until the next prompt
                # — emission never depends on it
                self._draft_pool = self._draft_prefill_jit(
                    self._draft_params, self._draft_pool, *args
                )
                self.dispatches += 1
        with self._ph_commit:
            sl.prefill_pos += real
            self.total_prefill_tokens += real
            self.block_pool.note_filled(req.req_id, sl.prefill_pos)
            self._share_filled_blocks(slot)
        if not last:
            return real
        if self.role == "prefill":
            # disaggregated split: the first token is sampled HERE
            # (same (seed, position) rule as a local prefill, so the
            # decode continuation is bit-identical) and shipped as a
            # host value with the filled block tiles; the slot frees —
            # a prefill worker never decodes
            with self._ph_wait:
                tok = int(tok)
            with self._ph_commit:
                n_ship = self.pool_cfg.blocks_for(plen)
                ids = self.block_pool.blocks_of(req.req_id)[:n_ship]
                names = self.pool_cfg.paged_names
                self.shipped.append(
                    {
                        "req_id": req.req_id,
                        "first_token": tok,
                        "n_blocks": n_ship,
                        "prompt_len": plen,
                        **dict(zip(names, extract_block_regions(
                            self._pool, ids, names
                        ))),
                    }
                )
                self.shipped_out += 1
                self.block_pool.free(req.req_id)
                self._prompt_keys.pop(req.req_id, None)
                self._tables[slot] = 0
                self._positions[slot] = 0
                self._active[slot] = False
                self._slots[slot] = _Slot()
            return real
        self._track(_InFlight([(slot, sl, plen)], tok,
                              lp[0] if lp else None, first=True))
        sl.phase = "decode"
        sl.ahead = 1
        self._positions[slot] = plen
        self._active[slot] = True
        if self._sync_cause is not None:
            # in lockstep the first token is read at once
            self._commit_inflight(finished)
        return real

    def _advance_window(self, slot: int, start: int, end: int):
        """Bring ``slot``'s ring over the window layers' blocks up to a
        step that writes positions ``[start, end)``: blocks wholly
        behind ``start``'s window given back, blocks up to ``end``
        taken (nothing for a model without windows).  A block given
        back here may be re-issued at once: the programs already queued
        read it before anything dispatched later writes it."""
        if self.window is None:
            return
        req_id = self._slots[slot].req.req_id
        blocks = self.block_pool.window
        if blocks.advance(req_id, start - self.window + 1, end):
            self._wtables[slot] = blocks.table_row(req_id)

    def _lane_tables(self, slot: int, start: int, end: int) -> np.ndarray:
        """The table row a step program of ``slot`` that writes
        positions ``[start, end)`` is handed, a fresh array: the
        sequence's table and, behind it, its ring over the window
        layers' blocks (:meth:`_advance_window`)."""
        self._advance_window(slot, start, end)
        return np.concatenate([self._tables[slot], self._wtables[slot]])

    @staticmethod
    def _as_tuple(out):
        return out if isinstance(out, tuple) else (out,)

    def _track(self, rec: _InFlight):
        """Queue a dispatch for a later commit and start its samples
        on their way to the host, so that the reads neither queue
        behind each other nor wait for the commit to ask."""
        for out in (rec.toks, rec.lps, *(rec.rows or {}).values()):
            if out is not None:
                out.copy_to_host_async()
        rec.iteration = self.iterations
        self._inflight.append(rec)

    def _dispatch_decode(self) -> int:
        """Enqueue one decode step over every lane that still has a
        token to sample, from the device's own token vector; returns
        the number of lanes it runs.  Nothing is read back here:
        positions advance by one, and a lane whose tokens in flight
        complete its ``max_new`` sits out until they are committed."""
        S, MB = self.sched.max_slots, self.sched.max_blocks_per_seq
        MW = MB + self._wtables.shape[1]
        lanes = [
            (slot, sl) for slot, sl in enumerate(self._slots)
            if sl.phase == "decode"
            and len(sl.generated) + sl.ahead < sl.req.max_new
        ]
        if not lanes:
            return 0
        self._lanes_decode = len(lanes)
        self._lanes_ahead = sum(sl.ahead > 0 for _, sl in lanes)
        self.ahead_steps += self._lanes_ahead > 0
        with self._ph_dispatch:
            # ONE upload a step, built fresh: the host mutates its
            # tables and positions while the program is in flight
            if self.window is not None:
                for slot, _ in lanes:
                    pos = int(self._positions[slot])
                    self._advance_window(slot, pos, pos + 1)
                    self._step_kv_rows[0] += min(pos + 1, self.window)
                    self._step_kv_rows[1] += pos + 1
            elif self._counts_full_rows:
                at = self._positions[[slot for slot, _ in lanes]]
                self._step_kv_rows[1] += int(at.sum()) + len(lanes)
            packed = np.empty((S, MW + 2), np.int32)
            packed[:, :MB] = self._tables
            packed[:, MB:MW] = self._wtables
            packed[:, MW] = self._positions
            packed[:, MW + 1] = 0
            packed[[slot for slot, _ in lanes], MW + 1] = 1
            self._pool, self._tokens_dev, *lps = self._decode_jit(
                self._params,
                self._pool,
                self._tokens_dev,
                packed,
                self._keys,
            )
            self.dispatches += 1
            rec = _InFlight(
                [], self._tokens_dev, lps[0] if lps else None,
                rows=lps[1] if self.per_token else None,
            )
            for slot, sl in lanes:
                self._note_selection(int(self._positions[slot]) + 1)
                sl.ahead += 1
                self._positions[slot] += 1
                rec.lanes.append((slot, sl, int(self._positions[slot])))
            self._track(rec)
        return len(lanes)

    def _commit_inflight(self, finished: List[GenResult],
                         before: Optional[int] = None) -> int:
        """Read and commit the dispatches in flight, oldest first —
        all of them, or those of iterations ``before`` the given one;
        returns the number of decode tokens appended.  A lane whose
        slot has changed hands since the dispatch ended by EOS one
        step earlier: its sample is counted as overrun and dropped."""
        n = sum(
            before is None or rec.iteration < before
            for rec in self._inflight
        )
        self._step_commits += n
        self._land_chunk_rows(before)
        sampled = 0
        for rec in self._inflight[:n]:
            with self._ph_wait:
                toks = np.asarray(rec.toks).tolist()
                lps = (
                    None if rec.lps is None
                    else np.asarray(rec.lps).tolist()
                )
                rows = {
                    name: np.asarray(leaf)
                    for name, leaf in (rec.rows or {}).items()
                }
            with self._ph_commit:
                if rows:
                    self._note_experts(
                        rows, [slot for slot, _, _ in rec.lanes]
                    )
                for slot, sl, pos in rec.lanes:
                    if self._slots[slot] is not sl:
                        self.overrun_tokens += 1
                        self._step_overrun += 1
                        continue
                    sl.ahead -= 1
                    if rec.first:
                        tok, lp = toks, lps
                    else:
                        tok = toks[slot]
                        lp = None if lps is None else lps[slot]
                        self.block_pool.note_filled(sl.req.req_id, pos)
                        sampled += 1
                        if rows:
                            # the step computed position ``pos - 1``
                            for name, leaf in rows.items():
                                sl.rows[name][pos - 1] = leaf[slot]
                            sl.rows_upto = pos
                    self._next_token[slot] = tok
                    self._append_token(slot, tok, finished, lp=lp)
        del self._inflight[:n]
        return sampled

    def _commit_first(self, cause: str, finished: List[GenResult]):
        """Where the loop may not run ahead: commit what is in flight
        before going on, and count the occasion by its cause."""
        if self._inflight:
            self.sync_steps[cause] = self.sync_steps.get(cause, 0) + 1
            self._commit_inflight(finished)

    def _decode_multi_once(self, finished: List[GenResult]) -> int:
        """One fused K-step decode window (drafts + verify in ONE
        dispatch); returns the number of tokens accepted across
        lanes."""
        decoding = [
            i for i, sl in enumerate(self._slots)
            if sl.phase == "decode"
        ]
        if not decoding:
            return 0
        self._lanes_decode = len(decoding)
        self.sync_steps["multi_token"] = (
            self.sync_steps.get("multi_token", 0) + 1
        )
        K = self.decode_k
        temp = float(self.sched.temperature)
        jnp = self._jnp
        t0 = time.monotonic()
        draft_mode = (
            self._decode_multi_draft_jit is not None
            and self._draft_params is not None
        )
        lp_drafts = lp_ver = None
        with self._ph_dispatch:
            # copies: the commit loop below mutates these while, on the
            # CPU backend, an upload may still alias the host's buffer
            lanes = tuple(
                jnp.asarray(a.copy()) for a in (
                    self._next_token, self._tables, self._positions,
                    self._active,
                )
            ) + (self._keys,)
            if draft_mode:
                (self._pool, self._draft_pool, drafts, ver, n_match,
                 lp_ver) = self._decode_multi_draft_jit(
                    self._params,
                    self._draft_params,
                    self._pool,
                    self._draft_pool,
                    *lanes,
                )
            elif self.capture_logprobs:
                (self._pool, drafts, ver, n_match, lp_drafts,
                 lp_ver) = self._decode_multi_jit(
                    self._params, self._pool, *lanes
                )
            else:
                self._pool, drafts, ver, n_match = (
                    self._decode_multi_jit(
                        self._params, self._pool, *lanes
                    )
                )
            self.dispatches += 1
        with self._ph_wait:
            if lp_drafts is not None:
                lp_drafts = np.asarray(lp_drafts)
            if lp_ver is not None:
                lp_ver = np.asarray(lp_ver)
            drafts = np.asarray(drafts)
            ver = np.asarray(ver)
            n_match = np.asarray(n_match)
        sampled = 0
        with self._ph_commit:
            for slot in decoding:
                sl = self._slots[slot]
                remaining = sl.req.max_new - len(sl.generated)
                if draft_mode:
                    # separate drafter: ``ver`` is the policy's true
                    # conditioned stream at EVERY temperature (at temp 0
                    # it's the policy argmax); drafts only bound how far
                    # the window stays conditioned on matched prefixes
                    acc = min(int(n_match[slot]) + 1, K)
                    emitted = ver[slot]
                    emitted_lp = lp_ver
                elif temp <= 0:
                    # drafts ARE the K=1 greedy stream (each draft step
                    # is the K=1 computation); the verify pass gates how
                    # far we trust the window, never what we emit
                    acc = max(1, int(n_match[slot]))
                    emitted = drafts[slot]
                    emitted_lp = lp_drafts
                else:
                    # rejection-style: every emitted token is the
                    # real-rule sample conditioned on a prefix that
                    # matched the drafts it was scored against
                    acc = min(int(n_match[slot]) + 1, K)
                    emitted = ver[slot]
                    emitted_lp = lp_ver
                acc = min(acc, remaining, K)
                self.lane_windows += 1
                kept_last = None
                done = False
                for j in range(acc):
                    tok = int(emitted[j])
                    self._positions[slot] += 1
                    self.block_pool.note_filled(
                        sl.req.req_id, int(self._positions[slot])
                    )
                    sampled += 1
                    self.accepted_tokens += 1
                    kept_last = tok
                    lp = (
                        float(emitted_lp[slot, j])
                        if emitted_lp is not None else None
                    )
                    if self._append_token(slot, tok, finished, lp=lp):
                        done = True
                        break
                if not done and kept_last is not None:
                    self._next_token[slot] = kept_last
        if self._events is not None and self._events.enabled:
            from dlrover_tpu.observability.events import anchored_now

            dur = max(time.monotonic() - t0, 1e-9)
            self._events.complete(
                "verify",
                anchored_now(t0),
                dur,
                drafted=K * len(decoding),
                accepted=sampled,
            )
        return sampled

    def step(self) -> List[GenResult]:
        """One scheduler iteration: admit -> dispatch one prefill
        chunk -> (grow/preempt) -> dispatch one decode step -> read
        and commit what the PREVIOUS iteration dispatched (the K-step
        window reads its own).  Returns the sequences that finished."""
        if self._params is None:
            raise RuntimeError(
                "sync_weights() before step() — the scheduler has no "
                "params to serve with"
            )
        t0 = time.monotonic()
        emit = self._events is not None and self._events.enabled
        phases = (
            self._ph_admit, self._ph_dispatch, self._ph_wait,
            self._ph_commit,
        )
        for ph in phases:
            ph.total_s = 0.0
        self._lanes_decode = self._lanes_prefill = 0
        self._lanes_ahead = self._step_overrun = 0
        self._step_commits = 0
        self._step_state_resets = self._step_prefill_heads = 0
        self._step_sel_rows = self._step_index_bytes = 0
        self._step_cached_rows = self._step_read_rows = 0
        self._step_experts = {}
        self._step_kv_rows = [0, 0]
        self._step_chunk = {}
        finished: List[GenResult] = []
        if self._adopt_finished:
            finished.extend(self._adopt_finished)
            self._adopt_finished.clear()
        with self._ph_admit:
            self._admit(finished)
        pre_t0 = time.monotonic()
        hit_blocks = self._window_hit_blocks
        self._window_hit_blocks = 0
        pre = self._prefill_one(finished)
        pre_t1 = time.monotonic()
        with self._ph_admit:
            # in lockstep a first-token EOS may have freed a slot
            self._admit(finished)
            grown = self._ensure_blocks()
        if not grown:
            # the pool is dry: a preemption needs the lanes whole
            self._commit_first("preempt", finished)
            with self._ph_admit:
                self._ensure_blocks()
        dec_t0 = time.monotonic()
        if self._decode_multi_jit is not None:
            dec = self._decode_multi_once(finished)
        else:
            if self._sync_cause is not None:
                self._commit_first(self._sync_cause, finished)
            self._dispatch_decode()
            # what earlier iterations dispatched is read only now, with
            # this iteration's programs queued behind it on the device
            dec = self._commit_inflight(finished, before=self.iterations)
        dec_t1 = time.monotonic()
        with self._ph_admit:
            self._admit(finished)
        self.iterations += 1
        if emit and (pre or self._lanes_decode or self._step_commits):
            from dlrover_tpu.observability.events import anchored_now

            if pre:
                # one chunk serves exactly one slot: the span names
                # its request
                self._events.complete(
                    "prefill",
                    anchored_now(pre_t0),
                    pre_t1 - pre_t0,
                    tokens=pre,
                    prefix_hit_blocks=hit_blocks,
                    req_id=self._last_prefill_req,
                    **self._step_chunk,
                )
            if dec or self._lanes_decode:
                self._events.complete(
                    "decode",
                    anchored_now(dec_t0),
                    dec_t1 - dec_t0,
                    new_tokens=dec,
                )
            dur = max(time.monotonic() - t0, 1e-9)
            # the iteration's host time by leaf phase, in ms: the five
            # sum to ``dur`` (``other`` is what no phase covers — the
            # list scans, the two records above); the lane counts give
            # batch occupancy.  Labels on the ONE record an iteration
            # already writes, not lines of their own.
            admit, dispatch, wait, commit = (
                1e3 * ph.total_s for ph in phases
            )
            self._events.complete(
                "serve_step",
                anchored_now(t0),
                dur,
                tokens=pre,
                new_tokens=dec,
                throughput_tps=round((pre + dec) / dur, 2),
                admit_ms=round(admit, 4),
                dispatch_ms=round(dispatch, 4),
                wait_ms=round(wait, 4),
                commit_ms=round(commit, 4),
                other_ms=round(
                    1e3 * dur - admit - dispatch - wait - commit, 4
                ),
                lanes_decode=self._lanes_decode,
                lanes_prefill=self._lanes_prefill,
                prefill_heads=self._step_prefill_heads,
                lanes_ahead=self._lanes_ahead,
                overrun_tokens=self._step_overrun,
                slots=self.sched.max_slots,
                state_bytes=self.state_bytes,
                state_resets=self._step_state_resets,
                **self._cache_labels(),
                **self._selection_labels(),
            )
        self.sel_rows += self._step_sel_rows
        self.index_bytes += self._step_index_bytes
        return finished

    def _cache_labels(self) -> Dict:
        """The ``serve_step`` labels of a model that keeps per-lane
        state: how its layers divide between the two kinds of cache and
        what the cache held this step — the state slabs and the blocks
        live over the layers that page; of a model that pages no K / V,
        the bytes its live blocks hold (beside its slabs, where it keeps
        both: ``models/kimi_linear.py``); none for a model of K / V
        pages only (its record is as it was)."""
        if self.pool_cfg.pages_kv and not self.lane_state:
            return {}
        out = dict(
            cache_bytes=self.state_bytes
            + self.block_pool.used_blocks * self._block_bytes,
        )
        if self.lane_state:
            out.update(
                state_layers=self.pool_cfg.n_state_layers,
                paged_layers=self.pool_cfg.n_paged_layers,
            )
        return out

    def _selection_labels(self) -> Dict:
        """The ``serve_step`` labels of a model with an indexer or a
        router; none for a model without (its record is as it was)."""
        out = dict(self._step_experts)
        if self._has_indexer:
            # a lane-step's attention reads the selected rows of the
            # cached ones, a layer: both summed over the decoding lanes
            out.update(
                sel_rows=self._step_sel_rows,
                cached_rows=self._step_cached_rows,
                read_rows=self._step_read_rows,
            )
            if self.pool_cfg.pages_kv:
                # beside K and V the paged leaves are the index keys
                # alone: what the indexer had to scan
                out.update(index_bytes=self._step_index_bytes)
        if self.window is not None:
            # rows the decode kernels had to read, summed over the lanes
            # that decoded and the layers of each kind; the window
            # layers' blocks taken and given back since the last record
            cfgp, blocks = self.pool_cfg, self.block_pool.window
            taken, released = self._window_counts
            self._window_counts = (blocks.allocated, blocks.released)
            out.update(
                kv_rows_window=self._step_kv_rows[0] * cfgp.n_window_layers,
                kv_rows_full=self._step_kv_rows[1] * cfgp.n_full_layers,
                window_blocks_live=blocks.live_blocks,
                window_blocks_taken=blocks.allocated - taken,
                window_blocks_released=blocks.released - released,
                full_blocks_live=self.block_pool.used_blocks,
            )
        elif self._counts_full_rows:
            out.update(
                kv_rows_full=self._step_kv_rows[1]
                * self.pool_cfg.n_full_layers,
            )
        return out

    def run(self, max_iterations: int = 1_000_000) -> List[GenResult]:
        """Drive until idle (offline / bench mode)."""
        out: List[GenResult] = []
        for _ in range(max_iterations):
            if self.idle:
                break
            out.extend(self.step())
        return out

    def drain(self) -> List[GenRequest]:
        """Stop admitting and evict every in-flight sequence, handing
        back requeueable requests (the PR-9 preemption-drain dual for
        serving: nothing in flight is lost, it re-runs elsewhere and
        — sampling being (seed, position)-pure — reproduces the same
        tail).  Each handed-back request carries its generated tail
        as ``resume_tokens``, so an in-process requeue resumes instead
        of regenerating (cross-process dispatchers resubmit the
        original prompt; both are deterministic-identical).  A step in
        flight is committed first, so every tail is whole; what
        FINISHES by that commit comes out of ``settle()`` or the next
        ``step()``, not of the hand-back."""
        self.draining = True
        self._commit_first("drain", self._adopt_finished)
        requeue: List[GenRequest] = list(self._queue)
        self._queue.clear()
        self._queued_interactive = 0
        for req in requeue:
            # a handed-back ship payload would outlive the weights it
            # was prefilled under — the dispatcher re-prefills instead
            req.shipped = None
        for slot, sl in enumerate(self._slots):
            if sl.req is None:
                continue
            self.block_pool.free(sl.req.req_id)
            self._tables[slot] = 0
            self._wtables[slot] = 0
            self._positions[slot] = 0
            self._active[slot] = False
            sl.req.resume_tokens = np.asarray(sl.generated, np.int32)
            sl.req.resume_logprobs = np.asarray(
                sl.logprobs, np.float32
            )
            requeue.append(sl.req)
            self._slots[slot] = _Slot()
        self._prompt_keys.clear()  # handed-back requests left us
        if requeue:
            logger.info(
                "scheduler drained: %d request(s) handed back",
                len(requeue),
            )
        return requeue
