"""Cross-process generation: the continuous-batching multi-replica
serving plane.

Reference parity: ``atorch/atorch/rl/inference_backend/
vllm_backend.py`` — actor weights are SHIPPED to a dedicated vLLM
serving engine, not pointer-shared — plus ``rl/ds_hybrid_engine/``
(train<->inference layout resharding).  The TPU redesign:

- a dedicated GENERATION PROCESS runs the sampler (its own jax
  runtime / mesh, its own compiled programs);
- actor weights travel over the flash-checkpoint shm substrate
  (``agent/ckpt_shm.SharedMemoryHandler``: double-buffered segment +
  SharedDict meta) — the same zero-extra-infrastructure path training
  snapshots already ride, so a policy update is ONE ``save_state``
  and N replicas adopt it from ONE segment (fan-out by attach, not
  by copy);
- train->inference RESHARDING happens at restore: the worker's params
  template carries the inference shardings, and
  ``restore_to_target`` device_puts every leaf onto them in one
  batched call (train-side layouts never leak into the generator).

:class:`ServingEngine` is the one serving shape: N replica workers,
each running the token-level continuous-batching scheduler
(``rl/scheduler.py``) over a paged KV cache, behind a dispatcher with
per-replica shm-ring request/response transport (the PR-4 zero-copy
path — prompts and sampled tokens never pickle through a socket).
Replicas are first-class elastic workloads: SIGUSR1/SIGTERM drains a
replica (unfinished sequences requeue onto survivors — sampling is
(seed, position)-pure, so a requeued tail is the same tail), a
SIGKILL'd replica's in-flight requests redispatch automatically, and
completions dedup by request id so every request finishes exactly
once.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

from dlrover_tpu.common.env import (
    fleet_imbalance_cap,
    fleet_min_ship_prompt,
    fleet_prefill_workers,
    fleet_ship_slots,
    gen_close_timeout_s,
    gen_timeout_s,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.parallel_io import (
    input_copy_workers,
    parallel_memcpy,
)

WORKER_SPEC_ENV = "DLROVER_TPU_GEN_SPEC"

# response-ring message kinds
_KIND_RESULT = 0
_KIND_READY = 1
_KIND_DRAINED = 2
_KIND_STATS = 3
# a request the replica REJECTED (scheduler refused the submit):
# the dispatcher must fail it to the caller immediately — silence
# here would block result() for the whole request timeout
_KIND_REJECT = 4
# a prefill worker finished filling a request's KV blocks and staged
# them in the ship arena: meta carries the slot + block count and
# tokens[0] the first sampled token — the dispatcher relays the
# manifest to a decode replica (disaggregated fleet only)
_KIND_SHIP = 5
# a DRAINING replica hands one unfinished request back WITH its
# generated-so-far tail (+ per-token logprobs): the dispatcher stores
# the tail and re-dispatches with ``resume_tokens`` so the survivor
# re-prefills the whole [prompt|tail] prefix through the block-hash
# cache instead of regenerating it (flywheel layer; a SIGKILL'd
# replica can't send these — its requests redispatch fresh)
_KIND_REQUEUE = 6
_FINISH_CODES = {"length": 0, "eos": 1}
_FINISH_NAMES = {v: k for k, v in _FINISH_CODES.items()}

#: Explicit schema version of BOTH shm-ring payloads.  PR 14 silently
#: widened the response ``times`` vector 4→8 floats — a mixed-width
#: reader would have misparsed stats as garbage numbers instead of
#: failing.  v4 (this layout): request meta carries
#: [req_id, prompt_len, max_new, seed, schema_version, submit_wall_ns,
#: slo_class, tenant_hash, ship_mode, ship_slot, first_token,
#: n_blocks, route, resume_len] — the prompt buffer holds
#: [prompt|resume tail] and ``resume_lp`` the tail's per-token
#: logprobs (NaN where unknown) — and response meta carries
#: [req_id, kind, total_len, new_tokens, finish_code, weights_version,
#: schema_version, ship_slot, n_blocks] plus a ``logprobs`` f4 vector
#: (per sampled token, capture mode only; zeros otherwise).
#: ship_mode: 0 = serve
#: locally, 1 = prefill-and-ship (the replica fills the KV blocks,
#: stages them in the ship arena slot and answers _KIND_SHIP),
#: 2 = adopt-and-decode (the replica splices the staged blocks into
#: its own pool and runs a pure token loop).  Bump on ANY layout
#: change.
RING_SCHEMA_VERSION = 4

#: request ``route`` codes — how the dispatcher picked the replica;
#: the scheduler stamps the name on the request's serve_request span
_ROUTE_NAMES = {0: "least_outstanding", 1: "affinity", 2: "ship"}


def _key_digest(hex_key: str) -> int:
    """31-bit digest of one ``prefix_block_keys`` chain key — small
    enough to piggyback dozens of them in a STATS message's otherwise
    unused int32 ``tokens`` field (the per-replica shared-block index
    the affinity router matches against)."""
    return int(hex_key[:8], 16) & 0x7FFFFFFF


def _tenant_hash(tenant: str) -> int:
    """Stable cross-process tenant key (``hash()`` is salted per
    interpreter — the fair-share lanes only need distinctness)."""
    if not tenant:
        return 0
    import zlib

    return zlib.crc32(tenant.encode("utf-8", "replace")) or 1


class RingSchemaMismatch(RuntimeError):
    """A ring message written under a different payload schema than
    this reader understands (a mixed-version dispatcher/replica pair
    — e.g. a rolling upgrade that restarted only one side)."""

    def __init__(self, got: int, what: str):
        self.got = int(got)
        self.expected = RING_SCHEMA_VERSION
        super().__init__(
            f"{what} payload schema v{self.got} != reader schema "
            f"v{self.expected} — dispatcher and replica were built "
            "from different ring layouts; restart both sides on one "
            "version"
        )


def _parse_stats(times, schema_version: int) -> Dict:
    """Decode one replica STATS ``times`` vector into the stats dict
    the serving pane renders.  Refuses (typed, naming both versions)
    rather than misparse a different layout."""
    if int(schema_version) != RING_SCHEMA_VERSION:
        raise RingSchemaMismatch(int(schema_version), "replica STATS")
    return {
        "tokens_per_s": round(float(times[0]), 2),
        "queue_depth": int(times[1]),
        "kv_blocks_used": int(times[2]),
        "kv_utilization": round(float(times[3]), 4),
        "preemptions": int(times[4]),
        "prefix_hit_rate": round(float(times[5]), 4),
        "accepted_per_step": round(float(times[6]), 4),
        # flywheel adoption accounting (cumulative): how many weight
        # generations this replica actually adopted, and how many
        # SharedDict meta RPCs its adopt probe burned — the
        # generation side-segment keeps the second flat while the
        # first only moves when a publish lands
        "adoptions": int(times[10]),
        "meta_rpcs": int(times[11]),
    }


def _import_factory(path: str) -> Callable:
    """"pkg.module:attr" -> callable."""
    mod_name, _, attr = path.partition(":")
    if not attr:
        raise ValueError(
            f"factory must be 'module:callable', got {path!r}"
        )
    import importlib

    return getattr(importlib.import_module(mod_name), attr)


def tiny_llama_factory(**cfg_kwargs):
    """Built-in factory: a llama sampler whose config comes from the
    spec (tests / example).  Returns the serving worker contract — what
    a factory must provide for the replica to serve a model:

    - ``forward_fn(params, tokens) -> logits``: the whole-sequence
      forward (the reference a served tail is checked against);
    - ``params_template_fn() -> tree``: the weights the replica HOLDS,
      inference-sharded — the target every shm adoption restores onto,
      and what the scheduler serves until the first publish;
    - ``cfg``: the model config the scheduler builds its pool from
      (``ContinuousBatchingScheduler`` says which attributes and which
      optional ``lane_state()`` it reads);
    - optionally ``paged_decode_fn`` / ``paged_prefill_fn`` /
      ``paged_verify_fn``: the step programs, where they are not the
      llama ones (signatures on the scheduler's class), and
      ``serving_params_fn(tree) -> tree``: the copy of the held weights
      those programs compute on (default: the tree as given).

    A ``draft`` sub-dict (flywheel speculative decode) adds
    ``draft_cfg`` + ``draft_template_fn`` for the separately-published
    drafter the scheduler runs K cheap steps of per verify.
    :func:`falcon_h1_factory` is the sibling for the hybrid block."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import (
        LlamaConfig,
        forward,
        init_params,
    )

    def _undtype(kw):
        if isinstance(kw.get("dtype"), str):
            # the spec rides through JSON: dtype arrives as a name
            kw = dict(kw, dtype=jnp.dtype(kw["dtype"]))
        return kw

    cfg_kwargs = _undtype(dict(cfg_kwargs))
    draft_kwargs = cfg_kwargs.pop("draft", None)
    cfg = LlamaConfig(**cfg_kwargs)

    def forward_fn(params, tokens):
        return forward(params, tokens, cfg)

    def params_template_fn():
        # the template's shardings ARE the inference layout; default:
        # replicated on this process's devices.  A multi-chip serving
        # mesh would device_put leaves onto its NamedShardings here.
        return init_params(jax.random.PRNGKey(0), cfg)

    parts = {
        "forward_fn": forward_fn,
        "params_template_fn": params_template_fn,
        "cfg": cfg,
    }
    if draft_kwargs:
        draft_cfg = LlamaConfig(**_undtype(dict(draft_kwargs)))
        parts["draft_cfg"] = draft_cfg
        parts["draft_template_fn"] = lambda: init_params(
            jax.random.PRNGKey(1), draft_cfg
        )
    return parts


def falcon_h1_factory(**cfg_kwargs):
    """Built-in factory of the hybrid block (``models/falcon_h1.py``:
    Mamba-2 heads beside attention heads): the same worker contract as
    :func:`tiny_llama_factory`, with the model's own step programs —
    its prefill is told the lane and the count of real tokens, because
    ``cfg.lane_state()`` declares a conv tail and a recurrent state a
    lane — and its own ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import falcon_h1

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = falcon_h1.FalconH1Config(**cfg_kwargs)
    return {
        "forward_fn": partial(falcon_h1.forward, cfg=cfg),
        "params_template_fn": lambda: falcon_h1.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(falcon_h1.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(falcon_h1.paged_prefill_chunk, cfg=cfg),
        "serving_params_fn": partial(falcon_h1.serving_params, cfg=cfg),
    }


def olmo_hybrid_factory(**cfg_kwargs):
    """Built-in factory of the decoder with gated delta-rule and full
    attention layers (``models/olmo_hybrid.py``): the same worker
    contract, with the model's own step programs — its prefill is told
    the lane and the count of real tokens, because ``cfg.lane_state()``
    declares a conv tail and a recurrent state a lane, and
    ``cfg.layer_keeps()`` tells the cache which layers keep them and
    which keep pages — and its own ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import olmo_hybrid

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = olmo_hybrid.OlmoHybridConfig(**cfg_kwargs)
    return {
        "forward_fn": partial(olmo_hybrid.forward, cfg=cfg),
        "params_template_fn": lambda: olmo_hybrid.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(olmo_hybrid.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(
            olmo_hybrid.paged_prefill_chunk, cfg=cfg
        ),
        "serving_params_fn": partial(olmo_hybrid.serving_params, cfg=cfg),
    }


def keye_vl2_factory(**cfg_kwargs):
    """Built-in factory of the decoder with sparse experts and a
    learned top-k indexer (``models/keye_vl2.py``): the same worker
    contract, with the model's own step programs — they page an index
    key beside K and V (``cfg.paged_leaves()``) and return the experts
    every position was sent to (``cfg.per_token_outputs()``) — and its
    own ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import keye_vl2

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = keye_vl2.KeyeVL2Config(**cfg_kwargs)
    return {
        "forward_fn": partial(keye_vl2.forward, cfg=cfg),
        "params_template_fn": lambda: keye_vl2.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(keye_vl2.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(keye_vl2.paged_prefill_chunk, cfg=cfg),
        "serving_params_fn": partial(keye_vl2.serving_params, cfg=cfg),
    }


def trinity_factory(**cfg_kwargs):
    """Built-in factory of the decoder with window and full attention
    layers and a share of its routed experts (``models/trinity.py``):
    the same worker contract, with the model's own step programs — its
    config declares the window layers to the cache
    (``cfg.layer_windows()``), so they take a lane's two tables side by
    side, and they return the experts every position was sent to
    (``cfg.per_token_outputs()``) — and its own ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import trinity

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = trinity.TrinityConfig(**cfg_kwargs)
    return {
        "forward_fn": partial(trinity.forward, cfg=cfg),
        "params_template_fn": lambda: trinity.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(trinity.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(trinity.paged_prefill_chunk, cfg=cfg),
        "serving_params_fn": partial(trinity.serving_params, cfg=cfg),
    }


def deepseek_v32_factory(**cfg_kwargs):
    """Built-in factory of the decoder with latent attention, a learned
    top-k indexer and a share of its group-routed experts
    (``models/deepseek_v32.py``): the same worker contract, with the
    model's own step programs — its config declares that it pages no K
    and no V (``cfg.pages_kv``), only one latent row and one index key
    a token (``cfg.paged_leaves()``), and they return the experts every
    position was sent to (``cfg.per_token_outputs()``) — and its own
    ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import deepseek_v32

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = deepseek_v32.DeepSeekV32Config(**cfg_kwargs)
    return {
        "forward_fn": partial(deepseek_v32.forward, cfg=cfg),
        "params_template_fn": lambda: deepseek_v32.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(deepseek_v32.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(
            deepseek_v32.paged_prefill_chunk, cfg=cfg
        ),
        "serving_params_fn": partial(deepseek_v32.serving_params, cfg=cfg),
    }


def kimi_linear_factory(**cfg_kwargs):
    """Built-in factory of the decoder with Kimi Delta Attention and
    NoPE latent-attention layers over a share of its routed experts
    (``models/kimi_linear.py``): the same worker contract, with the
    model's own step programs — its config declares that it pages no K
    and no V (``cfg.pages_kv``), one latent row a token in the layers
    that page (``cfg.paged_leaves()``) AND a conv tail and a recurrent
    state a lane in the others (``cfg.lane_state()``,
    ``cfg.layer_keeps()``), so its prefill is told the lane and the
    count of real tokens, and they return the experts every position
    was sent to (``cfg.per_token_outputs()``) — and its own
    ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import kimi_linear

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = kimi_linear.KimiLinearConfig(**cfg_kwargs)
    return {
        "forward_fn": partial(kimi_linear.forward, cfg=cfg),
        "params_template_fn": lambda: kimi_linear.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(kimi_linear.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(
            kimi_linear.paged_prefill_chunk, cfg=cfg
        ),
        "serving_params_fn": partial(kimi_linear.serving_params, cfg=cfg),
    }


def lfm2_moe_factory(**cfg_kwargs):
    """Built-in factory of the decoder with gated short-convolution
    layers between grouped-query attention layers over routed experts
    held whole (``models/lfm2_moe.py``): the same worker contract, with
    the model's own step programs — its config declares a conv tail a
    lane in the layers of the first kind (``cfg.lane_state()``,
    ``cfg.layer_keeps()``), pages in rows of two 64-wide KV heads in the
    others (``cfg.kv_row_heads``), so its prefill is told the lane and
    the count of real tokens, and they return the experts every position
    was sent to (``cfg.per_token_outputs()``) — and its own
    ``serving_params_fn``."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models import lfm2_moe

    if isinstance(cfg_kwargs.get("dtype"), str):
        # the spec rides through JSON: dtype arrives as a name
        cfg_kwargs = dict(cfg_kwargs, dtype=jnp.dtype(cfg_kwargs["dtype"]))
    cfg = lfm2_moe.Lfm2MoeConfig(**cfg_kwargs)
    return {
        "forward_fn": partial(lfm2_moe.forward, cfg=cfg),
        "params_template_fn": lambda: lfm2_moe.init_params(
            jax.random.PRNGKey(0), cfg
        ),
        "cfg": cfg,
        "paged_decode_fn": partial(lfm2_moe.paged_decode_step, cfg=cfg),
        "paged_prefill_fn": partial(lfm2_moe.paged_prefill_chunk, cfg=cfg),
        "serving_params_fn": partial(lfm2_moe.serving_params, cfg=cfg),
    }


def worker_main() -> int:
    """Generation-process entry (``python -m
    dlrover_tpu.rl.generation_service``); spec arrives via env."""
    return _serving_worker_loop(json.loads(os.environ[WORKER_SPEC_ENV]))


def _worker_env(spec) -> Dict[str, str]:
    """Environment of a spawned serving replica.  The parent
    never asks JAX which backend it has (on a TPU that would take the
    chip the worker needs): the worker's platform is whatever the
    environment this process was given says (``JAX_PLATFORMS``), and
    its compile cache the one shared directory of the checkout."""
    from dlrover_tpu.common.jax_env import export_compile_cache

    env = dict(os.environ)
    env[WORKER_SPEC_ENV] = json.dumps(spec)
    export_compile_cache(env)
    return env


# --------------------------------------------------------------------------
# shm-ring transport (PR-4 zero-copy path, serving-sized slots)
# --------------------------------------------------------------------------


def _req_spec(max_prompt: int):
    from dlrover_tpu.data.shm_dataloader import BatchSpec

    return BatchSpec(
        {
            # req_id, prompt_len, max_new, seed, schema_version,
            # submit_wall_ns (the dispatcher's wall clock at submit —
            # the request-trace anchor; same-host processes share it),
            # slo_class (0 batch / 1 interactive), tenant_hash,
            # ship_mode (0 local / 1 prefill-and-ship / 2 adopt),
            # ship_slot (arena slot, -1 none), first_token (adopt
            # only), n_blocks (adopt only), route (_ROUTE_NAMES code),
            # resume_len (generated tail carried back from a drained
            # replica; the tail rides the prompt buffer at
            # [prompt_len : prompt_len + resume_len])
            "meta": ((14,), "<i8"),
            "prompt": ((max_prompt,), "<i4"),
            # the resume tail's per-token logprobs (NaN = unknown);
            # only the first resume_len entries are meaningful
            "resume_lp": ((max_prompt,), "<f4"),
        }
    )


def _resp_spec(max_total: int):
    from dlrover_tpu.data.shm_dataloader import BatchSpec

    return BatchSpec(
        {
            # req_id, kind, total_len, new_tokens, finish_code,
            # weights_version, schema_version, ship_slot, n_blocks
            "meta": ((9,), "<i8"),
            # STATS additionally piggybacks the replica's shared-block
            # key index here: tokens[0] = K, tokens[1..K] = 31-bit
            # chain-key digests (the affinity router's per-replica
            # view; SHIP carries first_token in tokens[0])
            "tokens": ((max_total,), "<i4"),
            # RESULT/REQUEUE: per-token logprobs for the sampled tail
            # (flywheel capture mode; zeros when capture is off)
            "logprobs": ((max_total,), "<f4"),
            # RESULT: latency_s, ttft_s, worker_gen_s, tokens_per_s,
            #         tbt_p99_s, queue_wait_s (trailing spare)
            # READY:  block_region_nbytes (the ship-arena slot sizer)
            # STATS:  tokens_per_s, queue_depth, kv_blocks_used,
            #         kv_utilization, preemptions, prefix_hit_rate,
            #         accepted_tokens_per_step, ttft_p99_s,
            #         prefix_hits_total, prefix_lookups_total,
            #         adoptions_total, meta_rpcs_total
            "times": ((12,), "<f8"),
        }
    )


def _per_token_spec(max_total: int, leaves: Dict):
    """The ring a replica's per-position arrays ride to the dispatcher
    on, one message a RESULT and just before it: ``leaves`` is the
    scheduler's ``per_token`` (``{name: (shape, dtype)}``), a slot
    ``max_total`` positions of each; a message is written in place and
    holds ``meta[1]`` of them, what lies past those is never read."""
    from dlrover_tpu.data.shm_dataloader import BatchSpec

    return BatchSpec(
        {
            "meta": ((2,), "<i8"),  # req_id, positions
            **{
                name: (
                    (max_total,) + tuple(shape),
                    np.dtype(dtype).newbyteorder("<").str,
                )
                for name, (shape, dtype) in sorted(leaves.items())
            },
        }
    )


def _copied(rows: np.ndarray) -> np.ndarray:
    """A copy of ``rows`` (contiguous) the way the ring itself copies:
    chunked over the copy workers where it is large."""
    out = np.empty_like(rows)
    parallel_memcpy(out, rows, workers=input_copy_workers())
    return out


class _Ring:
    """Single-writer single-reader fixed-slot message ring over the
    PR-4 shm substrate (``data/shm_dataloader._ShmRing``): prompts and
    token tails move as zero-copy numpy views, never pickled.

    The slot protocol (FREE -> WRITING -> fence -> FULL) intentionally
    mirrors ``ShmBatchWriter.put`` / ``ShmDataLoader.next_batch``;
    those classes assume the CONSUMER creates the ring and block on
    reads, while serving needs creator-side writers, attach-side
    readers and non-blocking polls on both ends — if the dataloader
    grows those seams this wrapper should collapse into it."""

    def __init__(self, name: str, spec=None, num_slots: int = 8,
                 create: bool = False):
        from dlrover_tpu.data import shm_dataloader as sd

        if create:
            self._ring = sd._ShmRing(
                name, spec, num_slots, create=True, touch=True
            )
        else:
            self._ring = sd._attach_ring(name)
        self._next_w = 0
        self._next_r = 0

    def reserve(self, timeout: float = 0.0):
        """Take the next slot for writing IN PLACE: its fields as
        zero-copy views over the segment, or None when the ring stayed
        full for ``timeout`` seconds.  The slot is WRITING — invisible
        to the reader — until :meth:`publish`."""
        from dlrover_tpu.data import shm_dataloader as sd

        slot = self._next_w
        deadline = time.monotonic() + timeout
        delay = 0.0002
        while self._ring.slot_state(slot) != sd.SLOT_FREE:
            if time.monotonic() >= deadline:
                return None
            delay = sd._backoff_sleep(delay)
        self._ring.set_slot_state(slot, sd.SLOT_WRITING)
        return self._ring.slot_views(slot)

    def publish(self):
        """Hand the reserved slot to the reader: payload visible before
        the FULL publication."""
        from dlrover_tpu.data import shm_dataloader as sd

        sd._memory_fence()
        self._ring.set_slot_state(self._next_w, sd.SLOT_FULL)
        self._next_w = (self._next_w + 1) % self._ring.num_slots

    def try_put(self, msg: Dict[str, np.ndarray],
                timeout: float = 0.0) -> bool:
        if self.reserve(timeout) is None:
            return False
        self._ring.write_slot(self._next_w, msg)
        self.publish()
        return True

    def peek(self) -> Optional[Dict[str, np.ndarray]]:
        """The oldest published message IN PLACE — its fields as
        zero-copy views, the reader's until :meth:`release` — or None."""
        from dlrover_tpu.data import shm_dataloader as sd

        slot = self._next_r
        if self._ring.slot_state(slot) != sd.SLOT_FULL:
            return None
        sd._memory_fence()
        return self._ring.slot_views(slot)

    def release(self):
        """Give the slot :meth:`peek` returned back to the writer."""
        from dlrover_tpu.data import shm_dataloader as sd

        self._ring.set_slot_state(self._next_r, sd.SLOT_FREE)
        self._next_r = (self._next_r + 1) % self._ring.num_slots

    def try_get(self) -> Optional[Dict[str, np.ndarray]]:
        if self.peek() is None:
            return None
        msg = self._ring.read_slot(self._next_r, copy=True)
        self.release()
        return msg

    def close(self, unlink: bool = False):
        self._ring.close(unlink=unlink)


# --------------------------------------------------------------------------
# serving replica worker
# --------------------------------------------------------------------------


def _serving_worker_loop(spec) -> int:
    """One continuous-batching replica: shm-ring requests in, shm-ring
    responses out, weights adopted from the shared publish segment,
    SIGUSR1/SIGTERM = drain (stop admitting, hand unfinished
    sequences back to the dispatcher by exiting cleanly — the
    dispatcher requeues everything it never saw complete)."""
    from dlrover_tpu.observability.events import (
        anchored_now,
        get_event_logger,
    )

    # the ``startup`` stages, one after the other up to READY
    # (observability/events.py ``STARTUP_STAGES``); first what lies
    # behind: the interpreter's start and this module's own imports
    events = get_event_logger()
    events.process_stage()
    with events.span("startup", stage="imports"):
        from dlrover_tpu.common.jax_env import (
            device_report,
            install_compile_meter,
        )

        # before this process's first compile: every program's trace,
        # lowering and backend compile or cache load is a ``compile``
        # record from here on (the meter's import of jax.monitoring is
        # what brings JAX in)
        install_compile_meter(events)
        import jax

        from dlrover_tpu.agent.ckpt_shm import (
            SharedMemoryHandler,
            restore_to_target,
        )
        from dlrover_tpu.observability.metrics import Histogram
        from dlrover_tpu.ops.paged_attention import paged_kernel_backend
        from dlrover_tpu.ops.pallas_utils import use_interpret
        from dlrover_tpu.rl.kv_cache import region_nbytes_per_block
        from dlrover_tpu.rl.scheduler import (
            ContinuousBatchingScheduler,
            SchedulerConfig,
        )

    name = spec["name"]
    replica = int(spec["replica"])
    tag = f"{name}-r{replica}"
    role = str(spec.get("role", "unified"))
    if role == "prefill":
        # prefill workers are throughput devices — on a host shared
        # with decode replicas they must never steal CPU from a
        # token-latency loop, so they deprioritize themselves (the
        # decode replica preempts a mid-chunk prefill the moment it
        # has a token to produce)
        try:
            os.nice(10)
        except OSError:
            pass
    drain = {"flag": False, "reason": ""}

    def _on_signal(signum, _frame):
        drain["flag"] = True
        drain["reason"] = signal.Signals(signum).name

    for sig in (signal.SIGUSR1, signal.SIGTERM):
        signal.signal(sig, _on_signal)

    # the first device query is the runtime's initialisation: it has a
    # stage of its own, before a factory that may make arrays
    sid = events.begin("startup", stage="backend_init")
    device = device_report()
    events.end("startup", sid, device_kind=device["device_kind"])
    with events.span("startup", stage="factory"):
        factory = _import_factory(spec["factory"])
        parts = factory(**spec.get("factory_kwargs", {}))
    cfg = parts.get("cfg")
    if cfg is None:
        raise RuntimeError(
            "serving mode needs the factory to expose 'cfg' (the "
            "model config the paged decode programs build from)"
        )
    s = spec["sched"]
    # logprob capture (the trajectory stream's old_logp source) and
    # the separately-published draft model: what the engine was asked
    # for through ``capture_logprobs=`` / a ``draft`` sub-dict
    fly = spec.get("flywheel") or {}
    draft_cfg = parts.get("draft_cfg")
    sid = events.begin("startup", stage="pool")
    scheduler = ContinuousBatchingScheduler(
        cfg,
        SchedulerConfig(
            max_slots=int(s["max_slots"]),
            block_size=int(s["block_size"]),
            num_blocks=int(s["num_blocks"]),
            max_seq_len=int(s["max_seq_len"]),
            prefill_chunk=int(s["prefill_chunk"]),
            max_new_default=int(s["max_new_default"]),
            temperature=float(s["temperature"]),
            eos_id=s.get("eos_id"),
        ),
        paged_decode_fn=parts.get("paged_decode_fn"),
        paged_prefill_fn=parts.get("paged_prefill_fn"),
        paged_verify_fn=parts.get("paged_verify_fn"),
        serving_params_fn=parts.get("serving_params_fn"),
        events=events,
        replica=tag,
        role=("prefill" if role == "prefill" else "unified"),
        capture_logprobs=bool(fly.get("capture")),
        draft_cfg=draft_cfg,
    )
    jax.block_until_ready(scheduler._pool)
    # the cache as the program sized it: every leaf of the pool
    # (``k``, ``v``; ``wk``, ``wv`` of the layers with a window; lane
    # state; further paged leaves) and their bytes together
    pool = scheduler.pool_report()
    events.end("startup", sid, pool_bytes=pool["pool_bytes"])
    ttft_hist = Histogram()
    # chaos seam of the health tests (spec["faults"], keyed by
    # replica index): "sleep_s" stalls every scheduler iteration (an
    # SLO straggler — slow but progressing), "wedge_after_tokens"
    # freezes the loop outright once N tokens were sampled (dead air —
    # outstanding work, a live process, no progress, no stats).
    # Signals still land, so drain/close stay clean.
    fault = (spec.get("faults") or {}).get(str(replica)) or {}
    fault_sleep_s = float(fault.get("sleep_s", 0.0))
    wedge_after = int(fault.get("wedge_after_tokens", 0))
    # ``template`` is the weights this replica HOLDS, in the dtype they
    # are published in (float32 masters): the target every shm adoption
    # restores onto.  The step programs do not read it — the scheduler
    # owns a resident copy in the model's compute dtype, made by
    # ``sync_weights`` once per adoption (the same arrays where the
    # dtypes already agree).
    sid = events.begin("startup", stage="weights")
    template = parts["params_template_fn"]()
    if draft_cfg is not None:
        # draft mode: the publish segment carries ONE combined
        # {"policy", "draft"} tree, restored onto a combined template.
        # Until the first publish adopts, the scheduler self-drafts
        # (sync_weights without draft params) — the random-init draft
        # template is never decoded with.
        template = {
            "policy": template,
            "draft": parts["draft_template_fn"](),
        }
    # waited for inside its stage: ``sync_weights`` consumes the tree
    # next, so no overlap is lost
    jax.block_until_ready(template)
    events.end(
        "startup", sid,
        bytes=sum(a.nbytes for a in jax.tree_util.tree_leaves(template)),
    )
    scheduler.sync_weights(
        template["policy"] if draft_cfg is not None else template
    )

    shm = SharedMemoryHandler(rank=0, name=name)
    req_ring = _Ring(f"{tag}-req")
    resp_ring = _Ring(f"{tag}-resp")
    max_total = int(s["max_seq_len"])
    version = -1
    gen_seen = -1  # newest generation-segment value acted on
    adoptions = 0  # cumulative weight adoptions (STATS payload)
    meta_rpcs = 0  # cumulative get_step meta RPCs (STATS payload)

    # --- disaggregated prefill/decode plumbing (fleet layer) -------
    # the ship arena is a dispatcher-owned shm segment of fixed-size
    # slots; both sides derive the SAME slot geometry from the sched
    # spec + this pool's per-block region size, so a staged [L,
    # n_blocks, block_size, KV, head_dim] pair round-trips bitwise
    if scheduler.pool_cfg.paged_leaves and spec.get("ship_arena"):
        raise ValueError(
            "a disaggregated fleet's ship arena carries K and V only: "
            "the model also pages "
            + ", ".join(scheduler.pool_cfg.leaf_names)
            + ", which a shipped prefill would lose"
        )
    # (a model that pages no K / V has no arena: refused above)
    block_bytes = (
        region_nbytes_per_block(scheduler._pool)
        if scheduler.pool_cfg.pages_kv else 0
    )
    import math as _math

    ship_slot_bytes = 2 * block_bytes * _math.ceil(
        int(s["max_seq_len"]) / int(s["block_size"])
    )
    ship_arena = None
    pending_ship: Dict[int, int] = {}  # req_id -> arena slot

    def _ship_buf():
        nonlocal ship_arena
        if ship_arena is None:
            from multiprocessing import shared_memory

            ship_arena = shared_memory.SharedMemory(
                name=spec["ship_arena"]
            )
        return ship_arena.buf

    def _read_shipped(slot: int, n_blocks: int):
        """Splice source: reconstruct the staged k/v regions from the
        arena slot (k in the first half, v in the second) using this
        pool's own dtype/geometry."""
        pool_k = scheduler._pool["k"]
        lyr, _, bsz, kvh, hdim = pool_k.shape
        dt = np.dtype(pool_k.dtype)
        cnt = lyr * n_blocks * bsz * kvh * hdim
        buf = _ship_buf()
        base = slot * ship_slot_bytes
        shape = (lyr, n_blocks, bsz, kvh, hdim)
        k_r = np.frombuffer(
            buf, dtype=dt, count=cnt, offset=base
        ).reshape(shape).copy()
        v_r = np.frombuffer(
            buf, dtype=dt, count=cnt,
            offset=base + ship_slot_bytes // 2,
        ).reshape(shape).copy()
        return k_r, v_r

    def _adopt_weights():
        nonlocal version, template, gen_seen, adoptions, meta_rpcs
        # fast path: one atomic-width load off the generation
        # side-segment.  The publisher bumps it AFTER save_state
        # completes, so an unchanged value means there is nothing new
        # to adopt — zero SharedDict RPCs, zero snapshot reads.  A
        # torn publish (publisher died mid-save) never bumps it, so
        # replicas keep serving the previous generation.
        gen = shm.peek_generation()
        if gen >= 0:
            if gen <= gen_seen:
                return
        else:
            # no generation segment (a publisher that never bumps
            # one): the meta-RPC probe
            meta_rpcs += 1
            try:
                step = shm.get_step()
            except Exception:  # noqa: BLE001 - nothing published yet
                return
            if step <= version:
                return
        try:
            step, arrays = shm.load_state(copy=False)
        except Exception:  # noqa: BLE001 - gen raced ahead of meta
            return
        if gen >= 0:
            gen_seen = gen
        if step <= version:
            return
        template = restore_to_target(
            template, arrays, to_device=True, copy_host=True
        )
        jax.block_until_ready(template)
        generation = gen if gen >= 0 else None
        if draft_cfg is not None and isinstance(template, dict) \
                and "draft" in template:
            scheduler.sync_weights(
                template["policy"], template["draft"],
                generation=generation,
            )
        else:
            scheduler.sync_weights(template, generation=generation)
        version = step
        adoptions += 1
        del arrays

    parent_pid = os.getppid()

    def _respond(kind: int, req_id: int = -1, tokens=None,
                 new_tokens: int = 0, finish: str = "length",
                 times=(), ship_slot: int = -1, n_blocks: int = 0,
                 logprobs=None):
        """Publish one message; a RESULT (or SHIP — the request's
        only path to a decode replica) must never be silently dropped
        (the dispatcher would block its caller for the full request
        timeout on a request whose compute finished), so a full ring
        WAITS for the dispatcher to drain — giving up only when the
        dispatcher process itself is gone (we are orphaned and about
        to exit anyway).  STATS are best-effort."""
        total = 0 if tokens is None else int(tokens.size)
        buf = np.zeros((max_total,), np.int32)
        if tokens is not None:
            buf[:total] = tokens
        lp_buf = np.zeros((max_total,), np.float32)
        if logprobs is not None:
            lp = np.asarray(logprobs, np.float32).reshape(-1)
            lp_buf[: lp.size] = lp[:max_total]
        padded = np.zeros((12,), np.float64)
        padded[: len(times)] = times
        msg = {
            "meta": np.asarray(
                [req_id, kind, total, new_tokens,
                 _FINISH_CODES.get(finish, 0), version,
                 RING_SCHEMA_VERSION, ship_slot, n_blocks],
                np.int64,
            ),
            "tokens": buf,
            "logprobs": lp_buf,
            "times": padded,
        }
        while True:
            if resp_ring.try_put(
                msg, timeout=0.0 if kind == _KIND_STATS else 5.0
            ):
                return True
            if kind == _KIND_STATS:
                return False  # periodic; the next window resends
            if os.getppid() != parent_pid:
                logger.warning(
                    "replica %s orphaned (dispatcher gone): "
                    "dropping message for req %d", tag, req_id,
                )
                return False
            logger.warning(
                "replica %s: response ring full, waiting for the "
                "dispatcher to drain", tag,
            )

    # what the model's programs return a position (a router's
    # experts; the scheduler's ``per_token``, empty for a model without
    # or with logprobs not captured) rides a ring of its own, which
    # the dispatcher creates from READY's description of it
    pt_ring = None

    def _put_per_token(res) -> int:
        """The request's rows into the ring's slot, in place: ``[:n]``
        of each name (``meta`` says ``n``; what an older, longer
        request left past it in the slot is never read).  Returns the
        bytes written."""
        nonlocal pt_ring
        if pt_ring is None:
            pt_ring = _Ring(f"{tag}-pt")
        while (slot := pt_ring.reserve(timeout=5.0)) is None:
            if os.getppid() != parent_pid:
                return 0
        n = res.tokens.size
        slot["meta"][:] = (res.req_id, n)
        copied = sum(
            parallel_memcpy(
                slot[name][:n], rows, workers=input_copy_workers()
            )
            for name, rows in res.per_token.items()
        )
        pt_ring.publish()
        return copied

    def _flush_result(res) -> int:
        """A finished request's way out; returns the bytes of
        per-position rows this thread wrote on it."""
        ttft_hist.observe(res.stats.get("ttft_s", 0.0))
        copied = _put_per_token(res) if scheduler.per_token else 0
        _respond(
            _KIND_RESULT,
            req_id=res.req_id,
            tokens=res.tokens,
            new_tokens=res.new_tokens,
            finish=res.finish_reason,
            logprobs=res.logprobs,
            times=(
                res.latency_s,
                res.stats.get("ttft_s", 0.0),
                res.latency_s,
                res.new_tokens / max(res.latency_s, 1e-9),
                res.stats.get("tbt_p99_s", 0.0),
                res.stats.get("queue_wait_s", 0.0),
            ),
        )
        return copied

    # the mark of READY: "engine up", from inside
    events.instant(
        "device_report",
        platform=device["platform"],
        device_kind=device["device_kind"],
        device_count=device["device_count"],
        replica=tag,
        kernel_backend=paged_kernel_backend(),
        interpret=use_interpret(),
        **pool,
    )
    # READY carries the per-block region size so the dispatcher can
    # size the ship arena without instantiating the model itself
    _respond(
        _KIND_READY, times=(float(block_bytes),),
        # a model with per-position outputs describes them here (the
        # bytes of a JSON object, one an int32): the dispatcher makes
        # their ring from it
        tokens=(
            np.frombuffer(
                json.dumps(
                    {n: [list(sh), str(dt)]
                     for n, (sh, dt) in scheduler.per_token.items()}
                ).encode(), np.uint8,
            ).astype(np.int32)
            if scheduler.per_token else None
        ),
    )
    logger.info("serving replica %s ready (pid %d)", tag, os.getpid())
    served = 0
    window_tokens = 0
    window_t0 = time.monotonic()
    while True:
        if drain["flag"]:
            break
        if wedge_after and scheduler.total_new_tokens >= wedge_after:
            # injected dead air: the process lives, its outstanding
            # requests never progress, no stats ever flow again
            time.sleep(0.05)
            continue
        # intake and reply are the loop's own host work around the
        # scheduler's step: leaves on the profiler's clock, like the
        # scheduler's sched.* phases (observability/events.py
        # LEAF_ANNOTATIONS), so a replica's idle time between two
        # steps has a name
        with events.leaf("sched.intake"):
            _adopt_weights()
            if fault_sleep_s:
                time.sleep(fault_sleep_s)  # injected SLO straggler
            # admit everything queued on the ring (token-level admission
            # happens inside the scheduler)
            while True:
                msg = req_ring.try_get()
                if msg is None:
                    break
                (req_id, plen, max_new, seed, ring_ver, wall_ns,
                 slo_i, tenant_h, ship_mode, ship_slot, first_tok,
                 n_ship, route_code, resume_len) = (
                    int(v) for v in msg["meta"]
                )
                if ring_ver != RING_SCHEMA_VERSION:
                    raise RingSchemaMismatch(ring_ver, "dispatch request")
                try:
                    kwargs = dict(
                        max_new=max_new,
                        seed=seed,
                        req_id=req_id,
                        submit_wall=(
                            wall_ns / 1e9 if wall_ns > 0 else None
                        ),
                        slo_class=(
                            "interactive" if slo_i == 1 else "batch"
                        ),
                        tenant=(str(tenant_h) if tenant_h else ""),
                        route=_ROUTE_NAMES.get(route_code,
                                               "least_outstanding"),
                    )
                    if resume_len > 0:
                        # a drained replica's hand-back: the tail rides
                        # the prompt buffer past the prompt; re-prefill
                        # reuses every cached [prompt|tail] block
                        kwargs["resume_tokens"] = msg["prompt"][
                            plen:plen + resume_len
                        ]
                        kwargs["resume_logprobs"] = msg["resume_lp"][
                            :resume_len
                        ]
                    if ship_mode == 1:
                        # prefill-and-ship: remember which arena slot the
                        # dispatcher reserved; the blocks stage there when
                        # the prefill completes
                        pending_ship[req_id] = ship_slot
                    elif ship_mode == 2:
                        k_r, v_r = _read_shipped(ship_slot, n_ship)
                        kwargs["shipped"] = {
                            "k": k_r,
                            "v": v_r,
                            "first_token": first_tok,
                        }
                    scheduler.submit(msg["prompt"][:plen], **kwargs)
                except ValueError as e:
                    # belt-and-suspenders (the dispatcher validates at
                    # its own submit): a malformed ring message must not
                    # kill the replica — a dead replica cascades the
                    # request onto the survivors — and must be ANSWERED,
                    # or the caller blocks for the full request timeout
                    logger.error(
                        "replica %s rejected request %d: %s",
                        tag, req_id, e,
                    )
                    pending_ship.pop(req_id, None)
                    _respond(_KIND_REJECT, req_id=req_id)
        if scheduler.idle:
            time.sleep(0.002)
            continue
        finished = scheduler.step()
        with events.leaf("sched.reply"):
            for res in finished:
                served += 1
                window_tokens += res.new_tokens
                # one record a finished request: what the leaf around
                # it spends on the request's way out, over the whole
                # run, and what this thread wrote of its rows from the
                # scheduler's hand-over to the ring's publish
                reply_wall = anchored_now()
                t0 = time.perf_counter()
                copied = _flush_result(res)
                events.complete(
                    "reply",
                    reply_wall,
                    time.perf_counter() - t0,
                    req_id=res.req_id,
                    per_token_bytes=sum(
                        a.nbytes for a in res.per_token.values()
                    ),
                    copied_bytes=copied,
                )
            if scheduler.shipped:
                # prefill worker: stage each completed prefill's KV
                # blocks in its reserved arena slot and hand the manifest
                # to the dispatcher; the decode replica splices them in
                for rec in scheduler.shipped:
                    slot = pending_ship.pop(rec["req_id"], -1)
                    if slot < 0:
                        continue  # locally-submitted on a prefill role
                    ship_wall = anchored_now()
                    t0 = time.perf_counter()
                    k_b = rec["k"].tobytes()
                    v_b = rec["v"].tobytes()
                    buf = _ship_buf()
                    base = slot * ship_slot_bytes
                    buf[base:base + len(k_b)] = k_b
                    half = base + ship_slot_bytes // 2
                    buf[half:half + len(v_b)] = v_b
                    ship_s = max(time.perf_counter() - t0, 1e-9)
                    nbytes = len(k_b) + len(v_b)
                    events.complete(
                        "kv_ship",
                        ship_wall,
                        ship_s,
                        blocks=int(rec["n_blocks"]),
                        bytes=nbytes,
                        throughput_gbps=round(nbytes / ship_s / 1e9, 3),
                    )
                    window_tokens += rec["prompt_len"]
                    _respond(
                        _KIND_SHIP,
                        req_id=rec["req_id"],
                        tokens=np.asarray(
                            [rec["first_token"]], np.int32
                        ),
                        ship_slot=slot,
                        n_blocks=int(rec["n_blocks"]),
                    )
                scheduler.shipped.clear()
            now = time.monotonic()
            if now - window_t0 >= 1.0:
                tps = window_tokens / (now - window_t0)
                st = scheduler.stats()
                # the dispatcher records the serving gauges from these
                # numbers (``_dispatch_once``: it is the process with a
                # registry somebody reads); the replica's
                # shared-block key index and its cumulative prefix
                # counters ride along — the affinity router's whole
                # view, no extra RPC
                digs = [
                    _key_digest(k)
                    for k in list(
                        scheduler.block_pool._shared_by_key
                    )[-(max_total - 1):]
                ]
                _respond(
                    _KIND_STATS,
                    tokens=np.asarray([len(digs)] + digs, np.int32),
                    times=(
                        tps,
                        float(scheduler.queue_depth),
                        float(scheduler.block_pool.used_blocks),
                        float(st["kv_utilization"]),
                        float(st["preemptions"]),
                        float(st["prefix_hit_rate"]),
                        float(st["accepted_per_step"]),
                        ttft_hist.quantile(0.99),
                        float(scheduler.block_pool.prefix_hits),
                        float(scheduler.block_pool.prefix_queries),
                        float(adoptions),
                        float(meta_rpcs),
                    ),
                )
                window_tokens = 0
                window_t0 = now

    # drain: stop admitting, flush what finishes inside the grace
    # window (their compute is not thrown away), then hand the rest
    # back to the dispatcher (it requeues everything not seen
    # complete); tell it we left cleanly
    from dlrover_tpu.common.env import serving_drain_grace_s

    scheduler.draining = True
    grace_deadline = time.monotonic() + serving_drain_grace_s()
    while (
        scheduler.active_count and time.monotonic() < grace_deadline
    ):
        for res in scheduler.step():
            served += 1
            _flush_result(res)
    # the decode loop runs one step ahead: what is still in flight is
    # committed here, so a request it completes is answered and the
    # tails handed back below are whole
    for res in scheduler.settle("drain"):
        served += 1
        _flush_result(res)
    requeued = scheduler.drain()
    for r in requeued:
        # hand each unfinished request back WITH its generated tail
        # so the survivor resumes (re-prefilling the cached prefix)
        # instead of regenerating; the dispatcher falls back to a
        # fresh dispatch for anything these messages don't cover
        tail = np.asarray(r.resume_tokens, np.int32).reshape(-1)
        _respond(
            _KIND_REQUEUE,
            req_id=r.req_id,
            tokens=tail,
            new_tokens=int(tail.size),
            logprobs=r.resume_logprobs,
        )
    _respond(_KIND_DRAINED, new_tokens=len(requeued))
    events.instant(
        "device_report",
        platform=device["platform"],
        device_kind=device["device_kind"],
        device_count=device["device_count"],
        replica=tag,
        compile_counts=json.dumps(scheduler.compile_counts()),
        pool_stats=json.dumps(scheduler.block_pool.stats()),
    )
    logger.info(
        "serving replica %s drained on %s: served %d, handed back %d",
        tag, drain["reason"], served, len(requeued),
    )
    if ship_arena is not None:
        ship_arena.close()
    req_ring.close()
    resp_ring.close()
    shm.close()
    return 0


# --------------------------------------------------------------------------
# dispatcher
# --------------------------------------------------------------------------


def least_outstanding(replicas):
    """Routing policy: fewest in-flight requests wins, ties broken by
    LOWEST replica id — fully deterministic whatever order the alive
    list was built in, so bench runs and the kill-one-mid-load test
    reproduce across dict/list orderings (pinned by test)."""
    return min(replicas, key=lambda r: (len(r.outstanding), r.idx))


@dataclass
class _InFlight:
    req_id: int
    prompt: np.ndarray
    max_new: int
    seed: int
    submit_t: float
    submit_wall: float = 0.0  # epoch seconds; rides the request ring
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[Dict] = None
    attempts: int = 0
    slo_class: str = "batch"
    tenant: str = ""
    digests: tuple = ()  # the prompt's chain-key digests (affinity)
    ship_slot: int = -1  # arena slot reserved for this request
    # generated-so-far tail handed back by a draining replica (or
    # supplied at submit): the next dispatch resumes instead of
    # regenerating; logprobs ride along NaN-padded where unknown
    resume_tokens: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), np.int32)
    )
    resume_logprobs: np.ndarray = field(
        default_factory=lambda: np.zeros((0,), np.float32)
    )


class _Replica:
    def __init__(self, idx: int, proc, req_ring: _Ring,
                 resp_ring: _Ring, role: str = "decode"):
        self.idx = idx
        self.proc = proc
        self.req_ring = req_ring
        self.resp_ring = resp_ring
        self.role = role  # "decode" serves end-to-end; "prefill" ships
        self.outstanding: Dict[int, _InFlight] = {}
        self.ready = False
        self.alive = True
        self.draining = False  # signaled; stop routing to it
        self.drained = False  # clean-handshake confirmation arrived
        self.stats: Dict = {}  # newest _KIND_STATS payload
        self.block_bytes = 0  # per-block region size (READY payload)
        # the ring of the model's per-position arrays (READY payload),
        # None for a model without
        self.pt_ring: Optional[_Ring] = None
        self.prefix_keys: set = set()  # newest STATS key-index digest
        self.last_prefix = (0.0, 0.0)  # cumulative (hits, lookups)


class ServingEngine:
    """The continuous-batching serving plane: N replicas behind a
    dispatcher.  ``submit``/``result`` is the streaming surface;
    ``generate`` is the whole-batch surface of the in-process backends
    (``rl/inference.py``), so PPO rollouts swap engines without edits.

    Elasticity: ``drain_replica`` (SIGUSR1) / ``close`` (SIGTERM)
    drain; a replica that dies ANY way hands its uncompleted requests
    back to the dispatch queue, completions dedup by request id, and
    a request that kills ``max_attempts`` replicas in a row fails
    loudly instead of poisoning the fleet forever."""

    MAX_ATTEMPTS = 3

    def __init__(
        self,
        factory: str,
        max_new_tokens: int,
        temperature: float = 1.0,
        factory_kwargs: Optional[Dict] = None,
        name: Optional[str] = None,
        num_replicas: int = 2,
        max_slots: int = 8,
        block_size: int = 16,
        num_blocks: int = 512,
        max_seq_len: int = 512,
        prefill_chunk: int = 32,
        eos_id: Optional[int] = None,
        start_timeout: float = 300.0,
        ring_slots: int = 8,
        faults: Optional[Dict] = None,
        capture_logprobs: bool = False,
    ):
        from dlrover_tpu.agent.ckpt_shm import SharedMemoryHandler
        from dlrover_tpu.common.multi_process import SOCKET_DIR_ENV
        from dlrover_tpu.observability.health import ServingHealthEngine
        from dlrover_tpu.observability.metrics import Histogram

        self._name = name or f"serve-{os.getpid()}"
        # pin the socket namespace for the engine's whole lifetime: a
        # replica added LATER (scale-out) must land its ring handshake
        # where the existing fleet's sockets live, even if the
        # environment moved underneath us
        self._socket_dir = os.getenv(SOCKET_DIR_ENV, "")
        self._max_new = int(max_new_tokens)
        self._max_seq_len = int(max_seq_len)
        self._shm = SharedMemoryHandler(
            rank=0, name=self._name, host=True
        )
        self._version = 0
        self.publish_s = 0.0
        self._reqs: Dict[int, _InFlight] = {}
        self._dispatch_q: deque = deque()
        self._completed: set = set()  # delivered-but-uncollected ids
        self._completed_total = 0  # lifetime counter (the status pane)
        self._lock = threading.Lock()
        self._closed = False
        self._latency = Histogram()
        # serving observatory: per-request SLO histograms in the
        # registry, mirrored per-replica gauges, and the
        # ServingHealthEngine derivations
        self._health = ServingHealthEngine()
        # logprob capture (the trajectory stream's old_logp) and the
        # co-published draft model (a "draft" sub-dict in
        # factory_kwargs) are what the caller asks for
        factory_kwargs = factory_kwargs or {}
        self._capture = bool(capture_logprobs)
        self._draft_mode = bool(factory_kwargs.get("draft"))
        self._spec = {
            "name": self._name,
            "factory": factory,
            "factory_kwargs": factory_kwargs,
            "faults": {
                str(k): v for k, v in (faults or {}).items()
            },
            "sched": {
                "max_slots": int(max_slots),
                "block_size": int(block_size),
                "num_blocks": int(num_blocks),
                "max_seq_len": int(max_seq_len),
                "prefill_chunk": int(prefill_chunk),
                "max_new_default": int(max_new_tokens),
                "temperature": float(temperature),
                "eos_id": eos_id,
            },
        }
        if self._capture or self._draft_mode:
            self._spec["flywheel"] = {"capture": self._capture}
        # fleet layer: affinity routing + SLO lanes + optional
        # prefill/decode split
        self._imbalance_cap = fleet_imbalance_cap()
        # at least one decode replica must remain, whatever the env
        self._n_prefill = max(
            0, min(fleet_prefill_workers(), int(num_replicas) - 1)
        )
        self._min_ship_prompt = fleet_min_ship_prompt()
        self._ship_nslots = fleet_ship_slots()
        self._ship_arena = None
        self._ship_slot_bytes = 0
        self._ship_free: List[int] = []
        self._adopt_q: deque = deque()  # staged manifests to relay
        self._fleet_hits = 0.0  # current-window prefix hit deltas
        self._fleet_lookups = 0.0
        self._fleet_hit_rate = 0.0
        if self._n_prefill:
            self._spec["ship_arena"] = f"{self._name}-ship"
        self._next_id = 0
        self._replicas: List[_Replica] = []
        for i in range(int(num_replicas)):
            self._replicas.append(self._spawn(i))
        deadline = time.monotonic() + start_timeout
        for rep in self._replicas:
            self._await_ready(rep, deadline)
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop,
            name=f"serve-dispatch-{self._name}",
            daemon=True,
        )
        self._dispatcher.start()
        logger.info(
            "serving engine %s up: %d replica(s), %d slots each",
            self._name, len(self._replicas), max_slots,
        )

    # ----------------------------------------------------- lifecycle
    @contextlib.contextmanager
    def _pinned_dir(self):
        """A ring's handshake dict lives under the socket directory
        this engine was built with, whatever the caller's is now."""
        from dlrover_tpu.common.multi_process import SOCKET_DIR_ENV

        old = os.environ.get(SOCKET_DIR_ENV)
        if self._socket_dir:
            os.environ[SOCKET_DIR_ENV] = self._socket_dir
        try:
            yield
        finally:
            if old is None:
                os.environ.pop(SOCKET_DIR_ENV, None)
            else:
                os.environ[SOCKET_DIR_ENV] = old

    def _spawn(self, idx: int) -> _Replica:
        from dlrover_tpu.common.multi_process import SOCKET_DIR_ENV

        tag = f"{self._name}-r{idx}"
        with self._pinned_dir():
            req_ring = _Ring(
                f"{tag}-req",
                spec=_req_spec(self._max_seq_len),
                num_slots=8,
                create=True,
            )
            resp_ring = _Ring(
                f"{tag}-resp",
                spec=_resp_spec(self._max_seq_len),
                num_slots=8,
                create=True,
            )
        role = "prefill" if idx < self._n_prefill else "decode"
        spec = dict(self._spec, replica=idx, role=role)
        env = _worker_env(spec)
        if self._socket_dir:
            env[SOCKET_DIR_ENV] = self._socket_dir
        proc = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.rl.generation_service"],
            env=env,
        )
        return _Replica(idx, proc, req_ring, resp_ring, role=role)

    def _note_ready(self, rep: _Replica, msg):
        """READY landed: record the replica's per-block region size
        and (first READY of a disaggregated fleet) size + create the
        ship arena every prefill worker stages into."""
        rep.ready = True
        try:
            rep.block_bytes = int(float(msg["times"][0]))
        except Exception:  # noqa: BLE001 - pre-v3 payload shape
            rep.block_bytes = 0
        described = int(msg["meta"][2])
        if described and rep.pt_ring is None:
            leaves = json.loads(
                msg["tokens"][:described].astype(np.uint8).tobytes()
            )
            with self._pinned_dir():
                rep.pt_ring = _Ring(
                    f"{self._name}-r{rep.idx}-pt",
                    spec=_per_token_spec(self._max_seq_len, leaves),
                    num_slots=4,
                    create=True,
                )
        if (
            self._n_prefill
            and self._ship_arena is None
            and rep.block_bytes > 0
        ):
            import math
            from multiprocessing import shared_memory

            s = self._spec["sched"]
            self._ship_slot_bytes = 2 * rep.block_bytes * math.ceil(
                int(s["max_seq_len"]) / int(s["block_size"])
            )
            self._ship_arena = shared_memory.SharedMemory(
                name=self._spec["ship_arena"],
                create=True,
                size=self._ship_slot_bytes * self._ship_nslots,
            )
            self._ship_free = list(range(self._ship_nslots))

    def _await_ready(self, rep: _Replica, deadline: float):
        while time.monotonic() < deadline:
            msg = rep.resp_ring.try_get()
            if msg is not None and int(msg["meta"][1]) == _KIND_READY:
                self._note_ready(rep, msg)
                return
            if rep.proc.poll() is not None:
                raise RuntimeError(
                    f"serving replica {rep.idx} died during startup "
                    f"(exit {rep.proc.returncode})"
                )
            time.sleep(0.01)
        raise TimeoutError(
            f"serving replica {rep.idx} not ready in time"
        )

    # ----------------------------------------------------------- API
    def sync_weights(self, params, draft_params=None) -> float:
        """One shm publish; every replica adopts it between scheduler
        iterations (fan-out by attach — N readers, one segment).  In
        draft mode (a ``draft`` sub-dict in ``factory_kwargs``) the
        policy and the drafter co-publish as ONE combined tree —
        ``draft_params`` is then required every call, since replicas
        restore onto a combined template.  The generation
        side-segment is bumped AFTER the save
        completes, so replicas detect the new snapshot with one
        atomic-width load instead of a meta RPC per iteration — and a
        publisher killed mid-save never bumps it (replicas keep the
        previous generation)."""
        if self._draft_mode:
            if draft_params is None:
                raise ValueError(
                    "draft mode: sync_weights needs draft_params "
                    "(replicas restore a combined {'policy', "
                    "'draft'} tree)"
                )
            params = {"policy": params, "draft": draft_params}
        elif draft_params is not None:
            raise ValueError(
                "draft_params given but the engine was not built "
                "with a 'draft' factory sub-config"
            )
        self._version += 1
        t0 = time.perf_counter()
        self._shm.save_state(self._version, params)
        self._shm.publish_generation(self._version)
        self.publish_s = time.perf_counter() - t0
        return self.publish_s

    def submit(self, prompt, max_new: Optional[int] = None,
               seed: int = 0, slo_class: str = "batch",
               tenant: str = "", resume_tokens=None,
               resume_logprobs=None) -> int:
        """Queue one prompt; returns the request id.  ``slo_class``
        ("interactive" gets the reserved decode-slot lanes and
        preempts last) and ``tenant`` (the fair-share key within a
        class) steer the scheduler's lanes.  ``resume_tokens``
        (a previously generated tail — e.g. carried across an engine
        restart) makes the replica re-prefill [prompt|tail] through
        its block-hash cache and continue from there instead of
        regenerating; ``resume_logprobs`` optionally carries the
        tail's captured logprobs (NaN-padded where unknown)."""
        if self._closed:
            raise RuntimeError("serving engine is closed")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must hold at least one token")
        max_new = int(
            self._max_new if max_new is None else max_new
        )
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        resume = (
            np.asarray(resume_tokens, np.int32).reshape(-1)
            if resume_tokens is not None
            else np.zeros((0,), np.int32)
        )
        if resume.size >= max_new:
            raise ValueError(
                f"resume tail of {resume.size} leaves no room under "
                f"max_new {max_new}"
            )
        rlp = np.full((resume.size,), np.nan, np.float32)
        if resume_logprobs is not None and resume.size:
            got = np.asarray(
                resume_logprobs, np.float32
            ).reshape(-1)[: resume.size]
            rlp[: got.size] = got
        if prompt.size + max_new > self._max_seq_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"max_seq_len {self._max_seq_len}"
            )
        # the replica scheduler's pool guard, enforced HERE with the
        # SAME definition
        # (kv_cache.pool_can_ever_hold): a request whose worst case
        # exceeds a replica's whole pool would otherwise be refused
        # inside the worker — answered as a rejection, but only after
        # burning a dispatch — so fail it at the front door
        from dlrover_tpu.rl.kv_cache import (
            pool_can_ever_hold,
            prefix_block_keys,
        )

        s = self._spec["sched"]
        if not pool_can_ever_hold(
            int(s["num_blocks"]), int(s["block_size"]),
            prompt.size + max_new,
        ):
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new} exceeds "
                f"the replica pool of {int(s['num_blocks']) - 1} "
                "blocks"
            )
        # the prompt's chain-key digests are the affinity router's
        # match input — computed once, at the front door
        digests = tuple(
            _key_digest(k)
            for k in prefix_block_keys(
                prompt, int(s["block_size"])
            )[:64]
        )
        with self._lock:
            req_id = self._next_id
            self._next_id += 1
            inflight = _InFlight(
                req_id=req_id,
                prompt=prompt,
                max_new=max_new,
                seed=int(seed),
                submit_t=time.monotonic(),
                submit_wall=time.time(),
                slo_class=(
                    "interactive"
                    if slo_class == "interactive" else "batch"
                ),
                tenant=str(tenant),
                digests=digests,
                resume_tokens=resume,
                resume_logprobs=rlp,
            )
            self._reqs[req_id] = inflight
            self._dispatch_q.append(req_id)
        return req_id

    def result(self, req_id: int,
               timeout: Optional[float] = None) -> Dict:
        """Block for one request's completion; returns
        ``{"tokens", "finish_reason", "latency_s", ...}``."""
        timeout = gen_timeout_s() if timeout is None else timeout
        req = self._reqs.get(req_id)
        if req is None:
            raise KeyError(f"unknown request id {req_id}")
        if not req.done.wait(timeout=timeout):
            raise TimeoutError(
                f"request {req_id} not completed within {timeout}s "
                f"({self._alive_count()} replica(s) alive)"
            )
        res = req.result
        # collection point: a delivered result leaves the engine's
        # bookkeeping (an unbounded serving lifetime must not retain
        # every prompt/tail ever served); late duplicates still land
        # harmlessly — _complete finds no pending request
        self._reqs.pop(req_id, None)
        with self._lock:
            self._completed_total += 1
            self._completed.discard(req_id)
        if res.get("error"):
            raise RuntimeError(res["error"])
        return res

    def generate(self, prompts, rng=None, seed: Optional[int] = None):
        """Whole-batch surface: [B, P] in, [B, P + max_new] out.
        Per-row sampling seeds derive from ``seed`` + row."""
        if seed is None:
            seed = 0
            if rng is not None:
                import jax

                seed = int(
                    np.asarray(jax.random.key_data(rng)).ravel()[-1]
                )
        prompts = np.asarray(prompts, np.int32)
        ids = [
            self.submit(row, max_new=self._max_new,
                        seed=int(seed) + i * 1000003)
            for i, row in enumerate(prompts)
        ]
        rows = []
        width = prompts.shape[1] + self._max_new
        for rid in ids:
            res = self.result(rid)
            row = np.zeros((width,), np.int32)
            toks = res["tokens"][:width]
            row[: toks.size] = toks
            rows.append(row)
        return np.stack(rows)

    # ------------------------------------------------------ elasticity
    def drain_replica(self, idx: int, sig: int = signal.SIGUSR1):
        """PR-9 drain protocol: SIGUSR1 (or SIGTERM — same handler)
        -> the replica stops admitting and its unfinished sequences
        requeue onto survivors.  The dispatcher stops routing to it
        IMMEDIATELY — a request dispatched into the drain window
        would only burn one of its redispatch attempts."""
        rep = self._replicas[idx]
        rep.draining = True
        if rep.proc.poll() is None:
            rep.proc.send_signal(sig)

    def kill_replica(self, idx: int):
        """Chaos arm: hard-kill (the crash path — requests redispatch
        exactly as on drain, minus the clean handshake)."""
        rep = self._replicas[idx]
        if rep.proc.poll() is None:
            rep.proc.send_signal(signal.SIGKILL)

    def add_replica(self, wait_ready: bool = True,
                    timeout: float = 300.0) -> int:
        """Elastic scale-out: spawn one more replica; the dispatcher
        starts routing to it the moment its READY lands.  Returns the
        new replica index."""
        if self._closed:
            raise RuntimeError("serving engine is closed")
        rep = self._spawn(len(self._replicas))
        self._replicas.append(rep)
        if wait_ready:
            deadline = time.monotonic() + timeout
            # the dispatcher thread owns the response rings now; wait
            # on the flag it flips, not on the ring itself
            while not rep.ready:
                if rep.proc.poll() is not None:
                    raise RuntimeError(
                        f"replica {rep.idx} died during scale-out "
                        f"(exit {rep.proc.returncode})"
                    )
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"replica {rep.idx} not ready in {timeout}s"
                    )
                time.sleep(0.01)
        return rep.idx

    def _alive_count(self) -> int:
        return sum(1 for r in self._replicas if r.alive)

    # ------------------------------------------------------ dispatcher
    def _free_ship_slot(self, req_id: int):
        """Return a request's arena slot to the free list (completion,
        rejection, or a death-requeue that re-dispatches it fresh)."""
        req = self._reqs.get(req_id)
        if req is not None and req.ship_slot >= 0:
            self._ship_free.append(req.ship_slot)
            req.ship_slot = -1

    def _complete(self, req_id: int, result: Dict):
        with self._lock:
            if req_id in self._completed:
                return  # dedup: drain/crash races can answer twice
            self._completed.add(req_id)
        self._free_ship_slot(req_id)
        req = self._reqs.get(req_id)
        if req is None:
            return
        req.result = result
        if "latency_s" in result:
            self._latency.observe(result["latency_s"])
        req.done.set()

    def _handle_responses(self, rep: _Replica) -> int:
        n = 0
        while True:
            msg = rep.resp_ring.try_get()
            if msg is None:
                return n
            n += 1
            meta = msg["meta"]
            kind = int(meta[1])
            if kind == _KIND_DRAINED:
                rep.drained = True
                rep.draining = True
                self._retire_replica_series(rep)
                continue
            if kind == _KIND_READY:
                self._note_ready(rep, msg)
                continue
            if kind == _KIND_STATS:
                rep.stats = _parse_stats(msg["times"], meta[6])
                # the piggybacked shared-block key index + the fleet
                # hit-rate deltas (cumulative counters so a dropped
                # STATS window loses nothing)
                k = int(msg["tokens"][0])
                rep.prefix_keys = {
                    int(x) for x in msg["tokens"][1:1 + k]
                }
                hits = float(msg["times"][8])
                looks = float(msg["times"][9])
                ph, pl = rep.last_prefix
                if hits >= ph and looks >= pl:
                    self._fleet_hits += hits - ph
                    self._fleet_lookups += looks - pl
                rep.last_prefix = (hits, looks)
                rep.stats["ttft_p99_s"] = round(
                    float(msg["times"][7]), 4
                )
                self._health.note_stats(rep.idx, rep.stats)
                continue
            if kind == _KIND_SHIP:
                # a prefill worker staged this request's KV blocks:
                # hand the manifest to a decode replica (next pump)
                req_id = int(meta[0])
                rep.outstanding.pop(req_id, None)
                self._adopt_q.append(
                    (req_id, int(meta[7]), int(meta[8]),
                     int(msg["tokens"][0]))
                )
                # a ship IS the prefill worker's completion
                self._health.note_ship(rep.idx)
                continue
            if kind == _KIND_REQUEUE:
                # a draining replica handed this request back with
                # its generated tail: store the tail and requeue —
                # the next dispatch resumes from it.  Popping the
                # request from ``outstanding`` here keeps the later
                # death-requeue from double-queueing it.
                req_id = int(meta[0])
                rep.outstanding.pop(req_id, None)
                req = self._reqs.get(req_id)
                if req is None or req_id in self._completed:
                    continue
                n_tail = int(meta[3])
                req.resume_tokens = (
                    msg["tokens"][:n_tail].astype(np.int32).copy()
                )
                req.resume_logprobs = (
                    msg["logprobs"][:n_tail].copy()
                )
                self._free_ship_slot(req_id)
                with self._lock:
                    self._dispatch_q.appendleft(req_id)
                continue
            if kind == _KIND_REJECT:
                req_id = int(meta[0])
                rep.outstanding.pop(req_id, None)
                self._complete(
                    req_id,
                    {
                        "error": (
                            f"request {req_id} rejected by replica "
                            f"{rep.idx} (scheduler refused the "
                            "submit — see the replica log)"
                        )
                    },
                )
                continue
            if kind != _KIND_RESULT:
                continue
            req_id = int(meta[0])
            total = int(meta[2])
            rep.outstanding.pop(req_id, None)
            req = self._reqs.get(req_id)
            latency = (
                time.monotonic() - req.submit_t if req else 0.0
            )
            result = {
                "tokens": msg["tokens"][:total].copy(),
                "new_tokens": int(meta[3]),
                "finish_reason": _FINISH_NAMES.get(
                    int(meta[4]), "length"
                ),
                "version": int(meta[5]),
            }
            if self._capture:
                result["logprobs"] = (
                    msg["logprobs"][: int(meta[3])].copy()
                )
            if rep.pt_ring is not None:
                # put before the RESULT, so it is there: the request's
                # own positions leave the slot in one copy
                rows = rep.pt_ring.peek()
                if rows is not None:
                    if int(rows["meta"][0]) == req_id:
                        result["per_token"] = {
                            name: _copied(a[:total])
                            for name, a in rows.items() if name != "meta"
                        }
                    rep.pt_ring.release()
            self._complete(
                req_id,
                {
                    **result,
                    "latency_s": latency,
                    "worker_latency_s": float(msg["times"][0]),
                    "ttft_s": float(msg["times"][1]),
                    "tbt_p99_s": float(msg["times"][4]),
                    "queue_wait_s": float(msg["times"][5]),
                    "replica": rep.idx,
                },
            )
            from dlrover_tpu.observability.metrics import (
                record_serving_latency,
            )

            slo = dict(
                ttft_s=float(msg["times"][1]),
                tbt_p99_s=float(msg["times"][4]),
                e2e_s=latency,
                queue_wait_s=float(msg["times"][5]),
            )
            record_serving_latency(replica=str(rep.idx), **slo)
            self._health.note_result(rep.idx, **slo)

    def _retire_replica_series(self, rep: _Replica):
        """Zero-and-drop a dead/drained replica's per-replica series
        (the mirrored gauges AND the SLO histograms) from this
        process's registry: a frozen last value on ``/metrics`` reads
        as a live replica — absence reads as the death it is."""
        try:
            from dlrover_tpu.observability.metrics import get_registry

            get_registry().retire_series({"replica": str(rep.idx)})
        except Exception as e:  # noqa: BLE001 - never block dispatch
            logger.warning(
                "serving series retirement failed for replica %d: %s",
                rep.idx, e,
            )

    def _handle_death(self, rep: _Replica):
        rep.alive = False
        self._retire_replica_series(rep)
        rc = rep.proc.returncode
        requeue = [
            rid for rid in rep.outstanding
            if rid not in self._completed
        ]
        rep.outstanding.clear()
        if requeue:
            logger.warning(
                "serving replica %d exited (rc=%s): requeueing %d "
                "in-flight request(s)", rep.idx, rc, len(requeue),
            )
        for rid in requeue:
            # a requeued request re-dispatches fresh; its staged
            # blocks (if any) die with the reservation
            self._free_ship_slot(rid)
        with self._lock:
            for rid in reversed(requeue):
                self._dispatch_q.appendleft(rid)

    def _req_msg(self, req: _InFlight, ship_mode: int = 0,
                 ship_slot: int = -1, first_token: int = -1,
                 n_blocks: int = 0, route: int = 0) -> Dict:
        """One v4 request-ring payload."""
        resume = req.resume_tokens
        n_resume = int(resume.size)
        prompt_buf = np.zeros((self._max_seq_len,), np.int32)
        prompt_buf[: req.prompt.size] = req.prompt
        lp_buf = np.zeros((self._max_seq_len,), np.float32)
        if n_resume:
            prompt_buf[
                req.prompt.size:req.prompt.size + n_resume
            ] = resume
            lp = np.full((n_resume,), np.nan, np.float32)
            got = req.resume_logprobs[:n_resume]
            lp[: got.size] = got
            lp_buf[:n_resume] = lp
        return {
            "meta": np.asarray(
                [req.req_id, req.prompt.size, req.max_new, req.seed,
                 RING_SCHEMA_VERSION, int(req.submit_wall * 1e9),
                 1 if req.slo_class == "interactive" else 0,
                 _tenant_hash(req.tenant), ship_mode, ship_slot,
                 first_token, n_blocks, route, n_resume],
                np.int64,
            ),
            "prompt": prompt_buf,
            "resume_lp": lp_buf,
        }

    def _route(self, req: _InFlight, targets: List[_Replica]):
        """Pick the serving replica: deepest matching prefix chain
        (each replica's shared-block key index rides its STATS
        piggyback) among replicas within ``imbalance_cap`` of the
        least-loaded — affinity must never starve a replica — else
        the PR-13 least-outstanding rule.  Returns ``(replica,
        route_code)``."""
        if not req.digests or len(targets) < 2:
            return least_outstanding(targets), 0
        floor = min(len(r.outstanding) for r in targets)
        best, best_depth = None, 0
        for r in sorted(
            targets, key=lambda r: (len(r.outstanding), r.idx)
        ):
            if len(r.outstanding) > floor + self._imbalance_cap:
                continue
            depth = 0
            for d in req.digests:
                if d not in r.prefix_keys:
                    break
                depth += 1
            if depth > best_depth:
                best, best_depth = r, depth
        if best is not None:
            return best, 1
        return least_outstanding(targets), 0

    def _dispatch_loop(self):
        from dlrover_tpu.observability.metrics import record_serving

        self._last_gauges = 0.0
        while not self._closed:
            try:
                moved = self._dispatch_once(record_serving)
            except Exception as e:  # noqa: BLE001 - a dead dispatcher
                # thread wedges EVERY caller; log and keep pumping
                logger.error("serving dispatcher error: %s", e)
                moved = 0
            if not moved:
                time.sleep(0.002)

    def _dispatch_once(self, record_serving) -> int:
        """One pump: drain responses, detect deaths, route the queue,
        refresh gauges.  Returns how much moved (0 = idle tick)."""
        moved = 0
        for rep in self._replicas:
            if not rep.alive:
                continue
            moved += self._handle_responses(rep)
            if rep.proc.poll() is not None:
                # late responses may still sit in the ring
                moved += self._handle_responses(rep)
                self._handle_death(rep)
        alive = [
            r for r in self._replicas
            if r.alive and r.ready and not r.draining
        ]
        prefill_alive = [r for r in alive if r.role == "prefill"]
        targets = [r for r in alive if r.role != "prefill"]
        if self._dispatch_q and not any(
            r.alive for r in self._replicas
        ):
            # nothing is left (or starting) that could ever serve
            # these: fail them now, not at the request timeout
            with self._lock:
                stranded = list(self._dispatch_q)
                self._dispatch_q.clear()
            for req_id in stranded:
                self._complete(
                    req_id,
                    {
                        "error": (
                            f"request {req_id} cannot be served: no "
                            "replica is alive (exit codes "
                            f"{[r.proc.returncode for r in self._replicas]})"
                        )
                    },
                )
            moved += len(stranded)
        # relay staged manifests first: a parked manifest holds an
        # arena slot and its request's clock has been running since
        # submit — the decode replica splices the blocks and starts a
        # pure token loop
        while self._adopt_q and targets:
            req_id, slot, n_blocks, first = self._adopt_q[0]
            if req_id in self._completed or req_id not in self._reqs:
                self._adopt_q.popleft()
                self._free_ship_slot(req_id)
                continue
            req = self._reqs[req_id]
            rep = least_outstanding(targets)
            ok = rep.req_ring.try_put(
                self._req_msg(req, ship_mode=2, ship_slot=slot,
                              first_token=first, n_blocks=n_blocks,
                              route=2),
                timeout=0.02,
            )
            if not ok:
                break  # ring full; retry next pump
            self._adopt_q.popleft()
            rep.outstanding[req_id] = req
            moved += 1
        while self._dispatch_q and targets:
            with self._lock:
                if not self._dispatch_q:
                    break
                req_id = self._dispatch_q.popleft()
            if req_id in self._completed:
                continue
            req = self._reqs[req_id]
            req.attempts += 1
            if req.attempts > self.MAX_ATTEMPTS:
                self._complete(
                    req_id,
                    {
                        "error": (
                            f"request {req_id} failed after "
                            f"{self.MAX_ATTEMPTS} dispatch "
                            "attempts (replicas keep dying)"
                        )
                    },
                )
                continue
            use_ship = (
                prefill_alive
                and self._ship_arena is not None
                and self._ship_free
                and req.prompt.size >= self._min_ship_prompt
                # a resumed request's tail predates any shipped
                # blocks; serve it end-to-end on a decode replica
                and not req.resume_tokens.size
            )
            if use_ship:
                slot = self._ship_free.pop()
                rep = least_outstanding(prefill_alive)
                ok = rep.req_ring.try_put(
                    self._req_msg(req, ship_mode=1, ship_slot=slot,
                                  route=2),
                    timeout=0.02,
                )
                if not ok:
                    self._ship_free.append(slot)
                    req.attempts -= 1  # ring full is not a failure
                    with self._lock:
                        self._dispatch_q.appendleft(req_id)
                    break
                req.ship_slot = slot
            else:
                rep, route = self._route(req, targets)
                ok = rep.req_ring.try_put(
                    self._req_msg(req, route=route), timeout=0.02,
                )
                if not ok:
                    req.attempts -= 1  # ring full is not a failure
                    with self._lock:
                        self._dispatch_q.appendleft(req_id)
                    break
            rep.outstanding[req_id] = req
            moved += 1
        now = time.monotonic()
        if now - self._last_gauges >= 1.0:
            self._last_gauges = now
            record_serving(
                replica="dispatcher",
                tokens_per_s=None,
                queue_depth=len(self._dispatch_q),
                kv_blocks_used=None,
                p99_latency_s=self._latency.quantile(0.99),
            )
            # fleet-level prefix hit rate: windowed over the STATS
            # deltas accumulated since the last tick with lookups in
            # it (an idle window keeps the last value instead of
            # flapping to 0)
            if self._fleet_lookups > 0:
                self._fleet_hit_rate = (
                    self._fleet_hits / self._fleet_lookups
                )
                self._fleet_hits = 0.0
                self._fleet_lookups = 0.0
            record_serving(
                replica="fleet",
                tokens_per_s=None,
                queue_depth=None,
                kv_blocks_used=None,
                prefix_hit_rate=self._fleet_hit_rate,
            )
            # mirror each live replica's newest STATS into THIS
            # process's registry so the engine's /metrics carries the
            # fleet (the per-replica series retirement on death/drain
            # acts here)
            for rep in self._replicas:
                if not rep.alive or rep.drained or not rep.stats:
                    continue
                st = rep.stats
                record_serving(
                    replica=str(rep.idx),
                    tokens_per_s=st.get("tokens_per_s"),
                    queue_depth=st.get("queue_depth"),
                    kv_blocks_used=st.get("kv_blocks_used"),
                    kv_utilization=st.get("kv_utilization"),
                    preemptions=st.get("preemptions"),
                    prefix_hit_rate=st.get("prefix_hit_rate"),
                    accepted_tokens_per_step=st.get(
                        "accepted_per_step"
                    ),
                )
        # internally throttled to the derivation interval
        self._health.evaluate(
            [
                {
                    "idx": r.idx,
                    "alive": r.alive,
                    "drained": r.drained,
                    "outstanding": len(r.outstanding),
                    "role": r.role,
                    **r.stats,
                }
                for r in self._replicas
            ]
        )
        return moved

    # --------------------------------------------------------- status
    def _slo_quantile(self, metric: str, q: float) -> float:
        """Fleet quantile of one registry SLO histogram, merged across
        every ``replica`` series (identical bucket bounds — counts
        sum)."""
        from dlrover_tpu.observability.metrics import (
            Histogram,
            get_registry,
        )

        series = get_registry().histogram_series(metric)
        merged = None
        for hist in series.values():
            if merged is None:
                merged = Histogram(hist.bounds)
            if merged.bounds != hist.bounds:
                continue  # foreign layout; never ours
            for i, c in enumerate(hist.counts):
                merged.counts[i] += c
            merged.count += hist.count
            merged.sum += hist.sum
        return merged.quantile(q) if merged is not None else 0.0

    def status(self) -> Dict:
        """The serving pane: what ``scripts/top.py`` renders.  ``slo``
        carries the fleet quantiles off the registry histograms and
        ``health`` the ServingHealthEngine's newest per-replica
        derivations."""
        return {
            "replicas": [
                dict(
                    {
                        "idx": r.idx,
                        "alive": r.alive,
                        "drained": r.drained,
                        "outstanding": len(r.outstanding),
                        "role": r.role,
                    },
                    **r.stats,
                )
                for r in self._replicas
            ],
            "queue_depth": len(self._dispatch_q),
            "completed": self._completed_total + len(self._completed),
            "p50_latency_s": round(self._latency.quantile(0.5), 4),
            "p99_latency_s": round(self._latency.quantile(0.99), 4),
            "version": self._version,
            "slo": {
                "ttft_p99_s": round(self._slo_quantile(
                    "dlrover_tpu_serving_ttft_seconds", 0.99
                ), 4),
                "tbt_p99_s": round(self._slo_quantile(
                    "dlrover_tpu_serving_tbt_seconds", 0.99
                ), 4),
                "e2e_p99_s": round(self._slo_quantile(
                    "dlrover_tpu_serving_e2e_seconds", 0.99
                ), 4),
                "queue_wait_p99_s": round(self._slo_quantile(
                    "dlrover_tpu_serving_queue_wait_seconds", 0.99
                ), 4),
                "fleet_prefix_hit_rate": round(
                    self._fleet_hit_rate, 4
                ),
            },
            "health": self._health.snapshot(),
        }

    def close(self):
        if self._closed:
            return
        timeout = gen_close_timeout_s()
        for rep in self._replicas:
            if rep.proc.poll() is None:
                rep.proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        for rep in self._replicas:
            remain = max(deadline - time.monotonic(), 0.1)
            try:
                rep.proc.wait(timeout=remain)
            except subprocess.TimeoutExpired:
                rep.proc.kill()
        self._closed = True
        if self._dispatcher.is_alive():
            self._dispatcher.join(timeout=5.0)
        for rep in self._replicas:
            rep.req_ring.close(unlink=True)
            rep.resp_ring.close(unlink=True)
            if rep.pt_ring is not None:
                rep.pt_ring.close(unlink=True)
        if self._ship_arena is not None:
            try:
                self._ship_arena.close()
                self._ship_arena.unlink()
            except Exception:  # noqa: BLE001 - already gone is fine
                pass
        self._shm.close(unlink=True)


if __name__ == "__main__":
    sys.exit(worker_main())
