"""Inference backends for RLHF generation.

Reference parity: ``atorch/atorch/rl/inference_backend/
vllm_backend.py`` — the actor's rollout generation runs on a dedicated
serving engine whose weights are synced from the trainer.  The TPU
duals:

- :class:`JitSamplerBackend` — full-forward autoregressive sampling
  (no cache); simple, correct, O(T^2) — fine for short responses.
- :class:`KVCacheBackend` — prefill + cached decode via the model's
  ``decode_step`` (the vLLM-style serving path): a T-token generation
  costs one prefill plus T O(1)-attention steps on the training mesh.
  The prefill is a single batched forward that fills the whole cache
  in one call when the model provides one (``models.llama.prefill``);
  models without a prefill fn fall back to feeding the prompt one
  token at a time through ``lax.scan``.

Both expose ``generate(params, prompts, rng)`` and take their weights
directly from the live train state (``sync_weights`` is a pointer
swap — trainer and generator share the mesh, so there is no
cross-process weight shipping like the reference needs for vLLM).

Shape bucketing (``DLROVER_TPU_GEN_BUCKETS``, e.g. ``"16,32,64"``):
both backends jit-compile per input shape, so a stream of
distinct-length prompts used to retrace per ``[B, P]``.  With buckets
set, prompts pad up to the smallest bucket >= their length and the
REAL length rides in as a traced scalar — one compile per (batch,
bucket), and causal masking makes the padded result identical to the
exact-shape one at ANY temperature (padding sits strictly to the
right of every attended position, and the batch dim — which shapes
the sampler's noise — is never padded).  The continuous-batching
scheduler (``rl/scheduler.py``) goes further — fixed slot lanes, zero
retraces — this keeps the whole-batch backends cheap for RLHF
rollouts.
"""

from abc import ABCMeta, abstractmethod
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.common.env import gen_buckets


def bucket_len(plen: int, buckets: Tuple[int, ...]) -> int:
    """The padded length for a ``plen``-token prompt: the smallest
    bucket that fits, exact length when none does (an oversized
    prompt must still run).  ONLY the length dim buckets — padding
    the batch dim would change what ``jax.random.categorical`` draws
    per row (its noise is shaped by the full batch), breaking the
    identical-results contract at temperature > 0."""
    for bk in buckets:
        if bk >= plen:
            return bk
    return plen


def _pad_prompts(prompts, padded_len: int):
    plen = prompts.shape[1]
    return jnp.pad(prompts, ((0, 0), (0, padded_len - plen)))


class InferenceBackend(metaclass=ABCMeta):
    """Generation engine fed from the trainer's weights."""

    def __init__(self):
        self._params = None

    def sync_weights(self, params):
        """Point the backend at the trainer's current actor params (a
        reference swap — same device memory, no copy)."""
        self._params = params

    @abstractmethod
    def generate(self, prompts, rng, params=None):
        """prompts [B, P] -> tokens [B, P + max_new] (left part
        verbatim, right part sampled)."""

    def compile_count(self) -> int:
        """How many programs the backend's jitted generator holds —
        the bucket satellite's regression meter (one per bucket, not
        one per distinct ``[B, P]``)."""
        fn = getattr(self, "_compiled_fn", None)
        return -1 if fn is None else int(fn._cache_size())


class JitSamplerBackend(InferenceBackend):
    """Full-forward sampler (no KV cache)."""

    def __init__(self, forward_fn: Callable, max_new_tokens: int,
                 temperature: float = 1.0):
        super().__init__()
        from dlrover_tpu.rl.engine import ModelEngine

        self._max_new = int(max_new_tokens)
        self._sample = ModelEngine.make_sampler(
            forward_fn, max_new_tokens, temperature
        )
        self._compiled_fn = self._sample

    def generate(self, prompts, rng, params=None):
        params = params if params is not None else self._params
        prompts = jnp.asarray(prompts)
        plen = prompts.shape[1]
        buckets = gen_buckets()
        if not buckets:
            return self._sample(params, prompts, rng)
        out = self._sample(
            params,
            _pad_prompts(prompts, bucket_len(plen, buckets)),
            rng,
            jnp.int32(plen),
        )
        return out[:, : plen + self._max_new]


class KVCacheBackend(InferenceBackend):
    """Prefill + cached decode on the model's ``decode_step``.

    ``cfg`` is the model's LlamaConfig (or any config accepted by the
    supplied ``decode_step_fn``/``init_cache_fn``).  ``prefill_fn``
    (``(params, tokens, cache) -> (logits [B, P, V], cache)``)
    enables the batched single-forward prefill; the default wires the
    llama one when the default decode fns are in use, and models
    without one keep the scan path."""

    _AUTO = object()

    def __init__(
        self,
        cfg,
        max_new_tokens: int,
        temperature: float = 1.0,
        decode_step_fn: Optional[Callable] = None,
        init_cache_fn: Optional[Callable] = None,
        prefill_fn=_AUTO,
    ):
        super().__init__()
        from dlrover_tpu.models import llama

        self._cfg = cfg
        self._max_new = int(max_new_tokens)
        self._temp = temperature
        default_model = decode_step_fn is None and init_cache_fn is None
        self._decode = decode_step_fn or partial(
            llama.decode_step, cfg=cfg
        )
        self._init_cache = init_cache_fn or partial(
            llama.init_kv_cache, cfg
        )
        if prefill_fn is KVCacheBackend._AUTO:
            prefill_fn = (
                partial(llama.prefill, cfg=cfg)
                if default_model
                else None
            )
        self._prefill = prefill_fn
        self._generate = jax.jit(self._build())
        self._compiled_fn = self._generate

    def _build(self):
        decode, temp, max_new = self._decode, self._temp, self._max_new
        init_cache, cfg = self._init_cache, self._cfg
        batched_prefill = self._prefill

        def generate(params, prompts, plen, rng):
            b, padded_len = prompts.shape
            total = padded_len + max_new
            cache = init_cache(b, total)

            if batched_prefill is not None:
                # one forward fills every prompt position's K/V; the
                # last REAL position's logits seed the first sample
                all_logits, cache = batched_prefill(
                    params, prompts, cache
                )
                logits = jnp.take(all_logits, plen - 1, axis=1)
            else:
                # scan fallback: feed the prompt one position at a
                # time through the cached step, carrying the logits
                # of the last real position (padding runs past it)
                def prefill_step(carry, t):
                    cache, last = carry
                    logits, cache = decode(
                        params, prompts[:, t], cache, t
                    )
                    last = jnp.where(t == plen - 1, logits, last)
                    return (cache, last), None

                (cache, logits), _ = jax.lax.scan(
                    prefill_step,
                    (
                        cache,
                        jnp.zeros(
                            (b, cfg.vocab_size), jnp.float32
                        ),
                    ),
                    jnp.arange(padded_len),
                )

            out = jnp.concatenate(
                [
                    prompts,
                    jnp.zeros((b, max_new), dtype=prompts.dtype),
                ],
                axis=1,
            )

            def step(carry, i):
                out, cache, logits, rng = carry
                pos = plen + i
                rng, sub = jax.random.split(rng)
                if temp <= 0:
                    nxt = jnp.argmax(logits, axis=-1)
                else:
                    nxt = jax.random.categorical(
                        sub, logits / temp, axis=-1
                    )
                nxt = nxt.astype(out.dtype)
                out = jax.lax.dynamic_update_slice(
                    out, nxt[:, None], (0, pos)
                )
                logits, cache = decode(params, nxt, cache, pos)
                return (out, cache, logits, rng), None

            (out, cache, logits, rng), _ = jax.lax.scan(
                step, (out, cache, logits, rng),
                jnp.arange(max_new),
            )
            return out

        return generate

    def generate(self, prompts, rng, params=None):
        params = params if params is not None else self._params
        prompts = jnp.asarray(prompts)
        plen = prompts.shape[1]
        buckets = gen_buckets()
        if not buckets:
            return self._generate(
                params, prompts, jnp.int32(plen), rng
            )
        out = self._generate(
            params,
            _pad_prompts(prompts, bucket_len(plen, buckets)),
            jnp.int32(plen),
            rng,
        )
        return out[:, : plen + self._max_new]
