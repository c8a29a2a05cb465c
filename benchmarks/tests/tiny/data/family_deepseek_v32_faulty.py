"""``family_deepseek_v32`` with ONE PLANTED FAULT on the served side, for
the rehearsal that a cell's ``correct`` turns false on it
(``tests/test_deepseek_v32_cell.py``); which one, the configuration
file says under ``planted_fault``:

- ``indexer_bypassed``: every query reads the NEWEST ``index_topk``
  rows whatever the indexer scores.  The reference is forced onto those
  picks, so the logprobs agree: the fault shows in the selection's
  slack and nowhere else;
- ``int8_weights``: every matrix the served side multiplies with is
  rounded through int8 (one scale a tensor), the precision below the
  configuration's.

The reference, the counts and everything else are the family's own.
Never a benchmark configuration's family.
"""

import family_deepseek_v32
from family_deepseek_v32 import *  # noqa: F401,F403
from family_deepseek_v32 import __all__  # noqa: F401


def model_kwargs(cfg, max_seq_len):
    return dict(
        family_deepseek_v32.model_kwargs(cfg, max_seq_len),
        planted_fault=cfg["planted_fault"],
    )


def _newest(pa):
    import jax.numpy as jnp

    def decode_newest(qi, w, keys, seq_lens):
        at = jnp.arange(keys.shape[1], dtype=jnp.float32)[None]
        return jnp.where(at < seq_lens[:, None], at, -jnp.inf)

    def prefill_newest(qi, w, keys, start_pos, backend=None):
        at = jnp.arange(keys.shape[0], dtype=jnp.float32)[None]
        rows = (start_pos + jnp.arange(qi.shape[0]))[:, None]
        return jnp.where(at <= rows, at, -jnp.inf)

    pa.decode_index_scores = decode_newest  # this replica process only
    pa.prefill_index_scores = prefill_newest


def serving_parts(planted_fault, **model_kwargs):
    parts = family_deepseek_v32.serving_parts(**model_kwargs)
    if planted_fault == "indexer_bypassed":
        from dlrover_tpu.ops import paged_attention

        _newest(paged_attention)
    elif planted_fault == "int8_weights":
        from tolerance_probe_trinity import altered_weights

        sound = parts["serving_params_fn"]
        parts = dict(
            parts,
            serving_params_fn=lambda params: sound(
                altered_weights(params, "int8_weights")
            ),
        )
    else:
        raise ValueError(f"no planted fault {planted_fault!r}")
    return parts
