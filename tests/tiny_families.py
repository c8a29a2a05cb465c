"""The one place a test gets a tiny served family.

A family is named as the benchmark names it: by a configuration whose
``family`` key is the module beside the harness that holds its
architecture (``benchmarks/family_*.py``).  The tiny configurations are
the files under ``benchmarks/tests/tiny/data/configs/``; a test with a
configuration of its own (a published file cut down, a planted value)
hands the dict instead of the name.  The parts a family's factory
returns and its seeded weights are made once a process and shared:
they are immutable.  A scheduler, an engine and a pool are state and
are built where they are used, by :func:`scheduler`.

Two files that ask for the same family at the same geometry lower the
same step programs, and the run's one compile cache
(``tests/conftest.py``) hands the second what the first compiled.
``docs/testing.md`` has the rules.
"""

import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: family -> its tiny configuration file; the order ROADMAP's lowered-
#: text check walks
FAMILIES = {
    "falcon_h1": "tiny-falcon-h1.json",
    "keye_vl2": "tiny-keye-vl2.json",
    "trinity": "tiny-trinity.json",
    "olmo_hybrid": "tiny-olmo-hybrid.json",
    "deepseek_v32": "tiny-deepseek-v32.json",
    "kimi_linear": "tiny-kimi-linear.json",
    "lfm2_moe": "tiny-lfm2-moe.json",
}


def config(family):
    """The tiny configuration of ``family`` as its file holds it (a new
    dict a call: the caller may plant a value in it)."""
    with open(os.path.join(
        BENCH, "tests", "tiny", "data", "configs", FAMILIES[family]
    )) as f:
        return json.load(f)


def published(name):
    """A configuration of the benchmark itself
    (``benchmarks/configs/<name>.json``)."""
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _cfg(family):
    """``family``: a name of ``FAMILIES``, or a configuration of the
    caller's own."""
    return config(family) if isinstance(family, str) else family


def _key(family):
    """The configuration as one hashable: its JSON."""
    return json.dumps(_cfg(family), sort_keys=True)


def kwargs(family, max_seq_len, dtype="float32"):
    """The keyword arguments of the program's config object, as the
    family reads them from the configuration."""
    import harness

    cfg = _cfg(family)
    return dict(
        harness.family(cfg).model_kwargs(cfg, max_seq_len), dtype=dtype
    )


@functools.cache
def _parts(key, max_seq_len, dtype):
    import harness

    cfg = json.loads(key)
    return harness.family(cfg).serving_parts(
        **kwargs(cfg, max_seq_len, dtype)
    )


def parts(family, max_seq_len, dtype="float32"):
    """What the family's factory returns (``cfg``, the forward, the
    paged programs, the serving copy) for sequences up to
    ``max_seq_len`` in ``dtype``."""
    return _parts(_key(family), max_seq_len, dtype)


@functools.cache
def _params(key, seed, dtype):
    import harness
    import jax

    cfg = json.loads(key)
    tree = harness.family(cfg).seeded_params(cfg, seed)
    if dtype is not None:
        tree = jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)
    return tree


def params(family, seed, dtype=None):
    """The family's plain reference's weights from ``seed`` (as the
    reference makes them, or every leaf cast to ``dtype``)."""
    return _params(_key(family), seed, dtype)


def scheduler(served, geometry, weights=None, **kw):
    """A new ``ContinuousBatchingScheduler`` over ``served`` (what
    :func:`parts` returned) at ``geometry`` (``SchedulerConfig``'s
    fields), logprobs captured, its weights synced when given; ``kw``
    goes to the scheduler as it is (``events``, ``role``, ...)."""
    from dlrover_tpu.rl.scheduler import (
        ContinuousBatchingScheduler,
        SchedulerConfig,
    )

    kw.setdefault("capture_logprobs", True)
    for name in ("paged_decode_fn", "paged_prefill_fn", "serving_params_fn"):
        if name in served:
            kw.setdefault(name, served[name])
    sch = ContinuousBatchingScheduler(
        served["cfg"], SchedulerConfig(**geometry), **kw
    )
    if weights is not None:
        sch.sync_weights(weights)
    return sch
