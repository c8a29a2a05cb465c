"""Shared bench-model factory (ISSUE 20, satellite 2).

"The tiny llama the benches run": ``bench_flywheel.py``'s legs build
their model through ``bench_cfg_kwargs()`` / ``bench_model()``, with
overrides for the few axes a leg legitimately varies (size for the
publish-at-scale leg and for the drafter).

Import as ``from _bench_models import ...`` (the scripts directory is
on ``sys.path`` when any bench runs) — this is bench plumbing, not
library surface, hence the underscore.
"""

from typing import Dict, Tuple

#: the canonical bench model — identical across every bench leg that
#: does not explicitly override a knob
BASE_CFG_KW: Dict = dict(
    vocab_size=128,
    dim=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    mlp_dim=64,
    max_seq_len=128,
    remat="none",
)

#: the co-published drafter (flywheel draft mode): one layer, half
#: width — genuinely cheaper than the policy, same vocab so the
#: verify step is well-defined
DRAFT_OVERRIDES: Dict = dict(dim=16, n_layers=1, mlp_dim=32)


def bench_cfg_kwargs(**overrides) -> Dict:
    """The bench model's ``LlamaConfig`` kwargs, with overrides.
    Returns a fresh dict each call — callers mutate freely."""
    return {**BASE_CFG_KW, **overrides}


def draft_cfg_kwargs(**overrides) -> Dict:
    """Kwargs for the small drafter published alongside the policy."""
    return bench_cfg_kwargs(**{**DRAFT_OVERRIDES, **overrides})


def bench_model(seed: int = 0, **overrides) -> Tuple[object, object]:
    """Build (cfg, params) for the bench model; ``overrides`` are
    ``bench_cfg_kwargs`` knobs.  Same (seed, overrides) -> bitwise
    identical params, so two processes that each call this agree."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, init_params

    kw = bench_cfg_kwargs(**overrides)
    if isinstance(kw.get("dtype"), str):
        # same name->dtype hop the cross-process factory spec makes
        kw["dtype"] = jnp.dtype(kw["dtype"])
    cfg = LlamaConfig(**kw)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, params
