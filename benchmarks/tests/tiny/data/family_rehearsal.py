"""A second model family, for the rehearsal alone: the dense family's
program parts and counts under another module name, with a plain
reference module of its own.  No line of the harness, the runners, the
worker or the serving factory names this module: ``configs/
tiny-rehearsal.json`` does, and that is all a new architecture needs.
"""

from family_dense import (
    matmul_params,
    model_kwargs,
    serving_parts,
    total_params,
    train_flops_per_token,
    train_parts,
)

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs",
    "matmul_params", "total_params", "train_flops_per_token",
]


def seeded_params(cfg, seed):
    import reference_rehearsal

    return reference_rehearsal.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_rehearsal

    return reference_rehearsal.token_logprobs(params, tokens, cfg)
