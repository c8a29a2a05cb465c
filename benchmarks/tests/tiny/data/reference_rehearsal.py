"""The rehearsal family's plain reference: the dense block's arithmetic
on weights of ITS OWN seeding (the seed shifted by one).  A caller that
took weights or logprobs from ``reference.py`` by name, and not through
the configuration's family, would serve one model and check another:
the rollout cell's logprob comparison would fail.
"""

import reference
from reference import token_logprobs

__all__ = ["seeded_params", "token_logprobs"]


def seeded_params(cfg, seed):
    return reference.seeded_params(cfg, seed + 1)
