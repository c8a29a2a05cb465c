"""A family with a ROUTER, for the rehearsal alone: what a sparse-expert
configuration's family module looks like to the reference check.  Beside
``token_logprobs`` it provides the optional ``token_logprobs_forced``
(``family_dense.py``, point 4), so ``reference_check.py`` forces the
reference onto the choices that rode in ``sample.npz`` and reports how
far they lie from the reference's own scores.

The program serves no sparse block yet, so there is no program part to
name: ``model_kwargs``, ``train_parts`` and ``serving_parts`` fail by
name, and the served side of every test of this family is a stand-in
(``tests/test_reference_check.py``).  The model's PR brings a family
whose three program parts are real.
"""

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs", "token_logprobs_forced",
    "matmul_params", "total_params",
]


def seeded_params(cfg, seed):
    import reference_rehearsal_sparse

    return reference_rehearsal_sparse.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference_rehearsal_sparse

    return reference_rehearsal_sparse.token_logprobs(params, tokens, cfg)


def token_logprobs_forced(params, tokens, cfg, served):
    import reference_rehearsal_sparse

    return reference_rehearsal_sparse.token_logprobs_forced(
        params, tokens, cfg, served
    )


def _no_program(part):
    from harness import CellFailed

    raise CellFailed(
        f"family_rehearsal_sparse has no {part}: the program serves no "
        "block of routed experts yet, so no cell can run this "
        "configuration; it rehearses the reference check alone"
    )


def model_kwargs(cfg, max_seq_len):
    _no_program("model_kwargs")


def train_parts(cfg, seq):
    _no_program("train_parts")


def serving_parts(**model_kwargs):
    _no_program("serving_parts")


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layer_dense_params(cfg):
    d = cfg["hidden_size"]
    return (
        4 * d * d + d * cfg["n_routed_experts"]
        + cfg["n_shared_experts"] * _expert_params(cfg)
    )


def matmul_params(cfg):
    """Parameters a token is multiplied with: attention, router, the
    shared expert and the ``num_experts_per_tok`` experts it is sent to
    (not every expert), and the head."""
    return (
        cfg["num_hidden_layers"] * (
            _layer_dense_params(cfg)
            + cfg["num_experts_per_tok"] * _expert_params(cfg)
        )
        + cfg["hidden_size"] * cfg["vocab_size"]
    )


def total_params(cfg):
    """Every parameter of the reference's tree."""
    d = cfg["hidden_size"]
    return (
        cfg["num_hidden_layers"] * (
            _layer_dense_params(cfg)
            + cfg["n_routed_experts"] * (_expert_params(cfg) + 1)
            + 2 * d
        )
        + 2 * d * cfg["vocab_size"] + d
    )
