"""The dense decoder family: RMSNorm, RoPE, grouped-query attention,
SwiGLU, no biases, an untied head — ``dlrover_tpu.models.llama``'s one
block, which Mistral-7B and DeepSeek-LLM 7B both are.

A configuration file names its family (``"family": "family_dense"``): a
module beside the harness that holds everything the benchmark knows
about one architecture, so that the harness, the runners, the worker,
the serving factory and the readers name no model.  A family provides
exactly what those callers take, all keyed by the configuration file's
dict ``cfg``:

1. ``model_kwargs(cfg, max_seq_len)`` — JSON-able keywords of the
   program's model object (they ride through the engine's spec);
2. ``train_parts(cfg, seq)`` — what ``worker_train.py`` hands to
   ``auto_accelerate`` and to its first-batch check;
3. ``serving_parts(**model_kwargs)`` — the serving worker contract that
   ``serve_factory.factory`` completes with seeded weights;
4. the plain reference, which imports nothing of the program:
   ``seeded_params(cfg, seed)``, ``token_logprobs(params, tokens,
   cfg) -> [n, L-1]`` — here ``reference.py``'s.  A family whose model
   makes a discrete choice inside (a router's top-k) MAY also provide
   ``token_logprobs_forced(params, tokens, cfg, served) -> (logprobs
   [n, L-1] float32, slack [n, L-1] float32)``: ``served`` holds what
   the served side's replies carried under ``per_token``, each name an
   array ``[n, L, ...]`` (``rollout_cell.py``'s docstring; in
   ``sample.npz`` ``served_<name>``).  The float32 reference takes, at
   every position and layer, the choices the served side made in place
   of its own, computes gates and everything else itself, and reports
   per position how far — under ITS OWN float32 selection scores on
   that forced path — the worst choice taken lies below the best one
   left out: 0 where the taken set is a valid choice of its scores,
   ``inf`` where a row is malformed (an id out of range, a duplicate,
   -1 at a computed position, a wrong count).  ``reference_check.py``
   calls it where the module has it (and fails where the sample then
   holds no ``served_*`` array); the cell's traffic file must then hold
   ``routing_slack_max`` beside ``logprob_tol``, set like it from sound
   runs and controls on the chip.  Such a family's configuration file
   states under ``assumed``: ``routing_slack`` — the unit of the slack
   and why it is that — and ``served_arrays`` — ``{name: {"dtype",
   "per_position": [...]}}``, each array's shape after the position
   axis, a key of the file an entry
   (``tests/test_family_contract.py`` builds its filler from it; the
   rehearsal: ``tests/tiny/data/family_rehearsal_sparse.py``);
5. the counts: ``matmul_params(cfg)``, ``total_params(cfg)``,
   ``train_flops_per_token(cfg, seq)`` — here ``flops.py``'s.  For
   sparse experts these count the parameters a token is multiplied
   with, not every expert.  A kernel's byte or operation function for a
   roofline share belongs here too (``kernel.paged_bw_pct``, PERF.md
   section 7, will be the first).

Another architecture is another such module with its own reference and
counts, and a configuration file that names it: files only.

Importing a family imports neither JAX nor the program: the harness's
own process resolves the family of every cell it loads, and seconds of
imports there are seconds of every run's set-up.  Whoever calls a part
that needs them pays for them.
"""

from flops import matmul_params, total_params, train_flops_per_token

__all__ = [
    "model_kwargs", "train_parts", "serving_parts",
    "seeded_params", "token_logprobs",
    "matmul_params", "total_params", "train_flops_per_token",
]


def seeded_params(cfg, seed):
    import reference

    return reference.seeded_params(cfg, seed)


def token_logprobs(params, tokens, cfg):
    import reference

    return reference.token_logprobs(params, tokens, cfg)


def model_kwargs(cfg, max_seq_len):
    """Keyword arguments of the program's ``LlamaConfig`` from the
    configuration file's (Hugging Face) keys."""
    return dict(
        vocab_size=cfg["vocab_size"],
        dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_seq_len=max_seq_len,
        rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
    )


def train_parts(cfg, seq):
    from dlrover_tpu.models.llama import (
        LlamaConfig,
        forward,
        init_params,
        loss_fn,
        param_logical_axes,
    )

    model = LlamaConfig(**model_kwargs(cfg, seq))
    return dict(
        model=model,
        init_params_fn=lambda rng: init_params(rng, model),
        loss_fn=lambda params, batch: loss_fn(params, batch, model),
        param_axes=param_logical_axes(model),
        forward=lambda params, tokens: forward(params, tokens, model),
    )


def serving_parts(**model_kwargs):
    from dlrover_tpu.rl.generation_service import tiny_llama_factory

    return tiny_llama_factory(**model_kwargs)
