"""Readers of the metrics an expert layer whose every expert is hit
adds, beside the readers that are there (which this file leaves as they
are).

Like ``readers.py``: a reader returns None when there is nothing to
read — a program without the labels (the parent of the PR that added
them), a configuration without expert layers — and the harness leaves
the metric out of the line; nothing here raises for it.
"""

from readers_spans import _window_spans


def rows_per_hit_expert(ctx):
    """Computed assignments an expert that was given at least one row, a
    layer and committed decode step: summed ``expert_rows_local`` (the
    assignments that fell on the held experts, over the expert layers)
    over summed ``experts_hit`` (distinct held experts with a row, the
    MEAN over the expert layers) times the configuration's expert
    layers, over the ``serve_step`` records that start in the window
    and carry both."""
    cfg = ctx["cell"]["config"]
    layers = cfg.get("num_hidden_layers", 0) - cfg.get("num_dense_layers", 0)
    both = [
        s["labels"] for s in _window_spans(ctx, "serve_step")
        if "expert_rows_local" in s["labels"] and "experts_hit" in s["labels"]
    ]
    hit = sum(labels["experts_hit"] for labels in both) * layers
    if hit <= 0:
        return None
    return sum(labels["expert_rows_local"] for labels in both) / hit
