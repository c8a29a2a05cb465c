"""Readers of the metrics latent attention adds, beside the readers that
are there (which this file leaves as they are).

Like ``readers.py``: a reader returns None when there is nothing to
read — no trace, a program without the kernel or the label (the parent
of the PR that added them), a family without the functions — and the
harness leaves the metric out of the line; nothing here raises for it.
"""

from harness import family
from readers_window import _kernel_seconds, _peak, _records, lowest_run


def decode_roofline_share(ctx, pattern, bytes_fn, flops_fn, rows_label):
    """A decode attention kernel's share of ITS roofline where it sits
    near the chip's ridge: the least time the chip could take a decode
    step — the LARGER of ``bytes_fn(cfg, rows, lanes) /
    hbm_bytes_per_s`` and ``flops_fn(cfg, rows, lanes) /
    bf16_flops_per_s`` of the cell's family, ``rows`` the step's
    ``rows_label`` (the token rows its lanes really read, a layer),
    ``lanes`` its ``lanes_decode`` — over the kernel's summed time in
    the trace, in percent.  The trace is a few seconds somewhere inside
    the window, so of all runs of as many consecutive ``serve_step``
    records as the trace holds steps (the kernel's calls over the
    layers) the one that asks for least is taken
    (``readers_window.lowest_run``): a share can only read low."""
    cfg = ctx["cell"]["config"]
    fam = family(cfg)
    moved, done = getattr(fam, bytes_fn, None), getattr(fam, flops_fn, None)
    seconds = _kernel_seconds(ctx, pattern)
    steps = [
        r for r in _records(ctx, "serve_step", (rows_label, "lanes_decode"))
        if r["lanes_decode"] > 0
    ]
    if moved is None or done is None or not steps or sum(seconds) <= 0:
        return None
    hbm, peak = _peak(ctx, "hbm_bytes_per_s"), _peak(ctx, "bf16_flops_per_s")
    least = lowest_run(
        [
            max(
                moved(cfg, r[rows_label], r["lanes_decode"]) / hbm,
                done(cfg, r[rows_label], r["lanes_decode"]) / peak,
            )
            for r in steps
        ],
        len(seconds) // cfg["num_hidden_layers"],
    )
    return 100.0 * least / sum(seconds)
