"""Readers for a model whose layers are of several kinds, beside the
readers that are there (which this file leaves as they are).

``readers_latent.decode_roofline_share`` and
``readers_window.prefill_mxu_share`` take the steps (or chunks) a trace
holds as a kernel's calls over ``num_hidden_layers``: right where every
layer calls the kernel (DeepSeek-V3.2), a quarter of the truth where one
layer in four does (Kimi Linear's latent-attention layers).  The reader
here hands such a reader the same run with the layers counted that call
the kernel — ``layers_of_kind(cfg)[kind]`` of the cell's family, whose
byte and operation functions count by that kind themselves.

Like ``readers.py``: it returns None when there is nothing to read — no
trace, a program without the kernel or the label, a family without
``layers_of_kind`` — and the harness leaves the metric out of the line;
nothing here raises for it.
"""

from harness import family, resolve


def of_kind(ctx, reader, kind, **args):
    """``reader(ctx, **args)`` (``"module:function"``) with the
    configuration's ``num_hidden_layers`` read as the number of layers
    of ``kind``."""
    cell = ctx["cell"]
    kinds = getattr(family(cell["config"]), "layers_of_kind", None)
    layers = kinds(cell["config"]).get(kind) if kinds else None
    if not layers:
        return None
    view = dict(
        ctx,
        cell=dict(cell, config=dict(cell["config"], num_hidden_layers=layers)),
    )
    return resolve(reader)(view, **args)
