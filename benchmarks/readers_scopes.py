"""Readers of the device's time by the model's part (ISSUE 38).

The program enters ``jax.named_scope`` around each part of its two hot
programs (``dlrover_tpu/observability/events.py`` ``DEVICE_SCOPES``; the
names are repeated here, because the yardstick may not move with the
program).  A scope lands on the PATH of every HLO instruction traced
inside it (``op_name``), and the profiler keeps that path with the
operation: in the ``.xplane.pb`` each device plane's event metadata
carries it as the stat ``tf_op``, e.g.

    jit(_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
        rematted_computation/mlp/dot_general:

``jax.profiler.ProfileData`` shows an event's own three stats and not
its metadata's, so the paths are read from the file itself (a few
protobuf fields, decoded below) and joined to the events by plane and
event name; the times stay ``ProfileData``'s, as in ``xplane.py``.

What a path says (``classify``):

- ROLE: the first of ``prefill`` / ``decode`` / ``verify`` on it — the
  serving step program's outer scope; none in the train step;
- PART: the innermost of ``embed``, ``attn``, ``mlp``, ``ssm``, ``head``,
  ``head_loss``, ``optimizer``, ``sample``; none: *unscoped*;
- DIRECTION, which nobody enters as a scope — JAX writes it:
  ``rematted_computation`` on the path is a ``jax.checkpoint`` replay
  (``recompute``), else ``transpose(`` a backward pass (``bwd``), else
  ``fwd``.

Every operation's SELF time (``xplane.self_times``: the layer scan's
``while`` must not swallow its body) falls under exactly one key
``<role>/<part>/<direction>`` (``-`` for none), so the keys partition
the device's busy time; a metric is the share of the keys its pattern
matches.  A fusion carries ONE path, that of the instruction the
compiler named it after, whatever else was fused into it.

Like ``readers.py``: None where there is nothing to read — no trace, no
device plane, or a program without scopes (the parent of the PR that
added them) — and nothing here raises for it.  A program without scopes
is one with under half of its busy time under any: a stray scoped
operation can be there all the same, because JAX's persistent compile
cache keys a program WITHOUT its debug info, so a tree without scopes
may load a small program (the first token's sampler) that a tree with
them compiled, paths and all.
"""

import gzip
import os
import re

ROLES = ("prefill", "decode", "verify")
PARTS = (
    "embed", "attn", "mlp", "ssm", "head", "head_loss", "optimizer",
    "sample",
)
NONE = "-"
#: the share of busy time under some scope below which a trace is read
#: as a program without scopes (the module docstring says why not 0)
SCOPED_FLOOR = 0.5
#: the stat of a device plane's event metadata that holds the path
PATH_STAT = "tf_op"

_JIT = re.compile(r"\bp?jit\([^()]*\)")  # a function's name, not a scope
_WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")


def classify(path):
    """``(role, part, direction)`` of one operation's path."""
    words = _WORD.findall(_JIT.sub("", path))
    role = next((w for w in words if w in ROLES), NONE)
    part = next((w for w in reversed(words) if w in PARTS), NONE)
    if "rematted_computation" in words:
        direction = "recompute"
    elif "transpose(" in path:
        direction = "bwd"
    else:
        direction = "fwd"
    return role, part, direction


# ------------------------------------------------ the file's own record


def _varint(buf, i):
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a view of its bytes (nothing is
    copied: a plane's lines, megabytes of events, are stepped over)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(entries):
    """The values of a protobuf map field's entries (key 1, value 2)."""
    for entry in entries:
        for num, value in _fields(entry):
            if num == 2:
                yield value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def op_paths(path):
    """``{plane name: {event name: path}}`` from an ``.xplane.pb`` (or
    ``.gz`` of one): for every plane, each event metadata's stat
    ``tf_op`` (``PATH_STAT``).

    The schema (``tsl/profiler/protobuf/xplane.proto``): ``XSpace.planes``
    1; ``XPlane.name`` 2, ``.event_metadata`` 4 and ``.stat_metadata`` 5
    (maps); ``XEventMetadata.name`` 2, ``.stats`` 5;
    ``XStatMetadata.id`` 1, ``.name`` 2; ``XStat.metadata_id`` 1,
    ``.str_value`` 5, ``.ref_value`` 7 (the id of a stat metadata whose
    name is the string)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for num, value in _fields(plane):
            if num == 2:
                name = _text(value)
            elif num == 4:
                events.append(value)
            elif num == 5:
                stats.append(value)
        stat_names = {}
        for meta in _map_values(stats):
            meta = dict(_fields(meta))
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        paths = {}
        for meta in _map_values(events):
            event_name, found = "", ""
            for num, value in _fields(meta):
                if num == 2:
                    event_name = _text(value)
                elif num == 5:
                    st = dict(_fields(value))
                    if stat_names.get(st.get(1)) != PATH_STAT:
                        continue
                    if 5 in st:
                        found = _text(st[5])
                    elif 7 in st:
                        found = stat_names.get(st[7], "")
            paths[event_name] = found
        out[name] = paths
    return out


# --------------------------------------------------------- the partition


def _trace_file(ctx):
    """The traced run's ``.xplane.pb``.  The harness keeps the parsed
    profile in ``ctx`` and not the file's path: a run's files lie under
    ``.cache/benchmarks/<cell>/`` of the checkout (``harness.run_cell``),
    emptied before every run, so the one trace there is this run's.  A
    test hands its recorded file in as ``ctx["trace_path"]``."""
    import xplane
    from harness import REPO

    if ctx.get("trace_path"):
        return ctx["trace_path"]
    run_dir = os.path.join(REPO, ".cache", "benchmarks", ctx["cell"]["name"])
    return xplane.find_xplane(run_dir) if os.path.isdir(run_dir) else None


def partition(ctx):
    """The traced window's device busy time by scope, in percent of it
    (mean over the chips), computed once a run and kept in ``ctx``:

    - ``by_key``: ``{"<role>/<part>/<direction>": pct}``, every key with
      time under it — the partition itself;
    - ``by_part``, ``by_role``, ``by_direction``: its three margins
      (``unscoped`` is ``by_part["-"]``), each summing to ``total``;
    - ``total``: the sum of all SELF times over the busy time — 100 but
      for rounding; more means an operation was counted twice;
    - ``busy_s``: the busy time (the union of the operations' intervals,
      as ``xplane.reduce`` takes it).

    None without a trace or a device plane, or where under half of the
    busy time lies under any scope (``SCOPED_FLOOR``)."""
    if "scope_partition" not in ctx:
        ctx["scope_partition"] = _partition(ctx)
    return ctx["scope_partition"]


def _partition(ctx):
    import xplane

    prof = ctx.get("trace_profile")
    path = _trace_file(ctx) if prof is not None else None
    if path is None:
        return None
    paths = op_paths(path)
    by_key, busy_ns, chips = {}, 0.0, 0
    for plane in prof.planes:
        if not re.match(xplane.DEVICE_PLANE, plane.name):
            continue
        known = paths.get(plane.name, {})
        ops = sorted(
            (
                (float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                 "/".join(classify(known.get(ev.name, ""))))
                for line in plane.lines if line.name == xplane.OP_LINE
                for ev in line.events
            ),
            key=lambda o: (o[0], -o[1]),
        )
        if not ops:
            continue
        chips += 1
        busy_ns += 1e9 * xplane.union_s(ops)
        for own, key in xplane.self_times(ops):
            by_key[key] = by_key.get(key, 0.0) + own
    scoped = sum(
        ns for key, ns in by_key.items()
        if key.split("/")[:2] != [NONE, NONE]
    )
    if busy_ns <= 0 or scoped < SCOPED_FLOOR * busy_ns:
        return None
    by_key = {
        key: 100.0 * ns / busy_ns
        for key, ns in sorted(by_key.items(), key=lambda kv: -kv[1])
        if ns > 0  # (a scan's ``while`` has no time of its own)
    }

    def margin(index):
        out = {}
        for key, pct in by_key.items():
            name = key.split("/")[index]
            out[name] = out.get(name, 0.0) + pct
        return out

    return {
        "by_key": by_key,
        "by_part": margin(1),
        "by_role": margin(0),
        "by_direction": margin(2),
        "total": sum(by_key.values()),
        "busy_s": busy_ns / 1e9 / chips,
    }


def scope_share(ctx, pattern):
    """Self time of the device operations whose key
    ``<role>/<part>/<direction>`` matches ``pattern`` (``re.search``)
    over the device's busy time in the traced window, in percent."""
    parts = partition(ctx)
    if parts is None:
        return None
    return sum(
        pct for key, pct in parts["by_key"].items() if re.search(pattern, key)
    )
