"""Paged KV cache: a block pool + per-sequence block tables.

Reference parity: vLLM's ``BlockAllocator``/block tables (PagedAttention)
— the serving path's answer to ``rl/inference.py``'s dense
``[L, B, max_len, KV, head_dim]`` slab, which reserves worst-case
memory per *batch* and cannot admit a new sequence without recompiling
or re-allocating.  Here the cache is one fixed pool of
``block_size``-token blocks (``[L, num_blocks, block_size, KV, D]``,
the layout ``ops/paged_attention.py`` gathers), sequences own integer
block lists, and admission/eviction is pure host-side bookkeeping —
the device arrays never change shape, so the decode program compiles
exactly once.

Block 0 is the NULL block: never allocated, the scatter/gather target
for inactive lanes and unwritten table entries (always masked).

Allocation is incremental (vLLM-style): admit on prompt blocks + a
small headroom, :meth:`extend` the table on demand at decode time, and
let the scheduler preempt the lowest-priority sequence when the pool
runs dry.

**Prefix caching**: a FULL prompt
block is content-addressed by a chained hash of its tokens
(:func:`prefix_block_keys`) and registered in a ref-counted
shared-block index, so N requests with a common system-prompt prefix
map the SAME physical blocks.  Sharing is read-only — a block is
immutable once full, so no copy-on-write is ever needed for the
full-block prefix (the partial tail block is always private).  A
shared block whose last holder frees it moves to a ref-count-gated
LRU cache (content retained for future hits) and is evicted back to
the free list only under allocation pressure, oldest first.

**Layers of two kinds**: a model MAY declare a window a layer
(:func:`paged_cache_config`).  Layers without one keep the pool and the
tables above, to the byte; layers WITH one keep only the positions a
step can still read, in blocks of their own under a second table a
lane — a ring that :class:`WindowBlocks`, owned by the
:class:`BlockPool`, fills as the lane advances and empties behind the
window.  At 16 lanes of 32 k positions, one full and four window layers
of 4096 cost 2.39 + 1.62 GB where one table for all five would cost
11.9.

**Tokens that keep no keys and values**: a model whose attention reads
ONE compressed row a token for every head (a latent) MAY say that it
pages no ``k`` / ``v`` at all (``pages_kv = False``).  The pool then
holds its ``paged_leaves()`` alone — no zero-width stand-in, no unread
``v`` — and everything a block id names (bytes, shipping regions,
labels) follows from those leaves.

**Layers that keep no keys**: a model whose lanes keep state beside
their pages MAY say, a layer, which of the two that layer keeps
(``layer_keeps()``: ``"pages"``, ``"state"`` or ``"both"``).  ``k`` /
``v`` then hold the layers that page and each state slab the layers
that hold state, each indexed by the layer's rank among its kind: nine
recurrent layers and three attention layers page 3 layers' blocks and
hold 9 layers' state, not 12 of each.

Accounting (the observatory's ``kv_blocks_used`` /
``kv_utilization`` gauges read these):

- ``used_blocks`` / ``free_blocks`` — pool occupancy;
- ``internal_fragmentation()`` — reserved-but-unfilled token slots as
  a share of reserved capacity (block-granularity waste, the quantity
  paging keeps bounded at < ``block_size`` tokens/sequence where the
  dense slab wastes ``max_len - len`` per sequence);
- ``utilization()`` — filled cache positions as a share of the whole
  pool's capacity (the number incremental admission pushes toward
  1.0);
- ``prefix_hits`` / ``prefix_queries`` — shared-block lookups.
"""

import hashlib
import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

#: the lanes of the device's vector registers: the minor axis its memory
#: is tiled in (a narrower minor axis is padded to it)
ROW_LANES = 128


def blocks_needed(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache positions."""
    return -(-max(int(n_tokens), 0) // int(block_size))


def pool_can_ever_hold(num_blocks: int, block_size: int,
                       n_tokens: int) -> bool:
    """Can a pool of ``num_blocks`` (INCLUDING its null block 0) ever
    hold one sequence of ``n_tokens``?  The ONE definition of the
    worst-case admission guard — the scheduler's
    ``submit`` and the serving dispatcher's ``submit`` must agree, or
    an oversized request slips past the dispatcher and kills the
    replica whose scheduler then refuses it."""
    return blocks_needed(n_tokens, block_size) <= int(num_blocks) - 1


@dataclass(frozen=True)
class PagedCacheConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    num_blocks: int  # pool size INCLUDING the null block
    block_size: int = 16
    dtype: object = jnp.bfloat16
    # what a LANE keeps per layer beside its paged K/V, as the model
    # declares it: ``((leaf, shape, dtype), ...)``.  Empty for a block
    # whose only state is keys and values.
    lane_state: Tuple[Tuple[str, Tuple[int, ...], object], ...] = ()
    max_slots: int = 0  # lanes the ``lane_state`` slabs are made for
    # what a TOKEN keeps per layer beside its K and V, as the model
    # declares it, same form: a leaf that lives in the same blocks
    # under the same tables (an index key a token).  Empty for a block
    # whose pages are keys and values only.
    paged_leaves: Tuple[Tuple[str, Tuple[int, ...], object], ...] = ()
    # a window a layer, as the model declares it (``None``: the layer
    # keeps every position).  Empty for a model whose layers all do.
    # Layers WITH a window have blocks of their own (``wk``, ``wv``)
    # under a second table a lane of ``window_table_blocks`` entries,
    # sized by :func:`window_table_blocks`
    layer_windows: Tuple[Optional[int], ...] = ()
    window_table_blocks: int = 0
    # what each layer keeps, as the model declares it: ``"pages"``,
    # ``"state"`` (the ``lane_state`` leaves and no keys) or ``"both"``.
    # Empty for a model whose layers all keep whatever it declares.
    layer_keeps: Tuple[str, ...] = ()
    # a block's K (or V) lies ``[block_size * KV, head_dim]``, its rows
    # side by side, and not ``[block_size, KV, head_dim]``: as the model
    # declares it, whose step programs address the pool
    flat_pages: bool = False
    # the model pages ``k`` and ``v``; False for one whose tokens keep
    # the ``paged_leaves`` alone (a latent row a token, no per-head
    # keys or values): ``n_kv_heads`` / ``head_dim`` are then 0 and
    # unread
    pages_kv: bool = True
    # ``((leaf, minor), ...)``: the paged leaves whose blocks lie in
    # rows of ``minor`` elements, as the model declares them
    leaf_rows: Tuple[Tuple[str, int], ...] = ()

    @property
    def n_window_layers(self) -> int:
        return sum(w is not None for w in self.layer_windows)

    @property
    def n_state_layers(self) -> int:
        """Layers of the ``lane_state`` slabs (none for a model of
        pages only, all of them for one that declares no
        ``layer_keeps``)."""
        if not self.lane_state:
            return 0
        return self.n_layers - self.layer_keeps.count("pages")

    @property
    def n_paged_layers(self) -> int:
        """Layers that keep pages of either kind (``k`` / ``v`` or
        ``wk`` / ``wv``)."""
        return self.n_layers - self.layer_keeps.count("state")

    @property
    def n_full_layers(self) -> int:
        """Layers of the ``k`` / ``v`` pool: those that page and keep
        every position (all of them for a model that declares neither a
        window nor ``layer_keeps``)."""
        return self.n_paged_layers - self.n_window_layers

    @property
    def window(self) -> Optional[int]:
        """The window of the layers that have one."""
        return next((w for w in self.layer_windows if w is not None), None)

    @property
    def window_blocks(self) -> int:
        """Blocks of one window layer, the null block included: every
        lane's allotment, which no lane can exceed."""
        if not self.n_window_layers:
            return 0
        return self.max_slots * self.window_table_blocks + 1

    @property
    def paged_names(self) -> Tuple[str, ...]:
        """Every leaf of the pool that is paged: ``k``, ``v`` (of a
        model that pages them) and the model's further ones — what a
        block ship carries."""
        return (("k", "v") if self.pages_kv else ()) + self.leaf_names

    @property
    def leaf_names(self) -> Tuple[str, ...]:
        """The ``paged_leaves`` by name."""
        return tuple(name for name, _, _ in self.paged_leaves)

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # block 0 is the null block

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache positions."""
        return blocks_needed(n_tokens, self.block_size)


def window_table_blocks(window: int, prefill_chunk: int,
                        block_size: int) -> int:
    """Entries of a lane's table over the window layers' blocks: a
    step that writes positions ``[start, end)`` reads none before
    ``start - window + 1``, and the widest step is a prefill chunk, so
    a lane never holds more than ``window - 1 + prefill_chunk``
    positions there — that many blocks, and one more because the span
    need not start at a block's first token (4096 / 2048 / 16: 385)."""
    return blocks_needed(window - 1 + prefill_chunk, block_size) + 1


def paged_cache_config(
    model_cfg, num_blocks: int, block_size: int, max_slots: int,
    prefill_chunk: int = 0,
) -> PagedCacheConfig:
    """The cache a model asks for, from its own declaration — the ONE
    place a model config is read for it (policy and draft pools
    alike).  A serving model's config provides the paged K/V geometry
    (``n_layers``, ``n_kv_heads``, ``head_dim``, ``dtype``) and MAY
    provide ``lane_state() -> {leaf: (shape, dtype)}``: state a lane
    keeps per layer that is no page of a sequence — it has no
    positions, cannot be shared by prefix or rebuilt from blocks, and
    every token overwrites it (a recurrent state, a convolution's
    tail).  Such leaves live in the pool as ``[n_layers, max_slots,
    *shape]`` slabs indexed by lane; :class:`BlockPool` never sees
    them.

    It MAY also provide ``paged_leaves() -> {leaf: (shape, dtype)}``:
    what a TOKEN keeps per layer beside its K and V — the opposite
    kind: it has a position, so it lives in the pool as ``[n_layers,
    num_blocks, block_size * prod(shape)]`` in the SAME blocks under the
    same tables as ``k`` and ``v``, is shared by prefix with its block,
    shipped with it and freed with it (a learned sparse attention's
    index key).  :class:`BlockPool` hands out block ids and never knew
    what a block holds.

    And it MAY provide ``layer_windows() -> (window | None, ...)``, one
    entry a layer: a layer with a window reads, at query position
    ``t``, the keys ``t - window < s <= t`` only, so its pages behind
    the window are dead.  Layers without one keep the pool above and
    its tables, to the byte; layers WITH one get blocks of their own,
    ``wk``, ``wv`` ``[window layers, max_slots * W + 1, block_size, KV,
    D]`` with ``W`` = :func:`window_table_blocks` (from the window,
    ``prefill_chunk`` and ``block_size``: nothing else sizes it), and a
    SECOND table a lane of ``W`` entries used as a ring — the block of
    positions ``[b * block_size, (b + 1) * block_size)`` sits at entry
    ``b % W`` — which :class:`WindowBlocks` (owned by the
    :class:`BlockPool`) fills as the lane advances and empties behind
    the window.  Such blocks are never shared by prefix or shipped.

    Last, a model that declares ``lane_state()`` MAY provide
    ``layer_keeps() -> ("pages" | "state" | "both", ...)``, one entry a
    layer: a ``"state"`` layer keeps the lane-state leaves and NO keys
    (a recurrent layer between attention layers), a ``"pages"`` layer
    keys and no state, ``"both"`` — every layer's default — both.  ``k``
    / ``v`` (and the ``paged_leaves``) are then ``[layers that page,
    ...]`` and each lane-state slab ``[layers that hold state,
    max_slots, ...]``; a step program addresses either by the layer's
    RANK among its kind, as it does ``wk`` / ``wv``.  This is the one
    per-layer declaration beside ``layer_windows()``, and the two
    cannot disagree: a layer with a window keeps ``"pages"`` (a model
    with windows declares no lane state at all).  A declaration that
    says what the default says leaves the config as it was.

    One thing about the LAYOUT of a page is the model's to say as well,
    because its step programs address the pool: ``flat_pages = True`` —
    a block's K (or V) lies ``[block_size * KV, head_dim]``, the same
    bytes in the same order as ``[block_size, KV, head_dim]`` (row ``t *
    KV + h`` is token ``t`` of KV head ``h``: the view the paged kernels
    take of any pool).  For a KV head count that is no multiple of the
    chip's sublane tile (30): there ``[block_size, 30, head_dim]`` is
    padded to 32 in memory, and turning it into the kernels' view moves
    the whole pool.

    And ``kv_row_heads = r`` — a row of the pool holds ``r`` KV heads
    side by side, ``k``, ``v`` ``[.., KV / r, r * head_dim]``: again the
    same bytes in the same order, so a token's keys are written as they
    come, and the paged kernels see ``KV / r`` heads of ``r * head_dim``
    (``ops/paged_attention.row_queries`` says what is left to the model:
    ``models/lfm2_moe.py``, two heads of 64 a row).  For heads narrower
    than the device's 128 lanes: a minor axis of 64 is padded to 128 —
    the pool then takes twice its bytes — or laid out otherwise, and the
    kernels are built for rows of 128.  A model whose ``head_dim`` is no
    multiple of ``ROW_LANES`` although whole rows of its heads exist
    (``ROW_LANES / head_dim`` divides ``n_kv_heads``) and that does not
    declare them is REFUSED by name; narrower heads that fill no row (a
    test's sizes) pass, and a declared row is either ``ROW_LANES`` wide
    or the token's every head.

    A model whose attention reads one compressed row a token for every
    head declares ``pages_kv = False``: its tokens keep NO per-head keys
    and values, so the pool holds its ``paged_leaves()`` alone
    (``n_kv_heads`` / ``head_dim`` are not read) — a ``v`` of equal size
    that is never read would double the cache.  Such a model declares at
    least one paged leaf and no windows.  It MAY declare ``lane_state()``
    — with ``layer_keeps()``, every layer ``"pages"`` or ``"state"``
    (``models/kimi_linear.py``: recurrent layers that keep a state slab
    and no row, between latent-attention layers that keep a row a token
    and no state): the paged leaves are then ``[layers that page, ...]``
    and each slab ``[layers that hold state, max_slots, ...]``, each
    addressed by the layer's rank among its kind as ``k`` / ``v`` and
    the slabs of a model that pages them are.  ``"both"`` stays refused
    for such a model (no step program addresses a layer's row AND its
    slab), and so does ``layer_keeps()`` without lane state (every
    layer keeps the paged leaves).

    Any model MAY say, for some of its paged leaves, in rows of how many
    elements a block lies (``paged_leaf_rows() -> {leaf: minor}``): the
    leaf is then ``[layers, num_blocks, block_size * width / minor,
    minor]`` — the same bytes in the same order as the flat layout, with
    ``minor`` (a multiple of the device's 128 lanes; a block shorter
    than a row is one row) the minor axis, so that the pool viewed as
    rows (merging the leading axes: free) is read row by row through a
    gather.  A minor axis of a token's own width that is no multiple of
    128 (576, 64) is one the device pads or lays out blocks-minor, and
    every program then copies the leaf."""

    def declared(method):
        method = getattr(model_cfg, method, None)
        return tuple(
            (name, tuple(shape), dtype)
            for name, (shape, dtype) in (method() if method else {}).items()
        )

    leaves, paged = declared("lane_state"), declared("paged_leaves")
    names = [name for name, _, _ in leaves + paged]
    clash = sorted(
        {n for n in names if names.count(n) > 1} | ({"k", "v"} & set(names))
    )
    if clash:
        raise ValueError(
            f"lane_state / paged_leaves leaf name(s) {clash} are taken "
            "(``k`` and ``v`` are the paged K/V pool's)"
        )
    pages_kv = bool(getattr(model_cfg, "pages_kv", True))
    windows = getattr(model_cfg, "layer_windows", None)
    windows = tuple(windows()) if windows else ()
    keeps = getattr(model_cfg, "layer_keeps", None)
    keeps = tuple(keeps()) if keeps else ()
    if not pages_kv:
        for ok, what in (
            (bool(paged), "and declares no paged_leaves(): it caches nothing"),
            (not any(w is not None for w in windows),
             "beside layer_windows(): the window layers' blocks are wk / wv"),
            (bool(leaves) or not keeps,
             "beside layer_keeps() without lane_state(): every layer "
             "keeps the paged leaves"),
            (not leaves or bool(keeps),
             "beside lane_state() without layer_keeps(): say which layers "
             "keep the state slabs and which the paged leaves"),
            (not leaves or "both" not in keeps,
             "beside lane_state() with a layer that keeps \"both\": a "
             "layer keeps the paged leaves or the state slabs"),
        ):
            if not ok:
                raise ValueError(
                    f"a model that pages no k / v (pages_kv false) {what}"
                )
    leaf_rows = getattr(model_cfg, "paged_leaf_rows", None)
    leaf_rows = dict(leaf_rows() if leaf_rows else {})
    widths = {name: math.prod(shape) for name, shape, _ in paged}
    for name, minor in leaf_rows.items():
        if name not in widths:
            raise ValueError(
                f"paged_leaf_rows(): {name!r} is no paged leaf "
                f"({sorted(widths)})"
            )
        # a block shorter than a row (a test's) is one row
        minor = math.gcd(block_size * widths[name], minor)
        if minor % widths[name] and widths[name] % minor:
            raise ValueError(
                f"paged_leaf_rows(): rows of {minor} hold no whole token "
                f"of {name!r} ({widths[name]}) and no token whole rows"
            )
        leaf_rows[name] = minor
    leaf_rows = tuple(leaf_rows.items())
    n_kv, head_dim = 0, 0
    if pages_kv:
        n_kv, head_dim = model_cfg.n_kv_heads, model_cfg.head_dim
        row_heads = int(getattr(model_cfg, "kv_row_heads", 1))
        fit = ROW_LANES // head_dim if ROW_LANES % head_dim == 0 else 0
        if row_heads == 1 and fit > 1 and n_kv % fit == 0:
            raise ValueError(
                f"head_dim {head_dim}: a pool whose minor axis is "
                f"{head_dim} of the device's {ROW_LANES} lanes is padded "
                f"to twice its bytes or more; declare kv_row_heads = {fit} "
                f"(a row of {fit} KV heads side by side) and read it "
                "through ops/paged_attention.row_queries / row_outputs"
            )
        if row_heads < 1 or n_kv % row_heads or (
            row_heads > 1 and row_heads != n_kv
            and row_heads * head_dim != ROW_LANES
        ):
            raise ValueError(
                f"kv_row_heads {row_heads}: rows of {row_heads} of the "
                f"{n_kv} KV heads of {head_dim} are neither {ROW_LANES} "
                "lanes wide nor a token's every head"
            )
        n_kv, head_dim = n_kv // row_heads, head_dim * row_heads
    table_blocks = 0
    if not any(w is not None for w in windows):
        windows = ()
    else:
        if len(windows) != model_cfg.n_layers:
            raise ValueError(
                f"layer_windows() names {len(windows)} layers of "
                f"{model_cfg.n_layers}"
            )
        sizes = {int(w) for w in windows if w is not None}
        if len(sizes) != 1 or min(sizes) < 1 or prefill_chunk < 1:
            raise ValueError(
                f"layer_windows(): one window >= 1 for the layers that "
                f"have one (got {sorted(sizes)}) and the prefill chunk "
                f"that sizes their tables (got {prefill_chunk})"
            )
        if all(w is not None for w in windows) or paged or leaves:
            raise ValueError(
                "layer_windows(): at least one layer keeps every "
                "position (the sequence's pool and tables are its), and "
                "a model with windows declares no lane_state / "
                "paged_leaves"
            )
        table_blocks = window_table_blocks(
            min(sizes), prefill_chunk, block_size
        )
    if keeps:
        kinds = ("pages", "state", "both")
        for ok, what in (
            (len(keeps) == model_cfg.n_layers,
             f"names {len(keeps)} layers of {model_cfg.n_layers}"),
            (all(k in kinds for k in keeps),
             f"takes one of {kinds} a layer (got {sorted(set(keeps))})"),
            (bool(leaves) or all(k == "pages" for k in keeps),
             "names layers that keep state, and the model declares no "
             "lane_state()"),
            (not leaves or any(k != "pages" for k in keeps),
             "leaves no layer to keep the lane_state() the model declares"),
            (any(k != "state" for k in keeps),
             "leaves no layer that keeps pages (the sequence's pool and "
             "tables are theirs)"),
            (all(k == "pages" for k, w in zip(keeps, windows)
                 if w is not None),
             "disagrees with layer_windows(): a layer with a window "
             "keeps pages"),
        ):
            if not ok:
                raise ValueError(f"layer_keeps() {what}")
        if all(k == ("both" if leaves else "pages") for k in keeps):
            keeps = ()  # what every layer does undeclared
    return PagedCacheConfig(
        n_layers=model_cfg.n_layers,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        num_blocks=num_blocks,
        block_size=block_size,
        dtype=model_cfg.dtype,
        lane_state=leaves,
        max_slots=max_slots,
        paged_leaves=paged,
        layer_windows=windows,
        window_table_blocks=table_blocks,
        layer_keeps=keeps,
        flat_pages=bool(getattr(model_cfg, "flat_pages", False)),
        pages_kv=pages_kv,
        leaf_rows=leaf_rows,
    )


def init_block_pool(cfg: PagedCacheConfig) -> Dict[str, jnp.ndarray]:
    """The device-side pool, stacked on the layer dim like the params:
    ``k``, ``v`` ``[L, num_blocks, block_size, KV, head_dim]``, one
    zeroed ``[L, max_slots, *shape]`` slab per ``lane_state`` leaf and
    one zeroed ``[L, num_blocks, block_size * prod(shape)]`` per
    ``paged_leaves`` leaf (token ``t`` of a block at ``[t * width, (t +
    1) * width)`` of its row).  Where the model declares windows,
    ``k``, ``v`` hold the layers WITHOUT one (in layer order) and
    ``wk``, ``wv`` ``[window layers, window_blocks, block_size, KV,
    head_dim]`` the others'; where it declares ``layer_keeps``, ``k``,
    ``v`` (or, of a model that pages neither, its paged leaves) hold
    the layers that page and each slab the layers that hold state, both
    in layer order; where it declares ``flat_pages``, a
    block's rows lie side by side: ``[L, num_blocks, block_size * KV,
    head_dim]``.  A model that pages no ``k`` / ``v`` gets its paged
    leaves alone; a leaf declared in rows of ``minor`` lies ``[L,
    num_blocks, block_size * width / minor, minor]``."""
    shape = (
        cfg.n_full_layers,
        cfg.num_blocks,
        cfg.block_size,
        cfg.n_kv_heads,
        cfg.head_dim,
    )
    if cfg.flat_pages:
        shape = shape[:2] + (cfg.block_size * cfg.n_kv_heads, cfg.head_dim)
    pool = {}
    if cfg.pages_kv:
        pool["k"] = jnp.zeros(shape, dtype=cfg.dtype)
        pool["v"] = jnp.zeros(shape, dtype=cfg.dtype)
    if cfg.n_window_layers:
        wshape = (cfg.n_window_layers, cfg.window_blocks) + shape[2:]
        pool["wk"] = jnp.zeros(wshape, dtype=cfg.dtype)
        pool["wv"] = jnp.zeros(wshape, dtype=cfg.dtype)
    for name, leaf_shape, dtype in cfg.lane_state:
        pool[name] = jnp.zeros(
            (cfg.n_state_layers, cfg.max_slots) + leaf_shape, dtype=dtype
        )
    for name, leaf_shape, dtype in cfg.paged_leaves:
        # a block's rows side by side in ONE minor axis: a minor axis
        # of a token's own width (64) is one the device pads or lays
        # out blocks-minor, and every program then copies the leaf
        # — unless the model reads it row by row and says in rows of
        # how many lanes a block lies
        block = cfg.block_size * math.prod(leaf_shape)
        minor = dict(cfg.leaf_rows).get(name)
        pool[name] = jnp.zeros(
            shape[:2] + (
                (block,) if minor is None else (block // minor, minor)
            ),
            dtype=dtype,
        )
    return pool


def lane_state_nbytes(pool: Dict[str, jnp.ndarray],
                      cfg: PagedCacheConfig) -> int:
    """Bytes of the per-lane state slabs of a pool (0 for a pool of
    pages only)."""
    return sum(int(pool[name].nbytes) for name, _, _ in cfg.lane_state)


def prefix_block_keys(tokens, block_size: int) -> List[str]:
    """Content keys for the FULL blocks of a token stream: key ``i``
    is a chained hash over blocks ``0..i`` (position-dependent by
    construction — two prompts share block ``i`` iff their first
    ``(i + 1) * block_size`` tokens are identical)."""
    import numpy as np

    toks = np.asarray(tokens, np.int32).reshape(-1)
    keys: List[str] = []
    h = hashlib.sha1()
    for start in range(0, toks.size - block_size + 1, block_size):
        h.update(toks[start:start + block_size].tobytes())
        keys.append(h.hexdigest())
    return keys


def block_nbytes(pool: Dict[str, jnp.ndarray],
                 leaves: Sequence[str]) -> int:
    """Bytes ONE block id names over ``leaves``
    (``PagedCacheConfig.paged_names``) and all their layers: what a
    live block costs, and what a ship of it carries."""
    return sum(region_nbytes_per_block(pool, name) for name in leaves)


def region_nbytes_per_block(pool: Dict[str, jnp.ndarray],
                            leaf: str = "k") -> int:
    """Bytes one block occupies in ONE stream (``k``, the same as
    ``v``; or a further paged ``leaf``) across all layers — the unit
    the ship-arena slot sizing is quoted in.  Both ends of a ship must
    agree on this number (same model config => same pool shape), and it
    is derived from the pool itself so a dtype or head-dim change can
    never desynchronize them."""
    return int(pool[leaf].nbytes // pool[leaf].shape[1])


def extract_block_regions(
    pool: Dict[str, jnp.ndarray], block_ids: Sequence[int],
    leaves: Sequence[str] = ("k", "v"),
):
    """Pull the contiguous ``[L, n_blocks, block_size, KV, head_dim]``
    tiles for ``block_ids`` out of the device pool as host numpy
    arrays (k and v) — the prefill side of a KV block ship.  Full
    blocks are immutable, so the copy is a consistent snapshot; the
    bytes are bit-exact pool content (no dtype round trip).  ``leaves``
    (``PagedCacheConfig.paged_names`` for a model that pages more than
    K and V) names what is pulled: one region a leaf, in that order.

    Blocks are pulled one at a time with a *traced* index
    (``dynamic_index_in_dim``) so the gather compiles once per pool
    shape and is reused for every block id and every region length —
    a fancy-index gather would recompile per distinct ``len(block_ids)``
    and stall the prefill worker's loop mid-ship."""
    import numpy as np
    from jax import lax

    return tuple(
        np.stack(
            [
                np.asarray(
                    lax.dynamic_index_in_dim(
                        pool[name], jnp.int32(b), axis=1, keepdims=False
                    )
                )
                for b in block_ids
            ],
            axis=1,
        )
        for name in leaves
    )


def insert_block_regions(
    pool: Dict[str, jnp.ndarray],
    block_ids: Sequence[int],
    *regions,
    leaves: Sequence[str] = ("k", "v"),
) -> Dict[str, jnp.ndarray]:
    """Splice shipped block tiles into the receiving pool at
    ``block_ids`` (freshly allocated there) — the decode side of a KV
    block ship.  Returns the updated pool dict.  The regions must be
    the ``[L, n, block_size, KV, head_dim]`` layout
    :func:`extract_block_regions` produced, one a name of ``leaves``
    and in that order; dtype is preserved so the inserted blocks are
    bitwise-identical attention inputs.

    Blocks are spliced one at a time with a *traced* index
    (``dynamic_update_index_in_dim``) so the scatter compiles once per
    pool shape and is reused for every block id and region length — a
    fancy-index ``.at[ids].set`` recompiles per distinct
    ``len(block_ids)``, which stalls the decode replica's token loop
    (seconds of XLA compile) the first time each prompt length adopts."""
    import numpy as np
    from jax import lax

    if len(regions) != len(leaves):
        raise ValueError(
            f"{len(regions)} region(s) for the leaves {tuple(leaves)}"
        )
    out = dict(pool)
    for name, region in zip(leaves, regions):
        leaf, region = out[name], np.asarray(region)
        for j, bid in enumerate(block_ids):
            leaf = lax.dynamic_update_index_in_dim(
                leaf, jnp.asarray(region[:, j], leaf.dtype),
                jnp.int32(bid), axis=1,
            )
        out[name] = leaf
    return out


class OutOfBlocksError(RuntimeError):
    """The pool cannot satisfy an allocation — admission control
    should have checked :meth:`BlockPool.can_allocate` first, or
    preempted a running sequence."""


class DoubleFreeError(RuntimeError):
    """A block id was returned to the free list twice.  Freeing loudly
    beats corrupting the LIFO free list into handing one block to two
    sequences — the scatter/gather would silently interleave their
    K/V (e.g. an evict racing a drain-requeue)."""


@dataclass
class _SeqAlloc:
    blocks: List[int] = field(default_factory=list)
    filled_tokens: int = 0  # cache positions actually written
    shared_prefix: int = 0  # leading blocks held via the shared index


class WindowBlocks:
    """Host-side accounting of the blocks of the layers WITH a window
    (one id names a block in every such layer, as a pool block id does
    in every layer without).  A sequence holds the blocks of the
    positions a step may still read or is about to write, and nothing
    behind its window: :meth:`advance` is called before every step
    with the first position the step can read and the end of what it
    writes, gives back every block wholly before the first, and takes
    blocks up to the second.  A lane's table is a RING of
    ``table_blocks`` entries: the block of logical index ``b`` (its
    positions are ``[b * block_size, (b + 1) * block_size)``) sits at
    entry ``b % table_blocks``; an entry whose block was given back
    names the null block 0.  The live span never exceeds the ring
    (:func:`window_table_blocks`), so two live blocks never meet in one
    entry, and the pool holds ``max_slots`` rings: it cannot run dry.

    LIFO free list, as :class:`BlockPool`'s: a block given back is
    re-issued first — to whichever lane asks next, so a stale entry
    reads ANOTHER sequence's keys, never zeros."""

    def __init__(self, cfg: PagedCacheConfig):
        self.cfg = cfg
        self.table_blocks = cfg.window_table_blocks
        self._free: List[int] = list(range(cfg.window_blocks - 1, 0, -1))
        # seq -> [first live logical block, one past the last, ring]
        self._seqs: Dict[int, list] = {}
        self.allocated = 0  # blocks taken, ever
        self.released = 0  # blocks given back BEHIND a live window
        self.peak_live = 0

    @property
    def live_blocks(self) -> int:
        return self.cfg.window_blocks - 1 - len(self._free)

    def advance(self, seq_id: int, first_read: int, end_write: int) -> bool:
        """Before a step of ``seq_id`` that reads no position before
        ``first_read`` and writes up to ``end_write`` (exclusive);
        whether the sequence's ring changed."""
        bs, ring_len = self.cfg.block_size, self.table_blocks
        lo = max(int(first_read), 0) // bs
        hi = blocks_needed(end_write, bs)
        if hi - lo > ring_len:
            raise ValueError(
                f"seq {seq_id}: positions [{first_read}, {end_write}) "
                f"span {hi - lo} blocks > the lane's allotment of "
                f"{ring_len} over the window layers"
            )
        st = self._seqs.get(seq_id)
        if st is None:
            st = self._seqs[seq_id] = [lo, lo, [0] * ring_len]
        first, last, ring = st
        for b in range(first, min(lo, last)):
            self._free.append(ring[b % ring_len])
            ring[b % ring_len] = 0
            self.released += 1
        first = max(first, lo)
        for b in range(max(last, first), hi):
            ring[b % ring_len] = self._free.pop()
            self.allocated += 1
        changed = (first, max(last, hi)) != (st[0], st[1])
        st[0], st[1] = first, max(last, hi)
        self.peak_live = max(self.peak_live, self.live_blocks)
        return changed

    def live_range(self, seq_id: int) -> Tuple[int, int]:
        """Logical blocks ``[first, end)`` the sequence holds."""
        first, last, _ = self._seqs[seq_id]
        return first, last

    def table_row(self, seq_id: int) -> List[int]:
        """The sequence's ring, ``table_blocks`` entries (zeros for a
        sequence that holds nothing yet)."""
        st = self._seqs.get(seq_id)
        return list(st[2]) if st else [0] * self.table_blocks

    def free(self, seq_id: int) -> int:
        st = self._seqs.pop(seq_id, None)
        if st is None:
            return 0
        first, last, ring = st
        for b in range(first, last):
            self._free.append(ring[b % self.table_blocks])
        return last - first

    def stats(self) -> Dict[str, int]:
        return {
            "window_blocks_live": self.live_blocks,
            "window_blocks_peak": self.peak_live,
            "window_blocks_allocated": self.allocated,
            "window_blocks_released": self.released,
        }


class BlockPool:
    """Host-side block accounting (free list + per-sequence tables +
    the ref-counted shared-block index).

    Pure bookkeeping — device memory is the fixed-size pool from
    :func:`init_block_pool`; this class only decides which block ids a
    sequence owns.  LIFO free list: a just-freed block is re-issued
    first, which keeps the hot working set small.
    """

    def __init__(self, cfg: PagedCacheConfig):
        self.cfg = cfg
        # block 0 reserved as the null block
        self._free: List[int] = list(range(cfg.num_blocks - 1, 0, -1))
        self._free_set = set(self._free)
        self._seqs: Dict[int, _SeqAlloc] = {}
        # shared-block index: content key <-> block id, per-block
        # refcount, and the LRU of refcount-0 cached blocks
        self._shared_by_key: Dict[str, int] = {}
        self._shared_key_of: Dict[int, str] = {}
        self._ref: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.alloc_count = 0
        self.free_count = 0
        self.peak_used = 0
        self.prefix_hits = 0  # full-block lookups answered shared
        self.prefix_queries = 0  # full-block lookups attempted
        # the blocks of the layers with a window, where the model has
        # any: a second allocator, freed with the sequence
        self.window: Optional[WindowBlocks] = (
            WindowBlocks(cfg) if cfg.n_window_layers else None
        )

    # ---------------------------------------------------------- queries
    @property
    def used_blocks(self) -> int:
        """Blocks held by LIVE sequences.  Refcount-0 cached shared
        blocks are excluded — their content is retained for prefix
        hits but they are reclaimable on demand, i.e. not leaked."""
        return (
            self.cfg.usable_blocks - len(self._free) - len(self._lru)
        )

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def available_blocks(self) -> int:
        """Blocks an allocation can claim: truly free plus refcount-0
        shared blocks the LRU would evict under pressure."""
        return len(self._free) + len(self._lru)

    @property
    def cached_shared_blocks(self) -> int:
        return len(self._lru)

    @property
    def live_sequences(self) -> int:
        return len(self._seqs)

    def can_allocate(self, n_tokens: int) -> bool:
        return self.cfg.blocks_for(n_tokens) <= len(self._free)

    def blocks_of(self, seq_id: int) -> List[int]:
        return list(self._seqs[seq_id].blocks)

    def covered_tokens(self, seq_id: int) -> int:
        """Cache positions the sequence's current table can hold."""
        return len(self._seqs[seq_id].blocks) * self.cfg.block_size

    def internal_fragmentation(self) -> float:
        """Reserved-but-unfilled cache slots / reserved slots (0.0
        when nothing is allocated)."""
        reserved = sum(
            len(s.blocks) * self.cfg.block_size
            for s in self._seqs.values()
        )
        if reserved == 0:
            return 0.0
        filled = sum(s.filled_tokens for s in self._seqs.values())
        return 1.0 - filled / reserved

    def utilization(self) -> float:
        """Filled cache positions / whole-pool capacity — shared
        blocks count once (physical occupancy, capped at 1.0)."""
        cap = self.cfg.usable_blocks * self.cfg.block_size
        if cap <= 0:
            return 0.0
        filled = sum(s.filled_tokens for s in self._seqs.values())
        # shared blocks are filled once but counted by every holder;
        # subtract the duplicate holders' worth
        dup_blocks = sum(
            max(self._ref.get(b, 1) - 1, 0)
            for b in self._shared_key_of
        )
        filled -= dup_blocks * self.cfg.block_size
        return min(max(filled / cap, 0.0), 1.0)

    def prefix_hit_rate(self) -> float:
        if self.prefix_queries == 0:
            return 0.0
        return self.prefix_hits / self.prefix_queries

    def stats(self) -> Dict[str, float]:
        more = {}
        if self.window is not None:
            # the same count under the name that tells the two kinds
            # apart in a report
            more = dict(self.window.stats(), full_blocks_live=self.used_blocks)
        return {
            **more,
            # of the model's layers, how many keep pages and how many
            # per-lane state (a block id names a block in every one of
            # the former; what a block holds is not known here)
            "paged_layers": self.cfg.n_paged_layers,
            "state_layers": self.cfg.n_state_layers,
            "used_blocks": self.used_blocks,
            "free_blocks": self.free_blocks,
            "cached_shared_blocks": self.cached_shared_blocks,
            "peak_used_blocks": self.peak_used,
            "live_sequences": self.live_sequences,
            "allocs": self.alloc_count,
            "frees": self.free_count,
            "prefix_hits": self.prefix_hits,
            "prefix_queries": self.prefix_queries,
            "prefix_hit_rate": round(self.prefix_hit_rate(), 4),
            "internal_fragmentation": round(
                self.internal_fragmentation(), 4
            ),
            "kv_utilization": round(self.utilization(), 4),
        }

    # -------------------------------------------------- free-list core
    def _push_free(self, block_id: int):
        if block_id in self._free_set:
            raise DoubleFreeError(
                f"block {block_id} freed twice: it is already on the "
                "free list (evict racing a drain-requeue?)"
            )
        if block_id in self._shared_key_of or block_id in self._lru:
            raise DoubleFreeError(
                f"block {block_id} freed while still in the shared "
                "index"
            )
        self._free.append(block_id)
        self._free_set.add(block_id)

    def _pop_free(self) -> int:
        block = self._free.pop()
        self._free_set.discard(block)
        return block

    def _evict_lru(self, need: int):
        """Reclaim up to ``need`` refcount-0 shared blocks (oldest
        first) back onto the free list."""
        while need > 0 and self._lru:
            block, _ = self._lru.popitem(last=False)
            key = self._shared_key_of.pop(block)
            self._shared_by_key.pop(key, None)
            self._ref.pop(block, None)
            self._push_free(block)
            need -= 1

    def _take_blocks(self, need: int) -> List[int]:
        if need > len(self._free):
            self._evict_lru(need - len(self._free))
        if need > len(self._free):
            raise OutOfBlocksError(
                f"need {need} blocks, {len(self._free)} free "
                f"({len(self._lru)} cached-shared)"
            )
        return [self._pop_free() for _ in range(need)]

    # ---------------------------------------------------- shared index
    def peek_prefix(self, keys: Sequence[str]) -> Tuple[int, int]:
        """How many leading keys the shared index could answer RIGHT
        NOW — side-effect free (no refcounts, no hit/query counters);
        the admission sizing probe.  Returns ``(hits, hits_in_lru)``:
        a hit currently parked in the refcount-0 LRU is NOT evictable
        capacity once acquired, so admission math must not count it
        both as a hit and as an available block."""
        n = in_lru = 0
        for key in keys:
            block = self._shared_by_key.get(key)
            if block is None:
                break
            n += 1
            if block in self._lru:
                in_lru += 1
        return n, in_lru

    def acquire_prefix(self, keys: Sequence[str]) -> List[int]:
        """Longest-prefix lookup in the shared-block index: returns
        the block ids of the leading keys already cached (refs bumped,
        removed from the LRU).  Every key attempted counts as a query;
        every answered one as a hit."""
        hit: List[int] = []
        for key in keys:
            self.prefix_queries += 1
            block = self._shared_by_key.get(key)
            if block is None:
                break
            self.prefix_hits += 1
            self._ref[block] = self._ref.get(block, 0) + 1
            self._lru.pop(block, None)
            hit.append(block)
        return hit

    def share_block(self, seq_id: int, block_index: int,
                    key: str) -> bool:
        """Promote one of ``seq_id``'s PRIVATE blocks (by index into
        its table) into the shared index under ``key`` — called by the
        scheduler the moment prefill fills a whole prompt block (full
        blocks are immutable, so sharing is safe from then on).
        Returns False when the key is already indexed (a concurrent
        identical prompt won the race; this copy stays private)."""
        if key in self._shared_by_key:
            return False
        block = self._seqs[seq_id].blocks[block_index]
        if block in self._shared_key_of:
            return False  # already shared (resumed re-prefill)
        self._shared_by_key[key] = block
        self._shared_key_of[block] = key
        self._ref[block] = self._ref.get(block, 0) + 1
        return True

    def _release_block(self, block: int):
        """Return one block at sequence-free time: shared blocks
        decref (refcount 0 -> LRU, content retained); private blocks
        go straight to the free list."""
        key = self._shared_key_of.get(block)
        if key is None:
            self._push_free(block)
            return
        ref = self._ref.get(block, 0) - 1
        if ref < 0:
            raise DoubleFreeError(
                f"shared block {block} released below refcount 0"
            )
        self._ref[block] = ref
        if ref == 0:
            self._lru[block] = None
            self._lru.move_to_end(block)

    # ------------------------------------------------------- lifecycle
    def allocate(
        self,
        seq_id: int,
        n_tokens: int,
        extra_blocks: int = 0,
        prefix_blocks: Optional[List[int]] = None,
    ) -> List[int]:
        """Reserve blocks for ``n_tokens`` cache positions (plus
        ``extra_blocks`` growth headroom): the scheduler passes the
        prompt plus a small headroom and grows on demand via
        :meth:`extend`.
        ``prefix_blocks`` (already acquired via
        :meth:`acquire_prefix`) become the leading table entries; only
        the remainder is newly allocated."""
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already allocated")
        prefix = list(prefix_blocks or [])
        need = max(
            self.cfg.blocks_for(n_tokens) - len(prefix), 0
        ) + max(int(extra_blocks), 0)
        try:
            blocks = self._take_blocks(need)
        except OutOfBlocksError:
            raise OutOfBlocksError(
                f"need {need} blocks for seq {seq_id}, "
                f"{len(self._free)} free"
            ) from None
        self._seqs[seq_id] = _SeqAlloc(
            blocks=prefix + blocks,
            shared_prefix=len(prefix),
        )
        self.alloc_count += need
        self.peak_used = max(self.peak_used, self.used_blocks)
        return list(self._seqs[seq_id].blocks)

    def extend(self, seq_id: int, n_blocks: int) -> List[int]:
        """Grow a live sequence's table by ``n_blocks`` (the
        incremental-allocation decode path).  Raises
        :class:`OutOfBlocksError` when the pool (free + evictable
        shared) cannot satisfy it — the scheduler then preempts."""
        alloc = self._seqs[seq_id]
        blocks = self._take_blocks(max(int(n_blocks), 0))
        alloc.blocks.extend(blocks)
        self.alloc_count += len(blocks)
        self.peak_used = max(self.peak_used, self.used_blocks)
        return blocks

    def note_filled(self, seq_id: int, filled_tokens: int):
        """Record how many cache positions the sequence has actually
        written (drives the fragmentation/utilization figures)."""
        self._seqs[seq_id].filled_tokens = int(filled_tokens)

    def free(self, seq_id: int) -> int:
        """Return a finished/evicted/preempted sequence's blocks:
        private blocks to the pool, shared blocks decref'd (a
        refcount-0 shared block parks in the LRU with its content
        intact for future prefix hits).  Raises
        :class:`DoubleFreeError` if any block would land on the free
        list twice."""
        if self.window is not None:
            self.window.free(seq_id)
        alloc = self._seqs.pop(seq_id, None)
        if alloc is None:
            return 0
        for block in reversed(alloc.blocks):
            self._release_block(block)
        # allocs/frees count OWNERSHIP churn, symmetrically: allocs =
        # blocks this sequence newly took from the pool (acquired
        # prefix hits excluded), frees = those same blocks released
        # from its ownership — whether they land on the free list or
        # park in the LRU (a later LRU eviction moves an already-
        # released block and touches neither counter).  Under this
        # definition allocs == frees after any full drain.
        self.free_count += len(alloc.blocks) - alloc.shared_prefix
        return len(alloc.blocks)

    def table_row(
        self, seq_id: int, max_blocks: int
    ) -> Optional[List[int]]:
        """The sequence's block table padded to ``max_blocks`` with
        null-block ids (the fixed-shape row the jitted decode step
        consumes)."""
        alloc = self._seqs.get(seq_id)
        if alloc is None:
            return None
        if len(alloc.blocks) > max_blocks:
            raise ValueError(
                f"seq {seq_id} owns {len(alloc.blocks)} blocks > "
                f"table width {max_blocks}"
            )
        return alloc.blocks + [0] * (max_blocks - len(alloc.blocks))
