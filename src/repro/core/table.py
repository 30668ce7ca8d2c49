"""IndexedTable — one partition of the Indexed DataFrame.

Paper §III-C: a partition is (1) a cTrie index pointing at the *latest* row
per key, (2) row batches holding the tabular data, (3) backward pointers
chaining equal-key rows.  Paper §III-E: appends snapshot the index so
divergent children share the parent's state and store only deltas.

TPU adaptation (DESIGN.md §2/§4): a partition is an ordered tuple of
**capacity-reserved arena segments**.  ``create_index`` builds segment 0
over-allocated to a power-of-two capacity class; ``append`` within the
reserved capacity is a jit-compiled fused on-device ingest — hash the
delta, write its bucket/chain planes, link parent heads, bump the
``fill`` scalar — with ZERO pytree shape change, so jitted read sites
compile once per class instead of once per version.  Capacity exhaustion
seals the tail and opens the next class (one recompile, geometric
amortization); past a segment-count threshold the table compacts.  The
pre-arena path — one exactly-sized delta segment per append, parent
segments shared by reference (the paper's persistent-data-structure
scheme; Listing 2's divergent appends with no copy-on-write) — survives
as ``append(..., mode="segment")`` and anchors the equivalence property
tests.  Non-donated arena appends are equally functional (the parent is
never touched); ``donate=True`` trades the parent for true in-place
buffer aliasing.

Row storage is batch-granular: a segment's data is ``[num_batches,
rows_per_batch, width_words] int32`` (row layout) or per-column typed arrays
(columnar layout).  ``rows_per_batch`` is the paper's Fig-5 knob.

The read hot path (probe -> chain walk -> gather) runs **fused** over the
table's stored ``Snapshot`` (core/snapshot.py, DESIGN.md §3): ragged
per-segment bucket planes (split int64 keys), one flat backward-pointer
array, and optional contiguous data for single-gather decode.  The snapshot
is part of the table's *pytree form* — ``create_index`` builds it eagerly,
``append`` extends it incrementally — so jitted call sites that take the
table as an argument trace it as leaves instead of rebuilding it in-graph.
The original segment-looped methods survive as ``*_ref`` and anchor the
parity tests.

Everything here is written to be **vmap-friendly over a leading shard
axis**: the inner segment constructor is pure (no host branching), padding
rows carry ``valid=False`` and an EMPTY key, and the overflow-doubling retry
lives in thin host wrappers.  dist/dtable.py stacks whole tables (segments
AND snapshot) across shards and vmaps these same functions.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashindex as hix
from repro.core import hashing
from repro.core import snapshot as snap_mod
from repro.core.hashindex import EMPTY_KEY, HashIndex
from repro.core.pointers import NULL_PTR, PTR_DTYPE
from repro.core.schema import Schema
from repro.core.snapshot import (FlatBlock, Snapshot, extend_snapshot,
                                 snapshot_from_segments)
# kernels only imports leaf core modules (hashing/hashindex/pointers/
# snapshot), so this does not cycle; importing here (not inside methods)
# keeps module constants from being created inside an active jit trace.
from repro.kernels import ops as kops
from repro.kernels import ref as kref

# Back-compat alias: PR-1 exported the probe-side view as ``FlatView``.
FlatView = Snapshot

# Logical (occupied-entry) index accounting, shared with the Fig-11
# benchmark so the formula lives in exactly one place:
INDEX_ENTRY_BYTES = 12   # int64 key + int32 ptr per occupied bucket slot
ROW_PTR_BYTES = 5        # int32 prev + bool valid per live row

# ---------------------------------------------------------------------------
# Segment
# ---------------------------------------------------------------------------

@partial(jax.tree_util.register_dataclass,
         data_fields=["data", "index", "prev", "valid"],
         meta_fields=["row_base", "layout"])
@dataclasses.dataclass(frozen=True)
class Segment:
    """One immutable append unit (segment 0 = the created index)."""

    data: object          # [nb, rpb, W] int32  |  dict[name -> [nb, rpb] typed]
    index: HashIndex      # delta index: key -> GLOBAL row id (latest in segment)
    prev: jax.Array       # [nb*rpb] int32 — backward ptrs, GLOBAL row ids
    valid: jax.Array      # [nb*rpb] bool — False for padding rows
    row_base: int         # global row id of this segment's row 0
    layout: str

    @property
    def capacity(self) -> int:
        return self.prev.shape[-1]

    def _row_bytes(self) -> int:
        """Bytes per row — shard-stack-agnostic (shape-tail based, so a
        dist layer's [num_shards, ...] leading axis doesn't inflate it)."""
        if self.layout == "row":
            return self.data.shape[-1] * 4
        return sum(a.dtype.itemsize for a in self.data.values())

    def data_nbytes(self, *, logical: bool = False):
        """Row-storage bytes.  ``logical=False`` (default) counts the full
        reserved planes; ``logical=True`` counts only valid rows — arenas
        over-allocate (DESIGN.md §4), and the paper's Fig-11 overhead claim
        is about logical bytes, not arena slack."""
        if logical:
            return jnp.sum(self.valid) * self._row_bytes()
        if self.layout == "row":
            return self.data.size * 4
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self.data.values())

    def index_nbytes(self, *, logical: bool = False):
        if logical:
            occupied = jnp.sum(self.index.bucket_keys != EMPTY_KEY)
            return (occupied * INDEX_ENTRY_BYTES
                    + jnp.sum(self.valid) * ROW_PTR_BYTES)
        return self.index.nbytes + self.prev.size * 4 + self.valid.size


@partial(jax.tree_util.register_dataclass,
         data_fields=["segments", "snapshot", "version", "hot"],
         meta_fields=["schema", "rows_per_batch", "layout", "slots"])
@dataclasses.dataclass(frozen=True)
class IndexedTable:
    """A fully functional (immutable) indexed partition with MVCC versions.

    ``snapshot`` is the stored read-optimized form (DESIGN.md §3): both the
    segments and the snapshot are pytree data, so the table round-trips
    through jit/vmap with the fused-path arrays as leaves.

    ``version`` is a *data leaf* (scalar int32), not treedef metadata
    (DESIGN.md §4): the arena append path bumps it on-device with zero
    pytree shape change, so successive versions stay structurally equal
    and every jitted read site taking the table as an argument keeps its
    compile-cache entry across appends.
    """

    segments: tuple[Segment, ...]
    snapshot: Snapshot
    version: jax.Array    # scalar int32 — paper §III-D MVCC version
    schema: Schema
    rows_per_batch: int
    layout: str           # "row" | "columnar"
    slots: int
    hot: object = None    # HotTracker | None — skew detection (§15)

    # -- shape facts ----------------------------------------------------------
    @property
    def capacity(self) -> int:
        return self.segments[-1].row_base + self.segments[-1].capacity

    @property
    def num_segments(self) -> int:
        return len(self.segments)

    @property
    def fill(self) -> jax.Array:
        """First unwritten global row id (scalar int32 leaf)."""
        return self.snapshot.fill

    def spare_capacity(self) -> int:
        """Reserved-but-unwritten rows left in the arena tail (host int —
        reads the ``fill`` scalar; appends are host-coordinated anyway)."""
        tail = self.segments[-1]
        return (tail.row_base + tail.capacity
                - int(jax.device_get(self.snapshot.fill)))

    def num_rows(self):
        """Valid (non-padding) rows; array under trace, int when concrete."""
        return sum(jnp.sum(s.valid) for s in self.segments)

    def data_nbytes(self, *, logical: bool = False):
        """Reserved row-storage bytes; ``logical=True`` counts valid rows
        only (Fig 11 must not be distorted by arena slack, DESIGN.md §4)."""
        return sum(s.data_nbytes(logical=logical) for s in self.segments)

    def index_nbytes(self, *, logical: bool = False):
        """Index memory overhead — the paper's Fig-11 measurement."""
        return sum(s.index_nbytes(logical=logical) for s in self.segments)

    # -- snapshot access (fused-path representation, DESIGN.md §3) -------------

    def flat_view(self) -> Snapshot:
        """The stored Snapshot for this version (a field access — the view
        is built eagerly by ``create_index`` and extended by ``append``)."""
        return self.snapshot

    def with_flat_data(self) -> "IndexedTable":
        """This table with the snapshot's flat data materialized.

        Use before passing the table as a jit *argument* to call sites that
        decode rows (``gather_rows`` / ``joins.indexed_lookup``): with the
        data on board, the whole fused pipeline traces as stored leaves —
        zero in-graph rebuilds.  Appends carry materialized data forward.
        This is the ONLY way the stored pytree gains the data leaf — host
        reads never mutate the table's structure (jit caches and captured
        treedefs stay valid).
        """
        if self.snapshot.data is not None:
            return self
        return dataclasses.replace(
            self, snapshot=dataclasses.replace(self.snapshot,
                                               data=self._flat_data()))

    def _flat_data(self):
        """Flat data for single-gather decode.  Prefers the snapshot's
        stored copy; otherwise builds it once and caches it on the host
        instance (``_flatdata``, deliberately OUTSIDE the pytree: the
        table's structure must not change as a side effect of a read)."""
        d = self.snapshot.data
        if d is not None:
            return d
        d = getattr(self, "_flatdata", None)
        if d is None:
            d = snap_mod.flat_data_from_segments(self.segments, self.schema,
                                                 self.layout)
            leaves = jax.tree_util.tree_leaves(d)
            if not any(isinstance(a, jax.core.Tracer) for a in leaves):
                object.__setattr__(self, "_flatdata", d)
        return d

    # -- point operations ------------------------------------------------------
    #
    # The default path is the FUSED one: probe -> chain walk -> gather runs
    # against the Snapshot in one pass (Pallas kernel on TPU, vectorized flat
    # gathers elsewhere).  The *_ref methods keep the original segment-looped
    # code as the semantic reference the parity tests sweep against.

    def probe_latest(self, keys, *, fused: bool = True) -> jax.Array:
        """Global row id of the *latest* row per key (NULL_PTR if absent).

        Probes delta indexes newest -> oldest and takes the first hit —
        the cTrie-snapshot read path of paper §III-E.
        """
        if not fused:
            return self.probe_latest_ref(keys)
        return kops.fused_probe(keys, self.snapshot)

    def probe_latest_ref(self, keys) -> jax.Array:
        """Segment-looped reference: one full probe per delta index."""
        keys = jnp.asarray(keys, jnp.int64)
        out = jnp.full(keys.shape, NULL_PTR, PTR_DTYPE)
        for seg in reversed(self.segments):
            hit = hix.probe(seg.index, keys)
            out = jnp.where(out == NULL_PTR, hit, out)
        return out

    def gather_prev(self, rids, *, fused: bool = True) -> jax.Array:
        """prev[rid] across segments (NULL for NULL/out-of-range input)."""
        if not fused:
            return self.gather_prev_ref(rids)
        prev = self.snapshot.prev
        cap = self.snapshot.capacity
        rids = jnp.asarray(rids, PTR_DTYPE)
        in_range = (rids >= 0) & (rids < self.snapshot.fill)
        got = prev[jnp.clip(rids, 0, cap - 1)]
        return jnp.where(in_range, got, NULL_PTR)

    def gather_prev_ref(self, rids) -> jax.Array:
        """Segment-looped reference: re-scans every segment per call."""
        rids = jnp.asarray(rids, PTR_DTYPE)
        out = jnp.full(rids.shape, NULL_PTR, PTR_DTYPE)
        for seg in self.segments:
            local = rids - seg.row_base
            in_seg = (local >= 0) & (local < seg.capacity)
            got = seg.prev[jnp.clip(local, 0, seg.capacity - 1)]
            out = jnp.where(in_seg, got, out)
        return out

    def lookup(self, keys, max_matches: int, *, fused: bool = True):
        """[Q] keys -> ([Q, max_matches] global row ids newest-first,
        truncated flags).  Paper's point-lookup: cTrie probe + backward-
        pointer traversal — fused into one pass over the Snapshot."""
        if not fused:
            return self.lookup_ref(keys, max_matches)
        return kops.fused_lookup(keys, self.snapshot,
                                 max_matches=max_matches)

    def lookup_ref(self, keys, max_matches: int):
        """Segment-looped reference lookup (the pre-fusion hot path)."""
        head = self.probe_latest_ref(keys)

        def step(cur, _):
            nxt = jnp.where(cur >= 0, self.gather_prev_ref(cur), NULL_PTR)
            return nxt, cur

        last, rows = jax.lax.scan(step, head, None, length=max_matches)
        return jnp.moveaxis(rows, 0, 1), last >= 0

    def gather_rows(self, rids, names=None, *, fused: bool = True) -> dict:
        """Decode rows for global row ids (zeros where rid out of range)."""
        if not fused:
            return self.gather_rows_ref(rids, names=names)
        data = self._flat_data()
        rids = jnp.asarray(rids, PTR_DTYPE)
        # fill-masked: reserved-but-unwritten arena lanes never decode
        # (with donation they may alias retired buffers — DESIGN.md §4)
        in_range = (rids >= 0) & (rids < self.snapshot.fill)
        safe = jnp.clip(rids, 0, self.capacity - 1)
        if self.layout == "row":
            flat = jnp.where(in_range[..., None], data[safe], 0)
            return self.schema.decode_rows(flat, names=names)
        out = {}
        for name in (names or self.schema.names):
            col = self.schema.column(name)
            out[name] = jnp.where(in_range, data[name][safe],
                                  jnp.zeros((), col.jnp_dtype))
        return out

    def gather_rows_ref(self, rids, names=None) -> dict:
        """Segment-looped reference: one masked pass per segment."""
        rids = jnp.asarray(rids, PTR_DTYPE)
        if self.layout == "row":
            w = self.schema.width_words
            flat = jnp.zeros(rids.shape + (w,), jnp.int32)
            for seg in self.segments:
                local = rids - seg.row_base
                in_seg = (local >= 0) & (local < seg.capacity)
                lc = jnp.clip(local, 0, seg.capacity - 1)
                got = seg.data.reshape(seg.capacity, w)[lc]
                flat = jnp.where(in_seg[..., None], got, flat)
            return self.schema.decode_rows(flat, names=names)
        out = {}
        for name in (names or self.schema.names):
            col = self.schema.column(name)
            acc = jnp.zeros(rids.shape, col.jnp_dtype)
            for seg in self.segments:
                local = rids - seg.row_base
                in_seg = (local >= 0) & (local < seg.capacity)
                lc = jnp.clip(local, 0, seg.capacity - 1)
                arr = seg.data[name].reshape(-1)
                acc = jnp.where(in_seg, arr[lc], acc)
            out[name] = acc
        return out

    def scan_column(self, name: str):
        """Full column scan (baseline path) -> (values, valid)."""
        parts, valid = [], []
        for seg in self.segments:
            if self.layout == "row":
                w = self.schema.width_words
                flat = seg.data.reshape(seg.capacity, w)
                vals = self.schema.decode_rows(flat, names=(name,))[name]
            else:
                vals = seg.data[name].reshape(-1)
            parts.append(vals)
            valid.append(seg.valid)
        return jnp.concatenate(parts), jnp.concatenate(valid)


# ---------------------------------------------------------------------------
# Segment construction (vmap-friendly core + host wrappers)
# ---------------------------------------------------------------------------

ARENA_GROWTH = 2
DEFAULT_COMPACT_THRESHOLD = 8


def pad_to_batches(n: int, rows_per_batch: int) -> int:
    nb = max(1, -(-n // rows_per_batch))
    return nb * rows_per_batch


def capacity_class(n_rows: int, rows_per_batch: int,
                   growth: int = ARENA_GROWTH) -> int:
    """Reserved arena capacity for ``n_rows``: the smallest power-of-two
    number of row batches covering ``growth * n_rows`` (DESIGN.md §4).
    Power-of-two classes mean a growing table visits O(log n) distinct
    plane shapes — one read-site recompile per class, geometrically
    amortized — and ``growth`` leaves headroom so appends land in the
    zero-shape-change in-place ingest instead of promoting immediately."""
    need = max(1, int(n_rows)) * growth
    nb = max(1, -(-need // rows_per_batch))
    return (1 << (nb - 1).bit_length()) * rows_per_batch


def prepare_cols(cols: dict, schema: Schema, rows_per_batch: int,
                 valid=None, *, min_capacity: int = 0):
    """Left-pack valid rows, pad columns to a batch multiple (at least
    ``min_capacity`` rows); returns (padded cols, valid, cap).

    Packing keeps the arena invariant — written lanes are exactly
    ``[0, valid_count)`` — and is a stable permutation, so per-key MVCC
    chain order (append order) is preserved.
    """
    n = int(next(iter(cols.values())).shape[0])
    cap = max(pad_to_batches(n, rows_per_batch),
              pad_to_batches(min_capacity, rows_per_batch)
              if min_capacity else 0)
    pad = cap - n
    if valid is not None:
        valid = jnp.asarray(valid, bool)
        order = jnp.argsort(~valid, stable=True)   # valid first, order kept
        cols = {c.name: jnp.asarray(cols[c.name], c.jnp_dtype)[order]
                for c in schema.columns}
        valid = valid[order]
    out = {}
    for c in schema.columns:
        a = jnp.asarray(cols[c.name], c.jnp_dtype)
        out[c.name] = jnp.pad(a, (0, pad))
    if valid is None:
        valid = jnp.ones((n,), bool)
    valid = jnp.pad(jnp.asarray(valid, bool), (0, pad))
    return out, valid, cap


def make_segment_arrays(cols: dict, valid, parent_heads, schema: Schema, *,
                        row_base: int, rows_per_batch: int, layout: str,
                        num_buckets: int, slots: int):
    """Pure segment constructor (jit/vmap-friendly).

    cols         : dict of [cap]-padded typed columns
    valid        : [cap] bool
    parent_heads : [cap] int32 — parent's latest row per key (NULL if none /
                   no parent); the MVCC chain link (paper §III-E)
    Returns (Segment, overflow scalar).
    """
    cap = int(valid.shape[0])
    nb = cap // rows_per_batch
    keys = jnp.where(valid, jnp.asarray(cols[schema.key], jnp.int64),
                     EMPTY_KEY)

    if layout == "row":
        words = schema.encode_rows(cols)
        data = words.reshape(nb, rows_per_batch, schema.width_words)
    else:
        data = {c.name: jnp.asarray(cols[c.name], c.jnp_dtype)
                        .reshape(nb, rows_per_batch)
                for c in schema.columns}

    gids = jnp.arange(cap, dtype=PTR_DTYPE) + PTR_DTYPE(row_base)
    bk, bp, prev_rows, prev_vals, overflow = hix._build_arrays(
        keys, gids, valid, num_buckets, slots)
    index = HashIndex(bk, bp, num_buckets, slots)

    prev = jnp.full((cap,), NULL_PTR, PTR_DTYPE)
    prev = prev.at[prev_rows - PTR_DTYPE(row_base)].set(prev_vals,
                                                        mode="drop")
    # chain the OLDEST row per appended key into the parent's latest row
    need_link = valid & (prev == NULL_PTR) & (parent_heads != NULL_PTR)
    prev = jnp.where(need_link, parent_heads, prev)

    seg = Segment(data=data, index=index, prev=prev, valid=valid,
                  row_base=row_base, layout=layout)
    return seg, overflow


def _build_segment_retrying(cols, valid, parent_heads, schema, *, row_base,
                            rows_per_batch, layout, slots,
                            num_buckets=None, max_retries: int = 5):
    cap = int(valid.shape[0])
    nb = num_buckets or hix.suggest_num_buckets(cap, slots)
    for _ in range(max_retries):
        seg, overflow = make_segment_arrays(
            cols, valid, parent_heads, schema, row_base=row_base,
            rows_per_batch=rows_per_batch, layout=layout, num_buckets=nb,
            slots=slots)
        if int(overflow) == 0:
            return seg
        nb *= 2
    raise RuntimeError("segment index build kept overflowing")


def create_index(cols: dict, schema: Schema, *, rows_per_batch: int = 4096,
                 layout: str = "row", slots: int = hix.DEFAULT_SLOTS,
                 valid=None, reserve: int | None = None,
                 track_hot: int | None = None,
                 hot_mode: str = "topk") -> IndexedTable:
    """Paper Listing 1 ``createIndex``: build the index over a dataframe.

    In the distributed layer this is preceded by the hash-partition shuffle;
    here we build one partition.  The probe-side Snapshot is built eagerly
    as part of the table's stored form (DESIGN.md §3); flat data stays lazy.

    Segment 0 is a **capacity-reserved arena** (DESIGN.md §4): its data /
    index / pointer planes are over-allocated to the power-of-two capacity
    class of the input, fill tracked by the snapshot's ``valid_count``
    scalar (``fill``), so appends within the reserved capacity run as an
    in-place on-device ingest with zero pytree shape change.  ``reserve``
    overrides the class policy: an explicit minimum row capacity, or ``0``
    for no over-allocation (the pre-arena PR-3 write path, kept for the
    segment-chain reference and benchmarks' before/after comparison).
    """
    n = int(next(iter(cols.values())).shape[0])
    reserved = (capacity_class(n, rows_per_batch) if reserve is None
                else pad_to_batches(max(n, int(reserve), 1), rows_per_batch))
    cols_p, valid_p, cap = prepare_cols(cols, schema, rows_per_batch, valid,
                                        min_capacity=reserved)
    heads = jnp.full((cap,), NULL_PTR, PTR_DTYPE)
    seg = _build_segment_retrying(cols_p, valid_p, heads, schema, row_base=0,
                                  rows_per_batch=rows_per_batch,
                                  layout=layout, slots=slots)
    snap = snapshot_from_segments((seg,), layout, schema=schema)
    # track_hot attaches an EMPTY tracker (see with_hot: the created rows
    # are not back-counted — replay-deterministic by construction)
    hot = (None if track_hot is None
           else empty_tracker(track_hot, mode=hot_mode))
    return IndexedTable(segments=(seg,), snapshot=snap, schema=schema,
                        rows_per_batch=rows_per_batch, layout=layout,
                        version=jnp.asarray(0, jnp.int32), slots=slots,
                        hot=hot)


# ---------------------------------------------------------------------------
# Arena append: fused on-device in-place ingest (DESIGN.md §4)
# ---------------------------------------------------------------------------

def _delta_order(keys, valid):
    """Lexsort delta lanes by (key, arrival): the chain/head scaffold.

    Returns ``(order, same, is_head)`` — ``same[i]`` marks a sorted lane
    whose predecessor holds the same key (its backward pointer stays
    intra-delta), ``is_head`` the newest valid lane per key (the lane that
    lands in the bucket planes).
    """
    d = keys.shape[0]
    order = jnp.lexsort((jnp.arange(d, dtype=PTR_DTYPE), keys))
    k_s, v_s = keys[order], valid[order]
    same = jnp.concatenate(
        [jnp.zeros((1,), bool), (k_s[1:] == k_s[:-1]) & v_s[1:] & v_s[:-1]])
    is_head = jnp.concatenate(
        [k_s[1:] != k_s[:-1], jnp.ones((1,), bool)]) & v_s
    return order, same, is_head


# ---------------------------------------------------------------------------
# Hot-key tracker (skew detection, DESIGN.md §15)
# ---------------------------------------------------------------------------

DEFAULT_HOT_TOP_K = 128
SKETCH_DEPTH = 4
SKETCH_WIDTH = 1024


@partial(jax.tree_util.register_dataclass,
         data_fields=["keys", "counts", "sketch"], meta_fields=["mode"])
@dataclasses.dataclass(frozen=True)
class HotTracker:
    """Exact top-k hot-key counts maintained at ingest (DESIGN.md §15).

    Every mutable field is a DATA leaf (the §4 arena trick): the hot set
    changes across appends with ZERO pytree shape change, so the hybrid
    dispatch consuming it never retraces.  Entries live in canonical
    (count desc, key asc) order with ``EMPTY_KEY`` marking vacant slots,
    which makes the ingest-time fold idempotent: merging an all-invalid
    delta (a held ring flush) reproduces the tracker bit-for-bit.

    ``mode="topk"`` keeps Misra-Gries-style counts — exact while the
    distinct-key population fits ``top_k``, a lower bound after
    evictions (an evicted key re-enters at its fresh delta count).
    ``mode="sketch"`` adds count-min planes for unbounded streams:
    counts become CMS upper-bound estimates over the whole history, the
    candidate set is still (tracker ∪ delta heads).
    """

    keys: jax.Array    # [T] int64 — EMPTY_KEY = vacant slot
    counts: jax.Array  # [T] int64 — lower bounds (topk) / CMS estimates
    sketch: object     # [D, W] int64 count-min planes | None (topk mode)
    mode: str          # "topk" | "sketch"


def empty_tracker(top_k: int = DEFAULT_HOT_TOP_K, *, mode: str = "topk",
                  sketch_depth: int = SKETCH_DEPTH,
                  sketch_width: int = SKETCH_WIDTH,
                  num_shards: int | None = None) -> HotTracker:
    """A fresh all-vacant tracker (``num_shards`` stacks the dist leading
    axis — each shard counts its OWN ingest; routing partitions by key,
    so per-shard hot sets are disjoint and a global top-H is a flat merge
    of the per-shard arrays)."""
    if mode not in ("topk", "sketch"):
        raise ValueError(f"tracker mode must be 'topk' or 'sketch', "
                         f"got {mode!r}")
    lead = () if num_shards is None else (num_shards,)
    sketch = (jnp.zeros(lead + (sketch_depth, sketch_width), jnp.int64)
              if mode == "sketch" else None)
    return HotTracker(keys=jnp.full(lead + (top_k,), EMPTY_KEY, jnp.int64),
                      counts=jnp.zeros(lead + (top_k,), jnp.int64),
                      sketch=sketch, mode=mode)


def with_hot(table: IndexedTable, top_k: int = DEFAULT_HOT_TOP_K, *,
             mode: str = "topk", sketch_depth: int = SKETCH_DEPTH,
             sketch_width: int = SKETCH_WIDTH) -> IndexedTable:
    """Attach an empty tracker — ONE treedef change (like adding a queue),
    done before entering jitted loops.  Rows already in the table are NOT
    back-counted: the hot set accumulates from subsequent ingest only, so
    lineage replay (which re-attaches an empty tracker before replaying
    the append log) reproduces the live tracker bit-identically."""
    return dataclasses.replace(table, hot=empty_tracker(
        top_k, mode=mode, sketch_depth=sketch_depth,
        sketch_width=sketch_width))


def _seg_scan(op, vals, newrun):
    """Segmented inclusive scan: ``op`` restarts at every ``newrun`` lane,
    so a run's LAST lane holds the run's full reduction."""
    def comb(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))
    _, out = jax.lax.associative_scan(comb, (newrun, vals))
    return out


def _tracker_top(cand_k, cand_c, top_k: int, *, combine: str):
    """Combine equal candidate keys (``sum`` of exact per-delta counts;
    ``max`` when candidates are whole-history re-estimates), then keep the
    ``top_k`` entries in canonical (count desc, key asc) order.  Vacant
    (EMPTY_KEY / zero-count) lanes sort last, so the result is unique,
    permutation-invariant in the candidates, and idempotent on an
    all-vacant candidate set — a held flush cannot perturb the tracker."""
    n = cand_k.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    o = jnp.lexsort((idx, cand_k))
    k_s = cand_k[o]
    c_s = cand_c[o].astype(jnp.int64)
    newrun = jnp.concatenate([jnp.ones((1,), bool), k_s[1:] != k_s[:-1]])
    is_end = jnp.concatenate([k_s[1:] != k_s[:-1], jnp.ones((1,), bool)])
    op = jnp.add if combine == "sum" else jnp.maximum
    total = _seg_scan(op, c_s, newrun)
    live = is_end & (k_s != EMPTY_KEY) & (total > 0)
    rk = jnp.where(live, k_s, EMPTY_KEY)
    rc = jnp.where(live, total, jnp.int64(0))
    o2 = jnp.lexsort((rk, -rc))
    return rk[o2][:top_k], rc[o2][:top_k]


def _tracker_fold(hkeys, hcounts, hsketch, hot_k, hot_c):
    """Fold per-key delta head counts into the tracker arrays.

    ``hot_k``/``hot_c`` carry one lane per distinct delta key (EMPTY / 0
    elsewhere).  Returns ``(keys, counts, sketch)``; pure sorts and
    scatter-adds — safe inside the fused ingest and under vmap/shard_map.
    """
    top_k = hkeys.shape[0]
    if hsketch is not None:
        depth, width = hsketch.shape
        sk = hsketch
        for r in range(depth):
            slot = jnp.where(hot_k != EMPTY_KEY,
                             hashing.sketch_hash(hot_k, r, width),
                             jnp.int32(width))
            sk = sk.at[r, slot].add(hot_c, mode="drop")
        cand = jnp.concatenate([hkeys, hot_k])
        est = jnp.full(cand.shape, jnp.iinfo(jnp.int64).max, jnp.int64)
        for r in range(depth):
            est = jnp.minimum(est, sk[r, hashing.sketch_hash(cand, r,
                                                             width)])
        est = jnp.where(cand == EMPTY_KEY, jnp.int64(0), est)
        nk, nc = _tracker_top(cand, est, top_k, combine="max")
        return nk, nc, sk
    nk, nc = _tracker_top(jnp.concatenate([hkeys, hot_k]),
                          jnp.concatenate([hcounts, hot_c]),
                          top_k, combine="sum")
    return nk, nc, None


@jax.jit
def _tracker_ingest(hot: HotTracker, keys, valid) -> HotTracker:
    """Standalone delta fold (the promote path): same lexsort scaffold and
    merge as the in-ingest update, so both paths land bit-identical
    trackers for the same delta."""
    order, same, is_head = _delta_order(keys, valid)
    k_s, v_s = keys[order], valid[order]
    cnt = _seg_scan(jnp.add, v_s.astype(jnp.int64), ~same)
    hot_k = jnp.where(is_head, k_s, EMPTY_KEY)
    hot_c = jnp.where(is_head, cnt, jnp.int64(0))
    nk, nc, sk = _tracker_fold(hot.keys, hot.counts, hot.sketch,
                               hot_k, hot_c)
    return dataclasses.replace(hot, keys=nk, counts=nc, sketch=sk)


def _ingest_arrays(state, parent_blocks, cols_p, valid_p, *, schema, layout,
                   rb, bucket_counts, slots):
    """One fused on-device pass over the tail's DEDUPLICATED mutable state.

    ``state`` holds each overwritten buffer exactly once (the donation/
    aliasing rules of DESIGN.md §4): the tail's bucket-pointer plane is the
    snapshot block's ``ptrs`` (always one buffer), and a single-segment
    tail's ``prev`` IS the snapshot's flat ``prev`` (``tprev=None`` then).
    Keeping the signature deduplicated is what makes the donated variant
    legal — XLA rejects the same buffer donated twice, which is exactly
    what jit-of-the-whole-table would do.

    state = dict(bk      [nb, slots] int64  tail bucket keys,
                 bhi/blo [nb, slots] int32  tail block key planes,
                 bptr    [nb, slots] int32  tail head ptrs (index AND block),
                 sprev   [total]     int32  snapshot flat prev,
                 tprev   [cap_t] | None     tail-local prev (None iff rb==0),
                 tvalid  [cap_t] bool,
                 tdata   tail row storage,
                 sdata   flat data | None (None also when single-segment:
                         derived from tdata by reshape at reassembly),
                 hkeys/hcounts/hsketch  hot-key tracker leaves | None
                         (DESIGN.md §15 — folded in this same pass),
                 fill / version scalars)
    Returns (new state, overflow).
    """
    sch = schema
    nb_t, _ = state["bk"].shape
    cap_t = state["tvalid"].shape[0]
    fill_g = state["fill"]
    drop = jnp.int32(2**31 - 1)                     # scatter target: dropped

    keys = jnp.where(valid_p, jnp.asarray(cols_p[sch.key], jnp.int64),
                     EMPTY_KEY)
    # packed row ids: valid delta lanes land at [fill, fill + nv)
    pos = jnp.cumsum(valid_p.astype(PTR_DTYPE)) - 1
    rid_g = jnp.where(valid_p, fill_g.astype(PTR_DTYPE) + pos, drop)
    rid_l = jnp.where(valid_p, rid_g - PTR_DTYPE(rb), drop)
    nv = jnp.sum(valid_p).astype(jnp.int32)

    # -- backward chains (sorted order) -------------------------------------
    order, same, is_head = _delta_order(keys, valid_p)
    k_s, v_s = keys[order], valid_p[order]
    gid_s = jnp.where(v_s, rid_g[order], drop)
    pred = jnp.concatenate([jnp.full((1,), NULL_PTR, PTR_DTYPE),
                            gid_s[:-1]])
    # parent head per key: fused probe of the WHOLE pre-insert snapshot
    # (newest -> oldest across all segments), inside this same jit
    probe_snap = snap_mod.probe_view(
        parent_blocks + (FlatBlock(state["bhi"], state["blo"],
                                   state["bptr"], nb_t),),
        state["sprev"], fill_g, bucket_counts=bucket_counts, layout=layout)
    bids = jnp.stack([hashing.bucket_hash(k_s, nb) for nb in bucket_counts])
    qhi, qlo = hashing.split64(k_s)
    parent_head = kref.fused_probe_ref(bids, qhi, qlo, probe_snap)
    prev_vals = jnp.where(v_s, jnp.where(same, pred, parent_head), NULL_PTR)

    out = dict(state)
    out["sprev"] = state["sprev"].at[jnp.where(v_s, gid_s, drop)
                                     ].set(prev_vals, mode="drop")
    if state["tprev"] is not None:
        out["tprev"] = state["tprev"].at[
            jnp.where(v_s, gid_s - PTR_DTYPE(rb), drop)
        ].set(prev_vals, mode="drop")

    # -- row data (original delta order; invalid lanes scatter-drop) --------
    out["tvalid"] = state["tvalid"].at[rid_l].set(True, mode="drop")
    if layout == "row":
        words = sch.encode_rows({c.name: jnp.asarray(cols_p[c.name],
                                                     c.jnp_dtype)
                                 for c in sch.columns})
        out["tdata"] = (state["tdata"].reshape(cap_t, sch.width_words)
                        .at[rid_l].set(words, mode="drop")
                        .reshape(state["tdata"].shape))
        if state["sdata"] is not None:
            out["sdata"] = state["sdata"].at[rid_g].set(words, mode="drop")
    else:
        out["tdata"] = {
            c.name: (state["tdata"][c.name].reshape(cap_t)
                     .at[rid_l].set(jnp.asarray(cols_p[c.name], c.jnp_dtype),
                                    mode="drop")
                     .reshape(state["tdata"][c.name].shape))
            for c in sch.columns}
        if state["sdata"] is not None:
            out["sdata"] = {
                c.name: (state["sdata"][c.name]
                         .at[rid_g].set(jnp.asarray(cols_p[c.name],
                                                    c.jnp_dtype),
                                        mode="drop"))
                for c in sch.columns}

    # -- bucket/head insert on the tail planes (index + snapshot block) -----
    hk = jnp.where(is_head, k_s, EMPTY_KEY)
    flat_slot, overflow = hix.arena_insert_plan(state["bk"], hk, is_head)
    head_ptr = jnp.where(v_s, gid_s, NULL_PTR)
    hhi, hlo = hashing.split64(hk)
    # 2-D scatters, never flatten: TPU stores an [nb, slots] plane
    # slot-major, and a row-major reshape would relayout it into
    # 128-lane tiles (16x its size) on every append.
    at = (flat_slot // slots, flat_slot % slots)   # nb*slots -> row nb: drop
    out["bk"] = state["bk"].at[at].set(hk, mode="drop")
    out["bhi"] = state["bhi"].at[at].set(hhi, mode="drop")
    out["blo"] = state["blo"].at[at].set(hlo, mode="drop")
    out["bptr"] = state["bptr"].at[at].set(head_ptr, mode="drop")

    # -- hot-key tracker (skew detection, DESIGN.md §15) --------------------
    # Rides the same lexsort scaffold the chain writer just built — zero
    # extra sorts over the delta, zero host syncs.  ``hk`` already holds
    # each distinct key at its head lane (EMPTY elsewhere); the per-key
    # count is the valid-run total at that lane.  A fully-gated delta (a
    # held flush) folds all-vacant candidates: bit-identical no-op.
    if state["hkeys"] is not None:
        cnt = _seg_scan(jnp.add, v_s.astype(jnp.int64), ~same)
        hot_c = jnp.where(is_head, cnt, jnp.int64(0))
        out["hkeys"], out["hcounts"], out["hsketch"] = _tracker_fold(
            state["hkeys"], state["hcounts"], state["hsketch"], hk, hot_c)

    out["fill"] = fill_g + nv
    out["version"] = state["version"] + 1
    return out, overflow


def _dedup_state(table: IndexedTable) -> dict:
    """The tail's mutable buffers, each exactly once (DESIGN.md §4)."""
    tail = table.segments[-1]
    snap = table.snapshot
    single = len(table.segments) == 1
    hot = table.hot
    return dict(bk=tail.index.bucket_keys,
                bhi=snap.blocks[-1].key_hi,
                blo=snap.blocks[-1].key_lo,
                bptr=snap.blocks[-1].ptrs,
                sprev=snap.prev,
                tprev=None if single else tail.prev,
                tvalid=tail.valid,
                tdata=tail.data,
                sdata=None if single else snap.data,
                hkeys=None if hot is None else hot.keys,
                hcounts=None if hot is None else hot.counts,
                hsketch=None if hot is None else hot.sketch,
                fill=snap.fill,
                version=table.version)


def _reassemble(table: IndexedTable, out: dict) -> IndexedTable:
    """Rebuild the child table from an ingest's output state, restoring
    the aliasing-by-construction invariants: the tail index and snapshot
    block share ONE ptrs plane; a single-segment tail shares its prev (and
    derives flat data by reshape) with the snapshot."""
    tail = table.segments[-1]
    snap = table.snapshot
    sch = table.schema
    single = len(table.segments) == 1
    nb_t = tail.index.num_buckets
    slots = tail.index.slots
    tail_new = dataclasses.replace(
        tail, data=out["tdata"], valid=out["tvalid"],
        prev=out["sprev"] if single else out["tprev"],
        index=HashIndex(out["bk"], out["bptr"], nb_t, slots))
    if snap.data is None:
        sdata = None
    elif single:
        # leading-axis-agnostic reshape: works on [nb, rpb, ...] segment
        # data AND its shard-stacked [s, nb, rpb, ...] form (the dist
        # layer reassembles the stacked table outside the mapped region)
        if table.layout == "row":
            td = out["tdata"]
            sdata = td.reshape(td.shape[:-3] + (-1, sch.width_words))
        else:
            sdata = {c.name: out["tdata"][c.name].reshape(
                         out["tdata"][c.name].shape[:-2] + (-1,))
                     for c in sch.columns}
    else:
        sdata = out["sdata"]
    blk_new = FlatBlock(key_hi=out["bhi"], key_lo=out["blo"],
                        ptrs=out["bptr"], num_buckets=nb_t)
    snap_new = dataclasses.replace(
        snap, blocks=snap.blocks[:-1] + (blk_new,), prev=out["sprev"],
        data=sdata, fill=out["fill"])
    hot = table.hot
    if hot is not None:
        hot = dataclasses.replace(hot, keys=out["hkeys"],
                                  counts=out["hcounts"],
                                  sketch=out["hsketch"])
    return dataclasses.replace(
        table, segments=table.segments[:-1] + (tail_new,),
        snapshot=snap_new, version=out["version"], hot=hot)


def _arena_ingest_core(table: IndexedTable, cols_p: dict, valid_p):
    """Delta -> the parent's arena tail, zero pytree shape change.

    Hashes the delta, writes its bucket/chain planes, links parent heads,
    writes row data, and bumps ``fill``/``version`` — the child is
    structurally equal to the parent, so every jitted read site stays
    compile-cached (DESIGN.md §4).  Pure and collective-free: the
    distributed layer maps it per shard through ``mesh.axis_map``
    unchanged.  Returns ``(child, overflow)``; non-zero overflow means a
    *new* key found its bucket full — the host wrapper discards the child
    and promotes instead (counted, never silent).
    """
    out, overflow = _ingest_arrays(
        _dedup_state(table), table.snapshot.blocks[:-1], cols_p, valid_p,
        schema=table.schema, layout=table.layout,
        rb=table.segments[-1].row_base,
        bucket_counts=table.snapshot.bucket_counts,
        slots=table.slots)
    return _reassemble(table, out), overflow


_arena_ingest = jax.jit(_arena_ingest_core)


@partial(jax.jit, static_argnames=("schema", "layout", "rb",
                                   "bucket_counts", "slots"),
         donate_argnums=(0,))
def _ingest_arrays_donated(state, parent_blocks, cols_p, valid_p, *,
                           schema, layout, rb, bucket_counts, slots):
    """Donated ingest: every buffer in ``state`` is handed to XLA for
    in-place aliasing — true zero-copy appends.  The parent table is
    CONSUMED (its arrays become invalid); MVCC divergence (paper
    Listing 2) needs the non-donated path.  Legal only because ``state``
    is deduplicated — see ``_ingest_arrays``."""
    return _ingest_arrays(state, parent_blocks, cols_p, valid_p,
                          schema=schema, layout=layout, rb=rb,
                          bucket_counts=bucket_counts, slots=slots)


def _arena_fits_core(bucket_keys, keys, valid):
    """Would this delta's new keys overflow the tail's buckets?  Pure —
    ``_flush_core`` folds it into the fused flush; the jitted ``_arena_fits``
    wrapper runs it standalone BEFORE a donated ingest (donation consumes
    the parent, so the overflow -> promote fallback must be decided on the
    intact table)."""
    order, _, is_head = _delta_order(keys, valid)
    hk = jnp.where(is_head, keys[order], EMPTY_KEY)
    _, overflow = hix.arena_insert_plan(bucket_keys, hk, is_head)
    return overflow


_arena_fits = jax.jit(_arena_fits_core)


def _append_promote(table: IndexedTable, cols_p: dict, valid_p, nv: int
                    ) -> IndexedTable:
    """Capacity exhaustion (or bucket overflow): seal the tail and open a
    fresh arena segment at the next capacity class — at least double the
    sealed tail, and large enough for the delta's own class.  One read-site
    recompile per class (new pytree structure), geometrically amortized."""
    rpb = table.rows_per_batch
    tail_cap = table.segments[-1].capacity
    # prepare_cols left-packed the valid rows, so a sparse valid-mask
    # delta can be trimmed to its valid-row class before padding (the
    # class covers nv, not the raw lane count — without the trim a
    # mostly-invalid delta would need a capacity beyond its class)
    keep = pad_to_batches(max(nv, 1), rpb)
    if keep < valid_p.shape[0]:
        cols_p = {k: v[:keep] for k, v in cols_p.items()}
        valid_p = valid_p[:keep]
    new_cap = max(2 * tail_cap, capacity_class(max(nv, 1), rpb),
                  valid_p.shape[0])
    pad = new_cap - valid_p.shape[0]
    cols_r = {k: jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
              for k, v in cols_p.items()}
    valid_r = jnp.pad(valid_p, (0, pad))
    keys = jnp.where(valid_r, jnp.asarray(cols_r[table.schema.key],
                                          jnp.int64), EMPTY_KEY)
    heads = table.probe_latest_ref(keys)
    seg = _build_segment_retrying(cols_r, valid_r, heads, table.schema,
                                  row_base=table.capacity,
                                  rows_per_batch=rpb, layout=table.layout,
                                  slots=table.slots)
    snap = extend_snapshot(table.snapshot, seg, schema=table.schema)
    hot = table.hot
    if hot is not None:
        # the promote path bypasses _ingest_arrays; fold the delta here
        # with the same merge so both write paths count identically
        hot = _tracker_ingest(hot, keys, valid_r)
    return dataclasses.replace(table, segments=table.segments + (seg,),
                               snapshot=snap, version=table.version + 1,
                               hot=hot)


def append(table: IndexedTable, cols: dict, valid=None, *,
           mode: str = "arena", donate: bool = False,
           compact_threshold: int | None = None) -> IndexedTable:
    """Paper Listing 1 ``appendRows``: functional append -> new version.

    ``mode="arena"`` (default, DESIGN.md §4): within the tail's reserved
    capacity the delta lands via the jit-compiled in-place ingest — zero
    pytree shape change, so structurally-equal appends hit the compile
    cache at every read site.  On capacity exhaustion (or bucket
    overflow) the tail is sealed and a next-class arena opens (one
    recompile per class); when the segment count then exceeds
    ``compact_threshold`` (default ``DEFAULT_COMPACT_THRESHOLD``) the
    table is compacted so MVCC probe fan-out stays bounded.
    ``donate=True`` additionally donates the parent's buffers to XLA for
    in-place aliasing — the parent table becomes unusable (skip it when
    divergent appends on one parent are needed, paper Listing 2).

    ``mode="segment"`` is the pre-arena path — one exactly-sized delta
    segment per append, parent buffers shared by reference — kept as the
    semantic reference for the equivalence property tests and the
    before/after benchmarks.
    """
    if mode not in ("arena", "segment"):
        raise ValueError(f"append mode must be 'arena' or 'segment', "
                         f"got {mode!r}")
    cols_p, valid_p, cap = prepare_cols(cols, table.schema,
                                        table.rows_per_batch, valid)
    if mode == "segment":
        keys = jnp.where(valid_p,
                         jnp.asarray(cols_p[table.schema.key], jnp.int64),
                         EMPTY_KEY)
        # Head-link probe: the eager segment-looped reference — the fused
        # core's jit would retrace per append on this growing-shape path.
        heads = table.probe_latest_ref(keys)
        seg = _build_segment_retrying(cols_p, valid_p, heads, table.schema,
                                      row_base=table.capacity,
                                      rows_per_batch=table.rows_per_batch,
                                      layout=table.layout,
                                      slots=table.slots)
        snap = extend_snapshot(table.snapshot, seg, schema=table.schema)
        child = dataclasses.replace(table,
                                    segments=table.segments + (seg,),
                                    snapshot=snap,
                                    version=table.version + 1)
        if compact_threshold is not None \
                and child.num_segments > compact_threshold:
            child = compact(child, _bump_version=False)
        return child

    # host syncs below go through jax.device_get — the funnel the
    # benchmarks' SyncCounter instruments, so syncs-per-append is a
    # measured number (the queue's flush path pays ONE of these total)
    nv = int(jax.device_get(jnp.sum(valid_p)))
    if nv <= table.spare_capacity():
        if donate:
            keys = jnp.where(valid_p,
                             jnp.asarray(cols_p[table.schema.key],
                                         jnp.int64), EMPTY_KEY)
            ovf = int(jax.device_get(
                _arena_fits(table.segments[-1].index.bucket_keys,
                            keys, valid_p)))
            if ovf == 0:
                out, _ = _ingest_arrays_donated(
                    _dedup_state(table), table.snapshot.blocks[:-1],
                    cols_p, valid_p, schema=table.schema,
                    layout=table.layout,
                    rb=table.segments[-1].row_base,
                    bucket_counts=table.snapshot.bucket_counts,
                    slots=table.slots)
                return _reassemble(table, out)
        else:
            child, ovf = _arena_ingest(table, cols_p, valid_p)
            if int(jax.device_get(ovf)) == 0:
                return child
    child = _append_promote(table, cols_p, valid_p, nv)
    threshold = (DEFAULT_COMPACT_THRESHOLD if compact_threshold is None
                 else compact_threshold)
    if child.num_segments > threshold:
        child = compact(child, _bump_version=False)
    return child


def coalesce_deltas(deltas, schema: Schema, valids=None):
    """Concatenate N append deltas into ONE delta (host-side numpy).

    Delta ``i``'s rows precede delta ``i+1``'s, and the arena ingest's
    lexsort keys on (key, arrival lane), so landing the coalesced delta
    through one ``append`` yields per-key MVCC chains bit-identical to N
    sequential appends — while paying the per-append host round-trip
    (``_arena_fits`` pre-flight + ``int(fill)`` capacity check) and ingest
    launch ONCE instead of N times.  The coalesced append bumps the
    version once; use sequential appends when each delta must be its own
    queryable version.

    Returns ``(cols, valid)`` — ``valid`` is None when ``valids`` is None
    (every row valid), else the concatenation with per-delta ``None``
    meaning all-valid.
    """
    deltas = list(deltas)
    if not deltas:
        raise ValueError("coalesce_deltas needs at least one delta")
    cols = {c.name: np.concatenate([np.asarray(d[c.name]) for d in deltas])
            for c in schema.columns}
    if valids is None:
        return cols, None
    valids = list(valids)
    if len(valids) != len(deltas):
        raise ValueError(f"{len(valids)} validity masks for "
                         f"{len(deltas)} deltas")
    valid = np.concatenate([
        np.ones(np.shape(np.asarray(d[schema.key]))[0], bool)
        if v is None else np.asarray(v, bool)
        for d, v in zip(deltas, valids)])
    return cols, valid


# ---------------------------------------------------------------------------
# Device-resident append queue (DESIGN.md §13)
# ---------------------------------------------------------------------------

DEFAULT_QUEUE_LANES = 8

# Trace counters for the CI gate (scripts/trace_gate.py): bumped once per
# TRACE of the enqueue/flush cores — a full ring wrap must not retrace.
QUEUE_TRACES = {"enqueue": 0, "flush": 0}


class QueueOverflow(ValueError):
    """A delta does not fit the ring: the lane rows are too small for it,
    or every lane is occupied (flush first — ``frame.append(queued=True)``
    does both automatically)."""


@partial(jax.tree_util.register_dataclass,
         data_fields=["cols", "valid", "fills", "count"],
         meta_fields=["lanes", "lane_rows"])
@dataclasses.dataclass(frozen=True)
class AppendQueue:
    """A fixed-lane ring of pending deltas living beside the arena.

    Every field the ring mutates is a *data leaf* — per-lane fill
    counters and the occupied-lane ``count`` scalar included, the same
    trick as ``Snapshot.fill`` (DESIGN.md §4) — so enqueue and flush are
    pure on-device ops with ZERO pytree shape change: jitted read sites
    and the enqueue/flush sites themselves stay compile-cached across a
    full ring wrap (fill lanes -> flush -> fill again).

    ``cols`` holds one ``[lanes, lane_rows]`` typed plane per schema
    column (layout-agnostic: rows encode at flush, inside the fused
    ingest); ``valid`` masks real rows inside each lane; ``fills[l]`` is
    lane ``l``'s valid-row count; ``count`` is the number of occupied
    lanes (lanes ``[0, count)`` are pending, in enqueue order).  The
    distributed layer stacks a leading ``[num_shards]`` axis on every
    leaf and axis-maps the same enqueue/flush cores per shard.

    Queued rows are NOT part of any table version: they sit outside the
    arena and outside ``fill``, so every reader hard-masks them out
    (``snapshot.probe_view``) until a flush moves them into the arena —
    MVCC snapshot isolation with no reader changes.  Unlike the table,
    the ring is a *staging buffer*, not an MVCC object: the frame owns it
    linearly, and a flush resets it in place.
    """

    cols: dict            # {name: [lanes, lane_rows] typed}
    valid: jax.Array      # [lanes, lane_rows] bool
    fills: jax.Array      # [lanes] int32 — valid rows per lane
    count: jax.Array      # scalar int32 — occupied lanes
    lanes: int
    lane_rows: int

    @property
    def capacity_rows(self) -> int:
        return self.lanes * self.lane_rows

    def nbytes(self) -> int:
        return (sum(int(np.prod(a.shape)) * a.dtype.itemsize
                    for a in self.cols.values())
                + self.valid.size + self.fills.size * 4 + 4)


def _set_queue_mirror(queue: AppendQueue, lanes_used, rows):
    """Host mirror of the pending counts, OUTSIDE the pytree (like
    ``IndexedTable._flatdata``): the facade issues every enqueue, so it
    knows the counts for free — no device sync to answer 'is the ring
    full?' or 'how many rows are pending?'."""
    object.__setattr__(queue, "_host_lanes", int(lanes_used))
    object.__setattr__(queue, "_host_rows", int(rows))
    return queue


def queue_pending(queue: AppendQueue):
    """``(lanes_used, pending_rows)`` as host ints.  Reads the host
    mirror the enqueue/flush wrappers maintain; falls back to ONE device
    sync when the queue came back through a jit boundary (the mirror does
    not survive tracing).  UNDER a trace (the frame itself is a jit
    argument) the counts are unknowable host-side — report (0, 0): the
    ring is reader-invisible anyway, so traced read plans never depend
    on it."""
    lanes_used = getattr(queue, "_host_lanes", None)
    rows = getattr(queue, "_host_rows", None)
    if lanes_used is None or rows is None:
        if isinstance(queue.count, jax.core.Tracer):
            return 0, 0
        count, fills = jax.device_get((queue.count, queue.fills))
        lanes_used = int(np.asarray(count).reshape(-1)[0])
        rows = int(np.asarray(fills)[..., :lanes_used].sum())
        _set_queue_mirror(queue, lanes_used, rows)
    return lanes_used, rows


def empty_queue(schema: Schema, *, lanes: int = DEFAULT_QUEUE_LANES,
                lane_rows: int = 4096,
                num_shards: int | None = None) -> AppendQueue:
    """A fresh all-empty ring (``num_shards`` stacks the dist leading
    axis; per-shard ``count`` scalars stay in lockstep — every enqueue
    touches every shard's ring, possibly with zero valid rows)."""
    lead = () if num_shards is None else (num_shards,)
    cols = {c.name: jnp.zeros(lead + (lanes, lane_rows), c.jnp_dtype)
            for c in schema.columns}
    q = AppendQueue(cols=cols,
                    valid=jnp.zeros(lead + (lanes, lane_rows), bool),
                    fills=jnp.zeros(lead + (lanes,), jnp.int32),
                    count=jnp.zeros(lead, jnp.int32),
                    lanes=lanes, lane_rows=lane_rows)
    return _set_queue_mirror(q, 0, 0)


def reset_queue(queue: AppendQueue) -> AppendQueue:
    """Empty the ring without touching the (stale, masked) column planes."""
    q = dataclasses.replace(queue,
                            valid=jnp.zeros_like(queue.valid),
                            fills=jnp.zeros_like(queue.fills),
                            count=jnp.zeros_like(queue.count))
    return _set_queue_mirror(q, 0, 0)


def _enqueue_core(queue: AppendQueue, lane_cols: dict, lane_valid):
    """Pure on-device scatter of one delta into the next free lane.

    One dynamic-index write per plane at lane ``count`` (scatter-dropped
    if a misuse ever aims past the ring) plus the fill/count bumps —
    zero host syncs, zero pytree shape change.  The distributed layer
    axis-maps this unchanged per shard.
    """
    QUEUE_TRACES["enqueue"] += 1
    c = queue.count
    nv = jnp.sum(lane_valid).astype(jnp.int32)
    cols = {k: queue.cols[k].at[c].set(
                jnp.asarray(lane_cols[k], queue.cols[k].dtype), mode="drop")
            for k in queue.cols}
    valid = queue.valid.at[c].set(jnp.asarray(lane_valid, bool), mode="drop")
    fills = queue.fills.at[c].set(nv, mode="drop")
    count = jnp.minimum(c + 1, jnp.int32(queue.lanes))
    return dataclasses.replace(queue, cols=cols, valid=valid, fills=fills,
                               count=count)


_enqueue = jax.jit(_enqueue_core)
# The ring is linearly owned (see AppendQueue docstring), so donating it
# makes enqueue a true in-place lane write — the hot streaming loop's
# default cost.  The PARENT queue object becomes unusable, exactly like
# a donated table append.
_enqueue_donated = jax.jit(_enqueue_core, donate_argnums=(0,))


def _lane_arrays(queue: AppendQueue, cols: dict, valid):
    """Pad a host delta to one ``[lane_rows]`` lane (+ mask).  Host-side
    shape work only — no device round-trip."""
    n = int(np.shape(cols[next(iter(queue.cols))])[0])
    if n > queue.lane_rows:
        raise QueueOverflow(
            f"delta has {n} rows but queue lanes hold {queue.lane_rows}; "
            f"append() it directly or size the ring with "
            f"with_queue(lane_rows=...)")
    pad = queue.lane_rows - n
    lane_cols = {k: jnp.pad(jnp.asarray(cols[k], q.dtype), (0, pad))
                 for k, q in queue.cols.items()}
    v = (jnp.ones((n,), bool) if valid is None
         else jnp.asarray(valid, bool))
    nv = n if valid is None else int(np.asarray(valid, bool).sum())
    return lane_cols, jnp.pad(v, (0, pad)), nv


def enqueue(queue: AppendQueue, cols: dict, valid=None, *,
            donate: bool = True) -> AppendQueue:
    """Stage one delta in the ring — NO host sync, NO table change.

    The delta becomes visible (and the version bumps, once for the whole
    ring) only at ``flush_queue``.  Raises ``QueueOverflow`` when the
    ring is full or the delta exceeds a lane — the facade's
    ``append(queued=True)`` auto-flushes / falls back.  ``donate=True``
    (default) writes the lane in place; pass ``False`` to keep the parent
    queue object alive (divergent staging is NOT an MVCC feature — the
    ring is linearly owned).
    """
    lanes_used, rows = queue_pending(queue)
    if lanes_used >= queue.lanes:
        raise QueueOverflow(
            f"append queue is full ({queue.lanes} lanes pending); flush() "
            f"first (frame.append(queued=True) does this automatically)")
    lane_cols, lane_valid, nv = _lane_arrays(queue, cols, valid)
    out = (_enqueue_donated if donate else _enqueue)(queue, lane_cols,
                                                     lane_valid)
    return _set_queue_mirror(out, lanes_used + 1, rows + nv)


def _flush_core(state, parent_blocks, queue: AppendQueue, *, schema, layout,
                rb, bucket_counts, slots, cap, axis=None):
    """ONE fused flush: ring -> arena with the pre-flight folded in.

    Flattens the occupied lanes, lexsorts + chains them, probes parent
    heads, and ingests into the arena exactly like ``_ingest_arrays`` —
    but the capacity check AND the bucket-overflow pre-flight
    (``_arena_fits_core``) run inside the same jit, and the ENTIRE write
    is gated on their conjunction ``ok``: when the ring does not fit,
    every scatter drops (all-False valid), ``fill``/version stay put, and
    the ring keeps its contents — the host reads the single ``ok`` flag
    and takes the overflow -> promote path on the intact state.  Under a
    shard axis (``axis``), ``ok`` is psum-reduced so every shard flushes
    or holds *together* (uniform versions across the stacked pytree).

    Works over the tail's DEDUPLICATED state (``_dedup_state``) exactly
    like ``_ingest_arrays``, so the donated variant is legal: a donated
    flush is a true in-place ring -> arena move, the streaming hot
    path's cost.  ``cap`` is the tail's ``row_base + capacity`` (static).

    Returns ``(out_state, ring_after, ok)``.  The only host sync in a
    successful flush is the caller's read of ``ok``.
    """
    QUEUE_TRACES["flush"] += 1
    lanes, lane_rows = queue.lanes, queue.lane_rows
    d = lanes * lane_rows
    occ = jnp.arange(lanes, dtype=jnp.int32) < queue.count       # [lanes]
    valid_flat = (queue.valid & occ[:, None]).reshape(d)
    cols_flat = {k: v.reshape((d,) + v.shape[2:])
                 for k, v in queue.cols.items()}
    keys = jnp.where(valid_flat, jnp.asarray(cols_flat[schema.key],
                                             jnp.int64), EMPTY_KEY)
    nv = jnp.sum(queue.fills * occ.astype(jnp.int32))
    room = jnp.int32(cap) - state["fill"]
    fits = nv <= room
    ovf = _arena_fits_core(state["bk"], keys, valid_flat)
    ok = fits & (ovf == 0)
    if axis is None:
        ok = ok & (nv > 0)
    else:
        bad = jax.lax.psum((~ok).astype(jnp.int32), axis)
        total = jax.lax.psum(nv, axis)
        ok = (bad == 0) & (total > 0)
    gated_valid = valid_flat & ok
    version = state["version"]
    out, _ = _ingest_arrays(
        state, parent_blocks, cols_flat, gated_valid, schema=schema,
        layout=layout, rb=rb, bucket_counts=bucket_counts, slots=slots)
    # _ingest_arrays bumps unconditionally; a held flush must not.
    out["version"] = version + ok.astype(jnp.int32)
    ring = dataclasses.replace(
        queue,
        valid=queue.valid & ~ok,
        fills=jnp.where(ok, 0, queue.fills),
        count=jnp.where(ok, 0, queue.count))
    return out, ring, ok


_FLUSH_STATICS = ("schema", "layout", "rb", "bucket_counts", "slots", "cap",
                  "axis")
_flush = jax.jit(_flush_core, static_argnames=_FLUSH_STATICS)
# Donating state + ring makes flush a true in-place lane -> arena move
# (the table's tail planes are rewritten in place, the ring is cleared in
# place); parent blocks stay shared.  A HELD flush (ok=False) writes the
# state back unchanged and keeps the ring contents, so the promote slow
# path still works off the returned (content-identical) buffers.
_flush_donated = jax.jit(_flush_core, donate_argnums=(0, 2),
                         static_argnames=_FLUSH_STATICS)


def drain_queue(queue: AppendQueue):
    """Ring contents -> host ``(cols, valid=None)`` in enqueue order
    (lane-major; within a lane, arrival order).  The overflow -> promote
    slow path and the resilience layer's ring rebuild use this — the fast
    path never does."""
    cols, valid, count, fills = jax.device_get(
        (queue.cols, queue.valid, queue.count, queue.fills))
    c = int(np.asarray(count).reshape(-1)[0])
    v = np.asarray(valid) & (np.arange(queue.lanes)[:, None] < c)
    flat_v = v.reshape(-1)
    return ({k: np.asarray(a).reshape(-1)[flat_v]
             for k, a in cols.items()}, None)


def flush_queue(table: IndexedTable, queue: AppendQueue, *,
                donate: bool = False,
                compact_threshold: int | None = None):
    """Land the ring in the arena: ONE fused jit + ONE host sync (the
    ``ok`` flag) on the fast path.  Returns ``(table', ring', promoted)``.

    ``donate=True`` trades the parent table AND ring for a true in-place
    move (the streaming loop's cost); the returned pair is the only
    usable version afterwards — same contract as ``append(donate=True)``.

    The overflow -> promote contract: when the ring would blow the tail's
    capacity or buckets, the fused flush holds (bit-identical state, no
    version bump), the ring is drained host-side, and the coalesced delta
    lands through the ordinary ``append`` — which seals the tail and
    opens the next capacity class.  Either way the flush is exactly ONE
    version bump, same as a coalesced list append, and the decoded table
    is bit-identical to having appended the deltas directly (the lane-
    major drain order equals enqueue order — tests/test_queue.py).
    An empty ring is a no-op (no bump, no sync).
    """
    lanes_used, _ = queue_pending(queue)
    if lanes_used == 0:
        return table, queue, False
    tail = table.segments[-1]
    fn = _flush_donated if donate else _flush
    out, ring, ok = fn(_dedup_state(table), table.snapshot.blocks[:-1],
                       queue, schema=table.schema, layout=table.layout,
                       rb=tail.row_base,
                       bucket_counts=table.snapshot.bucket_counts,
                       slots=table.slots,
                       cap=tail.row_base + tail.capacity)
    child = _reassemble(table, out)
    if bool(jax.device_get(ok)):              # THE one host sync per flush
        return child, _set_queue_mirror(ring, 0, 0), False
    # held: child is content-identical to the parent (all scatters
    # dropped, version post-corrected); under donation the PARENT buffers
    # are consumed, so the promote lands on the reassembled child.
    cols, valid = drain_queue(ring)
    child = append(child, cols, valid, donate=donate,
                   compact_threshold=compact_threshold)
    return child, reset_queue(ring), True


def compact(table: IndexedTable, *, reserve: int | None = None,
            _bump_version: bool = True) -> IndexedTable:
    """Merge all segments into one fresh arena (bounds probe fan-out after
    promotions; the paper's cTrie amortizes the same way via trie-node
    sharing).  The result is reserved at the capacity class of the live
    row count, so post-compaction appends re-enter the in-place path."""
    if table.num_segments == 1 and reserve is None:
        return table
    # Host-level: gather valid rows in global (append) order.
    valid_all = np.concatenate([np.asarray(s.valid) for s in table.segments])
    bases = np.concatenate([np.asarray(s.row_base + np.arange(s.capacity))
                            for s in table.segments])
    rids = jnp.asarray(bases[valid_all], PTR_DTYPE)
    cols = table.gather_rows(rids)
    fresh = create_index(cols, table.schema,
                         rows_per_batch=table.rows_per_batch,
                         layout=table.layout, slots=table.slots,
                         reserve=reserve)
    version = table.version + 1 if _bump_version else table.version
    # compaction rewrites storage, not history: the tracker's ingest
    # counts carry through unchanged (DESIGN.md §15)
    return dataclasses.replace(fresh, version=jnp.asarray(version,
                                                          jnp.int32),
                               hot=table.hot)
