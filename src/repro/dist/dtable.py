"""DistributedTable — the hash-partitioned Indexed DataFrame (paper §III-C/D).

A dtable stacks per-shard ``IndexedTable``s leaf-wise into ONE pytree whose
every array leaf carries a leading ``[num_shards]`` axis — segments AND the
stored Snapshot.  That buys two things the paper's distributed design needs:

* **The single-partition code IS the distributed code.**  Every query
  vmaps the unchanged ``IndexedTable`` methods over the shard axis; the
  fused lookup consumes each shard's Snapshot leaves directly (zero
  in-graph view rebuilds).  On a real mesh the same functions run under
  ``shard_map`` with the leading axis sharded over devices; CPU CI vmaps.
* **Jitted distributed queries take the dtable as a pytree argument** —
  e.g. ``jax.jit(lambda dt, q: indexed_join_bcast(dt, {"k": q}, "k", 16))``
  compiles once and stays cached across failure/rebuild cycles (leaf
  shapes are deterministic) and across structurally equal appends.

Construction routes rows to their owning shard (``partition_hash``) on the
host, pads every shard to a common capacity with ``valid=False`` lanes, and
builds all shards in one vmapped ``make_segment_arrays`` call (the
overflow-doubling retry stays a host loop, doubling until *every* shard
fits — bucket counts must agree across shards for the stacked pytree).

MVCC (paper §III-D/E): ``append_distributed`` is the functional append.
Shard planes are **capacity-reserved arenas** (DESIGN.md §4): within
reserved capacity the delta lands through the same fused in-place ingest
as the single-table path, axis-mapped per shard — zero pytree shape
change, so jitted distributed queries stay compile-cached across appends
under BOTH backends (vmap and shard_map).  Versions (global and
per-shard) are data leaves for the same reason.  Capacity exhaustion on
ANY shard promotes every shard to the next class together (the stacked
pytree needs uniform shapes), and ``compact_distributed`` bounds segment
fan-out.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashindex as hix
from repro.core import hashing
from repro.core import joins
from repro.core import planner as planner_mod
from repro.core import snapshot as snap_mod
from repro.core import table as table_mod
from repro.core.hashindex import EMPTY_KEY
from repro.core.pointers import NULL_PTR, PTR_DTYPE
from repro.core.schema import Schema
from repro.core.table import (IndexedTable, capacity_class,
                              make_segment_arrays, pad_to_batches)
from repro.dist import mesh, shuffle


@partial(jax.tree_util.register_dataclass,
         data_fields=["table", "version", "replica"],
         meta_fields=["num_shards"])
@dataclasses.dataclass(frozen=True)
class DistributedTable:
    """Shard-stacked Indexed DataFrame: one pytree, leading shard axis.

    ``version`` is a scalar int32 *data leaf* (DESIGN.md §4): arena
    appends bump it on-device, so successive dtable versions stay
    structurally equal and jitted distributed queries keep their compile
    cache across appends.

    ``replica`` is the optional hot-key mirror (``HotReplica``,
    DESIGN.md §15): hot rows replicated to every shard so skewed point
    queries answer locally instead of concentrating the routed exchange
    on one owner.  Its leaves carry NO shard axis — the mirror is
    identical everywhere by construction, and the hybrid dispatch reads
    it outside the axis-mapped region.  Appends carry it through
    unchanged; its stored fetch version then trails ``version``, which
    the hybrid dispatch treats as stale (pure routing) until
    ``refresh_replica`` re-mirrors — MVCC consistency by version gating,
    never by mutation."""

    table: IndexedTable   # every array leaf is [num_shards, ...]
    version: jax.Array    # global MVCC version (paper §III-D), scalar int32
    num_shards: int
    replica: object = None  # HotReplica | None — hot-key mirror (§15)

    @property
    def schema(self) -> Schema:
        return self.table.schema

    @property
    def rows_per_batch(self) -> int:
        return self.table.rows_per_batch

    @property
    def layout(self) -> str:
        return self.table.layout

    @property
    def slots(self) -> int:
        return self.table.slots

    def num_rows(self):
        """Total valid rows across all shards."""
        return self.table.num_rows()

    def index_nbytes(self) -> int:
        return self.table.index_nbytes()

    def data_nbytes(self) -> int:
        return self.table.data_nbytes()


# ---------------------------------------------------------------------------
# Host-side routing (ingest path: exact, no capacity bound)
# ---------------------------------------------------------------------------

def _route_host(cols, schema: Schema, num_shards: int, rows_per_batch: int,
                valid=None):
    """Partition columns by key hash into [num_shards, cap] padded host
    (numpy) arrays.

    The ingest path routes on the host (numpy) so it is exact — capacity is
    *derived* from the worst shard's row count, not guessed; query-time
    probe routing is the vectorized ``dist.shuffle`` instead.
    """
    keys = np.asarray(cols[schema.key]).astype(np.int64)
    n = keys.shape[0]
    v = (np.ones(n, bool) if valid is None
         else np.asarray(valid, bool).copy())
    # Host mirror of the device hash (bit-identical by test): rows land on
    # exactly the shard that query-time routing will probe.
    dest = hashing.partition_hash_host(keys, num_shards)
    counts = np.bincount(dest[v], minlength=num_shards)
    cap = pad_to_batches(max(int(counts.max()), 1), rows_per_batch)
    out = {c.name: np.zeros((num_shards, cap), np.dtype(c.dtype))
           for c in schema.columns}
    vout = np.zeros((num_shards, cap), bool)
    for d in range(num_shards):
        m = v & (dest == d)
        k = int(m.sum())
        for c in schema.columns:
            out[c.name][d, :k] = np.asarray(cols[c.name])[m]
        vout[d, :k] = True
    return out, vout, cap


@functools.lru_cache(maxsize=None)
def _segment_build_fn(rt: mesh.Runtime, schema: Schema, row_base: int,
                      rows_per_batch: int, layout: str, num_buckets: int,
                      slots: int):
    """Jitted, axis-mapped segment build for one runtime and static
    configuration: one program per shape instead of an op-by-op dispatch
    of the build's sorts and scatters over the shard axis."""
    return jax.jit(mesh.axis_map(
        lambda c, v, h: make_segment_arrays(
            c, v, h, schema, row_base=row_base,
            rows_per_batch=rows_per_batch, layout=layout,
            num_buckets=num_buckets, slots=slots), rt))


@functools.lru_cache(maxsize=None)
def _snapshot_fn(rt: mesh.Runtime, schema: Schema, layout: str):
    """Jitted, axis-mapped flat-data snapshot of a fresh one-segment
    dtable (see ``create_distributed``)."""
    return jax.jit(mesh.axis_map(lambda s: snap_mod.snapshot_from_segments(
        (s,), layout, schema=schema, with_data=True), rt))


def _build_stacked_segment(shard_cols, shard_valid, heads, schema: Schema, *,
                           row_base: int, rows_per_batch: int, layout: str,
                           slots: int, rt: mesh.Runtime | None = None,
                           max_retries: int = 6):
    """One axis-mapped segment build across shards, retrying until no
    shard's bucket array overflows (all shards share one bucket count —
    the stacked pytree needs uniform shapes)."""
    cap = int(shard_valid.shape[1])
    nb = hix.suggest_num_buckets(cap, slots)
    for _ in range(max_retries):
        seg, overflow = _segment_build_fn(
            mesh.resolve(rt), schema, row_base, rows_per_batch, layout, nb,
            slots)(shard_cols, shard_valid, heads)
        if int(jnp.max(overflow)) == 0:
            return seg
        nb *= 2
    raise RuntimeError("distributed segment build kept overflowing")


def place_dtable(dt: "DistributedTable",
                 rt: mesh.Runtime | None) -> "DistributedTable":
    """``dt`` on ``rt``'s mesh by structure: the stacked table split one
    shard per device, the global version and the replica replicated."""
    return dataclasses.replace(
        dt, table=mesh.place(dt.table, rt),
        version=mesh.place(dt.version, rt, stacked=False),
        replica=mesh.place(dt.replica, rt, stacked=False))


def shard_capacity(share: int, fullest: int, rows_per_batch: int) -> int:
    """Per-shard arena capacity of a new dtable: the capacity class of
    the routed per-shard ``share`` of the rows, raised to the
    power-of-two cover of the ``fullest`` shard when skew puts more than
    that class on one shard."""
    return max(capacity_class(share, rows_per_batch),
               capacity_class(fullest, rows_per_batch, growth=1))


def create_distributed(cols: dict, schema: Schema, num_shards: int, *,
                       rows_per_batch: int = 4096, layout: str = "row",
                       slots: int = hix.DEFAULT_SLOTS, valid=None,
                       reserve: int | None = None,
                       track_hot: int | None = None, hot_mode: str = "topk",
                       rt: mesh.Runtime | None = None) -> DistributedTable:
    """Paper Listing 1 ``createIndex`` at cluster scope: hash-partition the
    dataframe, then build every shard's index in one axis-mapped pass
    (vmap lanes or shard_map devices, per ``rt`` — dist/mesh.py).

    Shard snapshots are built **with flat data**: distributed queries take
    the dtable as a jit argument, so everything the fused pipeline needs
    (probe planes, prev, row data) must live in the stored pytree.

    Every shard's planes are reserved to one common capacity class
    (DESIGN.md §4) so appends within the class run the in-place ingest
    with zero pytree shape change on every shard at once.  The class is
    that of the routed per-shard share (``shard_capacity``): hash routing
    leaves the fullest shard a little over the share, and sizing from the
    fullest shard would double every shard's planes whenever the share
    sits at a class edge.  ``reserve`` overrides it (per-shard minimum
    rows; ``0`` = no over-allocation, the pre-arena layout).
    """
    rt = mesh.resolve(rt).check(num_shards)
    sc, sv, cap = _route_host(cols, schema, num_shards, rows_per_batch,
                              valid)
    if reserve is None:
        reserved = shard_capacity(-(-int(sv.sum()) // num_shards), cap,
                                  rows_per_batch)
    else:
        reserved = pad_to_batches(max(cap, int(reserve), 1), rows_per_batch)
    pad = ((0, 0), (0, reserved - cap))
    heads = np.full((num_shards, reserved), NULL_PTR, PTR_DTYPE)
    # padded on the host, then each shard's rows straight to its device
    # (a stacked staging copy would hold every shard on device 0)
    sc, sv, heads = jax.tree.map(jnp.asarray, mesh.place(
        ({k: np.pad(v, pad) for k, v in sc.items()}, np.pad(sv, pad), heads),
        rt))
    seg = _build_stacked_segment(sc, sv, heads, schema, row_base=0,
                                 rows_per_batch=rows_per_batch,
                                 layout=layout, slots=slots, rt=rt)
    snap = _snapshot_fn(rt, schema, layout)(seg)
    # track_hot attaches an EMPTY per-shard tracker (created rows are not
    # back-counted — see table.with_hot: replay-deterministic)
    hot = (None if track_hot is None
           else table_mod.empty_tracker(track_hot, mode=hot_mode,
                                        num_shards=num_shards))
    table = IndexedTable(segments=(seg,), snapshot=snap, schema=schema,
                         rows_per_batch=rows_per_batch, layout=layout,
                         version=jnp.zeros((num_shards,), jnp.int32),
                         slots=slots, hot=hot)
    return place_dtable(DistributedTable(table=table, num_shards=num_shards,
                                         version=jnp.asarray(0, jnp.int32)),
                        rt)


@functools.lru_cache(maxsize=None)
def _dist_ingest_fn(rt: mesh.Runtime, donate: bool, schema: Schema,
                    layout: str, rb: int, bucket_counts: tuple, slots: int):
    """Jitted, axis-mapped arena ingest for one runtime + table structure
    (cached so repeated appends hit one compile-cache entry).  Works over
    the DEDUPLICATED tail state — required for the donated variant (XLA
    rejects the same buffer donated twice) and shared by the non-donated
    one for a single compile path."""

    def per_shard(state, parent_blocks, cols, valid):
        return table_mod._ingest_arrays(
            state, parent_blocks, cols, valid, schema=schema, layout=layout,
            rb=rb, bucket_counts=bucket_counts, slots=slots)

    mapped = mesh.axis_map(per_shard, rt)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _dist_arena_ingest(dt: DistributedTable, sc, sv,
                       rt: mesh.Runtime, donate: bool):
    """Axis-mapped arena ingest over the stacked table; returns
    ``(child_table, overflow [s])``."""
    t = dt.table
    fn = _dist_ingest_fn(rt, donate, t.schema, t.layout,
                         t.segments[-1].row_base,
                         t.snapshot.bucket_counts, t.slots)
    out, ovf = fn(table_mod._dedup_state(t), t.snapshot.blocks[:-1], sc, sv)
    return table_mod._reassemble(t, out), ovf


def append_distributed(dt: DistributedTable, cols: dict, valid=None,
                       rt: mesh.Runtime | None = None, *,
                       donate: bool = False,
                       compact_threshold: int | None = None
                       ) -> DistributedTable:
    """Functional distributed append -> new version (paper §III-D MVCC).

    Routes the delta to owning shards, then lands it through the fused
    arena ingest (DESIGN.md §4), axis-mapped per shard: each shard probes
    its parent for head links, writes its bucket/chain planes, and bumps
    its ``fill`` — zero pytree shape change, so jitted distributed
    queries stay compile-cached across appends under both backends.  The
    parent dtable is untouched unless ``donate=True`` (in-place buffer
    aliasing; the parent becomes unusable).

    If ANY shard would exceed its reserved capacity (or overflow its
    buckets), every shard promotes together to the next capacity class —
    one recompile per class — and past ``compact_threshold`` segments the
    dtable is compacted (``compact_distributed``) to bound probe fan-out.
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    schema, rpb = dt.schema, dt.rows_per_batch
    sc, sv, cap = _route_host(cols, schema, dt.num_shards, rpb, valid)
    sc, sv = jax.tree.map(jnp.asarray, (sc, sv))
    # per-shard fit: routed rows are left-packed, so counts are sv sums
    # host syncs via jax.device_get: the benchmarks' SyncCounter funnel
    counts = np.asarray(jax.device_get(sv)).sum(axis=1)
    tail = dt.table.segments[-1]
    spare = (tail.row_base + tail.capacity
             - np.asarray(jax.device_get(dt.table.snapshot.fill)))
    fits = bool((counts <= spare).all())

    if fits and donate:
        keys = jnp.where(sv, jnp.asarray(sc[schema.key], jnp.int64),
                         EMPTY_KEY)
        ovf = mesh.axis_map(table_mod._arena_fits, rt)(
            tail.index.bucket_keys, keys, sv)
        if int(jax.device_get(jnp.max(ovf))) == 0:
            child, _ = _dist_arena_ingest(dt, sc, sv, rt, True)
            return DistributedTable(table=child, num_shards=dt.num_shards,
                                    version=dt.version + 1,
                                    replica=dt.replica)
    elif fits:
        child, ovf = _dist_arena_ingest(dt, sc, sv, rt, False)
        if int(jax.device_get(jnp.max(ovf))) == 0:
            return DistributedTable(table=child, num_shards=dt.num_shards,
                                    version=dt.version + 1,
                                    replica=dt.replica)

    # promotion: seal every shard's tail, open a next-class arena on all
    # shards together (uniform shapes across the stacked pytree)
    new_cap = max(2 * tail.capacity,
                  capacity_class(max(int(counts.max()), 1), rpb))
    pad = new_cap - cap
    if pad:
        sc = {k: jnp.pad(v, ((0, 0), (0, pad))) for k, v in sc.items()}
        sv = jnp.pad(sv, ((0, 0), (0, pad)))
    keys = jnp.where(sv, jnp.asarray(sc[schema.key], jnp.int64), EMPTY_KEY)
    heads = mesh.axis_map(lambda t, k: t.probe_latest_ref(k), rt)(dt.table,
                                                                  keys)
    seg = _build_stacked_segment(sc, sv, heads, schema,
                                 row_base=dt.table.capacity,
                                 rows_per_batch=rpb, layout=dt.layout,
                                 slots=dt.slots, rt=rt)
    snap = mesh.axis_map(lambda sn, sg: snap_mod.extend_snapshot(
        sn, sg, schema=schema), rt)(dt.table.snapshot, seg)
    table = dataclasses.replace(dt.table,
                                segments=dt.table.segments + (seg,),
                                snapshot=snap,
                                version=dt.table.version + 1)
    child = DistributedTable(table=table, num_shards=dt.num_shards,
                             version=dt.version + 1, replica=dt.replica)
    threshold = (table_mod.DEFAULT_COMPACT_THRESHOLD
                 if compact_threshold is None else compact_threshold)
    if child.table.num_segments > threshold:
        child = compact_distributed(child, rt=rt, _bump_version=False)
    return child


# ---------------------------------------------------------------------------
# Device-resident append queue, per shard (DESIGN.md §13)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dist_enqueue_fn(rt: mesh.Runtime, donate: bool):
    """Jitted, axis-mapped ring enqueue (one compile-cache entry per
    runtime): every shard scatters its routed slice of the delta into its
    own ring's next lane — possibly zero valid rows, keeping per-shard
    ``count`` scalars in lockstep."""
    mapped = mesh.axis_map(table_mod._enqueue_core, rt)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _dist_flush_fn(rt: mesh.Runtime, donate: bool, schema: Schema,
                   layout: str, rb: int, bucket_counts: tuple, slots: int,
                   cap: int):
    """Jitted, axis-mapped fused flush over the deduplicated tail state
    (one compile-cache entry per runtime + table structure, like
    ``_dist_ingest_fn``).  ``axis=rt.axis`` makes the ok gate a psum:
    every shard lands its ring or holds it *together*, so the stacked
    versions/fills stay uniform (same all-or-nothing contract as
    ``append_distributed`` promotion)."""

    def per_shard(state, parent_blocks, q):
        return table_mod._flush_core(
            state, parent_blocks, q, schema=schema, layout=layout, rb=rb,
            bucket_counts=bucket_counts, slots=slots, cap=cap,
            axis=rt.axis)

    return jax.jit(mesh.axis_map(per_shard, rt),
                   donate_argnums=(0, 2) if donate else ())


def enqueue_distributed(dt: DistributedTable, queue, cols: dict, valid=None,
                        *, rt: mesh.Runtime | None = None,
                        donate: bool = True):
    """Stage one delta across every shard's ring — NO table change, and
    the only host work is the numpy route (no device round-trip).

    The delta is hash-partitioned exactly like ``append_distributed``
    (host mirror of the device hash), each shard's slice landing in ITS
    ring's next lane, so a later ``flush_queue_distributed`` ingests the
    same per-shard rows in the same order as a direct append — bit
    identical by the parity tests.  Raises ``QueueOverflow`` when the
    rings are full or one shard's slice exceeds a lane.
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    lanes_used, rows = table_mod.queue_pending(queue)
    if lanes_used >= queue.lanes:
        raise table_mod.QueueOverflow(
            f"append queue is full ({queue.lanes} lanes pending); "
            f"flush first")
    n = int(np.shape(cols[dt.schema.key])[0])
    nv = n if valid is None else int(np.asarray(valid, bool).sum())
    sc, sv, cap = _route_host(cols, dt.schema, dt.num_shards, 1, valid)
    sc, sv = jax.tree.map(jnp.asarray, (sc, sv))
    if cap > queue.lane_rows:
        raise table_mod.QueueOverflow(
            f"one shard owns {cap} delta rows but queue lanes hold "
            f"{queue.lane_rows}; append() it directly or size the ring "
            f"with with_queue(lane_rows=...)")
    pad = queue.lane_rows - cap
    if pad:
        sc = {k: jnp.pad(v, ((0, 0), (0, pad))) for k, v in sc.items()}
        sv = jnp.pad(sv, ((0, 0), (0, pad)))
    out = _dist_enqueue_fn(rt, donate)(queue, sc, sv)
    return table_mod._set_queue_mirror(out, lanes_used + 1, rows + nv)


def drain_queue_distributed(queue):
    """Ring contents -> host ``(cols, valid=None)`` in enqueue order.

    Lane-major across shards — (lane, shard, row) — so re-routing the
    drained delta packs every shard's rows back in exactly its ring
    order: the overflow -> promote path stays bit-identical to having
    flushed in place.
    """
    cols, valid, count = jax.device_get(
        (queue.cols, queue.valid, queue.count))
    c = int(np.asarray(count).reshape(-1)[0])
    v = np.asarray(valid) & (np.arange(queue.lanes)[None, :, None] < c)
    flat_v = np.transpose(v, (1, 0, 2)).reshape(-1)
    return ({k: np.transpose(np.asarray(a), (1, 0, 2)).reshape(-1)[flat_v]
             for k, a in cols.items()}, None)


def flush_queue_distributed(dt: DistributedTable, queue, *,
                            rt: mesh.Runtime | None = None,
                            donate: bool = False,
                            compact_threshold: int | None = None):
    """Land every shard's ring in its arena: ONE fused axis-mapped jit +
    ONE host sync (the psum'd ``ok`` flag, identical on all shards).
    Returns ``(dtable', ring', promoted)`` — same overflow -> promote and
    ``donate`` contracts as the local ``flush_queue``: a held flush
    drains the rings host-side and lands through ``append_distributed``
    (which seals and promotes every shard together).  Exactly ONE global
    version bump either way; an empty ring is a no-op.
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    lanes_used, _ = table_mod.queue_pending(queue)
    if lanes_used == 0:
        return dt, queue, False
    t = dt.table
    tail = t.segments[-1]
    fn = _dist_flush_fn(rt, donate, t.schema, t.layout, tail.row_base,
                        t.snapshot.bucket_counts, t.slots,
                        tail.row_base + tail.capacity)
    out, ring, ok = fn(table_mod._dedup_state(t), t.snapshot.blocks[:-1],
                       queue)
    child_t = table_mod._reassemble(t, out)
    if bool(np.asarray(jax.device_get(ok)).reshape(-1)[0]):  # THE one sync
        child = DistributedTable(table=child_t, num_shards=dt.num_shards,
                                 version=dt.version + 1, replica=dt.replica)
        return child, table_mod._set_queue_mirror(ring, 0, 0), False
    # held: child_t is content-identical to the parent; under donation
    # the parent buffers are consumed, so promote off the reassembled one
    held = DistributedTable(table=child_t, num_shards=dt.num_shards,
                            version=dt.version, replica=dt.replica)
    cols, valid = drain_queue_distributed(ring)
    child = append_distributed(held, cols, valid, rt=rt, donate=donate,
                               compact_threshold=compact_threshold)
    return child, table_mod.reset_queue(ring), True


def collect_cols(dt: DistributedTable,
                 rt: mesh.Runtime | None = None) -> dict:
    """All valid rows as host columns (shard-major, append order within —
    per-key MVCC chains keep their newest-first order because a key's rows
    never span shards)."""
    out = {}
    mask = None
    for name in dt.schema.names:
        vals, valid = mesh.axis_map(
            lambda t, _n=name: t.scan_column(_n), rt)(dt.table)
        if mask is None:
            mask = np.asarray(valid).reshape(-1)
        out[name] = np.asarray(vals).reshape(-1)[mask]
    return out


def compact_distributed(dt: DistributedTable, *,
                        rt: mesh.Runtime | None = None,
                        rt_out: "mesh.Runtime | None" = None,
                        reserve: int | None = None,
                        _bump_version: bool = True) -> DistributedTable:
    """Merge every shard's segments into one fresh arena (DESIGN.md §4).

    Collection is order-preserving per shard and routing is deterministic
    (``partition_hash``), so each row lands back on its own shard and
    per-key chains stay newest-first — lookups are bit-identical before
    and after.  The result is reserved at the capacity class of the live
    row count, so post-compaction appends re-enter the in-place path.
    """
    cols = collect_cols(dt, rt=rt)
    rt_out = rt_out if rt_out is not None else rt
    fresh = create_distributed(
        cols, dt.schema, dt.num_shards, rows_per_batch=dt.rows_per_batch,
        layout=dt.layout, slots=dt.slots, reserve=reserve, rt=rt_out)
    old_tv = int(np.asarray(dt.table.version).ravel()[0])
    bump = 1 if _bump_version else 0
    # compaction rewrites storage, not history: tracker counts and the
    # (version-gated) mirror carry through unchanged (DESIGN.md §15)
    table = dataclasses.replace(
        fresh.table, version=jnp.full((dt.num_shards,), old_tv + bump,
                                      jnp.int32), hot=dt.table.hot)
    return place_dtable(DistributedTable(
        table=table, num_shards=dt.num_shards, version=dt.version + bump,
        replica=dt.replica), rt_out)


# ---------------------------------------------------------------------------
# Distributed queries (axis-mapped single-partition ops + collective select)
# ---------------------------------------------------------------------------

def lookup(dt: DistributedTable, keys, *, max_matches: int, names=None,
           rt: mesh.Runtime | None = None):
    """Distributed point lookup -> (cols [Q, M], valid [Q, M], owner [Q]).

    The broadcast path: every shard answers the full query batch through
    its own Snapshot (one axis-mapped per-shard function, identical under
    both backends); the owner shard's answer is then selected per query by
    indexing the stacked answers at ``[owner, iq]`` OUTSIDE the mapped
    region.  Under vmap that is one local gather (bit-exact always);
    under shard_map the stacked output is a device-sharded global array
    and GSPMD lowers the cross-shard gather to collectives.  Rows for a
    key live only on its owner, so the select is exact — with ONE
    platform caveat: XLA lowers cross-device float combines (psum,
    sharded gather, all_gather alike) as zero-padded sums, so a stored
    float ``-0.0`` can come back ``+0.0`` from the shard_map broadcast
    path (numerically equal; valid masks unaffected — DESIGN.md §10).
    ``lookup_routed`` moves answers as word-packed ints over
    ``all_to_all`` and IS bit-exact for every payload under both
    backends — and is the better path at large Q anyway; compute here is
    s× redundant (``choose_lookup`` picks).
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    joins.check_max_matches(max_matches)
    q = joins.as_int64_keys(keys)
    owner = hashing.partition_hash(q, dt.num_shards)

    def shard(t, qq):
        rids, _ = t.lookup(qq, max_matches)
        valid = rids != NULL_PTR
        # NULL rids decode to exact zeros — miss lanes carry no garbage
        cols = t.gather_rows(jnp.where(valid, rids, NULL_PTR), names=names)
        return cols, valid

    cols_s, valid_s = mesh.axis_map(shard, rt, in_axes=(0, None))(
        dt.table, q)
    iq = jnp.arange(q.shape[0])
    return ({k: v[owner, iq] for k, v in cols_s.items()},
            valid_s[owner, iq], owner)


def lookup_routed(dt: DistributedTable, keys, valid=None, *,
                  max_matches: int, capacity: int | None = None, names=None,
                  rt: mesh.Runtime | None = None):
    """Shuffle-routed point lookup: probe each query ONCE, on its owner.

    keys arrive sharded [s, n] (each shard's local query batch).  Queries
    ride the capacity-bounded exchange to their owning shard
    (``route_local`` + ``lax.all_to_all``, exactly like
    ``indexed_join_shuffle``'s probe side), the owner probes its Snapshot
    over the inbox, and the answers ride the inverse all-to-all home —
    chunk ``d`` of a source's outbox comes back as chunk ``d`` of its
    answer inbox, so the return trip needs no extra addressing beyond the
    locally-kept lane ids.

    Returns ``(cols [s, n, M], valid [s, n, M], answered [s, n],
    dropped [s])``.  ``answered[i, j]`` is False when query (i, j) was
    invalid on input OR overflowed its exchange lane; overflow is also
    counted in ``dropped[i]`` — the retry contract (resubmit with a
    bigger ``capacity``; the default ``n`` can never drop).  A dropped
    query is *reported*, never a silent miss; inbox padding probes the
    EMPTY sentinel, never key 0.

    Cost: each shard probes s*capacity inbox lanes instead of the full
    broadcast batch — with capacity ~ 2n/s that is ~2Q total probes
    versus broadcast's sQ (the s× redundancy the ROADMAP flags).
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    joins.check_max_matches(max_matches)
    s = dt.num_shards
    q = joins.as_int64_keys(keys)
    assert q.ndim == 2 and q.shape[0] == s, (q.shape, s)
    n = q.shape[1]
    cap = capacity if capacity is not None else n
    qv = (jnp.ones((s, n), bool) if valid is None
          else jnp.asarray(valid, bool))

    def shard(t, k, v):
        lane = jnp.arange(n, dtype=jnp.int32)
        ok, op, ov, dropped = shuffle.route_local(k, {"lane": lane}, v, s,
                                                  cap)
        # forward exchange, ONE collective: validity rides the key plane —
        # empty outbox slots carry the EMPTY sentinel, which the probe
        # side already treats as can-never-match (EMPTY_KEY is reserved;
        # a user query for it is a guaranteed miss on any path).  The
        # outbox lane ids stay local for the answer scatter.
        in_k = shuffle.all_to_all_axis(jnp.where(ov, ok, EMPTY_KEY),
                                       rt.axis)               # [s*cap]
        in_v = in_k != EMPTY_KEY
        rids, _ = t.lookup(in_k, max_matches)
        hit = (rids != NULL_PTR) & in_v[:, None]
        cols = t.gather_rows(jnp.where(hit, rids, NULL_PTR), names=names)
        # return exchange, ONE collective: the all-to-all is its own
        # inverse here — chunk d of the word-packed answer matrix is this
        # shard's reply to source d, arriving back in outbox lane order.
        # The words stay packed through the per-query scatter (scatter
        # cost on CPU is per-INDEX, so one [s*cap -> n] row scatter beats
        # one per answer leaf) and unpack at per-query size; unanswered
        # lanes keep all-zero words, which unpack to exactly the
        # zeros/False fill the contract promises.
        packed, spec = shuffle.pack_words((cols, hit))
        home = shuffle.all_to_all_axis(
            packed.reshape(s, cap, packed.shape[1]), rt.axis)
        slot = jnp.where(ov, op["lane"], jnp.int32(n)).reshape(-1)
        per_query = (jnp.zeros((n, home.shape[1]), home.dtype)
                     .at[slot].set(home, mode="drop"))
        out_cols, out_valid = shuffle.unpack_words(per_query, spec)
        answered = (jnp.zeros((n,), bool)
                    .at[slot].set(ov.reshape(-1), mode="drop"))
        return out_cols, out_valid, answered, dropped

    return mesh.axis_map(shard, rt)(dt.table, q, qv)


def lookup_routed_report(dt: DistributedTable, keys, *, max_matches: int,
                         capacity: int | None = None, names=None,
                         rt: mesh.Runtime | None = None):
    """Routed point lookup, flat contract, WITH the drop-retry report:
    ``[Q]`` keys in, ``(cols [Q, M], valid [Q, M], answered [Q],
    dropped [s])`` out.

    Splits the batch into ``num_shards`` equal source lanes (padding the
    tail with invalid queries) and rides ``lookup_routed``'s two
    all-to-alls.  ``capacity`` bounds each (src, dest) exchange lane —
    ``None`` means the lane count, which can never drop; anything smaller
    surfaces overflow as ``answered=False`` per query plus per-shard
    ``dropped`` counts, never a silent miss.  That is the retry contract
    a caller (or ``dist.resilience.RecoveryManager``, which automates it
    with doubled capacity under a backoff budget) resubmits against.
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    joins.check_max_matches(max_matches)
    q = joins.as_int64_keys(keys)
    assert q.ndim == 1, q.shape
    s = dt.num_shards
    qn = q.shape[0]
    n = max(1, -(-qn // s))
    qpad = jnp.pad(q, (0, s * n - qn))
    # serving pads batches to a bucket with the reserved EMPTY_KEY
    # sentinel (serving/query_engine.py PAD_KEY): mask those lanes out
    # of the exchange entirely, so pad lanes never consume routed
    # capacity or count as drops — they come back cols=0/valid=False
    # exactly like the tail padding
    qvalid = (jnp.arange(s * n) < qn) & (qpad != EMPTY_KEY)
    cols, valid, answered, dropped = lookup_routed(
        dt, qpad.reshape(s, n), qvalid.reshape(s, n),
        max_matches=max_matches, capacity=capacity, names=names, rt=rt)
    flat = {k: v.reshape((s * n,) + v.shape[2:])[:qn]
            for k, v in cols.items()}
    return (flat, valid.reshape(s * n, max_matches)[:qn],
            answered.reshape(s * n)[:qn], dropped)


def lookup_routed_flat(dt: DistributedTable, keys, *, max_matches: int,
                       names=None, rt: mesh.Runtime | None = None):
    """Routed point lookup with the FLAT contract: ``[Q]`` keys in,
    ``(cols [Q, M], valid [Q, M])`` out — the adapter the facade and the
    planner execute "RoutedLookup" through.

    Capacity is the per-shard lane count, so the exchange can never drop
    a query — the retry contract never fires on this path
    (``lookup_routed_report`` is the capacity-bounded form that surfaces
    it).
    """
    cols, valid, _, _ = lookup_routed_report(
        dt, keys, max_matches=max_matches, capacity=None, names=names,
        rt=rt)
    return cols, valid


# ---------------------------------------------------------------------------
# Hot-key replication + hybrid dispatch (skew resilience, DESIGN.md §15)
# ---------------------------------------------------------------------------

DEFAULT_REPLICA_SLOTS = 128
DEFAULT_REPLICA_MATCHES = 8

# Trace counter for the CI gate (scripts/trace_gate.py gate_skew): the
# refresh site must trace ONCE per (runtime, table structure) — hot-set
# churn across appends reuses the cached entry.
REPLICA_TRACES = {"refresh": 0}


@partial(jax.tree_util.register_dataclass,
         data_fields=["keys", "cols", "valid", "version"],
         meta_fields=["max_matches"])
@dataclasses.dataclass(frozen=True)
class HotReplica:
    """Fixed-capacity mirror of the hottest keys' rows (DESIGN.md §15).

    Conceptually each shard holds an identical copy beside its main
    arena; since the copies are identical by construction, the pytree
    stores ONE un-stacked instance (no ``[num_shards]`` axis) that the
    hybrid dispatch reads outside the axis-mapped region — under
    shard_map that is a replicated operand, exactly the broadcast the
    design calls for.  All mutable fields are data leaves (§4), so the
    hot set can churn across refreshes with zero retraces.

    MVCC rule: ``version`` records the dtable version the rows were
    fetched at.  The mirror is consulted ONLY while it equals the live
    version — any append/flush/compact bump makes it stale and the
    hybrid degrades to pure routing (bit-identical answers, no staleness
    window) until ``refresh_replica`` re-mirrors.  Rows are fetched
    through ``lookup_routed_flat`` (word-packed ints), so mirrored
    answers are bit-exact under both backends — the broadcast ``lookup``
    path's float ``-0.0`` caveat never applies.

    A query with ``max_matches`` ≤ ``max_matches`` stored here is fully
    answerable from the mirror: matches are newest-first, so the stored
    prefix IS the routed answer prefix, whatever the chain length.
    """

    keys: jax.Array     # [H] int64 hot keys — EMPTY_KEY = vacant slot
    cols: dict          # {name: [H, M] typed} — full schema, newest-first
    valid: jax.Array    # [H, M] bool match mask
    version: jax.Array  # scalar int32 — dtable version at fetch time
    max_matches: int    # M — the deepest chain prefix the mirror answers


def attach_replica(dt: DistributedTable, *,
                   capacity: int = DEFAULT_REPLICA_SLOTS,
                   max_matches: int = DEFAULT_REPLICA_MATCHES
                   ) -> DistributedTable:
    """Attach an empty, STALE mirror (version −1: never consulted until
    the first ``refresh_replica``).  One treedef change, done before
    entering jitted loops — like attaching a queue or tracker."""
    if dt.table.hot is None:
        raise ValueError(
            "attach_replica needs a hot-key tracker on the table "
            "(create with track_hot=... or frame.with_hot_tracker())")
    joins.check_max_matches(max_matches)
    sch = dt.schema
    cols = {c.name: jnp.zeros((capacity, max_matches), c.jnp_dtype)
            for c in sch.columns}
    rep = HotReplica(keys=jnp.full((capacity,), EMPTY_KEY, jnp.int64),
                     cols=cols,
                     valid=jnp.zeros((capacity, max_matches), bool),
                     version=jnp.asarray(-1, jnp.int32),
                     max_matches=max_matches)
    return dataclasses.replace(dt, replica=rep)


@functools.lru_cache(maxsize=None)
def _refresh_fn(rt: mesh.Runtime):
    """Jitted replica refresh for one runtime: merge the per-shard
    trackers into the global top-H (keys are disjoint across shards —
    routing partitions by key — so the merge is one flat sort), fetch
    those keys' newest rows through the bit-exact routed path, and stamp
    the live version.  Zero host syncs; returns only the new mirror, so
    the table's leaves never round-trip through the jit."""

    def core(dt):
        REPLICA_TRACES["refresh"] += 1
        rep = dt.replica
        hot = dt.table.hot
        h = rep.keys.shape[0]
        flat_k = hot.keys.reshape(-1)
        flat_c = hot.counts.reshape(-1)
        if flat_k.shape[0] < h:
            flat_k = jnp.pad(flat_k, (0, h - flat_k.shape[0]),
                             constant_values=EMPTY_KEY)
            flat_c = jnp.pad(flat_c, (0, h - flat_c.shape[0]))
        o = jnp.lexsort((flat_k, -flat_c))   # count desc, key asc: stable
        hot_k = jnp.where(flat_c[o[:h]] > 0, flat_k[o[:h]], EMPTY_KEY)
        cols, valid = lookup_routed_flat(dt, hot_k,
                                         max_matches=rep.max_matches,
                                         rt=rt)
        return dataclasses.replace(
            rep, keys=hot_k, cols=cols, valid=valid,
            version=jnp.asarray(dt.version, jnp.int32))

    return jax.jit(core)


def refresh_replica(dt: DistributedTable, *,
                    rt: mesh.Runtime | None = None) -> DistributedTable:
    """Re-mirror the current global top-H hot keys at the live version.

    ONE cached jit call (no host sync): tracker merge + routed fetch of
    H keys.  Callers decide cadence — the facade refreshes after every
    append/flush when a mirror is attached, keeping the hybrid hot; a
    skipped refresh is safe (stale mirror ⇒ pure routing).
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    if dt.replica is None:
        raise ValueError("refresh_replica: no replica attached "
                         "(attach_replica first)")
    if dt.table.hot is None:
        raise ValueError("refresh_replica: table has no hot-key tracker")
    # the un-stacked mirror is replicated, as attached (place_dtable)
    return dataclasses.replace(dt, replica=mesh.place(
        _refresh_fn(rt)(dt), rt, stacked=False))


def _replica_split(dt: DistributedTable, q):
    """In-graph hot/cold split: ``(eligible [Q], slot [Q])``.

    A query is hot when its key sits in the mirror AND the mirror is
    fresh (fetch version == live version).  EMPTY_KEY queries (serving
    pads, masked tails) are never hot — they stay guaranteed misses on
    the cold path, consuming no exchange capacity either way."""
    rep = dt.replica
    hit = q[:, None] == rep.keys[None, :]                      # [Q, H]
    fresh = jnp.asarray(rep.version) == jnp.asarray(dt.version)
    eligible = jnp.any(hit, axis=1) & (q != EMPTY_KEY) & fresh
    return eligible, jnp.argmax(hit, axis=1)


def lookup_hybrid_report(dt: DistributedTable, keys, *, max_matches: int,
                         capacity: int | None = None, names=None,
                         rt: mesh.Runtime | None = None):
    """Skew-resilient point lookup: hot keys answer locally from the
    mirror, the cold tail routes — same flat report contract as
    ``lookup_routed_report`` (``cols [Q, M], valid [Q, M], answered [Q],
    dropped [s]``), bit-identical answers to pure routing.

    The split is in-graph: hot lanes are masked to ``EMPTY_KEY`` before
    the exchange, so (by the routed path's pad contract) they never
    consume a (src, dest) capacity lane and never count as drops — the
    owner of a celebrity key sees only the cold tail.  Hot answers
    gather from the mirror and recombine in input order.  Statically
    falls back to pure routing when no mirror is attached or the query
    wants deeper chains than the mirror stores; dynamically degrades to
    pure routing per-batch while the mirror is stale (version gate).
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    joins.check_max_matches(max_matches)
    q = joins.as_int64_keys(keys)
    rep = dt.replica
    if rep is None or max_matches > rep.max_matches:
        return lookup_routed_report(dt, q, max_matches=max_matches,
                                    capacity=capacity, names=names, rt=rt)
    eligible, slot = _replica_split(dt, q)
    cold_q = jnp.where(eligible, EMPTY_KEY, q)
    cols_r, valid_r, answered_r, dropped = lookup_routed_report(
        dt, cold_q, max_matches=max_matches, capacity=capacity,
        names=names, rt=rt)
    nm = tuple(names) if names is not None else tuple(dt.schema.names)
    cols = {k: jnp.where(eligible[:, None],
                         rep.cols[k][slot, :max_matches], cols_r[k])
            for k in nm}
    valid = jnp.where(eligible[:, None],
                      rep.valid[slot, :max_matches], valid_r)
    return cols, valid, eligible | answered_r, dropped


def lookup_hybrid_flat(dt: DistributedTable, keys, *, max_matches: int,
                       names=None, rt: mesh.Runtime | None = None):
    """Hybrid point lookup with the FLAT contract (``[Q]`` keys →
    ``(cols [Q, M], valid [Q, M])``) — what the facade and planner
    execute "HybridLookup" through.  Cold capacity is the lane count
    (never drops), hot lanes never reach the exchange at all."""
    cols, valid, _, _ = lookup_hybrid_report(
        dt, keys, max_matches=max_matches, capacity=None, names=names,
        rt=rt)
    return cols, valid


def indexed_join_hybrid(dt: DistributedTable, probe_cols: dict,
                        probe_key: str, *, max_matches: int, names=None,
                        rt: mesh.Runtime | None = None):
    """Skew-resilient equi-join, flat local contract (same as
    ``indexed_join_routed``): hot probe keys join against the mirror
    locally, the cold tail rides the routed exchange — a power-law probe
    side no longer concentrates its exchange lanes on one owner."""
    q = joins.as_int64_keys(probe_cols[probe_key])
    build_cols, valid = lookup_hybrid_flat(dt, q, max_matches=max_matches,
                                           names=names, rt=rt)
    m = valid.shape[1]
    probe_b = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                   (q.shape[0], m))
               for k, v in probe_cols.items()}
    return build_cols, probe_b, valid


def reseed_tracker(hot, num_shards: int):
    """Host-side tracker re-seed for ``reshard``: route the surviving
    tracker entries to their NEW owning shards (``partition_hash_host`` —
    same bits as the device routing) and keep each new shard's top-T.

    Top-k counts carry through as exact lower bounds (entries were
    disjoint across the old shards, so the merge has no duplicate keys);
    sketch planes restart at zero — per-plane cell sums cannot be
    re-partitioned by key, so after a reshard the sketch re-estimates
    from subsequent ingest while the carried top-k entries keep the hot
    set warm."""
    top_k = hot.keys.shape[-1]
    k = np.asarray(jax.device_get(hot.keys)).reshape(-1)
    c = np.asarray(jax.device_get(hot.counts)).reshape(-1)
    live = k != np.int64(EMPTY_KEY)
    k, c = k[live], c[live]
    owner = hashing.partition_hash_host(k, num_shards)
    keys = np.full((num_shards, top_k), np.int64(EMPTY_KEY))
    counts = np.zeros((num_shards, top_k), np.int64)
    for s in range(num_shards):
        m = owner == s
        ks, cs = k[m], c[m]
        o = np.lexsort((ks, -cs))[:top_k]          # count desc, key asc
        keys[s, :o.size] = ks[o]
        counts[s, :o.size] = cs[o]
    sketch = (None if hot.sketch is None
              else jnp.zeros((num_shards,) + hot.sketch.shape[-2:],
                             jnp.int64))
    return dataclasses.replace(hot, keys=jnp.asarray(keys),
                               counts=jnp.asarray(counts), sketch=sketch)


def hot_fraction(dt: DistributedTable, keys) -> float:
    """Host-side diagnostic: fraction of CONCRETE query keys the mirror
    would answer locally (``explain()`` reports it; never called under a
    trace).  Uses a host mirror of the replica keys cached on the
    instance — one device_get per replica object, not per call."""
    rep = dt.replica
    if rep is None or isinstance(rep.keys, jax.core.Tracer):
        return 0.0
    hk = getattr(rep, "_host_keys", None)
    if hk is None:
        hk = np.asarray(jax.device_get(rep.keys))
        object.__setattr__(rep, "_host_keys", hk)
    q = np.asarray(keys).astype(np.int64).reshape(-1)
    if q.size == 0:
        return 0.0
    return float(np.isin(q, hk[hk != np.int64(EMPTY_KEY)]).mean())


def choose_lookup(dt, total_queries: int, *,
                  routed_threshold: int = 4096) -> str:
    """Back-compat shim: the bcast/routed cost rule now lives in the
    Planner (rules L2/L3, ``Planner.lookup_flavor``); this keeps the
    original string-returning helper for existing call sites."""
    planner = planner_mod.Planner(routed_threshold=routed_threshold)
    op, _ = planner.lookup_flavor(int(getattr(dt, "num_shards", 1)),
                                  total_queries)
    return op


def indexed_join_bcast(dt: DistributedTable, probe_cols: dict,
                       probe_key: str, max_matches: int, *, names=None,
                       rt: mesh.Runtime | None = None):
    """Broadcast equi-join: ship the (small) probe side to every shard.

    Returns (build_cols [Q, M], probe_cols broadcast [Q, M], valid [Q, M])
    — the same contract as ``core.joins.indexed_join``.
    """
    q = joins.as_int64_keys(probe_cols[probe_key])
    build_cols, valid, _ = lookup(dt, q, max_matches=max_matches,
                                  names=names, rt=rt)
    m = valid.shape[1]
    probe_b = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                   (q.shape[0], m))
               for k, v in probe_cols.items()}
    return build_cols, probe_b, valid


def indexed_join_shuffle(dt: DistributedTable, probe_cols: dict,
                         probe_key: str, probe_valid, max_matches: int, *,
                         capacity: int | None = None, names=None,
                         rt: mesh.Runtime | None = None):
    """Shuffle equi-join: the (large) probe side arrives sharded [s, n];
    probe rows ride the all-to-all to the shard owning their key
    (``shuffle.shuffle_global_axis``), then join locally — results stay
    sharded on their owner.

    Returns (build_cols [s, s*cap, M], probe_cols [s, s*cap, M],
    valid [s, s*cap, M], dropped [s]).  ``capacity`` bounds each
    (src, dest) exchange lane; the default ``n`` can never drop.
    """
    rt = mesh.resolve(rt).check(dt.num_shards)
    joins.check_max_matches(max_matches)
    s = dt.num_shards
    keys = joins.as_int64_keys(probe_cols[probe_key])
    assert keys.shape[0] == s, (keys.shape, s)
    cap = capacity if capacity is not None else keys.shape[1]
    payload = {k: jnp.asarray(v) for k, v in probe_cols.items()}

    def local(t, k, p, v):
        rk, rp, rv, dropped = shuffle.shuffle_global_axis(
            k, p, v, s, cap, rt.axis)
        rids, _ = t.lookup(jnp.where(rv, rk, EMPTY_KEY), max_matches)
        valid = (rids != NULL_PTR) & rv[:, None]
        cols = t.gather_rows(jnp.where(valid, rids, NULL_PTR), names=names)
        probe_b = {kk: jnp.broadcast_to(vv[..., None],
                                        vv.shape + (max_matches,))
                   for kk, vv in rp.items()}
        return cols, probe_b, valid, dropped

    return mesh.axis_map(local, rt)(dt.table, keys, payload,
                                    jnp.asarray(probe_valid, bool))


def indexed_join_routed(dt: DistributedTable, probe_cols: dict,
                        probe_key: str, *, max_matches: int, names=None,
                        rt: mesh.Runtime | None = None):
    """Shuffle-flavored equi-join with the FLAT local contract: probe keys
    ride the routed exchange to their owning shard (two all-to-alls, each
    key probed exactly once — the same data movement as
    ``indexed_join_shuffle``'s probe side), while the probe *payload*
    never leaves the caller: answers come home in input order and the
    probe columns broadcast locally.

    Returns (build_cols [Q, M], probe_cols broadcast [Q, M],
    valid [Q, M]) — the same contract as ``core.joins.indexed_join`` and
    ``indexed_join_bcast``, which is what lets the facade/planner swap
    flavors per call without changing callers.  ``indexed_join_shuffle``
    remains the owner-sharded-output form for pipelines that continue
    shard-local.
    """
    q = joins.as_int64_keys(probe_cols[probe_key])
    build_cols, valid = lookup_routed_flat(dt, q, max_matches=max_matches,
                                           names=names, rt=rt)
    m = valid.shape[1]
    probe_b = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                   (q.shape[0], m))
               for k, v in probe_cols.items()}
    return build_cols, probe_b, valid


def choose_join(dt, probe_rows: int, *,
                bcast_threshold: int = 1_000_000) -> str:
    """Back-compat shim: the bcast/shuffle cost rule now lives in the
    Planner (rules J2/J3, ``Planner.join_flavor``)."""
    planner = planner_mod.Planner(bcast_threshold=bcast_threshold)
    op, _ = planner.join_flavor(probe_rows)
    return op
