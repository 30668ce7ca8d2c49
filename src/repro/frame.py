"""IndexedFrame — ONE dataframe facade over both execution backends.

The paper's public object (Listing 1) is a single *Indexed DataFrame*
with ``createIndex / getRows / appendRows / join`` semantics; PRs 1-4
grew that into ~20 free functions split across ``repro.core`` (one
partition) and ``repro.dist`` (hash-partitioned shards), with every
caller hand-picking the backend AND the physical operator.  This module
is the seam that puts the paper's abstraction back on top (the same
place Modin's dataframe algebra and Cylon's unified local/distributed
API draw it):

* ``IndexedFrame.from_columns(cols, schema, num_shards=...)`` builds a
  local ``IndexedTable`` (``num_shards=1``) or a ``DistributedTable``
  behind the same handle.
* ``.lookup`` / ``.join`` route through the **Planner's physical-operator
  selection** (core/planner.py rules L1-L3 / J1-J3): the facade auto-picks
  local vs broadcast vs routed/shuffle per call from the query volume and
  shard count, and ``.plan_lookup(...).explain()`` names the rule that
  fired.  The free functions remain the stable internal layer — each
  facade method IS a thin dispatch onto one of them, bit-identical by
  test (tests/test_frame.py).
* ``.append`` is the MVCC write path (parent stays queryable); a *list*
  of deltas is coalesced host-side into ONE fused ingest launch, paying
  the per-append host round-trip once (``core.table.coalesce_deltas``).
* ``.filter/.select/.agg`` build ``core.planner`` logical trees over the
  frame's relation, with ``.explain()`` / ``.execute()``.
* ``.save/.load/.reshard`` delegate to ``dist.checkpoint``.

The frame is a registered pytree whose ONLY data field is the wrapped
table, so jitted call sites can take the frame itself as an argument:
facade dispatch adds zero retraces (the trace gate drives the fused read
sites through the Frame API — scripts/trace_gate.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import hashing
from repro.core import joins
from repro.core import partition as partition_mod
from repro.core import planner as planner_mod
from repro.core import table as table_mod
from repro.core.pointers import PTR_DTYPE
from repro.core.schema import Schema

if False:  # annotations only (PEP 563 strings; dist itself loads lazily)
    from repro.dist import mesh

_LOOKUP_OPS = ("auto", "local", "bcast", "routed", "hybrid")
_JOIN_OPS = ("auto", "local", "bcast", "shuffle", "hybrid")

# re-exported for the facade surface: repro.PartitionSpec is the
# partition_by= argument type (core/partition.py, DESIGN.md §16)
PartitionSpec = partition_mod.PartitionSpec


def _dtable():
    """The distributed layer, imported on first distributed use — local
    frames (and repro.core-only consumers like serving/kvcache.py) never
    pull in repro.dist."""
    from repro.dist import dtable
    return dtable


def _checkpoint():
    from repro.dist import checkpoint
    return checkpoint


def _hash_string_cols(cols: dict, schema: Schema,
                      dictionary: "hashing.StringDictionary | None" = None
                      ) -> dict:
    """String-valued columns -> int64 FNV-1a keys, vectorized.

    The facade accepts raw string columns anywhere a delta enters
    (``from_columns`` / ``append`` / ``enqueue``) and hashes them in one
    numpy batch (``hashing.hash_strings_host``, bit-identical to the
    scalar ``hash_string_host`` loop) — the paper's Fig-15 string-ingest
    tax paid vectorized instead of per row.  Device arrays and numeric
    columns pass through untouched.  An optional
    ``hashing.StringDictionary`` caches vocabulary -> code across
    batches so repeated strings skip the byte-matrix hash entirely
    (codes stay bit-identical either way).
    """
    encode = (hashing.hash_strings_host if dictionary is None
              else dictionary.encode)
    out, changed = dict(cols), False
    for name, v in cols.items():
        if isinstance(v, jax.Array):
            continue
        a = np.asarray(v)
        if a.dtype.kind in "US" or (a.dtype.kind == "O" and a.size
                                    and isinstance(a.reshape(-1)[0], str)):
            out[name] = encode(a)
            changed = True
    return out if changed else cols


@dataclasses.dataclass(frozen=True)
class FramePlan:
    """A logical-plan builder over a frame's relation: chain ``filter`` /
    ``select`` / ``agg``, then ``explain()`` (which physical operators and
    why — the paper's ``df.explain`` verification) or ``execute()``."""

    node: Any
    planner: planner_mod.Planner

    def filter(self, pred) -> "FramePlan":
        return FramePlan(planner_mod.Filter(self.node, pred), self.planner)

    def select(self, *names) -> "FramePlan":
        return FramePlan(planner_mod.Project(self.node, tuple(names)),
                         self.planner)

    def agg(self, op: str, col: str) -> "FramePlan":
        return FramePlan(planner_mod.Aggregate(self.node, op, col),
                         self.planner)

    def plan(self) -> planner_mod.Physical:
        return self.planner.plan(self.node)

    def explain(self) -> str:
        return self.plan().explain()

    def execute(self):
        return self.planner.execute(self.node)


@partial(jax.tree_util.register_dataclass, data_fields=["data", "queue"],
         meta_fields=["rt"])
@dataclasses.dataclass(frozen=True)
class IndexedFrame:
    """The paper's Indexed DataFrame: one facade, either backend.

    ``data`` is the wrapped ``IndexedTable`` or ``DistributedTable`` (a
    pytree data field — successive MVCC versions of a frame stay
    structurally equal exactly when the wrapped table does, so jitted
    read sites taking the frame as an argument never retrace across
    in-class appends).  ``queue`` is the optional device-resident append
    ring (``core.table.AppendQueue``, DESIGN.md §13) behind
    ``enqueue``/``flush``/``append(queued=True)`` — also a data field
    with fixed lane shapes, so a frame streams deltas and flushes with
    ZERO treedef change (attaching a queue to a queue-less frame is the
    one-time treedef change, hence one retrace — do it before the jitted
    read loop, or at construction).  ``rt`` is the ``dist.mesh.Runtime``
    every distributed op executes under (treedef metadata; None = the
    vmap emulation backend).
    """

    data: Any
    rt: mesh.Runtime | None = None
    queue: Any = None

    # -- construction ---------------------------------------------------------

    @classmethod
    def from_columns(cls, cols: dict, schema: Schema, *, num_shards: int = 1,
                     rt: mesh.Runtime | None = None,
                     rows_per_batch: int = 4096, layout: str = "row",
                     slots: int | None = None, valid=None,
                     reserve: int | None = None,
                     track_hot: int | None = None,
                     hot_mode: str = "topk",
                     partition_by: partition_mod.PartitionSpec | None = None,
                     dictionary: "hashing.StringDictionary | None" = None
                     ) -> "IndexedFrame":
        """Paper Listing 1 ``createIndex``: build the index over a keyed
        columnar dict — one partition (``num_shards=1``) or hash-
        partitioned across shards, same handle either way.  ``track_hot``
        attaches a top-k hot-key tracker (DESIGN.md §15) counting
        subsequent ingest; ``hot_mode="sketch"`` uses the count-min
        fallback for unbounded key universes.

        ``partition_by`` (a ``core.partition.PartitionSpec``) builds a
        PARTITIONED frame instead: per-partition arenas grouped under a
        range/list partition map (DESIGN.md §16), pruned reads via
        planner rules P1-P3, and O(1) retention through
        ``drop_partition`` / ``retain``.  With ``num_shards > 1`` each
        partition is shard-stacked (partition-major, shard-minor).

        ``dictionary`` (a ``hashing.StringDictionary``) caches the
        string-column vocabulary -> int64 code table so repeated strings
        skip the FNV byte walk; pass the same dictionary to later
        ``append`` / ``enqueue`` calls to amortize across a stream
        (codes are bit-identical with or without it)."""
        cols = _hash_string_cols(cols, schema, dictionary)
        kw = {} if slots is None else {"slots": slots}
        if partition_by is not None:
            t = partition_mod.create_partitioned(
                cols, schema, partition_by, num_shards=num_shards, rt=rt,
                rows_per_batch=rows_per_batch, layout=layout, valid=valid,
                reserve=reserve, track_hot=track_hot, hot_mode=hot_mode,
                **kw)
        elif num_shards == 1:
            t = table_mod.create_index(
                cols, schema, rows_per_batch=rows_per_batch, layout=layout,
                valid=valid, reserve=reserve, track_hot=track_hot,
                hot_mode=hot_mode, **kw)
        else:
            t = _dtable().create_distributed(
                cols, schema, num_shards, rows_per_batch=rows_per_batch,
                layout=layout, valid=valid, reserve=reserve, rt=rt,
                track_hot=track_hot, hot_mode=hot_mode, **kw)
        return cls(data=t, rt=rt)

    # -- shape facts / passthroughs -------------------------------------------

    @property
    def is_distributed(self) -> bool:
        # duck-typed like the planner (_is_dist): DistributedTable is the
        # only backend with a shard count, and this keeps repro.dist out
        # of local frames' import graph.  A PartitionedTable has no
        # ``num_shards`` itself (its partitions may) — check
        # ``is_partitioned`` first when dispatching.
        return hasattr(self.data, "num_shards")

    @property
    def is_partitioned(self) -> bool:
        return isinstance(self.data, partition_mod.PartitionedTable)

    @property
    def num_shards(self) -> int:
        if self.is_partitioned:
            return self.data.shards_per_partition
        return self.data.num_shards if self.is_distributed else 1

    @property
    def num_partitions(self) -> int:
        return self.data.num_partitions if self.is_partitioned else 1

    @property
    def partition_ids(self) -> tuple:
        return self.data.partition_ids if self.is_partitioned else ()

    @property
    def schema(self) -> Schema:
        return self.data.schema

    @property
    def version(self):
        return self.data.version

    def num_rows(self):
        return self.data.num_rows()

    def index_nbytes(self, **kw) -> int:
        return self.data.index_nbytes(**kw)

    def data_nbytes(self, **kw) -> int:
        return self.data.data_nbytes(**kw)

    def with_flat_data(self) -> "IndexedFrame":
        """Materialize the snapshot's flat data (local frames) so jitted
        call sites taking the frame as an argument trace the whole fused
        pipeline as stored leaves; dist frames always carry it."""
        if self.is_distributed:
            return self
        return dataclasses.replace(self, data=self.data.with_flat_data())

    def _planner(self, planner: planner_mod.Planner | None,
                 max_matches: int = 64) -> planner_mod.Planner:
        if planner is not None:
            return planner
        return planner_mod.Planner(max_matches=max_matches, rt=self.rt)

    # -- reads: planner-routed physical operators -----------------------------

    def _forced_plan(self, op: str, ops: tuple, kinds: dict
                     ) -> planner_mod.Physical:
        """A Physical node for an explicitly forced flavor, rejecting ops
        the frame's backend cannot run (``kinds["local"]`` is the one
        single-partition operator; the rest need shards)."""
        if op not in ops:
            raise ValueError(f"op must be one of {ops}, got {op!r}")
        kind = kinds[op]
        wants_local = kind == kinds["local"]
        if wants_local == self.is_distributed:
            raise ValueError(
                f"op={op!r} needs a "
                f"{'local' if wants_local else 'distributed'} frame; "
                f"this frame has {self.num_shards} shard(s)")
        return planner_mod.Physical(kind, f"forced: op={op!r}", self.data)

    def _annotate(self, phys: planner_mod.Physical,
                  keys) -> planner_mod.Physical:
        """The uniform reason suffix every planned read carries:
        ``pending_ring_rows=N`` (rows staged in the ring, invisible until
        flush) and, for hybrid flavors with concrete keys, the measured
        ``hot_fraction`` — so ``explain()`` reads the same for every
        flavor.  Both are host facts; under a trace (the gated read sites
        drive planning with tracer keys) the hot fraction is skipped."""
        notes = [f"pending_ring_rows={self.pending_rows}"]
        if (phys.kind in ("HybridLookup", "HybridJoin")
                and not isinstance(keys, jax.core.Tracer)):
            frac = _dtable().hot_fraction(self.data, keys)
            notes.append(f"hot_fraction={frac:.2f}")
        return dataclasses.replace(
            phys, reason=phys.reason + "; " + " ".join(notes))

    def plan_lookup(self, keys, *, max_matches: int = 64, op: str = "auto",
                    planner: planner_mod.Planner | None = None
                    ) -> planner_mod.Physical:
        """The physical operator ``lookup`` would run for this query batch
        (rules L1-L4) — ``.explain()`` on the result names the rule."""
        with obs.span("frame.plan", kind="lookup"):
            if op == "auto":
                p = self._planner(planner, max_matches)
                phys = p.physical_lookup(self.data, int(jnp.shape(keys)[0]),
                                         keys=keys)
            elif self.is_partitioned:
                raise ValueError(
                    f"a partitioned frame picks the per-partition flavor "
                    f"itself (rule P1); op must be 'auto', got {op!r}")
            else:
                phys = self._forced_plan(op, _LOOKUP_OPS,
                                         {"local": "IndexedLookup",
                                          "bcast": "BroadcastLookup",
                                          "routed": "RoutedLookup",
                                          "hybrid": "HybridLookup"})
            return self._annotate(phys, keys)

    def lookup(self, keys, *, max_matches: int = 64, names=None,
               op: str = "auto",
               planner: planner_mod.Planner | None = None):
        """Paper Listing 1 ``getRows``: rows for each key, newest-first.

        Returns ``(cols [Q, max_matches], valid [Q, max_matches])`` on
        every backend and flavor; the Planner picks local vs broadcast vs
        routed (``op`` forces a flavor; ``plan_lookup`` explains).
        """
        joins.check_max_matches(max_matches)
        keys = joins.as_int64_keys(keys)
        kind = self.plan_lookup(keys, max_matches=max_matches, op=op,
                                planner=planner).kind
        if kind == "PartitionedLookup":
            return partition_mod.lookup_partitioned(
                self.data, keys, max_matches=max_matches, names=names,
                rt=self.rt, routed_threshold=self._planner(
                    planner, max_matches).routed_threshold)
        if kind == "IndexedLookup":
            return joins.indexed_lookup(self.data, keys,
                                        max_matches=max_matches, names=names)
        if kind == "BroadcastLookup":
            cols, valid, _ = _dtable().lookup(
                self.data, keys, max_matches=max_matches, names=names,
                rt=self.rt)
            return cols, valid
        if kind == "HybridLookup":
            return _dtable().lookup_hybrid_flat(
                self.data, keys, max_matches=max_matches, names=names,
                rt=self.rt)
        return _dtable().lookup_routed_flat(
            self.data, keys, max_matches=max_matches, names=names,
            rt=self.rt)

    def plan_join(self, probe_cols: dict, on: str, *, max_matches: int = 64,
                  op: str = "auto",
                  planner: planner_mod.Planner | None = None
                  ) -> planner_mod.Physical:
        """The physical operator ``join`` would run for this probe side
        (rules J1-J4)."""
        with obs.span("frame.plan", kind="join"):
            if op == "auto":
                p = self._planner(planner, max_matches)
                phys = p.physical_join(self.data,
                                       int(jnp.shape(probe_cols[on])[0]),
                                       keys=probe_cols[on])
            elif self.is_partitioned:
                raise ValueError(
                    f"a partitioned frame picks the per-partition flavor "
                    f"itself (rule P3); op must be 'auto', got {op!r}")
            else:
                phys = self._forced_plan(op, _JOIN_OPS,
                                         {"local": "IndexedJoin",
                                          "bcast": "BroadcastJoin",
                                          "shuffle": "ShuffleJoin",
                                          "hybrid": "HybridJoin"})
            return self._annotate(phys, probe_cols[on])

    def join(self, probe_cols: dict, on: str, *, max_matches: int = 64,
             names=None, op: str = "auto",
             planner: planner_mod.Planner | None = None):
        """Equi-join with this frame as the build side.

        Returns ``(build_cols [Q, M], probe_cols broadcast [Q, M],
        valid [Q, M])`` on every backend and flavor — the shuffle flavor
        routes probe keys to their owners and brings answers home
        (``dist.indexed_join_routed``), so results land in probe order
        like every other flavor.
        """
        joins.check_max_matches(max_matches)
        with obs.span("frame.join", rows=len(probe_cols[on])):
            keys = joins.as_int64_keys(probe_cols[on])
            kind = self.plan_join(probe_cols, on, max_matches=max_matches,
                                  op=op, planner=planner).kind
            if kind == "PartitionedJoin":
                return partition_mod.join_partitioned(
                    self.data, probe_cols, on, max_matches=max_matches,
                    names=names, rt=self.rt, routed_threshold=self._planner(
                        planner, max_matches).routed_threshold)
            if kind == "IndexedJoin":
                return joins.indexed_join(self.data, probe_cols, on,
                                          max_matches=max_matches,
                                          names=names)
            if kind == "BroadcastJoin":
                return _dtable().indexed_join_bcast(
                    self.data, probe_cols, on, max_matches, names=names,
                    rt=self.rt)
            if kind == "HybridJoin":
                return _dtable().indexed_join_hybrid(
                    self.data, probe_cols, on, max_matches=max_matches,
                    names=names, rt=self.rt)
            return _dtable().indexed_join_routed(
                self.data, probe_cols, on, max_matches=max_matches,
                names=names, rt=self.rt)

    # -- writes: MVCC appends, compaction -------------------------------------

    def append(self, cols, valid=None, *, donate: bool = False,
               mode: str = "arena", queued: bool = False,
               compact_threshold: int | None = None,
               dictionary: "hashing.StringDictionary | None" = None
               ) -> "IndexedFrame":
        """Paper Listing 1 ``appendRows``: functional append -> a new
        frame; the parent stays queryable (divergent MVCC children,
        Listing 2 — unless ``donate=True`` trades the parent for in-place
        buffer aliasing).

        ``cols`` may be a list/tuple of deltas: they are coalesced
        host-side (``core.table.coalesce_deltas``) and land through ONE
        fused ingest launch — one ``_arena_fits`` pre-flight and one
        ``int(fill)`` check for the whole batch, one version bump —
        instead of one host round-trip per delta (the ROADMAP's write-hot
        streams item).  ``valid`` is then a matching list of masks (or
        None).

        ``queued=True`` stages the delta in the device-resident ring
        instead (``enqueue`` — zero host syncs, invisible until
        ``flush``), auto-attaching a default ring and auto-flushing when
        the ring fills; an oversize delta flushes then lands directly
        (the documented lane-size bypass).  String-valued columns are
        hashed to int64 keys in one vectorized batch either way.

        Partitioned frames route the delta host-side on the partition
        column and land it in the receiving partitions only (one global
        version bump); they have no frame-level ring, so ``queued=True``
        degrades to the direct append.
        """
        queued = queued and not self.is_partitioned
        if queued:
            if isinstance(cols, (list, tuple)):
                fr = self
                for i, d in enumerate(cols):
                    fr = fr.append(d, None if valid is None else valid[i],
                                   queued=True, donate=donate,
                                   compact_threshold=compact_threshold,
                                   dictionary=dictionary)
                return fr
            try:
                return self.enqueue(cols, valid, donate=donate,
                                    dictionary=dictionary)
            except table_mod.QueueOverflow:
                fr = self.flush(compact_threshold=compact_threshold)
                try:
                    return fr.enqueue(cols, valid, donate=donate,
                                      dictionary=dictionary)
                except table_mod.QueueOverflow:
                    # oversize for a lane even when empty -> land directly
                    return fr.append(cols, valid, donate=donate,
                                     compact_threshold=compact_threshold,
                                     dictionary=dictionary)
        if isinstance(cols, (list, tuple)):
            cols, valid = table_mod.coalesce_deltas(
                [_hash_string_cols(d, self.schema, dictionary)
                 for d in cols],
                self.schema, valid)
        else:
            cols = _hash_string_cols(cols, self.schema, dictionary)
        if self.is_partitioned:
            if mode != "arena":
                raise ValueError(
                    f"partitioned append supports only mode='arena' "
                    f"(got {mode!r})")
            new = partition_mod.append_partitioned(
                self.data, cols, valid, rt=self.rt, donate=donate,
                compact_threshold=compact_threshold)
        elif self.is_distributed:
            if mode != "arena":
                raise ValueError(
                    f"distributed append supports only mode='arena' "
                    f"(got {mode!r}); the segment-chain reference path is "
                    f"single-partition")
            new = self._refreshed(_dtable().append_distributed(
                self.data, cols, valid, rt=self.rt, donate=donate,
                compact_threshold=compact_threshold))
        else:
            new = table_mod.append(self.data, cols, valid, mode=mode,
                                   donate=donate,
                                   compact_threshold=compact_threshold)
        return dataclasses.replace(self, data=new)

    # -- streaming ingest: the device-resident ring (DESIGN.md §13) ------------

    @property
    def pending_deltas(self) -> int:
        """Occupied ring lanes (0 for a queue-less frame) — host mirror,
        no device sync on the facade path."""
        return 0 if self.queue is None else table_mod.queue_pending(
            self.queue)[0]

    @property
    def pending_rows(self) -> int:
        """Valid rows staged in the ring, invisible to readers until
        ``flush`` (``plan_lookup`` reasons mention them)."""
        return 0 if self.queue is None else table_mod.queue_pending(
            self.queue)[1]

    def with_queue(self, *, lanes: int = table_mod.DEFAULT_QUEUE_LANES,
                   lane_rows: int | None = None) -> "IndexedFrame":
        """Attach a fresh device-resident append ring (idempotent on
        shape: an already-attached same-shape ring is kept).  This is the
        frame's ONE treedef change — do it before entering a jitted read
        loop and streaming stays retrace-free."""
        if self.is_partitioned:
            raise ValueError(
                "partitioned frames have no frame-level append ring (each "
                "partition keeps its own arena); use append — it routes "
                "and lands the delta per partition")
        lr = self.data.rows_per_batch if lane_rows is None else int(lane_rows)
        q = self.queue
        if q is not None and (q.lanes, q.lane_rows) == (lanes, lr):
            return self
        if not self.is_distributed:
            q = table_mod.empty_queue(self.schema, lanes=lanes, lane_rows=lr)
        else:
            q = self._placed(table_mod.empty_queue(
                self.schema, lanes=lanes, lane_rows=lr,
                num_shards=self.num_shards))
        return dataclasses.replace(self, queue=q)

    def enqueue(self, cols, valid=None, *, donate: bool = True,
                dictionary: "hashing.StringDictionary | None" = None
                ) -> "IndexedFrame":
        """Stage one delta in the ring — NO host sync, NO table change;
        rows become visible (one version bump for the whole ring) at
        ``flush``.  Auto-attaches a default ring on first use.  The ring
        is linearly owned, so the parent frame's ring is donated by
        default (``donate=False`` keeps it alive; the *table* is MVCC
        either way).  Raises ``core.table.QueueOverflow`` when full —
        ``append(queued=True)`` auto-flushes instead."""
        fr = self.with_queue() if self.queue is None else self
        cols = _hash_string_cols(cols, self.schema, dictionary)
        if fr.is_distributed:
            q = _dtable().enqueue_distributed(fr.data, fr.queue, cols, valid,
                                              rt=fr.rt, donate=donate)
        else:
            q = table_mod.enqueue(fr.queue, cols, valid, donate=donate)
        return dataclasses.replace(fr, queue=q)

    def flush(self, *, donate: bool = False,
              compact_threshold: int | None = None) -> "IndexedFrame":
        """Land the ring in the arena: ONE fused jit + ONE host sync (the
        overflow flag) for however many deltas are staged — vs one
        pre-flight + one fill check per ``append`` call.  Exactly one
        version bump; on capacity pressure the flush holds and the
        drained ring lands through the ordinary promote path
        (bit-identical either way).  ``donate=True`` hands the parent
        table state AND the ring to XLA (true in-place landing — only
        when no other frame aliases them).  Empty ring: no-op, returns
        self."""
        if self.queue is None or self.pending_deltas == 0:
            return self
        if self.is_distributed:
            data, q, _ = _dtable().flush_queue_distributed(
                self.data, self.queue, rt=self.rt, donate=donate,
                compact_threshold=compact_threshold)
            data = self._refreshed(data)
        else:
            data, q, _ = table_mod.flush_queue(
                self.data, self.queue, donate=donate,
                compact_threshold=compact_threshold)
        return dataclasses.replace(self, data=data, queue=q)

    def compact(self, *, reserve: int | None = None) -> "IndexedFrame":
        """Merge all segments into one fresh arena (bounds MVCC probe
        fan-out; DESIGN.md §4) — lookups bit-identical before and after.
        Partitioned frames compact per partition (one global version
        bump)."""
        if self.is_partitioned:
            return dataclasses.replace(self, data=partition_mod.
                                       compact_partitioned(
                                           self.data, rt=self.rt,
                                           reserve=reserve))
        if self.is_distributed:
            new = self._refreshed(_dtable().compact_distributed(
                self.data, rt=self.rt, reserve=reserve))
        else:
            new = table_mod.compact(self.data, reserve=reserve)
        return dataclasses.replace(self, data=new)

    # -- partitions: pruned reads, O(1) retention (DESIGN.md §16) --------------

    def _need_partitioned(self, what: str):
        if not self.is_partitioned:
            raise ValueError(f"{what} needs a partitioned frame; build "
                             f"with from_columns(partition_by=...)")

    def drop_partition(self, pid) -> "IndexedFrame":
        """O(1) retention: structurally remove one partition (by id or
        index) — one version bump, no compact, no data movement; the
        surviving partitions' read sites never recompile
        (gate_partition)."""
        self._need_partitioned("drop_partition")
        return dataclasses.replace(
            self, data=partition_mod.drop_partition(self.data, pid))

    def retain(self, *, min_value=None, keep=None) -> "IndexedFrame":
        """Rolling retention sweep: ``min_value`` drops every range
        partition wholly below it (the hot-recent-window expiry);
        ``keep`` names the surviving partition ids.  One version bump."""
        self._need_partitioned("retain")
        return dataclasses.replace(
            self, data=partition_mod.retain(self.data, min_value=min_value,
                                            keep=keep))

    def per_partition_bytes(self) -> list:
        """Logical vs reserved bytes per partition (memory accounting —
        arena slack in cold partitions stays attributed to them)."""
        self._need_partitioned("per_partition_bytes")
        return self.data.per_partition_bytes()

    # -- skew resilience: hot-key tracking + replication (DESIGN.md §15) -------

    def with_hot_tracker(self, top_k: int | None = None, *,
                         mode: str = "topk") -> "IndexedFrame":
        """Attach an exact top-k hot-key tracker (``mode="sketch"`` for
        the count-min fallback) counting subsequent ingest — ONE treedef
        change, like attaching a queue; do it at (or right after)
        construction so lineage replay reproduces the hot set."""
        if self.is_partitioned:
            raise ValueError("hot-key tracking is per-table; attach "
                             "track_hot at construction "
                             "(from_columns(track_hot=..., "
                             "partition_by=...)) to track every partition")
        k = table_mod.DEFAULT_HOT_TOP_K if top_k is None else int(top_k)
        if self.is_distributed:
            hot = self._placed(table_mod.empty_tracker(
                k, mode=mode, num_shards=self.num_shards))
            data = dataclasses.replace(
                self.data, table=dataclasses.replace(self.data.table,
                                                     hot=hot))
        else:
            data = table_mod.with_hot(self.data, k, mode=mode)
        return dataclasses.replace(self, data=data)

    def with_replica(self, *, capacity: int | None = None,
                     max_matches: int | None = None) -> "IndexedFrame":
        """Attach the fixed-capacity hot-key mirror the hybrid flavors
        (rules L4/J4) answer hot queries from.  Starts stale (never
        consulted) until the first refresh; the facade auto-refreshes
        after every version bump from here on.  Needs a hot-key tracker
        and a distributed frame."""
        if not self.is_distributed:
            raise ValueError("with_replica needs a distributed frame "
                             "(a single partition has no exchange to skip)")
        dd = _dtable()
        kw = {}
        if capacity is not None:
            kw["capacity"] = int(capacity)
        if max_matches is not None:
            kw["max_matches"] = int(max_matches)
        return dataclasses.replace(self, data=dd.place_dtable(
            dd.attach_replica(self.data, **kw), self.rt))

    def refresh_replica(self) -> "IndexedFrame":
        """Re-mirror the current global top-H hot keys at the live
        version (one cached jit call, zero host syncs) — normally
        implicit: ``append``/``flush``/``compact`` refresh automatically
        when a mirror is attached."""
        return dataclasses.replace(
            self, data=_dtable().refresh_replica(self.data, rt=self.rt))

    def _placed(self, tree):
        """A shard-stacked pytree put on this frame's mesh, leaf by leaf
        (``dist.mesh.place``) — for state built outside an axis-mapped
        jit, so the read sites never see a new placement."""
        from repro.dist import mesh
        return mesh.place(tree, self.rt)

    def _refreshed(self, data):
        """Auto re-mirror after a version bump: a stale mirror is always
        SAFE (the hybrid degrades to pure routing) but cold — keeping it
        fresh on the write path is what keeps the Zipf sweep flat."""
        if getattr(data, "replica", None) is not None:
            data = _dtable().refresh_replica(data, rt=self.rt)
        return data

    # -- supervision (self-healing reads) --------------------------------------

    def supervised(self, *, lineage=None, policy=None, injector=None,
                   checkpoint_dir: str | None = None):
        """Wrap this distributed frame in a ``dist.resilience``
        ``RecoveryManager``: reads are version-fenced, integrity-probed,
        auto-healed (restore latest checkpoint + replay the lineage
        suffix + splice), and routed drops auto-retry with doubled
        capacity — failure handling as part of the operator contract
        instead of the caller's job (DESIGN.md §12).  The manager owns
        the live frame from here on (``manager.frame``).

        A PARTITIONED distributed frame heals per partition: one
        ``RecoveryManager`` per partition behind a
        ``PartitionedSupervisor`` whose reads route pruned sub-batches
        to the owning partition's manager — a fault in one partition
        never touches another partition's read path.  Inject faults per
        partition via ``supervisor.managers[i].injector``; pass
        ``lineage=True`` to auto-build one replay recipe per partition
        (a single frame-level ``Lineage`` cannot be split)."""
        from repro.dist import resilience
        if self.is_partitioned:
            if injector is not None or (lineage is not None
                                        and lineage is not True):
                raise ValueError(
                    "partitioned supervision is per partition: pass "
                    "lineage=True for auto per-partition lineages and "
                    "set supervisor.managers[i].injector for faults")
            return resilience.PartitionedSupervisor(
                self, policy=policy, checkpoint_dir=checkpoint_dir,
                with_lineage=lineage is True)
        return resilience.RecoveryManager(
            self, lineage=lineage, policy=policy, injector=injector,
            checkpoint_dir=checkpoint_dir)

    def serve(self, **kw):
        """Wrap this frame in a ``serving.query_engine.QueryEngine``:
        FIFO admission from many client streams, pad-to-bucket
        micro-batching into the fused read sites (one trace per bucket),
        writer deltas interleaved through the append ring (reads ride
        the pre-flush snapshot), p50/p99 SLO accounting (DESIGN.md §14).
        The engine owns the frame from here on (``engine.frame``);
        a supervised frame serves via ``frame.supervised(...).serve()``
        — i.e. ``QueryEngine(manager, **kw)``."""
        from repro.serving.query_engine import QueryEngine
        return QueryEngine(self, **kw)

    # -- relational plans ------------------------------------------------------

    def relation(self, name: str = "frame") -> planner_mod.Relation:
        """This frame as a ``core.planner`` Relation leaf (either
        backend; the planner dispatches on it)."""
        return planner_mod.Relation(name, table=self.data)

    def filter(self, pred, *,
               planner: planner_mod.Planner | None = None) -> FramePlan:
        return FramePlan(planner_mod.Filter(self.relation(), pred),
                         self._planner(planner))

    def select(self, *names,
               planner: planner_mod.Planner | None = None) -> FramePlan:
        return FramePlan(planner_mod.Project(self.relation(), tuple(names)),
                         self._planner(planner))

    def agg(self, op: str, col: str, *,
            planner: planner_mod.Planner | None = None) -> FramePlan:
        return FramePlan(planner_mod.Aggregate(self.relation(), op, col),
                         self._planner(planner))

    # -- persistence / elasticity ---------------------------------------------

    def save(self, path: str):
        """Checkpoint the frame's table (dist.checkpoint leaf format;
        partitioned frames save one CRC-verified subdir per partition
        plus the spec)."""
        if self.is_partitioned:
            partition_mod.save_partitioned(path, self.data)
        elif self.is_distributed:
            _checkpoint().save_dtable(path, self.data)
        else:
            _checkpoint().save_table(path, self.data)

    @classmethod
    def load(cls, path: str, like: "IndexedFrame") -> "IndexedFrame":
        """Restore a checkpoint into ``like``'s structure (``like``
        supplies the treedef AND the runtime, exactly as
        ``dist.checkpoint.restore_dtable``)."""
        if like.is_partitioned:
            data = partition_mod.restore_partitioned(path, like.data)
        elif like.is_distributed:
            data = _checkpoint().restore_dtable(path, like.data)
        else:
            data = _checkpoint().restore_table(path, like.data)
        return dataclasses.replace(like, data=data)

    def reshard(self, num_shards: int, *,
                rt_out: mesh.Runtime | None = None) -> "IndexedFrame":
        """Elastic scale: re-route every valid row into a ``num_shards``
        topology (``dist.checkpoint.reshard_dtable``; a local frame is
        promoted by the same collect -> re-route -> re-index pass).  The
        global MVCC version is preserved.  A pending append ring is
        flushed first (its lane shapes are per-topology), and the
        resharded frame comes back queue-less — ``with_queue()`` again
        on the new topology."""
        self = self.flush()
        if self.is_partitioned:
            # per-partition reshard: each partition re-routes its own rows
            # into the new topology (partition-major, shard-minor); the
            # global MVCC version is preserved
            parts = tuple(
                IndexedFrame(data=p, rt=self.rt)
                .reshard(num_shards, rt_out=rt_out).data
                for p in self.data.parts)
            pt = dataclasses.replace(self.data, parts=parts)
            return IndexedFrame(data=pt, rt=rt_out)
        dd = _dtable() if self.is_distributed else None
        if self.is_distributed:
            old = self.data
            new = _checkpoint().reshard_dtable(self.data, num_shards, rt=self.rt,
                                      rt_out=rt_out)
            if old.table.hot is not None:
                # carry the hot set into the new topology: re-route the
                # tracker entries to their new owners (counts survive as
                # exact lower bounds; DESIGN.md §15)
                new = dataclasses.replace(new, table=dataclasses.replace(
                    new.table,
                    hot=dd.reseed_tracker(old.table.hot, num_shards)))
            if old.replica is not None:
                new = dd.attach_replica(
                    new, capacity=old.replica.keys.shape[0],
                    max_matches=old.replica.max_matches)
                new = dd.refresh_replica(new, rt=rt_out)
            return IndexedFrame(data=new, rt=rt_out)
        t = self.data
        valid_all = np.concatenate([np.asarray(s.valid)
                                    for s in t.segments])
        bases = np.concatenate([np.asarray(s.row_base
                                           + np.arange(s.capacity))
                                for s in t.segments])
        cols = t.gather_rows(jnp.asarray(bases[valid_all], PTR_DTYPE))
        dt = _dtable().create_distributed(
            {k: np.asarray(v) for k, v in cols.items()}, t.schema,
            num_shards, rows_per_batch=t.rows_per_batch, layout=t.layout,
            slots=t.slots, rt=rt_out)
        dt = dataclasses.replace(
            dt, version=jnp.asarray(int(np.asarray(t.version)), jnp.int32))
        if t.hot is not None:
            dt = dataclasses.replace(dt, table=dataclasses.replace(
                dt.table, hot=_dtable().reseed_tracker(t.hot, num_shards)))
        dt = _dtable().place_dtable(dt, rt_out)
        return IndexedFrame(data=dt, rt=rt_out)
