"""Distributed runtime: lineage recovery, version fencing, stragglers.

Paper §III-D + Fig 12: an executor failure must not lose the indexed
cache — the lost partition is rebuilt from *lineage* (the deterministic
recipe: re-route the base dataframe, re-index, replay appends), the failed
query pays the rebuild, and subsequent queries return to steady state.
``benchmarks/fault_tolerance.py`` measures exactly that spike shape.

Because a dtable's construction pipeline is deterministic (host routing,
vmapped builds, host-coordinated overflow retries), a lineage replay
reproduces the lost shard's leaves bit-for-bit shape-wise — so a rebuilt
dtable re-enters the same jit cache entry as the original (no recompile
after recovery, which is what keeps the Fig-12 tail flat).

``VersionVector`` is the stale-read fence of §III-D: readers carry the
version they indexed against; a shard that has moved on (or is marked
stale during rebuild) rejects the read.  ``StragglerPolicy`` plans
speculative re-execution for shards running past a deadline factor —
the standard lineage-system mitigation the paper inherits from Spark.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashindex as hix
from repro.core import hashing
from repro.core.hashindex import EMPTY_KEY
from repro.core.pointers import NULL_PTR
from repro.core.schema import Schema
from repro.dist import dtable as _dtable
from repro.dist import mesh as _mesh


class Lineage:
    """Host-side append log: the deterministic recipe for any shard.

    Records the base dataframe and every appended delta (defensive copies
    — lineage must survive mutation of the caller's buffers).  ``replay``
    re-runs the exact construction pipeline at any shard count.

    The log would grow without bound under a write-hot stream, so it can
    be **checkpoint-anchored** (``truncate``): once a checkpoint holds the
    dtable at version ``v``, every delta at or below ``v`` is subsumed by
    the checkpoint and dropped — replay becomes restore-the-anchor plus
    the *suffix* of deltas since it, O(deltas since last checkpoint)
    instead of O(full history) (paper §III-D; DESIGN.md §12).
    """

    def __init__(self, schema: Schema, base_cols: dict, *,
                 rows_per_batch: int = 4096, layout: str = "row",
                 slots: int = hix.DEFAULT_SLOTS):
        self.schema = schema
        self.rows_per_batch = rows_per_batch
        self.layout = layout
        self.slots = slots
        self.base = {k: np.array(v, copy=True)
                     for k, v in base_cols.items()}
        self.deltas: list[tuple[dict, np.ndarray | None]] = []
        # version the replay STARTS from: 0 = the base recipe; after
        # truncate(v, path) the anchor checkpoint holds version v.
        self.base_version = 0
        self.anchor_path: str | None = None

    @property
    def version(self) -> int:
        """The dtable version a full replay reproduces (one bump per
        recorded append; appends are the only version-bumping ops a
        lineage records)."""
        return self.base_version + len(self.deltas)

    @property
    def has_base(self) -> bool:
        """Whether a from-scratch replay is still possible (False once
        ``truncate`` anchored the log to a checkpoint)."""
        return self.base is not None

    def record_append(self, delta_cols: dict, valid=None):
        self.deltas.append((
            {k: np.array(v, copy=True) for k, v in delta_cols.items()},
            None if valid is None else np.array(valid, bool, copy=True)))

    def deltas_since(self, version: int) -> list:
        """The replay suffix for a dtable restored at ``version``."""
        k = version - self.base_version
        if not 0 <= k <= len(self.deltas):
            raise ValueError(
                f"lineage covers versions [{self.base_version}, "
                f"{self.version}]; cannot take the suffix after {version}")
        return self.deltas[k:]

    def truncate(self, version: int, checkpoint_path: str):
        """Anchor the log at a checkpoint: deltas at or below ``version``
        are subsumed by ``checkpoint_path`` and dropped (the base recipe
        too).  Closes the unbounded delta log: replay cost from here on is
        O(deltas since the anchor)."""
        suffix = self.deltas_since(version)   # validates the version
        self.deltas = suffix
        self.base_version = version
        self.anchor_path = checkpoint_path
        self.base = None

    def _apply(self, dt: _dtable.DistributedTable, deltas,
               rt: "_mesh.Runtime | None") -> _dtable.DistributedTable:
        for delta, valid in deltas:
            dt = _dtable.append_distributed(dt, delta, valid, rt=rt)
        return dt

    def replay_from(self, checkpoint_path: str, version: int, like, *,
                    rt: "_mesh.Runtime | None" = None
                    ) -> _dtable.DistributedTable:
        """Restore the checkpoint holding ``version`` into ``like``'s
        structure, then replay only the lineage suffix since it — the
        fast recovery path (O(deltas since checkpoint)).  Raises
        ``ValueError`` on a corrupt/missing checkpoint (CRC-verified,
        dist/checkpoint.py) or a version outside the log."""
        from repro.dist import checkpoint as _ckpt
        suffix = self.deltas_since(version)   # validate before touching IO
        dt = _ckpt.restore_dtable(checkpoint_path, like)
        return self._apply(dt, suffix, rt)

    def replay(self, num_shards: int,
               rt: "_mesh.Runtime | None" = None, *,
               like=None) -> _dtable.DistributedTable:
        """Re-run the construction pipeline — on whichever execution
        backend the live system uses (lineage is backend-agnostic: the
        two are bit-identical, tests/test_mesh_parity.py).  A truncated
        lineage replays from its anchor checkpoint instead of the base
        recipe and then needs ``like`` (the live dtable) as the restore
        template."""
        if self.base is None:
            if like is None:
                raise ValueError(
                    "lineage was truncated to a checkpoint anchor; "
                    "replay needs like= (the live dtable) as the restore "
                    "template")
            return self.replay_from(self.anchor_path, self.base_version,
                                    like, rt=rt)
        dt = _dtable.create_distributed(
            self.base, self.schema, num_shards,
            rows_per_batch=self.rows_per_batch, layout=self.layout,
            slots=self.slots, rt=rt)
        if like is not None and like.table.hot is not None:
            # Re-attach an EMPTY tracker shaped like the live one BEFORE
            # replaying the append log: trackers are attached at creation
            # and count ingest only (never back-counted), so the replay
            # reproduces the live tracker bit-identically — and the
            # spliced pytree structurally matches (splice_shard tree_maps
            # the whole table).
            h = like.table.hot
            sd, sw = ((h.sketch.shape[-2], h.sketch.shape[-1])
                      if h.sketch is not None
                      else (_dtable.table_mod.SKETCH_DEPTH,
                            _dtable.table_mod.SKETCH_WIDTH))
            dt = dataclasses.replace(dt, table=dataclasses.replace(
                dt.table, hot=_mesh.place(_dtable.table_mod.empty_tracker(
                    h.keys.shape[-1], mode=h.mode, sketch_depth=sd,
                    sketch_width=sw, num_shards=num_shards), rt)))
        return self._apply(dt, self.deltas, rt)


def fail_shard(dt: _dtable.DistributedTable,
               shard: int) -> _dtable.DistributedTable:
    """Simulate executor loss: blank the shard's slice of every leaf.

    Shapes (and therefore jit caches) are preserved — only the shard's
    contents are gone, exactly like a re-attached blank executor.  Index
    structures are blanked to their *sentinels* (EMPTY keys, NULL
    pointers, valid=False), not zero: zero is a legal key and a legal row
    id, and a dead shard must answer every lookup with a miss, never a
    fabricated key-0 match.  The blanked dtable keeps ``dt``'s placement.
    """

    def kill(leaf, fill):
        return leaf.at[shard].set(jnp.asarray(fill).astype(leaf.dtype))

    t = dt.table
    ehi, elo = hashing.split64(jnp.full((), EMPTY_KEY, jnp.int64))
    segments = tuple(dataclasses.replace(
        s,
        data=jax.tree.map(lambda a: kill(a, 0), s.data),
        valid=kill(s.valid, False),
        prev=kill(s.prev, NULL_PTR),
        index=dataclasses.replace(s.index,
                                  bucket_keys=kill(s.index.bucket_keys,
                                                   EMPTY_KEY),
                                  bucket_ptrs=kill(s.index.bucket_ptrs,
                                                   NULL_PTR)))
        for s in t.segments)
    snap = dataclasses.replace(
        t.snapshot,
        blocks=tuple(dataclasses.replace(b, key_hi=kill(b.key_hi, ehi),
                                         key_lo=kill(b.key_lo, elo),
                                         ptrs=kill(b.ptrs, NULL_PTR))
                     for b in t.snapshot.blocks),
        prev=kill(t.snapshot.prev, NULL_PTR),
        # arena fill -> 0: the dead shard's fused reads mask everything
        # out (defense in depth on top of the EMPTY/NULL sentinels)
        fill=kill(t.snapshot.fill, 0),
        data=(None if t.snapshot.data is None
              else jax.tree.map(lambda a: kill(a, 0), t.snapshot.data)))
    table = dataclasses.replace(t, segments=segments, snapshot=snap)
    if t.hot is not None:
        # The shard's hot-key counts died with it (rebuilt by lineage
        # replay, which replays them bit-identically into the splice).
        table = dataclasses.replace(table, hot=dataclasses.replace(
            t.hot, keys=kill(t.hot.keys, EMPTY_KEY),
            counts=kill(t.hot.counts, 0),
            sketch=(None if t.hot.sketch is None
                    else kill(t.hot.sketch, 0))))
    out = dataclasses.replace(dt, table=table)
    if dt.replica is not None:
        # The dead executor's replica copy is gone; our un-stacked
        # representation models that as global staleness (version -1 ⇒
        # hybrid degrades to pure routing), and the supervisor's heal
        # re-mirrors after the splice — bit-identical to a refresh on a
        # never-failed dtable, since tracker and rows are restored
        # bit-identically first.
        out = dataclasses.replace(out, replica=dataclasses.replace(
            dt.replica, version=jnp.asarray(-1, jnp.int32)))
    return _mesh.place_like(out, dt)


def splice_shard(dt: _dtable.DistributedTable, shard: int,
                 fresh: _dtable.DistributedTable
                 ) -> _dtable.DistributedTable:
    """Splice one shard's slice of ``fresh`` into ``dt`` (the recovery
    state machine's final step — DESIGN.md §12).  Leaf shapes and
    placements are ``dt``'s, so the spliced dtable re-enters every live
    jit cache entry.  Raises if the two dtables disagree on
    the global version (a lineage that missed a ``record_append``)."""
    if int(np.asarray(fresh.version)) != int(np.asarray(dt.version)):
        raise ValueError(
            f"lineage replays to version {int(np.asarray(fresh.version))} "
            f"but the dtable is at version {int(np.asarray(dt.version))}; "
            f"every append_distributed must be paired with "
            f"Lineage.record_append")

    def splice(broken, rebuilt):
        return broken.at[shard].set(rebuilt[shard])

    table = jax.tree.map(splice, dt.table, fresh.table)
    return _mesh.place_like(dataclasses.replace(dt, table=table), dt)


def rebuild_shard(dt: _dtable.DistributedTable, shard: int,
                  lineage: Lineage,
                  rt: "_mesh.Runtime | None" = None
                  ) -> _dtable.DistributedTable:
    """Lineage recovery (paper Fig 12): rebuild one shard and splice it in.

    CI-scale replays the whole pipeline and takes the shard's slice —
    determinism makes the splice exact; a production runtime re-routes
    only the lost partition's rows.  A checkpoint-anchored lineage
    replays restore + suffix instead of the full history.  Raises if the
    lineage's version disagrees with the live dtable (missed
    ``record_append``).
    """
    fresh = lineage.replay(dt.num_shards, rt=rt, like=dt)
    return splice_shard(dt, shard, fresh)


@dataclasses.dataclass
class VersionVector:
    """Per-shard MVCC fencing (paper §III-D stale-read detection)."""

    versions: list
    stale: set

    @classmethod
    def fresh(cls, num_shards: int) -> "VersionVector":
        return cls(versions=[0] * num_shards, stale=set())

    def bump(self, shard: int):
        self.versions[shard] += 1

    def bump_all(self):
        self.versions = [v + 1 for v in self.versions]

    def mark_stale(self, shard: int):
        """Fence a shard out (failed / mid-rebuild): no version passes."""
        self.stale.add(shard)

    def mark_fresh(self, shard: int, version: int | None = None):
        self.stale.discard(shard)
        if version is not None:
            self.versions[shard] = version

    def check_fresh(self, shard: int, version: int) -> bool:
        """True iff a read indexed at ``version`` is safe on ``shard``."""
        return shard not in self.stale and version >= self.versions[shard]


class StragglerPolicy:
    """Speculative re-execution planning (deadline = factor x median).

    ``min_deadline`` is an absolute floor (seconds): an all-fast batch has
    a near-zero median, and ``factor × ~0`` would flag every harmless
    microsecond of jitter as a straggler.  Below the floor, nothing is
    slow enough to be worth a speculative copy.
    """

    def __init__(self, deadline_factor: float = 2.0,
                 min_deadline: float = 1e-3):
        if deadline_factor <= 0 or min_deadline < 0:
            raise ValueError(
                f"deadline_factor must be > 0 and min_deadline >= 0, got "
                f"{deadline_factor!r} / {min_deadline!r}")
        self.deadline_factor = deadline_factor
        self.min_deadline = min_deadline
        self.slow: list[int] = []

    def observe(self, durations) -> list:
        """Record per-shard task durations; returns straggler indices.
        An empty batch observes nothing (and clears the straggler set)."""
        d = np.asarray(durations, dtype=np.float64)
        if d.size == 0:
            self.slow = []
            return self.slow
        deadline = max(self.deadline_factor * float(np.median(d)),
                       self.min_deadline)
        self.slow = [i for i, t in enumerate(d) if t > deadline]
        return self.slow

    def plan_speculative(self, num_shards: int) -> dict:
        """{straggler shard -> healthy shard to run the backup copy on};
        backups round-robin over the healthy shards."""
        healthy = [i for i in range(num_shards) if i not in self.slow]
        if not healthy:
            return {}
        return {s: healthy[j % len(healthy)]
                for j, s in enumerate(self.slow)}
