"""Checkpoint / restore / elastic reshard for DistributedTable.

The paper's recovery story (§III-D) is lineage replay; checkpointing is
the complementary fast path — persist the dtable's leaves once, restore
in O(load) instead of O(replay).  Because a dtable is one pytree, a
checkpoint is just its flattened leaves plus structural metadata; restore
validates the template's structure leaf-by-leaf (shape mismatches are a
hard error, not a silent reinterpretation — restoring a 4-shard
checkpoint into an 8-shard dtable would scramble ownership).

``reshard_dtable`` is elastic scaling: collect every valid row (order-
preserving per shard, so per-key MVCC chains keep their newest-first
order), then re-route and re-index at the new shard count.  This is the
checkpoint-portable form of scaling — save at 4 shards, restore the data
at 8 by resharding, not by reinterpreting leaves.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zipfile
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import dtable as _dtable
from repro.dist import mesh as _mesh

_LEAVES = "leaves.npz"
_META = "meta.json"
# v1: leaves + num_leaves only.  v2: adds per-leaf CRC32s — restore
# verifies them, so a flipped bit (disk rot, partial write, an injected
# checkpoint_corruption fault) is a clear ValueError, never a silently
# scrambled index.
FORMAT_VERSION = 2


def _leaf_crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def _save_leaves(path: str, obj, extra_meta: dict):
    """Persist any registered pytree: flattened leaves + metadata.

    MVCC versions and arena fill counters are data *leaves* (DESIGN.md
    §4), so they ride in ``leaves.npz`` like everything else; the meta
    entries carry the format version, per-leaf CRC32s (integrity,
    DESIGN.md §12), and informational fields (back-compat for old
    readers).
    """
    os.makedirs(path, exist_ok=True)
    leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(obj)]
    np.savez(os.path.join(path, _LEAVES),
             **{f"leaf_{i}": a for i, a in enumerate(leaves)})
    meta = {"format_version": FORMAT_VERSION, "num_leaves": len(leaves),
            "leaf_crc32": [_leaf_crc(a) for a in leaves], **extra_meta}
    with open(os.path.join(path, _META), "w") as f:
        json.dump(meta, f)


def _restore_leaves(path: str, like, meta: dict):
    """Unflatten a checkpoint into ``like``'s treedef, validating every
    leaf's shape against the template AND its recorded CRC32 (format v2)
    — shape mismatches and flipped bits are hard ``ValueError``s, not a
    silent reinterpretation / silently scrambled restore."""
    like_leaves, treedef = jax.tree_util.tree_flatten(like)
    if meta["num_leaves"] != len(like_leaves):
        raise ValueError(
            f"checkpoint has {meta['num_leaves']} leaves; template has "
            f"{len(like_leaves)} (different segment count or layout?)")
    leaves_path = os.path.join(path, _LEAVES)
    try:
        with np.load(leaves_path) as data:
            saved = [data[f"leaf_{i}"] for i in range(meta["num_leaves"])]
    except FileNotFoundError:
        raise ValueError(
            f"checkpoint at {path!r} has no {_LEAVES} (interrupted save?)")
    except KeyError as e:
        raise ValueError(
            f"checkpoint at {path!r} is truncated: {_LEAVES} is missing "
            f"{e} of the {meta['num_leaves']} recorded leaves") from e
    except (zipfile.BadZipFile, OSError) as e:
        raise ValueError(
            f"checkpoint {_LEAVES} at {path!r} is corrupt: {e}") from e
    crcs = meta.get("leaf_crc32")
    if crcs is not None:
        if len(crcs) != len(saved):
            raise ValueError(
                f"checkpoint meta at {path!r} is truncated: "
                f"{len(crcs)} CRCs for {len(saved)} leaves")
        for i, (s, want) in enumerate(zip(saved, crcs)):
            got = _leaf_crc(s)
            if got != want:
                raise ValueError(
                    f"checkpoint corruption at {path!r}: leaf {i} CRC32 "
                    f"{got:#010x} != recorded {want:#010x} (bit flip or "
                    f"partial write); restore from an older checkpoint or "
                    f"replay lineage")
    for i, (s, l) in enumerate(zip(saved, like_leaves)):
        if tuple(s.shape) != tuple(np.shape(l)):
            raise ValueError(
                f"leaf {i}: checkpoint shape {tuple(s.shape)} != template "
                f"shape {tuple(np.shape(l))}")
    # MVCC versions are data leaves (DESIGN.md §4), so unflatten restores
    # the checkpoint's own versions — no meta surgery needed (a version-0
    # empty-clone template cannot demote version-3 data).
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(a) for a in saved])


def _read_meta(path: str) -> dict:
    meta_path = os.path.join(path, _META)
    try:
        with open(meta_path) as f:
            text = f.read()
    except FileNotFoundError:
        raise ValueError(
            f"no checkpoint at {path!r}: {_META} is missing (not a "
            f"checkpoint directory, or an interrupted save)")
    try:
        meta = json.loads(text)
    except json.JSONDecodeError as e:
        raise ValueError(
            f"checkpoint {_META} at {path!r} is corrupt or truncated: "
            f"{e}") from e
    if not isinstance(meta, dict) or "num_leaves" not in meta:
        raise ValueError(
            f"checkpoint {_META} at {path!r} is not a checkpoint record "
            f"(missing num_leaves)")
    return meta


def save_dtable(path: str, dt: _dtable.DistributedTable):
    """Persist a dtable: flattened pytree leaves + structural metadata."""
    _save_leaves(path, dt, {
        "num_shards": dt.num_shards,
        "version": int(np.asarray(dt.version)),
        "table_version": int(np.asarray(dt.table.version).ravel()[0])})


def restore_dtable(path: str,
                   like: _dtable.DistributedTable) -> _dtable.DistributedTable:
    """Restore a checkpoint into ``like``'s structure and placement.

    ``like`` supplies the treedef (a dtable of the same construction —
    typically the live one or a freshly built empty clone).  Every leaf is
    validated against the template's shape; any mismatch (different shard
    count, capacity, segment count...) raises ``ValueError``.  Each
    restored leaf lands on ``like``'s sharding (``mesh.place_like``): on
    a shard_map mesh every shard on its own device.
    """
    meta = _read_meta(path)
    if meta.get("num_shards", like.num_shards) != like.num_shards:
        raise ValueError(
            f"checkpoint was saved with {meta['num_shards']} shards; "
            f"template has {like.num_shards} — reshard_dtable the restored "
            f"table instead of restoring into a different topology")
    return _mesh.place_like(_restore_leaves(path, like, meta), like)


def save_table(path: str, t):
    """Persist a single-partition ``IndexedTable`` — the same leaves+meta
    layout as ``save_dtable``, so the facade's ``.save`` works for either
    backend."""
    _save_leaves(path, t, {"version": int(np.asarray(t.version))})


def restore_table(path: str, like):
    """Restore an ``IndexedTable`` checkpoint into ``like``'s structure
    (leaf-by-leaf shape validation, as ``restore_dtable``)."""
    meta = _read_meta(path)
    if "num_shards" in meta:
        raise ValueError(
            f"checkpoint at {path!r} holds a {meta['num_shards']}-shard "
            f"DistributedTable; restore it with restore_dtable")
    return _restore_leaves(path, like, meta)


def reshard_dtable(dt: _dtable.DistributedTable, num_shards: int, *,
                   rt: "_mesh.Runtime | None" = None,
                   rt_out: "_mesh.Runtime | None" = None
                   ) -> _dtable.DistributedTable:
    """Elastic scale up/down: collect valid rows, re-route, re-index.

    Preserves the dtable's global MVCC version; the resharded table is a
    single-segment compaction (per-key newest-first order survives because
    collection is order-preserving within each shard and a key's rows
    never span shards).  ``rt`` maps the collection over ``dt``'s shard
    axis; ``rt_out`` builds the new topology (they differ whenever the
    shard count changes — a shard_map runtime is pinned to its mesh size).
    """
    cols = _collect_cols(dt, rt=rt)
    fresh = _dtable.create_distributed(
        cols, dt.schema, num_shards, rows_per_batch=dt.rows_per_batch,
        layout=dt.layout, slots=dt.slots, rt=rt_out)
    return dataclasses.replace(
        fresh, version=_mesh.place(dt.version, rt_out, stacked=False))


# Row collection lives with the dtable now (compact_distributed shares it);
# kept under the old name for external callers.
_collect_cols = _dtable.collect_cols
