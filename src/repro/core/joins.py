"""Join and lookup operators: the indexed paths plus the vanilla baselines.

The paper's comparison (Fig 7/8, Table III) is *indexed join vs. what Spark
does*: per-query hash-table builds (BroadcastHash) or sort-merge.  We
implement all of them with identical output contracts so the benchmarks and
property tests compare like for like:

* ``indexed_join``     — paper §III-C: the indexed side is the pre-built
                         *build* side; probe rows are looked up against it.
* ``hash_join``        — baseline: builds a fresh transient index per call
                         (Spark's per-query hash-table build, amortized never).
* ``sort_merge_join``  — baseline: sort both sides + binary-search merge.
* ``scan_lookup``      — baseline point lookup: O(n) linear scan.
* ``indexed_lookup``   — paper's point lookup: O(1) probe + chain walk.

Output contract for joins: ``(result_cols, valid)`` where every probe row
yields ``max_matches`` slots (newest-first, padded) — static shapes for XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import hashindex as hix
from repro.core.pointers import NULL_PTR
from repro.core.table import IndexedTable


# ---------------------------------------------------------------------------
# Input validation — ONE contract for every lookup/join entry point
# ---------------------------------------------------------------------------
#
# The facade (repro/frame.py), the local operators here, and the dist layer
# all enforce the same two checks through these helpers, so a bad call fails
# with the same ValueError no matter which surface it entered through.

def check_max_matches(max_matches: int):
    """Reject non-positive match-slot counts before any tracing happens."""
    if max_matches <= 0:
        raise ValueError(
            f"max_matches must be a positive match-slot count, "
            f"got {max_matches}")


def as_int64_keys(keys) -> jnp.ndarray:
    """Coerce ``keys`` to a jnp array and reject non-int64 dtypes."""
    keys = jnp.asarray(keys)
    if keys.dtype != jnp.int64:
        raise ValueError(
            f"query keys must be int64 (got {keys.dtype}); keys are int64 "
            f"at every API boundary — pre-hash string keys at ingest "
            f"(hashing.hash_string_host, DESIGN.md §9) and cast narrower "
            f"integer keys explicitly")
    return keys


# ---------------------------------------------------------------------------
# Indexed paths (the paper's contribution)
# ---------------------------------------------------------------------------

def indexed_lookup(table: IndexedTable, keys, *, max_matches: int,
                   names=None, fused: bool = True):
    """Point lookup: rows for each key, newest-first.  Returns
    (cols dict with shape [Q, max_matches], valid [Q, max_matches]).

    ``fused=True`` (default) runs the probe -> chain-walk -> gather pipeline
    in one pass over the table's stored Snapshot (DESIGN.md §3);
    ``fused=False`` keeps the segment-looped reference path for parity
    sweeps."""
    check_max_matches(max_matches)
    with obs.span("joins.probe"):
        keys = as_int64_keys(keys)
        rids, _ = table.lookup(keys, max_matches, fused=fused)
        valid = rids != NULL_PTR
    cols = table.gather_rows(jnp.maximum(rids, 0), names=names, fused=fused)
    return cols, valid


def indexed_join(table: IndexedTable, probe_cols: dict, probe_key: str, *,
                 max_matches: int, names=None, fused: bool = True):
    """Equi-join: ``table`` (indexed) is the build side; ``probe_cols`` rows
    probe it locally (the distributed layer shuffles probes to the owning
    partition first; see dist/dtable.py).

    Returns (build_cols [Q, M], probe_cols broadcast [Q, M], valid [Q, M]).
    """
    keys = jnp.asarray(probe_cols[probe_key], jnp.int64)
    build_cols, valid = indexed_lookup(table, keys, max_matches=max_matches,
                                       names=names, fused=fused)
    m = valid.shape[1]
    probe_b = {k: jnp.broadcast_to(v[:, None], (v.shape[0], m))
               for k, v in probe_cols.items()}
    return build_cols, probe_b, valid


# ---------------------------------------------------------------------------
# Vanilla baselines (what Spark does per query)
# ---------------------------------------------------------------------------

def hash_join(build_cols: dict, build_key: str, probe_cols: dict,
              probe_key: str, *, max_matches: int,
              num_buckets: int | None = None):
    """Per-call hash join: builds the hash table *inside* the call, exactly
    the repeated work the paper's Fig 1 flame graph shows for vanilla Spark.

    With ``num_buckets`` given the build is single-shot (jit-traceable,
    used by the benchmarks); otherwise the host-coordinated
    overflow-doubling retry runs (exact, used by tests).
    """
    bkeys = jnp.asarray(build_cols[build_key], jnp.int64)
    n = bkeys.shape[0]
    rids = jnp.arange(n, dtype=jnp.int32)
    if num_buckets is not None:
        valid = jnp.ones((n,), bool)
        bk, bp, prev_rows, prev_vals, _ = hix._build_arrays(
            bkeys, rids, valid, num_buckets, hix.DEFAULT_SLOTS)
        index = hix.HashIndex(bk, bp, num_buckets, hix.DEFAULT_SLOTS)
    else:
        index, prev_rows, prev_vals = hix.build_index(bkeys, rids)
    prev = jnp.full((n,), NULL_PTR, jnp.int32)
    prev = prev.at[prev_rows].set(prev_vals, mode="drop")

    qkeys = jnp.asarray(probe_cols[probe_key], jnp.int64)
    head = hix.probe(index, qkeys)
    rows, _ = hix.chain_walk(prev, head, max_matches)
    valid = rows != NULL_PTR
    safe = jnp.maximum(rows, 0)
    out_build = {k: jnp.asarray(v)[safe] for k, v in build_cols.items()}
    m = valid.shape[1]
    out_probe = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                     (v.shape[0], m))
                 for k, v in probe_cols.items()}
    return out_build, out_probe, valid


def sort_merge_join(build_cols: dict, build_key: str, probe_cols: dict,
                    probe_key: str, *, max_matches: int):
    """Sort both sides, binary-search each probe key into the sorted build
    side, emit up to ``max_matches`` matches (newest build rows first, to
    match the indexed contract)."""
    bkeys = jnp.asarray(build_cols[build_key], jnp.int64)
    n = bkeys.shape[0]
    order = jnp.lexsort((jnp.arange(n), bkeys))
    k_s = bkeys[order]
    qkeys = jnp.asarray(probe_cols[probe_key], jnp.int64)
    lo = jnp.searchsorted(k_s, qkeys, side="left")
    hi = jnp.searchsorted(k_s, qkeys, side="right")
    # newest-first: walk from hi-1 downward
    offs = jnp.arange(max_matches, dtype=jnp.int32)
    pos = (hi - 1)[:, None] - offs[None, :]
    valid = pos >= lo[:, None]
    safe = jnp.clip(pos, 0, n - 1)
    rows = order[safe]
    out_build = {k: jnp.asarray(v)[rows] for k, v in build_cols.items()}
    out_probe = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                     (v.shape[0], max_matches))
                 for k, v in probe_cols.items()}
    return out_build, out_probe, valid


def scan_lookup(table: IndexedTable, keys, *, max_matches: int, names=None):
    """O(n) linear-scan point lookup (Spark without index/partitioning).
    Same output contract as indexed_lookup."""
    all_keys, row_valid = table.scan_column(table.schema.key)
    q = jnp.asarray(keys, jnp.int64)
    eq = (all_keys[None, :] == q[:, None]) & row_valid[None, :]   # [Q, N]
    n = all_keys.shape[0]
    # newest-first top-k via sorting match positions descending
    pos = jnp.where(eq, jnp.arange(n, dtype=jnp.int32)[None, :],
                    jnp.int32(-1))
    topk = jax.lax.top_k(pos, max_matches)[0]                      # [Q, M]
    valid = topk >= 0
    cols = table.gather_rows(jnp.maximum(topk, 0), names=names)
    return cols, valid


# ---------------------------------------------------------------------------
# Simple relational reducers used by the planner + benchmarks
# ---------------------------------------------------------------------------

def _reduce_identity(dtype, op: str):
    """Dtype-preserving identity for min/max (no silent int->float cast)."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return dtype.type(info.max if op == "min" else info.min)
    return dtype.type(jnp.inf if op == "min" else -jnp.inf)


def aggregate(values, valid, op: str):
    v = jnp.asarray(values)
    if op == "sum":
        return jnp.sum(jnp.where(valid, v, v.dtype.type(0)))
    if op == "count":
        return jnp.sum(valid)
    if op in ("min", "max"):
        red = jnp.min if op == "min" else jnp.max
        return red(jnp.where(valid, v, _reduce_identity(v.dtype, op)))
    if op == "mean":
        total = jnp.sum(jnp.where(valid, v, v.dtype.type(0)))
        return total / jnp.maximum(jnp.sum(valid), 1)
    raise ValueError(op)
