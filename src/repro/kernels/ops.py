"""jit'd public wrappers around the Pallas kernels.

``probe`` is a drop-in accelerated replacement for
``repro.core.hashindex.probe`` — same signature, same results (tests sweep
both) — run as the one-segment case of the fused kernel.
``fused_lookup`` is the multi-segment hot path: probe + in-kernel chain
walk over a table's stored Snapshot (DESIGN.md §3).  The wrappers own
everything that does not belong in the vector kernel: bucket-id hashing
(64-bit scalar math), int64 -> (hi, lo) plane splitting, tile padding, and
EMPTY-key masking.

Backend dispatch: ``interpret=None`` resolves per jax backend (kernel
compiled on TPU, interpret elsewhere).  For the fused path on non-TPU
backends the Pallas interpreter's per-query scalar loops are pure overhead,
so the dispatcher runs the *vectorized* flat oracle (ref.fused_lookup_ref —
bit-identical contract, swept against the kernel in tests) unless
``use_kernel=True`` forces the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing, snapshot
from repro.core.hashindex import EMPTY_KEY, HashIndex
from repro.core.pointers import NULL_PTR
from repro.kernels import hash_probe, ref, runtime
from repro.kernels import decode_attention as _da


def probe(index: HashIndex, query_keys, *, interpret: bool | None = None):
    """Latest row id per query key — the fused kernel's one-segment,
    ``max_matches=1`` case.  A bare index has no chains and no arena, so
    ``prev`` is one all-NULL lane row (every hop ends) and ``fill`` masks
    nothing."""
    hi, lo = hashing.split64(index.bucket_keys)
    block = snapshot.FlatBlock(key_hi=hi, key_lo=lo, ptrs=index.bucket_ptrs,
                               num_buckets=index.num_buckets)
    snap = snapshot.probe_view(
        (block,), jnp.full((hash_probe.LANES,), NULL_PTR, jnp.int32),
        jnp.int32(jnp.iinfo(jnp.int32).max),
        bucket_counts=(index.num_buckets,), layout="row")
    rows, _ = fused_lookup(query_keys, snap, max_matches=1,
                           interpret=interpret, use_kernel=True)
    return rows[:, 0]


# ---------------------------------------------------------------------------
# Fused multi-segment lookup (probe -> chain walk) over a stored Snapshot
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_matches",))
def _fused_ref_jit(qp, snap, *, max_matches):
    bids = jnp.stack([hashing.bucket_hash(qp, nb)
                      for nb in snap.bucket_counts])
    qhi, qlo = hashing.split64(qp)
    return ref.fused_lookup_ref(bids, qhi, qlo, snap, max_matches)


@functools.partial(jax.jit, static_argnames=("max_matches", "interpret"))
def _fused_kernel_jit(q, snap, *, max_matches, interpret):
    """Kernel-branch prep (pad, hash, split) fused into one jitted program
    so a direct fused_lookup call dispatches once, not per prep op."""
    pad = (-q.shape[0]) % hash_probe.QUERY_TILE
    qp = jnp.pad(q, (0, pad), constant_values=int(EMPTY_KEY))
    bids = jnp.stack([hashing.bucket_hash(qp, nb)
                      for nb in snap.bucket_counts])
    qhi, qlo = hashing.split64(qp)
    rows, last = hash_probe.fused_lookup_tiles(
        bids, qhi, qlo, snap, max_matches=max_matches, interpret=interpret)
    return rows[:q.shape[0]], last[:q.shape[0]]


def fused_lookup(query_keys, snap, *, max_matches: int,
                 interpret: bool | None = None,
                 use_kernel: bool | None = None):
    """[Q] keys against a table's Snapshot -> ([Q, M] rows, truncated).

    query_keys : [Q] int64
    snap       : core.snapshot.Snapshot — ragged per-segment (hi, lo, ptrs)
                 bucket planes, per-segment bucket counts (treedef meta;
                 each segment's bucket ids are computed modulo its own
                 count), and the flat [capacity] int32 backward-pointer
                 array.  A registered pytree: under jit/vmap the arrays
                 trace as leaves (zero in-graph view rebuilds) and the
                 same code runs per-shard in the distributed layer.
    Returns rows [Q, max_matches] global row ids newest-first (NULL-padded)
    and truncated [Q] bool — identical contract to IndexedTable.lookup_ref.

    The probe path never reads row data, so the snapshot's optional
    ``data`` is stripped before entering the jitted cores: lookup compile
    caches are independent of when a table materializes its flat data.

    ``use_kernel=True`` with ``interpret=True`` is a parity-test/debug
    combination: emulating the unrolled per-segment loop is slow to trace
    beyond ~8 segments.  Production paths never hit it — the dispatcher
    picks the compiled kernel on TPU and the vectorized oracle elsewhere.
    """
    q = jnp.asarray(query_keys, jnp.int64)
    snap = snapshot.strip_data(snap)
    if use_kernel is None:
        use_kernel = not runtime.resolve_interpret(interpret)

    if use_kernel:
        rows, last = _fused_kernel_jit(
            q, snap, max_matches=max_matches,
            interpret=runtime.resolve_interpret(interpret))
    else:
        rows, last = _fused_ref_jit(q, snap, max_matches=max_matches)

    # EMPTY query keys never match (EMPTY slots hold NULL ptrs) — explicit
    # mask for defense in depth, mirroring probe():
    empty = (q == EMPTY_KEY)[:, None]
    rows = jnp.where(empty, NULL_PTR, rows)
    truncated = jnp.where(empty[:, 0], False, last >= 0)
    return rows, truncated


def fused_probe(query_keys, snap, *, interpret: bool | None = None,
                use_kernel: bool | None = None):
    """Head (latest) row id per key over a Snapshot's planes. [Q] int32."""
    # A one-step fused lookup: rows[:, 0] is the head pointer.
    rows, _ = fused_lookup(query_keys, snap, max_matches=1,
                           interpret=interpret, use_kernel=use_kernel)
    return rows[:, 0]


def decode_attention(q, k_pages, v_pages, page_table, lengths, scale, *,
                     interpret: bool | None = None):
    """Paged GQA flash decode attention (serving hot path)."""
    return _da.decode_paged(q, k_pages, v_pages, page_table, lengths, scale,
                            interpret=interpret)
