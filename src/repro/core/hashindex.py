"""Dense bucketized hash index — the TPU-native replacement for the cTrie.

The paper's cTrie (§III-C) maps ``key -> pointer to the *latest* row holding
that key``; rows sharing a key are chained through *backward pointers*.  A
pointer-chasing trie does not vectorize on a TPU, so we keep the contract and
swap the mechanism (DESIGN.md §2):

* ``bucket_keys  : [num_buckets, slots] int64``  (EMPTY = int64 min)
* ``bucket_ptrs  : [num_buckets, slots] int32``  (flat row id, NULL = -1)

A probe is one gather of a ``[Q, slots]`` tile followed by a vector compare —
one VREG-wide operation per query tile instead of a pointer walk.  Inserts
are *bulk and functional*: hash → lexsort → segment-rank → one scatter.  The
concurrency the cTrie gets from CAS, we get from turning contention into a
parallel scan; the lock-free *snapshot* becomes delta chaining in
``table.py``.

Collision policy: each bucket has ``slots`` lanes.  If a bulk build overflows
a bucket, the build reports ``overflow_count`` and the host-level wrapper
retries with 2x buckets (the paper's index (re)build is likewise a heavyweight
host-coordinated operation).  Probes are exact for every key that was
inserted; overflow is therefore a *build-time* failure mode only, never a
silent wrong answer at query time.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing
from repro.core.pointers import NULL_PTR, PTR_DTYPE

EMPTY_KEY = jnp.int64(np.iinfo(np.int64).min)
DEFAULT_SLOTS = 8


@partial(jax.tree_util.register_dataclass, data_fields=["bucket_keys", "bucket_ptrs"],
         meta_fields=["num_buckets", "slots"])
@dataclasses.dataclass(frozen=True)
class HashIndex:
    """Immutable dense hash index over one table partition."""

    bucket_keys: jax.Array  # [num_buckets, slots] int64
    bucket_ptrs: jax.Array  # [num_buckets, slots] int32 (flat row ids)
    num_buckets: int
    slots: int

    @property
    def nbytes(self) -> int:
        return self.bucket_keys.size * 8 + self.bucket_ptrs.size * 4


def empty_index(num_buckets: int, slots: int = DEFAULT_SLOTS) -> HashIndex:
    return HashIndex(
        bucket_keys=jnp.full((num_buckets, slots), EMPTY_KEY, jnp.int64),
        bucket_ptrs=jnp.full((num_buckets, slots), NULL_PTR, PTR_DTYPE),
        num_buckets=num_buckets,
        slots=slots,
    )


# ---------------------------------------------------------------------------
# Bulk build
# ---------------------------------------------------------------------------

def _segment_rank(sorted_ids):
    """Rank of each element within its run of equal ``sorted_ids``."""
    n = sorted_ids.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    # cummax, not associative_scan: the TPU lowering is a native scan,
    # where an unrolled associative_scan at 2^24 elements takes the TPU
    # compiler minutes.
    start_pos = jax.lax.cummax(jnp.where(is_start, idx, -1))
    return idx - start_pos


@partial(jax.jit, static_argnames=("num_buckets", "slots"))
def _build_arrays(keys, row_ids, valid, num_buckets: int, slots: int):
    """One fused build pass.  Returns (bucket_keys, bucket_ptrs, prev, overflow).

    ``prev`` is the backward-pointer array *scattered by row id* — callers
    hand in row ids that are already offset into the partition-global row
    space, so ``prev`` must be combined by the caller (table.py) with the
    destination capacity.  Here we return (prev_src_rows, prev_vals) pairs
    instead of a dense array so the caller controls the scatter target.
    """
    n = keys.shape[0]
    keys = jnp.where(valid, keys, EMPTY_KEY)

    # --- backward pointers: lexsort by (key, row_id) -----------------------
    order = jnp.lexsort((row_ids, keys))
    k_s, r_s, v_s = keys[order], row_ids[order], valid[order]
    same_as_prev = jnp.concatenate(
        [jnp.zeros((1,), bool), (k_s[1:] == k_s[:-1]) & v_s[1:] & v_s[:-1]])
    prev_vals = jnp.where(same_as_prev, jnp.concatenate(
        [jnp.full((1,), NULL_PTR), r_s[:-1].astype(PTR_DTYPE)]), NULL_PTR)
    # Invalid rows scatter to int32 max so any caller-side offset still
    # lands out of range and is dropped.
    prev_rows = jnp.where(v_s, r_s.astype(PTR_DTYPE), jnp.int32(2**31 - 1))

    # --- head per key: last element of each equal-key run ------------------
    is_head = jnp.concatenate([k_s[1:] != k_s[:-1], jnp.ones((1,), bool)]) & v_s

    # --- bucket placement ---------------------------------------------------
    bucket = hashing.bucket_hash(k_s, num_buckets)
    # Sort heads by bucket; non-heads sort to the end (bucket = num_buckets).
    bucket_or_inf = jnp.where(is_head, bucket, jnp.int32(num_buckets))
    order2 = jnp.argsort(bucket_or_inf, stable=True)
    b2, k2, r2, head2 = (bucket_or_inf[order2], k_s[order2], r_s[order2],
                         is_head[order2])
    rank = _segment_rank(b2)
    overflow = jnp.sum((rank >= slots) & head2)
    ok = head2 & (rank < slots)
    # 2-D scatter (row num_buckets = out of range = drop): TPU keeps the
    # planes slot-major, and a flat build + reshape would relayout them
    # through 128-lane tiles at 16x their size.
    at = (jnp.where(ok, b2, jnp.int32(num_buckets)),
          jnp.minimum(rank, slots - 1))
    bucket_keys = jnp.full((num_buckets, slots), EMPTY_KEY, jnp.int64)
    bucket_ptrs = jnp.full((num_buckets, slots), NULL_PTR, PTR_DTYPE)
    bucket_keys = bucket_keys.at[at].set(k2, mode="drop")
    bucket_ptrs = bucket_ptrs.at[at].set(r2.astype(PTR_DTYPE), mode="drop")
    return bucket_keys, bucket_ptrs, prev_rows, prev_vals, overflow


def arena_insert_plan(bucket_keys, head_keys, is_head):
    """Slot placement for inserting per-key head pointers into a *live*
    bucket table (the arena append path, DESIGN.md §4).

    The bulk build (`_build_arrays`) packs each bucket's occupied slots
    left-to-right, and arena inserts preserve that invariant, so placement
    is branch-free: a head whose key already sits in the table reuses its
    slot (the pointer is overwritten with the newer row); a new key takes
    ``occupancy + rank`` where ``rank`` orders the batch's new keys within
    their bucket.  Returns ``(flat_slot [d] int32, overflow scalar)`` —
    ``flat_slot`` indexes the flattened ``[nb * slots]`` planes and is set
    to ``nb * slots`` (out of range, scatter-dropped) for non-head lanes
    and overflowing inserts.  Overflow is *counted, never silent* — the
    same build-time-only failure contract as the bulk build; the host
    wrapper reacts by promoting the arena (more buckets), so probes stay
    exact for every inserted key.
    """
    nb, slots = bucket_keys.shape
    b = hashing.bucket_hash(head_keys, nb)
    row_keys = bucket_keys[b]                               # [d, slots]
    match = ((row_keys == head_keys[:, None]) & is_head[:, None]
             & (head_keys != EMPTY_KEY)[:, None])
    exists = match.any(axis=1)
    slot_exist = jnp.argmax(match, axis=1).astype(jnp.int32)
    occ = jnp.sum(row_keys != EMPTY_KEY, axis=1).astype(jnp.int32)  # b's
    new_head = is_head & ~exists
    b_or_inf = jnp.where(new_head, b, jnp.int32(nb))
    order = jnp.argsort(b_or_inf, stable=True)
    rank = (jnp.zeros(b.shape, jnp.int32)
            .at[order].set(_segment_rank(b_or_inf[order])))
    slot_new = occ + rank
    overflow = jnp.sum(new_head & (slot_new >= slots))
    slot = jnp.where(exists, slot_exist, slot_new)
    ok = is_head & (slot < slots)
    flat = jnp.where(ok, b * slots + slot, jnp.int32(nb * slots))
    return flat, overflow


def suggest_num_buckets(n_keys: int, slots: int = DEFAULT_SLOTS,
                        load: float = 0.25) -> int:
    """Power-of-two bucket count targeting ``load`` mean occupancy/slot."""
    want = max(16, int(n_keys / max(1, slots * load)))
    return 1 << (want - 1).bit_length()


def build_index(keys, row_ids, *, valid=None, num_buckets: int | None = None,
                slots: int = DEFAULT_SLOTS, max_retries: int = 4):
    """Host-coordinated build with overflow-doubling retry.

    Returns ``(HashIndex, prev_rows, prev_vals)`` — the prev pairs are the
    backward-pointer scatter the caller applies to its row space.
    """
    keys = jnp.asarray(keys, jnp.int64)
    row_ids = jnp.asarray(row_ids, PTR_DTYPE)
    if valid is None:
        valid = jnp.ones(keys.shape, bool)
    nb = num_buckets or suggest_num_buckets(int(keys.shape[0]), slots)
    for _ in range(max_retries):
        bk, bp, prev_rows, prev_vals, overflow = _build_arrays(
            keys, row_ids, valid, nb, slots)
        if int(overflow) == 0:
            return (HashIndex(bk, bp, nb, slots), prev_rows, prev_vals)
        nb *= 2
    raise RuntimeError(
        f"hash index build overflowed after {max_retries} doublings "
        f"(final num_buckets={nb}); pathological key distribution?")


# ---------------------------------------------------------------------------
# Probe (pure-JAX reference path; the Pallas kernel in kernels/hash_probe.py
# implements the same contract and is swept against probe() in tests)
# ---------------------------------------------------------------------------

def probe(index: HashIndex, query_keys) -> jax.Array:
    """Latest row id per query key (NULL_PTR where absent).  [Q] int32."""
    q = jnp.asarray(query_keys, jnp.int64)
    b = hashing.bucket_hash(q, index.num_buckets)
    keys_b = index.bucket_keys[b]                       # [Q, S] gather
    ptrs_b = index.bucket_ptrs[b]
    hit = (keys_b == q[:, None]) & (q[:, None] != EMPTY_KEY)
    slot = jnp.argmax(hit, axis=1)
    ptr = jnp.take_along_axis(ptrs_b, slot[:, None], axis=1)[:, 0]
    return jnp.where(hit.any(axis=1), ptr, NULL_PTR)


def chain_walk(prev, head_ptrs, max_matches: int):
    """Follow backward pointers: [Q] head ptrs -> [Q, max_matches] row ids.

    Row ids are emitted newest-first and padded with NULL_PTR, mirroring the
    paper's traversal of the per-key linked list.  ``truncated`` flags keys
    whose chain is longer than ``max_matches``.
    """
    prev = jnp.asarray(prev, PTR_DTYPE)
    cur = jnp.asarray(head_ptrs, PTR_DTYPE)

    def step(cur, _):
        nxt = jnp.where(cur >= 0, prev[jnp.maximum(cur, 0)], NULL_PTR)
        return nxt, cur

    last, rows = jax.lax.scan(step, cur, None, length=max_matches)
    truncated = last >= 0
    return jnp.moveaxis(rows, 0, 1), truncated


def match_counts(prev, head_ptrs, max_matches: int):
    rows, _ = chain_walk(prev, head_ptrs, max_matches)
    return jnp.sum(rows >= 0, axis=1)
