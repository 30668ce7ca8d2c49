"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *semantic contract*; kernel tests sweep shapes/dtypes
and assert_allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NULL = jnp.int32(-1)


def fused_probe_ref(bucket_ids, q_hi, q_lo, snapshot):
    """Oracle for the probe stage of hash_probe.fused_lookup_tiles.

    bucket_ids [S, Q]; ``snapshot`` is a core.snapshot.Snapshot whose
    per-segment (hi, lo, ptrs) planes are each [nb_s, slots] (ragged).
    One [Q, slots] gather + compare per segment, then a first-non-NULL
    select newest -> oldest.  This IS the vectorized flat lookup — on
    non-TPU backends ops.fused_lookup runs it directly instead of
    emulating the Pallas kernel (DESIGN.md §3).
    """
    cands = []
    for s, (hi, lo, ptr) in enumerate(snapshot.key_planes):
        row_hi = hi[bucket_ids[s]]                    # [Q, slots]
        row_lo = lo[bucket_ids[s]]
        row_ptr = ptr[bucket_ids[s]]
        match = (row_hi == q_hi[:, None]) & (row_lo == q_lo[:, None])
        cands.append(jnp.max(jnp.where(match, row_ptr, NULL), axis=-1))
    # First non-NULL newest -> oldest via one stacked argmax select.  (An
    # unrolled where(head==NULL, ...) fold compiles pathologically on the
    # CPU backend beyond ~10 segments — XLA fusion goes combinatorial.)
    cands = jnp.stack(cands)[::-1]                    # [S, Q] newest first
    hit = cands != NULL
    first = jnp.argmax(hit, axis=0)                   # [Q]
    head = jnp.take_along_axis(cands, first[None], axis=0)[0]
    head = jnp.where(hit.any(axis=0), head, NULL)
    # fill-masked: an arena tail's reserved-but-unwritten lanes (row ids
    # >= fill) can never be answered — by construction no bucket entry
    # points there, but with buffer donation a reserved lane may alias
    # retired memory, so the mask is the hard guarantee (DESIGN.md §4).
    return jnp.where(head < snapshot.fill, head, NULL)


def fused_lookup_ref(bucket_ids, q_hi, q_lo, snapshot, max_matches: int):
    """Oracle for hash_probe.fused_lookup_tiles: fused probe + chain walk
    over a Snapshot (probe planes + flat ``prev``).

    Returns (rows [Q, max_matches] newest-first NULL-padded, last [Q] — the
    would-be next row id; >= 0 means truncated)."""
    head = fused_probe_ref(bucket_ids, q_hi, q_lo, snapshot)
    prev = snapshot.prev
    fill = snapshot.fill

    def step(cur, _):
        nxt = jnp.where(cur >= 0, prev[jnp.maximum(cur, 0)], NULL)
        nxt = jnp.where(nxt < fill, nxt, NULL)    # fill-masked chain walk
        return nxt, cur

    last, rows = jax.lax.scan(step, head, None, length=max_matches)
    return jnp.moveaxis(rows, 0, 1), last


def decode_attention_ref(q, k_pages, v_pages, page_table, lengths, scale):
    """Oracle for decode_attention: GQA flash decode over paged KV.

    q          : [B, Hq, D]
    k_pages    : [P, page, Hkv, D]   (pages = the indexed cache's row batches)
    v_pages    : [P, page, Hkv, D]
    page_table : [B, max_pages] int32  (NULL = -1 padding)
    lengths    : [B] int32  (total valid KV per sequence)
    returns    : [B, Hq, D] float32
    """
    b, hq, d = q.shape
    p, page, hkv, _ = k_pages.shape
    groups = hq // hkv
    max_pages = page_table.shape[1]

    # materialize per-sequence KV [B, max_pages*page, Hkv, D]
    safe = jnp.maximum(page_table, 0)
    k = k_pages[safe].reshape(b, max_pages * page, hkv, d)
    v = v_pages[safe].reshape(b, max_pages * page, hkv, d)
    pos = jnp.arange(max_pages * page)[None, :]
    mask = pos < lengths[:, None]

    qg = q.reshape(b, hkv, groups, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kf) * jnp.float32(scale)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    w = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", w, vf)
    return out.reshape(b, hq, d)
