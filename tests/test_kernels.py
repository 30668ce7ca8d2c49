"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.hashindex import build_index, probe as probe_jnp
from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_paged
from repro.kernels.hash_probe import QUERY_TILE


# --- hash probe ------------------------------------------------------------

@pytest.mark.parametrize("n_keys", [10, 1000, 5000])
@pytest.mark.parametrize("n_query", [1, 255, 1024])
def test_probe_kernel_sweep(rng, n_keys, n_query):
    keys = rng.integers(-2**62, 2**62, n_keys).astype(np.int64)
    idx, _, _ = build_index(keys, np.arange(n_keys, dtype=np.int32))
    q = np.concatenate([
        rng.choice(keys, min(n_query, n_keys)),
        rng.integers(-2**62, 2**62, max(0, n_query - n_keys))
    ])[:n_query].astype(np.int64)
    a = np.asarray(probe_jnp(idx, q))
    b = np.asarray(ops.probe(idx, q, interpret=True))
    np.testing.assert_array_equal(a, b)


def test_probe_kernel_empty_and_negative_keys(rng):
    keys = np.array([-5, 0, 5, np.iinfo(np.int64).max], np.int64)
    idx, _, _ = build_index(keys, np.arange(4, dtype=np.int32))
    q = np.array([-5, 0, 5, np.iinfo(np.int64).max, 17], np.int64)
    a = np.asarray(probe_jnp(idx, q))
    b = np.asarray(ops.probe(idx, q, interpret=True))
    np.testing.assert_array_equal(a, b)
    assert b[4] == -1


def test_probe_kernel_tile_padding(rng):
    """Non-multiple-of-tile query counts are padded internally."""
    keys = rng.integers(0, 10**6, 100).astype(np.int64)
    idx, _, _ = build_index(keys, np.arange(100, dtype=np.int32))
    for nq in (1, QUERY_TILE - 1, QUERY_TILE, QUERY_TILE + 1):
        q = rng.choice(keys, nq).astype(np.int64)
        a = np.asarray(probe_jnp(idx, q))
        b = np.asarray(ops.probe(idx, q, interpret=True))
        np.testing.assert_array_equal(a, b)


# --- fused multi-segment lookup ---------------------------------------------

@pytest.mark.parametrize("n_segments", [1, 3])
@pytest.mark.parametrize("n_query,max_matches", [(1, 4), (255, 1), (600, 8)])
def test_fused_lookup_kernel_sweep(rng, n_segments, n_query, max_matches):
    """Pallas fused kernel (interpret) vs the vectorized flat oracle."""
    from repro.core import Schema, append, create_index
    from repro.kernels import ops
    from repro.kernels import ref as ref_mod
    from repro.core.hashing import bucket_hash, split64
    from repro.kernels.hash_probe import QUERY_TILE, fused_lookup_tiles

    sch = Schema.of("k", k="int64", v="float32")
    base = {"k": rng.integers(0, 120, 400).astype(np.int64),
            "v": rng.random(400).astype(np.float32)}
    t = create_index(base, sch, rows_per_batch=64)
    for _ in range(n_segments - 1):
        t = append(t, {"k": rng.integers(0, 120, 50).astype(np.int64),
                       "v": rng.random(50).astype(np.float32)})
    fv = t.flat_view()

    q = np.concatenate([rng.choice(base["k"], min(n_query, 300)),
                        rng.integers(120, 240, max(0, n_query - 300))
                        ])[:n_query].astype(np.int64)
    pad = (-len(q)) % QUERY_TILE
    qp = jnp.pad(jnp.asarray(q), (0, pad),
                 constant_values=np.iinfo(np.int64).min)
    bids = jnp.stack([bucket_hash(qp, nb) for nb in fv.bucket_counts])
    qhi, qlo = split64(qp)

    rk, lk = fused_lookup_tiles(bids, qhi, qlo, fv,
                                max_matches=max_matches, interpret=True)
    ro, lo = ref_mod.fused_lookup_ref(bids, qhi, qlo, fv, max_matches)
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(lk), np.asarray(lo))

    # ... and through the public dispatcher against the table reference
    rows_k, trunc_k = ops.fused_lookup(q, fv, max_matches=max_matches,
                                       use_kernel=True, interpret=True)
    rows_r, trunc_r = t.lookup_ref(q, max_matches)
    np.testing.assert_array_equal(np.asarray(rows_k), np.asarray(rows_r))
    np.testing.assert_array_equal(np.asarray(trunc_k), np.asarray(trunc_r))


# --- decode attention --------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,d,page,npages", [
    (1, 4, 1, 64, 8, 2),
    (2, 8, 2, 64, 16, 4),
    (3, 16, 4, 128, 16, 3),
])
def test_decode_attention_sweep(rng, dtype, b, hq, hkv, d, page, npages):
    p_total = b * npages + 2
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((p_total, page, hkv, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((p_total, page, hkv, d)), dtype)
    pt = np.full((b, npages), -1, np.int32)
    lengths = np.zeros(b, np.int32)
    for i in range(b):
        used = rng.integers(1, npages + 1)
        pt[i, :used] = rng.choice(p_total, used, replace=False)
        lengths[i] = rng.integers(1, used * page + 1)
    out_k = decode_paged(q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths),
                         d ** -0.5, interpret=True)
    out_r = ref.decode_attention_ref(q, kp, vp, jnp.asarray(pt),
                                     jnp.asarray(lengths), d ** -0.5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=tol, atol=tol)


def test_decode_attention_single_token(rng):
    """length=1: softmax over one position is exact."""
    b, hq, hkv, d, page = 1, 2, 1, 64, 8
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((2, page, hkv, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((2, page, hkv, d)), jnp.float32)
    pt = jnp.asarray([[0, -1]], jnp.int32)
    lengths = jnp.asarray([1], jnp.int32)
    out = decode_paged(q, kp, vp, pt, lengths, d ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[0, 0],
                               np.asarray(vp)[0, 0, 0], rtol=1e-5)


# --- the kernel under vmap (the dist layer's one-device runtime) ------------

def _stackable_tables(rng, n_tables):
    from repro.core import Schema, append, create_index
    sch = Schema.of("k", k="int64", v="float32")
    tables = []
    for _ in range(n_tables):
        t = create_index({"k": rng.integers(0, 90, 300).astype(np.int64),
                          "v": rng.random(300).astype(np.float32)},
                         sch, rows_per_batch=64, reserve=512)
        t = append(t, {"k": rng.integers(0, 90, 40).astype(np.int64),
                       "v": rng.random(40).astype(np.float32)})
        tables.append(t)
    return tables


@pytest.mark.parametrize("stacked", [True, False])
def test_fused_lookup_kernel_under_vmap(rng, stacked):
    """vmap over stacked snapshots (one table per lane) and over query
    sets against one table both match the per-table oracle."""
    tables = _stackable_tables(rng, 3)
    snaps = [t.snapshot for t in tables]
    q = rng.integers(-5, 100, (3, 70)).astype(np.int64)

    def look(keys, snap):
        return ops.fused_lookup(keys, snap, max_matches=6, use_kernel=True,
                                interpret=True)

    if stacked:
        stack = jax.tree.map(lambda *xs: jnp.stack(xs), *snaps)
        rows, trunc = jax.vmap(look)(jnp.asarray(q), stack)
    else:
        rows, trunc = jax.vmap(look, in_axes=(0, None))(jnp.asarray(q),
                                                        snaps[0])
        snaps = [snaps[0]] * 3
    for i, snap in enumerate(snaps):
        ro, to = ops.fused_lookup(q[i], snap, max_matches=6,
                                  use_kernel=False)
        np.testing.assert_array_equal(np.asarray(rows[i]), np.asarray(ro))
        np.testing.assert_array_equal(np.asarray(trunc[i]), np.asarray(to))
