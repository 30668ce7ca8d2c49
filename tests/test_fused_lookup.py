"""Fused lookup pipeline vs segment-looped reference (DESIGN.md §3).

The fused path (stored Snapshot + one-pass probe/chain-walk/gather) is the
default through joins.indexed_lookup / indexed_join; these sweeps pin it to
the original segment-looped code bit for bit, and pin the Pallas kernel to
the vectorized oracle that stands in for it off-TPU.  Pytree/jit/vmap
properties of the Snapshot live in test_snapshot.py; the distributed layer
built on it in test_dist.py.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Schema, append, compact, create_index, joins
from repro.core.hashindex import EMPTY_KEY
from repro.kernels import ops

SCH = Schema.of("k", k="int64", v="float32", tag="int32")
# Every column width the row codec has: int32, int64, float32, float64.
WIDE = Schema.of("k", k="int64", v="float32", tag="int32", x="float64")


def _cols(rng, n, key_range, schema=SCH, tag_base=0):
    cols = {"k": rng.integers(0, key_range, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
            "tag": np.arange(n, dtype=np.int32) + tag_base}
    if "x" in schema.names:
        cols["x"] = rng.standard_normal(n)
    return cols


def _table(rng, n_base, n_appends, layout, key_range=60, rows_per_batch=64,
           append_rows=37, mode="segment", schema=SCH):
    """``mode="segment"`` (default here) grows one delta segment per
    append — the multi-segment machinery these sweeps exercise;
    ``mode="arena"`` lands appends in the reserved tail (DESIGN.md §4)."""
    t = create_index(_cols(rng, n_base, key_range, schema), schema,
                     rows_per_batch=rows_per_batch, layout=layout,
                     reserve=0 if mode == "segment" else None)
    for i in range(n_appends):
        extra = _cols(rng, append_rows, key_range, schema,
                      tag_base=1000 * (i + 1))
        t = append(t, extra, mode=mode)
    return t


def _queries(rng, key_range):
    """Duplicate-heavy present keys + absent keys + the EMPTY sentinel."""
    q = np.concatenate([
        rng.integers(0, key_range, 80),          # present (dup-heavy)
        rng.integers(key_range, 2 * key_range, 15),  # absent
        [np.iinfo(np.int64).min],                # EMPTY sentinel
        [np.iinfo(np.int64).max, -1],            # extreme values
    ])
    return q.astype(np.int64)


@pytest.mark.parametrize("layout", ["row", "columnar"])
@pytest.mark.parametrize("n_appends", [0, 1, 4, 15])
@pytest.mark.parametrize("mode", ["segment", "arena"])
def test_fused_lookup_parity_sweep(rng, layout, n_appends, mode):
    """Fused row ids are bit-identical to the segment-looped reference —
    on the growing segment chain AND on arena tables (whose appends land
    in-place in the reserved tail; compile-cache tests in test_arena.py)."""
    t = _table(rng, 300, n_appends, layout, mode=mode)
    if mode == "segment":
        assert t.num_segments == n_appends + 1
    else:
        assert t.num_segments == 1   # every append fit the reserved tail
    q = _queries(rng, 60)
    for mm in (1, 4, 8):
        rf, tf = t.lookup(q, mm)
        rr, tr = t.lookup_ref(q, mm)
        np.testing.assert_array_equal(np.asarray(rf), np.asarray(rr))
        np.testing.assert_array_equal(np.asarray(tf), np.asarray(tr))


def _assert_same_cols(got, want):
    assert set(got) == set(want)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))


@pytest.mark.parametrize("schema", [SCH, WIDE], ids=["narrow", "wide"])
@pytest.mark.parametrize("layout", ["row", "columnar"])
def test_fused_gather_and_probe_parity(rng, layout, schema):
    t = _table(rng, 250, 3, layout, schema=schema)
    q = _queries(rng, 60)
    np.testing.assert_array_equal(np.asarray(t.probe_latest(q)),
                                  np.asarray(t.probe_latest_ref(q)))
    rids, _ = t.lookup(q, 6)
    safe = jnp.maximum(rids, 0)
    _assert_same_cols(t.gather_rows(safe), t.gather_rows_ref(safe))
    # NULL, fill-edge and out-of-range ids decode as the reference does
    fill = int(t.fill)
    probe_ids = jnp.asarray([-1, 0, 5, fill - 1, fill, t.capacity - 1,
                             t.capacity, 10**6], jnp.int32)
    _assert_same_cols(t.gather_rows(probe_ids), t.gather_rows_ref(probe_ids))
    subset = ("tag", "x") if "x" in schema.names else ("tag", "k")
    _assert_same_cols(t.gather_rows(probe_ids, names=subset),
                      t.gather_rows_ref(probe_ids, names=subset))
    np.testing.assert_array_equal(np.asarray(t.gather_prev(probe_ids)),
                                  np.asarray(t.gather_prev_ref(probe_ids)))


def test_fused_truncation_matches_reference(rng):
    """All-equal keys: chains longer than max_matches truncate identically."""
    n = 100
    cols = {"k": np.zeros(n, np.int64),
            "v": rng.random(n).astype(np.float32),
            "tag": np.arange(n, dtype=np.int32)}
    t = create_index(cols, SCH, rows_per_batch=32)
    t = append(t, {"k": np.zeros(8, np.int64),
                   "v": np.ones(8, np.float32),
                   "tag": np.arange(8, dtype=np.int32)})
    q = np.array([0, 1], np.int64)
    for mm in (4, 108, 128):
        rf, tf = t.lookup(q, mm)
        rr, tr = t.lookup_ref(q, mm)
        np.testing.assert_array_equal(np.asarray(rf), np.asarray(rr))
        np.testing.assert_array_equal(np.asarray(tf), np.asarray(tr))
    assert bool(t.lookup(q, 4)[1][0])        # 108 rows > 4 -> truncated
    assert not bool(t.lookup(q, 128)[1][0])  # fits -> not truncated


@pytest.mark.parametrize("layout", ["row", "columnar"])
def test_indexed_join_fused_default_matches_ref(rng, layout):
    t = _table(rng, 400, 2, layout)
    pk = rng.integers(0, 80, 64).astype(np.int64)
    probe_cols = {"pk": pk, "tag": np.arange(64, dtype=np.int32)}
    bf, pf, vf = joins.indexed_join(t, probe_cols, "pk", max_matches=16)
    br, pr, vr = joins.indexed_join(t, probe_cols, "pk", max_matches=16,
                                    fused=False)
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vr))
    for name in bf:
        np.testing.assert_array_equal(np.asarray(bf[name]),
                                      np.asarray(br[name]))


def test_fused_kernel_matches_oracle_and_reference(rng):
    """Force the Pallas kernel (interpret) — parity with both the oracle
    path and the segment-looped reference."""
    t = _table(rng, 200, 2, "row", key_range=40)
    fv = t.flat_view()
    q = _queries(rng, 40)
    rk, tk = ops.fused_lookup(q, fv, max_matches=5, use_kernel=True,
                              interpret=True)
    ro, to = ops.fused_lookup(q, fv, max_matches=5, use_kernel=False)
    rr, tr = t.lookup_ref(q, 5)
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(ro))
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(to))
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(tr))


def test_snapshot_append_reuses_parent_blocks(rng):
    """Regression: append extends the parent's stored Snapshot — it must
    reuse the parent's per-segment blocks by reference, never rebuild."""
    t = _table(rng, 300, 2, "row")
    fv1 = t.snapshot
    t2 = append(t, {"k": np.array([1, 2], np.int64),
                    "v": np.array([0.5, 0.7], np.float32),
                    "tag": np.array([7, 8], np.int32)}, mode="segment")
    fv2 = t2.snapshot
    assert fv2 is t2.flat_view()
    assert len(fv2.blocks) == len(fv1.blocks) + 1
    for b1, b2 in zip(fv1.blocks, fv2.blocks):
        assert b2 is b1  # shared by reference, never recomputed
    # parent's snapshot is untouched (MVCC: versions coexist)
    assert t.snapshot is fv1
    assert len(fv1.blocks) == t.num_segments


def test_snapshot_eager_probe_side_lazy_data(rng):
    """create_index stores the probe-side Snapshot eagerly; the flat-data
    side stays lazy, and host reads must NOT mutate the pytree structure
    (the lazy cache lives outside the tree; with_flat_data is the only way
    the stored form gains the data leaf)."""
    import jax
    cols = {"k": np.arange(50, dtype=np.int64),
            "v": np.ones(50, np.float32),
            "tag": np.zeros(50, np.int32)}
    t = create_index(cols, SCH, rows_per_batch=32)
    assert t.snapshot is t.flat_view()
    assert len(t.snapshot.blocks) == 1
    assert t.snapshot.data is None              # probe path needs no rows
    treedef_before = jax.tree_util.tree_structure(t)
    t.gather_rows(jnp.asarray([0, 1, 2], jnp.int32))   # first fused decode
    assert t.snapshot.data is None              # read did not mutate the tree
    assert jax.tree_util.tree_structure(t) == treedef_before
    assert getattr(t, "_flatdata", None) is not None   # host cache amortizes
    td = t.with_flat_data()                     # explicit materialization
    assert td is not t and td.snapshot.data is not None
    assert td.with_flat_data() is td            # no-op once materialized


@pytest.mark.parametrize("layout", ["row", "columnar"])
def test_eager_decode_compiles_once(rng, layout, tmp_path):
    """The eager fused decode is one cached program: under a profile, five
    reads, an append that stays in its capacity class and five more reads
    compile it once (``joins.decode_compiles``).  Reads leave the pytree
    alone and cache the flat data on the host; under ``jit`` and ``vmap``
    the same decode inlines and answers as the eager one does."""
    import jax
    from repro import obs
    # column names of its own, so no earlier test has compiled this decode
    sch = Schema.of("kc", kc="int64", vc="float32", tc="int32", xc="float64")
    n = 90
    cols = {"kc": rng.integers(0, 40, n).astype(np.int64),
            "vc": rng.random(n).astype(np.float32),
            "tc": np.arange(n, dtype=np.int32),
            "xc": rng.standard_normal(n)}
    t = create_index(cols, sch, rows_per_batch=32, layout=layout)
    rids = jnp.asarray([-1, 0, 7, n - 1, n, t.capacity - 1, t.capacity, 3],
                       jnp.int32)
    treedef = jax.tree_util.tree_structure(t)
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eager = [jax.block_until_ready(t.gather_rows(rids))
                 for _ in range(5)]
        t2 = append(t, {c: v[:4] for c, v in cols.items()})
        assert t2.capacity == t.capacity and t2.num_segments == 1
        eager2 = [jax.block_until_ready(t2.gather_rows(rids))
                  for _ in range(5)]
    finally:
        jax.profiler.stop_trace()
    counters = obs.profiled()["counters"]
    obs.reset()
    assert counters == {"joins.decode_compiles": 1}
    assert t.snapshot.data is None                  # no pytree mutation
    assert jax.tree_util.tree_structure(t) == treedef
    assert getattr(t, "_flatdata", None) is not None
    _assert_same_cols(eager[0], t.gather_rows_ref(rids))
    _assert_same_cols(eager2[-1], t2.gather_rows_ref(rids))
    site = jax.jit(lambda tb, r: tb.gather_rows(r))
    _assert_same_cols(site(t, rids), eager[0])
    _assert_same_cols(site(t.with_flat_data(), rids), eager[0])
    stacked = jnp.stack([rids, rids[::-1]])
    batched = jax.vmap(t.gather_rows)(stacked)
    _assert_same_cols({c: v[0] for c, v in batched.items()}, eager[0])
    _assert_same_cols({c: v[1] for c, v in batched.items()},
                      t.gather_rows(rids[::-1]))


def test_flatview_mixed_bucket_counts(rng):
    """Segments whose delta indexes have different bucket counts keep
    ragged planes; each segment probes modulo its own count."""
    cols = {"k": rng.integers(0, 5000, 3000).astype(np.int64),
            "v": rng.random(3000).astype(np.float32),
            "tag": np.arange(3000, dtype=np.int32)}
    t = create_index(cols, SCH, rows_per_batch=256)
    t = append(t, {"k": rng.integers(0, 5000, 10).astype(np.int64),
                   "v": rng.random(10).astype(np.float32),
                   "tag": np.arange(10, dtype=np.int32)}, mode="segment")
    fv = t.flat_view()
    assert len(set(fv.bucket_counts)) > 1  # genuinely mixed
    q = np.concatenate([cols["k"][:50],
                        rng.integers(5000, 10000, 20)]).astype(np.int64)
    rf, tf = t.lookup(q, 8)
    rr, tr = t.lookup_ref(q, 8)
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(tf), np.asarray(tr))


def test_compact_resets_flatview(rng):
    t = _table(rng, 200, 3, "row", key_range=20)
    t.flat_view()
    tc = compact(t)
    assert tc.num_segments == 1
    q = np.arange(25, dtype=np.int64)
    rf, _ = tc.lookup(q, 32)
    rr, _ = tc.lookup_ref(q, 32)
    np.testing.assert_array_equal(np.asarray(rf), np.asarray(rr))


def test_aggregate_preserves_integer_dtypes():
    """min/max/sum on integer columns must not promote to float."""
    vals = jnp.asarray([5, -3, 7, 2], jnp.int32)
    valid = jnp.asarray([True, False, True, True])
    mn = joins.aggregate(vals, valid, "min")
    mx = joins.aggregate(vals, valid, "max")
    sm = joins.aggregate(vals, valid, "sum")
    assert mn.dtype == jnp.int32 and int(mn) == 2
    assert mx.dtype == jnp.int32 and int(mx) == 7
    # sum may widen for overflow safety but must stay integral
    assert jnp.issubdtype(sm.dtype, jnp.integer) and int(sm) == 14
    # all-invalid: identity values, still the column dtype
    none = jnp.zeros(4, bool)
    assert joins.aggregate(vals, none, "min").dtype == jnp.int32
    i64 = jnp.asarray([2**40, -2**40], jnp.int64)
    v64 = jnp.asarray([True, True])
    assert joins.aggregate(i64, v64, "max").dtype == jnp.int64
    assert int(joins.aggregate(i64, v64, "max")) == 2**40
    # floats unchanged
    f = jnp.asarray([1.5, 2.5], jnp.float32)
    assert joins.aggregate(f, v64, "min").dtype == jnp.float32
    assert float(joins.aggregate(f, jnp.zeros(2, bool), "max")) == -np.inf


def test_interpret_resolution():
    from repro.kernels import runtime
    import jax
    on_tpu = jax.default_backend() == "tpu"
    assert runtime.resolve_interpret(None) == (not on_tpu)
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False
