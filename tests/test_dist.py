"""Distributed Indexed DataFrame: shuffle, dtable ops, fault tolerance,
checkpoint/elastic reshard (paper §III-C/D, Fig 12)."""

import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("repro.dist")

from repro.core import Schema, create_index, joins
from repro.dist import (append_distributed, checkpoint, choose_join,
                        create_distributed, indexed_join_bcast,
                        indexed_join_shuffle, lookup, runtime)
from repro.dist import shuffle as shf

SCH = Schema.of("k", k="int64", v="float32")


@pytest.fixture
def dt_and_cols(rng):
    n = 3000
    cols = {"k": rng.integers(0, 500, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32)}
    return create_distributed(cols, SCH, 4, rows_per_batch=256), cols


# --- shuffle ------------------------------------------------------------

def test_route_local_exact(rng):
    from repro.core import hashing
    n, s, cap = 200, 4, 80
    keys = rng.integers(0, 10**6, n).astype(np.int64)
    rows = rng.integers(0, 100, (n, 3)).astype(np.int32)
    valid = rng.random(n) < 0.9
    lk, lr, lv, dropped = shf.route_local(jnp.asarray(keys), jnp.asarray(rows),
                                          jnp.asarray(valid), s, cap)
    assert int(dropped) == 0
    dest = np.asarray(hashing.partition_hash(jnp.asarray(keys), s))
    lv_, lk_ = np.asarray(lv), np.asarray(lk)
    for d in range(s):
        sent = np.sort(keys[valid & (dest == d)])
        got = np.sort(lk_[d][lv_[d]])
        np.testing.assert_array_equal(got, sent)


def test_route_overflow_detected(rng):
    keys = np.zeros(100, np.int64)  # all to one shard
    rows = np.zeros((100, 1), np.int32)
    _, _, _, dropped = shf.route_local(jnp.asarray(keys),
                                       jnp.asarray(rows),
                                       jnp.ones(100, bool), 4, 10)
    assert int(dropped) == 90


def test_shuffle_global_delivers_everything(rng):
    s, n, cap = 4, 120, 60
    keys = rng.integers(0, 10**6, (s, n)).astype(np.int64)
    rows = keys[..., None].astype(np.int32)
    valid = np.ones((s, n), bool)
    rk, rr, rv, dropped = shf.shuffle_global(jnp.asarray(keys),
                                             jnp.asarray(rows),
                                             jnp.asarray(valid), s, cap)
    assert int(np.asarray(dropped).sum()) == 0
    got = np.sort(np.asarray(rk)[np.asarray(rv)])
    np.testing.assert_array_equal(got, np.sort(keys.ravel()))


# --- dtable --------------------------------------------------------------

def test_dist_lookup_matches_single_table(dt_and_cols, rng):
    dt, cols = dt_and_cols
    t = create_index(cols, SCH, rows_per_batch=256)
    q = np.concatenate([cols["k"][:50], [10**12]]).astype(np.int64)
    gd, vd, _ = lookup(dt, q, max_matches=32)
    gs, vs = joins.indexed_lookup(t, q, max_matches=32)
    np.testing.assert_array_equal(np.asarray(vd).sum(1), np.asarray(vs).sum(1))
    # same multiset of matched values per query
    for i in range(len(q)):
        np.testing.assert_allclose(
            np.sort(np.asarray(gd["v"][i])[np.asarray(vd[i])]),
            np.sort(np.asarray(gs["v"][i])[np.asarray(vs[i])]), rtol=1e-6)


def test_join_shuffle_and_bcast_agree(dt_and_cols, rng):
    dt, cols = dt_and_cols
    p = 64
    pk = rng.choice(cols["k"], p).astype(np.int64)
    pc_sharded = {"pk": pk.reshape(4, -1),
                  "tag": np.arange(p, dtype=np.int32).reshape(4, -1)}
    bc, pc, v, dropped = indexed_join_shuffle(
        dt, pc_sharded, "pk", jnp.ones((4, p // 4), bool), 32)
    assert int(np.asarray(dropped).sum()) == 0
    bc2, pc2, v2 = indexed_join_bcast(dt, {"pk": pk}, "pk", 32)
    assert int(np.asarray(v).sum()) == int(np.asarray(v2).sum())


def test_choose_join_threshold():
    class D: pass
    assert choose_join(D(), 100) == "bcast"
    assert choose_join(D(), 10**7) == "shuffle"


def test_distributed_append_mvcc(dt_and_cols, rng):
    dt, cols = dt_and_cols
    key = int(cols["k"][0])
    base = int(np.sum(cols["k"] == key))
    dt2 = append_distributed(dt, {"k": np.array([key], np.int64),
                                  "v": np.array([42.0], np.float32)})
    assert dt2.version == 1 and dt.version == 0
    _, v2, _ = lookup(dt2, np.array([key], np.int64), max_matches=64)
    _, v1, _ = lookup(dt, np.array([key], np.int64), max_matches=64)
    assert int(v2.sum()) == base + 1
    assert int(v1.sum()) == base


# --- fault tolerance -------------------------------------------------------

def test_fail_and_rebuild_shard(dt_and_cols, rng):
    dt, cols = dt_and_cols
    lin = runtime.Lineage(SCH, cols, rows_per_batch=256)
    delta = {"k": np.array([int(cols["k"][0])], np.int64),
             "v": np.array([7.0], np.float32)}
    dt = append_distributed(dt, delta)
    lin.record_append(delta)

    q = cols["k"][:40].astype(np.int64)
    expect, ve, _ = lookup(dt, q, max_matches=64)
    ve = np.asarray(ve)

    broken = runtime.fail_shard(dt, 1)
    rebuilt = runtime.rebuild_shard(broken, 1, lin)
    got, vg, _ = lookup(rebuilt, q, max_matches=64)
    np.testing.assert_array_equal(np.asarray(vg), ve)
    np.testing.assert_allclose(np.asarray(got["v"]) * ve,
                               np.asarray(expect["v"]) * ve, rtol=1e-6)


def test_failed_shard_answers_miss_not_key_zero(rng):
    """A dead shard must answer every lookup with a miss: blanking must use
    EMPTY/NULL sentinels, not zeros (0 is a legal key and a legal row id)."""
    from repro.core import hashing
    cols = {"k": np.arange(64, dtype=np.int64),
            "v": np.ones(64, np.float32)}
    dt = create_distributed(cols, SCH, 4, rows_per_batch=32)
    owner0 = int(np.asarray(
        hashing.partition_hash(jnp.asarray([0], jnp.int64), 4))[0])
    broken = runtime.fail_shard(dt, owner0)
    _, v, _ = lookup(broken, np.array([0], np.int64), max_matches=8)
    assert int(np.asarray(v).sum()) == 0


def test_version_vector_fencing():
    vv = runtime.VersionVector.fresh(4)
    assert vv.check_fresh(0, 0)
    vv.bump_all()
    assert not vv.check_fresh(0, 0)
    assert vv.check_fresh(0, 1)
    vv.mark_stale(2)
    assert not vv.check_fresh(2, 1)


def test_straggler_policy():
    sp = runtime.StragglerPolicy(deadline_factor=2.0)
    slow = sp.observe([1.0, 1.1, 0.9, 5.0])
    assert slow == [3]
    plan = sp.plan_speculative(4)
    assert plan == {3: 0}


# --- checkpoint / elastic -----------------------------------------------

def test_checkpoint_roundtrip(tmp_path, dt_and_cols):
    dt, cols = dt_and_cols
    path = str(tmp_path / "ck")
    checkpoint.save_dtable(path, dt)
    dt2 = checkpoint.restore_dtable(path, dt)
    q = cols["k"][:10].astype(np.int64)
    g1, v1, _ = lookup(dt, q, max_matches=16)
    g2, v2, _ = lookup(dt2, q, max_matches=16)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_elastic_reshard(dt_and_cols):
    dt, cols = dt_and_cols
    for m in (2, 8):
        dtm = checkpoint.reshard_dtable(dt, m)
        assert dtm.num_shards == m
        q = cols["k"][:20].astype(np.int64)
        g1, v1, _ = lookup(dt, q, max_matches=32)
        g2, v2, _ = lookup(dtm, q, max_matches=32)
        np.testing.assert_array_equal(np.asarray(v1).sum(1),
                                      np.asarray(v2).sum(1))


def test_restore_shape_mismatch_raises(tmp_path, dt_and_cols):
    dt, _ = dt_and_cols
    path = str(tmp_path / "ck")
    checkpoint.save_dtable(path, dt)
    bigger = checkpoint.reshard_dtable(dt, 8)
    with pytest.raises(ValueError):
        checkpoint.restore_dtable(path, bigger)


def test_mesh_place_puts_each_shard_on_its_device(rng):
    """``mesh.place``: stacked leaves split one shard per device, global
    leaves replicated, on every device of the mesh; vmap: unchanged."""
    import jax
    from repro.dist import mesh
    n = len(jax.devices())
    rt = mesh.mesh_runtime(n)
    cols = {"k": rng.integers(0, 500, 300).astype(np.int64),
            "v": rng.random(300).astype(np.float32)}
    dt = create_distributed(cols, Schema.of("k", k="int64", v="float32"),
                            n, rows_per_batch=64, rt=rt)
    for leaf in jax.tree_util.tree_leaves(dt):
        assert leaf.sharding.device_set == set(jax.devices())
        if leaf.ndim:
            assert leaf.shape[0] == n
            assert all(s.data.shape[0] == 1 for s in leaf.addressable_shards)
        else:
            assert leaf.sharding.is_fully_replicated
    tree = {"stacked": jnp.zeros((n, 3)), "scalar": jnp.zeros(())}
    assert mesh.place(tree, mesh.vmap_runtime()) is tree


def test_shard_capacity_sized_from_the_routed_share(rng):
    """A new dtable reserves the capacity class of the per-shard share:
    a fullest shard a little over the share does not double every
    shard's planes; skew beyond the class still gets a cover."""
    from repro.core import hashing
    from repro.core.table import capacity_class
    from repro.dist.dtable import shard_capacity
    assert shard_capacity(256, 270, 64) == capacity_class(256, 64) == 512
    assert capacity_class(270, 64) == 1024          # the fullest's class
    assert shard_capacity(256, 700, 64) == 1024     # skew: cover fullest
    keys = rng.choice(10**9, 1024, replace=False).astype(np.int64)
    counts = np.bincount(hashing.partition_hash_host(keys, 4), minlength=4)
    assert counts.max() > 256                 # routing overshoots the share
    dt = create_distributed({"k": keys, "v": np.ones(1024, np.float32)},
                            SCH, 4, rows_per_batch=64)
    assert dt.table.capacity == 512
    got, valid, _ = lookup(dt, keys[:50], max_matches=1)
    assert bool(np.asarray(valid).all())
    np.testing.assert_array_equal(np.asarray(got["k"])[:, 0], keys[:50])
