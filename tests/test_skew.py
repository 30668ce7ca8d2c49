"""Skew-resilient execution (DESIGN.md §15, ISSUE 9): hot-key tracking,
replication, and the routed/broadcast hybrid.

Contracts pinned here:

  * determinism — the hot tracker is a pure fold over ingest deltas: the
    same stream produces a bit-identical hot set whether the rows arrive
    through plain appends or through the device ring (enqueue+flush),
    whether the shard axis is vmap-emulated or a real forced-8 shard_map
    mesh, and regardless of row order WITHIN a delta (the fold counts a
    multiset per delta, not a sequence),
  * exactness — in ``topk`` mode with capacity >= distinct keys the
    per-shard counts equal an exact host-side bincount; in ``sketch``
    mode the count-min estimates upper-bound and agree on heavy hitters,
  * parity — the hybrid flavors are bit-identical to the pure-routing
    oracle: hot hits, cold hits, misses, EMPTY pads, a stale mirror
    (version gating degrades to pure routing), and deeper-than-mirror
    ``max_matches`` (static fallback) all produce the same bits,
  * planning — rules L4/J4 fire exactly when a fresh-capable mirror
    covers the read, with the uniform reason format (est_fanout,
    pending_ring_rows, hot_fraction),
  * supervision — a killed shard blanks its tracker slice and stales the
    mirror; heal restores BOTH bit-identically; under capacity pressure
    a hot-only batch answers from the mirror with zero drops and zero
    retries while pure routing must retry (the satellite-1 fix),
  * elasticity — reshard re-seeds the tracker onto the new owners and
    re-mirrors, so L4 keeps firing across topology changes.
"""

import os
import subprocess
import sys
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

pytest.importorskip("repro.dist")

from repro import dist
from repro.core import Schema, hashing
from repro.core import planner as planner_mod
from repro.core import table as table_mod
from repro.core.hashindex import EMPTY_KEY
from repro.dist import dtable as dt_mod
from repro.dist import resilience, runtime as drt
from repro.frame import IndexedFrame

NDEV = len(jax.devices())
SCH = Schema.of("k", k="int64", v="float32")

KEYS = st.lists(st.integers(min_value=0, max_value=11), min_size=1,
                max_size=60)


def _cols_from(keys, base=0):
    keys = np.asarray(keys, np.int64)
    return {"k": keys,
            "v": (np.arange(len(keys), dtype=np.float32) * 0.5
                  + np.float32(base))}


def _skewed(rng, n=120, celebrity=7):
    k = np.where(rng.random(n) < 0.5, np.int64(celebrity),
                 rng.integers(100, 200, n).astype(np.int64))
    return {"k": k, "v": np.arange(n, dtype=np.float32)}


def _tracker_leaves(dt):
    h = dt.table.hot
    out = {"keys": np.asarray(h.keys), "counts": np.asarray(h.counts)}
    if h.sketch is not None:
        out["sketch"] = np.asarray(h.sketch)
    return out


def _assert_same_tracker(a, b):
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"tracker {k}")


def _assert_same_answers(res_a, res_b):
    ca, va = res_a
    cb, vb = res_b
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    for k in ca:
        np.testing.assert_array_equal(np.asarray(ca[k]),
                                      np.asarray(cb[k]), err_msg=k)


# -- tracker determinism ------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(st.lists(KEYS, min_size=1, max_size=4))
def test_property_tracker_flush_equals_coalesced_append(deltas):
    """The ring flush folds its coalesced pending rows into the tracker
    as ONE delta — bit-identical to appending the coalesced rows."""
    base = _cols_from([0, 1, 2, 3])
    fa = IndexedFrame.from_columns(base, SCH, num_shards=2, track_hot=16,
                                   rows_per_batch=16, reserve=1024)
    fb = fa.with_queue(lanes=4, lane_rows=256)
    all_rows = [_cols_from(dk, 10 * i) for i, dk in enumerate(deltas)]
    merged = {c: np.concatenate([r[c] for r in all_rows]) for c in ("k", "v")}
    fa = fa.append(merged)
    for r in all_rows:
        fb = fb.enqueue(r)
    fb = fb.flush()
    _assert_same_tracker(_tracker_leaves(fa.data), _tracker_leaves(fb.data))


@settings(max_examples=10, deadline=None)
@given(KEYS, st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_property_tracker_permutation_invariant_within_delta(keys, seed):
    """One delta is a multiset: permuting its rows cannot change the
    tracker (the fold sorts before counting)."""
    base = _cols_from([0, 1, 2, 3])
    perm = np.random.default_rng(seed).permutation(len(keys))
    cols = _cols_from(keys)
    fa = IndexedFrame.from_columns(base, SCH, num_shards=2, track_hot=16,
                                   reserve=256).append(cols)
    fb = IndexedFrame.from_columns(base, SCH, num_shards=2, track_hot=16,
                                   reserve=256).append(
        {c: cols[c][perm] for c in cols})
    _assert_same_tracker(_tracker_leaves(fa.data), _tracker_leaves(fb.data))


@settings(max_examples=10, deadline=None)
@given(st.lists(KEYS, min_size=1, max_size=4))
def test_property_topk_counts_exact_when_capacity_covers(deltas):
    """topk capacity >= distinct keys => Misra-Gries lower bounds are
    exact: per-shard (key, count) pairs equal a host bincount over the
    shard's ingested rows (creation rows are NOT back-counted)."""
    fr = IndexedFrame.from_columns(_cols_from([0, 1, 2, 3]), SCH,
                                   num_shards=2, track_hot=16, reserve=1024)
    streamed = np.concatenate([np.asarray(dk, np.int64) for dk in deltas])
    for i, dk in enumerate(deltas):
        fr = fr.append(_cols_from(dk, 10 * i))
    t = _tracker_leaves(fr.data)
    owner = hashing.partition_hash_host(streamed, 2)
    for s in range(2):
        mine = streamed[owner == s]
        want = {int(k): int(c) for k, c in
                zip(*np.unique(mine, return_counts=True))}
        got = {int(k): int(c)
               for k, c in zip(t["keys"][s], t["counts"][s])
               if k != int(np.asarray(EMPTY_KEY))}
        assert got == want


def test_sketch_mode_upper_bounds_and_agrees_on_heavy_hitter():
    rng = np.random.default_rng(3)
    cols = _skewed(rng, n=300)
    kw = dict(num_shards=2, reserve=1024)
    fr_t = IndexedFrame.from_columns(_cols_from([0]), SCH, track_hot=8,
                                     **kw).append(cols)
    fr_s = IndexedFrame.from_columns(_cols_from([0]), SCH, track_hot=8,
                                     hot_mode="sketch", **kw).append(cols)
    for fr in (fr_t, fr_s):
        t = _tracker_leaves(fr.data)
        flat = {int(k): int(c) for ks, cs in zip(t["keys"], t["counts"])
                for k, c in zip(ks, cs) if k != int(np.asarray(EMPTY_KEY))}
        # the celebrity tops both trackers...
        assert max(flat, key=flat.get) == 7
        # ...topk is a lower bound, the sketch an upper bound
        true = int((cols["k"] == 7).sum())
        if fr is fr_t:
            assert flat[7] <= true
        else:
            assert flat[7] >= true


# -- hybrid parity vs the pure-routing oracle ---------------------------------


def _built_replicated(rng, num_shards=4):
    fr = IndexedFrame.from_columns(_cols_from([0, 1, 2, 3]), SCH,
                                   num_shards=num_shards, track_hot=16,
                                   reserve=4096)
    fr = fr.with_replica(capacity=8, max_matches=4)
    return fr.append(_skewed(rng, n=200))     # auto-refreshes the mirror


QUERIES = st.lists(
    st.one_of(st.just(7),                      # the celebrity (hot)
              st.integers(min_value=100, max_value=199),   # cold hits
              st.integers(min_value=5000, max_value=5010),  # misses
              st.just(int(np.asarray(EMPTY_KEY)))),         # pad lanes
    min_size=1, max_size=40)


@settings(max_examples=15, deadline=None)
@given(QUERIES, st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_property_hybrid_bit_identical_to_routed(qkeys, seed):
    fr = _built_replicated(np.random.default_rng(seed % 5))
    q = np.asarray(qkeys, np.int64)
    _assert_same_answers(fr.lookup(q, max_matches=4, op="hybrid"),
                         fr.lookup(q, max_matches=4, op="routed"))
    rep = fr.data.replica
    assert int(np.asarray(rep.version)) == int(np.asarray(fr.data.version))
    elig, _ = dt_mod._replica_split(fr.data, jnp.asarray(q))
    elig = np.asarray(elig)
    assert elig[q == 7].all()                  # celebrity answered locally
    assert not elig[q == int(np.asarray(EMPTY_KEY))].any()   # pads never


def test_hybrid_join_bit_identical_to_shuffle():
    fr = _built_replicated(np.random.default_rng(1))
    probe = {"k": np.array([7, 150, 42, 7], np.int64),
             "w": np.arange(4, dtype=np.float32)}
    bh, ph, vh = fr.join(probe, "k", max_matches=4, op="hybrid")
    bs, ps, vs = fr.join(probe, "k", max_matches=4, op="shuffle")
    np.testing.assert_array_equal(np.asarray(vh), np.asarray(vs))
    for k in bh:
        np.testing.assert_array_equal(np.asarray(bh[k]), np.asarray(bs[k]))
    for k in ph:
        np.testing.assert_array_equal(np.asarray(ph[k]), np.asarray(ps[k]))


def test_stale_mirror_version_gated_to_pure_routing():
    """An un-refreshed mirror after a version bump is never consulted:
    eligibility collapses to empty and the hybrid IS the routed path."""
    fr = _built_replicated(np.random.default_rng(2))
    dt2 = dist.append_distributed(fr.data, _cols_from([7, 7, 300], 99),
                                  rt=fr.rt)       # raw append: NO refresh
    q = jnp.asarray(np.array([7, 150, 300], np.int64))
    elig, _ = dt_mod._replica_split(dt2, q)
    assert not bool(np.asarray(elig).any())
    _assert_same_answers(
        dist.lookup_hybrid_flat(dt2, q, max_matches=4, rt=fr.rt),
        dist.lookup_routed_flat(dt2, q, max_matches=4, rt=fr.rt))


def test_deeper_than_mirror_static_fallback():
    """max_matches > replica.max_matches cannot be served from the
    mirror prefix — the hybrid statically lowers to pure routing."""
    fr = _built_replicated(np.random.default_rng(4))
    q = np.array([7, 150], np.int64)
    _assert_same_answers(fr.lookup(q, max_matches=16, op="hybrid"),
                         fr.lookup(q, max_matches=16, op="routed"))
    assert fr.plan_lookup(np.full(5000, 7, np.int64),
                          max_matches=16).kind == "RoutedLookup"


# -- planner rules + uniform reasons ------------------------------------------


def test_planner_L4_J4_and_uniform_reasons():
    fr = _built_replicated(np.random.default_rng(5))
    q_big = np.full(5000, 7, np.int64)
    p = fr.plan_lookup(q_big, max_matches=4)
    assert p.kind == "HybridLookup" and "L4" in p.reason
    assert "est_fanout=hot:0x cold:1x" in p.reason
    assert "pending_ring_rows=0" in p.reason
    assert "hot_fraction=1.00" in p.reason
    p3 = fr.plan_lookup(q_big, max_matches=16)     # deeper than mirror
    assert p3.kind == "RoutedLookup" and "L3" in p3.reason
    assert "est_fanout=1x" in p3.reason
    p2 = fr.plan_lookup(np.array([7], np.int64), max_matches=4)
    assert p2.kind == "BroadcastLookup" and "L2" in p2.reason
    assert "est_fanout=4x" in p2.reason
    small = planner_mod.Planner(max_matches=4, bcast_threshold=10)
    pj = fr.plan_join({"k": q_big[:50]}, "k", max_matches=4, planner=small)
    assert pj.kind == "HybridJoin" and "J4" in pj.reason
    assert "est_fanout=hot:0x cold:1x" in pj.reason
    # no mirror -> L3/J3 exactly as before the feature
    bare = IndexedFrame.from_columns(_cols_from([0, 1]), SCH, num_shards=4,
                                     reserve=64)
    pb = bare.plan_lookup(q_big, max_matches=4)
    assert pb.kind == "RoutedLookup" and "L3" in pb.reason


def test_pending_ring_rows_annotation_counts_unflushed():
    fr = _built_replicated(np.random.default_rng(6)).with_queue(
        lanes=2, lane_rows=64)
    fr = fr.enqueue(_cols_from([7, 7, 8]))
    p = fr.plan_lookup(np.full(5000, 7, np.int64), max_matches=4)
    assert "pending_ring_rows=3" in p.reason


# -- supervision: kill+heal, pressure retries ---------------------------------


def test_supervised_kill_heal_restores_tracker_and_mirror_bitwise():
    rng = np.random.default_rng(7)
    base = _cols_from([0, 1, 2, 3])
    fr = IndexedFrame.from_columns(base, SCH, num_shards=4, track_hot=16,
                                   reserve=4096)
    fr = fr.with_replica(capacity=8, max_matches=4)
    lin = drt.Lineage(SCH, base, rows_per_batch=fr.data.table.rows_per_batch)
    delta = _skewed(rng, n=200)
    fr = fr.append(delta)
    lin.record_append(delta)
    want_rep = fr.data.replica
    want_hot = _tracker_leaves(fr.data)
    q = np.full(64, 7, np.int64)
    want = fr.lookup(q, max_matches=4, op="routed")

    mgr = fr.supervised(lineage=lin, checkpoint_dir=tempfile.mkdtemp())
    mgr.frame = type(fr)(data=drt.fail_shard(mgr.frame.data, 2),
                         rt=fr.rt, queue=fr.queue)
    killed = mgr.frame.data
    assert int(np.asarray(killed.replica.version)) == -1   # mirror staled
    assert (np.asarray(killed.table.hot.keys)[2]
            == int(np.asarray(EMPTY_KEY))).all()           # slice blanked
    got = mgr.lookup(q, max_matches=4)                     # heals inline
    _assert_same_answers(got, want)
    assert mgr.last_report.recovered == (2,)
    healed = mgr.frame.data
    _assert_same_tracker(_tracker_leaves(mgr.frame.data), want_hot)
    np.testing.assert_array_equal(np.asarray(healed.replica.keys),
                                  np.asarray(want_rep.keys))
    np.testing.assert_array_equal(np.asarray(healed.replica.valid),
                                  np.asarray(want_rep.valid))
    for k in want_rep.cols:
        np.testing.assert_array_equal(np.asarray(healed.replica.cols[k]),
                                      np.asarray(want_rep.cols[k]))
    assert (int(np.asarray(healed.replica.version))
            == int(np.asarray(want_rep.version)))


def test_capacity_pressure_hot_batch_answers_from_mirror_without_retries():
    """The satellite-1 fix: under exchange pressure a celebrity-only
    batch is fully served by the mirror (0 drops, 0 retries), while the
    same batch on a mirror-less frame must drop and retry its way
    through the throttled exchange."""
    q = np.full(64, 7, np.int64)        # every lane targets ONE owner

    def pressured(fr, op):
        inj = resilience.FaultInjector(
            [resilience.Fault(kind="capacity_pressure", step=0,
                              severity=4.0)])
        mgr = fr.supervised(injector=inj)
        out = mgr.lookup(q, max_matches=4, op=op)
        return out, mgr.last_report

    fr_h = _built_replicated(np.random.default_rng(8), num_shards=4)
    bare = IndexedFrame.from_columns(_cols_from([0, 1, 2, 3]), SCH,
                                     num_shards=4, reserve=4096)
    bare = bare.append(_skewed(np.random.default_rng(8), n=200))
    got_h, rep_h = pressured(fr_h, "hybrid")
    got_r, rep_r = pressured(bare, "routed")
    assert rep_h.dropped == 0 and rep_h.retries == 0
    assert rep_r.retries > 0                # pure routing had to double
    assert rep_r.dropped == 0               # ...but delivered in the end
    _assert_same_answers(got_h, got_r)
    assert rep_h.answered.all() and rep_r.answered.all()


# -- elasticity ---------------------------------------------------------------


def test_reshard_reseeds_tracker_and_remirrors():
    fr = _built_replicated(np.random.default_rng(9), num_shards=4)
    q = np.full(5000, 7, np.int64)
    want = fr.lookup(q, max_matches=4, op="routed")
    fr2 = fr.reshard(2)
    assert fr2.data.table.hot is not None
    assert (int(np.asarray(fr2.data.replica.version))
            == int(np.asarray(fr2.data.version)))
    p = fr2.plan_lookup(q, max_matches=4)
    assert p.kind == "HybridLookup"          # L4 survives the topology flip
    _assert_same_answers(fr2.lookup(q, max_matches=4), want)
    # the celebrity's count rode along to its new owner
    t = _tracker_leaves(fr2.data)
    flat = {int(k): int(c) for ks, cs in zip(t["keys"], t["counts"])
            for k, c in zip(ks, cs) if k != int(np.asarray(EMPTY_KEY))}
    assert flat.get(7, 0) > 0


# -- forced-8 shard_map determinism -------------------------------------------

_SUBPROCESS_SKEW = r"""
import numpy as np, jax, jax.numpy as jnp
assert len(jax.devices()) == 8, jax.devices()
from repro.core.schema import Schema
from repro.frame import IndexedFrame
from repro.dist import mesh
from repro.dist import dtable as dt_mod

SCH = Schema.of("k", k="int64", v="float32")
rng = np.random.default_rng(11)
base = {"k": np.arange(4, dtype=np.int64),
        "v": np.zeros(4, np.float32)}
stream_k = np.where(rng.random(160) < 0.5, np.int64(7),
                    rng.integers(100, 200, 160).astype(np.int64))
stream = {"k": stream_k, "v": np.arange(160, dtype=np.float32)}


def build(rt):
    fr = IndexedFrame.from_columns(base, SCH, num_shards=8, rt=rt,
                                   track_hot=16, reserve=4096)
    fr = fr.with_replica(capacity=8, max_matches=4)
    return fr.append(stream)


fv = build(None)                      # vmap emulation
fm = build(mesh.mesh_runtime(8))      # real shard_map mesh
hv, hm = fv.data.table.hot, fm.data.table.hot
np.testing.assert_array_equal(np.asarray(hv.keys), np.asarray(hm.keys))
np.testing.assert_array_equal(np.asarray(hv.counts), np.asarray(hm.counts))
np.testing.assert_array_equal(np.asarray(fv.data.replica.keys),
                              np.asarray(fm.data.replica.keys))
for k in fv.data.replica.cols:
    np.testing.assert_array_equal(np.asarray(fv.data.replica.cols[k]),
                                  np.asarray(fm.data.replica.cols[k]))
q = np.array([7, 150, 5000, int(np.asarray(dt_mod.EMPTY_KEY))], np.int64)
for fr in (fv, fm):
    ch, vh = fr.lookup(q, max_matches=4, op="hybrid")
    cr, vr = fr.lookup(q, max_matches=4, op="routed")
    np.testing.assert_array_equal(np.asarray(vh), np.asarray(vr))
    for k in ch:
        np.testing.assert_array_equal(np.asarray(ch[k]), np.asarray(cr[k]))
print("SKEW_8DEV_OK")
"""


def _run_forced_8(script: str) -> subprocess.CompletedProcess:
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never a chip
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=600)


@pytest.mark.skipif(NDEV >= 8, reason="in-process mesh tests already "
                    "run on this topology")
def test_same_stream_same_hot_set_on_forced_8_mesh_subprocess():
    """The acceptance property: one ingest stream, two topologies
    (vmap emulation vs an 8-device shard_map mesh) — bit-identical hot
    set, mirror, and hybrid answers."""
    proc = _run_forced_8(_SUBPROCESS_SKEW)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "SKEW_8DEV_OK" in proc.stdout


_SUBPROCESS_REPLICA_PLACEMENT = r"""
import numpy as np, jax
n = len(jax.devices())
assert n == 8, jax.devices()
from repro.core.schema import Schema
from repro.frame import IndexedFrame
from repro.dist import mesh

SCH = Schema.of("k", k="int64", v="float32")
rng = np.random.default_rng(3)
fr = IndexedFrame.from_columns(
    {"k": np.arange(64, dtype=np.int64), "v": np.zeros(64, np.float32)},
    SCH, num_shards=n, rt=mesh.mesh_runtime(n), track_hot=16, reserve=4096)
# a replica as wide as the mesh: its [H] axis must not be split
fr = fr.with_replica(capacity=n, max_matches=4)
traces = [0]


@jax.jit
def site(f, q):
    traces[0] += 1
    return f.lookup(q, max_matches=4, op="hybrid")


def check(f):
    for leaf in jax.tree_util.tree_leaves(f.data.replica):
        assert leaf.sharding.is_fully_replicated, leaf.sharding
    assert f.data.version.sharding.is_fully_replicated
    for leaf in jax.tree_util.tree_leaves(f.data.table):
        assert all(s.data.shape[0] == 1 for s in leaf.addressable_shards)


q = np.array([7, 3, 5000], np.int64)
check(fr)
site(fr, q)
for step in range(2):          # each append refreshes the mirror
    fr = fr.append({"k": np.full(16, 7, np.int64),
                    "v": rng.random(16).astype(np.float32)})
    check(fr)
    ch, vh = site(fr, q)
    cr, vr = fr.lookup(q, max_matches=4, op="routed")
    np.testing.assert_array_equal(np.asarray(vh), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(ch["v"]), np.asarray(cr["v"]))
assert traces[0] == 1, traces
print("REPLICA_PLACEMENT_OK")
"""


@pytest.mark.skipif(NDEV >= 8, reason="in-process mesh tests already "
                    "run on this topology")
def test_replica_as_wide_as_mesh_stays_replicated_subprocess():
    """A hot-key replica with ``capacity == num_devices`` is replicated
    over the mesh when attached and after every refresh, so the hybrid
    read site keeps one trace across appends."""
    proc = _run_forced_8(_SUBPROCESS_REPLICA_PLACEMENT)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "REPLICA_PLACEMENT_OK" in proc.stdout
