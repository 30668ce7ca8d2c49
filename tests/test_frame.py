"""IndexedFrame facade parity (ISSUE 5): every facade method must be
bit-identical to the free-function path it dispatches to, on both
backends — the facade is a seam, not a reimplementation.

Covers: planner-driven physical-operator selection (rules L1-L3/J1-J3
named by ``explain()``), lookup/join parity local + distributed (vmap
in-process; shard_map in-process on >=8 devices, else via a forced-8
subprocess), MVCC divergent versions through the facade, coalesced
list-append ≡ sequential appends (one version bump, one ingest),
relational plans, save/load/reshard, the unified input validation, and
zero retraces for jitted sites taking the frame as an argument.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import IndexedFrame
from repro.core import Schema, append, coalesce_deltas, create_index, joins
from repro.core.planner import Col, Eq, Filter, Lit, Planner
from repro import dist
from repro.dist import mesh

NDEV = len(jax.devices())
SCH = Schema.of("k", k="int64", v="float32", tag="int32")


def _cols(rng, n=400, key_range=50):
    return {"k": rng.integers(0, key_range, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
            "tag": rng.integers(0, 9, n).astype(np.int32)}


def _delta(rng, n=16, key_range=50):
    return {"k": rng.integers(0, key_range, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
            "tag": rng.integers(0, 9, n).astype(np.int32)}


def _assert_cols_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), k)


@pytest.fixture
def local(rng):
    cols = _cols(rng)
    return cols, IndexedFrame.from_columns(cols, SCH, rows_per_batch=64)


@pytest.fixture
def dframe(rng):
    cols = _cols(rng)
    return cols, IndexedFrame.from_columns(cols, SCH, num_shards=4,
                                           rows_per_batch=64)


# --- lookup parity ---------------------------------------------------------

def test_local_lookup_matches_free_function(local, rng):
    cols, fr = local
    q = np.concatenate([rng.choice(cols["k"], 24), [10**12]]).astype(np.int64)
    fc, fv = fr.lookup(q, max_matches=8)
    t = create_index(cols, SCH, rows_per_batch=64)
    gc, gv = joins.indexed_lookup(t, q, max_matches=8)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(gv))
    _assert_cols_equal(fc, gc)


def test_dist_lookup_bcast_matches_free_function(dframe, rng):
    cols, fr = dframe
    q = np.concatenate([rng.choice(cols["k"], 24), [10**12]]).astype(np.int64)
    assert fr.plan_lookup(q).kind == "BroadcastLookup"
    fc, fv = fr.lookup(q, max_matches=8)
    gc, gv, _ = dist.lookup(fr.data, q, max_matches=8)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(gv))
    _assert_cols_equal(fc, gc)


def test_dist_lookup_routed_matches_bcast_bitwise(dframe, rng):
    """The routed flavor answers every query identically to broadcast
    (including the word-packed float payload, bit-exact)."""
    cols, fr = dframe
    q = np.concatenate([rng.choice(cols["k"], 30),
                        [10**12, -7]]).astype(np.int64)
    bc, bv = fr.lookup(q, max_matches=8, op="bcast")
    rc, rv = fr.lookup(q, max_matches=8, op="routed")
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(bv))
    _assert_cols_equal(rc, bc)


def test_dist_lookup_routed_matches_free_function(dframe, rng):
    """Facade routed ≡ dist.lookup_routed with the same source split."""
    cols, fr = dframe
    q = rng.choice(cols["k"], 32).astype(np.int64)
    fc, fv = fr.lookup(q, max_matches=8, op="routed")
    gc, gv, answered, dropped = dist.lookup_routed(
        fr.data, q.reshape(4, 8), max_matches=8)
    assert int(np.asarray(dropped).sum()) == 0
    assert bool(np.asarray(answered).all())
    np.testing.assert_array_equal(np.asarray(fv),
                                  np.asarray(gv).reshape(32, 8))
    for k in fc:
        np.testing.assert_array_equal(
            np.asarray(fc[k]), np.asarray(gc[k]).reshape(32, 8), k)


def test_lookup_ragged_batch_routed(dframe, rng):
    """Q not divisible by num_shards: the flat adapter pads with invalid
    lanes and trims the answers back to input order."""
    cols, fr = dframe
    q = rng.choice(cols["k"], 13).astype(np.int64)
    bc, bv = fr.lookup(q, max_matches=8, op="bcast")
    rc, rv = fr.lookup(q, max_matches=8, op="routed")
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(bv))
    _assert_cols_equal(rc, bc)


# --- planner physical selection --------------------------------------------

def test_planner_selects_lookup_flavor_by_volume(dframe):
    cols, fr = dframe
    small = np.zeros(16, np.int64)
    big = np.zeros(4096, np.int64)
    p_small = fr.plan_lookup(small)
    p_big = fr.plan_lookup(big)
    assert p_small.kind == "BroadcastLookup" and "L2" in p_small.reason
    assert p_big.kind == "RoutedLookup" and "L3" in p_big.reason
    # the threshold is a Planner knob, not a constant
    p = Planner(routed_threshold=8)
    assert fr.plan_lookup(small, planner=p).kind == "RoutedLookup"


def test_planner_selects_join_flavor_by_probe_rows(dframe):
    cols, fr = dframe
    pc = {"k": np.zeros(32, np.int64)}
    p_small = fr.plan_join(pc, "k")
    assert p_small.kind == "BroadcastJoin" and "J2" in p_small.reason
    p = Planner(bcast_threshold=8)
    p_big = fr.plan_join(pc, "k", planner=p)
    assert p_big.kind == "ShuffleJoin" and "J3" in p_big.reason


def test_planner_local_rules(local):
    cols, fr = local
    q = np.zeros(10**7, np.int64)[:0]  # shape only matters
    pl = fr.plan_lookup(np.zeros(8, np.int64))
    assert pl.kind == "IndexedLookup" and "L1" in pl.reason
    pj = fr.plan_join({"k": np.zeros(8, np.int64)}, "k")
    assert pj.kind == "IndexedJoin" and "J1" in pj.reason


def test_choose_helpers_delegate_to_planner():
    """The legacy dist.choose_* helpers and the Planner rules must never
    disagree (the cost model lives in ONE place now)."""
    class D:
        num_shards = 8
    p = Planner()
    for q in (1, 64, 4095, 4096, 10**6):
        assert dist.choose_lookup(D(), q) == p.lookup_flavor(8, q)[0]
    for r in (1, 10**6, 10**6 + 1, 10**8):
        assert dist.choose_join(D(), r) == p.join_flavor(r)[0]


def test_forced_op_validation(local, dframe):
    _, fr = local
    _, df = dframe
    q = np.zeros(4, np.int64)
    with pytest.raises(ValueError):
        fr.lookup(q, op="routed")        # nothing to route on 1 shard
    with pytest.raises(ValueError):
        df.lookup(q, op="local")
    with pytest.raises(ValueError):
        df.lookup(q, op="sideways")


# --- join parity ------------------------------------------------------------

def test_local_join_matches_free_function(local, rng):
    cols, fr = local
    pc = {"k": rng.choice(cols["k"], 40).astype(np.int64),
          "ev": np.arange(40, dtype=np.int32)}
    fb, fp, fv = fr.join(pc, "k", max_matches=8)
    t = create_index(cols, SCH, rows_per_batch=64)
    gb, gp, gv = joins.indexed_join(t, pc, "k", max_matches=8)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(gv))
    _assert_cols_equal(fb, gb)
    _assert_cols_equal(fp, gp)


def test_dist_join_bcast_matches_free_function(dframe, rng):
    cols, fr = dframe
    pc = {"k": rng.choice(cols["k"], 40).astype(np.int64),
          "ev": np.arange(40, dtype=np.int32)}
    fb, fp, fv = fr.join(pc, "k", max_matches=8)
    gb, gp, gv = dist.indexed_join_bcast(fr.data, pc, "k", 8)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(gv))
    _assert_cols_equal(fb, gb)
    _assert_cols_equal(fp, gp)


def test_dist_join_shuffle_matches_bcast(dframe, rng):
    """The shuffle flavor (routed exchange, flat contract) returns the
    same rows in the same probe order as broadcast."""
    cols, fr = dframe
    pc = {"k": np.concatenate([rng.choice(cols["k"], 39),
                               [10**12]]).astype(np.int64),
          "ev": np.arange(40, dtype=np.int32)}
    bb, bp, bv = fr.join(pc, "k", max_matches=8, op="bcast")
    sb, sp, sv = fr.join(pc, "k", max_matches=8, op="shuffle")
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(bv))
    _assert_cols_equal(sb, bb)
    _assert_cols_equal(sp, bp)


def test_join_local_vs_dist_same_semantics(local, dframe, rng):
    cols_l, fr = local
    cols_d, df = dframe
    # same data in both frames -> same multiset of join matches
    pc = {"k": rng.choice(cols_l["k"], 24).astype(np.int64)}
    fd = IndexedFrame.from_columns(cols_l, SCH, num_shards=4,
                                   rows_per_batch=64)
    lb, _, lv = fr.join(pc, "k", max_matches=16)
    db, _, dv = fd.join(pc, "k", max_matches=16)
    assert int(np.asarray(lv).sum()) == int(np.asarray(dv).sum())
    np.testing.assert_array_equal(
        np.sort(np.asarray(lb["v"])[np.asarray(lv)]),
        np.sort(np.asarray(db["v"])[np.asarray(dv)]))


# --- appends: MVCC + coalescing --------------------------------------------

def test_append_matches_free_function(local, rng):
    cols, fr = local
    d = _delta(rng)
    fr2 = fr.append(d)
    t2 = append(create_index(cols, SCH, rows_per_batch=64), d)
    q = np.unique(np.concatenate([d["k"], cols["k"][:8]]))
    fc, fv = fr2.lookup(q, max_matches=16)
    gc, gv = joins.indexed_lookup(t2, q, max_matches=16)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(gv))
    _assert_cols_equal(fc, gc)


@pytest.mark.parametrize("num_shards", [1, 4])
def test_append_list_coalesces_to_one_version(rng, num_shards):
    cols = _cols(rng)
    fr = IndexedFrame.from_columns(cols, SCH, num_shards=num_shards,
                                   rows_per_batch=64)
    deltas = [_delta(rng, n) for n in (16, 5, 32)]
    seq = fr
    for d in deltas:
        seq = seq.append(d)
    batched = fr.append(deltas)
    # one fused ingest -> ONE version bump; sequential bumped three times
    v0 = int(np.asarray(fr.version).ravel()[0])
    assert int(np.asarray(batched.version).ravel()[0]) == v0 + 1
    assert int(np.asarray(seq.version).ravel()[0]) == v0 + 3
    # ...but decoded answers are bit-identical (chain order preserved)
    q = np.unique(np.concatenate([d["k"] for d in deltas]))
    sc, sv = seq.lookup(q, max_matches=32)
    bc, bv = batched.lookup(q, max_matches=32)
    np.testing.assert_array_equal(np.asarray(bv), np.asarray(sv))
    _assert_cols_equal(bc, sc)


def test_coalesce_deltas_valid_masks(rng):
    d1, d2 = _delta(rng, 6), _delta(rng, 4)
    v2 = np.asarray([True, False, True, False])
    cols, valid = coalesce_deltas([d1, d2], SCH, [None, v2])
    assert valid.shape == (10,)
    assert valid[:6].all() and np.array_equal(valid[6:], v2)
    with pytest.raises(ValueError):
        coalesce_deltas([], SCH)
    with pytest.raises(ValueError):
        coalesce_deltas([d1, d2], SCH, [None])


def test_mvcc_divergent_versions_through_facade(local, rng):
    cols, fr = local
    key = int(cols["k"][0])
    da = {"k": np.asarray([key], np.int64),
          "v": np.asarray([111.0], np.float32),
          "tag": np.asarray([1], np.int32)}
    db = {"k": np.asarray([key], np.int64),
          "v": np.asarray([222.0], np.float32),
          "tag": np.asarray([2], np.int32)}
    child_a, child_b = fr.append(da), fr.append(db)
    q = np.asarray([key], np.int64)
    base_n = int(np.asarray(fr.lookup(q, max_matches=32)[1]).sum())
    ca, va = child_a.lookup(q, max_matches=32)
    cb, vb = child_b.lookup(q, max_matches=32)
    # parent unchanged, children diverge (paper Listing 2)
    assert int(np.asarray(fr.lookup(q, max_matches=32)[1]).sum()) == base_n
    assert int(np.asarray(va).sum()) == base_n + 1
    assert float(np.asarray(ca["v"])[0, 0]) == 111.0
    assert float(np.asarray(cb["v"])[0, 0]) == 222.0


def test_compact_preserves_lookups(dframe, rng):
    cols, fr = dframe
    fr2 = fr.append([_delta(rng), _delta(rng)])
    q = rng.choice(cols["k"], 16).astype(np.int64)
    before = fr2.lookup(q, max_matches=16)
    after = fr2.compact().lookup(q, max_matches=16)
    np.testing.assert_array_equal(np.asarray(after[1]),
                                  np.asarray(before[1]))
    _assert_cols_equal(after[0], before[0])


# --- relational plans -------------------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 4])
def test_filter_execute_matches_lookup(rng, num_shards):
    cols = _cols(rng)
    fr = IndexedFrame.from_columns(cols, SCH, num_shards=num_shards,
                                   rows_per_batch=64)
    key = int(cols["k"][0])
    plan = fr.filter(Eq(Col("k"), Lit(key)),
                     planner=Planner(max_matches=128))
    txt = plan.explain()
    assert "R1" in txt
    if num_shards > 1:
        assert "BroadcastLookup" in txt and "L2" in txt
    else:
        assert "IndexedLookup" in txt
    rows, valid = plan.execute()
    exp = np.sort(cols["v"][cols["k"] == key])
    np.testing.assert_allclose(
        np.sort(np.asarray(rows["v"])[np.asarray(valid)]), exp)


def test_join_plan_sees_through_wrapped_probe(dframe):
    """J2/J3 uses the probe subtree's source cardinality even when the
    probe side is wrapped in Filter/Project (not a bare Relation)."""
    from repro.core.planner import Join, Project, Relation
    _, df = dframe
    probe = Relation("p", cols={"k": np.zeros(64, np.int64)})
    wrapped = Project(probe, ("k",))
    phys = Planner(bcast_threshold=32).plan(
        Join(df.relation(), wrapped, on="k"))
    assert phys.kind == "ShuffleJoin"
    assert "probe_rows=64" in phys.reason


def test_agg_and_join_plans(local, dframe, rng):
    cols, fr = local
    key = int(cols["k"][0])
    got = fr.filter(Eq(Col("k"), Lit(key)),
                    planner=Planner(max_matches=128)).agg("count",
                                                          "v").execute()
    assert int(got) == int(np.sum(cols["k"] == key))
    _, df = dframe
    # join plan through the relation tree names the dist flavor
    from repro.core.planner import Join, Relation
    probe = Relation("p", cols={"k": np.arange(5, dtype=np.int64)})
    phys = Planner().plan(Join(df.relation(), probe, on="k"))
    assert phys.kind == "BroadcastJoin"
    assert "R2" in phys.reason and "J2" in phys.reason


# --- persistence / elasticity ----------------------------------------------

@pytest.mark.parametrize("num_shards", [1, 4])
def test_save_load_roundtrip(rng, tmp_path, num_shards):
    cols = _cols(rng)
    fr = IndexedFrame.from_columns(cols, SCH, num_shards=num_shards,
                                   rows_per_batch=64).append(_delta(rng))
    path = str(tmp_path / "ckpt")
    fr.save(path)
    fr2 = IndexedFrame.load(path, fr)
    q = rng.choice(cols["k"], 16).astype(np.int64)
    a, b = fr.lookup(q, max_matches=8), fr2.lookup(q, max_matches=8)
    np.testing.assert_array_equal(np.asarray(b[1]), np.asarray(a[1]))
    _assert_cols_equal(b[0], a[0])
    v1 = np.asarray(fr.version).ravel()[0]
    assert int(np.asarray(fr2.version).ravel()[0]) == int(v1)


def test_load_rejects_wrong_backend(rng, tmp_path, local, dframe):
    _, fr = local
    _, df = dframe
    p1, p2 = str(tmp_path / "l"), str(tmp_path / "d")
    fr.save(p1)
    df.save(p2)
    with pytest.raises(ValueError):
        IndexedFrame.load(p2, fr)   # dtable ckpt into local template
    with pytest.raises(ValueError):
        dist.checkpoint.restore_table(p2, fr.data)


def test_reshard_local_to_distributed(local, rng):
    cols, fr = local
    fr2 = fr.append(_delta(rng))
    df = fr2.reshard(4)
    assert df.is_distributed and df.num_shards == 4
    q = np.unique(rng.choice(cols["k"], 16)).astype(np.int64)
    a, b = fr2.lookup(q, max_matches=16), df.lookup(q, max_matches=16)
    valid = np.asarray(a[1])
    np.testing.assert_array_equal(np.asarray(b[1]), valid)
    # invalid-lane fill is backend-defined (local decodes a clamped row 0,
    # dist zero-fills); the contract covers valid lanes
    for k in a[0]:
        np.testing.assert_array_equal(np.asarray(b[0][k])[valid],
                                      np.asarray(a[0][k])[valid], k)
    assert int(np.asarray(df.version).ravel()[0]) == int(
        np.asarray(fr2.version).ravel()[0])


def test_reshard_distributed(dframe, rng):
    cols, fr = dframe
    df2 = fr.reshard(2)
    assert df2.num_shards == 2
    q = rng.choice(cols["k"], 16).astype(np.int64)
    a, b = fr.lookup(q, max_matches=8), df2.lookup(q, max_matches=8)
    np.testing.assert_array_equal(np.asarray(b[1]), np.asarray(a[1]))
    _assert_cols_equal(b[0], a[0])


# --- unified validation ------------------------------------------------------

def test_validation_facade_and_dist_layer(local, dframe):
    _, fr = local
    _, df = dframe
    q64 = np.zeros(8, np.int64)
    bad_dtype = [np.zeros(8, np.int32), np.zeros(8, np.float32)]
    for frame in (fr, df):
        with pytest.raises(ValueError):
            frame.lookup(q64, max_matches=0)
        with pytest.raises(ValueError):
            frame.join({"k": q64}, "k", max_matches=-3)
        for bad in bad_dtype:
            with pytest.raises(ValueError):
                frame.lookup(bad, max_matches=4)
    # the dist free functions now reject what joins.indexed_lookup rejects
    for bad in bad_dtype:
        with pytest.raises(ValueError):
            dist.lookup(df.data, bad, max_matches=4)
        with pytest.raises(ValueError):
            dist.lookup_routed(df.data, bad.reshape(4, 2), max_matches=4)
    with pytest.raises(ValueError):
        dist.lookup(df.data, q64, max_matches=0)
    with pytest.raises(ValueError):
        dist.lookup_routed(df.data, q64.reshape(4, 2), max_matches=0)
    with pytest.raises(ValueError):
        dist.indexed_join_shuffle(df.data, {"k": q64.reshape(4, 2)}, "k",
                                  np.ones((4, 2), bool), 0)


# --- zero retraces through the facade ---------------------------------------

def test_jitted_frame_sites_do_not_retrace_across_appends(rng):
    cols = _cols(rng, key_range=64)
    fr = IndexedFrame.from_columns(cols, SCH,
                                   rows_per_batch=64).with_flat_data()
    q = jnp.asarray(rng.integers(0, 64, 32).astype(np.int64))
    counts = {"lookup": 0}

    @jax.jit
    def f(frame, qq):
        counts["lookup"] += 1
        return frame.lookup(qq, max_matches=4)[1]

    jax.block_until_ready(f(fr, q))
    for _ in range(6):
        fr = fr.append(_delta(rng, key_range=64))
        jax.block_until_ready(f(fr, q))
    assert counts["lookup"] == 1


# --- shard_map backend (forced-8 when single-device) ------------------------

_SUBPROCESS_FRAME = r"""
import numpy as np, jax
from repro import IndexedFrame
from repro.core import Schema
from repro.dist import mesh
assert len(jax.devices()) == 8, jax.devices()
SCH = Schema.of("k", k="int64", v="float32", tag="int32")
rng = np.random.default_rng(5)
cols = {"k": rng.integers(0, 200, 800).astype(np.int64),
        "v": rng.random(800).astype(np.float32),
        "tag": rng.integers(0, 9, 800).astype(np.int32)}
fv = IndexedFrame.from_columns(cols, SCH, num_shards=8, rows_per_batch=64,
                               rt=mesh.vmap_runtime())
fs = IndexedFrame.from_columns(cols, SCH, num_shards=8, rows_per_batch=64,
                               rt=mesh.mesh_runtime(8))
q = np.concatenate([rng.choice(cols["k"], 31), [10**12]]).astype(np.int64)
for op in ("bcast", "routed"):
    av, bv = fv.lookup(q, max_matches=8, op=op), fs.lookup(q, max_matches=8,
                                                           op=op)
    np.testing.assert_array_equal(np.asarray(av[1]), np.asarray(bv[1]))
    np.testing.assert_array_equal(np.asarray(av[0]["tag"]),
                                  np.asarray(bv[0]["tag"]))
    if op == "routed":  # word-packed exchange: float payload bit-exact
        np.testing.assert_array_equal(np.asarray(av[0]["v"]),
                                      np.asarray(bv[0]["v"]))
pc = {"k": rng.choice(cols["k"], 24).astype(np.int64),
      "ev": np.arange(24, dtype=np.int32)}
for op in ("bcast", "shuffle"):
    ja, jb = fv.join(pc, "k", max_matches=8, op=op), fs.join(
        pc, "k", max_matches=8, op=op)
    np.testing.assert_array_equal(np.asarray(ja[2]), np.asarray(jb[2]))
    np.testing.assert_array_equal(np.asarray(ja[0]["tag"]),
                                  np.asarray(jb[0]["tag"]))
d = {"k": rng.integers(0, 200, 16).astype(np.int64),
     "v": rng.random(16).astype(np.float32),
     "tag": rng.integers(0, 9, 16).astype(np.int32)}
av = fv.append([d, d]).lookup(q, max_matches=8)
bv = fs.append([d, d]).lookup(q, max_matches=8)
np.testing.assert_array_equal(np.asarray(av[1]), np.asarray(bv[1]))
print("FRAME_PARITY_8DEV_OK")
"""


@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices (ci.sh forced-8 "
                    "pass; the subprocess test covers single-device runs)")
def test_frame_parity_shard_map_in_process():
    env_script = compile(_SUBPROCESS_FRAME, "<frame-parity>", "exec")
    exec(env_script, {})


@pytest.mark.skipif(NDEV >= 8, reason="in-process test runs on this "
                    "topology")
def test_frame_parity_shard_map_subprocess():
    """Facade parity on the shard_map backend, forced-8 host topology."""
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never a chip
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_FRAME],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "FRAME_PARITY_8DEV_OK" in proc.stdout
