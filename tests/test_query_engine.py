"""The serving path's test wall (ISSUE 8).

The continuous-batching ``QueryEngine`` sits on top of everything the
repo has built — planner-routed reads, the MVCC arena write path, the
device-resident append ring, supervised recovery — so its contract is
checked against all of them:

* pad-to-bucket batched answers bit-identical to per-request
  ``frame.lookup`` / ``frame.join``, including every bucket boundary
  (1, B-1, B, B+1, ladder max), all-miss batches, and duplicate keys
  (explicit cases + a hypothesis property sweep);
* strict FIFO head-run batching (never reorders past an incompatible
  request);
* the one-version-bump MVCC interleaving contract: reads ride the
  pre-flush snapshot, a flush lands the whole ring as ONE version, and
  ``replay_unbatched`` proves the engine's answers equal an unbatched
  twin replaying ``write_log`` at the recorded versions;
* zero retraces after warmup: traces == distinct (site, bucket) pairs;
* both backends (vmap in-process, shard_map in-process on >=8 devices
  else via the forced-8 subprocess), forced-routed with pad sentinels
  through the exchange, and supervised serving mid-heal.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import IndexedFrame
from repro.core import Schema
from repro.dist import mesh
from repro.serving.query_engine import (PAD_KEY, QueryEngine, bucket_ladder,
                                        pad_keys, pick_bucket,
                                        replay_unbatched)

NDEV = len(jax.devices())
SCH = Schema.of("k", k="int64", v="float32")
N = 512


def _cols(rng, n=N):
    return {"k": np.arange(n, dtype=np.int64),
            "v": rng.random(n).astype(np.float32)}


def _frame(rng, **kw):
    return IndexedFrame.from_columns(_cols(rng), SCH, rows_per_batch=128,
                                     reserve=2048, **kw)


def _twin_frames(rng, **kw):
    """A (reference, engine-owned) pair built from the SAME columns."""
    cols = _cols(rng)
    mk = lambda: IndexedFrame.from_columns(cols, SCH, rows_per_batch=128,
                                           reserve=2048, **kw)
    return mk(), mk()


def _assert_req_equals_direct(req, frame):
    """One request's engine answer == the un-padded facade call."""
    cols, valid = frame.lookup(req.keys, max_matches=req.max_matches)
    np.testing.assert_array_equal(req.result[1], np.asarray(valid))
    for c in cols:
        np.testing.assert_array_equal(req.result[0][c], np.asarray(cols[c]))


# -- units -------------------------------------------------------------------


def test_bucket_ladder_and_pick():
    assert bucket_ladder(64, min_bucket=8) == (8, 16, 32, 64)
    assert bucket_ladder(60, min_bucket=5) == (8, 16, 32, 64)
    lad = bucket_ladder(64, min_bucket=8)
    assert pick_bucket(1, lad) == 8
    assert pick_bucket(8, lad) == 8
    assert pick_bucket(9, lad) == 16
    assert pick_bucket(64, lad) == 64
    with pytest.raises(ValueError):
        pick_bucket(65, lad)
    with pytest.raises(ValueError):
        bucket_ladder(4, min_bucket=8)


def test_pad_keys_sentinel():
    out = pad_keys(np.asarray([3, 1, 2], np.int64), 8)
    np.testing.assert_array_equal(out[:3], [3, 1, 2])
    assert (out[3:] == PAD_KEY).all() and out.dtype == np.int64
    # the sentinel is the reserved EMPTY slot marker: a guaranteed miss
    from repro.core.hashindex import EMPTY_KEY
    assert PAD_KEY == int(np.asarray(EMPTY_KEY))


def test_admission_validation(rng):
    eng = QueryEngine(_frame(rng), ladder=(8, 16))
    with pytest.raises(ValueError):
        eng.submit_lookup(np.zeros(0, np.int64))          # empty
    with pytest.raises(ValueError):
        eng.submit_lookup(np.zeros(17, np.int64))         # > ladder max
    with pytest.raises(ValueError):
        eng.submit_lookup(np.zeros(4, np.float32))        # non-integer keys
    with pytest.raises(ValueError):
        QueryEngine(_frame(rng), ladder=(16, 8))          # not increasing


# -- batched == unbatched, bit-identical -------------------------------------


def test_bucket_boundaries_bit_identical(rng):
    """Every boundary size, all-miss, and duplicate keys: the padded
    batch answer equals the per-request unbatched facade call."""
    frame, owned = _twin_frames(rng)
    eng = QueryEngine(owned, ladder=(8, 16, 32), max_matches=4)
    sizes = [1, 7, 8, 9, 16, 32]                  # 1, B-1, B, B+1, ladder max
    reqs = []
    for s in sizes:
        reqs.append(eng.submit_lookup(
            rng.integers(0, N, size=s).astype(np.int64)))
        eng.tick()                                # one batch per tick
    # all-miss batch (every key absent) and duplicates within one batch
    reqs.append(eng.submit_lookup(np.full(5, N + 999, np.int64)))
    eng.tick()
    reqs.append(eng.submit_lookup(np.asarray([7, 7, 7, 3, 7], np.int64)))
    # a key equal to the pad sentinel itself: a guaranteed miss, not a crash
    reqs.append(eng.submit_lookup(np.asarray([PAD_KEY, 3], np.int64)))
    eng.drain()
    for r in reqs:
        assert r.done and r.bucket in (8, 16, 32)
        _assert_req_equals_direct(r, frame)
    assert eng.zero_retraces_after_warmup


@settings(max_examples=15, deadline=None)
@given(st.lists(st.lists(st.integers(min_value=-3, max_value=N + 3),
                         min_size=1, max_size=32),
                min_size=1, max_size=6))
def test_property_batched_equals_unbatched(key_lists):
    """Hypothesis sweep: arbitrary request mixes (hits, misses, negative
    keys, duplicates, any size <= ladder max) answered through the
    engine == per-request ``frame.lookup``, bit-identical in order."""
    rng = np.random.default_rng(0)
    frame, owned = _twin_frames(rng)
    eng = QueryEngine(owned, ladder=(8, 16, 32), max_matches=4)
    reqs = [eng.submit_lookup(np.asarray(ks, np.int64)) for ks in key_lists]
    eng.drain()
    for r in reqs:
        _assert_req_equals_direct(r, frame)


def test_fifo_head_run_batching(rng):
    """Compatible neighbours coalesce into ONE padded batch; an
    incompatible request (different max_matches) breaks the run and is
    NEVER reordered past."""
    eng = QueryEngine(_frame(rng), ladder=(8, 16, 32), max_matches=4)
    a = eng.submit_lookup(rng.integers(0, N, 3).astype(np.int64))
    b = eng.submit_lookup(rng.integers(0, N, 5).astype(np.int64))
    c = eng.submit_lookup(rng.integers(0, N, 2).astype(np.int64),
                          max_matches=2)          # incompatible: new batch
    d = eng.submit_lookup(rng.integers(0, N, 4).astype(np.int64),
                          max_matches=2)
    eng.tick()
    assert eng.stats.batches == 2
    assert a.bucket == b.bucket == 8              # 3 + 5 -> one bucket-8 batch
    assert c.bucket == d.bucket == 8
    assert a.t_done <= c.t_done                   # FIFO order preserved
    # ladder-max bound: head run stops before overflowing the top bucket
    e = eng.submit_lookup(rng.integers(0, N, 20).astype(np.int64))
    f = eng.submit_lookup(rng.integers(0, N, 20).astype(np.int64))
    eng.tick()
    assert eng.stats.batches == 4                 # 20 + 20 > 32: two batches
    assert e.bucket == f.bucket == 32


# -- MVCC interleaving --------------------------------------------------------


def test_reads_ride_preflush_snapshot(rng):
    """A delta admitted in tick t is invisible to tick-t reads (staged in
    the ring), visible after the deadline flush — ONE version bump for
    the whole ring, host mirror exact."""
    eng = QueryEngine(_frame(rng), ladder=(8,), max_matches=4,
                      flush_deadline_ticks=2)
    v0 = eng.version_host
    new_key = np.asarray([N + 1], np.int64)
    w = eng.submit_append({"k": new_key, "v": np.asarray([1.5], np.float32)})
    r1 = eng.submit_lookup(new_key)
    eng.tick()                                    # reads first, then staging
    assert not r1.result[1].any() and r1.version == v0
    assert eng.staged_writes == 1 and w.t_visible is None
    r2 = eng.submit_lookup(new_key)
    eng.tick()                                    # tick 2: deadline flush
    assert not r2.result[1].any()                 # still pre-flush snapshot
    assert w.t_visible is not None and w.version == v0 + 1
    r3 = eng.submit_lookup(new_key)
    eng.tick()
    assert r3.result[1][0, 0] and r3.version == v0 + 1
    assert eng.stats.flushes == 1 and eng.verify_version()


def test_ring_full_autoflush_and_oversize_bypass(rng):
    """A full ring flushes mid-tick and the delta retries; a delta too
    big for any lane lands through the direct coalesced append."""
    eng = QueryEngine(_frame(rng), ladder=(8,), queue_lanes=2,
                      queue_lane_rows=4, flush_deadline_ticks=100)
    for i in range(5):                            # 5 deltas, 2 lanes
        eng.submit_append({"k": np.asarray([N + i], np.int64),
                           "v": np.asarray([float(i)], np.float32)})
    big = eng.submit_append(
        {"k": np.arange(N + 10, N + 30, dtype=np.int64),
         "v": np.zeros(20, np.float32)})          # 20 rows > lane_rows=4
    eng.tick()
    assert eng.stats.direct_appends == 1 and big.t_visible is not None
    assert eng.stats.flushes >= 2                 # ring-full auto-flushes
    eng.drain()
    r = eng.submit_lookup(np.asarray([N, N + 4, N + 15], np.int64))
    eng.drain()
    assert r.result[1][:, 0].all()                # every delta landed
    assert eng.verify_version()


def test_write_log_twin_replay(rng):
    """The committed bit-identity claim: a mixed read/write run replayed
    unbatched on a twin at the recorded versions -> zero mismatches."""
    frame0, owned = _twin_frames(rng)
    eng = QueryEngine(owned, ladder=(8, 16), max_matches=4,
                      flush_deadline_ticks=2)
    reqs = []
    for step in range(8):
        reqs.append(eng.submit_lookup(
            rng.integers(-3, N + 20, size=int(rng.integers(1, 16)))
            .astype(np.int64)))
        eng.submit_append({"k": np.asarray([N + step], np.int64),
                           "v": np.asarray([float(step)], np.float32)})
        eng.tick()
    eng.drain()
    assert eng.stats.flushes >= 2                 # interleaving actually ran
    assert replay_unbatched(frame0, reqs, eng.write_log) == 0


# -- joins --------------------------------------------------------------------


def test_join_batching_parity(rng):
    frame, owned = _twin_frames(rng)
    eng = QueryEngine(owned, ladder=(8, 16), max_matches=4)
    reqs = []
    for s in (1, 5, 8, 9):
        pc = {"k": rng.integers(0, N, s).astype(np.int64),
              "p": rng.random(s).astype(np.float32)}
        reqs.append(eng.submit_join(pc, "k"))
    eng.drain()
    for r in reqs:
        bcols, pcols, valid = frame.join(r.probe_cols, "k",
                                         max_matches=r.max_matches)
        np.testing.assert_array_equal(r.result[2], np.asarray(valid))
        for c in bcols:
            np.testing.assert_array_equal(r.result[0][c],
                                          np.asarray(bcols[c]))
        for c in pcols:
            np.testing.assert_array_equal(r.result[1][c],
                                          np.asarray(pcols[c]))
    assert eng.zero_retraces_after_warmup


# -- zero retraces ------------------------------------------------------------


def test_zero_retraces_across_ladder_and_writes(rng):
    """Two full passes over the ladder with appends interleaved: traces
    == distinct (site, bucket) pairs, pass 2 adds ZERO."""
    eng = QueryEngine(_frame(rng), ladder=(8, 16, 32), max_matches=4,
                      flush_deadline_ticks=1)
    for pas in range(2):
        for s in (1, 8, 9, 16, 17, 32):
            eng.submit_lookup(rng.integers(0, N, s).astype(np.int64))
            eng.submit_append({"k": np.asarray([N + s + 100 * pas], np.int64),
                               "v": np.asarray([0.0], np.float32)})
            eng.tick()
        if pas == 0:
            warm = eng.retraces
            assert warm == eng.expected_traces == 3     # one per bucket
    assert eng.retraces == warm                          # pass 2: zero new
    assert eng.zero_retraces_after_warmup


# -- distributed + supervised -------------------------------------------------


def test_dist_vmap_engine_parity(rng):
    rt = mesh.vmap_runtime()
    for op in ("auto", "routed"):
        frame0, owned = _twin_frames(rng, num_shards=4, rt=rt)
        eng = QueryEngine(owned, ladder=(8, 16), max_matches=4, op=op,
                          flush_deadline_ticks=2)
        reqs = []
        for step in range(4):
            reqs.append(eng.submit_lookup(
                rng.integers(-3, N + 9, size=int(rng.integers(1, 16)))
                .astype(np.int64)))
            eng.submit_append({"k": np.asarray([N + step], np.int64),
                               "v": np.asarray([float(step)], np.float32)})
            eng.tick()
        eng.drain()
        assert replay_unbatched(frame0, reqs, eng.write_log, op=op) == 0
        assert eng.zero_retraces_after_warmup, op


def test_supervised_serve_through_heal(rng, tmp_path):
    """The engine serves traffic across a shard kill + automatic heal:
    one recovery, no dead shards, answers == the unbatched twin."""
    from repro.dist.resilience import Fault, FaultInjector, RecoveryPolicy
    from repro.dist.runtime import Lineage
    rt = mesh.vmap_runtime()
    cols = _cols(np.random.default_rng(0))
    twin = IndexedFrame.from_columns(cols, SCH, num_shards=4,
                                     rows_per_batch=128, rt=rt)
    mgr = IndexedFrame.from_columns(cols, SCH, num_shards=4,
                                    rows_per_batch=128, rt=rt).supervised(
        lineage=Lineage(SCH, cols, rows_per_batch=128),
        injector=FaultInjector([Fault("shard_loss", step=3, shard=3)],
                               seed=7),
        policy=RecoveryPolicy(checkpoint_every=2),
        checkpoint_dir=str(tmp_path))
    eng = QueryEngine(mgr, ladder=(8, 16), max_matches=4,
                      flush_deadline_ticks=2)
    assert eng.supervised and eng.frame is mgr.frame
    reqs = []
    for step in range(6):
        reqs.append(eng.submit_lookup(
            rng.integers(0, N, size=5).astype(np.int64)))
        eng.submit_append({"k": np.asarray([N + step], np.int64),
                           "v": np.asarray([float(step)], np.float32)})
        eng.tick()
    eng.drain()
    assert mgr.stats.recoveries == 1 and not mgr.dead
    assert replay_unbatched(twin, reqs, eng.write_log) == 0
    assert eng.verify_version()


def test_frame_serve_entrypoint(rng):
    """``frame.serve(...)`` is the facade door to the engine."""
    eng = _frame(rng).serve(ladder=(8,), max_matches=4)
    r = eng.submit_lookup(np.asarray([3], np.int64))
    eng.drain()
    assert isinstance(eng, QueryEngine) and r.done
    assert r.result[1][0, 0]                       # key 3 exists


# -- forced-8 shard_map topology ---------------------------------------------

_SUBPROCESS_SERVE = """
import numpy as np, jax
from repro import IndexedFrame
from repro.core import Schema
from repro.dist import mesh
from repro.serving.query_engine import QueryEngine, replay_unbatched
assert len(jax.devices()) == 8, jax.devices()
SCH = Schema.of("k", k="int64", v="float32")
rng = np.random.default_rng(5)
N = 1024
cols = {"k": np.arange(N, dtype=np.int64),
        "v": rng.random(N).astype(np.float32)}
rt = mesh.mesh_runtime(8)
frame0 = IndexedFrame.from_columns(cols, SCH, num_shards=8,
                                   rows_per_batch=128, rt=rt)
eng = QueryEngine(
    IndexedFrame.from_columns(cols, SCH, num_shards=8, rows_per_batch=128,
                              rt=rt),
    ladder=(8, 16, 32), max_matches=4, flush_deadline_ticks=2)
reqs = []
for step in range(6):
    for s in (1, 8, 9, 32):
        reqs.append(eng.submit_lookup(
            rng.integers(-3, N + 9, size=s).astype(np.int64)))
    eng.submit_append({"k": np.asarray([N + step], np.int64),
                       "v": np.asarray([float(step)], np.float32)})
    eng.tick()
eng.drain()
assert replay_unbatched(frame0, reqs, eng.write_log) == 0
assert eng.zero_retraces_after_warmup, (eng.retraces, eng.expected_traces)
assert eng.verify_version()
# every leaf of the flushed frame, its ring included, spans the mesh
for leaf in jax.tree_util.tree_leaves(eng.frame):
    assert leaf.sharding.device_set == set(jax.devices()), leaf.sharding
print("SERVE_8DEV_OK")
"""


@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices (ci.sh forced-8 "
                    "pass; the subprocess test covers single-device runs)")
def test_serve_shard_map_in_process():
    exec(compile(_SUBPROCESS_SERVE, "<serve-8dev>", "exec"), {})


@pytest.mark.skipif(NDEV >= 8, reason="in-process test runs on this "
                    "topology")
def test_serve_shard_map_subprocess():
    """Engine bit-identity + zero retraces on the real shard_map backend
    under a forced 8-device host topology."""
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never a chip
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SERVE],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "SERVE_8DEV_OK" in proc.stdout
