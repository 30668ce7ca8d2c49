"""ISSUE 7: the device-resident append queue (DESIGN.md §13).

Property tests: for random delta sequences, enqueue+flush, ONE coalesced
list append, and N sequential appends must answer every lookup with
bit-identical decoded columns and valid masks — locally, on the
vmap-distributed backend, and (forced-8 subprocess) on shard_map.
Plus the MVCC visibility contract (queued rows invisible, one version
bump per flush), the overflow -> promote path, ring-full behaviour
(QueueOverflow vs ``append(queued=True)`` auto-flush), the zero-retrace
guarantee across full ring wraps, the ≤1-host-sync flush, and the
vectorized string hasher's bit-identity with the scalar reference.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import IndexedFrame
from repro.core import Schema, table as table_mod
from repro.core.hashing import hash_string_host, hash_strings_host
from repro.dist import mesh

NDEV = len(jax.devices())
SCH = Schema.of("k", k="int64", v="float32")

KEYS = st.lists(st.integers(min_value=0, max_value=11), min_size=1,
                max_size=24)
DELTAS = st.lists(KEYS, min_size=1, max_size=6)


def _base(n=64):
    rng = np.random.default_rng(0)
    return {"k": rng.integers(0, 12, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32)}


def _delta(keys, tag):
    keys = np.asarray(keys, np.int64)
    return {"k": keys,
            "v": (np.arange(len(keys), dtype=np.float32) * 0.5
                  + np.float32(tag))}


def _vals(fr, max_matches=256):
    cols, valid = fr.lookup(np.arange(12, dtype=np.int64),
                            max_matches=max_matches)
    m = np.asarray(valid)
    return np.where(m, np.asarray(cols["v"]), np.nan), m


def _assert_same(fa, fb, tag=""):
    va, ma = _vals(fa)
    vb, mb = _vals(fb)
    np.testing.assert_array_equal(ma, mb, err_msg=tag)
    np.testing.assert_array_equal(va, vb, err_msg=tag)


# --- equivalence: enqueue+flush == coalesced == sequential -----------------

@settings(max_examples=20, deadline=None)
@given(DELTAS)
def test_queue_flush_equivalence_local(key_lists):
    deltas = [_delta(ks, i) for i, ks in enumerate(key_lists)]
    fr0 = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                    reserve=1024)
    fq = fr0.with_queue(lanes=8, lane_rows=32)
    for d in deltas:
        fq = fq.enqueue(d, donate=False)
    fq = fq.flush()
    fc = fr0.append(list(deltas))
    fs = fr0
    for d in deltas:
        fs = fs.append(d)
    _assert_same(fq, fc, "queued vs coalesced")
    _assert_same(fq, fs, "queued vs sequential")
    assert fq.version == fc.version == 1
    assert fs.version == len(deltas)
    assert fq.pending_rows == 0


@settings(max_examples=8, deadline=None)
@given(DELTAS)
def test_queue_flush_equivalence_dist_vmap(key_lists):
    deltas = [_delta(ks, i) for i, ks in enumerate(key_lists)]
    fr0 = IndexedFrame.from_columns(_base(), SCH, num_shards=4,
                                    rt=mesh.vmap_runtime(),
                                    rows_per_batch=64, reserve=1024)
    fq = fr0.with_queue(lanes=8, lane_rows=32)
    for d in deltas:
        fq = fq.enqueue(d, donate=False)
    fq = fq.flush()
    fc = fr0.append(list(deltas))
    _assert_same(fq, fc, "dist queued vs coalesced")
    assert fq.version == fc.version
    assert fq.pending_rows == 0


# --- MVCC visibility --------------------------------------------------------

def test_queued_rows_invisible_until_flush():
    fr = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                   reserve=1024).with_queue(lanes=4,
                                                            lane_rows=32)
    v0, m0 = _vals(fr)
    fr = fr.enqueue(_delta([3, 3, 7], 9), donate=False)
    assert fr.pending_deltas == 1 and fr.pending_rows == 3
    v1, m1 = _vals(fr)
    np.testing.assert_array_equal(m0, m1)   # ring rows hard-masked out
    np.testing.assert_array_equal(v0, v1)
    assert fr.version == 0                  # no bump before flush
    assert "pending_ring_rows=3" in fr.plan_lookup(np.arange(4)).reason
    fr = fr.flush()
    assert fr.version == 1                  # exactly ONE bump for the ring
    _, m2 = _vals(fr)
    assert m2.sum() == m0.sum() + 3


# --- overflow -> promote ----------------------------------------------------

def test_flush_overflow_promotes_bit_identical():
    deltas = [_delta(list(range(10)), i) for i in range(3)]
    fr0 = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                    reserve=8)   # ring > spare capacity
    t0 = fr0.data
    q = table_mod.empty_queue(SCH, lanes=4, lane_rows=16)
    for d in deltas:
        q = table_mod.enqueue(q, d, donate=False)
    child, ring, promoted = table_mod.flush_queue(t0, q)
    assert promoted                          # held flush took the promote path
    assert table_mod.queue_pending(ring) == (0, 0)
    ref = fr0.append(list(deltas))
    import dataclasses
    _assert_same(dataclasses.replace(fr0, data=child), ref, "promoted parity")
    assert int(np.asarray(child.version)) == int(np.asarray(ref.data.version))


# --- ring-full: QueueOverflow vs append(queued=True) auto-flush -------------

def test_ring_full_raises_and_queued_append_autoflushes():
    fr = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                   reserve=1024).with_queue(lanes=2,
                                                            lane_rows=16)
    d = _delta([1, 2, 3], 0)
    fr = fr.enqueue(d, donate=False).enqueue(d, donate=False)
    with pytest.raises(table_mod.QueueOverflow):
        fr.enqueue(d, donate=False)
    with pytest.raises(table_mod.QueueOverflow):   # oversize delta
        fr.flush().enqueue(_delta(list(range(17)), 0), donate=False)
    # the facade auto-flushes instead of raising
    fr2 = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                    reserve=1024).with_queue(lanes=2,
                                                             lane_rows=16)
    deltas = [_delta([i, i + 1], i) for i in range(5)]
    for dd in deltas:
        fr2 = fr2.append(dd, queued=True)
    fr2 = fr2.flush()
    _assert_same(fr2, IndexedFrame.from_columns(
        _base(), SCH, rows_per_batch=64, reserve=1024).append(deltas),
        "auto-flush stream parity")
    assert fr2.pending_rows == 0


# --- zero retraces across full ring wraps ----------------------------------

@pytest.mark.parametrize("dist", [False, True])
def test_ring_wrap_zero_retraces(dist):
    kw = (dict(num_shards=4, rt=mesh.vmap_runtime()) if dist else {})
    fr = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                   reserve=4096, **kw).with_queue(
                                       lanes=3, lane_rows=16)
    traced = None
    for wrap in range(3):
        for i in range(3):
            fr = fr.enqueue(_delta([wrap, i, 5], wrap * 3 + i), donate=False)
        fr = fr.flush()
        if wrap == 0:
            traced = dict(table_mod.QUEUE_TRACES)
    assert dict(table_mod.QUEUE_TRACES) == traced, (
        "enqueue/flush retraced after the first full ring wrap")


# --- ≤1 host sync per flush -------------------------------------------------

def test_flush_costs_one_host_sync(monkeypatch):
    fr = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                   reserve=1024).with_queue(lanes=4,
                                                            lane_rows=32)
    for i in range(3):
        fr = fr.enqueue(_delta([i, i], i), donate=False)
    real = jax.device_get
    syncs = {"n": 0}

    def counting(x):
        syncs["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    fr = fr.flush()
    monkeypatch.setattr(jax, "device_get", real)
    assert syncs["n"] == 1, f"flush cost {syncs['n']} host syncs, want 1"
    assert fr.version == 1


def test_enqueue_costs_zero_host_syncs(monkeypatch):
    fr = IndexedFrame.from_columns(_base(), SCH, rows_per_batch=64,
                                   reserve=1024).with_queue(lanes=4,
                                                            lane_rows=32)
    real = jax.device_get
    syncs = {"n": 0}

    def counting(x):
        syncs["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    fr = fr.enqueue(_delta([1, 2], 0), donate=False)
    monkeypatch.setattr(jax, "device_get", real)
    assert syncs["n"] == 0, f"enqueue cost {syncs['n']} host syncs, want 0"
    assert fr.pending_rows == 2     # host mirror, no device round-trip


# --- vectorized string hashing ---------------------------------------------

@settings(max_examples=50, deadline=None)
@given(st.lists(st.text(min_size=0, max_size=12), min_size=0, max_size=16))
def test_hash_strings_host_matches_scalar(strings):
    vec = hash_strings_host(strings)
    ref = np.array([np.int64(np.uint64(hash_string_host(s)
                                       & 0xFFFFFFFFFFFFFFFF))
                    for s in strings], dtype=np.int64)
    np.testing.assert_array_equal(vec, ref)


def test_string_keys_stream_through_queue():
    names = [f"user-{i}" for i in range(40)]
    rng = np.random.default_rng(2)
    cols = {"k": np.array(names, dtype=object),
            "v": rng.random(40).astype(np.float32)}
    fr = IndexedFrame.from_columns(cols, SCH, rows_per_batch=64,
                                   reserve=512).with_queue(lanes=4,
                                                           lane_rows=16)
    d = {"k": ["user-new-a", "user-new-b"],
         "v": np.array([1.5, 2.5], np.float32)}
    fr = fr.enqueue(d, donate=False).flush()
    q = hash_strings_host(["user-3", "user-new-b", "missing"])
    got, valid = fr.lookup(q, max_matches=4)
    m = np.asarray(valid)
    assert m[0].any() and m[1].any() and not m[2].any()
    assert np.asarray(got["v"])[1][m[1]][0] == np.float32(2.5)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.lists(st.text(min_size=0, max_size=12),
                         min_size=0, max_size=12),
                min_size=0, max_size=4))
def test_string_dictionary_codes_bit_identical(batches):
    from repro.core.hashing import StringDictionary
    d = StringDictionary()
    seen = set()
    for b in batches:
        np.testing.assert_array_equal(d.encode(b), hash_strings_host(b))
        seen |= set(b)
    assert len(d) == len(seen)
    assert d.hashed == len(seen)          # each unique hashed exactly once
    before = d.reused
    codes = d.encode(sorted(seen))        # warm dict: every row reused
    assert d.reused == before + len(seen) and d.hashed == len(seen)
    assert d.decode(codes) == sorted(seen)


def test_string_dictionary_through_facade():
    from repro.core.hashing import StringDictionary
    d = StringDictionary()
    names = [f"user-{i % 8}" for i in range(24)]
    vals = np.arange(24, dtype=np.float32)
    fr_d = IndexedFrame.from_columns(
        {"k": np.array(names, dtype=object), "v": vals}, SCH,
        rows_per_batch=64, reserve=256, dictionary=d)
    fr_p = IndexedFrame.from_columns(
        {"k": np.array(names, dtype=object), "v": vals}, SCH,
        rows_per_batch=64, reserve=256)
    assert d.hashed == 8      # 24 rows, 8 unique strings byte-walked
    delta = {"k": ["user-3", "user-99"], "v": np.array([9., 9.],
                                                      np.float32)}
    fr_d = fr_d.append(dict(delta), dictionary=d)
    fr_p = fr_p.append(dict(delta))
    assert d.hashed == 9      # only the novel string paid the byte walk
    assert d.reused == 1      # "user-3" answered from the warm table
    q = hash_strings_host(["user-3", "user-99"])
    cd, vd = fr_d.lookup(q, max_matches=8)
    cp, vp = fr_p.lookup(q, max_matches=8)
    np.testing.assert_array_equal(np.asarray(vd), np.asarray(vp))
    md = np.asarray(vd)
    np.testing.assert_array_equal(np.asarray(cd["v"])[md],
                                  np.asarray(cp["v"])[md])


# --- shard_map backend (forced-8 when single-device) ------------------------

_SUBPROCESS_QUEUE = r"""
import numpy as np, jax
from repro import IndexedFrame
from repro.core import Schema
from repro.dist import mesh
assert len(jax.devices()) == 8, jax.devices()
SCH = Schema.of("k", k="int64", v="float32")
rng = np.random.default_rng(11)
cols = {"k": rng.integers(0, 100, 400).astype(np.int64),
        "v": rng.random(400).astype(np.float32)}
deltas = [{"k": rng.integers(0, 100, 32).astype(np.int64),
           "v": rng.random(32).astype(np.float32)} for _ in range(3)]
q = np.arange(100, dtype=np.int64)
outs = []
for rt in (mesh.vmap_runtime(), mesh.mesh_runtime(8)):
    f = IndexedFrame.from_columns(cols, SCH, num_shards=8, rows_per_batch=64,
                                  rt=rt).with_queue(lanes=4, lane_rows=32)
    for d in deltas:
        f = f.enqueue(d)
    assert f.pending_rows == 96, f.pending_rows
    f = f.flush()
    assert f.pending_rows == 0
    c, v = f.lookup(q, max_matches=16)
    outs.append((np.asarray(c["v"]), np.asarray(v)))
np.testing.assert_array_equal(outs[0][0], outs[1][0])
np.testing.assert_array_equal(outs[0][1], outs[1][1])
# held flush -> promote under shard_map, donated end to end
mk = lambda: IndexedFrame.from_columns(cols, SCH, num_shards=8,
                                       rows_per_batch=64,
                                       rt=mesh.mesh_runtime(8), reserve=8)
ref = mk().append(list(deltas))
f = mk().with_queue(lanes=4, lane_rows=32)
for d in deltas:
    f = f.enqueue(d)
f = f.flush(donate=True)
ca, va = f.lookup(q, max_matches=16)
cb, vb = ref.lookup(q, max_matches=16)
np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
np.testing.assert_array_equal(np.asarray(ca["v"]), np.asarray(cb["v"]))
assert f.version == ref.version, (f.version, ref.version)
print("QUEUE_PARITY_8DEV_OK")
"""


@pytest.mark.skipif(NDEV < 8, reason="needs 8 devices (ci.sh forced-8 "
                    "pass; the subprocess test covers single-device runs)")
def test_queue_parity_shard_map_in_process():
    exec(compile(_SUBPROCESS_QUEUE, "<queue-parity>", "exec"), {})


@pytest.mark.skipif(NDEV >= 8, reason="in-process test runs on this "
                    "topology")
def test_queue_parity_shard_map_subprocess():
    """Queue parity on the shard_map backend, forced-8 host topology."""
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"   # forced host devices, never a chip
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_QUEUE],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "QUEUE_PARITY_8DEV_OK" in proc.stdout
