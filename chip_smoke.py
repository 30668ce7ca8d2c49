#!/usr/bin/env python3
"""Smoke test of the IndexedFrame serving path on a TPU.

    python3 chip_smoke.py              # one chip: 2^24 rows
    python3 chip_smoke.py --chips 4    # the 4-shard shard_map frame

One chip: build a frame of ``--rows`` (k int64, v float32) rows through
``IndexedFrame.from_columns``, serve a few hundred lookup requests through
``QueryEngine`` (every rung of the bucket ladder, misses, the EMPTY_KEY
pad sentinel) with appends interleaved through the append ring, then run
one indexed ``IndexedFrame.join`` of a 2^16-row probe table.  Every answer
is compared with a plain numpy reference written here (a sort of all rows
by key, newest row first), and every lookup also with the XLA oracle
``ref.fused_lookup_ref`` run on the chip.

``--chips 4``: only the 4-shard ``shard_map`` frame (``--rows`` rows per
shard): routed lookups through ``QueryEngine`` over a supervised frame,
ring flushes (the ``psum`` flush verdict), one seeded shard kill healed
from a checkpoint inside the checkout, and a shuffle join — checked
against the numpy reference, with every frame leaf spread over the four
devices after the build, a flush and the heal.

The data is made from ``--seed``: about a quarter of the keys carry 2-8
versions, so chain walks and ``max_matches`` truncation both happen.
Without a TPU the script fails; it starts no child process.  The last
line of standard output is ``{"ok": true, "device": {...}}``.  Any failed
check exits nonzero before it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CKPT_DIR = os.path.join(ROOT, ".smoke_ckpt")
EMPTY_KEY = np.iinfo(np.int64).min
MAX_MATCHES = 4                  # keys carry up to 8 versions: truncation
PROBE_ROWS = 1 << 16             # the join's probe table
APPEND_ROWS = 64                 # rows per interleaved append
TICKS = 96                       # engine ticks: ~300 lookup requests
# The 4-chip call costs four chips per second: one ladder rung (one
# compile of the routed site) and fewer ticks, still through a flush and
# the heal.
MESH_LADDER, MESH_TICKS = (256,), 24


def log(*parts):
    print(*parts, flush=True)


# -- data and the numpy reference ---------------------------------------------

class Keyspace:
    """Seeded bijection from key ids to distinct int64 keys: id ranges
    never collide, and no id maps to EMPTY_KEY or 0."""

    def __init__(self, rng):
        self.mult = np.uint64(int(rng.integers(1 << 61)) * 2 + 1)

    def __call__(self, ids) -> np.ndarray:
        with np.errstate(over="ignore"):
            x = (np.asarray(ids, np.uint64) + np.uint64(1)) * self.mult
        return x.view(np.int64)


def make_base(rng, keyspace, rows: int):
    """``rows`` rows over ``rows // 2`` keys; a quarter of the keys carry
    2-8 versions (mean 5), the rest one.  Rows come in random order."""
    n_keys = rows // 2
    counts = np.ones(n_keys, np.int64)
    counts[:n_keys // 4] = rng.integers(2, 9, n_keys // 4)
    ids = np.repeat(np.arange(n_keys), counts)
    ids = ids[rng.permutation(ids.size)][:rows]
    if ids.size < rows:                       # top up with single keys
        ids = np.concatenate([ids, n_keys + np.arange(rows - ids.size)])
    keys = keyspace(ids)
    vals = rng.standard_normal(rows).astype(np.float32)
    return keys, vals


class Reference:
    """Newest-first rows per key over base rows plus appended rows, at any
    prefix of the append history — independent of ``repro``."""

    def __init__(self, keys, vals):
        self.base_keys, self.base_vals = keys, vals
        order = np.lexsort((-np.arange(keys.size), keys))  # key, newest 1st
        self.order, self.sorted_keys = order, keys[order]
        self.app_keys: list = []
        self.app_vals: list = []
        self.by_key: dict = {}

    def append(self, keys, vals):
        for k, v in zip(keys.tolist(), vals.tolist()):
            self.by_key.setdefault(k, []).append(len(self.app_keys))
            self.app_keys.append(k)
            self.app_vals.append(v)

    def lookup(self, queries, visible_appends: int, m: int):
        """(k [Q, m], v [Q, m], valid [Q, m]) for the rows visible when
        ``visible_appends`` appended rows had landed."""
        q = np.asarray(queries, np.int64)
        out_k = np.zeros((q.size, m), np.int64)
        out_v = np.zeros((q.size, m), np.float32)
        valid = np.zeros((q.size, m), bool)
        lo = np.searchsorted(self.sorted_keys, q, "left")
        hi = np.searchsorted(self.sorted_keys, q, "right")
        app_v = np.asarray(self.app_vals, np.float32)
        for i, key in enumerate(q.tolist()):
            vs = [app_v[j] for j in reversed(self.by_key.get(key, ()))
                  if j < visible_appends][:m]
            base = self.order[lo[i]:min(hi[i], lo[i] + m - len(vs))]
            vs = np.concatenate([np.asarray(vs, np.float32),
                                 self.base_vals[base]])
            out_v[i, :vs.size] = vs
            out_k[i, :vs.size] = key
            valid[i, :vs.size] = True
        return out_k, out_v, valid


def mismatches(got_k, got_v, got_valid, want) -> int:
    """Rows (queries) whose answer differs from the reference: the valid
    mask, and bit patterns of k and v on valid lanes."""
    want_k, want_v, want_valid = want
    got_valid = np.asarray(got_valid)
    bad = (got_valid != want_valid).any(axis=1)
    got_k = np.where(got_valid, np.asarray(got_k), 0)
    got_v = np.where(got_valid, np.asarray(got_v, np.float32), 0)
    bad |= (got_k != want_k).any(axis=1)
    bad |= (got_v.view(np.int32) != want_v.view(np.int32)).any(axis=1)
    return int(bad.sum())


def query_mix(rng, keyspace, base_keys, new_ids, n):
    """Lookup keys: existing keys, keys appended so far, misses and the
    EMPTY_KEY sentinel."""
    kind = rng.integers(0, 10, n)
    q = base_keys[rng.integers(0, base_keys.size, n)]
    if new_ids:
        q = np.where(kind == 7, keyspace(rng.choice(new_ids, n)), q)
    q = np.where(kind == 8, keyspace((1 << 50) + rng.integers(0, 1 << 30, n)),
                 q)
    return np.where(kind == 9, EMPTY_KEY, q)


# -- the serving loop shared by both paths ------------------------------------

def serve(eng, rng, keyspace, ref, base_keys, *, ticks: int, on_tick=None):
    """Drive ``ticks`` engine ticks: lookup requests whose batches cover
    every ladder rung, and one append every other tick.  Returns the
    lookup requests."""
    rungs = list(eng.ladder)
    new_ids, reqs, next_id = [], [], 1 << 40
    for t in range(ticks):
        if t < len(rungs):            # one batch per rung first
            sizes = [rungs[t] - (rungs[t] // 4 if t else 0)]
        else:
            sizes = rng.integers(1, rungs[-1] // 2, rng.integers(2, 6))
        for s in sizes:
            reqs.append(eng.submit_lookup(
                query_mix(rng, keyspace, base_keys, new_ids, int(s))))
        if t % 2 == 0:
            fresh = list(range(next_id, next_id + APPEND_ROWS // 2))
            next_id += len(fresh)
            new_ids += fresh
            keys = np.concatenate([
                keyspace(fresh),
                base_keys[rng.integers(0, base_keys.size,
                                       APPEND_ROWS - len(fresh))]])
            vals = rng.standard_normal(keys.size).astype(np.float32)
            eng.submit_append({"k": keys, "v": vals})
            ref.append(keys, vals)
        eng.tick()
        if on_tick is not None:
            on_tick(t)
    eng.drain()
    return reqs


def check_requests(eng, ref, reqs, v0: int):
    """Compare every served lookup with the reference at its version."""
    landed = {v0: 0}
    total = 0
    for g in sorted(eng.write_log, key=lambda g: g["version"]):
        total += sum(int(np.asarray(c["k"]).size) for c, _ in g["writes"])
        landed[g["version"]] = total
    bad = hits_new = 0
    for r in reqs:
        cols, valid = r.result
        want = ref.lookup(r.keys, landed[r.version], eng.max_matches)
        bad += mismatches(cols["k"], cols["v"], valid, want)
        hits_new += int(np.isin(r.keys,
                                ref.app_keys[:landed[r.version]]).sum())
    return bad, hits_new


class CompileClock:
    """Seconds spent in XLA/Mosaic compilation, from JAX's own events."""

    def __init__(self, jax):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += duration


def require(ok: bool, what: str):
    if not ok:
        log(f"FAILED: {what}")
        sys.exit(1)


# -- one chip -----------------------------------------------------------------

def run_one_chip(args, jax, clock):
    import jax.numpy as jnp
    from repro import IndexedFrame
    from repro.core import Schema
    from repro.kernels import ops
    from repro.serving.query_engine import QueryEngine

    rng = np.random.default_rng(args.seed)
    keyspace = Keyspace(rng)
    keys, vals = make_base(rng, keyspace, args.rows)
    ref = Reference(keys, vals)
    sch = Schema.of("k", k="int64", v="float32")

    t0, c0 = time.perf_counter(), clock.seconds
    frame = IndexedFrame.from_columns({"k": keys, "v": vals}, sch)
    jax.block_until_ready(frame)
    log(f"build: rows={args.rows} seconds={time.perf_counter() - t0:.3f} "
        f"(compile {clock.seconds - c0:.3f})")

    eng = QueryEngine(frame, max_matches=MAX_MATCHES, flush_deadline_ticks=2)
    del frame                     # the engine owns the live version now
    v0 = int(np.asarray(eng.frame.version))
    t0, c0 = time.perf_counter(), clock.seconds
    reqs = serve(eng, rng, keyspace, ref, keys, ticks=TICKS)
    serve_s = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    bad, hits_new = check_requests(eng, ref, reqs, v0)
    buckets = sorted({r.bucket for r in reqs})
    log(f"serve: requests={len(reqs)} buckets={buckets} "
        f"flushes={eng.stats.flushes} reads_of_appended_keys={hits_new} "
        f"seconds={serve_s:.3f} (compile {compile_s:.3f})")
    log(f"serve: mismatches_vs_numpy={bad}")
    log(f"serve: retraces={eng.retraces} "
        f"expected_traces={eng.expected_traces}")
    require(bad == 0, "served lookups differ from the numpy reference")
    require(buckets == list(eng.ladder), "a ladder rung was never served")
    require(eng.stats.flushes >= 1 and hits_new > 0,
            "no flush landed before a read of the appended keys")
    require(eng.retraces == eng.expected_traces, "a read site retraced")

    # the engine's read site runs the compiled Pallas kernel
    fr = eng.frame
    q = jnp.asarray(reqs[-1].keys)
    site = jax.jit(lambda f, k: f.lookup(k, max_matches=MAX_MATCHES))
    has_kernel = "tpu_custom_call" in site.lower(fr, q).compile().as_text()
    log(f"kernel: lookup site HLO holds tpu_custom_call={has_kernel}")
    require(has_kernel, "the lookup site does not run the Pallas kernel")

    # every served key through the kernel and the on-chip XLA oracle
    allq = jnp.asarray(np.concatenate([r.keys for r in reqs]))
    snap = fr.data.snapshot
    rk, tk = ops.fused_lookup(allq, snap, max_matches=MAX_MATCHES,
                              use_kernel=True)
    ro, to = ops.fused_lookup(allq, snap, max_matches=MAX_MATCHES,
                              use_kernel=False)
    rk, tk, ro, to = (np.asarray(a) for a in (rk, tk, ro, to))
    bad_o = int(((rk != ro).any(axis=1) | (tk != to)).sum())
    log(f"kernel: keys={allq.shape[0]} truncated={int(tk.sum())} "
        f"chains_walked={int((rk[:, 1] >= 0).sum())} "
        f"mismatches_vs_xla_oracle={bad_o}")
    require(bad_o == 0, "the kernel differs from ref.fused_lookup_ref")
    require(tk.sum() > 0, "no lookup was truncated at max_matches")

    # one indexed join of a 2^16-row probe table
    pk = query_mix(rng, keyspace, keys, [], PROBE_ROWS)
    pk[::7] = np.asarray(ref.app_keys)[
        rng.integers(0, len(ref.app_keys), pk[::7].size)]
    probe = {"k": pk, "p": np.arange(PROBE_ROWS, dtype=np.int32)}
    t0, c0 = time.perf_counter(), clock.seconds
    bcols, pcols, valid = fr.join(probe, "k", max_matches=MAX_MATCHES)
    jax.block_until_ready(valid)
    join_s = time.perf_counter() - t0
    want = ref.lookup(pk, len(ref.app_keys), MAX_MATCHES)
    bad_j = mismatches(bcols["k"], bcols["v"], valid, want)
    bad_j += int((np.asarray(pcols["p"])
                  != probe["p"][:, None]).any(axis=1).sum())
    log(f"join: probe_rows={PROBE_ROWS} matches={int(np.asarray(valid).sum())}"
        f" seconds={join_s:.3f} (compile {clock.seconds - c0:.3f}) "
        f"mismatches_vs_numpy={bad_j}")
    require(bad_j == 0, "the join differs from the numpy reference")


# -- four chips: the shard_map frame ------------------------------------------

def spread_over(tree, devices) -> int:
    """Leaves NOT spread over every device of the mesh (a leaf with the
    leading shard axis must hold one shard per device)."""
    import jax
    n, bad = len(devices), 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if leaf.sharding.device_set != set(devices):
            bad += 1
        elif leaf.ndim and leaf.shape[0] == n and any(
                s.data.shape[0] != 1 for s in leaf.addressable_shards):
            bad += 1
    return bad


def run_four_chips(args, jax, clock):
    from repro import IndexedFrame
    from repro.core import Schema
    from repro.dist import mesh
    from repro.dist import runtime as drt
    from repro.dist.resilience import Fault, FaultInjector, RecoveryPolicy
    from repro.serving.query_engine import QueryEngine

    shards = 4
    rt = mesh.mesh_runtime(shards)
    devices = list(rt.mesh.devices.flat)
    rng = np.random.default_rng(args.seed)
    keyspace = Keyspace(rng)
    keys, vals = make_base(rng, keyspace, args.rows * shards)
    ref = Reference(keys, vals)
    sch = Schema.of("k", k="int64", v="float32")

    t0, c0 = time.perf_counter(), clock.seconds
    frame = IndexedFrame.from_columns({"k": keys, "v": vals}, sch,
                                      num_shards=shards, rt=rt)
    frame = frame.with_queue()
    jax.block_until_ready(frame)
    placement = {"build": spread_over(frame, devices)}
    log(f"build: shards={shards} rows_per_shard={args.rows} "
        f"seconds={time.perf_counter() - t0:.3f} "
        f"(compile {clock.seconds - c0:.3f})")

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    dead = int(rng.integers(0, shards))
    mgr = frame.supervised(
        lineage=drt.Lineage(sch, {"k": keys, "v": vals}),
        injector=FaultInjector([Fault("shard_loss", step=MESH_TICKS,
                                      shard=dead)]),
        policy=RecoveryPolicy(checkpoint_every=0, keep_checkpoints=1),
        checkpoint_dir=CKPT_DIR)
    del frame                     # the manager owns the live version now
    eng = QueryEngine(mgr, ladder=MESH_LADDER, max_matches=MAX_MATCHES,
                      op="routed", flush_deadline_ticks=2)
    v0 = int(np.asarray(mgr.frame.version))

    def after_tick(t):
        if "flush" not in placement and eng.stats.flushes:
            placement["flush"] = spread_over(mgr.frame, devices)
        if "heal" not in placement and mgr.stats.recoveries:
            placement["heal"] = spread_over(mgr.frame, devices)

    t0, c0 = time.perf_counter(), clock.seconds
    reqs = serve(eng, rng, keyspace, ref, keys, ticks=MESH_TICKS,
                 on_tick=after_tick)
    serve_s = time.perf_counter() - t0
    bad, hits_new = check_requests(eng, ref, reqs, v0)
    log(f"serve: op=routed requests={len(reqs)} flushes={eng.stats.flushes} "
        f"reads_of_appended_keys={hits_new} killed_shard={dead} "
        f"recoveries={mgr.stats.recoveries} seconds={serve_s:.3f} "
        f"(compile {clock.seconds - c0:.3f})")
    log(f"serve: mismatches_vs_numpy={bad}")
    log(f"serve: retraces={eng.retraces} "
        f"expected_traces={eng.expected_traces}")
    log("placement: leaves_not_spread_over_4_devices " + " ".join(
        f"{k}={v}" for k, v in placement.items()))
    require(bad == 0, "served lookups differ from the numpy reference")
    require(eng.stats.flushes >= 1 and hits_new > 0,
            "no flush landed before a read of the appended keys")
    require(mgr.stats.recoveries == 1 and not mgr.dead,
            "the killed shard did not heal")
    require(eng.retraces == eng.expected_traces, "a read site retraced")
    require(set(placement) == {"build", "flush", "heal"}
            and not any(placement.values()),
            "a frame leaf is not sharded over the four devices")

    pk = query_mix(rng, keyspace, keys, [], PROBE_ROWS)
    pk[::7] = np.asarray(ref.app_keys)[
        rng.integers(0, len(ref.app_keys), pk[::7].size)]
    probe = {"k": pk, "p": np.arange(PROBE_ROWS, dtype=np.int32)}
    t0, c0 = time.perf_counter(), clock.seconds
    bcols, pcols, valid = mgr.frame.join(probe, "k", max_matches=MAX_MATCHES,
                                         op="shuffle")
    jax.block_until_ready(valid)
    join_s = time.perf_counter() - t0
    want = ref.lookup(pk, len(ref.app_keys), MAX_MATCHES)
    bad_j = mismatches(bcols["k"], bcols["v"], valid, want)
    bad_j += int((np.asarray(pcols["p"])
                  != probe["p"][:, None]).any(axis=1).sum())
    log(f"join: op=shuffle probe_rows={PROBE_ROWS} "
        f"matches={int(np.asarray(valid).sum())} seconds={join_s:.3f} "
        f"(compile {clock.seconds - c0:.3f}) mismatches_vs_numpy={bad_j}")
    require(bad_j == 0, "the shuffle join differs from the numpy reference")
    shutil.rmtree(CKPT_DIR, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=1 << 24,
                    help="rows (per shard with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import repro  # noqa: F401  (fails outside a checkout of this repo)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"no TPU: JAX sees {dev[0].platform} devices", file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, JAX "
              f"sees {len(dev)}", file=sys.stderr)
        return 2
    log(f"device: {dev[0].device_kind} x{len(dev)} jax={jax.__version__} "
        f"cache={jax.config.jax_compilation_cache_dir}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args, jax, clock)
    else:
        run_four_chips(args, jax, clock)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in dev[:args.chips])
    log(f"total: seconds={time.perf_counter() - t0:.3f} "
        f"compile_seconds={clock.seconds:.3f} peak_bytes_in_use={peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
