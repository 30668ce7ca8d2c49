"""ISSUE 8: the QPS × write-rate serving grid — "millions of users",
measured.

An open-loop driver (arrivals on a fixed schedule, so queueing delay is
charged to latency — the coordinated-omission-safe way to measure a
server) pushes mixed lookup traffic through the continuous-batching
``QueryEngine`` while a writer stream stages deltas into the append
ring.  Per grid cell:

* p50/p99/mean request latency (scheduled arrival -> answer ready) and
  achieved read throughput;
* write-visibility lag (submit -> flush made it readable);
* MEASURED host syncs per tick (``common.SyncCounter``), trace counts,
  flushes, pad overhead.

Cells run on the vmap emulation backend and on the REAL shard_map
backend over 4 of ``jax.devices()`` (on the CPU the caller provides them:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``), all in this
process, plus one supervised cell where a seeded shard kill lands
mid-run and the engine serves through the heal.  Every cell's answers
are verified bit-identical to an unbatched MVCC twin replaying the
engine's ``write_log``
(``replay_unbatched``); the committed summary asserts
``zero_retraces_after_warmup`` and ``batched_equals_unbatched`` under
BOTH topologies.

Results -> ``BENCH_serve.json`` at the repo root.
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

from repro import IndexedFrame
from repro.core import Schema
from repro.dist import mesh
from repro.serving.query_engine import (EngineStats, QueryEngine,
                                        replay_unbatched)
from benchmarks.common import Report, SyncCounter

SCH = Schema.of("k", k="int64", v="float32")
ARTIFACT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_serve.json")

N_ROWS = 4096
LADDER = (8, 16, 32)
SIZES = (1, 4, 8, 9, 16, 32)          # request sizes, boundary-heavy
MESH_SHARDS = 4                       # the shard_map grid: one v5e host


def _build(num_shards, rt, rng):
    cols = {"k": np.arange(N_ROWS, dtype=np.int64),
            "v": rng.standard_normal(N_ROWS).astype(np.float32)}
    mk = lambda: IndexedFrame.from_columns(
        cols, SCH, num_shards=num_shards, rows_per_batch=512,
        reserve=4 * N_ROWS, rt=rt)
    return mk(), mk(), cols


def _drive(eng, rng, *, qps, write_rate, requests):
    """Open-loop mixed traffic: reads arrive every 1/qps seconds (each a
    multi-key request), every ``1/write_rate``-th arrival is a writer
    delta instead.  Ticks run continuously between arrivals."""
    interval = 1.0 / qps
    reqs, wi = [], 0
    t0 = time.perf_counter()
    for i in range(requests):
        due = t0 + i * interval
        while time.perf_counter() < due:
            if eng.has_work:
                eng.tick()
        if write_rate > 0 and (i + 1) % max(1, round(1 / write_rate)) == 0:
            eng.submit_append(
                {"k": np.asarray([N_ROWS + wi], np.int64),
                 "v": np.asarray([float(wi)], np.float32)},
                stream_id=99, t_submit=due)
            wi += 1
        else:
            size = SIZES[int(rng.integers(len(SIZES)))]
            reqs.append(eng.submit_lookup(
                rng.integers(-5, N_ROWS + 64, size).astype(np.int64),
                stream_id=i % 4, t_submit=due))
        eng.tick()
    eng.drain()
    elapsed = time.perf_counter() - t0
    return reqs, elapsed


def _cell(eng, site_cache, backend, num_shards, *, qps, write_rate,
          requests, seed):
    """One grid cell on a SHARED warmed engine: the frame at cell start
    (an MVCC parent — it stays queryable as the engine appends past it)
    seeds the unbatched replay twin, so jitted sites carry across cells
    and compile time never pollutes a cell's p99."""
    rng = np.random.default_rng(seed)
    frame0 = dataclasses.replace(eng.frame, queue=None)
    eng.stats = EngineStats()
    eng.write_log = []
    with SyncCounter() as sc:
        reqs, elapsed = _drive(eng, rng, qps=qps, write_rate=write_rate,
                               requests=requests)
    summary = eng.latency_summary()
    mismatches = replay_unbatched(frame0, reqs, eng.write_log,
                                  site_cache=site_cache)
    return {
        "backend": backend, "shards": num_shards,
        "offered_qps": qps, "write_rate": write_rate,
        "requests": len(reqs), "writes": eng.stats.writes,
        "achieved_qps": len(reqs) / elapsed if elapsed else 0.0,
        "read_p50_ms": summary["read"].get("p50_ms"),
        "read_p99_ms": summary["read"].get("p99_ms"),
        "read_mean_ms": summary["read"].get("mean_ms"),
        "write_visibility_p99_ms":
            summary["write_visibility"].get("p99_ms"),
        "keys_per_s": eng.stats.batched_keys / elapsed if elapsed else 0.0,
        "mean_batch_keys": summary["mean_batch_keys"],
        "pad_fraction": summary["pad_fraction"],
        "syncs_per_tick": sc.syncs / max(1, eng.stats.ticks),
        "ticks": eng.stats.ticks, "flushes": eng.stats.flushes,
        "retraces": eng.retraces,
        "expected_traces": eng.expected_traces,
        "zero_retraces_after_warmup": eng.zero_retraces_after_warmup,
        "batched_equals_unbatched": mismatches == 0,
        "mismatches": mismatches,
    }


def _supervised_cell(num_shards, rt, *, requests, seed):
    """One chaos cell: a seeded shard kill lands mid-run; the engine
    keeps serving through the automatic heal."""
    import tempfile
    from repro.dist.resilience import (Fault, FaultInjector,
                                       RecoveryPolicy)
    from repro.dist.runtime import Lineage
    rng = np.random.default_rng(seed)
    cols = {"k": np.arange(N_ROWS, dtype=np.int64),
            "v": rng.standard_normal(N_ROWS).astype(np.float32)}
    mk = lambda: IndexedFrame.from_columns(
        cols, SCH, num_shards=num_shards, rows_per_batch=512,
        reserve=4 * N_ROWS, rt=rt)
    with tempfile.TemporaryDirectory() as ckpt:
        mgr = mk().supervised(
            lineage=Lineage(SCH, cols, rows_per_batch=512),
            injector=FaultInjector([Fault("shard_loss", step=12,
                                          shard=num_shards - 1)],
                                   seed=seed),
            policy=RecoveryPolicy(checkpoint_every=3),
            checkpoint_dir=ckpt)
        eng = QueryEngine(mgr, ladder=LADDER, max_matches=4,
                          flush_deadline_ticks=2)
        # warmup mirrors _grid: compile every rung + the write path
        # before the measured window, then replay from the warmed frame
        for b in LADDER:
            eng.submit_lookup(rng.integers(0, N_ROWS, b).astype(np.int64))
            eng.tick()
        for wi in range(2):   # two cycles: see the sharding note in _grid
            eng.submit_append({"k": np.asarray([2 * N_ROWS + wi], np.int64),
                               "v": np.asarray([0.0], np.float32)})
            eng.drain()
        twin = dataclasses.replace(eng.frame, queue=None)
        eng.stats = EngineStats()
        eng.write_log = []
        reqs, elapsed = _drive(eng, rng, qps=100, write_rate=0.2,
                               requests=requests)
        summary = eng.latency_summary()
        mismatches = replay_unbatched(twin, reqs, eng.write_log)
        return {
            "backend": "vmap+supervised", "shards": num_shards,
            "offered_qps": 100, "write_rate": 0.2,
            "requests": len(reqs),
            "achieved_qps": len(reqs) / elapsed if elapsed else 0.0,
            "read_p50_ms": summary["read"].get("p50_ms"),
            "read_p99_ms": summary["read"].get("p99_ms"),
            "recoveries": mgr.stats.recoveries,
            "dead_shards": sorted(mgr.dead),
            "flushes": eng.stats.flushes,
            "batched_equals_unbatched": mismatches == 0,
            "mismatches": mismatches,
            "served_through_heal": (mgr.stats.recoveries == 1
                                    and not mgr.dead),
        }


def _grid(backend, num_shards, rt, *, quick: bool, seed0: int = 31):
    qps_axis = (100, 400) if quick else (50, 200, 800)
    wr_axis = (0.0, 0.2) if quick else (0.0, 0.1, 0.3)
    requests = 48 if quick else 192
    rng = np.random.default_rng(seed0)
    _, owned, _ = _build(num_shards, rt, rng)
    eng = QueryEngine(owned, ladder=LADDER, max_matches=4,
                      flush_deadline_ticks=2)
    for b in LADDER:                      # warm every rung once per backend
        eng.submit_lookup(rng.integers(0, N_ROWS, b).astype(np.int64))
        eng.tick()
    # Warm the write path TWICE: the first enqueue/flush cycle compiles
    # against fresh uncommitted host arrays, and XLA re-lowers both
    # executables once more when the ring comes back device-committed
    # (NamedSharding) from that first flush.  Cycle two pins the
    # steady-state layout so no measured cell pays the ~1.3s re-lower.
    for wi in range(2):
        eng.submit_append({"k": np.asarray([N_ROWS + wi], np.int64),
                           "v": np.asarray([0.0], np.float32)})
        eng.drain()
    site_cache = {}                       # replay oracle compiles, shared
    rows = []
    for qi, qps in enumerate(qps_axis):
        for wi, wr in enumerate(wr_axis):
            rows.append(_cell(eng, site_cache, backend, num_shards,
                              qps=qps, write_rate=wr, requests=requests,
                              seed=seed0 + 10 * qi + wi))
    return rows


def run(quick: bool = True):
    rng_label = "quick" if quick else "full"
    rep = Report("serve")
    rows = _grid("vmap", 4, mesh.vmap_runtime(), quick=quick)
    rows += _grid("shard_map", MESH_SHARDS, mesh.mesh_runtime(MESH_SHARDS),
                  quick=quick, seed0=57)
    rows.append(_supervised_cell(4, mesh.vmap_runtime(),
                                 requests=48 if quick else 192, seed=91))
    for r in rows:
        rep.add(f"{r['backend']} qps={r['offered_qps']} "
                f"wr={r['write_rate']}",
                p50_ms=r.get("read_p50_ms"), p99_ms=r.get("read_p99_ms"),
                achieved_qps=r.get("achieved_qps"))

    plain = [r for r in rows if "supervised" not in r["backend"]]
    summary = {
        "zero_retraces_after_warmup":
            all(r["zero_retraces_after_warmup"] for r in plain),
        "batched_equals_unbatched":
            all(r["batched_equals_unbatched"] for r in rows),
        "backends": sorted({r["backend"] for r in rows}),
        "served_through_heal":
            all(r.get("served_through_heal", True) for r in rows),
        "max_syncs_per_tick":
            max(r.get("syncs_per_tick", 0.0) for r in rows),
    }
    doc = {"benchmark": "serve", "mode": rng_label,
           "ladder": list(LADDER), "request_sizes": list(SIZES),
           "grid": rows, "summary": summary}
    with open(ARTIFACT, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {ARTIFACT}")
    for k, v in summary.items():
        print(f"  {k}: {v}")
    if not (summary["zero_retraces_after_warmup"]
            and summary["batched_equals_unbatched"]):
        raise RuntimeError(f"serving acceptance violated: {summary}")
    return rep.to_dict()


if __name__ == "__main__":
    run(quick="--full" not in sys.argv)
