"""Fig 6: scalability of the distributed Indexed DataFrame.

Three sweeps:

* horizontal / vertical (vmap lanes, as before): fixed data over more
  shards; fixed shards over more data.
* **mesh sweep** (the Fig-6 shape): the shard_map backend over 1/2/4 of
  ``jax.devices()``, timing the broadcast point lookup against
  ``lookup_routed`` at large Q.
  Broadcast probes every query on every device (s×Q lanes); routing
  probes each query once on its owner plus two all-to-alls (~2Q lanes at
  the 2x-overprovisioned capacity) — the s× redundancy the ROADMAP
  flags, measured.

* **skew sweep** (ISSUE 9): Zipf-distributed data and queries at
  s in {0.5, 1.0, 1.5}, pure routing at the standard 2x exchange
  capacity vs the hot-key-replicated hybrid (DESIGN.md §15) — on the
  vmap-4 backend and on the 4-device shard_map mesh.  The headline: at
  s=1.5 routing drops and needs capacity-doubling retries to deliver;
  the hybrid stays flat at zero drops, bit-identical to the
  full-capacity routed oracle.

Everything runs in this process.  The mesh sweeps need 4 devices: on
the CPU the caller provides them before JAX starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``, as
``scripts/ci.sh`` does).  Results land in ``BENCH_scale.json`` at the
repo root (the committed artifact) as well as the harness report.
"""

import json
import os
import sys

import jax
import numpy as np

from repro.core import Schema
from repro.core.planner import Planner
from benchmarks.common import Report, powerlaw_keys, timeit

SCH = Schema.of("k", k="int64", v="float32")
MESH_DEVICES = (1, 2, 4)               # up to the 4 chips of one v5e host
ZIPF_S = (0.5, 1.0, 1.5)


def _zipf_keys(rng, n, uniques, s):
    ranks = np.arange(1, uniques + 1, dtype=np.float64)
    p = ranks ** -float(s)
    p /= p.sum()
    return rng.choice(uniques, size=n, p=p).astype(np.int64)


def _skew_rows(num_shards, rt, quick, topology):
    """The skew sweep (ISSUE 9 headline): Zipf-distributed data AND
    queries at s in ZIPF_S, pure routing at the standard 2x-provisioned
    exchange capacity vs the hot-key-replicated hybrid.

    Per cell: one-shot routed latency + drops at the standard capacity,
    the retry-until-delivered blowup (the RecoveryManager's doubling
    contract replayed by hand — total wall clock a pressured caller
    actually waits), hybrid latency + drops at the SAME capacity, hot
    coverage, and a bitwise parity check against the full-capacity
    routed oracle.  At s=1.5 most queries hit one owner: routing
    collapses (drops at any fixed capacity, delivered only after
    doublings) while the hybrid stays flat — its hot lanes never enter
    the exchange.
    """
    from repro import dist

    n = 40_000 if quick else 200_000
    total_q = 8_192 if quick else 32_768
    uniques = 4_096
    max_matches = 8
    per = -(-total_q // num_shards)
    cap = max(64, -(-2 * per // num_shards))    # standard 2x provisioning
    rows = []
    for s_exp in ZIPF_S:
        rng = np.random.default_rng(17 + int(s_exp * 10))
        data_k = _zipf_keys(rng, n, uniques, s_exp)
        q = _zipf_keys(rng, total_q, uniques, s_exp)
        base = {"k": np.arange(4, dtype=np.int64),
                "v": np.zeros(4, np.float32)}
        dt = dist.create_distributed(base, SCH, num_shards,
                                     rows_per_batch=2048,
                                     reserve=n + 4096, track_hot=64, rt=rt)
        dt = dist.append_distributed(
            dt, {"k": data_k, "v": rng.random(n).astype(np.float32)},
            rt=rt)
        dt = dist.attach_replica(dt, capacity=64, max_matches=max_matches)
        dt = dist.refresh_replica(dt, rt=rt)

        jr = jax.jit(lambda t_, p_, _rt=rt, _c=cap:
                     dist.lookup_routed_report(
                         t_, p_, max_matches=max_matches, capacity=_c,
                         rt=_rt))
        jh = jax.jit(lambda t_, p_, _rt=rt, _c=cap:
                     dist.lookup_hybrid_report(
                         t_, p_, max_matches=max_matches, capacity=_c,
                         rt=_rt))
        tr = timeit(jr, dt, q, reps=5)["median_s"]
        th = timeit(jh, dt, q, reps=5)["median_s"]
        routed_drops = int(np.asarray(jr(dt, q)[3]).sum())
        hybrid_drops = int(np.asarray(jh(dt, q)[3]).sum())

        # retry-until-delivered: double capacity per attempt until the
        # exchange stops dropping (the resilience layer's contract)
        deliver_ms, retries, c = 0.0, 0, cap
        while True:
            ja = jax.jit(lambda t_, p_, _rt=rt, _c=c:
                         dist.lookup_routed_report(
                             t_, p_, max_matches=max_matches, capacity=_c,
                             rt=_rt))
            deliver_ms += timeit(ja, dt, q, reps=3)["median_s"] * 1e3
            if int(np.asarray(ja(dt, q)[3]).sum()) == 0 or retries >= 8:
                break
            retries += 1
            c *= 2

        ch, vh = dist.lookup_hybrid_flat(dt, q, max_matches=max_matches,
                                         rt=rt)
        cr, vr = dist.lookup_routed_flat(dt, q, max_matches=max_matches,
                                         rt=rt)
        parity = bool(np.array_equal(np.asarray(vh), np.asarray(vr))
                      and all(np.array_equal(np.asarray(ch[k]),
                                             np.asarray(cr[k]))
                              for k in ch))
        rows.append({"label": f"skew {topology} s={s_exp}",
                     "topology": topology, "zipf_s": s_exp,
                     "num_shards": num_shards, "total_queries": total_q,
                     "capacity": cap,
                     "routed_ms": tr * 1e3, "routed_dropped": routed_drops,
                     "routed_delivered_ms": deliver_ms,
                     "routed_retries": retries,
                     "hybrid_ms": th * 1e3, "hybrid_dropped": hybrid_drops,
                     "hot_fraction": dist.hot_fraction(dt, q),
                     "parity_ok": parity})
    return rows


def _vmap_sweeps(rep, rng, n):
    from repro.dist import create_distributed, indexed_join_bcast

    sch = SCH
    cols = {"k": powerlaw_keys(rng, n, n // 8),
            "v": rng.random(n).astype(np.float32)}
    probe = rng.choice(cols["k"], 256).astype(np.int64)
    jfn = jax.jit(lambda dt, p: indexed_join_bcast(dt, {"pk": p}, "pk", 16))

    # horizontal: fixed data, more shards (vmap lanes on CPU)
    base = None
    for shards in (1, 2, 4, 8):
        dt = create_distributed(cols, sch, shards, rows_per_batch=2048)
        t = timeit(jfn, dt, probe, reps=3)["median_s"]
        base = base or t
        rep.add(f"horizontal shards={shards}", ms=t * 1e3,
                vs_1shard=t / base)

    # vertical: fixed shards, growing data
    for mult in (1, 2, 4):
        nn = n * mult
        cc = {"k": powerlaw_keys(rng, nn, nn // 8),
              "v": rng.random(nn).astype(np.float32)}
        dt = create_distributed(cc, sch, 4, rows_per_batch=2048)
        t = timeit(jfn, dt, probe, reps=3)["median_s"]
        rep.add(f"vertical n={nn}", ms=t * 1e3)


def _mesh_sweep(rep, quick: bool):
    """shard_map backend over 1/2/4 of ``jax.devices()``: broadcast vs
    routed point lookups per device count, then the skew sweep on the
    widest mesh.  Returns ``(mesh_rows, skew_rows)``."""
    from repro import dist
    from repro.dist import mesh

    sch = SCH
    rng = np.random.default_rng(7)
    n = 60_000 if quick else 400_000
    total_q = 131_072 if quick else 262_144
    max_matches = 8
    cols = {"k": powerlaw_keys(rng, n, n // 8),
            "v": rng.random(n).astype(np.float32)}
    # point-lookup workload: the key universe queried uniformly (each
    # distinct entity equally likely) — per-(src,dest) exchange lanes stay
    # near their expected load, so the 2x capacity never drops and the
    # broadcast/routed comparison is exact-vs-exact
    uniq = np.unique(cols["k"])
    q_flat = rng.choice(uniq, total_q).astype(np.int64)

    rows = []
    for d in MESH_DEVICES:
        rt = mesh.mesh_runtime(d)
        dt = dist.create_distributed(cols, sch, d, rows_per_batch=2048,
                                     rt=rt)
        per = total_q // d
        q_sharded = q_flat[:per * d].reshape(d, per)
        # 2x-overprovisioned exchange lanes: expected per-(src,dest) load
        # is per/d; drops are counted and reported (retry contract)
        cap = max(64, -(-2 * per // d))

        jb = jax.jit(lambda t_, p_, _rt=rt: dist.lookup(
            t_, p_, max_matches=max_matches, rt=_rt))
        jr = jax.jit(lambda t_, p_, _rt=rt, _c=cap: dist.lookup_routed(
            t_, p_, max_matches=max_matches, capacity=_c, rt=_rt))

        tb = timeit(jb, dt, q_flat, reps=5)["median_s"]
        tr = timeit(jr, dt, q_sharded, reps=5)["median_s"]
        dropped = int(np.asarray(jr(dt, q_sharded)[3]).sum())
        phys = Planner().physical_lookup(dt, total_q)
        rows.append({"label": f"mesh devices={d}",
                     "devices": d, "total_queries": total_q,
                     "bcast_ms": tb * 1e3, "routed_ms": tr * 1e3,
                     "routed_speedup": tb / tr,
                     "routed_capacity": cap, "routed_dropped": dropped,
                     "planner": ("routed" if phys.kind == "RoutedLookup"
                                 else "bcast"),
                     "planner_rule": phys.reason})
    for r in rows:
        rep.add(r["label"], bcast_ms=r["bcast_ms"],
                routed_ms=r["routed_ms"],
                routed_speedup=r["routed_speedup"],
                routed_dropped=r["routed_dropped"])
    skew_rows = _skew_rows(max(MESH_DEVICES),
                           mesh.mesh_runtime(max(MESH_DEVICES)), quick,
                           f"shard_map-{max(MESH_DEVICES)}")
    return rows, skew_rows


def run(quick: bool = True):
    rng = np.random.default_rng(7)
    n = 30_000 if quick else 300_000
    rep = Report("scalability")
    _vmap_sweeps(rep, rng, n)
    skew_rows = _skew_rows(4, None, quick, "vmap-4")
    for r in skew_rows:
        rep.add(r["label"], routed_ms=r["routed_ms"],
                routed_dropped=r["routed_dropped"],
                routed_delivered_ms=r["routed_delivered_ms"],
                hybrid_ms=r["hybrid_ms"],
                hybrid_dropped=r["hybrid_dropped"],
                hot_fraction=r["hot_fraction"], parity_ok=r["parity_ok"])
    mesh_rows, skew_mesh = _mesh_sweep(rep, quick)
    for r in skew_mesh:
        rep.add(r["label"], routed_ms=r["routed_ms"],
                routed_dropped=r["routed_dropped"],
                routed_delivered_ms=r["routed_delivered_ms"],
                hybrid_ms=r["hybrid_ms"],
                hybrid_dropped=r["hybrid_dropped"],
                hot_fraction=r["hot_fraction"], parity_ok=r["parity_ok"])

    out_path = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                            "BENCH_scale.json"))
    with open(out_path, "w") as f:
        json.dump({"benchmark": "scalability", "quick": quick,
                   "backend": jax.default_backend(),
                   "mesh_sweep": mesh_rows,
                   "skew_sweep": skew_rows + skew_mesh,
                   "rows": rep.to_dict()["rows"]}, f, indent=2)
    return rep.to_dict()


if __name__ == "__main__":
    run(quick="--full" not in sys.argv)
