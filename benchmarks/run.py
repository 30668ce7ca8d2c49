"""Benchmark harness entry point: one module per paper table/figure.

  python -m benchmarks.run [--full] [--only NAME] [--out results.json]

Default is quick mode (CI-scale datasets); --full uses the larger sizes.
See DESIGN.md §8 for the module ↔ paper figure mapping.
"""

import argparse
import importlib
import json
import sys
import time

# Modules import lazily so one broken dependency cannot take down the whole
# harness.  lookup_path, fault_tolerance, and scalability additionally write
# the committed artifacts BENCH_lookup.json / BENCH_dist.json /
# BENCH_scale.json at the repo root (scalability's mesh sweep runs in
# process over 4 of jax.devices(); on the CPU the caller forces them with
# XLA_FLAGS=--xla_force_host_platform_device_count=4); append_read_latency and
# write_throughput share BENCH_append.json (Fig 9 + Fig 10, the arena
# write path before/after — DESIGN.md §4).
MODULES = {
    "lookup_path": "Fig 1 / §III-C hot path (-> BENCH_lookup.json)",
    "join_scaling": "Fig 7 + Table III",
    "operators": "Fig 8",
    "append_read_latency": "Fig 9 (-> BENCH_append.json)",
    "write_throughput": "Fig 10 (-> BENCH_append.json)",
    "ingest": "ISSUE 7 streaming ingest: ring enqueue/flush vs facade "
              "appends, measured syncs (-> BENCH_ingest.json)",
    "memory_overhead": "Fig 11 (logical vs reserved)",
    "fault_tolerance": "Fig 12 chaos sweep: fault x write rate through "
                       "the supervised frame (-> BENCH_dist.json)",
    "serve": "ISSUE 8 continuous-batching query engine: QPS x write-rate "
             "grid, p50/p99 SLOs, both topologies (-> BENCH_serve.json)",
    "batch_size_sweep": "Fig 5",
    "scalability": "Fig 6 (mesh sweep + ISSUE 9 Zipf skew sweep "
                   "-> BENCH_scale.json)",
    "tpcds_join": "Fig 14",
    "snb_queries": "Fig 13",
    "flights_queries": "Fig 15",
}


def _load(name: str):
    return importlib.import_module(f"benchmarks.{name}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", choices=list(MODULES))
    ap.add_argument("--out", default="benchmarks/results.json")
    args = ap.parse_args(argv)

    todo = [args.only] if args.only else list(MODULES)
    results, failures = [], 0
    for name in todo:
        print(f"\n== {name} ==", flush=True)
        t0 = time.time()
        try:
            results.append(_load(name).run(quick=not args.full))
            print(f"   done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # report and continue
            failures += 1
            print(f"   FAILED: {type(e).__name__}: {e}", flush=True)
            results.append({"benchmark": name, "error": str(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"\nwrote {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
