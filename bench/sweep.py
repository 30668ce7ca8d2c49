#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one set-up, then one
window per offered rate, lowest first.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s>
        --rates <r> [<r> ...]

One JSON line per rate: the offered and the completed requests per
second, lookup latency p50/p99, the median latency of the window's first
and last quarter (a queue that grows all through the window shows as a
last quarter far above the first), visibility p99 and how late the
generator ran.  The cell's rate in its mix file is set once from such a
sweep (about 0.8 of the knee); the benchmark's own runs never search.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = run.Bench(run.ROOT)
    cell = bench.cell(args.workload)
    cfg, cfgmod = bench.config(cell["config"])
    mix, loopmod = bench.mix(cell["traffic"])
    try:
        run.start_jax(True, int(cell["chips"]))
    except run.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    data = cfgmod.make(cfg, args.seed)
    loop = loopmod.Loop(cfg, cfgmod, data,
                        {**mix, "rate_per_s": args.rates[0]}, args.seed,
                        lambda name: contextlib.nullcontext())
    loop.setup(args.seconds)
    for rate in sorted(args.rates):
        loop.mix = {**mix, "rate_per_s": rate}
        loop._schedule(args.seconds)
        raw = loop.window(args.seconds)
        lat = raw["lookup_latency_s"] * 1e3
        q = max(1, lat.size // 4)
        print(json.dumps({
            "rate_per_s": rate,
            "completed_per_s": raw["attempted"] / raw["window_s"],
            "window_s": raw["window_s"],
            "lookup_p50_ms": float(np.percentile(lat, 50)),
            "lookup_p99_ms": float(np.percentile(lat, 99)),
            "first_quarter_p50_ms": float(np.median(lat[:q])),
            "last_quarter_p50_ms": float(np.median(lat[-q:])),
            "visible_p95_ms": float(np.percentile(
                raw["visible_latency_s"] * 1e3, 95)),
            "late_p99_ms": float(np.percentile(raw["late_s"], 99) * 1e3),
            "keys_per_batch": raw["counters"]["batched_keys"]
            / max(1, raw["counters"]["batches"]),
            "flushes": raw["counters"]["flushes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
