"""A copy of the benchmark at a size the CPU runs in seconds.

``tiny_root(dst)`` copies ``BENCHMARK.json`` and ``bench/`` into ``dst``
with every configuration cut to a few thousand rows and every mix to a
low rate, so that ``run.run(args, root=dst, require_tpu=False)`` drives
a whole run, reference and checks included, on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

TINY_CONFIG = {"snb_knows_sf100": {"rows": 1 << 14, "persons": 184},
               "tpcds_customer_sf300": {"rows": 4096}}
TINY_MIX = {"is3_open": {"rate_per_s": 400},
            "ss_join": {"probe_rows": 1024, "blocks": 4}}


def tiny_root(dst: str) -> str:
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("out", "tests",
                                                  "__pycache__"))
    for name, sizes in TINY_CONFIG.items():
        edit(os.path.join(dst, "bench", "configs", name + ".json"), sizes)
    for name, params in TINY_MIX.items():
        edit(os.path.join(dst, "bench", "mixes", name + ".json"), params)
    return dst


def edit(path: str, values: dict):
    with open(path) as f:
        d = json.load(f)
    d.update(values)
    with open(path, "w") as f:
        json.dump(d, f)


def args(workload: str, seed: int = 2**31 + 11, seconds: float = 1.0,
         trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
