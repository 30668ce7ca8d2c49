"""A new configuration, mix, cell and metric are new files and new entries
in ``BENCHMARK.json``: the harness finds them by name, and no file that
was there changes."""

from __future__ import annotations

import hashlib
import json
import os
import shutil

import run
import tiny

NEW_METRIC = '''"""Median lookup latency, ms (a metric added as a new file)."""

import numpy as np


def read(ctx):
    lat = ctx["raw"].get("lookup_latency_s", ())
    return float(np.median(lat) * 1e3) if len(lat) else None
'''

NEW_COUNTER = '''"""Flushes in the window (a per-layer metric, a new file)."""


def read(ctx):
    return ctx["raw"]["counters"].get("flushes") or None
'''


def digests(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_config_mix_and_metrics_are_new_files(tmp_path):
    root = tiny.tiny_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = digests(root)
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    del before["BENCHMARK.json"]            # entries are added to it

    # a configuration: another deployment size of the same table
    src = os.path.join(bench, "configs", "snb_knows_sf100")
    dst = os.path.join(bench, "configs", "snb_knows_small")
    shutil.copy(src + ".py", dst + ".py")
    tiny.edit(shutil.copy(src + ".json", dst + ".json"),
              {"name": "snb_knows_small", "rows": 1 << 13, "persons": 92})
    # a mix: the same loop at another rate and write share
    with open(os.path.join(bench, "mixes", "is3_open.json")) as f:
        mix = json.load(f)
    mix.update(rate_per_s=200, write_share=0.5)
    with open(os.path.join(bench, "mixes", "ins8_heavy.json"), "w") as f:
        json.dump(mix, f)
    # two metrics, each its own reader
    with open(os.path.join(bench, "metrics", "lookup_p50_ms.py"), "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(bench, "metrics", "flushes.small.py"), "w") as f:
        f.write(NEW_COUNTER)

    cell = "snb_knows_small.ins8_heavy"
    spec["configs"].append({"name": "snb_knows_small", "source": "x",
                            "file": "bench/configs/snb_knows_small.json",
                            "reduced": ["rows"], "why": "x"})
    spec["workloads"].append({"name": cell, "config": "snb_knows_small",
                              "traffic": "ins8_heavy", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].append({"name": "lookup_p50_ms", "unit": "ms",
                               "better": "lower", "bound": 0.05,
                               "source": "host_clock", "workloads": [cell]})
    for m in spec["end_to_end"]:
        if m["name"] in ("lookup_p99_ms", "visible_p95_ms"):
            m["workloads"].append(cell)
    spec["per_layer"].append({"name": "flushes.small", "unit": "flushes",
                              "better": "lower", "source": "program_counter",
                              "layer": "write path",
                              "moves": "visible_p95_ms", "workloads": [cell]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    result = run.run(tiny.args(cell), root=root, require_tpu=False)
    assert result["correct"]
    want = {"lookup_p50_ms", "lookup_p99_ms", "visible_p95_ms", "setup_s"}
    if result["device"]["memory_peak_bytes"]:     # the CPU reports none
        want.add("peak_bytes_per_row")
    assert set(result["metrics"]) == want
    traced = run.run(tiny.args(cell, trace=1), root=root, require_tpu=False)
    assert traced["correct"] and traced["metrics"]["flushes.small"][
        "value"] > 0
    after = digests(root)
    assert {k: after[k] for k in before} == before
