#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py      # on a TPU

Builds a 2^14-row SNB table and a 4096-row customer table, then, inside
the ``bench.window`` span under the profiler: a few engine ticks of 1-key IS3
lookups with one flush, and two 1024-row joins.  Writes
``data/recorded.xplane.pb`` and ``data/recorded.json``, which holds what
the recording did (lookup kernel launches, flush runs) for the test to
find again in the trace.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main() -> int:
    jax, devices = run.start_jax(True, 1)
    import numpy as np
    from repro import IndexedFrame
    from repro.core import Schema
    from repro.serving.query_engine import QueryEngine

    bench = run.Bench(run.ROOT)
    snb, snbmod = bench.config("snb_knows_sf100")
    snb = {**snb, "rows": 1 << 14, "persons": 184}
    data = snbmod.make(snb, 5)
    frame = IndexedFrame.from_columns(
        data["cols"], Schema.of("src", **dict(snb["schema"])))
    eng = QueryEngine(frame, ladder=(8,), max_matches=20,
                      flush_deadline_ticks=2)
    cust, custmod = bench.config("tpcds_customer_sf300")
    cust = {**cust, "rows": 4096}
    cdata = custmod.make(cust, 5)
    cframe = IndexedFrame.from_columns(
        cdata["cols"], Schema.of(cust["key"], **dict(cust["schema"])))
    block = custmod.make_probe_blocks(cust, cdata,
                                      np.random.default_rng(5), 1, 1024)[0]
    rng = np.random.default_rng(6)
    keys = data["read_keys"]
    writes = snbmod.make_writes(snb, data, rng, 2)

    def ticks(with_writes: bool):
        for t in range(4):
            for _ in range(8):
                eng.submit_lookup(keys[rng.integers(0, keys.size, 1)])
            if with_writes and t < len(writes):
                eng.submit_append(writes[t])
            eng.tick()

    def joins():
        for _ in range(2):
            jax.block_until_ready(cframe.join(block, "ss_customer_sk",
                                              max_matches=1))

    ticks(False)                       # warm every shape first
    eng.submit_append(snbmod.make_writes(snb, data, rng, 1)[0])
    eng.drain()
    joins()
    flushes0 = eng.stats.flushes
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out)
    span = run.span_factory(True, jax)
    with span("window"):
        with span("tick"):
            ticks(True)
            eng.drain()
        with span("join_call"):
            joins()
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    data_dir = os.path.join(HERE, "data")
    os.makedirs(data_dir, exist_ok=True)
    shutil.copy(pb[0], os.path.join(data_dir, "recorded.xplane.pb"))
    shutil.rmtree(out)
    made = {"lookup_kernel_launches": 4 + 2,
            "flush_runs": eng.stats.flushes - flushes0,
            "device_kind": devices[0].device_kind}
    with open(os.path.join(data_dir, "recorded.json"), "w") as f:
        json.dump(made, f, indent=1)
    print(json.dumps(made))
    return 0


if __name__ == "__main__":
    sys.exit(main())
