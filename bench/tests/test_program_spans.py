"""The per-layer metrics that read the program's own spans and counters
(``repro.obs``, ``bench/metrics/_program.py``): a traced CPU rehearsal of
each cell at a tiny size prints every one of them as a positive number.
The device-trace metrics need a device plane, which the CPU has not."""

from __future__ import annotations

import json
import os

import pytest

import run
import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
PROGRAM = ("program_span", "program_counter")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_prints_the_program_metrics(root, cell):
    from repro import obs
    want = {m["name"] for m in SPEC["per_layer"]
            if m["source"] in PROGRAM and cell in m["workloads"]}
    assert want
    obs.reset()
    result = run.run(tiny.args(cell, trace=1), root=root, require_tpu=False)
    assert result["correct"] is True
    got = result["metrics"]
    assert want <= set(got)
    for name in want:
        assert got[name]["value"] > 0, name
    if cell.endswith("is3_open"):
        assert got["engine_copies_per_request.is3"]["value"] == 4.0
