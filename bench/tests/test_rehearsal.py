"""A CPU rehearsal of every cell at a tiny size: a whole run, reference and
checks included, prints a result line that matches the contract."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import run
import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]
TOP_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))


def e2e_names(cell: str) -> set:
    return {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_prints_a_contract_line(root, cell, capsys):
    result = run.run(tiny.args(cell), root=root, require_tpu=False)
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == TOP_KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = e2e_names(cell)
    if not line["device"]["memory_peak_bytes"]:     # the CPU reports none
        want.discard("peak_bytes_per_row")
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert [e for e in err if e.startswith("check: ")] == \
        err[-len(line["checks"]):]


def test_without_a_tpu_it_exits_nonzero_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    """A directory with only ``BENCHMARK.json`` and ``bench/``: the
    program under test is missing."""
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
