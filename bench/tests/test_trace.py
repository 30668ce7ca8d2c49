"""The reduction from a profiler trace to busy/idle time, kernel time by
name and the roofline share, on a small trace recorded on a TPU v5e and
committed beside this file, and on hand-made events."""

from __future__ import annotations

import json
import os

import pytest

import trace_reduce as tr
from _shared import is_flush, is_kernel, probe_bytes, probe_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
E = tr.Event


def test_busy_idle_and_gaps_by_span():
    trace = tr.Trace(
        ops={"/device:TPU:0": [E("a", 100, 50), E("b", 120, 100),
                               E("a", 400, 100), E("a", 2000, 10)]},
        modules={"/device:TPU:0": [E("jit_f", 100, 120)]},
        spans=[E("bench.window", 0, 1000), E("bench.tick", 50, 300),
               E("bench.submit", 350, 100), E("bench.tick", 600, 300)])
    s = tr.reduce(trace, chips=1)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(220e-9)         # [100,220] + [400,500]
    assert s.idle_share == pytest.approx(0.78)
    assert s.op_counts == {"a": 2, "b": 1}           # the op at 2000 is out
    assert s.op_seconds["a"] == pytest.approx(150e-9)
    assert s.module_time(lambda n: n == "jit_f") == pytest.approx(
        (120e-9, 1))
    assert s.idle_by_span == pytest.approx(
        {"other": 250e-9, "tick": 480e-9, "submit": 50e-9})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "a" and b["idle_gaps"][0][0] == "tick"


def test_nested_spans_name_the_innermost():
    trace = tr.Trace(ops={"/device:TPU:0": [E("a", 0, 10)]}, modules={},
                     spans=[E("bench.window", 0, 100), E("bench.tick", 10, 80),
                            E("bench.submit", 30, 20)])
    s = tr.reduce(trace, chips=1)
    assert s.idle_by_span == pytest.approx(
        {"tick": 60e-9, "submit": 20e-9, "other": 10e-9})


def test_roofline_bytes_and_share():
    probe = {"queries": 256, "calls": 1, "matches": 256 * 20, "slots": 8,
             "segments": 1, "max_matches": 20}
    want = 256 * 8 * 3 * 4 + 256 * 20 * 12 + 256 * 20 * 4
    assert probe_bytes(probe) == want
    s = tr.Summary(window_s=1.0, busy_s=0.5,
                   op_seconds={"fused_lookup": 1e-3}, op_counts={
                       "fused_lookup": 1}, module_seconds={},
                   module_counts={}, idle_by_span={})
    ctx = {"trace": s, "raw": {"probe": probe},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert probe_roofline(ctx) == pytest.approx(want / 819e9 / 1e-3 * 100)


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: engine ticks
    with a flush, then two joins.  The reduction finds every lookup
    kernel launch and every flush the recording made, and the device
    busy for less than the window and at least as long as the kernel."""
    with open(os.path.join(DATA, "recorded.json")) as f:
        made = json.load(f)
    s = tr.reduce(tr.load(DATA), chips=1)
    kernel_s, launches = s.op_time(is_kernel)
    flush_s, flushes = s.module_time(is_flush)
    assert launches == made["lookup_kernel_launches"]
    assert flushes == made["flush_runs"] >= 1
    assert 0 < kernel_s <= s.busy_s < s.window_s and flush_s > 0
    gaps = s.breakdown()["idle_gaps"]
    assert {name for name, _ in gaps} <= {"tick", "join_call", "other"}
    assert sum(v for _, v in gaps) == pytest.approx(s.window_s - s.busy_s)
