"""Program spans and counters (``repro.obs``) at the host boundaries of
the serving engine, the ring flush and the join facade.

They record only while a profile is being collected and only in eager
code; each span knows its parent and its self time; the counts agree
with the engine's own ``EngineStats``; and the spans reach the host
plane of the profiler's trace.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro import IndexedFrame, obs
from repro.core import Schema
from repro.serving.query_engine import QueryEngine

SCH = Schema.of("k", k="int64", v="float32", w="int32")
N = 512
COLUMNS = len(SCH.names)


def _frame():
    rng = np.random.default_rng(5)
    cols = {"k": np.arange(N, dtype=np.int64) % 97,
            "v": rng.random(N).astype(np.float32),
            "w": np.arange(N, dtype=np.int32)}
    return IndexedFrame.from_columns(cols, SCH, rows_per_batch=128,
                                     reserve=2048)


def _delta(k):
    return {"k": np.asarray([k], np.int64),
            "v": np.asarray([0.5], np.float32),
            "w": np.asarray([k], np.int32)}


def _serve(eng, rounds=3):
    """A few ticks of lookups with appends between them."""
    rng = np.random.default_rng(9)
    for i in range(rounds):
        for n in (1, 3, 2):
            eng.submit_lookup(rng.integers(0, 97, n).astype(np.int64))
        eng.submit_append(_delta(200 + i))
        eng.tick()
    eng.drain()


@pytest.fixture
def profiled(tmp_path):
    """Run ``body`` under the profiler; returns (table, trace dir)."""
    def run(body):
        obs.reset()
        jax.profiler.start_trace(str(tmp_path))
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        table = obs.profiled()
        obs.reset()
        return table, str(tmp_path)
    return run


def test_nothing_recorded_without_a_profile():
    obs.reset()
    eng = QueryEngine(_frame(), ladder=(8, 16), max_matches=4,
                      flush_deadline_ticks=1)
    _serve(eng)
    frame = _frame()
    frame.join({"k": np.arange(8, dtype=np.int64)}, "k", max_matches=2)
    assert eng.stats.batches > 0 and eng.stats.flushes > 0
    assert obs.profiled() == {"spans": {}, "counters": {}}


def test_engine_spans_match_engine_stats(profiled):
    eng = QueryEngine(_frame(), ladder=(8, 16), max_matches=4,
                      flush_deadline_ticks=1)
    _serve(eng, rounds=1)                       # warm every shape
    before = (eng.stats.batches, eng.stats.flushes)
    table, _ = profiled(lambda: _serve(eng))
    batches = eng.stats.batches - before[0]
    flushes = eng.stats.flushes - before[1]
    spans, counters = table["spans"], table["counters"]
    assert batches > 0 and flushes > 0
    for name in ("engine.admit", "engine.wait", "engine.deliver"):
        assert spans[name]["n"] == batches, name
    assert spans["engine.wait"]["parents"] == {"engine.admit": batches}
    assert spans["engine.flush"]["n"] == flushes
    assert spans["table.flush_check"]["n"] == flushes
    assert spans["table.flush_check"]["parents"] == {"engine.flush": flushes}
    assert spans["engine.stage"]["n"] == 3
    assert counters["engine.answered"] == 3 * 3
    assert counters["engine.host_copies"] == \
        (COLUMNS + 1) * counters["engine.answered"]


def test_self_time_is_at_most_the_span_time(profiled):
    eng = QueryEngine(_frame(), ladder=(8, 16), max_matches=4,
                      flush_deadline_ticks=1)

    def body():
        _serve(eng)
        eng.frame.join({"k": np.arange(16, dtype=np.int64)}, "k",
                       max_matches=2)
    spans = profiled(body)[0]["spans"]
    assert {"engine.admit", "engine.flush", "frame.join",
            "joins.decode"} <= set(spans)
    for name, row in spans.items():
        assert row["n"] > 0 and row["s"] > 0, name
        assert 0 <= row["self_s"] <= row["s"], name
    admit, wait = spans["engine.admit"], spans["engine.wait"]
    assert admit["self_s"] == pytest.approx(admit["s"] - wait["s"],
                                            abs=1e-6)


def test_ring_full_flush_is_a_child_of_stage(profiled):
    eng = QueryEngine(_frame(), ladder=(8,), max_matches=4,
                      queue_lanes=2, flush_deadline_ticks=100)

    def body():
        for i in range(3):                     # the third fills the ring
            eng.submit_append(_delta(300 + i))
        eng.tick()
    spans = profiled(body)[0]["spans"]
    assert eng.stats.flushes == 1 and eng.staged_writes == 1
    assert spans["engine.stage"]["n"] == 3
    assert spans["engine.flush"]["parents"] == {"engine.stage": 1}
    stage = spans["engine.stage"]
    assert stage["self_s"] < stage["s"]


def test_eager_join_records_the_facade_spans(profiled):
    frame = _frame()
    probe = {"k": np.arange(32, dtype=np.int64), "x": np.ones(32, np.int32)}
    frame.join(probe, "k", max_matches=2)       # warm
    spans = profiled(lambda: [jax.block_until_ready(
        frame.join(probe, "k", max_matches=2)) for _ in range(2)])[0]["spans"]
    assert spans["frame.join"]["n"] == 2
    assert spans["frame.join"]["parents"] == {None: 2}
    for name in ("frame.plan", "joins.probe", "joins.decode"):
        assert spans[name]["n"] == 2, name
    assert spans["frame.plan"]["parents"] == {"frame.join": 2}
    assert spans["joins.probe"]["parents"] == {"frame.join": 2}


def test_jitted_join_records_nothing(profiled):
    frame = _frame().with_flat_data()
    probe = {"k": np.arange(32, dtype=np.int64)}
    site = jax.jit(lambda fr, pc: fr.join(pc, "k", max_matches=2))
    table, _ = profiled(lambda: [jax.block_until_ready(site(frame, probe))
                                 for _ in range(2)])
    assert table == {"spans": {}, "counters": {}}


def test_trace_holds_deliver_spans_on_a_host_plane(profiled):
    eng = QueryEngine(_frame(), ladder=(8, 16), max_matches=4)
    table, trace_dir = profiled(lambda: _serve(eng, rounds=2))
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    data = jax.profiler.ProfileData.from_file(paths[-1])
    found = [e for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "repro.engine.deliver"]
    assert len(found) == table["spans"]["engine.deliver"]["n"] > 0
    assert all(e.duration_ns > 0 for e in found)
