"""The comparison that decides ``correct`` fails what it must fail.

On the CPU at a tiny size: the control (the reference with one stated
guarantee broken, put in the program's place) reads above the limit on
three seeds while the program reads within it; and a run with the timed
path broken underneath (each fault the cell can have, planted by
patching the program in this process) comes out not correct.  The stale
snapshot is checked on its own as well: its lookups, served and
reported one flush behind, agree with the reference at the version they
report, and only the check of each lookup's version against the appends
acknowledged before it catches them.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

import control
import run
import tiny

IS3 = "snb_knows_sf100.is3_open"
JOIN = "tpcds_customer_sf300.ss_join"
SEEDS = [2**31 + 3, 2**32 + 5, 7]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", [IS3, JOIN])
def test_control_fails_where_the_program_passes(root, cell):
    lines = list(control.read_seeds(cell, SEEDS, 1.0, root=root,
                                    require_tpu=False))
    assert len(lines) == len(SEEDS)
    for line in lines:
        assert all(v <= line["limits"][n] for n, v in line["program"].items())
        assert any(v > line["limits"][n] for n, v in line["control"].items())
    print(json.dumps(lines))


def _engine_fault(monkeypatch, alter):
    """Patch the engine's batch so that ``alter(requests)`` runs on the
    answers where they are produced."""
    from repro.serving.query_engine import QueryEngine
    orig = QueryEngine._run_batch

    def run_batch(self, batch):
        out = orig(self, batch)
        alter(out)
        return out

    monkeypatch.setattr(QueryEngine, "_run_batch", run_batch)


def _answer_altered(reqs):
    cols, valid = reqs[0].result
    cols = dict(cols)
    cols["dst"] = cols["dst"] ^ 1
    reqs[0].result = (cols, valid)


def _half_left_out(reqs):
    for r in reqs[len(reqs) // 2:] if len(reqs) > 1 else reqs:
        cols, valid = r.result
        r.result = (cols, np.zeros_like(valid))


def _flush_unchanged(monkeypatch):
    from repro import IndexedFrame
    monkeypatch.setattr(IndexedFrame, "flush", lambda self, **kw: self)


def _stale_snapshot(monkeypatch):
    """Lookups served, and reported, one flush behind: each read runs on
    the frame as it stood before the latest flush, at that version."""
    from repro.serving.query_engine import QueryEngine
    flush, run_batch = QueryEngine.flush, QueryEngine._run_batch

    def stale_flush(self):
        prior = (self._frame, self._version_host)
        flush(self)
        if self._version_host != prior[1]:
            self._behind = prior

    def stale_run_batch(self, batch):
        if not hasattr(self, "_behind"):
            return run_batch(self, batch)
        now = (self._frame, self._version_host)
        self._frame, self._version_host = self._behind
        try:
            return run_batch(self, batch)
        finally:
            self._frame, self._version_host = now

    monkeypatch.setattr(QueryEngine, "flush", stale_flush)
    monkeypatch.setattr(QueryEngine, "_run_batch", stale_run_batch)


def _join_fault(monkeypatch, alter):
    from repro import IndexedFrame
    orig = IndexedFrame.join

    def join(self, *a, **kw):
        return alter(*orig(self, *a, **kw))

    monkeypatch.setattr(IndexedFrame, "join", join)


def _join_answer_altered(bcols, pcols, valid):
    bcols = dict(bcols)
    bcols["c_email_address"] = bcols["c_email_address"] + 1
    return bcols, pcols, valid


def _join_half_left_out(bcols, pcols, valid):
    n = valid.shape[0]
    return bcols, pcols, valid.at[n // 2:].set(False)


FAULTS = {
    (IS3, "answer_altered"): lambda mp: _engine_fault(mp, _answer_altered),
    (IS3, "half_left_out"): lambda mp: _engine_fault(mp, _half_left_out),
    (IS3, "state_unchanged"): _flush_unchanged,
    (IS3, "stale_snapshot"): _stale_snapshot,
    (JOIN, "answer_altered"): lambda mp: _join_fault(
        mp, _join_answer_altered),
    (JOIN, "half_left_out"): lambda mp: _join_fault(mp, _join_half_left_out),
}


def test_stale_snapshot_is_caught_by_the_version_check(root, monkeypatch):
    _stale_snapshot(monkeypatch)
    result = run.run(tiny.args(IS3, seed=SEEDS[1]), root=root,
                     require_tpu=False)
    checks = result["checks"]
    assert checks["stale_reads"]["value"] > 0
    assert checks["lookup_mismatches"]["value"] == 0
    assert result["correct"] is False


@pytest.mark.parametrize("cell,fault", list(FAULTS))
def test_planted_fault_is_not_correct(root, cell, fault, monkeypatch):
    FAULTS[cell, fault](monkeypatch)
    result = run.run(tiny.args(cell, seed=SEEDS[0]), root=root,
                     require_tpu=False)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
