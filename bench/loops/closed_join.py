"""Closed-loop indexed joins through the ``IndexedFrame`` facade.

One client calls ``IndexedFrame.join`` the way a library user does,
eagerly and with numpy probe blocks, waits for the answer on the device
(``block_until_ready``) and sends the next block.  The window ends with
the call that is running when the time is up; the rate is every probe
key answered over the whole window.  The blocks are made before the
window opens and cycled.  The answers of a seeded sample of calls
(reservoir sampling, so the sample does not depend on how many calls
fit) are kept on the device and checked once the window has closed.

Mix parameters: ``probe_rows``, ``max_matches``, ``blocks``,
``sample_calls``.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from reference import Reference, mismatches


class Loop:
    def __init__(self, cfg, cfgmod, data, mix, seed, span):
        self.cfg, self.cfgmod, self.data, self.mix = cfg, cfgmod, data, mix
        self.seed, self.span = seed, span
        self.key = cfg["key"]
        self.on = cfg["probe"]["on"]

    def setup(self, seconds: float):
        from repro import IndexedFrame
        from repro.core import Schema

        mix = self.mix
        schema = Schema.of(self.key, **dict(self.cfg["schema"]))
        self.frame = IndexedFrame.from_columns(self.data["cols"], schema)
        self.blocks = self.cfgmod.make_probe_blocks(
            self.cfg, self.data, np.random.default_rng([self.seed, 2]),
            int(mix["blocks"]), int(mix["probe_rows"]))
        for b in self.blocks[:2]:                  # one shape: warm it
            jax.block_until_ready(self._call(b))
        self.slots = int(self.frame.data.slots)
        self.segments = int(self.frame.data.num_segments)

    def _call(self, block):
        return self.frame.join(block, self.on,
                               max_matches=int(self.mix["max_matches"]))

    def window(self, seconds: float) -> dict:
        span, blocks = self.span, self.blocks
        keep = int(self.mix["sample_calls"])
        rng = np.random.default_rng([self.seed, 3])
        self.sample = []
        calls = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            block = blocks[calls % len(blocks)]
            with span("join_call"):
                out = jax.block_until_ready(self._call(block))
            if calls < keep:
                self.sample.append((calls, out))
            else:
                j = int(rng.integers(0, calls + 1))
                if j < keep:
                    self.sample[j] = (calls, out)
            calls += 1
        window_s = time.perf_counter() - t0
        rows = int(self.mix["probe_rows"])
        matches = sum(int((b[self.on] != np.iinfo(np.int64).min).sum())
                      for b in blocks)
        return {
            "window_s": window_s,
            "keys": calls * rows,
            "counters": {"calls": calls},
            "attempted": calls,
            "failed": 0,
            "probe": {"queries": calls * rows, "calls": calls,
                      # every non-NULL key matches one customer
                      "matches": matches * calls // len(blocks),
                      "slots": self.slots, "segments": self.segments,
                      "max_matches": int(self.mix["max_matches"])},
        }

    def rows_stored(self) -> int:
        return int(self.cfg["rows"])

    def release(self):
        """Copy the sampled answers to the host and free the frame."""
        self.sample = [(i, jax.tree.map(np.asarray, out))
                       for i, out in self.sample]
        self.frame = None

    def check(self, control=None) -> list:
        """``[(name, value, limit)]``: the sampled calls' build columns,
        broadcast probe columns and valid mask against the reference;
        with ``control`` its answers stand in for the program's build
        columns and valid mask."""
        ref = Reference(self.key, self.data["cols"])
        names = [c for c, _ in self.cfg["schema"]]
        m = int(self.mix["max_matches"])
        bad = 0
        for i, (bcols, pcols, valid) in self.sample:
            block = self.blocks[i % len(self.blocks)]
            q = block[self.on]
            if control is not None:
                bcols, valid = control(self.cfg, ref, q, 0, m)
            want_cols, want_valid = ref.lookup(q, 0, m)
            wrong = mismatches(bcols, valid, want_cols, want_valid, names)
            for c, v in block.items():
                wrong |= (np.asarray(pcols[c]) != v[:, None]).any(axis=1)
            bad += int(wrong.sum())
        return [("join_mismatches", bad, 0),
                ("join_calls_unchecked", int(not self.sample), 0)]
