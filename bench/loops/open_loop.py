"""Open-loop lookups and appends through ``QueryEngine``.

Independent users send requests on a schedule, whatever the server's
pace: the arrivals are fixed before the window opens, and every request
that is due is submitted before each ``tick()``.  A lookup's latency runs
from its scheduled arrival to the moment the client holds its answer,
after the ``tick()`` that served it returns.  An append's visibility
runs from its scheduled arrival to the end of the first tick that served
a lookup at a version holding it.

The arrivals are the same for every seed: the inter-arrival gaps are the
midpoint quantiles of an exponential law at the mix's rate (a Poisson
stream's gaps, in a seeded order), and the writes take a fixed share of
the positions.  The seed chooses the order, the keys and the writes.

Mix parameters: ``rate_per_s``, ``write_share``, ``keys_per_request``,
``max_matches``, ``min_bucket``, ``max_bucket``, ``flush_deadline_ticks``.
"""

from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from reference import Reference, mismatches


class Loop:
    def __init__(self, cfg, cfgmod, data, mix, seed, span):
        self.cfg, self.cfgmod, self.data, self.mix = cfg, cfgmod, data, mix
        self.seed, self.span = seed, span
        self.key = cfg["key"]

    # -- set-up ---------------------------------------------------------------

    def setup(self, seconds: float):
        from repro import IndexedFrame
        from repro.core import Schema
        from repro.serving.query_engine import QueryEngine, bucket_ladder

        mix = self.mix
        schema = Schema.of(self.key, **dict(self.cfg["schema"]))
        frame = IndexedFrame.from_columns(self.data["cols"], schema)
        self.eng = QueryEngine(
            frame, ladder=bucket_ladder(mix["max_bucket"],
                                        min_bucket=mix["min_bucket"]),
            max_matches=mix["max_matches"],
            flush_deadline_ticks=mix["flush_deadline_ticks"])
        del frame
        self._schedule(seconds)
        self._warm()
        self.slots = int(self.eng.frame.data.slots)
        self.segments = int(self.eng.frame.data.num_segments)

    def _schedule(self, seconds: float):
        mix, rng = self.mix, np.random.default_rng([self.seed, 2])
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)[rng.permutation(n)]
        gaps *= seconds / gaps.sum()
        self.times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        n_w = int(round(n * mix["write_share"]))
        self.is_write = np.zeros(n, bool)
        self.is_write[rng.permutation(n)[:n_w]] = True
        keys = self.data["read_keys"]
        k = int(mix["keys_per_request"])
        self.read_keys = keys[rng.integers(0, keys.size, (n - n_w, k))]
        self.writes = self.cfgmod.make_writes(self.cfg, self.data, rng, n_w)

    def _warm(self):
        """Every shape the window uses: one full batch on each rung of
        the ladder (so every per-request result slice is seen), a
        ring-full flush and a deadline flush."""
        eng, rng = self.eng, np.random.default_rng([self.seed, 1])
        keys = self.data["read_keys"]
        k = int(self.mix["keys_per_request"])
        lanes = self.eng.frame.queue.lanes
        for b in eng.ladder:
            for _ in range(max(1, b // k)):
                eng.submit_lookup(keys[rng.integers(0, keys.size, k)])
            eng.tick()
        writes = self.cfgmod.make_writes(self.cfg, self.data, rng, lanes + 2)
        # every append the loop makes, as (the client's rows, its request)
        self.submitted = [(c, eng.submit_append(c)) for c in writes[:-1]]
        eng.tick()
        self.submitted.append((writes[-1], eng.submit_append(writes[-1])))
        for _ in range(eng.flush_deadline_ticks + 1):
            eng.submit_lookup(keys[rng.integers(0, keys.size, k)])
            eng.tick()
        eng.drain()

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        eng, span = self.eng, self.span
        st = eng.stats
        before = (st.batches, st.batched_keys, st.padded_lanes, st.flushes)
        times, is_write = self.times, self.is_write
        n = times.size
        reads, writes, late = [], [], np.zeros(n)
        # per tick: start, seconds, requests answered, flushes in it
        ticks = []
        # the client's view of visibility: the highest version at which
        # an append of its own was acknowledged, as each lookup is admitted
        unacked = [w for _, w in self.submitted if w.version is None]
        acked = max((w.version for _, w in self.submitted
                     if w.version is not None), default=0)
        gc0 = sum(g["collections"] for g in gc.get_stats())
        ri = wi = i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if i < n and times[i] <= now:
                with span("submit"):
                    while i < n and times[i] <= now:
                        due = t0 + times[i]
                        if is_write[i]:
                            c = self.writes[wi]
                            w = eng.submit_append(c, t_submit=due)
                            w.sched = due
                            writes.append(w)
                            unacked.append(w)
                            self.submitted.append((c, w))
                            wi += 1
                        else:
                            r = eng.submit_lookup(self.read_keys[ri],
                                                  t_submit=due)
                            r.sched, r.acked = due, acked
                            reads.append(r)
                            ri += 1
                        late[i] = time.perf_counter() - due
                        i += 1
            if eng.has_work:
                flushes, ts = st.flushes, time.perf_counter()
                with span("tick"):
                    done = eng.tick()
                t = time.perf_counter()
                ticks.append((ts - t0, t - ts, len(done),
                              st.flushes - flushes))
                for r in done:
                    r.t_client = t
                # appends land in the order they were admitted
                while unacked and unacked[0].version is not None:
                    acked = max(acked, unacked.pop(0).version)
            elif i < n:
                with span("generate"):
                    time.sleep(max(0.0, times[i] - (time.perf_counter() - t0)))
            else:
                break
        t_close = time.perf_counter()
        gc1 = sum(g["collections"] for g in gc.get_stats())
        after = (st.batches, st.batched_keys, st.padded_lanes, st.flushes)
        self.reads, self.writes_done = reads, writes
        lat = np.array([r.t_client - r.sched for r in reads
                        if getattr(r, "t_client", None) is not None])
        # one last lookup reads the final flush, so every append is seen
        probe = eng.submit_lookup(self.read_keys[0])
        eng.tick()
        t_served = [r.t_client for r in reads
                    if getattr(r, "t_client", None) is not None]
        t_served.append(time.perf_counter())
        ver_served = [r.version for r in reads
                      if getattr(r, "t_client", None) is not None]
        ver_served.append(probe.version)
        # versions served only rise, so the first answer at a version that
        # holds an append is found by a search
        order = np.argsort(t_served, kind="stable")
        tick_t = np.array(t_served)[order]
        first = np.maximum.accumulate(np.array(ver_served)[order])
        vis = []
        for w in writes:
            if w.version is None:
                continue
            at = int(np.searchsorted(first, w.version, "left"))
            if at < first.size:
                vis.append(tick_t[at] - w.sched)
        self.unseen = len(writes) - len(vis)
        self.unanswered = len(reads) - lat.size
        d = dict(zip(("batches", "batched_keys", "padded_lanes", "flushes"),
                     (a - b for a, b in zip(after, before))))
        got = [np.asarray(r.result[1]).sum(axis=-1) for r in reads
               if r.result is not None]
        got = np.concatenate(got) if got else np.zeros(0, int)
        m = int(self.mix["max_matches"])
        tk = np.array(ticks).reshape(-1, 4)
        slow = int(np.argmax(tk[:, 1])) if tk.size else 0
        return {
            "window_s": t_close - t0,
            "diagnostics": {
                "ticks": len(ticks),
                "tick_max_ms": tk[slow, 1] * 1e3 if tk.size else 0.0,
                "tick_max_at_s": tk[slow, 0] if tk.size else 0.0,
                "tick_max_requests": int(tk[slow, 2]) if tk.size else 0,
                "tick_max_flushes": int(tk[slow, 3]) if tk.size else 0,
                "tick_before_max_ms": tk[slow - 1, 1] * 1e3 if slow else 0.0,
                "tick_before_max_flushes": int(tk[slow - 1, 3]) if slow
                else 0,
                "ticks_over_500ms": int((tk[:, 1] > 0.5).sum()),
                "gc_collections": gc1 - gc0,
                "full_chain_share": float((got >= m).mean()) if got.size
                else 0.0},
            "lookup_latency_s": lat,
            "visible_latency_s": np.array(vis),
            "late_s": late,
            "counters": d,
            "attempted": n,
            "failed": self.unanswered + self.unseen,
            "probe": {"queries": d["batched_keys"], "calls": d["batches"],
                      "matches": int(got.sum()), "slots": self.slots,
                      "segments": self.segments, "max_matches": m},
        }

    def rows_stored(self) -> int:
        return int(self.cfg["rows"]) + sum(
            int(np.asarray(c[self.key]).size) for c, w in self.submitted
            if w.version is not None)

    def release(self):
        """Read every acknowledged append back (each written key, once,
        at the final version), keep what the check needs on the host and
        free the frame."""
        eng = self.eng
        keys = [np.asarray(c[self.key]) for c, _ in self.submitted]
        eng.drain()
        acked = max((w.version for _, w in self.submitted
                     if w.version is not None), default=0)
        self.readback = [eng.submit_lookup(k[None]) for k in
                         np.unique(np.concatenate(keys))] if keys else []
        for r in self.readback:
            r.acked = acked
        eng.drain()
        self.eng = None

    # -- the check ------------------------------------------------------------

    def reference(self) -> Reference:
        """The base rows plus the client's own appends, each at the
        version the engine acknowledged it at, in the order admitted."""
        ref = Reference(self.key, self.data["cols"])
        landed = sorted((w.version, k) for k, (_, w) in
                        enumerate(self.submitted) if w.version is not None)
        for v, group in itertools.groupby(landed, key=lambda x: x[0]):
            rows = [self.submitted[k][0] for _, k in group]
            ref.append({n: np.concatenate([np.asarray(c[n]) for c in rows])
                        for n in ref.names}, v)
        return ref

    def answers(self, reads):
        reads = [r for r in reads if r.result is not None]
        names = [c for c, _ in self.cfg["schema"]]
        q = np.concatenate([r.keys for r in reads])
        ver = np.concatenate([np.full(r.keys.size, r.version) for r in reads])
        cols = {c: np.concatenate([r.result[0][c] for r in reads])
                for c in names}
        valid = np.concatenate([r.result[1] for r in reads])
        return q, ver, cols, valid, names

    def check(self, control=None) -> list:
        """``[(name, value, limit)]``: every lookup served in the window
        against the reference at its version; every append read back
        after the window (each written key looked up at the final
        version); no lookup served at a version older than an append the
        client saw acknowledged before admitting it; every lookup
        answered and every append seen.  With ``control(cfg, ref,
        queries, versions, m)`` its answers stand in for the
        program's."""
        ref = self.reference()
        m = int(self.mix["max_matches"])
        out = []
        for name, reads in (("lookup_mismatches", self.reads),
                            ("readback_mismatches", self.readback)):
            bad = 0
            if reads:
                q, ver, cols, valid, names = self.answers(reads)
                if control is not None:
                    cols, valid = control(self.cfg, ref, q, ver, m)
                want_cols, want_valid = ref.lookup(q, ver, m)
                bad = int(mismatches(cols, valid, want_cols, want_valid,
                                     names).sum())
            out.append((name, bad, 0))
        stale = sum(r.version is not None and r.version < r.acked
                    for r in self.reads + self.readback)
        unread = sum(r.result is None for r in self.readback)
        return out + [("stale_reads", int(stale), 0),
                      ("appends_unseen", int(self.unseen) + unread, 0),
                      ("lookups_unanswered", int(self.unanswered), 0)]
