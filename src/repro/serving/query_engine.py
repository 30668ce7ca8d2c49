"""Continuous-batching query serving engine with p50/p99 SLOs.

The MVCC design's whole point is readers staying on a consistent
snapshot while writers append (PAPER.md §3), and the ROADMAP's north
star is "heavy traffic from millions of users" — this module is the
serving loop that makes that story measurable.  The shape follows
rtp-llm's FIFO scheduler + KV-cache manager (PAPERS.md) applied to the
paper's dataframe operators instead of token decode:

* **Admission** — many client streams ``submit_lookup`` / ``submit_join``
  / ``submit_append`` into one FIFO queue.  No reordering: a micro-batch
  is a contiguous head run of compatible requests (same kind,
  ``max_matches``, probe columns), exactly the FIFOScheduler contract.
* **Pad-to-bucket micro-batching** — each batch's key vector is padded
  to the smallest bucket in a power-of-two ladder with the reserved
  ``PAD_KEY`` sentinel (``core.hashindex.EMPTY_KEY`` — a guaranteed miss
  on every physical operator), so every batch size hits an existing jit
  cache entry: the arena/ring static-shape trick (DESIGN.md §4, §13)
  applied to query batching.  One trace per (site, bucket), zero
  retraces thereafter — ``scripts/trace_gate.py`` gates it.
* **Write interleaving** — writer deltas are staged into the PR-7
  device-resident ``AppendQueue`` between ticks (zero host syncs) and
  flushed on ring-full or a tick deadline: ONE fused ingest, ONE version
  bump for the whole ring.  Reads admitted in the same tick ride the
  pre-flush snapshot — the one-version-bump MVCC contract, observable
  per request via ``QueryRequest.version``.
* **Supervision** — hand the engine a ``RecoveryManager``
  (``frame.supervised(...)``) instead of a bare frame and every batch
  runs through the PR-6 self-healing read path: the engine serves
  traffic mid-heal (tests/test_query_engine.py).
* **SLO accounting** — per-request latency (submit -> answer on the
  host) and write visibility lag feed ``latency_summary()`` (p50/p99/mean);
  ``benchmarks/serve.py`` sweeps a QPS × write-rate grid into
  ``BENCH_serve.json``.

The engine OWNS the frame from construction on (like ``supervised``):
it attaches the append ring up front (the one treedef change, before
any read site compiles) and replaces the frame on every write.
``write_log`` records each landed version with its coalesced delta
group, so an unbatched twin can replay the exact interleaving and
verify bit-identity (scripts/serve_smoke.py).
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import joins
from repro.core import partition as partition_mod
from repro.core import table as table_mod
from repro.core.hashindex import EMPTY_KEY

# The reserved pad sentinel: the probe side treats EMPTY_KEY as
# can-never-match on every physical operator (local fused probe, bcast,
# routed exchange — dist masks it out of the all-to-all entirely), so a
# padded lane costs one probe of an empty slot and can never fabricate a
# hit, consume routed capacity, or perturb neighbouring answers.
PAD_KEY = int(np.asarray(EMPTY_KEY))

DEFAULT_MIN_BUCKET = 8
DEFAULT_MAX_BUCKET = 256


def bucket_ladder(max_bucket: int = DEFAULT_MAX_BUCKET, *,
                  min_bucket: int = DEFAULT_MIN_BUCKET) -> tuple[int, ...]:
    """The power-of-two bucket ladder ``(min, 2*min, ..., max)``.

    Every micro-batch is padded up to a rung, so the number of distinct
    shapes the jitted read sites ever see — and therefore the number of
    compiles — is ``len(ladder)``, not the number of request sizes.
    """
    if min_bucket < 1 or max_bucket < min_bucket:
        raise ValueError(f"need 1 <= min_bucket <= max_bucket, got "
                         f"{min_bucket} / {max_bucket}")
    lo = 1 << (min_bucket - 1).bit_length()
    hi = 1 << (max_bucket - 1).bit_length()
    return tuple(lo << i for i in range((hi // lo).bit_length()))


def pick_bucket(n: int, ladder: tuple[int, ...]) -> int:
    """Smallest rung >= n (callers bound n by ``ladder[-1]`` at admission)."""
    for b in ladder:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} rows exceeds the ladder max "
                     f"{ladder[-1]}")


def pad_keys(keys: np.ndarray, bucket: int) -> np.ndarray:
    """Pad a key vector to ``bucket`` lanes with the ``PAD_KEY`` sentinel."""
    out = np.full(bucket, PAD_KEY, np.int64)
    out[:keys.shape[0]] = keys
    return out


@dataclasses.dataclass
class QueryRequest:
    """One admitted read: a lookup key batch or a join probe block."""

    req_id: int
    stream_id: int
    kind: str                      # "lookup" | "join"
    keys: np.ndarray | None        # lookup: [n] int64
    probe_cols: dict | None        # join: columnar probe block
    on: str | None
    max_matches: int
    t_submit: float
    t_done: float | None = None    # the answer is on the host
    version: int | None = None     # MVCC version the answer was read at
    bucket: int | None = None
    batch: int | None = None       # the engine batch that served it
    result: tuple | None = None    # lookup: (cols, valid); join: 3-tuple

    @property
    def size(self) -> int:
        return (self.keys.shape[0] if self.kind == "lookup"
                else next(iter(self.probe_cols.values())).shape[0])

    @property
    def done(self) -> bool:
        return self.t_done is not None

    @property
    def latency_s(self) -> float | None:
        return None if self.t_done is None else self.t_done - self.t_submit


@dataclasses.dataclass
class WriteRequest:
    """One admitted writer delta: staged into the ring, visible at flush."""

    req_id: int
    stream_id: int
    cols: dict
    valid: np.ndarray | None
    t_submit: float
    t_staged: float | None = None
    t_visible: float | None = None
    version: int | None = None     # version that made the delta visible

    @property
    def latency_s(self) -> float | None:
        return (None if self.t_visible is None
                else self.t_visible - self.t_submit)


class EngineStats:
    """Counters + latency samples the SLO summary and benchmarks read."""

    def __init__(self):
        self.ticks = 0
        self.reads = 0
        self.writes = 0
        self.batches = 0
        self.batched_keys = 0
        self.padded_lanes = 0
        self.flushes = 0
        self.direct_appends = 0
        self.read_latencies_s: list[float] = []
        self.write_latencies_s: list[float] = []

    def to_dict(self) -> dict:
        d = {k: v for k, v in vars(self).items()
             if not isinstance(v, list)}
        d["read_latency"] = percentiles(self.read_latencies_s)
        d["write_latency"] = percentiles(self.write_latencies_s)
        return d


def percentiles(latencies_s) -> dict:
    """p50/p99/mean/max in milliseconds over a latency sample."""
    if not len(latencies_s):
        return {"n": 0}
    ms = np.asarray(latencies_s, np.float64) * 1e3
    return {"n": int(ms.size), "p50_ms": float(np.percentile(ms, 50)),
            "p99_ms": float(np.percentile(ms, 99)),
            "mean_ms": float(ms.mean()), "max_ms": float(ms.max())}


class QueryEngine:
    """Continuous-batching serving loop over an ``IndexedFrame`` (or a
    ``RecoveryManager`` wrapping one — duck-typed: a manager has no
    ``plan_lookup``).

    ``tick()`` is one scheduler step: (1) drain the read FIFO into
    pad-to-bucket micro-batches against the CURRENT snapshot, (2) stage
    admitted writer deltas into the append ring (auto-flushing a full
    ring), (3) flush on the tick deadline.  ``drain()`` runs ticks until
    idle and lands the final flush.  The engine owns the frame; callers
    keep handles to their ``QueryRequest``s and read results off them.
    """

    def __init__(self, frame, *, ladder: tuple[int, ...] | None = None,
                 max_bucket: int = DEFAULT_MAX_BUCKET,
                 min_bucket: int = DEFAULT_MIN_BUCKET,
                 max_matches: int = 8, names=None, op: str = "auto",
                 flush_deadline_ticks: int = 4,
                 queue_lanes: int = table_mod.DEFAULT_QUEUE_LANES,
                 queue_lane_rows: int | None = None,
                 flush_donate: bool = False,
                 enqueue_donate: bool = True):
        self.ladder = (tuple(ladder) if ladder is not None
                       else bucket_ladder(max_bucket, min_bucket=min_bucket))
        if list(self.ladder) != sorted(set(self.ladder)):
            raise ValueError(f"ladder must be strictly increasing, got "
                             f"{self.ladder}")
        self.max_matches = int(max_matches)
        joins.check_max_matches(self.max_matches)
        self.names = None if names is None else tuple(names)
        self.op = op
        self.flush_deadline_ticks = max(1, int(flush_deadline_ticks))
        self.flush_donate = flush_donate
        self.enqueue_donate = enqueue_donate

        # a RecoveryManager (supervised mode) vs a bare frame: the
        # manager owns healing + its own jitted sites; the engine only
        # adds admission/batching/interleaving on top.
        self._mgr = None
        if not hasattr(frame, "plan_lookup"):
            self._mgr = frame
            frame = frame.frame
        # partitioned frames have no frame-level ring (appends route per
        # partition) and no engine-owned jit sites (the partition layer's
        # per-partition sites carry the compile cache + pruning); writes
        # go through the direct-append path, one version bump each
        self._partitioned = bool(getattr(frame, "is_partitioned", False))
        self._part0 = (partition_mod.site_traces(),
                       partition_mod.expected_site_traces())
        # attach the ring NOW — the frame's one treedef change happens
        # before any read site compiles, so streaming stays retrace-free
        if frame.queue is None and not self._partitioned:
            frame = frame.with_queue(lanes=queue_lanes,
                                     lane_rows=queue_lane_rows)
        if self._mgr is not None:
            self._mgr.frame = frame
        else:
            self._frame = frame

        self._readq: deque[QueryRequest] = deque()
        self._writeq: deque[WriteRequest] = deque()
        self._staged: list[WriteRequest] = []
        self._next_id = 0
        self._ticks_since_flush = 0
        self._sites: dict = {}         # site key -> (jit fn, trace ctr)
        self._bucket_use: set = set()  # (site key, bucket) pairs driven
        self.stats = EngineStats()
        # host-side MVCC version mirror: ONE sync at construction, then
        # +1 per flush / direct append — serving never reads the device
        # scalar back (verify_version() checks the mirror in tests)
        self._version_host = int(np.asarray(self.frame.version))
        self.write_log: list[dict] = []

    # -- frame ownership -------------------------------------------------------

    @property
    def frame(self):
        """The live frame (the manager's, in supervised mode)."""
        return self._mgr.frame if self._mgr is not None else self._frame

    def _set_frame(self, fr):
        if self._mgr is not None:
            self._mgr.frame = fr
        else:
            self._frame = fr

    @property
    def supervised(self) -> bool:
        return self._mgr is not None

    @property
    def version_host(self) -> int:
        """Host mirror of the frame's MVCC version (no device sync)."""
        return self._version_host

    def verify_version(self) -> bool:
        """One device sync: does the host mirror match the device scalar?
        (One bump per flush — the MVCC contract check for tests/smoke.)"""
        return int(np.asarray(self.frame.version)) == self._version_host

    # -- admission (the FIFO queue) --------------------------------------------

    def _admit_keys(self, keys) -> np.ndarray:
        arr = np.asarray(keys)
        if arr.ndim != 1 or arr.size == 0:
            raise ValueError(f"a request is a non-empty [n] key vector, "
                             f"got shape {arr.shape}")
        if arr.dtype.kind not in "iu":
            raise ValueError(f"keys must be integers, got {arr.dtype}")
        if arr.size > self.ladder[-1]:
            raise ValueError(
                f"request of {arr.size} keys exceeds the ladder max "
                f"{self.ladder[-1]}; split it across requests")
        return arr.astype(np.int64)

    def submit_lookup(self, keys, *, stream_id: int = 0,
                      max_matches: int | None = None,
                      t_submit: float | None = None) -> QueryRequest:
        """Admit one lookup request (``getRows`` over a key batch).
        ``t_submit`` lets an open-loop driver charge queueing delay from
        the scheduled arrival time, not the submit call."""
        mm = self.max_matches if max_matches is None else int(max_matches)
        joins.check_max_matches(mm)
        r = QueryRequest(
            req_id=self._next_id, stream_id=stream_id, kind="lookup",
            keys=self._admit_keys(keys), probe_cols=None, on=None,
            max_matches=mm,
            t_submit=time.perf_counter() if t_submit is None else t_submit)
        self._next_id += 1
        self._readq.append(r)
        self.stats.reads += 1
        return r

    def submit_join(self, probe_cols: dict, on: str, *, stream_id: int = 0,
                    max_matches: int | None = None,
                    t_submit: float | None = None) -> QueryRequest:
        """Admit one join request (this frame as the build side)."""
        mm = self.max_matches if max_matches is None else int(max_matches)
        joins.check_max_matches(mm)
        pc = {k: np.asarray(v) for k, v in probe_cols.items()}
        self._admit_keys(pc[on])          # validates size/dtype via on-col
        r = QueryRequest(
            req_id=self._next_id, stream_id=stream_id, kind="join",
            keys=None, probe_cols=pc, on=on, max_matches=mm,
            t_submit=time.perf_counter() if t_submit is None else t_submit)
        self._next_id += 1
        self._readq.append(r)
        self.stats.reads += 1
        return r

    def submit_append(self, cols: dict, valid=None, *, stream_id: int = 0,
                      t_submit: float | None = None) -> WriteRequest:
        """Admit one writer delta: staged into the device-resident ring
        at the next tick, visible at the next flush."""
        w = WriteRequest(
            req_id=self._next_id, stream_id=stream_id, cols=cols,
            valid=valid,
            t_submit=time.perf_counter() if t_submit is None else t_submit)
        self._next_id += 1
        self._writeq.append(w)
        self.stats.writes += 1
        return w

    @property
    def pending_reads(self) -> int:
        return len(self._readq)

    @property
    def pending_writes(self) -> int:
        """Admitted but not yet staged (ring-staged deltas are counted
        by ``staged_writes`` until the flush makes them visible)."""
        return len(self._writeq)

    @property
    def staged_writes(self) -> int:
        return len(self._staged)

    @property
    def has_work(self) -> bool:
        return bool(self._readq or self._writeq or self._staged)

    # -- jitted read sites (one compile per (site, bucket)) --------------------

    def _site(self, skey):
        if skey not in self._sites:
            ctr = {"n": 0}
            if skey[0] == "lookup":
                _, mm, names, op = skey

                def f(fr, q):
                    ctr["n"] += 1
                    return fr.lookup(q, max_matches=mm, names=names, op=op)
            else:
                _, on, mm, names, op, _colnames = skey

                def f(fr, pc):
                    ctr["n"] += 1
                    return fr.join(pc, on, max_matches=mm, names=names,
                                   op=op)
            self._sites[skey] = (jax.jit(f), ctr)
        return self._sites[skey]

    def _batch_key(self, r: QueryRequest):
        if r.kind == "lookup":
            return ("lookup", r.max_matches, self.names, self.op)
        return ("join", r.on, r.max_matches, self.names, self.op,
                tuple(sorted(r.probe_cols)))

    @property
    def trace_counts(self) -> dict:
        """Traces per engine-owned read site (supervised mode: the
        manager's sites count instead — see ``retraces``)."""
        return {k: ctr["n"] for k, (_, ctr) in self._sites.items()}

    @property
    def retraces(self) -> int:
        """Total traces across the serving read sites.  Equals
        ``expected_traces`` exactly when nothing retraced: each
        (site, bucket) pair compiles once and every later batch of that
        shape reuses the cache entry.

        Partitioned frames: the partition layer's counters are
        PROCESS-GLOBAL, so this is a baseline-subtracted window — exact
        only while no OTHER partitioned frame or engine in the process
        runs lookups concurrently (their traces would be misattributed
        to this engine).  Gates and benchmarks drive one engine at a
        time, which is the supported measurement setup."""
        if self._mgr is not None:
            return self._mgr.retraces
        if self._partitioned:
            return partition_mod.site_traces() - self._part0[0]
        return sum(ctr["n"] for _, ctr in self._sites.values())

    @property
    def expected_traces(self) -> int:
        """Distinct (read site, bucket) pairs this engine has driven.
        Partitioned frames count the partition layer's per-partition
        sites instead (its fingerprints subsume the bucket ladder) —
        process-global with a construction-time baseline, same caveat
        as ``retraces``."""
        if self._mgr is None and self._partitioned:
            return partition_mod.expected_site_traces() - self._part0[1]
        return len(self._bucket_use)

    @property
    def zero_retraces_after_warmup(self) -> bool:
        return self.retraces == self.expected_traces

    # -- micro-batching --------------------------------------------------------

    def _take_batch(self) -> list[QueryRequest]:
        """A contiguous FIFO head run of compatible requests bounded by
        the ladder max — strict arrival order, never reordered past an
        incompatible request (the FIFOScheduler admission contract)."""
        head = self._readq.popleft()
        batch, key, total = [head], self._batch_key(head), head.size
        while self._readq:
            nxt = self._readq[0]
            if (self._batch_key(nxt) != key
                    or total + nxt.size > self.ladder[-1]):
                break
            batch.append(self._readq.popleft())
            total += nxt.size
        return batch

    def _run_batch(self, batch: list[QueryRequest]) -> list[QueryRequest]:
        key = self._batch_key(batch[0])
        n = sum(r.size for r in batch)
        bucket = pick_bucket(n, self.ladder)
        bid = self.stats.batches
        self.stats.batches += 1
        self.stats.batched_keys += n
        self.stats.padded_lanes += bucket - n
        self._bucket_use.add((key, bucket))
        for r in batch:
            r.batch = bid
        # admission: padding, the key transfer and the read site's
        # dispatch; its engine.wait child is the wait for the device
        with obs.span("engine.admit", batch=bid, keys=n, bucket=bucket):
            if batch[0].kind == "lookup":
                out = self._exec_lookup(key, batch, n, bucket)
            else:
                out = self._exec_join(key, batch, n, bucket)
        # delivery: each answer cut out of the batch and copied to the host
        with obs.span("engine.deliver", batch=bid, requests=len(batch)):
            off = 0
            for r in batch:
                sl = slice(off, off + r.size)
                r.result = tuple(
                    {k: np.asarray(v[sl]) for k, v in part.items()}
                    if isinstance(part, dict) else np.asarray(part[sl])
                    for part in out)
                r.bucket = bucket
                r.version = self._version_host
                off += r.size
            t_done = time.perf_counter()
            copies = sum(len(p) if isinstance(p, dict) else 1 for p in out)
            obs.count("engine.answered", len(batch))
            obs.count("engine.host_copies", copies * len(batch))
        for r in batch:
            r.t_done = t_done
            self.stats.read_latencies_s.append(r.latency_s)
        return batch

    def _exec_lookup(self, skey, batch, n, bucket):
        padded = pad_keys(np.concatenate([r.keys for r in batch]), bucket)
        mm = skey[1]
        if self._mgr is not None:
            cols, valid = self._mgr.lookup(
                jnp.asarray(padded), max_matches=mm, names=self.names,
                op=self.op)
        elif self._partitioned:
            # eager call: routing needs HOST keys (pruning), and the
            # partition layer's own jitted per-partition sites are the
            # compile cache — an engine-level jit would turn keys into
            # tracers and forfeit both
            cols, valid = self._frame.lookup(
                padded, max_matches=mm, names=self.names, op=self.op)
        else:
            fn, _ = self._site(skey)
            cols, valid = fn(self._frame, jnp.asarray(padded))
        with obs.span("engine.wait", batch=batch[0].batch):
            jax.block_until_ready(valid)
        return cols, valid

    def _exec_join(self, skey, batch, n, bucket):
        on = skey[1]
        cat = {c: np.concatenate([r.probe_cols[c] for r in batch])
               for c in batch[0].probe_cols}
        padded = {}
        for c, v in cat.items():
            fill = np.zeros(bucket, v.dtype)
            if c == on:
                fill = pad_keys(np.zeros(0, np.int64), bucket)
            fill[:n] = v
            padded[c] = fill
        mm = skey[2]
        if self._mgr is not None:
            bcols, pcols, valid = self._mgr.join(
                {k: jnp.asarray(v) for k, v in padded.items()}, on,
                max_matches=mm, names=self.names, op=self.op)
        elif self._partitioned:
            bcols, pcols, valid = self._frame.join(
                padded, on, max_matches=mm, names=self.names, op=self.op)
        else:
            fn, _ = self._site(skey)
            bcols, pcols, valid = fn(
                self._frame, {k: jnp.asarray(v) for k, v in padded.items()})
        with obs.span("engine.wait", batch=batch[0].batch):
            jax.block_until_ready(valid)
        return bcols, pcols, valid

    # -- write interleaving ----------------------------------------------------

    def _enqueue(self, cols, valid):
        if self._mgr is not None:
            self._mgr.enqueue(cols, valid)
        else:
            self._frame = self._frame.enqueue(cols, valid,
                                              donate=self.enqueue_donate)

    def _append_direct(self, w: WriteRequest):
        """The documented oversize bypass: a delta too big for a ring
        lane lands through the ordinary coalesced append — its own
        version bump, immediately visible."""
        if self._mgr is not None:
            self._mgr.append(w.cols, w.valid)
        else:
            self._frame = self._frame.append(w.cols, w.valid)
        self._version_host += 1
        t = time.perf_counter()
        w.t_staged = w.t_visible = t
        w.version = self._version_host
        self.write_log.append({"version": self._version_host,
                               "writes": [(w.cols, w.valid)]})
        self.stats.direct_appends += 1
        self.stats.write_latencies_s.append(w.latency_s)

    def _stage_write(self, w: WriteRequest):
        with obs.span("engine.stage", request=w.req_id):
            if self._partitioned:
                # no frame-level ring on partitioned frames (supervised
                # or not): every write is a routed direct append, its own
                # version bump — the twin replay stays bit-identical
                # because write_log records each as its own group
                self._append_direct(w)
                return
            try:
                self._enqueue(w.cols, w.valid)
            except table_mod.QueueOverflow:
                self.flush()                   # ring-full: flush, retry
                try:
                    self._enqueue(w.cols, w.valid)
                except table_mod.QueueOverflow:
                    self._append_direct(w)
                    return
            w.t_staged = time.perf_counter()
            self._staged.append(w)

    def flush(self):
        """Land the staged ring: ONE fused ingest, ONE version bump for
        however many deltas are staged; the flushed group is recorded in
        ``write_log`` so a twin can replay the interleaving."""
        if not self._staged:
            return
        with obs.span("engine.flush", version=self._version_host + 1,
                      deltas=len(self._staged)):
            if self._mgr is not None:
                self._mgr.flush()
            else:
                self._frame = self._frame.flush(donate=self.flush_donate)
            self._version_host += 1
            t = time.perf_counter()
            self.write_log.append({
                "version": self._version_host,
                "writes": [(w.cols, w.valid) for w in self._staged]})
            for w in self._staged:
                w.t_visible = t
                w.version = self._version_host
                self.stats.write_latencies_s.append(w.latency_s)
            self._staged.clear()
            self.stats.flushes += 1
            self._ticks_since_flush = 0

    # -- the scheduler tick ----------------------------------------------------

    def tick(self) -> list[QueryRequest]:
        """One continuous-batching step: drain reads against the current
        (pre-flush) snapshot, stage writes into the ring, flush on the
        deadline.  Returns the requests completed this tick."""
        self.stats.ticks += 1
        done = []
        while self._readq:
            done.extend(self._run_batch(self._take_batch()))
        while self._writeq:
            self._stage_write(self._writeq.popleft())
        self._ticks_since_flush += 1
        if self._staged and \
                self._ticks_since_flush >= self.flush_deadline_ticks:
            self.flush()
        return done

    def drain(self) -> list[QueryRequest]:
        """Tick until idle, then land the final flush."""
        done = []
        while self._readq or self._writeq:
            done.extend(self.tick())
        self.flush()
        return done

    # -- SLO summary -----------------------------------------------------------

    def latency_summary(self) -> dict:
        """p50/p99 read latency + write visibility lag + batching shape
        (the per-cell record ``benchmarks/serve.py`` commits)."""
        s = self.stats
        return {
            "read": percentiles(s.read_latencies_s),
            "write_visibility": percentiles(s.write_latencies_s),
            "reads": s.reads, "writes": s.writes, "ticks": s.ticks,
            "batches": s.batches,
            "mean_batch_keys": (s.batched_keys / s.batches
                                if s.batches else 0.0),
            "pad_fraction": (s.padded_lanes
                             / (s.padded_lanes + s.batched_keys)
                             if s.batched_keys else 0.0),
            "flushes": s.flushes, "direct_appends": s.direct_appends,
            "retraces": self.retraces,
            "expected_traces": self.expected_traces,
            "zero_retraces_after_warmup": self.zero_retraces_after_warmup,
        }


def replay_unbatched(frame0, requests, write_log, *,
                     names=None, op: str = "auto", site_cache=None):
    """Verify the serving run against an unbatched MVCC twin.

    Replays ``write_log`` version by version on ``frame0`` (the frame the
    engine was BUILT from, pre-serving) and answers every request
    individually — no admission queue, no padding, no ring — at exactly
    the version the engine answered it.  Returns the number of requests
    whose engine answers are NOT bit-identical to the twin's (0 is the
    acceptance claim in scripts/serve_smoke.py and BENCH_serve.json).

    ``site_cache``: an optional dict the caller owns.  When given, the
    twin's per-request reads run through jitted sites cached there (one
    compile per request shape, reused across calls AND across replays
    sharing the dict — successive MVCC twins are structurally equal, so
    nothing retraces).  The answers are bit-identical to the eager path;
    benchmarks pass a shared dict so grid cells on the slow-compiling
    shard_map backend don't pay the oracle's compile cost per cell.
    """
    twin = frame0
    log = sorted(write_log, key=lambda g: g["version"])
    li = 0
    mismatches = 0

    def read(r):
        if site_cache is None:
            if r.kind == "lookup":
                return twin.lookup(jnp.asarray(r.keys),
                                   max_matches=r.max_matches, names=names,
                                   op=op)
            return twin.join({k: jnp.asarray(v)
                              for k, v in r.probe_cols.items()}, r.on,
                             max_matches=r.max_matches, names=names, op=op)
        if r.kind == "lookup":
            skey = ("lookup", r.keys.shape[0], r.max_matches, names, op)
            if skey not in site_cache:
                mm = r.max_matches
                site_cache[skey] = jax.jit(lambda fr, q, _mm=mm: fr.lookup(
                    q, max_matches=_mm, names=names, op=op))
            return site_cache[skey](twin, jnp.asarray(r.keys))
        skey = ("join", r.on, next(iter(r.probe_cols.values())).shape[0],
                r.max_matches, names, op, tuple(sorted(r.probe_cols)))
        if skey not in site_cache:
            mm, on = r.max_matches, r.on
            site_cache[skey] = jax.jit(lambda fr, pc, _mm=mm, _on=on:
                                       fr.join(pc, _on, max_matches=_mm,
                                               names=names, op=op))
        return site_cache[skey](twin, {k: jnp.asarray(v)
                                       for k, v in r.probe_cols.items()})

    for r in sorted([r for r in requests if r.done],
                    key=lambda r: r.version):
        while li < len(log) and log[li]["version"] <= r.version:
            group = log[li]["writes"]
            cols = [c for c, _ in group]
            valid = [v for _, v in group]
            if any(v is not None for v in valid):
                twin = twin.append(cols if len(cols) > 1 else cols[0],
                                   valid if len(cols) > 1 else valid[0])
            else:
                twin = twin.append(cols if len(cols) > 1 else cols[0])
            li += 1
        if not _results_equal(r.result, read(r)):
            mismatches += 1
    return mismatches


def _results_equal(got, want) -> bool:
    for g, w in zip(got, want):
        if isinstance(g, dict):
            for k in w:
                if not np.array_equal(np.asarray(g[k]), np.asarray(w[k])):
                    return False
        elif not np.array_equal(np.asarray(g), np.asarray(w)):
            return False
    return True
