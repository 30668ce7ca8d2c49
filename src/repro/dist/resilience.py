"""Self-healing distributed execution: fault injection + supervised recovery.

The paper's §III-D / Fig-12 story is that an executor failure costs one
slow query, not the cache.  PR 2 built the recovery *pieces* — ``Lineage``
replay, ``fail_shard``/``rebuild_shard``, ``VersionVector`` fencing,
``StragglerPolicy`` — but they were disconnected props only the benchmark
drove by hand, and the routed lookup's ``answered=False``/``dropped``
retry contract was every caller's problem.  This module makes failure
handling part of the operator contract (the way Modin/Cylon-class
dataframe runtimes do):

* ``FaultInjector`` — a deterministic, seedable chaos plan: named faults
  (``shard_loss``, ``straggler``, ``capacity_pressure``,
  ``checkpoint_corruption``) that fire at planned supervision steps.
* ``RecoveryManager`` — the supervision layer ``IndexedFrame.supervised``
  routes distributed reads through.  Every read is fenced
  (``VersionVector``), integrity-probed (a cheap fill/sentinel scan), and
  auto-healed; dropped routed lookups auto-retry with doubled capacity
  under a bounded exponential-backoff budget.  On shard death it runs the
  full state machine:

      mark stale -> restore newest intact checkpoint -> replay only the
      lineage suffix since it (``Lineage.truncate`` keeps the log
      checkpoint-anchored, so replay is O(deltas since checkpoint)) ->
      splice the shard back (``runtime.splice_shard``) -> mark fresh.

  Leaf shapes never change, so the healed dtable re-enters the SAME jit
  cache entry — zero recompiles of the fused read sites, the Fig-12 flat
  tail (the manager's own retrace counter proves it; scripts/
  fault_smoke.py gates it in CI).  When the recovery budget is exhausted
  (every checkpoint corrupt, no base recipe) it degrades gracefully:
  surviving shards answer, the dead shard's queries come back as honest
  misses with a per-query ``answered`` mask and drop accounting in
  ``ReadReport`` — never fabricated matches.

DESIGN.md §12 records the fault model and the state machine;
benchmarks/fault_tolerance.py sweeps fault type × write rate into
``BENCH_dist.json``.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import time

import jax
import numpy as np

from repro.core import hashing
from repro.core import table as table_mod
from repro.core.hashindex import EMPTY_KEY
from repro.dist import checkpoint as _ckpt
from repro.dist import dtable as _dtable
from repro.dist import mesh as _mesh
from repro.dist import runtime as _runtime

FAULT_KINDS = ("shard_loss", "straggler", "capacity_pressure",
               "checkpoint_corruption")


@dataclasses.dataclass(frozen=True)
class Fault:
    """One named fault at a planned supervision step.

    ``shard`` targets shard loss / straggler delay; ``severity`` scales
    the fault (straggler slowdown factor; capacity divisor for pressure).
    """

    kind: str
    step: int
    shard: int = 0
    severity: float = 4.0

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; one of {FAULT_KINDS}")
        if self.step < 0 or self.severity <= 0:
            raise ValueError(
                f"step must be >= 0 and severity > 0, got "
                f"{self.step!r} / {self.severity!r}")


class FaultInjector:
    """A deterministic, seedable chaos plan.

    The supervision loop calls ``tick()`` once per step (read or write);
    faults whose ``step`` matches fire and are returned for the
    ``RecoveryManager`` to apply.  Determinism matters: the chaos sweep
    and the CI smoke must reproduce bit-identically from a seed.
    """

    def __init__(self, faults=(), *, seed: int = 0):
        self.plan = tuple(sorted(faults, key=lambda f: f.step))
        self.rng = np.random.default_rng(seed)
        self.seed = seed
        self.step = -1
        self.fired: list[Fault] = []

    @classmethod
    def plan_random(cls, *, seed: int, num_shards: int, steps: int,
                    kinds=FAULT_KINDS, n_faults: int = 1,
                    min_step: int = 1) -> "FaultInjector":
        """A seeded random plan: ``n_faults`` faults at distinct steps in
        ``[min_step, steps)`` — same seed, same chaos."""
        rng = np.random.default_rng(seed)
        span = np.arange(min_step, steps)
        at = rng.choice(span, size=min(n_faults, span.size), replace=False)
        faults = [Fault(kind=str(rng.choice(list(kinds))), step=int(st),
                        shard=int(rng.integers(num_shards)),
                        severity=float(2 ** rng.integers(1, 4)))
                  for st in sorted(int(s) for s in at)]
        return cls(faults, seed=seed)

    def tick(self) -> list[Fault]:
        """Advance one supervision step; return the faults firing now."""
        self.step += 1
        now = [f for f in self.plan if f.step == self.step]
        self.fired.extend(now)
        return now

    def corrupt_checkpoint(self, path: str) -> str:
        """Flip one seeded-random bit in the checkpoint's largest leaf —
        meta.json (with its recorded CRC32s) is left intact, so a restore
        MUST detect the flip (dist/checkpoint.py).  Returns the corrupted
        leaf's archive name."""
        leaves_path = os.path.join(path, "leaves.npz")
        with np.load(leaves_path) as data:
            arrs = {k: np.array(data[k]) for k in data.files}
        victims = [k for k, a in arrs.items() if a.nbytes > 0]
        if not victims:
            raise ValueError(f"checkpoint at {path!r} has no bytes to flip")
        name = max(victims, key=lambda k: arrs[k].nbytes)
        flat = np.ascontiguousarray(arrs[name]).reshape(-1).view(np.uint8)
        bit = int(self.rng.integers(flat.size * 8))
        flat[bit // 8] ^= np.uint8(1 << (bit % 8))
        arrs[name] = flat.view(arrs[name].dtype).reshape(arrs[name].shape)
        np.savez(leaves_path, **arrs)
        return name


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Budgets for the supervision layer.

    ``max_retries``/``backoff_*`` bound the routed drop->retry loop
    (capacity doubles per attempt, sleeps grow exponentially to the cap).
    ``checkpoint_every`` appends triggers an automatic checkpoint (0 =
    manual only); ``keep_checkpoints`` is the ring size — the lineage is
    truncated to the OLDEST kept checkpoint, so a corrupt newest
    checkpoint still has an older anchor plus a longer (but bounded)
    suffix.  ``probe_every`` reads runs the integrity probe (1 = every
    read).
    """

    max_retries: int = 4
    backoff_base_s: float = 0.002
    backoff_factor: float = 2.0
    backoff_cap_s: float = 0.25
    checkpoint_every: int = 8
    keep_checkpoints: int = 2
    probe_every: int = 1


@dataclasses.dataclass
class ReadReport:
    """Honest per-read accounting (the degraded-mode contract): which
    queries were answered by a live owner, what was dropped/retried, and
    what healed before the read ran."""

    answered: np.ndarray          # [Q] bool — owner alive AND delivered
    dropped: int                  # exchange drops left after retries
    retries: int                  # capacity-doubling retries this read
    recovered: tuple              # shards healed before this read
    degraded: bool                # some owner permanently dead
    operator: str                 # physical operator that answered


class RecoveryStats:
    """Counters the chaos sweep and CI smoke report (MTTR, replay cost,
    retrace count, retry/drop accounting)."""

    def __init__(self):
        self.reads = 0
        self.appends = 0
        self.enqueues = 0
        self.flushes = 0
        self.probes = 0
        self.recoveries = 0
        self.mttr_s: list[float] = []
        self.replayed_deltas: list[int] = []
        self.retries = 0
        self.drops = 0
        self.degraded_reads = 0
        self.corrupt_checkpoints = 0
        self.straggler_events = 0
        self.speculative_plans: list[dict] = []

    def to_dict(self) -> dict:
        return {**{k: v for k, v in vars(self).items()
                   if not k.startswith("_")}}


class RecoveryManager:
    """Supervises a distributed ``IndexedFrame``: reads are fenced,
    integrity-probed, auto-healed, and drop-retried — failure handling as
    part of the operator contract, not the caller's job (DESIGN.md §12).

    Build one with ``frame.supervised(...)``.  The manager owns the live
    frame (``.frame`` — recovery replaces its wrapped dtable) and mirrors
    the facade's read/write surface: ``lookup`` / ``join`` / ``append`` /
    ``checkpoint``.  Reads run through manager-owned jitted sites whose
    trace counter (``stats`` + ``retraces``) proves recovery re-enters
    the same compile-cache entry.
    """

    def __init__(self, frame, *, lineage: _runtime.Lineage | None = None,
                 policy: RecoveryPolicy | None = None,
                 injector: FaultInjector | None = None,
                 checkpoint_dir: str | None = None):
        if not getattr(frame, "is_distributed", False):
            raise ValueError(
                "supervision wraps the distributed backend; build the "
                "frame with num_shards > 1 (a single partition has no "
                "shard to lose)")
        self.frame = frame
        self.policy = policy if policy is not None else RecoveryPolicy()
        self.injector = injector
        self.lineage = lineage
        self.checkpoint_dir = checkpoint_dir
        s = frame.num_shards
        self.vv = _runtime.VersionVector.fresh(s)
        self.vv.versions = [self._version()] * s
        self.straggler = _runtime.StragglerPolicy()
        self.stats = RecoveryStats()
        self.last_report: ReadReport | None = None
        self.dead: set[int] = set()        # unrecoverable (budget spent)
        self._ckpts: list[tuple[int, str]] = []   # (version, path), old->new
        self._appends_since_ckpt = 0
        self._pressure_divisor: float | None = None
        self._sites: dict = {}             # (kind, mm, names) -> (jit fn, ctr)
        self._pending: list = []           # host (cols, valid) per ring delta
        self._expected_fill = self._fill()
        if checkpoint_dir is not None:
            # anchor immediately: recovery never needs the full history
            self.checkpoint()

    # -- cheap host facts ------------------------------------------------------

    def _version(self) -> int:
        return int(np.asarray(self.frame.version))

    def _fill(self) -> np.ndarray:
        return np.asarray(self.frame.data.table.snapshot.fill).reshape(-1)

    @property
    def retraces(self) -> int:
        """Total traces across the manager's jitted read sites — stays at
        one per (operator, max_matches, names) site across any number of
        appends AND recoveries (the Fig-12 zero-recompile claim)."""
        return sum(ctr["n"] for _, ctr in self._sites.values())

    # -- integrity probe -------------------------------------------------------

    def probe(self) -> list[int]:
        """The cheap dead-shard detector: a shard whose arena ``fill``
        disagrees with the supervisor's expectation, or whose bucket
        planes hold only EMPTY sentinels while rows are expected, is dead
        (``fail_shard`` blanks exactly these).  One [s] device->host
        transfer of ``fill`` plus one reduced sentinel scan."""
        self.stats.probes += 1
        dt = self.frame.data
        fill = self._fill()
        has_keys = np.zeros(fill.shape[0], bool)
        for seg in dt.table.segments:
            has_keys |= np.asarray(
                (seg.index.bucket_keys != EMPTY_KEY).any(axis=(1, 2)))
        expected = self._expected_fill
        alive = (fill == expected) & (has_keys | (expected == 0))
        return sorted(int(i) for i in np.nonzero(~alive)[0])

    # -- checkpoint ring -------------------------------------------------------

    def checkpoint(self) -> str:
        """Checkpoint the live dtable into the ring and truncate the
        lineage to the OLDEST kept checkpoint (the corruption fallback
        anchor) — the delta log stays bounded by the ring span."""
        if self.checkpoint_dir is None:
            raise ValueError("RecoveryManager has no checkpoint_dir")
        v = self._version()
        path = os.path.join(self.checkpoint_dir, f"ckpt_v{v}")
        _ckpt.save_dtable(path, self.frame.data)
        self._ckpts = [c for c in self._ckpts if c[0] != v] + [(v, path)]
        while len(self._ckpts) > max(1, self.policy.keep_checkpoints):
            _, old = self._ckpts.pop(0)
            shutil.rmtree(old, ignore_errors=True)
        if self.lineage is not None:
            oldest_v, oldest_path = self._ckpts[0]
            if oldest_v > self.lineage.base_version or \
                    self.lineage.has_base:
                self.lineage.truncate(oldest_v, oldest_path)
        self._appends_since_ckpt = 0
        return path

    # -- the supervision state machine ----------------------------------------

    def _recover_shard(self, shard: int) -> bool:
        """stale -> restore newest intact checkpoint -> replay the lineage
        suffix -> splice -> fresh.  Returns False when the budget is
        exhausted (the shard joins ``dead`` and reads degrade)."""
        if self.lineage is None:
            self.dead.add(shard)
            return False
        t0 = time.perf_counter()
        self.vv.mark_stale(shard)
        dt = self.frame.data
        fresh = replayed = None
        for version, path in reversed(self._ckpts):      # newest first
            try:
                fresh = self.lineage.replay_from(path, version, dt,
                                                 rt=self.frame.rt)
                replayed = self.lineage.version - version
                break
            except ValueError:
                self.stats.corrupt_checkpoints += 1
        if fresh is None and self.lineage.has_base:
            fresh = self.lineage.replay(self.frame.num_shards,
                                        rt=self.frame.rt, like=dt)
            replayed = len(self.lineage.deltas)
        if fresh is None:                  # budget exhausted: degrade
            self.dead.add(shard)
            return False
        healed = _runtime.splice_shard(dt, shard, fresh)
        if healed.replica is not None:
            # fail_shard marked the mirror stale (the dead executor's
            # copy died with it); with tracker and rows spliced back
            # bit-identically, one refresh restores the replica arena
            # bit-identically to a never-failed twin's.
            healed = _dtable.refresh_replica(healed, rt=self.frame.rt)
        self.frame = dataclasses.replace(self.frame, data=healed)
        self.vv.mark_fresh(shard, version=self._version())
        self._expected_fill = self._fill()
        self.stats.recoveries += 1
        self.stats.replayed_deltas.append(int(replayed))
        self.stats.mttr_s.append(time.perf_counter() - t0)
        return True

    def _heal(self) -> list[int]:
        """Fence + probe + recover: every shard the probe flags dead or
        the VersionVector fences stale is healed before the read runs."""
        version = self._version()
        suspects = set(self.probe())
        suspects.update(sh for sh in range(self.frame.num_shards)
                        if not self.vv.check_fresh(sh, version))
        recovered = []
        for shard in sorted(suspects - self.dead):
            if self._recover_shard(shard):
                recovered.append(shard)
        if recovered and self._pending:
            self._rebuild_ring()
        return recovered

    def _rebuild_ring(self):
        """Deterministically re-stage every pending (unflushed) delta
        into a FRESH ring after a heal: lineage replay restores the table
        to the last FLUSHED version, and re-enqueueing the manager's host
        mirror of the ring (``_pending``, in enqueue order) reproduces
        the lost shard's lanes bit-identically — a shard killed mid-ring
        heals to exactly the state a never-failed twin holds
        (scripts/fault_smoke.py gates this)."""
        q = self.frame.queue
        fr = dataclasses.replace(self.frame, queue=None).with_queue(
            lanes=q.lanes, lane_rows=q.lane_rows)
        for cols, valid in self._pending:
            fr = fr.enqueue(cols, valid)
        self.frame = fr

    # -- fault application -----------------------------------------------------

    def _apply_faults(self, faults):
        for f in faults:
            if f.kind == "shard_loss":
                self.frame = dataclasses.replace(
                    self.frame,
                    data=_runtime.fail_shard(self.frame.data, f.shard),
                    queue=self._fail_queue_shard(f.shard))
            elif f.kind == "capacity_pressure":
                self._pressure_divisor = max(2.0, float(f.severity))
            elif f.kind == "checkpoint_corruption":
                if self._ckpts and self.injector is not None:
                    self.injector.corrupt_checkpoint(self._ckpts[-1][1])
            elif f.kind == "straggler":
                base = 0.01
                durations = np.full(self.frame.num_shards, base)
                durations[f.shard] = base * float(f.severity)
                slow = self.straggler.observe(durations)
                if slow:
                    self.stats.straggler_events += 1
                    self.stats.speculative_plans.append(
                        self.straggler.plan_speculative(
                            self.frame.num_shards))

    def _fail_queue_shard(self, shard: int):
        """Blank the lost shard's slice of the append ring (a real
        executor death takes its staged lanes with it); the host mirror
        of what SHOULD be pending survives in ``_pending``, which is what
        ``_rebuild_ring`` heals from."""
        q = self.frame.queue
        if q is None:
            return None
        blanked = _mesh.place_like(dataclasses.replace(
            q,
            cols={k: v.at[shard].set(0) for k, v in q.cols.items()},
            valid=q.valid.at[shard].set(False),
            fills=q.fills.at[shard].set(0),
            count=q.count.at[shard].set(0)), q)
        return table_mod._set_queue_mirror(blanked,
                                           *table_mod.queue_pending(q))

    def _tick(self):
        if self.injector is not None:
            self._apply_faults(self.injector.tick())

    # -- jitted read sites (the zero-recompile proof) --------------------------

    def _site(self, kind: str, max_matches: int, names):
        key = (kind, max_matches, names)
        if key not in self._sites:
            ctr = {"n": 0}

            if kind == "BroadcastLookup":
                def f(fr, q):
                    ctr["n"] += 1
                    cols, valid, _ = _dtable.lookup(
                        fr.data, q, max_matches=max_matches, names=names,
                        rt=fr.rt)
                    return cols, valid
            elif kind == "RoutedLookup":
                def f(fr, q):
                    ctr["n"] += 1
                    return _dtable.lookup_routed_flat(
                        fr.data, q, max_matches=max_matches, names=names,
                        rt=fr.rt)
            elif kind == "HybridLookup":
                def f(fr, q):
                    ctr["n"] += 1
                    return _dtable.lookup_hybrid_flat(
                        fr.data, q, max_matches=max_matches, names=names,
                        rt=fr.rt)
            elif kind == "BroadcastJoin":
                def f(fr, pc, on):
                    ctr["n"] += 1
                    return _dtable.indexed_join_bcast(
                        fr.data, pc, on, max_matches, names=names,
                        rt=fr.rt)
            elif kind == "ShuffleJoin":
                def f(fr, pc, on):
                    ctr["n"] += 1
                    return _dtable.indexed_join_routed(
                        fr.data, pc, on, max_matches=max_matches,
                        names=names, rt=fr.rt)
            elif kind == "HybridJoin":
                def f(fr, pc, on):
                    ctr["n"] += 1
                    return _dtable.indexed_join_hybrid(
                        fr.data, pc, on, max_matches=max_matches,
                        names=names, rt=fr.rt)
            else:
                raise ValueError(f"unknown read site kind {kind!r}")
            static = (2,) if kind.endswith("Join") else ()
            self._sites[key] = (jax.jit(f, static_argnums=static), ctr)
        return self._sites[key]

    # -- reads -----------------------------------------------------------------

    def _answered_mask(self, keys_np: np.ndarray) -> np.ndarray:
        if not self.dead:
            return np.ones(keys_np.shape[0], bool)
        owner = hashing.partition_hash_host(keys_np,
                                            self.frame.num_shards)
        # EMPTY_KEY lanes are pad sentinels (serving pad-to-bucket) or
        # explicit guaranteed-miss probes: no owner needs to be alive to
        # answer them, so they never mark a read degraded
        pad = keys_np == int(np.asarray(EMPTY_KEY))
        return pad | ~np.isin(owner, np.asarray(sorted(self.dead)))

    def _routed_with_retry(self, q, max_matches: int, names):
        """The automated drop->retry contract: start at the pressured
        capacity, double per attempt under the exponential-backoff
        budget, stop at zero drops or budget exhaustion (drops are then
        reported honestly, never silently missed).

        When a fresh hot-key mirror covers this read's ``max_matches``,
        every attempt goes through the hybrid report: hot queries answer
        from the replica arena and are masked OUT of the exchange before
        capacity is spent, so a dropped-then-retried batch never re-routes
        its hot lanes at doubled capacity — the retry only re-runs the
        cold tail that actually dropped (the skew fix: under pressure a
        celebrity key can otherwise never be delivered at any doubling).
        """
        rep = self.frame.data.replica
        report = (_dtable.lookup_hybrid_report
                  if rep is not None and max_matches <= rep.max_matches
                  else _dtable.lookup_routed_report)
        s = self.frame.num_shards
        lanes = max(1, -(-int(np.shape(q)[0]) // s))
        cap = max(1, int(lanes / self._pressure_divisor))
        attempt = 0
        while True:
            cols, valid, answered, dropped = report(
                self.frame.data, q, max_matches=max_matches,
                capacity=min(cap, lanes), names=names, rt=self.frame.rt)
            n_dropped = int(np.asarray(dropped).sum())
            if n_dropped == 0 or attempt >= self.policy.max_retries:
                break
            self.stats.retries += 1
            self.stats.drops += n_dropped
            time.sleep(min(
                self.policy.backoff_base_s
                * self.policy.backoff_factor ** attempt,
                self.policy.backoff_cap_s))
            cap *= 2
            attempt += 1
        if n_dropped == 0:
            self._pressure_divisor = None     # delivery proven: relieved
        return cols, valid, np.asarray(answered), n_dropped, attempt

    def lookup(self, keys, *, max_matches: int = 64, names=None,
               op: str = "auto"):
        """Supervised ``frame.lookup``: same ``(cols [Q, M], valid
        [Q, M])`` contract, with fencing, healing, and drop-retry inside.
        ``self.last_report`` carries the per-read accounting."""
        self._tick()
        self.stats.reads += 1
        recovered = self._heal()
        names_t = None if names is None else tuple(names)
        kind = self.frame.plan_lookup(keys, max_matches=max_matches,
                                      op=op).kind
        q_np = np.asarray(keys).astype(np.int64).reshape(-1)
        retries = n_dropped = 0
        if (kind in ("RoutedLookup", "HybridLookup")
                and self._pressure_divisor is not None):
            q = jax.numpy.asarray(q_np)
            cols, valid, answered_x, n_dropped, retries = \
                self._routed_with_retry(q, max_matches, names_t)
            # pad-sentinel lanes never enter the routed exchange (their
            # qvalid is masked off), so answered_x reports them False —
            # but a guaranteed miss needs nobody to answer it
            answered = self._answered_mask(q_np) & (
                answered_x | (q_np == int(np.asarray(EMPTY_KEY))))
        else:
            fn, _ = self._site(kind, max_matches, names_t)
            cols, valid = fn(self.frame, jax.numpy.asarray(q_np))
            answered = self._answered_mask(q_np)
        degraded = bool((~answered).any())
        if degraded:
            self.stats.degraded_reads += 1
        self.stats.drops += n_dropped
        self.last_report = ReadReport(
            answered=answered, dropped=n_dropped, retries=retries,
            recovered=tuple(recovered), degraded=degraded, operator=kind)
        return cols, valid

    def join(self, probe_cols: dict, on: str, *, max_matches: int = 64,
             names=None, op: str = "auto"):
        """Supervised ``frame.join``: ``(build, probe, valid)`` in probe
        order, healed and fenced exactly like ``lookup``."""
        self._tick()
        self.stats.reads += 1
        recovered = self._heal()
        names_t = None if names is None else tuple(names)
        kind = self.frame.plan_join(probe_cols, on,
                                    max_matches=max_matches, op=op).kind
        fn, _ = self._site(kind, max_matches, names_t)
        out = fn(self.frame, {k: jax.numpy.asarray(v)
                              for k, v in probe_cols.items()}, on)
        q_np = np.asarray(probe_cols[on]).astype(np.int64).reshape(-1)
        answered = self._answered_mask(q_np)
        degraded = bool((~answered).any())
        if degraded:
            self.stats.degraded_reads += 1
        self.last_report = ReadReport(
            answered=answered, dropped=0, retries=0,
            recovered=tuple(recovered), degraded=degraded, operator=kind)
        return out

    # -- writes ----------------------------------------------------------------

    def append(self, cols, valid=None, *, donate: bool = False,
               compact_threshold: int | None = None) -> "RecoveryManager":
        """Supervised ``frame.append``: heals first (an ingest must never
        land on a blanked shard), records the delta into the lineage, and
        auto-checkpoints every ``policy.checkpoint_every`` appends.
        Returns ``self`` — the manager owns the new version."""
        self._tick()
        self._heal()
        if isinstance(cols, (list, tuple)):
            cols, valid = table_mod.coalesce_deltas(cols,
                                                    self.frame.schema,
                                                    valid)
        self.frame = self.frame.append(cols, valid, donate=donate,
                                       compact_threshold=compact_threshold)
        if self.lineage is not None:
            self.lineage.record_append(cols, valid)
        self.stats.appends += 1
        self.vv.bump_all()
        self._expected_fill = self._fill()
        self._appends_since_ckpt += 1
        if (self.checkpoint_dir is not None and self.policy.checkpoint_every
                and self._appends_since_ckpt >= self.policy.checkpoint_every):
            self.checkpoint()
        return self

    def enqueue(self, cols, valid=None) -> "RecoveryManager":
        """Supervised ``frame.enqueue``: stages the delta in the
        device-resident ring AND mirrors it host-side (``_pending``) so a
        shard killed mid-ring heals bit-identically — lineage only
        records landed versions, so the manager itself must remember
        what is staged.  No version bump, no checkpoint pressure."""
        self._tick()
        self._heal()
        from repro.frame import _hash_string_cols
        cols = _hash_string_cols(cols, self.frame.schema)
        host = ({k: np.asarray(v).copy() for k, v in cols.items()},
                None if valid is None else np.asarray(valid, bool).copy())
        self.frame = self.frame.enqueue(cols, valid)
        self._pending.append(host)
        self.stats.enqueues += 1
        return self

    def flush(self, *,
              compact_threshold: int | None = None) -> "RecoveryManager":
        """Supervised ``frame.flush``: lands the ring (one fused jit, one
        sync, ONE version bump) and records the coalesced pending deltas
        into the lineage as ONE append — replaying the log reproduces the
        flush bit-identically (flush ≡ coalesced append by the parity
        tests), keeping version parity between live and healed tables."""
        self._tick()
        self._heal()
        if not self._pending:
            return self
        self.frame = self.frame.flush(compact_threshold=compact_threshold)
        if self.lineage is not None:
            cols, valid = table_mod.coalesce_deltas(
                [c for c, _ in self._pending], self.frame.schema,
                [v for _, v in self._pending])
            self.lineage.record_append(cols, valid)
        self._pending.clear()
        self.stats.flushes += 1
        self.vv.bump_all()
        self._expected_fill = self._fill()
        self._appends_since_ckpt += 1
        if (self.checkpoint_dir is not None and self.policy.checkpoint_every
                and self._appends_since_ckpt >= self.policy.checkpoint_every):
            self.checkpoint()
        return self


def supervise(frame, *, lineage: _runtime.Lineage | None = None,
              policy: RecoveryPolicy | None = None,
              injector: FaultInjector | None = None,
              checkpoint_dir: str | None = None) -> RecoveryManager:
    """Functional entry point (``IndexedFrame.supervised`` delegates
    here): wrap a distributed frame in a ``RecoveryManager``."""
    return RecoveryManager(frame, lineage=lineage, policy=policy,
                           injector=injector, checkpoint_dir=checkpoint_dir)


# ---------------------------------------------------------------------------
# Partitioned supervision: one RecoveryManager per partition
# ---------------------------------------------------------------------------

class PartitionedSupervisor:
    """Per-partition supervision for a partitioned distributed frame
    (DESIGN.md §16): each partition gets its OWN ``RecoveryManager``
    (own checkpoints under ``checkpoint_dir/part_<id>``, own fault
    injector via ``supervisor.managers[i].injector``, own jitted read
    sites), and reads route pruned sub-batches to the owning partition's
    manager — a shard kill in one partition heals there without ever
    entering another partition's read path.

    Duck-types the ``RecoveryManager`` surface the serving engine and
    the facade rely on (``frame`` / ``lookup`` / ``join`` / ``append`` /
    ``flush`` / ``checkpoint`` / ``retraces`` / ``last_report``); like a
    manager it has no ``plan_lookup``, which is how ``QueryEngine``
    recognizes supervised mode."""

    def __init__(self, frame, *, policy: RecoveryPolicy | None = None,
                 checkpoint_dir: str | None = None,
                 with_lineage: bool = False):
        from repro.core import partition as _part
        pt = frame.data
        if not isinstance(pt, _part.PartitionedTable) or not pt.dist:
            raise ValueError(
                "PartitionedSupervisor wraps a PARTITIONED distributed "
                "frame (from_columns(partition_by=..., num_shards>1))")
        self._part = _part
        self._frame_cls = type(frame)
        self.rt = frame.rt
        self.spec = pt.spec
        self._version = pt.version
        self.managers = []
        for i, part in enumerate(pt.parts):
            sub = self._frame_cls(data=part, rt=frame.rt)
            lin = None
            if with_lineage:
                # one replay recipe per partition: its VALID base rows
                # (collect_cols drops pad lanes), at the partition's own
                # arena config so replay is bit-identical
                lin = _runtime.Lineage(
                    pt.schema, _dtable.collect_cols(part, rt=frame.rt),
                    rows_per_batch=pt.rows_per_batch, layout=pt.layout,
                    slots=pt.slots)
            cdir = (None if checkpoint_dir is None else
                    os.path.join(checkpoint_dir, f"part_{pt.spec.ids[i]}"))
            self.managers.append(RecoveryManager(sub, lineage=lin,
                                                 policy=policy,
                                                 checkpoint_dir=cdir))
        self.last_report: ReadReport | None = None

    # -- frame ownership ------------------------------------------------------

    @property
    def frame(self):
        pt = self._part.PartitionedTable(
            parts=tuple(m.frame.data for m in self.managers),
            version=self._version, spec=self.spec)
        return self._frame_cls(data=pt, rt=self.rt)

    @frame.setter
    def frame(self, fr):
        pt = fr.data
        if tuple(pt.spec.ids) != tuple(self.spec.ids):
            raise ValueError("cannot re-point a PartitionedSupervisor at a "
                             "different partition layout")
        for m, part in zip(self.managers, pt.parts):
            m.frame = dataclasses.replace(m.frame, data=part)
        self._version = pt.version

    @property
    def retraces(self) -> int:
        return sum(m.retraces for m in self.managers)

    # -- reads (pruned routing into per-partition managers) -------------------

    def _route(self, keys_np: np.ndarray):
        dest = self.spec.route_host(keys_np)
        return dest, [int(p) for p in np.unique(dest[dest >= 0])]

    def lookup(self, keys, *, max_matches: int = 64, names=None,
               op: str = "auto"):
        """Supervised pruned lookup: each touched partition's manager
        fences/heals/reads its own masked sub-batch; untouched
        partitions run nothing.  ``last_report`` merges per-partition
        accounting."""
        if op != "auto":
            raise ValueError("partitioned supervision picks per-partition "
                             "flavors itself; op must be 'auto'")
        fr = self.frame
        self._part._check_keyed(fr.data, "lookup")
        keys_np = np.asarray(keys).astype(np.int64).reshape(-1)
        q = keys_np.shape[0]
        sel = (tuple(names) if names is not None else fr.schema.names)
        import jax.numpy as jnp
        out_cols = {n: jnp.zeros((q, max_matches),
                                 fr.schema.column(n).jnp_dtype)
                    for n in sel}
        out_valid = jnp.zeros((q, max_matches), bool)
        answered = np.ones(q, bool)
        dropped = retries = 0
        recovered: list = []
        degraded = False
        dest, touched = self._route(keys_np)
        for p in touched:
            masked = np.where(dest == p, keys_np,
                              np.int64(np.asarray(EMPTY_KEY)))
            c, v = self.managers[p].lookup(
                jax.numpy.asarray(masked), max_matches=max_matches,
                names=names)
            out_valid = out_valid | v
            out_cols = {n: jnp.where(v, c[n], out_cols[n]) for n in sel}
            rep = self.managers[p].last_report
            answered &= rep.answered
            dropped += rep.dropped
            retries += rep.retries
            recovered.extend(rep.recovered)
            degraded |= rep.degraded
        self.last_report = ReadReport(
            answered=answered, dropped=dropped, retries=retries,
            recovered=tuple(recovered), degraded=degraded,
            operator="PartitionedLookup")
        return out_cols, out_valid

    def join(self, probe_cols: dict, on: str, *, max_matches: int = 64,
             names=None, op: str = "auto"):
        """Supervised pruned join: per-partition local joins through each
        owning partition's manager; probe broadcast rebuilt from the
        ORIGINAL probe side so output matches ``joins.indexed_join``."""
        if op != "auto":
            raise ValueError("partitioned supervision picks per-partition "
                             "flavors itself; op must be 'auto'")
        if on not in probe_cols:
            raise ValueError(f"probe column {on!r} not in probe_cols "
                             f"{sorted(probe_cols)}")
        import jax.numpy as jnp
        keys_np = np.asarray(probe_cols[on]).astype(np.int64).reshape(-1)
        bc, valid = self.lookup(keys_np, max_matches=max_matches,
                                names=names)
        m = valid.shape[1]
        probe_b = {k: jnp.broadcast_to(jnp.asarray(v)[:, None],
                                       (np.shape(v)[0], m))
                   for k, v in probe_cols.items()}
        return bc, probe_b, valid

    # -- writes ---------------------------------------------------------------

    def append(self, cols, valid=None, *, donate: bool = False,
               compact_threshold: int | None = None
               ) -> "PartitionedSupervisor":
        """Routed supervised append: each receiving partition's manager
        heals first, lands its slice, and records it in its own lineage;
        one global version bump."""
        if isinstance(cols, (list, tuple)):
            cols, valid = table_mod.coalesce_deltas(
                cols, self.managers[0].frame.schema, valid)
        for p, sub, sub_valid in self._part.split_by_partition(
                self.spec, cols, valid):
            self.managers[p].append(sub, sub_valid, donate=donate,
                                    compact_threshold=compact_threshold)
        self._version = self._version + 1
        return self

    def flush(self, **kw) -> "PartitionedSupervisor":
        """No frame-level ring on partitioned frames: nothing staged,
        nothing to land."""
        return self

    def checkpoint(self):
        """Checkpoint every partition (each manager anchors its own
        recovery)."""
        return [m.checkpoint() for m in self.managers]

    def drop_partition(self, pid) -> "PartitionedSupervisor":
        """O(1) retention under supervision: drop the partition AND its
        manager (its checkpoints stop being maintained)."""
        i = self.spec.index_of(pid)
        self.spec = self.spec.drop(i)
        del self.managers[i]
        self._version = self._version + 1
        return self
