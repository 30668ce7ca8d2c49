"""Reduce a JAX profiler trace to device busy time, op times and idle gaps.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per device
operation and their ``XLA Modules`` line one per compiled program run.
The benchmark's own ``TraceAnnotation`` spans, named ``bench.<what>``,
sit on the host plane, on the same clock; the traced window is the
``bench.window`` span.

Busy time is the union of the op intervals inside the window, averaged
over the chips used; an idle gap is a stretch of the window with no op
on a chip, named by the innermost benchmark span it falls in.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

# The benchmark's own spans carry this prefix; the window is one of them.
PREFIX = "bench."
WINDOW_SPAN = PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """What the reduction needs from one trace, as plain lists."""

    ops: dict            # device name -> [Event] of the ops line
    modules: dict        # device name -> [Event] of the modules line
    spans: list          # [Event] benchmark spans on the host


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[
                        plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [Event(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events if e.name.startswith(PREFIX)]
    return Trace(ops=ops, modules=modules, spans=spans)


def _union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over the chips used
    op_seconds: dict                  # op name -> seconds, summed on chips
    op_counts: dict
    module_seconds: dict
    module_counts: dict
    idle_by_span: dict                # span name -> idle seconds (mean)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, match) -> tuple:
        """(seconds, count) of the ops whose name ``match`` accepts."""
        s = sum(v for k, v in self.op_seconds.items() if match(k))
        n = sum(v for k, v in self.op_counts.items() if match(k))
        return s, n

    def module_time(self, match) -> tuple:
        s = sum(v for k, v in self.module_seconds.items() if match(k))
        n = sum(v for k, v in self.module_counts.items() if match(k))
        return s, n

    def breakdown(self, top: int = 10, width: int = 120) -> dict:
        """The device ops that took most time (an op's name is its HLO
        text, cut to ``width`` characters) and the idle time by the span
        the host was in."""
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k[:width], v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce(trace: Trace, chips: int) -> Summary:
    """Busy and idle time, op and module totals inside the window span."""
    wins = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError("the trace holds no window span")
    w0 = min(s.start_ns for s in wins)
    w1 = max(s.end_ns for s in wins)
    devices = sorted(trace.ops, key=lambda d: int(d.rsplit(":", 1)[-1]))
    devices = devices[:chips]
    if not devices:
        raise ValueError("the trace holds no device ops line")
    pieces = _innermost(s for s in trace.spans if s.name != WINDOW_SPAN)
    busy = 0
    op_s, op_n = collections.Counter(), collections.Counter()
    mod_s, mod_n = collections.Counter(), collections.Counter()
    idle = collections.Counter()
    for dev in devices:
        ivs = []
        for e in trace.ops[dev]:
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t <= s:
                continue
            ivs.append((s, t))
            op_s[e.name] += (t - s) * 1e-9
            op_n[e.name] += 1
        for e in trace.modules.get(dev, ()):
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t > s:
                mod_s[e.name] += (t - s) * 1e-9
                mod_n[e.name] += 1
        merged = _union(ivs)
        busy += sum(t - s for s, t in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, ns in _attribute(gaps, pieces):
            idle[name] += ns * 1e-9 / len(devices)
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy * 1e-9 / len(devices),
                   op_seconds=dict(op_s), op_counts=dict(op_n),
                   module_seconds=dict(mod_s), module_counts=dict(mod_n),
                   idle_by_span=dict(idle))


def _innermost(spans) -> list:
    """Disjoint ``(start, end, name)`` pieces of the host timeline, each
    named by the innermost (latest-started) benchmark span covering it."""
    marks = []
    for k, sp in enumerate(spans):
        marks.append((sp.start_ns, 1, k, sp.name))
        marks.append((sp.end_ns, 0, k, sp.name))
    marks.sort()
    active, out, prev = [], [], None
    for t, opening, k, name in marks:
        if active and prev is not None and t > prev:
            out.append((prev, t, active[-1][1][len(PREFIX):]))
        if opening:
            active.append((k, name))
        else:
            active = [a for a in active if a[0] != k]
        prev = t
    return out


def _attribute(gaps: list, pieces: list):
    """Split sorted disjoint gaps over the named pieces (``other`` where
    no benchmark span covers them): yields ``(name, ns)``."""
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        t, k = g0, j
        while t < g1:
            if k < len(pieces) and pieces[k][0] < g1:
                a, b, name = pieces[k]
                if a > t:
                    yield "other", a - t
                    t = a
                end = min(b, g1)
                yield name, end - t
                t = end
                k += 1
            else:
                yield "other", g1 - t
                t = g1
