"""Program spans and counters on the profiler's clock.

``span(name, **args)`` marks a host step of the program as a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``: it lands on the
host plane of a profiler trace, on the same clock as the device planes,
so a stretch of device idle time can be named by the host step it falls
in.  ``count(name, n)`` counts work at the same boundaries.

Both record only while a profile is being collected
(``jax.profiler.start_trace`` .. ``stop_trace``, or a capture through the
profiler server).  Otherwise a span costs one check and does nothing
else: no annotation, no clock read, no allocation beyond its keyword
arguments.  Spans and counters also record nothing under a JAX
transformation: the code that runs under ``jit`` (the facade's reads
inside a jitted read site) runs only while tracing, and its spans would
time the trace, not the work.

While recording, each span knows its parent (the innermost span open on
the same thread) and its self time (its duration minus the durations of
its direct children), and adds its count, seconds and self seconds to a
process-wide table that ``profiled()`` returns.  The table holds what
the process did while it was being profiled, across every collection
since the last ``reset()``.  In a benchmark run that starts the profile
after its warm-up and stops it when its measured window closes, that is
the window, plus whatever the run does inside the profile after it: a
serving loop's one closing tick, for instance.
"""

from __future__ import annotations

import threading
import time

import jax
from jax._src import core as _core
from jax._src.lib import _profiler

PREFIX = "repro."

# The two checks every span makes, each wrapped here only: is a profile
# being collected (the profiler's own TraceMe switch), and is this call
# at the top level, outside any JAX transformation.
_collecting = _profiler.TraceMe.is_enabled
_top_level = _core.trace_state_clean

_spans: dict[str, dict] = {}
_counters: dict[str, float] = {}
_lock = threading.Lock()
_local = threading.local()


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "annotation", "parent", "t0", "child_s")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + name, **args)
        self.child_s = 0.0

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.annotation.__exit__(*exc)
        _stack().pop()
        parent = self.parent
        if parent is not None:
            parent.child_s += dt
        with _lock:
            row = _spans.get(self.name)
            if row is None:
                row = _spans[self.name] = {"n": 0, "s": 0.0, "self_s": 0.0,
                                           "parents": {}}
            row["n"] += 1
            row["s"] += dt
            row["self_s"] += dt - self.child_s
            p = None if parent is None else parent.name
            row["parents"][p] = row["parents"].get(p, 0) + 1
        return False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def span(name: str, **args):
    """A context manager timing one host step as ``repro.<name>``, with
    ``args`` (ints or strings) attached to its trace event."""
    if not _collecting() or not _top_level():
        return _OFF
    return _Span(name, args)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``, under the same rule as spans."""
    if not _collecting() or not _top_level():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def profiled() -> dict:
    """What was recorded since the last ``reset()``: ``{"spans": {name:
    {"n", "s", "self_s", "parents": {parent name or None: n}}},
    "counters": {name: total}}``, as a copy."""
    with _lock:
        return {"spans": {k: {**v, "parents": dict(v["parents"])}
                          for k, v in _spans.items()},
                "counters": dict(_counters)}


def reset() -> None:
    """Clear the table."""
    with _lock:
        _spans.clear()
        _counters.clear()
