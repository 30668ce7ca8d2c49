"""Readers of the program's own spans and counters (``repro.obs``).

The table holds what the process recorded while it was being profiled:
in a ``--trace 1`` run, the measured window and the loop's closing tick.
A reader returns ``None`` where the program has no such table (a program
older than ``repro/obs.py``) or the run recorded no such span or counter.
"""

from __future__ import annotations


def _table():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.profiled()


def span(name: str):
    """``{"n", "s", "self_s", ...}`` of the span ``name``, or ``None``."""
    table = _table()
    row = None if table is None else table["spans"].get(name)
    return row if row and row["n"] else None


def counter(name: str):
    table = _table()
    return None if table is None else table["counters"].get(name) or None


def ms_per(seconds, n):
    """``seconds`` over ``n`` events, in ms; ``None`` if either is missing."""
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
