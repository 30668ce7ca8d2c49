"""Arithmetic shared by the metric readers: each reader is one file named
after its metric, and returns ``None`` where it finds nothing to read.

``ctx`` is what a run hands every reader: ``raw`` (the loop's samples
and counters over the window), ``setup_s``, ``peak_bytes``,
``rows_stored``, ``trace`` (the reduced profiler trace of a ``--trace 1``
run, else ``None``) and ``peaks`` (the device's row of ``peaks.json``).
"""

from __future__ import annotations

import numpy as np

# The Pallas lookup kernel's device operations, by the name the trace
# gives them.
KERNEL_NAMES = ("fused_lookup",)
# The ring flush's program, by the name of the jitted function.
FLUSH_NAMES = ("_flush_core",)


def is_kernel(name: str) -> bool:
    return any(k in name for k in KERNEL_NAMES)


def is_flush(name: str) -> bool:
    return any(k in name for k in FLUSH_NAMES)


def p99_ms(samples):
    a = np.asarray(samples, np.float64)
    return None if a.size == 0 else float(np.percentile(a, 99) * 1e3)


def probe_bytes(probe: dict) -> int:
    """Bytes the probe and chain walk must move, from the cell's shapes
    and the answers' match counts, whatever implements them.  Per query,
    one bucket window of each segment (a key hi word, a key lo word and a
    row pointer per slot); per match walked, one chain pointer and the
    two key words compared; and the ``max_matches`` row ids written."""
    q = int(probe["queries"])
    window = probe["segments"] * probe["slots"] * 3 * 4
    return (q * window + int(probe["matches"]) * (4 + 8)
            + q * probe["max_matches"] * 4)


def kernel_seconds(ctx):
    """(seconds, ops) of the lookup kernel in the traced window."""
    tr = ctx["trace"]
    if tr is None:
        return None
    s, n = tr.op_time(is_kernel)
    return (s, n) if n else None


def probe_device_ms(ctx):
    """Kernel device time per read call (engine batch or join call)."""
    k = kernel_seconds(ctx)
    calls = ctx["raw"]["probe"]["calls"]
    if k is None or not calls:
        return None
    return k[0] / calls * 1e3


def probe_roofline(ctx):
    """Least time the bytes need at the HBM peak over the kernel's time,
    in %.  Bytes bound it: the probe does no arithmetic worth a FLOP
    bound."""
    k = kernel_seconds(ctx)
    if k is None or not k[0] or not ctx["peaks"]:
        return None
    least = probe_bytes(ctx["raw"]["probe"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return least / k[0] * 100.0


def idle_share(ctx):
    tr = ctx["trace"]
    if tr is None or tr.busy_s <= 0:
        return None
    return tr.idle_share * 100.0
