"""Host time per join call in the probe, in ms: key coercion, the split
of the keys, bucket ids and the lookup kernel's dispatch (the
``repro.joins.probe`` spans over the ``repro.frame.join`` calls)."""

from _program import ms_per, span


def read(ctx):
    probe, call = span("joins.probe"), span("frame.join")
    return None if probe is None or call is None else \
        ms_per(probe["s"], call["n"])
