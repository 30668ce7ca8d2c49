"""Host time per answered request spent cutting its answer out of the
batch and copying it to the host, in ms (the ``repro.engine.deliver``
spans over the ``engine.answered`` counter)."""

from _program import counter, ms_per, span


def read(ctx):
    s = span("engine.deliver")
    return None if s is None else ms_per(s["s"], counter("engine.answered"))
