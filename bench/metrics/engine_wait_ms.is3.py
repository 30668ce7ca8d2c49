"""Host time per engine batch spent waiting for the read site on the
device, in ms (the ``repro.engine.wait`` span around
``block_until_ready``)."""

from _program import ms_per, span


def read(ctx):
    s = span("engine.wait")
    return None if s is None else ms_per(s["s"], s["n"])
