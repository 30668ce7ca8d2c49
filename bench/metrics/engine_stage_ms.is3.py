"""Host time per append staged into the ring, in ms (the self time of the
``repro.engine.stage`` span: a ring-full flush inside it is its
``engine.flush`` child and left out)."""

from _program import ms_per, span


def read(ctx):
    s = span("engine.stage")
    return None if s is None else ms_per(s["self_s"], s["n"])
