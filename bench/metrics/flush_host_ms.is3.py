"""Host time per ring flush, in ms: the flush program's dispatch, the
wait for its overflow flag and the host mirrors (the
``repro.engine.flush`` span)."""

from _program import ms_per, span


def read(ctx):
    s = span("engine.flush")
    return None if s is None else ms_per(s["s"], s["n"])
