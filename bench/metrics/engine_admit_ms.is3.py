"""Host time per engine batch spent admitting it, in ms: padding, the
key transfer and the dispatch of the read site (the self time of the
``repro.engine.admit`` span, which leaves out its ``engine.wait``
child)."""

from _program import ms_per, span


def read(ctx):
    s = span("engine.admit")
    return None if s is None else ms_per(s["self_s"], s["n"])
