"""Host time per join call in the facade itself, in ms: key coercion, the
probe-side broadcast and the rest (the self time of the
``repro.frame.join`` span, which leaves out its plan, probe and decode
children)."""

from _program import ms_per, span


def read(ctx):
    s = span("frame.join")
    return None if s is None else ms_per(s["self_s"], s["n"])
