"""Host time per plan of a join, in ms: the planner's rule and the plan's
annotation (the ``repro.frame.plan`` span)."""

from _program import ms_per, span


def read(ctx):
    s = span("frame.plan")
    return None if s is None else ms_per(s["s"], s["n"])
