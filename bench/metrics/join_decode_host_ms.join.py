"""Host time per join call in the row decode, in ms: the dispatch of the
row gather and the row-word relayout (the ``repro.joins.decode`` spans
over the ``repro.frame.join`` calls)."""

from _program import ms_per, span


def read(ctx):
    decode, call = span("joins.decode"), span("frame.join")
    return None if decode is None or call is None else \
        ms_per(decode["s"], call["n"])
