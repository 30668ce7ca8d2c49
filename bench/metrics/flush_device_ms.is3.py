"""Device time per run of the ring flush's program, in ms."""

from _shared import is_flush


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    s, n = tr.module_time(is_flush)
    return s / n * 1e3 if n else None
