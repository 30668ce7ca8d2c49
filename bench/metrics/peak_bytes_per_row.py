"""Peak device bytes in use on the fullest chip after the window, per
stored row (base plus appended): how many rows fit on a chip."""


def read(ctx):
    if not ctx["peak_bytes"] or not ctx["rows_stored"]:
        return None
    return ctx["peak_bytes"] / ctx["rows_stored"]
