"""Device busy time per join call outside the lookup kernel, in ms: the
row decode, the row-word relayout and the rest of the read site."""

from _shared import kernel_seconds


def read(ctx):
    tr, calls = ctx["trace"], ctx["raw"]["probe"]["calls"]
    k = kernel_seconds(ctx)
    if tr is None or k is None or not calls:
        return None
    return (tr.busy_s - k[0]) / calls * 1e3
