"""Real keys per engine batch over the window."""


def read(ctx):
    c = ctx["raw"]["counters"]
    return c["batched_keys"] / c["batches"] if c.get("batches") else None
