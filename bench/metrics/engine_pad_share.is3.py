"""Share of the engine's batched lanes that are padding, in %: lanes the
bucket ladder adds to a batch over all lanes sent to the read site."""


def read(ctx):
    c = ctx["raw"]["counters"]
    lanes = c.get("padded_lanes", 0) + c.get("batched_keys", 0)
    return 100.0 * c["padded_lanes"] / lanes if lanes else None
