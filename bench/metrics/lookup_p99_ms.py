"""99th percentile over every lookup of the window, from its scheduled
arrival to the moment the client holds its answer, in ms."""

from _shared import p99_ms


def read(ctx):
    return p99_ms(ctx["raw"].get("lookup_latency_s", ()))
