"""Probe keys answered over the whole window, per second."""


def read(ctx):
    raw = ctx["raw"]
    if not raw.get("keys"):
        return None
    return raw["keys"] / raw["window_s"]
