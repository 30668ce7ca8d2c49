"""Seconds from process start to the opening of the window: making the
data, building the index, warming every shape the window uses."""


def read(ctx):
    return ctx["setup_s"]
