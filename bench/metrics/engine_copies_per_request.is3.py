"""Device-to-host copies per answered request (the ``engine.host_copies``
counter over ``engine.answered``)."""

from _program import counter


def read(ctx):
    copies = counter("engine.host_copies")
    answered = counter("engine.answered")
    return None if copies is None or not answered else copies / answered
