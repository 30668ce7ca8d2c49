"""Device time of the Pallas lookup kernel per read call (an engine
batch or a join call), in ms."""

from _shared import probe_device_ms as read  # noqa: F401
