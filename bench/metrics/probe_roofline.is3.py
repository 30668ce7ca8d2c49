"""The lookup kernel's share of its HBM roofline, in %: the bytes the
probe and chain walk must move at 819 GB/s over the kernel's time."""

from _shared import probe_roofline as read  # noqa: F401
