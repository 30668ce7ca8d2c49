"""Seeded bijections onto distinct int64 keys (copied from the chip smoke)."""

from __future__ import annotations

import numpy as np


class Keyspace:
    """Seeded bijection from ids to distinct int64 keys: id ranges never
    collide, and no id maps to the EMPTY_KEY sentinel (int64 min) or 0."""

    def __init__(self, rng):
        self.mult = np.uint64(int(rng.integers(1 << 61)) * 2 + 1)

    def __call__(self, ids) -> np.ndarray:
        with np.errstate(over="ignore"):
            x = (np.asarray(ids, np.uint64) + np.uint64(1)) * self.mult
        return x.view(np.int64)
