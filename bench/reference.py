"""The plain reference: keyed rows, newest first, at an MVCC version.

A straightforward numpy implementation of the read contract the program
under test serves, written without it: the rows of a key are its base
rows plus the rows appended later, ordered newest first (a later row is
newer), and an appended row is visible at version ``v`` when the write
that carried it landed at a version ``<= v``.  A lookup returns the
first ``m`` visible rows of each query key, with a valid mask; the
``EMPTY_KEY`` sentinel (int64 min, a NULL probe key) never matches.

It generalises the chip smoke's reference from ``(k, v)`` rows to whole
rows of any schema, and answers a batch of queries with array
operations instead of a Python loop per query.
"""

from __future__ import annotations

import numpy as np

EMPTY_KEY = np.iinfo(np.int64).min


class Reference:
    """Base rows plus versioned appends; ``lookup`` answers at versions."""

    def __init__(self, key: str, cols: dict):
        self.key = key
        self.names = tuple(cols)
        self.base = {n: np.asarray(v) for n, v in cols.items()}
        keys = self.base[key].astype(np.int64)
        self.n_base = keys.size
        self.order = np.lexsort((-np.arange(keys.size), keys))
        self.sorted_keys = keys[self.order]
        self._groups: list = []          # (cols, version) in landing order
        self._app = None

    def append(self, cols: dict, version: int):
        """Rows that landed at ``version`` (later calls are newer)."""
        self._groups.append(({n: np.asarray(cols[n]) for n in self.names},
                             int(version)))
        self._app = None

    def _appended(self):
        if self._app is None:
            if self._groups:
                cols = {n: np.concatenate([c[n] for c, _ in self._groups])
                        for n in self.names}
                ver = np.concatenate([np.full(c[self.key].size, v, np.int64)
                                      for c, v in self._groups])
            else:
                cols = {n: self.base[n][:0] for n in self.names}
                ver = np.zeros(0, np.int64)
            keys = cols[self.key].astype(np.int64)
            order = np.lexsort((-np.arange(keys.size), keys))
            self._app = (cols, order, keys[order], ver[order])
        return self._app

    def lookup(self, queries, versions, m: int):
        """``(cols {name: [Q, m]}, valid [Q, m])`` for each query key at its
        version (a scalar or one per query)."""
        q = np.asarray(queries, np.int64).reshape(-1)
        ver = np.broadcast_to(np.asarray(versions, np.int64), q.shape)
        app_cols, app_order, app_keys, app_ver = self._appended()
        lo = np.searchsorted(self.sorted_keys, q, "left")
        nbase = np.searchsorted(self.sorted_keys, q, "right") - lo
        alo = np.searchsorted(app_keys, q, "left")
        ahi = np.searchsorted(app_keys, q, "right")
        # newest first, a key's landing versions never rise: the rows not
        # yet visible at a version are a prefix of its appended block
        hidden = np.zeros(q.shape, np.int64)
        longest = int((ahi - alo).max()) if q.size else 0
        last = max(app_keys.size - 1, 0)
        for j in range(longest):
            at = alo + j
            hidden += (at < ahi) & (app_ver[np.minimum(at, last)] > ver)
        napp = ahi - alo - hidden
        start = alo + hidden
        live = q != EMPTY_KEY
        j = np.arange(m)[None, :]
        take_app = (j < napp[:, None]) & live[:, None]
        bj = j - napp[:, None]
        take_base = ~take_app & (bj < nbase[:, None]) & live[:, None]
        base_at = self.order[np.clip(lo[:, None] + bj, 0, self.n_base - 1)]
        app_at = app_order[np.clip(start[:, None] + j, 0, last)] \
            if app_keys.size else np.zeros_like(base_at)
        valid = take_app | take_base
        out = {}
        for n in self.names:
            col = np.where(take_app, app_cols[n][app_at] if app_keys.size
                           else 0, self.base[n][base_at])
            out[n] = np.where(valid, col, 0).astype(self.base[n].dtype)
        return out, valid


def bits(a) -> np.ndarray:
    """The bit pattern of an array, as unsigned integers of its width."""
    a = np.ascontiguousarray(a)
    return a.view(f"u{a.dtype.itemsize}")


def mismatches(got_cols: dict, got_valid, want_cols: dict, want_valid,
               names) -> np.ndarray:
    """Per query: does the answer differ from the reference?  The valid
    mask, and the bit pattern of every named column on valid lanes."""
    got_valid = np.asarray(got_valid, bool)
    bad = (got_valid != want_valid).any(axis=1)
    for n in names:
        want = want_cols[n]
        got = np.where(got_valid, np.asarray(got_cols[n]).astype(want.dtype),
                       0).astype(want.dtype)
        bad |= (bits(got) != bits(np.where(got_valid, want, 0)
                                  .astype(want.dtype))).any(axis=1)
    return bad
