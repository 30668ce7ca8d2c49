"""LDBC SNB Person_knows_Person at SF100, cut to 2^24 directed rows.

``make`` builds the table from a seed with vectorised numpy; the sizes,
the cut and every assumption are in ``snb_knows_sf100.json`` beside it.
The degree multiset is the same for every seed (quantiles of the degree
law); the seed chooses which person has which degree, how the stubs
pair up, the creation dates and the person ids.
"""

from __future__ import annotations

import numpy as np

from keyspace import Keyspace

DATE_LO = 1262304000000          # 2010-01-01T00:00:00Z, ms
DATE_HI = 1356998400000          # 2013-01-01T00:00:00Z, ms
EXPONENT = 2.3                   # pdf exponent of the degree law
CAP = 5000


def degrees(persons: int, rows: int) -> np.ndarray:
    """Degree sequence summing to ``rows``: Pareto quantiles, capped."""
    u = (np.arange(persons) + 0.5) / persons
    shape = u ** (-1.0 / (EXPONENT - 1.0))

    def total(dmin):
        return np.minimum(np.maximum(np.floor(dmin * shape), 1), CAP).sum()

    lo, hi = 1.0, float(CAP)
    for _ in range(60):                      # bisect the minimum degree
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if total(mid) < rows else (lo, mid)
    deg = np.minimum(np.maximum(np.floor(hi * shape), 1), CAP).astype(np.int64)
    extra = int(deg.sum()) - rows            # trim the excess off the top
    i = 0
    while extra > 0:
        take = min(extra, int(deg[i]) - 1)
        deg[i] -= take
        extra -= take
        i += 1
    deg[-1] -= extra                         # extra <= 0: top up the last
    return deg


def make(cfg: dict, seed: int) -> dict:
    persons, rows = int(cfg["persons"]), int(cfg["rows"])
    rng = np.random.default_rng(seed)
    ids = Keyspace(rng)
    deg = degrees(persons, rows)[rng.permutation(persons)]
    stubs = np.repeat(np.arange(persons), deg)[rng.permutation(rows)]
    u, v = stubs[:rows // 2], stubs[rows // 2:].copy()
    loops = np.flatnonzero(u == v)
    while loops.size:                        # re-pair self-loops by swaps
        for i, j in zip(loops.tolist(),
                        rng.integers(0, v.size, loops.size).tolist()):
            v[i], v[j] = v[j], v[i]
        loops = np.flatnonzero(u == v)
    dates = np.sort(rng.integers(DATE_LO, DATE_HI, rows // 2))
    src = np.stack([u, v], axis=1).reshape(-1)
    dst = np.stack([v, u], axis=1).reshape(-1)
    cols = {"src": ids(src), "dst": ids(dst),
            "creationDate": np.repeat(dates, 2)}
    return {"cols": cols, "read_keys": ids(np.arange(persons)),
            "next_date": int(dates[-1]) + 1}


def make_writes(cfg: dict, data: dict, rng, n: int) -> list:
    """``n`` INS8 "add friendship" writes: two rows each, both directions,
    between two distinct persons, dated after every stored row."""
    people = data["read_keys"]
    a = rng.integers(0, people.size, n)
    b = (a + rng.integers(1, people.size, n)) % people.size
    dates = data["next_date"] + np.arange(n, dtype=np.int64)
    data["next_date"] += n
    return [{"src": np.array([people[a[i]], people[b[i]]]),
             "dst": np.array([people[b[i]], people[a[i]]]),
             "creationDate": np.array([dates[i], dates[i]])}
            for i in range(n)]


def control_lookup(cfg: dict, ref, queries, versions, m: int):
    """The control: each key's oldest ``m`` stored rows and no appended
    ones, which breaks the stated order (newest first, ORDER BY
    creationDate DESC LIMIT 20) and the visibility of appends."""
    q = np.asarray(queries, np.int64)
    lo = np.searchsorted(ref.sorted_keys, q, "left")
    hi = np.searchsorted(ref.sorted_keys, q, "right")
    j = np.arange(m)[None, :]
    valid = j < (hi - lo)[:, None]
    at = ref.order[np.clip(hi[:, None] - 1 - j, 0, ref.n_base - 1)]
    cols = {n: np.where(valid, ref.base[n][at], 0).astype(ref.base[n].dtype)
            for n in ref.names}
    return cols, valid
