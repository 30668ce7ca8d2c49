"""TPC-DS ``customer`` at SF300, probed by ``store_sales`` rows.

``make`` builds all 18 columns from a seed with vectorised numpy; the
sizes and every assumption are in ``tpcds_customer_sf300.json`` beside
it.  String columns hold the 64-bit FNV-1a hash of their blank-padded
``char(n)`` bytes, computed here over byte matrices.
"""

from __future__ import annotations

import numpy as np

EMPTY_KEY = np.iinfo(np.int64).min
FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(0x100000001B3)
SALUTATIONS = [b"Mr.", b"Mrs.", b"Ms.", b"Miss", b"Sir", b"Dr."]
VOCAB_SEED = 20010101           # the vocabularies are fixed, not seeded


def fnv1a64(mat: np.ndarray) -> np.ndarray:
    """FNV-1a 64 of each row of a ``[N, w]`` uint8 byte matrix -> int64."""
    cols = np.ascontiguousarray(mat.T)          # one contiguous row a byte
    h = np.full(mat.shape[0], FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for byte in cols:
            np.bitwise_xor(h, byte, out=h)
            np.multiply(h, FNV_PRIME, out=h)
    return h.view(np.int64)


def byte_matrix(strings: np.ndarray, width: int, pad: bytes = b" "):
    """Fixed-width ``S`` strings -> ``[N, width]`` uint8, blank-padded."""
    s = np.asarray(strings, f"S{width}")
    mat = s.view(np.uint8).reshape(s.size, width).copy()
    mat[mat == 0] = pad[0]
    return mat


def char_hash(strings, width: int) -> np.ndarray:
    return fnv1a64(byte_matrix(strings, width))


def varchar_hash(strings) -> np.ndarray:
    """Unpadded: hash each string's own bytes (rows of equal length)."""
    s = np.asarray(strings, "S")
    out = np.empty(s.size, np.int64)
    lens = np.strings.str_len(s)
    for n in np.unique(lens):
        at = lens == n
        out[at] = fnv1a64(s[at].astype(f"S{max(n, 1)}").view(np.uint8)
                          .reshape(-1, max(n, 1))[:, :n])
    return out


def vocab(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` distinct capitalised words of ``lo..hi`` letters."""
    lens = rng.integers(lo, hi + 1, n)
    letters = rng.integers(ord("a"), ord("z") + 1, (n, hi)).astype(np.uint8)
    letters[:, 0] -= 32
    letters[np.arange(hi)[None, :] >= lens[:, None]] = 0
    words = letters.view(f"S{hi}").reshape(n)
    words = np.unique(words)
    return words


def make(cfg: dict, seed: int) -> dict:
    n = int(cfg["rows"])
    sz = cfg["sizes"]
    voc = np.random.default_rng(VOCAB_SEED)
    first, last = vocab(voc, 5000, 3, 11), vocab(voc, 5000, 3, 14)
    countries, domains = vocab(voc, 212, 4, 18), vocab(voc, 10, 3, 8)
    rng = np.random.default_rng(seed)
    sk = np.arange(1, n + 1, dtype=np.int64)
    digits = (sk[:, None] >> (4 * np.arange(7, -1, -1))) & 15
    cid = np.concatenate([np.full((n, 8), ord("A"), np.uint8),
                          (digits + ord("A")).astype(np.uint8)], axis=1)
    fi = rng.integers(0, first.size, n)
    li = rng.integers(0, last.size, n)
    email = np.strings.add(np.strings.add(np.strings.add(
        first[fi], b"."), np.strings.add(last[li], b"@")),
        np.strings.add(domains[rng.integers(0, domains.size, n)], b".com"))
    d0, d1 = sz["date_sk_range"]
    r0, r1 = sz["review_date_sk_range"]

    def ids(hi):
        return rng.integers(1, hi + 1, n).astype(np.int64)

    cols = {
        "c_customer_sk": sk,
        "c_customer_id": fnv1a64(cid),
        "c_current_cdemo_sk": ids(sz["customer_demographics"]),
        "c_current_hdemo_sk": ids(sz["household_demographics"]),
        "c_current_addr_sk": ids(sz["customer_address"]),
        "c_first_shipto_date_sk": rng.integers(d0, d1 + 1, n),
        "c_first_sales_date_sk": rng.integers(d0, d1 + 1, n),
        "c_salutation": char_hash(SALUTATIONS, 10)[
            rng.integers(0, len(SALUTATIONS), n)],
        "c_first_name": char_hash(first, 20)[fi],
        "c_last_name": char_hash(last, 30)[li],
        "c_preferred_cust_flag": char_hash([b"N", b"Y"], 1)[
            rng.integers(0, 2, n)],
        "c_birth_day": rng.integers(1, 29, n).astype(np.int32),
        "c_birth_month": rng.integers(1, 13, n).astype(np.int32),
        "c_birth_year": rng.integers(1924, 1993, n).astype(np.int32),
        "c_birth_country": varchar_hash(countries)[
            rng.integers(0, countries.size, n)],
        "c_login": np.full(n, char_hash([b""], 13)[0]),
        "c_email_address": char_hash(email, 50),
        "c_last_review_date_sk": rng.integers(r0, r1 + 1, n),
    }
    cols = {k: np.asarray(v, np.int32 if t == "int32" else np.int64)
            for (k, t), v in zip(cfg["schema"], (cols[c] for c, _ in
                                                 cfg["schema"]))}
    return {"cols": cols, "read_keys": sk}


def make_probe_blocks(cfg: dict, data: dict, rng, blocks: int,
                      rows: int) -> list:
    """``store_sales`` probe blocks: ``ss_customer_sk`` uniform over the
    customers with the NULL share probing as misses, a ticket number and
    the net paid."""
    n = data["read_keys"].size
    out, ticket = [], 1
    for _ in range(blocks):
        sk = rng.integers(1, n + 1, rows).astype(np.int64)
        sk[rng.random(rows) < cfg["probe"]["null_share"]] = EMPTY_KEY
        out.append({
            "ss_customer_sk": sk,
            "ss_ticket_number": ticket + np.arange(rows, dtype=np.int64),
            "ss_net_paid": rng.integers(0, 2_000_000, rows).astype(np.int32),
        })
        ticket += rows
    return out


def control_lookup(cfg: dict, ref, queries, versions, m: int):
    """The control: string hashes cut to their low 32 bits, which breaks
    the guarantee that every column is exact at its declared width."""
    cols, valid = ref.lookup(queries, versions, m)
    for name in cfg["strings"]:
        cols[name] = cols[name] & np.int64(0xFFFFFFFF)
    return cols, valid
