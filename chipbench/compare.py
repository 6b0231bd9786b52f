"""Exact comparison of two row sets, independent of their order.

Columns are compared by their bits, so a float that differs in its last
place, or a NaN, counts as a different row.  Every column must be 4 bytes
wide (int32, float32).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _bits(col) -> np.ndarray:
    col = np.ascontiguousarray(col)
    if col.dtype.itemsize != 4:
        raise ValueError(f"only 4-byte columns are compared, got {col.dtype}")
    return col.view(np.uint32).astype(np.uint64)


def canonical(cols: list) -> list:
    """A form of the rows ``zip(*cols)`` that two equal multisets share
    exactly: the sorted pairs of the first two columns, then, for each
    further column, the sorted pairs of (rank of the rows so far, column)."""
    u = [_bits(c) for c in cols]
    key = (u[0] << np.uint64(32)) | u[1]
    out, perm = [], None
    for nxt in u[2:]:
        order = np.argsort(key)
        key = key[order]
        perm = order if perm is None else perm[order]
        out.append(key)
        run = np.zeros(len(key), np.uint64)
        np.cumsum(key[1:] != key[:-1], out=run[1:])
        key = (run << np.uint64(32)) | nxt[perm]
    out.append(np.sort(key))
    return out


def rows_mismatched(got: dict, ref: dict, key: str) -> int:
    """Rows of ``got`` and ``ref`` that differ once both are in canonical
    order, plus the difference of their row counts; 0 means the two are the
    same multiset of rows, bit for bit."""
    n_got, n_ref = len(got[key]), len(ref[key])
    if set(got) != set(ref):
        return max(n_got, n_ref)
    names = [key] + sorted(c for c in ref if c != key)
    with ThreadPoolExecutor(2) as pool:
        g, r = pool.map(canonical, ([got[c] for c in names],
                                    [ref[c] for c in names]))
    n = min(n_got, n_ref)
    bad = np.zeros(n, bool)
    for x, y in zip(g, r, strict=True):
        bad |= x[:n] != y[:n]
    return int(bad.sum()) + abs(n_got - n_ref)
