"""Exact comparison of two row sets, independent of their order, and the
split of row sets by key range that lets the references and the comparison
work on the ranges in host threads.

Columns are compared by their bits, so a float that differs in its last
place, or a NaN, counts as a different row.  Every column must be 4 bytes
wide (int32, float32).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

RANGES = 16                     # key ranges a row set is split into
SAMPLE = 1 << 16                # keys sampled to place the range bounds


def pool() -> ThreadPoolExecutor:
    """Host threads for the key ranges (numpy's sorts, searches and
    gathers release the interpreter lock)."""
    return ThreadPoolExecutor(min(RANGES, os.cpu_count() or 1))


def range_bounds(keys: np.ndarray) -> np.ndarray:
    """Bounds that cut ``keys`` into up to ``RANGES`` ranges of about equal
    rows: quantiles of an evenly strided sample.  Rows of one key always
    fall in one range."""
    sample = np.sort(keys[::max(1, len(keys) // SAMPLE)])
    if not len(sample):
        return sample
    at = np.linspace(0, len(sample) - 1, RANGES + 1)[1:-1].astype(np.int64)
    return np.unique(sample[at])


def by_key_range(cols: dict, key: str, bounds: np.ndarray, threads) -> list:
    """The rows of ``cols`` split into the key ranges that ``bounds`` cut,
    in key order; within a range rows keep their order.  The work runs on
    ``threads`` (a :func:`pool`)."""
    keys = cols[key]
    part = np.empty(len(keys), np.uint8)

    def place(sl):
        part[sl] = np.searchsorted(bounds, keys[sl], side="right")

    step = -(-len(keys) // RANGES) or 1
    list(threads.map(place, [slice(a, a + step)
                             for a in range(0, len(keys), step)]))
    order = np.argsort(part, kind="stable")
    ends = np.cumsum(np.bincount(part, minlength=len(bounds) + 1))
    starts = np.concatenate([[0], ends[:-1]])
    moved = dict(zip(cols, threads.map(lambda v: v[order], cols.values())))
    return [{k: v[a:b] for k, v in moved.items()} for a, b in zip(starts, ends)]


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


def _mismatched(got: dict, ref: dict, names: list) -> int:
    n_got, n_ref = len(got[names[0]]), len(ref[names[0]])
    g = canonical([got[c] for c in names])
    r = canonical([ref[c] for c in names])
    n = min(n_got, n_ref)
    bad = np.zeros(n, bool)
    for x, y in zip(g, r, strict=True):
        bad |= x[:n] != y[:n]
    return int(bad.sum()) + abs(n_got - n_ref)


def rows_mismatched(got: dict, ref: dict, key: str) -> int:
    """Rows of ``got`` and ``ref`` that differ once both are in canonical
    order, plus the difference of their row counts, summed over the key
    ranges (the rows of one key lie in one range); 0 means the two are the
    same multiset of rows, bit for bit."""
    n_got, n_ref = len(got[key]), len(ref[key])
    if set(got) != set(ref):
        return max(n_got, n_ref)
    names = [key] + sorted(c for c in ref if c != key)
    bounds = range_bounds(ref[key])
    with pool() as threads:
        g, r = (by_key_range(t, key, bounds, threads) for t in (got, ref))
        return sum(threads.map(lambda a, b: _mismatched(a, b, names), g, r))
