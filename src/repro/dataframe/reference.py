"""Pure-numpy oracles for the dataframe operators (tests + benchmarks)."""
from __future__ import annotations

import numpy as np


def ref_sort(data: dict, key: str) -> dict:
    order = np.argsort(data[key], kind="stable")
    return {k: np.asarray(v)[order] for k, v in data.items()}


def ref_join_inner(left: dict, right: dict, key: str) -> dict:
    """Inner join with duplicates, left-key-sorted output (matches
    ops_local.join_inner / ops_dist ordering after sorting)."""
    lk, rk = np.asarray(left[key]), np.asarray(right[key])
    l_order = np.argsort(lk, kind="stable")
    r_order = np.argsort(rk, kind="stable")
    # sorted probes: each binary search starts where the previous one ended
    lk_s, rk_s = lk[l_order], rk[r_order]
    lo = np.searchsorted(rk_s, lk_s, side="left")
    counts = np.searchsorted(rk_s, lk_s, side="right") - lo
    l_idx = np.repeat(l_order, counts)
    # pair j of sorted left row i takes right match lo[i] + (j - first pair)
    firsts = np.cumsum(counts) - counts
    r_idx = r_order[np.arange(len(l_idx)) + np.repeat(lo - firsts, counts)]
    out = {}
    for k, v in left.items():
        name = k if k == key else (f"l_{k}" if k in right else k)
        out[name] = np.asarray(v)[l_idx]
    for k, v in right.items():
        if k == key:
            continue
        name = f"r_{k}" if k in left else k
        out[name] = np.asarray(v)[r_idx]
    return out


def ref_groupby_sum(data: dict, key: str, value_cols) -> dict:
    keys = np.asarray(data[key])
    uniq, inv = np.unique(keys, return_inverse=True)
    out = {key: uniq}
    for vc in value_cols:
        v = np.asarray(data[vc])
        acc = np.zeros((len(uniq),) + v.shape[1:], v.dtype)
        np.add.at(acc, inv, v)
        out[vc] = acc
    return out


def sorted_rows(data: dict, keys=None) -> np.ndarray:
    """Canonical row ordering for set-equality comparisons."""
    names = keys or sorted(data)
    arr = np.stack([np.asarray(data[n]).astype(np.float64) for n in names], 1)
    order = np.lexsort(arr.T[::-1])
    return arr[order]
