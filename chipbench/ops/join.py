"""Inner join of two tables: the task body, its plain reference, its
comparison and the bytes it has to move."""
from __future__ import annotations

import numpy as np

from chipbench.compare import rows_mismatched

TASK = "dist_join"


def build(mesh, config, on_overflow):
    """The operator, from the program's public factory."""
    from repro.dataframe import ops_dist as D
    return D.make_dist_join(mesh, config["key"], slack=config["slack"],
                            out_factor=config["out_factor"],
                            on_overflow=on_overflow)


def payload(comm, tables, config):
    """One pilot task: build the operator, run it on the resident tables
    and wait for the output."""
    import jax
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("chipbench/build"):
        fn = build(comm.mesh, config, "raise")
    with TraceAnnotation("chipbench/call"):
        out, overflow = fn(*tables)
        jax.block_until_ready(out)
    return out, overflow


def reference(tables: list, key: str) -> dict:
    """Every pair of a left and a right row with equal keys.  A left
    column keeps its name, a right one too unless the left has it, when
    the two become ``l_<name>`` and ``r_<name>``."""
    left, right = tables
    lk, rk = left[key], right[key]
    l_order, r_order = np.argsort(lk), np.argsort(rk)
    lk_s, rk_s = lk[l_order], rk[r_order]
    lo = np.searchsorted(rk_s, lk_s, side="left")
    counts = np.searchsorted(rk_s, lk_s, side="right") - lo
    l_idx = np.repeat(l_order, counts)
    # pair j of sorted left row i takes right match lo[i] + (j - first pair)
    firsts = np.cumsum(counts) - counts
    r_idx = r_order[np.arange(len(l_idx)) + np.repeat(lo - firsts, counts)]
    out = {}
    for k, v in left.items():
        out[k if k == key or k not in right else f"l_{k}"] = v[l_idx]
    for k, v in right.items():
        if k != key:
            out[f"r_{k}" if k in left else k] = v[r_idx]
    return out


def compare(got: dict, ref: dict, key: str) -> dict:
    """The join's output order is not part of its result: rows are
    compared as a multiset."""
    return {"rows_mismatched": rows_mismatched(got, ref, key)}


def least_bytes(rows_in: list, row_bytes_in: list, rows_out: int,
                row_bytes_out: int, chips: int) -> tuple:
    """Bytes one chip moves at the least in one join, as ``(hbm, ici)``:
    its share of the inputs read once and of the output written once, and
    the share of its input rows that a uniform hash sends to the other
    chips, sent once."""
    read = sum(r * b for r, b in zip(rows_in, row_bytes_in, strict=True))
    hbm = (read + rows_out * row_bytes_out) / chips
    ici = read / chips * (chips - 1) / chips
    return hbm, ici
