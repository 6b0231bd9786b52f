"""Global sort of one table by its key: the task body, its plain reference,
its comparison and the bytes it has to move.  The op protocol's set-up,
operation, check and least time are the table ones of ``_tables``."""
from __future__ import annotations

import numpy as np

from chipbench.compare import by_key_range, pool, range_bounds, rows_mismatched
from chipbench.ops._tables import (check, input_shapes, least_time,  # noqa: F401
                                   operation, setup)

TASK = "dist_sort"


def build(mesh, config, on_overflow):
    """The operator, from the program's public factory."""
    from repro.dataframe import ops_dist as D
    return D.make_dist_sort(mesh, config["key"], slack=config["slack"],
                            on_overflow=on_overflow)


def payload(comm, tables, config):
    """One pilot task: build the operator, run it on the resident table and
    wait for the output."""
    import jax
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("chipbench/build"):
        fn = build(comm.mesh, config, "raise")
    with TraceAnnotation("chipbench/call"):
        out, overflow = fn(*tables)
        jax.block_until_ready(out)
    return out, overflow


def reference(tables: list, key: str) -> dict:
    """The rows in key order, rows of one key in their input order: each
    key range sorted apart, in host threads, and the ranges in order."""
    (table,) = tables
    with pool() as threads:
        parts = list(threads.map(
            lambda t: _sorted(t, key),
            by_key_range(table, key, range_bounds(table[key]), threads)))
    return {k: np.concatenate([p[k] for p in parts]) for k in table}


def _sorted(table: dict, key: str) -> dict:
    order = np.argsort(table[key], kind="stable")
    return {k: v[order] for k, v in table.items()}


def compare(got: dict, ref: dict, key: str) -> dict:
    """Keys in global order across the ranks, taken in rank order; the
    order among rows of one key is not part of the result, so rows are
    compared as a multiset."""
    k = got[key]
    return {"order_breaks": int(np.count_nonzero(k[1:] < k[:-1])),
            "rows_mismatched": rows_mismatched(got, ref, key)}


def least_bytes(rows_in: list, row_bytes_in: list, rows_out: int,
                row_bytes_out: int, chips: int) -> tuple:
    """Bytes one chip moves at the least in one sort, as ``(hbm, ici)``:
    its share of the table read once and of the output written once, and
    the share of its rows that belong on other chips when keys are spread
    evenly, sent once."""
    (rows,), (row_bytes,) = rows_in, row_bytes_in
    hbm = (rows * row_bytes + rows_out * row_bytes_out) / chips
    ici = rows * row_bytes / chips * (chips - 1) / chips
    return hbm, ici
