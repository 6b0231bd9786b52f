"""Cylon 'local operators': run on locally resident data only.
All static-shape: outputs are (capacity,)-padded with explicit nrows and an
overflow flag where the logical result size is data-dependent (join).

Each phase runs under a ``jax.named_scope`` (``argsort``, ``permute``,
``search``, ``gather``), which names its operations in the compiled
program's metadata and leaves the computation as it is.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dataframe.table import Table, key_sentinel

_HASH_MULT = jnp.uint32(2654435761)


def hash_key(key: jnp.ndarray) -> jnp.ndarray:
    """Knuth multiplicative hash -> uint32 (partitioner + hash-join)."""
    k = key.astype(jnp.uint32)
    h = (k * _HASH_MULT) ^ (k >> 16)
    return h * _HASH_MULT


def masked_key(table: Table, key: str) -> jnp.ndarray:
    col = table.columns[key]
    return jnp.where(table.valid_mask(), col, key_sentinel(col.dtype))


def sort_by(table: Table, key: str) -> Table:
    """Stable local sort by key; invalid rows stay at the end."""
    with jax.named_scope("argsort"):
        order = jnp.argsort(masked_key(table, key), stable=True)
    return Table(columns=permute(table.columns, order), nrows=table.nrows)


def permute(columns: dict, order) -> dict:
    """Every column taken in ``order``."""
    with jax.named_scope("permute"):
        return {k: v[order] for k, v in columns.items()}


def filter_rows(table: Table, keep: jnp.ndarray) -> Table:
    """Compact rows where keep & valid (stable)."""
    keep = keep & table.valid_mask()
    with jax.named_scope("argsort"):
        order = jnp.argsort(~keep, stable=True)  # kept rows first, stable
    return Table(columns=permute(table.columns, order),
                 nrows=jnp.sum(keep).astype(jnp.int32))


def project(table: Table, names) -> Table:
    return Table(columns={k: table.columns[k] for k in names},
                 nrows=table.nrows)


def concat(a: Table, b: Table, capacity: int) -> Table:
    """Concatenate valid rows of a and b into a new padded table."""
    an, bn = a.nrows, b.nrows
    cols = {}
    for k in a.columns:
        va, vb = a.columns[k], b.columns[k]
        buf = jnp.zeros((capacity,) + va.shape[1:], va.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(buf, va, 0, axis=0)
        # place b's rows starting at a.nrows via scatter
        idx = jnp.arange(vb.shape[0]) + an
        idx = jnp.where(jnp.arange(vb.shape[0]) < bn, idx, capacity)
        buf = buf.at[idx].set(vb, mode="drop")
        cols[k] = buf
    return Table(columns=cols, nrows=(an + bn).astype(jnp.int32))


def join_inner(left: Table, right: Table, key: str, out_capacity: int):
    """Sort-merge inner join with duplicate keys.

    Returns (Table, overflow: bool array).  Non-key columns are prefixed
    l_/r_ on name collision.  Output order: left-key sorted, stable.
    """
    ls = sort_by(left, key)
    rs = sort_by(right, key)
    with jax.named_scope("search"):
        lk = masked_key(ls, key)
        rk = masked_key(rs, key)
        lo = jnp.searchsorted(rk, lk, side="left")
        hi = jnp.searchsorted(rk, lk, side="right")
        # clamp matches against invalid right rows
        hi = jnp.minimum(hi, rs.nrows)
        lo = jnp.minimum(lo, rs.nrows)
        counts = jnp.where(ls.valid_mask(), hi - lo, 0)
        ends = jnp.cumsum(counts)
        total = ends[-1]
        starts = ends - counts

        out_idx = jnp.arange(out_capacity)
        li = jnp.searchsorted(ends, out_idx, side="right")  # left row of pair j
        li_c = jnp.minimum(li, ls.capacity - 1)
        ri = lo[li_c] + (out_idx - starts[li_c])
        valid_out = out_idx < jnp.minimum(total, out_capacity)
        li_g = jnp.where(valid_out, li_c, 0)
        ri_g = jnp.where(valid_out, jnp.minimum(ri, rs.capacity - 1), 0)

    cols = {}
    with jax.named_scope("gather"):
        for k, v in ls.columns.items():
            name = k if k == key else (f"l_{k}" if k in rs.columns else k)
            cols[name] = jnp.where(
                _expand(valid_out, v.ndim), v[li_g], jnp.zeros_like(v[li_g]))
        for k, v in rs.columns.items():
            if k == key:
                continue
            name = f"r_{k}" if k in ls.columns else k
            cols[name] = jnp.where(
                _expand(valid_out, v.ndim), v[ri_g], jnp.zeros_like(v[ri_g]))
    out = Table(columns=cols,
                nrows=jnp.minimum(total, out_capacity).astype(jnp.int32))
    return out, total > out_capacity


def _expand(mask, ndim):
    return mask.reshape(mask.shape + (1,) * (ndim - 1))


def groupby_sum(table: Table, key: str, value_cols) -> Table:
    """Sum value_cols per key.  Output: unique keys (padded) + sums."""
    ts = sort_by(table, key)
    k = masked_key(ts, key)
    valid = ts.valid_mask()
    is_start = valid & ((jnp.arange(ts.capacity) == 0) | (k != jnp.roll(k, 1)))
    seg_ids = jnp.cumsum(is_start) - 1            # group index per row
    n_groups = jnp.sum(is_start).astype(jnp.int32)
    cap = ts.capacity
    cols = {}
    # representative key per group
    first_pos = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(is_start, seg_ids, cap)].set(jnp.arange(cap), mode="drop")
    cols[key] = jnp.where(jnp.arange(cap) < n_groups,
                          ts.columns[key][first_pos], 0)
    for vc in value_cols:
        v = jnp.where(_expand(valid, ts.columns[vc].ndim), ts.columns[vc], 0)
        seg = jnp.where(valid, seg_ids, cap)
        summed = jnp.zeros((cap,) + v.shape[1:], v.dtype).at[seg].add(
            v, mode="drop")
        cols[vc] = summed
    return Table(columns=cols, nrows=n_groups)
