"""Cylon 'local operators': run on locally resident data only.
All static-shape: outputs are (capacity,)-padded with explicit nrows and an
overflow flag where the logical result size is data-dependent (join).

Each phase runs under a ``jax.named_scope`` (``argsort``, ``permute``,
``search``, ``gather``), which names its operations in the compiled
program's metadata and leaves the computation as it is.  The join's
``search`` merges the two sorted key arrays by one stable sort and expands
each left row's matches into output slots by scatter-adds and running
sums: it has no binary search, whose every step gathers one value for each
row.
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
    l_/r_ on name collision.  Output order: left-key sorted, stable; each
    left row's right matches in stable key order.
    """
    ls = sort_by(left, key)
    rs = sort_by(right, key)
    with jax.named_scope("search"):
        li, ri, total = _match_pairs(masked_key(ls, key), masked_key(rs, key),
                                     ls.nrows, rs.nrows, out_capacity)
        valid_out = jnp.arange(out_capacity) < jnp.minimum(total, out_capacity)
        li_g = jnp.where(valid_out, li, 0)
        ri_g = jnp.where(valid_out, ri, 0)

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


def _match_pairs(lk, rk, l_nrows, r_nrows, out_capacity):
    """Output slot j's left row ``li`` and right row ``ri``, for j below
    the number of matching pairs, which is returned as well.  ``lk`` and
    ``rk`` are sorted keys; rows from ``l_nrows`` and ``r_nrows`` on are
    padding.

    One stable sort of the right keys followed by the left ones merges
    them: every right row comes before the left rows of its key, and left
    rows keep their order.  A running count of valid right rows then reads
    each left row's matches ``[lo, hi)``: ``hi`` at the row, ``lo`` where
    its key's run begins.  A running sum of the counts gives each left
    row's first output slot, ``starts``.

    Slot j belongs to the last row whose first slot is at most j, and
    ``ri = j + lo - starts`` of that row.  Both are running sums over the
    slots of steps added at each row's first slot: 1 at a left row for
    ``li``, and the change in ``lo - starts`` from the row before for
    ``ri``.  Right rows and left rows without matches share the next
    row's first slot, so their steps add up to that row's values.
    """
    r_cap = rk.shape[0]
    keys, pos = jax.lax.sort(
        (jnp.concatenate([rk, lk]),
         jnp.arange(r_cap + lk.shape[0], dtype=jnp.int32)), is_stable=True)
    is_left = pos >= r_cap
    valid_right = (pos < r_nrows).astype(jnp.int32)
    hi = jnp.cumsum(valid_right)
    run_start = jnp.concatenate([jnp.ones(1, bool), keys[1:] != keys[:-1]])
    lo = jax.lax.cummax(jnp.where(run_start, hi - valid_right, 0))
    counts = jnp.where(is_left & (pos - r_cap < l_nrows), hi - lo, 0)
    ends = jnp.cumsum(counts)
    starts = ends - counts

    def running(step):
        slots = jnp.zeros(out_capacity, step.dtype).at[starts].add(
            step, mode="drop", indices_are_sorted=True)
        return jnp.cumsum(slots)

    li = running(is_left.astype(jnp.int32)) - 1
    ri = running(jnp.diff(lo - starts, prepend=0))
    return li, ri + jnp.arange(out_capacity, dtype=ri.dtype), ends[-1]


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
