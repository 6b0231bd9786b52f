"""Cylon 'distributed operators': BSP SPMD programs under shard_map.

Each operator is built for a Communicator (the private per-task mesh the
runtime delivers) and runs as one jit'd shard_map program over the 'df' axis:

  * shuffle       — hash/range repartition rows via all_to_all
  * dist_sort     — sample sort: local sort -> splitter all_gather -> range
                    shuffle -> local sort  (globally sorted across ranks)
  * dist_join     — hash-shuffle both sides, local sort-merge inner join
  * dist_groupby  — hash shuffle + local segmented sum

Static shapes: every rank holds (capacity,) padded columns + nrows.  Send
buffers have per-destination capacity slack; overflow is detected and
reported (overflow flag), never silently dropped.

Phases run under named scopes that the compiled program's metadata carries:
``pack`` and ``splitters`` here, ``exchange`` in :mod:`comm`, ``argsort``,
``permute``, ``search`` and ``gather`` in :mod:`ops_local`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dataframe import comm
from repro.dataframe import ops_local as L
from repro.dataframe.table import Table


class ShuffleOverflow(RuntimeError):
    """A shuffle dropped rows: some rank's per-destination row count
    exceeded its send-buffer capacity (``counts > send_cap``).  Carries the
    structured context callers need to retry with more slack — or to switch
    to the out-of-core path (``repro.dataframe.shuffle``), which has no
    fixed send capacity at all."""

    def __init__(self, op: str, slack: float):
        self.op = op
        self.slack = slack
        super().__init__(
            f"{op}: send buffer overflow (some rank's rows for one "
            f"destination exceeded capacity * slack / n_parts with "
            f"slack={slack}); retry with a larger slack= or use the "
            f"out-of-core shuffle (repro.dataframe.shuffle)")


def _checked(fn, op: str, slack: float, on_overflow: str):
    """Wrap a jitted ``(table, ovf)`` op: ``on_overflow="return"`` keeps the
    legacy pass-through; ``"raise"`` turns a True overflow flag into a
    :class:`ShuffleOverflow` so it can never be silently dropped."""
    if on_overflow not in ("return", "raise"):
        raise ValueError(f"on_overflow={on_overflow!r} "
                         "(expected 'return' or 'raise')")
    if on_overflow == "return":
        return fn

    def wrapped(*args):
        out, ovf = fn(*args)
        if bool(ovf):
            raise ShuffleOverflow(op, slack)
        return out, ovf

    return wrapped


def _unit_nrows(t: Table) -> Table:
    """Inside shard_map each rank's nrows must be rank-1 (length 1) so the
    out_specs concatenation over the df axis yields a (P,) vector outside."""
    return Table(columns=t.columns, nrows=t.nrows.reshape(1).astype(jnp.int32))


def _table_spec(axis: str):
    # columns sharded on rows over the df axis; nrows is per-rank (one scalar
    # per shard stored as a (P,) vector)
    return P(axis)


# ---------------------------------------------------------------------------
# shuffle
# ---------------------------------------------------------------------------
def _local_shuffle_pack(table: Table, target, n_parts: int, send_cap: int):
    """Pack rows into a (P, send_cap, ...) send buffer by destination."""
    with jax.named_scope("pack"):
        cap = table.capacity
        valid = table.valid_mask()
        tgt = jnp.where(valid, target, n_parts)          # invalid -> dropped
        order = jnp.argsort(jnp.where(valid, tgt, n_parts), stable=True)
        sorted_t = tgt[order]
        start = jnp.searchsorted(sorted_t, jnp.arange(n_parts), side="left")
        pos_sorted = jnp.arange(cap) - start[jnp.minimum(sorted_t, n_parts - 1)]
        pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
        counts = jnp.bincount(jnp.where(valid, tgt, n_parts), length=n_parts + 1)[:n_parts]
        overflow = jnp.any(counts > send_cap)

        bufs = {}
        row_ok = valid & (pos < send_cap)
        e = jnp.where(row_ok, tgt, n_parts)
        pp = jnp.where(row_ok, pos, 0)
        for k, v in table.columns.items():
            buf = jnp.zeros((n_parts, send_cap) + v.shape[1:], v.dtype)
            bufs[k] = buf.at[e, pp].set(v, mode="drop")
        sent = jnp.minimum(counts, send_cap).astype(jnp.int32)  # (P,) rows per dest
        return bufs, sent, overflow


def _shuffle_inside(table: Table, target, axis: str, slack: float):
    """Runs INSIDE shard_map. Returns (Table with capacity P*send_cap, overflow)."""
    n_parts = comm.axis_size(axis)
    send_cap = int(table.capacity * slack) // n_parts + 8
    bufs, sent, overflow = _local_shuffle_pack(table, target, n_parts, send_cap)
    recv = {k: comm.all_to_all(v, axis) for k, v in bufs.items()}   # (P, send_cap, ...)
    recv_counts = comm.all_to_all(sent.reshape(-1, 1), axis)[:, 0]  # (P,)
    # compact: rows arrive as P blocks with per-block validity
    pos_in_block = jnp.arange(send_cap)[None, :]
    rvalid = (pos_in_block < recv_counts[:, None]).reshape(-1)
    cols = {k: v.reshape((-1,) + v.shape[2:]) for k, v in recv.items()}
    # received rows are scattered across P blocks — mark ALL slots valid, then
    # compact by the true receive mask
    out = Table(columns=cols,
                nrows=jnp.asarray(rvalid.shape[0], jnp.int32))
    out = L.filter_rows(out, rvalid)
    return out, comm.psum(overflow.astype(jnp.int32), axis) > 0


def make_shuffle(mesh, axis: str = "df", slack: float = 2.0,
                 on_overflow: str = "return"):
    """Returns a jit'd shuffle(table, target) over the given mesh.
    ``on_overflow="raise"`` turns a dropped-rows overflow into a
    :class:`ShuffleOverflow` instead of a flag callers may ignore."""
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, P()),
             check_vma=False)
    def _shuf(table, target):
        out, ovf = _shuffle_inside(table, target, axis, slack)
        return _unit_nrows(out), ovf

    return _checked(jax.jit(_shuf), "shuffle", slack, on_overflow)


# ---------------------------------------------------------------------------
# distributed sample sort
# ---------------------------------------------------------------------------
def _dist_sort_inside(table: Table, key: str, axis: str, slack: float):
    n_parts = comm.axis_size(axis)
    ts = L.sort_by(table, key)
    # sample n_parts values per rank at even quantiles of the VALID rows
    with jax.named_scope("splitters"):
        q = (jnp.arange(n_parts) + 0.5) / n_parts
        idx = jnp.clip((q * jnp.maximum(ts.nrows, 1)).astype(jnp.int32), 0,
                       table.capacity - 1)
        samples = ts.columns[key][idx]                       # (P,)
        all_samples = comm.all_gather(samples, axis).reshape(-1)  # (P*P,)
        ssorted = jnp.sort(all_samples)
        splitters = ssorted[(jnp.arange(1, n_parts) * n_parts)]   # (P-1,)
        target = jnp.searchsorted(splitters, ts.columns[key], side="right")
        target = jnp.where(ts.valid_mask(), target.astype(jnp.int32), 0)
    shuffled, ovf = _shuffle_inside(ts, target, axis, slack)
    return L.sort_by(shuffled, key), ovf


def make_dist_sort(mesh, key: str, axis: str = "df", slack: float = 2.0,
                   on_overflow: str = "return"):
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
             check_vma=False)
    def _sort(table):
        out, ovf = _dist_sort_inside(table, key, axis, slack)
        return _unit_nrows(out), ovf

    return _checked(jax.jit(_sort), "dist_sort", slack, on_overflow)


# ---------------------------------------------------------------------------
# distributed hash join
# ---------------------------------------------------------------------------
def _dist_join_inside(left: Table, right: Table, key: str, axis: str,
                      slack: float, out_factor: float):
    n_parts = comm.axis_size(axis)

    def hash_target(t):
        h = (L.hash_key(t.columns[key]) % jnp.uint32(n_parts)).astype(jnp.int32)
        return jnp.where(t.valid_mask(), h, 0)

    ls, ovl = _shuffle_inside(left, hash_target(left), axis, slack)
    rs, ovr = _shuffle_inside(right, hash_target(right), axis, slack)
    out_cap = int(max(left.capacity, right.capacity) * out_factor)
    joined, ovj = L.join_inner(ls, rs, key, out_cap)
    ovf = ovl | ovr | (comm.psum(ovj.astype(jnp.int32), axis) > 0)
    return joined, ovf


def make_dist_join(mesh, key: str, axis: str = "df", slack: float = 2.0,
                   out_factor: float = 2.0, on_overflow: str = "return"):
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec),
             out_specs=(spec, P()), check_vma=False)
    def _join(left, right):
        out, ovf = _dist_join_inside(left, right, key, axis, slack, out_factor)
        return _unit_nrows(out), ovf

    return _checked(jax.jit(_join), "dist_join", slack, on_overflow)


# ---------------------------------------------------------------------------
# distributed groupby-sum
# ---------------------------------------------------------------------------
def make_dist_groupby_sum(mesh, key: str, value_cols, axis: str = "df",
                          slack: float = 2.0, on_overflow: str = "return"):
    spec = P(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
             check_vma=False)
    def _gb(table):
        n_parts = comm.axis_size(axis)
        h = (L.hash_key(table.columns[key]) % jnp.uint32(n_parts)).astype(jnp.int32)
        tgt = jnp.where(table.valid_mask(), h, 0)
        shuffled, ovf = _shuffle_inside(table, tgt, axis, slack)
        return _unit_nrows(L.groupby_sum(shuffled, key, value_cols)), ovf

    return _checked(jax.jit(_gb), "dist_groupby_sum", slack, on_overflow)


# ---------------------------------------------------------------------------
# host-side helpers: build a sharded global Table for a communicator
# ---------------------------------------------------------------------------
def shard_table(comm_obj, data: dict, capacity_per_rank: int) -> Table:
    """Round-robin partition host data into a (P*cap,) global Table placed on
    the communicator's mesh (leading dim sharded over 'df')."""
    import numpy as np
    from jax.sharding import NamedSharding

    n = len(next(iter(data.values())))
    pcount = comm_obj.size
    per = [n // pcount + (1 if r < n % pcount else 0) for r in range(pcount)]
    assert max(per) <= capacity_per_rank, (max(per), capacity_per_rank)
    cols = {}
    sharding = NamedSharding(comm_obj.mesh, P("df"))
    offs = np.cumsum([0] + per)
    for k, v in data.items():
        v = np.asarray(v)
        buf = np.zeros((pcount, capacity_per_rank) + v.shape[1:], v.dtype)
        for r in range(pcount):
            buf[r, :per[r]] = v[offs[r]:offs[r + 1]]
        cols[k] = jax.device_put(
            buf.reshape((pcount * capacity_per_rank,) + v.shape[1:]), sharding)
    nrows = jax.device_put(np.asarray(per, np.int32), sharding)
    return Table(columns=cols, nrows=nrows)


def collect_table(table: Table) -> dict:
    """Gather a distributed Table back to host as dict of np arrays (tests)."""
    import numpy as np
    nrows = np.asarray(table.nrows).reshape(-1)
    pcount = nrows.shape[0]
    out = {k: [] for k in table.columns}
    for k, v in table.columns.items():
        v = np.asarray(v).reshape((pcount, -1) + v.shape[1:])
        for r in range(pcount):
            out[k].append(v[r, :nrows[r]])
        out[k] = np.concatenate(out[k], axis=0)
    return out
