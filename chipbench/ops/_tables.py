"""The op protocol for operators over columnar tables, shared by ``join.py``
and ``sort.py``: tables from the seed placed on the cell's chips, one pilot
task an operation, and the exact comparison with the module's reference.

The op module that imports these names gives the table-specific parts:
``TASK`` (the task's name), ``build(mesh, config, on_overflow)`` (the
operator), ``payload(comm, tables, config)`` (the task body, returning the
output table and its overflow flag), ``reference(tables, key)``,
``compare(got, ref, key)`` and ``least_bytes``.  They are looked up on
``cell.op`` when called, so a test may replace one on a loaded module.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Optional

import numpy as np

from chipbench import tables as traffic_gen

OP_TIMEOUT_S = 900.0        # one task that takes longer has hung


def rows(cell) -> int:
    """Rows a table: the configuration's rows a rank, times the chips."""
    return cell.config["rows_per_rank"] * cell.chips


def capacity(cell) -> int:
    """Slots a rank of every placed table, as the repo's examples place
    them."""
    return rows(cell) // cell.chips * 2 + 64


def input_shapes(cell, mesh) -> list:
    """The abstract tables that :func:`setup` places: ``capacity`` slots a
    rank of every column, sharded over the mesh's ``df`` axis, and the
    valid-row count of each rank."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dataframe.table import Table

    spec = NamedSharding(mesh, P("df"))
    slots = cell.chips * capacity(cell)
    return [Table(columns={k: jax.ShapeDtypeStruct((slots,), np.dtype(t),
                                                   sharding=spec)
                           for k, t in schema.items()},
                  nrows=jax.ShapeDtypeStruct((cell.chips,), np.int32,
                                             sharding=spec))
            for schema in cell.config["tables"]]


@dataclasses.dataclass
class State:
    cell: object
    host: Optional[list]            # the host tables, for the reference
    resident: Optional[list]        # the same tables on the cell's chips
    ref_rows: int = -1              # the reference's output rows, once known


def setup(cell, seed: int, comm) -> State:
    """Tables from the seed, placed on the communicator's chips, and the
    operator compiled for them (into the persistent cache) without a run."""
    from repro.dataframe import ops_dist as D
    host = traffic_gen.make_tables(cell.config, cell.traffic, rows(cell), seed)
    resident = [D.shard_table(comm, t, capacity(cell)) for t in host]
    cell.op.build(comm.mesh, cell.config, "return").lower(
        *input_shapes(cell, comm.mesh)).compile()
    return State(cell=cell, host=host, resident=resident)


def operation(session, state: State):
    """One pilot task of the operator over the resident tables; its output
    table is ``None`` when the task failed."""
    from repro.core import TaskDescription
    cell = state.cell
    desc = TaskDescription(name=cell.op.TASK, ranks=cell.chips,
                           fn=cell.op.payload,
                           args=(state.resident, cell.config), max_retries=0,
                           tags={"pipeline": cell.name})
    (task,) = session.submit([desc])
    if not session.wait_any(timeout=OP_TIMEOUT_S):
        raise RuntimeError(f"task {desc.name} did not end in {OP_TIMEOUT_S}s")
    if task.result is None:
        print(f"chipbench: task {task.uid} failed: {task.error}",
              file=sys.stderr, flush=True)
        return (task.uid,), None, {}
    out, overflow = task.result
    task.result = None                # the session keeps every Task
    return (task.uid,), out, {"rows_out": int(np.asarray(out.nrows).sum()),
                              "overflow": bool(overflow)}


def collect(table) -> dict:
    """A distributed output table on the host: each rank's valid rows, in
    rank order."""
    nrows = np.asarray(table.nrows).reshape(-1)
    out = {}
    for name, col in table.columns.items():
        per_rank = np.asarray(col).reshape((len(nrows), -1))
        out[name] = np.concatenate([per_rank[r, :n] for r, n in enumerate(nrows)])
    return out


def check(state: State, kept, ops: list) -> dict:
    """The kept output against the reference over the host tables, and
    every window task's row count and overflow flag; every number has the
    limit 0."""
    cell, key = state.cell, state.cell.config["key"]
    got = collect(kept) if kept is not None else None
    kept = state.resident = None          # the program's tables
    gc.collect()
    t0 = time.perf_counter()
    ref = cell.op.reference(state.host, key)
    state.host = None
    state.ref_rows = len(ref[key])
    ok = [o for o in ops if o.ok]
    checks = {
        "overflow": sum(o.facts["overflow"] for o in ok),
        "rows_out_diff": max((abs(o.facts["rows_out"] - state.ref_rows)
                              for o in ok), default=state.ref_rows),
    }
    if got is None:
        checks["rows_mismatched"] = state.ref_rows
    else:
        checks.update(cell.op.compare(got, ref, key))
    print(f"chipbench: reference and comparison took "
          f"{time.perf_counter() - t0!r} s", file=sys.stderr, flush=True)
    return checks


def row_bytes(schema: dict) -> int:
    return sum(np.dtype(t).itemsize for t in schema.values())


def least_time(cell, state: State, peaks: dict) -> tuple:
    """Least time of one operation and its bound (``"hbm"`` or ``"ici"``):
    the op's ``least_bytes`` at the reference's output rows (known once
    :func:`check` has run) over the peaks."""
    schemas = cell.config["tables"]
    out_bytes = row_bytes({k: t for s in schemas for k, t in s.items()})
    hbm, ici = cell.op.least_bytes(
        [rows(cell)] * len(schemas), [row_bytes(s) for s in schemas],
        state.ref_rows, out_bytes, cell.chips)
    t_hbm = hbm / peaks["hbm_bytes_per_s"]
    t_ici = ici / peaks["ici_bytes_per_s"]
    return (t_ici, "ici") if t_ici > t_hbm else (t_hbm, "hbm")
