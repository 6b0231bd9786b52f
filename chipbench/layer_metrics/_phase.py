"""Operators: device time of one named phase of the operator, per window
operation.  Shared by the ``<phase>_ms`` readers.

The program runs each phase under a ``jax.named_scope``; the compiled
program's metadata (``op_name="jit(_join)/search/..."``) carries it, the
device trace does not (its event names are HLO text without metadata, its
event stats times only).  So the cell's operator is compiled once more for
the tables the run placed, after the window; each instruction of its HLO
text is given the innermost phase its ``op_name`` names, and each device
operation in a window operation's span counts its own time (without what
runs nested in it) toward its instruction's phase.  A program without the
scopes gives nothing to read.
"""
from __future__ import annotations

import re

from chipbench import xplane

PHASES = ("pack", "exchange", "splitters", "argsort", "permute", "search",
          "gather")
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)
_last: list = [None, None]           # the trace last read, and its phases


def phases_of(hlo_text: str) -> dict:
    """Instruction name -> the innermost phase its ``op_name`` names, for
    the instructions under some phase.  An ``op_name`` is the scopes, then
    the primitive (``.../permute/gather``), which is no scope."""
    out = {}
    for name, op_name in INSTRUCTION.findall(hlo_text):
        inner = [p for p in op_name.split("/")[:-1] if p in PHASES]
        if inner:
            out[name] = inner[-1]
    return out


def phase_seconds(trace, phases: dict, lo: float, hi: float) -> dict:
    """Own device seconds of each phase inside ``[lo, hi]``, averaged over
    the trace's chips."""
    out: dict = {}
    for evs in trace.ops.values():
        for label, d in xplane.self_times(evs, lo, hi).items():
            phase = phases.get(label.split(" in ", 1)[0])
            if phase is not None:
                out[phase] = out.get(phase, 0.0) + d / len(trace.ops)
    return out


def compiled_text(cell) -> str:
    """HLO text of the cell's operator compiled for the tables the run
    placed: ``rows // chips * 2 + 64`` slots a rank on the cell's first
    chips, as the harness places them."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import build_communicator
    from repro.dataframe.table import Table

    comm = build_communicator(jax.devices()[:cell.chips])
    rows = cell.rows // cell.chips * 2 + 64
    spec = NamedSharding(comm.mesh, P("df"))
    tables = [Table(columns={k: jax.ShapeDtypeStruct(
                        (cell.chips * rows,), np.dtype(t), sharding=spec)
                             for k, t in schema.items()},
                    nrows=jax.ShapeDtypeStruct((cell.chips,), np.int32,
                                               sharding=spec))
              for schema in cell.config["tables"]]
    lowered = cell.op.build(comm.mesh, cell.config, "return").lower(*tables)
    names = lowered.as_text(debug_info=True)
    if not any(f"/{p}/" in names for p in PHASES):
        return ""                   # a program without the scopes
    # the persistent cache's key leaves metadata out, so by default it may
    # hand back a program compiled from the same operations under other
    # scopes (an older version's): this compile's key holds the metadata
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(key, before)


def read(run, phase: str):
    if run.trace is None or not run.trace.ops:
        return None
    if _last[0] is not run.trace:
        _last[:] = [run.trace, phases_of(compiled_text(run.cell))]
    phases = _last[1]
    if not phases:
        return None
    times = [phase_seconds(run.trace, phases, lo, hi).get(phase, 0.0)
             for lo, hi in run.trace.spans("chipbench/op")]
    return 1e3 * sum(times) / len(times) if times else None
