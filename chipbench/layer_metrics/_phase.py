"""Operators: device time of one named phase of the operator, per window
operation.  Shared by the ``<phase>_ms`` readers.

The program runs each phase under a ``jax.named_scope``; the compiled
program's metadata (``op_name="jit(_join)/search/..."``) carries it, the
device trace does not (its event names are HLO text without metadata, its
event stats times only).  So the cell's operator (its op module's
``build``) is compiled once more for the inputs the run placed (its
``input_shapes``), after the window; each instruction of its HLO
text is given the innermost phase its ``op_name`` names, and each device
operation in a window operation's span counts its own time (without what
runs nested in it) toward its instruction's phase.  A program without the
phase's scope, or an op without one such program, gives nothing to read.
"""
from __future__ import annotations

import re

from chipbench import xplane

PHASES = ("pack", "exchange", "splitters", "argsort", "permute", "search",
          "gather")
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)
# a phase as a scope in the lowered program's locations: the whole name
# (``loc("jit(_join)/search/lt")``) or, inside a ``shard_map`` over several
# chips, one relative to it (``loc("search"(#loc4))``)
SCOPED = re.compile(r'loc\("(?:[^"]*/)?(?:%s)[/"]' % "|".join(PHASES))
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
    """HLO text of the cell's operator compiled for the inputs the run
    placed on the cell's first chips."""
    import jax
    from repro.core import build_communicator

    if not hasattr(cell.op, "input_shapes"):
        return ""                   # an op without one such program
    mesh = build_communicator(jax.devices()[:cell.chips]).mesh
    lowered = cell.op.build(mesh, cell.config, "return").lower(
        *cell.op.input_shapes(cell, mesh))
    if not SCOPED.search(lowered.as_text(debug_info=True)):
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
    if phase not in phases.values():
        return None                 # the program has no such phase
    times = [phase_seconds(run.trace, phases, lo, hi).get(phase, 0.0)
             for lo, hi in run.trace.spans("chipbench/op")]
    return 1e3 * sum(times) / len(times) if times else None
