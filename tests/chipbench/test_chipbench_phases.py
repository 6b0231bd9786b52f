"""The readers of the program's own counts and named phases, off the chip:
``op_compiles`` from the terminal trace events, and the ``<phase>_ms``
readers on a synthetic trace whose operations the compiled program's
metadata puts under the phases."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, xplane  # noqa: E402
from chipbench.layer_metrics import _phase  # noqa: E402

METRICS = ROOT / "chipbench" / "layer_metrics"

# chip 0 runs fusion.1 (argsort) in 2..3 s, the loop while.2 (search) in
# 3..6 s with fusion.3 (search) nested in 3.5..5.5 s, fusion.4 (pack, then
# permute inside it) in 8..9 s and copy.5 (no phase) in 9..9.5 s; chip 1
# runs fusion.1 in 2..4 s.  Tasks: 1..7 s and 7..10 s.
SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 2 offset_ps: 3000000000000 duration_ps: 3000000000000 }
    events { metadata_id: 3 offset_ps: 3500000000000 duration_ps: 2000000000000 }
    events { metadata_id: 4 offset_ps: 8000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 5 offset_ps: 9000000000000 duration_ps: 500000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p.0), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = (s32[]) while((s32[]) %t.0)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p.2), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.5 = s32[8]{0} copy(s32[8]{0} %p.0)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000000 duration_ps: 2000000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p.0), kind=kLoop" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000000 duration_ps: 9000000000000 }
    events { metadata_id: 2 offset_ps: 1000000000000 duration_ps: 6000000000000 }
    events { metadata_id: 2 offset_ps: 7000000000000 duration_ps: 3000000000000 } }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1100000000000 duration_ps: 5800000000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench/window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench/op" } }
  event_metadata { key: 3 value { id: 3 name: "repro/compute" } }
}
"""

HLO = """
ENTRY %main {
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %p.0), kind=kLoop, calls=%c.1, metadata={op_name="jit(_join)/argsort/jit(argsort)/sort" stack_frame_id=3}
  %while.2 = (s32[]) while((s32[]) %t.0), condition=%cond, body=%body, metadata={op_name="jit(_join)/search/jit(searchsorted)/while"}
  %copy.5 = s32[8]{0} copy(s32[8]{0} %p.0)
  ROOT %fusion.4 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, calls=%c.4, metadata={op_name="jit(_join)/pack/permute/gather"}
}
%body {
  %fusion.3 = s32[8]{0} fusion(s32[8]{0} %p.2), kind=kLoop, calls=%c.3, metadata={op_name="jit(_join)/search/jit(searchsorted)/while/body/lt"}
}
"""

#: by hand: task 1 holds argsort 1 s (chip 0) and 2 s (chip 1), search
#: 1 s of while.2's own time plus 2 s of fusion.3 (chip 0); task 2 holds
#: permute 1 s (chip 0); each a mean over the two chips, then the tasks
EXPECTED_MS = {"argsort_ms": (1.5 + 0.0) / 2 * 1e3,
               "search_ms": (1.5 + 0.0) / 2 * 1e3,
               "permute_ms": (0.0 + 0.5) / 2 * 1e3}


def reader(name):
    return harness.load_module(METRICS / f"{name}.py")


def synthetic_run(cell=None):
    from jax.profiler import ProfileData
    trace = xplane.from_profile(ProfileData.from_text_proto(SYNTHETIC), [0, 1])
    return harness.Run(cell=cell, ops=[], events=[], jit=[], least_s=0.0,
                       trace=trace)


def test_phases_of_takes_the_innermost_phase():
    assert _phase.phases_of(HLO) == {"fusion.1": "argsort", "while.2": "search",
                                     "fusion.4": "permute", "fusion.3": "search"}


@pytest.mark.parametrize("name", sorted(EXPECTED_MS))
def test_phase_readers_on_a_synthetic_trace(monkeypatch, name):
    monkeypatch.setattr(_phase, "compiled_text", lambda cell: HLO)
    assert reader(name).read(synthetic_run()) == pytest.approx(EXPECTED_MS[name])


@pytest.mark.parametrize("name", sorted(EXPECTED_MS))
def test_phase_readers_find_nothing_without_scopes(monkeypatch, name):
    """A program without the scopes (the parent of this change) and a trace
    without a device plane (the CPU) give nothing to read."""
    unscoped = re.sub(r"/(argsort|search|pack|permute)(?=/)", "", HLO)
    monkeypatch.setattr(_phase, "compiled_text", lambda cell: unscoped)
    assert reader(name).read(synthetic_run()) is None
    no_device = synthetic_run()
    no_device.trace.ops = {}
    assert reader(name).read(no_device) is None
    assert reader(name).read(harness.Run(cell=None, ops=[], events=[], jit=[],
                                         least_s=0.0)) is None


@pytest.mark.parametrize("name", ["pack_ms", "gather_ms", "splitters_ms"])
def test_phase_readers_find_nothing_for_a_phase_the_program_lacks(
        monkeypatch, name):
    """No instruction of the program is under the phase (here ``gather``
    and ``splitters``; ``pack`` holds only ``permute``): nothing to read,
    not 0."""
    monkeypatch.setattr(_phase, "compiled_text", lambda cell: HLO)
    assert reader(name).read(synthetic_run()) is None


def test_op_compiles_reads_the_terminal_events():
    from repro.core import TraceEvent
    run = harness.Run(cell=None, ops=[], jit=[], least_s=0.0, events=[
        TraceEvent(t=0.0, kind="submit", uid=1),
        TraceEvent(t=1.0, kind="done", uid=1, data={"compiles": 0}),
        TraceEvent(t=2.0, kind="fail", uid=2, data={"compiles": 3})])
    assert reader("op_compiles").read(run) == 1.5
    # a program whose terminal events carry no count
    run.events = [TraceEvent(t=1.0, kind="done", uid=1, data={"hub_calls": 0})]
    assert reader("op_compiles").read(run) is None


@pytest.mark.parametrize("cell,phases", [
    ("join-uniform-1chip", {"pack", "argsort", "permute", "search", "gather"}),
    ("sort-uniform-1chip", {"pack", "argsort", "permute"})])
def test_compiled_operator_names_its_phases(cell, phases):
    """The operator compiled for a cell's placed inputs (tiny here, on one
    CPU device, where no exchange is left) names the phases."""
    c = harness.load_cell(cell)
    c.config["rows_per_rank"] = 500
    assert phases <= set(_phase.phases_of(_phase.compiled_text(c)).values())



STALE_CACHE = r"""
import contextlib, json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[2])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from chipbench import harness
from chipbench.layer_metrics import _phase
cell = harness.load_cell("join-uniform-1chip")
cell.config["rows_per_rank"] = 500
update, scope = jax.config.update, jax.named_scope


def phases(metadata_in_key):
    # the phases the reader finds, its compile keyed with or without the
    # metadata (without: as every other compile of the program is)
    jax.config.update = (update if metadata_in_key else
                         lambda k, v: k.endswith("metadata_in_key") or update(k, v))
    try:
        return sorted(set(_phase.phases_of(_phase.compiled_text(cell)).values()))
    finally:
        jax.config.update = update


peaks = json.load(open(sys.argv[1] + "/chipbench/peaks.json"))
harness.load_peaks = lambda kind: peaks["devices"]["TPU v5 lite"]
jax.named_scope = lambda name: contextlib.nullcontext()   # an older program
harness.run_cell(cell, 7, 0.0, False, 0.0)     # its set-up fills the cache
jax.named_scope = scope
print(json.dumps([phases(False), phases(True)]))
"""


def test_a_cached_program_of_other_scopes_is_not_read(tmp_path):
    """The persistent cache holds the same operations compiled without the
    scopes; the reader's compile still carries the scopes of the program
    that runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", STALE_CACHE, str(ROOT),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    stale, fresh = json.loads(r.stdout.strip().splitlines()[-1])
    assert stale == []               # what a compile keyed as usual finds
    assert {"argsort", "permute", "search", "gather"} <= set(fresh)
