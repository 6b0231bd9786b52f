"""The benchmark's core: find a cell by name, set it up, time its window of
operations, check what the window produced, and assemble the result.

Everything particular to one configuration, traffic mix, kind of operation
or per-layer metric sits in a file of its own, found by the names in
``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the deployment's sizes and options
  (the file ``BENCHMARK.json`` names); its ``op`` names the op module;
* ``chipbench/traffic/<traffic>.json``: the parameters of the traffic mix,
  read by the op module;
* ``chipbench/ops/<op>.py``: the op protocol, the only names the harness
  reads from an op module:

  - ``setup(cell, seed, comm) -> state``: makes the inputs from the
    configuration, the traffic and the seed, places them on the cell's
    chips (``comm`` is a communicator over them) and compiles every program
    the window runs, into the persistent cache; set-up ends when it
    returns;
  - ``operation(session, state) -> (uids, output, facts)``: one timed
    operation.  It submits pilot tasks through the ``SchedulerSession``,
    waits for them and returns their uids, the output the sampler may keep
    (``None`` when the operation failed) and a dict of facts about it for
    ``check``.  An exception ends the run with no result;
  - ``check(state, kept, ops) -> {name: number or (number, limit)}``:
    after the window, the comparison with the op's plain reference of the
    kept output and of the window's :class:`Op` records.  A bare number has
    the limit 0 (an exact comparison); a pair states its own limit, as a
    loss or a norm compared with a reference needs.  A number over its
    limit makes the run not correct.  The harness holds no other reference
    to the kept output or to the program's state by then, so ``check`` can
    free them before its reference runs;
  - ``least_time(cell, state, peaks) -> (seconds, bound)``: the least time
    of one operation on the device, called after ``check``;

  an op with one program of fixed inputs also gives ``build`` (the
  program, for a mesh and the configuration) and ``input_shapes(cell,
  mesh)``, its abstract arguments, which ``rehearse.py`` and the phase
  readers compile (``ops/_tables.py`` holds them for the table operators);
* ``chipbench/layer_metrics/<metric>.py``: ``read(run)`` returns the
  metric from a :class:`Run`, or ``None`` when it finds nothing to read.

The harness itself keeps what every cell shares: the device check, the
pilot and its session, the closed loop of the window, the sampler, the
profiler trace, the device memory peak, ``ops_failed`` and the result line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import re
import sys
import threading
import time
from pathlib import Path
from typing import Optional

from chipbench.tables import rng_for

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "request",
                "/jax/compilation_cache/cache_hits": "hit"}


class DeviceError(SystemExit):
    """No usable accelerator: the run prints no result."""


def load_module(path: Path):
    name = re.sub(r"\W", "_", f"chipbench_{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    op: object                      # the module chipbench/ops/<op>.py
    end_to_end: list                # metric entries this cell reports
    per_layer: list                 # (entry, reader module) pairs


def reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads`` key
    lists, else every cell (an end-to-end metric) or every cell that
    reports the end-to-end metric it ``moves`` (a per-layer metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    here = root / "chipbench"
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    op = load_module(here / "ops" / f"{config['op']}.py")
    e2e = [m for m in bench["end_to_end"] if reports(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [(m, load_module(here / "layer_metrics" / f"{m['name']}.py"))
             for m in bench["per_layer"] if reports(m, workload, names)]
    return Cell(name=workload, chips=w["chips"], config=config,
                traffic=traffic, op=op, end_to_end=e2e, per_layer=layer)


def load_peaks(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise DeviceError(f"device kind {kind!r} is not in chipbench/"
                          f"peaks.json ({sorted(peaks)}): no peaks to "
                          f"measure against")
    return peaks[kind]


def check_device(chips: int) -> dict:
    """The chips this cell runs on; anything but enough TPUs of a known
    kind ends the run before any work."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise DeviceError(f"no TPU: JAX found {d.platform!r} devices; this "
                          "benchmark runs only on the chip")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX found "
                          f"{len(devices)}")
    load_peaks(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Op:
    uids: tuple                # the pilot tasks of the operation
    t0: float                  # host clock at its start
    t1: float                  # host clock when its end was seen
    ok: bool
    facts: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """What the per-layer readers read."""
    cell: Cell
    ops: list                  # Op of each window operation
    events: list               # the scheduler's TraceEvents of their tasks
    jit: list                  # (host clock, event, seconds) in the window
    least_s: float             # least time of one operation
    trace: Optional[object] = None   # chipbench.xplane.DeviceTrace


class JitListener:
    """Host time JAX spends tracing, lowering and compiling (or loading a
    compiled program from the cache), with the host clock of each event."""

    def __init__(self):
        import jax
        self.events = []
        self.cache = []         # (host clock, "request" or "hit")
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **_):
        if event in JIT_EVENTS:
            with self._lock:
                self.events.append((time.perf_counter(), event, seconds))

    def _on_event(self, event, **_):
        kind = CACHE_EVENTS.get(event)
        if kind:
            with self._lock:
                self.cache.append((time.perf_counter(), kind))

    def compiles_between(self, lo: float, hi: float) -> int:
        """Programs compiled, not loaded from the persistent cache."""
        with self._lock:
            got = [k for t, k in self.cache if lo <= t <= hi]
            backend = sum(1 for t, e, _ in self.events
                          if lo <= t <= hi and e == JIT_EVENTS[2])
        return backend - got.count("hit")

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def between(self, lo: float, hi: float) -> list:
        with self._lock:
            return [e for e in self.events if lo <= e[0] <= hi]


class Sampler:
    """Keeps the output of one window operation, drawn uniformly from the
    seed (reservoir of one), so every output may be the one compared."""

    def __init__(self, seed: int):
        self.rng = rng_for(seed, stream=1)
        self.seen = 0
        self.kept = None

    def offer(self, out):
        self.seen += 1
        if self.rng.random() < 1.0 / self.seen:
            self.kept = out

    def take(self):
        """The kept output, which the sampler then no longer holds."""
        kept, self.kept = self.kept, None
        return kept


def run_op(cell: Cell, session, state, ops: list):
    """One operation, recorded in ``ops``; returns its output (``None``
    when it failed)."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation("chipbench/op"):
        t0 = time.perf_counter()
        uids, out, facts = cell.op.operation(session, state)
        t1 = time.perf_counter()
    ops.append(Op(uids=tuple(uids), t0=t0, t1=t1, ok=out is not None,
                  facts=facts))
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run of ``cell``: set-up, the window, the check."""
    jit = JitListener()
    try:
        return _run_cell(cell, seed, seconds, trace, t_start, jit)
    finally:
        jit.close()


def _run_cell(cell, seed, seconds, trace, t_start, jit) -> dict:
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core import (PilotDescription, PilotManager, SchedulerSession,
                            ThreadExecutor, build_communicator)

    devices = jax.devices()

    # set-up: the op's inputs from the seed, placed on the cell's chips, and
    # its programs compiled (into the persistent cache) without a run
    rm = PilotManager().submit_pilot(
        PilotDescription(n_devices=cell.chips)).resource_manager
    cell_devices = rm.all_devices
    state = cell.op.setup(cell, seed, build_communicator(cell_devices))
    session = SchedulerSession(ThreadExecutor(), rm, result_cache="0",
                               ckpt_root="")
    setup_s = time.perf_counter() - t_start

    # the window: a closed loop of one operation at a time
    trace_dir = None
    if trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    sampler = Sampler(seed)
    ops: list = []
    with TraceAnnotation("chipbench/window"):
        w0 = time.perf_counter()
        while not ops or time.perf_counter() - w0 < seconds:
            out = run_op(cell, session, state, ops)
            if out is not None:
                sampler.offer(out)
            out = None
        w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    report = session.close()
    window_uids = {u for o in ops for u in o.uids}
    events = [e for e in report.trace if e.uid in window_uids]
    bad = [e.kind for e in report.trace
           if e.kind in ("retry", "fail", "cache_hit")]

    # device memory first: nothing after this allocates on the chips for
    # the program
    stats = [d.memory_stats() or {} for d in cell_devices]
    peaks_b = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    memory_peak = max(peaks_b) if peaks_b else None
    # the kept output goes to the op's check as its only reference, so the
    # check can free it and the program's state before its reference runs
    session = report = None
    gc.collect()
    checks = {"ops_failed": sum(not o.ok for o in ops) + len(bad),
              **cell.op.check(state, sampler.take(), ops)}
    checks = {k: v if isinstance(v, tuple) else (v, 0)
              for k, v in checks.items()}

    n_ok = sum(o.ok for o in ops)
    window_s = w1 - w0
    kind = devices[0].device_kind
    least_s, bound = cell.op.least_time(cell, state, load_peaks(kind))
    print(f"chipbench: {cell.name} seed={seed} ops={len(ops)} "
          f"window_s={window_s!r} setup_s={setup_s!r} least_s={least_s!r} "
          f"({bound}) compiles_in_window={jit.compiles_between(w0, w1)} "
          f"op_s={[round(o.t1 - o.t0, 4) for o in ops]}",
          file=sys.stderr, flush=True)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": all(v <= limit for v, limit in checks.values()),
              "attempted": len(ops), "failed": len(ops) - n_ok}
    if trace:
        import shutil
        from chipbench import xplane
        dev_trace = xplane.load(trace_dir, [d.id for d in cell_devices])
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(cell=cell, ops=ops, events=events, jit=jit.between(w0, w1),
                  least_s=least_s, trace=dev_trace)
        metrics = {}
        for entry, reader in cell.per_layer:
            value = reader.read(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        device["busy_s"], device["window_s"] = dev_trace.busy_window()
        result.update(metrics=metrics, device=device,
                      breakdown=dev_trace.breakdown())
    else:
        values = {"op_time_s": window_s / n_ok if n_ok else None,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if values.get(m["name"]) is not None}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limit}
                        for k, (v, limit) in checks.items()}
    return result
