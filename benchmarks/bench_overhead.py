"""Paper Table 2 (overhead column): communicator-construction + task
description overhead vs rank count.

The paper reports 2.3-3.5 s (MPI bootstrap) roughly FLAT from 148 to 518
ranks.  Our JAX analogue builds a sub-mesh (data structure only) — measured
here at the same rank counts on 512 fake host devices — plus the one-time
program lowering cost which is the honest JAX equivalent of the MPI
bootstrap.  The claim checked: overhead is O(1)-ish in ranks (constant-factor
band), matching the paper's flat overhead column.
"""
from __future__ import annotations

import json
import os
import sys

from benchmarks.common import ART, ROOT, emit, run_with_devices, trace_summary
from repro.core import SimOptions, TaskDescription, simulate

RANKS = [148, 222, 296, 370, 444, 518]

SNIPPET = r"""
import json, time, statistics
import jax
from repro.core import build_communicator

devices = jax.devices()
out = []
for ranks in %RANKS%:
    builds = []
    for _ in range(5):
        t0 = time.perf_counter()
        comm = build_communicator(devices[:ranks], axes=("df",))
        builds.append(time.perf_counter() - t0)
    # cold overhead: mesh + first trivial lowering on the private mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    t0 = time.perf_counter()
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "df"),
                              mesh=comm.mesh, in_specs=P("df"), out_specs=P()))
    xs = jax.ShapeDtypeStruct((ranks, 8), jnp.float32)
    f.lower(xs).compile()
    cold = time.perf_counter() - t0
    out.append({"ranks": ranks, "build_s": statistics.median(builds),
                "cold_s": cold})
print("RESULT::" + json.dumps(out))
"""


def sim_trace_overhead():
    """Paper Table 2 overhead column via the scheduler's event trace: run one
    task per rank count through the unified core on the virtual clock and
    read the comm_build events back — the same trace schema the live
    executor emits, so overhead accounting is verified end-to-end."""
    rows = []
    for ranks in RANKS:
        rep = simulate([TaskDescription(
            name=f"probe{ranks}", ranks=ranks, fn=None,
            duration_model=lambda r: 1.0, tags={"pipeline": "probe"})],
            ranks, SimOptions(noise=0.0))
        ts = trace_summary(rep)
        rows.append({"ranks": ranks, "overhead_s": ts["comm_build_mean_s"]})
        emit(f"overhead/sim_trace/ranks={ranks}",
             ts["comm_build_mean_s"] * 1e6,
             f"n_dispatch={ts['n_dispatch']}")
    return rows


def _nop(comm):
    return 0


def _dispatch_latencies(report) -> list:
    disp = {e.uid: e.t for e in report.trace if e.kind == "dispatch"}
    return [e.t - disp[e.uid] for e in report.trace
            if e.kind == "done" and e.uid in disp]


def proc_dispatch_overhead(n_tasks: int = 24):
    """Paper §5 'minimal and constant overhead' claim for the MULTI-PROCESS
    pilot: round-trip dispatch->done latency of no-op tasks through
    ProcessExecutor (pickle over the wire, cross-process scheduling) vs the
    in-process ThreadExecutor baseline, at two workload sizes to show the
    per-task cost does not grow with the task count."""
    import statistics

    from repro.core import (ProcessExecutor, ResourceManager,
                            SchedulerSession, ThreadExecutor)

    def descs(n):
        return [TaskDescription(name=f"nop{i}", ranks=1, fn=_nop,
                                tags={"pipeline": "bench"}) for i in range(n)]

    rows = []
    with ProcessExecutor(n_workers=2, devices_per_worker=1,
                         build_comm=False, tick=0.005,
                         extra_pythonpath=[str(ROOT)]) as ex:
        # warm-up: first dispatch per worker pays payload-import costs
        SchedulerSession(ex, ex.resource_manager(),
                         tick=0.005).run(descs(2), timeout=120)
        for n in (max(n_tasks // 3, 4), n_tasks):
            sess = SchedulerSession(ThreadExecutor(build_comm=False,
                                                   tick=0.005),
                                    ResourceManager(["d0", "d1"]), tick=0.005)
            thr = statistics.median(
                _dispatch_latencies(sess.run(descs(n), timeout=120)))
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
            prc = statistics.median(
                _dispatch_latencies(sess.run(descs(n), timeout=120)))
            emit(f"overhead/proc_dispatch/n={n}", prc * 1e6,
                 f"thread_us={thr * 1e6:.1f};ratio={prc / max(thr, 1e-9):.1f}")
            rows.append({"n_tasks": n, "proc_us": prc * 1e6,
                         "thread_us": thr * 1e6})
    flat = rows[-1]["proc_us"] / max(rows[0]["proc_us"], 1e-9)
    emit("overhead/proc_dispatch/flatness_ratio", flat * 1e6,
         "paper_claims_constant;per_task_latency_large_over_small")
    return rows


def _placement_hold(comm, dur=0.6):
    import time as _t
    _t.sleep(dur)
    return "held"


def _placement_probe(comm, n_coll=16):
    """A spanning-size payload: n_coll allgathers.  Under pack (one part on
    one worker) they complete locally; under spread (parts straddling
    workers) each is a parent-hub round-trip.  The thread backend's comm has
    no cross-process collectives (one address space) — skipped there."""
    size = getattr(comm, "local_size", comm.size)
    for _ in range(n_coll):
        if hasattr(comm, "allgather"):
            comm.allgather(size)
    return getattr(comm, "hub_calls", 0)


def placement_compare(n_coll: int = 16):
    """Placement policy comparison (the tentpole claim): a task that FITS one
    worker but is dispatched over a fragmented pool.  ``spread`` reproduces
    the historical flat order — the task straddles two workers and pays
    ``n_coll`` hub collectives; ``pack`` places it on a single worker: zero
    hub collectives.  Reported per backend: hub-collective count and the
    probe task's wall time (dispatch->done from the trace)."""
    from repro.core import (ProcessExecutor, ResourceManager,
                            SchedulerSession, TaskDescription, ThreadExecutor)

    def descs():
        return [TaskDescription(name="hold", ranks=1, fn=_placement_hold,
                                tags={"pipeline": "bench"}),
                TaskDescription(name="probe", ranks=2, fn=_placement_probe,
                                kwargs={"n_coll": n_coll},
                                tags={"pipeline": "bench"})]

    def probe_wall(report):
        disp = {e.task: e.t for e in report.trace if e.kind == "dispatch"}
        done = {e.task: e.t for e in report.trace if e.kind == "done"}
        return done["probe"] - disp["probe"]

    rows = []
    for placement in ("spread", "pack"):
        with ProcessExecutor(n_workers=2, devices_per_worker=2,
                             build_comm=False, tick=0.005,
                             extra_pythonpath=[str(ROOT)]) as ex:
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005,
                                    placement=placement)
            rep = sess.run(descs(), timeout=120)
            by = {t.desc.name: t for t in rep.tasks}
            hub = by["probe"].result
            wall = probe_wall(rep)
        emit(f"placement/proc/{placement}", wall * 1e6,
             f"hub_collectives={hub};n_coll={n_coll}")
        rows.append({"backend": "proc", "placement": placement,
                     "hub_collectives": hub, "wall_s": wall})
    for placement in ("spread", "pack"):
        # thread backend: one address space, so placement cannot change the
        # collective count (always 0 hub trips) — the baseline that shows
        # the win is specific to the multi-process topology
        sess = SchedulerSession(ThreadExecutor(build_comm=False, tick=0.005),
                                ResourceManager([f"d{i}" for i in range(4)]),
                                tick=0.005, placement=placement)
        rep = sess.run(descs(), timeout=120)
        wall = probe_wall(rep)
        emit(f"placement/thread/{placement}", wall * 1e6,
             "hub_collectives=0")
        rows.append({"backend": "thread", "placement": placement,
                     "hub_collectives": 0, "wall_s": wall})
    return rows


def elastic_grow_latency():
    """Elastic pilot smoke (BENCH_ELASTIC=1): how quickly pending work runs
    after an elastic grow.  A 2-rank task is submitted against a 1-device
    pilot (infeasible), then ``add_worker`` spawns a second worker at
    runtime.  Reported from the ONE TraceEvent stream: time-to-first-
    dispatch measured from add_worker() returning (the paper-facing number:
    includes only scheduler absorption, the interpreter spawn already
    happened inside add_worker) and the add_worker wall time itself (the
    full cost of acquiring a node mid-run).  Rows land in
    ``benchmarks/artifacts/elastic_summary.json`` (the CI artifact)."""
    import time as _t

    from repro.core import ProcessExecutor, SchedulerSession

    with ProcessExecutor(n_workers=1, devices_per_worker=1,
                         build_comm=False, tick=0.005,
                         extra_pythonpath=[str(ROOT)]) as ex:
        sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
        # warm-up: the first dispatch pays payload-import costs
        sess.run([TaskDescription(name="warm", ranks=1, fn=_nop,
                                  tags={"pipeline": "bench"})], timeout=120)
        sess.submit([TaskDescription(name="wide", ranks=2, fn=_nop,
                                     tags={"pipeline": "bench"})])
        t0 = _t.perf_counter()
        ex.add_worker(devices_per_worker=1)
        t_added = _t.perf_counter()        # same clock as executor.now()
        sess.drain(timeout=120)
        rep = sess.close()
        ts = trace_summary(rep)
    grow_t = next(e.t for e in rep.trace if e.kind == "grow")
    disp_t = next(e.t for e in rep.trace
                  if e.kind == "dispatch" and e.task == "wide")
    row = {
        "add_worker_wall_s": t_added - t0,
        "grow_to_dispatch_s": disp_t - grow_t,
        "added_to_dispatch_s": disp_t - t_added,
        "trace_summary": ts,
    }
    assert ts["n_grow"] == 1 and ts["n_dispatch"] == 2
    emit("elastic/add_worker_wall", row["add_worker_wall_s"] * 1e6,
         "interpreter spawn + HELLO + address-book push")
    emit("elastic/time_to_first_dispatch", row["added_to_dispatch_s"] * 1e6,
         f"grow_to_dispatch_us={row['grow_to_dispatch_s'] * 1e6:.1f}")
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "elastic_summary.json").write_text(
        json.dumps(row, indent=2, default=str))
    return row


def _p2p_probe(comm, n_coll=6, nbytes=4 << 20):
    """A join/sort-shaped exchange: every part allgathers a large blob
    ``n_coll`` times (the paper's spanning intermediates), then reports the
    comm counters so the trace evidence can be cross-checked."""
    blob = bytes([comm.part]) * nbytes
    for _ in range(n_coll):
        vals = comm.allgather(blob)
        assert all(len(v) == nbytes for v in vals)
    return {"p2p_bytes": comm.p2p_bytes, "hub_calls": comm.hub_calls,
            "fallbacks": comm.p2p_fallbacks}


def p2p_compare(n_coll: int = 6, nbytes: int = 4 << 20):
    """Data-plane comparison (the tentpole claim): the SAME large-payload
    spanning allgather, once with the peer plane disabled (every byte relays
    through the parent hub — two socket hops per payload plus a central
    bottleneck) and once enabled (payloads move worker-to-worker; the hub
    keeps only the tiny per-collective control frame).  Reports wall time of
    the probe task (dispatch->done from the trace), bytes by path, and the
    uniform trace_summary fields; the rows are also written to
    ``benchmarks/artifacts/p2p_summary.json`` (the CI artifact)."""
    from repro.core import ProcessExecutor, SchedulerSession

    rows = []
    for p2p in (False, True):
        with ProcessExecutor(n_workers=2, devices_per_worker=1,
                             build_comm=False, tick=0.005, p2p=p2p,
                             extra_pythonpath=[str(ROOT)]) as ex:
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
            # warm-up: pay worker-side payload-import cost outside the probe
            sess.run([TaskDescription(name="warm", ranks=2, fn=_p2p_probe,
                                      kwargs={"n_coll": 1, "nbytes": 1 << 14},
                                      tags={"pipeline": "bench"})],
                     timeout=120)
            rep = sess.run([TaskDescription(
                name="probe", ranks=2, fn=_p2p_probe,
                kwargs={"n_coll": n_coll, "nbytes": nbytes},
                tags={"pipeline": "bench"})], timeout=300)
            by = {t.desc.name: t for t in rep.tasks}
            probe = by["probe"]
            disp = {e.task: e.t for e in rep.trace if e.kind == "dispatch"}
            done = {e.task: e.t for e in rep.trace if e.kind == "done"}
            wall = done["probe"] - disp["probe"]
            ts = trace_summary(rep)
            rows.append({
                "mode": "peer" if p2p else "hub-relay",
                "n_coll": n_coll, "nbytes": nbytes, "wall_s": wall,
                "p2p_bytes": probe.p2p_bytes,
                "hub_relay_bytes": ex.hub_relay_bytes,
                "hub_calls": probe.hub_calls,
                "fallbacks": probe.result["fallbacks"],
                "trace_summary": ts,
            })
        emit(f"p2p/allgather/{rows[-1]['mode']}", wall * 1e6,
             f"p2p_bytes={probe.p2p_bytes};"
             f"hub_relay_bytes={rows[-1]['hub_relay_bytes']};"
             f"n_coll={n_coll};nbytes={nbytes}")
    speedup = rows[0]["wall_s"] / max(rows[1]["wall_s"], 1e-9)
    emit("p2p/allgather/speedup_hub_over_peer", speedup * 1e6,
         "wall_hub/wall_peer;>1 means the peer plane wins")
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "p2p_summary.json").write_text(
        json.dumps({"rows": rows, "speedup_hub_over_peer": speedup},
                   indent=2, default=str))
    return rows


def _xport_probe(comm, n_coll=4, nbytes=1 << 20):
    """The transport-tier probe: every part allgathers an ``nbytes`` float64
    array ``n_coll`` times.  The SAME payload runs under every tier knob
    combination — pickled baseline included — so walls are comparable, and
    the comm counters come back for the telemetry cross-check."""
    import numpy as np
    m = np.full((nbytes // 8,), float(comm.part), dtype=np.float64)
    for _ in range(n_coll):
        vals = comm.allgather(m)
        assert len(vals) == comm.n_parts
    return {"p2p_bytes": comm.p2p_bytes, "raw": comm.raw_coll_bytes,
            "shm": comm.shm_bytes, "ring": comm.ring_steps,
            "fallbacks": comm.p2p_fallbacks, "hub_calls": comm.hub_calls}


# {pickled vs raw} x {direct vs ring} x {tcp vs shm}; ring legs only make
# sense at >= RING_MIN_PARTS so the 2-worker grid drops them (ring falls
# back to direct below 4 parts by design)
TRANSPORT_GRID = [
    ("pickled-direct-tcp", {"raw_frames": False, "ring": False,
                            "shm": False}),
    ("raw-direct-tcp", {"ring": False, "shm": False}),
    ("raw-direct-shm", {"ring": False, "shm": True}),
    ("raw-ring-tcp", {"ring": True, "shm": False}),
    ("raw-ring-shm", {"ring": True, "shm": True}),
]
TRANSPORT_SIZES = {64 << 10: 12, 1 << 20: 8, 8 << 20: 3}   # nbytes -> n_coll


def transport_compare():
    """Transport-tier A/B (BENCH_TRANSPORT=1): the same wide allgather
    workload across the tier grid — zero-copy raw framing vs pickle, ring
    vs direct fan-out, same-host shm handoff vs TCP — at 64 KiB / 1 MiB /
    8 MiB payloads on 2 and 4 workers.  Walls are dispatch->done from the
    trace; every row carries both the comm-reported counters (task result)
    and the trace-derived ones, asserted equal against the executor's
    running totals (the telemetry cross-check).  Acceptance keys in
    ``benchmarks/artifacts/transport_summary.json``: the wide (4-part)
    >= 1 MiB allgather beats the direct-pickled baseline by >= 1.5x, and
    shm beats tcp at >= 1 MiB."""
    from repro.core import ProcessExecutor, SchedulerSession

    rows = []
    for workers in (2, 4):
        for config, kw in TRANSPORT_GRID:
            if workers < 4 and kw.get("ring"):
                continue
            with ProcessExecutor(n_workers=workers, devices_per_worker=1,
                                 build_comm=False, tick=0.005, **kw,
                                 extra_pythonpath=[str(ROOT)]) as ex:
                # warm-up: payload-import cost + first peer channels
                SchedulerSession(ex, ex.resource_manager(), tick=0.005).run(
                    [TaskDescription(
                        name="warm", ranks=workers, fn=_xport_probe,
                        kwargs={"n_coll": 1, "nbytes": 1 << 14},
                        tags={"pipeline": "bench"})], timeout=120)
                for nbytes, n_coll in TRANSPORT_SIZES.items():
                    before = (ex.raw_coll_bytes, ex.shm_bytes, ex.ring_steps)
                    # fresh session per probe: its report then covers exactly
                    # this probe's tasks, making the counter deltas exact
                    sess = SchedulerSession(ex, ex.resource_manager(),
                                            tick=0.005)
                    rep = sess.run([TaskDescription(
                        name="probe", ranks=workers, fn=_xport_probe,
                        kwargs={"n_coll": n_coll, "nbytes": nbytes},
                        tags={"pipeline": "bench"})], timeout=300)
                    probe = rep.tasks[0]
                    disp = {e.task: e.t for e in rep.trace
                            if e.kind == "dispatch"}
                    done = {e.task: e.t for e in rep.trace
                            if e.kind == "done"}
                    wall = done["probe"] - disp["probe"]
                    ts = trace_summary(rep)
                    # telemetry cross-check: the trace-derived counters must
                    # equal what the executor accumulated for this session
                    assert ts["raw_coll_bytes"] == \
                        ex.raw_coll_bytes - before[0]
                    assert ts["shm_bytes"] == ex.shm_bytes - before[1]
                    assert ts["ring_steps"] == ex.ring_steps - before[2]
                    assert probe.result["fallbacks"] == 0
                    rows.append({
                        "workers": workers, "config": config,
                        "nbytes": nbytes, "n_coll": n_coll, "wall_s": wall,
                        "us_per_coll": wall / n_coll * 1e6,
                        "p2p_bytes": probe.p2p_bytes,
                        "raw_coll_bytes": probe.raw_coll_bytes,
                        "shm_bytes": probe.shm_bytes,
                        "ring_steps": probe.ring_steps,
                        "hub_calls": probe.hub_calls,
                        "trace_summary": ts,
                    })
                    emit(f"transport/{workers}w/{config}/nbytes={nbytes}",
                         wall / n_coll * 1e6,
                         f"shm_bytes={probe.shm_bytes};"
                         f"ring_steps={probe.ring_steps};"
                         f"raw_coll_bytes={probe.raw_coll_bytes}")

    def wall(workers, config, nbytes):
        return next(r["wall_s"] for r in rows
                    if r["workers"] == workers and r["config"] == config
                    and r["nbytes"] == nbytes)

    # acceptance: wide (4-part, >= 1 MiB) vs the direct-pickled baseline,
    # best tiered config wins the comparison
    tiered = [c for c, _ in TRANSPORT_GRID if c != "pickled-direct-tcp"]
    speedup_wide = {}
    for nbytes in TRANSPORT_SIZES:
        base = wall(4, "pickled-direct-tcp", nbytes)
        best_c = min(tiered, key=lambda c, n=nbytes: wall(4, c, n))
        speedup_wide[str(nbytes)] = {
            "speedup": base / max(wall(4, best_c, nbytes), 1e-9),
            "best_config": best_c}
        emit(f"transport/4w/speedup_vs_pickled/nbytes={nbytes}",
             speedup_wide[str(nbytes)]["speedup"] * 1e6,
             f"best={best_c};acceptance_bar=1.5_at_1MiB")
    # acceptance: shm vs tcp on the same-host pair, raw framing held equal
    shm_vs_tcp = {str(n): wall(2, "raw-direct-tcp", n) /
                  max(wall(2, "raw-direct-shm", n), 1e-9)
                  for n in TRANSPORT_SIZES}
    for n, s in shm_vs_tcp.items():
        emit(f"transport/2w/shm_over_tcp/nbytes={n}", s * 1e6,
             "wall_tcp/wall_shm;>1 means shm wins;acceptance_bar=1.0_at_1MiB")
    out = {"rows": rows, "speedup_wide_4p": speedup_wide,
           "shm_over_tcp_2p": shm_vs_tcp,
           "acceptance": {"wide_1mib_min_speedup": 1.5,
                          "shm_beats_tcp_at": 1 << 20}}
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "transport_summary.json").write_text(
        json.dumps(out, indent=2, default=str))
    return out


def _trace_probe(comm, n_coll=8, compute_s=0.02):
    # collective-heavy part with a realistic compute phase: the span volume
    # (launch/deserialize/compute + one wait span per hub round-trip) is what
    # the recorder pays for, the compute is what any real task amortizes it
    # against — a pure-collective probe would measure JSONL cost against an
    # empty denominator
    import time as _t
    for _ in range(n_coll):
        if hasattr(comm, "allgather"):
            comm.allgather(b"x" * 2048)
    _t.sleep(compute_s)
    return 0


def trace_overhead(n_tasks: int = 12, repeats: int = 3):
    """Flight-recorder cost (BENCH_TRACE=1): the SAME spanning workload run
    with tracing off and with tracing on (spans + telemetry + JSONL
    streaming), medians over ``repeats``.  The recorder's contract is
    "cheap enough to leave on" — the acceptance bar is < 5% wall-time
    overhead, recorded alongside the measurements in
    ``benchmarks/artifacts/trace_overhead.json`` (the CI artifact)."""
    import statistics
    import tempfile

    from repro.core import ProcessExecutor, SchedulerSession

    def descs():
        return [TaskDescription(name=f"probe{i}", ranks=2, fn=_trace_probe,
                                tags={"pipeline": "bench"})
                for i in range(n_tasks)]

    rows = []
    with ProcessExecutor(n_workers=2, devices_per_worker=1,
                         build_comm=False, tick=0.005,
                         extra_pythonpath=[str(ROOT)]) as ex:
        # warm-up: first dispatch per worker pays payload-import costs
        SchedulerSession(ex, ex.resource_manager(),
                         tick=0.005).run(descs()[:2], timeout=120)
        tmp = tempfile.mkdtemp(prefix="repro-trace-bench-")
        for mode, trace_path in (("off", None),
                                 ("on", os.path.join(tmp, "bench.jsonl"))):
            walls = []
            for _ in range(repeats):
                sess = SchedulerSession(ex, ex.resource_manager(),
                                        tick=0.005, trace_path=trace_path)
                rep = sess.run(descs(), timeout=120)
                walls.append(rep.makespan)
            rows.append({"mode": mode, "wall_s": statistics.median(walls),
                         "n_tasks": n_tasks,
                         "n_spans": len(rep.spans),
                         "n_telemetry": len(rep.telemetry)})
    overhead = rows[1]["wall_s"] / max(rows[0]["wall_s"], 1e-9) - 1.0
    for r in rows:
        emit(f"trace/{r['mode']}", r["wall_s"] * 1e6,
             f"n_spans={r['n_spans']};n_telemetry={r['n_telemetry']}")
    emit("trace/overhead_frac", overhead * 1e6,
         "acceptance_bar=0.05;wall_on/wall_off-1")
    out = {"rows": rows, "overhead_frac": overhead, "acceptance_bar": 0.05}
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "trace_overhead.json").write_text(
        json.dumps(out, indent=2, default=str))
    return out


def _poisson_arrivals(n: int, mean_gap_s: float, seed: int = 7):
    """Open-loop Poisson arrival offsets: exponential inter-arrival gaps,
    cumulative from t=0.  Open-loop means the schedule never waits for the
    server — a slow server accumulates backlog instead of slowing arrivals,
    which is what makes the latency percentiles honest."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(mean_gap_s, n))


def serve_compare(n_requests: int = 64, mean_gap_s: float = 0.0005):
    """Continuous batching vs the static-batch baseline (BENCH_SERVE=1): the
    SAME open-loop Poisson request stream — mixed prompt lengths {3, 5},
    mixed budgets (1 in 4 requests wants 24 tokens, the rest want 2) — served
    by both engines over the same model/params.  The static engine groups by
    prompt length and decodes every group to its LONGEST member before
    draining; the continuous engine frees a slot the moment a request
    finishes and admits mid-decode, so short requests stop paying for long
    neighbours.  Reported per mode: req/s and p50/p99 request latency
    (finish wall - arrival wall); outputs are asserted bit-identical across
    engines.  Acceptance key in ``benchmarks/artifacts/serve_summary.json``:
    continuous >= 1.3x static throughput."""
    import dataclasses
    import time as _t

    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.models import get_model
    from repro.serve import ContinuousEngine, Request, ServeEngine

    cfg = dataclasses.replace(reduced(get_config("granite-3-8b")), n_layers=2)
    api = get_model(cfg)
    params = api.init(jax.random.key(0), cfg)
    max_batch, max_seq = 4, 64
    rng = np.random.default_rng(1)
    plens = rng.choice([3, 5], n_requests)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, int(L))
                    .astype(np.int32),
                    max_new_tokens=(24 if i % 4 == 0 else 2), uid=i)
            for i, L in enumerate(plens)]
    arrivals = _poisson_arrivals(n_requests, mean_gap_s)

    eng_s = ServeEngine(cfg, params, max_batch=max_batch, max_seq=max_seq)
    eng_c = ContinuousEngine(cfg, params, max_batch=max_batch,
                             max_seq=max_seq)
    # warm-up: compile every shape either engine can hit, so the measured
    # loops pay dispatch cost only.  Static compiles per (batch, prompt_len)
    # prefill and per batch-width decode; continuous compiles exactly one
    # prefill per prompt_len (batch 1), one decode, one insert.
    for plen in (3, 5):
        for b in range(1, max_batch + 1):
            eng_s._run_batch([Request(prompt=np.zeros(plen, np.int32),
                                      max_new_tokens=1, uid=-1)] * b)
        eng_c.run([Request(prompt=np.zeros(plen, np.int32),
                           max_new_tokens=2, uid=-1)])
    eng_c.results.clear()
    eng_c.evicted.clear()

    def run_static():
        latency, outputs, backlog, i = {}, {}, [], 0
        t0 = _t.perf_counter()
        while len(latency) < n_requests:
            now = _t.perf_counter() - t0
            while i < n_requests and arrivals[i] <= now:
                backlog.append(reqs[i])
                i += 1
            if not backlog:
                _t.sleep(max(arrivals[i] - now, 0.0))
                continue
            # static admission: the largest same-prompt-length group that has
            # arrived (causal prefill forbids mixing lengths), up to max_batch
            by_len: dict[int, list] = {}
            for r in backlog:
                by_len.setdefault(len(r.prompt), []).append(r)
            group = max(by_len.values(), key=len)[:max_batch]
            taken = {id(r) for r in group}
            backlog = [r for r in backlog if id(r) not in taken]
            out = eng_s._run_batch(group)
            tdone = _t.perf_counter() - t0
            outputs.update(out)
            for uid in out:
                latency[uid] = tdone - arrivals[uid]
        return latency, outputs, _t.perf_counter() - t0

    def run_continuous():
        latency, i = {}, 0
        t0 = _t.perf_counter()
        while len(latency) < n_requests:
            now = _t.perf_counter() - t0
            while i < n_requests and arrivals[i] <= now:
                eng_c.submit(reqs[i])
                i += 1
            if eng_c.outstanding == 0:
                _t.sleep(max(arrivals[i] - now, 0.0))
                continue
            for r in eng_c.step():
                latency[r.uid] = (_t.perf_counter() - t0) - arrivals[r.uid]
        return latency, dict(eng_c.results), _t.perf_counter() - t0

    rows = []
    results = {}
    for mode, runner in (("static", run_static),
                         ("continuous", run_continuous)):
        latency, outputs, wall = runner()
        results[mode] = outputs
        lats = sorted(latency.values())
        row = {"mode": mode, "wall_s": wall,
               "req_per_s": n_requests / wall,
               "p50_latency_s": lats[len(lats) // 2],
               "p99_latency_s": lats[min(int(len(lats) * 0.99),
                                         len(lats) - 1)]}
        rows.append(row)
        emit(f"serve/{mode}/req_per_s", row["req_per_s"] * 1e6,
             f"p50_s={row['p50_latency_s']:.4f};"
             f"p99_s={row['p99_latency_s']:.4f};n={n_requests}")
    # the two engines must agree token-for-token before throughput means
    # anything
    for r in reqs:
        np.testing.assert_array_equal(results["static"][r.uid],
                                      results["continuous"][r.uid])
    speedup = rows[1]["req_per_s"] / max(rows[0]["req_per_s"], 1e-9)
    emit("serve/speedup_continuous_over_static", speedup * 1e6,
         "req_per_s ratio;acceptance_bar=1.3")
    out = {"model": "granite-3-8b reduced n_layers=2",
           "n_requests": n_requests, "max_batch": max_batch,
           "max_seq": max_seq, "arrival_mean_gap_s": mean_gap_s,
           "rows": rows, "speedup_continuous_over_static": speedup,
           "acceptance": {"min_speedup": 1.3, "meets_bar": speedup >= 1.3}}
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "serve_summary.json").write_text(
        json.dumps(out, indent=2, default=str))
    assert speedup >= 1.3, f"continuous vs static speedup {speedup:.2f} < 1.3"
    return out


def _ckpt_steps(comm, n_steps=8, step_s=0.25):
    """N sleep-per-step "training" steps, each durably checkpointed; resumes
    from ``comm.checkpoint`` when the runtime bound one (REPRO_CKPT_DIR)."""
    import time as _t

    import numpy as np

    ck = getattr(comm, "checkpoint", None)
    state = {"acc": np.zeros(4)}
    start = 0
    if ck is not None:
        last = ck.latest()
        if last is not None:
            state = ck.restore(last, like=state)
            start = last + 1
    executed = 0
    for step in range(start, n_steps):
        _t.sleep(step_s)
        state = {"acc": state["acc"] + 1.0}
        if ck is not None:
            ck.save(step, state)
        executed += 1
    return {"executed": executed, "start": start,
            "acc": [float(x) for x in state["acc"]]}


def _cache_sleep(comm, dur=0.2, tag=0):
    import time as _t
    _t.sleep(dur)
    return tag * 2


def ckpt_resume_compare(n_steps: int = 8, step_s: float = 0.25):
    """Crash-safe resume A/B (the PR 10 tentpole claim): a ProcessExecutor
    task is SIGKILLed mid-run after several durably checkpointed steps; the
    retry either resumes from the last completed step (session ckpt_root
    set) or re-runs from scratch.  Reported per mode: steps the recovery
    attempt re-executed, resumed_from_step evidence from the trace, and
    wall.  A result-cache section runs the same task list twice through one
    cache dir and reports the second run's cache_hits.  Everything lands in
    ``benchmarks/artifacts/ckpt_summary.json``."""
    import signal
    import tempfile
    import time as _t

    from repro.core import (ProcessExecutor, ResourceManager,
                            SchedulerSession, ThreadExecutor)

    def run_once(ckpt_root):
        with ProcessExecutor(n_workers=2, devices_per_worker=1,
                             build_comm=False, tick=0.005,
                             heartbeat_interval=0.2,
                             extra_pythonpath=[str(ROOT)]) as ex:
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005,
                                    ckpt_root=ckpt_root)
            t0 = _t.perf_counter()
            (task,) = sess.submit([TaskDescription(
                name="steps", ranks=1, fn=_ckpt_steps,
                kwargs={"n_steps": n_steps, "step_s": step_s},
                tags={"pipeline": "bench"})])
            # let roughly half the steps commit durably, then kill the
            # hosting worker mid-task
            _t.sleep(step_s * (n_steps // 2) + 0.5)
            ex.kill_worker(task.devices[0].worker, signal.SIGKILL)
            rep = sess.drain(timeout=180).close()
            wall = _t.perf_counter() - t0
        steps = next(t for t in rep.tasks if t.desc.name == "steps")
        assert steps.state.value == "DONE", steps.error
        res = steps.result
        ts = trace_summary(rep)
        return {"wall_s": wall, "reexecuted_steps": res["executed"],
                "resumed_from_step": steps.resumed_from_step,
                "n_resume": ts["n_resume"], "n_retry": ts["n_retry"],
                "final_acc": res["acc"][0]}

    with tempfile.TemporaryDirectory() as root:
        with_resume = run_once(os.path.join(root, "ckpt"))
    without_resume = run_once(None)
    for mode, row in (("with_resume", with_resume),
                      ("without_resume", without_resume)):
        emit(f"ckpt/{mode}/reexecuted_steps", row["reexecuted_steps"] * 1e6,
             f"wall_s={row['wall_s']:.2f};"
             f"resumed_from_step={row['resumed_from_step']}")

    # result cache: the same task list twice through one cache dir — the
    # second run completes from disk without dispatching
    with tempfile.TemporaryDirectory() as cache:
        def cache_run():
            sess = SchedulerSession(
                ThreadExecutor(build_comm=False, tick=0.005),
                ResourceManager(["d0", "d1"]), tick=0.005,
                result_cache=cache)
            t0 = _t.perf_counter()
            rep = sess.run([TaskDescription(
                name=f"c{i}", ranks=1, fn=_cache_sleep,
                kwargs={"dur": 0.2, "tag": i},
                tags={"pipeline": "bench"}) for i in range(3)], timeout=60)
            return trace_summary(rep), _t.perf_counter() - t0
        cold, cold_wall = cache_run()
        warm, warm_wall = cache_run()
    emit("ckpt/cache/second_run_hits", warm["cache_hits"] * 1e6,
         f"cold_wall_s={cold_wall:.2f};warm_wall_s={warm_wall:.2f}")

    out = {"n_steps": n_steps, "step_s": step_s,
           "with_resume": with_resume, "without_resume": without_resume,
           "cache": {"cold_wall_s": cold_wall, "warm_wall_s": warm_wall,
                     "cold_hits": cold["cache_hits"],
                     "warm_hits": warm["cache_hits"]},
           "acceptance": {
               "resumed_from_step_positive":
                   with_resume["resumed_from_step"] > 0,
               "fewer_reexecuted_steps":
                   with_resume["reexecuted_steps"]
                   < without_resume["reexecuted_steps"],
               "warm_run_all_hits": warm["cache_hits"] == 3}}
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "ckpt_summary.json").write_text(json.dumps(out, indent=2))
    assert all(out["acceptance"].values()), out["acceptance"]
    return out


def run():
    res = {}
    if os.environ.get("BENCH_REAL", "1") == "1":
        # the 544-fake-device mesh-build section; skippable (BENCH_REAL=0)
        # so CI smokes can run the cheap sections alone
        out = run_with_devices(SNIPPET.replace("%RANKS%", str(RANKS)), 544,
                               timeout=900)  # 544 > 518 max paper rank count
        data = json.loads(out.split("RESULT::")[1])
        builds = [d["build_s"] for d in data]
        for d in data:
            emit(f"overhead/comm_build/ranks={d['ranks']}",
                 d["build_s"] * 1e6, f"cold_lower_s={d['cold_s']:.3f}")
        flat = max(builds) / max(min(builds), 1e-9)
        emit("overhead/flatness_ratio", flat * 1e6,
             "paper_claims_constant;ratio_max_over_min")
        res["real"] = data
    res["sim_trace"] = sim_trace_overhead()
    if os.environ.get("BENCH_PROC", "0") == "1" or "--proc" in sys.argv:
        # opt-in: spawns worker interpreters, adds ~5s to the section
        res["proc_dispatch"] = proc_dispatch_overhead()
    if os.environ.get("BENCH_PLACEMENT", "0") == "1" or \
            "--placement" in sys.argv:
        # opt-in: pack-vs-spread for a spanning-size task (worker processes)
        res["placement"] = placement_compare()
    if os.environ.get("BENCH_P2P", "0") == "1" or "--p2p" in sys.argv:
        # opt-in: peer data plane vs hub relay for large spanning payloads
        res["p2p"] = p2p_compare()
    if os.environ.get("BENCH_TRANSPORT", "0") == "1" or \
            "--transport" in sys.argv:
        # opt-in: tier grid A/B — raw framing / ring / shm vs the pickled
        # direct-TCP baseline at three payload sizes on 2 and 4 workers
        res["transport"] = transport_compare()
    if os.environ.get("BENCH_ELASTIC", "0") == "1" or "--elastic" in sys.argv:
        # opt-in: runtime add_worker -> time-to-first-dispatch for pending
        # work that could not fit the initial inventory
        res["elastic"] = elastic_grow_latency()
    if os.environ.get("BENCH_TRACE", "0") == "1" or "--trace" in sys.argv:
        # opt-in: flight-recorder on/off A/B (spans + telemetry + JSONL)
        res["trace"] = trace_overhead()
    if os.environ.get("BENCH_SERVE", "0") == "1" or "--serve" in sys.argv:
        # opt-in: continuous batching vs static batch on the same Poisson
        # request stream (req/s + latency percentiles)
        res["serve"] = serve_compare()
    if os.environ.get("BENCH_CKPT", "0") == "1" or "--ckpt" in sys.argv:
        # opt-in: checkpoint-resume A/B under a mid-task SIGKILL, plus the
        # result cache's repeated-run hit rate
        res["ckpt"] = ckpt_resume_compare()
    return res


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
