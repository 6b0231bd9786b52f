"""Shared benchmark helpers: timing, CSV emission, subprocess launch."""
from __future__ import annotations

import os
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
ART = ROOT / "benchmarks" / "artifacts"
FAST = os.environ.get("BENCH_FAST", "1") == "1"   # default: CI-sized


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def time_call(fn, *args, iters: int = 5, warmup: int = 2):
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def run_with_devices(snippet: str, n_devices: int, timeout: int = 900) -> str:
    """Run ``snippet`` in a child interpreter that emulates ``n_devices``
    ranks on host CPU devices.  The child is pinned to the CPU backend so
    that, on an accelerator host, it never competes for the chip with a
    parent that already holds it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    return r.stdout


def trace_summary(report) -> dict:
    """Uniform consumer of the scheduler event trace (SimReport.trace) —
    shared by bench_hetero / bench_scaling / bench_overhead so live and
    simulated runs report identical schedule-derived metrics."""
    from collections import Counter

    kinds = Counter(e.kind for e in report.trace)
    submits = {e.uid: e.t for e in report.trace if e.kind == "submit"}
    waits = [e.t - submits[e.uid] for e in report.trace
             if e.kind == "dispatch" and e.uid in submits]
    comm = [e.value for e in report.trace if e.kind == "comm_build"]
    out = {
        "n_submit": kinds.get("submit", 0),
        "n_dispatch": kinds.get("dispatch", 0),
        "n_done": kinds.get("done", 0),
        "n_retry": kinds.get("retry", 0),
        "n_speculate": kinds.get("speculate", 0),
        # elastic-pool evidence: grow/retire events the core absorbed
        # (add_worker/retire_worker, inject_grow/inject_retire, grow_at/
        # retire_at) — zeros on a static-pool run
        "n_grow": kinds.get("grow", 0),
        "n_retire": kinds.get("retire", 0),
        "mean_wait_s": sum(waits) / len(waits) if waits else 0.0,
        "comm_build_total_s": sum(comm),
        "comm_build_mean_s": sum(comm) / len(comm) if comm else 0.0,
        # data-plane evidence, uniform across backends: the process executor
        # reports real worker-to-worker bytes / hub round-trips; thread and
        # virtual runs report plain zeros (never a KeyError downstream)
        "p2p_bytes": sum(getattr(e, "p2p", 0.0)
                         for e in report.trace if e.kind in ("done", "fail")),
        "hub_calls": sum(getattr(t, "hub_calls", 0) for t in report.tasks),
        "spills": sum(getattr(t, "spills", 0) for t in report.tasks),
        "p2p_fallbacks": sum(getattr(t, "p2p_fallbacks", 0)
                             for t in report.tasks),
        "hub_relay_bytes": sum(getattr(t, "hub_relay_bytes", 0)
                               for t in report.tasks),
        # transport-tier evidence: zero-copy framed bytes, same-host
        # shared-memory bytes, and ring-allgather forwards (PR 8)
        "raw_coll_bytes": sum(getattr(t, "raw_coll_bytes", 0)
                              for t in report.tasks),
        "shm_bytes": sum(getattr(t, "shm_bytes", 0) for t in report.tasks),
        "ring_steps": sum(getattr(t, "ring_steps", 0)
                          for t in report.tasks),
        # crash-safe resume + result-cache evidence (PR 10): attempts that
        # restored a checkpoint instead of re-running from scratch, the
        # steps they skipped, and tasks completed straight from the
        # result cache — zeros on runs without REPRO_CKPT_DIR/RESULT_CACHE
        "n_resume": kinds.get("resume", 0),
        "resumed_steps": sum(getattr(t, "resumed_from_step", 0)
                             for t in report.tasks),
        "cache_hits": kinds.get("cache_hit", 0),
    }
    # span-derived timing breakdown, present only when worker flight-recorder
    # spans exist (process or thread executor, or a loaded trace of such a
    # run); sim reports simply omit the keys
    spans = getattr(report, "spans", None) or ()
    if spans:
        from repro.obs.spans import WAIT_KINDS
        out["compute_s"] = sum(s["t1"] - s["t0"] for s in spans
                               if s["kind"] == "compute")
        out["comm_wait_s"] = sum(s["t1"] - s["t0"] for s in spans
                                 if s["kind"] in WAIT_KINDS)
    return out
