"""Out-of-core shuffle scaling (BENCH_SHUFFLE=1): the paper's claim surface
— distributed sort/join wall time vs row count on a multi-worker pilot
(Radical-Cylon reports 35M/3.5B-row joins; this is the same shape at CI
scale, growable via BENCH_FAST=0).

Two sections, both landing in ``benchmarks/artifacts/shuffle_summary.json``:

* **scaling** — rows-vs-wall curve for the out-of-core sample sort on 2
  workers under a memory budget ~1/3 of the per-part dataset, so the spill
  path is exercised at every size; each row records the full evidence
  (``p2p_bytes``, ``hub_relay_bytes``, ``hub_calls``, ``spills``) read
  back from the ONE TraceEvent stream via ``trace_summary``.
* **framing** — raw-buffer peer frames (``PEER_DATA_RAW``) vs pickled
  ``PEER_DATA`` for the identical multi-MiB bucket exchange: the transport
  A/B behind the REPRO_RAW_FRAMES knob, timed inside the task so only the
  exchange is measured.
"""
from __future__ import annotations

import json
import os
import sys

from benchmarks.common import ART, FAST, ROOT, emit, trace_summary
from repro.core import ProcessExecutor, SchedulerSession, TaskDescription
from repro.dataframe.shuffle import sort_task

SIZES = [25_000, 50_000, 100_000, 250_000] if FAST else \
    [50_000, 100_000, 250_000, 500_000, 1_000_000]

_ROW_BYTES = 12     # int32 key + one int64 value column


def _warm(ex):
    """First dispatch per worker pays payload-import cost; keep it out of
    the measured runs."""
    sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
    sess.run([TaskDescription(name="warm", ranks=2, fn=sort_task,
                              args=({"rows_per_part": 1000,
                                     "budget": 1 << 30},),
                              tags={"pipeline": "bench"})], timeout=120)


def scaling_curve(n_workers: int = 2):
    """Rows-vs-wall for the out-of-core sort; budget = per-part bytes / 3,
    so every size spills (budget < dataset) — the acceptance shape."""
    rows = []
    with ProcessExecutor(n_workers=n_workers, devices_per_worker=1,
                         build_comm=False, tick=0.005,
                         extra_pythonpath=[str(ROOT)]) as ex:
        _warm(ex)
        for rpp in SIZES:
            budget = max(64 << 10, (rpp * _ROW_BYTES) // 3)
            spec = {"rows_per_part": rpp, "seed": 42, "budget": budget}
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
            rep = sess.run([TaskDescription(
                name=f"sort{rpp}", ranks=n_workers, fn=sort_task,
                args=(spec,), tags={"pipeline": "bench"})], timeout=600)
            task = rep.tasks[0]
            assert task.error is None, task.error
            assert task.result["sorted"] and \
                task.result["n"] == rpp * n_workers
            name = f"sort{rpp}"
            disp = next(e.t for e in rep.trace
                        if e.kind == "dispatch" and e.task == name)
            done = next(e.t for e in rep.trace
                        if e.kind == "done" and e.task == name)
            wall = done - disp
            ts = trace_summary(rep)
            row = {
                "rows": rpp * n_workers, "rows_per_part": rpp,
                "n_workers": n_workers, "wall_s": wall,
                "dataset_bytes_per_part": rpp * _ROW_BYTES,
                "budget_bytes": budget,
                "spills": task.spills,
                "p2p_bytes": task.p2p_bytes,
                "hub_relay_bytes": ex.hub_relay_bytes,
                "hub_calls": task.hub_calls,
                "trace_summary": ts,
            }
            rows.append(row)
            emit(f"shuffle/sort/rows={rpp * n_workers}", wall * 1e6,
                 f"spills={task.spills};p2p_bytes={task.p2p_bytes};"
                 f"hub_relay_bytes={ex.hub_relay_bytes};budget={budget}")
            assert task.spills > 0, "budget < dataset must exercise spill"
            if ex.p2p and ex.raw_frames:
                assert task.p2p_bytes > 10 * ex.hub_relay_bytes, \
                    "bucket bytes must move p2p, not through the hub"
    return rows


def _xchg_probe(comm, n_rounds=4, rows=60_000, width=4):
    """Transport-only probe: ``n_rounds`` personalized all-to-alls of the
    same per-destination buckets, timed inside the task so generation and
    merge never pollute the comparison.  At the defaults each bucket is
    ~1 MiB (rows/2 * (4 + width*8) bytes on 2 parts)."""
    import time as _t

    import numpy as np
    n_parts = comm.n_parts
    rng = np.random.default_rng(comm.part)
    cols = {"key": rng.integers(0, 1 << 30, rows, dtype=np.int32)}
    for j in range(width):
        cols[f"v{j}"] = rng.integers(0, 1 << 62, rows, dtype=np.int64)
    chunks = [{k: np.ascontiguousarray(v[d::n_parts])
               for k, v in cols.items()} for d in range(n_parts)]
    bucket_bytes = sum(v.nbytes for v in chunks[0].values())
    t0 = _t.perf_counter()
    for _ in range(n_rounds):
        got = comm.all_to_all_arrays(chunks)
        assert len(got) == n_parts
    return {"xchg_s": _t.perf_counter() - t0,
            "bucket_bytes": bucket_bytes,
            "p2p_bytes": comm.p2p_bytes,
            "fallbacks": comm.p2p_fallbacks}


def framing_compare(n_rounds: int = 4, rows: int = 60_000, width: int = 4):
    """Raw-buffer frames vs pickled frames for the identical >= 1 MiB
    bucket exchange (the REPRO_RAW_FRAMES A/B)."""
    out = {}
    for raw in (False, True):
        with ProcessExecutor(n_workers=2, devices_per_worker=1,
                             build_comm=False, tick=0.005,
                             raw_frames=raw,
                             extra_pythonpath=[str(ROOT)]) as ex:
            sess = SchedulerSession(ex, ex.resource_manager(), tick=0.005)
            sess.run([TaskDescription(name="warm", ranks=2, fn=_xchg_probe,
                                      kwargs={"n_rounds": 1, "rows": 2000},
                                      tags={"pipeline": "bench"})],
                     timeout=120)
            rep = sess.run([TaskDescription(
                name="probe", ranks=2, fn=_xchg_probe,
                kwargs={"n_rounds": n_rounds, "rows": rows, "width": width},
                tags={"pipeline": "bench"})], timeout=300)
            probe = [t for t in rep.tasks if t.desc.name == "probe"][0]
            assert probe.error is None, probe.error
            mode = "raw" if raw else "pickled"
            out[mode] = {**probe.result, "p2p_bytes": probe.p2p_bytes,
                         "hub_relay_bytes": ex.hub_relay_bytes}
            emit(f"shuffle/framing/{mode}", out[mode]["xchg_s"] * 1e6,
                 f"bucket_bytes={out[mode]['bucket_bytes']};"
                 f"rounds={n_rounds};p2p_bytes={probe.p2p_bytes}")
    speedup = out["pickled"]["xchg_s"] / max(out["raw"]["xchg_s"], 1e-9)
    out["speedup_pickled_over_raw"] = speedup
    emit("shuffle/framing/speedup_pickled_over_raw", speedup * 1e6,
         ">1 means raw-buffer framing wins")
    return out


def run():
    if os.environ.get("BENCH_SHUFFLE", "0") != "1" and \
            "--shuffle" not in sys.argv:
        print("bench_shuffle: set BENCH_SHUFFLE=1 (spawns worker "
              "interpreters); skipping")
        return {}
    res = {"scaling": scaling_curve(), "framing": framing_compare()}
    ART.mkdir(parents=True, exist_ok=True)
    (ART / "shuffle_summary.json").write_text(
        json.dumps(res, indent=2, default=str))
    return res


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
