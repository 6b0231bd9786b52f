"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py              # one chip: data path, then model path
    python chip_smoke.py --chips 4    # four chips: the 4-chip communicator's
                                      # sort and join, and two concurrent
                                      # 2-chip tasks

Phases run one after another in this one process (a chip belongs to one
process; no child here touches JAX), and each frees what it placed on the
device before the next starts:

1. device check: a platform other than ``tpu`` exits non-zero before any
   work, so nothing ever falls back to the CPU;
2. data path: a ``PilotManager`` pilot under a ``SchedulerSession`` with a
   ``ThreadExecutor`` runs ``dist_sort`` and ``dist_join`` tasks over the
   paper's 35 M-row strong-scaling tables, compared row for row with
   ``repro.dataframe.reference``;
3. model path: qwen3-8b at its published widths (depth cut to 8 of its 36
   layers, random weights from ``--seed``) served by ``ContinuousEngine``
   through ``ServeDriver`` on the same kind of session, every stream
   checked against ``greedy_reference``.

Every task must end DONE with no ``retry`` or ``fail`` event; any failure
exits non-zero.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

PAPER_ROWS = 35_000_000       # rows a table: the paper's strong-scaling size
PAIR_ROWS = 8192              # rows a table in each concurrent 2-chip task
# The capacity rule already gives each rank twice its rows.  The operators'
# default slack and out_factor of 2 would double that again at every
# shuffle and once more for the join output (280 M output slots for a 35 M
# row join on one chip), and on the chip that padding made each join call
# take over five minutes.  Overflow still raises (``on_overflow="raise"``).
SLACK = 1.0                   # a send buffer holds capacity / chips rows
OUT_FACTOR = 1.0              # join output slots = the shuffled capacity
N_LAYERS = 8                  # qwen3-8b depth cut: 8 of 36 layers
N_REQUESTS = 8
PROMPT_LENS = (128, 1024)     # prompt lengths are drawn from this range
N_NEW = 32                    # tokens generated a request
MAX_BATCH = 96                # slots: with MAX_SEQ, a 6.4 GB bf16 KV cache
MAX_SEQ = 2048
# A stream may part from the oracle where bf16 rounding reorders a near tie.
# The cached decode path and the oracle's full forward compute the same
# function but round at different points through 8 residual layers, and
# both emit bf16 logits (8 significant bits: an ulp is 1/128 to 1/256 of
# the value).  At qwen3-8b's widths one logit of the two paths differed by
# up to 4.75 ulps of the top logit's binade (bf16 on the CPU), and the gap
# at a parting is bounded by two such errors: at the first position where
# the streams part, the oracle's logit of the engine's token must lie
# within 16 such ulps of the oracle's top logit.
TOL_ULPS = 16


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result or an unclean task trace."""


def check_device(n_chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"no TPU: JAX found {d.platform!r} devices; this smoke "
                 "runs only on the chip")
    if len(devices) < n_chips:
        sys.exit(f"--chips {n_chips}: JAX found {len(devices)} chips")
    return info


def memory_line(tag: str) -> str:
    import jax
    parts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        parts.append(f"chip{d.id}:peak={stats.get('peak_bytes_in_use')},"
                     f"in_use={stats.get('bytes_in_use')}")
    return f"{tag}: memory " + " ".join(parts)


def run_tasks(descs, n_devices: int, timeout: float):
    """Run ``descs`` through the pilot runtime on ``n_devices`` chips and
    insist on a clean trace: every task DONE, no retry, no fail."""
    from repro.core import (PilotDescription, PilotManager, SchedulerSession,
                            ThreadExecutor)
    pilot = PilotManager().submit_pilot(PilotDescription(n_devices=n_devices))
    session = SchedulerSession(ThreadExecutor(), pilot.resource_manager)
    report = session.run(descs, timeout=timeout)
    require_clean(report)
    return report


def require_clean(report):
    from repro.core import TaskState
    bad = [e.kind for e in report.trace if e.kind in ("retry", "fail")]
    failed = [f"{t.desc.name}: {t.state.value}: {t.error}"
              for t in report.tasks if t.state is not TaskState.DONE]
    if bad or failed:
        raise SmokeFailure(f"unclean task trace: events {bad}, "
                           f"failed {failed}")


def make_tables(seed: int, rows: int):
    """Two tables of ``rows`` rows: an int32 key uniform on [0, rows), so a
    left row matches about one right row, and a float32 payload each."""
    rng = np.random.default_rng(seed)

    def table(payload):
        return {"k": rng.integers(0, rows, rows, dtype=np.int32),
                payload: rng.standard_normal(rows, dtype=np.float32)}

    return table("v"), table("w")


def dist_payload(comm, op: str, tables, rows: int):
    """Task body: shard the tables over the task's communicator, run the
    operator twice (first call compiles), and collect the result."""
    import jax
    from repro.dataframe import ops_dist as D
    cap = rows // comm.size * 2 + 64            # the examples' capacity rule
    args = [D.shard_table(comm, t, cap) for t in tables]
    if op == "sort":
        fn = D.make_dist_sort(comm.mesh, "k", slack=SLACK,
                              on_overflow="raise")
    else:
        fn = D.make_dist_join(comm.mesh, "k", slack=SLACK,
                              out_factor=OUT_FACTOR, on_overflow="raise")
    times = []
    for _ in range(2):
        out = None                               # free the last call's output
        t0 = time.perf_counter()
        out, _ = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    placed = {d for col in out.columns.values() for d in col.devices()}
    if placed != set(comm.devices):
        raise SmokeFailure(f"{op}: output on {sorted(d.id for d in placed)}, "
                           f"task holds {sorted(d.id for d in comm.devices)}")
    return {"first_s": times[0], "warm_s": times[1],
            "chips": sorted(d.id for d in comm.devices),
            "table": D.collect_table(out)}


def reference_rows(op: str, tables) -> np.ndarray:
    from repro.dataframe import reference as R
    if op == "sort":
        return R.sorted_rows(R.ref_sort(tables[0], "k"))
    return R.sorted_rows(R.ref_join_inner(tables[0], tables[1], "k"))


def data_phase(seed: int, n_chips: int, rows: int = PAPER_ROWS,
               timeout: float = 900.0):
    """``dist_sort`` and ``dist_join`` on one ``n_chips`` communicator."""
    from repro.core import TaskDescription
    from repro.dataframe import reference as R
    left, right = make_tables(seed, rows)
    inputs = {"sort": (left,), "join": (left, right)}
    descs = [TaskDescription(name=f"dist_{op}", ranks=n_chips,
                             fn=dist_payload, args=(op, inputs[op], rows),
                             max_retries=0, tags={"pipeline": "smoke-data"})
             for op in ("sort", "join")]
    # the numpy reference runs on host threads while the chip works
    with ThreadPoolExecutor(2) as pool:
        refs = {op: pool.submit(reference_rows, op, inputs[op])
                for op in inputs}
        report = run_tasks(descs, n_chips, timeout)
        for task in report.tasks:
            op = task.desc.name.removeprefix("dist_")
            res = task.result
            got = R.sorted_rows(res.pop("table"))
            equal = np.array_equal(got, refs[op].result())
            print(f"data: dist_{op} chips={n_chips} rows_in="
                  f"{'x'.join(str(rows) for _ in inputs[op])} "
                  f"rows_out={len(got)} first_call_s={res['first_s']:.3f} "
                  f"warm_s={res['warm_s']:.3f} equals_reference={equal}",
                  flush=True)
            if not equal:
                raise SmokeFailure(f"dist_{op} differs from the reference")
    print(memory_line("data"), flush=True)


def pair_payload(comm, barrier, seed: int, rows: int):
    """One of two concurrent 2-chip tasks: both must hold their chips at
    once, and each must find its output on exactly its own chips."""
    barrier.wait(timeout=120)
    return dist_payload(comm, "sort", make_tables(seed, rows)[:1], rows)


def pair_phase(seed: int, rows: int = PAIR_ROWS, timeout: float = 600.0):
    """Two concurrent 2-chip ``dist_sort`` tasks on private communicators."""
    from repro.core import TaskDescription
    from repro.dataframe import reference as R
    barrier = threading.Barrier(2)
    descs = [TaskDescription(name=f"pair_sort{i}", ranks=2, fn=pair_payload,
                             args=(barrier, seed + 1 + i, rows),
                             max_retries=0, tags={"pipeline": f"pair{i}"})
             for i in range(2)]
    report = run_tasks(descs, 4, timeout)
    held = []
    for i, task in enumerate(report.tasks):
        res = task.result
        ref = reference_rows("sort", make_tables(seed + 1 + i, rows)[:1])
        equal = np.array_equal(R.sorted_rows(res["table"]), ref)
        print(f"pair: {task.desc.name} chips={res['chips']} outputs_on_own_"
              f"chips=True rows={rows} equals_reference={equal}", flush=True)
        if not equal:
            raise SmokeFailure(f"{task.desc.name} differs from the reference")
        held.append(set(res["chips"]))
    if held[0] & held[1] or any(len(h) != 2 for h in held):
        raise SmokeFailure(f"2-chip tasks share or lack chips: {held}")


def bf16_ulp(x: float) -> float:
    return 2.0 ** (np.floor(np.log2(x)) - 7)


def model_phase(seed: int, cfg=None, n_requests: int = N_REQUESTS,
                prompt_lens: tuple = PROMPT_LENS, n_new: int = N_NEW,
                max_batch: int = MAX_BATCH, max_seq: int = MAX_SEQ,
                timeout: float = 900.0):
    """Serve ``n_requests`` through ``ServeDriver`` and check every stream
    against the greedy full-forward oracle."""
    import jax
    from repro.configs import get_config
    from repro.core import (PilotDescription, PilotManager, SchedulerSession,
                            ThreadExecutor)
    from repro.models import get_model
    from repro.serve import (ContinuousEngine, Request, ServeDriver,
                             greedy_reference)
    if cfg is None:
        cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=N_LAYERS)
        print(f"model: qwen3-8b at published widths (d_model 4096, 32 query "
              f"/ 8 KV heads of 128, d_ff 12288, vocab 151936); depth cut to "
              f"{N_LAYERS} of 36 layers, standing for one stage of a 36-layer "
              f"pipeline over 4-5 chips, embedding and LM head whole; "
              f"random bf16 weights from seed {seed}", flush=True)
    api = get_model(cfg)
    params = jax.jit(lambda k: api.init(k, cfg))(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    requests = [Request(prompt=rng.integers(0, cfg.vocab_size, n,
                                            dtype=np.int32),
                        max_new_tokens=n_new, uid=i)
                for i, n in enumerate(rng.integers(lo, hi + 1, n_requests))]
    engine = ContinuousEngine(cfg, params, max_batch=max_batch,
                              max_seq=max_seq)
    pilot = PilotManager().submit_pilot(PilotDescription(n_devices=1))
    session = SchedulerSession(ThreadExecutor(), pilot.resource_manager)
    t0 = time.perf_counter()
    out = ServeDriver(engine, session).run(requests, timeout=timeout)
    wall = time.perf_counter() - t0
    report = session.close()
    require_clean(report)
    snap = engine.metrics.snapshot()
    print(f"model: requests_answered={len(out)}/{n_requests} prompt_lens="
          f"{[len(r.prompt) for r in requests]} new_tokens={n_new} "
          f"max_batch={max_batch} max_seq={max_seq} decode_rounds="
          f"{snap['serve_decode_steps']} serve_tasks={len(report.tasks)} "
          f"serve_wall_s={wall:.3f} (compiles included)", flush=True)
    print(memory_line("model"), flush=True)
    if len(out) != n_requests:
        raise SmokeFailure(f"{len(out)} of {n_requests} requests answered")
    # free the slot cache for the oracle: the engine, and the session whose
    # task closures hold it
    engine = session = report = None
    gc.collect()
    print(memory_line("model, cache freed"), flush=True)

    agree_total = 0
    for r in requests:
        got = out[r.uid]
        if got.shape != (n_new,) or not ((got >= 0) &
                                         (got < cfg.vocab_size)).all():
            raise SmokeFailure(f"request {r.uid}: malformed stream {got}")
        ref, logits = greedy_reference(cfg, params, r.prompt, n_new,
                                       pad_to=hi + n_new, return_logits=True)
        if not np.isfinite(logits).all():
            raise SmokeFailure(f"request {r.uid}: non-finite oracle logits")
        parted = np.flatnonzero(got != ref)
        agree = int(parted[0]) if parted.size else n_new
        agree_total += agree
        line = f"oracle: request {r.uid} agrees {agree}/{n_new}"
        if parted.size:
            z = logits[agree]
            gap = float(z[ref[agree]] - z[got[agree]])
            tol = TOL_ULPS * bf16_ulp(float(np.abs(z).max()))
            line += f"; parts at {agree}: logit gap {gap:.5f} (tol {tol:.5f})"
            if gap > tol:
                print(line, flush=True)
                raise SmokeFailure(f"request {r.uid}: engine token is "
                                   f"{gap} below the oracle's top logit")
        print(line, flush=True)
    print(f"oracle: {agree_total}/{n_requests * n_new} tokens agree",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip data path and the two "
                         "concurrent 2-chip tasks")
    args = ap.parse_args(argv)
    device = check_device(args.chips)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    data_phase(args.seed, args.chips)
    gc.collect()
    if args.chips == 4:
        pair_phase(args.seed)
    else:
        model_phase(args.seed)
    print(f"smoke: all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
