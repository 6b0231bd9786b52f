"""The paper's headline experiment as a runnable example: heterogeneous
(shared-pool) vs batch (static-partition) execution of two MPMD pipelines —
a join DAG and a sort DAG — on one resource pool, with *continuous DAG
release*: each stage is submitted the moment its own deps complete, so a
freed device immediately backfills work from any pipeline (expect the
heterogeneous policy to win; paper: 4-15%).

Two live backends share the identical scheduler core and payloads:

  thread (default) — every task in this process, one worker thread each:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/etl_pipeline.py

  process — the paper's multi-node mode: one fresh interpreter per "node",
  each owning its own host devices; the final merge stage's ranks span both
  worker processes and aggregate through the cross-process communicator:
    PYTHONPATH=src python examples/etl_pipeline.py --backend process
"""
import argparse
import time

import numpy as np


ROWS = 20_000


def _local(comm):
    """Per-node view of the communicator: under ProcessExecutor the dataframe
    ops run on this worker's private sub-mesh; under ThreadExecutor the task's
    whole communicator IS local."""
    return getattr(comm, "local_comm", comm)


def sort_payload(comm, *_deps):
    import jax
    from repro.dataframe import ops_dist as D
    lc = _local(comm)
    rng = np.random.default_rng(1)
    data = {"k": rng.integers(0, 1_000_000, ROWS).astype(np.int32)}
    t = D.shard_table(lc, data, ROWS // lc.size * 2 + 64)
    out, _ = D.make_dist_sort(lc.mesh, "k")(t)
    jax.block_until_ready(out.columns["k"])
    time.sleep(1.0)    # simulated residual work: this container has ONE core,
                       # so cross-task parallelism is demonstrated via sleep
    return "sorted"


def join_payload(comm, *_deps):
    import jax
    from repro.dataframe import ops_dist as D
    lc = _local(comm)
    rng = np.random.default_rng(2)
    cap = ROWS // lc.size * 2 + 64
    a = D.shard_table(lc, {"k": rng.integers(0, 1_000_000, ROWS).astype(np.int32),
                           "v": rng.normal(size=ROWS).astype(np.float32)}, cap)
    b = D.shard_table(lc, {"k": rng.integers(0, 1_000_000, ROWS).astype(np.int32),
                           "w": rng.normal(size=ROWS).astype(np.float32)}, cap)
    out, _ = D.make_dist_join(lc.mesh, "k", out_factor=3.0)(a, b)
    jax.block_until_ready(out.columns["k"])
    time.sleep(3.0)    # joins are the long pole (see sort_payload note)
    return "joined"


def merge_payload(comm, *deps):
    """Full-width stage: under the process backend its ranks span every
    worker, so each node sorts its local shard and the per-node row counts
    are combined through the cross-process communicator (the paper's
    heterogeneous MPI_Comm across nodes)."""
    import jax
    from repro.dataframe import ops_dist as D
    lc = _local(comm)
    rng = np.random.default_rng(3)
    data = {"k": rng.integers(0, 1_000_000, ROWS).astype(np.int32)}
    t = D.shard_table(lc, data, ROWS // lc.size * 2 + 64)
    out, _ = D.make_dist_sort(lc.mesh, "k")(t)
    jax.block_until_ready(out.columns["k"])
    local_rows = int(np.asarray(out.nrows).sum())
    if hasattr(comm, "allgather"):          # ProcessExecutor: one value/node
        total = sum(comm.allgather(local_rows))
    else:
        total = local_rows
    return f"merged({total} rows over {comm.size} ranks)"


def build_pipelines(n_dev, full_width=True):
    """Two DAG pipelines: 'join' is one heavy stage plus a cheap dependent
    summarize stage; 'sort' is a chain of sorts feeding a full-width merge.
    Under continuous release the summarize stage starts the moment its join
    finishes — while the other pipeline's sorts are still running (no wave
    barrier).

    ``full_width=False`` caps the merge at half the pool: a BATCH run's
    static partition can never host a task wider than its own share — the
    paper's rigidity argument against static partitioning, and exactly why
    the heterogeneous shared pool CAN run the cross-node merge."""
    from repro.core import Pipeline
    per = max(n_dev // 2, 1)
    merge_ranks = n_dev if full_width else per
    join = Pipeline("join")
    join.add("join0", ranks=per, fn=join_payload)
    join.add("join1", ranks=per, fn=join_payload)
    join.add("summarize", ranks=per,
             fn=lambda comm, *deps: f"summary({','.join(map(str, deps))})",
             deps=["join0", "join1"])
    sort = Pipeline("sort")
    sort.add("sort0", ranks=per, fn=sort_payload)
    sort.add("sort1", ranks=per, fn=sort_payload)
    sort.add("sort2", ranks=per, fn=sort_payload, deps=["sort0"])
    sort.add("sort3", ranks=per, fn=sort_payload, deps=["sort1"])
    sort.add("merge", ranks=merge_ranks, fn=merge_payload,
             deps=["sort2", "sort3"])
    return [join, sort]


def print_timeline(report, t0):
    for e in report.trace:
        if e.kind in ("dispatch", "done"):
            print(f"    t={e.t - t0:6.2f}s {e.kind:>8s} {e.task:<16s} "
                  f"ranks={e.ranks}")


def _run_policies(n, make_executor, make_rm, placement="spread",
                  work_stealing=False):
    from repro.core import BATCH, HETEROGENEOUS, run_pipelines
    results = {}
    for policy in (HETEROGENEOUS, BATCH):
        ex = make_executor()
        try:
            t0 = time.perf_counter()
            # full_width=False keeps the two policies on IDENTICAL
            # workloads (and a batch partition cannot host a full-pool
            # task anyway); the full-width cross-node merge is shown
            # separately below
            pipes = build_pipelines(n, full_width=False)
            res, rep = run_pipelines(pipes, make_rm(ex),
                                     policy=policy, timeout=900, executor=ex,
                                     placement=placement,
                                     work_stealing=work_stealing)
            assert res[("join", "summarize")].startswith("summary")
            assert res[("sort", "merge")].startswith("merged")
        finally:
            if hasattr(ex, "shutdown"):
                ex.shutdown()
        results[policy] = rep.makespan
        stolen = rep.events("steal")
        extra = f", {len(stolen)} steals" if stolen else ""
        print(f"[{policy:>13s}] makespan {rep.makespan:.2f}s  "
              f"(comm-build total {rep.overhead_total * 1e3:.1f}ms, "
              f"{len(rep.events('dispatch'))} dispatches, "
              f"placement={placement}{extra})")
        print_timeline(rep, t0)
    impr = (results[BATCH] - results[HETEROGENEOUS]) / results[BATCH] * 100
    print(f"heterogeneous vs batch improvement: {impr:.1f}% "
          f"(paper reports 4-15% at ORNL scale)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("thread", "process"),
                    default="thread")
    ap.add_argument("--workers", type=int, default=2,
                    help="process backend: worker interpreters (nodes)")
    ap.add_argument("--devices-per-worker", type=int, default=2)
    ap.add_argument("--placement", choices=("spread", "pack"),
                    default="spread",
                    help="pack keeps a fitting task's ranks on one worker "
                         "(process backend: no hub collectives)")
    ap.add_argument("--work-stealing", action="store_true",
                    help="batch policy: backlogged partitions lease idle "
                         "devices from sibling partitions")
    args = ap.parse_args()

    if args.backend == "thread":
        import jax
        from repro.core import (PilotDescription, PilotManager,
                                ThreadExecutor)
        n = len(jax.devices())
        _run_policies(
            n,
            make_executor=lambda: ThreadExecutor(),
            make_rm=lambda ex: PilotManager().submit_pilot(
                PilotDescription(n_devices=n)).resource_manager,
            placement=args.placement, work_stealing=args.work_stealing)
    else:
        from repro.core import (ProcessExecutor, SchedulerSession,
                                TaskDescription)
        n = args.workers * args.devices_per_worker
        print(f"process backend: {args.workers} workers x "
              f"{args.devices_per_worker} devices")
        # one executor (and its worker processes) per policy run keeps the
        # comparison fair: both start with cold per-task caches
        _run_policies(
            n,
            make_executor=lambda: ProcessExecutor(
                n_workers=args.workers,
                devices_per_worker=args.devices_per_worker,
                build_comm=True).start(),
            make_rm=lambda ex: ex.resource_manager(),
            placement=args.placement, work_stealing=args.work_stealing)
        # the paper's multi-node headline: ONE task whose communicator spans
        # every worker process — per-node sub-mesh sorts combined through
        # the cross-process allgather
        ex = ProcessExecutor(n_workers=args.workers,
                             devices_per_worker=args.devices_per_worker,
                             build_comm=True).start()
        try:
            sess = SchedulerSession(ex, ex.resource_manager())
            rep = sess.run([TaskDescription(name="merge_all", ranks=n,
                                            fn=merge_payload,
                                            tags={"pipeline": "demo"})],
                           timeout=300)
            task = rep.tasks[0]
            spans = {d.worker for d in task.devices}
            print(f"cross-node merge over {len(spans)} workers: "
                  f"{task.result}")
        finally:
            ex.shutdown()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
