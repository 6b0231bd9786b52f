"""Serving example, in two acts on the same pilot runtime:

1. the STATIC engine as one opaque task next to an ETL task (MPMD
   heterogeneous execution — the original demo);
2. the CONTINUOUS engine through ``ServeDriver``: prefill and decode as
   separately-tagged scheduler pipelines, serve telemetry in the session
   trace, and a ``ServeAutoscaler`` watching the queue/slot gauges.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core import (PilotDescription, PilotManager, RaptorMaster,
                        ResourceManager, SchedulerSession, TaskDescription,
                        ThreadExecutor)
from repro.dataframe import ops_dist as D
from repro.models import get_model
from repro.serve import (AutoscaleConfig, ContinuousEngine, Request,
                         ServeAutoscaler, ServeDriver, ServeEngine,
                         greedy_reference)


def main():
    cfg = dataclasses.replace(reduced(get_config("granite-3-8b")), n_layers=2)
    api = get_model(cfg)
    params = api.init(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    requests = [
        Request(prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                max_new_tokens=8, uid=i)
        for i, L in enumerate([4, 6, 4, 6, 5, 4])
    ]

    def serve_task(comm):
        engine = ServeEngine(cfg, params, max_batch=4, max_seq=32)
        return engine.run_requests(requests)

    def etl_task(comm):
        data = {"k": rng.integers(0, 999, 2000).astype(np.int32)}
        t = D.shard_table(comm, data, 2000 // comm.size * 2 + 64)
        out, _ = D.make_dist_sort(comm.mesh, "k")(t)
        return int(D.collect_table(out)["k"][-1])

    pm = PilotManager()
    n = len(jax.devices())
    pilot = pm.submit_pilot(PilotDescription(n_devices=n))
    master = RaptorMaster(pilot)
    master.submit(TaskDescription(name="serve", ranks=max(n // 2, 1),
                                  fn=serve_task, tags={"pipeline": "serve"}))
    master.submit(TaskDescription(name="etl", ranks=max(n // 2, 1),
                                  fn=etl_task, tags={"pipeline": "etl"}))
    rep = master.run(timeout=600)
    serve_out = next(t.result for t in rep.tasks if t.desc.name == "serve")
    etl_out = next(t.result for t in rep.tasks if t.desc.name == "etl")
    print(f"[runtime] served {len(serve_out)} requests + ETL max key {etl_out} "
          f"in {rep.makespan:.2f}s")

    # verify one sequence against the full-forward oracle
    ref = greedy_reference(cfg, params, requests[0].prompt, 8)
    assert (serve_out[0] == ref).all()
    print("generated (req 0):", serve_out[0].tolist(), "== oracle ✓")

    # -- act 2: continuous batching as scheduler pipelines ----------------
    engine = ContinuousEngine(cfg, params, max_batch=2, max_seq=32)
    ex = ThreadExecutor(build_comm=False, tick=0.01)
    sess = SchedulerSession(ex, ResourceManager(["d0", "d1"]), tick=0.01)
    autoscaler = ServeAutoscaler(
        grow=lambda: ex.inject_grow([f"g{len(autoscaler.actions)}"]),
        retire=lambda: None,
        config=AutoscaleConfig(queue_high=2, sustain_s=0.01,
                               cooldown_s=0.05, max_workers=2))
    driver = ServeDriver(engine, sess, autoscaler=autoscaler)
    out = driver.run(requests, timeout=300)
    rep = sess.drain(timeout=60).close()
    for r in requests:
        ref = greedy_reference(cfg, params, r.prompt, r.max_new_tokens)
        assert (out[r.uid] == ref).all()
    pipes = sorted({e.pipeline for e in rep.trace if e.kind == "dispatch"})
    tel = [e for e in rep.trace if e.kind == "telemetry"]
    print(f"[continuous] {len(out)} requests through pipelines {pipes}, "
          f"{engine.metrics.get('serve_decode_steps')} decode rounds, "
          f"{len(tel)} telemetry events, "
          f"{len(autoscaler.actions)} autoscale actions == oracle ✓")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
