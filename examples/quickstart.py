"""Quickstart: the three pillars of the framework in ~60 seconds on CPU.

  1. a pilot + heterogeneous runtime executing dataframe tasks on private
     sub-mesh communicators (the paper's contribution),
  2. a distributed dataframe op validated against numpy,
  3. a few training steps of a (reduced) assigned architecture.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import ParallelConfig, get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core import (PilotDescription, PilotManager, RaptorMaster,
                        TaskDescription)
from repro.dataframe import ops_dist as D
from repro.launch.mesh import make_local_mesh
from repro.train.data import SyntheticCorpus
from repro.train.trainer import Trainer


def main():
    # ---- 1. pilot runtime -------------------------------------------------
    pm = PilotManager()                       # all local devices
    pilot = pm.submit_pilot(PilotDescription(n_devices=len(jax.devices())))
    master = RaptorMaster(pilot)

    def sort_task(comm):
        rng = np.random.default_rng(0)
        data = {"k": rng.integers(0, 10_000, 5_000).astype(np.int32)}
        table = D.shard_table(comm, data, 5_000 // comm.size * 2 + 64)
        out, overflow = D.make_dist_sort(comm.mesh, "k")(table)
        got = D.collect_table(out)["k"]
        assert (np.diff(got) >= 0).all() and len(got) == 5_000
        return float(got[-1])

    master.submit(TaskDescription(name="sort", ranks=len(jax.devices()),
                                  fn=sort_task, tags={"pipeline": "etl"}))
    report = master.run()
    print(f"[runtime] sort task done in {report.makespan:.2f}s, "
          f"comm build {report.overhead_total * 1e3:.2f}ms, "
          f"max key = {report.tasks[0].result}")

    # ---- 2. train a reduced assigned arch ---------------------------------
    cfg = dataclasses.replace(reduced(get_config("qwen3-8b")), n_layers=2)
    mesh = make_local_mesh(1, 1)
    trainer = Trainer(cfg, mesh, ParallelConfig(),
                      ShapeConfig("t", "train", 64, 4))
    corpus = SyntheticCorpus(cfg.vocab_size)
    state, losses = trainer.fit(corpus.batches(4, 64, 12), steps=12,
                                log_every=4)
    print(f"[train] loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"over {len(losses)} steps")
    assert losses[-1] < losses[0]
    print("quickstart OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
