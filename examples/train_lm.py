"""End-to-end driver: the ETL pipeline (distributed dataframe ops on the
runtime) feeds LM training, with checkpoints and resume — the paper's
'data engineering + deep learning under one execution framework'.

Presets:
  --preset ci    ~3M param model, 60 steps   (default; minutes on CPU)
  --preset full  ~100M param qwen3-style model, 300 steps
Resume after interruption:  just re-run with the same --ckpt dir.
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ParallelConfig, get_config, reduced
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import build_communicator
from repro.launch.mesh import make_local_mesh
from repro.train.data import SyntheticCorpus, etl_token_batches, make_events
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer


def model_for(preset: str) -> tuple[ModelConfig, ShapeConfig, int]:
    if preset == "full":
        # ~100M-param qwen3-family config (assigned arch, scaled depth/width)
        cfg = dataclasses.replace(
            get_config("qwen3-8b"), name="qwen3-100m", n_layers=12,
            d_model=640, n_heads=10, n_kv_heads=2, head_dim=64, d_ff=1792,
            vocab_size=32768, dtype="float32", remat=False)
        return cfg, ShapeConfig("t", "train", 256, 8), 300
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-8b")), n_layers=4, d_model=128, d_ff=256,
        vocab_size=2048)
    return cfg, ShapeConfig("t", "train", 128, 8), 60


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ci", choices=["ci", "full"])
    ap.add_argument("--ckpt", default="/tmp/repro_train_lm")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--synthetic", action="store_true",
                    help="skip the ETL stage and use the synthetic corpus")
    args = ap.parse_args()

    cfg, shape, steps = model_for(args.preset)
    steps = args.steps or steps
    print(f"model {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"{steps} steps of batch {shape.global_batch} x seq {shape.seq_len}")

    # ---- stage 1: ETL on the runtime --------------------------------------
    if args.synthetic:
        corpus = SyntheticCorpus(cfg.vocab_size)
        batches = corpus.batches(shape.global_batch, shape.seq_len, steps)
    else:
        comm = build_communicator(jax.devices(), axes=("df",))
        need = steps * shape.global_batch * shape.seq_len
        events = make_events(max(next_pow2(need * 2), 1 << 15),
                             cfg.vocab_size, seed=0)
        doc_meta = {"doc_id": np.arange(256, dtype=np.int32),
                    "weight": np.ones(256, np.float32)}
        etl = list(etl_token_batches(
            comm, events, doc_meta, batch=shape.global_batch,
            seq=shape.seq_len,
            capacity_per_rank=len(events["event_id"]) // comm.size * 2 + 64))
        print(f"[etl] produced {len(etl)} batches via join+sort pipeline")
        # cycle ETL output if shorter than the run
        batches = (etl[i % len(etl)] for i in range(steps))

    # ---- stage 2: training with checkpoint/restart ------------------------
    mesh = make_local_mesh(1, 1)
    ocfg = OptimizerConfig(peak_lr=3e-3, warmup_steps=max(steps // 10, 5),
                           total_steps=steps)
    trainer = Trainer(cfg, mesh, ParallelConfig(), shape, ocfg,
                      ckpt_dir=args.ckpt, ckpt_every=max(steps // 3, 10))
    state = trainer.maybe_restore()
    if state:
        print(f"[resume] restored step {state.step} from {args.ckpt}")
    state, losses = trainer.fit(batches, steps=steps, state=state,
                                log_every=max(steps // 15, 1))
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}) "
          f"at step {state.step}")
    assert losses[-1] < losses[0], "loss did not decrease"


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
