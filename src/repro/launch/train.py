"""Distributed training entry point.

    python -m repro.launch.train --arch llama3.2-1b-smoke --steps 100

Small configs run for real on whatever devices exist (CPU here); full
configs lower for the production mesh (use launch/dryrun.py for that).
Wires: config -> Model -> DataPipeline (DynIMS-managed host cache) ->
pjit'd train step -> Trainer (checkpoint/restart, heartbeats,
stragglers).

Multi-pod notes baked in here rather than hidden in a doc:

* gradient all-reduce over ``pod`` overlaps the backward pass via XLA's
  latency-hiding scheduler; on real TPU set
  ``--xla_tpu_enable_latency_hiding_scheduler=true`` (XLA_FLAGS) --
  recorded in EXPERIMENTS.md §Perf as the collective-overlap knob.
* ``--compress`` enables int8 error-feedback gradient compression for
  the pod-crossing reduction (optim/compress.py).
"""

from __future__ import annotations

import argparse
import os
import tempfile

import jax
import numpy as np

from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b-smoke")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--data-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    from ..configs import get_config
    from ..configs.dynims import host_cache_params
    from ..core import GiB
    from ..core.plane import MemoryPlane, PlaneSpec
    from ..data import DataPipeline, PipelineConfig, ShardStore, write_corpus
    from ..models import Model
    from ..train import Trainer, TrainerConfig, TrainStepConfig

    cfg = get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params:,}")

    data_dir = args.data_dir or os.path.join(tempfile.gettempdir(),
                                             f"repro-corpus-{cfg.name}")
    if not os.path.exists(os.path.join(data_dir, "manifest.json")):
        write_corpus(data_dir, n_shards=32,
                     tokens_per_shard=max(args.seq_len * 16, 4096),
                     vocab_size=cfg.vocab_size, seed=args.seed)

    plane = MemoryPlane(PlaneSpec(params=host_cache_params(64 * GiB)))
    pipe = DataPipeline(
        ShardStore(data_dir),
        PipelineConfig(batch_size=args.batch_size, seq_len=args.seq_len,
                       seed=args.seed, cache_bytes=64 * 2**20),
        plane=plane)

    ckpt_dir = args.checkpoint_dir or os.path.join(
        tempfile.gettempdir(), f"repro-ckpt-{cfg.name}")
    trainer = Trainer(
        model, pipe,
        TrainStepConfig(microbatches=args.microbatches, peak_lr=args.lr,
                        warmup_steps=max(args.steps // 10, 1),
                        total_steps=args.steps, compress=args.compress),
        TrainerConfig(steps=args.steps, checkpoint_dir=ckpt_dir,
                      checkpoint_every=args.checkpoint_every),
        plane=plane)

    if args.resume:
        params, _ = trainer.resume(params)
    else:
        params, _ = trainer.fit(params)
    for row in trainer.metrics_log:
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in row.items()})
    pipe.close()


if __name__ == "__main__":
    main()
