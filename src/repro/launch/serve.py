"""Serving entry point: continuous batching over a DynIMS-managed pool.

    python -m repro.launch.serve --arch llama3.2-1b-smoke --requests 16

Runs the engine against synthetic prompts, printing throughput and pool
behaviour.  ``--burst`` simulates a host/device memory burst mid-run by
shrinking the KV pool through its controller (the paper's Fig. 7
scenario on the serving path) and reports preemption/recovery.
``--retune`` closes the ReplayLoop on the serving path: the plane
records its own KV-pool telemetry during the first wave of requests,
``retune_online`` re-tunes the pool gains on the captured workload and
hot-swaps the winner into the live plane, and a second wave serves
under the new parameter epoch.
"""

from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import numpy as np

from .compile_cache import enable_compile_cache


def main(argv: Optional[Sequence[str]] = None):
    """Run the CLI on ``argv`` (default ``sys.argv``); returns the engine."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b-smoke")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--burst", action="store_true")
    ap.add_argument("--retune", action="store_true",
                    help="capture the KV-pool workload, re-tune the pool "
                         "gains on it online, hot-swap, serve a second wave")
    ap.add_argument("--retune-budget", type=int, default=16)
    ap.add_argument("--retune-restarts", type=int, default=2,
                    help="supervised retune: restart a crashed tuning "
                         "round up to N times with backoff")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    from ..configs import get_config
    from ..configs.dynims import hbm_pool_params
    from ..core.plane import MemoryPlane, PlaneSpec
    from ..models import Model
    from ..serving import ServingConfig, ServingEngine

    cfg = get_config(args.arch)
    model = Model(cfg, remat="none")
    params = model.init(jax.random.key(args.seed))
    plane = MemoryPlane(PlaneSpec(params=hbm_pool_params(),
                                  record=2048 if args.retune else 0))
    engine = ServingEngine(model, params,
                           ServingConfig(max_batch=args.max_batch,
                                         max_len=args.max_len),
                           plane=plane)
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        engine.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                      max_new_tokens=args.max_new)

    t0 = time.time()
    if args.burst:
        for _ in range(10):
            engine.step()
        print("-- memory burst: shrinking KV pool to 25% --")
        engine.pool.set_capacity(engine.pool.capacity() * 0.25)
        print("   (preempted sequences requeue; with no sustained device "
              "pressure the plane re-grants capacity on the next tick)")
        for _ in range(5):
            engine.step()
    finished = engine.run_until_drained()
    dt = time.time() - t0
    stats = engine.stats()
    toks = sum(len(r.output) for r in finished.values())
    print(f"served {len(finished)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s on {jax.devices()[0].device_kind})")
    print("engine:", stats)

    if args.retune:
        from ..lab.tune import retune_online
        print("-- ReplayLoop: re-tuning pool gains on the captured "
              "KV workload --")
        handle = retune_online(plane, name="kv-pool-replay",
                               budget=args.retune_budget, block=False,
                               restarts=args.retune_restarts)
        result = handle.result()
        print("  ", result.summary())
        if handle.restarts:
            print(f"   retune supervisor: {handle.attempts} attempts, "
                  f"{handle.restarts} restarts")
        p = plane.params
        print(f"   live params now: r0={p.r0:.4f} lam={p.lam:.4f} "
              f"lam_grant={p.lam_grant} (epoch {plane.epoch})")
        print("  ", plane.health().summary())
        for _ in range(max(args.requests // 2, 1)):
            engine.submit(rng.integers(0, cfg.vocab_size, args.prompt_len),
                          max_new_tokens=args.max_new)
        wave2 = engine.run_until_drained()
        print(f"   second wave under epoch {plane.epoch}: served "
              f"{len(wave2)} requests")
    return engine


if __name__ == "__main__":
    main()
