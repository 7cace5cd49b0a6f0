#!/usr/bin/env python3
"""Bring-up smoke of the accelerator path, through the library's entry points.

    python chip_smoke.py              # six phases on one chip
    python chip_smoke.py --chips 4    # the multi-chip sweep phase only
    python chip_smoke.py --tiny       # toy sizes; also runs on the CPU

Phases (one process; nothing here starts a child that needs the chip):

1. ``sweep``    -- ``sweep_demand`` (XLA engine) on an HPCC fleet of
   4096 nodes x 3000 intervals (5 min at the Table-I 100 ms interval)
   over 64 gains mixing paper-law and beyond-paper points, cache off
   and with ``spark-iterative-cache``'s CacheSpec; checked against the
   float64 replay of Eq. 1 (``oracle_history``) on 4 gains x 256 nodes.
2. ``pallas``   -- the same inputs with ``engine="pallas"``: the backend
   must be Mosaic with a ``tpu_custom_call`` in the compiled program,
   bit-identical to phase 1 with the cache off and on.
3. ``halving``  -- ``halving_tune`` over 512 candidates (512 -> 128 -> 32)
   on both engines: same survivors, same winner.
4. ``appgraph`` -- ``spark-dag`` on the XLA engine: dynamic-vs-static
   makespan gap >= 2x, and the queue carry against ``reference_makespan``.
5. ``plane``    -- a 4096-node ``MemoryPlane`` on the ``array`` backend
   for 300 ticks, grants against the ``scalar`` backend within 1e-4;
   tick p50/p99 against the control interval.
6. ``serve``    -- ``repro.launch.serve`` on ``llama3.2-1b`` at full width
   (weights from ``--seed``), 4 requests with the plane attached through
   ``DeviceMemoryMonitor``; tokens checked against the model's forward
   pass, and the monitor's total against the chip's ``bytes_limit``;
   the decode and flash attention kernels, compiled by Mosaic at the
   model's head shapes, against their references.

``--chips N`` runs only ``mesh``: the phase-1 sweep on N devices over the
``("gains",)`` mesh (bit-identical to one device), over the
``("gains", "nodes")`` mesh with 2 and N node shards (reduction
tolerance), and ``fleet_sweep_demand`` on ``hpcc-spark`` (bit-identical
to one device).

Every phase prints one line: name, device kind, sizes, wall time with
compile apart (first call minus a warm repeat), and its worst parity
deltas against their limits.  Any failed phase makes the exit code
nonzero.  The repo's ``RuntimeWarning``s (engine fallbacks) are errors.
On success the last line is ``{"ok": true, "device": {...}}``; without
an accelerator the script refuses to run (unless ``--tiny``, which
then prints no result line).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    nodes: int
    intervals: int
    oracle_nodes: int
    plane_nodes: int
    plane_ticks: int
    arch: str


FULL = Sizes(nodes=4096, intervals=3000, oracle_nodes=256, plane_nodes=4096,
             plane_ticks=300, arch="llama3.2-1b")
TINY = Sizes(nodes=128, intervals=240, oracle_nodes=64, plane_nodes=64,
             plane_ticks=30, arch="llama3.2-1b-smoke")

P99_TOL = 5e-4          # streaming quantile: 12-level bracket + half a bin
STAT_RTOL = 1e-4        # every other streamed statistic
MESH_RTOL, MESH_ATOL = 2e-4, 2e-3    # psum reassociation over node shards
LOGIT_GAP = 5e-2        # decode vs forward argmax, share of max |logit|


class Checks:
    """Parity deltas of one phase, each against its limit."""

    def __init__(self):
        self.rows = []

    def _add(self, name, delta, limit, ok):
        self.rows.append((name, delta, limit, bool(ok)))

    def exact(self, name, got, want):
        """Bit-identical FleetStats (or arrays): count differing values."""
        a, b = _flat(got), _flat(want)
        n_diff = int(sum(np.count_nonzero(~((x == y) | (np.isnan(x)
                                                        & np.isnan(y))))
                         for x, y in zip(a, b)))
        self._add(name, n_diff, "0 differing", n_diff == 0)

    def stats_close(self, name, got, want, rtol=STAT_RTOL, atol=1e-12,
                    rtol_p99=P99_TOL):
        worst, where, ok = 0.0, "", True
        for field, x, y in zip(got._fields, got, want):
            x = np.asarray(x, np.float64)
            y = np.asarray(y, np.float64)
            r = rtol_p99 if field == "p99_utilization" else rtol
            lim = atol + r * np.abs(y)
            err = np.abs(x - y)
            ok &= bool(np.all(err <= lim) and np.all(np.isfinite(x)))
            ratio = float(np.max(err / lim))
            if ratio > worst:
                worst, where = ratio, field
        self._add(name, f"{worst:.3g}({where})" if where else 0.0,
                  "1 (x limit)", ok)

    def within(self, name, delta, limit):
        self._add(name, float(delta), limit, np.isfinite(delta)
                  and delta <= limit)

    def holds(self, name, cond, detail=""):
        self._add(name, detail or ("yes" if cond else "no"), "", cond)

    def failed(self):
        return [r for r in self.rows if not r[3]]

    def summary(self):
        out = []
        for name, delta, limit, ok in self.rows:
            d = f"{delta:.3g}" if isinstance(delta, float) else str(delta)
            lim = f"<= {limit}" if limit != "" else ""
            out.append(f"{name}={d}{lim}{'' if ok else ' FAIL'}")
        return " ".join(out)


def _flat(x):
    if hasattr(x, "_fields"):
        return [np.asarray(v) for v in x]
    return [np.asarray(x)]


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _first_and_warm(fn):
    """(result, warm seconds, compile seconds ~ first minus warm)."""
    first, t_first = _timed(fn)
    warm, t_warm = _timed(fn)
    return first, warm, t_warm, max(t_first - t_warm, 0.0)


def _fleet(sz):
    from repro.lab import ScenarioSpec
    spec = ScenarioSpec(name="hpcc-fleet", family="hpcc", n_nodes=sz.nodes,
                        n_intervals=sz.intervals,
                        description="Table-I HPCC fleet (Fig.-1 trace, "
                                    "phase-shifted, 125 GiB nodes)")
    return spec, spec.build_demand(seed=SEED), spec.build_node_memory(seed=SEED)


def _phase_gains():
    """48 paper-law points plus 16 with an asymmetric grant gain."""
    from repro.configs.dynims import PAPER_TABLE_I
    from repro.lab import grid_gains
    paper = grid_gains(PAPER_TABLE_I, lam=np.linspace(0.1, 1.8, 8),
                       r0=np.linspace(0.88, 0.98, 6))
    beyond = grid_gains(PAPER_TABLE_I, lam=np.linspace(0.3, 1.6, 4),
                        r0=np.linspace(0.90, 0.97, 4), lam_grant=(0.25,))
    return paper.concat(beyond)


def _halving_candidates():
    """512 points: a 16x16 paper-law plane and the three law variants."""
    from repro.configs.dynims import PAPER_TABLE_I
    from repro.lab import grid_gains
    lam8, r08 = np.linspace(0.3, 1.6, 8), np.linspace(0.90, 0.97, 8)
    g = grid_gains(PAPER_TABLE_I, lam=np.linspace(0.1, 1.8, 16),
                   r0=np.linspace(0.88, 0.98, 16))
    g = g.concat(grid_gains(PAPER_TABLE_I, lam=lam8, r0=r08,
                            lam_grant=(0.25,)))
    g = g.concat(grid_gains(PAPER_TABLE_I, lam=lam8, r0=r08,
                            deadband=(0.005,)))
    return g.concat(grid_gains(PAPER_TABLE_I, lam=lam8,
                               r0=np.linspace(0.90, 0.97, 16),
                               feedforward=(0.5,)))


# ---------------------------------------------------------------------------
# Phases: each returns (sizes text, timing text, Checks)
# ---------------------------------------------------------------------------

def phase_sweep(ctx, sz):
    from repro.configs.dynims import PAPER_TABLE_I
    from repro.core.traces import GiB
    from repro.lab import get_scenario, sweep_demand
    from repro.lab.sweep import oracle_history

    spec, demand, m = _fleet(sz)
    gains = _phase_gains()
    cache = get_scenario("spark-iterative-cache").cache
    chk, times, out = Checks(), [], {}
    for label, c in (("off", None), ("on", cache)):
        first, warm, t_warm, t_comp = _first_and_warm(lambda: sweep_demand(
            demand, gains, node_memory=m, interval_s=spec.interval_s,
            cache=c))
        chk.exact(f"cache_{label}.repeat", warm, first)
        out[label] = first
        times.append(f"cache_{label} {t_warm:.3f}s+compile {t_comp:.1f}s")
    ctx["demand"], ctx["m"], ctx["spec"] = demand, m, spec
    ctx["gains"], ctx["cache"], ctx["xla"] = gains, cache, out

    n_o = sz.oracle_nodes
    idx = [0, 27, 48, 63]                      # 2 paper-law, 2 asymmetric
    sub = sweep_demand(demand[:n_o], gains.take(idx), node_memory=m[:n_o],
                       interval_s=spec.interval_s)
    worst = {"mean": 0.0, "max": 0.0, "cap": 0.0, "p99": 0.0}
    for j, i in enumerate(idx):
        utils, caps = oracle_history(demand[:n_o], m[:n_o],
                                     gains.params_at(i, PAPER_TABLE_I))
        for key, got, want in (
                ("mean", sub.mean_utilization[j], utils.mean()),
                ("max", sub.max_utilization[j], utils.max()),
                ("cap", sub.mean_capacity_gib[j], caps.mean() / GiB)):
            worst[key] = max(worst[key],
                             abs(float(got) - want) / abs(want))
        worst["p99"] = max(worst["p99"], abs(float(sub.p99_utilization[j])
                                             - np.quantile(utils, 0.99)))
    for key in ("mean", "max", "cap"):
        chk.within(f"oracle.{key}_rel", worst[key], STAT_RTOL)
    chk.within("oracle.p99_abs", worst["p99"], P99_TOL)
    sizes = (f"{sz.nodes} nodes x {sz.intervals} intervals x {len(gains)} "
             f"gains; oracle {len(idx)} gains x {n_o} nodes")
    return sizes, "; ".join(times), chk


def phase_pallas(ctx, sz):
    import jax
    import jax.numpy as jnp
    from repro.lab import pallas_sweep as ps
    from repro.lab import sweep_demand
    from repro.lab.sweep import paper_law_mask

    chk = Checks()
    backend = ps._backend(None)
    on_cpu = jax.default_backend() == "cpu"
    chk.holds("backend", backend == ("scan" if on_cpu else "mosaic"),
              backend)
    gains, demand, m = ctx["gains"], ctx["demand"], ctx["m"]
    spec = ctx["spec"]
    if not on_cpu:
        paper = gains.take(np.flatnonzero(paper_law_mask(gains)))
        lanes = ps.TILE_GAINS * 2
        fn = ps.sweep_program(paper.slice(0, lanes), backend=backend,
                              cache=ctx["cache"], interval_s=spec.interval_s)
        shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (sz.intervals, sz.nodes), (ps._N_NODE_ROWS, sz.nodes),
            (ps._N_PARAM_ROWS, lanes), (1, lanes))]
        hlo = fn.lower(*shapes).compile().as_text()
        chk.holds("tpu_custom_call", "tpu_custom_call" in hlo)
    times = []
    for label, c in (("off", None), ("on", ctx["cache"])):
        first, warm, t_warm, t_comp = _first_and_warm(lambda: sweep_demand(
            demand, gains, node_memory=m, interval_s=spec.interval_s,
            cache=c, engine="pallas"))
        chk.exact(f"cache_{label}.repeat", warm, first)
        chk.exact(f"vs_xla.cache_{label}", first, ctx["xla"][label])
        times.append(f"cache_{label} {t_warm:.3f}s+compile {t_comp:.1f}s")
    sizes = f"same as sweep; backend={backend}"
    return sizes, "; ".join(times), chk


def phase_halving(ctx, sz):
    from repro.lab import halving_tune

    cands = _halving_candidates()
    chk, res, times = Checks(), {}, []
    for engine in ("xla", "pallas"):
        r, t = _timed(lambda: halving_tune(ctx["spec"], gains=cands,
                                           engine=engine, seed=SEED))
        res[engine] = r
        times.append(f"{engine} {t:.2f}s (incl. compile)")
    x, p = res["xla"], res["pallas"]

    def survivors(r):
        g = r.sweep.gains
        return {tuple(float(getattr(g, f)[i]) for f in
                      ("r0", "lam", "lam_grant", "deadband", "feedforward"))
                for i in range(len(g) - 1)}          # baseline is last
    rounds = [r["n_candidates"] for r in x.rounds]
    chk.holds("schedule", rounds[:2] == [512, 128] and
              len(survivors(x)) == 32, str(rounds))
    chk.holds("same_survivors", survivors(x) == survivors(p),
              f"{len(survivors(x) ^ survivors(p))} differ")
    chk.holds("same_winner", x.params == p.params)
    chk.within("winner_score_rel", abs(x.score - p.score)
               / max(abs(x.score), 1e-12), STAT_RTOL)
    sizes = (f"{len(cands)} candidates -> {' -> '.join(map(str, rounds))}"
             f" at {sz.nodes} nodes x {sz.intervals} intervals")
    return sizes, "; ".join(times), chk


def phase_appgraph(ctx, sz):
    from repro.configs.dynims import PAPER_TABLE_I
    from repro.core.cluster_sim import paper_controller_params
    from repro.core.traces import GiB
    from repro.lab import (GainSet, get_scenario, reference_makespan,
                           sweep_demand)

    spec = get_scenario("spark-dag")
    demand = spec.build_demand(seed=SEED)
    m = spec.build_node_memory(seed=SEED)
    static = GainSet.from_params(paper_controller_params(
        lam=0.0, u_min=25.0 * GiB, u_max=25.0 * GiB))
    kw = dict(node_memory=m, interval_s=spec.interval_s, cache=spec.cache,
              app_graph=spec.app_graph)
    chk = Checks()
    (st, dyn), t = _timed(lambda: (
        float(sweep_demand(demand, static, **kw).makespan[0]),
        float(sweep_demand(demand, GainSet.from_params(PAPER_TABLE_I),
                           **kw).makespan[0])))
    chk.holds("gap>=2x", st / dyn >= 2.0, f"{st / dyn:.3f}x "
              f"({st:.1f}s static / {dyn:.1f}s dynamic)")
    # Carry parity against the float64 replay, which mirrors the queue
    # carry on a fixed grant (it has no cache model): the static grant
    # with the cache off, within one interval per stage row.
    got = float(sweep_demand(demand, static, node_memory=m,
                             interval_s=spec.interval_s,
                             app_graph=spec.app_graph).makespan[0])
    ref = reference_makespan(spec.app_graph, demand, m,
                             np.full(demand.shape, 25.0 * GiB),
                             interval_s=spec.interval_s)["makespan_s"]
    slack = (spec.app_graph.n_stage_rows + 1) * spec.interval_s
    chk.within("vs_reference_makespan_s", abs(got - ref), slack)
    sizes = f"{demand.shape[0]} nodes x {demand.shape[1]} intervals"
    return sizes, f"{t:.2f}s (incl. compile)", chk


def phase_plane(ctx, sz):
    from repro.configs.dynims import PAPER_TABLE_I
    from repro.core import (MemoryPlane, NodeSpec, PlaneSpec,
                            SimulatedMonitor, StoreRegistry)

    n, ticks = sz.plane_nodes, sz.plane_ticks
    demand, m = ctx["demand"], ctx["m"]
    rows = [demand[i % demand.shape[0], :ticks] for i in range(n)]

    def plane(backend):
        return MemoryPlane(PlaneSpec(params=PAPER_TABLE_I, backend=backend,
                                     nodes=tuple(
            NodeSpec(f"n{i}", monitor=SimulatedMonitor(
                f"n{i}", total=float(m[i % len(m)]), usage=rows[i]),
                registry=StoreRegistry(), u0=PAPER_TABLE_I.u_max)
            for i in range(n))))

    planes = {b: plane(b) for b in ("array", "scalar")}
    tick_s, worst, ok = [], 0.0, True
    t_scalar = 0.0
    for _ in range(ticks):
        t0 = time.perf_counter()
        got = planes["array"].tick()
        tick_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        want = planes["scalar"].tick()
        t_scalar += time.perf_counter() - t0
        a = {x.node: x.u_next for x in got}
        b = {x.node: x.u_next for x in want}
        ok &= a.keys() == b.keys() and len(a) == n
        ga = np.array([a.get(k, np.nan) for k in b])
        gb = np.array(list(b.values()))
        lim = 1e4 + STAT_RTOL * np.abs(gb)               # bytes
        ok &= bool(np.all(np.abs(ga - gb) <= lim))
        worst = max(worst, float(np.max(np.abs(ga - gb) / lim)))
    chk = Checks()
    chk.holds("all_nodes_every_tick", ok)
    chk.within("grants_vs_scalar", worst, 1.0)
    warm = np.asarray(tick_s[1:])
    iv = PAPER_TABLE_I.interval_s
    times = (f"array tick p50 {np.percentile(warm, 50) * 1e3:.2f}ms "
             f"p99 {np.percentile(warm, 99) * 1e3:.2f}ms vs interval "
             f"{iv * 1e3:.0f}ms (first tick {tick_s[0]:.2f}s incl. "
             f"compile); scalar {t_scalar / ticks * 1e3:.1f}ms/tick")
    return f"{n} nodes x {ticks} ticks", times, chk


def phase_serve(ctx, sz):
    import jax
    import jax.numpy as jnp
    from repro.core.monitor import DeviceMemoryMonitor
    from repro.launch import serve

    n_req, prompt, new = 4, 16, 8
    argv = ["--arch", sz.arch, "--requests", str(n_req), "--prompt-len",
            str(prompt), "--max-new", str(new), "--max-len", "64",
            "--max-batch", "4", "--seed", str(SEED)]
    engine, t_first = _timed(lambda: serve.main(argv))
    chk = Checks()
    done = list(engine.finished.values())
    vocab = engine.model.cfg.vocab_size
    chk.holds("answered", len(done) == n_req and all(
        len(r.output) == new and 0 <= min(r.output) and max(r.output) < vocab
        for r in done), f"{len(done)}/{n_req}")
    # Warm repeat through the compiled decode step.
    rng = np.random.default_rng(SEED + 1)
    for _ in range(n_req):
        engine.submit(rng.integers(0, vocab, prompt), max_new_tokens=new)
    _, t_warm = _timed(engine.run_until_drained)
    # Reference: the parallel forward pass over prompt + generated tokens
    # must rank every greedily decoded token at (or within LOGIT_GAP of)
    # its top logit.
    toks = np.stack([np.concatenate([r.prompt, r.output[:-1]])
                     for r in done]).astype(np.int32)
    logits = np.asarray(jax.jit(engine.model.forward)(
        engine.params, {"tokens": jnp.asarray(toks)})[0], np.float32)
    gap = 0.0
    for b, r in enumerate(done):
        for j, tok in enumerate(r.output):
            row = logits[b, prompt - 1 + j]
            gap = max(gap, float(row.max() - row[tok])
                      / float(np.abs(row).max()))
    chk.within("greedy_vs_forward_gap", gap, LOGIT_GAP)
    mon = engine.monitor
    dev = jax.devices()[0]
    chk.holds("device_monitor", isinstance(mon, DeviceMemoryMonitor))
    total = mon.sample().total
    if dev.platform != "cpu":
        limit = dev.memory_stats()["bytes_limit"]
        chk.holds("monitor_total==bytes_limit", total == limit,
                  f"{total:.0f}")
    _attention_kernels(engine.model.cfg, chk, dev.platform != "cpu")
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(engine.params))
    sizes = (f"{sz.arch} ({n_params / 1e9:.2f}B params) {n_req} requests x "
             f"{prompt}+{new} tokens; decode step jit-compiled; decode and "
             f"flash kernels at its head shapes")
    times = (f"first wave {t_first:.1f}s incl. init+compile; warm wave "
             f"{t_warm:.2f}s")
    return sizes, times, chk


def _attention_kernels(cfg, chk, on_chip):
    """The decode and flash Pallas kernels at the model's head shapes.

    bf16 operands against the float32 references (the kernel tests'
    tolerance); on an accelerator each must compile to a Mosaic call.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import decode_attention_op, flash_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ref import attention_ref

    rng = np.random.default_rng(SEED)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def rand(*shape):
        return jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)

    def f32(args):
        return [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
                for a in args]

    b, s = 4, 512
    lens = jnp.asarray(rng.integers(1, s, b), jnp.int32)
    runs = {
        "decode": (functools.partial(decode_attention_op, block_k=128),
                   decode_attention_ref,
                   (rand(b, h, hd), rand(b, s, kv, hd), rand(b, s, kv, hd),
                    lens)),
        "flash": (flash_attention_op, attention_ref,
                  (rand(1, 256, h, hd), rand(1, 256, kv, hd),
                   rand(1, 256, kv, hd)))}
    for name, (kernel, ref, args) in runs.items():
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref(*f32(args)))
        got = np.asarray(kernel(*args), np.float32)
        chk.within(f"{name}_kernel_vs_ref", float(np.max(np.abs(got - want))),
                   3e-2)
        if on_chip:
            hlo = jax.jit(kernel).lower(*args).compile().as_text()
            chk.holds(f"{name}_kernel_mosaic", "tpu_custom_call" in hlo)


def phase_mesh(ctx, sz):
    import jax
    from repro.fleet import run_fleet_sweep

    from repro.lab import sweep_demand

    n_dev = len(jax.devices())
    spec, demand, m = _fleet(sz)
    gains = _phase_gains()
    chk, times = Checks(), []
    kw = dict(node_memory=m, interval_s=spec.interval_s)
    one, t1 = _timed(lambda: sweep_demand(demand, gains, devices=1, **kw))
    many, tn = _timed(lambda: sweep_demand(demand, gains, devices=n_dev,
                                           **kw))
    chk.exact("gains_mesh_vs_1", many, one)
    times.append(f"1 dev {t1:.2f}s, {n_dev} dev {tn:.2f}s (incl. compile)")
    for ns in sorted({2, n_dev}):
        got, t = _timed(lambda: sweep_demand(demand, gains, devices=n_dev,
                                             node_shards=ns, **kw))
        chk.stats_close(f"nodes{ns}_mesh_vs_1", got, one, rtol=MESH_RTOL,
                        atol=MESH_ATOL, rtol_p99=MESH_RTOL)
        times.append(f"node_shards={ns} {t:.2f}s")
    f_one, _ = run_fleet_sweep("hpcc-spark", gains, seed=SEED, devices=1)
    f_many, t = _timed(lambda: run_fleet_sweep("hpcc-spark", gains,
                                               seed=SEED, devices=n_dev)[0])
    chk.exact("fleet_gains_mesh_vs_1", f_many, f_one)
    times.append(f"fleet {t:.2f}s")
    sizes = (f"{n_dev} devices; {sz.nodes} nodes x {sz.intervals} "
             f"intervals x {len(gains)} gains; fleet hpcc-spark")
    return sizes, "; ".join(times), chk


PHASES = (("sweep", phase_sweep), ("pallas", phase_pallas),
          ("halving", phase_halving), ("appgraph", phase_appgraph),
          ("plane", phase_plane), ("serve", phase_serve))
SEED = 0


def main(argv=None) -> int:
    global SEED
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="1: the six phases; N > 1: the N-device mesh "
                         "phase only")
    ap.add_argument("--tiny", action="store_true",
                    help="toy sizes (a rehearsal; runs on the CPU too)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    SEED = args.seed

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.tiny:
        print("chip_smoke: JAX found no accelerator; refusing to run",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    warnings.simplefilter("error", RuntimeWarning)
    enable_compile_cache()
    sz = TINY if args.tiny else FULL
    phases = PHASES if args.chips == 1 else (("mesh", phase_mesh),)
    ctx, failed = {}, []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            sizes, times, chk = fn(ctx, sz)
            bad = chk.failed()
        except Exception as e:      # reported, and fails the run below
            traceback.print_exc()
            sizes, times, chk, bad = "-", "-", Checks(), [repr(e)]
        status = "FAIL" if bad else "ok"
        print(f"[{name}] {status} kind={dev.device_kind!r} | {sizes} | "
              f"{times} | total {time.perf_counter() - t0:.1f}s | "
              f"{chk.summary() or bad}", flush=True)
        if bad:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    if dev.platform == "cpu":
        print("chip_smoke: rehearsal passed on cpu (no device result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
