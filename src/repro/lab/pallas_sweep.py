"""PallasSweep: the fused (gains x nodes) sweep kernel + in-scan halving.

The ``engine="pallas"`` backend of the unified sweep API: the Mosaic
lowering of the closed-loop step in :mod:`.step` (law, cache carry,
streamed Kahan / fixed-bin-quantile accumulators) over a stacked
``(S, L, N)`` state block:

* **S** state planes (law + cache + accumulator lanes, all f32,
  :func:`~repro.lab.step.plane_names`),
* **L** gain lanes, tiled :data:`TILE_GAINS` at a time,
* **N** nodes as the vector axis.

Grid ``(gain_tiles, node_tiles, time_chunks)`` with semantics
``("parallel", "parallel", "arbitrary")``: each program keeps its
(:data:`TILE_GAINS` x :data:`NODE_TILE`) tile's state in VMEM scratch
across the sequential time axis, walks :data:`TIME_CHUNK` intervals as
an unrolled vector loop, and emits the uint16 utilization codes the
quantile bisection consumes.  Nothing of size T x N ever leaves the
device; per segment the host sees O(L) scalars.

**Backends.**  On an accelerator the Mosaic kernel runs, compiled.  On
the CPU backend the same step lowers through one ``lax.scan`` (fast, so
parity tests and tier-1 stay runnable); the true ``pallas_call`` runs
under ``interpret=True`` only when forced (``PALLAS_SWEEP_INTERPRET=1``
or ``force_interpret=True``), because XLA emulation of a Pallas grid is
~10x slower than the native scan.

**Numerics.**  State and every accumulator stay f32; ``precision="bf16"``
stores only the *demand stream* in bf16 -- it is read once per step and
upcast before use, so no accumulator ever rounds through bf16.

**In-scan successive halving** (:func:`halving_sweep`): the candidate
lanes, the always-alive baseline lane, and the per-lane ``alive`` mask
live in one jitted program.  At each horizon boundary (T/8, T/2 by
default) the program finalizes prefix stats *on device*, scores them
with the tuning objective, argsorts the candidate lanes, and gathers
the survivors (plus the baseline) into a smaller lane block -- no host
round-trip, no re-dispatch.  Lanes that only pad the survivor block up
to the tile width are marked dead in the alive mask; an all-dead tile
is skipped by ``pl.when`` and writes deterministic zero codes.  Because
every lane's closed loop is independent and deterministic, the running
prefix accumulators at a boundary are bit-identical to a from-scratch
run truncated there -- which is exactly what host-side
:func:`~repro.lab.tune.halving_tune` scores -- so the in-scan survivors
match the host survivors on the same grid.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.runtime import (count, dispatch_guard, record_trace,
                                sanitizers_enabled, span)
from . import step
from ._compat import warn_once
from .scenarios import CacheSpec
from .score import FleetStats, default_score
from .sweep import (GainSet, _check_args, _Chunks, _law_classes,
                    _pad_gains, _resolve_chunk, _run_chunks,
                    _run_law_classes, _spec_digest, plan_specialization,
                    resolve_devices, stage_demand)

# Gain lanes per kernel tile (the sublane axis of the VPU's 8x128
# geometry) and intervals walked per sequential grid step.  A segment
# whose length is not a TIME_CHUNK multiple uses its largest divisor.
TILE_GAINS = 8
TIME_CHUNK = 32
# Nodes per kernel tile (lanes of the vector unit), used when it
# divides the fleet; otherwise a tile spans the whole node axis.
NODE_TILE = 512

# Rows of the packed per-lane parameter matrix (P, L) are the step's
# GAIN_ROWS.  The threshold rows are precomputed in f32 on the host with
# the exact IEEE ops the XLA engine traces; the reciprocal row is filled
# on device by :func:`_reciprocals` with the engine's own division,
# which an accelerator need not round like the host.  Both engines then
# clamp and count against bit-identical constants.
_R0, _INV_R0, _THR_OVER, _THR_SETTLE = map(
    step.GAIN_ROWS.index, ("r0", "inv_r0", "thr_over", "thr_settle"))
_N_PARAM_ROWS = len(step.GAIN_ROWS)

# Rows of the packed per-node constant matrix (R, N): the step's
# NODE_ROWS.
_M, _INV_M, _W, _INV_W = map(step.NODE_ROWS.index,
                             ("m", "inv_m", "w", "inv_w"))
_N_NODE_ROWS = len(step.NODE_ROWS)


def _advance(state, d, t, g, nodes, loop: step.Loop,
             names: Tuple[str, ...], wf0):
    """One interval of :func:`repro.lab.step.step` on a tuple of (L, N)
    state planes in ``names`` order, for the Mosaic kernel body and both
    CPU lowerings.  A *tuple*, not the stacked (S, L, N) block: a
    stacked scan carry forces XLA's CPU backend to re-materialize the
    whole block every interval (~30x slower on the cache path), while
    tuple rows update in place; the lowerings stack only at segment and
    chunk boundaries."""
    out, codes = step.step(dict(zip(names, state)), d, t, g, nodes, loop,
                           wf0)
    # Static-length genexp of lazily indexed rows -- no host iteration.
    return (tuple(out[n] for n in names),  # planecheck: ignore[PC-T002]
            codes)


def _views(cols, rows, loop: step.Loop):
    """The step's gain and node rows over the packed blocks, and the
    cache's warm fraction ``wf0``."""
    g, nodes = step.Rows(cols, step.GAIN_ROWS), step.Rows(rows,
                                                          step.NODE_ROWS)
    wf0 = (step.warm_seed(loop.cache, g.u_max, nodes)[1]
           if loop.cache is not None else None)
    return g, nodes, wf0


def _init_state(cols, rows, d0, loop: step.Loop, names: Tuple[str, ...]):
    """Stacked initial state for (L, N) lanes, group by group."""
    g, nodes = step.Rows(cols, step.GAIN_ROWS), step.Rows(rows,
                                                          step.NODE_ROWS)
    zeros = jnp.zeros((cols.shape[1], rows.shape[-1]), jnp.float32)
    planes = dict.fromkeys(names, zeros)
    planes["u"] = zeros + g.u_max
    planes.update(step.acc_init(zeros, zeros, carried_clock=False))
    if loop.cache is not None:
        planes["resident"] = zeros + step.warm_seed(loop.cache, g.u_max,
                                                    nodes)[0]
    if not loop.paper_law:
        # Seed v_prev with the first interval's usage so the slope term
        # is exactly zero before there is a previous observation.
        planes["v_prev"] = step.observe(planes, d0, loop)
    return jnp.stack([planes[n] for n in names])


# ---------------------------------------------------------------------------
# The kernel and its two lowerings
# ---------------------------------------------------------------------------

def _sweep_kernel(dem_ref, lp_ref, np_ref, alive_ref, sin_ref,
                  sout_ref, codes_ref, state_ref, *, t0: int, chunk: int,
                  n_chunks: int, loop: step.Loop, names: Tuple[str, ...]):
    """One (gain_tile, node_tile, time_chunk) program of the fused sweep.

    The tile's stacked state lives in VMEM scratch across the
    sequential time axis; the chunk is an unrolled vector loop with
    (lane x node) dims vectorized.  A tile whose ``alive`` mask is all
    zero (pure survivor-padding lanes after an in-scan halving gather)
    skips the body entirely and writes deterministic zero codes.
    """
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _seed():
        state_ref[...] = sin_ref[...]

    live = jnp.any(alive_ref[...] > 0.5)

    @pl.when(live)
    def _body():
        lanes = lp_ref[...]                             # (TG, P)
        cols = tuple(lanes[:, i:i + 1]                  # P x (TG, 1)
                     for i in range(_N_PARAM_ROWS))
        g, nodes, wf0 = _views(cols, np_ref[...], loop)  # rows (R, NT)
        stacked = state_ref[...]
        state = tuple(stacked[i] for i in range(len(names)))
        for k in range(chunk):
            d = dem_ref[k:k + 1, :]                     # (1, NT)
            t = ic * chunk + (t0 + k)
            state, codes = _advance(state, d, t, g, nodes, loop, names,
                                    wf0)
            codes_ref[k] = codes
        state_ref[...] = jnp.stack(state)

    @pl.when(jnp.logical_not(live))
    def _dead():
        codes_ref[...] = jnp.zeros(codes_ref.shape, jnp.uint16)

    @pl.when(ic == n_chunks - 1)
    def _flush():
        sout_ref[...] = state_ref[...]


def _time_chunk(t_seg: int) -> int:
    """Largest divisor of the segment length <= :data:`TIME_CHUNK`."""
    for c in range(min(TIME_CHUNK, t_seg), 0, -1):
        if t_seg % c == 0:
            return c
    return 1


def _segment(state, demand_seg, lp, np_rows, alive, *, t0: int,
             backend: str, loop: step.Loop, names: Tuple[str, ...]):
    """Advance every lane over ``demand_seg``; returns (state, codes).

    ``backend`` selects the lowering: ``"mosaic"`` (real TPU kernel),
    ``"interpret"`` (the same ``pallas_call`` emulated by XLA -- the
    kernel-semantics reference on CPU), or ``"scan"`` (the production
    CPU path: one ``lax.scan`` over the identical :func:`_advance`).
    """
    t_seg, n_nodes = demand_seg.shape
    n_lanes = lp.shape[1]
    n_state = len(names)
    if backend == "scan":
        g, nodes, wf0 = _views(lp[:, :, None], np_rows, loop)

        def body(st, xs):
            d, t = xs
            return _advance(st, d.astype(jnp.float32), t, g, nodes, loop,
                            names, wf0)

        ts = jnp.arange(t_seg, dtype=jnp.int32) + t0
        # Carry layout is a measured CPU-fusion knob, not a semantic
        # one (stack/unstack is pure data movement, results are
        # bit-identical).  The cache path wants tuple rows with no
        # unroll (45M upd/s vs 4M stacked at the bench shape: the
        # per-step stack re-materializes the whole block and unrolling
        # defeats buffer reuse); the shorter cache-off step fuses best
        # stacked with unroll=2 (333M vs 125M tuple).
        if loop.cache is not None:
            carry0 = tuple(  # planecheck: ignore[PC-T002]  static unstack
                state[i] for i in range(n_state))
            carry, codes = jax.lax.scan(body, carry0, (demand_seg, ts))
            return jnp.stack(carry), codes

        def body_stacked(st, xs):
            out, codes = body(
                tuple(  # planecheck: ignore[PC-T002]  static unstack
                    st[i] for i in range(n_state)), xs)
            return jnp.stack(out), codes

        return jax.lax.scan(body_stacked, state, (demand_seg, ts),
                            unroll=2)
    chunk = _time_chunk(t_seg)
    n_chunks = t_seg // chunk
    tile = min(TILE_GAINS, n_lanes)
    node_tile = NODE_TILE if n_nodes % NODE_TILE == 0 else n_nodes
    kernel = functools.partial(_sweep_kernel, t0=t0, chunk=chunk,
                               n_chunks=n_chunks, loop=loop, names=names)
    # Mosaic tiles the last two block dims by (8, 128) unless a dim is
    # whole: demand goes in as (n_chunks, chunk, N) so any divisor of
    # the segment is a legal time chunk, and the per-lane params and
    # alive mask put lanes on sublanes.
    dem = demand_seg.reshape(n_chunks, chunk, n_nodes)
    return pl.pallas_call(
        kernel,
        grid=(n_lanes // tile, n_nodes // node_tile, n_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk, node_tile),
                         lambda ig, jn, ic: (ic, 0, jn)),
            pl.BlockSpec((tile, _N_PARAM_ROWS), lambda ig, jn, ic: (ig, 0)),
            pl.BlockSpec((_N_NODE_ROWS, node_tile),
                         lambda ig, jn, ic: (0, jn)),
            pl.BlockSpec((tile, 1), lambda ig, jn, ic: (ig, 0)),
            pl.BlockSpec((n_state, tile, node_tile),
                         lambda ig, jn, ic: (0, ig, jn)),
        ],
        out_specs=[
            pl.BlockSpec((n_state, tile, node_tile),
                         lambda ig, jn, ic: (0, ig, jn)),
            pl.BlockSpec((chunk, tile, node_tile),
                         lambda ig, jn, ic: (ic, ig, jn)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_state, n_lanes, n_nodes), jnp.float32),
            jax.ShapeDtypeStruct((t_seg, n_lanes, n_nodes), jnp.uint16),
        ],
        scratch_shapes=[pltpu.VMEM((n_state, tile, node_tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend == "interpret",
        name="lab_sweep_kernel",
    )(dem, lp.T, np_rows, alive.T, state)


def _finalize_lanes(state, codes, lp, loop: step.Loop,
                    names: Tuple[str, ...], n_steps: int) -> FleetStats:
    """Per-lane :class:`FleetStats` from the stacked accumulators.

    ``codes`` is the (T, L, N) prefix code history; the step's
    :func:`~repro.lab.step.finalize` is vmapped over lanes, so the
    reductions are the XLA engine's own, fold for fold.
    """
    n_nodes = state.shape[-1]
    codes_l = jnp.swapaxes(codes, 0, 1)                 # (L, T, N)

    def one(st, cl, r0_l):
        return step.finalize(step.Rows(st, names), cl, r0_l, n_steps,
                             n_nodes, loop.interval_s, cache=loop.cache)

    return jax.vmap(one, in_axes=(1, 0, 0))(state, codes_l, lp[_R0])


# ---------------------------------------------------------------------------
# Host-side packing + backend / fallback resolution
# ---------------------------------------------------------------------------

def _lane_pack(gains: GainSet) -> np.ndarray:
    """Gain columns + derived rows as one (P, L) f32 matrix.

    The derived rows use f32 host arithmetic (`np.float32` in, f32 ops
    out) so they equal the XLA engine's traced f32 hoists bitwise.
    """
    pack = np.zeros((_N_PARAM_ROWS, len(gains)), np.float32)
    for f in dataclasses.fields(GainSet):
        pack[step.GAIN_ROWS.index(f.name)] = getattr(gains, f.name)
    pack[_THR_OVER], pack[_THR_SETTLE] = step.thresholds(pack[_R0])
    return pack


def _node_pack(node_memory, n_nodes: int,
               cache: Optional[CacheSpec]) -> np.ndarray:
    pack = np.ones((_N_NODE_ROWS, n_nodes), np.float32)
    m = np.broadcast_to(np.asarray(node_memory, np.float64),
                        (n_nodes,)).astype(np.float32)
    pack[_M] = m
    if cache is not None:
        pack[_W] = step.working_set(cache, m, np.float32)
    return pack


def _reciprocals(lp, np_rows):
    """Fill the reciprocal rows on device, as the XLA engine divides."""
    return (lp.at[_INV_R0].set(1.0 / lp[_R0]),
            np_rows.at[_INV_M].set(1.0 / np_rows[_M])
            .at[_INV_W].set(1.0 / np_rows[_W]))


def _backend(force_interpret: Optional[bool]) -> str:
    if force_interpret is None:
        force_interpret = os.environ.get("PALLAS_SWEEP_INTERPRET",
                                         "0") == "1"
    if jax.default_backend() == "cpu":
        return "interpret" if force_interpret else "scan"
    return "mosaic"


def _single_device(devices, node_shards: int, who: str):
    """The pallas engine owns its tiling; shard knobs fall back warned."""
    devs = resolve_devices(devices)
    if len(devs) > 1:
        warn_once(f"{who}:devices",
                  f"{who}(engine='pallas') runs single-device (the kernel "
                  "grid already tiles the gain axis); ignoring the "
                  f"{len(devs)}-device mesh", RuntimeWarning)
    if node_shards > 1:
        warn_once(f"{who}:node_shards",
                  f"{who}(engine='pallas') does not shard the node axis; "
                  f"ignoring node_shards={node_shards}", RuntimeWarning)
    return devs[:1]


# ---------------------------------------------------------------------------
# The plain sweep driver
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _compiled_pallas_sweep(backend: str, loop: step.Loop, precision: str):
    """Jitted fused-sweep program for one (backend, loop, precision) key."""
    names = step.plane_names(loop.paper_law, loop.cache is not None)
    spec = _spec_digest("sweep", backend, loop, precision)

    def lab_pallas_chunk(demand_tn, np_rows, lp, alive):
        # Trace-time recompile counter (see lab.sweep._chunk_stats):
        # shapes from the operands, the rest of the cache key digested.
        record_trace("lab.sweep.pallas", chunk=int(lp.shape[1]),
                     horizon=int(demand_tn.shape[0]),
                     nodes=int(demand_tn.shape[1]), mode="sweep",
                     spec=spec)
        lp, np_rows = _reciprocals(lp, np_rows)
        cols = lp[:, :, None]
        d0 = demand_tn[0].astype(jnp.float32)
        state0 = _init_state(cols, np_rows, d0, loop, names)
        state, codes = _segment(state0, demand_tn, lp, np_rows, alive,
                                t0=0, backend=backend, loop=loop,
                                names=names)
        return _finalize_lanes(state, codes, lp, loop, names,
                               demand_tn.shape[0])

    return jax.jit(lab_pallas_chunk)


def sweep_program(gains: GainSet, *, backend: str,
                  cache: Optional[CacheSpec] = None, interval_s: float = 0.1,
                  occupancy: float = 1.0, precision: str = "f32"):
    """The jitted program a chunk of ``gains`` runs on ``backend``.

    Takes ``(demand (T, N), node rows (4, N), lane params (10, L),
    alive (1, L))``; :func:`pallas_sweep_demand` calls it per chunk,
    and ahead-of-time compiles lower it at described shapes.
    """
    plan = plan_specialization(gains, occupancy)
    return _compiled_pallas_sweep(
        backend, step.Loop.bake(plan, cache, interval_s, occupancy),
        precision)


def pallas_sweep_demand(
    demand: np.ndarray,
    gains: GainSet,
    *,
    node_memory,
    interval_s: float = 0.1,
    occupancy: float = 1.0,
    chunk: Optional[int] = None,
    devices=None,
    cache: Optional[CacheSpec] = None,
    app_graph=None,
    node_shards: int = 1,
    horizon: Optional[int] = None,
    precision: str = "f32",
    force_interpret: Optional[bool] = None,
) -> FleetStats:
    """The ``engine="pallas"`` backend of ``lab.sweep.sweep_demand``.

    Same contract and kwarg set as the XLA engine (``(N, T)`` demand in
    bytes, ``(G,)``-field stats out, mixed law classes partitioned,
    gain chunks bounded by the code budget) plus ``precision`` (``"f32"``
    | ``"bf16"``: bf16 stores only the demand stream) and
    ``force_interpret`` (the real ``pallas_call`` under XLA emulation on
    CPU -- the kernel-semantics parity reference, ~10x slower).
    ``interval_s`` / ``occupancy`` are compile-time constants here;
    sweeping many interval lengths compiles one executable each.
    ``devices`` meshes and ``node_shards`` fall back to the
    single-device kernel grid with a one-time warning.  ``app_graph``
    falls back to the XLA engine with a one-time warning: the queue
    carry needs two cross-lane scalar folds per step inside the tile,
    which the kernel's layout cannot express without a lane shuffle.
    """
    if app_graph is not None:
        warn_once("pallas:app_graph",
                  "pallas_sweep_demand: the AppGraph queue/barrier "
                  "carry is not kernelized yet; falling back to the "
                  "XLA sweep engine for this call", RuntimeWarning)
        from .sweep import sweep_demand
        return sweep_demand(
            demand, gains, node_memory=node_memory, interval_s=interval_s,
            occupancy=occupancy, chunk=chunk, devices=devices, cache=cache,
            app_graph=app_graph, node_shards=node_shards, horizon=horizon,
            engine="xla")
    demand = _check_args(demand, horizon, cache=cache, occupancy=occupancy,
                         node_shards=node_shards, precision=precision)
    n_nodes, n_steps = demand.shape
    _single_device(devices, node_shards, "pallas_sweep_demand")
    backend = _backend(force_interpret)
    with span("lab.sweep.stage"):
        # Demand and node rows go up once; law classes share them.
        demand_dev = stage_demand(demand, _demand_dtype(precision))
        np_dev = jnp.asarray(_node_pack(node_memory, n_nodes, cache))

        def pack(gains: GainSet) -> _Chunks:
            width = _resolve_chunk(chunk, len(gains), n_steps, n_nodes, 1)
            width = -(-width // TILE_GAINS) * TILE_GAINS
            n_real = len(gains)
            gains = _pad_gains(gains, width)
            fn = sweep_program(gains, backend=backend, cache=cache,
                               interval_s=interval_s, occupancy=occupancy,
                               precision=precision)
            lp_dev = jnp.asarray(_lane_pack(gains))
            alive = np.zeros((1, len(gains)), np.float32)
            alive[0, :n_real] = 1.0
            alive_dev = jnp.asarray(alive)
            calls = [(demand_dev, np_dev, lp_dev[:, lo:lo + width],
                      alive_dev[:, lo:lo + width])
                     for lo in range(0, len(gains), width)]
            return _Chunks(fn, calls, n_real, len(gains), n_steps)

        classes = _law_classes(gains)
        job = pack(gains) if classes is None else None
    if classes is None:
        return _run_chunks(job)
    return _run_law_classes(gains, classes, pack)


def _demand_dtype(precision: str):
    """The demand stream's device dtype; all state stays f32."""
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


# ---------------------------------------------------------------------------
# In-scan successive halving
# ---------------------------------------------------------------------------

class HalvingSweep(NamedTuple):
    """Everything one in-scan halving program returned, host-side."""

    stats: FleetStats          # final-round lanes: (k_last + B,) fields
    scores: np.ndarray         # objective over the same lanes
    survivor_idx: np.ndarray   # (k_last,) original candidate indices
    rounds: List[dict]         # {horizon, n_candidates, lanes} per rung
    elapsed_s: float           # dispatch to drained results


def halving_schedule(n_intervals: int, n_candidates: int,
                     rounds: Sequence[float], keep: float,
                     min_survivors: int) -> Tuple[List[int], List[int]]:
    """(horizons, survivor counts) exactly as the host tuner computes.

    The in-scan program bakes these in as static gather shapes; keeping
    the arithmetic in one place is what makes "in-scan survivors ==
    host survivors" an identity rather than a coincidence.
    """
    fracs = sorted(set(float(f) for f in rounds))
    if not fracs or fracs[0] <= 0.0 or fracs[-1] > 1.0:
        raise ValueError("rounds must be fractions in (0, 1]")
    if fracs[-1] != 1.0:
        fracs.append(1.0)
    horizons = [max(int(round(n_intervals * f)), 1) for f in fracs]
    horizons[-1] = n_intervals
    keeps = []
    n = n_candidates
    for _ in fracs[:-1]:
        k = min(max(int(np.ceil(n * keep)), min_survivors), n)
        keeps.append(k)
        n = k
    return horizons, keeps


@functools.lru_cache(maxsize=None)
def _compiled_halving(backend: str, loop: step.Loop, precision: str,
                      horizons: Tuple[int, ...],
                      keeps: Tuple[int, ...], n_cand: int, n_base: int,
                      objective: Callable):
    """One jitted program running the whole halving schedule in-scan.

    Candidate lanes ``[0, n_cand)``, baseline lanes right after, tile
    padding last.  At each boundary: finalize prefix stats -> score ->
    ``argsort`` the candidate lanes only -> gather survivors + baseline
    + alive-masked padding into the next (smaller) lane block.  The
    prefix code history rides along through the gathers so the p99 (and
    any objective built on it) is computed over the full prefix, just
    like the host tuner's from-scratch truncated runs.
    """
    names = step.plane_names(loop.paper_law, loop.cache is not None)
    spec = _spec_digest("halving", backend, loop, precision, horizons, keeps,
                        n_cand, n_base,
                        getattr(objective, "__qualname__", repr(objective)))

    def lab_halving(demand_tn, np_rows, lp, alive):
        record_trace("lab.sweep.pallas", chunk=int(lp.shape[1]),
                     horizon=int(demand_tn.shape[0]),
                     nodes=int(demand_tn.shape[1]), mode="halving",
                     spec=spec)
        lp, np_rows = _reciprocals(lp, np_rows)
        cols = lp[:, :, None]
        d0 = demand_tn[0].astype(jnp.float32)
        state = _init_state(cols, np_rows, d0, loop, names)
        orig = jnp.arange(lp.shape[1], dtype=jnp.int32)
        parts = []
        t_prev = 0
        cand = n_cand
        for i, h in enumerate(horizons):
            final = i == len(horizons) - 1
            with jax.named_scope(f"rung{i}"):
                if h > t_prev:
                    state, codes = _segment(
                        state, jax.lax.slice_in_dim(demand_tn, t_prev, h),
                        lp, np_rows, alive, t0=t_prev, backend=backend,
                        loop=loop, names=names)
                    parts.append(codes)
                    t_prev = h
                prefix = parts[0] if len(parts) == 1 else jnp.concatenate(
                    parts, axis=0)
                stats = _finalize_lanes(state, prefix, lp, loop, names, h)
                scores = objective(stats)
            if final:
                n_out = cand + n_base
                out_stats = jax.tree_util.tree_map(lambda a: a[:n_out],
                                                   stats)
                return out_stats, scores[:n_out], orig[:cand]
            k = keeps[i]
            # top_k (not argsort): O(cand log k) streaming selection,
            # and descending-with-ties-by-index order matches the host
            # tuner's np.argsort(-scores) ranking for distinct scores.
            _, idx = jax.lax.top_k(scores[:cand], k)
            sel = jnp.concatenate(
                [idx, jnp.arange(cand, cand + n_base, dtype=idx.dtype)])
            pad_n = (-(k + n_base)) % TILE_GAINS
            if pad_n:
                sel = jnp.concatenate(
                    [sel, jnp.broadcast_to(sel[-1:], (pad_n,))])
            state = state[:, sel, :]
            lp = lp[:, sel]
            cols = lp[:, :, None]
            parts = [c[:, sel, :] for c in parts]
            orig = orig[sel]
            alive = jnp.asarray(
                np.concatenate([np.ones((1, k + n_base), np.float32),
                                np.zeros((1, pad_n), np.float32)], axis=1))
            cand = k
        raise AssertionError("unreachable")

    return jax.jit(lab_halving)


def halving_sweep(
    demand: np.ndarray,
    gains: GainSet,
    base: GainSet,
    *,
    node_memory,
    interval_s: float = 0.1,
    occupancy: float = 1.0,
    cache: Optional[CacheSpec] = None,
    rounds: Sequence[float] = (0.125, 0.5, 1.0),
    keep: float = 0.25,
    min_survivors: int = 4,
    objective: Callable = default_score,
    chunk: Optional[int] = None,
    devices=None,
    node_shards: int = 1,
    horizon: Optional[int] = None,
    precision: str = "f32",
    force_interpret: Optional[bool] = None,
) -> HalvingSweep:
    """Run the whole successive-halving schedule as one device program.

    ``gains`` are the candidates, ``base`` the always-alive baseline
    lanes scored at the final horizon (the "never below baseline"
    guarantee); ``objective`` must be jax-traceable (both registry
    objectives are).  Dominated candidate lanes are masked dead and
    compacted away at each ``rounds`` boundary without leaving the
    device -- a 512-gain tune executes ~26% of the grid's lane-steps.
    A mixed paper/beyond-paper gain set runs whole on the generic law
    (identical results, no partition -- the lanes must share one
    program for the in-scan gathers).

    Returns a :class:`HalvingSweep`; ``lab.tune.halving_tune`` wraps it
    into the standard :class:`~repro.lab.tune.TuneResult`.  The host
    phases run under the spans ``lab.halving.stage`` / ``dispatch`` /
    ``drain``, the rungs under ``jax.named_scope("rung<i>")``, and the
    lane-steps alive and dispatched count into
    ``lab.halving.lane_steps.{live,run}``.
    """
    demand = _check_args(demand, horizon, cache=cache, occupancy=occupancy,
                         precision=precision)
    del chunk  # lane count is the schedule's; accepted for API uniformity
    n_nodes, n_steps = demand.shape
    _single_device(devices, node_shards, "halving_sweep")
    backend = _backend(force_interpret)
    horizons, keeps = halving_schedule(n_steps, len(gains), rounds, keep,
                                       min_survivors)
    n_cand, n_base = len(gains), len(base)
    with span("lab.halving.stage"):
        lanes = _pad_gains(gains.concat(base), TILE_GAINS)
        # One law class for the whole lane block: any beyond-paper point
        # drops every lane to the generic (identical-result) law.
        plan = plan_specialization(lanes, occupancy)
        loop = step.Loop.bake(plan, cache, interval_s, occupancy)
        fn = _compiled_halving(backend, loop, precision, tuple(horizons),
                               tuple(keeps), n_cand, n_base, objective)
        demand_dev = stage_demand(demand, _demand_dtype(precision))
        np_dev = jnp.asarray(_node_pack(node_memory, n_nodes, cache))
        lp_dev = jnp.asarray(_lane_pack(lanes))
        alive = np.zeros((1, len(lanes)), np.float32)
        alive[0, :n_cand + n_base] = 1.0
        alive_dev = jnp.asarray(alive)
    # Lanes alive and dispatched per rung (survivors + baseline, padded
    # to the tile as the program pads them), over each rung's intervals.
    live = [n_cand + n_base] + [k + n_base for k in keeps]
    run = [len(lanes)] + [-(-n // TILE_GAINS) * TILE_GAINS
                          for n in live[1:]]
    steps = np.diff([0] + horizons)
    count("lab.halving.lane_steps.live", int(np.dot(live, steps)))
    count("lab.halving.lane_steps.run", int(np.dot(run, steps)))
    if sanitizers_enabled():
        jax.block_until_ready(fn(demand_dev, np_dev, lp_dev, alive_dev))
    t0 = time.perf_counter()
    with span("lab.halving.dispatch"):
        with dispatch_guard():
            out = fn(demand_dev, np_dev, lp_dev, alive_dev)
    with span("lab.halving.drain"):
        stats_dev, scores_dev, orig_dev = out
        stats = jax.tree_util.tree_map(np.asarray, stats_dev)
        scores = np.asarray(scores_dev)
        survivor_idx = np.asarray(orig_dev)
    elapsed = time.perf_counter() - t0
    counts = [n_cand] + list(keeps)
    round_log = [{"horizon": h,
                  "n_candidates": counts[i] + (n_base if final else 0),
                  "lanes": run[i]}
                 for i, h in enumerate(horizons)
                 for final in [i == len(horizons) - 1]]
    return HalvingSweep(stats=stats, scores=scores,
                        survivor_idx=survivor_idx, rounds=round_log,
                        elapsed_s=elapsed)
