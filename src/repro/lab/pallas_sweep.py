"""PallasSweep: the fused (gains x nodes) sweep kernel + in-scan halving.

The ``engine="pallas"`` backend of the unified sweep API.  One tiled
pass fuses everything ``repro.lab.sweep`` runs as separate vmapped
stages -- the control law (:func:`~repro.core.control.vectorized_step`),
the CacheLoop carry, and the streamed Kahan / fixed-bin-quantile
accumulators -- over a stacked ``(S, L, N)`` state block:

* **S** state planes (law + cache + accumulator lanes, all f32),
* **L** gain lanes, tiled :data:`TILE_GAINS` at a time,
* **N** nodes as the vector axis.

Grid ``(gain_tiles, node_tiles, time_chunks)`` with semantics
``("parallel", "parallel", "arbitrary")``: each program keeps its
(:data:`TILE_GAINS` x :data:`NODE_TILE`) tile's state in VMEM scratch
across the sequential time axis, walks
:data:`TIME_CHUNK` intervals as an unrolled vector loop, and emits the
uint16 utilization codes the quantile bisection consumes.  Nothing of
size T x N ever leaves the device; per segment the host sees O(L)
scalars.

**Backends.**  On the CPU backend ``engine="pallas"`` lowers the
*identical* fused step through one ``lax.scan`` -- same ops, same
order, so parity tests and tier-1 stay runnable and fast; the true
``pallas_call`` executes under ``interpret=True`` only when forced
(``PALLAS_SWEEP_INTERPRET=1`` or ``force_interpret=True``), because XLA
emulation of a Pallas grid is ~10x slower than the native scan.  On an
accelerator backend the Mosaic kernel runs, compiled.  All three share
:func:`_fused_step`, which is the single source of truth for the step
math.

**Numerics.**  State and every accumulator stay f32 (the Kahan pairs
and the uint16 code stream make the f32 accumulation analysis of PR 3
carry over unchanged); ``precision="bf16"`` stores only the *demand
stream* in bf16 -- it is read once per step and upcast before use, so
no accumulator ever rounds through bf16.  Every op matches the XLA
engine's, the cache hit-curve ``f ** hit_exp`` included: on a TPU v5e
an ``exp2(e * log2(f))`` spelling (4e-5 relative off XLA's pow there),
or even a 5e-7-accurate polynomial, drifts the 4096-node closed loop
by up to 2e-2 in the violation rate, while ``**`` lowers to the same
pow in Mosaic and XLA and keeps the engines bit-identical.

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
import hashlib
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
from ..core.control import vectorized_step
from ..core.eviction import policy_model
from ..core.traces import GiB
from ._compat import warn_once
from .scenarios import CacheSpec
from .score import (FleetStats, OVER_R0_EPS, SETTLE_TOL, default_score,
                    finalize_fleet_stats, hpl_slowdown_curve, kahan_add,
                    quantile_from_codes, utilization_codes)
from .sweep import (GainSet, _Chunks, _law_classes, _pad_gains,
                    _resolve_chunk, _run_chunks, _run_law_classes,
                    plan_specialization, resolve_devices, stage_demand)

# Gain lanes per kernel tile (the sublane axis of the VPU's 8x128
# geometry) and intervals walked per sequential grid step.  A segment
# whose length is not a TIME_CHUNK multiple uses its largest divisor.
TILE_GAINS = 8
TIME_CHUNK = 32
# Nodes per kernel tile (lanes of the vector unit), used when it
# divides the fleet; otherwise a tile spans the whole node axis.
NODE_TILE = 512

# f32-exact module constants, mirroring the XLA engine's
# ``jnp.float32(...)`` trace-time casts bit for bit.
_INV_GIB = float(np.float32(1.0 / GiB))
_GIB_F32 = float(np.float32(GiB))

# Rows of the packed per-lane parameter matrix (P, L).  The threshold
# rows are precomputed in f32 on the host with the exact IEEE ops the
# XLA engine traces; the reciprocal rows are filled on device by
# :func:`_reciprocals` with the engine's own division, which an
# accelerator need not round like the host.  Both engines then clamp
# and count against bit-identical constants.
_R0, _LAM, _LAM_GRANT, _U_MIN, _U_MAX, _DB, _FF = range(7)
_INV_R0, _THR_OVER, _THR_SETTLE = 7, 8, 9
_N_PARAM_ROWS = 10

# Rows of the packed per-node constant matrix (R, N).
_M, _INV_M, _W, _INV_W = range(4)
_N_NODE_ROWS = 4


@dataclasses.dataclass(frozen=True)
class _EngineConsts:
    """Trace-time constants one fused executable specializes on.

    Hashable (it keys the compiled-program caches) and repr-stable (it
    feeds the ``record_trace`` spec digest).  Cache-model scalars are
    precomputed with f32 host arithmetic so the step math sees the same
    values the XLA engine's traced ``jnp.float32`` constants hold.
    """

    paper_law: bool
    unit_occupancy: bool
    occupancy: float
    interval_s: float
    precision: str
    has_cache: bool = False
    conc: float = 0.0
    hit_exp: float = 1.0
    miss_pen: float = 0.0
    evict_pen: float = 0.0
    access_g: float = 0.0
    refill_b: float = 0.0
    access_b: float = 0.0
    cold_mix: float = 0.0
    warm_frac: float = 0.0


def _engine_consts(plan, cache: Optional[CacheSpec], interval_s: float,
                   occupancy: float, precision: str) -> _EngineConsts:
    iv = np.float32(interval_s)
    base = dict(paper_law=plan.paper_law, unit_occupancy=plan.unit_occupancy,
                occupancy=float(occupancy), interval_s=float(iv),
                precision=precision)
    if cache is None:
        return _EngineConsts(**base)
    access_g = np.float32(cache.access_gibps) * iv
    return _EngineConsts(
        has_cache=True,
        conc=float(policy_model(cache.policy).concentration),
        hit_exp=1.0 - float(cache.reuse_skew),
        miss_pen=float(np.float32(cache.miss_penalty_s_per_gib)),
        evict_pen=float(np.float32(cache.evict_penalty_s_per_gib)),
        access_g=float(access_g),
        refill_b=float(np.float32(cache.refill_gibps * GiB) * iv),
        access_b=float(access_g * np.float32(GiB)),
        cold_mix=float(np.float32(cache.reuse_skew)),
        warm_frac=float(np.float32(cache.warm_frac)),
        **base)


def _state_names(paper_law: bool, has_cache: bool) -> Tuple[str, ...]:
    """Plane order of the stacked (S, L, N) state block."""
    names = ["u"]
    if not paper_law:
        names.append("v_prev")
    if has_cache:
        names.append("resident")
    names += ["us", "us_c", "cs", "cs_c", "c2", "mx",
              "n_r0", "n_viol", "last_bad"]
    if has_cache:
        names += ["hs", "hs_c", "es", "es_c", "ts", "ts_c"]
    return tuple(names)


def _warm_fraction0(cols, rows, con: _EngineConsts):
    """Warm-seeded working-set fraction ``wf0`` per (lane, node)."""
    res0 = con.warm_frac * jnp.minimum(cols[_U_MAX], rows[_W])
    return res0, res0 * rows[_INV_W]


def _fused_step(state, d, t, cols, rows, wf0, con: _EngineConsts,
                names: Tuple[str, ...], ix):
    """One closed-loop interval on a tuple of (L, N) state rows.

    The single source of truth for the fused step: the Mosaic kernel
    body, the interpret-mode kernel, and the CPU scan lowering all call
    this function, so "parity between backends" reduces to XLA
    compiling the same jaxpr two ways.  The math mirrors
    ``repro.lab.sweep._one_gain_stream`` op for op (law via
    :func:`vectorized_step`, Kahan accumulators, cold-scan cache carry)
    with lane-column parameters ``cols[row]`` of shape (L, 1)
    broadcasting against node rows ``rows[row]`` of shape (N,), down to
    the hit curve's ``**``: the closed loop amplifies any other pow
    spelling's last-bit differences past the parity bracket.

    ``state`` is a *tuple* of per-row (L, N) planes, not the stacked
    (S, L, N) block: a stacked scan carry forces XLA's CPU backend to
    re-materialize the whole block every interval (the per-step
    ``stack`` defeats carry aliasing, ~30x slower on the cache path),
    while tuple rows update in place.  The lowerings stack/unstack only
    at segment and chunk boundaries, which is pure data movement.
    """
    u = state[ix["u"]]
    if con.has_cache:
        resident = state[ix["resident"]]
        v = d + resident
    elif con.unit_occupancy:
        v = d + u
    else:
        v = d + con.occupancy * u
    if con.paper_law:
        v_eff = v
    else:
        # Feedforward applied to v up front, exactly as the XLA engine
        # does for a vmapped gain axis (identical to the law's own
        # trace-time branch).
        v_eff = v + cols[_FF] * (v - state[ix["v_prev"]])
    u_next = vectorized_step(
        u, v_eff, total_memory=rows[_M], r0=cols[_R0], lam=cols[_LAM],
        u_min=cols[_U_MIN], u_max=cols[_U_MAX],
        lam_grant=None if con.paper_law else cols[_LAM_GRANT],
        deadband=0.0 if con.paper_law else cols[_DB],
        inv_total_memory=rows[_INV_M], inv_r0=cols[_INV_R0])
    r = v * rows[_INV_M]
    tf = t.astype(jnp.float32)
    us, us_c = kahan_add(state[ix["us"]], state[ix["us_c"]], r)
    cap_gib = u_next * _INV_GIB
    cs, cs_c = kahan_add(state[ix["cs"]], state[ix["cs_c"]], cap_gib)
    out = {
        "u": u_next,
        "us": us, "us_c": us_c, "cs": cs, "cs_c": cs_c,
        "c2": state[ix["c2"]] + cap_gib * cap_gib,
        "mx": jnp.maximum(state[ix["mx"]], r),
        "n_r0": state[ix["n_r0"]] + (r > cols[_THR_OVER]),
        "n_viol": state[ix["n_viol"]] + (r > 1.0),
        "last_bad": jnp.where(r > cols[_THR_SETTLE], tf,
                              state[ix["last_bad"]]),
    }
    if not con.paper_law:
        out["v_prev"] = v
    if con.has_cache:
        res_ev = jnp.minimum(resident, u_next)
        ev_g = (resident - res_ev) * _INV_GIB
        f = jnp.minimum(res_ev * rows[_INV_W], 1.0)
        hit = con.conc * f ** con.hit_exp + (1.0 - con.conc) * f
        scanned = tf * con.access_b
        wf = jnp.minimum(wf0, f)
        hit = jnp.where(scanned < rows[_W],
                        wf + con.cold_mix * (hit - wf), hit)
        miss_g = (1.0 - hit) * con.access_g
        target = jnp.minimum(u_next, rows[_W])
        out["resident"] = jnp.minimum(
            target, res_ev + jnp.minimum(miss_g * _GIB_F32, con.refill_b))
        dt_app = (con.interval_s * hpl_slowdown_curve(r)
                  + miss_g * con.miss_pen + ev_g * con.evict_pen)
        hs, hs_c = kahan_add(state[ix["hs"]], state[ix["hs_c"]],
                             hit * con.access_g)
        es, es_c = kahan_add(state[ix["es"]], state[ix["es_c"]], ev_g)
        ts, ts_c = kahan_add(state[ix["ts"]], state[ix["ts_c"]], dt_app)
        out.update(hs=hs, hs_c=hs_c, es=es, es_c=es_c, ts=ts, ts_c=ts_c)
    # Static-length genexp of lazily indexed rows -- no host iteration.
    return (tuple(out[n] for n in names),  # planecheck: ignore[PC-T002]
            utilization_codes(r))


def _init_state(cols, rows, d0, con: _EngineConsts,
                names: Tuple[str, ...], ix):
    """Stacked initial state for (L, N) lanes -- mirrors the XLA seeds."""
    zeros = jnp.zeros((cols.shape[1], rows.shape[-1]), jnp.float32)
    u0 = zeros + cols[_U_MAX]
    planes = {n: zeros for n in names}
    planes["u"] = u0
    planes["last_bad"] = zeros - 1.0
    if con.has_cache:
        res0, _ = _warm_fraction0(cols, rows, con)
        planes["resident"] = zeros + res0
    if not con.paper_law:
        # Seed v_prev with the first interval's usage so the slope term
        # is exactly zero before there is a previous observation.
        if con.has_cache:
            planes["v_prev"] = d0 + planes["resident"]
        elif con.unit_occupancy:
            planes["v_prev"] = d0 + u0
        else:
            planes["v_prev"] = d0 + con.occupancy * u0
    return jnp.stack([planes[n] for n in names])


# ---------------------------------------------------------------------------
# The kernel and its two lowerings
# ---------------------------------------------------------------------------

def _sweep_kernel(dem_ref, lp_ref, np_ref, alive_ref, sin_ref,
                  sout_ref, codes_ref, state_ref, *, t0: int, chunk: int,
                  n_chunks: int, con: _EngineConsts,
                  names: Tuple[str, ...], ix):
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
        rows = np_ref[...]                              # (R, NT)
        wf0 = _warm_fraction0(cols, rows, con)[1] if con.has_cache else None
        stacked = state_ref[...]
        state = tuple(stacked[i] for i in range(len(names)))
        for k in range(chunk):
            d = dem_ref[k:k + 1, :]                     # (1, NT)
            t = ic * chunk + (t0 + k)
            state, codes = _fused_step(state, d, t, cols, rows, wf0,
                                       con, names, ix)
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
             backend: str, con: _EngineConsts, names: Tuple[str, ...], ix):
    """Advance every lane over ``demand_seg``; returns (state, codes).

    ``backend`` selects the lowering: ``"mosaic"`` (real TPU kernel),
    ``"interpret"`` (the same ``pallas_call`` emulated by XLA -- the
    kernel-semantics reference on CPU), or ``"scan"`` (the production
    CPU path: one ``lax.scan`` over the identical :func:`_fused_step`).
    """
    t_seg, n_nodes = demand_seg.shape
    n_lanes = lp.shape[1]
    n_state = len(names)
    if backend == "scan":
        cols = lp[:, :, None]
        wf0 = (_warm_fraction0(cols, np_rows, con)[1]
               if con.has_cache else None)

        def body(st, xs):
            d, t = xs
            return _fused_step(st, d.astype(jnp.float32), t, cols, np_rows,
                               wf0, con, names, ix)

        ts = jnp.arange(t_seg, dtype=jnp.int32) + t0
        # Carry layout is a measured CPU-fusion knob, not a semantic
        # one (stack/unstack is pure data movement, results are
        # bit-identical).  The cache path wants tuple rows with no
        # unroll (45M upd/s vs 4M stacked at the bench shape: the
        # per-step stack re-materializes the whole block and unrolling
        # defeats buffer reuse); the shorter cache-off step fuses best
        # stacked with unroll=2 (333M vs 125M tuple).
        if con.has_cache:
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
                               n_chunks=n_chunks, con=con, names=names,
                               ix=ix)
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


def _finalize_lanes(state, codes, lp, con: _EngineConsts,
                    names: Tuple[str, ...], ix, n_steps: int) -> FleetStats:
    """Per-lane :class:`FleetStats` from the stacked accumulators.

    ``codes`` is the (T, L, N) prefix code history; the quantile
    bisection and :func:`finalize_fleet_stats` are vmapped over lanes,
    so the reductions are the XLA engine's own, fold for fold.
    """
    n_nodes = state.shape[-1]
    codes_l = jnp.swapaxes(codes, 0, 1)                 # (L, T, N)

    def one(st, cl, r0_l):
        p99 = quantile_from_codes(cl, 0.99, n_steps * n_nodes)
        cache_kw = {}
        if con.has_cache:
            cache_kw = dict(hits_gib=st[ix["hs"]], evicted_gib=st[ix["es"]],
                            app_time_s=st[ix["ts"]],
                            accesses_gib=con.access_g * n_steps)
        return finalize_fleet_stats(
            util_sum=st[ix["us"]], util_max=st[ix["mx"]],
            caps_sum_gib=st[ix["cs"]], caps_sumsq_gib=st[ix["c2"]],
            over_r0_count=st[ix["n_r0"]],
            violation_count=st[ix["n_viol"]],
            last_bad=st[ix["last_bad"]], p99_utilization=p99, r0=r0_l,
            n_intervals=n_steps, interval_s=con.interval_s, **cache_kw)

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
    r0 = np.asarray(gains.r0, np.float32)
    pack[_R0] = r0
    pack[_LAM] = np.asarray(gains.lam, np.float32)
    pack[_LAM_GRANT] = np.asarray(gains.lam_grant, np.float32)
    pack[_U_MIN] = np.asarray(gains.u_min, np.float32)
    pack[_U_MAX] = np.asarray(gains.u_max, np.float32)
    pack[_DB] = np.asarray(gains.deadband, np.float32)
    pack[_FF] = np.asarray(gains.feedforward, np.float32)
    pack[_THR_OVER] = r0 + np.float32(OVER_R0_EPS)
    pack[_THR_SETTLE] = r0 + np.float32(SETTLE_TOL)
    return pack


def _node_pack(node_memory, n_nodes: int,
               cache: Optional[CacheSpec]) -> np.ndarray:
    pack = np.ones((_N_NODE_ROWS, n_nodes), np.float32)
    m = np.broadcast_to(np.asarray(node_memory, np.float64),
                        (n_nodes,)).astype(np.float32)
    pack[_M] = m
    if cache is not None:
        pack[_W] = np.float32(cache.working_set_frac) * m
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


def _spec_digest(*parts) -> str:
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:12]


# ---------------------------------------------------------------------------
# The plain sweep driver
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _compiled_pallas_sweep(backend: str, con: _EngineConsts,
                           names: Tuple[str, ...]):
    """Jitted fused-sweep program for one (backend, consts) key."""
    ix = {n: i for i, n in enumerate(names)}
    spec = _spec_digest("sweep", backend, con, names)

    def lab_pallas_chunk(demand_tn, np_rows, lp, alive):
        # Trace-time recompile counter (see lab.sweep._chunk_stats):
        # shapes from the operands, everything else -- backend, the
        # full consts dataclass (cache knobs, interval, precision),
        # state layout -- folded into the spec digest, so the key is
        # one-to-one with the executable cache entry.
        record_trace("lab.sweep.pallas", chunk=int(lp.shape[1]),
                     horizon=int(demand_tn.shape[0]),
                     nodes=int(demand_tn.shape[1]), mode="sweep",
                     spec=spec)
        lp, np_rows = _reciprocals(lp, np_rows)
        cols = lp[:, :, None]
        d0 = demand_tn[0].astype(jnp.float32)
        state0 = _init_state(cols, np_rows, d0, con, names, ix)
        state, codes = _segment(state0, demand_tn, lp, np_rows, alive,
                                t0=0, backend=backend, con=con, names=names,
                                ix=ix)
        return _finalize_lanes(state, codes, lp, con, names, ix,
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
    con = _engine_consts(plan, cache, interval_s, occupancy, precision)
    return _compiled_pallas_sweep(backend, con,
                                  _state_names(con.paper_law, con.has_cache))


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
    gain chunks bounded by the code budget) with the pallas-specific
    knobs on top: ``precision`` (``"f32"`` | ``"bf16"`` -- bf16 stores
    only the demand stream; all state and accumulators stay f32) and
    ``force_interpret`` (run the real ``pallas_call`` under XLA
    emulation on CPU instead of the fused-scan lowering -- the
    kernel-semantics parity reference, ~10x slower).  ``interval_s`` /
    ``occupancy`` are compile-time constants here (the XLA engine
    traces them); sweeping many interval lengths compiles one
    executable each.  ``devices`` meshes and ``node_shards`` are
    accepted for API uniformity but fall back to the single-device
    kernel grid with a one-time warning.

    ``app_graph`` (the AppGraph DAG co-simulation) is accepted for API
    uniformity but the queue/barrier carry is not kernelized yet: it
    needs two cross-lane scalar folds per step inside the tile, which
    the current mosaic layout cannot express without a lane shuffle.
    Falls back to the XLA engine with a one-time warning -- the fleet
    two-level carry precedent (see ROADMAP).
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
    demand = np.asarray(demand)
    if cache is not None and float(occupancy) != 1.0:
        raise ValueError("cache modeling replaces the occupancy "
                         "abstraction; need occupancy == 1.0")
    if node_shards < 1:
        raise ValueError("node_shards must be >= 1")
    if precision not in ("f32", "bf16"):
        raise ValueError("precision must be f32|bf16")
    if horizon is not None:
        if not 1 <= horizon <= demand.shape[1]:
            raise ValueError(f"horizon must be in [1, {demand.shape[1]}]")
        demand = demand[:, :horizon]
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
def _compiled_halving(backend: str, con: _EngineConsts,
                      names: Tuple[str, ...], horizons: Tuple[int, ...],
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
    ix = {n: i for i, n in enumerate(names)}
    spec = _spec_digest("halving", backend, con, names, horizons, keeps,
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
        state = _init_state(cols, np_rows, d0, con, names, ix)
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
                        con=con, names=names, ix=ix)
                    parts.append(codes)
                    t_prev = h
                prefix = parts[0] if len(parts) == 1 else jnp.concatenate(
                    parts, axis=0)
                stats = _finalize_lanes(state, prefix, lp, con, names, ix,
                                        h)
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
    demand = np.asarray(demand)
    if cache is not None and float(occupancy) != 1.0:
        raise ValueError("cache modeling replaces the occupancy "
                         "abstraction; need occupancy == 1.0")
    if precision not in ("f32", "bf16"):
        raise ValueError("precision must be f32|bf16")
    if horizon is not None:
        if not 1 <= horizon <= demand.shape[1]:
            raise ValueError(f"horizon must be in [1, {demand.shape[1]}]")
        demand = demand[:, :horizon]
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
        con = _engine_consts(plan, cache, interval_s, occupancy, precision)
        names = _state_names(con.paper_law, con.has_cache)
        fn = _compiled_halving(backend, con, names, tuple(horizons),
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
