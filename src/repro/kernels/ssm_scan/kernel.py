"""Chunked selective scan (Mamba S6) as a Pallas TPU kernel.

TPU adaptation of the CUDA selective-scan: instead of one warp-level
scan per channel, the sequence is tiled into chunks walked by the
sequential grid axis; each program holds a (channel-block x state) carry
in VMEM scratch and runs the within-chunk recurrence as an unrolled
vector loop over the chunk -- channels are the vector lanes (the VPU's
8x128 geometry), time is the sequential axis.  State never leaves VMEM
between chunks of the same channel block.

Grid: (batch, channel_blocks, seq_chunks), semantics
("parallel", "parallel", "arbitrary").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(decay_ref, drive_ref, h0_ref, out_ref, h_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)          # (bc, n)

    h = h_ref[...]
    # Unrolled time loop within the chunk; channel block x state dims
    # stay vectorized.  ``chunk`` is a compile-time constant.
    for t in range(chunk):
        a = decay_ref[0, t].astype(jnp.float32)             # (bc, n)
        b_ = drive_ref[0, t].astype(jnp.float32)
        h = a * h + b_
        out_ref[0, t] = h.astype(out_ref.dtype)
    h_ref[...] = h


def ssm_scan(decay: jax.Array, drive: jax.Array, h0: jax.Array, *,
             chunk: int = 64, block_c: int = 128,
             interpret: bool = False) -> jax.Array:
    """decay/drive: (B,S,C,N); h0: (B,C,N) -> (B,S,C,N) hidden states."""
    b, s, c, n = decay.shape
    chunk = min(chunk, s)
    block_c = min(block_c, c)
    assert s % chunk == 0 and c % block_c == 0
    n_chunks = s // chunk
    n_cblocks = c // block_c
    grid = (b, n_cblocks, n_chunks)

    kernel = functools.partial(_ssm_kernel, chunk=chunk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_c, n),
                         lambda ib, icb, ic: (ib, ic, icb, 0)),
            pl.BlockSpec((1, chunk, block_c, n),
                         lambda ib, icb, ic: (ib, ic, icb, 0)),
            pl.BlockSpec((1, block_c, n), lambda ib, icb, ic: (ib, icb, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_c, n),
                               lambda ib, icb, ic: (ib, ic, icb, 0)),
        out_shape=jax.ShapeDtypeStruct(decay.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_c, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(decay, drive, h0)
