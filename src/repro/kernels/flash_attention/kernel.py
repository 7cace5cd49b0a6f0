"""Flash attention as a Pallas TPU kernel (GQA, causal, sliding window).

TPU adaptation of the CUDA flash-attention blocking: the (block_q x
block_k) tiles are sized for VMEM and the MXU's 128-lane geometry, the
online-softmax carry lives in VMEM scratch across the sequential
``kv`` grid dimension, and fully-masked tiles are skipped *before* their
matmuls issue (``@pl.when`` on block-level causal/window bounds), which
on a sequential TPU grid is real skipped work, not a predicated no-op.

Grid: (batch*heads, q_blocks, kv_blocks) with semantics
("parallel", "parallel", "arbitrary") -- the kv axis must run in order
because the scratch carry accumulates along it.

Mosaic tiles the last two dims of every block by (8, 128) unless a dim
is whole, so the wrapper views q/k/v head-major, (B, H, S, hd), and a
block is one head's (block, hd) tile; the running max and sum are
(block_q, 1) columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # Block-level skip: the whole tile is masked out iff it lies entirely
    # above the causal diagonal or entirely left of the window's reach.
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window:
        live = jnp.logical_and(
            live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[...].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                       # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd) -> (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    assert h % kvh == 0, "GQA requires n_heads % n_kv_heads == 0"
    g = h // kvh
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, \
        "sequence lengths must divide block sizes (pad upstream)"
    n_q = sq // block_q
    n_k = skv // block_k
    grid = (b * h, n_q, n_k)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (hd ** 0.5), causal=causal,
        window=window, block_q=block_q, block_k=block_k, n_kv_blocks=n_k)

    q_spec = pl.BlockSpec((None, None, block_q, hd),
                          lambda bh, iq, ik: (bh // h, bh % h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda bh, iq, ik: (bh // h, (bh % h) // g, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),     # running acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
