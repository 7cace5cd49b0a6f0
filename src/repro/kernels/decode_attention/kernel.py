"""Single-token decode attention as a Pallas TPU kernel.

Serving hot path: one query token per sequence against a long KV cache.
The cache's sequence axis is tiled into ``block_k`` chunks walked by the
sequential grid axis with an online-softmax carry in VMEM scratch (same
recurrence as the flash kernel, degenerate q-block of one token per
(batch, head) program).  Per-sequence lengths arrive via scalar prefetch
(SMEM) so block-level skipping -- tiles entirely past ``len_b`` issue no
matmul -- is decided before the tile loads stream.

Mosaic tiles the last two dims of every block by (8, 128) unless a dim
is whole, so one program takes a KV head's whole group of query heads,
a (group, hd) block, against a (block_k, hd) tile of the cache viewed
head-major, (B, KV, S, hd).

This kernel is what the DynIMS-managed KV pool feeds: the pool hands out
whole cache pages, the engine materializes the (B,S,KV,hd) view, the
kernel never reads past ``lengths``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, window: int, block_k: int,
                   n_kv_blocks: int, n_kv_heads: int):
    ik = pl.program_id(1)
    seq_len = len_ref[pl.program_id(0) // n_kv_heads]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ik * block_k
    live = k_start < seq_len
    if window:
        live = jnp.logical_and(live, k_start + block_k > seq_len - window)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                   # (g, hd)
        k = k_ref[...].astype(jnp.float32)                   # (bk, hd)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                        # (g, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < seq_len
        if window:
            valid &= k_pos >= seq_len - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                                  # (g, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, window: int = 0,
                     block_k: int = 256, interpret: bool = False
                     ) -> jax.Array:
    """q: (B,H,hd); caches: (B,S,KV,hd); lengths: (B,) -> (B,H,hd)."""
    b, h, hd = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    assert h % kvh == 0
    g = h // kvh
    block_k = min(block_k, s)
    assert s % block_k == 0, "cache length must divide block_k"
    n_k = s // block_k
    grid = (b * kvh, n_k)

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / (hd ** 0.5), window=window,
        block_k=block_k, n_kv_blocks=n_k, n_kv_heads=kvh)

    q_spec = pl.BlockSpec((None, None, g, hd),
                          lambda bk, ik, lens: (bk // kvh, bk % kvh, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd),
                           lambda bk, ik, lens: (bk // kvh, bk % kvh, ik, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(b, kvh, g, hd),
      k_cache.transpose(0, 2, 1, 3), v_cache.transpose(0, 2, 1, 3))
    return out.reshape(b, h, hd)
