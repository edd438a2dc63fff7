"""Paged flash-decode attention kernel: one query token per sequence against
a paged KV cache (block table indirection), online-softmax accumulation.

This is the CU/"kernel-based" side of the paper's KV-fetch comparison
(§5.3.1): instead of DMA-fetching blocks into a contiguous buffer first, a
single kernel walks the dispersed blocks directly (one grid step per block —
the analogue of one workgroup per KV block).

Grid: (batch, kv_heads, max_blocks); scalar-prefetch operands are the block
table and per-sequence lengths.  The pools are laid out head-major,
``[n_pool, KV, bt, hd]``, so one K/V block is a ``(bt, hd)`` tile: the TPU
compiler needs the last two dims of a block to be (8, 128)-aligned or whole,
which a ``(1, hd)`` slice of a token-major pool is not.  VMEM scratch
carries the running max / normalizer / accumulator across the block axis
(grid iterates row-major, so the block axis is innermost).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    tbl_ref,      # [B, max_blocks] int32 (scalar prefetch)
    len_ref,      # [B] int32 (scalar prefetch)
    q_ref,        # [1, 1, G, hd]
    k_ref,        # [1, 1, bt, hd]
    v_ref,        # [1, 1, bt, hd]
    o_ref,        # [1, 1, G, hd]
    m_scr,        # [G, 1] f32
    l_scr,        # [G, 1] f32
    acc_scr,      # [G, hd] f32
    *,
    block_tokens: int,
    scale: float,
    softcap: float | None,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_blocks = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    base = j * block_tokens

    @pl.when(base < length)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)                    # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)                    # [bt, hd]
        v = v_ref[0, 0].astype(jnp.float32)                    # [bt, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [G, bt]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_scr[...]                                    # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                 # [G, bt]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,             # [B, KV, G, hd] (grouped query heads)
    k_pool: jax.Array,        # [n_pool, KV, bt, hd]
    v_pool: jax.Array,        # [n_pool, KV, bt, hd]
    block_tables: jax.Array,  # [B, max_blocks] int32
    lengths: jax.Array,       # [B] int32
    *,
    softcap: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns attention output [B, KV, G, hd]."""
    B, KV, G, hd = q.shape
    bt = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, tbl, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, j, tbl, ln: (tbl[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, j, tbl, ln: (tbl[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, j, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, block_tokens=bt, scale=scale,
                               softcap=softcap)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pool, v_pool)
