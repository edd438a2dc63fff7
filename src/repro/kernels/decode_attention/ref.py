"""Pure-jnp oracle for paged flash-decode attention."""
from __future__ import annotations

import jax.numpy as jnp


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               *, softcap=None):
    """q [B,KV,G,hd]; pools [n,KV,bt,hd]; tables [B,max_blocks]; lengths [B]."""
    B, KV, G, hd = q.shape
    bt = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    scale = 1.0 / (hd ** 0.5)
    outs = []
    for b in range(B):
        k = jnp.take(k_pool, block_tables[b], axis=0)   # [mb, KV, bt, hd]
        v = jnp.take(v_pool, block_tables[b], axis=0)
        k = jnp.swapaxes(k, 1, 2).reshape(max_blocks * bt, KV, hd)
        v = jnp.swapaxes(v, 1, 2).reshape(max_blocks * bt, KV, hd)
        s = jnp.einsum("kgd,skd->kgs", q[b].astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = jnp.arange(max_blocks * bt)
        s = jnp.where(pos[None, None, :] < lengths[b], s, -1e30)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        outs.append(jnp.einsum("kgs,skd->kgd", w, v.astype(jnp.float32)))
    return jnp.stack(outs).astype(q.dtype)
