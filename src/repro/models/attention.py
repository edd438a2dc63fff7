"""GQA attention: full (train/prefill, query-chunked for long sequences),
decode (one token against a — possibly rolling/sliding-window — KV cache),
and cross-attention (whisper).  Pure JAX; the Pallas decode kernel in
``repro/kernels/decode_attention`` implements the same math for the paged
serving path and is validated against this reference."""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from .layers import apply_rotary, softcap

# Sequences longer than this use the query-chunked path (bounds the
# materialized [*, chunk, S] score block instead of [*, S, S]).
CHUNK_THRESHOLD = 2048
Q_CHUNK = 512


class dense_attention_for_costing:
    """Context manager: disable query chunking so the roofline costing pass
    sees attention FLOPs/bytes exactly once (a chunk scan body is counted
    once by XLA cost analysis regardless of trip count)."""

    def __enter__(self):
        global CHUNK_THRESHOLD
        self._old = CHUNK_THRESHOLD
        CHUNK_THRESHOLD = 1 << 62
        return self

    def __exit__(self, *exc):
        global CHUNK_THRESHOLD
        CHUNK_THRESHOLD = self._old
        return False


def init_attention(cfg: ArchConfig, rng: jax.Array, *, cross: bool = False) -> dict:
    pd = jnp.dtype(cfg.param_dtype)
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    s = 1.0 / np.sqrt(D)
    p = {
        "wq": jax.random.normal(ks[0], (D, H * hd), pd) * s,
        "wk": jax.random.normal(ks[1], (D, KV * hd), pd) * s,
        "wv": jax.random.normal(ks[2], (D, KV * hd), pd) * s,
        "wo": jax.random.normal(ks[3], (H * hd, D), pd) * (1.0 / np.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), pd)
        p["bk"] = jnp.zeros((KV * hd,), pd)
        p["bv"] = jnp.zeros((KV * hd,), pd)
    return p


def _project_qkv(cfg: ArchConfig, p: dict, x: jax.Array, kv_x: jax.Array | None = None):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = x.dtype
    src = x if kv_x is None else kv_x
    Skv = src.shape[1]
    q = x @ p["wq"].astype(cd)
    k = src @ p["wk"].astype(cd)
    v = src @ p["wv"].astype(cd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return (
        q.reshape(B, S, KV, H // KV, hd),
        k.reshape(B, Skv, KV, hd),
        v.reshape(B, Skv, KV, hd),
    )


def _attn_scores_block(
    q: jax.Array,        # [B, C, KV, G, hd]
    k: jax.Array,        # [B, S, KV, hd]
    v: jax.Array,        # [B, S, KV, hd]
    q_pos: jax.Array,    # [C] int32 (query absolute positions)
    k_pos: jax.Array,    # [S] int32
    *,
    head_dim: int,
    causal: bool,
    window: int | None,
    cap: float | None,
) -> jax.Array:
    """Dense attention of one query block against the full K/V. [B,C,KV,G,hd]."""
    scores = jnp.einsum("bckgd,bskd->bkgcs", q, k) / np.sqrt(head_dim)
    scores = softcap(scores, cap)
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgcs,bskd->bckgd", w, v)


def attention_ctx(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,
    *,
    rope: tuple[jax.Array, jax.Array] | None,
    causal: bool = True,
    window: int | None = None,
    kv_x: jax.Array | None = None,
    q_chunk: int = Q_CHUNK,
    return_kv: bool = False,
):
    """Full-context attention (train / prefill / encoder / cross).

    Long sequences are processed in query chunks via ``lax.scan`` so the
    materialized score block is [*, chunk, S] (see DESIGN.md §7 for the
    cost-scope implication).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(cfg, p, x, kv_x)
    Skv = k.shape[1]
    if rope is not None:
        cos, sin = rope
        qf = q.reshape(B, S, H, hd)
        qf = apply_rotary(qf, cos, sin)
        q = qf.reshape(B, S, KV, H // KV, hd)
        k = apply_rotary(k, cos, sin)
    q_pos = jnp.arange(S, dtype=jnp.int32)
    k_pos = jnp.arange(Skv, dtype=jnp.int32)
    block = partial(_attn_scores_block, head_dim=hd, causal=causal, window=window,
                    cap=cfg.attn_softcap)

    if S <= CHUNK_THRESHOLD or S % q_chunk != 0:
        out = block(q, k, v, q_pos, k_pos)
    else:
        n_chunks = S // q_chunk
        qc = jnp.moveaxis(q.reshape(B, n_chunks, q_chunk, KV, H // KV, hd), 1, 0)
        pc = q_pos.reshape(n_chunks, q_chunk)

        def body(_, inp):
            qb, pb = inp
            return None, block(qb, k, v, pb, k_pos)

        _, outc = jax.lax.scan(body, None, (qc, pc))
        out = jnp.moveaxis(outc, 0, 1).reshape(B, S, KV, H // KV, hd)

    out = out.reshape(B, S, H * hd) @ p["wo"].astype(x.dtype)
    if return_kv:
        return out, (k, v)
    return out


# ------------------------------------------------------------- KV cache ----
@dataclasses.dataclass(frozen=True)
class CacheSpec:
    capacity: int


def init_attn_cache(cfg: ArchConfig, batch: int, capacity: int) -> dict:
    cd = jnp.dtype(cfg.compute_dtype)
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, capacity, KV, hd), cd),
        "v": jnp.zeros((batch, capacity, KV, hd), cd),
        "kpos": jnp.full((capacity,), -1, jnp.int32),
    }


def prefill_cache(cfg: ArchConfig, state: dict, capacity: int) -> dict:
    """Build a cache from a layer's prefill state: leaves [B, S, ...] with a
    token axis, K and V or a latent and its rotated key (S <= capacity;
    for a sliding-window cache, capacity = window and the tail of the
    sequence is kept)."""
    S = next(iter(state.values())).shape[1]
    if S > capacity:          # rolling window: keep last `capacity` tokens
        kpos = jnp.arange(S - capacity, S, dtype=jnp.int32)
        # slot layout must match pos % capacity
        order = jnp.argsort(kpos % capacity)
        out = {name: a[:, S - capacity:][:, order] for name, a in state.items()}
        kpos = kpos[order]
    else:
        pad = capacity - S
        out = {name: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
               for name, a in state.items()}
        kpos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                                jnp.full((pad,), -1, jnp.int32)])
    return {**out, "kpos": kpos}


def write_token(cache: dict, rows: dict, pos: jax.Array, at: tuple = ()) -> tuple[dict, dict]:
    """One token into a decode cache: each leaf of ``rows`` ([B, 1, ...])
    at slot ``pos % capacity``, and ``pos`` into ``kpos``, of the cache at
    index ``at``: a layer of a stack whose leaves are [L, B, capacity, ...]
    and [L, capacity], or ``()`` for one layer's cache.

    -> (the cache with only the token's rows written, so that a donated or
    carried stack is updated where it lies; the layer at ``at`` as the
    attention reads it: the cache's layer as it was, with the token
    selected in, which the attention dots read in place before the write.
    Read after the write, the layer gave the v5e compiler the write's
    layout to honour, and it copied every layer out for the dots.)"""
    kpos = cache["kpos"]
    cap = kpos.shape[-1]
    slot = (pos % cap).astype(jnp.int32)
    lead = tuple(jnp.asarray(i, jnp.int32) for i in at)
    zero = jnp.int32(0)
    layer = cache
    for i in lead:
        layer = {name: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                 for name, a in layer.items()}
    here = jnp.arange(cap, dtype=jnp.int32) == slot
    pos = pos.astype(jnp.int32)
    read = {name: jnp.where(here.reshape((cap,) + (1,) * (r.ndim - 2)), r, layer[name])
            for name, r in rows.items()}
    read["kpos"] = jnp.where(here, pos, layer["kpos"])
    out = {name: jax.lax.dynamic_update_slice(
               cache[name], r.reshape((1,) * len(at) + r.shape),
               lead + (zero, slot) + (zero,) * (r.ndim - 2))
           for name, r in rows.items()}
    out["kpos"] = jax.lax.dynamic_update_slice(kpos, pos.reshape((1,) * kpos.ndim),
                                               lead + (slot,))
    return out, read


def attention_decode(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,          # [B, 1, D] — ONE new token
    cache: dict,
    pos: jax.Array,        # scalar int32: absolute position of the new token
    *,
    rope_fn=None,          # positions -> (cos, sin) for a [B,1] position
    window: int | None = None,
    at: tuple = (),        # the layer of a stacked cache (``write_token``)
) -> tuple[jax.Array, dict]:
    """Single-token decode against a (rolling) KV cache; returns the
    cache with the token written (``write_token``)."""
    B, _, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k_new, v_new = _project_qkv(cfg, p, x)
    if rope_fn is not None:
        pos_b = jnp.broadcast_to(pos, (B, 1))
        cos_q, sin_q = rope_fn(pos_b)
        qf = apply_rotary(q.reshape(B, 1, H, hd), cos_q, sin_q)
        q = qf.reshape(B, 1, KV, H // KV, hd)
        k_new = apply_rotary(k_new, cos_q, sin_q)

    cache, layer = write_token(cache, {"k": k_new, "v": v_new}, pos, at)
    k, v, kpos = layer["k"], layer["v"], layer["kpos"]

    scores = jnp.einsum("bckgd,bskd->bkgcs", q, k) / np.sqrt(hd)
    scores = softcap(scores, cfg.attn_softcap)
    valid = (kpos >= 0) & (kpos <= pos)
    if window is not None:
        valid = valid & (pos - kpos < window)
    scores = jnp.where(valid[None, None, None, None, :], scores.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgcs,bskd->bckgd", w, v)
    out = out.reshape(B, 1, H * hd) @ p["wo"].astype(x.dtype)
    return out, cache
