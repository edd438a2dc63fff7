"""Multi-head latent attention (DeepSeek-V2/V3, no query LoRA).

Per token and layer the cache holds the RMS-normed latent ``c``
(``kv_lora_rank``) and the rotated key part ``k_pe`` (``qk_rope_head_dim``,
shared by every head), in place of per-head K and V:

    q = x Wq                    -> per head q_nope (nope) and q_pe (rope)
    [c, k_pe] = x Wkva;  c = RMSNorm(c)
    [k_nope, v] = c Wkvb        -> per head nope + v
    scores = (q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)

RoPE rotates ``q_pe`` and ``k_pe`` in pairs (2i, 2i+1): the deepseek_v3
code de-interleaves the pairs into halves and then rotates the halves, so
the rotated parts (and the cached ``k_pe``) lie de-interleaved.

Prefill decompresses the latent into per-head K/V.  Decode absorbs
``W_uk`` into the query and ``W_uv`` into the output and attends over the
latent cache itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from . import layers as L

# Prefill attends in query blocks of this many positions (when they divide
# the prompt), so the score block is [B, H, Q_CHUNK, S], not [B, H, S, S].
Q_CHUNK = 256


def init_mla(cfg: ArchConfig, rng: jax.Array) -> dict:
    a, pd = cfg.mla, jnp.dtype(cfg.param_dtype)
    D, H, R = cfg.d_model, cfg.n_heads, cfg.mla.kv_lora_rank
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": jax.random.normal(ks[0], (D, H * qk), pd) * D ** -0.5,
        "wkv_a": jax.random.normal(ks[1], (D, R + a.qk_rope_head_dim), pd) * D ** -0.5,
        "kv_norm": L.init_norm(cfg, R),
        "wkv_b": jax.random.normal(ks[2], (R, H * (a.qk_nope_head_dim + a.v_head_dim)),
                                   pd) * R ** -0.5,
        "wo": jax.random.normal(ks[3], (H * a.v_head_dim, D), pd) * (H * a.v_head_dim) ** -0.5,
    }


def rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, d]: de-interleave the pairs (2i, 2i+1) into halves, then
    rotate the halves; cos/sin [B, S, d/2]."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    return L.apply_rotary(x, cos, sin)


def _project(cfg: ArchConfig, p: dict, x: jax.Array, rope):
    """-> q_nope [B,S,H,nope], q_pe [B,S,H,rope], c [B,S,R], k_pe [B,S,rope]."""
    a = cfg.mla
    B, S, _ = x.shape
    cd = x.dtype
    q = (x @ p["wq"].astype(cd)).reshape(B, S, cfg.n_heads, -1)
    q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    kv = x @ p["wkv_a"].astype(cd)
    c = L.apply_norm(cfg, p["kv_norm"], kv[..., :a.kv_lora_rank])
    cos, sin = rope
    return (q_nope, rope_pairs(q_pe, cos, sin), c,
            rope_pairs(kv[..., None, a.kv_lora_rank:], cos, sin)[:, :, 0])


def _scale(cfg: ArchConfig) -> float:
    return 1.0 / np.sqrt(cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim)


def mla_ctx(cfg: ArchConfig, p: dict, x: jax.Array, *, rope, return_state: bool = False):
    """Causal attention over the whole prompt, the latent decompressed.
    With ``return_state`` also the cache leaves ``{"c", "k_pe"}`` [B, S, ...]."""
    a = cfg.mla
    B, S, _ = x.shape
    cd = x.dtype
    q_nope, q_pe, c, k_pe = _project(cfg, p, x, rope)
    kv = (c @ p["wkv_b"].astype(cd)).reshape(B, S, cfg.n_heads, -1)
    k_nope, v = kv[..., :a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]
    k_pos = jnp.arange(S, dtype=jnp.int32)

    def block(qn, qp, q_pos):
        s = (jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope, preferred_element_type=jnp.float32)
             + jnp.einsum("bqhp,bkp->bhqk", qp, k_pe, preferred_element_type=jnp.float32))
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s * _scale(cfg), -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(cd)
        return jnp.einsum("bhqk,bkhv->bqhv", w, v)

    if S > Q_CHUNK and S % Q_CHUNK == 0:
        n = S // Q_CHUNK

        def chunks(t):
            return jnp.moveaxis(t.reshape(B, n, Q_CHUNK, *t.shape[2:]), 1, 0)

        out = jax.lax.map(lambda args: block(*args),
                          (chunks(q_nope), chunks(q_pe), k_pos.reshape(n, Q_CHUNK)))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, cfg.n_heads, -1)
    else:
        out = block(q_nope, q_pe, k_pos)
    out = out.reshape(B, S, -1) @ p["wo"].astype(cd)
    if return_state:
        return out, {"c": c, "k_pe": k_pe}
    return out


def init_mla_cache(cfg: ArchConfig, batch: int, capacity: int) -> dict:
    cd = jnp.dtype(cfg.compute_dtype)
    return {
        "c": jnp.zeros((batch, capacity, cfg.mla.kv_lora_rank), cd),
        "k_pe": jnp.zeros((batch, capacity, cfg.mla.qk_rope_head_dim), cd),
        "kpos": jnp.full((capacity,), -1, jnp.int32),
    }


def mla_decode(cfg: ArchConfig, p: dict, x: jax.Array, cache: dict, pos: jax.Array, *,
               rope_fn) -> tuple[jax.Array, dict]:
    """One new token [B, 1, D] at position ``pos`` against the latent cache,
    with ``W_uk`` absorbed into the query and ``W_uv`` into the output."""
    a = cfg.mla
    B = x.shape[0]
    cd = x.dtype
    q_nope, q_pe, c_new, kpe_new = _project(cfg, p, x, rope_fn(jnp.broadcast_to(pos, (B, 1))))
    slot = (pos % cache["c"].shape[1]).astype(jnp.int32)
    c = jax.lax.dynamic_update_slice(cache["c"], c_new, (0, slot, 0))
    k_pe = jax.lax.dynamic_update_slice(cache["k_pe"], kpe_new, (0, slot, 0))
    kpos = jax.lax.dynamic_update_slice(cache["kpos"], pos[None].astype(jnp.int32), (slot,))

    wkv_b = p["wkv_b"].astype(cd).reshape(a.kv_lora_rank, cfg.n_heads, -1)
    w_uk, w_uv = wkv_b[..., :a.qk_nope_head_dim], wkv_b[..., a.qk_nope_head_dim:]
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c, preferred_element_type=jnp.float32)
         + jnp.einsum("bqhp,bsp->bhqs", q_pe, k_pe, preferred_element_type=jnp.float32))
    valid = (kpos >= 0) & (kpos <= pos)
    s = jnp.where(valid[None, None, None, :], s * _scale(cfg), -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(cd)
    o_lat = jnp.einsum("bhqs,bsr->bqhr", w, c)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv).reshape(B, 1, -1) @ p["wo"].astype(cd)
    return out, {"c": c, "k_pe": k_pe, "kpos": kpos}
