"""Shared neural-net layers: norms, rotary embeddings (RoPE / M-RoPE /
sinusoidal), gated MLPs, embeddings.  Pure-functional: params are nested
dicts of arrays, every ``apply`` is jit-safe."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------- norms ----
def init_norm(cfg: ArchConfig, d: int) -> dict:
    p = {"scale": jnp.ones((d,), _dtype(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg.param_dtype))
    return p


def apply_norm(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ------------------------------------------------------------- rotaries ----
def rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions [..., S] -> cos/sin [..., S, head_dim/2] (float32)."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def mrope_angles(positions: jax.Array, head_dim: int, theta: float,
                 sections: tuple[int, ...]) -> tuple[jax.Array, jax.Array]:
    """M-RoPE (Qwen2-VL): positions [3, B, S] (t/h/w); frequency bands are
    split into ``sections`` (sum = head_dim/2), each band rotated by its own
    position stream.  For text tokens the three streams coincide with the
    1-D position, recovering vanilla RoPE."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    # per-band position selector
    band = np.concatenate([np.full((s,), i) for i, s in enumerate(sections)])
    band = jnp.asarray(band)  # [half] in {0,1,2}
    pos = positions.astype(jnp.float32)            # [3, B, S]
    pos_per_freq = jnp.take(pos, band, axis=0)     # [half, B, S]
    ang = jnp.moveaxis(pos_per_freq, 0, -1) * inv_freq  # [B, S, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, hd]; cos/sin [B, S, hd/2] (broadcast over heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def sinusoidal_embedding(seq: int, d: int) -> jax.Array:
    """Whisper-style fixed sinusoidal positional embedding [seq, d]."""
    half = d // 2
    inv = np.exp(-np.log(10000.0) / (half - 1) * np.arange(half))
    ang = np.arange(seq)[:, None] * inv[None, :]
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=1)
    return jnp.asarray(emb, dtype=jnp.float32)


# ----------------------------------------------------------------- MLPs ----
def init_mlp(cfg: ArchConfig, rng: jax.Array, d: int, f: int) -> dict:
    pd = _dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in, scale_out = d ** -0.5, f ** -0.5   # Python floats keep ``pd``
    if cfg.act == "silu":
        return {
            "wg": jax.random.normal(k1, (d, f), pd) * scale_in,
            "wu": jax.random.normal(k2, (d, f), pd) * scale_in,
            "wd": jax.random.normal(k3, (f, d), pd) * scale_out,
        }
    return {
        "wu": jax.random.normal(k1, (d, f), pd) * scale_in,
        "bu": jnp.zeros((f,), pd),
        "wd": jax.random.normal(k2, (f, d), pd) * scale_out,
        "bd": jnp.zeros((d,), pd),
    }


def apply_mlp(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    cd = x.dtype
    if cfg.act == "silu":
        g = x @ p["wg"].astype(cd)
        u = x @ p["wu"].astype(cd)
        return (jax.nn.silu(g) * u) @ p["wd"].astype(cd)
    h = jax.nn.gelu(x @ p["wu"].astype(cd) + p["bu"].astype(cd))
    return h @ p["wd"].astype(cd) + p["bd"].astype(cd)


# ----------------------------------------------------------- embeddings ----
def init_embedding(cfg: ArchConfig, rng: jax.Array) -> jax.Array:
    pd = _dtype(cfg.param_dtype)
    return jax.random.normal(rng, (cfg.vocab, cfg.d_model), pd) * 0.02


def embed_tokens(cfg: ArchConfig, table: jax.Array, tokens: jax.Array) -> jax.Array:
    x = jnp.take(table, tokens, axis=0).astype(_dtype(cfg.compute_dtype))
    if cfg.family == "dense" and cfg.tie_embeddings and cfg.name.startswith("gemma2"):
        x = x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype)
    return x


def unembed(cfg: ArchConfig, table_or_w: jax.Array, x: jax.Array) -> jax.Array:
    """Project to vocab; applies gemma2 final logit soft-capping."""
    logits = x @ table_or_w.astype(x.dtype)
    if cfg.final_softcap:
        c = jnp.asarray(cfg.final_softcap, logits.dtype)
        logits = c * jnp.tanh(logits / c)
    return logits


def softcap(scores: jax.Array, cap: float | None) -> jax.Array:
    if not cap:
        return scores
    c = jnp.asarray(cap, scores.dtype)
    return c * jnp.tanh(scores / c)
