"""Mixture-of-Experts: routing, then a dropless dispatch (grouped, or dense
on few tokens) or a sort-based capacity dispatch, then the shared experts.

Design notes (DESIGN.md §6/§7):
* Routing: ``softmax`` (top-k of the softmax, renormalised) or DeepSeek-V3's
  ``sigmoid`` (noaux_tc): top-k of sigmoid scores plus a per-expert
  selection bias, each chosen expert weighted by its score alone,
  normalised to sum 1, times ``routed_scaling``.  The router runs in float32.
* Expert parallelism: a device holds ``MoEConfig.held`` experts, (first,
  count), routes over all ``n_experts`` and computes its own experts' part
  of the result; routes to the others add nothing here (on one chip, with no
  exchange).  Its weights are [count, ...].
* ``capacity_factor=None``: dropless, every route is computed, in one of
  two forms picked by the call's static token count ``T`` (``held_dense``):
  - grouped (``T`` above ``HELD_DENSE_TOKENS``: prefill, training): routes
    are sorted by held expert and each expert's contiguous rows go through
    grouped matmuls (``jax.lax.ragged_dot``), so only the held routes are
    computed and no buffer is padded to a capacity.  On TPU a grouped
    matmul is a custom call, which takes no fused operand: inside the
    layer scan each expert stack's per-layer slice is first copied to a
    buffer of its own, the held weights read and written once more before
    the matmul reads them.
  - dense (``T`` up to the bound: decode): every held expert on every
    token as plain batched dots, each token's output weighted by its gate
    for that expert (zero, by ``jnp.where``, where it did not pick it).
    The dots read the stacked weights where they lie, the layer slice fused
    into them.  Both forms read every held expert's weights once (at 32
    tokens, 7.66 of 8 are expected to be touched); the dense form spends
    ``T`` multiply-adds, ``2T`` FLOPs, on each 2-byte bf16 weight, so it
    stays bound by the weights' bytes while ``T`` is below the chip's FLOPs
    per HBM byte, 197 TFLOP/s / 819 GB/s ~ 240 on v5e.  The bound of 128
    keeps it near half that.
* ``capacity_factor`` set: dispatch is gather/scatter-based (argsort by
  expert id + per-expert capacity buffer), NOT a dense [T, E, C] one-hot
  einsum — so the compiled FLOPs equal the *active* expert FLOPs.  The
  expert buffer [E, C, D] carries a sharding constraint that places the
  expert axis on the 'model' mesh axis when divisible (expert parallelism):
  GSPMD then materializes the token exchange as all-to-all — exactly the
  collective the paper optimizes (swap/b2b for latency-bound sizes, §4.3).
  When E < mesh width (mixtral: 8 experts on 16 chips), the expert FFN
  hidden dim is sharded instead (tensor-parallel experts).  Every token
  keeps its top-k weights; routes over capacity are dropped, as in
  Switch/GShard-style systems.
* Shared experts: one MLP of ``n_shared_experts * d_ff_expert`` every token
  goes through, added once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from . import layers as L


def init_moe(cfg: ArchConfig, rng: jax.Array) -> dict:
    m = cfg.moe
    assert m is not None
    pd = jnp.dtype(cfg.param_dtype)
    D, F = cfg.d_model, m.d_ff_expert
    E = m.held_range[1]
    ks = jax.random.split(rng, 6)
    s_in, s_out = D ** -0.5, F ** -0.5          # Python floats keep ``pd``
    p = {
        "router": jax.random.normal(ks[0], (D, m.n_experts), pd) * s_in,
        "wg": jax.random.normal(ks[1], (E, D, F), pd) * s_in,
        "wu": jax.random.normal(ks[2], (E, D, F), pd) * s_in,
        "wd": jax.random.normal(ks[3], (E, F, D), pd) * s_out,
    }
    if m.scoring == "sigmoid":
        p["bias"] = jnp.zeros((m.n_experts,), jnp.float32)
    if m.n_shared_experts:
        p["shared"] = L.init_mlp(cfg, ks[4], D, m.n_shared_experts * F)
    return p


def capacity_for(cfg: ArchConfig, n_tokens: int) -> int:
    m = cfg.moe
    cap = int(np.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    # large capacities round to 128 so the capacity axis is shardable over
    # the DP mesh axes (16 or 32) — see sharding.rules 'expert' kind.
    mult = 128 if cap >= 128 else 8
    return max(8, int(np.ceil(cap / mult)) * mult)


def route(cfg: ArchConfig, p: dict, xf: jax.Array):
    """xf [T, D] -> (weights [T, K] f32, experts [T, K], aux loss)."""
    m = cfg.moe
    T = xf.shape[0]
    E, K = m.n_experts, m.top_k
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)     # [T, E]
    if m.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, topk_e = jax.lax.top_k(probs + p["bias"].astype(jnp.float32), K)
        topk_p = jnp.take_along_axis(probs, topk_e, axis=-1)
        topk_p = topk_p / (jnp.sum(topk_p, axis=-1, keepdims=True) + 1e-20)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)          # for the aux loss
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_e = jax.lax.top_k(probs, K)                          # [T, K]
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
    topk_p = topk_p * m.routed_scaling

    # ---- load-balance auxiliary loss (Switch-style) ----
    me = jnp.mean(probs, axis=0)                                          # [E]
    assign = jnp.zeros((E,), jnp.float32).at[topk_e.reshape(-1)].add(1.0)
    ce = assign / (T * K)
    aux = E * jnp.sum(me * ce) * m.router_aux_weight
    return topk_p, topk_e, aux


HELD_DENSE_TOKENS = 128


def held_dense(cfg: ArchConfig, n_tokens: int) -> bool:
    """Whether a call of ``n_tokens`` tokens computes its held experts in
    the dense form (module docstring): a dropless layer at a token count
    below the chip's FLOPs per HBM byte, with room to spare."""
    m = cfg.moe
    return m is not None and m.capacity_factor is None and n_tokens <= HELD_DENSE_TOKENS


def _held_dense(p: dict, xf, topk_p, local, held):
    """Every held expert on every token, combined by each token's gate for
    it; a token picks an expert at most once, so the gate is its one route
    weight there."""
    cd = xf.dtype
    hit = local[None] == jnp.arange(held, dtype=local.dtype)[:, None, None]   # [E, T, K]
    gate = jnp.sum(jnp.where(hit, topk_p[None], 0.0), axis=-1)                # [E, T]
    h = (jax.nn.silu(jnp.einsum("td,edf->etf", xf, p["wg"].astype(cd)))
         * jnp.einsum("td,edf->etf", xf, p["wu"].astype(cd)))
    y = jnp.einsum("etf,efd->etd", h, p["wd"].astype(cd))                     # [E, T, D]
    y = jnp.where(jnp.any(hit, axis=-1)[..., None], y, 0)   # an unrouted row's inf stays out
    return jnp.einsum("etd,et->td", y, gate.astype(cd),
                      preferred_element_type=jnp.float32).astype(cd)


def _dropless(p: dict, xf, topk_p, local, held):
    """Every route to a held expert through grouped matmuls, rows sorted by
    expert; the others (``local == held``) sort last and add nothing."""
    T, K = local.shape
    cd = xf.dtype
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
    xs = xf[order // K]                                                   # [T*K, D]
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"].astype(cd), sizes))
         * jax.lax.ragged_dot(xs, p["wu"].astype(cd), sizes))
    y = jax.lax.ragged_dot(h, p["wd"].astype(cd), sizes)                  # [T*K, D]
    y = jnp.where((flat[order] < held)[:, None], y, 0)
    y = y[jnp.argsort(order)].reshape(T, K, -1)                           # back to [T, K, D]
    w = jnp.where(local < held, topk_p, 0.0)
    return jnp.einsum("tkd,tk->td", y, w.astype(cd),
                      preferred_element_type=jnp.float32).astype(cd)


def _capacity(cfg: ArchConfig, p: dict, xf, topk_p, local, held, expert_sharding):
    T, K = local.shape
    C = capacity_for(cfg, T)
    cd = xf.dtype
    D = xf.shape[1]
    flat_e = local.reshape(-1)                                            # [T*K]
    order = jnp.argsort(flat_e)                                           # [T*K]
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(held + 1, dtype=sorted_e.dtype))
    pos = jnp.arange(T * K, dtype=jnp.int32) - group_start[sorted_e].astype(jnp.int32)
    keep = (pos < C) & (sorted_e < held)
    pos_c = jnp.where(keep, pos, C)                                       # C == drop slot
    token_of = (order // K).astype(jnp.int32)

    buf = jnp.zeros((held, C, D), cd)
    buf = buf.at[sorted_e, pos_c].set(xf[token_of], mode="drop")
    if expert_sharding is not None:
        buf = expert_sharding(buf)

    # ---- expert FFNs: active-FLOP einsum over the capacity buffer ----
    h = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(cd))
    h = jax.nn.silu(h) * u
    if expert_sharding is not None:
        h = expert_sharding(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["wd"].astype(cd))
    if expert_sharding is not None:
        y = expert_sharding(y)

    # ---- combine: gather back + weighted sum over k ----
    contrib = y[jnp.minimum(sorted_e, held - 1), pos_c] * keep[:, None].astype(cd)  # [T*K, D]
    weights = topk_p.reshape(-1)[order].astype(cd)
    return jnp.zeros((T, D), cd).at[token_of].add(contrib * weights[:, None])


def apply_moe(
    cfg: ArchConfig,
    p: dict,
    x: jax.Array,                      # [B, S, D]
    *,
    expert_sharding=None,              # optional fn: buffer [E,C,D/F] -> constrained
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output [B,S,D], router aux loss scalar, routes to held
    experts int32 scalar)."""
    m = cfg.moe
    B, S, D = x.shape
    first, held = m.held_range
    xf = x.reshape(B * S, D)
    topk_p, topk_e, aux = route(cfg, p, xf)
    local = topk_e - first
    local = jnp.where((local >= 0) & (local < held), local, held).astype(jnp.int32)
    if held_dense(cfg, B * S):
        out = _held_dense(p, xf, topk_p, local, held)
    elif m.capacity_factor is None:
        out = _dropless(p, xf, topk_p, local, held)
    else:
        out = _capacity(cfg, p, xf, topk_p, local, held, expert_sharding)
    if m.n_shared_experts:
        out = out + L.apply_mlp(cfg, p["shared"], xf)
    n_local = jnp.sum(local < held, dtype=jnp.int32)
    return out.reshape(B, S, D), aux, n_local
