"""Hierarchical (latte) MoE dispatch: shard_map + explicit expert all-to-all.

EXPERIMENTS.md §Perf found that GSPMD-transparent MoE dispatch dead-ends:
the global-argsort scatter is opaque to the partitioner, which replicates
the capacity buffer and all-reduces it per layer (4.2 TB/device/step on
mixtral train_4k).  This module is the identified fix, and it is the
paper's own story one level up — an EXPLICIT schedule (local pack + expert
all-to-all, the exact collective §4.3 optimizes with swap/b2b) replacing a
transparent runtime decision:

  1. shard_map over the expert-parallel axis: tokens arrive sharded.
  2. LOCAL top-k + LOCAL capacity pack (argsort never crosses devices).
  3. expert all-to-all (CommBackend: pairwise-swap/b2b/reference by size).
  4. local expert FFNs on owned experts.
  5. all-to-all back + local weighted combine.

Requires n_experts % axis_size == 0 (true expert parallelism).  Validated
against a no-drop dense oracle in tests/test_latte_moe.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.base import ArchConfig
from . import collectives as coll


def _local_capacity(cfg: ArchConfig, t_local: int) -> int:
    m = cfg.moe
    cap = int(np.ceil(t_local * m.top_k / m.n_experts * m.capacity_factor))
    return max(4, cap)


def latte_moe_local(cfg: ArchConfig, p: dict, xf: jax.Array, axis_name: str,
                    *, all_to_all=None):
    """Per-shard body (call inside shard_map over ``axis_name``).

    xf: [T_local, D] local tokens.  Expert weights in ``p`` are the LOCAL
    expert shards: router [D, E] (replicated), wg/wu/wd [E_local, ...].
    Returns ([T_local, D], aux).
    """
    a2a = all_to_all or coll.pairwise_all_to_all
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    T, D = xf.shape
    C = _local_capacity(cfg, T)
    n_shards = jax.lax.axis_size(axis_name)
    e_local = E // n_shards
    cd = xf.dtype

    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, K)
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    assign = jnp.zeros((E,), jnp.float32).at[topk_e.reshape(-1)].add(1.0)
    aux = E * jnp.sum(me * (assign / (T * K))) * m.router_aux_weight

    # ---- LOCAL pack: argsort over local assignments only ----
    flat_e = topk_e.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
    pos = jnp.arange(T * K, dtype=jnp.int32) - group_start[sorted_e].astype(jnp.int32)
    keep = pos < C
    pos_c = jnp.where(keep, pos, C)
    token_of = (order // K).astype(jnp.int32)

    send = jnp.zeros((E, C, D), cd).at[sorted_e, pos_c].set(
        xf[token_of] * keep[:, None].astype(cd), mode="drop")

    # ---- expert all-to-all: [n_shards, e_local, C, D] chunks ----
    send = send.reshape(n_shards, e_local, C, D)
    recv = a2a(send, axis_name)              # [n_shards(src), e_local, C, D]

    # ---- local expert FFNs over owned experts ----
    buf = jnp.moveaxis(recv, 0, 1).reshape(e_local, n_shards * C, D)
    h = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(cd))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["wd"].astype(cd))

    # ---- return trip + local combine ----
    back = jnp.moveaxis(y.reshape(e_local, n_shards, C, D), 1, 0)
    mine = a2a(back, axis_name).reshape(E, C, D)   # my tokens' outputs

    contrib = mine[sorted_e, pos_c] * keep[:, None].astype(cd)
    weights = topk_p.reshape(-1)[order].astype(cd)
    out = jnp.zeros((T, D), cd).at[token_of].add(contrib * weights[:, None])
    return out, aux


def make_latte_moe(cfg: ArchConfig, mesh, axis_name: str, *, all_to_all=None):
    """Returns fn(params, x [B,S,D]) -> (out, aux) running the hierarchical
    dispatch under shard_map: tokens sharded on batch over ``axis_name``,
    expert weights sharded on the expert dim."""
    from jax.sharding import PartitionSpec as P

    assert cfg.moe and cfg.moe.n_experts % mesh.shape[axis_name] == 0

    def fn(p, x):
        B, S, D = x.shape

        def body(router, wg, wu, wd, xl):
            b, s, d = xl.shape
            out, aux = latte_moe_local(
                cfg, {"router": router, "wg": wg, "wu": wu, "wd": wd},
                xl.reshape(b * s, d), axis_name, all_to_all=all_to_all)
            return out.reshape(b, s, d), jax.lax.pmean(aux, axis_name)

        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None), P(axis_name, None, None),
                      P(axis_name, None, None), P(axis_name, None, None),
                      P(axis_name, None, None)),
            out_specs=(P(axis_name, None, None), P()),
            check_vma=False)
        return mapped(p["router"], p["wg"], p["wu"], p["wd"], x)

    return fn
