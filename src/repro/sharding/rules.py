"""Logical sharding rules: param/activation PartitionSpecs per mesh.

Strategy (DESIGN.md §7):
* FSDP: the non-TP dimension of every large matrix is sharded over the
  data-parallel axes (('pod','data') on the multi-pod mesh) — ZeRO-style
  fully-sharded storage; GSPMD inserts the layer-wise all-gathers.
* TP:   head/ffn/vocab output dims shard over 'model'.
* Every rule is divisibility-guarded: a dim that doesn't divide the axis
  size falls back to replicated on that axis (e.g. qwen2-0.5b's 14 heads).
* Activations: hidden states are sharded batch-over-DP and sequence-over-
  'model' between blocks (Megatron-style sequence parallelism); attention
  and MLP internals reshard as GSPMD requires.
* batch==1 decode (long_500k): batch is unshardable — KV-cache capacity is
  sharded over the DP axes instead (context parallelism).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@dataclasses.dataclass(frozen=True)
class PerfOptions:
    """Beyond-baseline performance knobs (§Perf in EXPERIMENTS.md).

    Defaults are the OPTIMIZED configuration; the recorded baseline used
    ``PerfOptions.baseline()``.
    """

    expert_sharding: bool = True      # shard MoE capacity buffers over DP
    cast_params_bf16: bool = True     # gather bf16 weights, fp32 master copy
    light_resharding: bool = True     # one seq-reshard point per block, not two

    @classmethod
    def baseline(cls) -> "PerfOptions":
        return cls(expert_sharding=False, cast_params_bf16=False,
                   light_resharding=False)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    mesh: Mesh
    dp: Any          # data-parallel axes: ('pod','data') or 'data'
    tp: Any = "model"

    def _ok(self, dim: int, axes) -> Any:
        return axes if axes is not None and dim % _axsize(self.mesh, axes) == 0 else None

    def matrix(self, shape: tuple[int, ...], tp_dim: int, *, stacked: int = 0) -> P:
        """Spec for a (possibly layer-stacked) weight matrix: FSDP on the
        first non-stacked non-TP dim, TP on ``tp_dim``."""
        spec: list = [None] * len(shape)
        spec[tp_dim] = self._ok(shape[tp_dim], self.tp)
        for i in range(stacked, len(shape)):
            if i != tp_dim:
                spec[i] = self._ok(shape[i], self.dp)
                break
        return P(*spec)

    def replicated(self, shape) -> P:
        return P(*([None] * len(shape)))


def infer_param_specs(params_shape: Any, cfg: ArchConfig, rules: ShardingRules) -> Any:
    """Walk the (abstract) param tree and assign PartitionSpecs by leaf path."""

    def spec_for(path: tuple, leaf) -> P:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        shape = leaf.shape
        nd = len(shape)
        joined = "/".join(str(n) for n in names)
        stacked = 1 if any(n in ("blocks", "dense_blocks", "supers", "enc_blocks", "dec_blocks")
                           for n in names) else 0
        # extra stacking inside zamba superblocks: params under supers are
        # [trip, ...] only (inner layers are tuple-indexed, not stacked).
        if nd - stacked == 0:
            return rules.replicated(shape)
        last = names[-1]
        if last in ("embed", "dec_pos"):               # [V, D]
            return P(rules._ok(shape[0], rules.tp), rules._ok(shape[1], rules.dp))
        if last == "unembed":                          # [D, V]
            return P(rules._ok(shape[0], rules.dp), rules._ok(shape[1], rules.tp))
        if last == "router":                           # [.., D, E]
            spec = [None] * nd
            spec[-2] = rules._ok(shape[-2], rules.dp)
            return P(*spec)
        if last in ("wg", "wu", "wd") and "moe" in joined and "shared" not in names:
            # routed experts [.., E, D|F, F|D] (shared experts: a plain MLP)
            # Expert-parallel when E divides the TP axis; otherwise TP the
            # expert-FFN hidden dim.  (Padded EP for E < axis is rejected by
            # jit argument shardings; hierarchical shard_map dispatch is the
            # identified fix — EXPERIMENTS.md §Perf mixtral iterations.)
            spec = [None] * nd
            e = nd - 3
            d_dim = nd - 2 if last in ("wg", "wu") else nd - 1   # d_model dim
            f_dim = nd - 1 if last in ("wg", "wu") else nd - 2   # expert-FFN dim
            if shape[e] % _axsize(rules.mesh, rules.tp) == 0:
                spec[e] = rules.tp                                # expert parallel
                spec[d_dim] = rules._ok(shape[d_dim], rules.dp)
            else:
                spec[f_dim] = rules._ok(shape[f_dim], rules.tp)   # TP inside experts
                spec[d_dim] = rules._ok(shape[d_dim], rules.dp)
            return P(*spec)
        if last == "wkv_a":                            # MLA [.., D, latent + rope]
            spec = [None] * nd                         # one latent for every head
            spec[nd - 2] = rules._ok(shape[nd - 2], rules.dp)
            return P(*spec)
        in_dim_names = {"wo", "wd", "w_out", "wB"}
        out_dim_names = {"wq", "wk", "wv", "wg", "wu", "wA", "w_in", "wr",
                         "wkv_b"}                      # MLA's up-projection: per head
        if "cm" in names and last == "wv":              # rwkv channel-mix [F, D]
            return _in_dim_tp(rules, shape, stacked)
        if last in ("wq", "wk", "wv") and ("attn" in joined or "self_attn" in joined
                                           or "cross_attn" in joined or "shared_attn" in joined):
            # TP on the head axis only when WHOLE heads divide the axis:
            # splitting inside a head (e.g. qwen2-0.5b's 2 KV heads over 16
            # chips) forces a full KV-cache re-gather every decode step
            # (§Perf iteration 2 — measured 9.7 GB/step/device).
            heads = cfg.n_heads if last == "wq" else cfg.n_kv_heads
            if heads % _axsize(rules.mesh, rules.tp) != 0:
                spec = [None] * nd
                spec[nd - 2] = rules._ok(shape[nd - 2], rules.dp)
                return P(*spec)
            return rules.matrix(shape, nd - 1, stacked=stacked)
        if last == "wo" and ("attn" in joined or "self_attn" in joined
                             or "cross_attn" in joined or "shared_attn" in joined):
            if cfg.n_heads % _axsize(rules.mesh, rules.tp) != 0:
                spec = [None] * nd
                spec[nd - 1] = rules._ok(shape[nd - 1], rules.dp)
                return P(*spec)
            return _in_dim_tp(rules, shape, stacked)
        if nd >= 2 and last in out_dim_names:
            return rules.matrix(shape, nd - 1, stacked=stacked)  # out-dim TP
        if nd >= 2 and last in in_dim_names:
            return _in_dim_tp(rules, shape, stacked)
        if nd >= 2 and last == "conv_w":
            spec = [None] * nd
            spec[-1] = rules._ok(shape[-1], rules.tp)
            return P(*spec)
        return rules.replicated(shape)

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


def _in_dim_tp(rules: ShardingRules, shape, stacked: int) -> P:
    """TP on the input (second-to-last) dim, FSDP on the output dim."""
    nd = len(shape)
    spec: list = [None] * nd
    spec[nd - 2] = rules._ok(shape[nd - 2], rules.tp)
    spec[nd - 1] = rules._ok(shape[nd - 1], rules.dp)
    return P(*spec)


def make_activation_constrainer(cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules,
                                perf: "PerfOptions | None" = None):
    """Returns ac(x, kind) applying with_sharding_constraint inside models."""
    perf = perf or PerfOptions()
    mesh = rules.mesh
    batch_shardable = shape.global_batch % _axsize(mesh, rules.dp) == 0
    dp_size = _axsize(mesh, rules.dp)
    tp_size = _axsize(mesh, rules.tp)

    def ac(x, kind):
        if kind == "hidden_mid" and perf.light_resharding:
            return x    # §Perf: one reshard point per block suffices
        if kind in ("hidden", "hidden_mid", "partial"):
            # "partial": a sub-layer output whose TP contraction just
            # finished — constraining it (rather than the residual sum)
            # lets the partitioner emit reduce-scatter instead of
            # all-reduce + re-slice (§Perf iteration 3).
            if x.ndim != 3:
                return x
            b, s, d = x.shape
            bspec = rules.dp if batch_shardable else None
            sspec = rules.tp if (s % tp_size == 0 and s > 1) else None
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(bspec, sspec, None)))
        if kind == "logits":
            b, s, v = x.shape
            bspec = rules.dp if batch_shardable else None
            vspec = rules.tp if v % tp_size == 0 else None
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(bspec, None, vspec)))
        if kind == "expert":
            # DISABLED after measurement: every forced sharding of the MoE
            # capacity buffer (C over DP, C over TP, E over TP) REGRESSED
            # 3-12x — GSPMD cannot see locality through the global-argsort
            # scatter and falls back to involuntary full rematerialization
            # (replicate + re-partition).  The identified fix is a
            # hierarchical shard_map dispatch (local sort per DP shard +
            # explicit expert all-to-all, exactly the collective the paper
            # optimizes).  Full log: EXPERIMENTS.md §Perf / mixtral+olmoe.
            return x
        return x

    return ac


def cache_specs(cache_tree: Any, cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules) -> Any:
    """PartitionSpecs for KV caches / SSM states (stacked [L, B, ...])."""
    mesh = rules.mesh
    batch_ok = shape.global_batch % _axsize(mesh, rules.dp) == 0

    def spec_for(path: tuple, leaf) -> P:
        names = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        shape_ = leaf.shape
        nd = len(shape_)
        last = names[-1]
        if last == "kpos" or nd <= 2:
            return P(*([None] * nd))
        spec: list = [None] * nd
        # find the batch dim: first dim equal to global_batch after stacking dims
        bdim = None
        for i, s in enumerate(shape_):
            if s == shape.global_batch and i <= 2:
                bdim = i
                break
        if bdim is not None and batch_ok and shape.global_batch > 1:
            spec[bdim] = rules.dp
        elif last in ("k", "v", "c", "k_pe") and nd >= 3:
            # batch==1: context parallelism — shard capacity over DP axes
            cap_dim = (bdim + 1) if bdim is not None else nd - 3
            if shape_[cap_dim] % _axsize(mesh, rules.dp) == 0:
                spec[cap_dim] = rules.dp
        if last in ("k", "v"):
            kv_dim = nd - 2
            cap_dim = nd - 3
            if shape_[kv_dim] % _axsize(mesh, rules.tp) == 0:
                spec[kv_dim] = rules.tp
            elif spec[cap_dim] is None and shape_[cap_dim] % _axsize(mesh, rules.tp) == 0:
                # KV heads can't shard the TP axis (e.g. 8 heads / 16 chips):
                # shard cache CAPACITY over TP instead — without this, a
                # 32k-context cache replicates 16x and blows the 16GB HBM
                # budget (measured 43 GB/device on qwen2-vl decode_32k).
                spec[cap_dim] = rules.tp
        if last == "h" and nd >= 2:  # mamba state [.., B, nh, hs, N]
            if shape_[nd - 3] % _axsize(mesh, rules.tp) == 0:
                spec[nd - 3] = rules.tp
        if last == "S" and nd >= 2:  # rwkv state [.., B, nh, hs, hs]
            if shape_[nd - 3] % _axsize(mesh, rules.tp) == 0:
                spec[nd - 3] = rules.tp
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache_tree)


def batch_specs(batch_tree: Any, cfg: ArchConfig, shape: ShapeConfig, rules: ShardingRules) -> Any:
    mesh = rules.mesh
    batch_ok = shape.global_batch % _axsize(mesh, rules.dp) == 0 and shape.global_batch > 1

    def spec_for(path: tuple, leaf) -> P:
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        spec: list = [None] * nd
        # positions for mrope are [3, B, S]
        bdim = 1 if (nd >= 2 and leaf.shape[0] == 3 and cfg.rope_kind == "mrope"
                     and leaf.shape[1] == shape.global_batch) else 0
        if batch_ok and leaf.shape[bdim] == shape.global_batch:
            spec[bdim] = rules.dp
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, batch_tree)
