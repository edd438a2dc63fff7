"""Exact cost accounting for scanned programs.

XLA ``cost_analysis`` counts a while/scan body ONCE regardless of trip
count (measured: a 10-step scan of matmuls reports 1 matmul's flops), so
full-depth lowerings undercount by ~L.  We therefore lower depth-reduced
UNROLLED variants of each model (1 scan-unit and 2 scan-units per scan
stack) with dense (unchunked) attention and difference them:

    C(k units) = C_base + k * C_body    =>    C_body = C(2) - C(1)
    Total      = C_base + trip * C_body (per scan stack)

The SSM time scans (rwkv/mamba recurrence over seq_len steps) cannot be
unrolled at 32k steps; their per-step cost is tiny and closed-form, so an
analytic correction term ``(T-1) * step_cost * n_layers`` is added
(documented in EXPERIMENTS.md §Roofline methodology).

All metrics (flops, bytes, per-collective wire bytes) are PER-DEVICE (the
partitioned module's shapes are per-device).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.configs import get_config, get_shape
from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.roofline.hlo_parse import wire_bytes_by_kind


@dataclasses.dataclass
class CostVector:
    flops: float = 0.0
    bytes: float = 0.0
    wire: dict | None = None

    def __post_init__(self):
        self.wire = dict(self.wire or {})

    @property
    def wire_total(self) -> float:
        return sum(self.wire.values())

    def __sub__(self, o: "CostVector") -> "CostVector":
        keys = set(self.wire) | set(o.wire)
        return CostVector(self.flops - o.flops, self.bytes - o.bytes,
                          {k: self.wire.get(k, 0) - o.wire.get(k, 0) for k in keys})

    def __add__(self, o: "CostVector") -> "CostVector":
        keys = set(self.wire) | set(o.wire)
        return CostVector(self.flops + o.flops, self.bytes + o.bytes,
                          {k: self.wire.get(k, 0) + o.wire.get(k, 0) for k in keys})

    def scaled(self, f: float) -> "CostVector":
        return CostVector(self.flops * f, self.bytes * f,
                          {k: v * f for k, v in self.wire.items()})

    def clamped(self) -> "CostVector":
        return CostVector(max(self.flops, 0.0), max(self.bytes, 0.0),
                          {k: max(v, 0.0) for k, v in self.wire.items()})


def _scan_axes(cfg: ArchConfig) -> list[tuple[str, int, Callable[[ArchConfig, int], ArchConfig]]]:
    """(name, full_trip, cfg_builder(k_units)) for every scan stack."""
    axes = []
    per_unit = len(cfg.layer_pattern) if cfg.layer_pattern else 1
    if cfg.hybrid:
        per_unit = cfg.hybrid.attn_every
    trip = cfg.n_layers // per_unit

    def set_layers(c: ArchConfig, k: int) -> ArchConfig:
        return dataclasses.replace(c, n_layers=k * per_unit)

    axes.append(("layers", trip, set_layers))
    if cfg.encdec:
        def set_enc(c: ArchConfig, k: int) -> ArchConfig:
            return dataclasses.replace(
                c, encdec=dataclasses.replace(c.encdec, n_encoder_layers=k))
        axes.append(("enc", cfg.encdec.n_encoder_layers, set_enc))
    return axes


def _measure(arch_id: str, shape_id: str, mesh, cfg: ArchConfig, perf=None) -> CostVector:
    from repro.launch.dryrun import build_step
    with attn_mod.dense_attention_for_costing():
        built, reason = build_step(arch_id, shape_id, mesh, cfg=cfg, unroll=True, perf=perf)
        if built is None:
            raise RuntimeError(f"skipped: {reason}")
        fn, args, in_sh, out_sh = built
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args).compile()
    ca = compiled.cost_analysis()
    wire = wire_bytes_by_kind(compiled.as_text())
    return CostVector(float(ca.get("flops", 0.0)),
                      float(ca.get("bytes accessed", 0.0)), wire)


def _ssm_correction(cfg: ArchConfig, shape, dp_size: int) -> CostVector:
    """Analytic (T-1)-step correction for time-scan recurrences (per device)."""
    if shape.mode == "decode" or not cfg.ssm:
        return CostVector()
    T = shape.seq_len
    b_loc = max(shape.global_batch // dp_size, 1)
    if cfg.ssm.kind == "rwkv6":
        hs = cfg.ssm.head_size
        step_flops = 6.0 * b_loc * cfg.d_model * hs
        state_bytes = 4.0 * b_loc * cfg.d_model * hs      # f32 S matrix
        n_scans = cfg.n_layers
    else:  # mamba2
        d_in = cfg.ssm.expand * cfg.d_model
        N = cfg.ssm.state_size
        step_flops = 7.0 * b_loc * d_in * N
        state_bytes = 4.0 * b_loc * d_in * N
        n_scans = cfg.n_layers
    per_layer = CostVector(step_flops, 3.0 * state_bytes, {})
    return per_layer.scaled((T - 1) * n_scans)


def total_cost(arch_id: str, shape_id: str, mesh, *, dp_size: int, perf=None) -> dict:
    """Per-device totals with exact scan scaling.  Returns dict with
    CostVector 'total' plus the measured points for the record."""
    cfg = get_config(arch_id)
    shape = get_shape(shape_id)
    axes = _scan_axes(cfg)

    base_cfg = cfg
    for name, trip, build in axes:
        base_cfg = build(base_cfg, 1)
    c0 = _measure(arch_id, shape_id, mesh, base_cfg, perf)

    total = c0
    bodies = {}
    for i, (name, trip, build) in enumerate(axes):
        cfg_i = base_cfg
        for j, (n2, t2, b2) in enumerate(axes):
            cfg_i = b2(cfg_i, 2 if j == i else 1)
        ci = _measure(arch_id, shape_id, mesh, cfg_i, perf)
        body = (ci - c0).clamped()
        bodies[name] = body
        total = total + body.scaled(trip - 1)

    corr = _ssm_correction(cfg, shape, dp_size)
    total = total + corr
    return {
        "total": total,
        "base": c0,
        "bodies": bodies,
        "ssm_correction": corr,
        "trips": {name: trip for name, trip, _ in axes},
    }
