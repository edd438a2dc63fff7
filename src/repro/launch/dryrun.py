import os
# 512 emulated host devices: this tool only lowers and compiles, and must
# never take an attached accelerator, so it pins JAX to the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

__doc__ = """Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) and for both production meshes
(single-pod 16x16 = 256 chips; multi-pod 2x16x16 = 512 chips), lower and
compile the appropriate step function (train_step / prefill / serve_step)
with ShapeDtypeStruct inputs — no allocation — and record
``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs/bytes for the
roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_shape
from repro.models import build_model
from repro.models.transformer import Model
from repro.sharding.rules import (
    PerfOptions,
    ShardingRules,
    batch_specs,
    cache_specs,
    infer_param_specs,
    make_activation_constrainer,
)
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamWConfig, init_opt_state
from .compile_cache import enable_compile_cache
from .input_specs import input_specs, skip_reason
from .mesh import dp_axes, make_production_mesh

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b")


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    status: str                      # ok | skipped | failed
    reason: str = ""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_counts: dict | None = None
    memory_analysis: str = ""
    peak_bytes_per_device: float | None = None
    argument_bytes_per_device: float | None = None
    compile_seconds: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_step(arch_id: str, shape_id: str, mesh, *, cfg=None, unroll: bool = False,
               perf: PerfOptions | None = None):
    """Returns (fn, abstract_args, in_shardings, out_shardings) or a skip reason.

    ``cfg`` overrides the registered config (the roofline costing pass lowers
    depth-reduced variants); ``unroll`` replaces the layer scan with a python
    unroll so XLA cost analysis counts every layer.
    """
    cfg = cfg or get_config(arch_id)
    shape = get_shape(shape_id)
    reason = skip_reason(cfg, shape)
    if reason:
        return None, reason
    model = build_model(cfg)
    perf = perf or PerfOptions()
    rules = ShardingRules(mesh=mesh, dp=dp_axes(mesh))
    ac = make_activation_constrainer(cfg, shape, rules, perf)

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_specs = infer_param_specs(params_shape, cfg, rules)
    param_sh = _named(mesh, param_specs)

    specs = input_specs(cfg, shape, model)
    batch_sp = batch_specs(specs["batch"], cfg, shape, rules)
    batch_sh = _named(mesh, batch_sp)

    if shape.mode == "train":
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        opt_specs = {"m": param_specs, "v": param_specs, "step": P()}
        state_shape = {"params": params_shape, "opt": opt_shape}
        state_sh = {"params": param_sh, "opt": _named(mesh, opt_specs)}
        fn = make_train_step(model, AdamWConfig(), ac, unroll=unroll,
                             cast_params=perf.cast_params_bf16)
        metrics_sh = {"grad_norm": NamedSharding(mesh, P()),
                      "lr": NamedSharding(mesh, P()),
                      "loss": NamedSharding(mesh, P())}
        return (fn, (state_shape, specs["batch"]), (state_sh, batch_sh),
                (state_sh, metrics_sh)), None

    if shape.mode == "prefill":
        def fn(params, batch):
            logits, aux, caches = model.forward(params, batch, ac=ac,
                                                want_cache=True, remat=False,
                                                unroll=unroll)
            return logits, caches

        return (fn, (params_shape, specs["batch"]), (param_sh, batch_sh), None), None

    # decode (serve_step): ONE new token against the full-capacity cache.
    caches_shape = specs["caches"]
    cache_sp = cache_specs(caches_shape, cfg, shape, rules)
    cache_sh = _named(mesh, cache_sp)

    def fn(params, batch, caches):
        return model.decode_step(params, batch, caches, ac=ac, unroll=unroll)

    out_sh = (None, cache_sh)   # logits: let GSPMD choose; caches stay put
    return (fn, (params_shape, specs["batch"], caches_shape),
            (param_sh, batch_sh, cache_sh), out_sh), None


def run_one(arch_id: str, shape_id: str, *, multi_pod: bool = False,
            verbose: bool = True) -> DryrunResult:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    t0 = time.perf_counter()
    try:
        built, reason = build_step(arch_id, shape_id, mesh)
        if built is None:
            return DryrunResult(arch_id, shape_id, mesh_name, "skipped", reason=reason)
        fn, args, in_sh, out_sh = built
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        ca = compiled.cost_analysis()
        mem = compiled.memory_analysis()
        counts: dict[str, int] = {}
        try:
            text = compiled.as_text()
            for m in COLLECTIVE_RE.finditer(text):
                counts[m.group(1)] = counts.get(m.group(1), 0) + 1
        except Exception:
            counts = {}
        peak = getattr(mem, "temp_size_in_bytes", None)
        argbytes = getattr(mem, "argument_size_in_bytes", None)
        res = DryrunResult(
            arch_id, shape_id, mesh_name, "ok",
            flops=float(ca.get("flops", 0.0)),
            bytes_accessed=float(ca.get("bytes accessed", 0.0)),
            collective_counts=counts,
            memory_analysis=str(mem),
            peak_bytes_per_device=float(peak) if peak is not None else None,
            argument_bytes_per_device=float(argbytes) if argbytes is not None else None,
            compile_seconds=dt,
        )
        if verbose:
            print(f"[ok] {arch_id} x {shape_id} x {mesh_name}: "
                  f"flops={res.flops:.3e} bytes={res.bytes_accessed:.3e} "
                  f"collectives={counts} compile={dt:.1f}s")
            print(f"     memory_analysis: {mem}")
        return res
    except Exception as e:
        if verbose:
            traceback.print_exc()
        return DryrunResult(arch_id, shape_id, mesh_name, "failed",
                            reason=f"{type(e).__name__}: {e}",
                            compile_seconds=time.perf_counter() - t0)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    pairs = ([(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
             if args.all else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for mp in meshes:
        for a, s in pairs:
            if a is None or s is None:
                raise SystemExit("need --arch and --shape (or --all)")
            results.append(run_one(a, s, multi_pod=mp))
    n_fail = sum(r.status == "failed" for r in results)
    n_skip = sum(r.status == "skipped" for r in results)
    print(f"\n== dry-run summary: {len(results)} runs, {n_fail} failed, {n_skip} skipped ==")
    for r in results:
        if r.status != "ok":
            print(f"  [{r.status}] {r.arch} x {r.shape} x {r.mesh}: {r.reason}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump([r.to_json() for r in results], f, indent=1)
        print(f"wrote {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
