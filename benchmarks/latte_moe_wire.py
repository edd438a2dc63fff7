import os
# 512 emulated host devices: this tool only lowers and compiles, and must
# never take an attached accelerator, so it pins JAX to the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

__doc__ = """§Perf confirmation experiment: per-layer collective wire bytes of
GSPMD-transparent MoE dispatch vs the hierarchical latte dispatch
(local pack + explicit expert all-to-all) on the production 16x16 mesh,
olmoe-1b-7b geometry, fwd+bwd of one MoE layer.

    PYTHONPATH=src python -m benchmarks.latte_moe_wire
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.latte_moe import latte_moe_local
from repro.launch.mesh import make_production_mesh
from repro.models import moe as moe_mod
from repro.roofline.hlo_parse import wire_bytes_by_kind


def run(verbose: bool = True):
    mesh = make_production_mesh()
    cfg = get_config("olmoe-1b-7b")
    rng = jax.random.PRNGKey(0)
    p_shape = jax.eval_shape(lambda: moe_mod.init_moe(cfg, rng))
    B, S, D = 256, 4096, cfg.d_model
    x_sh = NamedSharding(mesh, P("data", "model", None))
    x_abs = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)

    def measure(loss_fn, p_sharding):
        g = jax.grad(loss_fn, argnums=(0, 1))
        with mesh:
            c = jax.jit(g, in_shardings=(p_sharding, x_sh)).lower(p_shape, x_abs).compile()
        w = wire_bytes_by_kind(c.as_text())
        return sum(w.values()), w

    def gspmd_loss(p, x):
        out, aux, _ = moe_mod.apply_moe(cfg, p, x)
        return jnp.sum(out.astype(jnp.float32)) + aux

    p_sh = {"router": NamedSharding(mesh, P(None, None)),
            "wg": NamedSharding(mesh, P("model", "data", None)),
            "wu": NamedSharding(mesh, P("model", "data", None)),
            "wd": NamedSharding(mesh, P("model", None, "data"))}
    wb_gspmd, wk1 = measure(gspmd_loss, p_sh)

    def latte_loss(p, x):
        def body(router, wg, wu, wd, xl):
            b, s, d = xl.shape
            out, aux = latte_moe_local(
                cfg, {"router": router, "wg": wg, "wu": wu, "wd": wd},
                xl.reshape(b * s, d), "model")
            return out.reshape(b, s, d), jax.lax.pmean(aux, "model")

        mapped = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(None, None), P("model", None, None),
                                     P("model", None, None), P("model", None, None),
                                     P("data", "model", None)),
                           out_specs=(P("data", "model", None), P()),
                           check_vma=False)
        out, aux = mapped(p["router"], p["wg"], p["wu"], p["wd"], x)
        return jnp.sum(out.astype(jnp.float32)) + aux

    p_sh2 = {"router": NamedSharding(mesh, P(None, None)),
             "wg": NamedSharding(mesh, P("model", None, None)),
             "wu": NamedSharding(mesh, P("model", None, None)),
             "wd": NamedSharding(mesh, P("model", None, None))}
    wb_latte, wk2 = measure(latte_loss, p_sh2)

    ratio = wb_gspmd / max(wb_latte, 1e-9)
    if verbose:
        print(f"GSPMD dispatch: {wb_gspmd/1e9:7.1f} GB/device  {wk1}")
        print(f"latte dispatch: {wb_latte/1e9:7.1f} GB/device  {wk2}")
        print(f"wire reduction: {ratio:.1f}x")
    assert ratio > 10, f"expected >10x reduction, got {ratio:.1f}x"
    return ratio


def main():
    run()


if __name__ == "__main__":
    main()
