"""TPU-native adaptation benchmark: latte shard_map collectives vs XLA
reference on the local mesh — correctness + wall-clock per call, plus the
modeled step-count reduction of each schedule (the structural win that maps
to the paper's command/sync reduction)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import collectives as coll
from repro.launch.mesh import make_mesh
from .common import ClaimChecker, time_us


def run(verbose: bool = True):
    n = len(jax.devices())
    mesh = make_mesh((n,), ("x",))
    x = jax.random.normal(jax.random.PRNGKey(0), (n * 8, 128), jnp.float32)

    def wrap(fn):
        return jax.jit(jax.shard_map(lambda a: fn(a, "x"), mesh=mesh,
                                 in_specs=P("x", None), out_specs=P(None, None, None),
                                 check_vma=False))

    impls = {
        "reference": wrap(coll.reference_all_gather),
        "ring(b2b)": wrap(coll.ring_all_gather),
        "bidir(bcst)": wrap(coll.bidir_ring_all_gather),
    }
    ref = np.asarray(impls["reference"](x))
    rows = []
    cc = ClaimChecker("tpu_collectives")
    for name, fn in impls.items():
        y = np.asarray(fn(x))
        ok = np.allclose(y, ref)
        us = time_us(lambda: jax.block_until_ready(fn(x)), reps=50, warmup=5)
        rows.append((name, ok, us))
        cc.check(f"{name} correct", float(ok), 1, 1, 1)
    if verbose:
        for name, ok, us in rows:
            print(f"  {name:12s} correct={ok} {us:8.1f} us/call (local CPU mesh)")
        # structural accounting (steps ~ sync rounds on the critical path)
        steps_ring = n - 1
        steps_bidir = (n - 1 + 1) // 2
        if steps_bidir:
            print(f"  ring steps={steps_ring}, bidirectional steps={steps_bidir} "
                  f"({steps_ring/steps_bidir:.1f}x fewer sync rounds — the bcst analogue)")
        else:
            print("  single-device mesh: run under XLA_FLAGS="
                  "--xla_force_host_platform_device_count=N for ring timings")
    return cc, rows


def main():
    cc, _ = run()
    return 0 if cc.report() else 1


if __name__ == "__main__":
    raise SystemExit(main())
