"""CPU-only demo: run the Pallas remote-DMA collective kernels (ring
all-gather with pcpy/b2b/bcst sync variants; swap/b2b all-to-all) on 8
emulated CPU devices in Pallas TPU interpret mode and validate against the
pure-jnp oracles.

Re-executes itself with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax locks the platform
and device count at first init), so it never takes an attached TPU.  On a
TPU host, ``python chip_smoke.py --chips 4`` runs the same kernels compiled.

    PYTHONPATH=src python examples/pallas_collectives.py
"""
import os
import subprocess
import sys

N = 8

if os.environ.get("_REPRO_PALLAS_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"
    env["JAX_PLATFORMS"] = "cpu"
    env["_REPRO_PALLAS_CHILD"] = "1"
    raise SystemExit(subprocess.call([sys.executable, os.path.abspath(__file__)], env=env))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.launch.mesh import make_mesh                            # noqa: E402
from repro.kernels.ring_all_gather.ops import ring_all_gather      # noqa: E402
from repro.kernels.ring_all_gather.ref import all_gather_ref       # noqa: E402
from repro.kernels.ring_all_to_all.ops import pallas_all_to_all    # noqa: E402
from repro.kernels.ring_all_to_all.ref import all_to_all_ref       # noqa: E402


def main():
    assert len(jax.devices()) == N
    mesh = make_mesh((N,), ("x",))
    x = jax.random.normal(jax.random.PRNGKey(0), (N * 8, 128), jnp.float32)
    print("== Pallas ring all-gather (remote DMA) ==")
    for variant in ("pcpy", "b2b", "bcst", "bcst_b2b"):
        y = ring_all_gather(x, mesh, "x", variant=variant, interpret=True)
        ok = np.allclose(np.asarray(y), np.asarray(all_gather_ref(x, N)))
        print(f"  {variant:9s}: {'OK' if ok else 'MISMATCH'}")
        assert ok

    xa = jax.random.normal(jax.random.PRNGKey(1), (N, N, 4, 128), jnp.float32)
    print("== Pallas all-to-all (swap / b2b) ==")
    for variant in ("per_round", "b2b"):
        y = pallas_all_to_all(xa, mesh, "x", variant=variant, interpret=True)
        ok = np.allclose(np.asarray(y), np.asarray(all_to_all_ref(xa)))
        print(f"  {variant:9s}: {'OK' if ok else 'MISMATCH'}")
        assert ok
    print("all kernel variants validated against oracles")


if __name__ == "__main__":
    main()
