"""Quickstart: the paper's DMA collective model + dispatch in 60 seconds.

Runs the calibrated MI300X engine model over the size spectrum, shows the
phase breakdown of a single DMA copy (Fig. 7), the best-variant dispatch
(Tables 2/3), and validates a latte collective against the XLA reference on
the local device mesh.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.dma import (
    allgather_schedule, alltoall_schedule, mi300x_platform, paper_dispatch,
    rccl_aa_calibration, rccl_ag_calibration, simulate, single_copy_breakdown,
)
from repro.core.dma.rccl_model import rccl_collective_latency
from repro.core import collectives as coll
from repro.launch.mesh import make_mesh

KB, MB = 1024, 1024 * 1024


def main():
    topo = mi300x_platform()

    print("== Fig.7: phases of a single DMA copy ==")
    for size in (4 * KB, 64 * KB, 1 * MB, 2 * MB):
        b = single_copy_breakdown(size, topo)
        print(f"  {size >> 10:5d}KB total={b.total*1e6:6.1f}us "
              f"copy={b.copy*1e6:5.1f}us non-copy={b.noncopy_fraction:5.1%}")

    print("\n== DMA all-gather vs RCCL across sizes (paper Fig. 13) ==")
    for size in (4 * KB, 256 * KB, 4 * MB, 256 * MB):
        variant = paper_dispatch("all_gather", size)
        dma = simulate(allgather_schedule(topo, size, variant), topo).latency
        rccl = rccl_collective_latency(topo, size, rccl_ag_calibration())
        print(f"  {size >> 10:7d}KB best={variant:15s} dma={dma*1e6:9.1f}us "
              f"rccl={rccl*1e6:9.1f}us speedup={rccl/dma:5.2f}x")

    print("\n== latte collective == reference on the local mesh ==")
    n = len(jax.devices())
    mesh = make_mesh((n,), ("x",))
    x = jax.random.normal(jax.random.PRNGKey(0), (n * 4, 32), jnp.float32)
    ring = jax.jit(jax.shard_map(lambda a: coll.ring_all_gather(a, "x").reshape(-1, a.shape[-1]),
                             mesh=mesh, in_specs=P("x", None),
                             out_specs=P(None, None), check_vma=False))
    ok = np.allclose(np.asarray(ring(x)), np.asarray(x))
    print(f"  ring all-gather matches reference: {ok}")
    assert ok


if __name__ == "__main__":
    main()
