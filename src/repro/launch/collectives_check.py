"""Every collective implementation in the repo against XLA's, bit for bit,
on a 1-D device mesh.

Implementations (all inside ``shard_map`` over one mesh axis):

* the Pallas remote-DMA kernels: ring all-gather pcpy / b2b / bcst /
  bcst_b2b and all-to-all per_round / b2b;
* the ppermute rings of ``core/collectives.py``: ring and bidirectional
  all-gather, pairwise all-to-all, ring reduce-scatter and all-reduce;
* ``CommBackend('latte')`` dispatching at the mesh's size.

Each is compared with ``jax.lax.all_gather`` / ``all_to_all`` / ``psum`` on
every device's own output buffer, so a result that only device 0 got right
fails.  Reduction inputs are small integers, whose sums are exact in any
order, so the reductions are compared bit for bit too.  The Pallas kernels
run compiled on a TPU and through the Pallas TPU interpreter on the CPU.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as coll
from repro.core.backend import CommBackend
from repro.kernels.ring_all_gather.ops import VARIANTS as AG_VARIANTS
from repro.kernels.ring_all_gather.ops import ring_all_gather_fn
from repro.kernels.ring_all_to_all.ops import VARIANTS as AA_VARIANTS
from repro.kernels.ring_all_to_all.ops import pallas_all_to_all_fn

KB, MB = 1024, 1024 * 1024
LANES = 128
# (per-device shard bytes, dtype) pairs the check sweeps by default.
DEFAULT_CASES = ((4 * KB, jnp.float32), (64 * KB, jnp.float32),
                 (1 * MB, jnp.float32), (16 * MB, jnp.float32),
                 (64 * MB, jnp.float32), (4 * KB, jnp.bfloat16),
                 (1 * MB, jnp.bfloat16))


def _mapped(mesh, fn, in_spec, out_spec):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec, check_vma=False))


@functools.lru_cache(maxsize=None)
def _mismatch_fn(mesh, axis, spec):
    """Per-device count of elements whose bits differ between two arrays of
    the same sharding, computed where each buffer lives."""
    def count(a, b):
        bits = jnp.uint16 if a.dtype.itemsize == 2 else jnp.uint32
        diff = (jax.lax.bitcast_convert_type(a, bits)
                != jax.lax.bitcast_convert_type(b, bits))
        return jnp.sum(diff, dtype=jnp.int32)[None]
    return _mapped(mesh, count, (spec, spec), P(axis))


def _implementations(mesh, axis: str, interpret: bool):
    """{collective: (out_spec, {name: jitted fn})}; the "xla" entry of each
    is the reference."""
    n = mesh.shape[axis]
    latte = CommBackend("latte", axis_devices=n, allow_stale_tables=True)
    row, stacked = P(axis, None), P(axis, None, None, None)
    rep2, rep3 = P(None, None), P(None, None, None)

    def ag(fn):     # [rows, F] shard -> [n*rows, F] replicated
        return _mapped(mesh, lambda a: fn(a, axis).reshape(-1, a.shape[-1]),
                       row, rep2)

    def per_dev(fn, out):   # [1, n, c, F] shard -> fn of the [n, c, F] chunks
        return _mapped(mesh, lambda a: fn(a[0], axis)[None], stacked, out)

    def ar(fn):     # [1, n, c, F] shard -> [n, c, F] replicated
        return _mapped(mesh, lambda a: fn(a[0], axis), stacked, rep3)

    all_gather = {
        "xla": ag(lambda a, ax: jax.lax.all_gather(a, ax, tiled=True)),
        **{f"pallas_{v}": ring_all_gather_fn(mesh, axis, v, interpret)
           for v in AG_VARIANTS},
        "ppermute_ring": ag(coll.ring_all_gather),
        "ppermute_bidir": ag(coll.bidir_ring_all_gather),
        "latte": ag(latte.all_gather),
    }
    all_to_all = {
        "xla": per_dev(coll.reference_all_to_all, stacked),
        **{f"pallas_{v}": pallas_all_to_all_fn(mesh, axis, v, interpret)
           for v in AA_VARIANTS},
        "ppermute_pairwise": per_dev(coll.pairwise_all_to_all, stacked),
        "latte": per_dev(latte.all_to_all, stacked),
    }
    reduce_scatter = {
        "xla": per_dev(coll.reference_reduce_scatter, P(axis, None, None)),
        "ppermute_ring": per_dev(coll.ring_reduce_scatter, P(axis, None, None)),
        "latte": per_dev(latte.reduce_scatter, P(axis, None, None)),
    }
    all_reduce = {
        "xla": ar(coll.reference_all_reduce),
        "ppermute_ring": ar(coll.ring_all_reduce),
        "latte": ar(latte.all_reduce),
    }
    return {"all_gather": (rep2, all_gather),
            "all_to_all": (stacked, all_to_all),
            "reduce_scatter": (P(axis, None, None), reduce_scatter),
            "all_reduce": (rep3, all_reduce)}


def _inputs(mesh, axis: str, shard_bytes: int, dtype, seed: int):
    """Device-resident inputs with ``shard_bytes`` per device: random values
    for the copies, small integers for the reductions."""
    n = mesh.shape[axis]
    rows = shard_bytes // (LANES * jnp.dtype(dtype).itemsize)
    assert rows % n == 0, (shard_bytes, dtype, n)
    flat = NamedSharding(mesh, P(axis, None))
    chunks = NamedSharding(mesh, P(axis, None, None, None))
    key = jax.random.PRNGKey(seed)

    @functools.partial(jax.jit, out_shardings=(flat, chunks, chunks))
    def make():
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (n * rows, LANES), dtype)
        xa = jax.random.normal(k2, (n, n, rows // n, LANES), dtype)
        xr = jnp.round(jax.random.uniform(k3, xa.shape, jnp.float32, -16, 16))
        return x, xa, xr.astype(dtype)

    x, xa, xr = make()
    return {"all_gather": x, "all_to_all": xa, "reduce_scatter": xr,
            "all_reduce": xr}


def run(mesh, axis: str = "x", cases=DEFAULT_CASES, *, seed: int = 0,
        log=print) -> int:
    """Check every implementation at every (shard bytes, dtype) case.
    Returns the number of comparisons; raises AssertionError on a mismatch."""
    interpret = jax.default_backend() == "cpu"
    impls = _implementations(mesh, axis, interpret)
    n_checks = 0
    for shard_bytes, dtype in cases:
        inputs = _inputs(mesh, axis, shard_bytes, dtype, seed)
        for collective, (out_spec, fns) in impls.items():
            x = inputs[collective]
            ref = fns["xla"](x)
            if shard_bytes <= 1 * MB and collective in ("all_gather", "all_to_all"):
                expect = np.asarray(x) if collective == "all_gather" \
                    else np.swapaxes(np.asarray(x), 0, 1)
                for s in ref.addressable_shards:
                    assert np.array_equal(np.asarray(s.data), expect[s.index]), \
                        f"XLA {collective} disagrees with the host on {s.device}"
            mismatch = _mismatch_fn(mesh, axis, out_spec)
            names = []
            for name, fn in fns.items():
                if name == "xla":
                    continue
                bad = np.asarray(mismatch(fn(x), ref))
                assert not bad.any(), (
                    f"{collective}/{name} at {shard_bytes} B/device "
                    f"{jnp.dtype(dtype).name}: elements differing from XLA "
                    f"per device {bad.tolist()}")
                names.append(name)
                n_checks += 1
            log(f"[collectives] {collective:14s} {shard_bytes:>9d} B/device "
                f"{jnp.dtype(dtype).name:8s} bit-identical to XLA on all "
                f"{mesh.shape[axis]} devices: {' '.join(names)}")
    return n_checks
