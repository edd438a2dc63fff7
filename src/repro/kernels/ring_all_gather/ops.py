"""Jit'd wrapper: shard_map-wrapped ring all-gather usable on any mesh axis."""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from .ring_all_gather import make_ring_all_gather

VARIANTS = ("pcpy", "b2b", "bcst", "bcst_b2b")


@functools.lru_cache(maxsize=None)
def ring_all_gather_fn(mesh, axis_name: str, variant: str = "b2b",
                       interpret: bool = False):
    """The jitted all-gather of a [N, F] array sharded on dim 0 over
    ``axis_name`` (built once per mesh/variant, so repeated calls reuse its
    compilation; ``.lower()`` on it compiles without running)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown ring all-gather variant {variant!r}")
    n = mesh.shape[axis_name]
    fn = make_ring_all_gather(axis_name, n,
                              defer_send_sync=variant in ("b2b", "bcst_b2b"),
                              bidirectional=variant.startswith("bcst"),
                              interpret=interpret)
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(axis_name, None),
                                 out_specs=P(None, None), check_vma=False))


def ring_all_gather(x: jax.Array, mesh, axis_name: str, *,
                    variant: str = "b2b", interpret: bool = False) -> jax.Array:
    """All-gather a [N, F] array sharded on dim 0 over ``axis_name``."""
    return ring_all_gather_fn(mesh, axis_name, variant, interpret)(x)
