"""Jit'd wrapper for the Pallas all-to-all kernel."""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from .ring_all_to_all import make_all_to_all

VARIANTS = ("per_round", "b2b")


@functools.lru_cache(maxsize=None)
def pallas_all_to_all_fn(mesh, axis_name: str, variant: str = "b2b",
                         interpret: bool = False):
    """The jitted all-to-all of an [n, n, chunk, F] array (dim0 = device,
    dim1 = destination chunk), built once per mesh/variant."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown all-to-all variant {variant!r}")
    n = mesh.shape[axis_name]
    fn = make_all_to_all(axis_name, n, b2b=(variant == "b2b"), interpret=interpret)

    def local(xl):
        return fn(xl[0])[None]

    spec = P(axis_name, None, None, None)
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def pallas_all_to_all(
    x: jax.Array,          # [n, n, chunk, F]: dim0 = device, dim1 = dest chunk
    mesh,
    axis_name: str,
    *,
    variant: str = "b2b",   # b2b | per_round
    interpret: bool = False,
) -> jax.Array:
    n = mesh.shape[axis_name]
    assert x.shape[0] == n and x.shape[1] == n
    return pallas_all_to_all_fn(mesh, axis_name, variant, interpret)(x)
