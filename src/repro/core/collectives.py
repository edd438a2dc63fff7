"""JAX-level latte collectives: shard_map/ppermute implementations of the
paper's schedule shapes, plus a reference (XLA one-shot) backend.

These are the *jit-composable* renderings used inside model code (the Pallas
kernels in ``repro/kernels`` are the explicit-DMA renderings).  Mapping:

* ``reference``   — ``jax.lax.all_gather`` / ``all_to_all`` (XLA chooses;
                    the analogue of the tuned CU library).
* ``ring``        — unidirectional ppermute ring: one chained transfer in
                    flight per step = the b2b single-engine queue.
* ``bidir_ring``  — every step forwards two chunks (to left AND right): one
                    local read feeding two destinations = bcst; halves steps.
* ``pairwise``    — XOR-partner exchange rounds for all-to-all = swap.

All functions are called INSIDE shard_map with ``axis_name`` bound.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """b2b analogue.  x: local shard -> [n, *x.shape] gathered (stacked)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks = [x]
    send = x
    for _ in range(n - 1):
        send = jax.lax.ppermute(send, axis_name, perm)
        chunks.append(send)
    stacked = jnp.stack(chunks)              # stacked[k] = x from device (idx-k)%n
    order = jnp.mod(idx - jnp.arange(n), n)  # out[j] = stacked[(idx-j)%n]
    return jnp.take(stacked, order, axis=0)


def bidir_ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """bcst analogue: both directions each step, ceil((n-1)/2) steps."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]
    n_fwd = (n - 1 + 1) // 2
    n_bwd = (n - 1) - n_fwd
    out = {0: x}
    send_f, send_b = x, x
    for k in range(1, n_fwd + 1):
        send_f = jax.lax.ppermute(send_f, axis_name, fwd_perm)
        out[k] = send_f                      # chunk from device idx-k (offset k)
        if k <= n_bwd:
            send_b = jax.lax.ppermute(send_b, axis_name, bwd_perm)
            out[(n - k) % n] = send_b        # chunk from device idx+k
    stacked = jnp.stack([out[o] for o in range(n)])   # stacked[o] = x_{(idx-o)%n}
    order = jnp.mod(idx - jnp.arange(n), n)
    return jnp.take(stacked, order, axis=0)


def pairwise_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """swap analogue.  x: [n, ...] local chunks -> out[j] = x_j[idx].

    Round r exchanges chunk x[idx^r] with partner idx^r (n power of two), a
    symmetric in-place pairwise swap; falls back to rotation pairing else.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    assert x.shape[0] == n
    power_of_two = (n & (n - 1)) == 0
    out = jnp.zeros_like(x)
    # own chunk stays
    own = jnp.take(x, idx, axis=0)
    out = jax.lax.dynamic_update_index_in_dim(out, own, idx, 0)
    for r in range(1, n):
        if power_of_two:
            perm = [(i, i ^ r) for i in range(n)]
            partner = idx ^ r
        else:
            perm = [(i, (i + r) % n) for i in range(n)]
            partner = jnp.mod(idx + r, n)
        send = jnp.take(x, partner, axis=0)
        recv = jax.lax.ppermute(send, axis_name, perm)
        src = jnp.mod(idx - r, n) if not power_of_two else partner
        out = jax.lax.dynamic_update_index_in_dim(out, recv, src, 0)
    return out


def reference_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    return jax.lax.all_gather(x, axis_name)


def reference_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring reduce-scatter (DESIGN.md §10).  x: [n, ...] per-device addend
    chunks -> this device's fully reduced chunk (``sum_e x_e[idx]``).

    The partial destined for device *o* starts at its successor ``o+1``,
    travels the ring forward for n-1 hops, and each visited device folds
    in its own contribution — at step *r* device *i* is holding (and
    sending) the partial destined for ``(i - r - 1) % n``.  This is the
    ppermute rendering of the ``ring_rs`` DMA schedule.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = jnp.take(x, jnp.mod(idx - 1, n), axis=0)
    for r in range(n - 1):
        recv = jax.lax.ppermute(acc, axis_name, perm)
        acc = recv + jnp.take(x, jnp.mod(idx - r - 2, n), axis=0)
    return acc


def reference_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """XLA analogue: full psum, then keep this device's chunk."""
    return jnp.take(jax.lax.psum(x, axis_name),
                    jax.lax.axis_index(axis_name), axis=0)


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce as ring reduce-scatter + ring all-gather (DESIGN.md §10).
    x: [n, ...] chunks -> [n, ...] with out[j] = ``sum_e x_e[j]``."""
    return ring_all_gather(ring_reduce_scatter(x, axis_name), axis_name)


def reference_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    return jax.lax.psum(x, axis_name)
