"""Host-memory KV store for context caching (paper §5.3).

The cache state of finished/parked contexts is SAVED to host memory (numpy
— the "CPU DRAM tier") in paged blocks and FETCHED back on a cache hit
instead of re-running prefill.  A context's state is the model's own
leaves, by name: ``k`` and ``v``, or multi-head latent attention's ``c``
and ``k_pe``.

A fetch is the paper's batched ``b2b`` transfer: a context's leaves move
to the device in ONE upload with one sync (``hipMemcpyBatchAsync`` routed
to one engine, §5.3.1), each read in the order its bytes lie in memory
(for ``to_blocks``'s views of a C-ordered batch, layer-major), and one
program on the device puts the axes back.  It returns device arrays, each
leaf ``[L, 1, n_blocks * bt, ...]``: the batch of one that was saved,
layer-major as the decode cache is.  Nothing is copied back to the host:
each saved byte crosses the host link once.  A fetch opens the profiler
span ``serve.kv.fetch`` (with the context ``key``) and, inside it,
``serve.kv.fetch.h2d`` around the upload, which waits for it; it adds its
bytes and its tokens to ``repro.serve.counters``.

The per-block and kernel-gather alternatives the paper compares against
are modeled, not served: ``core/serving_model.py`` and the DMA
simulator's ``kv_fetch_schedule``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax

from . import counters
from .kvcache import layer_major


def _in_memory_order(a: np.ndarray) -> tuple[np.ndarray, tuple[int, ...]]:
    """A view of ``a`` whose bytes, read in index order, come in the order
    they lie in memory: ``a``'s axes sorted from the largest stride to the
    smallest, each run of axes that lie contiguously merged into one.
    Returns the view and the order of ``a``'s axes in it."""
    order = tuple(sorted(range(a.ndim), key=lambda i: -a.strides[i]))
    t = a.transpose(order)
    shape = [t.shape[0]]
    for n, outer, inner in zip(t.shape[1:], t.strides, t.strides[1:]):
        if outer == inner * n:
            shape[-1] *= n
        else:
            shape.append(n)
    return t.reshape(shape), order


@functools.partial(jax.jit, static_argnames=("shapes", "orders"))
def _layer_major(arrays: tuple, shapes: tuple, orders: tuple):
    """Device blocks, leaf i of ``shapes[i]`` [n_blocks, bt, L, ...], as
    uploaded with their axes in ``orders[i]`` (and merged where they lay
    contiguously) -> layer-major [L, 1, n_blocks * bt, ...]."""
    out = []
    for x, shape, order in zip(arrays, shapes, orders):
        x = x.reshape([shape[i] for i in order])
        out.append(layer_major(x.transpose(tuple(int(i) for i in np.argsort(order))))[:, None])
    return tuple(out)


class HostKVStore:
    def __init__(self):
        self._store: dict[str, tuple[dict[str, np.ndarray], int]] = {}

    # ------------------------------------------------------------- save ----
    def save(self, key: str, blocks: dict[str, np.ndarray], n_tokens: int) -> None:
        """Keep a context's state: each leaf's blocks [n_blocks, bt, L, ...]."""
        self._store[key] = ({name: np.asarray(b) for name, b in blocks.items()}, n_tokens)

    def __contains__(self, key: str) -> bool:
        return key in self._store

    def saved(self, key: str) -> dict[str, np.ndarray]:
        """The host blocks saved under ``key``, by leaf."""
        return self._store[key][0]

    def tokens_for(self, key: str) -> int:
        return self._store[key][1]

    # ------------------------------------------------------------ fetch ----
    def fetch(self, key: str, backend: str = "b2b") -> dict[str, jax.Array]:
        """The state saved under ``key`` on the device, by leaf."""
        # ``backend`` stays only because the benchmark's timed store passes it on
        if backend != "b2b":
            raise ValueError(f"the store fetches with b2b only, not {backend!r}")
        blocks, n_tokens = self._store[key]
        with jax.profiler.TraceAnnotation("serve.kv.fetch", key=key):
            # every leaf in one upload and one sync, each read in the order
            # of its memory; the device puts the axes back
            views = [_in_memory_order(b) for b in blocks.values()]
            with jax.profiler.TraceAnnotation("serve.kv.fetch.h2d"):
                up = jax.block_until_ready(jax.device_put(tuple(v for v, _ in views)))
            out = _layer_major(up, tuple(b.shape for b in blocks.values()),
                               tuple(o for _, o in views))
        counters.add("kv.fetch.to_device_bytes", sum(a.nbytes for a in up))
        counters.add("kv.fetch.tokens", n_tokens)
        return dict(zip(blocks, out))
