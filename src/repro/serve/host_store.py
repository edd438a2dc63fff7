"""Host-memory KV store for context caching (paper §5.3).

The cache state of finished/parked contexts is SAVED to host memory (numpy
— the "CPU DRAM tier") in paged blocks and FETCHED back on a cache hit
instead of re-running prefill.  A context's state is the model's own
leaves, by name: ``k`` and ``v``, or multi-head latent attention's ``c``
and ``k_pe``.  Three fetch backends mirror the paper's comparison:

* ``pcpy``   — one transfer per block (baseline vLLM: one hipMemcpyAsync
               per dispersed block; here one ``jax.device_put`` each).
* ``b2b``    — ONE batched transfer: a context's leaves move with
               one launch + one sync (``hipMemcpyBatchAsync`` routed to one
               engine, §5.3.1); fan-out above the 4MB threshold.
* ``opt_b2b``— the b2b data path under the name
               ``CommBackend.kv_fetch_plan`` gives the optimized command
               stream (DESIGN.md §7/§8: batched submission + fused
               write+signal).  Only the DMA simulator
               (``repro.core.dma.kv_fetch_schedule``) tells the two apart.
* ``kernel`` — the whole pool region moves once; a Pallas gather kernel
               (repro/kernels/paged_kv_gather) reassembles dispersed blocks
               on device (the CU/workgroup-per-block alternative).

Every backend returns device arrays, each leaf ``[L, n_blocks * bt, ...]``
(layer-major, as the decode cache is), and copies nothing back to the
host: each saved byte crosses the host link once.  ``b2b`` reads the saved
blocks in the order their bytes lie in memory (for ``to_blocks``'s views
of a C-ordered batch, layer-major), and every layout change runs on the
device.  A fetch opens the profiler span ``serve.kv.fetch`` (with the
context ``key``), and one ``serve.kv.fetch.h2d`` around each upload inside
it, which waits for the upload; it adds its bytes and its tokens to
``repro.serve.counters``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import counters
from .kvcache import BLOCK_TOKENS, layer_major


@dataclasses.dataclass
class FetchResult:
    state: dict[str, jax.Array]   # by leaf: [L, n_blocks * bt, ...], on the device
    n_transfers: int


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
def _layer_major(arrays: tuple, shapes: tuple, orders: tuple | None = None):
    """Device blocks, leaf i of ``shapes[i]`` [n_blocks, bt, L, ...], as
    uploaded with their axes in ``orders[i]`` (and merged where they lay
    contiguously; None: as saved) -> layer-major [L, n_blocks * bt, ...]."""
    orders = orders or tuple(tuple(range(len(s))) for s in shapes)
    out = []
    for x, shape, order in zip(arrays, shapes, orders):
        x = x.reshape([shape[i] for i in order])
        out.append(layer_major(x.transpose(tuple(int(i) for i in np.argsort(order)))))
    return tuple(out)


class HostKVStore:
    def __init__(self, block_tokens: int = BLOCK_TOKENS):
        self.block_tokens = block_tokens
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

    def blocks_for(self, key: str) -> tuple[int, int]:
        """(n_blocks, bytes per block over every leaf) of a stored context —
        the inputs ``CommBackend.kv_fetch_plan`` needs to plan the fetch."""
        blocks = self.saved(key)
        return next(iter(blocks.values())).shape[0], sum(b[0].nbytes for b in blocks.values())

    # ------------------------------------------------------------ fetch ----
    def fetch(self, key: str, backend: str = "b2b") -> FetchResult:
        blocks, n_tokens = self._store[key]
        names = tuple(blocks)
        saved = tuple(blocks[n] for n in names)
        shapes = tuple(b.shape for b in saved)
        n_blocks = shapes[0][0]
        moved = 0

        def up(x):
            # wait for the copy here, so that its span holds it
            nonlocal moved
            with jax.profiler.TraceAnnotation("serve.kv.fetch.h2d"):
                out = jax.block_until_ready(jax.device_put(x))
            moved += sum(a.nbytes for a in jax.tree.leaves(out))
            return out

        with jax.profiler.TraceAnnotation("serve.kv.fetch", key=key):
            if backend == "pcpy":
                # one device_put per dispersed block — per-copy launch + sync
                out = _layer_major(tuple(jnp.stack([up(b[i]) for i in range(n_blocks)])
                                         for b in saved), shapes)
                n_transfers = len(saved) * n_blocks
            elif backend in ("b2b", "opt_b2b"):
                # ONE transfer and one sync for every leaf, each read in the
                # order of its memory; the device puts the axes back
                views = [_in_memory_order(b) for b in saved]
                out = _layer_major(up(tuple(v for v, _ in views)), shapes,
                                   tuple(o for _, o in views))
                n_transfers = 1
            elif backend == "kernel":
                # move the pool once; Pallas kernel gathers dispersed blocks.
                # It runs compiled on an accelerator; only the CPU backend (the
                # test suite) runs it through the Pallas interpreter.
                from repro.kernels.paged_kv_gather.ops import gather_blocks
                interpret = jax.default_backend() == "cpu"
                tbl = jnp.arange(n_blocks, dtype=jnp.int32)
                out = _layer_major(tuple(
                    gather_blocks(up(b.reshape(n_blocks, self.block_tokens, -1)), tbl,
                                  interpret=interpret) for b in saved), shapes)
                n_transfers = 1
            else:
                raise ValueError(backend)
        counters.add("kv.fetch.to_device_bytes", moved)
        counters.add("kv.fetch.tokens", n_tokens)
        return FetchResult(dict(zip(names, out)), n_transfers)
