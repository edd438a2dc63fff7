"""Host-memory KV store for context caching (paper §5.3).

KV for finished/parked contexts is SAVED to host memory (numpy — the "CPU
DRAM tier") in paged blocks and FETCHED back on a cache hit instead of
re-running prefill.  Three fetch backends mirror the paper's comparison:

* ``pcpy``   — one transfer per block (baseline vLLM: one hipMemcpyAsync
               per dispersed block; here one ``jax.device_put`` each).
* ``b2b``    — ONE batched transfer: a context's K and V blocks move with
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

Every backend returns device arrays, K and V each ``[L, n_blocks * bt, KV,
hd]`` (layer-major, as the decode cache is), and copies nothing back to the
host: each saved byte crosses the host link once.  ``b2b`` reads the saved
blocks in the order their bytes lie in memory (for ``kv_to_blocks``'s views
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
    k: jax.Array                # [L, n_blocks * bt, KV, hd], on the device
    v: jax.Array
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


@functools.partial(jax.jit, static_argnames=("shape", "k_order", "v_order"))
def _layer_major(k: jax.Array, v: jax.Array, shape: tuple[int, ...],
                 k_order=(0, 1, 2, 3, 4), v_order=(0, 1, 2, 3, 4)):
    """Device blocks of ``shape`` [n_blocks, bt, L, KV, hd], as uploaded
    with their axes in ``*_order`` (and merged where they lay contiguously)
    -> layer-major [L, n_blocks * bt, KV, hd], K and V at once."""
    def one(x, order):
        x = x.reshape([shape[i] for i in order])
        return layer_major(x.transpose(tuple(int(i) for i in np.argsort(order))))
    return one(k, k_order), one(v, v_order)


class HostKVStore:
    def __init__(self, block_tokens: int = BLOCK_TOKENS):
        self.block_tokens = block_tokens
        self._store: dict[str, tuple[np.ndarray, np.ndarray, int]] = {}

    # ------------------------------------------------------------- save ----
    def save(self, key: str, k_blocks: np.ndarray, v_blocks: np.ndarray,
             n_tokens: int) -> None:
        self._store[key] = (np.asarray(k_blocks), np.asarray(v_blocks), n_tokens)

    def __contains__(self, key: str) -> bool:
        return key in self._store

    def saved(self, key: str) -> tuple[np.ndarray, np.ndarray]:
        """The (k_blocks, v_blocks) host arrays saved under ``key``."""
        kb, vb, _ = self._store[key]
        return kb, vb

    def tokens_for(self, key: str) -> int:
        return self._store[key][2]

    def blocks_for(self, key: str) -> tuple[int, int]:
        """(n_blocks, bytes per K+V block) of a stored context — the inputs
        ``CommBackend.kv_fetch_plan`` needs to plan the fetch."""
        kb, vb, _ = self._store[key]
        return kb.shape[0], kb[0].nbytes + vb[0].nbytes

    # ------------------------------------------------------------ fetch ----
    def fetch(self, key: str, backend: str = "b2b") -> FetchResult:
        kb, vb, n_tokens = self._store[key]
        n_blocks = kb.shape[0]
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
                k_blocks = [up(kb[i]) for i in range(n_blocks)]
                v_blocks = [up(vb[i]) for i in range(n_blocks)]
                k, v = _layer_major(jnp.stack(k_blocks), jnp.stack(v_blocks), kb.shape)
                n_transfers = 2 * n_blocks
            elif backend in ("b2b", "opt_b2b"):
                # ONE transfer and one sync for K and V, each read in the
                # order of its memory; the device puts the axes back
                (ks, ko), (vs, vo) = _in_memory_order(kb), _in_memory_order(vb)
                k, v = _layer_major(*up((ks, vs)), kb.shape, k_order=ko, v_order=vo)
                n_transfers = 1
            elif backend == "kernel":
                # move the pool once; Pallas kernel gathers dispersed blocks.
                # It runs compiled on an accelerator; only the CPU backend (the
                # test suite) runs it through the Pallas interpreter.
                from repro.kernels.paged_kv_gather.ops import gather_blocks
                interpret = jax.default_backend() == "cpu"
                pool_k = up(kb.reshape(n_blocks, self.block_tokens, -1))
                pool_v = up(vb.reshape(n_blocks, self.block_tokens, -1))
                tbl = jnp.arange(n_blocks, dtype=jnp.int32)
                k, v = _layer_major(gather_blocks(pool_k, tbl, interpret=interpret),
                                    gather_blocks(pool_v, tbl, interpret=interpret),
                                    kb.shape)
                n_transfers = 1
            else:
                raise ValueError(backend)
        counters.add("kv.fetch.to_device_bytes", moved)
        counters.add("kv.fetch.tokens", n_tokens)
        return FetchResult(k, v, n_transfers)
