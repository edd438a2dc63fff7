"""Host-memory KV store for context caching (paper §5.3).

KV for finished/parked contexts is SAVED to host memory (numpy — the "CPU
DRAM tier") in paged blocks and FETCHED back on a cache hit instead of
re-running prefill.  Three fetch backends mirror the paper's comparison:

* ``pcpy``   — one transfer per block (baseline vLLM: one hipMemcpyAsync
               per dispersed block; here one ``jax.device_put`` each).
* ``b2b``    — ONE batched transfer: blocks are chained into a single
               contiguous staging buffer and moved with one launch + one
               sync (``hipMemcpyBatchAsync`` routed to one engine, §5.3.1);
               fan-out above the 4MB threshold.
* ``opt_b2b``— the b2b data path under the name
               ``CommBackend.kv_fetch_plan`` gives the optimized command
               stream (DESIGN.md §7/§8: batched submission + fused
               write+signal).  Only the DMA simulator
               (``repro.core.dma.kv_fetch_schedule``) tells the two apart.
* ``kernel`` — the whole pool region moves once; a Pallas gather kernel
               (repro/kernels/paged_kv_gather) reassembles dispersed blocks
               on device (the CU/workgroup-per-block alternative).

Every backend moves the blocks to the device and copies them back, so the
fetched arrays can be checked bit for bit against what was saved.  A fetch
opens the profiler span ``serve.kv.fetch`` (with the context ``key``), and
one ``serve.kv.fetch.h2d`` or ``serve.kv.fetch.d2h`` around each copy inside
it; it adds its bytes each way and its tokens to ``repro.serve.counters``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from . import counters
from .kvcache import BLOCK_TOKENS


@dataclasses.dataclass
class FetchResult:
    k_blocks: np.ndarray        # [n_blocks, bt, L, KV, hd]
    v_blocks: np.ndarray
    n_transfers: int


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
        moved = {"to_device": 0, "to_host": 0}

        def up(x):
            # wait for the copy here, so that its span holds it
            with jax.profiler.TraceAnnotation("serve.kv.fetch.h2d"):
                out = jax.device_put(x).block_until_ready()
            moved["to_device"] += out.nbytes
            return out

        def down(x):
            with jax.profiler.TraceAnnotation("serve.kv.fetch.d2h"):
                out = np.asarray(x)
            moved["to_host"] += out.nbytes
            return out

        with jax.profiler.TraceAnnotation("serve.kv.fetch", key=key):
            if backend == "pcpy":
                # one device_put per dispersed block — per-copy launch + sync
                k_out = np.stack([down(up(kb[i])) for i in range(n_blocks)])
                v_out = np.stack([down(up(vb[i])) for i in range(n_blocks)])
                n_transfers = 2 * n_blocks
            elif backend in ("b2b", "opt_b2b"):
                # chain into one staging buffer; ONE transfer, one sync
                staged = np.concatenate([kb.reshape(n_blocks, -1),
                                         vb.reshape(n_blocks, -1)], axis=1)
                out = down(up(staged))
                ksz = kb.reshape(n_blocks, -1).shape[1]
                k_out = out[:, :ksz].reshape(kb.shape)
                v_out = out[:, ksz:].reshape(vb.shape)
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
                k_out = down(gather_blocks(pool_k, tbl, interpret=interpret)).reshape(kb.shape)
                v_out = down(gather_blocks(pool_v, tbl, interpret=interpret)).reshape(vb.shape)
                n_transfers = 1
            else:
                raise ValueError(backend)
        counters.add("kv.fetch.to_device_bytes", moved["to_device"])
        counters.add("kv.fetch.to_host_bytes", moved["to_host"])
        counters.add("kv.fetch.tokens", n_tokens)
        return FetchResult(k_out, v_out, n_transfers)
