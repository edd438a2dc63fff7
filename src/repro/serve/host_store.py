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
* ``opt_b2b``— the b2b data path with the optimized command stream
               (DESIGN.md §7/§8): batched submission + fused write+signal
               over the batch's chunked sDMA commands.  This is what
               ``CommBackend.kv_fetch_plan`` requests for the latte backend.
* ``kernel`` — the whole pool region moves once; a Pallas gather kernel
               (repro/kernels/paged_kv_gather) reassembles dispersed blocks
               on device (the CU/workgroup-per-block alternative).

Each fetch also returns a MODELED latency from the calibrated MI300X engine
model, which the simulator's TTFT/throughput benchmarks consume.  It is a
prediction for that platform, never a measurement of the device the data
path ran on; the data path itself is real and correctness-checked.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.dma import kv_fetch_schedule, mi300x_platform, simulate
from repro.core.dma.rccl_model import kernel_copy_latency
from .kvcache import BLOCK_TOKENS


@dataclasses.dataclass
class FetchResult:
    k_blocks: np.ndarray        # [n_blocks, bt, L, KV, hd]
    v_blocks: np.ndarray
    n_transfers: int
    modeled_seconds: float      # MI300X DMA/kernel model latency (not measured)


class HostKVStore:
    def __init__(self, block_tokens: int = BLOCK_TOKENS):
        self.block_tokens = block_tokens
        self._store: dict[str, tuple[np.ndarray, np.ndarray, int]] = {}
        self.topo = mi300x_platform()

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
        block_bytes = kb[0].nbytes + vb[0].nbytes

        if backend == "pcpy":
            # one device_put per dispersed block — per-copy launch + sync
            k_dev = [np.asarray(jax.device_put(kb[i])) for i in range(n_blocks)]
            v_dev = [np.asarray(jax.device_put(vb[i])) for i in range(n_blocks)]
            k_out, v_out = np.stack(k_dev), np.stack(v_dev)
            sched = kv_fetch_schedule(self.topo, n_blocks, block_bytes, "pcpy")
            modeled = simulate(sched, self.topo).latency
            n_transfers = 2 * n_blocks
        elif backend in ("b2b", "opt_b2b"):
            # chain into one staging buffer; ONE transfer, one sync.  The
            # opt_ flavor moves the same bytes but models the optimized
            # command stream (batched submission + fused signal, DESIGN.md
            # §7/§8) for the latency estimate.
            staged = np.concatenate([kb.reshape(n_blocks, -1),
                                     vb.reshape(n_blocks, -1)], axis=1)
            moved = np.asarray(jax.device_put(staged))
            ksz = kb.reshape(n_blocks, -1).shape[1]
            k_out = moved[:, :ksz].reshape(kb.shape)
            v_out = moved[:, ksz:].reshape(vb.shape)
            variant = "prelaunch_b2b" if backend == "b2b" else "opt_prelaunch_b2b"
            sched = kv_fetch_schedule(self.topo, n_blocks, block_bytes, variant)
            modeled = simulate(sched, self.topo).latency
            n_transfers = 1
        elif backend == "kernel":
            # move the pool once; Pallas kernel gathers dispersed blocks.
            # It runs compiled on an accelerator; only the CPU backend (the
            # test suite) runs it through the Pallas interpreter.
            from repro.kernels.paged_kv_gather.ops import gather_blocks
            interpret = jax.default_backend() == "cpu"
            pool_k = jax.device_put(kb.reshape(n_blocks, self.block_tokens, -1))
            pool_v = jax.device_put(vb.reshape(n_blocks, self.block_tokens, -1))
            tbl = jnp.arange(n_blocks, dtype=jnp.int32)
            k_out = np.asarray(gather_blocks(pool_k, tbl, interpret=interpret)).reshape(kb.shape)
            v_out = np.asarray(gather_blocks(pool_v, tbl, interpret=interpret)).reshape(vb.shape)
            modeled = kernel_copy_latency(self.topo, n_blocks * block_bytes, n_launches=1)
            n_transfers = 1
        else:
            raise ValueError(backend)
        return FetchResult(k_out, v_out, n_transfers, modeled)
