"""Paged KV cache management (PagedAttention layout, vLLM-style).

Device-side pools hold KV in fixed-size blocks (16 tokens by default, the
vLLM default the paper cites); per-sequence block tables map logical block
index -> pool slot, and a pool block holds every model layer of its tokens.

On the host, ``kv_to_blocks`` gives a context's K/V the block shape
``[n_blocks, block_tokens, L, KV, hd]`` without copying it: the blocks are a
strided view of the batch's pulled ``[L, B, S, KV, hd]`` array, not
contiguous blocks, so their bytes lie layer-major: one contiguous run per
layer, within which tokens, heads and channels lie as the pulled array has
them (a TPU hands the batch back with tokens minor-most).  A fetch reads
them in that order and lets the device change the layout.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

BLOCK_TOKENS = 16


@dataclasses.dataclass
class PagedPools:
    """Device-side paged pools: k/v [n_blocks, block_tokens, L, KV, hd]."""

    k: jax.Array
    v: jax.Array
    block_tokens: int

    @property
    def n_blocks(self) -> int:
        return self.k.shape[0]

    @property
    def block_bytes(self) -> int:
        per = int(np.prod(self.k.shape[1:])) * self.k.dtype.itemsize
        return 2 * per  # k + v


def init_pools(cfg: ArchConfig, n_layers: int, n_blocks: int,
               block_tokens: int = BLOCK_TOKENS) -> PagedPools:
    cd = jnp.dtype(cfg.compute_dtype)
    shape = (n_blocks, block_tokens, n_layers, cfg.n_kv_heads, cfg.head_dim)
    return PagedPools(jnp.zeros(shape, cd), jnp.zeros(shape, cd), block_tokens)


class BlockAllocator:
    """Free-list allocator over pool slots."""

    def __init__(self, n_blocks: int):
        self.free = list(range(n_blocks - 1, -1, -1))
        self.n_blocks = n_blocks

    def alloc(self, n: int) -> list[int]:
        if n > len(self.free):
            raise MemoryError(f"paged pool exhausted: want {n}, have {len(self.free)}")
        return [self.free.pop() for _ in range(n)]

    def release(self, blocks: list[int]) -> None:
        self.free.extend(blocks)

    @property
    def n_free(self) -> int:
        return len(self.free)


def blocks_for_tokens(n_tokens: int, block_tokens: int = BLOCK_TOKENS) -> int:
    return (n_tokens + block_tokens - 1) // block_tokens


def kv_to_blocks(k: np.ndarray, v: np.ndarray, block_tokens: int = BLOCK_TOKENS):
    """Layer-stacked prefill KV [L, B=1, S, KV, hd] -> per-block arrays
    [n_blocks, block_tokens, L, KV, hd]: views of ``k`` and ``v`` where S is
    a multiple of ``block_tokens``, else copies with a zero-padded tail."""
    L, B, S, KV, hd = k.shape
    assert B == 1
    nb = blocks_for_tokens(S, block_tokens)
    pad = nb * block_tokens - S
    def conv(a):
        a = np.moveaxis(np.asarray(a)[:, 0], 0, 1)          # [S, L, KV, hd]
        if pad:
            a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        return a.reshape(nb, block_tokens, L, KV, hd)
    return conv(k), conv(v)


def layer_major(blocks):
    """Blocks [n_blocks, bt, L, KV, hd] -> [L, n_blocks * bt, KV, hd].  On a
    numpy array it is a view wherever the block axes can merge, as in
    ``kv_to_blocks``'s views; on a device array, a reshape and a transpose
    on the device."""
    nb, bt = blocks.shape[:2]
    return blocks.reshape(nb * bt, *blocks.shape[2:]).swapaxes(0, 1)
