"""Paged blocks of a context's cache state on the host (PagedAttention
layout, vLLM-style): fixed-size blocks of 16 tokens by default (the vLLM
default the paper cites), a block holding every model layer of its tokens.

A model's cache state is a set of leaves with a token axis: K and V per
head, or multi-head latent attention's latent and rotated key.  On the
host, ``to_blocks`` gives one leaf of a context the block shape
``[n_blocks, block_tokens, L, ...]`` without copying it: the blocks are a
strided view of the batch's pulled ``[L, B, S, ...]`` array, not contiguous
blocks, so their bytes lie layer-major: one contiguous run per layer,
within which tokens and channels lie as the pulled array has them (a TPU
hands the batch back with tokens minor-most).  A fetch reads them in that
order and lets the device change the layout.
"""
from __future__ import annotations

import numpy as np

BLOCK_TOKENS = 16


def blocks_for_tokens(n_tokens: int, block_tokens: int = BLOCK_TOKENS) -> int:
    return (n_tokens + block_tokens - 1) // block_tokens


def to_blocks(a: np.ndarray, block_tokens: int = BLOCK_TOKENS) -> np.ndarray:
    """One layer-stacked prefill state leaf [L, B=1, S, ...] (K, V, or a
    latent) -> blocks [n_blocks, block_tokens, L, ...]: a view of ``a``
    where S is a multiple of ``block_tokens``, else a copy with a
    zero-padded tail."""
    L, B, S = a.shape[:3]
    assert B == 1
    nb = blocks_for_tokens(S, block_tokens)
    pad = nb * block_tokens - S
    a = np.moveaxis(np.asarray(a)[:, 0], 0, 1)               # [S, L, ...]
    if pad:
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    return a.reshape(nb, block_tokens, *a.shape[1:])


def layer_major(blocks):
    """Blocks [n_blocks, bt, L, ...] -> [L, n_blocks * bt, ...].  On a
    numpy array it is a view wherever the block axes can merge, as in
    ``to_blocks``'s views; on a device array, a reshape and a transpose
    on the device."""
    nb, bt = blocks.shape[:2]
    return blocks.reshape(nb * bt, *blocks.shape[2:]).swapaxes(0, 1)
