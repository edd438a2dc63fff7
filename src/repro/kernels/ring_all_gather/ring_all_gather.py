"""Pallas TPU ring all-gather over remote DMA — the TPU-native analogue of
the paper's DMA-offloaded all-gather (DESIGN.md §4).

Feature mapping (paper -> kernel flag):
* pcpy  -> per-step full sync (``defer_send_sync=False``): every RDMA waits
           both its send and recv semaphores before the next is issued —
           one "signal" per copy, like one sync command per DMA engine.
* b2b   -> deferred send sync (``defer_send_sync=True``): steps chain on the
           data dependency only (recv); all send completions are drained by
           ONE trailing wait sequence — the single-signal back-to-back
           queue of §4.4.
* bcst  -> bidirectional ring (``bidirectional=True``): each step reads one
           local chunk and issues it to BOTH neighbours (one source read,
           two destinations, §4.2), halving the number of ring steps.
* prelaunch -> send descriptors are issued as soon as their data dependency
           (previous recv) is met, before prior sends complete — issue-ahead
           is inherent to the deferred-sync chain.

Synchronization uses PER-STEP DMA semaphore arrays: a count-based shared
semaphore lets a later arrival satisfy an earlier wait (observed data race
in interpret mode — see tests), per-step semaphores make every wait match
exactly its transfer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _neighbors(axis_name: str, n: int):
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)
    return my, left, right


def ring_all_gather_kernel(
    chunk_ref,        # [chunk, F]    local shard (ANY)
    out_ref,          # [n, chunk, F] gathered output (ANY)
    local_sem,        # DMA sem for the local HBM->HBM copy
    send_r, recv_r,   # DMA sem arrays [n-1], rightward stream
    send_l, recv_l,   # DMA sem arrays [n-1], leftward stream
    *,
    axis_name: str,
    num_devices: int,
    defer_send_sync: bool,
    bidirectional: bool,
):
    n = num_devices
    my, left, right = _neighbors(axis_name, n)

    # Neighbour-ready barrier (buffers allocated before anyone writes into
    # them remotely) — the analogue of the doorbell/queue handshake.
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id=left)
    pltpu.semaphore_signal(barrier, 1, device_id=right)
    pltpu.semaphore_wait(barrier, 2)

    local = pltpu.make_async_copy(chunk_ref, out_ref.at[my], local_sem)
    local.start()
    local.wait()

    def copy_right(k):    # step k (1-based): forward slot (my-k+1) rightward
        slot = jax.lax.rem(my - k + 1 + n, n)
        return pltpu.make_async_remote_copy(
            src_ref=out_ref.at[slot], dst_ref=out_ref.at[slot],
            send_sem=send_r.at[k - 1], recv_sem=recv_r.at[k - 1], device_id=right)

    def copy_left(k):     # step k: forward slot (my+k-1) leftward
        slot = jax.lax.rem(my + k - 1, n)
        return pltpu.make_async_remote_copy(
            src_ref=out_ref.at[slot], dst_ref=out_ref.at[slot],
            send_sem=send_l.at[k - 1], recv_sem=recv_l.at[k - 1], device_id=left)

    if not bidirectional:
        def body(k, _):
            copy = copy_right(k)
            copy.start()
            if defer_send_sync:
                copy.wait_recv()
            else:
                copy.wait()
            return 0

        jax.lax.fori_loop(1, n, body, 0)
        if defer_send_sync:
            def drain(k, _):
                copy_right(k).wait_send()
                return 0
            jax.lax.fori_loop(1, n, drain, 0)
        return

    # Bidirectional ("bcst"): two streams, half the steps.
    n_right = (n - 1 + 1) // 2     # chunks arriving from the left stream
    n_left = (n - 1) - n_right     # chunks arriving from the right stream

    def body(k, _):
        cr = copy_right(k)
        cl = copy_left(k)

        @pl.when(k <= n_right)
        def _():
            cr.start()

        @pl.when(k <= n_left)
        def _():
            cl.start()

        @pl.when(k <= n_right)
        def _():
            if defer_send_sync:
                cr.wait_recv()
            else:
                cr.wait()

        @pl.when(k <= n_left)
        def _():
            if defer_send_sync:
                cl.wait_recv()
            else:
                cl.wait()
        return 0

    jax.lax.fori_loop(1, n_right + 1, body, 0)
    if defer_send_sync:
        def drain(k, _):
            @pl.when(k <= n_right)
            def _():
                copy_right(k).wait_send()

            @pl.when(k <= n_left)
            def _():
                copy_left(k).wait_send()
            return 0
        jax.lax.fori_loop(1, n_right + 1, drain, 0)


def make_ring_all_gather(
    axis_name: str,
    num_devices: int,
    *,
    defer_send_sync: bool = True,
    bidirectional: bool = False,
    interpret: bool = False,
    collective_id: int = 0,
):
    """Returns fn(local_chunk [chunk, F]) -> [num_devices*chunk, F]; call it
    inside shard_map over ``axis_name``."""
    kernel = functools.partial(
        ring_all_gather_kernel,
        axis_name=axis_name,
        num_devices=num_devices,
        defer_send_sync=defer_send_sync,
        bidirectional=bidirectional,
    )
    n_steps = max(num_devices - 1, 1)

    def fn(chunk: jax.Array) -> jax.Array:
        c, f = chunk.shape
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((num_devices, c, f), chunk.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA]
            + [pltpu.SemaphoreType.DMA((n_steps,))] * 4,
            compiler_params=pltpu.CompilerParams(collective_id=collective_id),
            interpret=pltpu.InterpretParams() if interpret else False,
        )(chunk)
        return out.reshape(num_devices * c, f)

    return fn
