"""Pallas TPU all-to-all over remote DMA — the swap/b2b analogue (paper §4.3/4.4).

Device i holds input chunks x_i[0..n-1] (chunk j destined to device j) and
must end with out_i[j] = x_j[i].

Schedules:
* ``swap`` (XOR pairing, n a power of two): round r exchanges chunks with
  partner ``my ^ r`` — a symmetric in-place pairwise exchange: both
  directions of a pair travel the same (full-duplex) link simultaneously and
  land DIRECTLY in their final output slot, no staging buffer.  This is the
  TPU rendering of the paper's in-place ``swap`` command (Fig. 10).
* rotation pairing for other n.

Sync variants:
* ``per_round`` (pcpy-like): wait send+recv every round.
* ``b2b``: ALL rounds' sends are issued back-to-back up front — legal
  because every send reads the INPUT ref while receives land in the OUTPUT
  ref (no data hazard) — then one trailing drain of recvs/sends.  This is
  simultaneously the paper's b2b (single sync for a chain of copies) and
  prelaunch (issue off the critical path) applied to all-to-all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def all_to_all_kernel(
    x_ref,          # [n, chunk, F] input chunks (ANY)
    out_ref,        # [n, chunk, F] output (ANY)
    local_sem,
    send_sems,      # DMA sem array [n-1]
    recv_sems,      # DMA sem array [n-1]
    *,
    axis_name: str,
    num_devices: int,
    xor_pairing: bool,
    b2b: bool,
):
    n = num_devices
    my = jax.lax.axis_index(axis_name)

    # Every device writes into every other one, so the ready barrier spans
    # all peers (XOR partners are not ring neighbours for n > 2).
    barrier = pltpu.get_barrier_semaphore()

    def signal(r, _):
        pltpu.semaphore_signal(barrier, 1, device_id=jax.lax.rem(my + r, n))
        return 0
    jax.lax.fori_loop(1, n, signal, 0)
    pltpu.semaphore_wait(barrier, n - 1)

    local = pltpu.make_async_copy(x_ref.at[my], out_ref.at[my], local_sem)
    local.start()
    local.wait()

    def send_copy(r):
        partner = (my ^ r) if xor_pairing else jax.lax.rem(my + r, n)
        # my chunk `partner` lands in partner's out slot `my`
        return pltpu.make_async_remote_copy(
            src_ref=x_ref.at[partner], dst_ref=out_ref.at[my],
            send_sem=send_sems.at[r - 1], recv_sem=recv_sems.at[r - 1],
            device_id=partner)

    if b2b:
        def issue(r, _):
            send_copy(r).start()       # back-to-back issue, no intervening sync
            return 0
        jax.lax.fori_loop(1, n, issue, 0)

        def drain(r, _):
            c = send_copy(r)
            c.wait_send()
            c.wait_recv()
            return 0
        jax.lax.fori_loop(1, n, drain, 0)
    else:
        def round_(r, _):
            c = send_copy(r)
            c.start()
            c.wait()
            return 0
        jax.lax.fori_loop(1, n, round_, 0)


def make_all_to_all(
    axis_name: str,
    num_devices: int,
    *,
    b2b: bool = True,
    interpret: bool = False,
    collective_id: int = 1,
):
    """Returns fn(x [n, chunk, F]) -> [n, chunk, F] with out[j] = x_j[my];
    call inside shard_map over ``axis_name``."""
    xor_pairing = (num_devices & (num_devices - 1)) == 0
    kernel = functools.partial(
        all_to_all_kernel,
        axis_name=axis_name,
        num_devices=num_devices,
        xor_pairing=xor_pairing,
        b2b=b2b,
    )
    n_steps = max(num_devices - 1, 1)

    def fn(x: jax.Array) -> jax.Array:
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA((n_steps,)),
                            pltpu.SemaphoreType.DMA((n_steps,))],
            compiler_params=pltpu.CompilerParams(collective_id=collective_id),
            interpret=pltpu.InterpretParams() if interpret else False,
        )(x)

    return fn
