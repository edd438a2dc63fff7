"""Hypothesis property tests on system invariants.

The DMA strategies sample the FULL variant space: the six pre-PR-2 baseline
variants, the neighbor-ring renderings, the ``opt_`` optimized command
streams (DESIGN.md §7), chunk granularities (§8.1) and the per-chunk-signaled
pipelined rings (§9).  Invariants: latency positivity, traffic conservation,
per-link byte invariance under chunking/pipelining, monotone completion in
chunk count for non-pipelined streams, and per-chunk beating final-chunk-only
signaling for the pipelined rings.

CI runs this file un-skipped (the fast job installs ``hypothesis`` and a
guard step fails if collection comes back empty); locally the module skips
when hypothesis is unavailable.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.dma import (allgather_schedule, allreduce_schedule,
                            alltoall_schedule, chunk_sizes, kv_fetch_schedule,
                            link_traffic, mi300x_platform, reduce_scatter_schedule,
                            reduce_work, simulate, tpu_v5e_pod, variant_latency)
from repro.core.dma.claims import (pipe_vs_final_chunk_ratio,
                                   rs_pipe_vs_final_chunk_ratio)
from repro.core.dma.collectives import AR_AG_VARIANT, _pipe_granularity
from repro.data.pipeline import DataConfig, synth_batch
from repro.models.layers import apply_rotary, rope_angles
from repro.serve.kvcache import layer_major, to_blocks
from repro.train.checkpoint import restore_checkpoint, save_checkpoint

KB, MB = 1024, 1024 * 1024
TOPO = mi300x_platform()
TPU = tpu_v5e_pod(16)

sizes = st.integers(min_value=1024, max_value=1 << 32)
# The full all-gather variant space: baseline, ring renderings, optimized
# command streams (DESIGN.md §7) and the pipelined rings (§9).  The ring /
# pipe variants are legal on MI300X by explicit request — the simulator
# routes them over the fully-connected fabric.
variants_ag = st.sampled_from([
    "pcpy", "bcst", "b2b", "prelaunch_pcpy", "prelaunch_bcst", "prelaunch_b2b",
    "ring", "bidir_ring",
    "opt_pcpy", "opt_bcst", "opt_b2b", "opt_prelaunch_b2b",
    "opt_ring", "opt_bidir_ring",
    "pipe_b2b", "pipe_bidir_ring", "opt_pipe_b2b", "opt_pipe_bidir_ring",
    "prelaunch_pipe_b2b", "opt_prelaunch_pipe_bidir_ring",
])
variants_aa = st.sampled_from([
    "pcpy", "swap", "b2b", "prelaunch_swap", "ring",
    "opt_pcpy", "opt_swap", "opt_b2b", "opt_ring",
    "pipe_b2b", "opt_pipe_b2b",
])
# Direct (non-forwarding) all-to-all variants: each ordered pair is served by
# exactly one command — the rotation rings forward, so they are checked via
# per-link byte invariance instead.
variants_aa_direct = st.sampled_from([
    "pcpy", "swap", "b2b", "prelaunch_swap", "opt_pcpy", "opt_swap", "opt_b2b",
])
chunk_grains = st.sampled_from([0, 256 * KB, 1 * MB, 4 * MB])
pipe_depths = st.sampled_from([1, 2, 4, 8])
# The full reduce-scatter variant space (DESIGN.md §10): the ring reduce
# family with every prelaunch_/opt_/pipe_ composition.
variants_rs = st.sampled_from([
    "ring_rs", "bidir_ring_rs", "pipe_ring_rs", "pipe_bidir_ring_rs",
    "prelaunch_ring_rs", "prelaunch_bidir_ring_rs",
    "opt_ring_rs", "opt_bidir_ring_rs",
    "opt_pipe_ring_rs", "prelaunch_pipe_bidir_ring_rs",
    "opt_prelaunch_pipe_ring_rs", "opt_prelaunch_pipe_bidir_ring_rs",
])
variants_rs_base = st.sampled_from([
    "ring_rs", "bidir_ring_rs", "pipe_ring_rs", "pipe_bidir_ring_rs"])
topologies = st.sampled_from([TOPO, TPU])


_link_traffic = link_traffic


@settings(max_examples=40, deadline=None)
@given(size=sizes, v=variants_ag)
def test_allgather_positive_finite_latency(size, v):
    r = simulate(allgather_schedule(TOPO, size, v), TOPO)
    assert 0 < r.latency < 10.0
    for b in r.per_device.values():
        assert b.control >= 0 and b.schedule >= 0 and b.copy >= 0 and b.sync >= 0


@settings(max_examples=25, deadline=None)
@given(size=st.integers(min_value=1024, max_value=1 << 28), v=variants_aa)
def test_alltoall_positive_finite_latency(size, v):
    r = simulate(alltoall_schedule(TOPO, size, v), TOPO)
    assert 0 < r.latency < 10.0


@settings(max_examples=30, deadline=None)
@given(size=st.integers(min_value=1024, max_value=1 << 28),
       v=st.sampled_from(["pcpy", "bcst", "b2b", "ring", "bidir_ring",
                          "pipe_b2b", "pipe_bidir_ring"]))
def test_prelaunch_never_slower(size, v):
    """Arming queues ahead of time (§4.5) moves control/schedule off the
    critical path — it may never pessimize, pipelined variants included."""
    base = simulate(allgather_schedule(TOPO, size, v), TOPO).latency
    pre = simulate(allgather_schedule(TOPO, size, f"prelaunch_{v}"), TOPO).latency
    assert pre <= base


@settings(max_examples=30, deadline=None)
@given(size=sizes, v=variants_ag)
def test_allgather_delivers_n_minus_one_shards(size, v):
    """Conservation: every device receives exactly n-1 shards, whatever the
    variant/route/chunking (rings forward shard-sized payloads, so inbound
    bytes per device are (n-1) * shard for every all-gather rendering)."""
    sched = allgather_schedule(TOPO, size, v)
    n = TOPO.n_devices
    shard = max(1, size // n)
    inbound = {d: 0 for d in range(n)}
    for (_, dst), nbytes in _link_traffic(sched).items():
        inbound[dst] += nbytes
    assert inbound == {d: (n - 1) * shard for d in range(n)}


@settings(max_examples=40, deadline=None)
@given(size=sizes, v=variants_aa_direct)
def test_alltoall_traffic_conserved(size, v):
    """Every ordered (src, dst) pair receives exactly one shard, any direct
    variant — stated in bytes so it holds under chunking (§8.1), which
    splits a pair's shard across many commands."""
    sched = alltoall_schedule(TOPO, size, v)
    traffic = _link_traffic(sched)
    n = TOPO.n_devices
    shard = max(1, size // n)
    assert set(traffic) == {(a, b) for a in range(n) for b in range(n) if a != b}
    assert set(traffic.values()) == {shard}


@pytest.mark.slow   # duplicates the pinned-grid byte/monotonicity coverage; the fast job keeps the claim-guarded §9/§10 cases
@settings(max_examples=30, deadline=None)
@given(size=st.integers(min_value=1 * MB, max_value=1 << 31), v=variants_ag,
       grain_a=chunk_grains, grain_b=chunk_grains)
def test_per_link_bytes_invariant_under_chunking(size, v, grain_a, grain_b):
    """Chunk granularity (and pipeline chunking, §9) never changes WHAT moves:
    per-(src, dst) byte totals are identical at any max_chunk_bytes."""
    a = _link_traffic(allgather_schedule(TOPO, size, v, max_chunk_bytes=grain_a))
    b = _link_traffic(allgather_schedule(TOPO, size, v, max_chunk_bytes=grain_b))
    assert a == b


@pytest.mark.slow   # duplicates the pinned-grid byte/monotonicity coverage; the fast job keeps the claim-guarded §9/§10 cases
@settings(max_examples=20, deadline=None)
@given(size=st.integers(min_value=1 * MB, max_value=1 << 30), v=variants_ag,
       depth_a=pipe_depths, depth_b=pipe_depths)
def test_per_link_bytes_invariant_under_pipe_depth(size, v, depth_a, depth_b):
    a = _link_traffic(allgather_schedule(TOPO, size, v, pipe_depth=depth_a))
    b = _link_traffic(allgather_schedule(TOPO, size, v, pipe_depth=depth_b))
    assert a == b


@pytest.mark.slow   # duplicates the pinned-grid byte/monotonicity coverage; the fast job keeps the claim-guarded §9/§10 cases
@settings(max_examples=15, deadline=None)
@given(size=st.sampled_from([64 * MB, 256 * MB, 1 << 30, 1 << 31]),
       v=st.sampled_from(["pcpy", "b2b", "bcst", "prelaunch_pcpy"]))
def test_completion_monotone_in_chunk_count(size, v):
    """Non-pipelined streams: finer chunks (more commands) never complete
    sooner — per-chunk packet/issue costs only add.  (Pipelined streams are
    exempt by design: chunk count trades fill latency against per-chunk
    cost, DESIGN.md §9.1; opt_ streams are exempt because the §7.2 slot
    gate flips eligibility across the chunk-size boundary.)"""
    prev = 0.0
    for grain in (0, 16 * MB, 4 * MB, 1 * MB, 256 * KB):
        lat = variant_latency(TOPO, "all_gather", size, v, grain)
        assert lat >= prev * (1 - 1e-9), grain
        prev = lat


@settings(max_examples=12, deadline=None)
@given(size=st.sampled_from([512 * KB, 1 * MB, 2 * MB, 4 * MB, 8 * MB]),
       depth=st.sampled_from([2, 4]))
def test_pipe_beats_final_chunk_only_signaling(size, depth):
    """§9 acceptance invariant on the TPU torus: at >= 2 chunks, per-chunk
    signaling strictly beats final-chunk-only signaling of the same
    pipelined schedule across the mid-size band."""
    assert pipe_vs_final_chunk_ratio(TPU, size, depth) > 1.0


@settings(max_examples=30, deadline=None)
@given(size=sizes, v=variants_rs)
def test_rs_per_link_bytes_match_allgather_rings(size, v):
    """Conservation: a reduce-scatter moves exactly what its ring moves —
    every device receives n-1 shard-sized partials, whatever the
    variant/chunking/signaling grain (DESIGN.md §10)."""
    sched = reduce_scatter_schedule(TOPO, size, v)
    n = TOPO.n_devices
    shard = max(1, size // n)
    inbound = {d: 0 for d in range(n)}
    for (_, dst), nbytes in _link_traffic(sched).items():
        inbound[dst] += nbytes
    assert inbound == {d: (n - 1) * shard for d in range(n)}


@pytest.mark.slow   # duplicates the pinned-grid byte/monotonicity coverage; the fast job keeps the claim-guarded §9/§10 cases
@settings(max_examples=25, deadline=None)
@given(size=st.integers(min_value=1 * MB, max_value=1 << 31), v=variants_rs,
       grain_a=chunk_grains, grain_b=chunk_grains,
       depth_a=pipe_depths, depth_b=pipe_depths)
def test_rs_per_link_bytes_invariant_under_chunking_and_depth(
        size, v, grain_a, grain_b, depth_a, depth_b):
    """Chunk granularity AND pipeline depth never change WHAT a
    reduce-scatter moves: per-(src, dst) byte totals are identical."""
    a = _link_traffic(reduce_scatter_schedule(
        TOPO, size, v, max_chunk_bytes=grain_a, pipe_depth=depth_a))
    b = _link_traffic(reduce_scatter_schedule(
        TOPO, size, v, max_chunk_bytes=grain_b, pipe_depth=depth_b))
    assert a == b


@settings(max_examples=30, deadline=None)
@given(size=sizes, v=variants_rs, grain=chunk_grains, depth=pipe_depths,
       topo=topologies)
def test_rs_reduction_work_conserved(size, v, grain, depth, topo):
    """Conservation of reduction work — the §10 invariant class that caught
    PR 4's bidir off-by-one: each device performs exactly
    (n-1) * shard_chunks chunk reductions totalling (n-1) * shard bytes,
    under chunking AND pipe depth AND signaling grain."""
    sched = reduce_scatter_schedule(topo, size, v, max_chunk_bytes=grain,
                                    pipe_depth=depth)
    n = topo.n_devices
    shard = max(1, size // n)
    g = _pipe_granularity(shard, depth, grain) if "pipe_" in v else grain
    shard_chunks = len(chunk_sizes(shard, g))
    assert reduce_work(sched) == \
        {d: ((n - 1) * shard_chunks, (n - 1) * shard) for d in range(n)}


@settings(max_examples=12, deadline=None)
@given(size=st.sampled_from([512 * KB, 1 * MB, 2 * MB, 4 * MB, 8 * MB]),
       depth=st.sampled_from([1, 2, 4, 8]),
       v=st.sampled_from(["pipe_ring_rs", "pipe_bidir_ring_rs"]))
def test_pipe_rs_never_slower_than_final_chunk_only(size, depth, v):
    """§10 acceptance invariant: reducing each chunk as it lands never
    loses to final-chunk-only signaling of the same schedule (strictly
    wins at >= 2 chunks — pinned in tests/test_sim.py)."""
    assert rs_pipe_vs_final_chunk_ratio(TPU, size, depth, v) >= 1.0 - 1e-9


@settings(max_examples=15, deadline=None)
@given(size=st.integers(min_value=64 * KB, max_value=1 << 28),
       v=variants_rs_base, topo=topologies)
def test_allreduce_not_slower_than_sequential_rs_then_ag(size, v, topo):
    """The composed all-reduce (armed gather chained off the terminal
    reductions, DESIGN.md §10) never loses to running reduce-scatter and
    all-gather back to back."""
    ar = simulate(allreduce_schedule(topo, size, v), topo).latency
    rs = simulate(reduce_scatter_schedule(topo, size, v), topo).latency
    ag = simulate(allgather_schedule(topo, size, AR_AG_VARIANT[v]), topo).latency
    assert ar <= (rs + ag) * (1 + 1e-9)


@settings(max_examples=25, deadline=None)
@given(n_blocks=st.integers(1, 512), block_bytes=st.integers(256, 1 << 22))
def test_kv_fetch_b2b_fewer_signals_than_pcpy(n_blocks, block_bytes):
    pcpy = kv_fetch_schedule(TOPO, n_blocks, block_bytes, "pcpy")
    b2b = kv_fetch_schedule(TOPO, n_blocks, block_bytes, "b2b")
    sig = lambda s: sum(q.n_signals for q in s.queues)
    assert sig(b2b) <= sig(pcpy)
    assert sig(pcpy) == n_blocks


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), step=st.integers(0, 1000))
def test_data_pipeline_deterministic(seed, step):
    cfg = DataConfig(vocab=1024, seq_len=64, batch=2, seed=seed)
    a = synth_batch(cfg, step)["tokens"]
    b = synth_batch(cfg, step)["tokens"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(jnp.max(a)) < 1024 and int(jnp.min(a)) >= 0


@settings(max_examples=20, deadline=None)
@given(s=st.integers(1, 64), kv=st.sampled_from([1, 2, 4]),
       hd=st.sampled_from([8, 16]), layers=st.integers(1, 3),
       bt=st.sampled_from([4, 16]))
def test_kv_block_roundtrip(s, kv, hd, layers, bt):
    rng = np.random.default_rng(0)
    k = rng.normal(size=(layers, 1, s, kv, hd)).astype(np.float32)
    v = rng.normal(size=(layers, 1, s, kv, hd)).astype(np.float32)
    kb, vb = to_blocks(k, bt), to_blocks(v, bt)
    # the host reference of what a fetch and rebuild undo: blocks -> [L, 1, S, KV, hd]
    k2, v2 = (layer_major(b)[:, :s][:, None] for b in (kb, vb))
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)


@settings(max_examples=10, deadline=None)
@given(hd=st.sampled_from([16, 32, 64]), s=st.integers(2, 32))
def test_rotary_preserves_norm(hd, s):
    x = jax.random.normal(jax.random.PRNGKey(s), (1, s, 2, hd))
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    cos, sin = rope_angles(pos, hd, 10_000.0)
    y = apply_rotary(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(shapes=st.lists(st.tuples(st.integers(1, 5), st.integers(1, 5)),
                       min_size=1, max_size=4),
       seed=st.integers(0, 1 << 16))
def test_checkpoint_roundtrip(tmp_path_factory, shapes, seed):
    rng = np.random.default_rng(seed)
    tree = {"a": [jnp.asarray(rng.normal(size=s).astype(np.float32)) for s in shapes],
            "b": {"step": jnp.int32(seed % 97)}}
    path = str(tmp_path_factory.mktemp("ckpt") / "t.npz")
    save_checkpoint(path, tree)
    restored = restore_checkpoint(path, tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
