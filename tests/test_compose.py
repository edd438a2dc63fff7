"""Multi-schedule composition invariants (DESIGN.md §12).

``run_composed`` executes K independent command streams in ONE resource
world.  This suite pins the contract the serving simulation stands on:

* K=1 composition is BIT-IDENTICAL to ``simulate(..., symmetric=False)``
  (hypothesis-driven across the variant space);
* tag namespacing conserves per-schedule bytes and reduction work, and the
  composed world's aggregate HBM/reduction counters are the sums of the
  isolated runs;
* the composed makespan is bounded below by every isolated latency, no
  stream ever beats its own isolated latency, and per-resource busy time
  is additive when streams are added (contention monotonicity, stated
  modulo Graham-style scheduling anomalies — see the test's docstring);
* two streams sharing one host link serialize (busy time conserved, bounded
  by the makespan) while disjoint-resource streams compose with ZERO
  slowdown — bit-identical to their isolated runs;
* seeded workload generators are reproducible across processes, and one
  small composed serving run is pinned token-for-token (golden TTFTs).

CI runs this file un-skipped (the fast job installs ``hypothesis`` and a
guard step fails if collection comes back empty); locally the hypothesis
tests skip when it is unavailable.
"""
from __future__ import annotations

import subprocess
import sys

import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.dma import (allgather_schedule, allreduce_schedule,
                            alltoall_schedule, kv_fetch_schedule,
                            link_traffic, mi300x_platform,
                            reduce_scatter_schedule, reduce_work,
                            run_composed, simulate, tpu_v5e_pod)
from repro.core.dma.sim import _namespace_schedule

KB, MB = 1024, 1024 * 1024
TOPO = mi300x_platform()
TPU = tpu_v5e_pod(16)

# One strategy over the whole composable space: (builder, variant) pairs
# spanning baselines, optimized streams (§7), rings and pipelined rings
# (§9), plus reduction collectives (§10).
_BUILDS = [
    (allgather_schedule, "pcpy"), (allgather_schedule, "b2b"),
    (allgather_schedule, "opt_b2b"), (allgather_schedule, "ring"),
    (allgather_schedule, "pipe_bidir_ring"),
    (alltoall_schedule, "swap"), (alltoall_schedule, "opt_pcpy"),
    (alltoall_schedule, "pipe_b2b"),
    (reduce_scatter_schedule, "ring_rs"),
    (reduce_scatter_schedule, "pipe_bidir_ring_rs"),
    (allreduce_schedule, "ring_rs"),
]
builds = st.sampled_from(_BUILDS)
sizes = st.integers(min_value=8 * KB, max_value=64 * MB)
topos = st.sampled_from([TOPO, TPU])


def _build(topo, build, size):
    builder, variant = build
    return builder(topo, size, variant)


def _fetch(device, n_blocks=24, block_bytes=1 * MB, topo=TOPO):
    return kv_fetch_schedule(topo, n_blocks, block_bytes, "opt_prelaunch_b2b",
                             device=device)


# ------------------------------------------------------------------------ #
# K=1 bit-identity                                                         #
# ------------------------------------------------------------------------ #

@settings(max_examples=40, deadline=None)
@given(topos, builds, sizes)
def test_k1_composition_bit_identical_to_simulate(topo, build, size):
    sched = _build(topo, build, size)
    ref = simulate(sched, topo, symmetric=False)
    comp = run_composed([sched], topo)
    res = comp.result
    assert res.latency == ref.latency
    assert res.per_device == ref.per_device
    assert res.busy == ref.busy
    assert res.timelines == ref.timelines
    assert res.host_events == ref.host_events
    assert res.engine_atomics == ref.engine_atomics
    assert res.reduce_chunks == ref.reduce_chunks
    assert res.hbm_bytes == ref.hbm_bytes
    out, = comp.outcomes
    assert out.release == 0.0
    assert out.finish == ref.latency
    assert out.latency == ref.latency


def test_k1_matches_symmetric_fast_path_latency():
    # For a symmetric schedule the full loop equals the fast path, so the
    # composed K=1 latency also equals plain simulate().
    sched = allgather_schedule(TOPO, 4 * MB, "opt_b2b")
    assert run_composed([sched], TOPO).makespan == simulate(sched, TOPO).latency


# ------------------------------------------------------------------------ #
# Conservation under namespacing and composition                           #
# ------------------------------------------------------------------------ #

@settings(max_examples=25, deadline=None)
@given(topos, builds, sizes, st.integers(min_value=0, max_value=5))
def test_namespacing_conserves_bytes_and_reduction_work(topo, build, size, k):
    sched = _build(topo, build, size)
    ns = _namespace_schedule(sched, k)
    assert link_traffic(ns) == link_traffic(sched)
    assert reduce_work(ns) == reduce_work(sched)
    assert not ns.symmetric    # composed streams never take the fast path


def test_composed_counters_are_sums_of_isolated():
    s1 = reduce_scatter_schedule(TOPO, 8 * MB, "ring_rs")
    s2 = alltoall_schedule(TOPO, 4 * MB, "opt_pcpy")
    r1 = simulate(s1, TOPO, symmetric=False)
    r2 = simulate(s2, TOPO, symmetric=False)
    comp = run_composed([s1, s2], TOPO).result
    for d in r1.per_device:
        assert comp.hbm_bytes[d] == r1.hbm_bytes[d] + r2.hbm_bytes[d]
        assert comp.reduce_chunks[d] == (r1.reduce_chunks.get(d, 0)
                                         + r2.reduce_chunks.get(d, 0))
        assert comp.host_events[d] == r1.host_events[d] + r2.host_events[d]


# ------------------------------------------------------------------------ #
# Makespan bounds and contention monotonicity                              #
# ------------------------------------------------------------------------ #

@settings(max_examples=20, deadline=None)
@given(builds, builds, sizes,
       st.floats(min_value=0.0, max_value=5e-4, allow_nan=False))
def test_makespan_at_least_max_isolated(build_a, build_b, size, release):
    a = _build(TOPO, build_a, size)
    b = _build(TOPO, build_b, size)
    iso_a = simulate(a, TOPO, symmetric=False).latency
    iso_b = simulate(b, TOPO, symmetric=False).latency
    comp = run_composed([a, b], TOPO, [0.0, release])
    assert comp.makespan >= iso_a * (1 - 1e-9)
    assert comp.makespan >= release + iso_b * (1 - 1e-9)
    # No schedule beats its own isolated latency inside a shared world
    # (1e-9 slack: float-sum reassociation only).
    assert comp.outcomes[0].latency >= iso_a * (1 - 1e-9)
    assert comp.outcomes[1].latency >= iso_b * (1 - 1e-9)


@settings(max_examples=15, deadline=None)
@given(builds, builds, builds, sizes)
def test_adding_a_schedule_never_speeds_up_existing(build_a, build_b,
                                                    build_c, size):
    """Contention monotonicity, modulo scheduling anomalies.

    Strict per-schedule monotonicity is FALSE in any FIFO resource world
    (Graham's timing anomalies: an extra stream can perturb event
    interleaving so an existing stream grabs a link earlier — observed up
    to ~10% on this simulator).  The invariants that DO hold, and that the
    serving results stand on: a stream never beats its own isolated
    latency, the makespan covers every stream, and per-resource busy time
    is strictly additive when streams are added.
    """
    scheds = [_build(TOPO, bd, size) for bd in (build_a, build_b, build_c)]
    two = run_composed(scheds[:2], TOPO)
    three = run_composed(scheds, TOPO)
    for k in range(2):
        # Anomalies reshuffle queueing; they cannot manufacture bandwidth:
        # a stream never beats its own isolated latency, however the world
        # around it changes (1e-9 slack: float-sum reassociation only).
        iso = simulate(scheds[k], TOPO, symmetric=False).latency
        assert three.outcomes[k].latency >= iso * (1 - 1e-9)
        assert three.makespan >= iso * (1 - 1e-9)
    # Resource-time conservation: the third stream only ADDS busy time —
    # on every resource the 3-stream world's busy equals the 2-stream
    # world's plus the newcomer's isolated busy (transfer durations are
    # closed-form, contention moves them without stretching them).
    iso_c = simulate(scheds[2], TOPO, symmetric=False)
    for res, busy3 in three.result.busy.items():
        expect = two.result.busy.get(res, 0.0) + iso_c.busy.get(res, 0.0)
        assert busy3 == pytest.approx(expect, rel=1e-9, abs=1e-15)


# ------------------------------------------------------------------------ #
# Contention serialization on a shared link                                #
# ------------------------------------------------------------------------ #

def test_shared_hostlink_serializes():
    a, b = _fetch(0), _fetch(0)
    iso = simulate(a, TOPO, symmetric=False)
    comp = run_composed([a, b], TOPO)
    link = "hostlink:0:h2d"
    # Byte-work conservation on the shared link: composed busy time is the
    # sum of the isolated busy times (same transfers, one timeline).
    assert comp.result.busy[link] == pytest.approx(2 * iso.busy[link],
                                                   rel=1e-9)
    # The link serializes: its busy time bounds the makespan from below,
    # and no overlap-free timeline can beat the sum of transfer times.
    assert comp.makespan >= comp.result.busy[link]
    assert comp.outcomes[1].finish >= 2 * iso.busy[link]
    # The second stream pays for the first: both cannot finish at 1x.
    assert comp.outcomes[1].finish > iso.latency
    # Intervals on one timeline never overlap.
    intervals = comp.result.timelines[link]
    for (s0, e0), (s1, e1) in zip(intervals, intervals[1:]):
        assert e0 <= s1 or s1 >= s0  # sorted, coalesced


def test_disjoint_resources_compose_with_zero_slowdown():
    a, b = _fetch(0), _fetch(1)
    ra = simulate(a, TOPO, symmetric=False)
    rb = simulate(b, TOPO, symmetric=False)
    comp = run_composed([a, b], TOPO)
    # Bit-identical finishes: nothing shared, nothing slowed.
    assert comp.outcomes[0].finish == ra.latency
    assert comp.outcomes[1].finish == rb.latency
    assert comp.outcomes[0].per_device[0].as_dict() == \
        ra.per_device[0].as_dict()
    assert comp.outcomes[1].per_device[1].as_dict() == \
        rb.per_device[1].as_dict()


def test_release_shift_translates_lone_schedule():
    sched = _fetch(2)
    iso = simulate(sched, TOPO, symmetric=False).latency
    shift = 1.25e-3
    comp = run_composed([sched], TOPO, [shift])
    assert comp.outcomes[0].finish == pytest.approx(shift + iso, rel=1e-12)
    assert comp.outcomes[0].latency == pytest.approx(iso, rel=1e-9)


# ------------------------------------------------------------------------ #
# Seeded workloads: determinism across processes                           #
# ------------------------------------------------------------------------ #

def test_workload_generators_deterministic():
    from repro.core.workload import (bursty_arrivals, poisson_arrivals,
                                      synthetic_workload)
    assert poisson_arrivals(100.0, 50, seed=3) == poisson_arrivals(
        100.0, 50, seed=3)
    assert bursty_arrivals(100.0, 50, seed=3) == bursty_arrivals(
        100.0, 50, seed=3)
    assert poisson_arrivals(100.0, 50, seed=3) != poisson_arrivals(
        100.0, 50, seed=4)
    w1 = synthetic_workload(20, 500.0, seed=9, kind="bursty")
    w2 = synthetic_workload(20, 500.0, seed=9, kind="bursty")
    assert w1 == w2
    # Arrivals are strictly increasing and shapes jittered within bounds.
    arr = [r.arrival for r in w1]
    assert arr == sorted(arr)
    assert all(1536 <= r.prompt_tokens <= 2560 for r in w1)


def test_workload_deterministic_across_processes():
    code = ("from repro.core.workload import poisson_arrivals; "
            "print(repr(poisson_arrivals(250.0, 8, seed=42)))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, check=True).stdout.strip()
    from repro.core.workload import poisson_arrivals
    assert out == repr(poisson_arrivals(250.0, 8, seed=42))


def test_bursty_mean_rate_is_normalized():
    from repro.core.workload import bursty_arrivals
    arr = bursty_arrivals(200.0, 4000, seed=0)
    rate = len(arr) / arr[-1]
    assert rate == pytest.approx(200.0, rel=0.15)


# ------------------------------------------------------------------------ #
# Golden trace: one small composed serving run, pinned exactly             #
# ------------------------------------------------------------------------ #

def test_golden_serving_trace():
    """Per-request TTFTs of a small contended run, byte-for-byte.

    The whole §12 stack — seeded workload, admission, remainder carryover,
    run_composed — is deterministic pure Python/numpy, so exact float
    equality is the right pin: any behavioral drift (event ordering, tag
    namespacing, fluid-progress accounting) shows up here first.
    """
    from repro.core.serving_model import ServingConfig, ServingSimulator
    from repro.core.workload import synthetic_workload
    wl = synthetic_workload(6, 1800.0, seed=11, kind="bursty",
                            prompt_tokens=2048, output_tokens=2,
                            burst_factor=10.0, p_enter=0.4, p_exit=0.1)
    rep = ServingSimulator(ServingConfig()).run(wl)
    assert [t.ttft for t in rep.timings] == GOLDEN_TTFTS
    assert rep.makespan == GOLDEN_MAKESPAN


GOLDEN_TTFTS = [
    0.006820849473559791,
    0.006701581586704549,
    0.006746553942608438,
    0.00741472298598555,
    0.007356276562592923,
    0.007392437572635273,
]
GOLDEN_MAKESPAN = 0.0106606932103967
