"""The trace reduction on hand-made intervals and on a small trace recorded
on the CPU (three steps of one jitted program inside ``bench.window``)."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "cpu_step.xplane.pb"


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [(0, 4), (5, 7), (10, 11)]


def test_gaps_are_the_complement_inside_the_window():
    busy = [(2, 4), (6, 9)]
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (9, 10)]
    assert tr.gaps(busy, 3, 8) == [(4, 6)]
    assert tr.total(busy) + tr.total(tr.gaps(busy, 0, 10)) == 10


def test_clip_keeps_the_parts_inside_the_window():
    assert tr.clip([(0, 5), (8, 12), (10, 11)], 3, 10) == [(3, 5), (8, 10)]


def test_leaves_drop_enclosing_loop_events():
    ops = [("while.2", 0, 100), ("fusion.1", 0, 40), ("collective-permute-done.1", 40, 60),
           ("fusion.2", 70, 100), ("fusion.3", 120, 130)]
    assert tr.leaves(ops) == ops[1:]


def test_idle_time_goes_to_the_innermost_host_span():
    t = tr.Trace(ops={0: [("a", 0, 10), ("b", 30, 40), ("c", 90, 100)]}, modules={},
                 host=[("bench.window", 0, 100), ("bench.batch", 5, 95), ("fetch", 12, 28),
                       ("decode", 45, 85)])
    got = dict(tr.idle_by_host(t, 0, 0, 100, skip=("bench.window",)))
    assert got == pytest.approx({"fetch": 20e-9, "decode": 50e-9})
    assert tr.busy_ns(t, 0, 0, 100) == 30


def test_recorded_cpu_trace():
    t = tr.load(str(DATA))
    lo, hi = t.host_window("bench.window")
    steps = [h for h in t.host if h[0] == "bench.step"]
    assert len(steps) == 3
    runs = tr.modules_named(t, 0, r"jit__lambda", lo, hi)
    assert len(runs) == 3
    for (_, s, e), (_, hs, he) in zip(runs, steps):   # each program inside its step
        assert hs <= s < e <= he
    busy = tr.busy_ns(t, 0, lo, hi)
    assert 0 < busy < hi - lo
    ops = tr.op_seconds(t, [0], lo, hi)
    assert sum(ops.values()) * 1e9 >= busy       # ops may overlap on CPU threads
    idle = tr.idle_by_host(t, 0, lo, hi, skip=("bench.window",))
    assert sum(s for _, s in idle) == pytest.approx((hi - lo - busy) / 1e9)
