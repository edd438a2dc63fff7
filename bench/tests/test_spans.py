"""The span arithmetic of ``bench/spans.py`` on hand-made traces: self time,
the window, device idle inside a span, and the cut at a program's end."""
from types import SimpleNamespace

import pytest

import spans as sp
import trace_reduce as tr


def view(host, ops=(), modules=(), lo=0, hi=1000):
    t = tr.Trace(ops={0: list(ops)}, modules={0: list(modules)}, host=list(host))
    return SimpleNamespace(trace=t, lo=lo, hi=hi, devs=[0], reduce=tr)


def test_named_keeps_the_spans_that_start_in_the_window():
    v = view([("a", 5, 20), ("a", 90, 120), ("b", 30, 40), ("a", 150, 160)], lo=10, hi=150)
    assert sp.named(v, "a") == [(90, 120)]
    assert sp.named(v, "b") == [(30, 40)]
    assert sp.named(v, "c") == []


def test_self_time_leaves_out_nested_children():
    v = view([])
    fetches = [(0, 100), (200, 260)]
    copies = [(10, 30), (20, 50), (80, 120), (210, 220)]   # overlapping, one past its parent
    assert sp.self_ns(v, fetches, copies) == (100 - 40 - 20) + (60 - 10)
    assert sp.self_ns(v, fetches, []) == sp.length_ns(fetches) == 160


def test_within_and_outside_split_by_the_enclosing_span():
    first = [(0, 100)]
    builds = [(40, 60), (150, 170)]
    assert sp.within(builds, first) == [(40, 60)]
    assert sp.outside(builds, first) == [(150, 170)]


def test_idle_counts_the_time_no_program_runs_inside_each_span():
    progs = [("jit_decode_step(1)", 0, 6), ("jit__argmax(2)", 6, 7),
             ("jit_decode_step(1)", 9, 15), ("jit_decode_step(1)", 40, 46)]
    ops = [("fusion.1", 0, 2), ("fusion.2", 4, 6), ("fusion.1", 9, 15)]  # a gap inside a program
    v = view([], ops=ops, modules=progs)
    assert sp.idle_ns(v, [(0, 20)]) == 20 - 13
    assert sp.idle_ns(v, [(0, 20), (38, 48)]) == 7 + 4


def test_after_program_cuts_the_wait_at_the_program_end():
    progs = [("jit_prefill(1)", 10, 70), ("jit_decode_step(2)", 75, 80),
             ("jit_prefill(3)", 210, 250)]
    v = view([], modules=progs)
    pulls = [(20, 100),    # waits for the first prefill: counts 70..100
             (200, 240),   # ends before its prefill does: counts nothing
             (300, 330)]   # after the second prefill ended: counts it whole
    assert sp.after_program_ns(v, pulls, r"^jit_prefill\b") == 30 + 0 + 30
    assert sp.after_program_ns(v, [(0, 5)], r"^jit_prefill\b") == 0


def test_per_batch_divides_by_batches_of_the_kind():
    run = SimpleNamespace(batches=[{"hit": True}, {"hit": True}, {"hit": False}])
    assert sp.per_batch_ms(run, True, 3_000_000) == pytest.approx(1.5)
    assert sp.per_batch_ms(run, False, 3_000_000) == pytest.approx(3.0)
    assert sp.per_batch_ms(SimpleNamespace(batches=[{"hit": False}]), True, 5) is None
