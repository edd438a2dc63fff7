"""Arithmetic on the program's own spans in a traced window.

The serving engine opens ``jax.profiler.TraceAnnotation`` spans named
``serve.*`` at its layer boundaries; they land on the Python thread of the
same trace as the device events, on its clock.  ``view`` is what
``bench/run.py`` hands a metric as ``run.view``: the reduced trace and the
window ``[lo, hi)``.  Spans are ``(start_ns, end_ns)`` pairs.  A trace of a
program that opens none of these spans gives empty lists, and the readers
then report nothing.
"""
from __future__ import annotations


def named(view, name: str) -> list[tuple[int, int]]:
    """Host spans called ``name`` that start inside the window."""
    return [(s, e) for n, s, e in view.trace.host if n == name and view.lo <= s < view.hi]


def _inside(span, outer) -> bool:
    s, e = span
    return any(os <= s and e <= oe for os, oe in outer)


def within(spans, outer) -> list[tuple[int, int]]:
    """The ``spans`` that lie inside one of the ``outer`` spans."""
    return [t for t in spans if _inside(t, outer)]


def outside(spans, outer) -> list[tuple[int, int]]:
    """The ``spans`` that lie inside none of the ``outer`` spans."""
    return [t for t in spans if not _inside(t, outer)]


def length_ns(spans) -> int:
    return sum(e - s for s, e in spans)


def self_ns(view, spans, children) -> int:
    """Time of ``spans`` that none of the ``children`` covers."""
    red = view.reduce
    return sum(e - s - red.total(red.union(red.clip(children, s, e))) for s, e in spans)


def idle_ns(view, spans) -> int:
    """Time of ``spans`` in which no program ran on the first chip.  Gaps
    between the operations of one program are the program's own, as in a
    program's device time (``decode_step_ms``), and not counted."""
    red = view.reduce
    runs = [(s, e) for _, s, e in view.trace.modules.get(view.devs[0], [])]
    return sum(e - s - red.total(red.union(red.clip(runs, s, e))) for s, e in spans)


def after_program_ns(view, spans, pattern: str) -> int:
    """Time of each span after the end of the last execution of a program
    matching ``pattern`` that started before the span ended: the part of a
    wait that outlasts the device's work (host and device clocks mixed)."""
    runs = view.reduce.modules_named(view.trace, view.devs[0], pattern, view.lo, view.hi)
    out = 0
    for s, e in spans:
        ends = [pe for _, ps, pe in runs if ps < e]
        if ends:
            out += max(0, e - max(s, ends[-1]))
    return out


def per_batch_ms(run, hit: bool, ns: int | None):
    """``ns`` over the window's batches of that kind, in ms; None where
    there are none."""
    n = sum(1 for b in run.batches if b["hit"] == hit)
    return ns / n / 1e6 if n and ns is not None else None
