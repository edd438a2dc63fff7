"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the metrics read.

This is the one place where device time is computed.  ``load`` reads the
file into plain intervals; everything after it is arithmetic on
``(start_ns, end_ns)`` pairs, so it can be checked on hand-made input.

Device operations come from the planes ``/device:TPU:<n>``: their line
``XLA Ops`` holds one event per executed HLO operation, named by the HLO
instruction's text (``%fusion.3 = bf16[...] fusion(...)``, kept here as
``fusion.3``), and ``XLA Modules`` one per executed program
(``jit_decode_step(<fingerprint>)``).  A trace recorded on the CPU has no device plane;
there the events that carry an ``hlo_op`` stat, on the host's threads, stand
in for them, keyed by their ``device_ordinal`` stat.  Host spans are the
events of the Python thread of ``/host:CPU`` (``python`` or ``python3``):
the benchmark's own ``TraceAnnotation`` spans and the runtime's calls
beneath them.  Host and device clocks are those the profiler writes; on a
v5e the device's events sat about 1 ms early against the host's, which
moves nothing measured over seconds.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[tuple[str, int, int]]]       # device -> (op, start, end)
    modules: dict[int, list[tuple[str, int, int]]]   # device -> (program, start, end)
    host: list[tuple[str, int, int]]                 # python thread spans

    def host_window(self, name: str) -> tuple[int, int]:
        """(start, end) of the first host span called ``name``."""
        for n, s, e in self.host:
            if n == name:
                return s, e
        raise KeyError(f"no host span {name!r} in the trace")


def _op_name(name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list] = defaultdict(list)
    modules: dict[int, list] = defaultdict(list)
    host: list = []
    cpu_ops: dict[int, list] = defaultdict(list)
    cpu_modules: dict[tuple, list] = {}
    for plane in data.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    dst[dev].append((_op_name(ev.name), s, s + int(ev.duration_ns)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if line.name in ("python", "python3"):
                        host.append((ev.name, s, e))
                        continue
                    st = _stats(ev)
                    if "hlo_op" not in st:
                        continue
                    dev = int(st.get("device_ordinal", 0))
                    cpu_ops[dev].append((ev.name, s, e))
                    key = (dev, st.get("hlo_module", "?"), st.get("run_id"))
                    lo, hi = cpu_modules.get(key, (s, e))
                    cpu_modules[key] = (min(lo, s), max(hi, e))
    if not ops and cpu_ops:
        ops = cpu_ops
        for (dev, name, _), (s, e) in cpu_modules.items():
            modules[dev].append((name, s, e))
    for d in (ops, modules):
        for lst in d.values():
            lst.sort(key=lambda t: (t[1], -t[2]))
    ops = {dev: leaves(lst) for dev, lst in ops.items()}
    host.sort(key=lambda t: (t[1], -t[2]))
    return Trace(dict(ops), dict(modules), host)


def leaves(events):
    """Drop the events that enclose the next one: a ``while`` loop's event
    on a TPU's ``XLA Ops`` line spans the operations of its body, and
    counting it would mark a whole loop busy and every collective in it
    as hidden.  ``events`` sorted by (start, -end)."""
    return [ev for ev, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


# ------------------------------------------------------ interval arithmetic
def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of ``(start, end)`` pairs that lie inside [lo, hi)."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Merge overlapping ``(start, end)`` pairs into disjoint ones."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The parts of [lo, hi) that the disjoint, sorted ``busy`` leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# --------------------------------------------------------------- summaries
def busy_ns(trace: Trace, dev: int, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which some operation ran on ``dev``."""
    return total(union(clip([(s, e) for _, s, e in trace.ops.get(dev, [])], lo, hi)))


def op_seconds(trace: Trace, devs, lo: int, hi: int) -> dict[str, float]:
    """Device seconds per operation name inside [lo, hi), averaged over
    ``devs``."""
    acc: dict[str, int] = defaultdict(int)
    for d in devs:
        for name, s, e in trace.ops.get(d, []):
            acc[name] += total(clip([(s, e)], lo, hi))
    return {k: v / 1e9 / len(devs) for k, v in acc.items()}


def modules_named(trace: Trace, dev: int, pattern: str, lo: int, hi: int):
    """Program executions on ``dev`` whose name matches ``pattern`` and that
    start inside [lo, hi)."""
    rx = re.compile(pattern)
    return [(n, s, e) for n, s, e in trace.modules.get(dev, [])
            if rx.search(n) and lo <= s < hi]


def host_segments(trace: Trace, skip: tuple[str, ...] = ()):
    """Cut the host timeline into pieces, each named by the innermost host
    span that covers it: sorted ``(start, end, name)``.  Spans nest on one
    thread, so a stack holds the ones open at any time."""
    segs, stack, t = [], [], None
    events = [h for h in trace.host if h[0] not in skip]

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][2] <= limit:
            name, _, end = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for name, s, e in events:
        if t is None:
            t = s
        close_until(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][0]))
        t = max(t, s)
        stack.append((name, s, e))
    if stack:
        close_until(max(e for _, _, e in stack))
    return segs


def idle_by_host(trace: Trace, dev: int, lo: int, hi: int, top: int = 10,
                 skip: tuple[str, ...] = ()) -> list[list]:
    """Stretches of [lo, hi) with no operation on ``dev``, summed by the
    innermost host span at each stretch's middle ("idle" where none):
    the ``top`` largest as ``[[host span, seconds], ...]``."""
    import bisect

    busy = union(clip([(s, e) for _, s, e in trace.ops.get(dev, [])], lo, hi))
    segs = host_segments(trace, skip)
    starts = [s for s, _, _ in segs]
    acc: dict[str, int] = defaultdict(int)
    for s, e in gaps(busy, lo, hi):
        mid = (s + e) // 2
        i = bisect.bisect_right(starts, mid) - 1
        name = segs[i][2] if i >= 0 and segs[i][1] > mid else "idle"
        acc[name] += e - s
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]
