"""Reduction of a JAX profiler trace to device busy time, kernel time
and idle gaps attributed to what the host was doing.

A trace is read with ``jax.profiler.ProfileData`` (nothing but JAX) into
flat event lists: device operations, device programs, and the host
spans the benchmark itself opens with ``jax.profiler.TraceAnnotation``
(names starting ``bench.``), with the Python calls the profiler records
on that thread inside them.  All times are nanoseconds on the trace's
common clock.

Which planes and lines hold device operations depends on the platform:

* TPU: planes ``/device:TPU:<i>``; the line ``XLA Ops`` holds one event
  per operation, ``XLA Modules`` one per program launch.
* CPU (used only to test this reduction): plane ``/host:CPU``; XLA's
  operations run on the ``tf_XLAPjRtCpuClient`` threads, between the
  thread pool's own bookkeeping events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Sequence

Interval = tuple[int, int]

SPAN_PREFIX = "bench."
_CPU_NOISE = re.compile(r"^(ThreadpoolListener|ThunkExecutor|end: )")


@dataclasses.dataclass
class Event:
    name: str
    start: int
    end: int
    device: int = 0

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: list[Event]        # device operations
    modules: list[Event]    # device program launches (TPU only)
    spans: list[Event]      # host thread of the benchmark's spans
    devices: int            # device planes seen

    def span(self, name: str) -> Interval | None:
        """Extent of all host spans called ``name``."""
        hits = [s for s in self.spans if s.name == name]
        if not hits:
            return None
        return min(s.start for s in hits), max(s.end for s in hits)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops: list[Event] = []
    modules: list[Event] = []
    spans: list[Event] = []
    devices = 0
    tpu = re.compile(r"^/device:TPU:(\d+)$")
    for plane in pd.planes:
        m = tpu.match(plane.name)
        if m:
            devices += 1
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(_events(line.events, dev))
                elif line.name == "XLA Modules":
                    modules.extend(_events(line.events, dev))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = list(_events(line.events, 0))
                if any(e.name.startswith(SPAN_PREFIX) for e in evs):
                    spans.extend(e for e in evs if e.dur > 0)
                if line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops.extend(e for e in evs if e.dur > 0
                               and not _CPU_NOISE.match(e.name))
    if not devices and ops:
        devices = 1
    return Trace(ops=ops, modules=modules, spans=spans, devices=devices)


def _events(events, device: int) -> Iterable[Event]:
    for ev in events:
        start = int(ev.start_ns)
        yield Event(ev.name, start, start + int(ev.duration_ns), device)


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Union of intervals as sorted disjoint intervals."""
    out: list[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: Sequence[Event], lo: int, hi: int,
            devices: int = 1) -> float:
    """Time in ``[lo, hi)`` in which an operation ran, averaged over
    ``devices``: the union of operation intervals on each device."""
    total = 0
    for dev in range(max(devices, 1)):
        mine = [(e.start, e.end) for e in events if e.device == dev]
        total += sum(e - s for s, e in merge(clip(mine, lo, hi)))
    return total / max(devices, 1)


def gaps(events: Sequence[Event], lo: int, hi: int) -> list[Interval]:
    """Idle intervals in ``[lo, hi)``: no operation ran on any device."""
    busy = merge(clip([(e.start, e.end) for e in events], lo, hi))
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost(spans: Sequence[Event]) -> list[tuple[int, int, str]]:
    """Split the time the nested ``spans`` of one host thread cover into
    segments, each named by the innermost span open in it."""
    pts = []
    for i, s in enumerate(spans):
        pts.append((s.start, 1, -s.end, i))
        pts.append((s.end, 0, 0, i))
    pts.sort()
    stack: list[int] = []
    segs: list[tuple[int, int, str]] = []
    prev = None
    for t, opening, _, i in pts:
        if stack and prev is not None and t > prev:
            segs.append((prev, t, spans[stack[-1]].name))
        if opening:
            stack.append(i)
        elif i in stack:
            del stack[len(stack) - 1 - stack[::-1].index(i)]
        prev = t
    return segs


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Event]) -> dict[str, int]:
    """Idle nanoseconds by the innermost host span open during them;
    idle time under no span counts as ``host``."""
    segs = innermost(spans)
    out: dict[str, int] = {}
    j = 0
    for g0, g1 in idle:
        covered = 0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            lo, hi = max(g0, segs[k][0]), min(g1, segs[k][1])
            if lo < hi:
                out[segs[k][2]] = out.get(segs[k][2], 0) + hi - lo
                covered += hi - lo
            k += 1
        if g1 - g0 > covered:
            out["host"] = out.get("host", 0) + (g1 - g0 - covered)
    return out


def sum_matching(events: Sequence[Event], pattern: str, lo: int,
                 hi: int) -> tuple[int, int]:
    """(summed duration clipped to the window, number of events) of the
    events whose name matches ``pattern`` (a regular expression)."""
    rx = re.compile(pattern)
    total = count = 0
    for e in events:
        if rx.search(e.name) and e.end > lo and e.start < hi:
            total += min(e.end, hi) - max(e.start, lo)
            count += 1
    return total, count


def named_by_module(ops: Sequence[Event],
                    modules: Sequence[Event]) -> list[Event]:
    """Operations renamed ``<module>/<op>`` after the program launch
    they ran in, with the HLO text after the op's name dropped."""
    import bisect
    mods = sorted(modules, key=lambda m: (m.device, m.start))
    keys = [(m.device, m.start) for m in mods]
    out = []
    for e in ops:
        op = e.name.split(" = ", 1)[0].lstrip("%")
        i = bisect.bisect_right(keys, (e.device, e.start)) - 1
        if i >= 0 and mods[i].device == e.device and mods[i].end >= e.end:
            op = mods[i].name.split("(", 1)[0] + "/" + op
        out.append(Event(op, e.start, e.end, e.device))
    return out


def top_ops(events: Sequence[Event], lo: int, hi: int,
            n: int = 10) -> list[list]:
    """The ``n`` operation names with the most device time, in seconds."""
    tot: dict[str, int] = {}
    for e in events:
        if e.end > lo and e.start < hi:
            tot[e.name] = tot.get(e.name, 0) + min(e.end, hi) - max(e.start,
                                                                    lo)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]
