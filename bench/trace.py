"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time. It reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData`` (the tests build a ``Trace`` by hand).

Device events are the operations on the accelerator's planes
(``/device:TPU:<n>``), on the line of XLA operations, and the executables on
the line of XLA modules. Host spans are the benchmark's own
``TraceAnnotation`` spans, named ``bench.<what>``. All times are in
nanoseconds on the trace's clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: device lines: the XLA operations, and the executables (modules) they run in
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns
    dur: int            # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """What one traced window holds, per device and for the host."""

    ops: Dict[str, List[Event]]        # device plane -> XLA operations
    modules: Dict[str, List[Event]]    # device plane -> executables
    spans: List[Event]                 # the benchmark's host spans

    @property
    def devices(self) -> List[str]:
        return sorted(set(self.ops) | set(self.modules))


def load_xplane(path: str) -> Trace:
    """Read the device and host events of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = collections.defaultdict(list)
    modules: Dict[str, List[Event]] = collections.defaultdict(list)
    spans: List[Event] = []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name in OPS_LINES + MODULE_LINES:
                out = ops if line.name in OPS_LINES else modules
                out[plane.name].extend(
                    Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
            elif not on_device:
                spans.extend(
                    Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    for v in list(ops.values()) + list(modules.values()):
        v.sort(key=lambda e: e.start)
    spans.sort(key=lambda e: e.start)
    return Trace(dict(ops), dict(modules), spans)


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# -- reductions --------------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` that the disjoint ``intervals`` cover."""
    return sum(e - s for s, e in clip(intervals, lo, hi))


class Busy:
    """Disjoint sorted busy intervals with prefix sums, so that the busy
    time inside any ``[lo, hi)`` takes two bisections."""

    def __init__(self, intervals: Sequence[Tuple[int, int]]):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.cum = [0]
        for s, e in intervals:
            self.cum.append(self.cum[-1] + e - s)

    def upto(self, t: int) -> int:
        """Busy nanoseconds before instant ``t``."""
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        return self.cum[i - 1] + min(self.ends[i - 1], t) \
            - self.starts[i - 1]

    def within(self, lo: int, hi: int) -> int:
        return self.upto(hi) - self.upto(lo) if hi > lo else 0


def busy_intervals(trace: Trace, device: str) -> List[Tuple[int, int]]:
    """When the device ran an operation (its executables where the trace
    has no operation line)."""
    evs = trace.ops.get(device) or trace.modules.get(device, [])
    return union((e.start, e.end) for e in evs)


def idle_gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int):
    """The gaps in ``[lo, hi)`` where the device ran nothing."""
    gaps, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(spans: Sequence[Event], t: int) -> str:
    """Name of the innermost benchmark span open at instant ``t``."""
    best = None
    for s in spans:
        if s.start <= t < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else "outside_spans"


def op_name(e: Event) -> str:
    """The HLO instruction's name (``%copy.76 = bf16[...] copy(...)`` gives
    ``copy.76``)."""
    return e.name.split(" = ", 1)[0].lstrip("%")


#: operations that only hold others (their time is their body's)
CONTAINERS = (" while(", " call(", " conditional(")


def kernel_events(trace: Trace, device: str, kernel: str) -> List[Event]:
    """The custom calls of a Mosaic kernel. XLA names each one after the
    function that launches it (``paged_attention.11``), so ``kernel`` is
    that function's name."""
    return [e for e in trace.ops.get(device, [])
            if op_name(e).split(".")[0] == kernel and "custom-call" in e.name]


def top_ops(trace: Trace, device: str, n: int = 10):
    """The ``n`` operations with the most device time, in seconds, by
    instruction name; loops and calls are left out, their bodies count."""
    total: Dict[str, int] = collections.Counter()
    for e in trace.ops.get(device, []):
        if not any(c in e.name for c in CONTAINERS):
            total[op_name(e)] += e.dur
    return [[k, v * 1e-9] for k, v in total.most_common(n)]


def labelled_gaps(gaps, spans, n: int = 10):
    """The ``n`` longest idle gaps, each named by the host span it began
    in, in seconds."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    return [[span_at(spans, s), (e - s) * 1e-9] for s, e in longest]
