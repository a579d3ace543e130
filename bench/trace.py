"""Reduce a ``jax.profiler`` trace to device busy time, op times and gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation that ran, with start and duration in
nanoseconds on the same clock as the host planes.  The host plane holds
the harness's ``engine.step`` annotations (``StepTraceAnnotation``).

* the traced window runs from the first step annotation's start to the
  last one's end;
* busy time is the union of the device's op intervals in that window,
  averaged over the device planes;
* each idle gap between ops is named by what the host was doing: inside
  ``engine.step`` (the program's host work) or between steps (the
  harness waiting for the next request, or submitting).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_STEP = "engine.step"
# ops that hold others in the trace: busy, but no leaf of the breakdown
_CONTAINERS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str, width: int = 120) -> str:
    """An op's HLO text without the leading ``%`` and the layouts, cut
    to ``width``: ``fusion.173 = (f32[4,8,3,256], ...) fusion(...)``."""
    return _LAYOUT.sub("", hlo.lstrip("%"))[:width]


@dataclasses.dataclass
class Op:
    name: str               # short_name of the HLO text
    start_ns: float
    dur_ns: float

    @property
    def leaf(self) -> bool:
        return not self.name.startswith(_CONTAINERS)


@dataclasses.dataclass
class Events:
    """A traced window, reduced: the first device's ops in the window,
    the host's step spans, and busy time averaged over devices."""
    ops: list
    steps: list              # (start_ns, end_ns) of each engine.step
    window_s: float
    busy_s: float
    gaps: list               # (label, seconds), longest first

    @property
    def n_ops(self) -> int:
        return len(self.ops)

    def op_seconds(self, match) -> float:
        """Device seconds of the leaf ops whose name ``match`` picks."""
        return sum(o.dur_ns for o in self.ops
                   if o.leaf and match(o.name)) / 1e9

    def count(self, match) -> int:
        return sum(1 for o in self.ops if o.leaf and match(o.name))


def find_xplane(trace_dir) -> str:
    found = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union_ns(intervals: list) -> float:
    total, end = 0.0, -float("inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _label(gap_start: float, gap_end: float, steps: list) -> str:
    mid = 0.5 * (gap_start + gap_end)
    for i, (s, e) in enumerate(steps):
        if s <= mid <= e:
            return f"host in engine.step {i}"
    return "host between steps"


def reduce(planes) -> Events:
    """``planes``: an iterable of objects with ``name`` and ``lines``
    (``jax.profiler.ProfileData`` planes, or the same shape in tests)."""
    steps, devices = [], []
    for plane in planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices.append([
                        Op(short_name(e.name), float(e.start_ns),
                           float(e.duration_ns)) for e in line.events])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name == _STEP:
                        steps.append((float(e.start_ns), float(e.end_ns)))
    if not devices or not steps:
        raise ValueError(f"trace has {len(devices)} device op lines and "
                         f"{len(steps)} {_STEP} spans")
    steps.sort()
    w0, w1 = steps[0][0], max(e for _, e in steps)

    def clipped(ops):
        return [(max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1))
                for o in ops if o.start_ns < w1 and o.start_ns + o.dur_ns > w0]

    busy = sum(_union_ns(clipped(ops)) for ops in devices) / len(devices)
    first = sorted((o for o in devices[0]
                    if w0 <= o.start_ns < w1), key=lambda o: o.start_ns)
    gaps, end = [], w0
    for o in first:
        if o.start_ns > end:
            gaps.append((_label(end, o.start_ns, steps),
                         (o.start_ns - end) / 1e9))
        end = max(end, o.start_ns + o.dur_ns)
    if w1 > end:
        gaps.append((_label(end, w1, steps), (w1 - end) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Events(ops=first, steps=steps, window_s=(w1 - w0) / 1e9,
                  busy_s=busy / 1e9, gaps=gaps)


def load(trace_dir) -> Events:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(
        find_xplane(trace_dir)).planes)


def breakdown(events: Events, top: int = 10) -> dict:
    """The leaf device ops that took most time (by name) and the longest
    idle gaps (by what the host was doing)."""
    by_name: dict = {}
    for o in events.ops:
        if not o.leaf:
            continue
        by_name[o.name] = by_name.get(o.name, 0.0) + o.dur_ns / 1e9
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[k, v] for k, v in events.gaps[:top]])


def describe(path) -> str:
    """Planes, lines and a few events of a raw trace, for reading one by
    hand: ``python -m bench.trace <dir>``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(find_xplane(path))
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            names: dict = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            for name, ns in sorted(names.items(), key=lambda kv: -kv[1])[:12]:
                ex = next(e for e in evs if e.name == name)
                out.append(f"    {short_name(name)!r}: {ns / 1e6:.3f} ms; "
                           f"e.g. start {ex.start_ns} dur {ex.duration_ns}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(sys.argv[1]))
