"""The program's own phase spans and program names in a traced run.

``ServingEngine.step`` opens one ``serve.*`` span per host phase
(``repro.obs.spans``) on the profiler's host plane, on the clock of the
device's ``XLA Ops`` and ``XLA Modules`` lines, and each jitted program
of the serving path runs under a name of its own
(``jit_paged_chunk_prefill(<hash>)``).  This module reads, from the
traced run's xplane:

* the ``serve.*`` spans with their args (summed by ``step_args``);
* the first device's ``XLA Modules`` events by program name;
* each device-idle interval inside a ``serve.step``, cut at the span
  edges so that each piece lies in one innermost span, and labelled
  with the innermost span covering its midpoint.

A trace without ``serve.step`` spans (a program older than its phase
spans) reads as ``None``, and so does every metric read from it.

    python -m bench.spans <trace dir>

prints the per-phase idle table and the device time by program, with
the top ops inside each program.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import itertools
import os
import re

from . import spec
from .trace import _DEVICE, _OPS_LINE, _STEP, find_xplane, short_name

_MODULES_LINE = "XLA Modules"
_PREFIX = "serve."
_ROOT = "serve.step"
_PROGRAM = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: dict

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Spans:
    """A traced window's phase spans and the first device's programs.

    ``spans``: the ``serve.*`` spans by start; ``modules``: (program,
    start_ns, end_ns) by start; ``ops``: (start_ns, end_ns, name) of the
    first device's ops by start; ``window_ns``: (start, end) of the
    harness's ``engine.step`` annotations, where it has any."""
    spans: list
    modules: list
    ops: list
    window_ns: tuple

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    @property
    def steps(self) -> list:
        return self.named(_ROOT)

    def window_s(self) -> float:
        w0, w1 = self.window_ns
        return (w1 - w0) / 1e9


def program(module_name: str) -> str:
    """``jit_paged_chunk_prefill(1234)`` -> ``jit_paged_chunk_prefill``."""
    return _PROGRAM.match(module_name).group(1)


def reduce(planes):
    """``planes``: as for ``trace.reduce``.  None where the trace holds
    no ``serve.step`` span."""
    spans, modules, ops, steps = [], [], None, []
    for plane in planes:
        if _DEVICE.match(plane.name):
            if ops is not None:
                continue            # the first device only
            ops = []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops = [(float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            e.name) for e in line.events]
                elif line.name == _MODULES_LINE:
                    modules = [(program(e.name), float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns))
                               for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(_PREFIX):
                        spans.append(Span(e.name, float(e.start_ns),
                                          float(e.end_ns), dict(e.stats)))
                    elif e.name == _STEP:
                        steps.append((float(e.start_ns), float(e.end_ns)))
    if not any(s.name == _ROOT for s in spans):
        return None
    spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
    modules.sort(key=lambda m: m[1])
    ops = sorted(ops or [], key=lambda o: o[0])
    if steps:
        window = (min(s for s, _ in steps), max(e for _, e in steps))
    else:
        roots = [s for s in spans if s.name == _ROOT]
        window = (roots[0].start_ns, max(s.end_ns for s in roots))
    return Spans(spans=spans, modules=modules, ops=ops, window_ns=window)


@functools.lru_cache(maxsize=4)
def _load_file(path: str, mtime: float):
    import jax

    return reduce(jax.profiler.ProfileData.from_file(path).planes)


def load(trace_dir):
    """The reduced spans of the xplane under ``trace_dir`` (read once per
    file), or None where there is no trace or no ``serve.step`` span."""
    try:
        path = find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _load_file(path, os.path.getmtime(path))


def for_run(run):
    """The spans of a traced run: the harness writes its trace to
    ``.bench_trace/<cell>``.  None in an untraced run."""
    if run.trace is None:
        return None
    return load(spec.ROOT / ".bench_trace" / run.cell.name)


# -- device time against host phases -------------------------------------

def _busy_in(ops: list, reach: list, lo: float, hi: float) -> list:
    """The union of the op intervals clipped to [lo, hi], as sorted
    disjoint [start, end] pairs.  ``reach[i]``: the latest end among
    ``ops[:i + 1]``, so that a long op (a loop holding others) that
    started well before ``lo`` is still found."""
    out = []
    for s, e, _ in ops[bisect.bisect_right(reach, lo):]:
        if s >= hi:
            break
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(children: list, t: float):
    """The deepest span of ``children`` (one step's spans, by start)
    covering ``t``: the latest to start among those that cover it."""
    best = None
    for c in children:
        if c.start_ns > t:
            break
        if c.start_ns <= t <= c.end_ns:
            best = c
    return best


def step_idle(s: Spans) -> list:
    """Per ``serve.step``: (step span, [(label, idle ns), ...]) where the
    pieces are the device's idle time inside the step, cut at its child
    spans' edges, each labelled by the innermost span over it (the step
    itself where no child covers it)."""
    reach = list(itertools.accumulate((o[1] for o in s.ops), max))
    out = []
    roots = s.steps
    for root in roots:
        lo, hi = root.start_ns, root.end_ns
        children = [c for c in s.spans
                    if c is not root and c.name != _ROOT
                    and lo <= c.start_ns and c.end_ns <= hi]
        cuts = sorted({lo, hi} | {c.start_ns for c in children}
                      | {c.end_ns for c in children})
        busy = _busy_in(s.ops, reach, lo, hi)
        idle, t = [], lo
        for b0, b1 in busy:
            if b0 > t:
                idle.append((t, b0))
            t = max(t, b1)
        if hi > t:
            idle.append((t, hi))
        pieces = []
        for a, b in idle:
            i = bisect.bisect_right(cuts, a)
            edges = [a] + [c for c in cuts[i:] if c < b] + [b]
            for p0, p1 in zip(edges, edges[1:]):
                inner = _innermost(children, 0.5 * (p0 + p1))
                pieces.append((inner.name if inner else _ROOT, p1 - p0))
        out.append((root, pieces))
    return out


def idle_by_phase(s: Spans) -> dict:
    """Device idle inside ``serve.step``, in seconds, by innermost span."""
    total: dict = {}
    for _, pieces in step_idle(s):
        for label, ns in pieces:
            total[label] = total.get(label, 0.0) + ns / 1e9
    return total


def host_gap_ns(s: Spans) -> list:
    """Per ``serve.step``: its span minus the union of the first
    device's ops inside it, in ns."""
    return [sum(ns for _, ns in pieces) for _, pieces in step_idle(s)]


def program_seconds(s: Spans) -> dict:
    """Device time of the first device's programs by name, clipped to
    the traced window."""
    w0, w1 = s.window_ns
    out: dict = {}
    for name, a, b in s.modules:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def ops_by_program(s: Spans, top: int = 5) -> dict:
    """The leaf ops that took most device time inside each program, in
    the traced window: program -> [(op, seconds), ...]."""
    w0, w1 = s.window_ns
    starts = [m[1] for m in s.modules]
    by: dict = {}
    for a, b, name in s.ops:
        if not (w0 <= a < w1):
            continue
        short = short_name(name)
        if short.startswith(("while", "conditional", "call")):
            continue
        i = bisect.bisect_right(starts, a) - 1
        prog = s.modules[i][0] if i >= 0 and a < s.modules[i][2] \
            else "(outside any program)"
        ops = by.setdefault(prog, {})
        ops[short] = ops.get(short, 0.0) + (b - a) / 1e9
    return {p: sorted(o.items(), key=lambda kv: -kv[1])[:top]
            for p, o in by.items()}


def step_args(s: Spans) -> dict:
    """What the spans' args say over the trace: the queue (``waiting``)
    and the slots held (``active``) at each step's entry, as (mean, max);
    per program span (``serve.chunk``, ``serve.decode``) its calls and
    the summed live ``rows``, ``bucket`` rows and ``tokens``; and the
    requests preempted for decode blocks (``serve.reserve``)."""
    out = {}
    for key in ("waiting", "active"):
        vals = [x.args.get(key, 0) for x in s.steps]
        out[key] = (sum(vals) / len(vals), max(vals))
    for name in ("serve.chunk", "serve.decode"):
        calls = s.named(name)
        out[name] = dict(calls=len(calls), **{
            key: sum(x.args.get(key, 0) for x in calls)
            for key in ("rows", "bucket", "tokens")})
    out["preempted"] = sum(x.args.get("preempted", 0)
                           for x in s.named("serve.reserve"))
    return out


def describe(s: Spans) -> str:
    idle = idle_by_phase(s)
    total = sum(idle.values())
    steps = s.steps
    args = step_args(s)
    lines = [f"{len(steps)} serve.step spans over "
             f"{s.window_s():.6f} s; device idle inside them "
             f"{total:.6f} s",
             "at step entry: waiting mean {:.2f} max {}, active mean {:.2f} "
             "max {}; preempted for decode blocks {}".format(
                 *args["waiting"], *args["active"], args["preempted"])]
    for name in ("serve.chunk", "serve.decode"):
        a = args[name]
        fill = f" ({100 * a['rows'] / a['bucket']:.2f}%)" if a["bucket"] \
            else ""
        lines.append(f"{name:<13s} {a['calls']} calls: live rows "
                     f"{a['rows']} of {a['bucket']} bucket rows{fill}, "
                     f"{a['tokens']} tokens")
    lines += ["", "phase                 idle s      share"]
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<20s} {sec:10.6f} {100 * sec / total:9.2f}%"
                     if total else f"{name:<20s} {sec:10.6f}")
    progs = program_seconds(s)
    ops = ops_by_program(s)
    lines += ["", f"program device time over the {s.window_s():.6f} s "
              "window"]
    for name, sec in sorted(progs.items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:<40s} {sec:10.6f} s "
                     f"{100 * sec / s.window_s():6.2f}%")
        for op, t in ops.get(name, []):
            lines.append(f"    {t:10.6f} s  {op}")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    found = load(sys.argv[1])
    print("no serve.step spans in this trace" if found is None
          else describe(found))
