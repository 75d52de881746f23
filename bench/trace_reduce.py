"""Reduction of a ``--trace 1`` profile to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. On a TPU each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Modules`` holds one event per execution of a jitted program (named
``jit_<function>(<fingerprint>)``) and whose line ``XLA Ops`` holds the HLO
operations inside them, nested (a while loop's event spans its body's). The
host plane ``/host:CPU`` holds the Python thread's events, among them the
benchmark's own ``TraceAnnotation`` spans (``setup``, ``solve``, ``grid``,
``check``).

The traced window runs from the start of the first measured span (``solve``
or ``grid``) to the end of the last. Within it, per chip:

  busy       the union of the program executions' intervals;
  modules    executions and device seconds per jitted program, by name;
  collectives  self time of the all-reduce / all-gather / reduce-scatter /
             collective-permute / all-to-all operations;
  device_ops the operations with the most self time (time not covered by
             an operation nested inside them);
  idle_gaps  the stretches with no execution, each put down to the
             innermost host event on the benchmark's thread at its middle.

Busy time, executions, device seconds and collectives are averaged over
the chips.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil

MEASURED = ("solve", "grid")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
TOP = 10


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    """``jit__outer_step(6168915524674769513)`` -> ``jit__outer_step``."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """``%fusion.6 = f32[20000]{0} fusion(...)`` -> ``fusion.6``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def self_times(events):
    """Self time of each event of one line whose events nest: its length
    less the lengths of the events directly inside it."""
    out = {}
    stack = []                        # [start, end, name, child_total]
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and s >= stack[-1][1]:
            _close(stack, out)
        stack.append([s, e, name, 0.0])
    while stack:
        _close(stack, out)
    return out


def _close(stack, out):
    s, e, name, child = stack.pop()
    out[name] = out.get(name, 0.0) + max(e - s - child, 0.0)
    if stack:
        stack[-1][3] += e - s


def reduce_profile(pd) -> dict:
    """The device numbers of a ``ProfileData``; see the module docstring."""
    host_lines = [l for pl in pd.planes if pl.name == "/host:CPU"
                  for l in pl.lines]
    spans, marks = [], []
    for line in host_lines:
        ev = _events(line)
        measured = [t for t in ev if t[2] in MEASURED]
        if measured:
            spans.extend(measured)
            marks.extend(ev)
    if not spans:
        raise ValueError("no measured span (solve, grid) in the trace")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    window_ns = hi - lo

    devices = [pl for pl in pd.planes if DEVICE_PLANE.match(pl.name)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy_ns = coll_ns = 0.0
    modules, ops, gaps = {}, {}, {}
    marks.sort()
    starts = [s for s, _, _ in marks]
    for plane in devices:
        lines = {l.name: l for l in plane.lines}
        mods = [t for t in _events(lines["XLA Modules"])
                if lo <= t[0] < hi] if "XLA Modules" in lines else []
        busy = _union(_clip([(s, e) for s, e, _ in mods], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for s, e, name in mods:
            m = modules.setdefault(module_name(name),
                                   {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += (e - s) * 1e-9
        if "XLA Ops" in lines:
            for name, t in self_times(
                    [x for x in _events(lines["XLA Ops"])
                     if lo <= x[0] < hi]).items():
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + t
                if COLLECTIVE.search(key):
                    coll_ns += t
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                what = _innermost(marks, starts, (a + b) / 2)
                gaps[what] = gaps.get(what, 0.0) + (b - a)
    k = len(devices)
    for m in modules.values():
        m["count"] /= k
        m["seconds"] /= k
    return {
        "n_devices": k,
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9 / k,
        "collective_s": coll_ns * 1e-9 / k,
        "modules": modules,
        "breakdown": {
            "device_ops": _top(ops, k),
            "idle_gaps": _top(gaps, k),
        },
    }


def _innermost(marks, starts, t):
    """Name of the latest-starting host event that covers time t."""
    i = bisect.bisect_right(starts, t)
    best = None
    for s, e, name in marks[max(0, i - 4096):i]:
        if e >= t and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "(no host event)"


def _top(d, k):
    return [[name, ns * 1e-9 / k]
            for name, ns in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


class WindowTracer:
    """Profiles the start of a measured window: ``start`` at the window's
    start, ``tick(elapsed)`` after each unit of work (stops once
    `seconds` have passed), ``stop`` at the window's end, ``reduce``
    after it."""

    def __init__(self, directory: str, seconds: float):
        self.directory = directory
        self.seconds = seconds
        self.on = False

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        self.on = True

    def tick(self, elapsed: float):
        if self.on and elapsed >= self.seconds:
            self.stop()

    def stop(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def reduce(self) -> dict:
        found = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise ValueError(f"no trace under {self.directory}")
        return reduce_file(max(found, key=os.path.getmtime))
