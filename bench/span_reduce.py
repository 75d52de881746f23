"""Program spans and named stages in a ``--trace 1`` profile.

The program writes a profiler annotation ``repro/<name>`` for each phase of
its drivers (``solve > outer > dispatch, sync``; ``grid > grid_round >
grid_lanes, grid_dispatch, grid_sync, grid_retire, grid_bank, grid_fill,
grid_schedule``) and names the stages of its fused step with
``jax.named_scope`` (score, select, gather, inner_solve, scatter,
telemetry, chunk_control), which lands in each HLO op's framework name.
Within the window that ``trace_reduce`` takes (the same window, the same
busy intervals and idle gaps per chip):

  spans         count and host seconds per ``repro/*`` span;
  idle_by_span  device-idle time, each gap put down to the innermost
                ``repro/*`` span covering its middle, else ``NO_SPAN``;
  modules_by_span  program executions whose device start lies inside a
                span, per span name and program (every enclosing span
                counts, not only the innermost);
  stages        device self time per program and stage.

An op's stage is the first stage scope in its framework name, which
xprof's ``hlo_stats`` tool gives per (program id, HLO op name): the program
id is the number in the module event's name
(``jit__outer_step(3440402036843992252)``). An op whose name holds no stage
(a ``while`` or ``cond`` has none) takes the stage of the ops nested in its
event, ``MIXED`` where those span several stages; one with nothing nested
(a fusion XLA built itself inside a loop body, with no name) takes the
stage of the op whose event encloses it. The rest, and program time that
no op covers, is ``UNATTRIBUTED``. A program whose ops name no
stage at all (one built without scopes, or an eager op) gets no entry.
Seconds and counts are averaged over the chips. A profile with no span or
scope (a program that writes none) gives empty tables, never an error.

The metric readers call :func:`of_run`, which reduces the run's profile
once, keeps the tables on the run's ``trace`` under the keys above, and
adds ``idle_by_span`` and ``stages`` as ``[name, seconds]`` lists to the
breakdown the result line prints.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re

from . import trace_reduce as tr
from .harness import ROOT

PREFIX = "repro/"
NO_SPAN = "(no program span)"
STAGES = ("score", "select", "gather", "inner_solve", "scatter",
          "telemetry", "chunk_control")
MIXED = "chunk_control"
UNATTRIBUTED = "unattributed"
_WRAPPED = re.compile(r"^(?!p?jit\()[\w.]+\((.*)\)$")   # vmap(score)
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def stage_scopes(framework_name: str) -> list:
    """The stage scopes in an op's framework name, outermost first. The
    last component is the primitive (``gather``, ``scatter``...), never a
    scope; a transform's wrapper is taken off (``vmap(score)`` -> score),
    while ``jit(f)`` names a function, never a scope (``jit(scatter)``)."""
    out = []
    for part in framework_name.rstrip(":").split("/")[:-1]:
        m = _WRAPPED.match(part)
        name = m.group(1) if m else part
        if name in STAGES:
            out.append(name)
    return out


def stage_of(framework_name: str):
    scopes = stage_scopes(framework_name)
    return scopes[0] if scopes else None


def program_id(module_event_name: str):
    """``jit__outer_step(3440402036843992252)`` -> ``3440402036843992252``."""
    m = _PROGRAM_ID.search(module_event_name)
    return m.group(1) if m else None


def framework_names(xplane_path: str):
    """{(program id, HLO op name): framework op name} from xprof's
    ``hlo_stats`` tool, or None where xprof cannot read the profile."""
    try:
        from xprof.convert import _pywrap_profiler_plugin as xprof
    except ImportError:
        return None
    data, ok = xprof.xspace_to_tools_data(
        [xplane_path], "hlo_stats", {"use_saved_result": False})
    if not ok:
        return None
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    pid, op, fw = (cols.index(k) for k in
                   ("program_id", "hlo_op_name", "tf_op_name"))
    out = {}
    for row in table["rows"]:
        v = [c.get("v") for c in row["c"]]
        out[(str(v[pid]), str(v[op]))] = str(v[fw] or "").rstrip(":")
    return out


def host_spans(pd) -> list:
    """(start, end, name) of every ``repro/*`` event on the host plane,
    sorted by start."""
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s = float(e.start_ns)
                    out.append((s, s + float(e.duration_ns), e.name))
    out.sort()
    return out


def span_table(spans, lo, hi) -> dict:
    """{name: {count, seconds}} of the spans that start in [lo, hi), their
    host time clipped to it."""
    out = {}
    for s, e, name in spans:
        if lo <= s < hi:
            rec = out.setdefault(name, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += (min(e, hi) - s) * 1e-9
    return out


class _Cover:
    """The spans covering a time, innermost last (spans of one thread
    nest, so the latest-starting one covering t is the innermost)."""

    def __init__(self, spans):
        self.spans = spans
        self.starts = [s for s, _, _ in spans]

    def at(self, t) -> list:
        i = bisect.bisect_right(self.starts, t)
        return [sp for sp in self.spans[max(0, i - 4096):i] if sp[1] >= t]


def idle_by_span(cover: _Cover, gaps) -> dict:
    """{span name or NO_SPAN: ns} of the idle intervals `gaps`, each put
    down to the innermost span covering its middle."""
    out = {}
    for a, b in gaps:
        covering = cover.at((a + b) / 2)
        what = covering[-1][2] if covering else NO_SPAN
        out[what] = out.get(what, 0.0) + (b - a)
    return out


def modules_by_span(cover: _Cover, modules) -> dict:
    """{span name: {program: executions}} of the executions `modules`
    ((start, end, name)) by every span covering their device start."""
    out = {}
    for s, _, name in modules:
        program = name.split("(", 1)[0]
        for span in {sp[2] for sp in cover.at(s)}:
            per = out.setdefault(span, {})
            per[program] = per.get(program, 0) + 1
    return out


def stage_times(modules, ops, names) -> dict:
    """{program: {stage: ns}}: device self time of the ops `ops`
    ((start, end, HLO op name)) inside the executions `modules`, by stage.
    `names` is ``framework_names``' map."""
    out = {}
    mods = sorted(modules)
    starts = [s for s, _, _ in mods]
    per_module = [[] for _ in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < mods[i][1]:
            per_module[i].append(op)
    for (ms, me, mname), mops in zip(mods, per_module):
        pid = program_id(mname)
        stages, covered = _op_stages(
            mops, lambda op: stage_of(names.get((pid, op), "")))
        if not any(k in STAGES for k in stages):
            continue
        stages[UNATTRIBUTED] = stages.get(UNATTRIBUTED, 0.0) + max(
            me - ms - covered, 0.0)
        prog = out.setdefault(mname.split("(", 1)[0], {})
        for k, v in stages.items():
            prog[k] = prog.get(k, 0.0) + v
    return out


def _op_stages(ops, stage):
    """({stage: self ns}, ns covered by top-level ops) of one execution's
    nested ops; see the module docstring for ops that name no stage."""
    nodes = []              # [start, end, parent, stage, child ns, below]
    stack = []
    for s, e, name in sorted(ops, key=lambda t: (t[0], -t[1])):
        while stack and s >= nodes[stack[-1]][1]:
            stack.pop()
        nodes.append([s, e, stack[-1] if stack else -1, stage(name), 0.0,
                      set()])
        stack.append(len(nodes) - 1)
    for s, e, parent, own, _, below in reversed(nodes):   # children first
        if parent >= 0:
            nodes[parent][4] += e - s
            nodes[parent][5] |= {own} if own is not None else below
    out, resolved = {}, []
    covered = 0.0
    for s, e, parent, own, child, below in nodes:         # parents first
        if own is None:
            if below:
                own = next(iter(below)) if len(below) == 1 else MIXED
            else:
                own = resolved[parent] if parent >= 0 else UNATTRIBUTED
        resolved.append(own)
        out[own] = out.get(own, 0.0) + max(e - s - child, 0.0)
        if parent < 0:
            covered += e - s
    return out, covered


def window_chips(pd):
    """(lo, hi, chips) of a profile: ``trace_reduce``'s window and, per
    chip, its executions, ops and idle gaps in the window as
    (start, end, program event name), (start, end, HLO op name) and
    (start, end)."""
    measured = [t for pl in pd.planes if pl.name == "/host:CPU"
                for line in pl.lines for t in tr._events(line)
                if t[2] in tr.MEASURED]
    if not measured:
        raise ValueError("no measured span (solve, grid) in the trace")
    lo = min(s for s, _, _ in measured)
    hi = max(e for _, e, _ in measured)
    chips = []
    for plane in pd.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = [t for t in tr._events(lines["XLA Modules"])
                if lo <= t[0] < hi] if "XLA Modules" in lines else []
        ops = [(s, e, tr.op_name(n)) for s, e, n in tr._events(
            lines["XLA Ops"]) if lo <= s < hi] if "XLA Ops" in lines else []
        busy = tr._union(tr._clip([(s, e) for s, e, _ in mods], lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        chips.append((mods, ops, gaps))
    return lo, hi, chips


def reduce_profile(pd, names) -> dict:
    """The span and stage tables of a ``ProfileData``; `names` is
    ``framework_names``' map, or None (then no stage table)."""
    lo, hi, chips = window_chips(pd)
    spans = host_spans(pd)
    cover = _Cover(spans)
    idle, launches, stages = {}, {}, {}
    for modules, ops, gaps in chips:
        for name, ns in idle_by_span(cover, gaps).items():
            idle[name] = idle.get(name, 0.0) + ns
        for span, per in modules_by_span(cover, modules).items():
            acc = launches.setdefault(span, {})
            for prog, n in per.items():
                acc[prog] = acc.get(prog, 0) + n
        if names is not None:
            for prog, per in stage_times(modules, ops, names).items():
                acc = stages.setdefault(prog, {})
                for stage, ns in per.items():
                    acc[stage] = acc.get(stage, 0.0) + ns
    k = max(len(chips), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "spans": span_table(spans, lo, hi),
        "idle_by_span": {n: ns * 1e-9 / k for n, ns in idle.items()},
        "modules_by_span": {s: {p: n / k for p, n in per.items()}
                            for s, per in launches.items()},
        "stages": {p: {st: ns * 1e-9 / k for st, ns in per.items()}
                   for p, per in stages.items()},
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), framework_names(path))


def breakdown(reduced: dict) -> dict:
    """The ``[name, seconds]`` lists the result line prints: idle time by
    span, and stage time as ``<program>/<stage>``, largest first."""
    stages = [[f"{prog}/{st}", sec]
              for prog, per in reduced["stages"].items()
              for st, sec in per.items()]
    return {
        "idle_by_span": sorted(([n, s] for n, s in
                                reduced["idle_by_span"].items()),
                               key=lambda kv: -kv[1]),
        "stages": sorted(stages, key=lambda kv: -kv[1]),
    }


KEYS = ("spans", "idle_by_span", "modules_by_span", "stages")


def of_run(run, trace_dir: str | None = None):
    """The span and stage tables of a traced run, or None for an untraced
    one. The first call reduces the newest profile under `trace_dir` (the
    harness's default, ``<checkout>/.bench-trace``), checks that its window
    is the run's, and keeps the tables on ``run.trace`` (empty where no
    such profile is found); later calls read them from there."""
    t = run.trace
    if t is None:
        return None
    if "spans" not in t:
        t.update({k: {} for k in KEYS})
        found = glob.glob(os.path.join(
            trace_dir or str(ROOT / ".bench-trace"), "**", "*.xplane.pb"),
            recursive=True)
        if found:
            reduced = reduce_file(max(found, key=os.path.getmtime))
            if abs(reduced["window_s"] - t["window_s"]) <= 1e-9:
                t.update({k: reduced[k] for k in KEYS})
                t["breakdown"].update(breakdown(reduced))
    return t
