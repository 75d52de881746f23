"""Device self time of the collectives in a ``--trace 1`` profile, per
program, for the readers of the mesh's collectives.

An op is a collective by its HLO category in xprof's ``hlo_stats`` tool
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all),
looked up per (program id, HLO op name): its name does not tell, as XLA names
an all-reduce after the primitive that made it (``psum.59``, ``pmax.7``).
Within ``span_reduce``'s window, each op's self time counts for the program
whose execution holds it; seconds are averaged over the chips.
"""
from __future__ import annotations

import bisect
import glob
import json
import os

from bench import span_reduce as sr
from bench import trace_reduce as tr
from bench.harness import ROOT

CATEGORIES = ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all")
KEY = "collective_by_program"


def categories(xplane_path: str):
    """{(program id, HLO op name): category} from ``hlo_stats``, or None
    where xprof cannot read the profile."""
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
    pid, op, cat = (cols.index(k) for k in
                    ("program_id", "hlo_op_name", "category"))
    out = {}
    for row in table["rows"]:
        v = [c.get("v") for c in row["c"]]
        out[(str(v[pid]), str(v[op]))] = str(v[cat] or "")
    return out


def collective_seconds(chips, cats) -> dict:
    """{program: seconds of collective self time}, averaged over `chips`
    (``span_reduce.window_chips``' per-chip executions and ops)."""
    out = {}
    for mods, ops, _ in chips:
        mods = sorted(mods)
        starts = [s for s, _, _ in mods]
        per_module = [[] for _ in mods]
        for op in ops:
            i = bisect.bisect_right(starts, op[0]) - 1
            if i >= 0 and op[0] < mods[i][1]:
                per_module[i].append(op)
        for (_, _, mname), mops in zip(mods, per_module):
            pid = sr.program_id(mname)
            ns = sum(t for name, t in tr.self_times(mops).items()
                     if cats.get((pid, name)) in CATEGORIES)
            prog = tr.module_name(mname)
            out[prog] = out.get(prog, 0.0) + ns * 1e-9
    k = max(len(chips), 1)
    return {p: s / k for p, s in out.items()}


def of_run(run, trace_dir: str | None = None):
    """The run's ``{program: collective seconds}``, or None for an untraced
    run. As ``span_reduce.of_run``: the first call reads the newest profile
    under `trace_dir` (default ``<checkout>/.bench-trace``) if its window is
    the run's, and keeps the table on ``run.trace``; it is empty where no
    such profile, or no category table, is found."""
    t = run.trace
    if t is None:
        return None
    if KEY not in t:
        t[KEY] = {}
        found = glob.glob(os.path.join(
            trace_dir or str(ROOT / ".bench-trace"), "**", "*.xplane.pb"),
            recursive=True)
        if found:
            from jax.profiler import ProfileData
            path = max(found, key=os.path.getmtime)
            lo, hi, chips = sr.window_chips(ProfileData.from_file(path))
            cats = categories(path)
            if cats is not None and abs((hi - lo) * 1e-9
                                        - t["window_s"]) <= 1e-9:
                t[KEY] = collective_seconds(chips, cats)
    return t[KEY]
