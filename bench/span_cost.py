"""What the program's spans cost on this host, with the profiler off and on.

    python3 bench/span_cost.py [--seconds 10] [--cells CELL ...]

Prints one JSON object:

  span_us       microseconds per span (enter and exit of ``repro.obs.span``,
                with no args and with two int args), profiler off and on;
  cells         per cell: its end-to-end metric over alternating windows
                with the profiler off and on (host tracer on, Python tracer
                off), and the ``repro/*`` spans per solve and per grid
                round in the profiled windows.

The windows are the cell's own driver (``bench/drivers``) after its
set-up, each of ``--seconds``; nothing is checked for correctness. Needs
the chip, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CELLS = ["dense_ar1.lasso_solve", "dense_ar1.lasso_cv_grid"]


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def span_us(n: int) -> dict:
    """Microseconds per span, best of 5 loops of n, profiler off and on."""
    import jax
    from repro.obs import span

    def bare():
        for _ in range(n):
            with span("sync"):
                pass

    def with_args():
        for i in range(n):
            with span("outer", it=i, bucket=64):
                pass

    def best(fn):
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            out.append((time.perf_counter() - t0) / n * 1e6)
        return min(out)

    res = {"off": {"bare": best(bare), "two_int_args": best(with_args)}}
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=_profile_options())
    try:
        res["on"] = {"bare": best(bare), "two_int_args": best(with_args)}
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return res


class _Profiled:
    """A window's tracer hook: the whole window under the profiler."""

    def __init__(self, directory):
        self.directory = directory

    def start(self):
        import jax
        jax.profiler.start_trace(self.directory,
                                 profiler_options=_profile_options())

    def tick(self, elapsed):
        del elapsed

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def _span_counts(directory) -> dict:
    from jax.profiler import ProfileData

    from bench.span_reduce import host_spans
    path = max(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = {}
    for _, _, name in host_spans(ProfileData.from_file(path)):
        out[name] = out.get(name, 0) + 1
    return out


def cell_costs(name: str, seconds: float, pairs: int) -> dict:
    from bench.harness import enable_compile_cache, find_cell
    cell = find_cell(name)
    enable_compile_cache()
    driver = cell.driver
    state = driver.setup(cell, 2**31 + 77)
    metric = "solve_s" if "solve_s" in {m["name"] for m in cell.end_to_end} \
        else "grid_fits_per_s"
    runs = {"off": [], "on": []}
    counts = None
    for i in range(2 * pairs):
        side = ("off", "on")[(i + i // 2) % 2]          # off on on off ...
        hook = None
        d = tempfile.mkdtemp()
        if side == "on":
            hook = _Profiled(d)
        counters = {}
        w = driver.window(state, seconds, hook, counters)
        runs[side].append(w["end_to_end"][metric])
        if side == "on" and counts is None:
            c = _span_counts(d)
            units = c.get("repro/solve") or c.get("repro/grid_round")
            counts = {"spans": c, "per_unit": sum(
                v for k, v in c.items() if k != "repro/grid") / units
                if units else None,
                "unit": "solve" if "repro/solve" in c else "grid_round"}
        shutil.rmtree(d, ignore_errors=True)
    return {"metric": metric, "off": runs["off"], "on": runs["on"],
            **(counts or {})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--cells", nargs="*", default=CELLS)
    ap.add_argument("--n", type=int, default=20000)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("span_cost: no TPU", file=sys.stderr)
        return 3
    out = {"device": jax.devices()[0].device_kind, "span_us": span_us(args.n),
           "cells": {c: cell_costs(c, args.seconds, args.pairs)
                     for c in args.cells}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
