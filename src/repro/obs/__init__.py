"""repro.obs — the solver observability layer (DESIGN.md §11).

Three parts, threaded through the whole solve stack:

  * device-side telemetry rings (:mod:`repro.obs.rings`): preallocated
    ``[max_outer]`` pytree buffers recording per-outer KKT/gap/working-set
    curves INSIDE the fused dispatch, drained once per solve;
  * program spans (:func:`span`, :mod:`repro.obs.trace`): always present,
    written into the profiler's own trace as ``repro/<name>`` whenever a
    profile is captured (``jax.profiler.trace``), on the device events'
    clock; with an ``Obs`` attached they are also recorded by a span tracer
    with Chrome-trace/Perfetto JSON export. A central
    :class:`MetricsRegistry` (:mod:`repro.obs.registry`) holds the counters
    the legacy ad-hoc attributes are views into;
  * result surfaces: ``SolveResult.diagnostics`` et al.
    (:mod:`repro.obs.diagnostics`), the ``cross_val_path`` progress
    callback, and the ``python -m repro.obs.report`` CLI.

Quickstart::

    from repro.core import solve, Quadratic, L1
    from repro.obs import Obs

    obs = Obs()
    res = solve(X, y, Quadratic(), L1(lam), obs=obs)
    print(res.diagnostics.summary())      # per-outer kkt/gap/ws curves
    obs.export_chrome("trace.json")       # open in ui.perfetto.dev
    obs.dump("run.json")                  # python -m repro.obs.report run.json

The spans, by driver (DESIGN.md §11.2):

  solve            solve > probe, outer > dispatch, sync; drain
  cross_val_path   grid > grid_round > grid_lanes, grid_dispatch, grid_sync,
                   grid_retire, grid_bank, grid_fill, grid_schedule;
                   grid_checkpoint
  reg_path         path > lambda (> solve ...) or lambda_chunk
  server           serve.flush > serve.dispatch; serve.refit

The stages of the fused step are ``jax.named_scope``s in
``SolveEngine._step_body`` (score, select, gather, inner_solve, scatter,
telemetry) and ``_chunk_loop`` (chunk_control): they name the ops of the
compiled program, so a device trace's ops map to a stage.

The rest is opt-in: ``obs=None`` (the default) statically elides every
device-side op — the trace is bit-identical to the pre-obs program and adds
zero dispatches (asserted by tests/test_obs.py and the CI-enforced
``telemetry_overhead`` budget in BENCH_engine.json) — and records no tracer
event; its spans cost one profiler annotation each (about a microsecond
with no profile being captured).
"""
from __future__ import annotations

import json

import numpy as np

from .diagnostics import Diagnostics, SolveDiagnostics
from .registry import MetricsRegistry
from .rings import TelemetryRing, lasso_duality_gap
from .trace import Tracer, span

__all__ = ["Obs", "Tracer", "MetricsRegistry", "TelemetryRing",
           "Diagnostics", "SolveDiagnostics", "lasso_duality_gap", "span"]


class Obs:
    """User-facing observability handle passed to ``solve``/``reg_path``/
    ``cross_val_path`` (and through the estimators' ``**solve_kw``).

    Parameters
    ----------
    rings : bool, optional
        Carry a device telemetry ring through the fused step (per-outer
        kkt/gap/ws curves on the result's ``diagnostics``; one extra host
        readback per solve at drain time, zero extra dispatches).
    trace : bool, optional
        Also record the program's spans (solve → outer → dispatch/sync,
        path → lambda, grid → grid_round → ...) on :attr:`tracer`; they
        land in the profiler's trace either way.
    """

    def __init__(self, rings: bool = True, trace: bool = True):
        self.rings = rings
        self.trace = trace
        self.tracer = Tracer()
        self.registry = MetricsRegistry()
        self.solves: list = []          # Diagnostics of every solve seen

    def span(self, name, **args):
        """Open a program span, recorded on :attr:`tracer` too when
        ``trace=True`` (then it yields the tracer's event dict)."""
        if not self.trace:
            return span(name, **args)
        return self.tracer.span(name, **args)

    def note_solve(self, diagnostics: Diagnostics):
        """Called by the solver at drain time; keeps the run's curve sets
        for :meth:`run_report`."""
        self.solves.append(diagnostics)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object for the spans so far."""
        return self.tracer.chrome_trace()

    def export_chrome(self, path: str) -> str:
        """Write the Chrome/Perfetto trace JSON to ``path``."""
        return self.tracer.export_chrome(path)

    def run_report(self) -> dict:
        """JSON-serializable report of this run: the metrics registry, the
        per-span-name wall-time rollup, and every solve's curve set."""
        def _curves(d):
            return {k: np.asarray(v, np.float64).tolist()
                    for k, v in d.curves.items()}
        return {
            "registry": self.registry.as_dict(),
            "spans": self.tracer.summary(),
            "n_solves": len(self.solves),
            "solves": [{"curves": _curves(d),
                        "registry": d.registry.as_dict()}
                       for d in self.solves[:64]],
        }

    def dump(self, path: str) -> str:
        """Write :meth:`run_report` as JSON to ``path``; renderable with
        ``python -m repro.obs.report path``."""
        with open(path, "w") as f:
            json.dump(self.run_report(), f, indent=1)
        return path
