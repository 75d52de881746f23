"""Program spans and the span tracer with Chrome-trace export
(DESIGN.md §11.2).

:func:`span` is the one span of the program: a
``jax.profiler.TraceAnnotation`` named ``repro/<name>``, so every span lands
in the profiler's own trace, on the clock of the device's events, whenever
a profile is being captured — and costs about a microsecond when none is.

``Tracer.span`` enters the same annotation and also records the span as a
Chrome trace "complete" (``ph: "X"``) event — solve → outer →
dispatch/sync, path → lambda, grid → grid_round → grid_dispatch/... .
``export_chrome`` writes the standard ``{"traceEvents": [...]}`` JSON that
chrome://tracing and Perfetto (ui.perfetto.dev) open directly; nesting is
inferred from time containment per thread lane, which the with-statement
discipline guarantees.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "span"]

PREFIX = "repro/"


def span(name: str, **args):
    """Open the program span `name`: a profiler annotation
    ``repro/<name>`` carrying `args` (keep them to ints already at hand;
    they are formatted only while a profile is being captured). Entering it
    yields the annotation, never a tracer event."""
    return TraceAnnotation(PREFIX + name, **args)


class Tracer:
    """Collects nested spans; host-side, append-only, microsecond units."""

    def __init__(self):
        self.events: list = []
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._tids: dict = {}
        self._depth = threading.local()

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            if ident not in self._tids:
                self._tids[ident] = len(self._tids)
            return self._tids[ident]

    @contextmanager
    def span(self, name: str, **args):
        """Open a span, in the profiler's trace as :func:`span` does and
        on this tracer; yields the (mutable) event dict so callers can
        attach args discovered mid-span (e.g. ``ev["args"]["compiled"]``
        once a dispatch is known to have retraced)."""
        start = time.perf_counter()
        ev = {"name": name, "ph": "X", "pid": 0, "tid": self._tid(),
              "ts": (start - self._t0) * 1e6,
              "args": dict(args, depth=getattr(self._depth, "v", 0))}
        self._depth.v = ev["args"]["depth"] + 1
        try:
            with span(name, **args):
                yield ev
        finally:
            self._depth.v = ev["args"]["depth"]
            ev["dur"] = (time.perf_counter() - start) * 1e6
            with self._lock:
                self.events.append(ev)

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object for the spans so far."""
        with self._lock:
            events = sorted(self.events, key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome(self, path: str) -> str:
        """Write ``chrome_trace()`` to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def summary(self) -> dict:
        """Per-span-name rollup: {name: {count, total_s}} (nested spans
        double-count their parents by construction — this is a where-did-
        wall-time-go table, not a flat profile)."""
        out: dict = {}
        with self._lock:
            events = list(self.events)
        for ev in events:
            rec = out.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
            rec["count"] += 1
            rec["total_s"] += ev.get("dur", 0.0) / 1e6
        return out
