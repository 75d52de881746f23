"""Central metrics registry (DESIGN.md §11.3).

One named store for every solver metric — counters (monotonic ints),
gauges (last-value scalars), mappings (live dict views, e.g. the engine's
retrace counter), and histograms (observation lists with summary stats).
The scattered ad-hoc fields of the pre-obs stack (``engine.retraces``,
``engine.n_dispatches``, ``SolveResult.n_host_syncs``, the roofline stage
tables) are now *views* into a registry: the legacy attributes keep
working as properties, and everything is exportable as one JSON snapshot
(``as_dict``) for the ``python -m repro.obs.report`` CLI and the
BENCH_engine.json budget guard.

Naming scheme (dotted, lowercase):

  engine.retraces            mapping   {bucket key: compile count}
  engine.cd_sweep_block      mapping   {bucket: Gram CD sweep block size}
  engine.collective_bytes    mapping   {bucket: bytes a chip sends per step}
  engine.n_dispatches        counter   fused-step launches
  solve.n_host_syncs         counter   blocking readbacks of one solve
  solve.n_outer / n_epochs   counter   per-solve loop totals
  solve.collective_bytes     counter   the steps' collective_bytes, summed
                                       over the solve's n_outer outers
  path.retraces              mapping   PathResult compat view
  path.n_dispatches          counter   PathResult compat view
  grid.n_host_syncs          counter   cross_val_path sweep totals
  roofline.<name>.<stage>.*  gauge     per-stage cost-analysis numbers
"""
from __future__ import annotations

__all__ = ["MetricsRegistry"]


def _str_key(k):
    return k if isinstance(k, str) else repr(k)


class MetricsRegistry:
    """Counters, gauges, live mappings, and histograms under dotted names.

    All methods auto-create the metric on first touch; reads of absent
    metrics return a zero/default instead of raising, so view properties
    (``SolveResult.n_host_syncs`` et al.) are total functions.
    """

    def __init__(self):
        self._counters: dict = {}
        self._gauges: dict = {}
        self._mappings: dict = {}
        self._histograms: dict = {}

    # ---------------------------------------------------------- counters
    def inc(self, name: str, value: int = 1) -> int:
        """Add ``value`` to counter ``name`` (created at 0) and return it."""
        v = self._counters.get(name, 0) + int(value)
        self._counters[name] = v
        return v

    def counter(self, name: str) -> int:
        """Current value of counter ``name`` (0 when never incremented)."""
        return self._counters.get(name, 0)

    def set_counter(self, name: str, value: int):
        """Reset counter ``name`` (benchmark loops zero counters between
        timed repetitions)."""
        self._counters[name] = int(value)

    # ------------------------------------------------------------ gauges
    def set_gauge(self, name: str, value):
        """Record the last value of gauge ``name``."""
        self._gauges[name] = value

    def gauge(self, name: str, default=None):
        """Last recorded value of gauge ``name`` (``default`` if unset)."""
        return self._gauges.get(name, default)

    # ---------------------------------------------------------- mappings
    def mapping(self, name: str) -> dict:
        """LIVE dict view registered under ``name`` — mutations through the
        returned dict are visible to every other holder of the view (this
        is how ``engine.retraces[key] += 1`` keeps working verbatim)."""
        m = self._mappings.get(name)
        if m is None:
            m = self._mappings[name] = {}
        return m

    def set_mapping(self, name: str, value: dict):
        """Replace the CONTENTS of mapping ``name`` (the view object is
        preserved, so existing references stay live)."""
        m = self.mapping(name)
        m.clear()
        m.update(value)

    # -------------------------------------------------------- histograms
    def observe(self, name: str, value: float):
        """Append one observation to histogram ``name``."""
        self._histograms.setdefault(name, []).append(float(value))

    def histogram(self, name: str) -> list:
        """Raw observation list of histogram ``name`` (empty if unset)."""
        return self._histograms.get(name, [])

    def histogram_summary(self, name: str) -> dict:
        """{count, min, max, mean, sum} of histogram ``name``."""
        v = self._histograms.get(name, [])
        if not v:
            return {"count": 0}
        return {"count": len(v), "min": min(v), "max": max(v),
                "mean": sum(v) / len(v), "sum": sum(v)}

    # ----------------------------------------------------------- generic
    def get(self, name: str, default=None):
        """Look ``name`` up across every metric kind."""
        for store in (self._counters, self._gauges, self._mappings):
            if name in store:
                return store[name]
        if name in self._histograms:
            return self.histogram_summary(name)
        return default

    def __contains__(self, name: str) -> bool:
        return any(name in s for s in (self._counters, self._gauges,
                                       self._mappings, self._histograms))

    def __getitem__(self, name: str):
        v = self.get(name, default=_MISSING)
        if v is _MISSING:
            raise KeyError(name)
        return v

    def names(self) -> list:
        """Sorted names of every registered metric."""
        return sorted(set(self._counters) | set(self._gauges)
                      | set(self._mappings) | set(self._histograms))

    def merge(self, other: "MetricsRegistry"):
        """Fold another registry into this one: counters add, gauges and
        mapping entries overwrite, histogram observations concatenate."""
        for k, v in other._counters.items():
            self.inc(k, v)
        self._gauges.update(other._gauges)
        for k, m in other._mappings.items():
            self.mapping(k).update(m)
        for k, v in other._histograms.items():
            self._histograms.setdefault(k, []).extend(v)

    def as_dict(self) -> dict:
        """JSON-serializable snapshot (mapping keys stringified — retrace
        keys are tuples)."""
        return {
            "counters": dict(self._counters),
            "gauges": {k: v for k, v in self._gauges.items()},
            "mappings": {k: {_str_key(kk): vv for kk, vv in m.items()}
                         for k, m in self._mappings.items()},
            "histograms": {k: self.histogram_summary(k)
                           for k in self._histograms},
        }


_MISSING = object()
