"""Device-idle milliseconds of the lane scheduler's host bookkeeping per
round of a grid: the idle time put down to ``repro/grid_lanes`` (lane
re-gather), ``grid_retire`` (retire decision, held-out loss, output
scatter), ``grid_bank``, ``grid_fill`` and ``grid_schedule``, per
``repro/grid_round`` span."""
from bench.metrics._spans import idle_ms_per

BOOKKEEPING = ["repro/grid_lanes", "repro/grid_retire", "repro/grid_bank",
               "repro/grid_fill", "repro/grid_schedule"]


def read(run):
    return idle_ms_per(run, BOOKKEEPING, "repro/grid_round")
