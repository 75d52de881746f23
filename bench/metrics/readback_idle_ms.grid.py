"""Device-idle milliseconds inside each blocking readback of a grid: the
idle time put down to ``repro/grid_sync`` (the ``device_get`` of the lanes'
convergence scalars), per ``repro/grid_sync`` span."""
from bench.metrics._spans import idle_ms_per


def read(run):
    return idle_ms_per(run, ["repro/grid_sync"], "repro/grid_sync")
