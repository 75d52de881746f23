"""Device-idle milliseconds inside each blocking readback of a solve: the
idle time put down to ``repro/sync`` (the one ``device_get`` of each outer
iteration), per ``repro/sync`` span."""
from bench.metrics._spans import idle_ms_per


def read(run):
    return idle_ms_per(run, ["repro/sync"], "repro/sync")
