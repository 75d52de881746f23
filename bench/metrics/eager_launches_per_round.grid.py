"""Programs other than the chunk step (``_chunk_solve``) executed on the
device from inside a round of a grid, per ``repro/grid_round`` span: the
small eager programs of the lane bookkeeping."""
from bench.metrics._spans import launches_per


def read(run):
    return launches_per(run, "repro/grid_round", "_chunk_solve")
