"""Device milliseconds per execution of the grid's vmapped chunk step (the
jitted ``_chunk_solve``, up to sync_every outer iterations of every lane),
from the trace."""
from bench.metrics._read import module_ms


def read(run):
    return module_ms(run, "_chunk_solve")
