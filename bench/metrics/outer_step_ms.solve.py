"""Device milliseconds per execution of the fused outer step (the jitted
``_outer_step``), from the trace."""
from bench.metrics._read import module_ms


def read(run):
    return module_ms(run, "_outer_step")
