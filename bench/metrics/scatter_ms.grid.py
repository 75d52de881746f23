"""Device self milliseconds of the fused step's ``scatter`` stage (its
``jax.named_scope``, over every lane) per execution of the chunk step
``_chunk_solve``, from the trace."""
from bench.metrics._spans import stage_ms


def read(run):
    return stage_ms(run, "_chunk_solve", "scatter")
