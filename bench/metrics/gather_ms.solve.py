"""Device self milliseconds of the fused step's ``gather`` stage (its
``jax.named_scope``) per execution of ``_outer_step``, from the trace."""
from bench.metrics._spans import stage_ms


def read(run):
    return stage_ms(run, "_outer_step", "gather")
