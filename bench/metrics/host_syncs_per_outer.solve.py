"""Blocking host syncs per outer iteration of Algorithm 1, over the window
(the program's own counts: n_host_syncs over n_outer)."""
from bench.metrics._read import ratio


def read(run):
    return ratio(run, "n_host_syncs", "n_outer")
