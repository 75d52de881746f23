"""Outer iterations of Algorithm 1 per solve, over the window (the
program's own count n_outer)."""
from bench.metrics._read import ratio


def read(run):
    return ratio(run, "n_outer", "n_solves")
