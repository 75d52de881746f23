"""Tiny sizes of the benchmark's cells, for tests on the CPU."""
import time

from bench.harness import find_cell, run_cell

SIZES = {
    "dense_ar1": {"n": 200, "p": 400, "n_nonzero": 10},
}
MIX = {"lasso_cv_grid": {"n_lambdas": 6, "n_problems": 2},
       "lasso_solve": {"n_problems": 3}}


def cell(name):
    c = find_cell(name)
    c.config.update(SIZES[name.split(".")[0]])
    c.mix.update(MIX.get(name.split(".")[1], {}))
    return c


def run(name, seed=2**31 + 5, seconds=0.3, trace=False):
    return run_cell(cell(name), seed, seconds, trace, time.perf_counter(),
                    require_tpu=False, log=lambda msg: None)
