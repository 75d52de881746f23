"""The drivers: whole passes over the mix's problems, each in an order drawn
from the seed, over values that the mix fixes."""
import numpy as np
import pytest

from bench.drivers import common

from . import tiny


def test_pass_order_is_whole_passes_drawn_from_the_seed():
    def first(seed, n):
        it = common.pass_order(5, seed)
        return [next(it) for _ in range(n)]

    a = first(2**40 + 1, 15)
    for i in range(0, 15, 5):
        assert sorted(a[i:i + 5]) == list(range(5))
    assert a == first(2**40 + 1, 15)
    assert a != first(2**40 + 2, 15)


def test_values_seed_of_the_mix_wins():
    assert common.values_seed({"values_seed": 0}, 2**40) == 0
    assert common.values_seed({}, 2**40) == 2**40


@pytest.mark.parametrize("name", ["dense_ar1.lasso_solve",
                                  "dense_ar1.lasso_cv_grid"])
def test_every_seed_sets_up_the_same_problems(name):
    cell = tiny.cell(name)
    a = cell.driver.setup(cell, 2**33 + 1)
    b = cell.driver.setup(cell, 2**33 + 2)
    for Xa, Xb in zip(a.Xs, b.Xs):
        assert np.array_equal(np.asarray(Xa), np.asarray(Xb))
    np.testing.assert_array_equal(a.lmax, b.lmax)


@pytest.mark.parametrize("name, per_pass", [
    ("dense_ar1.lasso_solve", tiny.MIX["lasso_solve"]["n_problems"]),
    ("dense_ar1.lasso_cv_grid", tiny.MIX["lasso_cv_grid"]["n_problems"]
     * 5 * tiny.MIX["lasso_cv_grid"]["n_lambdas"])])
def test_window_runs_whole_passes(name, per_pass):
    r = tiny.run(name, seconds=0.05)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["attempted"] % per_pass == 0
