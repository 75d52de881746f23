"""The control fails each cell's ``kkt_gap`` limit where the program's own
reading keeps it: the reference's KKT arithmetic in the precision below the
configuration's, read at the answers of a short window at a tiny size."""
import pytest

from bench import readings
from bench.drivers import common

from . import tiny


@pytest.mark.parametrize("name", ["dense_ar1.lasso_solve",
                                  "dense_ar1.lasso_cv_grid"])
def test_control_fails_the_limit(name):
    limit = common.limits(name)["kkt_gap"]
    r = readings.read_seed(tiny.cell(name), 2**33 + 1, 0.3)
    assert r["gap_program"] <= limit < r["gap_control"], r
