"""Device self milliseconds of the collectives inside ``_outer_step`` per
execution of it, averaged over the chips, from the trace: every op whose
HLO category is a collective (``bench.metrics._collectives``), the
all-reduces named after ``psum`` and ``pmax`` among them."""
from bench.metrics import _collectives
from bench.metrics._read import module_ms


def read(run):
    if module_ms(run, "_outer_step") is None:
        return None
    table = _collectives.of_run(run)
    progs = [p for p in table if "_outer_step" in p]
    if not progs:
        return None
    count = sum(run.trace["modules"][p]["count"] for p in progs)
    return 1e3 * sum(table[p] for p in progs) / count
