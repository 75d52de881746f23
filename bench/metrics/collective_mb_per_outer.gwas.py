"""Megabytes one chip sends through the fused step's collectives per outer
iteration, over the window: the program's ``solve.collective_bytes``
(``repro.obs.collectives``: ring all-reduce and all-gather bytes counted
from the traced shapes) over ``n_outer``. None where the program keeps no
such counter."""


def read(run):
    c = run.counters
    if "collective_bytes" not in c or not c.get("n_outer"):
        return None
    return c["collective_bytes"] / c["n_outer"] / 1e6
