"""Whole solves on a design sharded over several chips: the ``solve``
driver's set-up, window and answers, with the window's collective traffic
counted and a check that brings the design from the chips a shard a transfer.

The window adds the counters ``n_chips`` and ``collective_bytes`` (the sum
of the window's solves' ``solve.collective_bytes``, left out where the
program keeps no such counter), and sets ``x_bytes`` to the bytes of one
chip's shard, what each chip's score pass reads. The check is the
``solve`` driver's, with the float64 reference fed row blocks of the
design: it comes to the host in float32, each chip's shard in one transfer,
and only one block at a time is held in float64.
"""
from __future__ import annotations

import math
import resource

import numpy as np

from . import common, solve
from ..reference.kkt import ROW_BLOCK

setup = solve.setup
answers = solve.answers

COUNTER = "solve.collective_bytes"


def window(st, seconds, tracer, counters):
    sent = []
    solve_one = solve._solve

    def counted(st, k):
        res = solve_one(st, k)
        reg = res.diagnostics.registry
        sent.append(reg.counter(COUNTER) if COUNTER in reg else None)
        return res

    solve._solve = counted
    try:
        out = solve.window(st, seconds, tracer, counters)
    finally:
        solve._solve = solve_one
    X = st.Xs[0]
    counters.update(n_chips=len(X.sharding.device_set),
                    x_bytes=math.prod(X.sharding.shard_shape(X.shape))
                    * X.dtype.itemsize)
    if None not in sent:
        counters["collective_bytes"] = sum(sent)
    return out


def _chip_peaks(X) -> list:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in sorted(X.sharding.device_set, key=lambda d: d.id)]


def _span(index, shape) -> tuple:
    """A shard's index (a slice per axis) as ((row0, row1), (col0, col1))."""
    return tuple(ix.indices(dim)[:2] for ix, dim in zip(index, shape))


class _Rows:
    """A design on the chips as ``bench.reference.kkt`` reads a dense one:
    its shape, and row blocks already in float64. Each distinct shard comes
    to the host whole, once, all chips at a time (a shard lies contiguous on
    its chip; a block of its rows does not). A block is cast into one buffer
    that the next block overwrites, which the reference allows: it reads a
    block only until it asks for the next. The host holds the design once,
    in float32, and one block in float64."""

    def __init__(self, X):
        self.shape = X.shape
        shards = {}
        for s in X.addressable_shards:
            shards.setdefault(_span(s.index, X.shape), s.data)
        for data in shards.values():
            data.copy_to_host_async()
        self._parts = [(span, np.asarray(data))
                       for span, data in shards.items()]
        self._block = np.empty((min(ROW_BLOCK, X.shape[0]), X.shape[1]))

    def __getitem__(self, rows: slice):
        a, b = rows.start, min(rows.stop, self.shape[0])
        block = self._block[:b - a]
        for ((r0, r1), (c0, c1)), part in self._parts:
            lo, hi = max(a, r0), min(b, r1)
            if lo < hi:
                block[lo - a:hi - a, c0:c1] = part[lo - r0:hi - r0]
        return block


def check(st, window):
    """``common.check``, the float64 KKT of each answer computed by
    ``bench.reference.kkt`` on the row blocks of ``_Rows``."""
    from ..reference import kkt as ref
    ans = answers(st)
    st.sample = None                     # the program's answers are in `ans`
    lim = common.limits(st.cell.name)
    kkt64 = common.by_design(lambda X, *rest: ref.kkt(_Rows(X), *rest), ans)
    common.note(f"device peak bytes per chip {_chip_peaks(st.Xs[0])}; host "
                f"peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}"
                f" KiB")
    tol = float(st.cell.mix["tol_frac"])
    return [
        {"name": "unconverged", "value": window["failed"], "limit": 0},
        {"name": "kkt", "value": float(np.max(kkt64 / ans.lmax)),
         "limit": tol + lim["kkt_gap"]},
        {"name": "kkt_gap", "value": common.gap(ans.kkt_dev, kkt64, ans.lmax),
         "limit": lim["kkt_gap"]},
    ]
