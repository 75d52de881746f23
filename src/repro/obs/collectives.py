"""Bytes the fused step's collectives send, counted from the shapes traced.

The sharded engine (DESIGN.md §6) issues its collectives through
:func:`psum`, :func:`pmax` and :func:`all_gather`: ``jax.lax``'s own, plus a
tally. While a :func:`tally` is open (``SolveEngine`` opens one around each
trace of its fused step), every collective traced adds what one chip sends
for it under the ring algorithms: an all-reduce of S bytes over an axis of k
devices sends 2 (k - 1) S / k bytes, an all-gather of S local bytes
(k - 1) S. A collective over an unsplit axis is never issued, so it counts
nothing; nor does one traced inside :func:`untallied`, which the step puts
round its inner solve, whose loops run a number of times no trace knows.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

__all__ = ["Tally", "tally", "untallied", "psum", "pmax", "all_gather"]

_OPEN = contextvars.ContextVar("collectives_tally", default=None)


class Tally:
    """Bytes one chip sends through the collectives traced while open."""

    def __init__(self, axis_sizes):
        self.axis_sizes = dict(axis_sizes)
        self.bytes = 0


@contextlib.contextmanager
def tally(axis_sizes):
    """Count the collectives traced inside; `axis_sizes` maps each mesh axis
    name to its size."""
    t = Tally(axis_sizes)
    token = _OPEN.set(t)
    try:
        yield t
    finally:
        _OPEN.reset(token)


@contextlib.contextmanager
def untallied():
    """Count nothing traced inside, even under an open tally."""
    token = _OPEN.set(None)
    try:
        yield
    finally:
        _OPEN.reset(token)


def _sent(x, axis, gather: bool):
    t = _OPEN.get()
    if t is not None:
        k = t.axis_sizes[axis]
        size = x.size * x.dtype.itemsize
        t.bytes += (k - 1) * size if gather else 2 * (k - 1) * size // k


def psum(x, axis):
    _sent(x, axis, gather=False)
    return jax.lax.psum(x, axis)


def pmax(x, axis):
    _sent(x, axis, gather=False)
    return jax.lax.pmax(x, axis)


def all_gather(x, axis):
    _sent(x, axis, gather=True)
    return jax.lax.all_gather(x, axis)
