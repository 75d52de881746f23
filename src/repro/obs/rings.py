"""Device-side telemetry rings (DESIGN.md §11.1).

A ``TelemetryRing`` is a pytree of preallocated ``[max_outer]`` buffers
(plus an int32 write cursor) carried THROUGH the fused outer step: the
step records per-outer KKT violation, objective, duality gap, working-set
size/occupancy, generalized-support size, inner epochs, and Anderson
acceptance count with in-dispatch ``.at[cursor].set(..., mode="drop")``
scatters, and the host drains the whole ring ONCE at solve end. The
engine's 1-dispatch + 1-sync-per-outer budget is untouched (the drain is
one extra readback per solve, not per iteration), and ``obs=None``
statically elides every ring op — the no-obs trace is the bit-identical
pre-obs program, exactly like the ``w=None`` weight leaf (DESIGN.md §9).

Under the chunked drivers the ring gains a leading lane axis
(``alloc(cap, lanes=C)``): the per-lane ring rides the same vmap as the
lambda/fold lanes and the cursor advances per lane. Under shard_map every
ring leaf is replicated (spec ``P()``, ``launch.shardings.ring_spec``) —
all recorded quantities are already mesh-replicated scalars.

Overflow: the cursor keeps counting past capacity while ``mode="drop"``
discards out-of-range writes, so a ring can never fault; ``drain()``
reports ``min(cursor, capacity)`` entries. Unwritten float slots stay NaN
and int slots stay -1 (visible sentinels, never mistaken for data).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from . import collectives

__all__ = ["TelemetryRing", "FIELDS", "gap_supported", "quadratic_l1_gap",
           "lasso_duality_gap"]

_FLOAT_FIELDS = ("kkt", "obj", "gap", "occupancy")
_INT_FIELDS = ("ws_size", "gsupp", "epochs", "accepts")
FIELDS = _FLOAT_FIELDS + _INT_FIELDS


@dataclass(frozen=True)
class TelemetryRing:
    """Preallocated per-outer telemetry buffers (a pytree; see module doc).

    Float curves (``kkt``, ``obj``, ``gap``, ``occupancy``) and int32
    curves (``ws_size``, ``gsupp``, ``epochs``, ``accepts``) are ``[cap]``
    — or ``[lanes, cap]`` with a per-lane ``cursor`` under the chunked
    drivers.
    """
    cursor: jax.Array
    kkt: jax.Array
    obj: jax.Array
    gap: jax.Array
    occupancy: jax.Array
    ws_size: jax.Array
    gsupp: jax.Array
    epochs: jax.Array
    accepts: jax.Array

    @classmethod
    def alloc(cls, cap: int, dtype=jnp.float64, lanes: int = 0):
        """Allocate an empty ring of ``cap`` slots (``lanes > 0`` adds a
        leading lane axis for the chunked drivers)."""
        shape = (lanes, cap) if lanes else (cap,)
        cshape = (lanes,) if lanes else ()
        kw = {f: jnp.full(shape, jnp.nan, dtype) for f in _FLOAT_FIELDS}
        kw.update({f: jnp.full(shape, -1, jnp.int32) for f in _INT_FIELDS})
        return cls(cursor=jnp.zeros(cshape, jnp.int32), **kw)

    @property
    def capacity(self) -> int:
        return self.kkt.shape[-1]

    def record(self, **values):
        """One in-dispatch write at the cursor (out-of-range writes drop);
        returns the advanced ring. Traced — called inside the fused step."""
        c = self.cursor
        upd = {"cursor": c + 1}
        for name, v in values.items():
            buf = getattr(self, name)
            upd[name] = buf.at[c].set(jnp.asarray(v).astype(buf.dtype),
                                      mode="drop")
        return dataclasses.replace(self, **upd)

    def drain(self):
        """ONE host readback of the whole ring. Returns ``(curves, n)``:
        curves maps field name -> np array (``[n]``, or ``[lanes, cap]``
        for lane rings), n is the recorded-entry count (int, or ``[lanes]``
        per-lane counts clipped to capacity)."""
        host = jax.device_get(self)
        cur = np.asarray(host.cursor)
        cap = self.capacity
        curves = {f: np.asarray(getattr(host, f)) for f in FIELDS}
        if cur.ndim == 0:
            n = int(min(int(cur), cap))
            return {k: v[:n] for k, v in curves.items()}, n
        return curves, np.minimum(cur, cap)


jax.tree_util.register_pytree_node(
    TelemetryRing,
    lambda r: (tuple(getattr(r, f) for f in ("cursor",) + FIELDS), None),
    lambda aux, ch: TelemetryRing(*ch))


# ------------------------------------------------------------- duality gap
def gap_supported(datafit, penalty, w) -> bool:
    """Static predicate: the in-step duality gap is recorded only for the
    unweighted Lasso pair (Quadratic + L1) whose dual-feasible rescaling is
    closed-form (core/screening.py); every other combination records NaN.
    Name-based so the obs layer never imports the core (no cycle)."""
    return (w is None and type(datafit).__name__ == "Quadratic"
            and type(penalty).__name__ == "L1")


def quadratic_l1_gap(y, Xb, grad, obj, n_glob, lam, data_axis, model_axis):
    """Traced Lasso duality gap at the incoming iterate, from quantities the
    fused step already holds: residual r = y - Xb, the score-pass gradient
    (grad = X^T(Xb - y)/n, data-axis psum done), and the primal objective.

    Same certificate as the gap-safe screening rule: theta = r/(lam n)
    rescaled into the dual-feasible ball by min(1, lam/max|X^T r/n|), dual =
    lam <y, theta> - lam^2 n/2 ||theta||^2, which reduces to
    scale <y,r>/n - scale^2 ||r||^2/(2n). ``data_axis``/``model_axis`` are
    the live mesh axes (None when unsplit) — the max|grad| completes with a
    pmax over the model axis and the two inner products with data-axis
    psums, so the recorded gap is the replicated global value.
    """
    r = y - Xb
    gmax = jnp.max(jnp.abs(grad))
    if model_axis is not None:
        gmax = collectives.pmax(gmax, model_axis)
    yr = jnp.vdot(y, r)
    rr = jnp.vdot(r, r)
    if data_axis is not None:
        yr = collectives.psum(yr, data_axis)
        rr = collectives.psum(rr, data_axis)
    scale = jnp.minimum(1.0, lam / jnp.maximum(gmax, 1e-300))
    dual = scale * yr / n_glob - scale * scale * rr / (2.0 * n_glob)
    return obj - dual


def lasso_duality_gap(X, y, beta, lam) -> float:
    """Host-side reference gap (same certificate as ``quadratic_l1_gap``) —
    the test oracle the ring's ``gap`` curve is checked against to 1e-10."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    beta = np.asarray(beta, np.float64)
    n = X.shape[0]
    r = y - X @ beta
    primal = float(r @ r) / (2 * n) + lam * float(np.abs(beta).sum())
    gmax = float(np.max(np.abs(X.T @ r))) / n if X.size else 0.0
    scale = min(1.0, lam / max(gmax, 1e-300))
    dual = scale * float(y @ r) / n - scale * scale * float(r @ r) / (2 * n)
    return primal - dual
