"""Coordinate-descent epochs (paper Algorithm 3), pure-JAX path.

Two variants:
  * cd_epoch_xb:   general datafits. Maintains Xb = X_ws @ beta_ws; each
                   coordinate update costs O(n) (dot + axpy), as in the paper.
  * cd_epoch_gram: quadratic datafits. Maintains q = G @ beta_ws on the
                   working-set Gram G = X_ws^T X_ws; each update costs O(K).
                   The cyclic sweep is blocked: each loop trip updates B
                   consecutive coordinates, unrolled, on length-B vectors
                   read in one slice each, with the block's B columns of G
                   (`gram_sweep` lays G, c and L out once per inner
                   solve). The coordinates, their
                   order, the prox and each axpy into q are those of the
                   one-coordinate-per-trip loop, which kernels/ref.py keeps
                   as the tests' reference; what changes is that an epoch
                   takes ceil(K / B) trips instead of K, and no
                   per-coordinate scalar is read by a dynamic index (see
                   kernels/cd_epoch.py for the Pallas version). The same
                   operations need not round the same: XLA's CPU backend
                   contracts a multiply and an add into one FMA where a
                   fusion's shape allows. In float64 the engine's L1,
                   elastic-net, SCAD and L0.5 solves give the plain loop's
                   coefficients bit for bit (tests/test_engine.py), MCP's
                   to rounding.

Both support scalar coordinates (beta_ws: [K]) and multitask blocks
(beta_ws: [K, T]).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def _axpy(carrier, vec, delta):
    """carrier += vec (x) delta, handling scalar and block coordinates."""
    if delta.ndim == 0:
        return carrier + vec * delta
    return carrier + vec[:, None] * delta[None, :]


def _prox_coord(penalty, x, step, j):
    """Coordinate prox: penalties with per-coordinate hyper-parameters
    (weighted L1 in reweighting schemes) expose prox_at(x, step, j)."""
    if hasattr(penalty, "prox_at"):
        return penalty.prox_at(x, step, j)
    return penalty.prox(x, step)


def cd_epoch_xb(Xt_ws, y, beta_ws, Xb, L_ws, offset_ws, datafit, penalty,
                axis=None, w=None):
    """One cyclic CD epoch over the working set; X stored transposed [K, n].

    `axis` names a mesh axis the samples are sharded over (mesh-native
    engine, DESIGN.md §6): Xt_ws/y/Xb then hold the local rows and each
    coordinate gradient is completed with one scalar psum. beta stays
    replicated. `w` is the optional per-sample weight vector forwarded to
    the datafit's raw gradient (None statically elides it, DESIGN.md §9)."""
    K = Xt_ws.shape[0]

    def body(i, state):
        beta, Xb = state
        xj = Xt_ws[i]
        raw = datafit.raw_grad(Xb, y) if w is None \
            else datafit.raw_grad(Xb, y, w)
        gj = xj @ raw
        if axis is not None:
            gj = jax.lax.psum(gj, axis)
        gj = gj + offset_ws[i]
        Lj = L_ws[i]
        step = 1.0 / jnp.maximum(Lj, 1e-30)
        new = _prox_coord(penalty, beta[i] - gj * step, step, i)
        new = jnp.where(Lj > 0.0, new, beta[i])
        Xb = _axpy(Xb, xj, new - beta[i])
        beta = beta.at[i].set(new)
        return beta, Xb

    return jax.lax.fori_loop(0, K, body, (beta_ws, Xb))


def cd_sweep_block(K: int) -> int:
    """Coordinates per trip of the blocked Gram sweep for a K-coordinate
    working set (from a sweep of B on a TPU v5e, PERF.md section 6)."""
    return min(K, 8)


class GramSweep(NamedTuple):
    """A Gram subproblem laid out for the blocked sweep, once per inner
    solve: K padded to a multiple of ``cd_sweep_block(K)`` with inert
    coordinates (L = 0 leaves beta and q untouched)."""
    G: jax.Array               # [Kp, Kp]
    c: jax.Array               # [Kp(, T)]
    L: jax.Array               # [Kp]


def _pad_rows(v, n):
    return jnp.pad(v, [(0, n)] + [(0, 0)] * (v.ndim - 1)) if n else v


def gram_sweep(G, c, L_ws) -> GramSweep:
    """Lay out the Gram subproblem (G, c, L) for `gram_sweep_epoch`."""
    K = G.shape[0]
    pad = -K % cd_sweep_block(K)
    return GramSweep(jnp.pad(G, [(0, pad)] * 2) if pad else G,
                     _pad_rows(c, pad), _pad_rows(L_ws, pad))


def gram_sweep_epoch(sweep: GramSweep, beta_ws, q, penalty,
                     axpy_per_column=False):
    """One cyclic CD epoch on a laid-out Gram subproblem (`gram_sweep`).
    `axpy_per_column` selects the layout of q's update (`_blocked_sweep`)."""
    K = beta_ws.shape[0]
    pad = sweep.L.shape[0] - K
    beta, q = _blocked_sweep(sweep, _pad_rows(beta_ws, pad),
                             _pad_rows(q, pad), penalty, cd_sweep_block(K),
                             axpy_per_column)
    return (beta[:K], q[:K]) if pad else (beta, q)


def cd_epoch_gram(G, c, beta_ws, q, L_ws, penalty):
    """One cyclic CD epoch on the Gram subproblem: grad = q - c, q = G beta.
    Runs the blocked sweep at ``cd_sweep_block(K)`` coordinates a trip."""
    return gram_sweep_epoch(gram_sweep(G, c, L_ws), beta_ws, q, penalty)


def _blocked_sweep(sweep, beta_ws, q, penalty, B, axpy_per_column=False):
    """Cyclic sweep over Kp (a multiple of B) coordinates, B per loop trip.

    A trip reads the block's q, c, L and beta as length-B vectors and its B
    columns of G, each in one slice: columns, as the plain loop's axpys
    read them, since G need not be bitwise symmetric (a TPU's float32
    Gram is not), and XLA then stores G column-major, as for the plain
    loop, so that the Gram's matmul rounds as it does there. It then runs
    the B updates unrolled, each on whole B-vectors: every lane computes
    its own prox step and a lane mask keeps lane r's, so no per-coordinate
    scalar is read by a dynamic index. The change delta_r of coordinate r
    is a sum over the block of the masked changes, every other term an
    exact zero. Update r + 1 reads the block's q already advanced by the
    plain loop's axpy G[blk, r] * delta_r; at the end of the trip q is
    advanced by the B axpys in coordinate order and beta's block written
    back at once. Their products come from one multiply of the block's
    columns by the block's changes, or with `axpy_per_column` from one
    axpy per column: the faster layout alone and under a vmap over lanes
    respectively on a TPU v5e (PERF.md section 6); the values are the
    same. A penalty's prox_at gets each lane's coordinate index j."""
    G, c, L_ws = sweep
    lane = jnp.arange(B)
    # per-coordinate values broadcast over a multitask block's T axis
    tail = lambda v: v.reshape(v.shape + (1,) * (beta_ws.ndim - 1))

    def trip(t, state):
        beta, q = state
        s = t * B
        take = lambda v: jax.lax.dynamic_slice_in_dim(v, s, B)
        C = jax.lax.dynamic_slice_in_dim(G, s, B, axis=1).T   # G[:, blk].T
        C_bb = jax.lax.dynamic_slice_in_dim(C, s, B, axis=1)
        q_b, c_b, L_b, b0 = take(q), take(c), take(L_ws), take(beta)
        step = tail(1.0 / jnp.maximum(L_b, 1e-30))
        live = tail(L_b > 0.0)
        b_b, deltas = b0, []
        for r in range(B):
            at = tail(lane == r)
            new = _prox_coord(penalty, b_b - (q_b - c_b) * step, step,
                              s + lane)
            new = jnp.where(at & live, new, b_b)
            deltas.append(jnp.sum(jnp.where(at, new - b_b, 0.0), axis=0))
            q_b = _axpy(q_b, C_bb[r], deltas[r])
            b_b = new
        if axpy_per_column:
            for r in range(B):
                q = _axpy(q, C[r], deltas[r])
        else:
            P = tail(C) * (b_b - b0)[:, None]         # [B, Kp(, T)]
            for r in range(B):
                q = q + P[r]
        return jax.lax.dynamic_update_slice_in_dim(beta, b_b, s, 0), q

    return jax.lax.fori_loop(0, G.shape[0] // B, trip, (beta_ws, q))
