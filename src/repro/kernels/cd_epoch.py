"""Pallas TPU kernels for coordinate-descent epochs (paper Algorithm 3).

TPU adaptation (DESIGN.md §2): the sequential per-coordinate updates run with
*all mutable state resident in VMEM* across the whole epoch block, so each
update is a VPU-width vector op with zero HBM round-trips:

  * cd_epoch_gram_kernel: state (beta, q = G beta) stays in VMEM as
    lane-major [1, K] rows; G streams HBM->VMEM eight rows per grid step
    (G is symmetric, so row i is column i). Each update is an O(K) axpy.
  * cd_epoch_xb_kernel: state (beta [1, K], Xb [1, n]) stays in VMEM; the
    working-set columns of X (rows of X_ws^T) stream eight per grid step.
    Each update is an O(n) dot + axpy.

Every block is (8, ·) rows by a full-width lane axis, the tiling Mosaic
accepts for float32. The coordinate inside a block is a static unrolled
index; its scalars (beta_j, q_j, L_j, ...) are read with a one-lane masked
sum, which is exact (one term plus zeros), and written back with a lane
select — no dynamic scalar indexing into vector memory. K is padded to a
multiple of 8 with inert coordinates (L_j = 0 leaves beta_j and the state
untouched).

Grid = (epochs, K / 8): one kernel launch runs a full M-epoch block of the
inner solver. Penalty hyper-parameters live in SMEM, so one compiled kernel
serves a whole regularization path.

Validated on CPU with interpret=True against repro/kernels/ref.py, and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import check_kernel_penalty, make_penalty

ROWS = 8                      # sublanes per float32 tile: rows per grid step


def _pad_to(v, K, axis=0):
    """Zero-pad `v` along `axis` to length K (no-op when already K)."""
    extra = K - v.shape[axis]
    if not extra:
        return v
    widths = [(0, 0)] * v.ndim
    widths[axis] = (0, extra)
    return jnp.pad(v, widths)


def _pick(at, v):
    """v[j] for the one-lane mask `at` as a [1, 1] array (exact: one term
    plus zeros)."""
    return jnp.sum(jnp.where(at, v, 0.0), axis=1, keepdims=True)


def _coord_update(pen, at, beta, L, gj):
    """One prox step on the coordinate selected by the lane mask `at`.
    Returns (delta [1, 1], beta with that coordinate replaced)."""
    Lj, bj = _pick(at, L), _pick(at, beta)
    step = 1.0 / jnp.maximum(Lj, 1e-30)
    new = jnp.where(Lj > 0.0, pen.prox(bj - gj * step, step), bj)
    return new - bj, jnp.where(at, new, beta)


def _cd_gram_kernel(penalty_cls, G_rows, c_ref, L_ref, params, beta0, q0,
                    beta_ref, q_ref):
    e, b = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (b == 0))
    def _init():
        beta_ref[...] = beta0[...]
        q_ref[...] = q0[...]

    pen = make_penalty(penalty_cls, params, beta_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    c, L = c_ref[...], L_ref[...]
    beta, q = beta_ref[...], q_ref[...]
    for r in range(ROWS):
        at = lane == b * ROWS + r
        delta, beta = _coord_update(pen, at, beta, L,
                                    _pick(at, q) - _pick(at, c))
        q = q + delta * G_rows[r:r + 1, :]
    beta_ref[...] = beta
    q_ref[...] = q


def cd_epoch_gram_pallas(G, c, beta0, q0, L, penalty_cls, params, *, epochs=1,
                         interpret=True):
    """Run `epochs` cyclic CD epochs on the Gram subproblem.

    G: [K, K] (symmetric); c, beta0, q0, L: [K]. Returns (beta, q), both [K].
    """
    check_kernel_penalty(penalty_cls)
    K = G.shape[0]
    Kp = -(-K // ROWS) * ROWS
    rows = lambda e, b: (b, 0)
    const = lambda e, b: (0, 0)
    vec = pl.BlockSpec((1, Kp), const)
    row = lambda v: _pad_to(v, Kp)[None, :]
    beta, q = pl.pallas_call(
        functools.partial(_cd_gram_kernel, penalty_cls),
        grid=(epochs, Kp // ROWS),
        in_specs=[
            pl.BlockSpec((ROWS, Kp), rows),                 # streamed G rows
            vec,                                            # c
            vec,                                            # L
            pl.BlockSpec(memory_space=pltpu.SMEM),          # penalty params
            vec,                                            # beta0
            vec,                                            # q0
        ],
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, Kp), G.dtype)] * 2,
        interpret=interpret,
        name="cd_epoch_gram",
    )(_pad_to(_pad_to(G, Kp, 0), Kp, 1), row(c), row(L),
      params.astype(G.dtype), row(beta0), row(q0))
    return beta[0, :K], q[0, :K]


def _cd_xb_kernel(penalty_cls, datafit_kind, n_samples, has_w, *refs):
    if has_w:
        (x_rows, y_ref, w_ref, off_ref, L_ref, params, beta0, Xb0, beta_ref,
         Xb_ref) = refs
    else:
        (x_rows, y_ref, off_ref, L_ref, params, beta0, Xb0, beta_ref,
         Xb_ref) = refs
    e, b = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (b == 0))
    def _init():
        beta_ref[...] = beta0[...]
        Xb_ref[...] = Xb0[...]

    pen = make_penalty(penalty_cls, params, beta_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape, 1)
    y = y_ref[...]
    off, L = off_ref[...], L_ref[...]
    beta, Xb = beta_ref[...], Xb_ref[...]
    # weighted raw-gradient formulas match repro.core.datafits (sum(w) = n
    # normalization is the caller's contract)
    if datafit_kind == "quadratic":
        raw_grad = lambda Xb: (Xb - y) / n_samples
    elif datafit_kind == "logistic":
        raw_grad = lambda Xb: -y * jax.nn.sigmoid(-y * Xb) / n_samples
    elif datafit_kind == "svc":
        if has_w:
            raise ValueError("QuadraticSVC does not support sample weights")
        raw_grad = lambda Xb: Xb
    else:
        raise ValueError(datafit_kind)
    for r in range(ROWS):
        raw = raw_grad(Xb)
        if has_w:
            raw = w_ref[...] * raw
        at = lane == b * ROWS + r
        xj = x_rows[r:r + 1, :]                            # (1, n)
        gj = jnp.sum(xj * raw, axis=1, keepdims=True) + _pick(at, off)
        delta, beta = _coord_update(pen, at, beta, L, gj)
        Xb = Xb + delta * xj
    beta_ref[...] = beta
    Xb_ref[...] = Xb


def cd_epoch_xb_pallas(Xt_ws, y, beta0, Xb0, L, offset, penalty_cls, params,
                       datafit_kind="quadratic", *, w=None, epochs=1,
                       interpret=True):
    """Run `epochs` CD epochs maintaining Xb. Xt_ws: [K, n]. Returns (beta, Xb).

    `w` (optional, [n], sum(w) = n) folds sample weights into the in-kernel
    raw gradient (quadratic / logistic only; QuadraticSVC has no weighted
    form). `w=None` adds no kernel input — the unweighted trace is unchanged.
    """
    check_kernel_penalty(penalty_cls)
    K, n = Xt_ws.shape
    Kp = -(-K // ROWS) * ROWS
    has_w = w is not None
    const = lambda e, b: (0, 0)
    kvec = pl.BlockSpec((1, Kp), const)
    nvec = pl.BlockSpec((1, n), const)
    row = lambda v: _pad_to(v, Kp)[None, :]
    kern = functools.partial(_cd_xb_kernel, penalty_cls, datafit_kind, n,
                             has_w)
    in_specs = [pl.BlockSpec((ROWS, n), lambda e, b: (b, 0)),  # X_ws^T rows
                nvec]                                          # y
    operands = [_pad_to(Xt_ws, Kp), y[None, :]]
    if has_w:
        in_specs.append(nvec)                                  # weights
        operands.append(w[None, :])
    in_specs += [kvec, kvec,                                   # offset, L
                 pl.BlockSpec(memory_space=pltpu.SMEM),        # params
                 kvec, nvec]                                   # beta0, Xb0
    operands += [row(offset), row(L), params.astype(Xt_ws.dtype),
                 row(beta0), Xb0[None, :]]
    beta, Xb = pl.pallas_call(
        kern,
        grid=(epochs, Kp // ROWS),
        in_specs=in_specs,
        out_specs=[kvec, nvec],
        out_shape=[jax.ShapeDtypeStruct((1, Kp), Xt_ws.dtype),
                   jax.ShapeDtypeStruct((1, n), Xt_ws.dtype)],
        interpret=interpret,
        name="cd_epoch_xb",
    )(*operands)
    return beta[0, :K], Xb[0]
