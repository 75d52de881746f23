"""Fused Pallas kernel: score pass + top-k candidate selection + candidate
column gather in ONE traversal of X (DESIGN.md §10).

The two-pass engine head reads X twice per outer iteration: once for the
score pass ``X.T @ raw`` and once to gather the selected working-set columns
(a K-column gather from a row-major [n, p] array touches ``min(p, K * G)``
elements per row at HBM transaction granularity G, i.e. the *whole* matrix
again in the p >> ws regime the paper targets). This kernel reads each X
tile exactly once and emits everything the outer step needs:

  * the per-feature violation scores (and the offset-corrected gradient),
  * a per-tile top-``kc`` candidate buffer (``kc = min(bp, ws_size)``), with
    the candidate *columns* copied out of the VMEM-resident tile while it is
    still loaded.

Because every tile contributes its own top-``kc`` candidates under the same
total order as ``lax.top_k`` (priority descending, index ascending on ties,
generalized support pinned to +inf), the global top-``ws_size`` set is
guaranteed to be a subset of the candidate union: the host-free merge is
just ``select_working_set`` on the emitted scores, and ``X[:, ws]`` is
recovered from the candidate buffer without touching X again
(``working_set.candidate_columns``). The recovered columns are bit-exact
copies of X (one-hot gather), and the selected indices match the two-pass
reference exactly on ties (exact arithmetic is order-independent) and
whenever the feature axis fits one tile; across multiple tiles the scores
agree up to blocked-matmul reduction-order rounding (~1e-14 in f64), the
same caveat any tiled ``X.T @ r`` carries. Proven in
tests/test_fused_ws.py.

In-kernel selection is ``kc`` rounds of (max, lowest-index argmax, one-hot
accumulate); the candidate columns come out of a single one-hot matmul
``H @ X_tile.T`` (rows are exact column copies — one-hot weights incur no
rounding at float32 contract precision), which is the MXU-friendly gather
form on TPU.

TPU layout: every per-feature vector (beta, L, offset, gsupp, scores, grad)
is a lane-major ``[R, p]`` row, the raw gradient a ``[R, n]`` row, so a tile
is ``[n, bp]`` of X and ``[·, bp]`` rows with ``bp`` a multiple of 128 (or
all of p). X is never padded: a ragged last tile reads past column p and
its out-of-range lanes are masked to zero before either matmul. ``bp`` is
sized from n so the double-buffered X tile and candidate block fit the
kernel's VMEM budget (``_pick_bp``).

Supports scalar coordinates (r [n], beta [p]) and multitask row blocks
(r [n, T], beta [p, T], Block* penalties: per-row block norms), and any
codec-registered penalty — the score arithmetic only needs prox /
subdiff_dist on the VMEM tile (``check_score_kernel_penalty``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (SCALAR_COORD_PENALTIES, check_score_kernel_penalty,
                     make_penalty)

LANES = 128
# v5e has 128 MiB of VMEM per core but a 16 MiB default scoped limit, which
# cannot hold one double-buffered [n, 128] X tile plus its [kc, n] candidate
# block once n passes ~8k. The kernel raises its limit to _VMEM_LIMIT and
# sizes bp so its own buffers stay within _VMEM_BUDGET, leaving the rest of
# the limit to Mosaic's own temporaries.
_VMEM_LIMIT = 100 * 2**20
_VMEM_BUDGET = 48 * 2**20


def _rup(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_bytes(n: int, bp: int, kc: int, R: int, itemsize: int) -> int:
    """VMEM held by one fused-head grid step: double-buffered blocks in
    (8, 128) tiles plus the single one-hot scratch."""
    nl, bl = _rup(n, LANES), _rup(bp, LANES)
    blocks = (_rup(n, 8) * bl                  # X tile [n, bp]
              + _rup(kc, 8) * nl               # candidate columns [kc, n]
              + _rup(R, 8) * nl                # raw gradient [R, n]
              + (2 * _rup(R, 8) + 4 * 8) * bl  # beta, grad; L, off, gs, sc
              + 8 * _rup(kc, LANES))           # candidate indices [1, kc]
    return itemsize * (2 * blocks + _rup(kc, 8) * bl)


def _pick_bp(n: int, p: int, ws: int, R: int = 1, itemsize: int = 4,
             cap: int = 1024) -> int:
    """Feature-tile width: all of p when p <= cap and that fits the VMEM
    budget, else the widest multiple of 128 (<= cap) that fits. Wider tiles
    mean fewer candidates (tiles * min(bp, ws)) to write back; the cap
    keeps the smoke fig2 shape single-tile (DESIGN.md §10)."""
    fits = lambda bp: _vmem_bytes(n, bp, min(bp, ws), R,
                                  itemsize) <= _VMEM_BUDGET
    if p <= cap and fits(p):
        return p
    for bp in range(min(cap, p) // LANES * LANES, 0, -LANES):
        if fits(bp):
            return bp
    raise ValueError(
        f"fused working-set head: n={n} samples leave no 128-wide feature "
        f"tile within the {_VMEM_BUDGET >> 20} MiB VMEM budget")


def _fused_kernel(penalty_cls, use_fp, p, bp, kc, X_blk, r_ref, beta_blk,
                  L_blk, off_blk, gs_blk, params, sc_ref, grad_ref, ci_ref,
                  cc_ref, H):
    j = pl.program_id(0)
    dtype = sc_ref.dtype
    pen = make_penalty(penalty_cls, params, dtype)
    hi = jax.lax.Precision.HIGHEST
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
    valid = (j * bp + lane) < p                              # [1, bp]
    X = X_blk[...]
    if p % bp:          # ragged last tile: lanes past p hold garbage
        X = jnp.where(valid, X, jnp.zeros((), dtype))

    # one MXU pass over the tile: grad = (X_tile^T r^T)^T + offset  [R, bp]
    grad = jnp.dot(X.T, r_ref[...].T, precision=hi,
                   preferred_element_type=dtype).T + off_blk[...]
    beta = beta_blk[...]
    step = 1.0 / jnp.maximum(L_blk[...], 1e-30)              # [1, bp]
    if penalty_cls not in SCALAR_COORD_PENALTIES:
        # block penalties act on feature rows: score the [bp, R] view
        g, b, st = grad.T, beta.T, step.T
        if use_fp:
            diff = b - pen.prox(b - g * st, st)
            sc = jnp.sqrt(jnp.sum(diff * diff, axis=1))[None, :]
        else:
            sc = pen.subdiff_dist(g, b)[None, :]
    elif use_fp:
        sc = jnp.abs(beta - pen.prox(beta - grad * step, step))
    else:
        sc = pen.subdiff_dist(grad, beta)

    # zero-mask the padded tail (its zero columns can still carry nonzero
    # penalty scores, e.g. Box at beta=0) so kkt/selection never see it
    sc = jnp.where(valid, sc, jnp.zeros((), dtype))
    sc_ref[...] = sc
    grad_ref[...] = jnp.where(valid, grad, jnp.zeros((), dtype))

    # iterative per-tile top-kc under the lax.top_k total order (priority
    # descending, lowest index on ties); support lanes pinned to +inf
    inf = jnp.asarray(jnp.inf, dtype)
    pri0 = jnp.where(gs_blk[...] > 0, inf, sc)
    pri0 = jnp.where(valid, pri0, -inf)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kc), 1)

    def pick(k, carry):
        pri, ci = carry
        m = jnp.max(pri)
        sel = jnp.min(jnp.where(pri == m, lane, bp))
        onehot = lane == sel
        H[pl.ds(k, 1), :] = onehot.astype(dtype)
        # exhausted tiles (everything already picked / padded) emit the
        # out-of-range index p: the merge scatter drops it
        ci = jnp.where(slot == k, jnp.where(sel < bp, j * bp + sel, p), ci)
        return jnp.where(onehot, -inf, pri), ci

    _, ci = jax.lax.fori_loop(0, kc, pick,
                              (pri0, jnp.zeros((1, kc), jnp.int32)))
    ci_ref[...] = ci
    # one-hot matmul gather: row k of cc is the EXACT column X[:, sel_k]
    cc_ref[...] = jax.lax.dot_general(
        H[...], X, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=dtype)


def fused_ws_pallas(X, r, beta, L, offset, gsupp, penalty_cls, params,
                    ws_size, *, use_fp=False, bp=None, interpret=True):
    """One-traversal score + candidate top-k + candidate-column gather.

    X: [n, p]; r: [n] or [n, T]; beta: [p] or [p, T]; L/offset/gsupp: [p]
    (gsupp as a 0/1 float mask). Returns ``(scores [p], grad [p] or [p, T],
    cand_idx [C] int32, cand_cols [C, n])`` with ``C = p_tiles * kc``,
    ``kc = min(bp, ws_size)``; entries of cand_idx >= p are exhausted-tile
    padding. The final working set is ``select_working_set(scores, gsupp,
    ws_size)`` and ``X[:, ws]`` is ``candidate_columns(cand_idx, cand_cols,
    ws, p)`` — the columns bit-exact, the scores exact up to blocked-matmul
    reduction order (bit-identical in the single-tile case).
    """
    check_score_kernel_penalty(penalty_cls)
    n, p = X.shape
    squeeze = r.ndim == 1
    rows = r[None, :] if squeeze else r.T                    # [R, n]
    beta_rows = beta[None, :] if squeeze else beta.T         # [R, p]
    R = rows.shape[0]
    bp = _pick_bp(n, p, ws_size, R, X.dtype.itemsize) if bp is None \
        else min(bp, p)
    tiles = -(-p // bp)
    pad = lambda v: jnp.pad(v, ((0, 0), (0, tiles * bp - p)))
    kc = min(bp, ws_size)
    tile = lambda j: (0, j)
    const = lambda j: (0, 0)
    scores, grad, cand_idx, cand_cols = pl.pallas_call(
        functools.partial(_fused_kernel, penalty_cls, use_fp, p, bp, kc),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((n, bp), tile),               # X tile (read ONCE)
            pl.BlockSpec((R, n), const),               # raw gradient
            pl.BlockSpec((R, bp), tile),               # beta
            pl.BlockSpec((1, bp), tile),               # L
            pl.BlockSpec((1, bp), tile),               # grad offset
            pl.BlockSpec((1, bp), tile),               # gsupp 0/1 mask
            pl.BlockSpec(memory_space=pltpu.SMEM),     # penalty params
        ],
        out_specs=[
            pl.BlockSpec((1, bp), tile),               # scores
            pl.BlockSpec((R, bp), tile),               # grad (+offset)
            pl.BlockSpec((pl.squeezed, 1, kc), lambda j: (j, 0, 0)),
            pl.BlockSpec((pl.squeezed, kc, n), lambda j: (j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, tiles * bp), X.dtype),
            jax.ShapeDtypeStruct((R, tiles * bp), X.dtype),
            jax.ShapeDtypeStruct((tiles, 1, kc), jnp.int32),
            jax.ShapeDtypeStruct((tiles, kc, n), X.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((kc, bp), X.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="fused_ws",
    )(X, rows, pad(beta_rows), pad(L[None, :]), pad(offset[None, :]),
      pad(gsupp[None, :]), params.astype(X.dtype))
    grad = grad[0, :p] if squeeze else grad[:, :p].T
    return (scores[0, :p], grad, cand_idx.reshape(-1),
            cand_cols.reshape(tiles * kc, n))
