"""Jit-compatible kernels for the three hot CSC primitives (DESIGN.md §7).

All shapes are static: the flat CSC arrays are padded by one column window
(``max_col_nnz`` entries) so per-column ``dynamic_slice`` windows never run
out of bounds, and padding entries carry value 0.0 so every scatter/segment
reduction they join is exact.

  csc_score          full score pass X.T @ raw as a segment-sum over the
                     nnz entries (O(nnz), never materializes dense X)
  csc_gather_columns densify K selected columns into the engine's [K, n]
                     working-set buffer (vmapped window slice + scatter-add)
  csc_incremental_xb Xb += X_ws @ delta via scatter-add on the gathered
                     (rows, vals) windows (O(K * max_col_nnz))
  csc_matvec         full X @ beta (initial residual of a warm start)

``csc_score_pallas`` is the Pallas epoch-backend variant of the score pass
(grid over feature tiles, VMEM-resident raw gradient, MXU-free gather-
multiply-accumulate over the per-column ELL windows). Like the CD epoch
kernels in ``kernels/cd_epoch.py`` it is validated against the pure-jax
reference (``tests/test_sparse.py``) and selected through the engine's
``backend="pallas"`` switch; it consumes the optional ELL layout
(``CSCDesign.from_scipy(..., ell=True)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import collectives


def _psum_if(x, axis):
    """psum over `axis`, statically elided for unsplit (None) axes."""
    return x if axis is None else collectives.psum(x, axis)


# ---------------------------------------------------------------- score pass
def csc_score(data, indices, col_ids, raw, p: int):
    """X.T @ raw over flat CSC arrays: [nnz_pad] -> [p] (or [p, T] for a
    multitask raw gradient [n, T] — the segment-sum reduces the leading nnz
    axis and carries the task axis through).

    Padding entries have data == 0.0 and col_ids == p - 1, so they add an
    exact 0.0 to the last segment.
    """
    gathered = raw[indices]
    contrib = (data * gathered if gathered.ndim == 1
               else data[:, None] * gathered)
    return jax.ops.segment_sum(contrib, col_ids, num_segments=p,
                               indices_are_sorted=True)


def csc_weighted_col_sq(data, indices, col_ids, w, p: int):
    """Per-column weighted squared norms sum_i w_i x_ij^2 over flat CSC
    arrays -> [p] (the w-weighted Lipschitz statistic, DESIGN.md §9).
    O(nnz), same segment-sum layout as the score pass; padding entries have
    data == 0.0 so they contribute exact zeros."""
    contrib = data * data * w[indices]
    return jax.ops.segment_sum(contrib, col_ids, num_segments=p,
                               indices_are_sorted=True)


def csc_score_ell(rows, vals, raw):
    """Reference for the Pallas kernel: score pass over the ELL layout
    (rows/vals [p, m], padding vals 0.0). Returns [p]."""
    return jnp.sum(vals * raw[rows], axis=1)


# ------------------------------------------------------- working-set windows
def csc_column_windows(data, indices, indptr, cols, max_col_nnz: int):
    """Per-column nnz windows of `cols` (local column indices, in range).

    Returns (rows [K, m], vals [K, m]) with vals masked to 0.0 beyond each
    column's nnz — window tails that spill into the next column's entries
    contribute exact zeros to every downstream scatter.
    """
    m = max_col_nnz
    starts = indptr[cols]
    nnz = indptr[cols + 1] - starts

    def window(s):
        r = jax.lax.dynamic_slice(indices, (s,), (m,))
        v = jax.lax.dynamic_slice(data, (s,), (m,))
        return r, v

    rows, vals = jax.vmap(window)(starts)
    mask = jnp.arange(m)[None, :] < nnz[:, None]
    return rows, jnp.where(mask, vals, jnp.zeros((), vals.dtype))


def csc_gather_columns(rows, vals, n_rows: int, model_axis=None):
    """Densify gathered column windows into the engine's [n, K] ws buffer.

    Under feature sharding `vals` are already masked to the owned columns;
    the psum over `model_axis` replicates the buffer like the dense
    `gather_ws_cols`.
    """
    K = rows.shape[0]
    Xt = jnp.zeros((K, n_rows), vals.dtype)
    Xt = Xt.at[jnp.arange(K)[:, None], rows].add(vals)
    return _psum_if(Xt, model_axis).T


def csc_incremental_xb(Xb, rows, vals, delta, model_axis=None):
    """Xb += X_ws @ delta via scatter-add on the gathered windows (exact:
    padding vals are 0.0). `delta` may be [K] (scalar coordinates, Xb [n])
    or [K, T] (multitask blocks, Xb [n, T])."""
    inc = jnp.zeros_like(Xb)
    if delta.ndim == 1:
        inc = inc.at[rows.reshape(-1)].add((vals * delta[:, None]).reshape(-1))
    else:
        T = delta.shape[1]
        contrib = vals[:, :, None] * delta[:, None, :]      # [K, m, T]
        inc = inc.at[rows.reshape(-1)].add(contrib.reshape(-1, T))
    return Xb + _psum_if(inc, model_axis)


# ----------------------------------------------------------------- full ops
def csc_matvec(data, indices, col_ids, beta, n_rows: int):
    """X @ beta over flat CSC arrays -> [n] (or [n, T] for multitask beta
    [p, T]). Padding cols point at p - 1 with data 0.0, so the gathered
    beta contributes exact zeros."""
    gathered = beta[col_ids]
    if gathered.ndim == 1:
        contrib = data * gathered
        return jnp.zeros((n_rows,), data.dtype).at[indices].add(contrib)
    contrib = data[:, None] * gathered
    return jnp.zeros((n_rows, beta.shape[1]),
                     data.dtype).at[indices].add(contrib)


# ------------------------------------------------------------- pallas kernel
def _score_kernel(m_tiles, square, rows_blk, vals_blk, raw_blk, out_blk, acc):
    """One (BP, BM) ELL tile: gather raw at the tile's row indices, multiply
    by the stored values (squared in weighted-Lipschitz mode), accumulate
    into the per-feature VMEM scratch. raw may carry a trailing task axis:
    acc is [BP, R]."""
    mt = pl.program_id(1)

    @pl.when(mt == 0)
    def _init():
        acc[:, :] = jnp.zeros_like(acc)

    vals = vals_blk[:, :]
    if square:
        vals = vals * vals
    raw = raw_blk[:, :]                                     # [n, R]
    acc[:, :] += jnp.sum(vals[:, :, None] * raw[rows_blk[:, :]], axis=1)

    @pl.when(mt == m_tiles - 1)
    def _emit():
        out_blk[:, :] = acc[:, :]


def csc_score_pallas(rows, vals, raw, *, bp=256, bm=512, interpret=None,
                     square=False):
    """Pallas score pass over the ELL layout: rows/vals [p, m], raw [n]
    (scalar) or [n, T] (multitask — the task axis rides along in VMEM).

    Grid = (p_tiles, m_tiles); the raw gradient stays VMEM-resident across
    the whole grid and each feature tile accumulates its gathered
    contributions in a VMEM scratch, emitted on the last m-step. Returns the
    [p] (or [p, T]) gradient (validated against ``csc_score_ell`` /
    ``csc_score``). ``square=True`` squares the stored values in-kernel —
    the weighted column-square reduction behind
    ``csc_weighted_col_sq_pallas``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    p, m = rows.shape
    n = raw.shape[0]
    squeeze = raw.ndim == 1
    raw2 = raw[:, None] if squeeze else raw
    R = raw2.shape[1]
    bp = min(bp, p)
    bm = min(bm, m)
    # pad to the tile grid (padding rows point at row 0 with value 0.0)
    pp, pm = -p % bp, -m % bm
    if pp or pm:
        rows = jnp.pad(rows, ((0, pp), (0, pm)))
        vals = jnp.pad(vals, ((0, pp), (0, pm)))
    m_tiles = (m + pm) // bm
    from jax.experimental.pallas import tpu as pltpu
    out = pl.pallas_call(
        functools.partial(_score_kernel, m_tiles, square),
        grid=((p + pp) // bp, m_tiles),
        in_specs=[
            pl.BlockSpec((bp, bm), lambda j, i: (j, i)),   # row indices
            pl.BlockSpec((bp, bm), lambda j, i: (j, i)),   # values
            pl.BlockSpec((n, R), lambda j, i: (0, 0)),     # raw gradient
        ],
        out_specs=pl.BlockSpec((bp, R), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((p + pp, R), vals.dtype),
        scratch_shapes=[pltpu.VMEM((bp, R), vals.dtype)],
        interpret=interpret,
    )(rows, vals, raw2)
    return out[:p, 0] if squeeze else out[:p]


def csc_weighted_col_sq_pallas(rows, vals, w, *, bp=256, bm=512,
                               interpret=None):
    """Pallas weighted column-square reduction over the ELL layout:
    sum_i w_i x_ij^2 -> [p], the grid-driver Lipschitz hot path (per-fold
    weighted L in cross_val_path / reg_path_grid). Same kernel as the score
    pass with in-kernel value squaring; validated against
    ``csc_weighted_col_sq``."""
    return csc_score_pallas(rows, vals, w, bp=bp, bm=bm, interpret=interpret,
                            square=True)
