"""Regularization paths (paper Figure 1 / §E.5) on the device-resident engine.

Solves Problem (1) for a decreasing grid of lambdas with warm starts. The
whole sweep shares ONE SolveEngine, so the per-bucket compiled fused steps
are reused across the entire grid: penalties are pytrees with
hyper-parameters as leaves, and the power-of-two working-set bucket is the
only retrace trigger (asserted by tests/test_engine.py via the engine's
retrace counter).

Two drivers:
  * sequential (vmap_chunk=1): lambda-by-lambda warm starts, one fused
    dispatch + one scalar sync per outer iteration (core/solver.py).
  * chunked (vmap_chunk=C>1): the dense head of the path is swept C lambdas
    at a time with the engine's vmapped chunk step — the *outer* loop runs
    on-device in a lax.while_loop, so the host syncs once per (chunk, bucket)
    instead of once per (lambda, iteration). Chunks hand their last (densest)
    solution to the next chunk as the shared warm start (FaSTGLZ-style
    multi-path batching); the host escalates the bucket and resumes the
    still-unconverged lanes when a chunk outgrows its working-set bucket.

Grid driver (DESIGN.md §9): ``cross_val_path`` generalizes the chunked
driver from a 1-D lambda sweep to a 2-D (fold x lambda) grid — every CV
fold (or bootstrap replicate) is a 0/1 sample-weight leaf on the SAME
(X, y), so all replicates share one static shape and the whole grid runs
through one compiled fused step per working-set bucket: lanes are
(fold, lambda) pairs, warm starts hand off per fold across lambda chunks,
bucket escalation is shared, and held-out scores reduce device-side from
the lanes' residuals (Xb is maintained on ALL rows — weights only enter
the datafit — so held-out predictions are free).

Per-lambda epoch/outer/time telemetry plus the engine's retrace/dispatch
counters land on PathResult, so perf regressions in the path driver are
observable, not vibes. Support/estimation metrics reproduce Figure 1's
support-recovery comparison (L1 vs MCP/SCAD bias).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import CheckpointConfig, restore_pytree
from repro.obs import SolveDiagnostics, TelemetryRing, span

from .api import lambda_max
from .datafits import Quadratic
from .engine import as_design
from .lanes import LaneScheduler
from .penalties import L1
from .solver import _place_design, make_engine, normalize_weights, solve
from .working_set import BucketPolicy, next_pow2

__all__ = ["reg_path", "PathResult", "support_metrics", "cross_val_path",
           "GridResult", "CheckpointConfig"]

# working-set growth factor of the engine's chunked device loop: the host
# mirrors it to detect "a lane outgrew its bucket" from the synced gcounts
_GROWTH = 2

_ENGINE_KW = ("M", "max_epochs", "accel", "use_fp_score", "use_gram",
              "use_kernels")

# The drivers' wall clock, indirected so the timing tests can pin it to a
# deterministic fake counter (tests/test_obs.py monkeypatches
# ``repro.core.path._now``).
_now = time.perf_counter


@dataclass
class PathResult:
    """Result of one :func:`reg_path` sweep.

    Attributes
    ----------
    lambdas : np.ndarray
        The decreasing regularization grid.
    betas : np.ndarray
        Solutions, ``[n_lambdas, p]`` or ``[n_lambdas, p, T]`` (multitask).
    kkts, nnzs, n_epochs, n_outer, times : np.ndarray
        Per-lambda KKT violation, nonzero count, inner epochs, outer
        iterations, and wall-clock seconds. ``times[i]`` is the seconds
        SPENT ON lambda i — the sequential driver stamps each solve's own
        duration, the chunked driver stamps every lambda of a chunk with
        that chunk's duration (``np.cumsum(times)`` recovers the
        sweep-cumulative curve older versions recorded; the old chunked
        stamping was buggy anyway — it wrote the running sweep total,
        conflating chunk cost with position in the sweep).
    metrics : list of dict
        Per-lambda ``metric_fn`` outputs (when provided).
    diagnostics : repro.obs.SolveDiagnostics
        Structured convergence record (DESIGN.md §11). The chunked driver
        run with ``obs=...`` fills ``curves`` with the drained per-lane
        telemetry rings (``[n_lambdas, max_outer]`` per field); otherwise
        the per-lambda aggregate curves. Its registry backs the legacy
        telemetry attributes below.
    retraces : dict
        The engine's compile counter per (bucket, driver) key — the proof
        behind "one compile per working-set bucket across a path". A LIVE
        property view into ``diagnostics.registry`` mapping
        ``"path.retraces"`` (reads and writes work as the pre-§11 field).
    n_dispatches : int
        Total fused-step launches of the sweep (property view into the
        ``"path.n_dispatches"`` counter).
    screened_fracs : np.ndarray, optional
        Fraction of features pre-screened per lambda (gap-safe runs only).
    """
    lambdas: np.ndarray
    betas: np.ndarray                 # [n_lambdas, p(, T)]
    kkts: np.ndarray
    nnzs: np.ndarray
    n_epochs: np.ndarray
    metrics: List[dict] = field(default_factory=list)
    # engine telemetry (per lambda / whole sweep)
    n_outer: Optional[np.ndarray] = None
    times: Optional[np.ndarray] = None          # per-lambda seconds
    diagnostics: SolveDiagnostics = field(default_factory=SolveDiagnostics)
    # gap-safe screening telemetry (screen="gap_safe" only)
    screened_fracs: Optional[np.ndarray] = None

    @property
    def retraces(self) -> dict:
        """Engine compile counter (live view into the registry)."""
        return self.diagnostics.registry.mapping("path.retraces")

    @retraces.setter
    def retraces(self, value: dict):
        self.diagnostics.registry.set_mapping("path.retraces", dict(value))

    @property
    def n_dispatches(self) -> int:
        """Fused-step launches of the sweep (view into the registry)."""
        return self.diagnostics.registry.counter("path.n_dispatches")

    @n_dispatches.setter
    def n_dispatches(self, value: int):
        self.diagnostics.registry.set_counter("path.n_dispatches",
                                              int(value))

    def summary(self) -> str:
        """Render the convergence diagnostics table."""
        return self.diagnostics.summary()


def _with_lam(penalty, lam: float):
    return dataclasses.replace(penalty, lam=lam)


def _check_grid(lambdas):
    """Validate a lambda grid and return it sorted DECREASING.

    Warm starts assume the grid runs from the sparsest problem (large
    lambda) down — an increasing or shuffled grid would silently warm-start
    each solve from a *denser* solution, wasting iterations and (with the
    chunked driver's shared bucket) inflating working sets. The grid is
    therefore canonicalized here; results are reported in the sorted order
    recorded on ``PathResult.lambdas`` / ``GridResult.lambdas``.
    """
    lambdas = np.asarray(lambdas, dtype=np.float64)
    if lambdas.ndim != 1 or lambdas.size == 0:
        raise ValueError(
            f"lambdas must be a non-empty 1-D grid, got shape "
            f"{lambdas.shape}")
    if not np.all(np.isfinite(lambdas)):
        raise ValueError("lambdas must be finite")
    if np.any(lambdas < 0):
        raise ValueError("lambdas must be non-negative")
    return np.sort(lambdas)[::-1].copy()


def reg_path(X, y, penalty, datafit=None, *, lambdas=None, n_lambdas=30,
             lambda_min_ratio=1e-2, tol=1e-6,
             metric_fn: Optional[Callable] = None, engine=None, vmap_chunk=1,
             mesh=None, data_axis="data", model_axis="model", screen=None,
             sample_weight=None, obs=None, **solve_kw) -> PathResult:
    """Warm-started path over a geometric lambda grid (lam_max -> ratio*lam_max).

    Parameters
    ----------
    X : array_like, scipy sparse matrix, or Design
        Design matrix (DESIGN.md §7); sparse paths run CSC-native end to
        end.
    y : array_like
        Targets ``[n]``, or ``[n, T]`` for multitask sweeps (block
        penalties; the betas stack to ``[n_lambdas, p, T]``, DESIGN.md §8).
    penalty : object
        Penalty template; its ``lam`` leaf is replaced per grid point
        without retracing.
    datafit : object, optional
        Defaults to ``Quadratic()``.
    lambdas : array_like, optional
        Explicit grid; otherwise ``n_lambdas`` points from ``lambda_max``
        down to ``lambda_min_ratio * lambda_max``. The grid is validated
        (finite, non-negative) and sorted decreasing — warm starts assume
        sparse-to-dense order — and ``PathResult.lambdas`` records the
        sorted grid the results follow.
    tol : float, optional
        Per-lambda outer KKT tolerance.
    metric_fn : callable, optional
        ``metric_fn(lam, beta)`` recorded per lambda on
        ``PathResult.metrics``.
    engine : SolveEngine, optional
        Share compiled steps across calls (see ``solver.make_engine``); one
        shared engine is looked up per config otherwise.
    vmap_chunk : int, optional
        ``C > 1`` sweeps C lambdas at a time through the device-resident
        chunk step (outer loop in a lax.while_loop, one host sync per
        (chunk, bucket) instead of per (lambda, iteration)); requires the
        "jax" backend and a penalty with a ``lam`` hyper-parameter.
    mesh : jax.sharding.Mesh, optional
        Run the whole sweep on the mesh-native engine (DESIGN.md §6): the
        sequential driver keeps its 1-dispatch/1-sync outer step and the
        chunked driver composes as vmap over lanes x shard_map over
        devices. Multitask/block sweeps shard too (DESIGN.md §8).
    screen : {"gap_safe"}, optional
        Sequential driver, L1 + Quadratic only: gap-safe sphere-test
        pre-filter per lambda (solutions unchanged — the rule is safe —
        only the per-lambda problem width shrinks;
        ``PathResult.screened_fracs`` records the screened fraction).
    sample_weight : array_like, optional
        Non-negative per-sample weights ``[n]`` shared by every lambda
        (DESIGN.md §9): validated and rescaled to sum to n once, then
        threaded through both drivers as a pytree leaf (never retraces).
    obs : repro.obs.Obs, optional
        Observability handle (DESIGN.md §11): opens nested path → lambda
        (-chunk) spans on ``obs.tracer`` and, on the chunked driver,
        carries a per-lane telemetry ring through the device-resident
        sweep — per-outer convergence curves for every lambda lane land on
        ``PathResult.diagnostics`` (the sequential driver's per-solve
        curves land on each solve's diagnostics via ``obs.solves``). Zero
        extra dispatches; ``obs=None`` is bit-identical to the pre-obs
        program.
    **solve_kw
        Forwarded to :func:`repro.core.solver.solve` (sequential driver) or
        restricted to engine-level keys (chunked driver).

    Returns
    -------
    PathResult
        Solutions plus per-lambda and engine telemetry.
    """
    datafit = Quadratic() if datafit is None else datafit
    design = as_design(X)
    if lambdas is None:
        lmax = lambda_max(design, y, datafit, sample_weight=sample_weight)
        lambdas = lmax * np.geomspace(1.0, lambda_min_ratio, n_lambdas)
    lambdas = _check_grid(lambdas)

    if engine is None:
        eng_kw = {k: solve_kw[k] for k in _ENGINE_KW if k in solve_kw}
        engine = make_engine(penalty, datafit, shared=True, mesh=mesh,
                             data_axis=data_axis, model_axis=model_axis,
                             **eng_kw)
    elif mesh is not None and engine.mesh is not mesh:
        raise ValueError("reg_path(mesh=..., engine=...): the engine was "
                         "built for a different mesh; pass mesh to "
                         "make_engine instead")
    # entry-time feasibility for BOTH drivers (the chunked one never reaches
    # solve()): unsupported mesh configs must raise here, not mid-trace
    n_tasks = y.shape[1] if (hasattr(y, "ndim") and y.ndim == 2) else 0
    engine.validate(datafit, penalty, n_tasks, shape=design.shape,
                    design=design, weighted=sample_weight is not None)
    if screen is not None:
        if screen != "gap_safe":
            raise ValueError(f"unknown screening rule {screen!r}; "
                             f"supported: 'gap_safe'")
        if sample_weight is not None:
            raise ValueError("screen='gap_safe' does not support "
                             "sample_weight: the sphere-test certificate "
                             "assumes the unweighted quadratic datafit")
        if vmap_chunk > 1:
            raise ValueError("screen='gap_safe' requires the sequential "
                             "driver (vmap_chunk=1): the per-lambda survivor "
                             "sets have different widths")
        if engine.mesh is not None:
            raise ValueError("screen='gap_safe' is not supported on the "
                             "mesh-native engine yet")
        if not (isinstance(penalty, L1) and isinstance(datafit, Quadratic)):
            raise ValueError(
                "screen='gap_safe' needs a duality certificate: only the "
                "convex L1 + Quadratic pair is supported (non-convex "
                "penalties are exactly the case the paper's working sets "
                "handle instead)")
    # validate + normalize ONCE; the sequential driver hands solve() the
    # host copy (its per-solve re-normalization is then a cheap host-side
    # no-op — no per-lambda device readback of a placed weight array)
    host_w = None if sample_weight is None \
        else np.asarray(sample_weight, np.float64)
    w = None if host_w is None \
        else normalize_weights(host_w, design.shape[0], design.dtype)
    if engine.mesh is not None:
        design, y, w = _place_design(engine, design, y, w)

    sp = obs.span if obs is not None else span
    driver = "chunked" if vmap_chunk > 1 \
        else ("screened" if screen is not None else "sequential")
    with sp("path", driver=driver, n_lambdas=len(lambdas)):
        if vmap_chunk > 1:
            res = _chunked_path(design, y, penalty, datafit, lambdas, tol,
                                engine, vmap_chunk, metric_fn, w=w, obs=obs,
                                **solve_kw)
        else:
            res = _sequential_path(design, y, penalty, datafit, lambdas,
                                   tol, engine, metric_fn, screen=screen,
                                   w=host_w, obs=obs, **solve_kw)
    res.retraces = dict(engine.retraces)
    res.n_dispatches = engine.n_dispatches
    if not res.diagnostics.curves:
        # no device rings ran: the per-lambda aggregates are the curves
        res.diagnostics.curves.update(kkt=np.asarray(res.kkts),
                                      epochs=np.asarray(res.n_epochs),
                                      time_s=np.asarray(res.times))
        res.diagnostics.n_recorded = len(res.lambdas)
    if obs is not None:
        obs.registry.inc("path.count")
    return res


def _sequential_path(design, y, penalty, datafit, lambdas, tol, engine,
                     metric_fn, *, screen=None, w=None, obs=None, **solve_kw):
    if screen is not None:
        return _screened_path(design, y, penalty, datafit, lambdas, tol,
                              engine, metric_fn, obs=obs, **solve_kw)
    sp = obs.span if obs is not None else span
    beta = None
    betas, kkts, nnzs, eps, outers, times, metrics = [], [], [], [], [], [], []
    for lam in lambdas:
        t_lam = _now()
        with sp("lambda", lam=float(lam)):
            res = solve(design, y, datafit, _with_lam(penalty, float(lam)),
                        tol=tol, beta0=beta, engine=engine, sample_weight=w,
                        obs=obs, **solve_kw)
        beta = res.beta
        betas.append(np.asarray(beta))
        kkts.append(res.kkt)
        nnzs.append(int(jnp.sum(beta != 0)))
        eps.append(res.n_epochs)
        outers.append(res.n_outer)
        times.append(_now() - t_lam)
        if metric_fn is not None:
            metrics.append(metric_fn(lam, beta))
    return PathResult(lambdas=lambdas, betas=np.stack(betas),
                      kkts=np.asarray(kkts), nnzs=np.asarray(nnzs),
                      n_epochs=np.asarray(eps), metrics=metrics,
                      n_outer=np.asarray(outers), times=np.asarray(times))


def _screened_path(design, y, penalty, datafit, lambdas, tol, engine,
                   metric_fn, *, obs=None, **solve_kw):
    """Sequential path with the gap-safe pre-filter (opt-in, L1+Quadratic).

    Per lambda: certify zeros with the previous solution's duality gap,
    solve the surviving-column subproblem (width padded to a power of two so
    compiled steps are shared across lambdas), scatter back into the full
    coefficient vector. Safe screening => identical solutions.
    """
    from .screening import gap_safe_mask_design

    sp = obs.span if obs is not None else span
    n, p = design.shape
    beta_full = np.zeros(p)
    betas, kkts, nnzs, eps, outers, times = [], [], [], [], [], []
    metrics, fracs = [], []
    for lam in lambdas:
        t_lam = _now()
        mask = np.asarray(gap_safe_mask_design(design, y,
                                               jnp.asarray(beta_full),
                                               float(lam)))
        surv = np.flatnonzero(mask)
        fracs.append(1.0 - len(surv) / p)
        beta_full = np.where(mask, beta_full, 0.0)
        if len(surv):
            width = min(p, next_pow2(max(len(surv), 16)))
            idx = np.full(width, -1, np.int64)
            idx[:len(surv)] = surv
            sub = design.take_columns(idx)
            beta0_sub = np.zeros(width)
            beta0_sub[:len(surv)] = beta_full[surv]
            with sp("lambda", lam=float(lam), width=int(width)):
                res = solve(sub, y, datafit, _with_lam(penalty, float(lam)),
                            tol=tol, beta0=jnp.asarray(beta0_sub),
                            engine=engine, obs=obs, **solve_kw)
            beta_full = np.zeros(p)
            beta_full[surv] = np.asarray(res.beta)[:len(surv)]
            kkts.append(res.kkt)
            eps.append(res.n_epochs)
            outers.append(res.n_outer)
        else:
            beta_full = np.zeros(p)
            kkts.append(0.0)
            eps.append(0)
            outers.append(0)
        betas.append(beta_full.copy())
        nnzs.append(int(np.sum(beta_full != 0)))
        times.append(_now() - t_lam)
        if metric_fn is not None:
            metrics.append(metric_fn(lam, beta_full))
    return PathResult(lambdas=lambdas, betas=np.stack(betas),
                      kkts=np.asarray(kkts), nnzs=np.asarray(nnzs),
                      n_epochs=np.asarray(eps), metrics=metrics,
                      n_outer=np.asarray(outers), times=np.asarray(times),
                      screened_fracs=np.asarray(fracs))


def _chunked_path(design, y, penalty, datafit, lambdas, tol, engine, chunk,
                  metric_fn, *, p0=64, max_outer=50, eps_inner_frac=0.3,
                  w=None, obs=None, **solve_kw):
    """Chunked vmap sweep with warm-start handoff between chunks."""
    # engine-level kwargs were consumed by make_engine; anything else the
    # sequential driver would honor (use_ws, beta0, ...) must not be
    # silently dropped here
    unsupported = set(solve_kw) - set(_ENGINE_KW)
    if unsupported:
        raise ValueError(
            f"vmap_chunk > 1 does not support solve kwargs "
            f"{sorted(unsupported)}; use the sequential driver (vmap_chunk=1)")
    sp = obs.span if obs is not None else span
    use_ring = obs is not None and getattr(obs, "rings", True)
    p = design.shape[1]
    policy = BucketPolicy(p0=p0)
    L = design.lipschitz(datafit) if w is None \
        else design.lipschitz(datafit, w, backend=engine.config.backend)
    offset = datafit.grad_offset(p, design.dtype)
    bshape = (p,) if y.ndim == 1 else (p, y.shape[1])
    beta_prev = jnp.zeros(bshape, design.dtype)
    Xb_prev = design.matvec(beta_prev)
    gcount_prev = 0

    betas, kkts, n_eps, outers, times = [], [], [], [], []
    ring_curves, ring_counts = [], []
    for lo in range(0, len(lambdas), chunk):
        t_chunk = _now()
        lams_c = jnp.asarray(lambdas[lo:lo + chunk], design.dtype)
        C = lams_c.shape[0]
        # all lanes warm-start from the previous chunk's densest solution
        betas0 = jnp.stack([beta_prev] * C)
        Xbs0 = jnp.stack([Xb_prev] * C)
        bucket = policy.first_bucket(gcount_prev, p)
        iters_left = max_outer
        chunk_iters = 0
        chunk_eps = np.zeros(C, np.int64)
        ring = TelemetryRing.alloc(max_outer, design.dtype, lanes=int(C)) \
            if use_ring else None
        with sp("lambda_chunk", lo=int(lo), n_lanes=int(C)):
            while True:
                out = engine.chunk(bucket, design, y, lams_c, betas0, Xbs0,
                                   L, offset, datafit, penalty, tol,
                                   eps_inner_frac, iters_left, w=w, obs=ring)
                if ring is not None:
                    (betas_c, Xbs_c, kkts_d, _, gcounts_d, neps_d, it_d,
                     ring) = out
                else:
                    betas_c, Xbs_c, kkts_d, _, gcounts_d, neps_d, it_d = out
                # one host sync per (chunk, bucket) attempt
                kkts_c, gcounts_c, neps_c, it = jax.device_get(
                    (kkts_d, gcounts_d, neps_d, it_d))
                iters_left -= int(it)
                chunk_iters += int(it)
                chunk_eps += np.asarray(neps_c, np.int64)
                done = bool(np.all(kkts_c <= tol))
                if done or bucket >= p or iters_left <= 0:
                    break
                # a lane outgrew the bucket: escalate and resume from the
                # partially-converged state (the ring cursor carries over —
                # resumed iterations append to the same per-lane curves)
                bucket = max(policy.escalate(bucket, p),
                             policy.next_bucket(bucket,
                                                int(np.max(gcounts_c)), p))
                betas0, Xbs0 = betas_c, Xbs_c
        if ring is not None:
            curves, counts = ring.drain()
            ring_curves.append(curves)
            ring_counts.append(counts)
        betas_np = np.asarray(betas_c)
        betas.extend(betas_np)
        kkts.extend(np.asarray(kkts_c).tolist())
        n_eps.extend(chunk_eps.tolist())
        outers.extend([chunk_iters] * C)
        # every lambda of the chunk is stamped with the CHUNK's duration —
        # the lanes solved simultaneously, so per-lambda attribution below
        # chunk granularity does not exist
        times.extend([_now() - t_chunk] * C)
        beta_prev = betas_c[-1]
        Xb_prev = Xbs_c[-1]
        gcount_prev = int(gcounts_c[-1])

    betas = np.stack(betas)
    metrics = []
    if metric_fn is not None:
        metrics = [metric_fn(lam, b) for lam, b in zip(lambdas, betas)]
    res = PathResult(lambdas=lambdas, betas=betas, kkts=np.asarray(kkts),
                     nnzs=np.asarray([(b != 0).sum() for b in betas]),
                     n_epochs=np.asarray(n_eps), metrics=metrics,
                     n_outer=np.asarray(outers), times=np.asarray(times))
    if ring_curves:
        res.diagnostics.curves.update(
            {k: np.concatenate([c[k] for c in ring_curves], axis=0)
             for k in ring_curves[0]})
        res.diagnostics.n_recorded = np.concatenate(ring_counts)
        if obs is not None:
            obs.note_solve(res.diagnostics)
    return res


# --------------------------------------------------------------- grid driver
@dataclass
class GridResult:
    """Result of one :func:`cross_val_path` (fold x lambda) grid sweep.

    Attributes
    ----------
    lambdas : np.ndarray
        The decreasing regularization grid ``[n_lambdas]``.
    betas : np.ndarray
        Per-replicate solutions ``[n_folds, n_lambdas, p(, T)]``.
    cv_loss : np.ndarray
        Held-out mean datafit loss per (fold, lambda) — the datafit's
        ``value`` semantics (half-MSE for quadratic losses, mean log-loss
        for logistic). NaN for replicates with no held-out rows (a
        bootstrap replicate that resampled every row).
    cv_mean, cv_std : np.ndarray
        Mean / standard deviation of ``cv_loss`` over valid folds,
        ``[n_lambdas]``.
    best_index, best_lambda : int, float
        Argmin of ``cv_mean`` and the corresponding grid point.
    kkts, n_epochs : np.ndarray
        Final KKT violation and inner epochs per (fold, lambda).
    fold_weights : np.ndarray
        The raw (un-normalized) train-weight matrix ``[n_folds, n]`` the
        grid solved — 0/1 rows for k-fold CV, counts for bootstrap.
    n_outer : int
        Total vmapped outer iterations driven across the sweep.
    times : np.ndarray
        Wall-clock seconds PER scheduler round (each entry is one round's
        own duration; ``np.cumsum`` recovers the sweep-cumulative curve).
    occupancy : np.ndarray
        Fraction of the lane pool holding live (fold, lambda) work at each
        round's dispatch — the lane scheduler's retire/backfill keeps this
        at 1.0 until the work queue drains (DESIGN.md §12).
    n_rounds : int
        Scheduler rounds driven (== dispatches of the sweep).
    resumed_from : int, optional
        The checkpoint step this grid resumed from (``resume=...`` runs
        only); ``None`` for uninterrupted grids.
    retraces : dict
        The engine's compile counter — the proof behind "one compile per
        working-set bucket across the whole grid".
    n_dispatches, n_host_syncs : int
        Fused-step launches / blocking host readbacks of the sweep (the
        contract is at most one of each per outer iteration — chunking
        amortizes far below that).
    diagnostics : repro.obs.SolveDiagnostics
        Structured convergence record (DESIGN.md §11): run with
        ``obs=...``, ``curves`` holds the drained per-lane telemetry rings
        reshaped to ``[n_folds, n_lambdas, max_outer]`` per field, and the
        registry mirrors the sweep counters under ``grid.*`` names.
    """
    lambdas: np.ndarray
    betas: np.ndarray                 # [F, n_lambdas, p(, T)]
    cv_loss: np.ndarray               # [F, n_lambdas]
    cv_mean: np.ndarray
    cv_std: np.ndarray
    best_index: int
    best_lambda: float
    kkts: np.ndarray
    n_epochs: np.ndarray
    fold_weights: np.ndarray
    n_outer: int = 0
    times: Optional[np.ndarray] = None
    occupancy: Optional[np.ndarray] = None
    n_rounds: int = 0
    resumed_from: Optional[int] = None
    retraces: dict = field(default_factory=dict)
    n_dispatches: int = 0
    n_host_syncs: int = 0
    diagnostics: SolveDiagnostics = field(default_factory=SolveDiagnostics)

    def summary(self) -> str:
        """Render the convergence diagnostics (per-lane rollup)."""
        return self.diagnostics.summary()


@functools.lru_cache(maxsize=32)
def _heldout_fn_cached(datafit):
    def lane(Xb, y, h):
        return datafit.value(Xb, y, h)

    return jax.jit(jax.vmap(lane, in_axes=(0, None, 0)))


def _heldout_fn(datafit):
    """Jitted [S, n(, T)] x [n(, T)] x [S, n] -> [S] held-out mean-loss map
    over the grid driver's lanes (each lane carries its own fold's held-out
    weight row), cached per (hashable) datafit so repeated grids reuse the
    compilation; datafits with unhashable leaves fall back to a per-call
    closure."""
    try:
        return _heldout_fn_cached(datafit)
    except TypeError:
        return _heldout_fn_cached.__wrapped__(datafit)


def _grid_fingerprint(lambdas, W, dims, tol):
    """Identity of a grid problem, stored in every checkpoint: a resumed
    run must present the SAME lambdas, fold weights, shapes, and solver
    knobs (mesh shape deliberately excluded — restore is mesh-elastic)."""
    digest = hashlib.sha1(np.ascontiguousarray(W).tobytes()).digest()[:8]
    return {
        "lambdas": np.asarray(lambdas, np.float64),
        "w_digest": np.frombuffer(digest, np.uint64).copy(),
        "dims": np.asarray(dims, np.int64),
        "tol": np.float64(tol),
    }


def _grid_state_template(sched, bshape, xshape, dtype, fingerprint,
                         use_ring, max_outer):
    """Zero-valued pytree matching a grid checkpoint's exact structure and
    shapes — the `restore_pytree` template (DESIGN.md §12). The round-log
    leaves (`times`, `occupancy`) grow with the round count, so their
    template entries are plain ints: shape-less template leaves accept
    whatever length the snapshot recorded."""
    F, nlam, S = sched.n_folds, sched.n_lambdas, sched.n_lanes
    state = {
        "round": np.int64(0), "bucket": np.int64(0),
        "total_outer": np.int64(0), "n_syncs": np.int64(0),
        "n_disp": np.int64(0),
        "sched": {k: np.zeros_like(np.asarray(v))
                  for k, v in sched.state_dict().items()},
        "lane_betas": np.zeros((S,) + bshape, dtype),
        "lane_xbs": np.zeros((S,) + xshape, dtype),
        "lane_lams": np.zeros(S, np.float64),
        "lane_fold": np.zeros(S, np.int64),
        "bank_betas": np.zeros((F,) + bshape, dtype),
        "bank_xbs": np.zeros((F,) + xshape, dtype),
        "out_betas": np.zeros((F, nlam) + bshape, dtype),
        "out_loss": np.zeros((F, nlam), dtype),
        "kkts_out": np.zeros((F, nlam)),
        "eps_out": np.zeros((F, nlam), np.int64),
        "item_done": np.zeros((F, nlam), np.uint8),
        "times": 0, "occupancy": 0,
        "fingerprint": fingerprint,
    }
    if use_ring:
        from repro.obs.rings import _FLOAT_FIELDS, _INT_FIELDS
        state["curves"] = {
            **{f: np.full((F, nlam, max_outer), np.nan, dtype)
               for f in _FLOAT_FIELDS},
            **{f: np.full((F, nlam, max_outer), -1, np.int32)
               for f in _INT_FIELDS}}
        state["n_recorded"] = np.zeros((F, nlam), np.int64)
    return state


def _emit_progress(progress, **ev):
    """Deliver one grid-progress event: ``progress`` is a callable (gets the
    event dict) or any other truthy value (one stderr line per event)."""
    if not progress:
        return
    if callable(progress):
        progress(dict(ev))
        return
    print("[cross_val_path] "
          + " ".join(f"{k}={v}" for k, v in ev.items()), file=sys.stderr)


def cross_val_path(X, y, datafit=None, penalty=None, *, lambdas=None,
                   n_lambdas=30, lambda_min_ratio=1e-2, cv=5,
                   fold_weights=None, sample_weight=None, seed=0, tol=1e-6,
                   vmap_chunk=10, p0=64, max_outer=50, eps_inner_frac=0.3,
                   sync_every=8, checkpoint=None, resume=None,
                   engine=None, mesh=None, data_axis="data",
                   model_axis="model", obs=None, progress=None,
                   **engine_kw) -> GridResult:
    """Solve a (fold x lambda) grid simultaneously through the fused step.

    Every fold (or bootstrap replicate) is a sample-weight leaf on the SAME
    (X, y) — 0/1 train membership for k-fold CV, resample counts for the
    bootstrap — so all replicates share one static shape and the whole grid
    vmaps through the chunked fused step: a FIXED pool of
    ``n_folds * vmap_chunk`` lanes runs (fold, lambda) cells from a global
    work queue, each fold warm-starts from its own densest completed
    solution, bucket escalation is shared across lanes, and held-out
    scores reduce device-side from the lanes' full-row residuals
    (DESIGN.md §9). One compiled step per working-set bucket serves the
    entire grid; the host syncs once per dispatch, every dispatch runs up
    to ``sync_every`` device-resident outer iterations, and at each sync
    the lane scheduler RETIRES converged lanes and BACKFILLS their slots
    from the queue, so late rounds run at full occupancy instead of
    padding to the initial lane count (DESIGN.md §12).

    Parameters
    ----------
    X : array_like, scipy sparse matrix, or Design
        Design matrix, shared by every replicate (dense, CSC-native sparse,
        or mesh-sharded — weights shard with the data axis).
    y : array_like
        Targets ``[n]`` (or ``[n, T]`` multitask).
    datafit : object, optional
        Defaults to ``Quadratic()``; must declare ``SUPPORTS_WEIGHTS``.
    penalty : object, optional
        Penalty template with a ``lam`` hyper-parameter leaf; defaults to
        ``L1(1.0)``.
    lambdas : array_like, optional
        Explicit grid (validated and sorted decreasing); otherwise
        ``n_lambdas`` geometric points from the full-data ``lambda_max``.
    cv : int, optional
        Number of k-fold splits (ignored when ``fold_weights`` is given).
    fold_weights : array_like, optional
        Explicit replicate-weight matrix ``[n_replicates, n]`` — e.g.
        ``repro.data.folds.bootstrap_weights`` resample counts. Held-out
        rows of a replicate are its zero-weight rows.
    sample_weight : array_like, optional
        Base observation weights multiplied into every replicate's train
        AND held-out weights.
    seed : int, optional
        Fold-assignment shuffle seed (k-fold mode).
    tol, p0, max_outer, eps_inner_frac : optional
        Per-lane outer KKT tolerance and chunk-driver knobs (as in
        :func:`reg_path`).
    vmap_chunk : int, optional
        Width of the lane pool in lambdas: every dispatch drives
        ``n_folds * vmap_chunk`` lanes, so one compiled program per bucket
        serves the whole grid. Slots the work queue can no longer fill
        keep their retired (converged) state and take the fused step's
        skip path — dead lanes never reach the held-out scores or the
        telemetry curves.
    sync_every : int, optional
        Outer-iteration block per dispatch: the device loop runs at most
        this many outers before the host syncs, retires converged lanes,
        and backfills. Smaller blocks react faster (higher occupancy) at
        the cost of more dispatches; the 1-sync/1-dispatch-per-outer
        budget contract holds for any value >= 1.
    checkpoint : repro.checkpoint.CheckpointConfig, optional
        Snapshot the full grid cursor (scheduler, device lane states,
        warm-start bank, accumulated outputs) under
        ``checkpoint.directory`` every ``checkpoint.every_n_chunks``
        scheduler rounds, through the sharding-agnostic
        ``repro.checkpoint.Checkpointer`` (atomic tmp-rename writes,
        optional async; DESIGN.md §12).
    resume : str, optional
        Directory holding a checkpoint written by a previous run of the
        SAME grid (validated by fingerprint): restores the latest snapshot
        — onto any mesh shape — and continues, replaying the exact
        schedule; the resumed result is bit-identical (dense/CSC) to an
        uninterrupted run with zero extra dispatches on the resumed
        segment (tests/test_grid_fault.py).
    engine, mesh, data_axis, model_axis : optional
        As in :func:`reg_path`; ``**engine_kw`` is restricted to engine
        config keys (M, max_epochs, accel, use_fp_score, use_gram,
        use_kernels).
    obs : repro.obs.Obs, optional
        Observability handle (DESIGN.md §11): records the grid's spans
        (grid → grid_round → grid_lanes, grid_dispatch, grid_sync,
        grid_retire, grid_bank, grid_fill, grid_schedule; written to any
        profiler capture with or without ``obs``) on ``obs.tracer``, plus
        a per-lane telemetry ring through every chunk dispatch — per-outer
        convergence curves for all (fold, lambda) lanes land on
        ``GridResult.diagnostics`` as ``[n_folds, n_lambdas, max_outer]``
        arrays. Zero extra dispatches.
    progress : callable or bool, optional
        Per-round progress events: a callable receives dicts like
        ``{"event": "bucket", "chunk": 1, "n_chunks": 3, "bucket": 64,
        "lanes_converged": 7, "n_lanes": 15, "lambdas_done": 10,
        "n_lambdas": 30, "elapsed_s": ..., "eta_s": ...}`` — one
        ``"bucket"`` event per dispatch, an ``"event": "chunk"`` dict on
        every round that retired lanes (``lambdas_done`` counts fully
        completed lambda columns), and an ``"event": "resume"`` dict when
        a run restores from a checkpoint; any other truthy value prints
        one stderr line per event.

    Returns
    -------
    GridResult
        Per-fold paths, the CV curve (mean/std held-out loss), the best
        lambda, and engine telemetry.
    """
    sp = obs.span if obs is not None else span
    with sp("grid") as grid_ev:
        datafit = Quadratic() if datafit is None else datafit
        penalty = L1(1.0) if penalty is None else penalty
        design = as_design(X)
        y = jnp.asarray(y)
        n, p = design.shape
        unsupported = set(engine_kw) - set(_ENGINE_KW)
        if unsupported:
            raise ValueError(f"cross_val_path does not support kwargs "
                             f"{sorted(unsupported)}")

        # replicate weights: 0/1 k-fold membership or explicit bootstrap counts
        if fold_weights is not None:
            W = np.asarray(fold_weights, np.float64)
            if W.ndim != 2 or W.shape[1] != n:
                raise ValueError(
                    f"fold_weights must be [n_replicates, n={n}], got shape "
                    f"{W.shape}")
            if not np.all(np.isfinite(W)) or np.any(W < 0):
                raise ValueError(
                    "fold_weights must be finite and non-negative")
        else:
            from repro.data.folds import kfold_weights
            W = kfold_weights(n, cv, seed=seed)
        H = np.where(W == 0.0, 1.0, 0.0)          # held-out indicator per fold
        if sample_weight is not None:
            sw = np.asarray(
                normalize_weights(sample_weight, n, jnp.float64))
            W = W * sw[None, :]
            H = H * sw[None, :]
        train_sums = W.sum(axis=1)
        if np.any(train_sums <= 0):
            raise ValueError("every fold/replicate needs at least one "
                             "training sample with positive weight")
        held_sums = H.sum(axis=1)
        valid_fold = held_sums > 0
        if not valid_fold.any():
            raise ValueError(
                "no replicate has any held-out rows (every fold_weights row "
                "is all-nonzero): there is nothing to cross-validate on — "
                "held-out rows are a replicate's zero-weight rows")

        if lambdas is None:
            lmax = lambda_max(design, y, datafit, sample_weight=sample_weight)
            lambdas = lmax * np.geomspace(1.0, lambda_min_ratio, n_lambdas)
        lambdas = _check_grid(lambdas)
        nlam = len(lambdas)

        if engine is None:
            engine = make_engine(penalty, datafit, shared=True, mesh=mesh,
                                 data_axis=data_axis, model_axis=model_axis,
                                 **engine_kw)
        elif mesh is not None and engine.mesh is not mesh:
            raise ValueError("cross_val_path(mesh=..., engine=...): the "
                             "engine was built for a different mesh; pass "
                             "mesh to make_engine instead")
        n_tasks = y.shape[1] if y.ndim == 2 else 0
        engine.validate(datafit, penalty, n_tasks, shape=design.shape,
                        design=design, weighted=True)

        if engine.mesh is not None:
            design, y, _ = _place_design(engine, design, y)
        # per-fold train weights normalized to sum n (the
        # row-subset-equivalent scaling, DESIGN.md §9) and held-out weights
        # normalized to mean weights
        Wd = jnp.asarray(W * (n / train_sums)[:, None], design.dtype)
        Hd = jnp.asarray(
            H * np.where(valid_fold, n / np.maximum(held_sums, 1e-300),
                         0.0)[:, None], design.dtype)
        if engine.mesh is not None:
            from repro.launch.shardings import weight_spec
            sh = NamedSharding(engine.mesh,
                               weight_spec(engine.data_axis, n_lanes=1))
            Wd, Hd = jax.device_put(Wd, sh), jax.device_put(Hd, sh)
        F = W.shape[0]
        # the grid-driver Lipschitz hot path: one weighted column-square
        # reduction per fold (Pallas segment-sum kernel on ELL sparse designs)
        L_folds = jnp.stack(
            [design.lipschitz(datafit, Wd[f], backend=engine.config.backend)
             for f in range(F)])
        offset = datafit.grad_offset(p, design.dtype)
        heldout = _heldout_fn(datafit)

        bshape = (p,) if n_tasks == 0 else (p, n_tasks)
        xshape = (n,) if n_tasks == 0 else (n, n_tasks)
        policy = BucketPolicy(p0=p0)
        chunk = max(1, min(int(vmap_chunk), nlam))
        S = F * chunk                          # the fixed lane pool
        sync_every = max(1, int(sync_every))
        sched = LaneScheduler(F, nlam, S, max_outer)
        dtype = design.dtype
        use_ring = obs is not None and getattr(obs, "rings", True)
        fingerprint = _grid_fingerprint(
            lambdas, W, [n, p, F, nlam, S, max_outer, sync_every, n_tasks,
                         int(use_ring)], tol)
        beta_sh, xb_sh = engine.lane_shardings(n_tasks)

        def _put_lanes(b_arr, x_arr):
            b, x = jnp.asarray(b_arr, dtype), jnp.asarray(x_arr, dtype)
            if beta_sh is not None:
                b, x = jax.device_put(b, beta_sh), jax.device_put(x, xb_sh)
            return b, x

        ckpt = checkpoint.make() if checkpoint is not None else None
        if use_ring:
            from repro.obs.rings import _FLOAT_FIELDS, _INT_FIELDS
            curves_store = {
                **{f: np.full((F, nlam, max_outer), np.nan, dtype)
                   for f in _FLOAT_FIELDS},
                **{f: np.full((F, nlam, max_outer), -1, np.int32)
                   for f in _INT_FIELDS}}
            counts_store = np.zeros((F, nlam), np.int64)
        else:
            curves_store = counts_store = None

        # driver-side lane maps, kept verbatim for DEAD slots too: their stale
        # entries keep every dispatch input identical between a resumed and an
        # uninterrupted run, which is what makes resume bit-exact
        lams_l = np.zeros(S, np.float64)
        fold_host = np.zeros(S, np.int64)
        lamidx_host = np.zeros(S, np.int64)
        kkts_out = np.zeros((F, nlam))
        eps_out = np.zeros((F, nlam), np.int64)
        item_done = np.zeros((F, nlam), np.uint8)
        times, occupancy = [], []
        round_idx, total_outer, n_syncs = 0, 0, 0
        resumed_from = None

        if resume is not None:
            template = _grid_state_template(sched, bshape, xshape, dtype,
                                            fingerprint, use_ring, max_outer)
            try:
                state, step = restore_pytree(template, str(resume))
            except KeyError as e:
                # leaf-set mismatch = the snapshot was written with a different
                # telemetry setting (obs on/off changes the checkpoint pytree)
                raise ValueError(
                    f"resume={str(resume)!r}: checkpoint leaf set does not "
                    f"match this grid — pass the same obs= (telemetry "
                    f"on/off) the checkpointing run used ({e})") from e
            state = jax.tree_util.tree_map(lambda a: np.array(a), state)
            fp = state["fingerprint"]
            if not (np.array_equal(fp["lambdas"], fingerprint["lambdas"])
                    and np.array_equal(fp["w_digest"], fingerprint["w_digest"])
                    and np.array_equal(fp["dims"], fingerprint["dims"])
                    and float(fp["tol"]) == float(tol)):
                raise ValueError(
                    f"resume={str(resume)!r}: the checkpoint was written by a "
                    f"different grid (lambdas / fold weights / shapes / "
                    f"solver knobs mismatch); refusing to mix solver states")
            sched.load_state(state["sched"])
            betas_l, Xbs_l = _put_lanes(state["lane_betas"], state["lane_xbs"])
            bank_b, bank_x = _put_lanes(state["bank_betas"], state["bank_xbs"])
            out_betas = jnp.asarray(state["out_betas"], dtype)
            out_loss = jnp.asarray(state["out_loss"], dtype)
            lams_l = np.asarray(state["lane_lams"], np.float64)
            fold_host = np.asarray(state["lane_fold"], np.int64)
            lamidx_host = np.where(sched.lane_lam >= 0, sched.lane_lam,
                                   0).astype(np.int64)
            kkts_out = np.array(state["kkts_out"])
            eps_out = np.asarray(state["eps_out"], np.int64)
            item_done = np.asarray(state["item_done"], np.uint8)
            times = [float(t) for t in np.atleast_1d(state["times"])]
            occupancy = [float(v) for v in np.atleast_1d(state["occupancy"])]
            round_idx = int(state["round"])
            bucket = int(state["bucket"])
            total_outer = int(state["total_outer"])
            n_syncs = int(state["n_syncs"])
            # report CUMULATIVE sweep counters: the resumed GridResult equals
            # the uninterrupted run's, dispatches included
            dispatches0 = engine.n_dispatches - int(state["n_disp"])
            resumed_from = int(step)
            if use_ring:
                curves_store = {k: np.array(v)
                                for k, v in state["curves"].items()}
                counts_store = np.array(state["n_recorded"])
            if obs is not None:
                obs.registry.inc("grid.resume.count")
                obs.registry.set_gauge("grid.resume.step", resumed_from)
            _emit_progress(progress, event="resume", round=round_idx,
                           step=resumed_from, items_done=int(sched.n_retired),
                           n_items=sched.total_items)
        else:
            dispatches0 = engine.n_dispatches
            betas_l, Xbs_l = _put_lanes(np.zeros((S,) + bshape),
                                        np.zeros((S,) + xshape))
            bank_b, bank_x = _put_lanes(np.zeros((F,) + bshape),
                                        np.zeros((F,) + xshape))
            out_betas = jnp.zeros((F, nlam) + bshape, dtype)
            out_loss = jnp.zeros((F, nlam), dtype)
            for s, f, j in sched.fill():
                lams_l[s], fold_host[s], lamidx_host[s] = lambdas[j], f, j
            bucket = policy.first_bucket(0, p)

        def _snapshot():
            st = {"round": np.int64(round_idx), "bucket": np.int64(bucket),
                  "total_outer": np.int64(total_outer),
                  "n_syncs": np.int64(n_syncs),
                  "n_disp": np.int64(engine.n_dispatches - dispatches0),
                  "sched": sched.state_dict(),
                  "lane_betas": betas_l, "lane_xbs": Xbs_l,
                  "lane_lams": lams_l, "lane_fold": fold_host,
                  "bank_betas": bank_b, "bank_xbs": bank_x,
                  "out_betas": out_betas, "out_loss": out_loss,
                  "kkts_out": kkts_out, "eps_out": eps_out,
                  "item_done": item_done,
                  "times": np.asarray(times, np.float64),
                  "occupancy": np.asarray(occupancy, np.float64),
                  "fingerprint": fingerprint}
            if use_ring:
                st["curves"] = curves_store
                st["n_recorded"] = counts_store
            return st

        n_chunks = -(-nlam // chunk)        # nominal lower bound on rounds
        t0 = _now()
        dirty = True                        # lane tensors need (re)gathering
        if isinstance(grid_ev, dict):
            grid_ev["args"].update(folds=F, n_lambdas=nlam, chunk=chunk)
        while not sched.done:
            with sp("grid_round", round=round_idx, bucket=int(bucket),
                    n_lanes=S):
                t_round = _now()
                if dirty:
                    with sp("grid_lanes"):
                        fold_dev = jnp.asarray(fold_host)
                        w_lanes = jnp.take(Wd, fold_dev, axis=0)
                        L_lanes = jnp.take(L_folds, fold_dev, axis=0)
                        H_lanes = jnp.take(Hd, fold_dev, axis=0)
                        lams_dev = jnp.asarray(lams_l, dtype)
                    dirty = False
                occupancy.append(sched.occupancy)
                mo = sched.dispatch_budget(sync_every)
                ring = TelemetryRing.alloc(max_outer, dtype, lanes=S) \
                    if use_ring else None
                bucket_used = bucket
                with sp("grid_dispatch"):
                    out = engine.chunk(bucket, design, y, lams_dev, betas_l,
                                       Xbs_l, L_lanes, offset, datafit,
                                       penalty, tol, eps_inner_frac, mo,
                                       w=w_lanes, obs=ring)
                if ring is not None:
                    (betas_l, Xbs_l, kkts_d, _, gcounts_d, neps_d, it_d,
                     ring) = out
                else:
                    betas_l, Xbs_l, kkts_d, _, gcounts_d, neps_d, it_d = out
                # ONE blocking host sync per dispatch: the convergence scalars
                # that drive the scheduler (the budget contract)
                with sp("grid_sync"):
                    kkts_c, gcounts_c, neps_c, it = jax.device_get(
                        (kkts_d, gcounts_d, neps_d, it_d))
                n_syncs += 1
                it = int(it)
                total_outer += it
                with sp("grid_retire"):
                    rep = sched.observe(kkts_c, gcounts_c, neps_c, it, tol)
                    if ring is not None:
                        # obs-only output path (not a scheduler sync, matching
                        # the pre-§12 chunk driver's drain accounting)
                        curves, counts = ring.drain()
                        for s, r0 in zip(rep.active, rep.rec_before):
                            f, j = int(fold_host[s]), int(lamidx_host[s])
                            r0 = int(r0)
                            r1 = min(r0 + it, max_outer)
                            for k, v in curves.items():
                                curves_store[k][f, j, r0:r1] = v[s, :r1 - r0]
                            counts_store[f, j] = r1
                    if rep.retired:
                        # harvest retired lanes device-side: scatter into the
                        # output buffers, no host transfer mid-grid (dead lanes
                        # never reach the held-out scores — there is no
                        # padding)
                        loss_l = heldout(Xbs_l, y, H_lanes)
                        sl_np = np.array([r.slot for r in rep.retired])
                        fl = np.array([r.fold for r in rep.retired])
                        jl = np.array([r.lam_idx for r in rep.retired])
                        sl = jnp.asarray(sl_np)
                        out_betas = out_betas.at[fl, jl].set(betas_l[sl])
                        out_loss = out_loss.at[fl, jl].set(loss_l[sl])
                        kkts_out[fl, jl] = kkts_c[sl_np]
                        eps_out[fl, jl] = np.array(
                            [r.n_epochs for r in rep.retired], np.int64)
                        item_done[fl, jl] = 1
                if rep.bank_updates:
                    with sp("grid_bank"):
                        fb = np.array([u[0] for u in rep.bank_updates])
                        sb = jnp.asarray(
                            np.array([u[1] for u in rep.bank_updates]))
                        bank_b = bank_b.at[fb].set(betas_l[sb])
                        bank_x = bank_x.at[fb].set(Xbs_l[sb])
                with sp("grid_fill"):
                    assigns = sched.fill()
                    if assigns:
                        sl_np = np.array([a[0] for a in assigns])
                        fl = np.array([a[1] for a in assigns])
                        jl = np.array([a[2] for a in assigns])
                        fl_d = jnp.asarray(fl)
                        betas_l = betas_l.at[jnp.asarray(sl_np)].set(
                            jnp.take(bank_b, fl_d, axis=0))
                        Xbs_l = Xbs_l.at[jnp.asarray(sl_np)].set(
                            jnp.take(bank_x, fl_d, axis=0))
                        lams_l[sl_np] = lambdas[jl]
                        fold_host[sl_np] = fl
                        lamidx_host[sl_np] = jl
                        dirty = True
                with sp("grid_schedule"):
                    # bucket for the next dispatch: escalate when a continuing
                    # lane outgrew it; an all-retired boundary may de-escalate
                    # to what the fresh warm starts need (the old chunk-handoff
                    # behavior)
                    cont = rep.continuing
                    if len(cont):
                        if bucket < p and np.any(
                                _GROWTH * gcounts_c[cont] > bucket):
                            bucket = max(policy.escalate(bucket, p),
                                         policy.next_bucket(
                                             bucket,
                                             int(np.max(gcounts_c[cont])), p))
                        if assigns:
                            bucket = max(bucket, max(
                                policy.first_bucket(int(sched.bank_gcount[f]),
                                                    p)
                                for f in fl))
                    elif assigns:
                        bucket = max(policy.first_bucket(
                            int(sched.bank_gcount[f]), p) for f in fl)
                    round_idx += 1
                    times.append(_now() - t_round)
                    elapsed = _now() - t0
                    lambdas_done = int(np.sum(np.all(item_done == 1, axis=0)))
                    _emit_progress(
                        progress, event="bucket", chunk=round_idx - 1,
                        n_chunks=n_chunks, bucket=int(bucket_used),
                        lanes_converged=int(np.sum(kkts_c <= tol)),
                        n_lanes=S, lambdas_done=lambdas_done, n_lambdas=nlam,
                        elapsed_s=elapsed)
                    if rep.retired:
                        _emit_progress(
                            progress, event="chunk", chunk=round_idx - 1,
                            n_chunks=n_chunks, bucket=int(bucket_used),
                            lanes_converged=int(np.sum(kkts_c <= tol)),
                            n_lanes=S, lambdas_done=lambdas_done,
                            n_lambdas=nlam, elapsed_s=elapsed,
                            eta_s=elapsed / max(lambdas_done, 1)
                            * (nlam - lambdas_done))
                if ckpt is not None and round_idx % ckpt.every == 0 \
                        and not sched.done:
                    with sp("grid_checkpoint", round=round_idx):
                        ckpt.save(_snapshot(), round_idx)

        if ckpt is not None:
            ckpt.wait()                     # surface async write errors here
        betas_np, loss_np = jax.device_get((out_betas, out_loss))
        betas_out = np.array(betas_np, np.float64)
        loss_out = np.array(loss_np, np.float64)
        loss_out[~valid_fold] = np.nan
        cv_mean = np.mean(loss_out[valid_fold], axis=0) if valid_fold.any() \
            else np.full(nlam, np.nan)
        cv_std = np.std(loss_out[valid_fold], axis=0) if valid_fold.any() \
            else np.full(nlam, np.nan)
        best = int(np.argmin(cv_mean)) if np.isfinite(cv_mean).any() else 0
        occ = np.asarray(occupancy)
        grid = GridResult(lambdas=lambdas, betas=betas_out, cv_loss=loss_out,
                          cv_mean=cv_mean, cv_std=cv_std, best_index=best,
                          best_lambda=float(lambdas[best]), kkts=kkts_out,
                          n_epochs=eps_out, fold_weights=W,
                          n_outer=total_outer,
                          times=np.asarray(times), occupancy=occ,
                          n_rounds=round_idx, resumed_from=resumed_from,
                          retraces=dict(engine.retraces),
                          n_dispatches=engine.n_dispatches - dispatches0,
                          n_host_syncs=n_syncs)
        reg = grid.diagnostics.registry
        reg.set_counter("grid.n_host_syncs", n_syncs)
        reg.set_counter("grid.n_dispatches", grid.n_dispatches)
        reg.set_counter("grid.n_outer", total_outer)
        reg.set_counter("grid.n_rounds", round_idx)
        reg.set_mapping("grid.retraces", dict(engine.retraces))
        reg.set_gauge("grid.lane_occupancy",
                      float(occ.mean()) if occ.size else 1.0)
        for v in occupancy:
            reg.observe("grid.occupancy", float(v))
        if curves_store is not None:
            grid.diagnostics.curves.update(curves_store)
            grid.diagnostics.n_recorded = counts_store
        if obs is not None:
            obs.registry.inc("grid.count")
            obs.registry.set_gauge("grid.lane_occupancy",
                                   float(occ.mean()) if occ.size else 1.0)
            obs.note_solve(grid.diagnostics)
    return grid


def support_metrics(beta, beta_true, X=None, y=None):
    """F1 of support recovery + estimation/prediction errors (Figure 1)."""
    beta = np.asarray(beta)
    beta_true = np.asarray(beta_true)
    s_hat = beta != 0
    s_true = beta_true != 0
    tp = int(np.sum(s_hat & s_true))
    prec = tp / max(int(np.sum(s_hat)), 1)
    rec = tp / max(int(np.sum(s_true)), 1)
    f1 = 2 * prec * rec / max(prec + rec, 1e-30)
    out = {
        "nnz": int(np.sum(s_hat)),
        "precision": prec, "recall": rec, "f1": f1,
        "exact_support": bool(np.array_equal(s_hat, s_true)),
        "est_err": float(np.linalg.norm(beta - beta_true)
                         / max(np.linalg.norm(beta_true), 1e-30)),
    }
    if X is not None and y is not None:
        resid = np.asarray(y) - np.asarray(X) @ beta
        out["pred_err"] = float(np.linalg.norm(resid) ** 2 / len(resid))
    return out
