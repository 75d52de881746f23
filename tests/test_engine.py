"""Device-resident engine tests (DESIGN.md §3).

Covers the PR-1 acceptance criteria:
  * Gram vs Xb inner-solver equivalence on quadratic datafits.
  * Warm-started paths equal per-lambda cold solves to tolerance.
  * A 30-lambda Lasso path (n=1000, p=2000) compiles the fused step at most
    once per working-set bucket (engine retrace counter), and the host
    performs <= 1 blocking sync per outer iteration.
  * backend="pallas" (use_kernels=True) agrees with backend="jax" to 1e-6 on
    beta for every penalty/datafit pair the kernel codec supports.
  * The penalty-parameter codec round-trips every penalty class and raises
    on penalties it cannot encode.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MCP, SCAD, L05, L23, L1, L1L2, BlockL1, BlockMCP,
                        Box, Logistic, Quadratic, QuadraticSVC, lambda_max,
                        make_engine, reg_path, solve)
from repro.core import penalties as pen_mod
from repro.core.working_set import BucketPolicy, next_pow2
from repro.data.synth import make_classification, make_correlated_design
from repro.kernels.common import (PENALTY_FIELDS, UnsupportedPenaltyError,
                                  make_penalty, penalty_params)


# ---------------------------------------------------------------- inner unify
@pytest.mark.parametrize("penalty", [L1(0.02), L1L2(0.02, 0.6),
                                     MCP(0.02, 3.0), SCAD(0.02, 3.7),
                                     L05(0.004)],
                         ids=lambda p: type(p).__name__)
def test_gram_and_xb_inner_solvers_agree(lasso_data, penalty):
    """One SubproblemSolver interface, two state representations: identical
    solutions on quadratic datafits."""
    X, y, _ = lasso_data
    lam = lambda_max(X, y) / 20
    penalty = dataclasses.replace(penalty, lam=lam) \
        if hasattr(penalty, "lam") else penalty
    res_g = solve(X, y, Quadratic(), penalty, tol=1e-9, use_gram=True)
    res_x = solve(X, y, Quadratic(), penalty, tol=1e-9, use_gram=False)
    assert res_g.converged and res_x.converged
    np.testing.assert_allclose(np.asarray(res_g.beta),
                               np.asarray(res_x.beta), atol=1e-6)


# --------------------------------------------------------------- path = cold
def test_warm_path_equals_cold_solves(lasso_data):
    X, y, _ = lasso_data
    engine = make_engine(L1(1.0), Quadratic())
    path = reg_path(X, y, L1(1.0), n_lambdas=6, lambda_min_ratio=0.03,
                    tol=1e-9, engine=engine)
    for lam, beta_warm in zip(path.lambdas, path.betas):
        cold = solve(X, y, Quadratic(), L1(float(lam)), tol=1e-9)
        np.testing.assert_allclose(beta_warm, np.asarray(cold.beta),
                                   atol=1e-6)


def test_chunked_path_matches_sequential(lasso_data):
    X, y, _ = lasso_data
    seq = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-9, engine=make_engine(L1(1.0), Quadratic()))
    chk = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-9, engine=make_engine(L1(1.0), Quadratic()),
                   vmap_chunk=4)
    assert np.all(chk.kkts <= 1e-9)
    np.testing.assert_allclose(chk.betas, seq.betas, atol=1e-6)


# ------------------------------------------------- retrace / host-sync budget
def test_one_compile_per_bucket_over_30_lambda_path():
    """Acceptance: a 30-lambda Lasso path on (n=1000, p=2000) synthetic data
    compiles the fused outer step at most ONCE per power-of-two ws bucket."""
    X, y, _ = make_correlated_design(n=1000, p=2000, n_nonzero=50, rho=0.5,
                                     snr=5.0, seed=0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    engine = make_engine(L1(1.0), Quadratic())
    path = reg_path(X, y, L1(1.0), n_lambdas=30, lambda_min_ratio=1e-2,
                    tol=1e-6, engine=engine)
    assert np.all(path.kkts <= 1e-6)
    assert path.retraces, "engine recorded no compilations"
    ladder = set(BucketPolicy(p0=64).ladder(2000))
    for bucket, count in path.retraces.items():
        assert count == 1, f"bucket {bucket} compiled {count}x"
        assert bucket in ladder
    # every outer iteration across the path was one fused dispatch
    assert path.n_dispatches == int(np.sum(path.n_outer)) + \
        np.count_nonzero(path.kkts <= 1e-6)


def test_single_host_sync_per_outer_iteration(lasso_data):
    X, y, _ = lasso_data
    lam = lambda_max(X, y) / 30
    res = solve(X, y, Quadratic(), L1(lam), tol=1e-9)
    # cold start: exactly one blocking readback per outer iteration driven
    assert res.n_host_syncs == len(res.kkt_history)
    warm = solve(X, y, Quadratic(), L1(lam), tol=1e-9, beta0=res.beta)
    # warm start adds a single pre-loop probe sync
    assert warm.n_host_syncs == len(warm.kkt_history) + 1


def test_blocked_sweep_does_the_plain_sweeps_work(monkeypatch):
    """At a reduced AR(1) shape, solve and cross_val_path with the blocked
    Gram CD sweep do the plain one-coordinate-per-trip sweep's work: the
    same outer iterations, epochs and support, and in float64 for the
    Lasso the same coefficients bit for bit, alone and under the chunk
    step's lane vmap, each in its layout of q's update.
    engine.cd_sweep_block names the block size of every bucket that ran."""
    from repro.core import cross_val_path
    from repro.core import engine as engine_mod
    from repro.core.cd import cd_sweep_block
    from repro.kernels.ref import cd_epoch_gram_ref

    def plain_epoch(sweep, b, q, pen, axpy_per_column):
        K = b.shape[0]
        return cd_epoch_gram_ref(sweep.G[:K, :K], sweep.c[:K], b, q,
                                 sweep.L[:K], pen)
    X, y, _ = make_correlated_design(n=200, p=500, n_nonzero=50, rho=0.6,
                                     snr=5.0, seed=3)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam = lambda_max(X, y) / 20

    def run():
        es, eg = make_engine(L1(lam), Quadratic()), \
            make_engine(L1(lam), Quadratic())
        res = solve(X, y, Quadratic(), L1(lam), tol=1e-6, engine=es)
        grid = cross_val_path(X, y, Quadratic(), L1(1.0), n_lambdas=6,
                              lambda_min_ratio=0.05, cv=3, vmap_chunk=2,
                              tol=1e-6, engine=eg)
        return res, grid, es, eg

    res, grid, es, eg = run()
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "gram_sweep_epoch", plain_epoch)
        res0, grid0, _, _ = run()
    assert res.beta.dtype == np.float64
    assert (res.n_outer, res.n_epochs) == (res0.n_outer, res0.n_epochs)
    np.testing.assert_array_equal(np.asarray(res.beta) != 0,
                                  np.asarray(res0.beta) != 0)
    np.testing.assert_array_equal(res.beta, res0.beta)
    assert grid.n_outer == grid0.n_outer
    np.testing.assert_array_equal(grid.n_epochs, grid0.n_epochs)
    np.testing.assert_array_equal(np.asarray(grid.betas) != 0,
                                  np.asarray(grid0.betas) != 0)
    np.testing.assert_array_equal(grid.betas, grid0.betas)
    for engine in (es, eg):
        buckets = set()
        for key in engine.retraces:
            while isinstance(key, tuple):
                key = key[-1] if key[0] != "chunk" else key[1]
            buckets.add(key)
        assert len(buckets) > 1
        assert engine.metrics.mapping("engine.cd_sweep_block") == \
            {b: cd_sweep_block(b) for b in buckets}


# ------------------------------------------------------- solve() edge cases
def test_solve_max_outer_zero_no_crash(lasso_data):
    X, y, _ = lasso_data
    res = solve(X, y, Quadratic(), L1(0.1), max_outer=0)
    assert res.n_outer == 0 and not res.converged
    assert res.kkt == float("inf")


def test_solve_n_outer_counts_exhausted_loop(lasso_data):
    X, y, _ = lasso_data
    lam = lambda_max(X, y) / 50
    res = solve(X, y, Quadratic(), L1(lam), tol=1e-14, max_outer=3,
                max_epochs=5)
    assert not res.converged
    assert res.n_outer == 3                       # not 2 (seed undercounted)
    assert len(res.kkt_history) == 3


# ------------------------------------------------------------ kernel backend
KERNEL_CASES = [
    (Quadratic(), L1(1.0)),
    (Quadratic(), L1L2(1.0, 0.6)),
    (Quadratic(), MCP(1.0, 3.0)),
    (Quadratic(), SCAD(1.0, 3.7)),
    (Quadratic(), L05(1.0)),
    (Quadratic(), L23(1.0)),
    (Logistic(), L1(1.0)),
    (Logistic(), MCP(1.0, 3.0)),
]
KERNEL_IDS = [f"{type(d).__name__}-{type(p).__name__}"
              for d, p in KERNEL_CASES]


@pytest.mark.parametrize("datafit,penalty", KERNEL_CASES, ids=KERNEL_IDS)
def test_kernel_and_jax_backends_agree(datafit, penalty):
    """Acceptance: use_kernels=True/False agree to 1e-6 on beta for every
    penalty/datafit pair the kernel codec supports (Gram AND Xb kernels)."""
    if isinstance(datafit, Logistic):
        X, y, _ = make_classification(n=120, p=240, n_nonzero=10, seed=0)
    else:
        X, y, _ = make_correlated_design(n=120, p=240, n_nonzero=10, seed=0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    # logistic at small lambda is near-separable (flat basin: beta precision
    # degrades far below the KKT tol); stay in the well-conditioned regime
    frac = 3 if isinstance(datafit, Logistic) else 8
    lam = lambda_max(X, y, datafit) / frac
    penalty = dataclasses.replace(penalty, lam=lam)
    kw = dict(tol=1e-10, max_outer=80)
    res_j = solve(X, y, datafit, penalty, **kw)
    res_k = solve(X, y, datafit, penalty, use_kernels=True, **kw)
    assert res_k.converged
    np.testing.assert_allclose(np.asarray(res_k.beta),
                               np.asarray(res_j.beta), atol=1e-6)


def test_kernel_backend_svc_agrees(logreg_data):
    from repro.core.api import svc_dual
    X, y, _ = logreg_data
    X, y = X[:80, :60], y[:80]
    res_j, w_j = svc_dual(X, y, C=1.0, tol=1e-7)
    res_k, w_k = svc_dual(X, y, C=1.0, tol=1e-7, use_kernels=True)
    assert res_k.converged
    np.testing.assert_allclose(np.asarray(res_k.beta),
                               np.asarray(res_j.beta), atol=1e-6)


# ------------------------------------------------------------- penalty codec
ALL_PENALTIES = [L1(0.3), L1L2(0.3, 0.7), MCP(0.3, 3.0), SCAD(0.3, 3.7),
                 Box(0.8), L05(0.3), L23(0.3), BlockL1(0.3),
                 BlockMCP(0.3, 3.0)]


@pytest.mark.parametrize("penalty", ALL_PENALTIES,
                         ids=lambda p: type(p).__name__)
def test_penalty_codec_roundtrips(penalty):
    """Every penalty class in core.penalties round-trips exactly."""
    params = penalty_params(penalty)
    assert params.shape == (len(PENALTY_FIELDS[type(penalty)]),)
    rebuilt = make_penalty(type(penalty), params, params.dtype)
    for name in PENALTY_FIELDS[type(penalty)]:
        np.testing.assert_allclose(float(getattr(rebuilt, name)),
                                   float(getattr(penalty, name)))


def test_codec_covers_every_penalty_class():
    import dataclasses as dc
    classes = [getattr(pen_mod, n) for n in pen_mod.__all__
               if isinstance(getattr(pen_mod, n), type)
               and dc.is_dataclass(getattr(pen_mod, n))]
    assert classes, "no penalty classes found"
    for cls in classes:
        assert cls in PENALTY_FIELDS, f"{cls.__name__} missing from codec"


def test_codec_rejects_unregistered_and_per_coordinate():
    @dataclasses.dataclass(frozen=True)
    class ThreeParam:
        lam: float
        gamma: float
        tau: float

    with pytest.raises(UnsupportedPenaltyError):
        penalty_params(ThreeParam(0.1, 3.0, 0.5))   # not silently truncated

    weighted = L1(jnp.ones(7))                      # per-coordinate weights
    with pytest.raises(UnsupportedPenaltyError):
        penalty_params(weighted)


def test_kernel_solve_runs_block_penalties(multitask_data):
    """Block penalties run on the Pallas backend since the fused-kernel
    generalization (fused block scoring + jax block inner epochs)."""
    from repro.core.datafits import MultitaskQuadratic
    X, Y, _ = multitask_data
    res = solve(X, Y, MultitaskQuadratic(), BlockL1(0.1), use_kernels=True,
                max_outer=2)
    assert res.beta.shape == (X.shape[1], Y.shape[1])


# --------------------------------------------------- review-found regressions
def test_chunked_path_converges_on_dense_solutions():
    """Dense solutions (support > p/2): the chunk loop must keep iterating at
    bucket == p instead of bouncing to the host and giving up unconverged."""
    X, y, _ = make_correlated_design(n=200, p=64, n_nonzero=40, seed=0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    seq = reg_path(X, y, L1(1.0), n_lambdas=6, lambda_min_ratio=1e-3,
                   tol=1e-8, engine=make_engine(L1(1.0), Quadratic()))
    chk = reg_path(X, y, L1(1.0), n_lambdas=6, lambda_min_ratio=1e-3,
                   tol=1e-8, engine=make_engine(L1(1.0), Quadratic()),
                   vmap_chunk=3)
    assert np.all(chk.kkts <= 1e-8)
    np.testing.assert_allclose(chk.betas, seq.betas, atol=1e-6)


def test_chunked_path_rejects_unsupported_solve_kwargs(lasso_data):
    X, y, _ = lasso_data
    with pytest.raises(ValueError, match="use_ws"):
        reg_path(X, y, L1(1.0), n_lambdas=4, vmap_chunk=2, use_ws=False)


def test_xb_anderson_refresh_keeps_out_of_ws_residual(logreg_data):
    """XbSolver's Anderson refresh must preserve the residual of nonzero
    coordinates OUTSIDE the working set (ctx.Xb_base): without it the
    rebuilt Xb dropped bound-pinned Box/SVC coordinates (empty generalized
    support, legitimately outside ws) and the solver accepted a corrupted
    state while reporting convergence."""
    from repro.core.datafits import QuadraticSVC
    from repro.core.working_set import violation_scores
    X, y, _ = logreg_data
    X, y = X[:300, :60], y[:300]
    Z = (y[:, None] * X).T
    df, pen = QuadraticSVC(), Box(0.02)
    res_x = solve(Z, y, df, pen, tol=1e-7, p0=16, max_outer=300,
                  use_gram=False)
    res_g = solve(Z, y, df, pen, tol=1e-7, p0=16, max_outer=300)
    assert res_x.converged
    grad = Z.T @ df.raw_grad(Z @ res_x.beta, y) + \
        df.grad_offset(Z.shape[1], Z.dtype)
    true_kkt = float(jnp.max(violation_scores(pen, res_x.beta, grad,
                                              df.lipschitz(Z))))
    assert true_kkt <= 1e-7, (res_x.kkt, true_kkt)
    np.testing.assert_allclose(np.asarray(res_x.beta),
                               np.asarray(res_g.beta), atol=1e-6)


def test_box_at_bound_coords_outside_ws_stay_exact(logreg_data):
    """Box pins coordinates at C with *empty* generalized support, so they
    can leave the working set while nonzero. The gram subproblem must
    linearize at the incoming iterate (coupling term!) and Xb must update
    incrementally; the seed silently dropped both and reported fake
    convergence at small C."""
    from repro.core.datafits import QuadraticSVC
    X, y, _ = logreg_data
    X, y = X[:300, :60], y[:300]
    Z = (y[:, None] * X).T
    df, pen = QuadraticSVC(), Box(0.02)
    res = solve(Z, y, df, pen, tol=1e-7, p0=16, max_outer=300)
    assert res.converged
    grad = Z.T @ df.raw_grad(Z @ res.beta, y) + \
        df.grad_offset(Z.shape[1], Z.dtype)
    from repro.core.working_set import violation_scores
    true_kkt = float(jnp.max(violation_scores(pen, res.beta, grad,
                                              df.lipschitz(Z))))
    assert true_kkt <= 1e-7, (res.kkt, true_kkt)
    assert int(jnp.sum(res.beta >= 0.02)) > 50     # regime with bound-pinned


# ------------------------------------------------------------- bucket policy
def test_bucket_policy_ladder_and_escalation():
    pol = BucketPolicy(p0=64)
    assert pol.ladder(2000) == [64, 128, 256, 512, 1024, 2000]
    assert pol.first_bucket(0, 2000) == 64
    assert pol.next_bucket(64, 100, 2000) == 256
    assert pol.escalate(64, 2000) == 128
    assert pol.escalate(1024, 2000) == 2000
    assert pol.ladder(64) == [64]
    for b in pol.ladder(5000)[:-1]:
        assert b == next_pow2(b)
