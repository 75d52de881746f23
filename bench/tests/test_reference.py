"""The float64 KKT reference passes a converged answer and fails a
perturbed one; its weighted form is a fold's problem; the control reads
farther from it than float32 rounding."""
import numpy as np
import pytest
from scipy import sparse as sp

from bench.reference import control, kkt as ref


def _cd(X, y, lam, w=None, iters=3000):
    """Plain float64 cyclic coordinate descent on
    (1/2n) sum w (y - Xb)^2 + lam |b|_1."""
    n, p = X.shape
    w = np.ones(n) if w is None else w
    b = np.zeros(p)
    r = y.copy()
    col = (w[:, None] * X * X).sum(axis=0) / n
    for _ in range(iters):
        for j in range(p):
            z = b[j] + X[:, j] @ (w * r) / n / col[j]
            new = np.sign(z) * max(abs(z) - lam / col[j], 0.0)
            r -= X[:, j] * (new - b[j])
            b[j] = new
    return b


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    n, p = 60, 30
    X = rng.standard_normal((n, p))
    y = X[:, :4] @ np.array([2.0, -1.0, 1.5, 0.5]) \
        + 0.3 * rng.standard_normal(n)
    lam = 0.2 * np.max(np.abs(X.T @ y)) / n
    return X, y, lam


def test_converged_passes_perturbed_fails(problem):
    X, y, lam = problem
    b = _cd(X, y, lam)
    good = ref.kkt(X, y[:, None], b[:, None], [lam])[0]
    assert good < 1e-10
    bent = b.copy()
    bent[np.argmax(np.abs(b))] *= 1.01
    assert ref.kkt(X, y[:, None], bent[:, None], [lam])[0] > 1e-3 * lam


def test_weighted_is_the_folds_problem(problem):
    X, y, lam = problem
    n = X.shape[0]
    W = ref.fold_train_weights(n, 5, [1, 2, 3, 4])
    w = W[2] * n / W[2].sum()
    b = _cd(X, y, lam, w)
    assert ref.kkt(X, y[:, None], b[:, None], [lam], w[:, None])[0] < 1e-10
    assert ref.kkt(X, y[:, None], b[:, None], [lam])[0] > 1e-3 * lam


def test_sparse_and_blocked_dense_agree(problem, monkeypatch):
    X, y, lam = problem
    B = np.random.default_rng(1).standard_normal((X.shape[1], 3))
    Y = np.repeat(y[:, None], 3, axis=1)
    monkeypatch.setattr(ref, "ROW_BLOCK", 7)
    dense = ref.gradients(X, Y, B)
    np.testing.assert_allclose(ref.gradients(sp.csc_matrix(X), Y, B), dense,
                               rtol=1e-12, atol=1e-12)


def test_fold_split_is_a_partition():
    W = ref.fold_train_weights(103, 5, [9, 9])
    held = (W == 0).sum(axis=0)
    assert np.all(held == 1)
    assert sorted((W == 0).sum(axis=1)) == [20, 20, 21, 21, 21]


def _float32_kkt(X, y, b, lam):
    import jax
    import jax.numpy as jnp
    X32, y32, b32 = (jnp.asarray(a, jnp.float32) for a in (X, y, b))
    hi = jax.lax.Precision.HIGHEST
    g = jnp.dot(X32.T, (jnp.dot(X32, b32, precision=hi) - y32) / len(y),
                precision=hi)
    return ref.l1_dist(np.asarray(g, np.float64), b, lam).max()


@pytest.mark.parametrize("kind", ["dense", "csc"])
def test_control_reads_farther_than_float32(problem, kind):
    X, y, lam = problem
    X = X.astype(np.float32).astype(np.float64)
    b = _cd(X, y, lam)
    Xc = sp.csc_matrix(X.astype(np.float32)) if kind == "csc" \
        else X.astype(np.float32)
    exact = ref.kkt(X, y[:, None], b[:, None], [lam])[0]
    f32 = _float32_kkt(X, y, b, lam)
    low = control.kkt(Xc, y[:, None], b[:, None], [lam])[0]
    assert abs(low - exact) > 3 * abs(f32 - exact)
