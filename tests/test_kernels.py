"""Pallas kernel tests: shape/dtype sweeps, allclose against ref.py oracles.

Kernels run with interpret=True on CPU (assignment contract); the oracles are
the pure-jnp implementations in repro.kernels.ref. The blocked Gram sweep of
the jax backend (repro.core.cd) is held to the same oracle.
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cd import (_blocked_sweep, cd_epoch_gram, cd_sweep_block,
                           gram_sweep, gram_sweep_epoch)
from repro.core.datafits import Logistic, Quadratic, QuadraticSVC
from repro.core.penalties import (MCP, SCAD, L05, L1, L1L2, L23, BlockL1,
                                  BlockMCP, Box, soft_threshold)
from repro.kernels import ops, ref
from repro.kernels.common import SCALAR_COORD_PENALTIES, penalty_params

PENALTIES = [L1(0.11), L1L2(0.11, 0.6), MCP(0.11, 3.0), SCAD(0.11, 3.7),
             Box(0.8), L05(0.05)]
IDS = [type(p).__name__ for p in PENALTIES]


def _tol(dtype):
    return {"float32": 2e-5, "float64": 1e-12}[np.dtype(dtype).name]


def _gram_inputs(K, dtype, seed=0):
    rng = np.random.default_rng(seed)
    n = 3 * K
    X = rng.standard_normal((n, K)).astype(dtype)
    y = rng.standard_normal(n).astype(dtype)
    G = (X.T @ X / n).astype(dtype)
    c = (X.T @ y / n).astype(dtype)
    beta0 = (rng.standard_normal(K) * 0.1).astype(dtype)
    q0 = G @ beta0
    L = np.diag(G).astype(dtype)
    return map(jnp.asarray, (G, c, beta0, q0, L))


@pytest.mark.parametrize("penalty", PENALTIES, ids=IDS)
@pytest.mark.parametrize("K", [8, 13, 64, 200])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_cd_epoch_gram_matches_ref(penalty, K, dtype):
    G, c, beta0, q0, L = _gram_inputs(K, dtype)
    params = penalty_params(penalty)
    for epochs in (1, 3):
        beta_k, q_k = ops.cd_epoch_gram(G, c, beta0, q0, L, type(penalty),
                                        params, epochs=epochs, interpret=True)
        beta_r, q_r = ref.cd_epoch_gram_ref(G, c, beta0, q0, L, penalty,
                                            epochs=epochs)
        np.testing.assert_allclose(beta_k, beta_r, atol=_tol(dtype), rtol=1e-5)
        np.testing.assert_allclose(q_k, q_r, atol=_tol(dtype), rtol=1e-5)


@dataclass(frozen=True)
class _WeightedL1:
    """L1 with a weight per coordinate, reached only through prox_at."""
    lam: float
    weights: jax.Array

    def prox_at(self, x, step, j):
        return soft_threshold(x, step * self.lam * self.weights[j])


def _sweep_penalties(K, dtype):
    scalar = [L1(0.11), L1L2(0.11, 0.6), MCP(0.11, 3.0), SCAD(0.11, 3.7),
              Box(0.8), L05(0.05), L23(0.05)]
    assert {type(p) for p in scalar} == set(SCALAR_COORD_PENALTIES)
    weights = jnp.asarray(np.linspace(0.2, 2.0, K).astype(dtype))
    return ([(p, None) for p in scalar] + [(_WeightedL1(0.11, weights), None)]
            + [(BlockL1(0.11), 3), (BlockMCP(0.11, 3.0), 3)])


@pytest.mark.parametrize("case", range(10), ids=[
    "L1", "L1L2", "MCP", "SCAD", "Box", "L05", "L23", "prox_at",
    "BlockL1-T3", "BlockMCP-T3"])
@pytest.mark.parametrize("K", [8, 13, 64, 200, 1024])
@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_blocked_gram_sweep_matches_plain_loop(case, K, dtype):
    """The blocked sweep of cd_epoch_gram (B coordinates a loop trip) gives
    the plain one-coordinate-per-trip loop's beta and q: scalar [K] and
    multitask [K, T] coordinates, K a multiple of B and not, alone and
    under a vmap over lanes in the layout of q's update the chunk step
    uses (one axpy per column). Coordinates with
    L = 0 are inert, which the padding to a multiple of B rests on: they
    keep their beta, and padded ones come back untouched."""
    penalty, T = _sweep_penalties(K, dtype)[case]
    G, c, beta0, q0, L = _gram_inputs(K, dtype)
    if T is not None:
        rng = np.random.default_rng(1)
        beta0 = jnp.asarray((rng.standard_normal((K, T)) * 0.1).astype(dtype))
        c = jnp.asarray(np.asarray(c)[:, None]
                        * np.linspace(0.5, 1.5, T).astype(dtype))
        q0 = G @ beta0
    L = L.at[::7].set(0.0)
    plain = jax.jit(lambda b, q: ref.cd_epoch_gram_ref(G, c, b, q, L,
                                                       penalty))
    one = lambda b, q: cd_epoch_gram(G, c, b, q, L, penalty)

    def lanes(b, q):                   # a vmap over lanes, as the chunk step
        epoch = lambda b, q: gram_sweep_epoch(gram_sweep(G, c, L), b, q,
                                              penalty, axpy_per_column=True)
        bb, qq = jax.vmap(epoch)(jnp.stack([b, b]), jnp.stack([q, q]))
        return bb[1], qq[1]

    for sweep in (jax.jit(one), jax.jit(lanes)):
        for epochs in (1, 5):
            beta, q = beta_r, q_r = beta0, q0
            for _ in range(epochs):
                beta, q = sweep(beta, q)
                beta_r, q_r = plain(beta_r, q_r)
            assert beta.shape == beta0.shape and q.shape == q0.shape
            np.testing.assert_allclose(beta, beta_r, atol=_tol(dtype),
                                       rtol=1e-5)
            np.testing.assert_allclose(q, q_r, atol=_tol(dtype), rtol=1e-5)
            np.testing.assert_array_equal(beta[::7], beta0[::7])
    B = cd_sweep_block(K)
    Kp = -(-K // B) * B
    if Kp != K:
        pad = lambda v: jnp.pad(v, [(0, Kp - K)] + [(0, 0)] * (v.ndim - 1))
        beta_p, q_p = jax.jit(
            lambda *a: _blocked_sweep(gram_sweep(*a[:3]), *a[3:], penalty,
                                      B))(G, c, L, pad(beta0), pad(q0))
        np.testing.assert_array_equal(beta_p[K:], 0.0)
        np.testing.assert_array_equal(q_p[K:], 0.0)


@pytest.mark.parametrize("penalty", [L1(0.07), MCP(0.07, 3.0), Box(0.9)],
                         ids=["L1", "MCP", "Box"])
@pytest.mark.parametrize("datafit,kind", [
    (Quadratic(), "quadratic"), (Logistic(), "logistic"),
    (QuadraticSVC(), "svc")], ids=["quad", "logistic", "svc"])
@pytest.mark.parametrize("K,n", [(13, 40), (16, 48), (96, 128)])
def test_cd_epoch_xb_matches_ref(penalty, datafit, kind, K, n):
    dtype = "float64"
    rng = np.random.default_rng(1)
    Xt = jnp.asarray(rng.standard_normal((K, n)).astype(dtype))
    y = jnp.asarray(np.sign(rng.standard_normal(n)).astype(dtype))
    beta0 = jnp.asarray((rng.standard_normal(K) * 0.05).astype(dtype))
    Xb0 = beta0 @ Xt
    L = jnp.sum(Xt * Xt, axis=1)
    if kind == "quadratic":
        L = L / n
    elif kind == "logistic":
        L = L / (4 * n)
    offset = datafit.grad_offset(K, Xt.dtype)
    params = penalty_params(penalty)
    beta_k, Xb_k = ops.cd_epoch_xb(Xt, y, beta0, Xb0, L, offset,
                                   type(penalty), params, kind, epochs=2,
                                   interpret=True)
    beta_r, Xb_r = ref.cd_epoch_xb_ref(Xt, y, beta0, Xb0, L, offset, datafit,
                                       penalty, epochs=2)
    np.testing.assert_allclose(beta_k, beta_r, atol=1e-11, rtol=1e-8)
    np.testing.assert_allclose(Xb_k, Xb_r, atol=1e-11, rtol=1e-8)


@pytest.mark.parametrize("penalty", PENALTIES, ids=IDS)
@pytest.mark.parametrize("n,p,bp,bn", [
    (128, 256, 64, 64), (256, 512, 256, 128), (64, 128, 128, 64)])
def test_ws_score_matches_ref(penalty, n, p, bp, bn):
    dtype = "float32"
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.standard_normal((n, p)).astype(dtype))
    r = jnp.asarray(rng.standard_normal(n).astype(dtype))
    beta = jnp.asarray(
        (rng.standard_normal(p) * (rng.random(p) < 0.3)).astype(dtype))
    L = jnp.sum(X * X, axis=0) / n
    offset = jnp.zeros(p, X.dtype)
    use_fp = not penalty.HAS_SUBDIFF
    params = penalty_params(penalty)
    got = ops.ws_score(X, r, beta, L, offset, type(penalty), params,
                       use_fp=use_fp, bp=bp, bn=bn, interpret=True)
    want = ref.ws_score_ref(X, r, beta, L, offset, penalty, use_fp=use_fp)
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=2e-3)


def test_ws_score_fp64_tight():
    penalty = MCP(0.09, 3.0)
    rng = np.random.default_rng(3)
    n, p = 128, 256
    X = jnp.asarray(rng.standard_normal((n, p)))
    r = jnp.asarray(rng.standard_normal(n))
    beta = jnp.asarray(rng.standard_normal(p) * (rng.random(p) < 0.3))
    L = jnp.sum(X * X, axis=0) / n
    offset = jnp.zeros(p, X.dtype)
    params = penalty_params(penalty)
    got = ops.ws_score(X, r, beta, L, offset, type(penalty), params,
                       bp=128, bn=64, interpret=True)
    want = ref.ws_score_ref(X, r, beta, L, offset, penalty)
    np.testing.assert_allclose(got, want, atol=1e-10, rtol=1e-8)


def test_kernel_solver_end_to_end_equivalence():
    """A full inner solve using kernel epochs matches the pure-JAX epochs."""
    rng = np.random.default_rng(4)
    n, K = 120, 32
    X = rng.standard_normal((n, K))
    y = rng.standard_normal(n)
    G = jnp.asarray(X.T @ X / n)
    c = jnp.asarray(X.T @ y / n)
    L = jnp.diag(G)
    pen = MCP(0.15, 3.0)
    params = penalty_params(pen)
    beta_k = jnp.zeros(K)
    q_k = G @ beta_k
    beta_r, q_r = beta_k, q_k
    for _ in range(10):
        beta_k, q_k = ops.cd_epoch_gram(G, c, beta_k, q_k, L, MCP, params,
                                        epochs=5, interpret=True)
        beta_r, q_r = ref.cd_epoch_gram_ref(G, c, beta_r, q_r, L, pen, epochs=5)
    np.testing.assert_allclose(beta_k, beta_r, atol=1e-10)


def test_solver_with_kernel_epochs_matches():
    """solve(use_kernels=True) routes Gram epochs through the Pallas kernel
    and must match the pure-JAX path exactly."""
    import jax.numpy as jnp
    from repro.core import Quadratic, solve
    from repro.core.api import lambda_max
    from repro.data.synth import make_correlated_design

    X, y, _ = make_correlated_design(n=120, p=240, n_nonzero=10, seed=0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam = lambda_max(X, y) / 5
    r_ref = solve(X, y, Quadratic(), MCP(lam, 3.0), tol=1e-8)
    r_ker = solve(X, y, Quadratic(), MCP(lam, 3.0), tol=1e-8,
                  use_kernels=True)
    assert r_ker.converged
    np.testing.assert_allclose(np.asarray(r_ker.beta), np.asarray(r_ref.beta),
                               atol=1e-10)
