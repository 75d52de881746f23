"""Dense AR(1)-correlated Gaussian designs, made on the device.

The paper's simulated setup (skglm, NeurIPS 2022, Appendix E.5), as
``repro.data.synth.make_correlated_design`` draws it on the host: column j is
x_j = rho x_{j-1} + sqrt(1 - rho^2) eps_j with x_0 = eps_0, so every column
has unit variance and corr(x_j, x_k) = rho^|j-k|. Each design's target comes
from its own sparse truth of `n_nonzero` ones and Gaussian noise at
signal-to-noise ratio `snr`.

Here the recurrence runs over blocks of B columns: inside a block
x_block = x_prev rho^(1..B) + eps_block T, with T the B x B upper-triangular
Toeplitz matrix s rho^(k-l), s = sqrt(1 - rho^2) (the first column of the
design takes s = 1), so a design is one scan of matmuls, and all the
designs of a run come from one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .seeds import device_key, host_rng

_BLOCK = 128


def _design(key, *, n, p, rho, dtype):
    B = _BLOCK
    nb = -(-p // B)
    s = float(np.sqrt(1.0 - rho * rho))
    k = np.arange(B)
    # T[l, k] = s rho^(k-l) for k >= l: the weight of eps_l in x_k
    T = np.where(k[None, :] >= k[:, None],
                 s * rho ** np.maximum(k[None, :] - k[:, None], 0), 0.0)
    T0 = T.copy()
    T0[0] = rho ** k                     # x_0 = eps_0: unit weight on eps_0
    decay = rho ** (k + 1.0)             # weight of x_prev in x_k
    eps = jax.random.normal(key, (nb, n, B), jnp.float32)
    hi = jax.lax.Precision.HIGHEST

    def block(prev, e_T):
        e, Tb = e_T
        x = prev[:, None] * decay[None, :] + jnp.dot(e, Tb, precision=hi)
        return x[:, -1], x

    Ts = jnp.asarray(np.stack([T0] + [T] * (nb - 1)), jnp.float32)
    _, xs = jax.lax.scan(block, jnp.zeros((n,), jnp.float32), (eps, Ts))
    return jnp.transpose(xs, (1, 0, 2)).reshape(n, nb * B)[:, :p].astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("n", "p", "rho", "dtype", "snr"))
def _problems(key, beta, *, n, p, rho, dtype, snr):
    K = beta.shape[0]
    kx, kn = jax.random.split(key)
    X = jax.lax.map(lambda kk: _design(kk, n=n, p=p, rho=rho, dtype=dtype),
                    jax.random.split(kx, K))                       # [K, n, p]
    signal = jnp.einsum("knp,kp->kn", X, beta.astype(dtype),
                        precision=jax.lax.Precision.HIGHEST)
    noise = jax.random.normal(kn, signal.shape, dtype)
    noise = noise * (jnp.linalg.norm(signal, axis=1, keepdims=True)
                     / (snr * jnp.linalg.norm(noise, axis=1, keepdims=True)))
    return tuple(X[k] for k in range(K)), signal + noise


def generate(cfg: dict, seed: int, n_problems: int):
    """`n_problems` designs and their targets, drawn from `seed`, on the
    device: (list of X [n, p], Y [n_problems, n])."""
    n, p = int(cfg["n"]), int(cfg["p"])
    rng = host_rng(seed, "truth")
    beta = np.zeros((n_problems, p), np.float32)
    for k in range(n_problems):
        beta[k, rng.choice(p, int(cfg["n_nonzero"]), replace=False)] = 1.0
    Xs, Y = _problems(device_key(seed, "design"), jnp.asarray(beta),
                      n=n, p=p, rho=float(cfg["rho"]),
                      dtype=jnp.dtype(cfg["dtype"]), snr=float(cfg["snr"]))
    return list(Xs), Y
