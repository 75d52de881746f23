"""Anderson extrapolation (paper Algorithm 4).

Given the last M+1 iterates beta^(0..M), form U = [beta^(i+1) - beta^(i)]_i,
solve (U U^T + reg I) z = 1_M, c = z / sum(z), and return sum_i c_i beta^(i+1).
Cost O(M^2 K + M^3) as stated in Algorithm 2. The caller must guard acceptance
with an objective-decrease test (done in solver.inner_solve).
"""
from __future__ import annotations

import jax.numpy as jnp


def anderson_extrapolate(hist):
    """hist: [M+1, ...] iterate ring (oldest first). Returns extrapolated point."""
    M = hist.shape[0] - 1
    flat = hist.reshape(M + 1, -1)
    U = flat[1:] - flat[:-1]                          # [M, KT]
    UUt = U @ U.T                                     # [M, M]
    scale = jnp.trace(UUt) / M
    # Ridge relative to the Gram's scale. UUt carries rounding of order eps
    # of its dtype, so a ridge below ~100 eps (float32: 1.2e-5) is a no-op
    # that leaves a near-singular system; float64 keeps 1e-10.
    rel = max(1e-10, 100.0 * float(jnp.finfo(flat.dtype).eps))
    reg = rel * jnp.maximum(scale, 1e-30)
    z = jnp.linalg.solve(UUt + reg * jnp.eye(M, dtype=flat.dtype),
                         jnp.ones((M,), dtype=flat.dtype))
    denom = jnp.sum(z)
    c = z / jnp.where(jnp.abs(denom) > 1e-30, denom, 1.0)
    extr = c @ flat[1:]
    ok = jnp.all(jnp.isfinite(extr)) & (jnp.abs(denom) > 1e-30)
    out = jnp.where(ok, extr, flat[-1])
    return out.reshape(hist.shape[1:])
