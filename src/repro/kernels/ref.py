"""Pure-jnp oracles for the Pallas kernels (the ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.cd import cd_epoch_xb
from repro.core.working_set import violation_scores


def cd_epoch_gram_ref(G, c, beta0, q0, L, penalty, epochs=1):
    """The plain cyclic sweep on the Gram subproblem, one coordinate per
    loop trip: grad = q - c, q = G beta. Scalar [K] or multitask [K, T]
    coordinates; penalties with per-coordinate parameters expose
    prox_at(x, step, j)."""
    def body(i, state):
        beta, q = state
        gj = q[i] - c[i]
        Lj = L[i]
        step = 1.0 / jnp.maximum(Lj, 1e-30)
        x = beta[i] - gj * step
        new = penalty.prox_at(x, step, i) if hasattr(penalty, "prox_at") \
            else penalty.prox(x, step)
        new = jnp.where(Lj > 0.0, new, beta[i])
        delta = new - beta[i]
        q = q + (G[:, i] * delta if delta.ndim == 0
                 else G[:, i][:, None] * delta[None, :])
        return beta.at[i].set(new), q

    beta, q = beta0, q0
    for _ in range(epochs):
        beta, q = jax.lax.fori_loop(0, G.shape[0], body, (beta, q))
    return beta, q


def cd_epoch_xb_ref(Xt_ws, y, beta0, Xb0, L, offset, datafit, penalty, epochs=1):
    beta, Xb = beta0, Xb0
    for _ in range(epochs):
        beta, Xb = cd_epoch_xb(Xt_ws, y, beta, Xb, L, offset, datafit, penalty)
    return beta, Xb


def ws_score_ref(X, r, beta, L, offset, penalty, use_fp=False):
    grad = X.T @ r + offset
    return violation_scores(penalty, beta, grad, L, use_fixed_point=use_fp)
