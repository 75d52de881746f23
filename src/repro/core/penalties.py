"""Separable penalties g(beta) = sum_j g_j(beta_j) for Problem (1) of the paper.

Each penalty implements:
  value(beta)               -> scalar penalty value
  prox(x, step)             -> elementwise prox_{step * g_j}(x)
  subdiff_dist(grad, beta)  -> per-coordinate score_j = dist(-grad_j, d g_j(beta_j))
                               (Eq. 2 of the paper and its analogues)
  generalized_support(beta) -> bool mask, Definition 4
  HAS_SUBDIFF               -> False when the subdifferential score is uninformative
                               (l_q, 0<q<1: Appendix C) and the fixed-point score
                               score^cd must be used instead.

Penalties are registered as pytrees with their hyper-parameters as *leaves*, so a
jitted solver is not re-traced when lambda changes (regularization paths).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

__all__ = [
    "L1", "L1L2", "MCP", "SCAD", "L05", "L23", "Box",
    "BlockL1", "BlockMCP", "soft_threshold",
]


def _register(cls):
    """Register a penalty dataclass as a pytree (hyper-params are leaves)."""
    fields = [f.name for f in dataclasses.fields(cls)]

    def flatten(obj):
        return tuple(getattr(obj, f) for f in fields), None

    def unflatten(aux, children):
        del aux
        return cls(*children)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def soft_threshold(x, t):
    """Elementwise soft-thresholding ``sign(x) * max(|x| - t, 0)`` — the
    prox of ``t * |.|`` (the Lasso shrinkage operator)."""
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - t, 0.0)


@_register
@dataclass(frozen=True)
class L1:
    """g_j = lam * |.| (the Lasso penalty)."""
    lam: float
    HAS_SUBDIFF = True

    def value(self, beta):
        return self.lam * jnp.sum(jnp.abs(beta))

    def prox(self, x, step):
        return soft_threshold(x, step * self.lam)

    def subdiff_dist(self, grad, beta):
        at0 = jnp.maximum(jnp.abs(grad) - self.lam, 0.0)
        away = jnp.abs(grad + self.lam * jnp.sign(beta))
        return jnp.where(beta == 0.0, at0, away)

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class L1L2:
    """Elastic net: g_j = lam * (rho*|.| + (1-rho)/2 * (.)^2)."""
    lam: float
    rho: float
    HAS_SUBDIFF = True

    def value(self, beta):
        return self.lam * (self.rho * jnp.sum(jnp.abs(beta))
                           + 0.5 * (1.0 - self.rho) * jnp.sum(beta ** 2))

    def prox(self, x, step):
        return (soft_threshold(x, step * self.lam * self.rho)
                / (1.0 + step * self.lam * (1.0 - self.rho)))

    def subdiff_dist(self, grad, beta):
        at0 = jnp.maximum(jnp.abs(grad) - self.lam * self.rho, 0.0)
        away = jnp.abs(grad + self.lam * self.rho * jnp.sign(beta)
                       + self.lam * (1.0 - self.rho) * beta)
        return jnp.where(beta == 0.0, at0, away)

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class MCP:
    """Minimax concave penalty (Zhang 2010), Proposition 7 of the paper.

    MCP_{lam,gamma}(x) = lam|x| - x^2/(2 gamma)    if |x| <= gamma lam
                       = gamma lam^2 / 2           otherwise
    alpha-semi-convex iff gamma > step (Assumption 6 / Prop. 7).
    """
    lam: float
    gamma: float
    HAS_SUBDIFF = True

    def value(self, beta):
        a = jnp.abs(beta)
        inner = self.lam * a - a ** 2 / (2.0 * self.gamma)
        outer = 0.5 * self.gamma * self.lam ** 2
        return jnp.sum(jnp.where(a <= self.gamma * self.lam, inner, outer))

    def prox(self, x, step):
        # requires gamma > step for a single-valued prox (alpha-semi-convexity)
        a = jnp.abs(x)
        shrunk = soft_threshold(x, step * self.lam) / (1.0 - step / self.gamma)
        out = jnp.where(a <= self.gamma * self.lam, shrunk, x)
        return jnp.where(a <= step * self.lam, 0.0, out)

    def subdiff_dist(self, grad, beta):
        a = jnp.abs(beta)
        at0 = jnp.maximum(jnp.abs(grad) - self.lam, 0.0)
        mid = jnp.abs(grad + self.lam * jnp.sign(beta) - beta / self.gamma)
        flat = jnp.abs(grad)
        return jnp.where(beta == 0.0, at0,
                         jnp.where(a < self.gamma * self.lam, mid, flat))

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class SCAD:
    """SCAD penalty (Fan & Li); gamma > 2. Prox requires gamma > 1 + step."""
    lam: float
    gamma: float
    HAS_SUBDIFF = True

    def value(self, beta):
        a = jnp.abs(beta)
        lam, g = self.lam, self.gamma
        p1 = lam * a
        p2 = (2.0 * g * lam * a - a ** 2 - lam ** 2) / (2.0 * (g - 1.0))
        p3 = lam ** 2 * (g + 1.0) / 2.0
        return jnp.sum(jnp.where(a <= lam, p1, jnp.where(a <= g * lam, p2, p3)))

    def prox(self, x, step):
        lam, g = self.lam, self.gamma
        a = jnp.abs(x)
        r1 = soft_threshold(x, step * lam)
        r2 = ((g - 1.0) * x - jnp.sign(x) * g * lam * step) / (g - 1.0 - step)
        return jnp.where(a <= lam * (1.0 + step), r1,
                         jnp.where(a <= g * lam, r2, x))

    def subdiff_dist(self, grad, beta):
        lam, g = self.lam, self.gamma
        a = jnp.abs(beta)
        at0 = jnp.maximum(jnp.abs(grad) - lam, 0.0)
        low = jnp.abs(grad + lam * jnp.sign(beta))
        mid = jnp.abs(grad + jnp.sign(beta) * (g * lam - a) / (g - 1.0))
        flat = jnp.abs(grad)
        return jnp.where(beta == 0.0, at0,
                         jnp.where(a <= lam, low,
                                   jnp.where(a <= g * lam, mid, flat)))

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class L05:
    """l_{1/2} penalty: g_j = lam * |.|^{1/2} (Appendix C of the paper).

    The subdifferential at 0 is R, so subdiff_dist is uninformative: the solver
    must use the fixed-point score score^cd (HAS_SUBDIFF = False).
    Prox is the half-thresholding operator (Xu et al. 2012): zero exactly on
    [-(3/2)(step*lam)^{2/3}, (3/2)(step*lam)^{2/3}] (paper, Eq. 26).
    """
    lam: float
    HAS_SUBDIFF = False

    def value(self, beta):
        return self.lam * jnp.sum(jnp.sqrt(jnp.abs(beta)))

    def prox(self, x, step):
        t = step * self.lam
        a = jnp.abs(x)
        thresh = 1.5 * t ** (2.0 / 3.0)
        # Outside the dead zone z = u^2, u the largest root of
        # h(u) = u^3 - a u + t/2 (stationarity of 0.5 (z - a)^2 + t sqrt(z)).
        # h is convex and increasing right of that root and h(sqrt(a)) = t/2
        # > 0, so Newton from u0 = sqrt(a) descends monotonically onto it.
        # (Same root as the arccos form of Xu et al. 2012, but built from
        # ops the TPU kernel compiler lowers.)
        def newton(_, u):
            hp = 3.0 * u * u - a
            return u - (u * u * u - a * u + 0.5 * t) / jnp.where(hp > 0.0, hp,
                                                                 1.0)

        u = jax.lax.fori_loop(0, 30, newton, jnp.sqrt(a))
        return jnp.where(a <= thresh, 0.0, jnp.sign(x) * u * u)

    def subdiff_dist(self, grad, beta):
        # Only meaningful away from 0: |grad + lam * sign(beta)/(2 sqrt|beta|)|.
        a = jnp.abs(beta)
        away = jnp.abs(grad + self.lam * jnp.sign(beta) / (2.0 * jnp.sqrt(jnp.maximum(a, 1e-30))))
        return jnp.where(beta == 0.0, 0.0, away)

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class L23:
    """l_{2/3} penalty: g_j = lam * |.|^{2/3} (paper §2.1, Foucart & Lai).

    Like l_0.5 the subdifferential at 0 is R (HAS_SUBDIFF=False -> fixed-point
    score). The prox solves u^4 - |x| u + (2/3) step lam = 0 with u = z^{1/3}
    (stationarity of 0.5 (z-|x|)^2 + step lam z^{2/3} on z>0); we take the
    largest root by guarded Newton (jit-friendly, converges quadratically from
    u0 = |x|^{1/3}) and compare the objective against z = 0 exactly.
    """
    lam: float
    HAS_SUBDIFF = False

    def value(self, beta):
        return self.lam * jnp.sum(jnp.abs(beta) ** (2.0 / 3.0))

    def prox(self, x, step):
        t = step * self.lam
        a = jnp.abs(x)
        a_safe = jnp.maximum(a, 1e-30)
        # a^(1/3) as a power: the TPU kernel compiler has no cbrt
        u_max = a_safe ** (1.0 / 3.0)              # largest-root init

        def newton(_, u):
            h = u ** 4 - a_safe * u + (2.0 / 3.0) * t
            hp = 4.0 * u ** 3 - a_safe
            u = u - h / jnp.where(jnp.abs(hp) > 1e-30, hp, 1e-30)
            return jnp.clip(u, 0.0, u_max)

        u = jax.lax.fori_loop(0, 40, newton, u_max)
        z = u ** 3
        # exact global choice: objective at the stationary point vs at 0
        obj_z = 0.5 * (z - a) ** 2 + t * z ** (2.0 / 3.0)
        obj_0 = 0.5 * a ** 2
        stationary = jnp.abs(u ** 4 - a_safe * u + (2.0 / 3.0) * t) < 1e-6 * \
            jnp.maximum(a_safe ** 2, 1.0)
        take = stationary & (obj_z < obj_0) & (a > 0)
        return jnp.where(take, jnp.sign(x) * z, 0.0)

    def subdiff_dist(self, grad, beta):
        a = jnp.abs(beta)
        away = jnp.abs(grad + self.lam * (2.0 / 3.0) * jnp.sign(beta)
                       / jnp.maximum(a, 1e-30) ** (1.0 / 3.0))
        return jnp.where(beta == 0.0, 0.0, away)

    def generalized_support(self, beta):
        return beta != 0.0


@_register
@dataclass(frozen=True)
class Box:
    """Indicator of [0, C]: the dual-SVM 'penalty' (paper Eq. 34).

    Generalized support = {j : 0 < beta_j < C} (Definition 4: the subdifferential
    is a singleton only in the interior).
    """
    C: float
    HAS_SUBDIFF = True

    def value(self, beta):
        return jnp.zeros((), dtype=beta.dtype)

    def prox(self, x, step):
        del step
        return jnp.clip(x, 0.0, self.C)

    def subdiff_dist(self, grad, beta):
        at0 = jnp.maximum(-grad, 0.0)          # N_[0,C](0) = (-inf, 0]
        atC = jnp.maximum(grad, 0.0)           # N_[0,C](C) = [0, +inf)
        inside = jnp.abs(grad)
        return jnp.where(beta <= 0.0, at0, jnp.where(beta >= self.C, atC, inside))

    def generalized_support(self, beta):
        return (beta > 0.0) & (beta < self.C)


def _row_norms(W):
    return jnp.sqrt(jnp.sum(W ** 2, axis=-1))


@_register
@dataclass(frozen=True)
class BlockL1:
    """Multitask l_{2,1}: g_j(W_j:) = lam * ||W_j:||_2 (paper Appendix D)."""
    lam: float
    HAS_SUBDIFF = True

    def value(self, W):
        return self.lam * jnp.sum(_row_norms(W))

    def prox(self, x, step):
        # x: [..., T] one block (or batched blocks); Proposition 18.
        nrm = jnp.sqrt(jnp.sum(x ** 2, axis=-1, keepdims=True))
        scale = jnp.maximum(nrm - step * self.lam, 0.0) / jnp.maximum(nrm, 1e-30)
        return x * scale

    def subdiff_dist(self, grad, W):
        # grad, W: [p, T]
        gn = _row_norms(grad)
        wn = _row_norms(W)
        at0 = jnp.maximum(gn - self.lam, 0.0)
        away = _row_norms(grad + self.lam * W / jnp.maximum(wn, 1e-30)[:, None])
        return jnp.where(wn == 0.0, at0, away)

    def generalized_support(self, W):
        return _row_norms(W) != 0.0


@_register
@dataclass(frozen=True)
class BlockMCP:
    """Multitask MCP: g_j(W_j:) = MCP_{lam,gamma}(||W_j:||) via Proposition 18."""
    lam: float
    gamma: float
    HAS_SUBDIFF = True

    def _scalar(self):
        return MCP(self.lam, self.gamma)

    def value(self, W):
        return self._scalar().value(_row_norms(W))

    def prox(self, x, step):
        nrm = jnp.sqrt(jnp.sum(x ** 2, axis=-1, keepdims=True))
        p = self._scalar().prox(nrm, step)
        return x * p / jnp.maximum(nrm, 1e-30)

    def subdiff_dist(self, grad, W):
        wn = _row_norms(W)
        gn = _row_norms(grad)
        at0 = jnp.maximum(gn - self.lam, 0.0)
        dirn = W / jnp.maximum(wn, 1e-30)[:, None]
        mid = _row_norms(grad + (self.lam - wn / self.gamma)[:, None] * dirn)
        flat = gn
        return jnp.where(wn == 0.0, at0,
                         jnp.where(wn < self.gamma * self.lam, mid, flat))

    def generalized_support(self, W):
        return _row_norms(W) != 0.0
