"""Distributed solves for huge-scale designs — facade over the mesh-native
engine (DESIGN.md §6).

The paper's target regime — "millions of samples and features" — exceeds one
device's HBM, so X is sharded over a (data, model) mesh: samples over `data`,
features over `model`. Historically this module carried its own host-driven
outer loop (~7 dispatches and syncs per outer iteration, full retrace per
lambda, quadratic datafits only). That loop is gone: `solve_distributed` now
delegates to `core.solver.solve(mesh=...)`, whose fused shard_map outer step
gives sharded solves the exact same 1-dispatch / 1-sync budget, bucketed
compilation, warm starts and Xb-form datafits (Logistic, QuadraticSVC) as a
single-device solve. `shard_design` remains the supported way to place a
design on a mesh.

`make_distributed_ops` (the seed-era bag of per-stage jitted primitives) is
kept only for the production dry-run's per-primitive cost accounting and is
DEPRECATED: new code should use the engine through `solve(mesh=...)`.
"""
from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.launch.shardings import design_specs

from .solver import SolveResult, solve
from .working_set import select_working_set_local, violation_scores

__all__ = ["shard_design", "solve_distributed", "make_distributed_ops"]


def shard_design(mesh, X, y, data_axis="data", model_axis="model"):
    """Place X [n, p] over (data, model) and y [n] over (data,)."""
    xspec, yspec, _ = design_specs(data_axis, model_axis)
    Xs = jax.device_put(X, NamedSharding(mesh, xspec))
    ys = jax.device_put(y, NamedSharding(mesh, yspec))
    return Xs, ys


def solve_distributed(mesh, X, y, datafit, penalty, *, tol=1e-6, max_outer=50,
                      max_epochs=1000, M=5, p0=64, data_axis="data",
                      model_axis="model", **solve_kw) -> SolveResult:
    """Distributed Algorithm 1 on a (data, model) mesh.

    Thin facade over ``core.solver.solve(mesh=...)`` — one fused shard_map
    dispatch and one host sync per outer iteration, any datafit the engine
    supports (Gram-form quadratics AND Xb-form Logistic / QuadraticSVC).
    X, y may be pre-sharded (see shard_design); unsharded input is placed on
    the mesh automatically.
    """
    return solve(X, y, datafit, penalty, tol=tol, max_outer=max_outer,
                 max_epochs=max_epochs, M=M, p0=p0, mesh=mesh,
                 data_axis=data_axis, model_axis=model_axis, **solve_kw)


def make_distributed_ops(mesh, n, p, penalty, *, data_axis="data",
                         model_axis="model"):
    """DEPRECATED: per-stage sharded primitives of the seed-era distributed
    loop. The mesh-native engine (core/engine.py) fuses all of them into one
    program; this factory survives only for the production dry-run's
    per-primitive cost/collective accounting (launch/dryrun_solver.py).

    The penalty's hyper-parameters are closed over (the engine, by contrast,
    treats them as pytree leaves and never retraces on a lambda change).
    """
    warnings.warn(
        "make_distributed_ops is deprecated: use solve(mesh=...) / "
        "reg_path(mesh=...) on the mesh-native engine instead",
        DeprecationWarning, stacklevel=2)
    xspec, yspec, bspec = design_specs(data_axis, model_axis)

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, xspec),
                           NamedSharding(mesh, yspec)),
             out_shardings=NamedSharding(mesh, bspec))
    def lipschitz(X, y):
        del y
        return jnp.sum(X * X, axis=0) / n

    def _scores_local(X_loc, r_loc, beta_loc, L_loc):
        # grad_loc = X_loc^T r_loc summed over the data axis: one psum.
        grad_loc = jnp.einsum("np,n->p", X_loc, r_loc)
        grad_loc = jax.lax.psum(grad_loc, data_axis)
        return violation_scores(penalty, beta_loc, grad_loc, L_loc)

    scores = jax.jit(shard_map(
        _scores_local, mesh=mesh, in_specs=(xspec, yspec, bspec, bspec),
        out_specs=bspec, check_vma=False))

    @partial(jax.jit, static_argnames=("k",))
    def global_topk(scores_arr, gsupp, k: int):
        """Exact distributed top-k (working_set.select_working_set_local):
        min(k, shard_width) local candidates per shard, so concentrated
        generalized support is never silently dropped."""
        local = partial(select_working_set_local, ws_size=k,
                        model_axis=model_axis)
        return shard_map(local, mesh=mesh, in_specs=(bspec, bspec),
                         out_specs=P(), check_vma=False)(scores_arr, gsupp)

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, xspec), None),
             out_shardings=NamedSharding(mesh, P(data_axis, None)))
    def gather_cols(X, ws):
        return X[:, ws]

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, P(data_axis, None)),
                           NamedSharding(mesh, yspec)),
             out_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P())))
    def gram(X_ws, y):
        G = X_ws.T @ X_ws / n
        c = X_ws.T @ y / n
        return G, c

    @partial(jax.jit,
             in_shardings=(NamedSharding(mesh, P(data_axis, None)), None),
             out_shardings=NamedSharding(mesh, yspec))
    def apply_ws(X_ws, beta_ws):
        return X_ws @ beta_ws

    @jax.jit
    def scatter(beta, ws, beta_ws):
        return beta.at[ws].set(beta_ws)

    return {"lipschitz": lipschitz, "scores": scores, "topk": global_topk,
            "gather": gather_cols, "gram": gram, "apply_ws": apply_ws,
            "scatter": scatter}
