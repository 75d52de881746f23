#!/usr/bin/env python
"""Run the solver's main path once on a TPU and check what comes out.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --mesh [--seed N]   # four chips

One chip runs four phases through the public entry points, in float32 (x64
off), on data made from ``--seed``:

  dense     make_correlated_design(n=10_000, p=20_000): Lasso at
            lambda_max/20 on the jax backend and on the Pallas kernels
            (fused head + Gram CD epochs), MCP on the kernels, and a
            5-point L1 ``reg_path`` on the jax backend.
  logistic  make_classification(n=10_000, p=20_000): sparse logistic
            regression at lambda_max/3 on the kernels (Xb CD epochs).
  sparse    make_sparse_design(n=50_000, p=200_000, density=1e-3), about
            10M nnz, passed as scipy CSC: Lasso at lambda_max/10.
  serve     a SparseModelServer holding the fitted models plus 100 seeded
            synthetic ones answers predict / predict_proba requests of mixed
            batch sizes.

``--mesh`` runs only the dense Lasso on a (1, 4) and a (2, 2) mesh over
``jax.devices()`` and on one chip, and checks that the betas agree.

Every solution is checked on the host in float64 numpy from the returned
coefficients, independently of the solver: the KKT violation of the penalty
at hand. Each check prints one line (name, shapes, dtype, device kind, cold
wall time including compilation, outer iterations, host value against its
bound, ok/fail). The last line is one JSON object naming the device. Any
failed check exits non-zero without that line, and the script refuses to run
where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# ---------------------------------------------------------------- tolerances
# Device stop: KKT <= TOL_FRAC * lambda_max. A float32 score entry
# x_j . r / n is a sum of n products and carries a rounding error of about
# sqrt(n) * eps_32 * |x_j| |r| / n, i.e. a few 1e-5 of lambda_max at n = 10^4
# for these designs; a stop at 1e-4 of lambda_max is above that noise and is
# attainable, a tighter one would chase rounding.
TOL_FRAC = 1e-4
# Host check: the float64 KKT of the returned float32 beta may exceed the
# device's float32 KKT by the same gradient rounding, and by the rounding of
# beta itself; twice the stop leaves room for both.
HOST_KKT_FRAC = 2 * TOL_FRAC
# Two solves stopped at TOL_FRAC * lambda_max (different backends, or a mesh
# against one chip) reach different iterates of the same problem; their
# distance is bounded by the stop over the restricted curvature, which on
# these correlated designs keeps them within 1% of max |beta|.
BETA_AGREE_FRAC = 1e-2
# Serving: a decision value is a float32 sum of at most S <= 1024 products;
# its error is below S * eps_32 (6.1e-5) of sum |x_i| |c_i| + |intercept|.
# A probability moves by at most a quarter of that; the check allows it that
# plus SERVE_RTOL in absolute terms (float32 sigmoid rounding is ~1e-7).
SERVE_RTOL = 1e-4


def _device():
    import jax
    d = jax.devices()[0]
    return d.platform, d.device_kind, len(jax.devices())


def require_platform(expect: str):
    """Fail unless JAX's first device is `expect`; on a TPU also fail if the
    Pallas kernels would fall back to interpret mode."""
    platform, kind, _ = _device()
    if platform != expect:
        raise RuntimeError(f"expected a {expect} device, JAX found "
                           f"{platform} ({kind})")
    if expect == "tpu":
        from repro.kernels.ops import _interpret_default
        if _interpret_default():
            raise RuntimeError("Pallas kernels would run in interpret mode "
                               "on the chip")


# ------------------------------------------------------ host float64 checks
def _l1_dist(g, b, lam):
    """dist(-g, lam * d|b|), per coordinate."""
    return np.where(b == 0, np.maximum(np.abs(g) - lam, 0.0),
                    np.abs(g + lam * np.sign(b)))


def _mcp_dist(g, b, lam, gamma):
    """dist(-g, dMCP(b)) for MCP_{lam, gamma}, per coordinate."""
    a = np.abs(b)
    mid = np.abs(g + lam * np.sign(b) - b / gamma)
    return np.where(b == 0, np.maximum(np.abs(g) - lam, 0.0),
                    np.where(a < gamma * lam, mid, np.abs(g)))


def host_kkt(X, y, beta, kind, lam, gamma=None):
    """Max KKT violation of `beta` in float64: quadratic (1/2n)||y - Xb||^2
    or logistic (1/n) sum log(1 + exp(-y Xb)), with an L1 or MCP penalty.
    `X` may be dense or scipy sparse."""
    b = np.asarray(beta, np.float64)
    y = np.asarray(y, np.float64)
    Xb = np.asarray(X @ b, np.float64)
    n = y.shape[0]
    if kind == "quadratic":
        raw = (Xb - y) / n
    else:
        raw = -y / (1.0 + np.exp(y * Xb)) / n
    g = np.asarray(X.T @ raw, np.float64)
    d = _l1_dist(g, b, lam) if gamma is None else _mcp_dist(g, b, lam, gamma)
    return float(d.max())


def _line(name, shape, dtype, seconds, n_outer, value, bound, ok, what="kkt"):
    _, kind, count = _device()
    return (f"{name}: shape={shape} dtype={dtype} device={kind} x{count} "
            f"cold_s={seconds:.3f} n_outer={n_outer} host_{what}={value:.3e} "
            f"bound={bound:.3e} {'ok' if ok else 'FAIL'}")


def _emit(line):
    print(line, flush=True)


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "beta", None))
    return out, time.perf_counter() - t0


# -------------------------------------------------------------------- phases
def phase_dense(seed, n=10_000, p=20_000, n_nonzero=150, expect="tpu"):
    """Lasso (jax and kernels), MCP (kernels), L1 path (jax). Returns
    (ok, fitted, X) with fitted = {name: beta} for the serve phase."""
    import jax.numpy as jnp
    from repro.core import L1, MCP, Quadratic, lambda_max, reg_path, solve
    from repro.data.synth import make_correlated_design
    require_platform(expect)
    X, y, _ = make_correlated_design(n=n, p=p, n_nonzero=n_nonzero, seed=seed,
                                     dtype=np.float32)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    lmax = lambda_max(Xd, yd)
    tol = TOL_FRAC * lmax
    bound = HOST_KKT_FRAC * lmax
    shape = f"{n}x{p}"
    ok, fitted = True, {}

    def check(name, lam, res, secs, gamma=None):
        nonlocal ok
        kkt = host_kkt(X, y, res.beta, "quadratic", lam, gamma)
        good = bool(res.converged) and kkt <= bound
        ok &= good
        _emit(_line(name, shape, X.dtype, secs, res.n_outer, kkt, bound,
                    good))

    lam = lmax / 20
    r_jax, s = _timed(lambda: solve(Xd, yd, Quadratic(), L1(lam), tol=tol))
    check("dense.lasso_jax", lam, r_jax, s)
    r_ker, s = _timed(lambda: solve(Xd, yd, Quadratic(), L1(lam), tol=tol,
                                    use_kernels=True))
    check("dense.lasso_kernels", lam, r_ker, s)
    b1, b2 = np.asarray(r_jax.beta), np.asarray(r_ker.beta)
    diff = float(np.max(np.abs(b1 - b2)))
    agree = BETA_AGREE_FRAC * float(np.max(np.abs(b1)))
    ok &= diff <= agree
    _emit(_line("dense.lasso_jax_vs_kernels", shape, X.dtype, 0.0,
                r_ker.n_outer, diff, agree, diff <= agree,
                what="max_abs_dbeta"))
    fitted["lasso"] = b2

    gamma = 3.0
    r_mcp, s = _timed(lambda: solve(Xd, yd, Quadratic(), MCP(lam, gamma),
                                    tol=tol, use_kernels=True))
    check("dense.mcp_kernels", lam, r_mcp, s, gamma=gamma)
    fitted["mcp"] = np.asarray(r_mcp.beta)

    t0 = time.perf_counter()
    path = reg_path(Xd, yd, L1(lmax), n_lambdas=5, lambda_min_ratio=0.05,
                    tol=tol)
    s = time.perf_counter() - t0
    worst = max(host_kkt(X, y, b, "quadratic", float(l))
                for l, b in zip(path.lambdas, path.betas))
    good = worst <= bound
    ok &= good
    _emit(_line("dense.l1_path5_jax", shape, X.dtype, s,
                int(np.sum(path.n_outer)), worst, bound, good))
    return ok, fitted, X


def phase_logistic(seed, n=10_000, p=20_000, expect="tpu"):
    """Sparse logistic regression on the Xb kernel."""
    import jax.numpy as jnp
    from repro.core import L1, Logistic, lambda_max, solve
    from repro.data.synth import make_classification
    require_platform(expect)
    X, y, _ = make_classification(n=n, p=p, seed=seed, dtype=np.float32)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    lmax = lambda_max(Xd, yd, Logistic())
    lam = lmax / 3
    bound = HOST_KKT_FRAC * lmax
    res, s = _timed(lambda: solve(Xd, yd, Logistic(), L1(lam),
                                  tol=TOL_FRAC * lmax, use_kernels=True))
    kkt = host_kkt(X, y, res.beta, "logistic", lam)
    good = bool(res.converged) and kkt <= bound
    _emit(_line("logistic.l1_kernels", f"{n}x{p}", X.dtype, s, res.n_outer,
                kkt, bound, good))
    return good, {"logistic": np.asarray(res.beta)}


def phase_sparse(seed, n=50_000, p=200_000, density=1e-3, expect="tpu"):
    """CSC-native Lasso from a scipy matrix."""
    from repro.core import L1, Quadratic, lambda_max, solve
    from repro.data.synth import make_sparse_design
    require_platform(expect)
    Xs, y, _ = make_sparse_design(n=n, p=p, density=density, seed=seed,
                                  dtype=np.float32)
    lmax = lambda_max(Xs, y)
    lam = lmax / 10
    bound = HOST_KKT_FRAC * lmax
    res, s = _timed(lambda: solve(Xs, y, Quadratic(), L1(lam),
                                  tol=TOL_FRAC * lmax))
    kkt = host_kkt(Xs.astype(np.float64), y, res.beta, "quadratic", lam)
    good = bool(res.converged) and kkt <= bound
    _emit(_line("sparse.lasso_jax", f"{n}x{p} nnz={Xs.nnz}", Xs.dtype, s,
                res.n_outer, kkt, bound, good))
    return good


def _serve_err(rows, coef, icept, decision=None, proba=None):
    """Largest error of served outputs against float64 numpy: decisions
    relative to sum |x_i c_i| + |intercept|, probabilities relative to a
    quarter of that (the sigmoid's slope bound) plus one."""
    r64, c64 = rows.astype(np.float64), coef.astype(np.float64)
    want = r64 @ c64 + icept
    scale = np.abs(r64) @ np.abs(c64) + abs(icept)
    err = 0.0
    if decision is not None:
        err = np.abs(np.asarray(decision, np.float64) - want) / scale
    if proba is not None:
        p1 = 1.0 / (1.0 + np.exp(-want))
        err = np.maximum(err, np.abs(np.asarray(proba, np.float64)[:, 1] - p1)
                         / (0.25 * scale + 1.0))
    return float(np.max(err))


def phase_serve(seed, fitted, X, n_synthetic=100, expect="tpu"):
    """Serve the fitted models plus seeded synthetic ones; predictions must
    match float64 numpy X @ coef + intercept."""
    from repro.serve.sparse_server import SparseModelServer
    require_platform(expect)
    rng = np.random.default_rng(seed)
    n, p = X.shape
    server = SparseModelServer(p, dtype=np.float32)
    models = {}
    for name, beta in fitted.items():
        kind = "logistic" if name == "logistic" else "linear"
        models[name] = (np.asarray(beta, np.float32), 0.5, kind)
    for i in range(n_synthetic):
        coef = np.zeros(p, np.float32)
        k = int(rng.integers(1, min(512, p) + 1))
        coef[rng.choice(p, k, replace=False)] = rng.standard_normal(k)
        models[f"syn{i}"] = (coef, float(rng.standard_normal()),
                             "logistic" if i % 2 else "linear")
    for mid, (coef, icept, kind) in models.items():
        server.admit(mid, coef, icept, kind)

    ids = list(models)
    sizes = [1, 3, 8, 17, 64, 100]
    reqs = []
    t0 = time.perf_counter()
    for i in range(24):
        mid = ids[i] if i < len(fitted) else ids[(7 * i) % len(ids)]
        rows = X[rng.choice(n, min(sizes[i % len(sizes)], n), replace=False)]
        reqs.append((server.submit(mid, rows), mid, rows))
    results = {r.ticket: r for r in server.flush()}
    s = time.perf_counter() - t0
    worst = max(_serve_err(rows, *models[mid][:2], results[t].decision,
                           results[t].proba)
                for t, mid, rows in reqs)
    # the convenience entry points, one request each
    rows = X[:5]
    worst = max(worst, _serve_err(rows, *models[ids[0]][:2],
                                  server.predict(ids[0], rows)))
    if "logistic" in models:
        worst = max(worst, _serve_err(
            rows, *models["logistic"][:2],
            proba=server.predict_proba("logistic", rows)))
    good = worst <= SERVE_RTOL
    _emit(_line("serve.predict", f"{len(models)} models p={p} "
                f"{len(reqs) + 2} requests", "float32", s, 0, worst,
                SERVE_RTOL, good, what="rel_err"))
    return good


def phase_mesh(seed, n=10_000, p=20_000, n_nonzero=150, expect="tpu"):
    """Dense Lasso on a (1, 4) and a (2, 2) mesh and on one chip: the mesh
    betas must match the single-chip betas; reports the peak bytes per
    device after the mesh solves (X arrives as shards, never whole)."""
    import jax
    import jax.numpy as jnp
    from repro.core import L1, Quadratic, solve
    from repro.data.synth import make_correlated_design
    from repro.launch.mesh import make_solver_mesh
    require_platform(expect)
    X, y, _ = make_correlated_design(n=n, p=p, n_nonzero=n_nonzero, seed=seed,
                                     dtype=np.float32)
    # lambda_max on the host: a device computation here would put all of X
    # on the first chip before the mesh solves shard it
    lmax = float(np.max(np.abs(X.T.astype(np.float64) @ y))) / n
    lam, tol = lmax / 20, TOL_FRAC * lmax
    bound = HOST_KKT_FRAC * lmax
    shape = f"{n}x{p}"
    ok, mesh_betas = True, {}
    for ms in ((1, 4), (2, 2)):
        mesh = make_solver_mesh(ms)
        res, s = _timed(lambda: solve(X, y, Quadratic(), L1(lam), tol=tol,
                                      mesh=mesh))
        kkt = host_kkt(X, y, res.beta, "quadratic", lam)
        good = bool(res.converged) and kkt <= bound
        ok &= good
        _emit(_line(f"mesh.lasso_{ms[0]}x{ms[1]}", shape, X.dtype, s,
                    res.n_outer, kkt, bound, good))
        mesh_betas[ms] = np.asarray(res.beta)
    peaks = [d.memory_stats().get("peak_bytes_in_use", -1)
             if d.memory_stats() else -1 for d in jax.devices()]
    _emit(f"mesh.peak_bytes_per_device: {peaks} (X is {X.nbytes} bytes)")
    single, s = _timed(lambda: solve(jnp.asarray(X), jnp.asarray(y),
                                     Quadratic(), L1(lam), tol=tol))
    b0 = np.asarray(single.beta)
    agree = BETA_AGREE_FRAC * float(np.max(np.abs(b0)))
    for ms, b in mesh_betas.items():
        diff = float(np.max(np.abs(b - b0)))
        ok &= diff <= agree
        _emit(_line(f"mesh.{ms[0]}x{ms[1]}_vs_1chip", shape, X.dtype, s,
                    single.n_outer, diff, agree, diff <= agree,
                    what="max_abs_dbeta"))
    return ok


# ---------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: dense Lasso on (1,4) and (2,2) meshes "
                         "against one chip, and nothing else")
    args = ap.parse_args(argv)

    import jax
    platform, kind, count = _device()
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {platform}: {kind})",
              file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: runs in float32; unset JAX_ENABLE_X64",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.mesh:
        if count < 4:
            print(f"chip_smoke --mesh: needs 4 devices, found {count}",
                  file=sys.stderr)
            return 2
        ok = phase_mesh(args.seed)
    else:
        ok, fitted, X = phase_dense(args.seed)
        good, fit_lg = phase_logistic(args.seed)
        fitted.update(fit_lg)
        ok &= good
        ok &= phase_sparse(args.seed)
        ok &= phase_serve(args.seed, fitted, X)
    if not ok:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
