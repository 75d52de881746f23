"""Whole solves, back to back: ``repro.core.solve`` on a Lasso, each from a
zero start to the cell's KKT tolerance.

The mix cycles through `n_problems` problems of one shape, each a design of
its own with its own target (users fitting one model after another, as in a
simulation study). Their values come from the mix's fixed ``values_seed``,
so every run does the same work; the run's seed draws the order of each
pass over them and the answers checked. The window runs whole passes and
ends with the pass in which ``--seconds`` ran out. Set-up solves each
problem once, which compiles or reads from the cache every working-set
bucket the window will use.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import numpy as np

from ..harness import quantile
from . import common


def _solve(st, k):
    import repro.core as rc
    return rc.solve(st.designs[k], st.Y[k], rc.Quadratic(),
                    rc.L1(st.lams[k]), tol=st.tols[k], mesh=st.mesh)


def setup(cell, seed):
    cfg, mix = cell.config, cell.mix
    K = int(mix["n_problems"])
    Xs, Y = cell.generator.generate(cfg, common.values_seed(mix, seed), K)
    common.note("data generated")
    lmax = np.concatenate([common.lambda_max(X, Y[k:k + 1])
                           for k, X in enumerate(Xs)])
    st = SimpleNamespace(cell=cell, seed=seed, Xs=Xs,
                         designs=[common.program_design(X) for X in Xs],
                         mesh=common.mesh_of(Xs[0]), Y=Y, lmax=lmax,
                         lams=[float(v) for v in cfg["lambda_frac"] * lmax],
                         tols=[float(v) for v in mix["tol_frac"] * lmax],
                         sample=common.Reservoir(int(mix["n_check"]), seed))
    common.note("designs placed, lambda_max taken")
    for k in range(K):
        jax.block_until_ready(_solve(st, k).beta)
    common.note(f"{K} warm-up solves done")
    return st


def window(st, seconds, tracer, counters):
    K = len(st.lams)
    order = common.pass_order(K, st.seed)
    times, flags = [], []
    outer = syncs = 0
    t_start = time.perf_counter()
    if tracer is not None:
        tracer.start()
    i = 0
    while True:
        k = next(order)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("solve"):
            res = _solve(st, k)
            jax.block_until_ready(res.beta)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        flags.append(bool(res.converged))
        outer += res.n_outer
        syncs += res.n_host_syncs
        st.sample.offer((k, res.beta, float(res.kkt)))
        i += 1
        if tracer is not None:
            tracer.tick(t1 - t_start)
        if i % K == 0 and t1 - t_start >= seconds:
            break
    if tracer is not None:
        tracer.stop()
    counters.update(n_solves=i, n_outer=outer, n_host_syncs=syncs,
                    x_bytes=common.x_bytes(st.Xs[0]))
    return {"attempted": i, "failed": flags.count(False),
            "end_to_end": {"solve_s": (t1 - t_start) / i,
                           "solve_p95_s": quantile(times, 0.95)}}


def answers(st):
    """The sampled answers, as ``common.Answers``."""
    ks = [k for k, _, _ in st.sample.items]
    return common.Answers(
        X=[st.Xs[k] for k in ks],
        Y=np.asarray(st.Y, np.float64)[ks].T,
        B=np.stack([np.asarray(b, np.float64)
                    for _, b, _ in st.sample.items], axis=1),
        lams=np.array([st.lams[k] for k in ks]), W=None,
        kkt_dev=np.array([v for _, _, v in st.sample.items]),
        lmax=st.lmax[ks])


def check(st, window):
    return common.check(st, window, answers(st))
