"""Whole cross-validation grids, back to back: ``repro.core.cross_val_path``
over `cv` folds and `n_lambdas` values of lambda down to
`lambda_min_ratio * lambda_max`, every (fold, lambda) fit to the cell's KKT
tolerance.

The mix cycles through `n_problems` problems of one shape, a grid each.
Their values come from the mix's fixed ``values_seed``, so every run does
the same work: how many lanes retire in a round follows the values, and
``cross_val_path`` compiles small programs shaped by that count, so fresh
values would also compile in every new seed's set-up. The run's seed draws
the order of each pass over the problems and the grid checked. The fold
split comes from a fixed stream and goes to the program as
``fold_weights``, so the reference knows each fit's training rows without
asking the program. The window runs whole passes and ends with the pass in
which ``--seconds`` ran out. Set-up runs each problem's grid once, which
compiles or reads from the cache every bucket of the lane pool the window
will use.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import numpy as np

from ..data.seeds import seed_words
from ..reference.kkt import fold_train_weights
from . import common


def _grid(st, k):
    import repro.core as rc
    mix = st.cell.mix
    return rc.cross_val_path(
        st.designs[k], st.Y[k], rc.Quadratic(), rc.L1(1.0),
        lambdas=st.lambdas[k], fold_weights=st.W, tol=st.tols[k],
        vmap_chunk=int(mix["vmap_chunk"]), sync_every=int(mix["sync_every"]))


def setup(cell, seed):
    cfg, mix = cell.config, cell.mix
    K = int(mix["n_problems"])
    Xs, Y = cell.generator.generate(cfg, common.values_seed(mix, seed), K)
    common.note("data generated")
    lmax = np.concatenate([common.lambda_max(X, Y[k:k + 1])
                           for k, X in enumerate(Xs)])
    path = np.geomspace(1.0, float(mix["lambda_min_ratio"]),
                        int(mix["n_lambdas"]))
    st = SimpleNamespace(
        cell=cell, seed=seed, Xs=Xs,
        designs=[common.program_design(X) for X in Xs], Y=Y, lmax=lmax,
        tols=[float(v) for v in mix["tol_frac"] * lmax],
        lambdas=[v * path for v in lmax],
        W=fold_train_weights(Xs[0].shape[0], int(mix["cv"]),
                             seed_words(0, "folds", 4)),
        sample=common.Reservoir(1, seed))
    common.note("designs placed, lambda_max taken")
    for k in range(K):
        _grid(st, k)
    common.note(f"{K} warm-up grids done")
    return st


def window(st, seconds, tracer, counters):
    K = len(st.Xs)
    order = common.pass_order(K, st.seed)
    fits = unconverged = outer = syncs = grids = 0
    t_start = time.perf_counter()
    if tracer is not None:
        tracer.start()
    while True:
        k = next(order)
        with jax.profiler.TraceAnnotation("grid"):
            g = _grid(st, k)
        t1 = time.perf_counter()
        fits += g.kkts.size
        unconverged += int(np.sum(~(g.kkts <= st.tols[k])))
        outer += g.n_outer
        syncs += g.n_host_syncs
        grids += 1
        st.sample.offer((k, g.betas, g.kkts))
        if tracer is not None:
            tracer.tick(t1 - t_start)
        if grids % K == 0 and t1 - t_start >= seconds:
            break
    if tracer is not None:
        tracer.stop()
    counters.update(n_grids=grids, n_fits=fits, n_outer=outer,
                    n_host_syncs=syncs, x_bytes=common.x_bytes(st.Xs[0]))
    return {"attempted": fits, "failed": unconverged,
            "end_to_end": {"grid_fits_per_s": fits / (t1 - t_start)}}


def answers(st):
    """Every fit of the sampled grid, as ``common.Answers``."""
    k, betas, kkt_dev = st.sample.items[0]
    F, nlam, p = betas.shape
    n = st.W.shape[1]
    Wn = st.W * (n / st.W.sum(axis=1, keepdims=True))      # sum to n
    y = np.asarray(st.Y[k], np.float64)
    return common.Answers(
        X=[st.Xs[k]] * (F * nlam), Y=np.repeat(y[:, None], F * nlam, axis=1),
        B=betas.reshape(F * nlam, p).T,
        lams=np.tile(st.lambdas[k], F), W=np.repeat(Wn, nlam, axis=0).T,
        kkt_dev=np.asarray(kkt_dev, np.float64).reshape(-1),
        lmax=np.full(F * nlam, st.lmax[k]))


def check(st, window):
    return common.check(st, window, answers(st))
