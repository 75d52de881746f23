"""Engine perf baseline: fig2 Lasso + fig5 MCP timings and host-dispatch
counts, recorded to BENCH_engine.json so the perf trajectory of later PRs
(sharded CD, multi-backend, serving) starts from the device-resident-engine
refactor. ``sparse_fig2`` measures the CSC-native sparse path (DESIGN.md §7)
on a news20-like power-law design — at the "small" scale this is the
paper-regime n=50k x p=200k at density 1e-3, solved without ever
materializing the dense X. ``fig4_meeg`` measures the block-coordinate
(multitask) engine path on the Figure 4 M/EEG-analog workload
(DESIGN.md §8) with the same 1-dispatch/1-sync-per-outer contract.
``cv_fig`` measures the weighted-grid engine (DESIGN.md §9): a 5-fold x
30-lambda Lasso CV grid (150 simultaneous solves, every fold a 0/1 weight
leaf on shared data) through the chunked fused step — one compile per
working-set bucket, well under 1 dispatch + 1 sync per outer iteration.
``serve_fig`` measures the serving surface (DESIGN.md §13): a
SparseModelServer bank of synthetic cohort models under a replayed
open-loop request stream — steady-state p50/p99 latency, throughput, and
the compile-once-per-(batch, support)-bucket proof. Every entry records
``compile_s`` (cold pass, compiles included) and ``steady_s`` (warm
caches) separately; ``wall_s`` is the steady-state alias.

``PYTHONPATH=src python -m benchmarks.bench_engine [--smoke] [--out PATH]``

``--check-budget BENCH_engine.json`` turns the run into a CI perf guard:
it fails when any benchmark's jit-dispatches-per-outer-iteration exceed the
budget recorded in the committed baseline (the fused-engine contract is
exactly 1), when the per-stage roofline table is missing or incomplete,
when the fused single-traversal head's score+select+gather bytes-per-outer
exceeds ``budget_fused_bytes_ratio`` (0.6) of the two-pass baseline
(DESIGN.md §10), or when the ``telemetry_overhead`` record shows the
device-side telemetry rings (DESIGN.md §11) adding any extra jit dispatch
or more than ``BUDGET_TELEMETRY_OVERHEAD`` (2%) wall time over the
obs=None solve at the smoke shapes, when the ``serve_fig`` p99 latency
exceeds the committed ``budget_p99_ms``, or when any serving (batch,
support) bucket pair compiled more than once.
The ``pallas_fused`` block records before (jax two-pass) /
after (Pallas fused kernel) wall clocks at the smoke shapes plus the modeled
bytes-per-outer; the ``roofline`` block is the full per-stage table printed
by ``benchmarks/roofline_report.py``.

The ``seed_before`` block is the measurement of the pre-engine host-driven
solver (3-4 jitted dispatches + 3 blocking scalar syncs per outer iteration),
taken on this container at the refactor commit; the ``engine_after`` block is
re-measured on every run. The ``mesh_2x4`` block re-measures the same two
benchmarks through the mesh-native engine on a 2x4 mesh, in this process,
over the devices it sees (on the CPU: run with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``);
``seed_distributed`` records the per-outer-iteration budget of the seed-era
core/distributed.py host loop that the mesh-native engine replaced
(counted from its code structure: scores/topk/gather/gram/inner/
scatter/apply_ws launches + kkt/gsupp/epochs blocking pulls).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402

from repro.core import (MCP, L1, BlockL1, MultitaskQuadratic, Quadratic,  # noqa: E402
                        lambda_max, make_engine, solve)
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.data.synth import make_correlated_design, make_sparse_design  # noqa: E402

# measured once on the seed (pre-engine) solver, same container, same configs:
# per outer iteration it launched _score_pass + _gather_ws + _inner_* (plus
# eager gathers) and blocked on float(kkt), int(gsupp), int(n_ep)
SEED_BEFORE = {
    "fig2_lasso": {"wall_s": 0.213, "n_outer": 8, "n_epochs": 40,
                   "jit_dispatches_per_outer": 3.125,
                   "host_syncs_per_outer": 3.0},
    "fig5_mcp": {"wall_s": 0.109, "n_outer": 6, "n_epochs": 30,
                 "jit_dispatches_per_outer": 3.167,
                 "host_syncs_per_outer": 3.0},
}

# the seed-era distributed host loop (deleted by the mesh-native engine):
# per outer iteration it launched scores + topk + gather + gram + _inner_gram
# + scatter + apply_ws (7 jitted dispatches) and blocked on float(max(sc)),
# int(sum(gsupp)) and int(n_ep) (3 syncs), retracing the penalty closure per
# lambda; quadratic datafits only
SEED_DISTRIBUTED = {
    "jit_dispatches_per_outer": 7.0,
    "host_syncs_per_outer": 3.0,
    "retrace_per_lambda": True,
    "datafits": ["Quadratic", "MultitaskQuadratic", "QuadraticSVC"],
}

CONFIGS = {
    "small": {
        "fig2_lasso": dict(n=300, p=1500, n_nonzero=30),
        "fig5_mcp": dict(n=400, p=2000, n_nonzero=40),
    },
    "smoke": {
        # 128 x 1024 keeps the smoke run fast while hitting a shape where
        # the fused single-read head's byte budget (ratio <= 0.6, see
        # repro/roofline/engine_stages.py) is meaningfully exercised
        "fig2_lasso": dict(n=128, p=1024, n_nonzero=10),
        "fig5_mcp": dict(n=100, p=400, n_nonzero=12),
    },
}

# the fused single-traversal head (kernels/fused_ws.py) must beat the
# two-pass score+select+gather HBM traffic by at least this factor per
# outer iteration; enforced by --check-budget against the analytic byte
# model (DESIGN.md §10)
BUDGET_FUSED_BYTES_RATIO = 0.6

# the zero-overhead telemetry contract (DESIGN.md §11): recording the
# per-outer convergence rings inside the fused step must add ZERO extra
# jit dispatches (the ring rides the existing step) and at most this
# fraction of wall clock over the obs=None solve
BUDGET_TELEMETRY_OVERHEAD = 0.02

# Figure 4's M/EEG analog (multitask regression, block penalty) through the
# block-coordinate fused engine (DESIGN.md §8): leadfield-like column-coherent
# design, T time samples, L2,1 penalty. The engine contract (1 dispatch +
# 1 host sync per outer iteration) is enforced for blocks exactly like for
# scalar coordinates.
MT_CONFIGS = {
    "small": {
        "fig4_meeg": dict(n=60, p_per_hemi=150, T=20),
    },
    "smoke": {
        "fig4_meeg": dict(n=30, p_per_hemi=60, T=8),
    },
}

# the paper's flagship regime (sparse news20-like design, DESIGN.md §7):
# solved CSC-native — the [n, p] dense X is never materialized. The "small"
# scale is the acceptance-criteria shape; smoke keeps CI fast.
SPARSE_CONFIGS = {
    "small": {
        "sparse_fig2": dict(n=50_000, p=200_000, density=1e-3,
                            n_nonzero=200),
    },
    "smoke": {
        "sparse_fig2": dict(n=1000, p=4000, density=5e-3, n_nonzero=40),
    },
}

# the weighted-grid engine (DESIGN.md §9): a 5-fold x 30-lambda Lasso CV
# grid solved SIMULTANEOUSLY — every fold is a 0/1 weight leaf on the
# shared (X, y), lanes are (fold, lambda) pairs through the chunked fused
# step, so 150 solves share one compiled program per working-set bucket.
# Budget contract: at most 1 fused dispatch + 1 host sync per vmapped outer
# iteration (chunking amortizes far below; the explicit
# budget_dispatches_per_outer=1.0 cap is what --check-budget enforces,
# scale-independently).
CV_CONFIGS = {
    # lambda_min_ratio 0.05 brackets the CV minimum (empirically at ratio
    # ~0.07 for this snr) without sweeping into the dense-tail regime where
    # every lane's working set escalates towards p
    "small": {
        "cv_fig": dict(n=10_000, p=20_000, n_nonzero=150, cv=5,
                       n_lambdas=30, vmap_chunk=10, tol=1e-7,
                       lambda_min_ratio=0.05),
    },
    "smoke": {
        "cv_fig": dict(n=400, p=800, n_nonzero=20, cv=3, n_lambdas=10,
                       vmap_chunk=5, tol=1e-7, lambda_min_ratio=0.05),
    },
}


# the serving-side benchmark (DESIGN.md §13): a SparseModelServer bank of
# n_models synthetic sparse cohort models under a replayed open-loop request
# stream with mixed batch sizes. Two identical passes — the first compiles
# (one fused step per (batch_bucket, support_bucket) pair), the second
# measures steady-state p50/p99 latency and throughput; --check-budget
# enforces the recorded p99 latency budget and that the steady pass added
# ZERO compiles (max_compiles_per_key stays 1).
SERVE_CONFIGS = {
    "small": {
        "serve_fig": dict(n_models=1000, p=512, nnz_lo=4, nnz_hi=40,
                          n_requests=600, flush_every=8,
                          batch_sizes=(1, 2, 5, 9, 17, 33, 3, 12, 7, 28),
                          budget_p99_ms=250.0),
    },
    "smoke": {
        "serve_fig": dict(n_models=200, p=256, nnz_lo=4, nnz_hi=24,
                          n_requests=150, flush_every=6,
                          batch_sizes=(1, 3, 8, 17, 5, 12),
                          budget_p99_ms=250.0),
    },
}


def _timed_solve(X, y, datafit, penalty, mesh, tol, use_kernels=False):
    """The shared measurement protocol: one timed compile pass, best-of-3
    timed steady solves, per-outer dispatch/sync telemetry. One protocol
    for every benchmark (scalar, sparse, multitask) so budget semantics
    can't fork. Every entry records ``compile_s`` (the cold first solve,
    compiles included) and ``steady_s`` (best-of-3 with warm caches)
    separately; ``wall_s`` is kept as an alias of ``steady_s`` for older
    readers. ``use_kernels=True`` routes through the Pallas backend (the
    fused score/select/gather head on dense designs)."""
    kw = dict(tol=tol, max_outer=100)
    engine = make_engine(penalty, datafit, mesh=mesh,
                         use_kernels=use_kernels)
    t0 = time.perf_counter()
    solve(X, y, datafit, penalty, engine=engine, **kw)       # compile pass
    compile_s = time.perf_counter() - t0
    wall = float("inf")
    for _ in range(3):                                       # best of 3
        engine.n_dispatches = 0
        t0 = time.perf_counter()
        res = solve(X, y, datafit, penalty, engine=engine, **kw)
        wall = min(wall, time.perf_counter() - t0)
    iters = max(len(res.kkt_history), 1)
    return {
        "wall_s": wall,
        "compile_s": compile_s,
        "steady_s": wall,
        "n_outer": res.n_outer,
        "n_epochs": res.n_epochs,
        "kkt": res.kkt,
        "converged": res.converged,
        "jit_dispatches_per_outer": engine.n_dispatches / iters,
        "host_syncs_per_outer": res.n_host_syncs / iters,
        "retraces": {str(k): v for k, v in engine.retraces.items()},
    }


def _measure(bench, cfg, mesh=None, sparse=False, use_kernels=False):
    if sparse:
        from repro.sparse import CSCDesign
        Xsp, y, _ = make_sparse_design(seed=0, snr=5.0, **cfg)
        y = jnp.asarray(y)
        nnz = int(Xsp.nnz)
        # convert outside the timed loop, like the dense jnp.asarray above:
        # wall_s must measure the CSC-native solve, not host conversion
        # (the Pallas score backend additionally needs the ELL layout)
        X = CSCDesign.from_scipy(Xsp, ell=use_kernels)
    else:
        X, y, _ = make_correlated_design(seed=0, rho=0.5, snr=5.0, **cfg)
        X, y = jnp.asarray(X), jnp.asarray(y)
        nnz = None
    lam = lambda_max(X, y) / 10
    penalty = L1(lam) if bench.startswith(("fig2", "sparse")) \
        else MCP(lam, 3.0)
    out = _timed_solve(X, y, Quadratic(), penalty, mesh, tol=1e-10,
                       use_kernels=use_kernels)
    if sparse:
        out["nnz"] = nnz
        out["shape"] = [cfg["n"], cfg["p"]]
    return out


def _measure_fig4(cfg):
    """Multitask (block-coordinate) engine measurement on the Figure 4
    M/EEG-analog workload (leadfield-like design, L2,1 penalty)."""
    from repro.data.synth import make_leadfield
    X, Y, _, _ = make_leadfield(seed=0, **cfg)
    X, Y = jnp.asarray(X), jnp.asarray(Y)
    datafit = MultitaskQuadratic()
    penalty = BlockL1(lambda_max(X, Y, datafit) / 10)
    out = _timed_solve(X, Y, datafit, penalty, None, tol=1e-9)
    out["n_tasks"] = cfg["T"]
    out["shape"] = [cfg["n"], 2 * cfg["p_per_hemi"]]
    return out


def _measure_cv(cfg):
    """Weighted-grid engine measurement: the simultaneous CV Lasso grid.

    Two passes on one fresh engine — the first compiles (one program per
    bucket) and is timed as ``compile_s``, the second measures the
    steady-state wall clock (``steady_s``; ``wall_s`` is its alias — the
    historical single ``wall_s`` conflated the two) and the
    dispatch/sync-per-outer budget the grid contract promises."""
    from repro.core.path import cross_val_path

    cfg = dict(cfg)
    cv, n_lambdas = cfg.pop("cv"), cfg.pop("n_lambdas")
    vmap_chunk, tol = cfg.pop("vmap_chunk"), cfg.pop("tol")
    ratio = cfg.pop("lambda_min_ratio")
    X, y, _ = make_correlated_design(seed=0, rho=0.5, snr=5.0, **cfg)
    X, y = jnp.asarray(X), jnp.asarray(y)
    engine = make_engine(L1(1.0), Quadratic(), shared=False)
    kw = dict(n_lambdas=n_lambdas, lambda_min_ratio=ratio, cv=cv, tol=tol,
              vmap_chunk=vmap_chunk, engine=engine, seed=0)
    t0 = time.perf_counter()
    cross_val_path(X, y, Quadratic(), L1(1.0), **kw)         # compile pass
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    g = cross_val_path(X, y, Quadratic(), L1(1.0), **kw)     # measured pass
    wall = time.perf_counter() - t0
    return {
        "wall_s": wall,
        "compile_s": compile_s,
        "steady_s": wall,
        "n_outer": g.n_outer,
        "n_solves": int(np.prod(g.cv_loss.shape)),
        "kkt": float(np.max(g.kkts)),
        "converged": bool(np.max(g.kkts) <= tol),
        "best_lambda": g.best_lambda,
        "jit_dispatches_per_outer": g.n_dispatches / max(g.n_outer, 1),
        "host_syncs_per_outer": g.n_host_syncs / max(g.n_outer, 1),
        # the scale-independent cap --check-budget enforces (the fused-grid
        # contract: never more than one dispatch per outer iteration)
        "budget_dispatches_per_outer": 1.0,
        "retraces": {str(k): v for k, v in engine.retraces.items()},
        "shape": [cfg["n"], cfg["p"]],
        "grid": f"{cv}x{n_lambdas}",
    }


def _measure_serve(cfg):
    """SparseModelServer under a replayed open-loop request stream.

    Admits ``n_models`` synthetic sparse cohort models (uniform support
    sizes in [nnz_lo, nnz_hi] — several support buckets), then replays the
    SAME request schedule twice: mixed batch sizes from ``batch_sizes``,
    a flush every ``flush_every`` submissions (the micro-batch quantum).
    The first pass is the compile pass (``compile_s``); latency/dispatch
    telemetry is then reset and the second pass measures steady state —
    p50/p99 request latency from the server's own histogram, throughput
    in rows/s, and the compile-count proof (the steady pass must add zero
    compiles: ``max_compiles_per_key`` stays 1)."""
    from repro.serve import SparseModelServer

    rng = np.random.default_rng(0)
    n_models, p = cfg["n_models"], cfg["p"]
    srv = SparseModelServer(p=p, batch_minimum=8, support_minimum=8)
    t0 = time.perf_counter()
    for i in range(n_models):
        nnz = int(rng.integers(cfg["nnz_lo"], cfg["nnz_hi"] + 1))
        coef = np.zeros(p)
        coef[rng.choice(p, nnz, replace=False)] = rng.standard_normal(nnz)
        srv.admit(f"m{i}", coef, intercept=float(rng.standard_normal()),
                  kind="linear")
    admit_s = time.perf_counter() - t0

    sizes = cfg["batch_sizes"]
    schedule = [(f"m{int(rng.integers(0, n_models))}",
                 rng.standard_normal((sizes[j % len(sizes)], p)))
                for j in range(cfg["n_requests"])]

    def replay():
        t0 = time.perf_counter()
        for j, (mid, X) in enumerate(schedule):
            srv.submit(mid, X)
            if j % cfg["flush_every"] == cfg["flush_every"] - 1:
                srv.flush()
        srv.flush()
        return time.perf_counter() - t0

    compile_s = replay()                             # compile pass
    # reset the steady-state telemetry (histograms/counters), keep the
    # compiled steps and the retrace proof
    srv.metrics.histogram("serve.latency_ms").clear()
    srv.metrics.histogram("serve.batch_occupancy").clear()
    srv.metrics.set_counter("serve.n_dispatches", 0)
    steady_s = replay()                              # measured pass

    retraces = srv.metrics.mapping("serve.retraces")
    occ = srv.metrics.histogram("serve.batch_occupancy")
    rows = sum(X.shape[0] for _, X in schedule)
    return {
        "wall_s": steady_s,
        "compile_s": compile_s,
        "steady_s": steady_s,
        "admit_s": admit_s,
        "n_models": n_models,
        "n_requests": cfg["n_requests"],
        "rows": rows,
        "p50_ms": float(srv.metrics.gauge("serve.p50_ms")),
        "p99_ms": float(srv.metrics.gauge("serve.p99_ms")),
        "throughput_rows_per_s": rows / steady_s,
        "throughput_requests_per_s": cfg["n_requests"] / steady_s,
        "n_dispatches": srv.metrics.counter("serve.n_dispatches"),
        "n_compiles": len(retraces),
        "max_compiles_per_key": max(retraces.values()),
        "batch_occupancy_mean": float(np.mean(occ)) if occ else 0.0,
        "bank_bytes": srv.bank.nbytes,
        "budget_p99_ms": cfg["budget_p99_ms"],
        "retraces": dict(retraces),
        "shape": [n_models, p],
    }


# the telemetry-overhead measurement shape: large enough that per-outer
# compute dominates the obs layer's FIXED per-solve costs (one ring
# allocation, one drain readback, the extra ring leaves through each
# dispatch — together ~4ms on this container, which would read as ~30%
# of the 13ms smoke solve but is <1% here), so the 2% budget measures
# the marginal in-step recording cost the zero-overhead claim is about
TELEMETRY_CONFIG = dict(n=1024, p=8192, n_nonzero=60)


def _measure_telemetry_overhead(n_repeats=7):
    """Obs-on vs obs-off cost of the device-side telemetry rings
    (DESIGN.md §11).

    Each mode gets a FRESH engine (obs-on compiles live under the disjoint
    ``("obs", bucket)`` retrace keys, so sharing one engine would conflate
    compile caches) and its own warm-up solve; the timed repeats are
    INTERLEAVED across modes so machine drift hits both equally, and the
    recorded walls are best-of-``n_repeats`` minima. The contract
    --check-budget enforces: recording per-outer kkt/gap/ws-size/epoch
    curves into the preallocated ring must ride the existing fused dispatch
    (extra_dispatches == 0 — the one extra host sync is the single drain
    readback at solve end) and cost at most ``BUDGET_TELEMETRY_OVERHEAD``
    extra wall."""
    from repro.obs import Obs

    cfg = TELEMETRY_CONFIG
    X, y, _ = make_correlated_design(seed=0, rho=0.5, snr=5.0, **cfg)
    X, y = jnp.asarray(X), jnp.asarray(y)
    penalty = L1(lambda_max(X, y) / 10)
    kw = dict(tol=1e-10, max_outer=100)
    modes = ("obs_off", "obs_on")
    engines = {m: make_engine(penalty, Quadratic()) for m in modes}
    obses = {"obs_off": None, "obs_on": Obs(trace=False)}
    for m in modes:                                          # compile
        solve(X, y, Quadratic(), penalty, engine=engines[m],
              obs=obses[m], **kw)
        engines[m].metrics.set_counter("engine.n_dispatches", 0)
    walls = {m: float("inf") for m in modes}
    syncs = {}
    for _ in range(n_repeats):
        for m in modes:
            t0 = time.perf_counter()
            res = solve(X, y, Quadratic(), penalty, engine=engines[m],
                        obs=obses[m], **kw)
            walls[m] = min(walls[m], time.perf_counter() - t0)
            syncs[m] = res.n_host_syncs
    rec = {}
    for m in modes:
        rec[m + "_wall_s"] = walls[m]
        rec[m + "_dispatches"] = \
            engines[m].metrics.counter("engine.n_dispatches") // n_repeats
        rec[m + "_host_syncs"] = syncs[m]
    rec["extra_dispatches"] = \
        rec["obs_on_dispatches"] - rec["obs_off_dispatches"]
    rec["overhead_frac"] = (rec["obs_on_wall_s"] - rec["obs_off_wall_s"]) \
        / max(rec["obs_off_wall_s"], 1e-12)
    rec["budget_overhead_frac"] = BUDGET_TELEMETRY_OVERHEAD
    rec["shape"] = [cfg["n"], cfg["p"]]
    return rec


def _measure_sharded(scale):
    """Measure the 2x4 mesh engine in this process, over the devices it
    sees: a chip host's own, or on the CPU the 8 host devices the caller
    forces with XLA_FLAGS=--xla_force_host_platform_device_count=8 (one
    process per chip — a JAX child could not reach a chip this process
    holds)."""
    from repro.launch.mesh import make_test_mesh
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"mesh_2x4 needs 8 devices, this process sees "
            f"{len(jax.devices())}: on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8, or pass --no-sharded")
    mesh = make_test_mesh((2, 4))
    return {bench: _measure(bench, cfg, mesh=mesh)
            for bench, cfg in CONFIGS[scale].items()}


def _check_budget(report, budget_path):
    """Perf-regression guard (CI): dispatches-per-outer-iteration of every
    measured benchmark must not exceed the budget recorded in the committed
    BENCH_engine.json (the engine contract is exactly 1 fused dispatch per
    outer iteration; any growth means the fused step split)."""
    with open(budget_path) as f:
        budget = json.load(f)
    failures = []
    for section in ("engine_after", "mesh_2x4"):
        ref = budget.get(section, {})
        for bench, m in report.get(section, {}).items():
            rb = ref.get(bench, {})
            # explicit scale-independent caps (grid benchmarks amortize
            # below 1 dispatch/outer by a scale-dependent factor) win over
            # the measured value
            cap = rb.get("budget_dispatches_per_outer",
                         rb.get("jit_dispatches_per_outer"))
            if cap is None:
                continue
            if m["jit_dispatches_per_outer"] > cap + 1e-9:
                failures.append(
                    f"{section}/{bench}: "
                    f"{m['jit_dispatches_per_outer']:.3f} dispatches/outer "
                    f"exceeds the recorded budget {cap:.3f}")
    # fused single-read byte budget (DESIGN.md §10): every roofline table in
    # this run must be complete (the five stages + the fused kernel) and its
    # deterministic fused/two-pass bytes-per-outer ratio must stay within
    # the recorded budget
    from repro.roofline.engine_stages import STAGES
    ratio_cap = budget.get("budget_fused_bytes_ratio",
                           BUDGET_FUSED_BYTES_RATIO)
    tables = report.get("roofline", {})
    if not tables:
        failures.append("roofline: no per-stage table recorded")
    for bench, table in tables.items():
        missing = [s for s in (*STAGES, "fused_kernel")
                   if s not in table.get("stages", {})]
        if missing:
            failures.append(f"roofline/{bench}: missing stages {missing}")
        if table["fused_ratio"] > ratio_cap + 1e-9:
            failures.append(
                f"roofline/{bench}: fused bytes-per-outer ratio "
                f"{table['fused_ratio']:.4f} exceeds the budget {ratio_cap}")
    for bench, rec in report.get("pallas_fused", {}).items():
        r = rec.get("fused_bytes_ratio")
        if r is not None and r > ratio_cap + 1e-9:
            failures.append(
                f"pallas_fused/{bench}: fused bytes-per-outer ratio "
                f"{r:.4f} exceeds the budget {ratio_cap}")
    # zero-overhead telemetry contract (DESIGN.md §11): the device-side
    # rings must add no dispatches and at most 2% wall over obs=None
    tele = report.get("telemetry_overhead")
    if tele is None:
        failures.append("telemetry_overhead: no record in this run")
    else:
        if tele["extra_dispatches"] != 0:
            failures.append(
                f"telemetry_overhead: obs-on added "
                f"{tele['extra_dispatches']} jit dispatches (must be 0 — "
                f"the ring must ride the existing fused step)")
        tele_cap = budget.get("telemetry_overhead", {}).get(
            "budget_overhead_frac", BUDGET_TELEMETRY_OVERHEAD)
        if tele["overhead_frac"] > tele_cap + 1e-9:
            failures.append(
                f"telemetry_overhead: obs-on wall overhead "
                f"{tele['overhead_frac']:.4f} exceeds the budget {tele_cap}")
    # serving latency budget (DESIGN.md §13): the open-loop replay's p99
    # must stay under the committed budget, and the steady pass must have
    # added zero compiles (one fused step per (batch, support) bucket pair)
    sv = report.get("serve_fig")
    if sv is None:
        failures.append("serve_fig: no record in this run")
    else:
        p99_cap = budget.get("serve_fig", {}).get("budget_p99_ms",
                                                  sv["budget_p99_ms"])
        if sv["p99_ms"] > p99_cap + 1e-9:
            failures.append(
                f"serve_fig: p99 latency {sv['p99_ms']:.2f}ms exceeds the "
                f"recorded budget {p99_cap:.2f}ms")
        if sv["max_compiles_per_key"] > 1:
            failures.append(
                f"serve_fig: {sv['max_compiles_per_key']} compiles for one "
                f"(batch, support) bucket pair (must be 1)")
    if failures:
        raise SystemExit("perf-budget regression:\n  "
                         + "\n  ".join(failures))
    print(f"dispatch + fused-byte + serve-latency budgets OK "
          f"(vs {budget_path})")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the 2x4-mesh measurement")
    ap.add_argument("--no-sparse", action="store_true",
                    help="skip the sparse_fig2 CSC-native measurement")
    ap.add_argument("--check-budget", default=None, metavar="PATH",
                    help="fail if dispatches/outer exceed the budgets "
                         "recorded in PATH (committed BENCH_engine.json)")
    args = ap.parse_args(argv)
    scale = "smoke" if args.smoke else "small"
    enable_compile_cache()
    out_path = args.out or ("experiments/bench/BENCH_engine_smoke.json"
                            if args.smoke else "BENCH_engine.json")

    report = {"scale": scale, "seed_before": SEED_BEFORE,
              "seed_distributed": SEED_DISTRIBUTED, "engine_after": {}}
    for bench, cfg in CONFIGS[scale].items():
        report["engine_after"][bench] = _measure(bench, cfg)
        after = report["engine_after"][bench]
        print(f"{bench}: {after['wall_s']:.3f}s, "
              f"{after['jit_dispatches_per_outer']:.2f} dispatches/outer, "
              f"{after['host_syncs_per_outer']:.2f} syncs/outer "
              f"(seed: {SEED_BEFORE[bench]['jit_dispatches_per_outer']:.2f} "
              f"/ {SEED_BEFORE[bench]['host_syncs_per_outer']:.2f})")
        if not after["converged"]:
            raise SystemExit(f"{bench} did not converge — engine regression")
        if after["host_syncs_per_outer"] > 1.0 + 1e-9:
            raise SystemExit(f"{bench} exceeded 1 host sync per outer iter")

    for bench, cfg in MT_CONFIGS[scale].items():
        report["engine_after"][bench] = _measure_fig4(cfg)
        m = report["engine_after"][bench]
        print(f"{bench} [multitask n={m['shape'][0]} p={m['shape'][1]} "
              f"T={m['n_tasks']}]: {m['wall_s']:.3f}s, "
              f"{m['jit_dispatches_per_outer']:.2f} dispatches/outer, "
              f"{m['host_syncs_per_outer']:.2f} syncs/outer")
        if not m["converged"]:
            raise SystemExit(f"{bench} did not converge — engine regression")
        if m["host_syncs_per_outer"] > 1.0 + 1e-9:
            raise SystemExit(f"{bench} exceeded 1 host sync per outer iter")

    for bench, cfg in CV_CONFIGS[scale].items():
        report["engine_after"][bench] = _measure_cv(cfg)
        m = report["engine_after"][bench]
        print(f"{bench} [cv grid {m['grid']} n={m['shape'][0]} "
              f"p={m['shape'][1]}]: {m['wall_s']:.3f}s for "
              f"{m['n_solves']} solves, "
              f"{m['jit_dispatches_per_outer']:.2f} dispatches/outer, "
              f"{m['host_syncs_per_outer']:.2f} syncs/outer")
        if not m["converged"]:
            raise SystemExit(f"{bench} did not converge — grid regression")
        if m["jit_dispatches_per_outer"] > 1.0 + 1e-9 or \
                m["host_syncs_per_outer"] > 1.0 + 1e-9:
            raise SystemExit(f"{bench} exceeded 1 dispatch/sync per outer")

    for bench, cfg in SERVE_CONFIGS[scale].items():
        report[bench] = _measure_serve(cfg)
        m = report[bench]
        print(f"{bench} [serve {m['n_models']} models p={m['shape'][1]}]: "
              f"compile {m['compile_s']:.3f}s, steady {m['steady_s']:.3f}s "
              f"for {m['n_requests']} requests ({m['rows']} rows), "
              f"p50 {m['p50_ms']:.2f}ms p99 {m['p99_ms']:.2f}ms, "
              f"{m['throughput_rows_per_s']:.0f} rows/s, "
              f"{m['n_compiles']} compiles / {m['n_dispatches']} dispatches")
        if m["max_compiles_per_key"] > 1:
            raise SystemExit(
                f"{bench}: a (batch, support) bucket compiled "
                f"{m['max_compiles_per_key']}x — the compile-once-per-"
                f"bucket-pair contract broke: {m['retraces']}")

    if not args.no_sparse:
        for bench, cfg in SPARSE_CONFIGS[scale].items():
            report["engine_after"][bench] = _measure(bench, cfg, sparse=True)
            m = report["engine_after"][bench]
            print(f"{bench} [csc n={cfg['n']} p={cfg['p']} "
                  f"density={cfg['density']}]: {m['wall_s']:.3f}s, "
                  f"{m['jit_dispatches_per_outer']:.2f} dispatches/outer, "
                  f"{m['host_syncs_per_outer']:.2f} syncs/outer, "
                  f"nnz={m['nnz']}")
            if not m["converged"]:
                raise SystemExit(f"{bench} did not converge")
            if m["host_syncs_per_outer"] > 1.0 + 1e-9:
                raise SystemExit(f"{bench} exceeded 1 host sync per outer")

    # fused Pallas head, before/after: the same benchmark solved through the
    # jax backend (two-pass score -> select -> gather) and the Pallas backend
    # (single-traversal fused kernel). Always measured at the smoke shapes:
    # Pallas runs in interpret mode on CPU, so the wall clocks are a
    # correctness/trajectory record while the byte models carry the perf
    # claim (their ratio is what --check-budget enforces).
    from repro.roofline.engine_stages import (fused_bytes_model, stage_table,
                                              two_pass_bytes_model)
    report["budget_fused_bytes_ratio"] = BUDGET_FUSED_BYTES_RATIO
    report["pallas_fused"] = {}
    fused_benches = [("fig2_lasso", CONFIGS["smoke"]["fig2_lasso"], False)]
    if not args.no_sparse:
        fused_benches.append(
            ("sparse_fig2", SPARSE_CONFIGS["smoke"]["sparse_fig2"], True))
    for bench, cfg, sp in fused_benches:
        before = _measure(bench, cfg, sparse=sp)
        after = _measure(bench, cfg, sparse=sp, use_kernels=True)
        rec = {"before_jax": before, "after_pallas": after,
               "shape": [cfg["n"], cfg["p"]]}
        if not sp:     # the dense fused head carries the byte-budget claim
            two = two_pass_bytes_model(cfg["n"], cfg["p"], 64)
            fus = fused_bytes_model(cfg["n"], cfg["p"], 64)
            rec["two_pass_bytes_per_outer"] = two["total"]
            rec["fused_bytes_per_outer"] = fus["total"]
            rec["fused_bytes_ratio"] = fus["total"] / two["total"]
        report["pallas_fused"][bench] = rec
        extra = (f", bytes/outer ratio {rec['fused_bytes_ratio']:.4f}"
                 if not sp else "")
        print(f"{bench} [pallas fused]: jax {before['wall_s']:.3f}s -> "
              f"pallas(interpret) {after['wall_s']:.3f}s{extra}")
        if not after["converged"]:
            raise SystemExit(f"{bench} [pallas fused] did not converge")

    # the per-stage roofline table CI enforces (deterministic byte models +
    # measured XLA costs at this scale's fig2_lasso shape, ws bucket 64).
    # The table is also published as roofline.* gauges into a
    # MetricsRegistry (DESIGN.md §11.3) so the printed budget line reads
    # from the same named views the obs layer exposes.
    from repro.obs import MetricsRegistry
    from repro.roofline import register_stage_table
    rl = CONFIGS[scale]["fig2_lasso"]
    report["roofline"] = {
        "fig2_lasso": stage_table(rl["n"], rl["p"], 64)}
    rl_reg = MetricsRegistry()
    register_stage_table(rl_reg, "fig2_lasso", report["roofline"]["fig2_lasso"])
    print(f"roofline fig2_lasso: fused/two-pass bytes-per-outer ratio "
          f"{rl_reg.gauge('roofline.fig2_lasso.fused_ratio'):.4f} "
          f"(budget {BUDGET_FUSED_BYTES_RATIO})")

    # zero-overhead telemetry proof (DESIGN.md §11): obs-on vs obs-off at
    # the smoke shapes — CI fails if the rings add any dispatch or >2% wall
    report["telemetry_overhead"] = _measure_telemetry_overhead()
    tele = report["telemetry_overhead"]
    print(f"telemetry_overhead: obs off {tele['obs_off_wall_s']:.4f}s / "
          f"on {tele['obs_on_wall_s']:.4f}s "
          f"(+{tele['overhead_frac'] * 100:.2f}%), "
          f"extra dispatches {tele['extra_dispatches']}, "
          f"syncs {tele['obs_off_host_syncs']} -> "
          f"{tele['obs_on_host_syncs']}")

    if not args.no_sharded:
        report["mesh_2x4"] = _measure_sharded(scale)
        for bench, m in report["mesh_2x4"].items():
            print(f"{bench} [mesh 2x4]: {m['wall_s']:.3f}s, "
                  f"{m['jit_dispatches_per_outer']:.2f} dispatches/outer, "
                  f"{m['host_syncs_per_outer']:.2f} syncs/outer "
                  f"(seed distributed loop: "
                  f"{SEED_DISTRIBUTED['jit_dispatches_per_outer']:.2f} / "
                  f"{SEED_DISTRIBUTED['host_syncs_per_outer']:.2f})")
            if not m["converged"]:
                raise SystemExit(f"{bench} [mesh] did not converge")
            if m["host_syncs_per_outer"] > 1.0 + 1e-9:
                raise SystemExit(f"{bench} [mesh] exceeded 1 sync per outer")

    if args.check_budget:
        _check_budget(report, args.check_budget)

    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, default=float)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
