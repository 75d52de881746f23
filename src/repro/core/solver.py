"""The skglm solver: paper Algorithm 1 (working sets) + Algorithm 2 (Anderson-CD).

This is the thin HOST driver over the device-resident engine
(`core/engine.py`, DESIGN.md §3): per outer iteration it launches exactly one
fused jitted step — score pass, working-set selection, gather, inner
Anderson-CD solve, scatter — compiled once per power-of-two working-set
bucket, and reads back one small scalar tuple (kkt, objective, |gsupp|,
epochs). Quadratic datafits use the Gram inner solver (TPU-native: the K x K
Gram and the K-vector state stay VMEM-resident; see DESIGN.md §2); general
datafits use the Xb inner solver (Algorithm 3 verbatim). The `backend`
switches CD epochs between pure XLA ("jax") and the Pallas kernels
("pallas", parameterized through the kernels/common.py penalty codec).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.obs import SolveDiagnostics, TelemetryRing, span

from .engine import EngineConfig, SolveEngine, as_design, get_engine
from .working_set import BucketPolicy

__all__ = ["solve", "SolveResult", "normalize_weights"]


def _place_design(engine, design, y, w=None):
    """Shard (design, y[, w]) on the engine's mesh (idempotent for
    pre-sharded input; sparse designs convert to their stacked per-shard
    form here). Multitask targets [n, T] keep the task dimension
    replicated; sample weights shard with the data axis like y."""
    from repro.launch.shardings import task_spec, weight_spec
    _, ys, _ = engine._specs()
    design = design.place(engine.mesh, engine.data_axis, engine.model_axis)
    spec = task_spec(ys, y.ndim - 1)
    y = jax.device_put(y, NamedSharding(engine.mesh, spec))
    if w is not None:
        w = jax.device_put(
            w, NamedSharding(engine.mesh, weight_spec(engine.data_axis)))
    return design, y, w


def normalize_weights(sample_weight, n, dtype):
    """Validate a user sample-weight vector and rescale it to sum to n.

    The solve stack's weighted datafits keep normalizing by the sample
    count (DESIGN.md §9), so rescaling to ``sum(w) = n`` makes the weighted
    objective exactly the weighted-mean loss — 0/1 fold weights then
    reproduce the row-subset problem at the same lambda. Raises
    ``ValueError`` on wrong shape, negative entries, non-finite entries, or
    an all-zero vector. Returns a device array of ``dtype``.
    """
    w = np.asarray(sample_weight, dtype=np.float64)
    if w.ndim != 1 or w.shape[0] != n:
        raise ValueError(
            f"sample_weight must be a 1-D vector of length n={n}, got "
            f"shape {w.shape}")
    if not np.all(np.isfinite(w)):
        raise ValueError("sample_weight must be finite")
    if np.any(w < 0):
        raise ValueError("sample_weight must be non-negative")
    s = float(w.sum())
    if s <= 0.0:
        raise ValueError("sample_weight sums to zero: no effective samples")
    return jnp.asarray(w * (n / s), dtype)


@dataclass
class SolveResult:
    """Result of one :func:`solve` call.

    Attributes
    ----------
    beta : jax.Array
        Final coefficients, ``[p]`` or ``[p, T]`` (multitask).
    kkt : float
        Final maximum optimality-violation score (paper Eq. 2).
    converged : bool
        Whether ``kkt <= tol`` within ``max_outer`` iterations.
    n_outer, n_epochs : int
        Outer iterations driven / total inner CD epochs.
    kkt_history, ws_history, obj_history, time_history : list
        Per-outer-iteration telemetry (violation, bucket size, objective,
        cumulative seconds).
    diagnostics : repro.obs.SolveDiagnostics
        Structured convergence record (DESIGN.md §11): ``curves`` holds the
        per-outer kkt/obj/time arrays (plus gap/ws_size/epochs/accepts/
        occupancy when a telemetry ring ran, i.e. ``solve(..., obs=...)``),
        ``registry`` the per-solve counters, and ``summary()`` renders the
        convergence table.
    n_host_syncs : int
        Blocking device-to-host readbacks (the engine contract is one per
        outer iteration, plus one probe for warm starts, plus one ring
        drain when telemetry is on). A property view into the
        ``"solve.n_host_syncs"`` counter of ``diagnostics.registry``;
        reads and ``+=`` writes work exactly as the pre-§11 plain field.
    """
    beta: jax.Array
    kkt: float                       # final max optimality violation
    converged: bool
    n_outer: int
    n_epochs: int
    kkt_history: list = field(default_factory=list)
    ws_history: list = field(default_factory=list)
    obj_history: list = field(default_factory=list)
    time_history: list = field(default_factory=list)
    diagnostics: SolveDiagnostics = field(default_factory=SolveDiagnostics)

    @property
    def n_host_syncs(self) -> int:
        """Blocking device->host readbacks (view into the registry)."""
        return self.diagnostics.registry.counter("solve.n_host_syncs")

    @n_host_syncs.setter
    def n_host_syncs(self, value: int):
        self.diagnostics.registry.set_counter("solve.n_host_syncs",
                                              int(value))


def make_engine(penalty, datafit, *, M=5, max_epochs=1000, accel=True,
                use_fp_score=None, use_gram="auto", use_kernels=False,
                mesh=None, data_axis="data", model_axis="model",
                shared=False):
    """Build a SolveEngine for a (datafit, penalty) family. `shared=True`
    returns the process-wide cached engine for the config (compiled steps are
    reused across solves); `shared=False` gives a fresh engine with isolated
    retrace/dispatch counters. `mesh` (a jax Mesh holding `data_axis` and
    `model_axis`) makes the engine mesh-native: the same fused step runs
    under shard_map on the sharded design (DESIGN.md §6)."""
    if use_fp_score is None:
        use_fp_score = not penalty.HAS_SUBDIFF
    gram = datafit.HAS_GRAM if use_gram == "auto" else bool(use_gram)
    cfg = EngineConfig(M=M, max_epochs=max_epochs, accel=accel,
                       use_fp_score=use_fp_score, gram=gram,
                       backend="pallas" if use_kernels else "jax")
    if shared:
        return get_engine(cfg, mesh=mesh, data_axis=data_axis,
                          model_axis=model_axis)
    return SolveEngine(cfg, mesh=mesh, data_axis=data_axis,
                       model_axis=model_axis)


def solve(X, y, datafit, penalty, *, tol=1e-6, max_outer=50, max_epochs=1000,
          M=5, p0=64, use_gram="auto", use_fp_score=None, eps_inner_frac=0.3,
          beta0=None, gsupp0=None, n_tasks=None, accel=True, use_ws=True,
          use_kernels=False, mesh=None, data_axis="data", model_axis="model",
          engine=None, bucket_policy=None, sample_weight=None, obs=None):
    """Solve Problem (1): ``argmin_beta F(X beta) + sum_j g_j(beta_j)``.

    The thin host driver over the device-resident fused engine: one jitted
    dispatch and one blocking scalar readback per outer iteration of
    Algorithm 1, compiled once per power-of-two working-set bucket.

    Parameters
    ----------
    X : array_like, scipy sparse matrix, or Design
        Design matrix ``[n, p]``. Scipy sparse input is converted to a
        CSC-native :class:`repro.sparse.CSCDesign` (DESIGN.md §7) and solved
        without ever materializing the dense X — the score pass is a
        segment-sum over the nnz entries and only the K working-set columns
        are densified for the inner solve.
    y : array_like
        Targets ``[n]``, or ``[n, T]`` for multitask datafits (the
        coefficients are then row blocks ``[p, T]``, DESIGN.md §8).
    datafit : object
        Smooth term F — see :mod:`repro.core.datafits`.
    penalty : object
        Separable penalty g — see :mod:`repro.core.penalties`. Penalties are
        pytrees with hyper-parameters as leaves: changing ``lam`` never
        retraces the compiled step.
    tol : float, optional
        Outer-loop KKT tolerance (max violation score, paper Eq. 2).
    max_outer, max_epochs, M : int, optional
        Outer-iteration cap, inner-epoch cap, and epochs per Anderson block.
    p0 : int, optional
        First working-set bucket (paper Algorithm 1 line 2).
    use_gram : {"auto", True, False}, optional
        "auto" picks the Gram inner solver for quadratic datafits (K-sized
        VMEM-resident state), the Xb form otherwise.
    use_fp_score : bool, optional
        Force the fixed-point violation score (default: automatic — True
        exactly for penalties without informative subdifferentials).
    eps_inner_frac : float, optional
        Inner tolerance as a fraction of the current outer KKT violation.
    beta0 : array_like, optional
        Warm start; its generalized support sizes the first bucket (one
        extra probe launch + sync per solve, unless ``gsupp0`` is given).
    gsupp0 : int, optional
        Generalized-support size of ``beta0``, when the caller already
        knows it host-side (e.g. the serving bank's slot metadata,
        DESIGN.md §13). Skips the warm-start probe entirely — the solve
        then launches zero readbacks beyond the per-outer scalar tuple,
        which is what keeps on-device refits free of coefficient
        round-trips. Ignored when ``beta0`` is None.
    n_tasks : int, optional
        Number of tasks T (inferred from ``y.ndim == 2`` when omitted).
    accel, use_ws : bool, optional
        Disable Anderson extrapolation / working sets (Figure 6 ablations).
    use_kernels : bool, optional
        Run the outer step and CD epochs through the Pallas kernels
        (VMEM-resident on TPU, interpret mode on CPU). Dense unsharded
        solves use the fused score→select→gather kernel (one X traversal
        per outer iteration, DESIGN.md §10); weighted and multitask solves
        are supported (block-penalty inner epochs fall back to the jax
        path). Only mesh=... and non-ELL sparse designs reject at entry.
    mesh : jax.sharding.Mesh, optional
        Run the SAME fused outer step under shard_map — X sharded samples x
        features over (``data_axis``, ``model_axis``), beta over features,
        residual over samples (DESIGN.md §6). The dispatch/sync budget is
        unchanged. Multitask/block penalties shard too (block top-k over the
        model axis, replicated block Gram inner solve, DESIGN.md §8); the
        combinations the engine cannot run — the Pallas backend under
        shard_map, per-coordinate penalty arrays, sample-sharded sparse
        designs, non-dividing shapes — raise here, before any trace.
    engine : SolveEngine, optional
        Share compiled fused steps across many solves (see
        :func:`make_engine`) and read back retrace/dispatch telemetry.
    bucket_policy : BucketPolicy, optional
        Override the working-set bucket ladder.
    sample_weight : array_like, optional
        Non-negative per-sample weights ``[n]`` (DESIGN.md §9). Validated
        and rescaled to sum to n at entry (the weighted objective is the
        weighted-mean loss, so 0/1 fold-membership weights reproduce the
        row-subset problem exactly); flows as a pytree leaf through the
        fused step, so changing weights never retraces. ``None`` keeps the
        bit-identical unweighted program. Weighted solves require a datafit
        with ``SUPPORTS_WEIGHTS`` and run on every backend (the Pallas
        kernels fold w into the in-kernel raw gradient).
    obs : repro.obs.Obs, optional
        Observability handle (DESIGN.md §11). When given, the solve carries
        a device telemetry ring through the fused step — per-outer
        kkt/gap/objective/ws curves land on ``result.diagnostics`` — and
        records the solve's nested spans (solve → outer → dispatch/sync)
        on ``obs.tracer``; with or without ``obs`` they are written to any
        profiler capture as ``repro/<name>`` (DESIGN.md §11.2). Zero extra
        dispatches; one extra blocking readback at drain time. ``obs=None`` (the default) statically elides every
        telemetry op: the compiled program is bit-identical to the pre-obs
        one (same mechanism as the ``w=None`` weight leaf).

    Returns
    -------
    SolveResult
        Final coefficients, convergence state, and per-iteration telemetry
        (kkt/objective/time histories, host-sync count).

    Examples
    --------
    >>> res = solve(X, y, Quadratic(), L1(0.1 * lambda_max(X, y)))
    >>> res.converged, res.beta.shape
    (True, (p,))
    """
    sp = obs.span if obs is not None else span
    with sp("solve") as solve_ev:
        # a mesh solve places a host array straight onto its shards
        design = as_design(X, on_host=(mesh if engine is None
                                       else engine.mesh) is not None)
        n_rows, p = design.shape
        if not use_ws:
            p0 = p
        if use_fp_score is None:
            use_fp_score = not penalty.HAS_SUBDIFF
        gram = datafit.HAS_GRAM if use_gram == "auto" else bool(use_gram)
        if n_tasks is None:
            n_tasks = y.shape[1] if (hasattr(y, "ndim") and y.ndim == 2) \
                else 0

        if engine is None:
            engine = make_engine(penalty, datafit, M=M, max_epochs=max_epochs,
                                 accel=accel, use_fp_score=use_fp_score,
                                 use_gram=gram, use_kernels=use_kernels,
                                 mesh=mesh, data_axis=data_axis,
                                 model_axis=model_axis, shared=True)
        elif mesh is not None and engine.mesh is not mesh:
            raise ValueError("solve(mesh=..., engine=...): the engine was "
                             "built for a different mesh; pass mesh to "
                             "make_engine instead")
        engine.validate(datafit, penalty, n_tasks, shape=design.shape,
                        design=design, weighted=sample_weight is not None)
        policy = bucket_policy or BucketPolicy(p0=p0)

        w = None if sample_weight is None \
            else normalize_weights(sample_weight, n_rows, design.dtype)
        if engine.mesh is not None:
            design, y, w = _place_design(engine, design, y, w)
        L = design.lipschitz(datafit) if w is None \
            else design.lipschitz(datafit, w, backend=engine.config.backend)
        offset = datafit.grad_offset(p, design.dtype)
        bshape = (p, n_tasks) if n_tasks else (p,)
        beta = jnp.zeros(bshape, design.dtype) if beta0 is None \
            else jnp.asarray(beta0)
        if engine.mesh is not None:
            from repro.launch.shardings import task_spec
            _, _, bs = engine._specs()
            beta = jax.device_put(
                beta, NamedSharding(engine.mesh, task_spec(bs, n_tasks)))
        Xb = design.matvec(beta)

        res = SolveResult(beta=beta, kkt=float("inf"), converged=False,
                          n_outer=0, n_epochs=0)
        if isinstance(solve_ev, dict):
            solve_ev["args"].update(n=n_rows, p=p, tol=tol,
                                    backend=engine.config.backend)
        ring = None
        if obs is not None and getattr(obs, "rings", True):
            ring = TelemetryRing.alloc(max_outer, design.dtype)
        t0 = time.perf_counter()

        # first-bucket sizing: cold starts have empty generalized support;
        # warm starts probe it once (one launch + one sync per solve, not
        # per iter)
        if beta0 is None:
            gcount = 0
        elif gsupp0 is not None:
            gcount = int(gsupp0)
        else:
            with sp("probe"):
                _, g0, _ = engine.probe(design, y, beta, Xb, L, offset,
                                        datafit, penalty, w=w)
                gcount = int(g0)
            res.n_host_syncs += 1
        bucket = policy.first_bucket(gcount, p)

        for t in range(max_outer):
            with sp("outer", it=t, bucket=bucket) as ev:
                r0 = sum(engine.retraces.values()) if obs is not None else 0
                with sp("dispatch", bucket=bucket):
                    out = engine.step(
                        bucket, design, y, beta, Xb, L, offset, datafit,
                        penalty, tol, eps_inner_frac, w=w, obs=ring)
                if ring is not None:
                    (beta, Xb, kkt_d, obj_d, gcount_d, nep_d, cov_d,
                     ring) = out
                else:
                    beta, Xb, kkt_d, obj_d, gcount_d, nep_d, cov_d = out
                # the single blocking host sync of this outer iteration
                with sp("sync"):
                    kkt, obj, gcount, n_ep, cov = jax.device_get(
                        (kkt_d, obj_d, gcount_d, nep_d, cov_d))
                if isinstance(ev, dict):
                    ev["args"]["compiled"] = \
                        sum(engine.retraces.values()) > r0
            res.n_host_syncs += 1
            if not bool(cov):
                raise RuntimeError(
                    "working-set selection dropped generalized-support "
                    "coordinates (bucket too small for |gsupp| — "
                    "bucket-policy invariant violated)")
            kkt = float(kkt)
            res.kkt_history.append(kkt)
            res.obj_history.append(float(obj))
            res.time_history.append(time.perf_counter() - t0)
            if kkt <= tol:
                res.converged = True
                res.n_outer = t
                break
            res.ws_history.append(bucket)
            res.n_epochs += int(n_ep)
            res.n_outer = t + 1
            bucket = policy.next_bucket(bucket, int(gcount), p)

        res.beta = beta
        res.kkt = res.kkt_history[-1] if res.kkt_history else float("inf")
        if ring is not None:
            # one extra (and final) blocking readback of the whole solve
            with sp("drain"):
                curves, n_rec = ring.drain()
            res.n_host_syncs += 1
            res.diagnostics.curves.update(curves)
            res.diagnostics.n_recorded = n_rec
        else:
            res.diagnostics.curves.update(
                kkt=np.asarray(res.kkt_history),
                obj=np.asarray(res.obj_history),
                ws_size=np.asarray(res.ws_history, dtype=np.int64))
            res.diagnostics.n_recorded = len(res.kkt_history)
        res.diagnostics.curves["time_s"] = np.asarray(res.time_history)
        reg = res.diagnostics.registry
        reg.set_counter("solve.n_outer", res.n_outer)
        reg.set_counter("solve.n_epochs", res.n_epochs)
        sent = engine.metrics.mapping("engine.collective_bytes")
        reg.set_counter("solve.collective_bytes",
                        sum(sent.get(b, 0) for b in res.ws_history))
    if obs is not None:
        obs.registry.inc("solve.count")
        obs.note_solve(res.diagnostics)
    return res
