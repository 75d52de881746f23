"""Device-resident solve engine (DESIGN.md §3).

The engine fuses one outer iteration of Algorithm 1 — score pass, working-set
selection, gather, inner Anderson-CD solve, scatter — into a single jitted
program compiled once per power-of-two working-set *bucket*. The host loop in
``core.solver.solve`` only launches that one program and reads back a small
scalar tuple (kkt, objective, |gsupp|, epoch count) per outer iteration: one
dispatch, one sync, instead of the historical 3-4 dispatches and 3 blocking
scalar pulls.

Layering (bottom-up):

  SubproblemSolver      Algorithm 2 on a fixed-size working set: blocks of M
    GramSolver          cyclic CD epochs + guarded Anderson extrapolation.
    XbSolver            Gram form for quadratic datafits (state = q = G beta),
                        Xb form for general datafits (state = Xb). Each epoch
                        runs through a pluggable backend: "jax" (pure-XLA
                        fori loop, cd.py) or "pallas" (VMEM-resident kernel,
                        kernels/cd_epoch.py) — the kernel is a first-class
                        backend, parameterized through the penalty codec in
                        kernels/common.py, not a bolt-on shim.
  SolveEngine           the fused outer step (scalar solves) and the vmapped
                        multi-lambda chunk step (regularization paths), plus
                        per-bucket retrace and dispatch telemetry.

Working-set sizes are bucketed to powers of two (working_set.BucketPolicy) so
a whole regularization path reuses one compiled step per bucket; penalties
and datafits are pytrees with hyper-parameters as leaves, so lambda changes
never retrace.

Mesh-native mode (DESIGN.md §6): constructed with a (data, model) mesh, the
SAME fused outer step runs under shard_map — X sharded samples x features,
beta/L/offset over features, y/Xb over samples; the score pass psums the
gradient over the data axis, working-set selection is an exact distributed
top-k over the model axis, and the K-sized inner subproblem runs replicated
(Gram form) or with per-coordinate data-axis psums (Xb form). One jitted
program per working-set bucket serves any mesh, including 1x1.

Block coordinates (DESIGN.md §8): every stage is written over coordinate
*blocks* — beta may be [p] (scalar coordinates) or [p, T] (multitask row
blocks, e.g. MultitaskQuadratic + BlockL1/BlockMCP). Violation scores are
per-row block norms, so selection/top-k/bucketing are unchanged; gathers
and scatters move [K, T] blocks; the Gram inner solve is the K x K Gram
against a [K, T] right-hand side; the task dimension is replicated on every
mesh. The Pallas backend scores multitask blocks in-kernel too (the fused
head handles [p, T]); only its CD *epoch* kernels are scalar-coordinate, so
block-penalty inner solves fall back to the jax epochs per M-block.

Sample weights (DESIGN.md §9): every step takes an optional per-sample
weight vector ``w`` [n], sharded with the data mesh axis exactly like y/Xb
and forwarded to the datafit's value/raw_grad/make_gram — the design
primitives (score / gather / incremental Xb) are untouched because the
weights enter through the raw gradient. ``w=None`` statically elides every
weight op, so the unweighted trace is the bit-identical pre-weight program.
The chunked driver additionally accepts *per-lane* weights [C, n] (and the
matching per-lane Lipschitz constants [C, p]): fold-membership 0/1 weights
make every CV/bootstrap replicate of a grid solve share one static shape,
so one compiled step per bucket serves the whole (fold x lambda) grid.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from repro.launch.shardings import design_specs, ring_spec, task_spec
from repro.obs import collectives
from repro.obs.registry import MetricsRegistry
from repro.obs.rings import gap_supported, quadratic_l1_gap

from .anderson import anderson_extrapolate
from .cd import cd_epoch_xb, cd_sweep_block, gram_sweep, gram_sweep_epoch
from .working_set import (candidate_columns, gather_ws_cols, gather_ws_vec,
                          scatter_ws, select_working_set,
                          select_working_set_local, shard_ws_mask,
                          violation_scores, ws_occupancy)

__all__ = ["EngineConfig", "SolveEngine", "SubproblemSolver", "GramSolver",
           "XbSolver", "get_engine", "KERNEL_DATAFIT_KINDS", "Design",
           "DenseDesign", "as_design", "pack_support", "scatter_packed"]


# explicit on the eager Design entry points (lambda_max, warm-start
# residuals), which run outside the f32_matmuls-traced steps
_HIGHEST = jax.lax.Precision.HIGHEST

# datafit class name -> kernels/cd_epoch.py datafit_kind tag (the Pallas Xb
# kernel hard-codes the raw-gradient formula per kind)
KERNEL_DATAFIT_KINDS = {
    "Quadratic": "quadratic",
    "Logistic": "logistic",
    "QuadraticSVC": "svc",
}

# Unified Pallas rejection wording (DESIGN.md §8.4): after weighted,
# multitask, and chunked solves gained Pallas support, exactly two
# combinations still reject — each has ONE message text shared by every
# raise site (engine.validate and the sparse design's defensive check), with
# a pointer to the supported-path matrix.
_PALLAS_MATRIX = ("see the supported-path matrix in README.md "
                  "(Pallas column) and DESIGN.md §8.4")
PALLAS_MESH_ERROR = (
    "backend='pallas' does not run under mesh=...: the kernels own the "
    "device grid that shard_map would partition; " + _PALLAS_MATRIX +
    "; use backend='jax' (use_kernels=False) for sharded solves")
PALLAS_SPARSE_ELL_ERROR = (
    "backend='pallas' on a sparse design requires the ELL score layout: "
    "build it with CSCDesign.from_scipy(X, ell=True); " + _PALLAS_MATRIX)
PALLAS_SPARSE_TPU_ERROR = (
    "backend='pallas' on a sparse design does not run on TPU: the ELL score "
    "kernel (sparse/ops.py csc_score_pallas) does not compile for the chip "
    "yet; " + _PALLAS_MATRIX + "; use backend='jax' (use_kernels=False) for "
    "sparse solves on TPU")


def f32_matmuls(fn):
    """Trace `fn` with float32-accurate matmuls. On TPU, XLA's default
    precision rounds float32 matmul operands to bfloat16 (about three
    significant digits), which would put every score pass, Gram and residual
    far outside any float32 KKT tolerance; on CPU it changes nothing."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return traced


# --------------------------------------------------------- design abstraction
class Design:
    """Protocol of the design matrix X as the engine consumes it (DESIGN.md
    §7). Only three primitives ever touch the full design — the score pass
    ``X.T @ raw``, the working-set column gather, and the residual update
    ``Xb += X_ws d`` — so a Design supplies exactly those (plus the eager
    host-level helpers solve() needs: matvec, Lipschitz constants, mesh
    placement). Implementations are pytrees; ``DenseDesign`` wraps a dense
    array and lowers to the bit-identical pre-Design program,
    ``sparse.CSCDesign`` is the CSC-native form that never materializes X.

    Traced methods run on LOCAL blocks inside shard_map (after
    ``local_block()`` strips any stacked shard axis); eager methods see the
    global design.
    """
    KIND = "abstract"

    # traced (inside the fused step) --------------------------------------
    def local_block(self):
        """This device's local feature block (strips any stacked shard
        axis inside shard_map; identity for unsharded designs)."""
        raise NotImplementedError

    def score(self, raw, backend="jax"):
        """This feature block's X.T @ raw (pre data-axis reduction)."""
        raise NotImplementedError

    def gather_ws(self, mine, loc_idx, model_axis):
        """Densify the ws columns -> ([n_loc, K] model-replicated, aux)."""
        raise NotImplementedError

    def update_xb(self, Xb, X_ws, ws_aux, delta, model_axis):
        """Xb + X_ws @ delta (aux carries sparse scatter windows)."""
        raise NotImplementedError

    # eager (host level) ---------------------------------------------------
    def matvec(self, beta):
        """X @ beta on the global design ([p] or multitask [p, T])."""
        raise NotImplementedError

    def lipschitz(self, datafit, w=None, backend="jax"):
        """Per-coordinate Lipschitz constants L_j of nabla_j f (`w`:
        optional per-sample weights, DESIGN.md §9; `backend="pallas"` lets
        sparse designs route the weighted column-square reduction through
        the Pallas segment-sum kernel on their grid-driver hot path)."""
        raise NotImplementedError

    def in_spec(self, data_axis, model_axis):
        """Single PartitionSpec used as the shard_map pytree-prefix spec for
        every leaf of this design."""
        raise NotImplementedError

    def place(self, mesh, data_axis, model_axis):
        """Shard the design onto `mesh` (idempotent)."""
        raise NotImplementedError


@dataclass(frozen=True)
class DenseDesign(Design):
    """Dense design: the identity wrapper. Every method lowers to the exact
    expression the engine used before the Design abstraction, so dense
    solves stay bit-identical (asserted by test_engine/test_mesh_engine)."""
    X: jax.Array

    KIND = "dense"

    @property
    def shape(self):
        return self.X.shape

    @property
    def dtype(self):
        return self.X.dtype

    @property
    def n_rows(self):
        return self.X.shape[0]

    @property
    def width(self):
        return self.X.shape[1]

    def local_block(self):
        return self

    def score(self, raw, backend="jax"):
        return jnp.matmul(self.X.T, raw, precision=_HIGHEST)

    def gather_ws(self, mine, loc_idx, model_axis):
        return gather_ws_cols(self.X, mine, loc_idx, model_axis), None

    def update_xb(self, Xb, X_ws, ws_aux, delta, model_axis):
        del ws_aux, model_axis          # X_ws is already model-replicated
        return Xb + _apply_T(X_ws.T, delta)

    def matvec(self, beta):
        return jnp.matmul(self.X, beta, precision=_HIGHEST)

    def lipschitz(self, datafit, w=None, backend="jax"):
        del backend                     # dense reduction is already one pass
        if w is None:
            return datafit.lipschitz(self.X)
        return datafit.lipschitz(self.X, w)

    def col_sq_norms(self):
        return jnp.sum(self.X * self.X, axis=0)

    def in_spec(self, data_axis, model_axis):
        return design_specs(data_axis, model_axis)[0]

    def place(self, mesh, data_axis, model_axis):
        spec = self.in_spec(data_axis, model_axis)
        return DenseDesign(jax.device_put(self.X, NamedSharding(mesh, spec)))

    def take_columns(self, idx):
        """Column subset with -1 entries as zero columns (screening pad)."""
        idx = np.asarray(idx)
        Xn = np.asarray(self.X)
        out = Xn[:, np.where(idx < 0, 0, idx)]
        out[:, idx < 0] = 0.0
        return DenseDesign(jnp.asarray(out))


jax.tree_util.register_pytree_node(
    DenseDesign, lambda d: ((d.X,), None),
    lambda aux, ch: DenseDesign(*ch))


def is_scipy_sparse(X) -> bool:
    """Structural check shared by every dispatch site (as_design, the
    estimators' predict/fit paths): scipy sparse without importing scipy."""
    return hasattr(X, "tocsc") and hasattr(X, "nnz")


def as_design(X, on_host=False) -> Design:
    """Dispatch any accepted design input to a Design: Design instances pass
    through, scipy sparse matrices convert to CSC, everything else is a
    dense array. ``on_host=True`` keeps a numpy array on the host, for a
    caller that places it straight onto a mesh (``Design.place``) instead
    of first copying all of it to one device."""
    if isinstance(X, Design):
        return X
    if is_scipy_sparse(X):
        from repro.sparse.matrix import CSCDesign
        return CSCDesign.from_scipy(X)
    if on_host and isinstance(X, np.ndarray):
        return DenseDesign(X)
    return DenseDesign(jnp.asarray(X))


def _lin(offset, beta):
    if beta.ndim == 2:
        return jnp.sum(offset[:, None] * beta)
    return jnp.vdot(offset, beta)


# Defensive dispatch of the optional sample-weight argument: w=None calls the
# two-argument form, so pre-weight custom datafits keep working and the
# unweighted trace is the bit-identical pre-weight program (DESIGN.md §9).
def _df_value(datafit, Xb, y, w):
    return datafit.value(Xb, y) if w is None else datafit.value(Xb, y, w)


def _df_raw(datafit, Xb, y, w):
    return datafit.raw_grad(Xb, y) if w is None \
        else datafit.raw_grad(Xb, y, w)


def _apply_T(Xt_ws, beta):
    """X_ws @ beta given X stored transposed [K, n]."""
    if beta.ndim == 2:
        return jnp.tensordot(beta, Xt_ws, axes=((0,), (0,))).T   # [n, T]
    return beta @ Xt_ws


@dataclass(frozen=True)
class EngineConfig:
    """Static (compile-time) solver configuration. Hashable: engines are
    cached per config, so identical solves share compiled programs."""
    M: int = 5
    max_epochs: int = 1000
    accel: bool = True
    use_fp_score: bool = False
    gram: bool = True
    backend: str = "jax"            # "jax" | "pallas"

    @property
    def max_blocks(self) -> int:
        return max(1, math.ceil(self.max_epochs / self.M))


@dataclass(frozen=True)
class WorkingSetContext:
    """Gathered per-working-set tensors consumed by a SubproblemSolver.

    `axis` names the mesh axis the SAMPLES are sharded over when the solve
    runs inside shard_map (mesh-native engine): Xt_ws/y then hold local rows
    and the Xb solver completes each n-reduction with a psum over `axis`.
    """
    Xt_ws: jax.Array                 # [K, n] gathered design, transposed
    y: jax.Array
    L_ws: jax.Array                  # [K]
    offset_ws: jax.Array             # [K]
    datafit: object
    penalty: object
    G: jax.Array = None              # [K, K] (Gram solvers only)
    c: jax.Array = None              # [K(, T)] (Gram solvers only)
    axis: str = None                 # data-shard mesh axis (sharded Xb form)
    w: jax.Array = None              # per-sample weights (Xb solvers only;
                                     # the Gram form bakes w into G/c)
    Xb_base: jax.Array = None        # Xb0 - X_ws beta_ws0: residual of the
                                     # nonzero coordinates OUTSIDE ws (Xb
                                     # solvers; Box pins coords at C with
                                     # empty generalized support)
    sweep: object = None             # G, c, L laid out for the blocked CD
                                     # sweep (core/cd.py GramSweep; Gram
                                     # solvers on the jax backend)


def _psum_if(x, axis):
    """psum over `axis`, statically elided for unsplit (size-1) axes."""
    return x if axis is None else collectives.psum(x, axis)


class _ShardedDatafit:
    """Per-shard view of a datafit inside shard_map.

    Sample-mean datafits (SAMPLE_MEAN) normalize by the LOCAL row count when
    handed a shard, so their outputs are rescaled by 1/n_data_shards to make
    them partial terms of the global-n quantities: raw_grad stays local
    (per-sample, correctly scaled), value() completes the objective with a
    psum over the data axis. `axis=None` (samples unsplit) makes both
    pass-throughs — the wrapper then lowers to the plain datafit.
    """

    def __init__(self, base, n_data_shards: int, axis: str):
        self.base = base
        # SAMPLE_MEAN is consulted only when the samples are actually split
        # (mesh engines validate it exists at entry): guessing a default
        # would silently mis-scale sum-form custom datafits on data-split
        # meshes, while dense engines must keep working with any datafit
        self.corr = (1.0 if n_data_shards == 1
                     else 1.0 / n_data_shards if base.SAMPLE_MEAN else 1.0)
        self.axis = axis

    @property
    def sample_mean(self):
        return self.base.SAMPLE_MEAN

    def raw_grad(self, Xb, y, w=None):
        # w multiplies per-sample terms, so the static 1/n_shards rescale is
        # unchanged: the solver pre-normalizes weights to sum(w) = n_glob and
        # the datafit keeps normalizing by the (local) sample count
        raw = _df_raw(self.base, Xb, y, w)
        return raw * self.corr if self.corr != 1.0 else raw

    def value(self, Xb, y, w=None):
        v = _df_value(self.base, Xb, y, w)
        return _psum_if(v * self.corr if self.corr != 1.0 else v, self.axis)


class SubproblemSolver:
    """Algorithm 2 on a fixed working set: blocks of M cyclic CD epochs, one
    guarded Anderson extrapolation per block, loop until the restricted KKT
    violation drops under eps. Subclasses supply the state representation
    (`prepare`/`refresh`), the epoch update, the objective, and the gradient;
    the block loop itself is shared."""

    def __init__(self, config: EngineConfig):
        self.config = config

    # -- state hooks -------------------------------------------------------
    def prepare(self, ctx, beta0):
        """Initial auxiliary state for beta0 (q = G beta or Xb)."""
        raise NotImplementedError

    def refresh(self, ctx, beta):
        """Recompute auxiliary state from scratch (Anderson candidates)."""
        raise NotImplementedError

    def epoch(self, ctx, beta, aux):
        """One cyclic CD epoch over the working set."""
        raise NotImplementedError

    def objective(self, ctx, beta, aux):
        """Restricted objective (Anderson acceptance test)."""
        raise NotImplementedError

    def gradient(self, ctx, beta, aux):
        """Restricted smooth gradient (KKT stopping test)."""
        raise NotImplementedError

    # -- shared Anderson-CD block loop ------------------------------------
    def solve(self, ctx, beta0, eps, aux0=None, track=False):
        """Returns (beta, aux, n_epochs, kkt, n_accepts). `aux0` lets the
        caller thread outer-loop state (the Xb path shares Xb across outer
        iterations). `track=True` (the telemetry-ring path, DESIGN.md
        §11.1) counts the accepted Anderson extrapolations as an extra
        int32 loop carry; `track=False` keeps the pre-obs loop state
        bit-identical and returns ``n_accepts=None``."""
        cfg = self.config
        M = cfg.M
        if aux0 is None:
            aux0 = self.prepare(ctx, beta0)

        def block(state):
            if track:
                beta, aux, k, _, acc = state
            else:
                beta, aux, k, _ = state
                acc = None
            hist = jnp.zeros((M + 1,) + beta.shape, beta.dtype).at[0].set(beta)

            def ep(e, s):
                beta, aux, hist = s
                beta, aux = self.epoch(ctx, beta, aux)
                return beta, aux, hist.at[e + 1].set(beta)

            beta, aux, hist = jax.lax.fori_loop(0, M, ep, (beta, aux, hist))
            if cfg.accel:
                be = ctx.penalty.prox(anderson_extrapolate(hist), 0.0)
                auxe = self.refresh(ctx, be)
                take = self.objective(ctx, be, auxe) < \
                    self.objective(ctx, beta, aux)
                beta = jnp.where(take, be, beta)
                aux = jnp.where(take, auxe, aux)
                if track:
                    acc = acc + take.astype(jnp.int32)
            grad = self.gradient(ctx, beta, aux)
            kkt = jnp.max(violation_scores(ctx.penalty, beta, grad, ctx.L_ws,
                                           use_fixed_point=cfg.use_fp_score))
            out = (beta, aux, k + 1, kkt)
            return out + (acc,) if track else out

        def cond(state):
            k, kkt = state[2], state[3]
            return (k < cfg.max_blocks) & (kkt > eps)

        init = (beta0, aux0, jnp.zeros((), jnp.int32),
                jnp.asarray(jnp.inf, beta0.dtype))
        if track:
            init = init + (jnp.zeros((), jnp.int32),)
        out = jax.lax.while_loop(cond, block, init)
        beta, aux, k, kkt = out[:4]
        return beta, aux, k * M, kkt, out[4] if track else None


def _scalar_epoch_kernel_ok(penalty, beta) -> bool:
    """The Pallas CD-epoch kernels update scalar coordinates; multitask /
    block-penalty inner solves fall back to the jax epochs (the fused score
    head still runs in Pallas, so block solves keep the single-traversal
    outer step)."""
    from repro.kernels.common import SCALAR_COORD_PENALTIES
    return beta.ndim == 1 and type(penalty) in SCALAR_COORD_PENALTIES


class GramSolver(SubproblemSolver):
    """Quadratic datafits: state q = G beta stays K-sized (VMEM-resident on
    TPU through the Pallas backend; see kernels/cd_epoch.py). On the jax
    backend each epoch is the blocked sweep of core/cd.py, on G, c and L
    laid out once per inner solve, with q's update in the layout that
    `axpy_per_column` selects; `metrics`, when given, records its block
    size per working-set size K under ``engine.cd_sweep_block`` as the
    solve is traced."""

    def __init__(self, config: EngineConfig, metrics=None,
                 axpy_per_column=False):
        super().__init__(config)
        self.metrics = metrics
        self.axpy_per_column = axpy_per_column

    def _kernel_epochs(self, ctx, beta):
        return self.config.backend == "pallas" and _scalar_epoch_kernel_ok(
            ctx.penalty, beta)

    def solve(self, ctx, beta0, eps, aux0=None, track=False):
        if not self._kernel_epochs(ctx, beta0):
            K = ctx.G.shape[0]
            if self.metrics is not None:
                self.metrics.mapping("engine.cd_sweep_block")[K] = \
                    cd_sweep_block(K)
            ctx = dataclasses.replace(
                ctx, sweep=gram_sweep(ctx.G, ctx.c, ctx.L_ws))
        return super().solve(ctx, beta0, eps, aux0=aux0, track=track)

    def prepare(self, ctx, beta0):
        return ctx.G @ beta0

    def refresh(self, ctx, beta):
        return ctx.G @ beta

    def epoch(self, ctx, beta, aux):
        if self._kernel_epochs(ctx, beta):
            from repro.kernels import ops as kops
            from repro.kernels.common import penalty_params
            return kops.cd_epoch_gram(ctx.G, ctx.c, beta, aux, ctx.L_ws,
                                      type(ctx.penalty),
                                      penalty_params(ctx.penalty), epochs=1)
        return gram_sweep_epoch(ctx.sweep, beta, aux, ctx.penalty,
                                self.axpy_per_column)

    def objective(self, ctx, beta, aux):
        return (0.5 * jnp.vdot(beta, aux) - jnp.vdot(ctx.c, beta)
                + ctx.penalty.value(beta))

    def gradient(self, ctx, beta, aux):
        return aux - ctx.c


class XbSolver(SubproblemSolver):
    """General datafits (Algorithm 3 verbatim): state Xb = X_ws beta
    (+ ctx.Xb_base, the constant contribution of nonzero coordinates outside
    the working set — without it, Anderson candidates rebuilt by `refresh`
    silently dropped those coordinates' residual and the solver could accept
    a corrupted state while reporting convergence, e.g. dual SVC at small C
    with bound-pinned coordinates outside ws under use_gram=False)."""

    def _rebuild(self, ctx, beta):
        Xb = _apply_T(ctx.Xt_ws, beta)
        return Xb if ctx.Xb_base is None else ctx.Xb_base + Xb

    def prepare(self, ctx, beta0):
        return self._rebuild(ctx, beta0)

    def refresh(self, ctx, beta):
        return self._rebuild(ctx, beta)

    def epoch(self, ctx, beta, aux):
        if self.config.backend == "pallas" and _scalar_epoch_kernel_ok(
                ctx.penalty, beta):
            from repro.kernels import ops as kops
            from repro.kernels.common import penalty_params
            kind = KERNEL_DATAFIT_KINDS[type(ctx.datafit).__name__]
            return kops.cd_epoch_xb(ctx.Xt_ws, ctx.y, beta, aux, ctx.L_ws,
                                    ctx.offset_ws, type(ctx.penalty),
                                    penalty_params(ctx.penalty), kind,
                                    w=ctx.w, epochs=1)
        return cd_epoch_xb(ctx.Xt_ws, ctx.y, beta, aux, ctx.L_ws,
                           ctx.offset_ws, ctx.datafit, ctx.penalty,
                           axis=ctx.axis, w=ctx.w)

    def objective(self, ctx, beta, aux):
        # ctx.datafit.value is globally reduced already in sharded contexts
        # (_ShardedDatafit psums internally); the K-sized terms are replicated
        return (_df_value(ctx.datafit, aux, ctx.y, ctx.w)
                + _lin(ctx.offset_ws, beta) + ctx.penalty.value(beta))

    def gradient(self, ctx, beta, aux):
        grad = ctx.Xt_ws @ _df_raw(ctx.datafit, aux, ctx.y, ctx.w)
        if ctx.axis is not None:
            grad = jax.lax.psum(grad, ctx.axis)
        return grad + (ctx.offset_ws[:, None] if grad.ndim == 2
                       else ctx.offset_ws)


class SolveEngine:
    """Bucketed, device-resident outer iteration of Algorithm 1.

    One engine owns one jitted fused step (compiled per power-of-two bucket)
    plus one jitted multi-lambda chunk step, and records:
      retraces:    {bucket or ("chunk", bucket, n_lanes): trace count}
      metrics["engine.cd_sweep_block"]: {bucket: coordinates per trip of
                   the jax Gram CD sweep}, for every bucket it was traced at
      metrics["engine.collective_bytes"]: {bucket: bytes one chip sends
                   through the fused step's collectives outside the inner
                   solve's loops (repro.obs.collectives), as last traced;
                   0 on one device}
      n_dispatches: fused-step launches (== outer iterations driven)

    Constructed with `mesh` (a jax Mesh with a data and a model axis) the
    same fused step runs under shard_map on the (samples x features)-sharded
    design — the host loop, bucket schedule, dispatch/sync budget and
    retrace counters are identical from one device to a pod (DESIGN.md §6).
    """

    def __init__(self, config: EngineConfig, mesh=None, data_axis="data",
                 model_axis="model"):
        self.config = config
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        if mesh is not None:
            missing = {data_axis, model_axis} - set(mesh.axis_names)
            if missing:
                raise ValueError(
                    f"mesh axes {sorted(missing)} not in {mesh.axis_names}")
        self.metrics = MetricsRegistry()
        self.retraces: dict = {}
        self.n_dispatches = 0
        self._jstep = jax.jit(f32_matmuls(self._outer_step),
                              static_argnames=("bucket",))
        self._jchunk = jax.jit(f32_matmuls(self._chunk_solve),
                               static_argnames=("bucket",))
        self._jprobe = jax.jit(f32_matmuls(self._probe))

    # legacy counter attributes are live views into the metrics registry
    # (DESIGN.md §11.3): the mutation idioms used everywhere —
    # ``engine.retraces[key] += 1``, ``engine.n_dispatches = 0`` in bench
    # reset loops, ``dict(engine.retraces)`` snapshots — keep working
    # verbatim, and the registry is the single export surface
    @property
    def retraces(self) -> dict:
        """Live {bucket key: compile count} mapping (view into
        ``metrics['engine.retraces']``)."""
        return self.metrics.mapping("engine.retraces")

    @retraces.setter
    def retraces(self, value: dict):
        self.metrics.set_mapping("engine.retraces", dict(value))

    @property
    def n_dispatches(self) -> int:
        """Fused-step launches (view into
        ``metrics['engine.n_dispatches']``)."""
        return self.metrics.counter("engine.n_dispatches")

    @n_dispatches.setter
    def n_dispatches(self, value: int):
        self.metrics.set_counter("engine.n_dispatches", value)

    def _make_inner(self, axpy_per_column=False):
        cfg = self.config
        return GramSolver(cfg, self.metrics, axpy_per_column) if cfg.gram \
            else XbSolver(cfg)

    def _specs(self):
        """(X, y/Xb, beta/L/offset) PartitionSpecs on the engine's mesh."""
        return design_specs(self.data_axis, self.model_axis)

    def _n_data_shards(self):
        return self.mesh.shape[self.data_axis] if self.mesh is not None else 1

    def lane_shardings(self, n_tasks: int = 0):
        """(beta_sharding, xb_sharding) of the chunked driver's per-lane
        state ``betas [S, p(, T)]`` / ``Xbs [S, n(, T)]`` — the placement
        targets when a grid checkpoint restores onto this engine's mesh
        (DESIGN.md §12), or ``(None, None)`` on a dense engine (leaves stay
        wherever ``jnp.asarray`` puts them)."""
        if self.mesh is None:
            return None, None
        from repro.launch.shardings import grid_lane_specs
        bs, xs = grid_lane_specs(self.data_axis, self.model_axis, n_tasks)
        return (NamedSharding(self.mesh, bs), NamedSharding(self.mesh, xs))

    def _live_axes(self):
        """(data_axis | None, model_axis | None): axis names with the size-1
        (unsplit) axes dropped — and both None on a dense (mesh-less) engine.
        Every collective/mask keyed on a None axis is elided statically, so
        ONE traced body serves dense and sharded engines alike: the 1x1 mesh
        lowers to the exact dense program (bit-identical solves) and
        partially-split meshes skip the no-op collectives on the unsplit
        axis."""
        if self.mesh is None:
            return None, None
        da = self.data_axis if self.mesh.shape[self.data_axis] > 1 else None
        ma = self.model_axis if self.mesh.shape[self.model_axis] > 1 else None
        return da, ma

    # ------------------------------------------------------------ traced body
    # One body serves every engine: on a mesh it runs INSIDE shard_map on the
    # local blocks; dense engines call it directly with the global arrays
    # (all collectives/masks statically elided via _live_axes -> None, None).
    # `design` is already the LOCAL block (local_block() stripped any stacked
    # shard axis in the caller).
    def _score_pass(self, design, y, w, beta, Xb, L, offset, datafit,
                    penalty):
        """Shared head of the fused step and the probe.

        Returns (sdf, grad, scores, kkt, gsupp, gcount, obj): grad/scores are
        this shard's feature block with the data-axis reduction done; kkt,
        gcount and obj are replicated scalars. `w` is the optional
        per-sample weight vector (local rows on a mesh, like y/Xb).
        """
        cfg = self.config
        da, ma = self._live_axes()
        sdf = _ShardedDatafit(datafit, self._n_data_shards(), da)
        raw = sdf.raw_grad(Xb, y, w)
        grad = design.score(raw, backend=cfg.backend)
        grad = _psum_if(grad, da) + (offset[:, None] if grad.ndim == 2
                                     else offset)
        scores = violation_scores(penalty, beta, grad, L,
                                  use_fixed_point=cfg.use_fp_score)
        kkt = jnp.max(scores)
        if ma is not None:
            kkt = collectives.pmax(kkt, ma)
        gsupp = penalty.generalized_support(beta)
        gcount = _psum_if(jnp.sum(gsupp, dtype=jnp.int32), ma)
        if ma is None:
            obj = sdf.value(Xb, y, w) + _lin(offset, beta) + \
                penalty.value(beta)
        else:
            obj = sdf.value(Xb, y, w) + \
                collectives.psum(_lin(offset, beta) + penalty.value(beta),
                                 ma)
        return sdf, grad, scores, kkt, gsupp, gcount, obj

    def _step_body(self, design, y, w, beta, Xb, L, offset, datafit, penalty,
                   tol, eps_frac, bucket, obs=None, inner=None):
        """Fused: score -> select -> gather -> inner solve -> scatter.

        Each stage runs under a ``jax.named_scope`` of that name (``score``,
        ``select``, ``gather``, ``inner_solve``, ``scatter``; the ring record
        under ``telemetry``), so every op of the compiled step names its
        stage in its ``op_name`` metadata and a device trace's ops can be
        put down to stages (DESIGN.md §11.2). Scopes change no op.

        On a mesh: local views design [n_loc, width], y/w/Xb [n_loc],
        beta/L/offset [width]; working-set indices are global; the K-sized
        subproblem runs replicated over the whole mesh (Gram form) or keeps
        its rows data-sharded with per-coordinate psums (Xb form).

        Returns (beta', Xb', kkt, obj, gsupp-count of beta', inner epochs,
        support-covered flag). kkt/obj are measured on the *incoming* iterate
        (the convergence test for this outer iteration); when it already
        passes tol the inner solve is skipped via lax.cond, so the converged
        launch is nearly free. The covered flag asserts the selected working
        set retained the whole generalized support (it must, while the
        bucket policy keeps bucket >= |gsupp|).

        ``inner`` is the subproblem solver (``_make_inner()`` when None).

        ``obs`` is an optional telemetry ring (repro.obs.rings, DESIGN.md
        §11.1): when given, the step records this iteration's
        kkt/gap/obj/ws curves in-dispatch and the advanced ring joins the
        return tuple as an 8th element. ``obs=None`` (the default)
        statically elides every ring op — the 7-tuple trace is the
        bit-identical pre-obs program, like ``w=None``.
        """
        cfg = self.config
        da, ma = self._live_axes()
        design = design.local_block()
        width = design.width
        n_glob = design.n_rows * self._n_data_shards()
        if cfg.backend == "pallas" and design.KIND == "dense" \
                and da is None and ma is None:
            # fused head (kernels/fused_ws.py): ONE X traversal yields the
            # scores, the offset-corrected gradient, AND the gathered
            # candidate columns. The host-free merge is select_working_set
            # on the kernel-emitted scores — bit-identical to the two-pass
            # path by construction — plus a candidate-row lookup for X_ws.
            from repro.kernels import ops as kops
            from repro.kernels.common import penalty_params
            fp = (not penalty.HAS_SUBDIFF) if cfg.use_fp_score is None \
                else cfg.use_fp_score
            with jax.named_scope("score"):
                raw = _df_raw(datafit, Xb, y, w)
                gsupp = penalty.generalized_support(beta)
                scores, grad, cand_idx, cand_cols = kops.fused_ws(
                    design.X, raw, beta, L, offset,
                    gsupp.astype(design.X.dtype), type(penalty),
                    penalty_params(penalty), bucket, use_fp=fp)
                kkt = jnp.max(scores)
                gcount0 = jnp.sum(gsupp, dtype=jnp.int32)
                obj = _df_value(datafit, Xb, y, w) + _lin(offset, beta) + \
                    penalty.value(beta)
            with jax.named_scope("select"):
                ws = select_working_set(scores, gsupp, bucket)
            mine, loc = None, ws
            with jax.named_scope("gather"):
                X_ws = candidate_columns(cand_idx, cand_cols, ws, width)
            sdf, ws_aux = None, None
        else:
            with jax.named_scope("score"):
                sdf, grad, scores, kkt, gsupp, gcount0, obj = \
                    self._score_pass(design, y, w, beta, Xb, L, offset,
                                     datafit, penalty)
            with jax.named_scope("select"):
                ws = select_working_set_local(scores, gsupp, bucket, ma)
                mine, loc = shard_ws_mask(ws, width, ma)
            with jax.named_scope("gather"):
                # [n_loc, K] model-replicated ws columns (+ sparse windows)
                X_ws, ws_aux = design.gather_ws(mine, loc, ma)
        with jax.named_scope("gather"):
            L_ws = gather_ws_vec(L, mine, loc, ma)
            offset_ws = gather_ws_vec(offset, mine, loc, ma)
            beta_ws0 = gather_ws_vec(beta, mine, loc, ma)
            grad_ws0 = gather_ws_vec(grad, mine, loc, ma)
            in_ws = gsupp[loc] if mine is None \
                else jnp.where(mine, gsupp[loc], False)
            cov = _psum_if(jnp.sum(in_ws, dtype=jnp.int32), ma) == gcount0
        inner = inner or self._make_inner()
        track = obs is not None           # count Anderson accepts for the ring
        # the pass-through sdf wrapper would break the pallas kernels'
        # datafit-kind lookup; hand the inner solver the bare datafit
        # whenever the samples are unsplit
        ctx_df = datafit if da is None else sdf

        with jax.named_scope("inner_solve"):
            pen_ws = penalty.restricted(ws) \
                if hasattr(penalty, "restricted") else penalty
            eps_in = jnp.maximum(eps_frac * kkt, 0.1 * tol)
            done = kkt <= tol
            if cfg.gram:
                if da is None:
                    # samples unsplit: honor the datafit's own make_gram (c
                    # is discarded — it assumes support ⊆ ws; see
                    # linearization)
                    G, _ = datafit.make_gram(X_ws, y) if w is None \
                        else datafit.make_gram(X_ws, y, w)
                else:
                    # exact distributed Gram: one sharded MXU matmul + psum;
                    # the K x K subproblem and its Anderson-CD run
                    # replicated (weights enter as X_ws^T diag(w) X_ws on
                    # the local rows)
                    Xw = X_ws if w is None else w[:, None] * X_ws
                    G = collectives.psum(X_ws.T @ Xw, da)
                    if sdf.sample_mean:
                        G = G / n_glob
                # linearize at the incoming iterate: grad_ws(b) = G (b - b0)
                # + grad0_ws, exact for quadratic datafits even when nonzero
                # coordinates live outside ws (Box pins coords at C with
                # empty generalized support)
                q0 = G @ beta_ws0
                c = q0 - grad_ws0
                ctx = WorkingSetContext(X_ws.T, y, L_ws, offset_ws, ctx_df,
                                        pen_ws, G=G, c=c)

                def run(_):
                    with collectives.untallied():
                        beta_ws, _, n_ep, _, n_acc = inner.solve(
                            ctx, beta_ws0, eps_in, aux0=q0, track=track)
                    return beta_ws, n_ep, n_acc

                def skip(_):
                    zero = jnp.zeros((), jnp.int32)
                    return beta_ws0, zero, (zero if track else None)

                beta_ws, n_ep, n_acc = jax.lax.cond(done, skip, run, None)
            else:
                # Xb form: rows stay data-sharded; each coordinate update's
                # n-reduction is completed with one psum over the data axis.
                # Xb_base carries the residual of nonzero coordinates
                # OUTSIDE ws so Anderson refresh cannot drop them
                ctx = WorkingSetContext(X_ws.T, y, L_ws, offset_ws, ctx_df,
                                        pen_ws, axis=da, w=w,
                                        Xb_base=Xb - _apply_T(X_ws.T,
                                                              beta_ws0))

                def run(_):
                    # Xb is shared outer-loop state: enter with the
                    # caller's Xb
                    with collectives.untallied():
                        beta_ws, Xb2, n_ep, _, n_acc = inner.solve(
                            ctx, beta_ws0, eps_in, aux0=Xb, track=track)
                    return beta_ws, Xb2, n_ep, n_acc

                def skip(_):
                    zero = jnp.zeros((), jnp.int32)
                    return beta_ws0, Xb, zero, (zero if track else None)

                beta_ws, Xb_new, n_ep, n_acc = jax.lax.cond(done, skip, run,
                                                            None)

        with jax.named_scope("scatter"):
            if cfg.gram:
                # incremental residual: exact even when a nonzero coordinate
                # sits outside ws
                Xb_new = design.update_xb(Xb, X_ws, ws_aux,
                                          beta_ws - beta_ws0, ma)
            beta_new = scatter_ws(beta, mine, loc, beta_ws)
            gcount = _psum_if(
                jnp.sum(penalty.generalized_support(beta_new),
                        dtype=jnp.int32), ma)
        if obs is None:
            return beta_new, Xb_new, kkt, obj, gcount, n_ep, cov
        # in-dispatch telemetry (DESIGN.md §11.1): scalars of THIS iteration
        # — kkt/obj/gap on the incoming iterate, epochs/accepts/occupancy of
        # the inner solve just run — written at the ring cursor. The gap
        # reuses the residual/gradient the score pass already produced, so
        # recording costs a handful of scalar FLOPs, never an extra pass
        with jax.named_scope("telemetry"):
            gap = (quadratic_l1_gap(y, Xb, grad, obj, n_glob, penalty.lam,
                                    da, ma)
                   if gap_supported(datafit, penalty, w)
                   else jnp.full((), jnp.nan, jnp.asarray(obj).dtype))
            ring = obs.record(
                kkt=kkt, obj=obj, gap=gap,
                ws_size=jnp.asarray(bucket, jnp.int32),
                gsupp=jnp.asarray(gcount0, jnp.int32),
                epochs=n_ep, accepts=n_acc,
                occupancy=ws_occupancy(beta_ws))
        return beta_new, Xb_new, kkt, obj, gcount, n_ep, cov, ring

    def _sharded_step(self, design, y, w, beta, Xb, L, offset, datafit,
                      penalty, tol, eps_frac, bucket, obs=None):
        xs = design.in_spec(self.data_axis, self.model_axis)
        _, ys, bs = self._specs()
        # multitask: y/Xb are [n, T], beta is [p, T] — the task dimension is
        # explicitly replicated; L/offset stay 1-D feature vectors and the
        # sample weights w stay a 1-D sample vector (spec = ys); the
        # telemetry ring's leaves are mesh-replicated (ring_spec), and
        # obs=None contributes no leaves at all
        T = y.ndim - 1
        yt, bt = task_spec(ys, T), task_spec(bs, T)

        def body(design, y, w, beta, Xb, L, offset, datafit, penalty, tol,
                 eps_frac, obs):
            return self._step_body(design, y, w, beta, Xb, L, offset,
                                   datafit, penalty, tol, eps_frac, bucket,
                                   obs=obs)

        out_specs = (bt, yt, P(), P(), P(), P(), P())
        if obs is not None:
            out_specs = out_specs + (ring_spec(),)
        return shard_map(
            body, mesh=self.mesh,
            in_specs=(xs, yt, ys, bt, yt, bs, bs, P(), P(), P(), P(),
                      ring_spec()),
            out_specs=out_specs,
            check_vma=False)(design, y, w, beta, Xb, L, offset, datafit,
                             penalty, tol, eps_frac, obs)

    def _outer_step(self, design, y, w, beta, Xb, L, offset, datafit,
                    penalty, tol, eps_frac, *, bucket, obs=None):
        # executes once per (bucket, arg-structure) compilation: the counter
        # is the proof behind "one compile per ws bucket across a path"
        # (sparse designs, multitask, weighted and telemetry-carrying solves
        # get their own key spaces so mixed use of a shared engine stays
        # observable — [p] and [p, T] traces are distinct compilations, as
        # are weighted and ring-carrying ones)
        key = bucket if design.KIND == "dense" else (design.KIND, bucket)
        if beta.ndim == 2:
            key = ("mt", key)
        if w is not None:
            key = ("wtd", key)
        if obs is not None:
            key = ("obs", key)
        self.retraces[key] = self.retraces.get(key, 0) + 1
        sizes = {} if self.mesh is None else self.mesh.shape
        with collectives.tally(sizes) as sent:
            if self.mesh is not None:
                out = self._sharded_step(design, y, w, beta, Xb, L, offset,
                                         datafit, penalty, tol, eps_frac,
                                         bucket, obs=obs)
            else:
                out = self._step_body(design, y, w, beta, Xb, L, offset,
                                      datafit, penalty, tol, eps_frac,
                                      bucket, obs=obs)
        self.metrics.mapping("engine.collective_bytes")[bucket] = sent.bytes
        return out

    def _probe(self, design, y, w, beta, Xb, L, offset, datafit, penalty):
        """Pre-loop probe: kkt/|gsupp|/obj of the initial iterate (sizes the
        first bucket under warm starts). One launch per solve, not per iter."""
        if self.mesh is not None:
            xs = design.in_spec(self.data_axis, self.model_axis)
            _, ys, bs = self._specs()
            T = y.ndim - 1
            yt, bt = task_spec(ys, T), task_spec(bs, T)

            def body(design, y, w, beta, Xb, L, offset, datafit, penalty):
                _, _, _, kkt, _, gcount, obj = self._score_pass(
                    design.local_block(), y, w, beta, Xb, L, offset, datafit,
                    penalty)
                return kkt, gcount, obj

            return shard_map(
                body, mesh=self.mesh,
                in_specs=(xs, yt, ys, bt, yt, bs, bs, P(), P()),
                out_specs=(P(), P(), P()),
                check_vma=False)(design, y, w, beta, Xb, L, offset, datafit,
                                 penalty)
        _, _, _, kkt, _, gcount, obj = self._score_pass(
            design.local_block(), y, w, beta, Xb, L, offset, datafit,
            penalty)
        return kkt, gcount, obj

    # ---------------------------------------------------- multi-lambda chunk
    def _chunk_loop(self, step_fn, p, lams, betas, Xbs, w, L, tol, max_outer,
                    growth, bucket, obs=None):
        """The device-resident chunk outer loop, shared by the dense and the
        sharded drivers. `step_fn(lam, beta, Xb, w, L[, ring])` is one fused
        outer step for one lane; `p` is the GLOBAL feature count
        (bucket-escalation test). `w` may be None (unweighted), [n] (one
        weight vector shared by every lane) or [C, n] (per-lane weights —
        the CV/bootstrap grid, DESIGN.md §9); `L` is the matching [p] shared
        or [C, p] per-lane Lipschitz constants. `obs` is an optional
        per-lane telemetry ring (leaves [C, cap], DESIGN.md §11.1): it
        rides the lane vmap and the while_loop carry, and the final ring
        joins the state tuple as an 8th element; `obs=None` keeps the
        7-tuple pre-obs loop bit-identical."""
        w_ax = 0 if (w is not None and w.ndim == 2) else None
        L_ax = 0 if L.ndim == 2 else None

        def cond(state):
            with jax.named_scope("chunk_control"):
                kkts, gcounts, it = state[2], state[4], state[6]
                unconverged = kkts > tol
                live = (it < max_outer) & jnp.any(unconverged)
                if bucket < p:
                    # hand back to the host for bucket escalation; at
                    # bucket == p the working set already covers every
                    # feature
                    outgrown = jnp.any(unconverged
                                       & (growth * gcounts > bucket))
                    live = live & ~outgrown
                return live

        C = lams.shape[0]
        with jax.named_scope("chunk_control"):
            init = (betas, Xbs, jnp.full((C,), jnp.inf, betas.dtype),
                    jnp.zeros((C,), betas.dtype),
                    jnp.zeros((C,), jnp.int32), jnp.zeros((C,), jnp.int32),
                    jnp.zeros((), jnp.int32))

        if obs is None:
            def lane(lam, beta, Xb, w_l, L_l):
                return step_fn(lam, beta, Xb, w_l, L_l)[:6]  # drop cov flag

            vstep = jax.vmap(lane, in_axes=(0, 0, 0, w_ax, L_ax))

            def body(state):
                betas, Xbs, kkts, objs, gcounts, n_eps, it = state
                betas, Xbs, kkts, objs, gcounts, d_ep = vstep(lams, betas,
                                                              Xbs, w, L)
                with jax.named_scope("chunk_control"):
                    return (betas, Xbs, kkts, objs, gcounts, n_eps + d_ep,
                            it + 1)

            return jax.lax.while_loop(cond, body, init)

        def lane(lam, beta, Xb, w_l, L_l, ring):
            out = step_fn(lam, beta, Xb, w_l, L_l, ring)
            return out[:6] + (out[7],)                   # drop cov flag

        vstep = jax.vmap(lane, in_axes=(0, 0, 0, w_ax, L_ax, 0))

        def body(state):
            betas, Xbs, kkts, objs, gcounts, n_eps, it, rings = state
            betas, Xbs, kkts, objs, gcounts, d_ep, rings = vstep(
                lams, betas, Xbs, w, L, rings)
            with jax.named_scope("chunk_control"):
                return (betas, Xbs, kkts, objs, gcounts, n_eps + d_ep,
                        it + 1, rings)

        return jax.lax.while_loop(cond, body, init + (obs,))

    def _chunk_solve(self, design, y, lams, betas, Xbs, L, offset, datafit,
                     penalty, tol, eps_frac, max_outer, growth, w, *,
                     bucket, obs=None):
        """Device-resident path chunk: vmap the fused step over a chunk of
        lambdas and drive the *outer* loop with lax.while_loop, so the host
        syncs once per chunk instead of once per (lambda, outer iteration).
        All lanes share one bucket; the loop hands control back to the host
        as soon as any unconverged lane's generalized support outgrows
        bucket/growth (Algorithm 1 would grow the working set there), so the
        host can escalate the bucket and resume from the partial state.
        On a mesh the lanes are vmapped INSIDE shard_map (lanes x devices:
        lambda is a penalty leaf, the collectives batch through vmap), so
        the whole sharded sweep is still one program per bucket. Per-lane
        weights [C, n] (with per-lane L [C, p]) turn the lambda sweep into a
        (fold x lambda) grid sweep: the weight and Lipschitz leaves ride the
        same vmap as lambda (DESIGN.md §9)."""
        # sparse designs get their own key space, like _outer_step, so mixed
        # dense/sparse use of a shared engine stays observable
        key = ("chunk", bucket, int(lams.shape[0])) \
            if design.KIND == "dense" \
            else ("chunk", design.KIND, bucket, int(lams.shape[0]))
        if betas.ndim == 3:               # [C, p, T] multitask lanes
            key = ("mt", key)
        if w is not None:
            key = ("wtd", key)
        if obs is not None:
            key = ("obs", key)
        self.retraces[key] = self.retraces.get(key, 0) + 1
        p_glob = design.shape[1]
        # the lanes' Gram sweeps advance q one axpy per column: the faster
        # layout under vmap on a TPU v5e (core/cd.py _blocked_sweep)
        inner = self._make_inner(axpy_per_column=True)

        if self.mesh is None:
            def step(lam, beta, Xb, w_l, L_l, ring=None):
                pen = dataclasses.replace(penalty, lam=lam)
                return self._step_body(design, y, w_l, beta, Xb, L_l, offset,
                                       datafit, pen, tol, eps_frac, bucket,
                                       obs=ring, inner=inner)

            return self._chunk_loop(step, p_glob, lams, betas, Xbs, w, L,
                                    tol, max_outer, growth, bucket, obs=obs)

        xs = design.in_spec(self.data_axis, self.model_axis)
        _, ys, bs = self._specs()
        T = y.ndim - 1
        # [C, p(, T)] lanes x features and [C, n(, T)] lanes x samples, the
        # task dimension (multitask sweeps) explicitly replicated — on the
        # shared y [n, T] too; weights are [n] (shared) or [C, n] lanes over
        # data-sharded samples, L is [p] shared or [C, p] lanes x features
        yt = task_spec(ys, T)
        lane_b = P(None, *task_spec(bs, T))
        lane_x = P(None, *yt)
        w_spec = P() if w is None else (ys if w.ndim == 1 else P(None, *ys))
        L_spec = bs if L.ndim == 1 else P(None, *bs)

        def body(design, y, lams, betas, Xbs, L, offset, datafit, penalty,
                 tol, eps_frac, max_outer, growth, w, obs):
            def step(lam, beta, Xb, w_l, L_l, ring=None):
                pen = dataclasses.replace(penalty, lam=lam)
                return self._step_body(design, y, w_l, beta, Xb, L_l, offset,
                                       datafit, pen, tol, eps_frac, bucket,
                                       obs=ring, inner=inner)

            return self._chunk_loop(step, p_glob, lams, betas, Xbs, w, L,
                                    tol, max_outer, growth, bucket, obs=obs)

        out_specs = (lane_b, lane_x, P(), P(), P(), P(), P())
        if obs is not None:
            out_specs = out_specs + (ring_spec(),)
        return shard_map(
            body, mesh=self.mesh,
            in_specs=(xs, yt, P(), lane_b, lane_x, L_spec, bs, P(), P(),
                      P(), P(), P(), P(), w_spec, ring_spec()),
            out_specs=out_specs,
            check_vma=False)(design, y, lams, betas, Xbs, L, offset, datafit,
                             penalty, tol, eps_frac, max_outer, growth, w,
                             obs)

    # ------------------------------------------------------------- host API
    def step(self, bucket, design, y, beta, Xb, L, offset, datafit, penalty,
             tol, eps_frac, w=None, obs=None):
        """One fused outer iteration. Single device dispatch; the caller does
        the (single) scalar readback. ``w`` is the optional normalized
        per-sample weight vector (DESIGN.md §9). ``obs`` is the optional
        telemetry ring (repro.obs.rings, DESIGN.md §11.1): when given, the
        step additionally records its per-outer scalars into the ring and
        returns it as an 8th output — still one dispatch, and ``obs=None``
        statically elides every telemetry op (same mechanism as
        ``w=None``)."""
        self.n_dispatches += 1
        return self._jstep(design, y, w, beta, Xb, L, offset, datafit,
                           penalty, tol, eps_frac, bucket=bucket, obs=obs)

    def probe(self, design, y, beta, Xb, L, offset, datafit, penalty,
              w=None):
        """One pre-loop launch returning (kkt, |gsupp|, obj) of the
        initial iterate (sizes the first bucket under warm starts)."""
        return self._jprobe(design, y, w, beta, Xb, L, offset, datafit,
                            penalty)

    def chunk(self, bucket, design, y, lams, betas, Xbs, L, offset, datafit,
              penalty, tol, eps_frac, max_outer, growth=2, w=None, obs=None):
        """One device-resident multi-lambda chunk solve. Returns the final
        (betas, Xbs, kkts, objs, gcounts, n_eps, n_outer) state. ``w`` may
        be None, a shared [n] weight vector, or per-lane [C, n] weights
        (with ``L`` then the per-lane [C, p] Lipschitz constants) — the
        grid-driver form (DESIGN.md §9). The Pallas kernels batch cleanly
        under vmap (pallas_call adds a leading grid dimension), so the
        chunked driver runs on every backend. ``obs`` is the optional
        per-lane telemetry ring (``lanes=C``; DESIGN.md §11.1), threaded
        through the lane vmap and returned as an 8th output when given —
        ``obs=None`` statically elides every telemetry op."""
        self.n_dispatches += 1
        return self._jchunk(design, y, lams, betas, Xbs, L, offset, datafit,
                            penalty, tol, eps_frac, max_outer, growth, w,
                            bucket=bucket, obs=obs)

    def validate(self, datafit, penalty, n_tasks, shape=None, design=None,
                 weighted=False):
        """Static feasibility checks, raised eagerly at ``solve()`` entry.

        Every combination the engine cannot run raises here — before any
        trace — with the exact messages documented in DESIGN.md §8.4. The
        supported matrix (datafit x penalty x dense/sparse/mesh/pallas) is
        in README.md. Since the fused-kernel generalization, the Pallas
        backend runs weighted, multitask (block-penalty), and chunked
        solves; the remaining Pallas rejections (mesh, non-ELL sparse,
        sparse on a TPU) share one message text each — PALLAS_MESH_ERROR,
        PALLAS_SPARSE_ELL_ERROR (also the sparse design's defensive check)
        and PALLAS_SPARSE_TPU_ERROR.
        ``weighted=True`` additionally checks the sample-weight leaf is
        runnable (the datafit declares SUPPORTS_WEIGHTS).
        """
        if weighted and not getattr(datafit, "SUPPORTS_WEIGHTS", False):
            raise NotImplementedError(
                f"sample_weight=...: datafit {type(datafit).__name__} "
                f"does not support sample weights (declare "
                f"SUPPORTS_WEIGHTS=True and accept w in "
                f"value/raw_grad/lipschitz/make_gram)")
        if n_tasks:
            from repro.kernels.common import SCALAR_COORD_PENALTIES
            if type(penalty) in SCALAR_COORD_PENALTIES:
                raise NotImplementedError(
                    f"multitask (2-D coefficients) solves need a block "
                    f"penalty (BlockL1/BlockMCP): "
                    f"{type(penalty).__name__} scores coordinates "
                    f"elementwise and cannot rank feature rows; see the "
                    f"supported-path matrix in README.md")
        if design is not None and design.KIND == "csc":
            if self.mesh is not None and \
                    self.mesh.shape[self.data_axis] > 1:
                raise NotImplementedError(
                    f"mesh=...: sparse designs cannot be sample-sharded "
                    f"(CSC rows are global); use a (1, k) mesh with the "
                    f"features on the {self.model_axis} axis")
            if self.config.backend == "pallas" and \
                    jax.default_backend() == "tpu":
                raise NotImplementedError(PALLAS_SPARSE_TPU_ERROR)
            if self.mesh is None and self.config.backend == "pallas" and \
                    not getattr(design, "has_ell", False):
                raise NotImplementedError(PALLAS_SPARSE_ELL_ERROR)
        if self.mesh is not None:
            if shape is not None:
                nd = self.mesh.shape[self.data_axis]
                nm = self.mesh.shape[self.model_axis]
                if shape[0] % nd or shape[1] % nm:
                    raise ValueError(
                        f"mesh=...: design shape {tuple(shape)} must divide "
                        f"the ({self.data_axis}, {self.model_axis}) mesh "
                        f"({nd}, {nm}) evenly; pad the design or pick a "
                        f"dividing mesh")
            if self.config.backend == "pallas":
                raise NotImplementedError(PALLAS_MESH_ERROR)
            if any(getattr(leaf, "ndim", 0) > 0
                   for leaf in jax.tree_util.tree_leaves(penalty)):
                raise NotImplementedError(
                    "mesh=...: per-coordinate penalty hyper-parameters are "
                    "not supported on the sharded engine yet")
            if not hasattr(datafit, "SAMPLE_MEAN"):
                raise NotImplementedError(
                    f"mesh=...: datafit {type(datafit).__name__} must "
                    f"declare SAMPLE_MEAN (True when value/raw_grad "
                    f"normalize by n, False for un-normalized sums) so "
                    f"per-shard quantities can be rescaled to the global n")
        if self.config.backend == "pallas":
            from repro.kernels.common import check_score_kernel_penalty, \
                penalty_params
            # any codec-registered penalty (incl. Block*) runs in the fused
            # score head; the scalar-epoch restriction is a runtime fallback
            # (_scalar_epoch_kernel_ok), not an entry rejection
            check_score_kernel_penalty(type(penalty))
            penalty_params(penalty)       # raises on per-coordinate params
            if not self.config.gram and n_tasks == 0 and \
                    type(datafit).__name__ not in KERNEL_DATAFIT_KINDS:
                raise ValueError(
                    f"backend='pallas' has no Xb kernel for datafit "
                    f"{type(datafit).__name__}")


# ------------------------------------------------- packed-support refit entry
# Device-side bridge between a dense coefficient vector (what solve()
# produces and consumes) and the packed (active-index, value) layout the
# serving bank stores (serve.sparse_server, DESIGN.md §13). Both are traced
# jax ops so a refit round-trips bank -> solve -> bank without the
# coefficients ever visiting the host.

def pack_support(beta, bucket: int):
    """Pack a dense ``[p]`` coefficient vector into ``bucket`` sparse slots.

    Returns ``(idx, val)``: ``idx`` ``[bucket]`` int32 active-coordinate
    indices, ``val`` ``[bucket]`` the matching coefficients — the
    ``bucket`` largest-|beta| coordinates (every nonzero, when the support
    fits, i.e. ``nnz(beta) <= bucket``; callers size the bucket with
    `repro.bucketing.pow2_bucket` so it always does). Padding slots carry
    ``idx=0, val=0``, which is exact under `scatter_packed`'s additive
    scatter. Traced (``lax.top_k``); runs on device.
    """
    p = beta.shape[0]
    k = min(int(bucket), p)
    _, idx = jax.lax.top_k(jnp.abs(beta), k)
    val = beta[idx]
    keep = val != 0
    idx = jnp.where(keep, idx, 0).astype(jnp.int32)
    val = jnp.where(keep, val, 0)
    if k < bucket:
        idx = jnp.pad(idx, (0, bucket - k))
        val = jnp.pad(val, (0, bucket - k))
    return idx, val


def scatter_packed(idx, val, p: int):
    """Dense ``[p]`` coefficient vector from packed ``(idx, val)`` slots.

    Additive scatter, so `pack_support`'s ``idx=0, val=0`` padding
    contributes nothing and the round trip
    ``scatter_packed(*pack_support(beta, b), p) == beta`` is exact whenever
    the support fit the bucket. Traced; the refit path feeds the result
    straight to ``solve(..., beta0=...)`` as a device-resident warm start.
    """
    return jnp.zeros((p,), val.dtype).at[idx].add(val)


_ENGINE_CACHE: dict = {}


def get_engine(config: EngineConfig, mesh=None, data_axis="data",
               model_axis="model") -> SolveEngine:
    """Engines are cached per (static config, mesh, axis names) so
    independent solve() calls in one process share compiled fused steps (a
    fresh SolveEngine(config) gives isolated retrace counters, e.g. for
    tests)."""
    key = (config, mesh, data_axis, model_axis)
    eng = _ENGINE_CACHE.get(key)
    if eng is None:
        eng = _ENGINE_CACHE[key] = SolveEngine(config, mesh=mesh,
                                               data_axis=data_axis,
                                               model_axis=model_axis)
    return eng
