"""SparseModelServer: the serving surface for fitted sparse GLMs.

This is the predict-side counterpart of the solve engine (DESIGN.md §13),
built on the same compile-once-per-pow2-bucket idiom as the LM engine in
:mod:`repro.serve.engine` — one fitted model per user cohort, thousands of
cohorts resident at once (the FaSTGLZ model-zoo workload):

  * :class:`CoefficientBank` keeps every admitted model on device in a
    *packed sparse* layout — per-model active-index + value rows padded to a
    power-of-two *support bucket* ``S`` and stacked into per-bucket groups
    ``idx [cap, S] int32`` / ``val [cap, S]`` — so predict gathers only the
    active columns of X instead of densifying an ``[n_models, p]`` matrix.
  * :class:`SparseModelServer` micro-batches predict requests: ``submit``
    enqueues, ``flush`` coalesces everything pending into one dispatch per
    ``(batch_bucket B, support_bucket S)`` key, with ``predict`` /
    ``predict_proba`` / ``decision_function`` fused into ONE jitted step
    (three output heads, one gather of X's active columns). Steps compile
    once per ``(B, S)`` pair — a trace-time counter in the step body proves
    it, exactly like the solve engine's per-bucket retrace counters.
  * ``refit`` re-solves a drifted cohort from the *resident* coefficients:
    the bank row is scattered to a dense warm start on device
    (:func:`repro.core.scatter_packed`), solved through the existing
    engine with the probe skipped (``solve(..., gsupp0=slot.n_active)``),
    re-packed on device (:func:`repro.core.pack_support`), and the bank
    slot swapped atomically — coefficients never visit the host; the only
    readbacks are solve's per-outer scalar tuple plus one nnz scalar.

Telemetry flows through the PR-8 observability layer: counters/histograms
land in a :class:`repro.obs.MetricsRegistry` (``serve.*`` namespace; the
attached ``obs.registry`` when an :class:`repro.obs.Obs` handle is given)
and every flush/dispatch/refit opens tracer spans, so
``python -m repro.obs.report`` renders serving next to solve diagnostics.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.bucketing import pow2_bucket
from repro.core.engine import is_scipy_sparse, pack_support, scatter_packed
from repro.core.solver import solve
from repro.obs import span
from repro.obs.registry import MetricsRegistry

__all__ = ["CoefficientBank", "SparseModelServer", "PredictResult",
           "RefitResult", "BANK_KINDS"]

# output-head families the bank can serve (estimators declare theirs via
# GeneralizedLinearEstimator.export_bank_entry)
BANK_KINDS = ("linear", "logistic", "svc")


@dataclass(frozen=True)
class _Slot:
    """Host-side metadata of one resident model (the device data lives in
    the bucket group). Frozen: a refit builds a NEW slot and swaps it in
    with a single assignment, so concurrent readers see old or new,
    never a mix."""
    bucket: int          # support bucket S (group key)
    row: int             # row within the group's [cap, S] arrays
    n_active: int        # true |support| (<= bucket)
    intercept: float
    kind: str            # "linear" | "logistic" | "svc"


class _Group:
    """One support bucket's packed store: idx [cap, S] int32, val [cap, S]."""

    def __init__(self, S: int, dtype, capacity: int):
        self.S = S
        self.capacity = capacity
        self.idx = jnp.zeros((capacity, S), jnp.int32)
        self.val = jnp.zeros((capacity, S), dtype)
        self.n = 0                       # rows ever allocated
        self.free: list = []             # rows released by cross-bucket refits


class CoefficientBank:
    """Device-resident packed sparse store for fitted coefficient vectors.

    Models are grouped by power-of-two *support bucket* ``S =
    pow2_bucket(nnz, support_minimum)`` (`repro.bucketing`): each group
    holds ``idx [cap, S] int32`` active-coordinate indices and ``val
    [cap, S]`` coefficients as two device arrays, padding slots carrying
    ``idx=0, val=0`` (exact under the additive scatter of
    :func:`repro.core.scatter_packed`). Group capacity grows by pow2
    doubling; growth rebuilds the group arrays, which retraces the predict
    steps touching that bucket — admit the fleet before taking traffic
    (`SparseModelServer` counts any such retrace against the ``(B, S)``
    compile budget, so churn is visible, not silent).

    Memory: a model costs ``S * (4 + itemsize)`` bytes instead of the
    ``p * itemsize`` of a dense ``[n_models, p]`` bank — at p=200k and a
    64-slot support that is ~3 orders of magnitude.
    """

    def __init__(self, p: int, *, dtype=None, support_minimum: int = 8,
                 capacity0: int = 8):
        self.p = int(p)
        self.dtype = jnp.asarray(0.0).dtype if dtype is None else dtype
        self.support_minimum = int(support_minimum)
        self.capacity0 = int(capacity0)
        self._groups: dict = {}          # S -> _Group
        self._slots: dict = {}           # model_id -> _Slot
        self.n_grows = 0

    # ------------------------------------------------------------- queries
    def __len__(self):
        return len(self._slots)

    def __contains__(self, model_id):
        return model_id in self._slots

    @property
    def model_ids(self):
        """All resident model ids (admission order)."""
        return list(self._slots)

    def slot(self, model_id) -> _Slot:
        """Host metadata of a resident model (raises KeyError if absent)."""
        return self._slots[model_id]

    def group(self, S: int) -> _Group:
        """The packed device arrays of support bucket ``S``."""
        return self._groups[S]

    @property
    def nbytes(self) -> int:
        """Device bytes held by the packed store (all bucket groups)."""
        return sum(int(g.idx.nbytes + g.val.nbytes)
                   for g in self._groups.values())

    def support_bucket(self, n_active: int) -> int:
        """The support bucket a model with ``n_active`` nonzeros lands in."""
        return pow2_bucket(max(int(n_active), 1),
                           minimum=self.support_minimum, maximum=self.p)

    # ----------------------------------------------------------- admission
    def _alloc_row(self, S: int):
        grp = self._groups.get(S)
        if grp is None:
            grp = self._groups[S] = _Group(S, self.dtype, self.capacity0)
        if grp.free:
            return grp, grp.free.pop()
        if grp.n == grp.capacity:
            cap2 = grp.capacity * 2
            grp.idx = jnp.pad(grp.idx, ((0, cap2 - grp.capacity), (0, 0)))
            grp.val = jnp.pad(grp.val, ((0, cap2 - grp.capacity), (0, 0)))
            grp.capacity = cap2
            self.n_grows += 1
        row = grp.n
        grp.n += 1
        return grp, row

    def admit(self, model_id, coef, intercept: float = 0.0,
              kind: str = "linear") -> _Slot:
        """Admit a host-side fitted model; returns its slot.

        ``coef`` is the dense ``[p]`` coefficient vector (this is the ONE
        host->device coefficient transfer of the model's lifetime — refits
        stay on device). Re-admitting an id replaces the model atomically.
        """
        if kind not in BANK_KINDS:
            raise ValueError(f"kind must be one of {BANK_KINDS}, got "
                             f"{kind!r}")
        coef = np.asarray(coef)
        if coef.shape != (self.p,):
            raise ValueError(f"coef must be [p]=[{self.p}], got "
                             f"{coef.shape} (multitask blocks are not "
                             f"servable yet)")
        nz = np.flatnonzero(coef)
        S = self.support_bucket(len(nz))
        idx = np.zeros(S, np.int32)
        val = np.zeros(S, coef.dtype)
        idx[:len(nz)] = nz
        val[:len(nz)] = coef[nz]
        return self._place(model_id, S, jnp.asarray(idx),
                           jnp.asarray(val, self.dtype), len(nz),
                           float(intercept), kind)

    def admit_packed(self, model_id, idx, val, n_active: int,
                     intercept: float, kind: str) -> _Slot:
        """Admit device-resident packed ``(idx [S], val [S])`` rows (the
        refit path — no host transfer; ``S`` must be a bucket this bank
        could produce)."""
        S = int(idx.shape[0])
        return self._place(model_id, S, idx, val, int(n_active),
                           float(intercept), kind)

    def _place(self, model_id, S, idx, val, n_active, intercept, kind):
        old = self._slots.get(model_id)
        grp, row = self._alloc_row(S)
        grp.idx = grp.idx.at[row].set(idx)
        grp.val = grp.val.at[row].set(val)
        slot = _Slot(bucket=S, row=row, n_active=n_active,
                     intercept=intercept, kind=kind)
        # the swap: one reference assignment AFTER the device rows are
        # fully built — readers resolve model_id through _slots and can
        # only ever observe the complete old or complete new model
        self._slots[model_id] = slot
        if old is not None:
            self._groups[old.bucket].free.append(old.row)
        return slot

    def beta(self, model_id):
        """Dense ``[p]`` coefficients of a resident model (device array,
        via the additive scatter — no host round trip)."""
        s = self._slots[model_id]
        g = self._groups[s.bucket]
        return scatter_packed(g.idx[s.row], g.val[s.row], self.p)


@dataclass
class PredictResult:
    """One request's outputs, sliced from its micro-batch dispatch.

    All three heads come out of the SAME fused jitted step:
    ``decision`` is ``X @ beta + intercept``; ``predict`` is the
    kind-appropriate head (``decision`` for linear models, its sign for
    logistic/svc); ``proba`` is the sigmoid two-class stack ``[b, 2]``
    for logistic models, None otherwise.
    """
    ticket: int
    model_id: object
    kind: str
    decision: np.ndarray
    predict: np.ndarray
    proba: object = None
    latency_ms: float = 0.0


@dataclass
class RefitResult:
    """Outcome of an on-device warm-start refit (`SparseModelServer.refit`).

    ``result`` is the underlying :class:`repro.core.SolveResult`;
    ``n_active``/``bucket`` describe the re-packed bank row; ``moved`` is
    True when the support outgrew (or shrank out of) its old bucket and
    the model changed groups.
    """
    model_id: object
    result: object
    n_active: int
    bucket: int
    moved: bool


class SparseModelServer:
    """Micro-batching predict server over a :class:`CoefficientBank`.

    ``submit`` enqueues a request (one model id + an ``[b, p]`` block of
    rows — dense or scipy-sparse); ``flush`` coalesces everything pending
    into one fused dispatch per ``(batch_bucket, support_bucket)`` key:
    rows of all requests whose models share a support bucket ``S`` are
    stacked, padded to a pow2 batch bucket ``B``, and pushed through a
    step that gathers only the active columns of each row
    (``take_along_axis``) and emits decision / sign / sigmoid heads
    together. Steps are compiled once per ``(B, S)`` — the trace-time
    counter in ``metrics.mapping("serve.retraces")`` is the proof, same
    contract as the solve engine's bucket retrace counters.

    Telemetry (``serve.*`` in :attr:`metrics`, and tracer spans when an
    ``obs`` handle is attached): request/row/dispatch/refit counters,
    per-key dispatch mapping, batch-occupancy and latency histograms,
    p50/p99 gauges refreshed every flush.
    """

    def __init__(self, p: int, *, dtype=None, batch_minimum: int = 8,
                 support_minimum: int = 8, capacity0: int = 8, obs=None):
        self.p = int(p)
        self.batch_minimum = int(batch_minimum)
        self.obs = obs
        self.metrics = obs.registry if obs is not None else MetricsRegistry()
        self.bank = CoefficientBank(p, dtype=dtype,
                                    support_minimum=support_minimum,
                                    capacity0=capacity0)
        self._steps: dict = {}           # (B, S) -> jitted fused predict
        self._pending: list = []         # (ticket, model_id, rows, t_submit)
        self._ticket = 0

    # ------------------------------------------------------------ admission
    def admit(self, model_id, model, intercept: float = 0.0,
              kind: str = "linear"):
        """Admit a fitted model: an estimator (anything with
        ``export_bank_entry()``), a bank-entry dict, or a raw dense
        ``[p]`` coefficient vector (+ ``intercept``/``kind``)."""
        if hasattr(model, "export_bank_entry"):
            model = model.export_bank_entry()
        if isinstance(model, dict):
            coef, intercept, kind = (model["coef"], model["intercept"],
                                     model["kind"])
        else:
            coef = model
        slot = self.bank.admit(model_id, coef, intercept, kind)
        self.metrics.set_gauge("serve.models", len(self.bank))
        self.metrics.set_gauge("serve.bank_bytes", self.bank.nbytes)
        self.metrics.set_counter("serve.bank_grows", self.bank.n_grows)
        return slot

    # ------------------------------------------------------------- requests
    def submit(self, model_id, X) -> int:
        """Enqueue a predict request for ``model_id`` on rows ``X``
        (``[b, p]`` dense or scipy-sparse, or a single ``[p]`` row);
        returns a ticket matched by the `flush` results. Nothing is
        dispatched until `flush` (or the `predict` convenience wrappers)."""
        if model_id not in self.bank:
            raise KeyError(f"model {model_id!r} is not resident; admit() "
                           f"it first")
        if is_scipy_sparse(X):
            X = np.asarray(X.todense())
        X = np.asarray(X)
        if X.ndim == 1:
            X = X[None, :]
        if X.shape[1] != self.p:
            raise ValueError(f"request rows must be [b, p]=[b, {self.p}], "
                             f"got {X.shape}")
        self._ticket += 1
        self._pending.append((self._ticket, model_id, X,
                              time.perf_counter()))
        self.metrics.inc("serve.requests")
        self.metrics.inc("serve.rows", X.shape[0])
        return self._ticket

    def _step_for(self, B: int, S: int):
        key = (B, S)
        step = self._steps.get(key)
        if step is None:
            retraces = self.metrics.mapping("serve.retraces")
            rkey = f"B{B} S{S}"

            def _fused(Xrows, rowsel, idx_bank, val_bank, icept, valid):
                # trace-time side effect: runs once per compilation of this
                # (B, S) step — the compile-count proof (engine.py idiom)
                retraces[rkey] = retraces.get(rkey, 0) + 1
                mi = idx_bank[rowsel]                       # [B, S]
                mv = val_bank[rowsel]
                xa = jnp.take_along_axis(Xrows, mi, axis=1)  # [B, S]
                z = jnp.sum(xa * mv, axis=-1) + icept
                z = jnp.where(valid, z, 0.0)
                sgn = jnp.sign(z + 1e-30)
                p1 = 1.0 / (1.0 + jnp.exp(-z))
                return z, sgn, jnp.stack([1.0 - p1, p1], axis=-1)

            step = self._steps[key] = jax.jit(_fused)
        return step

    def flush(self):
        """Dispatch everything pending; returns a `PredictResult` per
        request, in submit order. One fused jit call per
        ``(batch_bucket, support_bucket)`` key present in the queue."""
        if not self._pending:
            return []
        pending, self._pending = self._pending, []
        sp = self.obs.span if self.obs is not None else span
        dtype = self.bank.dtype
        keys = self.metrics.mapping("serve.dispatch_keys")
        out = {}
        with sp("serve.flush", n_requests=len(pending)):
            by_S: dict = {}
            for req in pending:
                by_S.setdefault(self.bank.slot(req[1]).bucket,
                                []).append(req)
            for S, reqs in sorted(by_S.items()):
                n = sum(r[2].shape[0] for r in reqs)
                B = pow2_bucket(n, minimum=self.batch_minimum)
                Xp = np.zeros((B, self.p), dtype)
                rowsel = np.zeros(B, np.int32)
                icept = np.zeros(B, dtype)
                valid = np.zeros(B, bool)
                spans, at = [], 0
                for ticket, mid, X, t0 in reqs:
                    b = X.shape[0]
                    slot = self.bank.slot(mid)
                    Xp[at:at + b] = X
                    rowsel[at:at + b] = slot.row
                    icept[at:at + b] = slot.intercept
                    valid[at:at + b] = True
                    spans.append((ticket, mid, slot, t0, at, at + b))
                    at += b
                grp = self.bank.group(S)
                step = self._step_for(B, S)
                with sp("serve.dispatch", B=B, S=S, rows=n):
                    z_d, sgn_d, proba_d = step(
                        jnp.asarray(Xp), jnp.asarray(rowsel), grp.idx,
                        grp.val, jnp.asarray(icept), jnp.asarray(valid))
                    z, sgn, proba = np.asarray(z_d), np.asarray(sgn_d), \
                        np.asarray(proba_d)
                self.metrics.inc("serve.n_dispatches")
                kstr = f"B{B} S{S}"
                keys[kstr] = keys.get(kstr, 0) + 1
                self.metrics.observe("serve.batch_occupancy", n / B)
                t_done = time.perf_counter()
                for ticket, mid, slot, t0, lo, hi in spans:
                    lat = (t_done - t0) * 1e3
                    self.metrics.observe("serve.latency_ms", lat)
                    pred = z[lo:hi] if slot.kind == "linear" else sgn[lo:hi]
                    out[ticket] = PredictResult(
                        ticket=ticket, model_id=mid, kind=slot.kind,
                        decision=z[lo:hi], predict=pred,
                        proba=proba[lo:hi] if slot.kind == "logistic"
                        else None, latency_ms=lat)
        lat_all = self.metrics.histogram("serve.latency_ms")
        self.metrics.set_gauge("serve.p50_ms",
                               float(np.percentile(lat_all, 50)))
        self.metrics.set_gauge("serve.p99_ms",
                               float(np.percentile(lat_all, 99)))
        return [out[t] for t in sorted(out)]

    # ------------------------------------------------- convenience wrappers
    def predict(self, model_id, X):
        """Single-request predict (submit + flush): the kind-appropriate
        head — ``X @ beta + intercept`` for linear models, its sign for
        logistic/svc."""
        t = self.submit(model_id, X)
        return next(r for r in self.flush() if r.ticket == t).predict

    def decision_function(self, model_id, X):
        """Single-request ``X @ beta + intercept`` (same dispatch as
        `predict` — the heads are fused)."""
        t = self.submit(model_id, X)
        return next(r for r in self.flush() if r.ticket == t).decision

    def predict_proba(self, model_id, X):
        """Single-request two-class sigmoid probabilities ``[b, 2]``
        (logistic models only)."""
        if self.bank.slot(model_id).kind != "logistic":
            raise ValueError("predict_proba is only served for "
                             "kind='logistic' models")
        t = self.submit(model_id, X)
        return next(r for r in self.flush() if r.ticket == t).proba

    # ----------------------------------------------------------------- refit
    def refit(self, model_id, X, y, datafit, penalty, **solve_kw):
        """Re-solve a drifted cohort from its RESIDENT coefficients.

        The bank row is scattered to a dense warm start on device
        (`repro.core.scatter_packed`), solved through the existing engine
        with the warm-start probe skipped (the slot's ``n_active`` is the
        ``gsupp0`` hint), re-packed on device (`repro.core.pack_support`),
        and the slot swapped atomically. Coefficients never visit the
        host: the only readbacks are ``solve``'s per-outer scalar tuple
        and one nnz scalar for re-bucketing. ``solve_kw`` is forwarded to
        :func:`repro.core.solve` (tol, engine=, obs=, ...). Returns a
        `RefitResult`.
        """
        slot = self.bank.slot(model_id)
        grp = self.bank.group(slot.bucket)
        sp = self.obs.span if self.obs is not None else span
        with sp("serve.refit", model=str(model_id), bucket=slot.bucket):
            beta0 = scatter_packed(grp.idx[slot.row], grp.val[slot.row],
                                   self.p)
            res = solve(X, y, datafit, penalty, beta0=beta0,
                        gsupp0=slot.n_active, **solve_kw)
            # one scalar readback to size the new support bucket
            nnz = int(jax.device_get(jnp.sum(res.beta != 0)))
            S_new = self.bank.support_bucket(nnz)
            idx2, val2 = pack_support(res.beta, S_new)
            new = self.bank.admit_packed(model_id, idx2, val2, nnz,
                                         slot.intercept, slot.kind)
        self.metrics.inc("serve.refits")
        self.metrics.set_gauge("serve.bank_bytes", self.bank.nbytes)
        self.metrics.set_counter("serve.bank_grows", self.bank.n_grows)
        return RefitResult(model_id=model_id, result=res, n_active=nnz,
                           bucket=S_new, moved=S_new != slot.bucket)
