"""A run with the timed path broken underneath reads ``correct`` false.

Each cell is driven at a tiny size on the CPU (the harness's look for a
chip skipped) once as it is, and once with each fault its cells can have
planted in the program: a step that returns its state unchanged, half of
the rows left out with the mean taken over the rest, and an answer altered
where it is produced. (The exchange between chips has no cell yet.)
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as rc
from repro.core.engine import SolveEngine

from . import tiny

CELLS = ["dense_ar1.lasso_solve", "dense_ar1.lasso_cv_grid"]


def _state_unchanged(monkeypatch):
    step, chunk = SolveEngine.step, SolveEngine.chunk

    def frozen_step(self, bucket, design, y, beta, Xb, *a, **k):
        out = step(self, bucket, design, y, beta, Xb, *a, **k)
        return (beta, Xb) + tuple(out[2:])

    def frozen_chunk(self, bucket, design, y, lams, betas, Xbs, *a, **k):
        out = chunk(self, bucket, design, y, lams, betas, Xbs, *a, **k)
        return (betas, Xbs) + tuple(out[2:])

    monkeypatch.setattr(SolveEngine, "step", frozen_step)
    monkeypatch.setattr(SolveEngine, "chunk", frozen_chunk)


def _half_rows(monkeypatch):
    solve, cvp = rc.solve, rc.cross_val_path

    def half_solve(X, y, *a, **k):
        w = np.ones(y.shape[0])
        w[y.shape[0] // 2:] = 0.0
        return solve(X, y, *a, sample_weight=w, **k)

    def half_grid(X, y, *a, fold_weights, **k):
        W = np.array(fold_weights)
        W[:, W.shape[1] // 2:] = 0.0
        return cvp(X, y, *a, fold_weights=W, **k)

    monkeypatch.setattr(rc, "solve", half_solve)
    monkeypatch.setattr(rc, "cross_val_path", half_grid)


def _altered(monkeypatch):
    solve, cvp = rc.solve, rc.cross_val_path

    def bent_solve(*a, **k):
        res = solve(*a, **k)
        j = int(jnp.argmax(jnp.abs(res.beta)))
        res.beta = res.beta.at[j].multiply(1.5)
        return res

    def bent_grid(*a, **k):
        g = cvp(*a, **k)
        j = int(np.argmax(np.abs(g.betas[0, -1])))
        g.betas[0, -1, j] *= 1.5
        return g

    monkeypatch.setattr(rc, "solve", bent_solve)
    monkeypatch.setattr(rc, "cross_val_path", bent_grid)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    r = tiny.run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", [_state_unchanged, _half_rows, _altered],
                         ids=["state_unchanged", "half_rows", "altered"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_reads_incorrect(name, fault, monkeypatch):
    fault(monkeypatch)
    r = tiny.run(name)
    assert not r["correct"], r["checks"]
