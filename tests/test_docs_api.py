"""Public-surface documentation tests (the CI `docs` job, also tier-1).

  * every symbol exported from the public modules carries a real docstring
    (dataclass auto-signatures don't count);
  * the documented classes' public protocol methods are documented too;
  * intra-repo markdown links in README.md / DESIGN.md resolve;
  * the combinations the engine rejects raise at solve()/reg_path() entry —
    before any fused-step dispatch — with the unified messages documented
    in DESIGN.md §8.4 (one text shared by engine.validate and the sparse
    design's defensive check).
"""
import dataclasses
import importlib
import inspect
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PUBLIC_MODULES = ["repro.core", "repro.sparse", "repro.core.engine",
                  "repro.core.solver", "repro.core.path",
                  "repro.core.estimators", "repro.core.penalties",
                  "repro.core.datafits", "repro.core.api",
                  "repro.bucketing", "repro.serve",
                  "repro.serve.sparse_server"]

# classes whose public methods form a documented protocol surface
PROTOCOL_CLASSES = ["repro.core.engine.Design",
                    "repro.core.engine.SolveEngine",
                    "repro.core.engine.SubproblemSolver",
                    "repro.serve.sparse_server.SparseModelServer",
                    "repro.serve.sparse_server.CoefficientBank"]


def _has_real_doc(obj, name):
    doc = (getattr(obj, "__doc__", None) or "").strip()
    if not doc:
        return False
    if isinstance(obj, type) and dataclasses.is_dataclass(obj) \
            and doc.startswith(name + "("):
        return False                      # dataclass auto-signature
    return True


def test_every_exported_symbol_has_a_docstring():
    missing = []
    for modname in PUBLIC_MODULES:
        mod = importlib.import_module(modname)
        assert mod.__doc__ and mod.__doc__.strip(), f"{modname} module doc"
        for name in getattr(mod, "__all__", []):
            if not _has_real_doc(getattr(mod, name), name):
                missing.append(f"{modname}.{name}")
    assert not missing, f"undocumented exports: {missing}"


def test_protocol_methods_have_docstrings():
    missing = []
    for path in PROTOCOL_CLASSES:
        modname, clsname = path.rsplit(".", 1)
        cls = getattr(importlib.import_module(modname), clsname)
        for name, member in vars(cls).items():
            if name.startswith("_") or not callable(member):
                continue
            if isinstance(member, (staticmethod, classmethod)):
                member = member.__func__
            if not inspect.isfunction(member):
                continue
            if not (member.__doc__ or "").strip():
                missing.append(f"{path}.{name}")
    assert not missing, f"undocumented protocol methods: {missing}"


# --------------------------------------------------------------- doc links
LINK_RE = re.compile(r"\[[^\]]+\]\(([^)\s]+)\)")


@pytest.mark.parametrize("doc", ["README.md", "DESIGN.md"])
def test_intra_repo_links_resolve(doc):
    path = os.path.join(ROOT, doc)
    assert os.path.exists(path), f"{doc} missing"
    text = open(path).read()
    broken = []
    for target in LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        rel = target.split("#", 1)[0]
        if not rel:
            continue                      # same-file anchor
        if not os.path.exists(os.path.join(ROOT, rel)):
            broken.append(target)
    assert not broken, f"{doc}: broken intra-repo links {broken}"


# -------------------------------------------- entry errors, unified wording
def test_remaining_rejections_raise_at_entry():
    """The DESIGN.md §8.4 rejections raise from validate at solve() entry:
    zero fused-step dispatches happen before the error. Since the
    fused-kernel generalization the Pallas backend runs weighted, multitask
    (block-penalty) and chunked solves; only two Pallas rejections remain —
    mesh and non-ELL sparse — each sharing one message text with the
    sparse design's defensive check."""
    import jax
    import scipy.sparse as sp
    from jax.sharding import Mesh
    from repro.core import (L1, MultitaskQuadratic, Quadratic, make_engine,
                            solve)
    from repro.core.engine import PALLAS_MESH_ERROR, PALLAS_SPARSE_ELL_ERROR

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((20, 32)))
    Y = jnp.asarray(rng.standard_normal((20, 3)))
    y = jnp.asarray(rng.standard_normal(20))

    # multitask + elementwise penalty: rejected on EVERY backend (scores
    # cannot rank feature rows) — entry error, not a mid-trace shape crash
    for kernels in (False, True):
        eng = make_engine(L1(0.1), MultitaskQuadratic(),
                          use_kernels=kernels)
        with pytest.raises(NotImplementedError, match="block penalty"):
            solve(X, Y, MultitaskQuadratic(), L1(0.1), engine=eng)
        assert eng.n_dispatches == 0, "rejection happened mid-solve"

    # mesh + pallas: the unified PALLAS_MESH_ERROR text
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    eng = make_engine(L1(0.1), Quadratic(), use_kernels=True, mesh=mesh)
    with pytest.raises(NotImplementedError) as em:
        solve(X, y, Quadratic(), L1(0.1), engine=eng)
    assert str(em.value) == PALLAS_MESH_ERROR
    assert eng.n_dispatches == 0

    # sparse + pallas without the ELL layout: solve() entry and the
    # design's defensive score() check raise the IDENTICAL message
    from repro.sparse import CSCDesign
    Xs_no_ell = sp.random(20, 32, density=0.2, random_state=0, format="csc")
    eng = make_engine(L1(0.1), Quadratic(), use_kernels=True)
    with pytest.raises(NotImplementedError, match="ell=True") as ei:
        solve(Xs_no_ell, y, Quadratic(), L1(0.1), engine=eng)
    assert str(ei.value) == PALLAS_SPARSE_ELL_ERROR
    assert eng.n_dispatches == 0
    D_no_ell = CSCDesign.from_scipy(Xs_no_ell)
    with pytest.raises(NotImplementedError, match="ell=True") as es:
        D_no_ell.score(y, backend="pallas")
    assert str(ei.value) == str(es.value), (
        "engine.validate and CSCDesign.score word the non-ELL rejection "
        "differently")
    # both messages point at the supported-path matrix
    assert "supported-path matrix" in PALLAS_MESH_ERROR
    assert "supported-path matrix" in PALLAS_SPARSE_ELL_ERROR


def test_sparse_pallas_rejected_at_entry_on_tpu(monkeypatch):
    """On a TPU the ELL score kernel does not compile: a sparse solve on the
    Pallas backend raises PALLAS_SPARSE_TPU_ERROR at entry (ELL layout or
    not) instead of falling back to the jax path in silence."""
    import jax
    import scipy.sparse as sp
    from repro.core import L1, Quadratic, make_engine, solve
    from repro.core.engine import PALLAS_SPARSE_TPU_ERROR
    from repro.sparse import CSCDesign

    Xs = sp.random(20, 32, density=0.2, random_state=0, format="csc")
    y = jnp.asarray(np.random.default_rng(0).standard_normal(20))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for X in (Xs, CSCDesign.from_scipy(Xs, ell=True)):
        eng = make_engine(L1(0.1), Quadratic(), use_kernels=True)
        with pytest.raises(NotImplementedError) as ei:
            solve(X, y, Quadratic(), L1(0.1), engine=eng)
        assert str(ei.value) == PALLAS_SPARSE_TPU_ERROR
        assert eng.n_dispatches == 0
    assert "supported-path matrix" in PALLAS_SPARSE_TPU_ERROR


def test_reg_path_rejects_at_entry_both_drivers():
    """Both path drivers raise the same entry error (the chunked driver
    never reaches solve())."""
    from repro.core import L1, Quadratic, reg_path
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((20, 32)))
    y = jnp.asarray(rng.standard_normal(20))
    msgs = []
    for chunk in (1, 2):
        with pytest.raises(Exception) as ei:
            reg_path(X, y, L1(jnp.full(32, 0.1)), Quadratic(), n_lambdas=2,
                     vmap_chunk=chunk, use_kernels=True)
        msgs.append(str(ei.value))
    assert msgs[0] == msgs[1]
