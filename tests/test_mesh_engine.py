"""Mesh-native engine tests (DESIGN.md §6).

Covers the PR-2 acceptance criteria:
  * mesh solves match the single-device engine — 1x1: bit-identical for
    every datafit (the engine statically elides collectives on unsplit axes,
    so the 1x1 program IS the dense program); 2x4: <= 1e-10 on quadratic
    datafits at tight tol.
  * exactly 1 fused dispatch + 1 blocking host sync per outer iteration of a
    sharded solve (same budget as the single-device engine).
  * <= 1 compile per working-set bucket across a sharded 20-lambda
    warm-started path, sequential and chunked (vmap lanes x shard_map).
  * a Logistic datafit converges through the sharded Xb path (previously
    NotImplementedError in the seed distributed loop).
  * multitask (block-coordinate) solves run through the same fused step:
    1x1 bit-identical to dense, 2x4 parity at 1e-8, 1-dispatch/1-sync
    budget (DESIGN.md §8).
  * the remaining unsupported sharded configs (per-coordinate penalty
    params, pallas backend) raise NotImplementedError at solve() entry,
    not mid-trace.
  * the distributed top-k retains generalized support concentrated on one
    shard (min(k, shard_width) local candidates + engine coverage flag).

1x1-mesh tests run in-process on any device count. The multi-device suite
runs on 8 host devices: in-process when the session already has them (the CI
`distributed` job sets XLA_FLAGS=--xla_force_host_platform_device_count=8)
and via one subprocess smoke otherwise, so plain tier-1 runs still exercise
the real 2x4 acceptance path.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from repro.core import (MCP, L1, BlockL1, BlockMCP, Box, Logistic,
                        MultitaskQuadratic, Quadratic, QuadraticSVC,
                        lambda_max, make_engine, reg_path, solve)
from repro.core.distributed import solve_distributed
from repro.core.engine import EngineConfig, get_engine
from repro.core.estimators import Lasso
from repro.data.synth import (make_classification, make_correlated_design,
                              make_multitask)
from repro.launch.mesh import make_solver_mesh, make_test_mesh

requires8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh11():
    return make_solver_mesh((1, 1))


@pytest.fixture(scope="module")
def quad_data():
    X, y, bt = make_correlated_design(n=160, p=384, n_nonzero=16, seed=0)
    return jnp.asarray(X), jnp.asarray(y), bt


# ------------------------------------------------------------ 1x1 bit parity
def _cases_1x1():
    Xq, yq, _ = make_correlated_design(n=120, p=256, n_nonzero=12, seed=1)
    Xc, yc, _ = make_classification(n=100, p=80, n_nonzero=8, seed=1)
    Xq, yq = jnp.asarray(Xq), jnp.asarray(yq)
    Xc, yc = jnp.asarray(Xc), jnp.asarray(yc)
    Z = (yc[:, None] * Xc).T
    lam_q = lambda_max(Xq, yq) / 8
    lam_l = lambda_max(Xc, yc, Logistic()) / 3
    return {
        "lasso": (Xq, yq, Quadratic(), L1(lam_q)),
        "mcp": (Xq, yq, Quadratic(), MCP(2 * lam_q, 3.0)),
        "logistic": (Xc, yc, Logistic(), L1(lam_l)),
        "svc": (Z, yc, QuadraticSVC(), Box(0.05)),
    }


@pytest.mark.parametrize("case", ["lasso", "mcp", "logistic", "svc"])
def test_mesh_1x1_bit_identical_to_dense(mesh11, case):
    """The 1x1 mesh lowers to the exact dense program: identical bits, not
    just identical to tolerance."""
    X, y, datafit, penalty = _cases_1x1()[case]
    ref = solve(X, y, datafit, penalty, tol=1e-8)
    res = solve(X, y, datafit, penalty, tol=1e-8, mesh=mesh11)
    assert res.converged == ref.converged
    assert np.array_equal(np.asarray(res.beta), np.asarray(ref.beta))
    assert res.n_outer == ref.n_outer


def test_mesh_xb_form_svc_matches_gram(mesh11):
    """The sharded Xb inner solver (per-coordinate data psums) also serves
    quadratic datafits when forced (use_gram=False) — including dual SVC
    with bound-pinned coordinates outside ws, the Anderson-refresh
    regression case."""
    Xc, yc, _ = make_classification(n=150, p=60, n_nonzero=8, seed=1)
    Xc, yc = jnp.asarray(Xc), jnp.asarray(yc)
    Z = (yc[:, None] * Xc).T
    df, pen = QuadraticSVC(), Box(0.02)
    res_x = solve(Z, yc, df, pen, tol=1e-7, p0=16, max_outer=300,
                  use_gram=False, mesh=mesh11)
    res_g = solve(Z, yc, df, pen, tol=1e-7, p0=16, max_outer=300)
    assert res_x.converged
    np.testing.assert_allclose(np.asarray(res_x.beta),
                               np.asarray(res_g.beta), atol=1e-6)


def test_mesh_1x1_sync_and_dispatch_budget(mesh11, quad_data):
    X, y, _ = quad_data
    lam = lambda_max(X, y) / 10
    eng = make_engine(L1(lam), Quadratic(), mesh=mesh11)
    res = solve(X, y, Quadratic(), L1(lam), tol=1e-9, engine=eng)
    assert res.converged
    assert res.n_host_syncs == len(res.kkt_history)
    assert eng.n_dispatches == len(res.kkt_history)


def test_mesh_path_one_compile_per_bucket(mesh11, quad_data):
    X, y, _ = quad_data
    eng = make_engine(L1(1.0), Quadratic(), mesh=mesh11)
    path = reg_path(X, y, L1(1.0), n_lambdas=12, lambda_min_ratio=1e-2,
                    tol=1e-8, engine=eng)
    assert np.all(path.kkts <= 1e-8)
    assert path.retraces and all(v == 1 for v in path.retraces.values())


def test_mesh_chunked_path_matches_sequential(mesh11, quad_data):
    X, y, _ = quad_data
    seq = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-9, engine=make_engine(L1(1.0), Quadratic()))
    eng = make_engine(L1(1.0), Quadratic(), mesh=mesh11)
    chk = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-9, engine=eng, vmap_chunk=4)
    assert np.all(chk.kkts <= 1e-9)
    np.testing.assert_allclose(chk.betas, seq.betas, atol=1e-6)
    assert any(isinstance(k, tuple) and k[0] == "chunk"
               for k in eng.retraces), "chunk step never compiled"


def test_facade_solve_distributed(mesh11, quad_data):
    """core.distributed is a facade over solve(mesh=...): same results,
    including Xb-form datafits the seed loop rejected."""
    X, y, _ = quad_data
    lam = lambda_max(X, y) / 10
    res = solve_distributed(mesh11, X, y, Quadratic(), L1(lam), tol=1e-8)
    ref = solve(X, y, Quadratic(), L1(lam), tol=1e-8)
    assert res.converged
    assert np.array_equal(np.asarray(res.beta), np.asarray(ref.beta))
    Xc, yc, _ = make_classification(n=100, p=80, n_nonzero=8, seed=1)
    Xc, yc = jnp.asarray(Xc), jnp.asarray(yc)
    laml = lambda_max(Xc, yc, Logistic()) / 3
    rl = solve_distributed(mesh11, Xc, yc, Logistic(), L1(laml), tol=1e-7)
    assert rl.converged


def test_estimator_mesh_kwarg(mesh11, quad_data):
    X, y, _ = quad_data
    lam = lambda_max(X, y) / 10
    est_m = Lasso(lam, tol=1e-8, mesh=mesh11).fit(X, y)
    est_d = Lasso(lam, tol=1e-8).fit(X, y)
    np.testing.assert_array_equal(est_m.coef_, est_d.coef_)


# ----------------------------------------------- multitask (block) solves
@pytest.fixture(scope="module")
def mt_data():
    # n, p divide every 8-device (data, model) split (8x1 / 2x4 / 1x8)
    X, Y, W = make_multitask(n=64, p=128, n_tasks=4, n_nonzero=8, seed=0)
    return jnp.asarray(X), jnp.asarray(Y), W


def test_mesh_1x1_multitask_bit_identical(mesh11, mt_data):
    """Block coordinates through the fused mesh step: the 1x1 mesh is the
    exact dense multitask program (DESIGN.md §8)."""
    X, Y, _ = mt_data
    lam = lambda_max(X, Y, MultitaskQuadratic()) / 10
    for pen in (BlockL1(lam), BlockMCP(lam, 3.0)):
        ref = solve(X, Y, MultitaskQuadratic(), pen, tol=1e-10)
        res = solve(X, Y, MultitaskQuadratic(), pen, tol=1e-10, mesh=mesh11)
        assert res.converged == ref.converged
        assert np.array_equal(np.asarray(res.beta), np.asarray(ref.beta))


def test_mesh_1x1_multitask_budget(mesh11, mt_data):
    """Multitask keeps the engine contract: 1 fused dispatch + 1 blocking
    host sync per outer iteration."""
    X, Y, _ = mt_data
    lam = lambda_max(X, Y, MultitaskQuadratic()) / 10
    eng = make_engine(BlockL1(lam), MultitaskQuadratic(), mesh=mesh11)
    res = solve(X, Y, MultitaskQuadratic(), BlockL1(lam), tol=1e-9,
                engine=eng)
    assert res.converged
    assert eng.n_dispatches == len(res.kkt_history) == res.n_host_syncs


def test_mesh_multitask_chunked_path_matches_sequential(mesh11, mt_data):
    """Multitask reg_path sweeps compose with the chunked vmap driver on a
    mesh (lanes x devices), matching the sequential dense path."""
    X, Y, _ = mt_data
    seq = reg_path(X, Y, BlockL1(1.0), MultitaskQuadratic(), n_lambdas=6,
                   lambda_min_ratio=0.05, tol=1e-9,
                   engine=make_engine(BlockL1(1.0), MultitaskQuadratic()))
    eng = make_engine(BlockL1(1.0), MultitaskQuadratic(), mesh=mesh11)
    chk = reg_path(X, Y, BlockL1(1.0), MultitaskQuadratic(), n_lambdas=6,
                   lambda_min_ratio=0.05, tol=1e-9, engine=eng, vmap_chunk=3)
    assert np.all(chk.kkts <= 1e-9)
    np.testing.assert_allclose(chk.betas, seq.betas, atol=1e-6)


# --------------------------------------------------- validate() entry errors
def test_mesh_rejects_unsupported_configs_at_entry(mesh11):
    Xq = jnp.asarray(np.random.default_rng(0).standard_normal((40, 64)))
    yq = jnp.asarray(np.random.default_rng(1).standard_normal(40))
    with pytest.raises(NotImplementedError, match="[Pp]allas"):
        solve(Xq, yq, Quadratic(), L1(0.1), mesh=mesh11, use_kernels=True)
    with pytest.raises(NotImplementedError, match="per-coordinate"):
        solve(Xq, yq, Quadratic(), L1(jnp.full(64, 0.1)), mesh=mesh11)


def test_mesh_engine_mismatch_raises(mesh11, quad_data):
    X, y, _ = quad_data
    eng = make_engine(L1(0.1), Quadratic())        # dense engine
    with pytest.raises(ValueError, match="different mesh"):
        solve(X, y, Quadratic(), L1(0.1), mesh=mesh11, engine=eng,
              max_outer=1)
    # reg_path must not silently drop mesh= either (it only builds an engine
    # when none is passed)
    with pytest.raises(ValueError, match="different mesh"):
        reg_path(X, y, L1(1.0), n_lambdas=2, mesh=mesh11, engine=eng)


def test_reg_path_validates_at_entry(mesh11):
    """Unsupported mesh configs raise the designed entry errors from BOTH
    path drivers (the chunked one never reaches solve())."""
    Xq = jnp.asarray(np.random.default_rng(0).standard_normal((40, 64)))
    yq = jnp.asarray(np.random.default_rng(1).standard_normal(40))
    for chunk in (1, 2):
        with pytest.raises(NotImplementedError, match="per-coordinate"):
            reg_path(Xq, yq, L1(jnp.full(64, 0.1)), Quadratic(), n_lambdas=2,
                     mesh=mesh11, vmap_chunk=chunk)

    class NoFlag:                       # custom datafit without SAMPLE_MEAN
        HAS_GRAM = True

    with pytest.raises(NotImplementedError, match="SAMPLE_MEAN"):
        solve(Xq, yq, NoFlag(), L1(0.1), mesh=mesh11)


def test_get_engine_cached_per_mesh(mesh11):
    cfg = EngineConfig()
    assert get_engine(cfg) is get_engine(cfg)
    assert get_engine(cfg, mesh=mesh11) is get_engine(cfg, mesh=mesh11)
    assert get_engine(cfg) is not get_engine(cfg, mesh=mesh11)


# ------------------------------------------------------- multi-device suite
MESH_SHAPES = [(2, 4), (1, 8), (8, 1)]


@requires8
@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("pen", ["l1", "mcp"])
def test_sharded_solve_matches_single_device(shape, pen, quad_data):
    """Acceptance: quadratic mesh solves match the dense engine to 1e-10 at
    tight tol, on every (data, model) split of 8 devices."""
    X, y, _ = quad_data
    lam = lambda_max(X, y) / 5
    penalty = L1(lam) if pen == "l1" else MCP(lam, 3.0)
    mesh = make_test_mesh(shape)
    res = solve(X, y, Quadratic(), penalty, tol=1e-12, mesh=mesh,
                max_outer=100)
    ref = solve(X, y, Quadratic(), penalty, tol=1e-12, max_outer=100)
    assert res.converged and ref.converged
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=1e-10)


@requires8
def test_sharded_sync_and_dispatch_budget_2x4(quad_data):
    """Acceptance: exactly 1 fused dispatch and 1 host sync per outer
    iteration on a 2x4 mesh (the seed distributed loop did ~7 of each)."""
    X, y, _ = quad_data
    lam = lambda_max(X, y) / 10
    mesh = make_test_mesh((2, 4))
    eng = make_engine(L1(lam), Quadratic(), mesh=mesh)
    res = solve(X, y, Quadratic(), L1(lam), tol=1e-9, engine=eng)
    assert res.converged
    iters = len(res.kkt_history)
    assert eng.n_dispatches == iters
    assert res.n_host_syncs == iters
    # warm start adds exactly the one probe sync
    eng2 = make_engine(L1(lam), Quadratic(), mesh=mesh)
    warm = solve(X, y, Quadratic(), L1(lam), tol=1e-9, engine=eng2,
                 beta0=res.beta)
    assert warm.n_host_syncs == len(warm.kkt_history) + 1


@requires8
def test_sharded_path_one_compile_per_bucket_2x4(quad_data):
    """Acceptance: <= 1 compile per working-set bucket across a sharded
    20-lambda warm-started path."""
    X, y, _ = quad_data
    mesh = make_test_mesh((2, 4))
    eng = make_engine(L1(1.0), Quadratic(), mesh=mesh)
    path = reg_path(X, y, L1(1.0), n_lambdas=20, lambda_min_ratio=1e-2,
                    tol=1e-7, engine=eng)
    assert np.all(path.kkts <= 1e-7)
    assert path.retraces and all(v == 1 for v in path.retraces.values())
    assert path.n_dispatches == int(np.sum(path.n_outer)) + \
        np.count_nonzero(path.kkts <= 1e-7)


@requires8
def test_sharded_logistic_converges_2x4():
    """Acceptance: Logistic converges through the sharded Xb path."""
    X, y, _ = make_classification(n=128, p=256, n_nonzero=10, seed=0)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam = lambda_max(X, y, Logistic()) / 3
    mesh = make_test_mesh((2, 4))
    res = solve(X, y, Logistic(), L1(lam), tol=1e-7, mesh=mesh)
    ref = solve(X, y, Logistic(), L1(lam), tol=1e-7)
    assert res.converged
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=1e-6)


@requires8
def test_sharded_chunked_path_2x4(quad_data):
    X, y, _ = quad_data
    mesh = make_test_mesh((2, 4))
    seq = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-8, engine=make_engine(L1(1.0), Quadratic()))
    chk = reg_path(X, y, L1(1.0), n_lambdas=8, lambda_min_ratio=0.02,
                   tol=1e-8, engine=make_engine(L1(1.0), Quadratic(),
                                                mesh=mesh), vmap_chunk=4)
    assert np.all(chk.kkts <= 1e-8)
    np.testing.assert_allclose(chk.betas, seq.betas, atol=1e-6)


@requires8
@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_sharded_multitask_matches_single_device(shape, mt_data):
    """Acceptance (DESIGN.md §8): multitask L2,1 on every (data, model)
    split of 8 devices matches the dense engine to 1e-8."""
    X, Y, _ = mt_data
    lam = lambda_max(X, Y, MultitaskQuadratic()) / 10
    mesh = make_test_mesh(shape)
    res = solve(X, Y, MultitaskQuadratic(), BlockL1(lam), tol=1e-10,
                mesh=mesh, max_outer=100)
    ref = solve(X, Y, MultitaskQuadratic(), BlockL1(lam), tol=1e-10,
                max_outer=100)
    assert res.converged and ref.converged
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=1e-8)


@requires8
def test_sharded_multitask_block_mcp_and_xb_form_2x4(mt_data):
    """Block MCP (non-convex) and the Xb-form inner solver both shard."""
    X, Y, _ = mt_data
    lam = lambda_max(X, Y, MultitaskQuadratic()) / 10
    mesh = make_test_mesh((2, 4))
    ref = solve(X, Y, MultitaskQuadratic(), BlockMCP(lam, 3.0), tol=1e-10)
    res = solve(X, Y, MultitaskQuadratic(), BlockMCP(lam, 3.0), tol=1e-10,
                mesh=mesh, max_outer=100)
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=1e-8)
    refx = solve(X, Y, MultitaskQuadratic(), BlockL1(lam), tol=1e-10)
    resx = solve(X, Y, MultitaskQuadratic(), BlockL1(lam), tol=1e-10,
                 mesh=mesh, use_gram=False, max_outer=100)
    np.testing.assert_allclose(np.asarray(resx.beta), np.asarray(refx.beta),
                               atol=1e-8)


@requires8
def test_mesh_rejects_non_dividing_shapes_at_entry():
    mesh = make_test_mesh((1, 8))
    X = jnp.asarray(np.random.default_rng(0).standard_normal((40, 100)))
    y = jnp.asarray(np.random.default_rng(1).standard_normal(40))
    with pytest.raises(ValueError, match="divide"):
        solve(X, y, Quadratic(), L1(0.1), mesh=mesh)   # 100 % 8 != 0


@requires8
def test_topk_retains_concentrated_support_1x8():
    """The sharded selector keeps min(k, shard_width) local candidates, so
    generalized support concentrated on ONE shard survives selection even
    when other shards carry higher scores."""
    from jax.sharding import PartitionSpec as P
    from repro.core.working_set import select_working_set_local
    mesh = make_test_mesh((1, 8))
    p, k = 128, 16
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.uniform(10.0, 20.0, p))   # big everywhere
    gsupp = np.zeros(p, bool)
    gsupp[:12] = True                                  # all on shard 0
    gsupp = jnp.asarray(gsupp)

    def sel(sc, gs):
        return select_working_set_local(sc, gs, k, "model")

    ws = shard_map(sel, mesh=mesh,
                   in_specs=(P("model"), P("model")), out_specs=P(),
                   check_vma=False)(scores, gsupp)
    ws = set(np.asarray(ws).tolist())
    assert set(range(12)) <= ws, f"support dropped: {sorted(ws)}"
    # and without support the selection is the exact global top-k
    ws2 = shard_map(sel, mesh=mesh, in_specs=(P("model"), P("model")),
                    out_specs=P(), check_vma=False)(
        scores, jnp.zeros(p, bool))
    want = set(np.argsort(np.asarray(scores))[-k:].tolist())
    assert set(np.asarray(ws2).tolist()) == want


# ------------------------------------------------- tier-1 subprocess smoke
_SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import BlockL1, L1, Logistic, MultitaskQuadratic, \\
        Quadratic, lambda_max, make_engine, reg_path, solve
    from repro.launch.mesh import make_test_mesh
    from repro.data.synth import (make_classification,
                                  make_correlated_design, make_multitask)

    mesh = make_test_mesh((2, 4))
    X, y, _ = make_correlated_design(n=128, p=512, n_nonzero=16, seed=3)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam = lambda_max(X, y) / 5

    eng = make_engine(L1(lam), Quadratic(), mesh=mesh)
    res = solve(X, y, Quadratic(), L1(lam), tol=1e-12, engine=eng,
                max_outer=100)
    ref = solve(X, y, Quadratic(), L1(lam), tol=1e-12, max_outer=100)
    assert res.converged, res.kkt
    np.testing.assert_allclose(np.asarray(res.beta), np.asarray(ref.beta),
                               atol=1e-10)
    iters = len(res.kkt_history)
    assert eng.n_dispatches == iters == res.n_host_syncs, (
        eng.n_dispatches, iters, res.n_host_syncs)

    eng2 = make_engine(L1(1.0), Quadratic(), mesh=mesh)
    path = reg_path(X, y, L1(1.0), n_lambdas=20, lambda_min_ratio=1e-2,
                    tol=1e-7, engine=eng2)
    assert np.all(path.kkts <= 1e-7)
    assert path.retraces and all(v == 1 for v in path.retraces.values()), \\
        path.retraces

    Xc, yc, _ = make_classification(n=128, p=256, n_nonzero=10, seed=0)
    Xc, yc = jnp.asarray(Xc), jnp.asarray(yc)
    rl = solve(Xc, yc, Logistic(), L1(lambda_max(Xc, yc, Logistic()) / 3),
               tol=1e-7, mesh=mesh)
    assert rl.converged, rl.kkt

    # multitask L2,1 parity on the feature-split (1, 8) mesh (DESIGN.md §8)
    Xm, Ym, _ = make_multitask(n=64, p=128, n_tasks=4, n_nonzero=8, seed=0)
    Xm, Ym = jnp.asarray(Xm), jnp.asarray(Ym)
    lmt = lambda_max(Xm, Ym, MultitaskQuadratic()) / 10
    rmt = solve(Xm, Ym, MultitaskQuadratic(), BlockL1(lmt), tol=1e-10,
                mesh=make_test_mesh((1, 8)), max_outer=100)
    rmd = solve(Xm, Ym, MultitaskQuadratic(), BlockL1(lmt), tol=1e-10,
                max_outer=100)
    assert rmt.converged, rmt.kkt
    np.testing.assert_allclose(np.asarray(rmt.beta), np.asarray(rmd.beta),
                               atol=1e-8)
    print("OK 8-device mesh engine")
""")


def test_mesh_engine_8_devices_subprocess():
    """Real 2x4 multi-device acceptance run (device count must be fixed
    before jax initializes, hence the subprocess)."""
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_TEST],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK 8-device" in r.stdout
