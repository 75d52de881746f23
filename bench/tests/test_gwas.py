"""The GWAS deployment (``gwas_dense_4chip``) on four host devices, each
check in a subprocess that sets the device count before JAX starts: the
sharded generator against the one-device one bit for bit, its genotype and
trait law against a plain numpy statement of it, a (1, 4) sharded solve
against the one-device solve and the float64 reference, and the cell
through the harness at a tiny size."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
TINY = {"n": 512, "p": 2048, "n_causal": 40}
N_TRAITS = 16
SEED = 2**35 + 11

_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    jax.config.update("jax_enable_x64", False)
    from bench import harness
    from bench.data import gwas_dense
    from repro.launch.mesh import make_solver_mesh
    cfg = dict(harness.find_cell("gwas_dense_4chip.phenotype_solves").config,
               **json.loads(sys.argv[2]))
    seed, T = int(sys.argv[3]), int(sys.argv[4])
    mesh = make_solver_mesh((1, 4))
    X1, Y1 = gwas_dense.problems(cfg, seed, T)
    X4, Y4 = gwas_dense.problems(cfg, seed, T, mesh)
    causal, effects = gwas_dense.truth(cfg, seed, T)

    # the check's reader of a design on the devices, over (1, 4) and (2, 2)
    from jax.sharding import NamedSharding
    from bench.drivers.sharded_solve import _Rows
    from bench.reference.kkt import ROW_BLOCK

    def read_rows(X):
        rows = _Rows(X)
        return np.concatenate([rows[i:i + ROW_BLOCK].copy()
                               for i in range(0, X.shape[0], ROW_BLOCK)])

    Z = np.random.default_rng(seed).standard_normal((2500, 64), np.float32)
    Z22 = jax.device_put(Z, NamedSharding(make_solver_mesh((2, 2)),
                                          jax.P("data", "model")))
    out = dict(X1=np.asarray(X1), Y1=np.asarray(Y1), X4=np.asarray(X4),
               Y4=np.asarray(Y4), laws=np.asarray(gwas_dense.laws(cfg, seed)),
               causal=causal, effects=effects,
               feature_sharded=X4.sharding.spec == jax.P(None, "model"),
               n_devices=len(X4.sharding.device_set),
               X4_rows=read_rows(X4), Z=Z, Z22_rows=read_rows(Z22),
               Z22_shards=len(Z22.addressable_shards))

    # float64 solves of trait 0: (1, 4) mesh against one device
    jax.config.update("jax_enable_x64", True)
    import repro.core as rc
    X64 = jnp.asarray(out["X1"], jnp.float64)
    y = jnp.asarray(out["Y1"][0], jnp.float64)
    lmax = float(np.max(np.abs(out["X1"].astype(np.float64).T
                               @ out["Y1"][0].astype(np.float64)))) / cfg["n"]
    lam, tol = cfg["lambda_frac"] * lmax, 1e-9 * lmax
    one = rc.solve(X64, y, rc.Quadratic(), rc.L1(lam), tol=tol)
    four = rc.solve(X64, y, rc.Quadratic(), rc.L1(lam), tol=tol, mesh=mesh)
    out.update(beta1=np.asarray(one.beta), beta4=np.asarray(four.beta),
               conv=np.asarray([one.converged, four.converged]),
               lam=lam, tol=tol, lmax=lmax,
               sent=four.diagnostics.registry.counter(
                   "solve.collective_bytes"))
    np.savez(sys.argv[1], **out)
""")


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    path = tmp_path_factory.mktemp("gwas") / "made.npz"
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(path), json.dumps(TINY),
         str(SEED), str(N_TRAITS)],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": f"{ROOT}{os.pathsep}{ROOT / 'src'}"})
    assert r.returncode == 0, r.stderr[-3000:]
    return dict(np.load(path))


def test_sharded_generator_equals_one_device(made):
    assert int(made["n_devices"]) == 4 and bool(made["feature_sharded"])
    assert made["X1"].dtype == np.float32
    assert made["X1"].shape == (TINY["n"], TINY["p"])
    assert made["Y1"].shape == (N_TRAITS, TINY["n"])
    np.testing.assert_array_equal(made["X4"].view(np.uint32),
                                  made["X1"].view(np.uint32))
    np.testing.assert_array_equal(made["Y4"].view(np.uint32),
                                  made["Y1"].view(np.uint32))


def _freqs(made):
    return made["laws"][2].astype(np.float64) / 2.0


def test_genotypes_follow_the_law(made):
    """Frequencies within bounds; every value a standardised genotype
    0, 1 or 2; the genotype counts of all columns against Hardy-Weinberg."""
    lo, hi = harness.find_cell(
        "gwas_dense_4chip.phenotype_solves").config["maf_bounds"]
    f = _freqs(made)
    assert np.all((f >= lo) & (f <= hi))
    assert f.min() < lo + 0.05 * (hi - lo) and f.max() > hi - 0.05 * (hi - lo)
    X = made["X1"].astype(np.float64)
    g = X * np.sqrt(2 * f * (1 - f)) + 2 * f
    G = np.rint(g)
    assert np.abs(g - G).max() < 1e-4 and set(np.unique(G)) <= {0, 1, 2}
    n = X.shape[0]
    for value, prob in ((0, (1 - f) ** 2), (1, 2 * f * (1 - f)),
                        (2, f * f)):
        expect = n * prob.sum()
        z = ((G == value).sum() - expect) / np.sqrt(
            n * (prob * (1 - prob)).sum())
        assert abs(z) < 5, (value, z)


def test_columns_are_standardised(made):
    """Per-column mean 0 and variance 1 within 5 standard errors of the
    binomial law: SE(mean) = 1/sqrt(n), SE(var) = sqrt((kurt - 1) / n)
    with kurt = 3 + (1 - 6 f (1 - f)) / (2 f (1 - f))."""
    X = made["X1"].astype(np.float64)
    n = X.shape[0]
    f = _freqs(made)
    pq = f * (1 - f)
    kurt = 3 + (1 - 6 * pq) / (2 * pq)
    assert np.all(np.abs(X.mean(axis=0)) < 5 / np.sqrt(n))
    z = (X.var(axis=0) - 1) / np.sqrt((kurt - 1) / n)
    assert np.all(np.abs(z) < 5), np.abs(z).max()
    assert abs(z.mean()) < 5 / np.sqrt(len(z))


def test_traits_have_their_heritability(made):
    """Each trait's genetic values are its causal columns times its effects,
    and var(g) / var(y) lies within 4 standard errors of h2 (SE =
    sqrt(2 (1 - h2)(1 + h2) / n)); their mean within 4 of its own."""
    h2 = harness.find_cell("gwas_dense_4chip.phenotype_solves").config["h2"]
    X = made["X1"].astype(np.float64)
    n = X.shape[0]
    se = np.sqrt(2 * (1 - h2) * (1 + h2) / n)
    est = []
    for t in range(N_TRAITS):
        g = X[:, made["causal"][t]] @ made["effects"][t].astype(np.float64)
        est.append(g.var() / made["Y1"][t].astype(np.float64).var())
    est = np.asarray(est)
    assert np.all(np.abs(est - h2) < 4 * se), est
    assert abs(est.mean() - h2) < 4 * se / np.sqrt(N_TRAITS), est.mean()


def test_sharded_solve_matches_one_device_and_reference(made):
    from bench.reference import kkt as ref
    assert made["conv"].all()
    b1, b4 = made["beta1"], made["beta4"]
    assert 0 < np.count_nonzero(b1) < b1.size
    np.testing.assert_array_equal(b1 != 0, b4 != 0)
    assert np.abs(b4 - b1).max() <= 1e-6 * float(made["lmax"])
    X = made["X1"].astype(np.float64)
    y = made["Y1"][0].astype(np.float64)[:, None]
    for b in (b1, b4):
        k = ref.kkt(X, y, b[:, None], np.array([float(made["lam"])]))
        assert k[0] <= float(made["tol"]) * (1 + 1e-6), k
    assert int(made["sent"]) > 0


def test_cell_runs_through_the_harness_on_four_devices():
    """The cell, shrunk to n=512, p=2,048 and 4 traits, runs through
    ``bench/run.py``'s harness on four virtual CPU devices, its answers
    checked: ``correct`` true."""
    script = textwrap.dedent(f"""
        import json, os, sys, time
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        from bench import harness
        harness.enable_compile_cache = lambda: "off"
        cell = harness.find_cell("gwas_dense_4chip.phenotype_solves")
        cell.config.update({json.dumps(TINY)})
        cell.mix.update(n_problems=4)
        r = harness.run_cell(cell, {SEED}, 0.3, False, time.perf_counter(),
                             require_tpu=False, log=lambda m: None)
        print(json.dumps(r))
    """)
    p = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": f"{ROOT}{os.pathsep}{ROOT / 'src'}"})
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4 and r["attempted"] % 4 == 0
    assert set(r["metrics"]) == {"solve_s", "solve_p95_s", "setup_s"}
    assert 0 < r["checks"]["kkt_gap"]["value"] < 1e-6


def test_check_reads_a_sharded_design_a_shard_a_transfer(made):
    """``_Rows`` puts each shard's rows of a block in place: a design split
    over columns on (1, 4), and one split over rows and columns on (2, 2)
    whose row split falls inside a block, read as float64 row blocks, hold
    the design's values."""
    for rows, X in ((made["X4_rows"], made["X1"]),
                    (made["Z22_rows"], made["Z"])):
        assert rows.dtype == np.float64 and rows.shape == X.shape
        np.testing.assert_array_equal(rows, X.astype(np.float64))
    assert int(made["Z22_shards"]) == 4


@pytest.mark.parametrize("n", [700, 2500])
def test_check_reads_the_design_in_row_blocks(n):
    """The check's float64 KKT reads a device design through ``_Rows``:
    the same blocks as a numpy design's, in float64, the last one short."""
    import jax.numpy as jnp

    from bench.drivers.sharded_solve import _Rows
    from bench.reference import kkt as ref
    rng = np.random.default_rng(n)
    X = rng.standard_normal((n, 30)).astype(np.float32)
    rows = _Rows(jnp.asarray(X))
    for i in range(0, n, ref.ROW_BLOCK):
        block = rows[i:i + ref.ROW_BLOCK]
        assert block.dtype == np.float64
        np.testing.assert_array_equal(block, X[i:i + ref.ROW_BLOCK])
    Y, B = rng.standard_normal((n, 2)), rng.standard_normal((30, 2)) * 0.1
    np.testing.assert_array_equal(ref.kkt(rows, Y, B, [0.1, 0.2]),
                                  ref.kkt(X, Y, B, [0.1, 0.2]))
