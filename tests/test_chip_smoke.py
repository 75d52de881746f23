"""float32 solves on the CPU, and the chip smoke script's phases at tiny
shapes.

The chip runs the solver in float32 (x64 off). These tests turn x64 off
inside each test (``tests/conftest.py`` turns it on for the process) and
check, with the float64 host recomputation ``chip_smoke.py`` itself uses,
that the jax backend and the Pallas kernels in interpret mode agree and
meet the stated float32 tolerances. The script's phase functions are
imported and called with ``expect="cpu"``; its ``main`` still refuses the
CPU.
"""
import contextlib
import importlib.util
import io
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (L1, MCP, Logistic, Quadratic, lambda_max, solve)
from repro.core.anderson import anderson_extrapolate
from repro.data.synth import make_classification, make_correlated_design

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def f32():
    with jax.enable_x64(False):
        yield


@pytest.mark.parametrize("kind,penalty", [
    ("quadratic", "L1"), ("quadratic", "MCP"), ("logistic", "L1")])
def test_float32_kernels_match_jax_backend(cs, f32, kind, penalty):
    """Interpret-mode kernels and the jax backend, both in float32, stop at
    TOL_FRAC * lambda_max, pass the float64 host KKT check and agree."""
    if kind == "quadratic":
        X, y, _ = make_correlated_design(n=200, p=400, n_nonzero=10, seed=3,
                                         dtype=np.float32)
        datafit, frac = Quadratic(), 20
    else:
        X, y, _ = make_classification(n=200, p=400, n_nonzero=10, seed=3,
                                      dtype=np.float32)
        datafit, frac = Logistic(), 3
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    lmax = lambda_max(Xd, yd, datafit)
    lam = lmax / frac
    pen = L1(lam) if penalty == "L1" else MCP(lam, 3.0)
    gamma = None if penalty == "L1" else 3.0
    betas = []
    for kernels in (False, True):
        res = solve(Xd, yd, datafit, pen, tol=cs.TOL_FRAC * lmax,
                    use_kernels=kernels)
        assert res.beta.dtype == jnp.float32
        assert res.converged
        kkt = cs.host_kkt(X, y, res.beta, kind, lam, gamma)
        assert kkt <= cs.HOST_KKT_FRAC * lmax, (kernels, kkt, lmax)
        betas.append(np.asarray(res.beta))
    scale = np.max(np.abs(betas[0]))
    assert scale > 0
    assert np.max(np.abs(betas[0] - betas[1])) <= cs.BETA_AGREE_FRAC * scale


def test_float32_anderson_is_finite_on_rank_deficient_history(f32):
    """Constant steps make U U^T rank one: the float32 ridge keeps the
    extrapolation finite, and on an exactly geometric sequence it lands on
    the limit to float32 precision."""
    step = jnp.asarray([1.0, -2.0, 0.5], jnp.float32)
    hist = jnp.stack([k * step for k in range(6)])
    out = anderson_extrapolate(hist)
    assert out.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out)))
    limit = jnp.asarray([2.0, 1.0, -4.0], jnp.float32)
    geo = jnp.stack([limit * (1.0 - 0.5 ** k) for k in range(6)])
    np.testing.assert_allclose(anderson_extrapolate(geo), limit, atol=1e-4)


def test_chip_smoke_phases_on_cpu(cs, f32, capsys):
    """Every one-chip phase runs end to end at tiny shapes and passes its
    own checks; each check prints one line."""
    ok, fitted, X = cs.phase_dense(0, n=120, p=256, n_nonzero=6,
                                   expect="cpu")
    assert ok
    good, fit_lg = cs.phase_logistic(0, n=120, p=256, expect="cpu")
    assert good
    fitted.update(fit_lg)
    assert cs.phase_sparse(0, n=400, p=2000, density=5e-3, expect="cpu")
    assert cs.phase_serve(0, fitted, X, n_synthetic=6, expect="cpu")
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 8
    assert all(ln.endswith(" ok") and "float32" in ln for ln in lines)


def test_chip_smoke_phase_refuses_wrong_platform(cs):
    with pytest.raises(RuntimeError, match="expected a tpu device"):
        cs.phase_sparse(0, n=50, p=100, expect="tpu")


def test_chip_smoke_main_refuses_cpu(cs):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cs.main([])
    assert rc != 0
    assert out.getvalue() == ""
    assert "no TPU" in err.getvalue()


def test_chip_smoke_mesh_phase_on_four_cpu_devices(tmp_path):
    """The --mesh phase on four forced host devices: (1,4) and (2,2) mesh
    betas match the single-device betas (a child process, since the device
    count is fixed when JAX starts)."""
    code = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        ok = cs.phase_mesh(0, n=160, p=320, n_nonzero=6, expect="cpu")
        sys.exit(0 if ok else 1)
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count(" ok") == 4


def test_compile_cache_placement(monkeypatch):
    from repro.compile_cache import CHECKOUT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    was_key = jax.config.jax_compilation_cache_include_metadata_in_key
    was_frames = jax.config.jax_traceback_in_locations_limit
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax-cache") == str(CHECKOUT / ".jax-cache")
        assert jax.config.jax_compilation_cache_dir == path
        # op names (stage scopes) are part of what a cached program is;
        # source frames are not
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_traceback_in_locations_limit == 0

        def f(x):
            with jax.named_scope("score"):
                return jnp.sin(x)
        hlo = jax.jit(f).lower(jnp.ones(3)).compile().as_text()
        assert 'op_name="jit(f)/score/sin"' in hlo
        assert "stack_frame_id" not in hlo
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was_key)
        jax.config.update("jax_traceback_in_locations_limit", was_frames)
