"""The engine's count of what the fused step's collectives send
(``repro.obs.collectives``, ``engine.collective_bytes``,
``solve.collective_bytes``): the ring byte counts of each collective, and a
hand count from the traced shapes on a (1, 4) and a (2, 2) mesh (four host
devices, in a subprocess so tier-1 runs it), 0 on one device."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.obs import collectives


def _traced_bytes(fn, x, axis_sizes):
    with collectives.tally(axis_sizes) as t:
        jax.make_jaxpr(fn, axis_env=list(axis_sizes.items()))(x)
    return t.bytes


@pytest.mark.parametrize("k", [1, 2, 4])
def test_ring_bytes_per_collective(k):
    x = jnp.zeros((10, 3), jnp.float32)                    # 120 bytes
    sizes = {"m": k}
    assert _traced_bytes(lambda v: collectives.psum(v, "m"), x, sizes) \
        == 2 * (k - 1) * 120 // k
    assert _traced_bytes(lambda v: collectives.pmax(v, "m"), x, sizes) \
        == 2 * (k - 1) * 120 // k
    assert _traced_bytes(lambda v: collectives.all_gather(v, "m"), x,
                         sizes) == (k - 1) * 120


def test_untallied_and_closed_count_nothing():
    x = jnp.zeros((8,), jnp.float32)

    def f(v):
        with collectives.untallied():
            return collectives.psum(v, "m")

    assert _traced_bytes(f, x, {"m": 4}) == 0
    jax.make_jaxpr(lambda v: collectives.psum(v, "m"),
                   axis_env=[("m", 4)])(x)             # no tally open: fine


_SCRIPT = textwrap.dedent("""
    import os, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    jax.config.update("jax_enable_x64", True)
    from repro.core import L1, Quadratic, lambda_max, make_engine, solve
    from repro.data.synth import make_correlated_design
    from repro.launch.mesh import make_solver_mesh
    X, y, _ = make_correlated_design(n=64, p=256, n_nonzero=12, seed=1)
    X, y = jnp.asarray(X), jnp.asarray(y)
    lam = float(lambda_max(X, y)) / 10
    out = {}
    for shape in ([1, 4], [2, 2], None):
        mesh = None if shape is None else make_solver_mesh(tuple(shape))
        eng = make_engine(L1(lam), Quadratic(), mesh=mesh)
        r = solve(X, y, Quadratic(), L1(lam), tol=1e-10, engine=eng,
                  max_outer=50)
        out[str(shape)] = {
            "per_bucket": {str(b): v for b, v in eng.metrics.mapping(
                "engine.collective_bytes").items()},
            "ws_history": r.ws_history, "converged": bool(r.converged),
            "solve": r.diagnostics.registry.counter(
                "solve.collective_bytes")}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def counted():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=600, cwd=root,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _hand_count(n, p, K, dm, mm, f=8, i=4):
    """Bytes one chip sends in one fused Lasso step on a (dm, mm) mesh, from
    the shapes: f-byte floats, i-byte ints, ring all-reduce and all-gather."""
    ar = lambda S, k: 2 * (k - 1) * S // k
    ag = lambda S, k: (k - 1) * S
    width, n_loc = p // mm, n // dm
    loc_k = min(K, width)
    score = (ar(width * f, dm)      # gradient over the data axis
             + ar(f, mm)            # kkt: pmax over the model axis
             + ar(i, mm)            # |gsupp|
             + ar(f, dm)            # datafit value over the data axis
             + ar(f, mm))           # linear and penalty terms
    select = ag(loc_k * f, mm) + ag(loc_k * i, mm)    # candidates
    gather = (ar(K * n_loc * f, mm)           # the working-set columns
              + 4 * ar(K * f, mm)             # L, offset, beta, grad
              + ar(i, mm))                    # support-covered count
    inner = ar(K * K * f, dm)                 # the Gram over the data axis
    scatter = ar(i, mm)                       # |gsupp| of the new beta
    return score + select + gather + inner + scatter


@pytest.mark.parametrize("shape", [[1, 4], [2, 2]], ids=["1x4", "2x2"])
def test_engine_counts_each_bucket_by_hand(counted, shape):
    got = counted[str(shape)]
    assert got["converged"] and len(got["per_bucket"]) >= 2
    for bucket, sent in got["per_bucket"].items():
        assert sent == _hand_count(64, 256, int(bucket), *shape), bucket


@pytest.mark.parametrize("shape", [[1, 4], [2, 2], None],
                         ids=["1x4", "2x2", "one_device"])
def test_solve_sums_its_outers(counted, shape):
    got = counted[str(shape)]
    per = {int(b): v for b, v in got["per_bucket"].items()}
    assert got["solve"] == sum(per[b] for b in got["ws_history"])
    if shape is None:
        assert set(per.values()) == {0} and got["solve"] == 0
    else:
        assert got["solve"] > 0
