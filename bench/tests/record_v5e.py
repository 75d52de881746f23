"""Records ``data/tiny_spans_v5e.xplane.pb.gz``: a profile of two warm
Lasso solves and one warm cross-validation grid at n=256, p=512 in
float32, between ``setup`` and ``check`` annotations as the harness writes
them, for the tests of ``bench.span_reduce`` on a real chip's trace.

    python3 -m bench.tests.record_v5e <out.xplane.pb.gz>

from the checkout's root, on a machine with one TPU.
"""
from __future__ import annotations

import glob
import gzip
import os
import sys
import tempfile
import time
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[2] / "src")


def main(out: str) -> int:
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    import repro.core as rc
    from repro.compile_cache import enable_compile_cache
    from repro.data.synth import make_correlated_design
    if jax.devices()[0].platform != "tpu":
        print("record_v5e: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    X, y, _ = make_correlated_design(n=256, p=512, n_nonzero=16, rho=0.5,
                                     snr=5.0, seed=0)
    X, y = jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)
    lmax = float(rc.lambda_max(X, y))

    def solve():
        res = rc.solve(X, y, rc.Quadratic(), rc.L1(lmax / 10),
                       tol=1e-4 * lmax)
        jax.block_until_ready(res.beta)

    def grid():
        rc.cross_val_path(X, y, rc.Quadratic(), rc.L1(1.0), n_lambdas=6,
                          lambda_min_ratio=0.1, cv=3, tol=1e-4 * lmax,
                          vmap_chunk=2, sync_every=2)

    solve()
    grid()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with TraceAnnotation("setup"):
        time.sleep(0.001)
    for _ in range(2):
        with TraceAnnotation("solve"):
            solve()
    with TraceAnnotation("grid"):
        grid()
    with TraceAnnotation("check"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
