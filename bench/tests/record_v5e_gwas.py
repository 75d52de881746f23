"""Records ``data/tiny_gwas_v5e.xplane.pb.gz``: a profile of one warm
Lasso solve on a small GWAS design (``bench/data/gwas_dense.py`` at
n=2,048, p=8,192, 100 causal variants, float32) sharded over a (1, 4) mesh
of four chips, between ``setup`` and ``check`` annotations as the harness
writes them, and beside it ``tiny_gwas_v5e.json``, the counters the
``sharded_solve`` driver would give for that solve, for the tests of the
``.gwas`` metric readers on a real four-chip trace.

    python3 -m bench.tests.record_v5e_gwas <out.xplane.pb.gz>

from the checkout's root, on a machine with four TPU chips.
"""
from __future__ import annotations

import glob
import gzip
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CFG = {"n": 2048, "p": 8192, "maf_bounds": [0.01, 0.5], "n_causal": 100,
       "h2": 0.5}


def main(out: str) -> int:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    import repro.core as rc
    from bench.data import gwas_dense
    from repro.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_solver_mesh
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < 4:
        print("record_v5e_gwas: needs four TPU chips", file=sys.stderr)
        return 3
    enable_compile_cache()
    mesh = make_solver_mesh((1, 4))
    X, Y = gwas_dense.problems(CFG, 0, 1, mesh)
    lmax = np.abs(np.asarray(X, np.float64).T
                  @ np.asarray(Y, np.float64).T).max(axis=0) / CFG["n"]

    def solve(k):
        res = rc.solve(X, Y[k], rc.Quadratic(), rc.L1(0.2 * lmax[k]),
                       tol=1e-4 * lmax[k], mesh=mesh)
        jax.block_until_ready(res.beta)
        return res

    solve(0)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with TraceAnnotation("setup"):
        time.sleep(0.001)
    with TraceAnnotation("solve"):
        results = [solve(0)]
    with TraceAnnotation("check"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    counters = {
        "n_solves": len(results),
        "n_outer": sum(r.n_outer for r in results),
        "n_host_syncs": sum(r.n_host_syncs for r in results),
        "n_chips": len(X.sharding.device_set),
        "x_bytes": math.prod(X.sharding.shard_shape(X.shape))
        * X.dtype.itemsize,
        "collective_bytes": sum(r.diagnostics.registry.counter(
            "solve.collective_bytes") for r in results)}
    Path(out.replace(".xplane.pb.gz", ".json")).write_text(
        json.dumps(counters, indent=2) + "\n")
    print(f"wrote {out} ({os.path.getsize(out)} bytes): {counters}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
