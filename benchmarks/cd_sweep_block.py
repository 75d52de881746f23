"""Time one Gram CD epoch on the device against the sweep's block size B.

    python -m benchmarks.cd_sweep_block [--out FILE]      # on a TPU
    python -m benchmarks.cd_sweep_block --rehearse        # tiny, any device

For each working-set size K and block size B (B = 1 is the plain sweep of
``repro.kernels.ref``, one coordinate per loop trip) it times a jitted loop
of Lasso CD epochs on a Gram matrix of an n = 1,000 Gaussian design, in
float32, alone and vmapped over 50 lanes that each hold their own Gram (the
CV grid's chunk step). Each line gives the median of 5 timed calls after a
compile, as microseconds per epoch and per coordinate update.
``repro.core.cd.cd_sweep_block`` is chosen from this table.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

KS = (64, 128, 256, 512, 1024, 2048)
BLOCKS = (1, 4, 8, 16, 32)
LANES = 50


def _problem(K, lanes, n, seed=0):
    import jax
    import jax.numpy as jnp
    shape = (n, K) if lanes is None else (lanes, n, K)
    kx, ky = jax.random.split(jax.random.key(seed))
    X = jax.random.normal(kx, shape, jnp.float32)
    y = jax.random.normal(ky, shape[:-1], jnp.float32)
    Xt = jnp.swapaxes(X, -1, -2)
    G = Xt @ X / n
    c = jnp.einsum("...nk,...n->...k", X, y) / n
    L = jnp.diagonal(G, axis1=-2, axis2=-1)
    lam = jnp.max(jnp.abs(c)) / 20
    return G, c, jnp.zeros_like(c), jnp.zeros_like(c), L, lam


def time_epochs(K, B, lanes, n=1000, reps=5):
    """Median seconds of one epoch over `reps` timed calls."""
    import jax

    from repro.core.cd import GramSweep, _blocked_sweep
    from repro.core.penalties import L1
    from repro.kernels.ref import cd_epoch_gram_ref
    G, c, beta, q, L, lam = _problem(K, lanes, n)
    pen = L1(lam)
    epochs = max(4, 32768 // K)

    def run(G, c, beta, q, L):
        sweep = GramSweep(G, c, L)

        def ep(_, s):
            if B == 1:
                return cd_epoch_gram_ref(G, c, s[0], s[1], L, pen)
            return _blocked_sweep(sweep, s[0], s[1], pen, B,
                                  axpy_per_column=lanes is not None)
        return jax.lax.fori_loop(0, epochs, ep, (beta, q))

    f = jax.jit(run if lanes is None else jax.vmap(run))
    jax.block_until_ready(f(G, c, beta, q, L))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(G, c, beta, q, L))
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / epochs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device JAX finds")
    ap.add_argument("--out", help="also write the lines as JSON here")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        sys.exit(f"no TPU here ({dev.platform}); use --rehearse")
    ks, blocks, lanes_of = ((16, 24), (1, 4, 8), 3) if args.rehearse \
        else (KS, BLOCKS, LANES)
    n = 32 if args.rehearse else 1000
    print(f"device {dev.device_kind} x{jax.device_count()}")
    rows = []
    for lanes in (None, lanes_of):
        for K in ks:
            for B in blocks:
                if B > K:
                    continue
                t = time_epochs(K, B, lanes, n=n)
                row = {"K": K, "B": B, "lanes": lanes or 1,
                       "epoch_us": t * 1e6, "update_us": t * 1e6 / K}
                rows.append(row)
                print(f"K={K:5d} B={B:3d} lanes={row['lanes']:3d} "
                      f"epoch_us={row['epoch_us']:.3f} "
                      f"update_us={row['update_us']:.5f}", flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump({"device": dev.device_kind, "rows": rows}, fh,
                      indent=1)


if __name__ == "__main__":
    main()
