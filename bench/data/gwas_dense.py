"""Simulated GWAS genotypes and quantitative traits, made on the device.

The genotype law of PLINK 1.9 ``--simulate`` without ``tags``/``haps``:
variant j has an allele frequency f_j drawn uniformly from ``maf_bounds``,
and each genotype is Binomial(2, f_j) (Hardy-Weinberg), every variant
independent of the others (no linkage disequilibrium). The design holds the
standardised genotypes of GCTA ``--simulate-qt`` (Yang et al. 2011):
w_ij = (g_ij - 2 f_j) / sqrt(2 f_j (1 - f_j)). Each trait draws its own
`n_causal` variants and their effects u ~ N(0, 1), and
y = sum_causal w_ij u_j + e with e ~ N(0, var(g) (1 / h2 - 1)), var(g) the
sample variance of the genetic values.

Column j comes from its own key, ``fold_in(key, j)``, so every value depends
on the seed and its (row, column) alone: the design is the same on one
device or sharded over a mesh's ``model`` axis, where each chip makes its own
columns in blocks, holding no more than its shard and one block. A trait's
genetic values are made from its causal columns, made again from their keys,
so they never read the sharded design either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .seeds import device_key, host_rng

BLOCK = 1024                     # columns a chip makes at a time


def _law(key, j, lo, hi):
    """(t0, t1, mean, scale) of column j: genotype thresholds (1 - f)^2 and
    1 - f^2 on a uniform draw, and the standardisation."""
    f = lo + (hi - lo) * jax.random.uniform(jax.random.fold_in(key, j), (),
                                            jnp.float32)
    return jnp.stack([(1.0 - f) ** 2, 1.0 - f * f, 2.0 * f,
                      jax.lax.rsqrt(2.0 * f * (1.0 - f))])


@functools.partial(jax.jit, static_argnames=("p", "bounds"))
def _laws(key, *, p, bounds):
    """[4, p]: the law of every column, one program whatever the mesh."""
    return jax.vmap(lambda j: _law(key, j, *bounds), out_axes=1)(
        jnp.arange(p))


def _column(key, j, law, n):
    """Standardised genotypes [n] of column j under its `law` [4]."""
    u = jax.random.uniform(jax.random.fold_in(jax.random.fold_in(key, j), 1),
                           (n,), jnp.float32)
    g = (u >= law[0]).astype(jnp.float32) + (u >= law[1]).astype(jnp.float32)
    return (g - law[2]) * law[3]


def _block_fill(key, laws, offset, n):
    """[n, width] columns offset .. offset + width - 1, BLOCK at a time (the
    last block moved back to end at the edge: it makes some columns again,
    to the same values). The blocks fill the transpose, whose rows lie as a
    v5e lays out a large shard's columns, so no copy follows the loop."""
    width = laws.shape[1]
    B = min(BLOCK, width)

    def block(b, Xt):
        start = jnp.minimum(b * B, width - B)
        cols = jax.vmap(lambda j, law: _column(key, offset + start + j, law,
                                               n), in_axes=(0, 1))(
            jnp.arange(B),
            jax.lax.dynamic_slice_in_dim(laws, start, B, axis=1))
        return jax.lax.dynamic_update_slice_in_dim(Xt, cols, start, axis=0)

    return jax.lax.fori_loop(0, -(-width // B), block,
                             jnp.zeros((width, n), jnp.float32)).T


def design(key, laws, n: int, mesh=None):
    """X [n, p] float32 on one device, or sharded P(None, "model") over
    `mesh`, each chip making its own columns."""
    if mesh is None:
        return jax.jit(_block_fill, static_argnums=3)(key, laws, 0, n)

    def local(key, laws):
        width = laws.shape[1]
        return _block_fill(key, laws, jax.lax.axis_index("model") * width, n)

    return jax.jit(shard_map(local, mesh=mesh,
                             in_specs=(P(), P(None, "model")),
                             out_specs=P(None, "model"), check_vma=False))(
        key, laws)


@functools.partial(jax.jit, static_argnames=("n", "h2"))
def _traits(key, noise, causal, effects, laws_c, *, n, h2):
    """Y [T, n] from each trait's causal columns [T, c], effects [T, c] and
    the columns' laws [T, 4, c]."""
    def trait(t, cols, u, law):
        W = jax.vmap(lambda j, lw: _column(key, j, lw, n), in_axes=(0, 1))(
            cols, law)                                            # [c, n]
        g = jnp.dot(u, W, precision=jax.lax.Precision.HIGHEST)
        e = jax.random.normal(jax.random.fold_in(noise, t), (n,),
                              jnp.float32)
        return g + e * jnp.sqrt(jnp.var(g) * (1.0 / h2 - 1.0))

    return jax.lax.map(lambda a: trait(*a), (jnp.arange(causal.shape[0]),
                                             causal, effects, laws_c))


def laws(cfg: dict, seed: int):
    """[4, p] float32: each column's genotype thresholds (1 - f)^2 and
    1 - f^2 on a uniform draw, mean 2 f and scale 1 / sqrt(2 f (1 - f))."""
    return _laws(device_key(seed, "genotypes"), p=int(cfg["p"]),
                 bounds=tuple(float(b) for b in cfg["maf_bounds"]))


def truth(cfg: dict, seed: int, n_problems: int):
    """Each trait's causal variants and their effects, [n_problems, c]."""
    rng = host_rng(seed, "traits")
    c = int(cfg["n_causal"])
    causal = np.stack([rng.choice(int(cfg["p"]), c, replace=False)
                       for _ in range(n_problems)]).astype(np.int32)
    return causal, rng.standard_normal((n_problems, c)).astype(np.float32)


def problems(cfg: dict, seed: int, n_problems: int, mesh=None):
    """(X [n, p], Y [n_problems, n]) from `seed`: X on one device, or
    sharded over `mesh` with Y replicated on it."""
    n = int(cfg["n"])
    key = device_key(seed, "genotypes")
    law = laws(cfg, seed)
    causal, effects = truth(cfg, seed, n_problems)
    Y = _traits(key, device_key(seed, "noise"), jnp.asarray(causal),
                jnp.asarray(effects), jnp.moveaxis(law[:, causal], 0, 1),
                n=n, h2=float(cfg["h2"]))
    if mesh is not None:
        law = jax.device_put(law, NamedSharding(mesh, P(None, "model")))
        Y = jax.device_put(Y, NamedSharding(mesh, P()))
    return design(key, law, n, mesh), Y


def generate(cfg: dict, seed: int, n_problems: int):
    """`n_problems` references to one design X, sharded over the mesh
    ``cfg["mesh"]`` of ``repro.launch.mesh.make_solver_mesh``, and the
    traits Y [n_problems, n] replicated on it."""
    from repro.launch.mesh import make_solver_mesh
    mesh = make_solver_mesh(tuple(cfg["mesh"]), tuple(cfg["mesh_axes"]))
    X, Y = problems(cfg, seed, n_problems, mesh)
    return [X] * n_problems, Y
