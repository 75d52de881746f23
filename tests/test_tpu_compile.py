"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed without a chip: it compiles for a *described*
v5e and raises what the chip's compiler would raise — unaligned blocks, a
kernel over its scoped VMEM, a primitive Mosaic cannot lower — none of
which interpret mode can see. Compiles run in float32 (x64 off, as on the
chip) at the widths ``chip_smoke.py`` drives: the fused working-set head at
n=10,000, p=20,000, ws=256; the Gram CD epoch at K=1024 for every penalty
the kernel codec accepts; the Xb CD epoch at K=256, n=10,000 for the
quadratic and logistic datafits. Each compiled program must hold a Mosaic
kernel (``tpu_custom_call``), i.e. nothing fell back to interpret mode. The
whole fused step of the jax backend compiles at the dense benchmark's
widths, with its blocked Gram CD sweep.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.common import SCALAR_COORD_PENALTIES, penalty_arity


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_f32():
    """Compile in float32 with the persistent cache off: a compile for a
    described chip is written to the cache but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *args, **static):
        with jax.enable_x64(False):
            return fn.lower(*args, **static).compile().as_text()
    try:
        yield run
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def test_fused_head_compiles_for_v5e(one_chip, compile_f32):
    from repro.core.penalties import L1
    n, p, ws = 10_000, 20_000, 256
    s = lambda *shape: _spec(one_chip, *shape)
    text = compile_f32(ops.fused_ws, s(n, p), s(n), s(p), s(p), s(p), s(p),
                       L1, s(penalty_arity(L1)), ws, interpret=False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "penalty_cls", sorted(SCALAR_COORD_PENALTIES, key=lambda c: c.__name__),
    ids=lambda c: c.__name__)
def test_gram_epoch_compiles_for_v5e(one_chip, compile_f32, penalty_cls):
    K = 1024
    s = lambda *shape: _spec(one_chip, *shape)
    text = compile_f32(ops.cd_epoch_gram, s(K, K), s(K), s(K), s(K), s(K),
                       penalty_cls, s(penalty_arity(penalty_cls)),
                       interpret=False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", ["quadratic", "logistic"])
def test_xb_epoch_compiles_for_v5e(one_chip, compile_f32, kind):
    from repro.core.penalties import L1
    K, n = 256, 10_000
    s = lambda *shape: _spec(one_chip, *shape)
    text = compile_f32(ops.cd_epoch_xb, s(K, n), s(n), s(K), s(n), s(K),
                       s(K), L1, s(penalty_arity(L1)), kind,
                       interpret=False)
    assert "tpu_custom_call" in text


def test_outer_step_ops_name_one_stage(one_chip, compile_f32):
    """The fused step at the dense benchmark's size (n=1,000, p=2,000,
    bucket 1,024): every fusion, custom call and dynamic-(update-)slice the
    chip's compiler emits (outside fused computations, whose instructions
    run inside their fusion's event) that carries an ``op_name`` names
    exactly one stage scope of ``SolveEngine._step_body``, so a device
    trace's ops map to stages (``bench.span_reduce``). A few fusions XLA
    builds itself inside the inner loops carry no ``op_name`` at all; they
    stay a small minority."""
    import re

    from bench.span_reduce import stage_scopes
    from repro.core import L1, Quadratic, make_engine
    from repro.core.engine import DenseDesign
    n, p, bucket = 1_000, 2_000, 1_024
    s = lambda *shape: _spec(one_chip, *shape)
    engine = make_engine(L1(0.1), Quadratic())
    text = compile_f32(engine._jstep, DenseDesign(s(n, p)), s(n), None,
                       s(p), s(n), s(p), s(p), Quadratic(), L1(0.1), 1e-4,
                       0.3, bucket=bucket, obs=None)
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", text))
    kinds = re.compile(r"= \S+ (fusion|custom-call|dynamic-slice|"
                       r"dynamic-update-slice)\(")
    comp, named, unnamed = None, [], []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif comp not in fused and kinds.search(line):
            name = re.search(r'op_name="([^"]*)"', line)
            if name is None:
                unnamed.append(line.split("=")[0].strip())
                continue
            scopes = stage_scopes(name.group(1))
            assert len(scopes) == 1, (name.group(1), line.strip()[:200])
            named.append(scopes[0])
    assert {"score", "select", "gather", "inner_solve", "scatter"} \
        <= set(named)
    assert len(unnamed) * 5 < len(named), unnamed


@pytest.mark.parametrize("bucket", [512, 1024])
def test_outer_step_compiles_for_v5e(one_chip, compile_f32, bucket):
    """The whole fused step ``_outer_step`` at the dense benchmark's widths
    (n=1,000, p=2,000, float32) at the buckets its solves reach: it
    compiles for a v5e, records the Gram CD sweep's block size for the
    bucket, and the sweep's loop runs bucket / B trips an epoch."""
    import re

    from repro.core import L1, Quadratic, make_engine
    from repro.core.cd import cd_sweep_block
    from repro.core.engine import DenseDesign
    n, p = 1_000, 2_000
    s = lambda *shape: _spec(one_chip, *shape)
    engine = make_engine(L1(0.1), Quadratic())
    text = compile_f32(engine._jstep, DenseDesign(s(n, p)), s(n), None,
                       s(p), s(n), s(p), s(p), Quadratic(), L1(0.1), 1e-4,
                       0.3, bucket=bucket, obs=None)
    B = cd_sweep_block(bucket)
    assert engine.metrics.mapping("engine.cd_sweep_block") == {bucket: B}
    conds = set(re.findall(r"condition=%([\w.\-]+)", text))
    bounds = set()
    for cond in conds:
        body = re.search(r"\n%" + re.escape(cond) + r" .*?\n\}", text, re.S)
        bounds |= {int(v) for v in
                   re.findall(r"s32\[\]\S* constant\((\d+)\)", body.group(0))}
    assert bucket // B in bounds and bucket not in bounds, sorted(bounds)


@pytest.fixture(scope="module")
def mesh_1x4(topo):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))


def test_sharded_step_fits_a_v5e_chip_at_gwas_size(mesh_1x4, compile_f32):
    """The fused step of the (1, 4) feature-sharded engine at the GWAS
    cell's size (n=50,000, p=120,000: a 6 GB float32 shard a chip, bucket
    1,024) compiles for a v5e 2x2 with temporaries of a few working-set
    blocks, not a second copy of the shard: the column gather copies the K
    columns one by one instead of the one-op gather's row slices of the
    whole shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import L1, Quadratic, make_engine
    from repro.core.engine import DenseDesign
    n, p, bucket = 50_000, 120_000, 1_024

    def s(spec, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh_1x4, spec))

    engine = make_engine(L1(0.1), Quadratic(), mesh=mesh_1x4)
    args = (DenseDesign(s(P("data", "model"), n, p)), s(P("data"), n), None,
            s(P("model"), p), s(P("data"), n), s(P("model"), p),
            s(P("model"), p), Quadratic(), L1(0.1), 1e-4, 0.3)
    with jax.enable_x64(False):
        compiled = engine._jstep.lower(*args, bucket=bucket,
                                       obs=None).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6e9
    assert mem.temp_size_in_bytes < 4 * n * bucket * 4     # < 0.82 GB
    assert "mini-gather" not in compiled.as_text()
    assert engine.metrics.mapping("engine.collective_bytes")[bucket] > 0


def test_gwas_design_is_made_a_block_at_a_time(mesh_1x4, compile_f32):
    """The GWAS generator makes each chip's 6 GB shard holding no more
    than the shard and one block of columns."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.data import gwas_dense
    n, p = 50_000, 120_000
    fill = jax.jit(jax.shard_map(
        lambda key, laws: gwas_dense._block_fill(
            key, laws, jax.lax.axis_index("model") * laws.shape[1], n),
        mesh=mesh_1x4, in_specs=(P(), P(None, "model")),
        out_specs=P(None, "model"), check_vma=False))
    key = jax.eval_shape(lambda: jax.random.key(0))
    args = (jax.ShapeDtypeStruct(key.shape, key.dtype,
                                 sharding=NamedSharding(mesh_1x4, P())),
            jax.ShapeDtypeStruct((4, p), jnp.float32,
                                 sharding=NamedSharding(mesh_1x4,
                                                        P(None, "model"))))
    with jax.enable_x64(False):
        mem = fill.lower(*args).compile().memory_analysis()
    assert mem.output_size_in_bytes == pytest.approx(n * p // 4 * 4, rel=1e-2)
    assert mem.temp_size_in_bytes <= 1.05 * n * gwas_dense.BLOCK * 4
