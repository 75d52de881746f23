"""Production mesh construction.

Functions (not module-level constants) so importing this module never
touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for CPU distributed tests (8 forced host devices)."""
    return _mesh(shape, axes)


def make_solver_mesh(shape=None, axes=("data", "model")):
    """(data, model) mesh for the mesh-native solve engine (DESIGN.md §6).

    `shape=None` uses every visible device on the model axis — feature
    sharding is what splits the O(np) score pass and the top-k, the solver's
    dominant costs. Pass an explicit (n_data, n_model) to shard samples too
    (huge-n designs)."""
    if shape is None:
        shape = (1, len(jax.devices()))
    return _mesh(shape, axes)


# TPU v5e-like hardware model used by the roofline analysis (DESIGN.md §5)
HW = {
    "peak_bf16_flops": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link direction
    "chips_per_pod": 256,
    "hbm_bytes": 16e9,
}
