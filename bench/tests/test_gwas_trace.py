"""The readers of the GWAS cell's per-layer metrics on a four-chip v5e trace
of a sharded solve (``data/tiny_gwas_v5e.xplane.pb.gz`` and the driver's
counters for it, ``data/tiny_gwas_v5e.json``, recorded by
``record_v5e_gwas.py``), and silent where a run has no trace or the program
no collective counter."""
import gzip
import json
from pathlib import Path

import pytest

from bench import span_reduce as sr
from bench import trace_reduce as tr
from bench.harness import BENCH, Run, find_cell, load_module, peaks
from bench.metrics import _collectives

DATA = Path(__file__).parent / "data"
CELL = "gwas_dense_4chip.phenotype_solves"
METRICS = [m["name"] for m in find_cell(CELL).per_layer]
COUNTERS = ("outer_iters_per_solve", "host_syncs_per_outer.solve")


def _read(metric, run):
    return load_module(BENCH / "metrics" / f"{metric}.py").read(run)


@pytest.fixture(scope="module")
def gwas_profile(tmp_path_factory):
    d = tmp_path_factory.mktemp("v5e_gwas")
    path = d / "plugins" / "profile" / "0" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(
        (DATA / "tiny_gwas_v5e.xplane.pb.gz").read_bytes()))
    return d, str(path)


@pytest.fixture(scope="module")
def gwas_run(gwas_profile):
    d, path = gwas_profile
    run = Run(cell=None, trace=tr.reduce_file(path),
              counters=json.loads((DATA / "tiny_gwas_v5e.json").read_text()),
              peaks=peaks("TPU v5 lite"))
    sr.of_run(run, str(d))
    _collectives.of_run(run, str(d))
    return run


def test_cell_reads_the_collectives_and_the_solve_layers():
    assert {"collective_ms_per_step.gwas", "collective_mb_per_outer.gwas",
            "outer_step_ms.solve", "gather_ms.solve", "scatter_ms.solve",
            "readback_idle_ms.solve"} <= set(METRICS)
    assert all(m["moves"] == "solve_s" for m in find_cell(CELL).per_layer)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_read_the_four_chip_trace(gwas_run, metric):
    value = _read(metric, gwas_run)
    assert value is not None and value >= 0.0


def test_collectives_seen_on_four_chips(gwas_run):
    t = gwas_run.trace
    assert t["n_devices"] == 4 and t["modules"]["jit__outer_step"]["count"]
    assert _read("collective_ms_per_step.gwas", gwas_run) > 0
    assert _read("collective_mb_per_outer.gwas", gwas_run) > 0
    assert 0 < _read("x_read_hbm_share.solve", gwas_run) <= 100
    assert _read("outer_step_ms.solve", gwas_run) >= sum(
        _read(f"{s}_ms.solve", gwas_run)
        for s in ("score", "select", "gather", "inner_solve", "scatter"))
    assert _read("outer_step_ms.solve", gwas_run) > _read(
        "collective_ms_per_step.gwas", gwas_run)


def test_collective_time_counts_all_reduces_named_after_psum_or_pmax(
        gwas_profile, gwas_run):
    """XLA names some all-reduces after the primitive (``pmax.7`` here,
    ``psum.59`` at the cell's size), which a match on op names misses; the
    reader takes each op's category from ``hlo_stats`` and counts them."""
    from jax.profiler import ProfileData
    _, path = gwas_profile
    _, _, chips = sr.window_chips(ProfileData.from_file(path))
    cats = _collectives.categories(path)
    named = {k: c for k, c in cats.items()
             if c in _collectives.CATEGORIES and not tr.COLLECTIVE.search(k[1])}
    assert named
    assert {op.split(".")[0] for _, op in named} <= {"psum", "pmax"}
    by_primitive = _collectives.collective_seconds(chips, named)
    every = _collectives.collective_seconds(chips, cats)
    step = "jit__outer_step"
    assert 0 < by_primitive[step] < every[step]
    count = gwas_run.trace["modules"][step]["count"]
    assert _read("collective_ms_per_step.gwas", gwas_run) == pytest.approx(
        1e3 * every[step] / count)


def test_readers_silent_without_trace_or_counter():
    run = Run(cell=None, counters={"n_outer": 9, "n_solves": 2,
                                   "n_host_syncs": 12, "x_bytes": 10})
    for metric in METRICS:
        value = _read(metric, run)
        if metric in COUNTERS:
            assert value is not None, metric
        else:
            assert value is None, metric
    assert _read("outer_iters_per_solve", run) == 4.5
