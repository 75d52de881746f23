"""The harness: found by name from data files, refuses a host without the
chip, and takes a new configuration, mix and metric as new files alone."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_unknown_device_kind_raises():
    with pytest.raises(harness.HarnessError, match="no peaks"):
        harness.peaks("TPU v99 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_tpu_is_refused():
    with pytest.raises(harness.HarnessError, match="no TPU"):
        harness.device_info(1, require_tpu=True)


def test_entry_point_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_parts_found_by_name(w):
    cell = harness.find_cell(w["name"])
    cfg = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert cfg["file"] == f"bench/configs/{w['config']}.json"
    assert cell.config["name"] == w["config"]
    assert sorted(cell.config["reduced"]) == sorted(cfg["reduced"])
    assert (harness.BENCH / "drivers" / f"{cell.mix['driver']}.py").is_file()
    assert (harness.BENCH / "data"
            / f"{cell.config['generator']}.py").is_file()
    assert (harness.BENCH / "limits" / f"{w['name']}.json").is_file()
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py").read)


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _copy(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    return tmp_path / "bench"


def _run_copy(tmp_path, script, env=()):
    head = (f"import json, sys, time\n"
            f"sys.path[:0] = [{str(tmp_path)!r}, {str(ROOT / 'src')!r}]\n"
            "from bench import harness\n"
            "harness.enable_compile_cache = lambda: 'off'\n")
    p = subprocess.run([sys.executable, "-c", head + script],
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **dict(env)),
                       capture_output=True, text=True, timeout=600,
                       cwd=tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _add_cell(tmp_path, name, config, traffic, chips=1):
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": chips,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("solve_s", "solve_p95_s"):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (tmp_path / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps({"kkt_gap": {"limit": 1e-5}}))


TINY = {"name": "tiny_ar1", "generator": "dense_ar1", "n": 150, "p": 300,
        "rho": 0.5, "n_nonzero": 8, "snr": 5.0, "dtype": "float32",
        "lambda_frac": 0.1, "reduced": []}


def test_new_cell_from_new_files_only(tmp_path):
    b = _copy(tmp_path)
    before = _digest(b)
    (b / "configs" / "tiny_ar1.json").write_text(json.dumps(TINY))
    (b / "mixes" / "few_solves.json").write_text(json.dumps({
        "driver": "solve", "n_problems": 2, "tol_frac": 1e-4,
        "n_check": 4}))
    (b / "metrics" / "solves_counted.solve.py").write_text(
        "def read(run):\n    return float(run.counters['n_solves'])\n")
    _add_cell(tmp_path, "tiny_ar1.few_solves", "tiny_ar1", "few_solves")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "solves_counted.solve", "unit": "solves", "better": "higher",
        "source": "program_counter", "layer": "host driver",
        "moves": "solve_s", "workloads": ["tiny_ar1.few_solves"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run_copy(tmp_path, (
        "cell = harness.find_cell('tiny_ar1.few_solves')\n"
        "r = harness.run_cell(cell, 2**35, 0.3, False, time.perf_counter(),"
        " require_tpu=False, log=lambda m: None)\n"
        "run = harness.Run(cell=cell, counters={'n_solves': 3})\n"
        "m = [harness.load_module(harness.BENCH / 'metrics' / "
        "f\"{x['name']}.py\").read(run) for x in cell.per_layer]\n"
        "print(json.dumps([r, [x['name'] for x in cell.per_layer], m]))\n"))
    r, names, values = out
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"solve_s", "solve_p95_s", "setup_s"}
    assert "solves_counted.solve" in names
    assert values[names.index("solves_counted.solve")] == 3.0
    after = _digest(b)
    assert all(after[k] == v for k, v in before.items())


def test_design_sharded_over_chips_solves_on_its_mesh(tmp_path):
    """A cell whose generator shards the design over several devices runs
    through the solve driver unchanged (four virtual CPU devices)."""
    b = _copy(tmp_path)
    (b / "configs" / "tiny_ar1.json").write_text(json.dumps(
        dict(TINY, generator="sharded_ar1")))
    (b / "data" / "sharded_ar1.py").write_text(
        "import jax\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from . import dense_ar1\n\n\n"
        "def generate(cfg, seed, n_problems):\n"
        "    Xs, Y = dense_ar1.generate(cfg, seed, n_problems)\n"
        "    m = jax.make_mesh((1, 4), ('data', 'model'), axis_types=(\n"
        "        jax.sharding.AxisType.Auto,) * 2)\n"
        "    s = NamedSharding(m, P(None, 'model'))\n"
        "    return [jax.device_put(X, s) for X in Xs], Y\n")
    _add_cell(tmp_path, "tiny_ar1.lasso_solve", "tiny_ar1", "lasso_solve",
              chips=4)
    r = _run_copy(tmp_path, (
        "from bench.drivers import common\n"
        "seen = []\n"
        "mesh_of = common.mesh_of\n"
        "common.mesh_of = lambda X: seen.append(mesh_of(X)) or seen[-1]\n"
        "cell = harness.find_cell('tiny_ar1.lasso_solve')\n"
        "r = harness.run_cell(cell, 7, 0.3, False, time.perf_counter(),"
        " require_tpu=False, log=lambda m: None)\n"
        "r['mesh'] = dict(seen[0].shape)\n"
        "print(json.dumps(r))\n"),
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4 and r["mesh"] == {"data": 1, "model": 4}


def test_sparse_design_from_a_new_generator_solves(tmp_path):
    """A generator that returns scipy CSC designs, added as a new file,
    runs through the solve driver and the float64 check unchanged."""
    b = _copy(tmp_path)
    (b / "configs" / "tiny_csc.json").write_text(json.dumps(
        dict(TINY, name="tiny_csc", generator="csc_tiny")))
    (b / "data" / "csc_tiny.py").write_text(
        "import numpy as np\n"
        "from scipy import sparse\n"
        "from .seeds import host_rng\n\n\n"
        "def generate(cfg, seed, n_problems):\n"
        "    rng = host_rng(seed, 'csc')\n"
        "    n, p = cfg['n'], cfg['p']\n"
        "    Xs, Y = [], []\n"
        "    for _ in range(n_problems):\n"
        "        X = sparse.random(n, p, density=0.05, format='csc',\n"
        "                          random_state=rng, dtype=np.float32,\n"
        "                          data_rvs=rng.standard_normal)\n"
        "        beta = np.zeros(p, np.float32)\n"
        "        beta[rng.choice(p, cfg['n_nonzero'], replace=False)] = 1\n"
        "        s = X @ beta\n"
        "        Xs.append(X)\n"
        "        Y.append(s + rng.standard_normal(n).astype(np.float32)\n"
        "                 * np.linalg.norm(s) / (cfg['snr'] * np.sqrt(n)))\n"
        "    return Xs, np.stack(Y).astype(np.float32)\n")
    _add_cell(tmp_path, "tiny_csc.lasso_solve", "tiny_csc", "lasso_solve")
    r = _run_copy(tmp_path, (
        "cell = harness.find_cell('tiny_csc.lasso_solve')\n"
        "cell.mix['n_problems'] = 2\n"
        "r = harness.run_cell(cell, 2**34 + 9, 0.2, False,"
        " time.perf_counter(), require_tpu=False, log=lambda m: None)\n"
        "print(json.dumps(r))\n"))
    assert r["correct"], r["checks"]
    assert r["attempted"] % 2 == 0 and r["checks"]["kkt_gap"]["value"] > 0
