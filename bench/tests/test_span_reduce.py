"""The program's spans and stages as ``bench.span_reduce`` reads them: a
tiny solve and grid profiled on the CPU (host plane only), the recorded
v5e trace of a program without spans or scopes, and made-up events."""
import gzip
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import span_reduce as sr
from bench import trace_reduce as tr
from bench.harness import BENCH, Run, load_module

TRACE = Path(__file__).parent / "data" / "tiny_solve_v5e.xplane.pb.gz"
NEW_METRICS = [
    "readback_idle_ms.solve", "score_ms.solve", "select_ms.solve",
    "gather_ms.solve", "inner_solve_ms.solve", "scatter_ms.solve",
    "readback_idle_ms.grid", "bookkeeping_idle_ms_per_round.grid",
    "eager_launches_per_round.grid", "score_ms.grid", "select_ms.grid",
    "gather_ms.grid", "inner_solve_ms.grid", "scatter_ms.grid"]


def _problem():
    from repro.data.synth import make_correlated_design
    X, y, _ = make_correlated_design(n=60, p=120, n_nonzero=6, rho=0.5,
                                     snr=5.0, seed=4)
    return jnp.asarray(X, jnp.float32), jnp.asarray(y, jnp.float32)


def _profiled(fn, directory):
    """Run fn() under the profiler; (its result, the host spans)."""
    with jax.profiler.trace(str(directory)):
        out = fn()
    path, = Path(directory).rglob("*.xplane.pb")
    return out, sr.host_spans(ProfileData.from_file(str(path)))


def _count(spans, name):
    return sum(1 for _, _, n in spans if n == sr.PREFIX + name)


def _inside(inner, outer):
    return all(any(s0 <= s and e <= e0 for s0, e0, _ in outer)
               for s, e, _ in inner)


@pytest.mark.parametrize("with_obs", [False, True], ids=["obs_none", "obs"])
def test_solve_spans_match_counters(tmp_path, with_obs):
    import repro.core as rc
    from repro.obs import Obs
    X, y = _problem()
    lam = float(rc.lambda_max(X, y)) / 10
    obs = Obs(rings=False) if with_obs else None
    res, spans = _profiled(
        lambda: rc.solve(X, y, rc.Quadratic(), rc.L1(lam), tol=1e-5,
                         obs=obs), tmp_path)
    assert _count(spans, "solve") == 1
    assert _count(spans, "sync") == res.n_host_syncs
    assert _count(spans, "outer") == _count(spans, "dispatch") \
        == res.n_outer + 1
    by = {n: [sp for sp in spans if sp[2] == sr.PREFIX + n]
          for n in ("solve", "outer", "sync")}
    assert _inside(by["sync"], by["outer"])
    assert _inside(by["outer"], by["solve"])
    if with_obs:                      # the same spans on the tracer
        roll = obs.tracer.summary()
        for name in ("solve", "outer", "dispatch", "sync"):
            assert roll[name]["count"] == _count(spans, name)


@pytest.mark.parametrize("with_obs", [False, True], ids=["obs_none", "obs"])
def test_grid_spans_match_counters(tmp_path, with_obs):
    import repro.core as rc
    from repro.obs import Obs
    X, y = _problem()
    obs = Obs(rings=False) if with_obs else None
    g, spans = _profiled(
        lambda: rc.cross_val_path(X, y, rc.Quadratic(), rc.L1(1.0),
                                  n_lambdas=6, lambda_min_ratio=0.1, cv=3,
                                  tol=1e-5, vmap_chunk=2, sync_every=2,
                                  obs=obs), tmp_path)
    assert g.n_rounds > 1
    assert _count(spans, "grid") == 1
    assert _count(spans, "grid_round") == g.n_rounds
    assert _count(spans, "grid_dispatch") == _count(spans, "grid_sync") \
        == g.n_host_syncs == g.n_rounds
    rounds = [sp for sp in spans if sp[2] == "repro/grid_round"]
    for name in ("grid_lanes", "grid_dispatch", "grid_sync", "grid_retire",
                 "grid_fill", "grid_schedule"):
        inner = [sp for sp in spans if sp[2] == sr.PREFIX + name]
        assert inner and _inside(inner, rounds), name
    assert _inside(rounds, [sp for sp in spans if sp[2] == "repro/grid"])
    if with_obs:
        assert obs.tracer.summary()["grid_round"]["count"] == g.n_rounds


# ------------------------------------------------- the recorded v5e trace
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace as a file (xprof reads files), and the harness's
    reduction of it."""
    d = tmp_path_factory.mktemp("v5e")
    path = d / "plugins" / "profile" / "0" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return d, str(path), tr.reduce_file(str(path))


def _harness_keys(reduced, **over):
    """A copy of what the harness's reduction holds, before any reader."""
    out = {k: reduced[k] for k in ("n_devices", "window_s", "busy_s",
                                   "collective_s", "modules")}
    out["breakdown"] = {k: reduced["breakdown"][k]
                        for k in ("device_ops", "idle_gaps")}
    return dict(out, **over)


def test_existing_reduction_unchanged(recorded):
    """Every key the harness reduces, with its value before the program
    had spans and stages, and after the readers added theirs."""
    d, _, reduced = recorded
    run = Run(cell=None, trace=reduced)
    before = {k: reduced[k] for k in ("n_devices", "window_s", "busy_s",
                                      "collective_s", "modules")}
    ops = list(reduced["breakdown"]["device_ops"])
    gaps = list(reduced["breakdown"]["idle_gaps"])
    assert ops[:6] == [
        ["while.75", 0.005633458],
        ["select_dynamic-update-slice_fusion.2", 0.0008897400000000001],
        ["dynamic_slice.52", 0.000617062], ["dynamic_slice.53", 0.000609839],
        ["dynamic_slice.54", 0.000600286], ["dynamic_slice.55", 0.000600278]]
    assert gaps[:3] == [["DevicePut", 0.018596877],
                        ["$solver.py:148 solve", 0.0019888370000000002],
                        ["$array.py:631 _value", 0.001301389]]
    assert reduced["modules"]["jit__outer_step"] == {
        "count": 6.0, "seconds": 0.009189793000000002}
    assert sr.of_run(run, str(d)) is reduced
    for k, v in before.items():
        assert reduced[k] == v
    assert reduced["breakdown"]["device_ops"] == ops
    assert reduced["breakdown"]["idle_gaps"] == gaps
    # a program without spans or scopes: every gap outside a span
    assert reduced["spans"] == {} and reduced["stages"] == {}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert reduced["idle_by_span"][sr.NO_SPAN] == pytest.approx(idle)
    assert reduced["breakdown"]["idle_by_span"] == [
        [sr.NO_SPAN, reduced["idle_by_span"][sr.NO_SPAN]]]
    assert reduced["breakdown"]["stages"] == []


def test_hlo_stats_join(recorded):
    _, path, _ = recorded
    names = sr.framework_names(path)
    assert names[("3440402036843992252", "sort")] == "jit(_outer_step)/top_k"
    # op names repeat across programs: the key holds the program id
    assert names[("12111979782722189662", "sort")] \
        == "jit(_outer_step)/top_k"
    assert names[("3440402036843992252", "dynamic_slice.52")].startswith(
        "jit(_outer_step)/cond/branch_0_fun/while/body/while/body/")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_silent_without_spans(recorded, metric):
    """On a program that writes no span and names no stage (the recorded
    one), each new reader finds nothing and returns None."""
    d, _, reduced = recorded
    run = Run(cell=None, trace=_harness_keys(reduced))
    sr.of_run(run, str(d))
    reader = load_module(BENCH / "metrics" / f"{metric}.py")
    assert reader.read(run) is None
    assert reader.read(Run(cell=None)) is None         # an untraced run


# ---------------------------------------------------------- made-up data
@pytest.mark.parametrize("name, scopes", [
    ("jit(_outer_step)/score/dot_general", ["score"]),
    ("jit(_outer_step)/gather/gather", ["gather"]),
    ("jit(_outer_step)/scatter:", []),
    ("jit(_outer_step)/inner_solve/cond/branch_0_fun/while/body/"
     "closed_call/dynamic_slice", ["inner_solve"]),
    ("jit(_chunk_solve)/while/body/vmap(select)/jit(_where)/select_n",
     ["select"]),
    ("jit(_chunk_solve)/while/cond/chunk_control/reduce_or",
     ["chunk_control"]),
    ("", []), ("design[0]:", []),
    ("jit(scatter)/scatter", []), ("jit(gather)/jit(_take)/gather", []),
])
def test_stage_scopes(name, scopes):
    assert sr.stage_scopes(name) == scopes


def test_op_stages_nest():
    stage = {"w": None, "a": "inner_solve", "b": "inner_solve",
             "m": None, "s": "score", "u": None, "c": None}.get
    ops = [(0, 10, "w"), (1, 4, "a"), (5, 7, "b"), (8, 9, "u"),
           (10, 20, "m"), (11, 12, "s"), (13, 15, "a"), (16, 17, "u"),
           (20, 21, "u"),                                 # names nothing
           (22, 24, "c"), (22.5, 23, "u")]                # nothing below
    out, covered = sr._op_stages(ops, stage)
    # w encloses only inner_solve ops: it and its unnamed op u take that
    # stage; m spans two stages (MIXED), and so does its unnamed op
    assert out == {"inner_solve": 3 + 2 + 1 + (10 - 6) + 2, "score": 1,
                   sr.MIXED: (10 - 4) + 1, sr.UNATTRIBUTED: 1 + 1.5 + 0.5}
    assert covered == 10 + 10 + 1 + 2


def test_stage_times_by_program():
    names = {("7", "fusion.1"): "jit(_outer_step)/score/mul",
             ("7", "while.2"): "",
             ("7", "fusion.3"): "jit(_outer_step)/inner_solve/while/body/add",
             ("9", "fusion.1"): "jit(add)/add"}
    modules = [(0, 100, "jit__outer_step(7)"), (200, 210, "jit_add(9)"),
               (300, 400, "jit__outer_step(7)")]
    ops = [(1, 20, "fusion.1"), (20, 90, "while.2"), (30, 80, "fusion.3"),
           (201, 209, "fusion.1"),
           (300, 350, "fusion.1"), (350, 390, "fusion.3")]
    out = sr.stage_times(modules, ops, names)
    assert set(out) == {"jit__outer_step"}           # no scope, no entry
    st = out["jit__outer_step"]
    assert st == {"score": 19 + 50, "inner_solve": 70 + 40,
                  sr.UNATTRIBUTED: (100 - 89) + (100 - 90)}
    assert sum(st.values()) == 200                   # the programs' time


def test_idle_and_launches_by_span():
    spans = [(0, 100, "repro/grid_round"), (10, 20, "repro/grid_sync"),
             (50, 60, "repro/grid_fill"), (100, 200, "repro/grid_round")]
    cover = sr._Cover(spans)
    gaps = [(12, 18), (40, 48), (52, 58), (98, 104), (250, 260)]
    assert sr.idle_by_span(cover, gaps) == {
        "repro/grid_sync": 6, "repro/grid_round": 8 + 6,
        "repro/grid_fill": 6, sr.NO_SPAN: 10}
    mods = [(15, 16, "jit__chunk_solve(1)"), (55, 56, "jit_take(2)"),
            (150, 151, "jit_take(2)"), (300, 301, "jit_take(2)")]
    assert sr.modules_by_span(cover, mods) == {
        "repro/grid_round": {"jit__chunk_solve": 1, "jit_take": 2},
        "repro/grid_sync": {"jit__chunk_solve": 1},
        "repro/grid_fill": {"jit_take": 1}}
    table = sr.span_table(spans, 0, 150)
    assert {k: v["count"] for k, v in table.items()} == {
        "repro/grid_round": 2, "repro/grid_sync": 1, "repro/grid_fill": 1}
    assert table["repro/grid_round"]["seconds"] == pytest.approx(1.5e-7)
    assert table["repro/grid_sync"]["seconds"] == pytest.approx(1e-8)


def test_readers_arithmetic():
    trace = {"window_s": 1.0, "busy_s": 0.5, "breakdown": {},
             "modules": {"jit__outer_step": {"count": 4.0, "seconds": 0.08},
                         "jit__chunk_solve": {"count": 2.0, "seconds": 0.2},
                         "jit_take": {"count": 30.0, "seconds": 0.001}},
             "spans": {"repro/sync": {"count": 5, "seconds": 0.05},
                       "repro/grid_round": {"count": 2, "seconds": 0.3},
                       "repro/grid_sync": {"count": 2, "seconds": 0.01}},
             "idle_by_span": {"repro/sync": 0.01, "repro/grid_sync": 0.004,
                              "repro/grid_fill": 0.02,
                              "repro/grid_retire": 0.03,
                              "repro/grid_round": 0.5},
             "modules_by_span": {"repro/grid_round": {
                 "jit__chunk_solve": 2.0, "jit_take": 30.0}},
             "stages": {"jit__outer_step": {"score": 0.004,
                                            "inner_solve": 0.072},
                        "jit__chunk_solve": {"gather": 0.01}}}
    run = Run(cell=None, trace=trace)

    def read(metric):
        return load_module(BENCH / "metrics" / f"{metric}.py").read(run)
    assert read("readback_idle_ms.solve") == pytest.approx(2.0)
    assert read("score_ms.solve") == pytest.approx(1.0)
    assert read("inner_solve_ms.solve") == pytest.approx(18.0)
    assert read("select_ms.solve") == 0.0
    assert read("gather_ms.grid") == pytest.approx(5.0)
    assert read("readback_idle_ms.grid") == pytest.approx(2.0)
    assert read("bookkeeping_idle_ms_per_round.grid") == pytest.approx(25.0)
    assert read("eager_launches_per_round.grid") == pytest.approx(15.0)


def test_of_run_skips_another_runs_profile(recorded, tmp_path):
    """A profile whose window is not the run's is not read."""
    d, _, reduced = recorded
    shutil.copytree(d, tmp_path / "t")
    run = Run(cell=None, trace=_harness_keys(reduced, window_s=1.0))
    t = sr.of_run(run, str(tmp_path / "t"))
    assert t["spans"] == {} and t["idle_by_span"] == {}
    assert "idle_by_span" not in t["breakdown"]
    assert t["busy_s"] == reduced["busy_s"]


# ------------------------------- a recorded v5e trace of this program
SPANS_TRACE = Path(__file__).parent / "data" / "tiny_spans_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def spans_run(tmp_path_factory):
    """Two warm solves and one grid (n=256, p=512) recorded on one TPU v5e
    by ``bench/tests/record_v5e.py``, reduced as a traced run is."""
    d = tmp_path_factory.mktemp("v5e_spans")
    path = d / "plugins" / "profile" / "0" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(SPANS_TRACE.read_bytes()))
    run = Run(cell=None, trace=tr.reduce_file(str(path)))
    sr.of_run(run, str(d))
    return run


def test_chip_spans_match_launches(spans_run):
    t = spans_run.trace
    count = {k: v["count"] for k, v in t["spans"].items()}
    assert count["repro/solve"] == 2 and count["repro/grid"] == 1
    assert count["repro/outer"] == count["repro/dispatch"] \
        == count["repro/sync"] == t["modules"]["jit__outer_step"]["count"]
    assert count["repro/grid_round"] == count["repro/grid_dispatch"] \
        == count["repro/grid_sync"] == t["modules"]["jit__chunk_solve"][
            "count"]
    # the chunk step runs inside its round; the eager bookkeeping too
    per_round = t["modules_by_span"]["repro/grid_round"]
    assert per_round["jit__chunk_solve"] == count["repro/grid_round"]
    assert sum(per_round.values()) > 2 * count["repro/grid_round"]


def test_chip_idle_put_down_to_spans(spans_run):
    t = spans_run.trace
    idle = t["window_s"] - t["busy_s"]
    assert sum(t["idle_by_span"].values()) == pytest.approx(idle)
    assert t["idle_by_span"].get(sr.NO_SPAN, 0.0) < 0.01 * idle
    assert t["breakdown"]["idle_by_span"][0][1] \
        == max(t["idle_by_span"].values())


@pytest.mark.parametrize("program", ["jit__outer_step", "jit__chunk_solve"])
def test_chip_stages_add_up(spans_run, program):
    """Every stage is there, the stages add up to the program's device
    time, and next to nothing is left unattributed."""
    t = spans_run.trace
    st = t["stages"][program]
    assert {"score", "select", "gather", "inner_solve", "scatter"} <= set(st)
    assert sum(st.values()) == pytest.approx(t["modules"][program]["seconds"],
                                             rel=1e-9)
    assert st[sr.UNATTRIBUTED] < 0.01 * sum(st.values())
    assert st["inner_solve"] == max(st.values())
    assert [program + "/inner_solve", st["inner_solve"]] \
        in t["breakdown"]["stages"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_chip_new_readers_read(spans_run, metric):
    value = load_module(BENCH / "metrics" / f"{metric}.py").read(spans_run)
    assert value is not None and value >= 0.0
