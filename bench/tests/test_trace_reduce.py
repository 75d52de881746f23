"""The trace reduction, on a trace recorded on one TPU v5e chip (two warm
Lasso solves at n=256, p=512, between `setup` and `check` spans) and on
made-up events."""
import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "tiny_solve_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    raw = gzip.decompress(TRACE.read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    return tr.reduce_profile(pd)


def test_window_and_busy(reduced):
    assert reduced["n_devices"] == 1
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["window_s"] == pytest.approx(0.034577579, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.009209628, rel=1e-6)


def test_modules(reduced):
    step = reduced["modules"]["jit__outer_step"]
    assert step["count"] == 6            # two solves of 2 + 1 outer steps
    assert step["seconds"] == pytest.approx(0.009189793, rel=1e-6)
    assert reduced["collective_s"] == 0.0


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= tr.TOP and 0 < len(gaps) <= tr.TOP
    assert ops[0][0] == "while.75"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle * (1 + 1e-9)
    assert gaps[0][0] == "DevicePut"


def test_self_times_nest():
    events = [(0.0, 10.0, "outer"), (1.0, 4.0, "a"), (2.0, 3.0, "b"),
              (5.0, 7.0, "a"), (12.0, 13.0, "c")]
    assert tr.self_times(events) == {"outer": 5.0, "a": 4.0, "b": 1.0,
                                     "c": 1.0}


def test_names():
    assert tr.module_name("jit__outer_step(6168915524674769513)") \
        == "jit__outer_step"
    assert tr.op_name("%fusion.6 = f32[20000]{0} fusion(%x)") == "fusion.6"
    assert tr.COLLECTIVE.search("all-reduce.3")
    assert not tr.COLLECTIVE.search("fusion.3")


def test_union_and_clip():
    assert tr._union([(3, 4), (0, 2), (1, 3)]) == [[0, 4]]
    assert tr._clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
