"""Finds a cell's parts by name and runs it once.

Everything particular to a configuration, a traffic mix, a driver or a
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` or another data file gives it:

  configs/<config>.json      sizes and guarantees of a deployment
  mixes/<traffic>.json       parameters of a traffic mix, naming its driver
  drivers/<driver>.py        set-up, measured window and check of one call
  data/<generator>.py        seeded data of one design kind
  metrics/<metric>.py        reader of one per-layer metric

so a later change adds a cell, a mix or a metric as new files alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class HarnessError(RuntimeError):
    """A cell cannot run here: no chip, too few chips, or a missing part."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise HarnessError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a part of the benchmark from its file (names may hold dots)."""
    if not path.is_file():
        raise HarnessError(f"missing {path.relative_to(ROOT)}")
    name = f"bench.{path.parent.name}.{path.stem.replace('.', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the parts it names."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # BENCHMARK.json metric entries of this cell
    per_layer: list

    @property
    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.mix['driver']}.py")

    @property
    def generator(self):
        return load_module(BENCH / "data" / f"{self.config['generator']}.py")


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, spec: dict | None = None) -> Cell:
    """The cell `name` of ``BENCHMARK.json`` (or of `spec`, a parsed copy)."""
    spec = load_json(ROOT / "BENCHMARK.json") if spec is None else spec
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                mix=load_json(BENCH / "mixes" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count of the devices JAX found; refuses a host
    without a TPU or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise HarnessError(f"no TPU: JAX found {d.platform} ({d.device_kind})")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind` (bench/peaks.json)."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise HarnessError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json")
    return table[device_kind]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``, else a fixed
    path in the checkout), with every program cached, however small."""
    import jax
    from repro.compile_cache import enable_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts lowerings to XLA (a compile or a compile-cache read) while
    armed: the measured window must hold none."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics."""
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def peak_memory_bytes(n_devices: int) -> int:
    import jax
    peaks_ = []
    for d in jax.devices()[:n_devices]:
        st = d.memory_stats() or {}
        peaks_.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks_)


@dataclasses.dataclass
class Run:
    """What a driver's window leaves for the metric readers and the check."""
    cell: Cell
    counters: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None         # bench.trace_reduce.reduce output
    peaks: dict | None = None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             trace_dir: str | None = None, log=None) -> dict:
    """Set up, measure and check one cell; returns the result object."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    import jax
    dev = device_info(cell.chips, require_tpu)
    cache = enable_compile_cache()
    log(f"cell {cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={dev['kind']} x{dev['count']} compile_cache={cache}")
    log(f"[{time.perf_counter():.3f}] devices found")
    counter = CompileCounter()
    driver = cell.driver
    run = Run(cell=cell,
              peaks=peaks(dev["kind"]) if require_tpu else None)
    with jax.profiler.TraceAnnotation("setup"):
        state = driver.setup(cell, seed)
    setup_s = time.perf_counter() - t_process

    counter.armed = True
    tracer = None
    if trace:
        from . import trace_reduce
        tracer = trace_reduce.WindowTracer(
            trace_dir or str(ROOT / ".bench-trace"),
            float(cell.mix.get("trace_seconds", seconds)))
    window = driver.window(state, float(seconds), tracer, run.counters)
    counter.armed = False
    counter.close()
    log(f"compiles_in_window={counter.count}")
    dev["memory_peak_bytes"] = peak_memory_bytes(cell.chips)

    with jax.profiler.TraceAnnotation("check"):
        checks = driver.check(state, window)
    checks.append({"name": "compiles_in_window", "value": counter.count,
                   "limit": 0})
    result = {"correct": all(c["value"] <= c["limit"] for c in checks),
              "attempted": window["attempted"], "failed": window["failed"],
              "metrics": {}, "device": dev}
    if trace:
        run.trace = tracer.reduce()
        dev["busy_s"] = run.trace["busy_s"]
        dev["window_s"] = run.trace["window_s"]
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = run.trace["breakdown"]
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise HarnessError(f"driver {cell.mix['driver']} gives no "
                                   f"{m['name']}")
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return result
