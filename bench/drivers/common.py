"""What the drivers share: the program's design from a generator's output,
lambda_max, the seeded sample of answers, and the check's report."""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data.seeds import host_rng
from ..harness import BENCH


def note(msg: str):
    """A timestamped line on standard error (set-up phases)."""
    print(f"[{time.perf_counter():.3f}] {msg}", file=sys.stderr, flush=True)


def program_design(X):
    """The design as the program takes it: a device array stays as it is;
    a scipy matrix becomes the program's CSC design on the device, once."""
    if hasattr(X, "tocsc"):
        from repro.sparse import CSCDesign
        return CSCDesign.from_scipy(X, dtype=np.float32)
    return X


def mesh_of(X):
    """The mesh a design sharded over several chips lives on (the solve then
    runs on it), or None for a design on one device or on the host."""
    sharding = getattr(X, "sharding", None)
    if sharding is None or len(sharding.device_set) == 1:
        return None
    return sharding.mesh


def lambda_max(X, Y) -> np.ndarray:
    """max_j |x_j . y_t| / n for each target t, computed by the benchmark:
    on the device at full float32 precision for a dense design, in float64
    on the host for a scipy one."""
    n = X.shape[0]
    if hasattr(X, "tocsc"):
        return np.abs(X.T.astype(np.float64) @ np.asarray(Y, np.float64).T) \
            .max(axis=0) / n
    g = jnp.dot(X.T, jnp.asarray(Y).T, precision=jax.lax.Precision.HIGHEST)
    return np.asarray(jnp.max(jnp.abs(g), axis=0), np.float64) / n


def x_bytes(X) -> int:
    """Bytes of one read of the design on the device: dense n*p*itemsize;
    CSC nnz values and row indices (4 B each) plus p + 1 column pointers."""
    if hasattr(X, "tocsc"):
        return int(X.nnz) * 8 + (X.shape[1] + 1) * 4
    return int(X.size) * X.dtype.itemsize


def values_seed(mix: dict, seed: int) -> int:
    """The seed the problems' values come from: the mix's fixed
    ``values_seed`` where it names one (every run does the same work),
    else the run's own."""
    return int(mix["values_seed"]) if "values_seed" in mix else int(seed)


def pass_order(k: int, seed: int):
    """Indices of `k` problems in whole passes, each pass in an order drawn
    from the seed."""
    rng = host_rng(seed, "order")
    while True:
        yield from (int(j) for j in rng.permutation(k))


class Reservoir:
    """A uniform sample of `k` answers out of however many the window
    yields, drawn from the seed (Algorithm R)."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = host_rng(seed, "check-sample")
        self.items = []
        self.seen = 0

    def offer(self, item):
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def limits(cell_name: str) -> dict:
    """The cell's limits on the numbers its check compares
    (bench/limits/<cell>.json)."""
    path = BENCH / "limits" / f"{cell_name}.json"
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()
            if isinstance(v, dict) and "limit" in v}


def host_design(X):
    """The design as the float64 reference reads it: dense designs come
    back from the device once the window has closed."""
    return X if hasattr(X, "tocsc") else np.asarray(X)


@dataclasses.dataclass
class Answers:
    """Answers to check, one column each: the design of each answer X [S]
    (device arrays or scipy matrices), targets Y [n, S], coefficients
    B [p, S], lambdas [S], weights W [n, S] (summing to n) or None, the
    program's own KKT value kkt_dev [S], and lambda_max [S]."""
    X: list
    Y: np.ndarray
    B: np.ndarray
    lams: np.ndarray
    W: np.ndarray | None
    kkt_dev: np.ndarray
    lmax: np.ndarray


def by_design(fn, ans: Answers, host: bool = False) -> np.ndarray:
    """fn(X, Y, B, lams, W) -> [S'] over the answers of each design in
    turn, in the answers' order; `host` brings each design to the host
    first, once."""
    out = np.empty(ans.B.shape[1])
    groups = {}
    for j, X in enumerate(ans.X):
        groups.setdefault(id(X), (X, []))[1].append(j)
    for X, idx in groups.values():
        idx = np.asarray(idx)
        out[idx] = fn(host_design(X) if host else X, ans.Y[:, idx],
                      ans.B[:, idx], ans.lams[idx],
                      None if ans.W is None else ans.W[:, idx])
    return out


def gap(kkt_a, kkt_b, lmax) -> float:
    """Largest distance between two KKT readings, over lambda_max."""
    return float(np.max(np.abs(kkt_a - kkt_b) / lmax))


def check(st, window, ans: Answers) -> list:
    """The numbers that decide ``correct``, each beside its limit:
    unconverged answers in the window (limit 0); the float64 KKT value of
    the sampled answers over lambda_max (limit: the stated tolerance plus
    the certificate's allowed error); and the certificate's error, the gap
    between the program's KKT value and the float64 one (limit set from
    sound runs and the lower-precision control, bench/limits/<cell>.json).
    """
    from ..reference import kkt as ref
    st.sample = None                     # the program's answers are in `ans`
    lim = limits(st.cell.name)
    kkt64 = by_design(ref.kkt, ans, host=True)
    tol = float(st.cell.mix["tol_frac"])
    return [
        {"name": "unconverged", "value": window["failed"], "limit": 0},
        {"name": "kkt", "value": float(np.max(kkt64 / ans.lmax)),
         "limit": tol + lim["kkt_gap"]},
        {"name": "kkt_gap", "value": gap(ans.kkt_dev, kkt64, ans.lmax),
         "limit": lim["kkt_gap"]},
    ]
