"""Per-stage roofline accounting for the solve engine's outer step.

Attributes FLOPs and bytes to each stage of the fused outer iteration —
score / select / gather / inner-solve / scatter — two ways:

  * **measured**: each stage is lowered and compiled in isolation
    (``jax.jit(stage).lower(...).compile()``) and XLA's ``cost_analysis()``
    supplies flops / "bytes accessed"; the optimized HLO text additionally
    runs through :func:`repro.roofline.hlo.collective_bytes` so sharded
    lowerings report their link traffic. XLA:CPU omits some counters, so
    missing keys read as 0.0 — the measured columns are diagnostics, not
    the CI contract.
  * **modeled**: exact element-count models of HBM traffic per outer
    iteration (DESIGN.md §10) for the two-pass head (score then gather,
    re-reading X) and the fused head (one X traversal,
    ``kernels/fused_ws.py``). The models are deterministic in (n, p, ws,
    itemsize), so CI enforces them via ``bench_engine.py --check-budget``:
    the fused score+select+gather bytes-per-outer must stay within
    ``budget_fused_bytes_ratio`` (0.6) of the two-pass baseline.

The gather model charges HBM *transaction granularity*: gathering K
columns from a row-major [n, p] array touches ``min(p, K * G)`` elements
per row (G = ``GATHER_GRANULARITY`` elements per transaction), which is the
whole matrix again in the p >> ws regime — the fact the fused kernel
exploits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_ws import _pick_bp
from .hlo import collective_bytes

# the engine stages, in dataflow order (two-pass head)
STAGES = ("score", "select", "gather", "inner_solve", "scatter")

# elements moved per HBM transaction when gathering strided columns: 1024
# bytes / 8-byte f64 lanes (TPU tiling; on CPU caches the effect is the
# same order). Only min(p, ws * G) elements per row are ever *not* touched.
GATHER_GRANULARITY = 128


# ------------------------------------------------------------ byte models
def two_pass_bytes_model(n: int, p: int, ws: int, itemsize: int = 8,
                         n_tasks: int = 0,
                         gather_granularity: int = GATHER_GRANULARITY):
    """HBM bytes per outer iteration of the two-pass head (score pass over
    X, then a separate ws-column gather re-touching X at transaction
    granularity). Returns per-stage bytes plus their 'total'."""
    R = max(n_tasks, 1)
    score = (n * p + n * R + p * (2 + 2 * R)) * itemsize
    #        X      raw      beta/grad [p,R], L/offset + scores write
    select = 2 * p * itemsize + ws * 4
    touched = n * min(p, ws * gather_granularity)
    gather = (touched + n * ws) * itemsize + ws * 4
    return {"score": score, "select": select, "gather": gather,
            "total": score + select + gather}


def fused_bytes_model(n: int, p: int, ws: int, itemsize: int = 8,
                      n_tasks: int = 0, bp: int | None = None):
    """HBM bytes per outer iteration of the fused head: the kernel reads
    each X tile ONCE and emits scores + gradient + gathered candidate
    columns; the merge is a [p]-sized select plus a candidate-row lookup
    (no X traffic). Returns per-stage bytes ('kernel', 'select',
    'recover') plus their 'total'."""
    R = max(n_tasks, 1)
    bp = _pick_bp(n, p, ws, R, itemsize) if bp is None else bp
    tiles = -(-p // bp)
    p_pad = tiles * bp
    kc = min(bp, ws)
    C = tiles * kc
    kernel = ((n * p                        # X tiles, each read once
               + n * R                      # raw gradient (revolving block)
               + p_pad * R                  # beta
               + 3 * p_pad                  # L, offset, gsupp
               + p_pad * (1 + R))           # scores + grad writes
              * itemsize
              + C * 4                       # cand_idx write (int32)
              + C * n * itemsize)           # cand_cols write
    select = 2 * p * itemsize + ws * 4
    recover = (2 * ws * n) * itemsize + (C + ws) * 4 + p * 4
    #          cand rows read + X_ws write;  idx reads;    pos scatter
    return {"kernel": kernel, "select": select, "recover": recover,
            "total": kernel + select + recover}


def fused_bytes_ratio(n: int, p: int, ws: int, itemsize: int = 8,
                      n_tasks: int = 0) -> float:
    """Fused / two-pass score+select+gather bytes-per-outer (the CI-enforced
    single-read budget; < 1 means the fused head wins)."""
    f = fused_bytes_model(n, p, ws, itemsize, n_tasks)["total"]
    t = two_pass_bytes_model(n, p, ws, itemsize, n_tasks)["total"]
    return f / t


# --------------------------------------------------------- measured costs
def _compiled_cost(fn, *args):
    """(flops, bytes_hlo, coll_bytes) of a jitted fn on example args, from
    XLA cost_analysis + the optimized-HLO collective parser. Counters XLA
    does not report (common on CPU) read as 0.0."""
    compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    flops = float(ca.get("flops", 0.0))
    bytes_hlo = float(ca.get("bytes accessed", 0.0))
    try:
        coll, _ = collective_bytes(compiled.as_text())
    except Exception:
        coll = 0.0
    return flops, bytes_hlo, coll


def measure_stage_costs(n: int, p: int, ws: int, dtype=jnp.float64,
                        include_fused: bool = True):
    """Lower each engine stage at shape (n, p, ws) and read its XLA cost.

    Returns {stage: {flops, bytes_hlo, coll_bytes}} for the five two-pass
    stages, plus a 'fused_kernel' entry (the single-traversal replacement
    for score+select+gather) when ``include_fused``.
    """
    from repro.core.cd import cd_epoch_gram
    from repro.core.penalties import L1
    from repro.core.working_set import select_working_set, violation_scores

    pen = L1(0.1)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    r = jnp.asarray(rng.standard_normal(n), dtype)
    beta = jnp.asarray(rng.standard_normal(p) * (rng.random(p) < 0.1), dtype)
    L = jnp.maximum(jnp.sum(X * X, axis=0) / n, 1e-12)
    offset = jnp.zeros(p, dtype)
    gsupp = pen.generalized_support(beta)
    scores = violation_scores(pen, beta, X.T @ r, L)
    ws_idx = select_working_set(scores, gsupp, ws)
    X_ws = X[:, ws_idx]
    G = X_ws.T @ X_ws / n
    c = X_ws.T @ r / n
    beta_ws = beta[ws_idx]
    q = G @ beta_ws
    L_ws = L[ws_idx]

    stages = {
        "score": (lambda X, r, b, L, off:
                  violation_scores(pen, b, X.T @ r + off, L),
                  (X, r, beta, L, offset)),
        "select": (lambda s, g: select_working_set(s, g, ws),
                   (scores, gsupp)),
        "gather": (lambda X, i: X[:, i], (X, ws_idx)),
        "inner_solve": (lambda G, c, b, q, L:
                        cd_epoch_gram(G, c, b, q, L, pen),
                        (G, c, beta_ws, q, L_ws)),
        "scatter": (lambda b, i, v: b.at[i].set(v),
                    (beta, ws_idx, beta_ws)),
    }
    out = {}
    for name, (fn, args) in stages.items():
        flops, bytes_hlo, coll = _compiled_cost(fn, *args)
        out[name] = {"flops": flops, "bytes_hlo": bytes_hlo,
                     "coll_bytes": coll}
    if include_fused:
        from repro.kernels import ops as kops
        from repro.kernels.common import penalty_params
        params = penalty_params(pen)

        def fused(X, r, b, L, off, g):
            return kops.fused_ws(X, r, b, L, off, g, L1, params, ws)

        flops, bytes_hlo, coll = _compiled_cost(
            fused, X, r, beta, L, offset, gsupp.astype(dtype))
        out["fused_kernel"] = {"flops": flops, "bytes_hlo": bytes_hlo,
                               "coll_bytes": coll}
    return out


def stage_table(n: int, p: int, ws: int, dtype=jnp.float64,
                n_tasks: int = 0, measure: bool = True):
    """The full per-stage roofline record written into BENCH_engine.json.

    Combines the measured XLA costs (when ``measure``) with the exact byte
    models and the CI-enforced fused/two-pass ratio.
    """
    itemsize = jnp.dtype(dtype).itemsize
    two = two_pass_bytes_model(n, p, ws, itemsize, n_tasks)
    fused = fused_bytes_model(n, p, ws, itemsize, n_tasks)
    table = {
        "shape": {"n": n, "p": p, "ws": ws, "itemsize": itemsize,
                  "n_tasks": n_tasks,
                  "gather_granularity": GATHER_GRANULARITY,
                  "bp": _pick_bp(n, p, ws, max(n_tasks, 1), itemsize)},
        "stages": {},
        "two_pass_bytes_model": two,
        "fused_bytes_model": fused,
        "two_pass_bytes_per_outer": two["total"],
        "fused_bytes_per_outer": fused["total"],
        "fused_ratio": fused["total"] / two["total"],
    }
    if measure:
        measured = measure_stage_costs(n, p, ws, dtype)
        for name in STAGES:
            table["stages"][name] = dict(measured[name])
        table["stages"]["fused_kernel"] = dict(measured["fused_kernel"])
        for name, bts in (("score", two["score"]), ("select", two["select"]),
                          ("gather", two["gather"])):
            table["stages"][name]["bytes_model"] = bts
        table["stages"]["fused_kernel"]["bytes_model"] = \
            fused["kernel"] + fused["select"] + fused["recover"]
    return table


def register_stage_table(registry, name: str, table) -> None:
    """Publish a :func:`stage_table` record as named metrics-registry gauges.

    The roofline numbers become ``roofline.<name>.<stage>.<metric>`` gauges
    (flops / bytes_hlo / bytes_model / coll_bytes per stage) plus the
    headline ``roofline.<name>.fused_bytes_per_outer`` /
    ``two_pass_bytes_per_outer`` / ``fused_ratio`` — the same registry
    namespace the solver counters live in (DESIGN.md §11.3), so one
    ``MetricsRegistry.as_dict()`` snapshot carries solver telemetry and
    roofline budgets side by side (``bench_engine.py --check-budget`` reads
    the ratio from here).
    """
    base = f"roofline.{name}"
    for stage, row in table.get("stages", {}).items():
        for metric, value in row.items():
            registry.set_gauge(f"{base}.{stage}.{metric}", float(value))
    for key in ("two_pass_bytes_per_outer", "fused_bytes_per_outer",
                "fused_ratio"):
        if key in table:
            registry.set_gauge(f"{base}.{key}", float(table[key]))


def format_stage_table(table) -> str:
    """Render a stage_table() record as an aligned text table."""
    sh = table["shape"]
    lines = [
        f"engine roofline @ n={sh['n']} p={sh['p']} ws={sh['ws']} "
        f"itemsize={sh['itemsize']} (gather granularity "
        f"{sh['gather_granularity']} elems)",
        f"{'stage':<14} {'flops':>14} {'bytes(HLO)':>14} "
        f"{'bytes(model)':>14} {'coll':>10}",
    ]
    for name, row in table["stages"].items():
        lines.append(
            f"{name:<14} {row.get('flops', 0.0):>14.3e} "
            f"{row.get('bytes_hlo', 0.0):>14.3e} "
            f"{row.get('bytes_model', float('nan')):>14.3e} "
            f"{row.get('coll_bytes', 0.0):>10.1f}")
    lines.append(
        f"bytes/outer: two-pass {table['two_pass_bytes_per_outer']:,} -> "
        f"fused {table['fused_bytes_per_outer']:,} "
        f"(ratio {table['fused_ratio']:.4f})")
    return "\n".join(lines)
