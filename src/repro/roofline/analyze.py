"""Roofline analyzer CLI for the solver engine's lowered outer step.

Attributes FLOPs and bytes per engine stage (score / select / gather /
inner-solve / scatter, plus the fused single-traversal kernel) at a given
(n, p, ws) shape, prints the per-stage table, and optionally writes the
record as JSON and/or enforces the fused single-read byte budget
(``--check-ratio``, the same model ``bench_engine.py --check-budget``
enforces in CI — see DESIGN.md §10).

    PYTHONPATH=src python -m repro.roofline.analyze --n 128 --p 1024 --ws 64
    PYTHONPATH=src python -m repro.roofline.analyze --check-ratio 0.6
"""
import argparse
import json
import os

import jax

jax.config.update("jax_enable_x64", True)

from repro.roofline.engine_stages import (format_stage_table,   # noqa: E402
                                          stage_table)


def run(n, p, ws, out=None, check_ratio=None, measure=True, n_tasks=0):
    """Build (and optionally persist / enforce) the per-stage table."""
    table = stage_table(n, p, ws, n_tasks=n_tasks, measure=measure)
    print(format_stage_table(table))
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(table, f, indent=1)
        print(f"[roofline] wrote {out}")
    if check_ratio is not None and table["fused_ratio"] > check_ratio:
        raise SystemExit(
            f"[roofline] FAIL: fused bytes-per-outer ratio "
            f"{table['fused_ratio']:.4f} exceeds the budget {check_ratio} "
            f"at n={n} p={p} ws={ws}")
    return table


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=128,
                    help="samples (default: the smoke fig2_lasso shape)")
    ap.add_argument("--p", type=int, default=1024, help="features")
    ap.add_argument("--ws", type=int, default=64, help="working-set bucket")
    ap.add_argument("--n-tasks", type=int, default=0,
                    help="multitask width T (0 = scalar coordinates)")
    ap.add_argument("--out", default=None,
                    help="write the table as JSON to this path")
    ap.add_argument("--check-ratio", type=float, default=None,
                    help="fail unless fused/two-pass bytes ratio <= this")
    ap.add_argument("--no-measure", action="store_true",
                    help="skip XLA lowering; byte models only")
    args = ap.parse_args()
    run(args.n, args.p, args.ws, out=args.out, check_ratio=args.check_ratio,
        measure=not args.no_measure, n_tasks=args.n_tasks)


if __name__ == "__main__":
    main()
