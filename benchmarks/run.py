"""Benchmark harness: one module per paper figure/table.

``PYTHONPATH=src python -m benchmarks.run [--scale smoke|small|paper] [--only name]``

``--smoke`` (= ``--scale smoke --skip-roofline-report``) runs every figure on
tiny instances; CI uses it so the perf scripts cannot silently rot.

Figure map:
  fig1_regpath   Figure 1  — reg paths: support recovery, estimation error
  fig2_lasso     Figure 2  — Lasso suboptimality vs time across solvers
                 (includes the Appendix E.2 / Figure 7 ADMM comparison)
  fig3_enet      Figure 3  — elastic net
  fig4_meeg      Figure 4  — M/EEG-style multitask source localization
  fig5_mcp       Figure 5  — MCP objective + optimality violation, vs IRL1
  fig6_ablation  Figure 6  — {working set} x {Anderson} ablation + claims
  fig9_svm       Figure 9  — dual SVM with hinge loss
  table1_models  Table 1   — model coverage matrix (datafit x penalty solves)
  roofline_report            §Dry-run / §Roofline tables from recorded JSONs

Each module prints CSV rows and writes experiments/bench/<name>.json.
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

import jax

# solver benchmarks validate KKT/duality gaps below float32 resolution
jax.config.update("jax_enable_x64", True)

BENCHES = ["fig1_regpath", "fig2_lasso", "fig3_enet", "fig4_meeg",
           "fig5_mcp", "fig6_ablation", "fig9_svm", "table1_models"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small",
                    choices=["smoke", "small", "paper"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny instances, no roofline report (CI)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-roofline-report", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        args.scale = "smoke"
        args.skip_roofline_report = True
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    names = [args.only] if args.only else BENCHES
    failures = []
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        print(f"\n===== {name} (scale={args.scale}) =====")
        t0 = time.perf_counter()
        try:
            mod.main(args.scale)
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s")
        except Exception as e:                      # noqa: BLE001
            traceback.print_exc()
            failures.append((name, repr(e)))
    if not args.only and not args.skip_roofline_report:
        try:
            from . import roofline_report
            print("\n===== roofline_report =====")
            roofline_report.main()
        except Exception as e:                      # noqa: BLE001
            traceback.print_exc()
            failures.append(("roofline_report", repr(e)))
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks completed")


if __name__ == "__main__":
    main()
