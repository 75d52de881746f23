"""Readings that set a cell's limit on ``kkt_gap``: the program's own and
the lower-precision control's, over several seeds in one process.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--fresh]

For each seed it runs the cell as a benchmark run does (set-up, a window of
``--seconds`` at the cell's own load), takes the same sample of answers, and
prints one JSON line: the float64 KKT value over lambda_max (``kkt``), the
gap of the program's KKT certificate to it (``gap_program``, the sound
reading) and the gap of the control's (``gap_control``, the reference's
arithmetic in the precision below the configuration's, read at the same
answers). With ``--fresh`` the problems' values come from each seed rather
than from the mix's fixed ``values_seed``, so the readings cover problems the
benchmark's runs never see. The benchmark's own runs do not run the
control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def read_seed(cell, seed: int, seconds: float) -> dict:
    import numpy as np
    from bench.drivers import common
    from bench.reference import control, kkt as ref
    driver = cell.driver
    st = driver.setup(cell, seed)
    window = driver.window(st, seconds, None, {})
    ans = driver.answers(st)
    st.sample = None
    k64 = common.by_design(ref.kkt, ans, host=True)
    kc = common.by_design(control.kkt, ans)
    return {"seed": seed, "answers": int(ans.B.shape[1]),
            "attempted": window["attempted"], "failed": window["failed"],
            "kkt": float(np.max(k64 / ans.lmax)),
            "gap_program": common.gap(ans.kkt_dev, k64, ans.lmax),
            "gap_control": common.gap(kc, k64, ans.lmax)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fresh", action="store_true",
                    help="draw the problems' values from each seed")
    args = ap.parse_args(argv)
    from bench.harness import device_info, enable_compile_cache, find_cell
    cell = find_cell(args.workload)
    if args.fresh:
        cell.mix.pop("values_seed", None)
    dev = device_info(cell.chips)
    enable_compile_cache()
    print(json.dumps({"workload": cell.name, "fresh": args.fresh,
                      "device": dev}), flush=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = read_seed(cell, seed, args.seconds)
        r["wall_s"] = time.perf_counter() - t0
        print(json.dumps(r), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
