"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

(``python3 -m bench.run`` with the same arguments is the same.) The cell is
an entry of ``workloads`` in ``BENCHMARK.json``. The run makes its data from
``--seed``, warms up the shapes its traffic uses, measures for ``--seconds``,
checks the answers against the float64 reference and prints, as the last
line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, ``breakdown`` (traced runs) and ``checks``, each
number compared beside its limit. It exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import HarnessError, find_cell, run_cell
    try:
        cell = find_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS)
    except HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
