"""One read of the design per outer step, as a share of the chip's HBM
bandwidth: x_bytes / (peak HBM bytes/s * device seconds per outer step).

The algorithm reads X once per outer iteration (the score pass), whichever
code path does the read, so the share stays defined when a kernel changes.
It is a bandwidth bound: the step does other work too, so the share is at
most 100% and well below it while the inner solve dominates."""
from bench.metrics._read import module_ms


def read(run):
    ms = module_ms(run, "_outer_step")
    if ms is None or run.peaks is None:
        return None
    return 100.0 * run.counters["x_bytes"] / (
        run.peaks["hbm_bytes_per_s"] * ms * 1e-3)
