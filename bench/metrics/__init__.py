"""One reader per per-layer metric, in a file named after the metric.

Each file defines ``read(run) -> float | None``: `run` is the harness's
``Run`` (the driver's counters, the reduced trace, the chip's peaks). A reader
that finds nothing to read returns None and the metric is left out of the
result line."""
