"""Small helpers the metric readers share."""
from __future__ import annotations


def ratio(run, num: str, den: str):
    c = run.counters
    if not c.get(den):
        return None
    return c[num] / c[den]


def module_ms(run, fragment: str):
    """Mean device milliseconds per execution of the jitted programs whose
    name holds `fragment`, over the traced window."""
    if run.trace is None:
        return None
    count = seconds = 0
    for name, m in run.trace["modules"].items():
        if fragment in name:
            count += m["count"]
            seconds += m["seconds"]
    return 1e3 * seconds / count if count else None


def idle_share(run):
    t = run.trace
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
