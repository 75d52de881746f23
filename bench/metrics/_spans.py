"""Helpers of the readers of the program's spans and stages
(``bench.span_reduce``)."""
from __future__ import annotations

from bench.span_reduce import of_run


def idle_ms_per(run, spans, per: str):
    """Device-idle milliseconds put down to the spans `spans`, per span
    `per` in the window; None where the program wrote no `per` span."""
    t = of_run(run)
    n = t and t["spans"].get(per, {}).get("count")
    if not n:
        return None
    return 1e3 * sum(t["idle_by_span"].get(s, 0.0) for s in spans) / n


def stage_ms(run, fragment: str, stage: str):
    """Device self milliseconds of `stage` per execution of the jitted
    programs whose name holds `fragment`; None where their ops name no
    stage."""
    t = of_run(run)
    progs = [p for p in (t["stages"] if t else {}) if fragment in p]
    count = sum(t["modules"][p]["count"] for p in progs)
    if not count:
        return None
    return 1e3 * sum(t["stages"][p].get(stage, 0.0) for p in progs) / count


def launches_per(run, span: str, skip: str):
    """Program executions, other than those whose name holds `skip`, that
    start on the device inside a `span` span, per such span."""
    t = of_run(run)
    n = t and t["spans"].get(span, {}).get("count")
    if not n:
        return None
    per = t["modules_by_span"].get(span, {})
    return sum(c for p, c in per.items() if skip not in p) / n
