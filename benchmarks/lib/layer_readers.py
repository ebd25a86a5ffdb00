"""Per-layer metrics: each is a small JSON file under layer_metrics/ that
names what it reads and one reduction. A reader that finds nothing to
read returns None and the harness leaves the metric out of the line.
Adding a metric adds files, never an edit: a reduction that is not on
the menu below is a module readers/<kind>.py with `read(evidence,
reader)`, and a roofline metric names its count function, a module
counts/<name>.py with `counts(evidence, reader, n_events)` returning
(operations, bytes) of the kernel's contract.

The evidence a reader sees (made by child.py and run.py):
  jobs              [{wall_s, traced, spans: {span: seconds}, ...}]
  registry_before / registry_after   {metric: [[labels, value]]}
  trace             trace_reduce.reduce() of the traced interval, or None
  memory            allocator peaks of the fullest chip
  shapes, device    sizes for the roofline counts; the device's kind
"""

from __future__ import annotations

import importlib
import statistics
from typing import Any, Callable, Dict, List, Optional

from benchmarks.lib import roofline, trace_reduce


def _labels_match(labels: dict, want: Optional[dict]) -> bool:
    return all(labels.get(k) == v for k, v in (want or {}).items())


def _series(snapshot: dict, metric: str, want: Optional[dict]) -> List[Any]:
    return [v for labels, v in snapshot.get(metric, [])
            if _labels_match(labels, want)]


def _delta(ev: dict, metric: str, want: Optional[dict], field=None):
    """after - before of a counter (field None) or of a histogram's
    `sum` / `count`."""
    def total(snap):
        vals = _series(snap, metric, want)
        return sum((v[field] if field else v) for v in vals), bool(vals)
    after, seen = total(ev.get("registry_after", {}))
    before, _ = total(ev.get("registry_before", {}))
    return (after - before) if seen else None


def span_median_per_job(ev: dict, r: dict):
    vals = [j["spans"][r["span"]] for j in ev.get("jobs", [])
            if r["span"] in j.get("spans", {})]
    return statistics.median(vals) * r.get("scale", 1.0) if vals else None


def histogram_mean(ev: dict, r: dict):
    total = _delta(ev, r["metric"], r.get("labels"), "sum")
    count = _delta(ev, r["metric"], r.get("labels"), "count")
    if not count:
        return None
    return total / count * r.get("scale", 1.0)


def counter_delta(ev: dict, r: dict):
    d = _delta(ev, r["metric"], r.get("labels"))
    if d is None:
        # a counter nothing has touched is a delta of 0 only where the
        # file says the count is expected to be absent (compilations)
        return 0.0 if r.get("absent_is_zero") else None
    return d * r.get("scale", 1.0)


def job_ratio(ev: dict, r: dict):
    """sum(numerator field) over sum(denominator fields) across jobs."""
    jobs = ev.get("jobs", [])
    if not jobs or r["numerator"] not in jobs[0]:
        return None
    num = sum(j[r["numerator"]] for j in jobs)
    den = sum(sum(j[f] for f in r["denominator"]) for j in jobs)
    return num / den * r.get("scale", 1.0) if den else None


def job_range_pct(ev: dict, r: dict):
    """(largest - smallest) over the median of a job field, in %."""
    vals = [j[r["field"]] for j in ev.get("jobs", []) if r["field"] in j]
    if len(vals) < 2:
        return None
    return 100.0 * (max(vals) - min(vals)) / statistics.median(vals)


def job_median(ev: dict, r: dict):
    """Median of a job field over the window's jobs."""
    vals = [j[r["field"]] for j in ev.get("jobs", []) if r["field"] in j]
    return statistics.median(vals) * r.get("scale", 1.0) if vals else None


def evidence_value(ev: dict, r: dict):
    node: Any = ev
    for key in r["path"]:
        if not isinstance(node, dict) or node.get(key) is None:
            return None
        node = node[key]
    return node * r.get("scale", 1.0)


def trace_idle_pct(ev: dict, r: dict):
    t = ev.get("trace")
    return t["idle_pct"] if t else None


def trace_kernel_time(ev: dict, r: dict):
    """Device seconds of the matching operations or programs, per event
    or per `per` units (a shapes key), scaled."""
    t = ev.get("trace")
    if not t:
        return None
    n, seconds = trace_reduce.kernel_time(t, r["pattern"],
                                          r.get("level", "ops"))
    if not n:
        return None
    per = r.get("per", "event")
    units = n if per == "event" else _units(ev, per)
    return seconds / units * r.get("scale", 1.0) if units else None


def _units(ev: dict, per: str) -> float:
    if per == "half_sweep":
        return 2.0 * ev["shapes"]["num_iterations"]
    raise ValueError(f"unknown unit {per!r}")


def _counts(ev: dict, r: dict, n_events: int):
    """(operations, bytes) from the count function the metric names."""
    module = importlib.import_module(f"benchmarks.counts.{r['counts']}")
    return module.counts(ev, r, n_events)


def trace_roofline(ev: dict, r: dict):
    """Share of the roofline: the least time the chip could take for the
    kernel's contract (lib/roofline.py) over the device time measured."""
    t = ev.get("trace")
    if not t:
        return None
    n, seconds = trace_reduce.kernel_time(t, r["pattern"],
                                          r.get("level", "ops"))
    if not n or seconds <= 0:
        return None
    counts = _counts(ev, r, n)
    if counts is None:
        return None
    pct, _bound = roofline.roofline_pct(counts[0], counts[1], seconds,
                                        ev["device"]["kind"])
    return pct


def trace_host_prep(ev: dict, r: dict):
    """Seconds from the traced job's start to the first matching program
    on the device."""
    t = ev.get("trace")
    if not t or not t.get("jobs"):
        return None
    first = trace_reduce.first_module_start(t, r["pattern"])
    return None if first is None else first - t["jobs"][0][0]


MENU: Dict[str, Callable[[dict, dict], Optional[float]]] = {
    f.__name__: f for f in (
        span_median_per_job, histogram_mean, counter_delta, job_ratio,
        job_range_pct, job_median, evidence_value, trace_idle_pct,
        trace_kernel_time, trace_roofline, trace_host_prep)}


def read(evidence: dict, reader: dict) -> Optional[float]:
    kind = reader["reader"]["kind"]
    if kind in MENU:
        reduce = MENU[kind]
    else:
        try:
            reduce = importlib.import_module(
                f"benchmarks.readers.{kind}").read
        except ImportError:
            raise ValueError(
                f"layer metric {reader.get('name')}: no reduction {kind!r} "
                f"on the menu {sorted(MENU)} and no benchmarks/readers/"
                f"{kind}.py") from None
    value = reduce(evidence, reader["reader"])
    return None if value is None else float(value)
