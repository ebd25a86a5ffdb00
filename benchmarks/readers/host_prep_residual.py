"""Host time before the first device program that no span names.

`trace_host_prep` (the traced job's start, on the profiler's clock, to
its first matching program on the device) minus the traced job's own
spans named in the metric file. None when there is no trace or when the
program records one of the spans not at all, as a program from before
those spans existed does: a residual over half the spans would read as
unattributed time that is in fact attributed.
"""

from __future__ import annotations

from benchmarks.lib import layer_readers


def read(evidence: dict, reader: dict):
    prep = layer_readers.trace_host_prep(evidence, reader)
    traced = [j for j in evidence.get("jobs", []) if j.get("traced")]
    if prep is None or not traced:
        return None
    spans = traced[0].get("spans", {})
    if any(name not in spans for name in reader["spans"]):
        return None
    return prep - sum(spans[name] for name in reader["spans"])
