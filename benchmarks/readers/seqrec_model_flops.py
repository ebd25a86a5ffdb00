"""Model FLOP/s utilization of the sequence model's steps: the model's
own operations for a train's tokens (counts/seqrec_model.py, the routed
slots from the program's counter) over the median `seqrec_steps` span of
the window's trains and the chip's peak (peaks.json), in %. None when the
program has no such span or counter, or on a device without a peak."""

from __future__ import annotations

import importlib

from benchmarks.lib import layer_readers, roofline


def read(evidence: dict, reader: dict):
    seconds = layer_readers.span_median_per_job(evidence, reader)
    slots = layer_readers._delta(evidence, reader["slots_metric"], None)
    jobs = evidence.get("jobs", [])
    shapes = evidence.get("shapes") or {}
    if not seconds or slots is None or not jobs or not shapes.get("steps"):
        return None
    try:
        peak = roofline.peaks(evidence["device"]["kind"])["flops_per_s"]
    except roofline.UnknownDevice:
        return None
    ops = importlib.import_module(
        f"benchmarks.counts.{reader['counts']}").counts(
        shapes, slots / len(jobs))
    return 100.0 * ops / seconds / peak
