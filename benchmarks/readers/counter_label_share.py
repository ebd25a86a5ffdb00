"""One label value's share of a labelled counter's gain over the window:
the series matching `labels` over all of `metric`'s series. 0 when the
counter moved under other labels only; None when the program has no such
counter or it did not move."""

from __future__ import annotations

from benchmarks.lib import layer_readers


def read(evidence: dict, reader: dict):
    total = layer_readers._delta(evidence, reader["metric"], None)
    if not total:
        return None
    part = layer_readers._delta(evidence, reader["metric"], reader["labels"])
    return (part or 0.0) / total * reader.get("scale", 1.0)
