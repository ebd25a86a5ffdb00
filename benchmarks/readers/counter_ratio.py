"""One counter's gain over the window as a share of several counters'
gain: `numerator` over the sum of `denominator` (metric names). None
when the program has none of them."""

from __future__ import annotations

from benchmarks.lib import layer_readers


def read(evidence: dict, reader: dict):
    def gain(metric):
        return layer_readers._delta(evidence, metric, reader.get("labels"))

    num = gain(reader["numerator"])
    den = [gain(m) for m in reader["denominator"]]
    if num is None or any(d is None for d in den) or not sum(den):
        return None
    return num / sum(den) * reader.get("scale", 1.0)
