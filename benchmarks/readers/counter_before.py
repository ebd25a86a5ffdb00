"""A counter's total when the window opens: what set-up (the warming
job and everything before it) added to it. None when the program has no
such counter."""

from __future__ import annotations

from benchmarks.lib import layer_readers


def read(evidence: dict, reader: dict):
    values = layer_readers._series(evidence.get("registry_before", {}),
                                   reader["metric"], reader.get("labels"))
    return sum(values) * reader.get("scale", 1.0) if values else None
