"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
share, device time by operation, module (compiled program) events, and
the idle gaps named by what the host was doing.

Layout of a TPU trace, read off a recorded one (tests/data): each
`/device:TPU:<n>` plane has a line "XLA Ops" (one event per HLO
operation, names are whole HLO instructions) and a line "XLA Modules"
(one event per executed program, `jit_<fn>(<hash>)`); the `/host:CPU`
plane has one line per thread, where `jax.profiler.TraceAnnotation`
spans appear under their own names. Device and host lines share one
time base to within about a millisecond.

Only this module touches jax.profiler.ProfileData; everything after
`load` works on plain lists and is tested on the recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the host annotations kept: the benchmark's own start with `bench_`,
#: the program's spans (obs/tracing.span) with `pio:`
ANNOTATION_PREFIXES = ("bench_", "pio:")

#: control-flow operations span the operations inside them; they count
#: towards busy time (a union) but are no entry of the top list
CONTAINER_OPS = ("while", "conditional", "call")

#: consecutive operations sit nanoseconds apart; that is no idle gap
MIN_GAP_S = 1e-6

Event = Tuple[str, float, float]          # name, start_s, duration_s


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_op_name(hlo: str) -> str:
    """`%fusion.75 = f32[..] fusion(..)` -> `fusion.75`; a custom call
    also carries its target: `custom-call.3_TopK`."""
    m = re.match(r"%?([^\s=]+)", hlo)
    name = m.group(1) if m else hlo[:60]
    t = re.search(r'custom_call_target="([^"]+)"', hlo)
    if t and t.group(1) not in name:
        name = f"{name}_{t.group(1)}"
    return name


def load(path: str) -> dict:
    """Read an .xplane.pb into plain lists:
    {"devices": {plane: {"ops": [Event], "modules": [Event]}},
     "annotations": [Event]} with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "annotations": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    dev[key].append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIXES):
                        out["annotations"].append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    out["annotations"].sort(key=lambda ev: ev[1])
    return out


def union_s(events: Sequence[Event]) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of the events' intervals, and the merged
    intervals themselves."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    merged: List[List[float]] = []
    for lo, hi in spans:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return sum(hi - lo for lo, hi in merged), [(a, b) for a, b in merged]


def _innermost(annotations: Sequence[Event], t: float) -> Optional[str]:
    best = None
    for name, s, d in annotations:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else None


def _span_name(annotation: str) -> str:
    """`pio:ingest_scan` and `bench_host:prepare` -> the span's own name."""
    if ":" in annotation:
        return annotation.split(":", 1)[1]
    return annotation[len("bench_"):]


def gap_pieces(annotations: Sequence[Event], lo: float, hi: float,
               position: str) -> List[Tuple[str, float]]:
    """An idle gap cut where the innermost host annotation changes: each
    piece is named by the span the host was in, a piece under no
    annotation by the gap's `position` among the device operations."""
    cuts = sorted({lo, hi} | {t for _, s, d in annotations
                              for t in (s, s + d) if lo < t < hi})
    pieces: List[List] = []
    for a, b in zip(cuts, cuts[1:]):
        note = _innermost(annotations, (a + b) / 2)
        name = _span_name(note) if note else position
        if pieces and pieces[-1][0] == name:
            pieces[-1][1] += b - a
        else:
            pieces.append([name, b - a])
    return [(n, t) for n, t in pieces]


def reduce(trace: dict, window: Optional[Tuple[float, float]] = None,
           host_label: str = "host", top: int = 10) -> dict:
    """The numbers every cell's traced run reports.

    `window` bounds the traced interval (seconds on the trace's clock);
    by default it is the outermost `bench_job` annotation, else the span
    from the first to the last device event. Busy time is the union of
    "XLA Ops" intervals inside the window, averaged over the device
    planes; an idle gap is cut where the host's innermost span changes
    and each piece named `<host_label>:<span or position>`.
    """
    jobs = [a for a in trace["annotations"] if a[0] == "bench_job"]
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane: no operation "
                         "ran on the device while it was recorded")
    if window is None:
        if jobs:
            window = (min(s for _, s, _ in jobs),
                      max(s + d for _, s, d in jobs))
        else:
            starts = [e[1] for dev in devices.values() for e in dev["ops"]]
            ends = [e[1] + e[2] for dev in devices.values()
                    for e in dev["ops"]]
            window = (min(starts), max(ends))
    w0, w1 = window
    busy_per_device = []
    op_totals: Dict[str, List[float]] = {}
    module_events: List[Event] = []
    gaps: List[Tuple[str, float]] = []
    inner = [a for a in trace["annotations"] if a[0] != "bench_job"]
    for dev in devices.values():
        ops = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
               for n, s, d in dev["ops"] if s + d > w0 and s < w1]
        busy, merged = union_s(ops)
        busy_per_device.append(busy)
        for name, _, d in ops:
            tot = op_totals.setdefault(short_op_name(name), [0, 0.0])
            tot[0] += 1
            tot[1] += d
        module_events.extend((n, s, d) for n, s, d in dev["modules"]
                             if s + d > w0 and s < w1)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for i in range(0, len(edges), 2):
            lo, hi = edges[i], edges[i + 1]
            if hi - lo < MIN_GAP_S:
                continue
            where = ("before_the_first_device_op" if i == 0 else
                     "after_the_last_device_op" if i == len(edges) - 2
                     else "between_device_ops")
            gaps.extend((f"{host_label}:{name}", t)
                        for name, t in gap_pieces(inner, lo, hi, where)
                        if t >= MIN_GAP_S)
    window_s = w1 - w0
    busy_s = sum(busy_per_device) / len(busy_per_device)
    gaps.sort(key=lambda g: -g[1])
    ops_sorted = sorted(((n, c, t) for n, (c, t) in op_totals.items()),
                        key=lambda r: -r[2])
    module_events.sort(key=lambda e: e[1])
    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s > 0
        else None,
        "n_devices": len(devices),
        "window": [w0, w1],
        "ops": [[n, c, t] for n, c, t in ops_sorted],
        "modules": [[n, s, d] for n, s, d in module_events],
        "jobs": [[s, d] for _, s, d in jobs],
        "device_ops_top": [[n, t] for n, _, t in ops_sorted
                           if not n.startswith(CONTAINER_OPS)][:top],
        "idle_gaps_top": [[n, t] for n, t in gaps[:top]],
    }


def kernel_time(reduced: dict, pattern: str, level: str = "ops"
                ) -> Tuple[int, float]:
    """(events, seconds) of the device operations (`ops`) or programs
    (`modules`) whose name matches `pattern` (a regular expression)."""
    rx = re.compile(pattern)
    if level == "ops":
        rows = [(c, t) for n, c, t in reduced["ops"] if rx.search(n)]
        return sum(c for c, _ in rows), sum(t for _, t in rows)
    rows = [d for n, _, d in reduced["modules"] if rx.search(n)]
    return len(rows), sum(rows)


def first_module_start(reduced: dict, pattern: str) -> Optional[float]:
    rx = re.compile(pattern)
    for n, s, _ in reduced["modules"]:
        if rx.search(n):
            return s
    return None
