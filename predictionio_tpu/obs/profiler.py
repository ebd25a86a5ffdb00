"""On-demand device profiling: bounded jax.profiler captures +
per-compile-family dispatch-time attribution.

"Which compiled family is eating the TPU" must be answerable in
production without redeploying instrumented code. Two mechanisms:

* **dispatch attribution** — ``ops.fn_cache`` wraps every cached
  compiled function so each dispatch's wall time lands in
  ``pio_device_dispatch_seconds_total{family}`` (a seconds counter:
  rate() it for device utilization per family; divide by the family's
  call count for mean dispatch time). Always cheap (one perf_counter
  pair + a counter add per dispatch); ``PIO_DISPATCH_ATTRIBUTION=0``
  disables the wrap entirely.

* **bounded trace capture** — :func:`capture` runs ``jax.profiler``
  (Python tracer off: the host plane holds the program's ``pio:<span>``
  annotations beside the runtime's events, on the device lines' time
  base) for a capped duration and returns the trace directory, exposed
  as ``POST /debug/profile`` on the query server and ``pio profile``.
  One capture at a time (a second request gets a busy error), duration
  clamped to :data:`MAX_CAPTURE_S` — an operator can never wedge a
  serving box with an unbounded profile.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Dict, Optional

from predictionio_tpu.obs.registry import MetricsRegistry, default_registry

DISPATCH_ENV = "PIO_DISPATCH_ATTRIBUTION"
DISPATCH_COUNTER = "pio_device_dispatch_seconds_total"

MAX_CAPTURE_S = 60.0

_capture_lock = threading.Lock()


def dispatch_attribution_enabled() -> bool:
    return os.environ.get(DISPATCH_ENV, "1").strip().lower() not in (
        "0", "false", "no", "off")


def dispatch_counter(registry: Optional[MetricsRegistry] = None):
    """The family-labelled device-dispatch seconds counter."""
    return (registry or default_registry()).counter(
        DISPATCH_COUNTER,
        "Wall seconds spent dispatching compiled functions, per fn_cache "
        "family (device attribution: rate() = share of device time)",
        labelnames=("family",))


def dispatch_table(registry: Optional[MetricsRegistry] = None
                   ) -> Dict[str, float]:
    """Seconds per family, highest first — the \"who is eating the
    device\" answer."""
    metric = (registry or default_registry()).get(DISPATCH_COUNTER)
    if metric is None:
        return {}
    table = {labels.get("family", "?"): value
             for labels, value in metric.samples()}
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


class ProfileBusy(Exception):
    """A capture is already running; exactly one at a time."""


def capture(seconds: float, outdir: Optional[str] = None) -> dict:
    """Run a bounded jax.profiler trace; returns {traceDir, seconds,
    dispatch} (the dispatch table rides along so one call answers both
    \"what ran\" and \"who ate the time\").

    Raises :class:`ProfileBusy` when a capture is in flight and
    RuntimeError when jax's profiler is unavailable. The sleep happens
    INSIDE the trace window — callers run this off the event loop."""
    seconds = min(max(0.01, float(seconds)), MAX_CAPTURE_S)
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusy("a profile capture is already running")
    try:
        import jax

        trace_dir = outdir or tempfile.mkdtemp(prefix="pio-profile-")
        t0 = time.perf_counter()
        # the Python tracer would write an event per Python call of the
        # host loop and bury the program's own `pio:<span>` annotations
        # (obs/tracing.span), which are what names the host plane
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        return {
            "traceDir": trace_dir,
            "seconds": round(time.perf_counter() - t0, 3),
            "dispatch": dispatch_table(),
        }
    except ImportError as e:
        raise RuntimeError(f"jax profiler unavailable: {e}") from e
    finally:
        _capture_lock.release()
