"""JAX device metrics: compile counts + live device-array footprint.

Callback gauges evaluated at scrape time, deliberately gated on jax
already being imported — a /metrics scrape on a process that never
touched jax (bare event server) must not trigger backend init.

Two compile counts, and they differ. ``pio_jax_compile_total`` is the
``ops.fn_cache`` ledger: first sightings of a cache key, not
compilations (a key that leaves out a shape sees nothing when ``jit``
retraces under it). ``pio_jax_backend_compile_total{fun}`` and its
companions count what the compiler itself reports through
``jax.monitoring``: every executable built or loaded from the persistent
cache, with the seconds spent tracing, lowering and compiling.
"""

from __future__ import annotations

import sys
import threading
import time

from predictionio_tpu.obs.registry import MetricsRegistry, default_registry

COMPILE_COUNTER = "pio_jax_compile_total"

#: how long one jax.live_arrays() walk is reused across gauges — the
#: bytes and count gauges (and the capacity ledger's watermark) share a
#: single O(live-arrays) sum per window instead of one walk per gauge
#: per scrape, which matters under sub-second telemetry intervals
LIVE_BUFFER_TTL_S = 0.5

_live_lock = threading.Lock()
_live_cache = (0.0, 0.0)   # (bytes, count)
_live_cache_ts = float("-inf")
_live_walks = 0            # walks actually performed (tests assert this)
_live_watermark = 0.0      # max bytes ever seen by a walk (capacity ledger)


def compile_counter(registry: MetricsRegistry = None):
    """The (family-labelled) fn_cache ledger counter."""
    return (registry or default_registry()).counter(
        COMPILE_COUNTER,
        "First sightings of a cache key per fn_cache family, not "
        "compilations (see pio_jax_backend_compile_total)",
        labelnames=("family",))


# -- the compiler's own events (jax.monitoring) ------------------------------

BACKEND_COMPILE_COUNTER = "pio_jax_backend_compile_total"
#: distinct `fun` label values kept; the rest fold into "other"
MAX_COMPILE_FUNS = 64


class _CompilerEvents:
    """The jax.monitoring listeners behind the compiler's counters. They
    feed the process registry: jax keeps one listener list per process,
    whichever registry asked first."""

    def __init__(self, reg: MetricsRegistry):
        self.compiles = reg.counter(
            BACKEND_COMPILE_COUNTER,
            "Executables built by the backend compiler or loaded from the "
            "persistent cache, per jitted function (jax.monitoring "
            "backend_compile_duration events)", labelnames=("fun",))
        self.compile_seconds = reg.counter(
            "pio_jax_backend_compile_seconds_total",
            "Seconds in the backend compiler (or the persistent cache's "
            "load), per jitted function", labelnames=("fun",))
        self.durations = {
            "/jax/core/compile/jaxpr_trace_duration": reg.counter(
                "pio_jax_trace_seconds_total",
                "Seconds tracing Python functions to jaxprs, outermost "
                "traces only (every eager primitive traces too)"),
            "/jax/core/compile/jaxpr_to_mlir_module_duration": reg.counter(
                "pio_jax_lower_seconds_total",
                "Seconds lowering jaxprs to MLIR modules"),
        }
        #: the trace state outside every trace (the listeners are
        #: registered from plain Python): jax reports a jitted function
        #: traced inside another one's trace on its own AND inside the
        #: outer one's duration, so only traces that start here count
        self._outside = _jax().core.get_opaque_trace_state()
        self.events = {
            "/jax/compilation_cache/cache_hits": reg.counter(
                "pio_jax_persistent_cache_hits_total",
                "Executables loaded from the persistent compilation cache"),
            "/jax/compilation_cache/cache_misses": reg.counter(
                "pio_jax_persistent_cache_misses_total",
                "Executables the persistent compilation cache did not hold"),
        }
        self.count = 0
        self._funs: set = set()
        self._lock = threading.Lock()

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            fun = str(kwargs.get("fun_name") or "?")
            with self._lock:
                self.count += 1
                if fun not in self._funs:
                    if len(self._funs) < MAX_COMPILE_FUNS:
                        self._funs.add(fun)
                    else:
                        fun = "other"
            self.compiles.inc(fun=fun)
            self.compile_seconds.inc(duration, fun=fun)
            return
        counter = self.durations.get(event)
        if counter is not None and (
                _jax().core.get_opaque_trace_state() == self._outside):
            counter.inc(duration)

    def on_event(self, event: str, **kwargs) -> None:
        counter = self.events.get(event)
        if counter is not None:
            counter.inc()


_compiler_events: "_CompilerEvents | None" = None
_listen_lock = threading.Lock()


def listen_to_compiler() -> None:
    """Register the listeners once per process, and only when jax is
    already imported."""
    global _compiler_events
    jax = _jax()
    if jax is None or _compiler_events is not None:
        return
    with _listen_lock:
        if _compiler_events is not None:
            return
        from jax import monitoring

        events = _CompilerEvents(default_registry())
        monitoring.register_event_duration_secs_listener(events.on_duration)
        monitoring.register_event_listener(events.on_event)
        _compiler_events = events


def backend_compile_count() -> int:
    """Executables the backend has built or loaded since the listeners
    were registered (0 before). Read it before and after a dispatch to
    learn whether that dispatch compiled."""
    events = _compiler_events
    return events.count if events is not None else 0


def _jax():
    """jax iff something else already imported it; never init from here."""
    return sys.modules.get("jax")


def _device_count() -> float:
    jax = _jax()
    if jax is None:
        return 0.0
    try:
        return float(len(jax.devices()))
    except Exception:
        return 0.0


def live_buffer_stats(ttl_s: float = LIVE_BUFFER_TTL_S
                      ) -> "tuple[float, float]":
    """(bytes, count) over live device arrays, memoized for `ttl_s`:
    one walk serves every gauge that fires inside the window."""
    global _live_cache, _live_cache_ts, _live_walks, _live_watermark
    jax = _jax()
    if jax is None:
        return (0.0, 0.0)
    now = time.monotonic()
    with _live_lock:
        if now - _live_cache_ts < ttl_s:
            return _live_cache
        try:
            arrays = jax.live_arrays()
            stats = (float(sum(int(a.nbytes) for a in arrays)),
                     float(len(arrays)))
        except Exception:
            stats = (0.0, 0.0)
        _live_walks += 1
        _live_cache, _live_cache_ts = stats, now
        if stats[0] > _live_watermark:
            _live_watermark = stats[0]
        return stats


def live_buffer_walks() -> int:
    """How many live_arrays() walks have actually run (TTL-memoization
    observability; tests assert scrapes inside the window share one)."""
    with _live_lock:
        return _live_walks


def device_watermark_bytes() -> float:
    """High-water mark of live device-array bytes seen by any walk since
    process start — the capacity ledger's 'how close did we get' gauge."""
    with _live_lock:
        return _live_watermark


def _live_buffer_bytes() -> float:
    return live_buffer_stats()[0]


def _live_buffer_count() -> float:
    return live_buffer_stats()[1]


def register_jax_metrics(registry: MetricsRegistry = None) -> MetricsRegistry:
    """Idempotently register the device gauges (+ the compile counter so
    it renders even before the first build) and, once jax is imported,
    the listeners behind the compiler's own counters."""
    reg = registry or default_registry()
    compile_counter(reg)
    listen_to_compiler()
    reg.gauge_callback("pio_jax_device_count",
                       "Visible JAX devices", _device_count)
    reg.gauge_callback("pio_jax_live_buffer_bytes",
                       "Bytes held by live device arrays",
                       _live_buffer_bytes)
    reg.gauge_callback("pio_jax_live_buffer_count",
                       "Number of live device arrays", _live_buffer_count)
    return reg
