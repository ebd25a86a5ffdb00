"""Request tracing: request IDs + contextvar span API + cross-hop carry.

Every request through the observability middleware gets a request ID
(taken from an incoming ``X-Request-ID`` header or generated) and an
active :class:`Trace` carried in a :mod:`contextvars` context, so
``span("predict")`` anywhere below the handler records a named stage
timing without threading arguments through every signature.

Beyond the original per-request contextvar, a trace now has an IDENTITY
that survives process and thread boundaries (obs/trace_context.py): a
``trace_id``/``span_id`` pair. Thread hops that used to drop the
request's trace (the WriteBuffer writer thread, the MicroBatcher
executor, the fold-in apply) capture it with :func:`capture_context`
and re-enter it on the worker thread with :func:`carried`, so the
flush/batch span is linked to the submitting request in the flight
recorder. Whole processes adopt a parent's context from the
``PIO_TRACE_CONTEXT`` env var with :func:`adopt` (batchpredict/train
shards), so one trace id stitches a fleet run end to end.

A span is a timed record: name, start and end on one monotonic clock
(``time.perf_counter_ns``) and the span that was open when it started,
kept in memory on the active :class:`Trace` and handed to the flight
recorder when the job or hop ends. Span timings feed three places: the
active trace (slow-request log lines, the flight recorder), the owning
registry's ``pio_span_duration_seconds`` histogram (``/metrics``), and --
once jax is imported -- the profiler's own trace, as a
``jax.profiler.TraceAnnotation`` named ``pio:<span>``, so that a capture
shows the program's spans on the time base of the device lines.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import logging
import os
import sys
import time
import uuid
from typing import Dict, List, Optional

from predictionio_tpu.obs.registry import MetricsRegistry, default_registry
from predictionio_tpu.obs.trace_context import (
    TraceContext, new_span_id, recorder,
)

logger = logging.getLogger("pio.obs")

REQUEST_ID_HEADER = "X-Request-ID"

#: env kill-switch for the tracing layer (metrics stay on)
TRACING_ENV = "PIO_TRACING"


def tracing_enabled() -> bool:
    return os.environ.get(TRACING_ENV, "1").strip().lower() not in (
        "0", "false", "no", "off")


_request_id_var: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("pio_request_id", default=None)
_trace_var: contextvars.ContextVar[Optional["Trace"]] = \
    contextvars.ContextVar("pio_trace", default=None)
#: the span open in this context (the parent of the next one); a context
#: variable, not a field of the trace: tasks of one request share its
#: trace, and each has its own innermost span
_open_var: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("pio_open_span", default=None)

#: prefix of the profiler annotations span() writes; a capture's host
#: plane holds ``pio:<span>`` beside the runtime's own events
ANNOTATION_PREFIX = "pio:"
#: timed records one hop hands to the flight recorder (the ring is
#: bounded by records, so each record is bounded too)
MAX_TIMELINE = 256

_annotation_cls = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation("pio:<name>")`` iff jax is already
    imported (a bare event server must not import it from here); costs a
    fraction of a microsecond while no profiler session runs."""
    global _annotation_cls
    if _annotation_cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        _annotation_cls = profiler.TraceAnnotation
    return _annotation_cls(ANNOTATION_PREFIX + name)


def new_request_id() -> str:
    return uuid.uuid4().hex


def current_request_id() -> Optional[str]:
    return _request_id_var.get()


def current_trace() -> Optional["Trace"]:
    return _trace_var.get()


def span_histogram(registry: MetricsRegistry):
    """Resolve the span histogram once (callers on hot paths cache this)."""
    return registry.histogram(
        "pio_span_duration_seconds",
        "Per-stage wall time recorded by span()", labelnames=("span",))


class Span:
    """One timed record of a trace: start and end in
    ``time.perf_counter_ns`` ticks, and the span that enclosed it."""

    __slots__ = ("name", "start_ns", "end_ns", "parent")

    def __init__(self, name: str, start_ns: int, end_ns: Optional[int],
                 parent: Optional["Span"]):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = end_ns          # None while the span is open
        self.parent = parent

    @property
    def seconds(self) -> float:
        return ((self.end_ns or self.start_ns) - self.start_ns) * 1e-9


class Trace:
    """Per-request (or per-job/per-hop) span accumulator with identity."""

    __slots__ = ("request_id", "registry", "span_hist", "spans",
                 "trace_id", "span_id", "parent_span_id")

    def __init__(self, request_id: str,
                 registry: Optional[MetricsRegistry] = None,
                 span_hist=None,
                 context: Optional[TraceContext] = None):
        self.request_id = request_id
        self.registry = registry
        #: pre-resolved pio_span_duration_seconds handle — span() exits on
        #: the query hot path must not take the registry lock per call
        self.span_hist = span_hist
        #: in start order, so a span's parent always precedes it
        self.spans: List[Span] = []
        # identity: adopt the carried context (this hop is a child of the
        # carrier), else the request id IS the trace id (root)
        if context is not None:
            self.trace_id = context.trace_id
            self.parent_span_id = context.span_id
        else:
            self.trace_id = request_id
            self.parent_span_id = None
        self.span_id = new_span_id()

    def add(self, name: str, seconds: float) -> None:
        """A stage timed by the caller's own clock: it ended now and
        lasted ``seconds``, under whatever span is open here."""
        end = time.perf_counter_ns()
        self.spans.append(Span(name, end - int(seconds * 1e9), end,
                               _open_var.get()))

    def spans_by_name(self) -> Dict[str, float]:
        """Seconds per span name. A span nested in one of its own name
        is inside that one's seconds already and is not added again."""
        out: Dict[str, float] = {}
        for s in self.spans:
            up = s.parent
            while up is not None and up.name != s.name:
                up = up.parent
            if up is None:
                out[s.name] = out.get(s.name, 0.0) + s.seconds
        return out

    def self_seconds(self) -> Dict[str, float]:
        """Seconds per span name that no child span covers: a span's
        duration minus the union of its children's intervals."""
        children: Dict[int, List[Span]] = {}
        for s in self.spans:
            if s.parent is not None and s.end_ns is not None:
                children.setdefault(id(s.parent), []).append(s)
        out: Dict[str, float] = {}
        for s in self.spans:
            if s.end_ns is None:
                continue
            covered, reach = 0, s.start_ns
            for c in sorted(children.get(id(s), ()),
                            key=lambda c: c.start_ns):
                lo, hi = max(c.start_ns, reach), min(c.end_ns, s.end_ns)
                if hi > lo:
                    covered += hi - lo
                    reach = hi
            out[s.name] = out.get(s.name, 0.0) \
                + (s.end_ns - s.start_ns - covered) * 1e-9
        return out

    def timeline(self, origin_ns: int) -> List[dict]:
        """The closed spans as JSON-ready rows: seconds from
        ``origin_ns``, and the parent as an index into the same list."""
        rows, index = [], {}
        for s in self.spans:
            if len(rows) >= MAX_TIMELINE:
                break
            if s.end_ns is None:
                continue
            index[id(s)] = len(rows)
            rows.append({
                "name": s.name,
                "start": round((s.start_ns - origin_ns) * 1e-9, 6),
                "end": round((s.end_ns - origin_ns) * 1e-9, 6),
                "parent": index.get(id(s.parent))})
        return rows

    def context(self) -> TraceContext:
        """This trace's position as a carryable context (the hop a child
        span/process attaches under)."""
        return TraceContext(self.trace_id, self.span_id)


def start_trace(request_id: str,
                registry: Optional[MetricsRegistry] = None,
                span_hist=None,
                context: Optional[TraceContext] = None):
    """Install a fresh trace + request id; returns tokens for
    :func:`reset_trace`."""
    trace = Trace(request_id, registry, span_hist, context=context)
    return (_request_id_var.set(request_id), _trace_var.set(trace),
            _open_var.set(None)), trace


def reset_trace(tokens) -> None:
    rid_token, trace_token, open_token = tokens
    _request_id_var.reset(rid_token)
    _trace_var.reset(trace_token)
    _open_var.reset(open_token)


def capture_context() -> Optional[TraceContext]:
    """The active trace's carryable context (None outside a trace) — the
    cheap contextvar read a submit path does so a worker thread can later
    :func:`carried` into the same trace."""
    trace = _trace_var.get()
    return trace.context() if trace is not None else None


@contextlib.contextmanager
def carried(context: Optional[TraceContext], name: str,
            registry: Optional[MetricsRegistry] = None,
            span_hist=None, record: bool = True,
            attrs: Optional[dict] = None):
    """Re-enter a captured trace context on another thread.

    Installs a child Trace of ``context`` (or a fresh root when the
    submitter had none) named ``name``; ``span()`` calls inside link to
    the originating request's trace id, and on exit the hop is recorded
    in the flight recorder (``record=False`` skips — e.g. per-batch hops
    that would flood the ring under load record selectively)."""
    rid = context.trace_id if context is not None else new_request_id()
    tokens, trace = start_trace(rid, registry, span_hist, context=context)
    t0 = time.perf_counter_ns()
    status = "ok"
    try:
        yield trace
    except BaseException:
        status = "error"
        raise
    finally:
        reset_trace(tokens)
        if record:
            recorder().record_span(
                trace_id=trace.trace_id, span_id=trace.span_id,
                parent_span_id=trace.parent_span_id, name=name,
                duration_s=(time.perf_counter_ns() - t0) * 1e-9,
                spans=trace.spans_by_name(), status=status, attrs=attrs,
                timeline=trace.timeline(t0))


@contextlib.contextmanager
def adopt(name: str, context: Optional[TraceContext] = None,
          registry: Optional[MetricsRegistry] = None,
          attrs: Optional[dict] = None):
    """Run a whole job (train, eval, a batchpredict shard) as one trace.

    ``context=None`` reads ``PIO_TRACE_CONTEXT`` from the environment —
    a shard spawned by a parent run joins the parent's trace — and
    falls back to the ACTIVE trace context: a workflow invoked
    in-process by a traced parent (an orchestrator cycle running
    run_train/run_evaluation as phases) joins the parent's trace id
    instead of starting a fresh root. A standalone run becomes a root.
    The job is recorded in the flight recorder on exit either way.

    A job's spans reach ``pio_span_duration_seconds`` of ``registry``
    (default: the process registry) without a ``registry=`` at each call
    site: the histogram is resolved here, once."""
    if registry is None:
        registry = default_registry()
    if context is None:
        from predictionio_tpu.obs.trace_context import from_env

        context = from_env()
        if context is None:
            context = capture_context()
    with carried(context, name, registry=registry,
                 span_hist=span_histogram(registry), attrs=attrs) as trace:
        yield trace


class span:
    """Record this block as a named, timed stage of the current request
    or job: a :class:`Span` on the active trace (parent: the span open
    around it), a sample of ``pio_span_duration_seconds``, and a
    ``pio:<name>`` annotation in the profiler's trace. Without a trace,
    a registry or jax it does nothing but read the clock."""

    __slots__ = ("name", "registry", "_trace", "_record", "_annotation",
                 "_t0")

    def __init__(self, name: str,
                 registry: Optional[MetricsRegistry] = None):
        self.name = name
        self.registry = registry

    def __enter__(self) -> "span":
        self._annotation = _annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        self._record = None
        self._t0 = time.perf_counter_ns()
        self._trace = trace = _trace_var.get()
        if trace is not None:
            self._record = Span(self.name, self._t0, None, _open_var.get())
            trace.spans.append(self._record)
            _open_var.set(self._record)
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter_ns()
        trace = self._trace
        if trace is not None:
            self._record.end_ns = end
            _open_var.set(self._record.parent)
        hist = _span_hist(trace, self.registry)
        if hist is not None:
            hist.observe((end - self._t0) * 1e-9, span=self.name)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def _span_hist(trace: Optional[Trace], registry: Optional[MetricsRegistry]):
    """The span histogram a stage's sample goes to: the given registry's,
    else the one the active trace resolved, else none."""
    if registry is not None:
        return span_histogram(registry)
    if trace is None:
        return None
    if trace.span_hist is None and trace.registry is not None:
        return span_histogram(trace.registry)
    return trace.span_hist


def timed_stage(name: str, seconds: float, hist=None) -> None:
    """A stage timed by the caller's own clock, on whatever thread spent
    it: it lasted ``seconds``. One sample of
    ``pio_span_duration_seconds{span=name}`` (``hist``: a handle a hot
    path resolved once; else as :class:`span` finds it) and one row on
    the active trace under the span open here (:meth:`Trace.add`). Such
    a row has a length, not a position: its end is the moment of this
    call. No profiler annotation: a capture's host lines hold only
    blocks that were open on the clock."""
    trace = _trace_var.get()
    if hist is None:
        hist = _span_hist(trace, None)
    if hist is not None:
        hist.observe(seconds, span=name)
    if trace is not None:
        trace.add(name, seconds)


def log_slow_request(service: str, method: str, path: str, status: int,
                     duration_s: float, trace: Optional[Trace]) -> None:
    """One structured line per over-threshold request (see
    OBSERVABILITY.md for the format contract)."""
    payload = {
        "requestId": trace.request_id if trace else None,
        "traceId": trace.trace_id if trace else None,
        "service": service,
        "method": method,
        "path": path,
        "status": status,
        "durationSec": round(duration_s, 6),
        "spans": {name: round(secs, 6) for name, secs in
                  (trace.spans_by_name() if trace else {}).items()},
    }
    logger.warning("slow request %s", json.dumps(payload, sort_keys=True))
