"""aiohttp observability: request middleware + /metrics + /debug routes.

``observability_middleware(registry, service)`` gives every request a
request ID (honouring an incoming ``X-Request-ID``), opens a trace for
the ``span()`` API, times the handler into
``pio_http_request_duration_seconds{service,method,handler,status}``,
tracks in-flight requests, and emits a structured slow-request log line
when the wall time crosses the threshold (``PIO_SLOW_REQUEST_SECONDS``,
default 1.0 s).

Cross-process propagation: an incoming ``X-Pio-Trace`` header
(``trace_id:span_id``) makes the request a CHILD of the carrier's trace
— the event server's request, a fold-in apply it triggers, and the swap
that follows all share one trace id. The response echoes the request's
own context in the same header, and every completed request is recorded
in the in-memory flight recorder, exposed at ``GET /debug/traces.json``
(and via ``pio traces``). ``PIO_TRACING=0`` disables the trace layer
(no contextvars, no recorder writes) while keeping every metric.

``add_metrics_routes(app, *registries)`` mounts ``GET /metrics``
(Prometheus text exposition 0.0.4), ``GET /metrics.json``, and
``GET /debug/traces.json`` rendering the given registries merged — by
convention the server's own registry first, then
:func:`default_registry` so workflow/JAX process metrics ride along on
every scrape.  The endpoints are deliberately unauthenticated (scrapers
hold no access keys); they expose aggregate counts and bounded trace
rings only.
"""

from __future__ import annotations

import logging
import os
import time

from aiohttp import web

from predictionio_tpu.obs.registry import (
    PROMETHEUS_CONTENT_TYPE, MetricsRegistry, default_registry,
    render_json, render_prometheus,
)
from predictionio_tpu.obs.trace_context import (
    TRACE_HEADER, TraceContext, recorder,
)
from predictionio_tpu.obs.tracing import (
    REQUEST_ID_HEADER, log_slow_request, new_request_id, reset_trace,
    span_histogram, start_trace, tracing_enabled,
)

logger = logging.getLogger("pio.obs")

DEFAULT_SLOW_REQUEST_SECONDS = 1.0


def slow_request_threshold() -> float:
    try:
        return float(os.environ.get("PIO_SLOW_REQUEST_SECONDS",
                                    DEFAULT_SLOW_REQUEST_SECONDS))
    except ValueError:
        return DEFAULT_SLOW_REQUEST_SECONDS


def _handler_label(request: web.Request) -> str:
    """Route template, not raw path — bounds label cardinality."""
    try:
        resource = request.match_info.route.resource
        if resource is not None:
            return resource.canonical
    except Exception:
        pass
    return "__unmatched__"


def observability_middleware(registry: MetricsRegistry, service: str,
                             slow_threshold_s: float = None):
    if slow_threshold_s is None:
        slow_threshold_s = slow_request_threshold()
    duration = registry.histogram(
        "pio_http_request_duration_seconds",
        "HTTP request wall time by service/method/handler/status",
        labelnames=("service", "method", "handler", "status"))
    in_flight = registry.gauge(
        "pio_http_requests_in_flight",
        "Requests currently being handled", labelnames=("service",))
    spans = span_histogram(registry)
    flight = recorder()

    @web.middleware
    async def middleware(request, handler):
        request_id = request.headers.get(REQUEST_ID_HEADER) or new_request_id()
        traced = tracing_enabled()
        tokens = trace = None
        if traced:
            parent = TraceContext.decode(request.headers.get(TRACE_HEADER))
            tokens, trace = start_trace(request_id, registry, spans,
                                        context=parent)
        in_flight.inc(service=service)
        t0 = time.perf_counter()
        status = 500
        try:
            response = await handler(request)
            status = response.status
            response.headers[REQUEST_ID_HEADER] = request_id
            if trace is not None:
                response.headers[TRACE_HEADER] = trace.context().encode()
            return response
        except web.HTTPException as exc:
            status = exc.status
            exc.headers[REQUEST_ID_HEADER] = request_id
            raise
        except Exception:
            # aiohttp's stock 500 carries no headers — answer ourselves so
            # crash responses still carry the correlation id
            logger.exception("unhandled error in %s %s %s",
                             service, request.method, request.path)
            return web.json_response(
                {"message": "Internal Server Error"}, status=500,
                headers={REQUEST_ID_HEADER: request_id})
        finally:
            in_flight.dec(service=service)
            dt = time.perf_counter() - t0
            handler_label = _handler_label(request)
            duration.observe(dt, service=service, method=request.method,
                             handler=handler_label,
                             status=str(status))
            if dt >= slow_threshold_s:
                log_slow_request(service, request.method, request.path,
                                 status, dt, trace)
            if trace is not None:
                flight.record_span(
                    trace_id=trace.trace_id, span_id=trace.span_id,
                    parent_span_id=trace.parent_span_id,
                    name=f"{service} {request.method} {handler_label}",
                    duration_s=dt, spans=trace.spans_by_name(),
                    status="ok" if status < 500 else "error")
                reset_trace(tokens)

    return middleware


METRICS_PATHS = ("/metrics", "/metrics.json", "/debug/traces.json")


def add_metrics_routes(app: web.Application,
                       *registries: MetricsRegistry) -> None:
    regs = tuple(registries) or (default_registry(),)

    async def handle_metrics(request):
        text = render_prometheus(regs)
        return web.Response(body=text.encode("utf-8"),
                            headers={"Content-Type": PROMETHEUS_CONTENT_TYPE})

    async def handle_metrics_json(request):
        return web.json_response(render_json(regs))

    async def handle_traces(request):
        trace_id = request.query.get("traceId")
        try:
            limit = int(request.query["limit"]) \
                if "limit" in request.query else None
        except ValueError:
            limit = None
        since_ts = None
        try:
            if "sinceS" in request.query:
                since_ts = time.time() - float(request.query["sinceS"])
        except ValueError:
            pass
        return web.json_response(recorder().to_json(trace_id, limit,
                                                    since_ts))

    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/metrics.json", handle_metrics_json)
    app.router.add_get("/debug/traces.json", handle_traces)
