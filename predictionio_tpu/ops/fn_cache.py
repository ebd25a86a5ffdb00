"""Shared cache for mesh-closed compiled functions.

jit's own cache keys on function identity, so any wrapper built per call
(`jax.jit(shard_map(closure, ...))`) re-traces every time. Model modules
register their builders here instead: one bounded LRU per family, keyed
on the (hashable) Mesh plus whatever static parameters shape the program.

The same machinery doubles as the serving-side compile ledger:
`shape_cached_fn` keys on static SHAPES alone (no mesh) so batch scorers
can register one entry per shape bucket — the build counter then reads
as "distinct compiled batch shapes per family", the number the bucketed
micro-batch hot path bounds at ``bucketing.bucket_count(max_batch)``
(``log2(max_batch) + 1`` for the power-of-two default).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Hashable, List, Tuple

_CACHES: Dict[str, "OrderedDict"] = {}
#: serving scorers register entries from executor threads; the training
#: paths were loop-single-threaded but the ledger no longer is
_LOCK = threading.Lock()

MAX_PER_FAMILY = 8


def _attributed(family: str, fn: Callable) -> Callable:
    """Per-family dispatch-time attribution (obs/profiler.py): each call
    of a cached compiled function adds its dispatch wall time to
    ``pio_device_dispatch_seconds_total{family}`` — the "which compiled
    family is eating the device" answer — and, when a micro-batch is
    live, into that batch's anatomy breakdown so requests get their
    amortized device-dispatch share (obs/anatomy.py). One perf_counter
    pair + a counter add + a contextvar read per dispatch; with both
    PIO_DISPATCH_ATTRIBUTION=0 and PIO_ANATOMY=0 the wrap is skipped
    entirely (zero overhead)."""
    from predictionio_tpu.obs import anatomy
    from predictionio_tpu.obs.profiler import (
        dispatch_attribution_enabled, dispatch_counter,
    )

    attributed = dispatch_attribution_enabled()
    if not attributed and not anatomy.anatomy_enabled():
        return fn
    counter = dispatch_counter() if attributed else None

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            dt = time.perf_counter() - t0
            if counter is not None:
                counter.inc(dt, family=family)
            anatomy.note_dispatch(dt)
    return dispatch


def _cached(family: str, key: Hashable, build: Callable[[], Callable],
            max_entries: int) -> Callable:
    with _LOCK:
        cache = _CACHES.setdefault(family, OrderedDict())
        fn = cache.get(key)
        if fn is not None:
            cache.move_to_end(key)
            return fn
    fn = _attributed(family, build())
    from predictionio_tpu.obs.jax_stats import compile_counter

    with _LOCK:
        cache = _CACHES.setdefault(family, OrderedDict())
        if key not in cache:
            # a first sighting of this key, not a compilation: a climbing
            # pio_jax_compile_total on a serving box flags a leak of keys,
            # what this cache exists to prevent (what the compiler built
            # is pio_jax_backend_compile_total, obs/jax_stats.py)
            compile_counter().inc(family=family)
            cache[key] = fn
            while len(cache) > max_entries:
                cache.popitem(last=False)
        else:
            fn = cache[key]
            cache.move_to_end(key)
    return fn


def mesh_cached_fn(family: str, mesh, static_key: Hashable,
                   build: Callable[[], Callable]) -> Callable:
    """The compiled fn for (family, mesh, static_key), building it on
    first use. `mesh` participates in the key directly (jax.sharding.Mesh
    is hashable by devices+axis names — no id() aliasing). Bounded LRU
    per family so long-lived servers retraining on growing data don't
    accumulate executables forever."""
    return _cached(family, (mesh, static_key), build, MAX_PER_FAMILY)


def shape_cached_fn(family: str, static_key: Hashable,
                    build: Callable[[], Callable],
                    max_entries: int = 256) -> Callable:
    """Mesh-free variant for serving scorers keyed on shape buckets.

    `build` may return a SHARED jitted function (jit's own cache then
    holds the executables), in which case this cache exists purely to
    count the first sighting of each shape key into
    ``pio_jax_compile_total{family=...}``. Keys usually combine the
    batch bucket with the other static shapes (k-bucket, catalog size,
    rank), so the per-family bound is ``bucket_count(max_batch)`` PER
    distinct (k-bucket, catalog) combination — a handful in practice.
    The default `max_entries` is deliberately far above any realistic
    live-key count: entries are cheap references, and evicting one would
    double-count its next sighting, faking the very retrace leak the
    counter exists to expose."""
    return _cached(family, static_key, build, max_entries)


def family_keys(family: str) -> List[Tuple]:
    """Snapshot of a family's live cache keys (introspection/tests)."""
    with _LOCK:
        return list(_CACHES.get(family, ()))
