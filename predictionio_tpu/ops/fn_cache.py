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
from typing import Callable, Dict, Hashable, List, Sequence, Tuple

_CACHES: Dict[str, "OrderedDict"] = {}
#: serving scorers register entries from executor threads; the training
#: paths were loop-single-threaded but the ledger no longer is
_LOCK = threading.Lock()

MAX_PER_FAMILY = 8


def _attributed(family: str, fn: Callable, key: Hashable = None,
                scopes: Sequence[str] = ()) -> Callable:
    """Per-family dispatch-time attribution (obs/profiler.py): each call
    of a cached compiled function adds the host's wall time around it to
    ``pio_device_dispatch_seconds_total{family}`` (what the calling
    thread spent dispatching: nothing waits for the device, so it is
    not device time; that is `pio profile`'s ``scopes``) and, when a
    micro-batch is live, into that batch's anatomy breakdown so requests
    get their amortized dispatch share (obs/anatomy.py). One
    perf_counter pair + a counter add + a contextvar read per dispatch;
    with both PIO_DISPATCH_ATTRIBUTION=0 and PIO_ANATOMY=0 a family
    that names no scopes is not wrapped at all (zero overhead).

    A family that names its `scopes` also publishes its compiled
    program's scope table (`_ScopeTable`), whatever those two say."""
    from predictionio_tpu.obs import anatomy
    from predictionio_tpu.obs.profiler import (
        dispatch_attribution_enabled, dispatch_counter,
    )

    attributed = dispatch_attribution_enabled()
    timed = attributed or anatomy.anatomy_enabled()
    if not timed and not scopes:
        return fn
    counter = dispatch_counter() if attributed else None
    table = _ScopeTable(family, key, fn, scopes) if scopes else None

    @functools.wraps(fn)
    def dispatch(*args, **kwargs):
        compiles = table.compiles() if table is not None else 0
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            if timed:
                dt = time.perf_counter() - t0
                if counter is not None:
                    counter.inc(dt, family=family)
                anatomy.note_dispatch(dt)
        if table is not None:
            table.after_dispatch(compiles, args, kwargs)
        return out
    return dispatch


class _ScopeTable:
    """Publishes which HLO instruction of a family's compiled program
    belongs to which of its ``jax.named_scope`` names
    (obs/profiler.publish_scope_table), after the entry's first dispatch
    and after any later one during which the compiler built something
    (a key that leaves out a shape: `models/als._cached_train_fn`).

    Nothing is traced, compiled or loaded for it: after a call,
    ``fn.lower(<the call's shapes>).compile()`` is the lowering and the
    executable that call made (jit keeps both; JAX reports the looked-up
    trace as an event of some tens of microseconds). The shapes are read
    off the call's own arguments after it returned: a donated array
    still knows its shape, dtype and sharding. An argument the caller
    did not commit to a device is lowered without a sharding, as the
    call lowered it, or the lookup would miss and compile. A failure is
    logged and never reaches the caller."""

    def __init__(self, family: str, key: Hashable, fn: Callable,
                 scopes: Sequence[str]):
        from predictionio_tpu.obs import jax_stats

        self.family, self.key, self.fn = family, key, fn
        self.scopes = tuple(scopes)
        self.made = False
        jax_stats.listen_to_compiler()
        #: executables the compiler has built or loaded in this process
        self.compiles = jax_stats.backend_compile_count

    def after_dispatch(self, compiles_before: int, args, kwargs) -> None:
        if self.made and self.compiles() == compiles_before:
            return
        self.made = True
        try:
            self._publish(args, kwargs)
        except Exception:
            import logging

            logging.getLogger(__name__).warning(
                "no scope table for %s", self.family, exc_info=True)

    def _publish(self, args, kwargs) -> None:
        import jax

        from predictionio_tpu.obs.profiler import publish_scope_table

        def shape_of(leaf):
            if not isinstance(leaf, jax.Array):
                return leaf
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
                sharding=leaf.sharding if leaf.committed else None)

        t0 = time.perf_counter()
        compiles = self.compiles()
        args, kwargs = jax.tree.map(shape_of, (args, kwargs))
        lowered = self.fn.lower(*args, **kwargs)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        text = compiled.as_text()
        t3 = time.perf_counter()
        table = publish_scope_table(
            self.family, self.key, self.scopes, text,
            {"lower": t1 - t0, "compile": t2 - t1, "text": t3 - t2})
        #: executables the compiler built or loaded while the table was
        #: made: 0 where the lookup hit (`benchmarks/tools/scope_probe`)
        table["compiled"] = self.compiles() - compiles


def _cached(family: str, key: Hashable, build: Callable[[], Callable],
            max_entries: int, scopes: Sequence[str] = ()) -> Callable:
    with _LOCK:
        cache = _CACHES.setdefault(family, OrderedDict())
        fn = cache.get(key)
        if fn is not None:
            cache.move_to_end(key)
            return fn
    fn = _attributed(family, build(), key, scopes)
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
                   build: Callable[[], Callable],
                   scopes: Sequence[str] = ()) -> Callable:
    """The compiled fn for (family, mesh, static_key), building it on
    first use. `mesh` participates in the key directly (jax.sharding.Mesh
    is hashable by devices+axis names — no id() aliasing). Bounded LRU
    per family so long-lived servers retraining on growing data don't
    accumulate executables forever.

    `scopes`: the ``jax.named_scope`` names of the program `build`
    returns (a jitted function). A family that gives them publishes, once
    per compiled program, which instruction belongs to which scope, and a
    capture's device time can be read by scope (obs/profiler.py); one
    that gives none gets no table and pays nothing."""
    return _cached(family, (mesh, static_key), build, MAX_PER_FAMILY,
                   scopes)


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
