"""Model blob (de)serialization.

Replaces the reference's Kryo/chill model blob machinery
(core/.../workflow/CoreWorkflow.scala:76-81, CreateServer.scala:62-76): every
model is a picklable Python object. A ``jax.Array`` anywhere in a model is
written as the numpy array of its value, so blobs are host-portable and
load without devices, with numpy leaves.

A release is written in one pass: ``dump_models`` pickles into whatever
writable it is given (protocol 5 hands each array's buffer to ``write``
without copying it), and ``DigestingWriter`` sits between the pickler and
the model store's file, counting the bytes and feeding each buffer to a
sha256 on a worker thread, so the digest of exactly the stored bytes is
ready when the file closes and nobody reads the blob a second time.
``serialize_models`` is the same stream into memory.

Nothing is pulled from a device up front. The pickler takes a device
array's host copy when it meets the array in the object graph, waiting for
that leaf alone, so a trainer that started its weights' copies
(``copy_to_host_async``) has the first leaves on their way to the file
while the rest are still in flight. The stream is the one the same model
with numpy leaves gives, byte for byte.

Both threads of a write account for their own seconds, each sum taken
with ``time.perf_counter`` on the thread that spends it: the pickling
thread's wait for leaves (``_ReleasePickler.wait_seconds``), its wait
for room in the digest thread's queue, its time inside the store's
``write`` and the slowest single ``write``; the digest thread's time in
``sha256.update`` and its wait for the next buffer. ``CollectorPauses``
adds the cyclic collector's runs on the pickling thread, which it takes
out of whichever of those clocks they fell inside.
"""

from __future__ import annotations

import gc
import hashlib
import io
import pickle
import queue
import sys
import threading
import time
from typing import Any, BinaryIO, List, NamedTuple, Optional

import numpy as np


class _RetrainSentinel:
    """Marks an algorithm slot whose model is retrained at deploy
    (the reference's Unit model, PAlgorithm.scala:112)."""

    def __repr__(self):
        return "RETRAIN_ON_DEPLOY"


RETRAIN_ON_DEPLOY = _RetrainSentinel()


class DeviceFetch(NamedTuple):
    """What one dump took from devices."""

    #: bytes that reached the pickler as device arrays
    device_bytes: int
    #: time the pickling thread waited for host copies still in flight
    wait_seconds: float


class CollectorPauses:
    """Seconds the cyclic collector ran on the thread that entered this
    block, while the block was open (``gc.callbacks``: hooked on entry,
    unhooked on exit, so nothing is hooked outside a persist). A clock
    that may have a collection inside it reads ``seconds`` at both its
    ends and takes the growth out of its own reading, so a pause counts
    once, here."""

    def __init__(self):
        self.seconds = 0.0
        self._thread: Optional[int] = None
        self._started: Optional[float] = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if threading.get_ident() != self._thread:
            return
        if phase == "start":
            self._started = time.perf_counter()
        elif self._started is not None:
            self.seconds += time.perf_counter() - self._started
            self._started = None

    def __enter__(self) -> "CollectorPauses":
        self._thread = threading.get_ident()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        gc.callbacks.remove(self._on_gc)


#: no collector is watched: every clock keeps all of its reading
_NO_PAUSES = CollectorPauses()


class _ReleasePickler(pickle.Pickler):
    """Protocol 5, and a ``jax.Array`` reduced as the numpy array of its
    value is: a leaf's type decides the path, a model of host arrays
    never takes it."""

    def __init__(self, fileobj, pauses: CollectorPauses = _NO_PAUSES):
        super().__init__(fileobj, protocol=pickle.HIGHEST_PROTOCOL)
        self._pauses = pauses
        self.device_bytes = 0
        self.wait_seconds = 0.0

    def reducer_override(self, obj):
        # no jax in the process, no jax.Array in the model
        jax = sys.modules.get("jax")
        if jax is None or not isinstance(obj, jax.Array):
            return NotImplemented
        paused, t0 = self._pauses.seconds, time.perf_counter()
        host = np.asarray(obj)      # read-only: DigestingWriter's rule holds
        self.wait_seconds += time.perf_counter() - t0 \
            - (self._pauses.seconds - paused)
        self.device_bytes += host.nbytes
        return host.__reduce_ex__(pickle.HIGHEST_PROTOCOL)


def dump_models(models: List[Any], fileobj,
                pauses: CollectorPauses = _NO_PAUSES) -> DeviceFetch:
    """Write the release's pickle stream to ``fileobj`` (anything with a
    ``write`` that takes bytes-like objects)."""
    pickler = _ReleasePickler(fileobj, pauses)
    pickler.dump([RETRAIN_ON_DEPLOY if m is None else m for m in models])
    return DeviceFetch(pickler.device_bytes, pickler.wait_seconds)


def serialize_models(models: List[Any]) -> bytes:
    buf = io.BytesIO()
    dump_models(models, buf)
    return buf.getvalue()


def deserialize_models(blob: bytes) -> List[Any]:
    models = pickle.loads(blob)
    return [None if isinstance(m, _RetrainSentinel) else m for m in models]


def _byte_view(data) -> memoryview:
    """What the pickler hands ``write`` as a flat view of its bytes: a
    ``pickle.PickleBuffer`` has no ``len()`` and a view of an array keeps
    the array's shape."""
    if isinstance(data, pickle.PickleBuffer):
        return data.raw()
    view = memoryview(data)
    return view if view.ndim == 1 and view.format == "B" else view.cast("B")


class DigestingWriter:
    """A writable that passes every buffer on to ``fileobj`` and, beside
    the write, to a sha256 on a worker thread (``hashlib.update`` on a
    large buffer and a file's ``write`` both release the GIL).

    The hash thread sees each buffer after ``write`` was called with it,
    so whoever writes must not mutate a buffer it has handed over: the
    pickler hands over its own finished frames, views of host arrays
    nothing else touches during a persist, and views of device arrays'
    host copies, which are read-only. The queue is bounded, so the
    writer never runs more than a few buffers ahead of the hash.

    Each thread keeps the sums of its own seconds. The writing thread:
    ``put_wait_seconds`` (blocked on the queue: the hash is
    ``_QUEUE_DEPTH`` buffers behind and paces the write),
    ``write_seconds`` (inside ``fileobj.write``) and the largest single
    call of those, ``slowest_write_seconds`` with its
    ``slowest_write_bytes`` and ``slowest_write_offset`` in the stream
    (a stall: one call, or all of them). The digest thread:
    ``hash_seconds`` (inside ``sha256.update``) and ``starved_seconds``
    (waiting for the next buffer, from the writer's construction on:
    the writer paces the hash), which together are that thread's whole
    life; ``life_seconds`` is the writer's construction to the end of
    ``close()``, the join included.

    ``close()`` (or leaving the ``with`` block) joins the thread; only
    then are ``hexdigest()``, ``size`` and the digest thread's sums
    final. It does not close ``fileobj``.
    """

    _QUEUE_DEPTH = 4

    def __init__(self, fileobj: BinaryIO,
                 pauses: CollectorPauses = _NO_PAUSES):
        self._born = time.perf_counter()
        self._file = fileobj
        self._pauses = pauses
        self._sha = hashlib.sha256()
        self._queue: "queue.Queue[Optional[memoryview]]" = queue.Queue(
            self._QUEUE_DEPTH)
        self._error: Optional[Exception] = None
        self.size = 0
        self.put_wait_seconds = 0.0
        self.write_seconds = 0.0
        self.slowest_write_seconds = 0.0
        self.slowest_write_bytes = 0
        self.slowest_write_offset = 0
        self.hash_seconds = 0.0
        self.starved_seconds = 0.0
        self.life_seconds = 0.0
        self._thread: Optional[threading.Thread] = threading.Thread(
            target=self._hash_loop, name="pio-release-digest", daemon=True)
        self._thread.start()

    def _hash_loop(self) -> None:
        # one reading of the clock a transition, from the writer's
        # construction on (the thread's start-up is a wait for the first
        # buffer like any other): the two sums leave nothing out
        at = self._born
        while True:
            view = self._queue.get()
            got = time.perf_counter()
            self.starved_seconds += got - at
            at = got
            if view is None:
                return
            if self._error is not None:
                continue    # keep draining: the writer must never block
            try:
                self._sha.update(view)
            except Exception as e:  # re-raised by close()
                self._error = e
            done = time.perf_counter()
            self.hash_seconds += done - at
            at = done

    def write(self, data) -> int:
        if self._thread is None:
            raise ValueError("write to a closed DigestingWriter")
        view = _byte_view(data)
        pauses = self._pauses
        paused0, t0 = pauses.seconds, time.perf_counter()
        self._queue.put(view)
        paused1, t1 = pauses.seconds, time.perf_counter()
        self._file.write(view)
        wrote = time.perf_counter() - t1 - (pauses.seconds - paused1)
        self.put_wait_seconds += t1 - t0 - (paused1 - paused0)
        self.write_seconds += wrote
        if wrote > self.slowest_write_seconds:
            self.slowest_write_seconds = wrote
            self.slowest_write_bytes = view.nbytes
            self.slowest_write_offset = self.size
        self.size += view.nbytes
        return view.nbytes

    def close(self) -> None:
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._queue.put(None)
        thread.join()
        self.life_seconds = time.perf_counter() - self._born
        if self._error is not None:
            raise self._error

    def hexdigest(self) -> str:
        if self._thread is not None:
            raise ValueError("hexdigest of a DigestingWriter still open")
        return self._sha.hexdigest()

    def __enter__(self) -> "DigestingWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
