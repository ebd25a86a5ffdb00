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
"""

from __future__ import annotations

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


class _ReleasePickler(pickle.Pickler):
    """Protocol 5, and a ``jax.Array`` reduced as the numpy array of its
    value is: a leaf's type decides the path, a model of host arrays
    never takes it."""

    def __init__(self, fileobj):
        super().__init__(fileobj, protocol=pickle.HIGHEST_PROTOCOL)
        self.device_bytes = 0
        self.wait_seconds = 0.0

    def reducer_override(self, obj):
        # no jax in the process, no jax.Array in the model
        jax = sys.modules.get("jax")
        if jax is None or not isinstance(obj, jax.Array):
            return NotImplemented
        t0 = time.perf_counter()
        host = np.asarray(obj)      # read-only: DigestingWriter's rule holds
        self.wait_seconds += time.perf_counter() - t0
        self.device_bytes += host.nbytes
        return host.__reduce_ex__(pickle.HIGHEST_PROTOCOL)


def dump_models(models: List[Any], fileobj) -> DeviceFetch:
    """Write the release's pickle stream to ``fileobj`` (anything with a
    ``write`` that takes bytes-like objects)."""
    pickler = _ReleasePickler(fileobj)
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

    ``close()`` (or leaving the ``with`` block) joins the thread; only
    then are ``hexdigest()``, ``size``, ``write_seconds`` and
    ``hash_seconds`` final. It does not close ``fileobj``.
    """

    _QUEUE_DEPTH = 4

    def __init__(self, fileobj: BinaryIO):
        self._file = fileobj
        self._sha = hashlib.sha256()
        self._queue: "queue.Queue[Optional[memoryview]]" = queue.Queue(
            self._QUEUE_DEPTH)
        self._error: Optional[Exception] = None
        self.size = 0
        self.write_seconds = 0.0
        self.hash_seconds = 0.0
        self._thread: Optional[threading.Thread] = threading.Thread(
            target=self._hash_loop, name="pio-release-digest", daemon=True)
        self._thread.start()

    def _hash_loop(self) -> None:
        while True:
            view = self._queue.get()
            if view is None:
                return
            if self._error is not None:
                continue    # keep draining: the writer must never block
            try:
                t0 = time.perf_counter()
                self._sha.update(view)
                self.hash_seconds += time.perf_counter() - t0
            except Exception as e:  # re-raised by close()
                self._error = e

    def write(self, data) -> int:
        if self._thread is None:
            raise ValueError("write to a closed DigestingWriter")
        view = _byte_view(data)
        self._queue.put(view)
        t0 = time.perf_counter()
        self._file.write(view)
        self.write_seconds += time.perf_counter() - t0
        self.size += view.nbytes
        return view.nbytes

    def close(self) -> None:
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._queue.put(None)
        thread.join()
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
