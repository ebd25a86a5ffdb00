"""Model blob (de)serialization.

Replaces the reference's Kryo/chill model blob machinery
(core/.../workflow/CoreWorkflow.scala:76-81, CreateServer.scala:62-76): every
model is a picklable Python object; pytrees of jax Arrays are converted to
numpy first so blobs are host-portable and loadable without devices.

A release is written in one pass: ``dump_models`` pickles into whatever
writable it is given (protocol 5 hands each array's buffer to ``write``
without copying it), and ``DigestingWriter`` sits between the pickler and
the model store's file, counting the bytes and feeding each buffer to a
sha256 on a worker thread, so the digest of exactly the stored bytes is
ready when the file closes and nobody reads the blob a second time.
``serialize_models`` is the same stream into memory.
"""

from __future__ import annotations

import hashlib
import io
import pickle
import queue
import threading
import time
from typing import Any, BinaryIO, List, Optional


class _RetrainSentinel:
    """Marks an algorithm slot whose model is retrained at deploy
    (the reference's Unit model, PAlgorithm.scala:112)."""

    def __repr__(self):
        return "RETRAIN_ON_DEPLOY"


RETRAIN_ON_DEPLOY = _RetrainSentinel()


def _to_host(obj: Any) -> Any:
    """Pull any jax arrays in a pytree down to numpy."""
    try:
        import jax

        leaves, treedef = jax.tree.flatten(obj)
        if any(isinstance(x, jax.Array) for x in leaves):
            return jax.tree.unflatten(
                treedef, [jax.device_get(x) if isinstance(x, jax.Array) else x
                          for x in leaves])
    except (ImportError, TypeError):
        pass
    return obj


def dump_models(models: List[Any], fileobj) -> None:
    """Write the release's pickle stream to ``fileobj`` (anything with a
    ``write`` that takes bytes-like objects)."""
    payload = [RETRAIN_ON_DEPLOY if m is None else _to_host(m) for m in models]
    pickle.dump(payload, fileobj, protocol=pickle.HIGHEST_PROTOCOL)


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
    pickler hands over its own finished frames and views of host arrays
    nothing else touches during a persist. The queue is bounded, so the
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
