"""Model blob store over any fsspec filesystem URL.

One backend replacing the reference's three file-oriented model stores —
LocalFSModels (storage/localfs/.../LocalFSModels.scala:32-62), HDFSModels
(storage/hdfs/.../HDFSModels.scala:31-63) and S3Models
(storage/s3/.../S3Models.scala:36-101) — via fsspec URL schemes: a plain
path, ``hdfs://``, ``s3://``, ``memory://``. File-per-model, like all three.

A write, whether `insert` of a blob in memory or `open_write` for a
writer that streams (``pio train`` pickles a release straight into it),
goes to a temporary name in the store's root and is renamed onto the
model's name when the writer is done: a reader sees the old file or the
whole new one.
"""

from __future__ import annotations

import contextlib
import uuid
from typing import BinaryIO, Iterator, Optional

from predictionio_tpu.storage import base
from predictionio_tpu.storage.base import Model


class FSModels(base.Models):
    def __init__(self, url: str):
        import fsspec

        self.url = url
        self.fs, self.root = fsspec.core.url_to_fs(url)
        self.fs.makedirs(self.root, exist_ok=True)

    def _path(self, model_id: str) -> str:
        if "/" in model_id or model_id.startswith("."):
            raise ValueError(f"invalid model id {model_id!r}")
        return f"{self.root}/pio_model_{model_id}.bin"

    streams_writes = True

    @contextlib.contextmanager
    def open_write(self, model_id: str) -> Iterator[BinaryIO]:
        # write-then-rename: a concurrent get() during a deploy must see
        # either the old blob or the new one, never a torn half-write.
        # The temp name stays inside the store root (same fs, same dir)
        # so the final mv is a metadata move, not a copy.
        from predictionio_tpu.obs.tracing import span

        path = self._path(model_id)
        tmp = f"{path}.tmp-{uuid.uuid4().hex}"
        try:
            with self.fs.open(tmp, "wb") as f:
                yield f
            with span("persist_commit"):
                self.fs.mv(tmp, path)
        except BaseException:
            try:
                if self.fs.exists(tmp):
                    self.fs.rm(tmp)
            except Exception:
                pass
            raise

    def insert(self, model: Model) -> None:
        with self.open_write(model.id) as f:
            f.write(model.models)

    def get(self, model_id: str) -> Optional[Model]:
        path = self._path(model_id)
        if not self.fs.exists(path):
            return None
        with self.fs.open(path, "rb") as f:
            return Model(id=model_id, models=f.read())

    def delete(self, model_id: str) -> None:
        path = self._path(model_id)
        if self.fs.exists(path):
            self.fs.rm(path)
