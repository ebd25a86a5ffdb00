"""Release manifest plumbing: digests, registration, selection.

A release is the deployable identity of one train run. Two digests make
"did anything actually change?" answerable without deserializing blobs:

  * ``params_digest`` — sha256 over the EngineInstance's four canonical
    params JSON strings (they are serialized with ``sort_keys=True`` by
    ``run_train``, so the digest is stable across processes).
  * ``model_digest`` — sha256 of the serialized model blob itself
    (``run_train`` takes it beside the write of the blob and hands it to
    ``record_release`` with the size; other callers hand over the blob).

``record_release`` is called by ``workflow.train.run_train`` after the
instance is COMPLETED; failures are logged, never raised — a missing
manifest degrades the deploy UX, it must not fail a finished train.
"""

from __future__ import annotations

import hashlib
import logging
from typing import Optional

from predictionio_tpu.storage.base import EngineInstance, Release, Releases

logger = logging.getLogger("pio.deploy")


def release_to_json(r: Release) -> dict:
    """THE wire shape of a release manifest — both the query server's
    /releases.json and the admin /cmd/releases emit this, so clients see
    one schema and a new Release field lands in both APIs at once."""
    return {
        "id": r.id, "version": r.version, "status": r.status,
        "engineId": r.engine_id,
        "engineVersion": r.engine_version,
        "engineVariant": r.engine_variant,
        "engineInstanceId": r.instance_id,
        "paramsDigest": r.params_digest, "modelDigest": r.model_digest,
        "modelSizeBytes": r.model_size_bytes,
        "createdTime": r.created_time.isoformat(),
        "trainSeconds": r.train_seconds, "batch": r.batch,
        "history": r.history,
    }


def params_digest(instance: EngineInstance) -> str:
    """Content digest of the engine params that produced the instance."""
    h = hashlib.sha256()
    for part in (instance.data_source_params, instance.preparator_params,
                 instance.algorithms_params, instance.serving_params):
        h.update((part or "").encode())
        h.update(b"\x00")
    return h.hexdigest()


def model_digest(blob: Optional[bytes]) -> str:
    """Content digest of the serialized model blob ('' when no blob was
    persisted — retrain-at-deploy algorithms)."""
    if not blob:
        return ""
    return hashlib.sha256(blob).hexdigest()


_digest_of_blob = model_digest


def record_release(instance: EngineInstance, train_seconds: float,
                   blob: Optional[bytes] = None, *,
                   model_digest: Optional[str] = None,
                   model_size_bytes: Optional[int] = None
                   ) -> Optional[Release]:
    """Register a COMPLETED instance as the variant's next release.

    The stored blob's digest and size come from ``blob``, or, from a
    caller that took them while writing the blob, from ``model_digest``
    and ``model_size_bytes`` (then no byte is read here).

    Returns the inserted Release, or None when registration failed (the
    train itself already succeeded; manifest writing is best-effort).
    """
    import time as _time

    from predictionio_tpu.storage.registry import Storage

    release = Release(
        engine_id=instance.engine_id,
        engine_version=instance.engine_version,
        engine_variant=instance.engine_variant,
        instance_id=instance.id,
        params_digest=params_digest(instance),
        model_digest=(_digest_of_blob(blob) if model_digest is None
                      else model_digest),
        model_size_bytes=((len(blob) if blob else 0)
                          if model_size_bytes is None else model_size_bytes),
        status="REGISTERED",
        train_seconds=train_seconds,
        batch=instance.batch,
        # seed the lineage up front: one insert, and no reader window
        # where a REGISTERED release has an empty history
        history=[{"status": "REGISTERED",
                  "timeMs": int(_time.time() * 1000),
                  "reason": "train completed"}],
    )
    from predictionio_tpu.storage.faults import maybe_kill

    try:
        # chaos seam: a kill on either side of the insert is the
        # "train completed but its manifest may or may not exist" window
        # the orchestrator's recovery must converge
        maybe_kill("releases:insert:pre")
        Storage.get_meta_data_releases().insert(release)
        maybe_kill("releases:insert:committed")
        logger.info("registered release v%d (%s) for %s/%s",
                    release.version, release.id, release.engine_id,
                    release.engine_variant)
        return release
    except Exception:
        logger.exception("release registration failed for instance %s",
                         instance.id)
        return None


def resolve_release(releases: Releases, engine_id: str, engine_version: str,
                    engine_variant: str,
                    selector: Optional[str] = None) -> Optional[Release]:
    """Resolve a CLI/API release selector to a manifest.

    ``selector`` may be a release id, a bare version number (``"3"``) or
    a ``"v3"`` form; None picks the newest release of the variant that
    was NOT rejected — an auto-rolled-back release must never ride back
    into production by being "the latest"; redeploying one takes an
    explicit selector.
    """
    if selector is None or selector == "":
        for r in releases.get_for_variant(engine_id, engine_version,
                                          engine_variant):
            if r.status != "ROLLED_BACK":
                return r
        return None
    release = releases.get(selector)
    if release is not None:
        # a raw id must still belong to THIS variant — deploying another
        # variant's release onto this server would load the wrong model
        # (and mis-attribute any prepare failure to the foreign lineage)
        if (release.engine_id, release.engine_version,
                release.engine_variant) != (engine_id, engine_version,
                                            engine_variant):
            return None
        return release
    raw = selector[1:] if selector[:1] in ("v", "V") else selector
    try:
        version = int(raw)
    except ValueError:
        return None
    return releases.get_by_version(engine_id, engine_version,
                                   engine_variant, version)
