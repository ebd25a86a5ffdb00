"""WorkflowContext: the single factory for device meshes.

Parity with the reference WorkflowContext (core/.../workflow/WorkflowContext.scala:28-47)
— the only place a SparkContext is created becomes the only place a
`jax.sharding.Mesh` is built. Everything downstream (DataSource reads,
Algorithm.train, serving) receives this context.

TPU-first design notes:
  * mesh axes default to a single "data" axis over all local devices; engine
    variants may request e.g. {"mesh_shape": [4, 2], "mesh_axes":
    ["data", "model"]} through runtime_conf (the sparkConf analog)
  * jax is imported lazily so storage/CLI paths never pay jax import cost
  * `local_mesh()` (mesh of 1) is the analog of the reference's L-components
    running on the driver (LAlgorithm.scala:48)
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Sequence, Tuple

logger = logging.getLogger("pio.workflow")


@dataclasses.dataclass
class WorkflowParams:
    """WorkflowParams.scala:32 — workflow-level flags."""

    batch: str = ""
    verbose: int = 2
    save_model: bool = True
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    #: jax/XLA settings overlay (the sparkEnv/sparkConf analog)
    runtime_conf: Dict[str, str] = dataclasses.field(default_factory=dict)


def mesh_of(ctx):
    """The mesh of a workflow context, or a fresh default mesh when the
    caller passed a bare context (tests, embedded use). Shared by every
    algorithm that trains on the mesh."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None:
        mesh = WorkflowContext.create(mode="Training").mesh
    return mesh


class WorkflowContext:
    """Holds the device mesh + app metadata for one workflow run."""

    def __init__(self, mode: str = "", batch: str = "",
                 mesh_shape: Optional[Sequence[int]] = None,
                 mesh_axes: Optional[Sequence[str]] = None,
                 devices=None):
        self.mode = mode
        self.batch = batch
        self._mesh = None
        self._mesh_shape = tuple(mesh_shape) if mesh_shape else None
        self._mesh_axes = tuple(mesh_axes) if mesh_axes else None
        self._devices = devices
        #: mid-training Checkpointer (workflow/checkpoint.py), set from
        #: runtime_conf checkpoint_dir/checkpoint_interval; None = off
        self.checkpointer = None
        logger.info("WorkflowContext: mode=%s batch=%s", mode, batch)

    # -- mesh ---------------------------------------------------------------
    @property
    def mesh(self):
        """The mesh, built lazily on first use (WorkflowContext.scala:45)."""
        if self._mesh is None:
            self._mesh = self._build_mesh()
        return self._mesh

    def _build_mesh(self):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devices = self._devices if self._devices is not None else jax.devices()
        if self._mesh_shape is None:
            shape: Tuple[int, ...] = (len(devices),)
            axes: Tuple[str, ...] = ("data",)
        else:
            shape = self._mesh_shape
            axes = self._mesh_axes or tuple(
                f"axis{i}" for i in range(len(shape)))
        n = 1
        for s in shape:
            n *= s
        from predictionio_tpu.utils.device import describe_devices

        arr = np.asarray(devices[:n]).reshape(shape)
        logger.info("mesh: shape=%s axes=%s %s", shape, axes,
                    describe_devices(devices[:n]))
        return Mesh(arr, axis_names=axes)

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def local_mesh(self):
        """Mesh of one device — the L-component path (SURVEY.md P6).
        Honors the context's device override like _build_mesh does."""
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devices = self._devices if self._devices is not None else jax.devices()
        return Mesh(np.asarray(devices[:1]), axis_names=("data",))

    # -- factory (WorkflowContext.apply parity) ------------------------------
    @classmethod
    def create(cls, mode: str = "", batch: str = "",
               workflow_params: Optional[WorkflowParams] = None,
               devices=None) -> "WorkflowContext":
        conf = dict(workflow_params.runtime_conf) if workflow_params else {}
        mesh_shape = conf.get("mesh_shape")
        if isinstance(mesh_shape, str):
            mesh_shape = [int(x) for x in mesh_shape.split(",") if x]
        mesh_axes = conf.get("mesh_axes")
        if isinstance(mesh_axes, str):
            mesh_axes = [x for x in mesh_axes.split(",") if x]
        ctx = cls(mode=mode, batch=batch, mesh_shape=mesh_shape,
                  mesh_axes=mesh_axes, devices=devices)
        ckpt_dir = conf.get("checkpoint_dir")
        if ckpt_dir:
            from predictionio_tpu.workflow.checkpoint import Checkpointer

            ctx.checkpointer = Checkpointer(
                ckpt_dir, interval=int(conf.get("checkpoint_interval", 10)))
        return ctx
