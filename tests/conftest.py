"""Test fixture: force CPU jax with 8 virtual devices.

The analog of the reference's local[*] Spark test fixture
(e2/.../fixture/SharedSparkContext.scala:21-44): distributed logic
(shard_map, mesh collectives) is exercised on host threads without TPUs.
Must run before jax is first imported.
"""

import os

# tests always run on the virtual CPU mesh, whatever the environment names
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# `pio` entry points turn on the persistent compilation cache
# (utils/device.enable_compile_cache); tests invoke them in-process and
# must neither write thousands of CPU programs into the checkout nor
# read a previous run's
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture
def anyio_backend():
    """Async tests (event/query server) run on asyncio via the anyio plugin."""
    return "asyncio"


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from jax.sharding import Mesh
    import numpy as np

    devices = np.array(jax.devices()[:8])
    assert devices.size == 8, "conftest should have forced 8 host devices"
    return Mesh(devices, axis_names=("data",))


@pytest.fixture(scope="session")
def repo_project():
    """The real tree parsed ONCE for every static-analysis gate
    (`pio check` rules; see predictionio_tpu/analysis/)."""
    import pathlib

    from predictionio_tpu.analysis import Project

    root = pathlib.Path(__file__).resolve().parent.parent
    return Project.from_root(root)
