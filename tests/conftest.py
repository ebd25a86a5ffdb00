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

import re  # noqa: E402
import signal  # noqa: E402

import pytest  # noqa: E402

#: seconds a test may run before it fails ALONE, its node id in the
#: message: a test that one day hangs costs itself, not the run's clock
TEST_LIMIT_S = 300


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Every test under an interval timer of its own (xdist runs a test
    on its worker's main thread, where the signal lands)."""
    def late(signum, frame):
        pytest.fail(f"{item.nodeid} ran past {TEST_LIMIT_S} s", pytrace=False)

    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


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


# -- programs compiled for a described (not attached) v5e -------------------
# tests/test_tpu_compile_*.py: the driver's command lets every worker load
# the TPU's library (ALLOW_MULTIPLE_LIBTPU_LOAD=1), so those files may run
# on several workers at once; a worker describes the topology once.

@pytest.fixture(scope="session")
def chips():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="session")
def one_chip(chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(chips[0])


def _kernel_calls(text, name):
    return len(re.findall(rf"{name}[.0-9]* = ", text))


def _relayouts(text, length, scope=None):
    """The `copy` and `transpose` instructions of a compiled program's
    text (under a `jax.named_scope`, if given) whose result is an array
    of `length` positions by 128 numbers or more: a relayout of an
    activation, not of a mask or a head's row sums."""
    import numpy as np

    found = []
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT\s+)?%?(\S+) = \w+\[([0-9,]*)\]\S*\s+"
                     r"(copy|transpose)\(", line)
        if not m or (scope and scope not in line):
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        if length in dims and np.prod(dims) >= length * 128:
            found.append(m.group(1))
    return found


def _cell_step(config, one_chip, packed=False):
    """A benchmark configuration's train step at its real size, lowered
    and compiled for one described chip. -> (its spec, how many parameters
    it draws, the compiled step)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models import seqrec

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", config + ".json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    rows = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)),
        *[rows] * (4 if packed else 2)).compile()
    parameters = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    return p, parameters, compiled
