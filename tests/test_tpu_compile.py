"""The TPU kernels of the main path compiled at their real sizes for a
described (not attached) v5e: what Mosaic or the XLA TPU compiler would
refuse on the chip (a block off the tiling, too much VMEM, a program
over the HBM) fails here. Nothing runs, so nothing is measured.

All of these live in this one file and describe the topology inside a
fixture: only the worker that runs the file loads the TPU's library."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(chips):
    return SingleDeviceSharding(chips[0])


@pytest.mark.parametrize("name,b,h,kv_heads,length,dk,dv,causal", [
    # kimivl-a3b-ep8.train: 2 sessions x 16 heads x 8,192, q/k 192, v 128
    ("cell", 2, 16, 16, 8192, 192, 128, True),
    # the longest `tiles` lets through at that width: dq fills its VMEM
    ("longest", 1, 2, 2, 24576, 192, 128, True),
    ("half-a-lane-tile", 1, 2, 2, 1536, 64, 128, False),
    # qwen3next-a3b-ep16.train: 1 session x 16 query heads over 2
    # key/value heads x 16,384, q/k = v = 256: blocks of 1024 and `dq` of
    # a head (32 MiB in its two buffers) within the kernels' VMEM
    ("grouped-cell", 1, 16, 2, 16384, 256, 256, True),
    ("grouped-longest", 1, 4, 1, 24576, 256, 256, True),
])
def test_attention_kernels_compile_for_v5e(one_chip, name, b, h, kv_heads,
                                           length, dk, dv, causal):
    from predictionio_tpu.ops import attention_pallas

    assert attention_pallas.tiles(length, length, dk, dv)

    def loss(q, k, v, mask, w):
        out = attention_pallas.flash_attention_pallas(q, k, v, mask, causal)
        return (out * w).sum()

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shape(b, h, length, dk), shape(b, kv_heads, length, dk),
        shape(b, kv_heads, length, dv), shape(b, length, dtype=jnp.bool_),
        shape(b, h, length, dv)).compile()
    text = compiled.as_text()
    for kernel in ("flash_attention_pallas_fwd", "flash_attention_pallas_bwd"):
        assert kernel in text


@pytest.mark.parametrize("name,mesh_shape,kernels", [
    ("one-chip", None, True),
    # batch over "data", a "model" axis beside it: the compiler partitions
    # no Mosaic kernel, so a step traced for four devices keeps the scan
    ("2x2-mesh", (2, 2), False),
])
def test_a_train_step_whose_shapes_tile_compiles_for_v5e(
        chips, monkeypatch, name, mesh_shape, kernels):
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, attention_pallas

    kind = chips[0].device_kind
    assert kind in attention_pallas.KINDS
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    p = seqrec.SeqRecParams(d_model=256, n_heads=2, n_layers=2, max_len=128,
                            batch_size=4)
    if mesh_shape is None:
        mesh, whole, rows = None, SingleDeviceSharding(chips[0]), None
    else:
        mesh = Mesh(np.asarray(chips).reshape(mesh_shape), ("data", "model"))
        whole = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P("data"))
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(np.random.default_rng(0), 50, p))
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole),
        tree)
    seqs = jax.ShapeDtypeStruct((4, 128), jnp.int32, sharding=rows or whole)
    text = seqrec.make_train_step(mesh, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile().as_text()
    assert ("flash_attention_pallas_bwd" in text) is kernels

    if mesh is not None:        # what the route keeps the step from
        monkeypatch.setattr(attention, "attention_route",
                            lambda *a, **kw: "pallas")
        with pytest.raises(NotImplementedError, match="partitioned"):
            seqrec.make_train_step(mesh, p, optimizer).lower(
                put(params), put(jax.eval_shape(optimizer.init, params)),
                seqs, seqs)
