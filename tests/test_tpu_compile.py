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
    # lfm2-a2b-ep8.train: 1 session x 32 query heads over 8 key/value
    # heads x 32,768, q/k = v = 64, half a lane tile: `dq` of a head is
    # 32 MiB in its two buffers, the longest `tiles` lets through there
    ("narrow-cell", 1, 32, 8, 32768, 64, 64, True),
    ("wide-qk-narrow-v", 1, 4, 2, 2048, 192, 64, True),
    # ouro-2.6b-pp8.train: 1 session x 16 heads (one key/value head a
    # query head) x 8,192, q/k = v = 128: the kernels' fourth shape
    ("looped-cell", 1, 16, 16, 8192, 128, 128, True),
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


@pytest.mark.parametrize("grad_dtype", [None, jnp.bfloat16],
                         ids=["float32-gradient", "bfloat16-gradient"])
def test_rotary_attention_compiles_for_v5e_token_first(one_chip, grad_dtype):
    """ouro-2.6b-pp8.train's attention between its two projections (1
    session x 16 heads x 8,192 x 128, a head a block of 128 columns of
    [1, 8192, 2048]): the rotary pass over the projection's columns, the
    kernels with `dq` of a head resident in VMEM, the pass back in either
    type, and no transpose or copy of a [B, L, .] array among them."""
    from predictionio_tpu.ops import attention_pallas

    assert attention_pallas.layout(128, 128) == "rows"
    assert attention_pallas.tiles(8192, 8192, 128, 128)

    def loss(qkv, mask, w):
        return (attention_pallas.rotary_attention_pallas(
            qkv, mask, 16, 1e6, True, False, grad_dtype) * w).sum()

    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    text = jax.jit(jax.grad(loss)).lower(
        shape(1, 8192, 3 * 2048), shape(1, 8192, dtype=jnp.bool_),
        shape(1, 8192, 2048)).compile().as_text()
    for kernel in ("attention_rotary_fwd", "flash_attention_pallas_fwd",
                   "flash_attention_pallas_bwd", "attention_rotary_bwd"):
        assert kernel in text
    assert not _relayouts(text, 8192)


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


@pytest.mark.parametrize("name,b,h,length,dk,dv", [
    # qwen3next-a3b-ep16.train: 1 session x 32 value heads x 16,384, 128
    ("cell", 1, 32, 16384, 128, 128),
    # the widest state `tiles` lets through, a length of an odd number of
    # pairs of chunks (one pair a grid step) and an odd number of heads
    ("widest-odd", 2, 3, 64 * 6, 256, 256),
    ("dk-over-dv", 1, 2, 1024, 256, 128),
])
def test_delta_rule_kernels_compile_for_v5e(one_chip, name, b, h, length, dk,
                                            dv):
    from predictionio_tpu.ops import linear_attention, linear_attention_pallas

    assert linear_attention_pallas.tiles(dk, dv)

    def loss(q, k, v, g, beta, w):
        return (linear_attention_pallas.gated_delta_rule_pallas(
            q, k, v, g, beta, linear_attention.CHUNK) * w).sum()

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        shape(b, length, h, dk), shape(b, length, h, dk),
        shape(b, length, h, dv), shape(b, length, h), shape(b, length, h),
        shape(b, length, h, dv)).compile().as_text()
    for kernel in ("gated_delta_rule_pallas_fwd",
                   "gated_delta_rule_pallas_bwd"):
        assert kernel in text


@pytest.mark.parametrize("name,b,hk,h,length", [
    # qwen3next-a3b-ep16.train: 16 key heads serve 32 value heads: a grid
    # step's two value heads share one key head read where it lies, and
    # the backward kernel adds their `dq`, `dk` in VMEM
    ("cell", 1, 16, 32, 16384),
    # four value heads a key head: two grid steps' partial sums a key head
    ("a-part-of-a-group", 1, 1, 4, 256),
    ("three-a-key-head-one-a-step", 1, 2, 6, 256),
])
def test_delta_rule_kernels_at_key_heads_compile_for_v5e(one_chip, name, b,
                                                         hk, h, length):
    from predictionio_tpu.ops import linear_attention, linear_attention_pallas

    def loss(q, k, v, g, beta, w):
        return (linear_attention_pallas.gated_delta_rule_pallas(
            q, k, v, g, beta, linear_attention.CHUNK) * w).sum()

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    grad = jax.grad(loss, (0, 1, 2, 3, 4))
    operands = (shape(b, length, hk, 128), shape(b, length, hk, 128),
                shape(b, length, h, 128), shape(b, length, h),
                shape(b, length, h), shape(b, length, h, 128))
    # dq, dk come back at the key heads
    assert [t.shape for t in jax.eval_shape(grad, *operands)[:2]] \
        == [(b, length, hk, 128)] * 2
    compiled = jax.jit(grad).lower(*operands).compile()
    text = compiled.as_text()
    for kernel in ("gated_delta_rule_pallas_fwd",
                   "gated_delta_rule_pallas_bwd"):
        assert kernel in text


@pytest.mark.parametrize("name,b,length,heads,taps", [
    # qwen3next-a3b-ep16.train: 16,384 positions, 16 key heads serving 32
    # value heads of 128, a convolution of 4: blocks of 512 rows by four
    # heads, a block's three earlier rows read from the eight before it
    ("cell", 1, 16384, (16, 32, 128, 128), 4),
    # heads of 256, an odd number of them, two batch rows, a length that
    # is filled up to whole blocks, a convolution that reaches 16 rows
    ("wide-odd", 2, 1000, (1, 3, 256, 256), 12),
])
def test_the_chains_fused_passes_compile_for_v5e(one_chip, name, b, length,
                                                 heads, taps):
    """`gated_delta_chain_pallas` forward and backward at real widths:
    Mosaic takes the passes' blocks, their unaligned row offsets into
    VMEM and the projection's gradient written in place, a block of
    columns a kernel."""
    from predictionio_tpu.ops import linear_attention, linear_attention_pallas

    hk, hv, dk, dv = heads
    total = 2 * hk * dk + 2 * hv * dv

    def loss(qkvz, w_taps, g, beta, scale, w):
        return (linear_attention_pallas.gated_delta_chain_pallas(
            qkvz, w_taps, g, beta, scale, heads, 1e-6,
            linear_attention.CHUNK) * w).sum()

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    text = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        shape(b, length, total), shape(taps, total - hv * dv),
        shape(b, length, hv), shape(b, length, hv), shape(dv),
        shape(b, length, hv * dv)).compile().as_text()
    for kernel, calls in (("gdn_chain_front_fwd", 3),
                          ("gdn_chain_back_fwd", 0),   # nobody reads it
                          ("gdn_chain_front_bwd", 3),
                          ("gdn_chain_back_bwd", 1),
                          ("gated_delta_rule_pallas_fwd", 1),
                          ("gated_delta_rule_pallas_bwd", 1)):
        # (outside a step's scopes the compiler names a call
        # `jvp_<kernel>_.<n>`)
        assert _kernel_calls(text, f"{kernel}[_.0-9]*") == calls, kernel


@pytest.mark.parametrize("name,b,length,d,taps", [
    # lfm2-a2b-ep8.train: one session of 32,768, b, c, u of 2048 columns
    # (sixteen lane tiles each), three taps: forward blocks of 512 x 512,
    # backward blocks of 256 rows x all 6,144 columns in and out
    ("cell", 1, 32768, 2048, 3),
    # two batch rows, one row block, a width that only 128 divides, a
    # convolution that reaches 16 rows
    ("narrow-odd", 2, 128, 384, 12),
])
def test_the_short_convolutions_fused_passes_compile_for_v5e(one_chip, name,
                                                             b, length, d,
                                                             taps):
    """`gated_short_conv_pallas` forward and backward at real widths:
    Mosaic takes three blocks of the one projection's output at their
    column offsets, the unaligned row offsets into VMEM, and a backward
    block as wide as the projection whose three column ranges are
    written a chunk of columns at a time."""
    from predictionio_tpu.ops import short_conv_pallas

    assert short_conv_pallas.tiles(length, d, taps)

    def loss(bcu, w_taps, w):
        return (short_conv_pallas.gated_short_conv_pallas(bcu, w_taps)
                * w).sum()

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        shape(b, length, 3 * d), shape(taps, d),
        shape(b, length, d)).compile().as_text()
    # (the forward pass's output is read by nobody here: `w` is the
    # cotangent; outside a step's scopes a call is `jvp_<kernel>_.<n>`)
    assert _kernel_calls(text, "short_conv_chain_bwd[_.0-9]*") == 1
    # the projection's gradient is the kernel's one output: nothing
    # concatenates or pads three parts into it
    assert not any(op in line for line in text.splitlines()
                   for op in (" concatenate(", " pad("))


def _kernel_calls(text, name):
    import re

    return len(re.findall(rf"{name}[.0-9]* = ", text))


def _relayouts(text, length, scope=None):
    """The `copy` and `transpose` instructions of a compiled program's
    text (under a `jax.named_scope`, if given) whose result is an array
    of `length` positions by 128 numbers or more: a relayout of an
    activation, not of a mask or a head's row sums."""
    import re

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


def test_the_hybrid_cells_step_fits_a_v5e_with_all_heads_at_once(
        chips, one_chip, monkeypatch):
    """qwen3next-a3b-ep16.train's step compiled for one described v5e: on
    the kernels' route a linear layer takes all its heads at once, its
    forward kernel runs twice a layer (the block's pass and `remat`'s)
    and no third time, the fused passes around it likewise (the front's
    three calls and the back's one twice forward, once backward: PERF.md
    section 6, PR 39), and arguments + temporaries leave the 16 GB chip
    1 GB and more (the rule by which the head groups went; PERF.md
    section 6, PR 32)."""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, linear_attention, moe

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    monkeypatch.setattr(linear_attention, "_device_kind", lambda: kind)
    monkeypatch.setattr(moe, "_device_kind", lambda: kind)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs",
                           "seqrec-qwen3-next-80b-a3b-ep16.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert p.remat and p.mixer_kinds().count("gdn") == 3
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "gated_delta_rule_pallas_fwd") == 2 * 3
    assert _kernel_calls(text, "gated_delta_rule_pallas_bwd") == 3
    for kernel, calls in (("gdn_chain_front_fwd", 3 * 2),
                          ("gdn_chain_back_fwd", 2),
                          ("gdn_chain_front_bwd", 3),
                          ("gdn_chain_back_bwd", 1)):
        assert _kernel_calls(text, kernel) == 3 * calls, kernel
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    # four expert layers, twelve grouped products each
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


@pytest.mark.parametrize("name,mesh_shape,kernels", [
    ("one-chip", None, True),
    ("2x2-mesh", (2, 2), False),
])
def test_a_hybrid_step_keeps_the_scan_under_a_mesh(chips, monkeypatch, name,
                                                   mesh_shape, kernels):
    """A small period of linear and full layers at the cell's head
    widths: kernels for both in a program for one chip, the scans in a
    step traced for four."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, linear_attention

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    monkeypatch.setattr(linear_attention, "_device_kind", lambda: kind)
    p = seqrec.SeqRecParams(
        d_model=256, n_heads=2, n_layers=2, max_len=256, batch_size=4,
        mixer=("gdn", "gqa"), norm="rms", positions="rope", n_kv_heads=1,
        head_dim=128, rotary_dim=32, linear_key_heads=2,
        linear_value_heads=4, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel=4, remat=True)
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
    seqs = jax.ShapeDtypeStruct((4, 256), jnp.int32, sharding=rows or whole)
    text = seqrec.make_train_step(mesh, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile().as_text()
    for kernel in ("gated_delta_rule_pallas_fwd",
                   "gated_delta_rule_pallas_bwd",
                   "flash_attention_pallas_bwd"):
        assert (kernel in text) is kernels, kernel


def test_the_conv_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """lfm2-a2b-ep8.train's step compiled for one described v5e: 32,768
    positions through a dense convolution layer, the attention layer on
    the kernels (two forward calls under `remat`, one backward) and
    three convolution layers with their experts, the head tied, every
    convolution layer's chain as the fused passes (likewise two forward,
    one backward); arguments + temporaries leave the 16 GB chip 1 GB and
    more."""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, linear_attention, moe

    kind = chips[0].device_kind
    for module in (attention, linear_attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "seqrec-lfm2-24b-a2b-ep8.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert p.remat and p.tied_head and p.max_len == 32768
    assert p.mixer_kinds() == ("conv", "gqa", "conv", "conv", "conv")
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert _kernel_calls(text, "short_conv_chain_fwd") == 4 * 2
    assert _kernel_calls(text, "short_conv_chain_bwd") == 4
    # the passes' instructions carry the layer's scope and their phase:
    # what `step_scope_ms.short_conv` adds their device time to
    from predictionio_tpu.obs import profiler

    rows = profiler.parse_scope_table(text, seqrec.STEP_SCOPES)[1]
    passes = sorted((key.split(".")[0], *row) for key, row in rows.items()
                    if "short_conv_chain" in key)
    assert passes == sorted(
        [("short_conv_chain_fwd", "seqrec_short_conv", "")] * 4
        + [("short_conv_chain_fwd", "seqrec_short_conv", "tr")] * 4
        + [("short_conv_chain_bwd", "seqrec_short_conv", "t")] * 4), passes
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_looped_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """ouro-2.6b-pp8.train's step compiled for one described v5e: 8,192
    positions four times through six layers as ONE loop of passes around
    one copy of the stack (the kernels' calls from a `while` body: two
    forward a layer under `remat`, one backward, six layers, once), the
    whole vocabulary's head a pass at a time; arguments + temporaries
    leave the 16 GB chip 1 GB and more. (The stack written out four
    times compiles to 12.1 GB of temporaries beside 6.1 of arguments and
    does not fit: PERF.md section 6, PR 38.)"""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "seqrec-ouro-2.6b-pp8.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert (p.n_loops, p.n_layers, p.max_len, p.remat) == (4, 6, 8192, True)
    assert seqrec.LOOP_UNROLL == 1
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 6
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 6
    # token-first: the rotary pass in front of every forward call, its
    # transpose behind every backward call, and no activation relaid
    # between a projection and a kernel
    assert _kernel_calls(text, "attention_rotary_fwd") == 2 * 6
    assert _kernel_calls(text, "attention_rotary_bwd") == 6
    assert not _relayouts(text, p.max_len, "seqrec_attention")
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


def test_the_state_space_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """nemotron3-super-tp8ep64.train's step compiled for one described
    v5e: 8,192 positions through one period of single-sub-layer layers
    (an attention, five state-space and five latent expert layers at this
    chip's tensor and expert share) and the multi-token-prediction
    module; the two attention layers (the stack's and the module's)
    through the Pallas attention kernels at 4 query heads on 1 key/value
    head of 128, the six expert layers' two-matrix experts through the
    grouped-product kernels at 1024 x 2688 (no `ragged-dot`), the scan as
    XLA operations; arguments +
    temporaries (6.8 + 4.1 GiB: PERF.md section 6, PR 40) leave the 16
    GB chip 1 GB and more."""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, moe, moe_pallas

    kind = chips[0].device_kind
    monkeypatch.setattr(attention, "_device_kind", lambda: kind)
    monkeypatch.setattr(moe, "_device_kind", lambda: kind)
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "configs",
            "seqrec-nemotron3-super-120b-a12b-tp8ep64.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert (len(p.layer_kinds()), p.max_len, p.remat) == (13, 8192, True)
    assert moe_pallas.tiles(8192, p.moe_latent_size, p.moe_width, 8) == 128
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == 607_038_960
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 2
    # a layer's two products forward, again in `remat`'s recomputation and
    # again inside the layer's own backward pass; four gradient products
    assert _kernel_calls(text, "grouped_product_pallas_rows") == 6 * 6
    assert _kernel_calls(text, "grouped_product_pallas_rows_t") == 6 * 2
    assert _kernel_calls(text, "grouped_product_pallas_groups") == 6 * 2
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held <= 15.75 * 2 ** 30 - 1e9, held


@pytest.mark.parametrize("block", [128, 256, 512, 1024])
def test_the_banded_attention_kernels_compile_for_v5e(one_chip, monkeypatch,
                                                      block):
    """laguna-xs2-ep8.train's sliding layers: 1 session x 64 query heads
    over 8 key/value heads x 16,384 x 128 under a window of 512, forward
    and backward, at each block the sweep that chose `WINDOW_BLOCK` ran
    (PERF.md section 6, PR 44), under names of their own."""
    from predictionio_tpu.ops import attention_pallas

    monkeypatch.setattr(attention_pallas, "WINDOW_BLOCK", block)
    assert attention_pallas.tiles(16384, 16384, 128, 128, 512)
    assert attention_pallas._block(16384, 512) == block

    def loss(q, k, v, mask, w):
        out = attention_pallas.window_attention_pallas(q, k, v, mask, 512)
        return (out * w).sum()

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        shape(1, 64, 16384, 128), shape(1, 8, 16384, 128),
        shape(1, 8, 16384, 128), shape(1, 16384, dtype=jnp.bool_),
        shape(1, 64, 16384, 128)).compile().as_text()
    for kernel in ("window_attention_pallas_fwd",
                   "window_attention_pallas_bwd"):
        assert kernel in text
    assert "flash_attention_pallas" not in text


def test_the_window_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """laguna-xs2-ep8.train's step compiled for one described v5e: 16,384
    positions through a full layer with a dense feed-forward, three
    sliding-window layers and a full one with their experts; the two
    full layers on the whole-causal kernels at 48 heads (two forward
    calls under `remat`, one backward, each), the three sliding ones on
    the banded kernels at 64 under names of their own, under the
    layer's own scope; the four expert layers' products on the
    grouped-product kernels at 2048 x 512; arguments + temporaries leave
    the 16 GB chip 0.5 GB and more."""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import profiler
    from predictionio_tpu.ops import attention, moe

    kind = chips[0].device_kind
    for module in (attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "seqrec-laguna-xs2-ep8.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert p.remat and p.max_len == 16384
    assert p.mixer_kinds() == ("gqa", "swa", "swa", "swa", "gqa")
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == 691_624_960
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs,
        seqs).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2 * 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 2
    assert _kernel_calls(text, "window_attention_pallas_fwd") == 3 * 2
    assert _kernel_calls(text, "window_attention_pallas_bwd") == 3
    # both kinds token-first: a pass in front and the gate's pass with
    # every forward call, the gate's backward pass and the pass behind
    # with every backward one, and no activation relaid in either scope
    assert _kernel_calls(text, "grouped_attention_front") == 5 * 2
    assert _kernel_calls(text, "attention_head_gate") == 5 * 2
    assert _kernel_calls(text, "attention_head_gate_bwd") == 5
    assert _kernel_calls(text, "grouped_attention_back") == 5
    for scope in ("seqrec_attention", "seqrec_window_attention"):
        assert not _relayouts(text, p.max_len, scope), scope
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    rows = profiler.parse_scope_table(text, seqrec.STEP_SCOPES)[1]
    scopes = {row[0] for key, row in rows.items()
              if "window_attention_pallas" in key}
    assert scopes == {"seqrec_window_attention"}, scopes
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("window cell step: arguments", memory.argument_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes)
    assert held <= 15.75 * 2 ** 30 - 0.5e9, held


def test_the_packed_cells_step_fits_a_v5e(chips, one_chip, monkeypatch):
    """mellum2-a2.5b-ep4.train's step compiled for one described v5e: 2
    packed rows of 8,192 positions (a position's session and its place in
    it two more arguments) through three sliding-window layers and a full
    one with their experts; the full layer on the whole-causal kernels at
    32 heads over 4 (two forward calls under `remat`, one backward), the
    sliding ones on the banded kernels, both token-first and both told
    the sessions; the four expert layers' products on the grouped-product
    kernels at 2304 x 896; arguments + temporaries leave the 16 GB chip
    0.5 GB and more."""
    import json
    import os

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, moe

    kind = chips[0].device_kind
    for module in (attention, moe):
        monkeypatch.setattr(module, "_device_kind", lambda: kind)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs",
                           "seqrec-mellum2-12b-a2.5b-ep4.json")) as f:
        cfg = json.load(f)
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    assert p.remat and p.packing and p.max_len == 8192
    assert p.mixer_kinds() == ("swa", "swa", "swa", "gqa")
    optimizer = seqrec.make_optimizer(p)
    params = jax.eval_shape(
        lambda: seqrec.init_params(None, cfg["n_items"], p))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) \
        == 595_153_152 + 4 * 64        # and each layer's selection bias
    put = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    rows = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=one_chip)
    compiled = seqrec.make_train_step(None, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), rows,
        rows, rows, rows).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, "flash_attention_pallas_fwd") == 2
    assert _kernel_calls(text, "flash_attention_pallas_bwd") == 1
    assert _kernel_calls(text, "window_attention_pallas_fwd") == 3 * 2
    assert _kernel_calls(text, "window_attention_pallas_bwd") == 3
    assert _kernel_calls(text, "grouped_attention_front") == 4 * 2
    assert _kernel_calls(text, "grouped_attention_back") == 4
    assert _kernel_calls(text, "attention_head_gate") == 0
    for scope in ("seqrec_attention", "seqrec_window_attention"):
        assert not _relayouts(text, p.max_len, scope), scope
    assert _kernel_calls(text, "grouped_product_pallas_[a-z_]*") == 4 * 12
    assert "ragged-dot" not in text
    memory = compiled.memory_analysis()
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("packed cell step: arguments", memory.argument_size_in_bytes,
          "temporaries", memory.temp_size_in_bytes)
    assert held <= 15.75 * 2 ** 30 - 0.5e9, held


@pytest.mark.parametrize("name,tokens,k,d,w,held,devices,kernels", [
    # a pass of each sequence cell's expert layers, forward and backward:
    # kimivl-a3b-ep8.train, lfm2-a2b-ep8.train, qwen3next-a3b-ep16.train
    ("kimi", 16384, 6, 2048, 1408, 8, 1, True),
    ("lfm2", 32768, 4, 2048, 1536, 8, 1, True),
    ("qwen", 16384, 10, 2048, 512, 32, 1, True),
    # a program for four devices keeps `ragged_dot`
    ("kimi-on-a-mesh", 16384, 6, 2048, 1408, 8, 4, False),
])
def test_grouped_product_kernels_compile_for_v5e(
        chips, one_chip, monkeypatch, name, tokens, k, d, w, held, devices,
        kernels):
    """`held_experts` and its backward pass at a cell's sizes: the three
    kernels at `tiles`' row tile by a whole matrix (float32 in two
    buffers, its rounded copy beside it) within the VMEM limit; twelve
    products a pass and no `ragged-dot`."""
    from predictionio_tpu.ops import moe, moe_pallas

    monkeypatch.setattr(moe, "_device_kind", lambda: chips[0].device_kind)
    assert moe_pallas.tiles(tokens, d, w, held) is not None

    def loss(x, w_gate, w_up, w_down, experts, gates):
        y, _, _ = moe.held_experts(
            x, w_gate, w_up, w_down, moe.Routing(experts, gates, None), 0,
            tokens, devices)
        return (y * y).sum()

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 5))).lower(
        shape(tokens, d), shape(held, d, w), shape(held, d, w),
        shape(held, w, d), shape(tokens, k, dtype=jnp.int32),
        shape(tokens, k)).compile()
    text = compiled.as_text()
    calls = {kernel: _kernel_calls(text, f"grouped_product_pallas_{kernel}")
             for kernel in ("rows", "rows_t", "groups")}
    if kernels:
        assert calls == {"rows": 6, "rows_t": 3, "groups": 3}
        assert "ragged-dot" not in text
    else:
        assert not any(calls.values()) and "ragged-dot" in text
    for grad in compiled.out_info:
        assert grad.dtype == jnp.float32
