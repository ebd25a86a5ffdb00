"""The TPU kernels of the main path compiled at their real sizes for a
described (not attached) v5e: what Mosaic or the XLA TPU compiler would
refuse on the chip (a block off the tiling, too much VMEM) fails here,
and a step traced for a mesh keeps the scans. Nothing runs, so nothing
is measured.

One of three files (`test_tpu_compile_kernels.py`,
`test_tpu_compile_steps_a.py`, `test_tpu_compile_steps_b.py`) so that
`--dist loadfile` can hand them to three workers: the topology's fixtures
(`chips`, `one_chip`) and the helpers live in tests/conftest.py, and the
driver's command lets each worker load the TPU's library
(`ALLOW_MULTIPLE_LIBTPU_LOAD=1`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import _kernel_calls, _relayouts
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)


def _lower_step(chips, p, mesh_shape):
    """`p`'s train step lowered for the first described chip or, with a
    `mesh_shape`, for a ("data", "model") mesh of the four: the batch
    over "data", everything else whole."""
    from predictionio_tpu.models import seqrec

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
    seqs = jax.ShapeDtypeStruct((p.batch_size, p.max_len), jnp.int32,
                                sharding=rows or whole)
    return seqrec.make_train_step(mesh, p, optimizer).lower(
        put(params), put(jax.eval_shape(optimizer.init, params)), seqs, seqs)


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
    text = _lower_step(chips, p, mesh_shape).compile().as_text()
    assert ("flash_attention_pallas_bwd" in text) is kernels

    if mesh_shape is not None:      # what the route keeps the step from
        monkeypatch.setattr(attention, "attention_route",
                            lambda *a, **kw: "pallas")
        with pytest.raises(NotImplementedError, match="partitioned"):
            _lower_step(chips, p, mesh_shape)


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
    text = _lower_step(chips, p, mesh_shape).compile().as_text()
    for kernel in ("gated_delta_rule_pallas_fwd",
                   "gated_delta_rule_pallas_bwd",
                   "flash_attention_pallas_bwd"):
        assert (kernel in text) is kernels, kernel


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
