"""Long-context attention: ring / Ulysses sequence parallelism vs the dense
reference, on the 8-device virtual CPU mesh (conftest.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.attention import (
    blockwise_attention, mha, ring_attention, ulysses_attention,
)


def qkv(seed=0, b=2, l=64, h=8, d=16, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(b, l, h, d)).astype(np.float32), dtype)
    return mk(), mk(), mk()


def test_blockwise_matches_dense():
    q, k, v = qkv()
    dense = mha(q, k, v)
    block = blockwise_attention(q, k, v, block_k=16)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5)


def test_blockwise_causal_matches_dense():
    q, k, v = qkv(seed=1)
    dense = mha(q, k, v, causal=True)
    block = blockwise_attention(q, k, v, block_k=16, causal=True)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(mesh8, causal):
    q, k, v = qkv(seed=2)
    dense = mha(q, k, v, causal=causal)
    ring = ring_attention(q, k, v, mesh8, axis="data", causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(mesh8, causal):
    q, k, v = qkv(seed=3)
    dense = mha(q, k, v, causal=causal)
    uly = ulysses_attention(q, k, v, mesh8, axis="data", causal=causal)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(dense),
                               atol=1e-5)


def test_ring_attention_bf16_inputs(mesh8):
    q, k, v = qkv(seed=4, dtype=jnp.bfloat16)
    dense = mha(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32))
    ring = ring_attention(q, k, v, mesh8, axis="data")
    assert ring.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(ring, dtype=np.float32), np.asarray(dense), atol=0.05)


def test_ring_rejects_indivisible_seq(mesh8):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 4, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(x, x, x, mesh8, axis="data")


def test_ulysses_rejects_indivisible_heads(mesh8):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 64, 4, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="heads"):
        ulysses_attention(x, x, x, mesh8, axis="data")


def test_key_mask_blocks_padding_keys():
    """Left-padded keys must not receive softmax mass (SASRec pad bug)."""
    rng = np.random.default_rng(9)
    b, l, h, d = 2, 16, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, l, h, d)).astype(np.float32))
               for _ in range(3))
    key_mask = jnp.asarray(np.arange(l)[None, :] >= 6).repeat(b, axis=0)
    dense = mha(q, k, v, causal=True, key_mask=key_mask)
    block = blockwise_attention(q, k, v, block_k=4, causal=True,
                                key_mask=key_mask)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5)
    # masked-out keys must not influence output: zero the padded K/V rows
    k2 = k.at[:, :6].set(0.0)
    v2 = v.at[:, :6].set(99.0)
    block2 = blockwise_attention(q, k2, v2, block_k=4, causal=True,
                                 key_mask=key_mask)
    np.testing.assert_allclose(np.asarray(block2), np.asarray(block),
                               atol=1e-5)


def test_ring_and_ulysses_key_mask(mesh8):
    rng = np.random.default_rng(10)
    b, l, h, d = 2, 64, 8, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, l, h, d)).astype(np.float32))
               for _ in range(3))
    key_mask = jnp.asarray(np.arange(l)[None, :] >= 24).repeat(b, axis=0)
    dense = mha(q, k, v, causal=True, key_mask=key_mask)
    ring = ring_attention(q, k, v, mesh8, axis="data", causal=True,
                          key_mask=key_mask)
    uly = ulysses_attention(q, k, v, mesh8, axis="data", causal=True,
                            key_mask=key_mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-5)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(dense), atol=1e-5)


def test_blockwise_non_divisible_block_k():
    q, k, v = qkv(seed=11, l=60)   # 60 not divisible by default 512
    dense = mha(q, k, v, causal=True)
    block = blockwise_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5)


def test_blockwise_prime_length_padded_blocks():
    q, k, v = qkv(seed=12, l=61)   # prime length exercises K/V padding
    dense = mha(q, k, v, causal=True)
    block = blockwise_attention(q, k, v, block_k=16, causal=True)
    np.testing.assert_allclose(np.asarray(block), np.asarray(dense),
                               atol=1e-5)


# -- the Pallas kernels (ops/attention_pallas.py), interpreted on the CPU --

def _pallas_case(b, h, lq, lk, dk, dv, left_pad, seed):
    """(q, k, v, w [B, L, H, D], key_mask) with row 0's first `left_pad`
    keys padding: under a causal mask its first queries see no key at
    all. The values are ones bfloat16 holds, so that the kernels' rounding
    of their operands on the way in changes nothing and what is left to
    compare is the rounding inside them."""
    rng = np.random.default_rng(seed)
    mk = lambda l, d: jnp.asarray(rng.normal(size=(b, l, h, d)),
                                  jnp.bfloat16).astype(jnp.float32)
    mask = np.ones((b, lk), bool)
    mask[0, :left_pad] = False
    return mk(lq, dk), mk(lk, dk), mk(lk, dv), mk(lq, dv), jnp.asarray(mask)


#: what the kernels may differ by from `mha` in float32 on the same
#: operands, as a share of the largest value: probabilities and `ds` are
#: rounded to bfloat16 (2**-9) on their way into a product. The cases
#: below read 5.6e-4 to 1.5e-3 forward and 2.0e-3 to 3.9e-3 in a gradient.
#: With the scores rounded to bfloat16 before the exponential (the wrong
#: rounding point) they read 1.8e-3 to 8.3e-3 forward, past the limit in
#: seven cases of ten, the masked and padded ones among them; a gradient
#: cannot tell (`ds` is rounded either way).
_FORWARD_TOL, _GRAD_TOL = 2e-3, 6e-3


@pytest.mark.parametrize("name,lq,lk,dk,dv,causal,left_pad", [
    ("causal", 384, 384, 24, 16, True, 0),            # 3 x 3 blocks of 128
    ("full", 384, 384, 24, 16, False, 0),
    ("one-block", 256, 256, 24, 16, True, 0),
    # 384 keys in blocks of 128 under a query block of 256, and the other
    # way round
    ("wider-query-blocks", 256, 384, 24, 16, True, 0),
    ("wider-key-blocks", 384, 256, 24, 16, True, 0),
    ("blocks-of-512", 1536, 1536, 24, 16, True, 0),
    ("cell-widths", 384, 384, 192, 128, True, 0),
    ("left-padding-and-masked-rows", 384, 384, 24, 16, True, 140),
    ("left-padding-full", 256, 384, 24, 16, False, 130),
    ("more-keys-than-queries", 256, 512, 24, 16, True, 3),
])
def test_pallas_kernels_match_dense(name, lq, lk, dk, dv, causal, left_pad):
    """Forward and jax.grad of the kernels against `mha`."""
    from predictionio_tpu.ops.attention_pallas import flash_attention_pallas

    q, k, v, w, mask = _pallas_case(2, 2, lq, lk, dk, dv, left_pad,
                                    seed=len(name))
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)

    def kernel(q, k, v):
        return heads_first(flash_attention_pallas(
            heads_first(q), heads_first(k), heads_first(v), mask, causal,
            True))

    def dense(q, k, v):
        return mha(q, k, v, causal=causal, key_mask=mask)

    got, want = kernel(q, k, v), dense(q, k, v)
    np.testing.assert_allclose(
        got, want, atol=_FORWARD_TOL * float(jnp.abs(want).max()))
    if causal and left_pad:
        assert not np.asarray(got[0, :left_pad]).any()     # masked rows: 0
    grads = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, want_g in zip(grads, wants):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(
            g, want_g, atol=_GRAD_TOL * float(jnp.abs(want_g).max()))


def test_blockwise_attention_pads_for_the_pallas_kernels(monkeypatch):
    """`blockwise_attention` on the kernels' route with lengths it has to
    pad (200 -> 256 positions): pad keys masked, pad queries cut off; and
    the route it took is what a listener hears."""
    from predictionio_tpu.ops import attention, attention_pallas

    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    kernels = attention_pallas.flash_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal, True))
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 200, 2, d)), jnp.float32)
               for d in (64, 64, 128))
    mask = jnp.asarray(np.arange(200)[None, :] >= np.array([[0], [37]]))
    heard = set()
    with attention.routes_into(heard):
        got = blockwise_attention(q, k, v, block_k=128, causal=True,
                                  key_mask=mask)
        blockwise_attention(q, k, v, block_k=128, causal=True,
                            key_mask=mask, devices=4)
    assert heard == {"pallas", "xla"}
    want = mha(q, k, v, causal=True, key_mask=mask)
    np.testing.assert_allclose(got, want, atol=1e-2)
    assert not np.asarray(got[1, :37]).any()


@pytest.mark.parametrize("kind,lq,lk,dk,dv,block,devices,route", [
    ("cpu", 8192, 8192, 192, 128, 512, 1, "xla"),    # off the TPU: the scan
    ("NVIDIA H100", 8192, 8192, 192, 128, 512, 1, "xla"),
    ("TPU v5 lite", 8192, 8192, 192, 128, 512, 1, "pallas"),    # the cell
    ("TPU v5 lite", 1024, 1024, 128, 128, 512, 1, "pallas"),
    ("TPU v5 lite", 256, 256, 64, 128, 512, 1, "pallas"),   # one block
    ("TPU v5 lite", 1536, 1536, 128, 128, 512, 1, "pallas"),   # of 512
    ("TPU v5 lite", 32768, 32768, 192, 128, 512, 1, "xla"),  # dq over VMEM
    ("TPU v5 lite", 200, 200, 192, 128, 128, 1, "pallas"),   # pads to 256
    ("TPU v5 lite", 32, 32, 32, 32, 512, 1, "xla"),     # the default block
    ("TPU v5 lite", 8192, 8192, 96, 128, 512, 1, "xla"),   # off the lanes
    ("TPU v5 lite", 8192, 8192, 192, 96, 512, 1, "xla"),
    # half a lane tile of v: a block's trailing dimension is the array's
    ("TPU v5 lite", 8192, 8192, 192, 64, 512, 1, "pallas"),
    ("TPU v5 lite", 32768, 32768, 64, 64, 512, 1, "pallas"),   # dq 32 MiB
    ("TPU v5 lite", 65536, 65536, 64, 64, 512, 1, "xla"),   # dq over VMEM
    ("TPU v5 lite", 640, 1000, 192, 128, 512, 1, "pallas"),   # pad to 1024
    ("TPU v5 lite", 200, 200, 192, 128, 512, 1, "xla"),   # a block of 200
    ("TPU v5 lite", 8192, 300, 192, 128, 512, 1, "xla"),
    # a program sharded over a mesh: no Mosaic kernel is partitioned
    ("TPU v5 lite", 8192, 8192, 192, 128, 512, 4, "xla"),
    ("TPU v5 lite", 1024, 1024, 128, 128, 512, 2, "xla"),
    # a TPU whose VMEM the kernels' limits were not measured on
    ("TPU v4", 8192, 8192, 192, 128, 512, 1, "xla"),
    ("TPU7x", 8192, 8192, 192, 128, 512, 1, "xla"),
])
def test_attention_route(kind, lq, lk, dk, dv, block, devices, route):
    from predictionio_tpu.ops.attention import attention_route

    assert attention_route(kind, lq, lk, dk, dv, block_k=block,
                           devices=devices) == route
