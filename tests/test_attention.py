"""Long-context attention: ring sequence parallelism vs the dense
reference, on the 8-device virtual CPU mesh (conftest.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.attention import (
    blockwise_attention, mha, ring_attention,
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


def test_ring_key_mask(mesh8):
    rng = np.random.default_rng(10)
    b, l, h, d = 2, 64, 8, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, l, h, d)).astype(np.float32))
               for _ in range(3))
    key_mask = jnp.asarray(np.arange(l)[None, :] >= 24).repeat(b, axis=0)
    dense = mha(q, k, v, causal=True, key_mask=key_mask)
    ring = ring_attention(q, k, v, mesh8, axis="data", causal=True,
                          key_mask=key_mask)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=1e-5)


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

def _pallas_case(b, h, lq, lk, dk, dv, left_pad, seed, group=1):
    """(q, k, v, w [B, L, H, D], key_mask) with row 0's first `left_pad`
    keys padding: under a causal mask its first queries see no key at
    all; k and v at h / group heads. The values are ones bfloat16 holds,
    so that the kernels' rounding of their operands on the way in changes
    nothing and what is left to compare is the rounding inside them."""
    rng = np.random.default_rng(seed)
    mk = lambda l, d, heads=h: jnp.asarray(
        rng.normal(size=(b, l, heads, d)), jnp.bfloat16).astype(jnp.float32)
    mask = np.ones((b, lk), bool)
    mask[0, :left_pad] = False
    return (mk(lq, dk), mk(lk, dk, h // group), mk(lk, dv, h // group),
            mk(lq, dv), jnp.asarray(mask))


#: what the kernels may differ by from `mha` in float32 on the same
#: operands, as a share of the largest value: probabilities and `ds` are
#: rounded to bfloat16 (2**-9) on their way into a product. The cases
#: below read 5.6e-4 to 1.5e-3 forward and 2.0e-3 to 3.9e-3 in a gradient.
#: With the scores rounded to bfloat16 before the exponential (the wrong
#: rounding point) they read 1.8e-3 to 8.3e-3 forward, past the limit in
#: seven cases of ten, the masked and padded ones among them; a gradient
#: cannot tell (`ds` is rounded either way).
_FORWARD_TOL, _GRAD_TOL = 2e-3, 6e-3


def _with_window_and_gate(cases):
    """(..., window, gated) behind the cases that name neither."""
    return [c + (None, False)[len(c) - 9:] for c in cases]


@pytest.mark.parametrize(
    "name,lq,lk,dk,dv,causal,left_pad,group,layout,window,gated",
    _with_window_and_gate([
    ("causal", 384, 384, 24, 16, True, 0, 1, "heads"),   # 3 x 3 blocks of 128
    ("full", 384, 384, 24, 16, False, 0, 1, "heads"),
    ("one-block", 256, 256, 24, 16, True, 0, 1, "heads"),
    # 384 keys in blocks of 128 under a query block of 256, and the other
    # way round
    ("wider-query-blocks", 256, 384, 24, 16, True, 0, 1, "heads"),
    ("wider-key-blocks", 384, 256, 24, 16, True, 0, 1, "heads"),
    ("blocks-of-512", 1536, 1536, 24, 16, True, 0, 1, "heads"),
    ("cell-widths", 384, 384, 192, 128, True, 0, 1, "heads"),
    ("left-padding-and-masked-rows", 384, 384, 24, 16, True, 140, 1, "heads"),
    ("left-padding-full", 256, 384, 24, 16, False, 130, 1, "heads"),
    ("more-keys-than-queries", 256, 512, 24, 16, True, 3, 1, "heads"),
    # four query heads a key/value head (head-first: the one layout
    # that takes grouped heads)
    ("grouped", 256, 256, 24, 16, True, 0, 4, "heads"),
    ("grouped-left-padding-full", 256, 384, 128, 128, False, 130, 4,
     "heads"),
    # token-first: a head is a block of columns of [B, L, heads x width],
    # whole lane tiles wide, behind the rotary pass over [q | k | v]
    ("rows-128", 384, 384, 128, 128, True, 0, 1, "rows"),
    ("rows-256", 256, 256, 256, 256, True, 0, 1, "rows"),
    ("rows-256-left-padding", 256, 256, 256, 256, True, 140, 1, "rows"),
    ("rows-left-padding-and-masked-rows", 384, 384, 128, 128, True, 140, 1,
     "rows"),
    ("rows-left-padding-full", 384, 384, 128, 128, False, 130, 1, "rows"),
    # token-first under grouped heads (`grouped_attention_pallas`, no
    # rotary table): q [B, L, H x D] over k, v [B, L, H / group x D], the
    # whole triangle and a band, against the head-first call
    ("rows-group-4", 384, 384, 128, 128, True, 0, 4, "rows"),
    ("rows-group-6-left-padding", 384, 384, 128, 128, True, 140, 6, "rows"),
    ("rows-group-8", 256, 256, 128, 128, True, 3, 8, "rows"),
    ("rows-group-4-of-256", 256, 256, 256, 256, True, 0, 4, "rows"),
    ("rows-group-8-window-of-a-block", 384, 384, 128, 128, True, 0, 8,
     "rows", 128),
    ("rows-group-6-window-under-a-block", 384, 384, 128, 128, True, 140, 6,
     "rows", 100),
    ("rows-group-4-of-256-window", 256, 256, 256, 256, True, 5, 4, "rows",
     37),
    # the gate of a column a head: its gradient is `delta` (1 - s), no
    # pass of its own, against autodiff of att * sigmoid(gate)[..., None]
    ("rows-group-4-gated", 384, 384, 128, 128, True, 0, 4, "rows", None,
     True),
    ("rows-group-6-gated-left-padding", 256, 256, 128, 128, True, 140, 6,
     "rows", None, True),
    ("rows-group-8-gated-window", 384, 384, 128, 128, True, 9, 8, "rows",
     100, True),
    ("rows-ungrouped-gated-of-256", 256, 256, 256, 256, True, 0, 1, "rows",
     None, True),
]))
def test_pallas_kernels_match_dense(name, lq, lk, dk, dv, causal, left_pad,
                                    group, layout, window, gated):
    """Forward and jax.grad of the kernels against `mha`, on operands
    head-first (`flash_attention_pallas`) or token-first (the kernels as
    `rotary_attention_pallas` reaches them, float32 gradients: q and k
    are turned by `rope` and rounded once on the dense side too). Grouped,
    banded or gated token-first operands (the kernels as
    `grouped_attention_pallas` reaches them) against the head-first
    kernels on the same operands, the gate applied on [B, L, H, D]."""
    from predictionio_tpu.ops import attention_pallas
    from predictionio_tpu.ops.attention import rope
    from predictionio_tpu.ops.attention_pallas import (
        flash_attention_pallas, window_attention_pallas,
    )

    heads = 2 * group
    grouped = layout == "rows" and (group > 1 or window is not None
                                    or gated)
    if layout == "rows":
        assert attention_pallas.layout(dk, dv) == "rows"
        assert lq == lk and dk == dv and (group == 1 or grouped)
    q, k, v, w, mask = _pallas_case(2, heads, lq, lk, dk, dv, left_pad,
                                    seed=len(name), group=group)
    gate = jnp.asarray(np.random.default_rng(lq + group).normal(
        size=(2, lq, heads)), jnp.float32) if gated else None
    heads_first = lambda t: jnp.swapaxes(t, 1, 2)
    flat = lambda t: t.reshape(*t.shape[:2], -1)

    def head_first(q, k, v):
        ops = heads_first(q), heads_first(k), heads_first(v), mask
        if window is not None:
            return heads_first(window_attention_pallas(*ops, window, True))
        return heads_first(flash_attention_pallas(*ops, causal, True))

    def kernel(q, k, v, *gate):
        if grouped:
            return attention_pallas.grouped_attention_pallas(
                flat(q), flat(k), flat(v), gate[0] if gate else None, mask,
                (), (heads, heads // group), (), window, True).reshape(
                    2, lq, heads, dv)
        if layout == "rows":
            return attention_pallas.rotary_attention_pallas(
                jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), mask,
                heads, 1e4, causal, True).reshape(2, lq, heads, dv)
        return head_first(q, k, v)

    def dense(q, k, v, *gate):
        if grouped:
            out = head_first(q, k, v)
            return out * jax.nn.sigmoid(gate[0])[..., None] if gate else out
        if layout == "rows":
            q, k = (rope(t, jnp.arange(lq), 1e4).astype(jnp.bfloat16).astype(
                jnp.float32) for t in (q, k))
        k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
        return mha(q, k, v, causal=causal, key_mask=mask)

    ops = (q, k, v) + ((gate,) if gated else ())
    got, want = kernel(*ops), dense(*ops)
    np.testing.assert_allclose(
        got, want, atol=_FORWARD_TOL * float(jnp.abs(want).max()))
    if causal and left_pad:
        assert not np.asarray(got[0, :left_pad]).any()     # masked rows: 0
    wrt = tuple(range(len(ops)))
    grads = jax.grad(lambda *a: (kernel(*a) * w).sum(), wrt)(*ops)
    wants = jax.grad(lambda *a: (dense(*a) * w).sum(), wrt)(*ops)
    assert len(grads) == (4 if gated else 3)
    for g, want_g in zip(grads, wants):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(
            g, want_g, atol=_GRAD_TOL * float(jnp.abs(want_g).max()))


_YARN = dict(factor=64.0, original_max_len=64, beta_fast=64.0, beta_slow=1.0,
             attention_factor=1.4158883083359672)


@pytest.mark.parametrize("heads,width,length,kv_heads,rotary_dim,yarn", [
    (2, 128, 256, 2, 128, False), (3, 128, 384, 3, 128, False),
    (2, 256, 128, 2, 256, False),
    # three products' outputs (`_grouped_front`, `_grouped_back`): fewer
    # key/value heads than query heads; a rotary part of the width (64 of
    # 128: a column's partner lies 32 lanes away; 64 of 256); a YaRN
    # table with its amplitude; no rotary positions at all
    (8, 128, 256, 2, 128, False), (4, 128, 256, 2, 64, False),
    (6, 128, 256, 1, 64, True), (8, 128, 128, 8, 128, True),
    (4, 256, 128, 2, 64, False), (4, 128, 128, 1, None, False)])
def test_rotary_on_the_flat_columns_is_rope_on_heads(heads, width, length,
                                                     kv_heads, rotary_dim,
                                                     yarn):
    """The pass between the projections and the token-first kernels
    (interpreted): q and k rotated where they lie equal `rope` on [B, L,
    H, D], v passes, each rounded once to bfloat16; its transpose is
    `rope`'s gradient to float32 round-off, in float32 unless it is told
    a narrower type, and then that gradient rounded once. Of one product's
    [q | k | v] at the whole width (`attention_pallas._rotary`,
    `_rotary_backward`) and of three products' q, k, v at any head counts
    and any of `rope`'s variants, which are numbers of
    `ops/attention.rotary_tables` (`_grouped_front`; `_grouped_back`,
    which is handed `dk` and `dv` a block of columns a QUERY head and adds
    a group's up)."""
    from predictionio_tpu.ops import attention_pallas
    from predictionio_tpu.ops.attention import (
        YarnScaling, rope, rotary_tables,
    )

    theta = 1e6
    rng = np.random.default_rng(heads + width)
    scaling = YarnScaling(**_YARN) if yarn else None
    positions, group = jnp.arange(length), heads // kv_heads
    draw = lambda n: jnp.asarray(rng.normal(size=(2, length, n * width)),
                                 jnp.float32)
    by_head = lambda t: t.reshape(2, length, -1, width)

    def turn(t):
        if rotary_dim is None:
            return by_head(t)
        return rope(by_head(t), positions, theta, rotary_dim, scaling)

    def close(got, want, dtype):
        """float32 round-off (a fused multiply-add or not), then the one
        rounding to `dtype`: a value may land on the neighbour."""
        want = want.reshape(got.shape)
        assert got.dtype == dtype
        if dtype == jnp.float32:
            np.testing.assert_allclose(
                got, want, atol=2e-6 * float(jnp.abs(want).max()))
            return
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   atol=2 ** -8 * float(jnp.abs(want).max()))
        assert float((got == want.astype(dtype)).mean()) > 0.999

    if heads == kv_heads and rotary_dim == width and not yarn:
        qkv = draw(3 * heads)
        table = attention_pallas.rotary_table(length, width, theta)

        def turned(qkv):
            q, k, v = jnp.split(qkv, 3, axis=-1)
            return turn(q), turn(k), by_head(v)

        got = attention_pallas._rotary(qkv, *table, heads, True)
        for g, want in zip(got, turned(qkv)):
            assert g.shape == (2, length, heads * width)
            close(g, want, jnp.bfloat16)
        grads = tuple(draw(heads) for _ in got)
        want = jax.vjp(turned, qkv)[1](tuple(by_head(g) for g in grads))[0]
        back = attention_pallas._rotary_backward(*grads, *table, heads, True)
        close(back, want, jnp.float32)
        # the one rounding the backward products of a projection at the
        # default precision would give it
        close(attention_pallas._rotary_backward(*grads, *table, heads, True,
                                                jnp.bfloat16), want,
              jnp.bfloat16)
    tables, shifts = ((), ()) if rotary_dim is None else rotary_tables(
        length, width, theta, rotary_dim, scaling)
    assert len(shifts) == (0 if rotary_dim is None
                           else 1 if rotary_dim == width else 2)
    q, k, v = draw(heads), draw(kv_heads), draw(kv_heads)
    sizes = (heads, kv_heads)

    def turned(q, k, v):        # the kernels' operands, a head a query head
        return turn(q), jnp.repeat(turn(k), group, axis=2), \
            jnp.repeat(by_head(v), group, axis=2)

    got = attention_pallas._grouped_front(q, k, v, tables, sizes, shifts,
                                          True)
    for g, t, want in zip(got, (q, k, v), (turn(q), turn(k), by_head(v))):
        assert g.shape == t.shape
        close(g, want, jnp.bfloat16)
    grads = tuple(draw(heads) for _ in got)
    wants = jax.vjp(turned, q, k, v)[1](tuple(by_head(g) for g in grads))
    for dtype in (jnp.float32, jnp.bfloat16):
        back = attention_pallas._grouped_back(*grads, tables, sizes, shifts,
                                              True, dtype)
        for g, t, want in zip(back, (q, k, v), wants):
            assert g.shape == t.shape
            close(g, want, dtype)


def _interpreted_kernels(monkeypatch):
    """`blockwise_attention` and `rotary_attention` as a v5e would route
    them, the kernels in the Pallas interpreter."""
    from predictionio_tpu.ops import attention, attention_pallas

    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    kernels = attention_pallas.flash_attention_pallas
    rotary = attention_pallas.rotary_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal, True))
    monkeypatch.setattr(
        attention_pallas, "rotary_attention_pallas",
        lambda qkv, mask, heads, theta, causal, grad_dtype=None: rotary(
            qkv, mask, heads, theta, causal, True, grad_dtype))
    banded = attention_pallas.window_attention_pallas
    grouped = attention_pallas.grouped_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "window_attention_pallas",
        lambda q, k, v, mask, window: banded(q, k, v, mask, window, True))
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda *a, operand_dtype=None, packed=False: grouped(
            *a, True, operand_dtype, packed))


def test_rotary_attention_is_one_result_on_every_layout(monkeypatch):
    """`rotary_attention` on a projection's [q | k | v] columns: token
    first through the kernels ("rows": a head 128 wide), head-first
    through them (`layout` patched to "heads") and as the scan give one
    result and one gradient up to the kernels' bfloat16 products, with
    lengths it has to pad (200 -> 256) and a left-padded row; a listener
    hears the route and the layout. The token-first gradient is float32
    as the kernels gave it; a caller that names `grad_dtype` gets that
    gradient rounded once, and the other routes do not read the name."""
    from predictionio_tpu.ops import attention, attention_pallas
    from predictionio_tpu.ops.attention import rotary_attention

    rng = np.random.default_rng(41)
    qkv = jnp.asarray(rng.normal(size=(2, 200, 3 * 2 * 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 200, 2 * 128)), jnp.float32)
    mask = jnp.asarray(np.arange(200)[None, :] >= np.array([[0], [37]]))

    def run(devices=1, grad_dtype=None):
        routes, layouts = set(), set()
        with attention.routes_into(routes, layouts):
            out, pull = jax.vjp(lambda t: rotary_attention(
                t, 2, 1e4, block_k=128, causal=True, key_mask=mask,
                devices=devices, grad_dtype=grad_dtype), qkv)
        return routes, layouts, out, pull(w)[0]

    scan = run()
    assert scan[:2] == ({"xla"}, set())
    _interpreted_kernels(monkeypatch)
    rows = run()
    assert rows[:2] == ({"pallas"}, {"rows"})
    assert run(devices=4)[:2] == ({"xla"}, set())
    as_bfloat16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    rounded = run(grad_dtype=jnp.bfloat16)
    assert rounded[3].dtype == rows[3].dtype == jnp.float32
    assert (rounded[2] == rows[2]).all()
    assert (rounded[3] == as_bfloat16(rows[3])).all()
    assert not (rows[3] == as_bfloat16(rows[3])).all()
    monkeypatch.setattr(attention_pallas, "layout", lambda dk, dv: "heads")
    heads = run()
    assert heads[:2] == ({"pallas"}, {"heads"})
    assert (run(grad_dtype=jnp.bfloat16)[3] == heads[3]).all()
    assert rows[2].shape == (2, 200, 256) and not np.asarray(
        rows[2][1, :37]).any()
    # both layouts round the same operands to bfloat16 for the same
    # products: they differ by the rotation's float32 round-off alone
    for other, tols in ((heads, (2e-3, 5e-3)), (scan, (1e-2, 1e-2))):
        for got, want, tol in zip(rows[2:], other[2:], tols):
            np.testing.assert_allclose(
                got, want, atol=tol * float(jnp.abs(want).max()))


@pytest.mark.parametrize("heads,kv_heads,width,rotary_dim,yarn,window,gated", [
    (6, 1, 128, 64, True, None, True),      # a full layer of the Laguna cell
    (8, 1, 128, 128, False, 100, True),     # a sliding one
    (4, 2, 256, 64, False, None, False),    # 256 wide, a part rotary
    (4, 1, 128, None, False, None, False),  # no rotary positions, no gate
])
def test_grouped_attention_is_one_result_on_both_layouts(
        monkeypatch, heads, kv_heads, width, rotary_dim, yarn, window, gated):
    """`grouped_attention` on three projections' outputs, token-first
    through the kernels, against `rope` on [B, L, H, D],
    `blockwise_attention` head-first through the same kernels and the
    gate on [B, L, H, 1]: one result and one gradient of q, k, v and the
    gate up to the rotation's float32 round-off, with lengths it has to
    pad (200 -> 256) and a left-padded row; a listener hears "rows"; a
    named `operand_dtype` rounds the three projections' gradients and
    the gated output once (float32 arrays of narrow values: what the
    caller's products alone read) and not the gate's gradient, nor an
    output with no gate, which is the kernels' own; `attention_layout`, which its caller asks beforehand,
    says where else there is no such route (a mesh, heads off the lane
    tiles: those the entry itself refuses)."""
    from predictionio_tpu.ops import attention
    from predictionio_tpu.ops.attention import (
        YarnScaling, attention_layout, grouped_attention, rope,
    )

    _interpreted_kernels(monkeypatch)
    rng = np.random.default_rng(45 + heads)
    l, theta = 200, None if rotary_dim is None else 5e5
    scaling = YarnScaling(**_YARN) if yarn else None
    draw = lambda n: jnp.asarray(rng.normal(size=(2, l, n)), jnp.float32)
    q, k, v = (draw(n * width) for n in (heads, kv_heads, kv_heads))
    gate, w = draw(heads) if gated else None, draw(heads * width)
    mask = jnp.asarray(np.arange(l)[None, :] >= np.array([[0], [37]]))
    layout = lambda width, devices: attention_layout(
        None, l, l, width, width, 128, devices=devices, window=window)
    assert layout(width, 1) == "rows" and layout(width, 4) == "xla"
    assert layout(192, 1) == "heads"

    def rows(q, k, v, gate, operand_dtype=None):
        return grouped_attention(
            q, k, v, width, gate=gate, theta=theta, rotary_dim=rotary_dim,
            scaling=scaling, window=window, block_k=128, key_mask=mask,
            operand_dtype=operand_dtype)

    def heads_apart(q, k, v, gate):
        def turn(t):
            t = t.reshape(2, l, -1, width)
            return t if theta is None else rope(t, jnp.arange(l), theta,
                                                rotary_dim, scaling)

        att = blockwise_attention(turn(q), turn(k), v.reshape(2, l, -1, width),
                                  block_k=128, causal=True, key_mask=mask,
                                  window=window)
        if gate is not None:
            att = att * jax.nn.sigmoid(gate)[..., None]
        return att.reshape(2, l, -1)

    def run(fn, **named):
        routes, layouts = set(), set()
        with attention.routes_into(routes, layouts):
            out, pull = jax.vjp(lambda *a: fn(*a, **named), q, k, v, gate)
        assert routes == {"pallas"}
        return layouts, out, pull(w)[:4 if gated else 3]

    layouts, got, grads = run(rows)
    assert layouts == {"rows"}
    layouts, want, wants = run(heads_apart)
    assert layouts == {"heads"}
    assert got.shape == (2, l, heads * width) and not np.asarray(
        got[1, :37]).any()
    np.testing.assert_allclose(got, want,
                               atol=2e-3 * float(jnp.abs(want).max()))
    for g, want_g in zip(grads, wants):
        assert g.dtype == jnp.float32 and g.shape == want_g.shape
        np.testing.assert_allclose(g, want_g,
                                   atol=5e-3 * float(jnp.abs(want_g).max()))
    as_bfloat16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    _, narrow, rounded = run(rows, operand_dtype=jnp.bfloat16)
    assert narrow.dtype == jnp.float32
    assert (narrow == (as_bfloat16(got) if gated else got)).all()
    assert not (got == as_bfloat16(got)).all()
    for g, r in zip(grads[:3], rounded):
        assert r.dtype == jnp.float32 and (r == as_bfloat16(g)).all()
        assert not (g == as_bfloat16(g)).all()
    if gated:
        assert (rounded[3] == grads[3]).all()
    with pytest.raises(AssertionError):
        grouped_attention(*(t[..., :192] for t in (q, k, v)), 192,
                          block_k=128)


def test_blockwise_attention_pads_for_the_pallas_kernels(monkeypatch):
    """`blockwise_attention` on the kernels' route with lengths it has to
    pad (200 -> 256 positions): pad keys masked, pad queries cut off; and
    the route it took is what a listener hears."""
    from predictionio_tpu.ops import attention

    _interpreted_kernels(monkeypatch)
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


@pytest.mark.parametrize("kind,length,dk,dv,devices,layout,window", [
    c + (None,)[len(c) - 6:] for c in [
    ("TPU v5 lite", 8192, 128, 128, 1, "rows"),     # ouro-2.6b-pp8.train
    # laguna-xs2-ep8.train: the full layers, and the sliding ones' band
    ("TPU v5 lite", 16384, 128, 128, 1, "rows"),
    ("TPU v5 lite", 16384, 128, 128, 1, "rows", 512),
    ("TPU v5 lite", 16384, 128, 128, 8, "xla", 512),
    ("TPU v5 lite", 16384, 64, 64, 1, "heads", 512),
    ("TPU v5 lite", 16384, 192, 192, 1, "heads", 512),
    ("TPU v5 lite", 8192, 256, 256, 1, "rows"),     # qwen3next-a3b-ep16
    ("TPU v5 lite", 16384, 256, 256, 1, "rows"),
    ("TPU v5 lite", 8192, 128, 256, 1, "rows"),
    ("TPU v5 lite", 8192, 192, 128, 1, "heads"),    # kimivl-a3b-ep8.train
    ("TPU v5 lite", 32768, 64, 64, 1, "heads"),     # lfm2-a2b-ep8.train
    ("TPU v5 lite", 8192, 192, 64, 1, "heads"),
    # whatever the widths: a mesh, another chip and a `dq` over its VMEM
    # keep the scan
    ("TPU v5 lite", 8192, 128, 128, 4, "xla"),
    ("TPU v5 lite", 8192, 192, 128, 2, "xla"),
    ("TPU v4", 8192, 128, 128, 1, "xla"),
    ("cpu", 8192, 128, 128, 1, "xla"),
    ("TPU v5 lite", 65536, 128, 128, 1, "xla"),
]])
def test_attention_layout(kind, length, dk, dv, devices, layout, window):
    """Where `rotary_attention`'s and `grouped_attention`'s operands lie
    follows the widths alone, on the route `attention_route` gives, under
    a window or none. (`blockwise_attention`, which the latent mixer and
    a grouped one with norms of its heads' own call at any of these
    widths, is head-first on the kernels' route: the next test.)"""
    from predictionio_tpu.ops.attention import (
        attention_layout, attention_route,
    )

    assert attention_layout(kind, length, length, dk, dv, devices=devices,
                            window=window) == layout
    assert (attention_route(kind, length, length, dk, dv, devices=devices,
                            window=window)
            == "xla") == (layout == "xla")


@pytest.mark.parametrize("heads,kv_heads,width", [
    (2, 2, 128), (4, 1, 128), (2, 1, 256)])
def test_blockwise_attention_is_head_first_at_any_width(monkeypatch, heads,
                                                        kv_heads, width):
    """The entry chooses the layout before the widths do: at widths of
    whole lane tiles, grouped or not, `blockwise_attention`'s [B, L, H,
    D] callers are heard on `layout="heads"`."""
    from predictionio_tpu.ops import attention

    _interpreted_kernels(monkeypatch)
    rng = np.random.default_rng(width + heads)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, n, width)), jnp.float32)
               for n in (heads, kv_heads, kv_heads))
    routes, layouts = set(), set()
    with attention.routes_into(routes, layouts):
        got = blockwise_attention(q, k, v, block_k=128, causal=True)
    assert (routes, layouts) == ({"pallas"}, {"heads"})
    want = mha(q, *(jnp.repeat(t, heads // kv_heads, axis=2)
                    for t in (k, v)), causal=True)
    np.testing.assert_allclose(got, want,
                               atol=1e-2 * float(jnp.abs(want).max()))


def test_a_steps_hash_keeps_the_kernels_and_drops_their_locations():
    """`benchmarks/tools/step_text_hash.py`: the text it hashes holds a
    Mosaic kernel's grid, blocks and index maps and not the source lines
    that built it: the same kernel called from two lines hashes alike
    where the raw payloads differ, and another index map does not."""
    import importlib.util
    import os
    import re

    from jax.experimental import pallas as pl

    spec = importlib.util.spec_from_file_location(
        "step_text_hash", os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "tools",
            "step_text_hash.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def call(index):
        return pl.pallas_call(
            kernel, grid=(2,), out_shape=jax.ShapeDtypeStruct(
                (256, 128), jnp.float32),
            in_specs=[pl.BlockSpec((128, 128), index)],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)))

    here = lambda x: call(lambda i: (i, 0))(x)
    there = lambda x: call(
        lambda i: (i, 0))(x)                # the same, a line further down
    other = lambda x: call(lambda i: (1 - i, 0))(x)

    def payload(f):
        text = jax.jit(f).trace(jax.ShapeDtypeStruct(
            (256, 128), jnp.float32)).lower(
                lowering_platforms=("tpu",)).as_text()
        return tool._BODY.search(tool._CONFIG.search(text).group(0)).group(1)

    a, b, c = (payload(f) for f in (here, there, other))
    assert a != b
    assert tool.kernel_text(a) == tool.kernel_text(b)
    assert tool.kernel_text(a) != tool.kernel_text(c)
    assert not re.search(r"loc\(", tool.kernel_text(a))
