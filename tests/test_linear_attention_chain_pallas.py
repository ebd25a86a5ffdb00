"""The chain around the gated delta rule as fused passes
(`gated_delta_chain_pallas`, ops/linear_attention_pallas.py, interpreted
on the CPU): the front's and the back's passes and the whole chain
against the plain layer, forward and every gradient. The rule's own
kernels are in tests/test_linear_attention_pallas.py: two files, so that
`--dist loadfile` can hand the interpreted kernels to two workers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_linear_attention_pallas import rel

from predictionio_tpu.ops import linear_attention, linear_attention_pallas


def chain_operands(b, length, heads, seed, taps=4, start=None):
    """The projection's output (sessions left-padded to `start`: zeros
    before it, as the key mask leaves them), taps, gates, scale."""
    hk, hv, dk, dv = heads
    rng = np.random.default_rng(seed)
    total = 2 * hk * dk + 2 * hv * dv
    qkvz = rng.normal(size=(b, length, total))
    if start is not None:
        for row, first in enumerate(start):
            qkvz[row, :first] = 0.0
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        qkvz, rng.normal(size=(taps, total - hv * dv)) * 0.5,
        -rng.uniform(0.0, 3.0, size=(b, length, hv)),
        rng.uniform(size=(b, length, hv)), 1.0 + rng.normal(size=(dv,))))


def plain_front(qkvz, taps, heads):
    hk, hv, dk, dv = heads
    b, length, _ = qkvz.shape
    cuts = [hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv]
    q, k, v, _ = jnp.split(qkvz, cuts, axis=-1)
    q, k, v = (linear_attention.causal_conv(t, w) for t, w in zip(
        (q, k, v), jnp.split(taps, cuts[:2], axis=-1)))

    def unit(t):
        t = t.reshape(b, length, -1, dk)
        t = t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)
        return t.reshape(b, length, -1)

    return unit(q) * dk ** -0.5, unit(k), v


def plain_back(o, qkvz, scale, heads, eps):
    hk, hv, dk, dv = heads
    b, length, _ = o.shape
    o = o.reshape(b, length, hv, dv)
    z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(b, length, hv, dv)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * scale
    return (o * jax.nn.silu(z)).reshape(b, length, -1)


CHAINS = [
    # name, batch rows, length, (Hk, Hv, dk, dv), taps, sessions start at
    ("one-block", 1, 128, (1, 2, 128, 128), 4, None),
    # blocks of 256 rows: the three carried rows cross a boundary
    # inside a session
    ("a-boundary-inside-a-session", 1, 768, (2, 4, 128, 128), 4, None),
    ("left-padded", 2, 384, (2, 4, 128, 128), 4, (131, 254)),
    ("no-whole-block", 2, 300, (2, 2, 128, 128), 4, (0, 7)),
    ("wide-heads-two-taps", 1, 256, (1, 2, 256, 256), 2, None),
    ("eight-heads-a-key-head-nine-taps", 1, 256, (1, 8, 128, 128), 9, None),
]


def whole(t):
    """Filled up to whole row blocks, as `gated_delta_chain_pallas` hands
    its passes their operands."""
    rows = linear_attention_pallas.CHAIN_ROWS[-1]
    return linear_attention_pallas._whole(t, t.shape[1] + -t.shape[1] % rows)


def fused_front(qkvz, w_taps, heads):
    return tuple(t[:, :qkvz.shape[1]] for t in linear_attention_pallas._front(
        whole(qkvz), w_taps, heads, True))


@pytest.mark.parametrize("name,b,length,heads,taps,start", CHAINS)
def test_the_fused_front_is_the_plain_chain(name, b, length, heads, taps,
                                            start):
    """The front's passes (`_front`, `_front_backward`) against
    `causal_conv` + SiLU + unit length + scale: q, k, v and, from given
    cotangents, the gradients of the projection's output and the taps."""
    qkvz, w_taps, *_ = chain_operands(b, length, heads, len(name), taps, start)
    got = fused_front(qkvz, w_taps, heads)
    want, pull = jax.vjp(lambda *a: plain_front(*a, heads), qkvz, w_taps)
    weights = [jnp.asarray(np.random.default_rng(i).normal(size=t.shape),
                           jnp.float32) for i, t in enumerate(want)]
    for a, b_ in zip(got, want):
        assert a.shape == b_.shape and a.dtype == jnp.float32
        assert rel(a, b_) < 1e-6
    d_qkvz, d_taps = linear_attention_pallas._front_backward(
        whole(qkvz), w_taps, [whole(w) for w in weights],
        jnp.zeros_like(whole(qkvz)), heads, True)
    d_want = pull(tuple(weights))
    for a, b_ in zip((d_qkvz[:, :length], d_taps), d_want):
        assert a.shape == b_.shape and rel(a, b_) < 2e-6
    # z's columns are not the front's: it leaves them as it found them
    assert not np.asarray(d_qkvz[..., -heads[1] * heads[3]:]).any()
    if start is not None:       # a left-padded session is the unpadded one
        for row, first in enumerate(start):
            alone = fused_front(qkvz[row:row + 1, first:], w_taps, heads)
            for a, b_ in zip(alone, got):
                np.testing.assert_allclose(a[0], b_[row, first:], rtol=0,
                                           atol=1e-6)


@pytest.mark.parametrize("name,b,length,heads,taps,start", CHAINS[:5])
def test_the_fused_back_is_the_head_norm_times_the_gate(name, b, length,
                                                        heads, taps, start):
    """The back's passes (`_back`, `_back_backward`) against
    `_rms_norm(o) * silu(z)`: the output and, from a given cotangent, the
    gradients of o, z and the scale."""
    qkvz, _, _, _, scale = chain_operands(b, length, heads, len(name), taps)
    rng = np.random.default_rng(5)
    o = jnp.asarray(rng.normal(size=(b, length, heads[1] * heads[3])),
                    jnp.float32)
    w = jnp.asarray(rng.normal(size=o.shape), jnp.float32)
    want, pull = jax.vjp(lambda *a: plain_back(*a, heads, 1e-6), o, qkvz,
                         scale)
    got = linear_attention_pallas._back(whole(o), whole(qkvz), scale, heads,
                                        1e-6, True)[:, :length]
    assert got.shape == want.shape and rel(got, want) < 1e-6
    d_qkvz, d_o, d_scale = linear_attention_pallas._back_backward(
        whole(o), whole(qkvz), scale, whole(w), jnp.zeros_like(whole(qkvz)),
        heads, 1e-6, True)
    for a, b_ in zip((d_o[:, :length], d_qkvz[:, :length], d_scale),
                     pull(w)):
        assert a.shape == b_.shape and rel(a, b_) < 2e-6


@pytest.mark.parametrize("name,b,length,heads,taps,start",
                         [CHAINS[1], CHAINS[3], CHAINS[5]])
def test_the_fused_chain_is_the_plain_layer(monkeypatch, name, b, length,
                                            heads, taps, start):
    """`gated_delta_chain_pallas` (front, the rule's kernels at the key
    heads, back, one backward pass that writes the projection's gradient
    into one array) against the plain chain around the scan, with the
    products' operands left float32: the output and every gradient."""
    monkeypatch.setattr(linear_attention_pallas, "_BF16", jnp.float32)
    hk, hv, dk, dv = heads
    args = chain_operands(b, length, heads, len(name), taps, start)
    w = jnp.asarray(np.random.default_rng(9).normal(
        size=(b, length, hv * dv)), jnp.float32)

    def plain(qkvz, w_taps, g, beta, scale):
        q, k, v = plain_front(qkvz, w_taps, heads)
        o = linear_attention.gated_delta_rule(
            q.reshape(b, length, hk, dk), k.reshape(b, length, hk, dk),
            v.reshape(b, length, hv, dv), g, beta)
        return plain_back(o.reshape(b, length, -1), qkvz, scale, heads, 1e-6)

    fused = lambda *a: linear_attention_pallas.gated_delta_chain_pallas(
        *a, heads, 1e-6, 64, True)
    with jax.default_matmul_precision("highest"):
        got, want = fused(*args), plain(*args)
        d_got = jax.grad(lambda *a: (fused(*a) * w).sum(),
                         (0, 1, 2, 3, 4))(*args)
        d_want = jax.grad(lambda *a: (plain(*a) * w).sum(),
                          (0, 1, 2, 3, 4))(*args)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    for a, b_ in zip(d_got, d_want):
        assert a.shape == b_.shape and rel(a, b_) < 1e-5


def test_the_chains_backward_pass_runs_no_forward_pass_again():
    """Recomputed around (the block under `remat`), the chain's passes
    and the rule's forward kernel run twice and their backward passes
    once; the names the other kernels' metrics match are in none."""
    heads = (1, 2, 128, 128)
    args = chain_operands(1, 128, heads, 3)
    chain = jax.checkpoint(
        lambda *a: jnp.tanh(linear_attention_pallas.gated_delta_chain_pallas(
            *a, heads, 1e-6, 64, True)).sum())
    text = str(jax.make_jaxpr(jax.grad(chain, argnums=(0, 1, 2, 3, 4)))(
        *args))
    for name, calls in (("gdn_chain_front_fwd", 2 * 3),
                        ("gdn_chain_back_fwd", 2),
                        ("gated_delta_rule_pallas_fwd", 2),
                        ("gdn_chain_front_bwd", 3), ("gdn_chain_back_bwd", 1),
                        ("gated_delta_rule_pallas_bwd", 1)):
        assert text.count(f"name={name}") == calls, name
    for other in ("gated_delta_rule_pallas", "flash_attention_pallas",
                  "grouped_product_pallas"):
        assert other not in "gdn_chain_front_fwd gdn_chain_back_bwd"


def test_a_convolution_longer_than_a_block_is_refused():
    with pytest.raises(ValueError, match="reaches over a block"):
        linear_attention_pallas.gated_delta_chain_pallas(
            *chain_operands(1, 128, (1, 1, 128, 128), 0, taps=130),
            (1, 1, 128, 128), 1e-6, 64, True)


def test_the_chains_gradients_take_their_primals_types():
    """bfloat16 operands: the passes see float32, the output is float32
    and every cotangent comes back in its primal's type."""
    heads = (1, 2, 128, 128)
    args = tuple(t.astype(jnp.bfloat16)
                 for t in chain_operands(1, 100, heads, 2))
    fused = lambda *a: linear_attention_pallas.gated_delta_chain_pallas(
        *a, heads, 1e-6, 64, True)
    out, pull = jax.vjp(fused, *args)
    assert out.shape == (1, 100, 256) and out.dtype == jnp.float32
    grads = pull(jnp.ones_like(out))
    for d, t in zip(grads, args):
        assert d.shape == t.shape and d.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(d, np.float32)).all()
    want = jax.vjp(fused, *(t.astype(jnp.float32) for t in args))[1](
        jnp.ones_like(out))
    for d, w in zip(grads, want):
        assert rel(d, w) < 1e-2
