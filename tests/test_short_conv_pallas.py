"""A gated short convolution on the kernels' route
(ops/short_conv_pallas.py, interpreted on the CPU) against the plain
chain `c * causal_conv(b * u)` and its `jax.grad`, and the route that
picks between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import (
    attention_pallas, linear_attention, short_conv_pallas,
)

V5E = attention_pallas.KINDS[0]


def plain(bcu, taps):
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return c * linear_attention.causal_conv(b * u, taps, activation=None)


def operands(b, length, d, taps, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for shape in ((b, length, 3 * d), (taps, d), (b, length, d)))


def on_kernels(monkeypatch):
    """`gated_short_conv` as a v5e would route it, the passes
    interpreted; -> the calls the passes got."""
    passes, calls = short_conv_pallas.gated_short_conv_pallas, []
    monkeypatch.setattr(linear_attention, "_device_kind", lambda: V5E)
    monkeypatch.setattr(
        short_conv_pallas, "gated_short_conv_pallas",
        lambda bcu, taps, **kw: calls.append(bcu.shape) or passes(
            bcu, taps, True, **kw))
    return calls


def test_here_the_chain_is_xlas(monkeypatch):
    """The CPU is no kind the passes are listed for: the model's other
    tests run the plain chain, and a listener hears it apart from the
    delta rule's routes."""
    assert jax.devices()[0].device_kind not in attention_pallas.KINDS
    monkeypatch.setattr(
        short_conv_pallas, "gated_short_conv_pallas",
        lambda *a: pytest.fail("the passes off a v5e"))
    bcu, taps, _ = operands(1, 128, 128, 3)
    rule, conv = set(), set()
    with linear_attention.routes_into(rule, conv):
        out = linear_attention.gated_short_conv(bcu, taps)
    assert (rule, conv) == (set(), {"xla"})
    np.testing.assert_array_equal(out, plain(bcu, taps))
    with linear_attention.routes_into(rule):        # nobody listens: fine
        linear_attention.gated_short_conv(bcu, taps)
    assert rule == set()


# one block; several forward blocks; several backward blocks (of 256 rows)
# and two batch rows, whose halos must not cross; a convolution of 4 taps;
# column blocks and chunks of 128, 256 and 512 + more than one of them
@pytest.mark.parametrize("b,length,d,taps", [
    (1, 128, 128, 3), (1, 384, 128, 3), (2, 768, 128, 3), (2, 256, 256, 4),
    (1, 512, 1024, 3), (2, 128, 384, 4), (1, 256, 128, 1), (1, 128, 128, 12),
])
def test_the_passes_against_the_plain_chain(monkeypatch, b, length, d, taps):
    """y, and from a given cotangent the projection's gradient in each of
    its three column ranges and the taps', float32 to rounding."""
    calls = on_kernels(monkeypatch)
    bcu, w, ct = operands(b, length, d, taps, seed=length + d + taps)
    heard = set()
    with linear_attention.routes_into(set(), heard):
        got, pull = jax.vjp(linear_attention.gated_short_conv, bcu, w)
    want, pull_plain = jax.vjp(plain, bcu, w)
    assert heard == {"pallas"} and calls == [bcu.shape]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    (d_bcu, d_w), (want_bcu, want_w) = pull(ct), pull_plain(ct)
    for name, at in (("b", 0), ("c", d), ("u", 2 * d)):
        np.testing.assert_allclose(
            d_bcu[..., at:at + d], want_bcu[..., at:at + d], rtol=1e-5,
            atol=1e-5, err_msg=name)
    np.testing.assert_allclose(d_w, want_w, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(want_w).max()))


def test_a_batch_rows_halo_is_its_own(monkeypatch):
    """Two sessions in one call are the two calls: a block's earlier rows
    come from its own session, zeros before the first."""
    on_kernels(monkeypatch)
    bcu, w, _ = operands(2, 256, 128, 3, seed=3)
    both = linear_attention.gated_short_conv(bcu, w)
    for row in range(2):
        np.testing.assert_array_equal(
            both[row], linear_attention.gated_short_conv(
                bcu[row:row + 1], w)[0])


def test_a_left_padded_session_is_the_unpadded_one(monkeypatch):
    """Padding positions come in as zeros (the layer masks x, so their
    b, c, u are 0): the real positions' outputs are those of the session
    alone."""
    on_kernels(monkeypatch)
    bcu, w, _ = operands(1, 256, 128, 3, seed=4)
    padded = bcu.at[:, :128].set(0.0)
    got = linear_attention.gated_short_conv(padded, w)
    np.testing.assert_array_equal(got[:, :128], 0.0)
    np.testing.assert_allclose(
        got[:, 128:], linear_attention.gated_short_conv(bcu[:, 128:], w),
        rtol=1e-6, atol=1e-6)


def test_any_floating_type_goes_in_and_comes_out(monkeypatch):
    on_kernels(monkeypatch)
    bcu, w, ct = (t.astype(jnp.bfloat16) for t in operands(1, 128, 128, 3))
    out, pull = jax.vjp(linear_attention.gated_short_conv, bcu, w)
    assert out.dtype == jnp.bfloat16
    assert [t.dtype for t in pull(ct)] == [jnp.bfloat16] * 2
    np.testing.assert_allclose(
        out.astype(jnp.float32),
        plain(bcu.astype(jnp.float32), w.astype(jnp.float32)),
        rtol=2e-2, atol=2e-2)


def test_a_named_gradient_type_is_the_float32_gradient_rounded_once(
        monkeypatch):
    """A caller that names `grad_dtype` gets the projection's gradient
    computed in float32 and rounded once where the pass writes it (what
    a product at one bfloat16 pass would round it to itself); y and the
    taps' gradient are what they were, and XLA's chain does not read
    the name."""
    bcu, w, ct = operands(2, 256, 128, 3, seed=6)

    def run(grad_dtype=None):
        out, pull = jax.vjp(lambda bcu, w: linear_attention.gated_short_conv(
            bcu, w, grad_dtype=grad_dtype), bcu, w)
        return (out, *pull(ct))

    plain_chain = run()
    assert all((a == b).all() for a, b in zip(run(jnp.bfloat16), plain_chain))
    on_kernels(monkeypatch)
    exact, rounded = run(), run(jnp.bfloat16)
    as_bfloat16 = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    assert rounded[1].dtype == exact[1].dtype == jnp.float32
    assert (rounded[0] == exact[0]).all() and (rounded[2] == exact[2]).all()
    assert (rounded[1] == as_bfloat16(exact[1])).all()
    assert not (exact[1] == as_bfloat16(exact[1])).all()


@pytest.mark.parametrize("kind,devices,d,length,taps,route", [
    (V5E, 1, 2048, 32768, 3, "pallas"),
    (V5E, 1, 128, 128, 4, "pallas"),
    (V5E, 1, 128, 128, 129, "pallas"),  # the widest halo: a block's rows
    (V5E, 1, 128, 128, 130, "xla"),
    (V5E, 4, 2048, 32768, 3, "xla"),    # a mesh: no Mosaic kernel is split
    (V5E, 1, 2048 + 64, 32768, 3, "xla"),
    (V5E, 1, 64, 128, 3, "xla"),
    (V5E, 1, 2048, 32768 - 64, 3, "xla"),
    (V5E, 1, 2048, 200, 3, "xla"),      # serving's forward, a short session
    ("cpu", 1, 2048, 32768, 3, "xla"),
    ("TPU v4", 1, 2048, 32768, 3, "xla"),
])
def test_the_route_is_decided_from_kind_devices_and_shapes(kind, devices, d,
                                                           length, taps,
                                                           route):
    assert linear_attention.gated_short_conv_route(
        kind, length, d, taps, devices) == route
    assert short_conv_pallas.tiles(length, d, taps) == (
        route == "pallas" or kind != V5E or devices != 1)


def test_under_a_checkpoint_the_forward_pass_runs_twice(monkeypatch):
    """A block recomputed around the chain: the backward pass keeps the
    projection's output alone, so the forward pass is run again and the
    backward pass once."""
    on_kernels(monkeypatch)
    runs = {"_forward": 0, "_backward": 0}
    for name in runs:
        real = getattr(short_conv_pallas, name)

        def counted(*a, real=real, name=name):
            runs[name] += 1
            return real(*a)

        monkeypatch.setattr(short_conv_pallas, name, counted)
    bcu, w, ct = operands(1, 128, 128, 3)
    _, pull = jax.vjp(jax.checkpoint(linear_attention.gated_short_conv),
                      bcu, w)
    pull(ct)
    assert runs == {"_forward": 2, "_backward": 1}
