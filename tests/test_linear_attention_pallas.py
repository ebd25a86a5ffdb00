"""The gated delta rule on the kernels' route
(ops/linear_attention_pallas.py, interpreted on the CPU) against the scan
and against the recurrence written position by position, forward and
every gradient; and the route that picks between kernels and scan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import (
    attention_pallas, linear_attention, linear_attention_pallas,
)

V5E = attention_pallas.KINDS[0]


def operands(b, length, h, dk, dv, seed, g_range=(0.0, 3.0), beta=None):
    rng = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        unit(rng.normal(size=(b, length, h, dk))) * dk ** -0.5,
        unit(rng.normal(size=(b, length, h, dk))),
        rng.normal(size=(b, length, h, dv)),
        -rng.uniform(*g_range, size=(b, length, h)),
        rng.uniform(size=(b, length, h)) if beta is None
        else np.full((b, length, h), beta)))


def by_position(q, k, v, g, beta):
    """S' = exp(g_t) S; S = S' + k_t (beta_t (v_t - S'^T k_t))^T;
    o_t = S^T q_t, one (batch row, head) at a time."""
    def one_head(q, k, v, g, beta):
        def position(s, x):
            q_t, k_t, v_t, g_t, beta_t = x
            s = jnp.exp(g_t) * s
            s = s + jnp.outer(k_t, beta_t * (v_t - s.T @ k_t))
            return s, s.T @ q_t

        return jax.lax.scan(position, jnp.zeros((q.shape[1], v.shape[1])),
                            (q, k, v, g, beta))[1]

    return jax.vmap(jax.vmap(one_head, in_axes=1, out_axes=1))(
        q, k, v, g, beta)


def on_kernels(monkeypatch):
    """`gated_delta_rule` as a v5e would route it, the kernels
    interpreted."""
    monkeypatch.setattr(linear_attention, "_device_kind", lambda: V5E)
    kernels = linear_attention_pallas.gated_delta_rule_pallas
    monkeypatch.setattr(linear_attention_pallas, "gated_delta_rule_pallas",
                        lambda *a: kernels(*a, True))


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


CASES = [
    # name, batch rows, length, heads, dk, dv, log-decay's range, beta
    ("one-chunk", 1, 64, 2, 128, 128, (0.0, 3.0), None),
    ("several-chunks", 1, 256, 2, 128, 128, (0.0, 3.0), None),
    ("a-part-of-a-chunk", 1, 150, 1, 128, 128, (0.0, 3.0), None),
    ("two-batch-rows-three-heads", 2, 128, 3, 128, 128, (0.0, 3.0), None),
    ("dk-over-dv", 1, 128, 2, 256, 128, (0.0, 3.0), None),
    ("dv-over-dk", 1, 128, 2, 128, 256, (0.0, 3.0), None),
    # the decay's rate reaches 16 and a softplus several units: nothing
    # of a position survives the next
    ("strong-decay", 1, 192, 2, 128, 128, (30.0, 80.0), None),
    ("no-decay", 1, 128, 2, 128, 128, (0.0, 0.0), None),
    ("beta-0", 1, 128, 2, 128, 128, (0.0, 3.0), 0.0),
    ("beta-1", 1, 128, 2, 128, 128, (0.0, 3.0), 1.0),
]


@pytest.mark.parametrize("name,b,length,h,dk,dv,g_range,beta", CASES)
def test_the_kernels_route_is_the_scan_and_the_recurrence(
        monkeypatch, name, b, length, h, dk, dv, g_range, beta):
    """With the products' operands left float32 the kernels are the scan
    to float32 roundings, forward and in every gradient, and both are
    the recurrence."""
    args = operands(b, length, h, dk, dv, len(name), g_range, beta)
    w = jnp.asarray(np.random.default_rng(1).normal(
        size=(b, length, h, dv)), jnp.float32)

    def out_and_grads(rule):
        return rule(*args), jax.grad(
            lambda *a: (rule(*a) * w).sum(), argnums=(0, 1, 2, 3, 4))(*args)

    with jax.default_matmul_precision("highest"):
        scan, scan_grads = out_and_grads(linear_attention.gated_delta_rule)
        plain, plain_grads = out_and_grads(by_position)
        on_kernels(monkeypatch)
        monkeypatch.setattr(linear_attention_pallas, "_BF16", jnp.float32)
        heard = set()
        with linear_attention.routes_into(heard):
            got, got_grads = out_and_grads(linear_attention.gated_delta_rule)
    assert heard == {"pallas"}
    assert got.shape == (b, length, h, dv) and got.dtype == jnp.float32
    assert rel(got, scan) < 1e-5 and rel(got, plain) < 1e-4
    for g, of_scan, of_plain in zip(got_grads, scan_grads, plain_grads):
        # under the strong decay the gradient of g itself underflows
        scale = max(float(jnp.abs(of_plain).max()), 1e-6)
        assert float(jnp.abs(g - of_scan).max()) < 1e-4 * scale
        assert float(jnp.abs(g - of_plain).max()) < 1e-3 * scale


@pytest.mark.parametrize("length,h", [(256, 2), (100, 3)])
def test_bfloat16_operands_stay_within_their_rounding(monkeypatch, length, h):
    """As the kernels run on the chip: every product's operands rounded
    to bfloat16 once, sums and states float32."""
    args = operands(1, length, h, 128, 128, 7)
    w = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, length, h, 128)), jnp.float32)
    loss = lambda rule: lambda *a: (rule(*a) * w).sum()
    want = by_position(*args)
    wants = jax.grad(loss(by_position), argnums=(0, 1, 2, 3, 4))(*args)
    on_kernels(monkeypatch)
    got = linear_attention.gated_delta_rule(*args)
    grads = jax.grad(loss(linear_attention.gated_delta_rule),
                     argnums=(0, 1, 2, 3, 4))(*args)
    assert 1e-6 < rel(got, want) < 1e-2         # rounded, and no further
    for g, want_g in zip(grads, wants):
        assert rel(g, want_g) < 1e-2


def kernels(*args):
    return linear_attention_pallas.gated_delta_rule_pallas(*args, 64, True)


def test_the_kernels_take_whole_grid_steps():
    """`gated_delta_rule` fills a length up; the kernels alone refuse
    one chunk of a pair."""
    with pytest.raises(ValueError, match="whole grid steps"):
        kernels(*operands(1, 192, 1, 128, 128, 0))
    with pytest.raises(ValueError, match="whole grid steps"):
        kernels(*operands(1, 100, 1, 128, 128, 0))


def test_a_forward_pass_alone_writes_no_states(monkeypatch):
    """Only a pass that a backward pass follows saves the chunks'
    states; both passes give the same output."""
    args = operands(1, 128, 2, 128, 128, 3)
    seen = []
    forward = linear_attention_pallas._forward
    monkeypatch.setattr(
        linear_attention_pallas, "_forward",
        lambda *a, **kw: seen.append(kw["save_states"]) or forward(*a, **kw))
    alone = kernels(*args)
    kept, pull = jax.vjp(kernels, *args)
    assert seen == [False, True]
    np.testing.assert_array_equal(alone, kept)
    assert all(np.isfinite(np.asarray(g)).all() for g in pull(kept))


def test_recomputed_around_the_rule_runs_its_forward_kernel_again():
    """A caller that recomputes around the rule alone
    (`jax.checkpoint`) calls the forward kernel twice and the backward
    kernel once: nothing of the kernels' is named to be kept (the
    model's layer runs the rule inside `gated_delta_chain_pallas`, whose
    own backward pass keeps what it needs)."""
    args = operands(1, 128, 2, 128, 128, 5)
    text = str(jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda *a: jnp.tanh(kernels(*a)).sum()),
        argnums=(0, 1, 2, 3, 4)))(*args))
    assert text.count("name=gated_delta_rule_pallas_fwd") == 2
    assert text.count("name=gated_delta_rule_pallas_bwd") == 1


@pytest.mark.parametrize("kind,dk,dv,devices,route", [
    (V5E, 128, 128, 1, "pallas"),
    (V5E, 256, 128, 1, "pallas"),
    (V5E, 128, 256, 1, "pallas"),
    ("cpu", 128, 128, 1, "xla"),
    ("TPU v4", 128, 128, 1, "xla"),
    ("NVIDIA H100 80GB HBM3", 128, 128, 1, "xla"),
    (V5E, 128, 128, 2, "xla"),            # a mesh: no Mosaic kernel
    (V5E, 128, 128, 4, "xla"),
    (V5E, 64, 128, 1, "xla"),             # half a lane tile
    (V5E, 128, 96, 1, "xla"),
    (V5E, 8, 8, 1, "xla"),
    (V5E, 512, 128, 1, "xla"),            # a state no test compiled
])
def test_the_route_is_a_function_of_kind_widths_and_devices(
        kind, dk, dv, devices, route):
    assert linear_attention.gated_delta_rule_route(kind, dk, dv, devices) \
        == route


def test_here_the_rule_takes_the_scan():
    """The CPU is no kind the kernels are listed for: every other test
    of the rule runs the scan, and a listener hears it."""
    assert jax.devices()[0].device_kind not in attention_pallas.KINDS
    heard = set()
    with linear_attention.routes_into(heard):
        linear_attention.gated_delta_rule(*operands(1, 70, 1, 128, 128, 0))
    assert heard == {"xla"}
    assert linear_attention_pallas.tiles(128, 128)


# -- key heads read where they lie --------------------------------------

@pytest.mark.parametrize("group,heads", [(1, 2), (2, 2), (4, 2), (2, 1),
                                         (4, 1), (3, 2)])
def test_the_kernels_read_a_key_head_where_it_lies(monkeypatch, group, heads):
    """q, k at Hk key heads against the same call with q, k repeated to
    the value heads: the output and all five gradients, `dq` and `dk`
    summed over a key head's value heads (inside the kernel where a grid
    step covers the group, after it where it covers a part: 4 over
    `HEADS` 2, any over 1; 3 value heads a key head take one a step)."""
    monkeypatch.setattr(linear_attention_pallas, "HEADS", (heads, 1))
    hk = 2
    q, k, v, g, beta = operands(1, 256, hk * group, 128, 128, 11)
    q, k = q[:, :, ::group], k[:, :, ::group]
    w = jnp.asarray(np.random.default_rng(3).normal(size=v.shape),
                    jnp.float32)
    loss = lambda rule: lambda *a: (rule(*a) * w).sum()
    repeated = lambda q, k, *rest: kernels(
        jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2), *rest)
    at = linear_attention_pallas._layout(1, 256, hk, hk * group, 64, True)
    assert at.heads == (1 if group == 3 else heads)
    assert at.shared is (group > 1)
    args = (q, k, v, g, beta)
    np.testing.assert_allclose(kernels(*args), repeated(*args), rtol=0,
                               atol=0)
    got = jax.grad(loss(kernels), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(repeated), argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert rel(a, b) < 1e-6


def test_the_scan_takes_key_heads_too():
    """`gated_delta_rule` on the scan's route repeats q and k itself."""
    q, k, v, g, beta = operands(1, 130, 4, 128, 128, 13)
    q, k = q[:, :, ::2], k[:, :, ::2]
    want = linear_attention.gated_delta_rule(
        jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v, g, beta)
    np.testing.assert_array_equal(
        linear_attention.gated_delta_rule(q, k, v, g, beta), want)
    with pytest.raises(ValueError, match="whole groups"):
        kernels(q[:, :128], k[:, :128], v[:, :128, :3], g[:, :128, :3],
                beta[:, :128, :3])


# -- the chain around the rule as fused passes --------------------------

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
