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
