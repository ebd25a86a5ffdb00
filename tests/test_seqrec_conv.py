"""The sequence model under a pattern of gated short-convolution layers
and ungated grouped-query attention, a leading dense layer, sigmoid-routed
experts behind a selection bias with no shared expert, and a tied head,
against the plain reference the benchmark brings
(benchmarks/checks/seqrec_conv_reference.py), on seeded random weights at
a small size; and the pieces the spec is made of against their
hand-computed values."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    VOCAB, batch, model, small_blocks,
)

from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import linear_attention, moe
from predictionio_tpu.ops.attention import mha, rope

#: the record (tests/seqrec_cases.py): a session of 24 takes three
#: attention blocks and a step's 48 tokens four token blocks
CASE = cases.CASES["conv"]
ref, L, PATTERN = CASE.ref, CASE.length, CASE.spec["mixer"]
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(model, pad):
    _, _, grads, _, _ = cases.loss_and_every_gradient_match_the_reference(
        model, pad)
    # the convolution's own groups, the dense layer's, no head (it is the
    # table) and no shared expert
    groups = seqrec._group_norms(grads)
    assert {"layer0.short_conv", "layer0.ffn", "layer1.attention",
            "layer1.experts", "layer4.short_conv", "embedding"} <= set(groups)
    assert not any("head" in g or "shared" in g for g in groups)
    # the selection bias takes no gradient
    assert not np.asarray(grads["layers"][2]["router_bias"]).any()


def test_logits_match_the_reference(model):
    cases.logits_match_the_reference(model)


def test_the_causal_convolution_with_and_without_its_activation():
    """`causal_conv` by hand at a length of 5: the taps' sum as it is
    (a gated short convolution), and under SiLU what it gave before it
    was told (the delta rule's convolution: the default)."""
    x = jnp.asarray(np.arange(10.0).reshape(1, 5, 2) - 4.0, jnp.float32)
    w = jnp.asarray([[1.0, 0.5], [0.0, -1.0], [2.0, 0.25]], jnp.float32)
    pre = np.zeros((5, 2))
    xs = np.asarray(x[0])
    for t in range(5):
        for j in range(3):
            if t - 2 + j >= 0:
                pre[t] += np.asarray(w)[j] * xs[t - 2 + j]
    plain = linear_attention.causal_conv(x, w, activation=None)
    np.testing.assert_allclose(plain[0], pre, rtol=1e-6, atol=1e-6)
    with_silu = pre / (1 + np.exp(-pre))
    for got in (linear_attention.causal_conv(x, w),
                linear_attention.causal_conv(x, w, activation=jax.nn.silu)):
        np.testing.assert_allclose(got[0], with_silu, rtol=1e-5, atol=1e-6)


def test_the_convolution_mixer_by_hand():
    """A length of 5, d 2, three taps: [B | C | u] = x W_in; y_t = (C_t *
    sum_j w[j] (B u)_{t-2+j}) W_out, position by position in numpy; a
    padding position contributes nothing."""
    rng = np.random.default_rng(2)
    d, l = 2, 5
    layer = {"conv_in": rng.normal(size=(d, 3 * d)),
             "conv_taps": rng.normal(size=(3, d)),
             "conv_out": rng.normal(size=(d, d))}
    x = rng.normal(size=(1, l, d))
    mask = np.asarray([[False, True, True, True, True]])
    with jax.default_matmul_precision("highest"):
        got = seqrec._short_conv(
            jax.tree.map(lambda t: jnp.asarray(t, jnp.float32), layer),
            jnp.asarray(x, jnp.float32), jnp.asarray(mask))
    want = np.zeros((l, d))
    streams = np.where(mask[0][:, None], x[0], 0.0) @ layer["conv_in"]
    b, c, u = streams[:, :d], streams[:, d:2 * d], streams[:, 2 * d:]
    a = b * u
    for t in range(l):
        mixed = sum(layer["conv_taps"][j] * a[t - 2 + j]
                    for j in range(3) if t - 2 + j >= 0)
        want[t] = (c[t] * mixed) @ layer["conv_out"]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[0, 0]).any()
    # position 1 sees nothing of the padding before it: one tap only
    np.testing.assert_allclose(
        got[0, 1], (c[1] * layer["conv_taps"][2] * a[1]) @ layer["conv_out"],
        rtol=1e-5, atol=1e-6)


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


@pytest.mark.parametrize("route,lq,width", [("xla", 24, 16),
                                            ("pallas", 256, 64)])
def test_ungated_grouped_query_attention_is_mha_over_repeated_heads(
        route, lq, width, monkeypatch):
    """The `gqa` mixer without its gate, 4 query heads a key/value head,
    rotary positions on the whole head: the layer's output is `mha` on
    the heads repeated, through `wo`; on the scan at a head width of 16
    and through the kernels (in the Pallas interpreter; their products
    take bfloat16 operands) at q/k = v = 64, half a lane tile."""
    from predictionio_tpu.ops import attention, attention_pallas

    if route == "pallas":
        assert attention_pallas.tiles(lq, lq, width, width)
        monkeypatch.setattr(attention, "_device_kind",
                            lambda: attention_pallas.KINDS[0])
        kernels = attention_pallas.flash_attention_pallas
        monkeypatch.setattr(
            attention_pallas, "flash_attention_pallas",
            lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal,
                                                  True))
        monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 512)
    p = small_spec(head_dim=width, rotary_dim=width, max_len=lq)
    layer = weights(p)["layers"][1]
    assert "wq" in layer and "wq_gate" not in layer
    rng = np.random.default_rng(len(route))
    x = jnp.asarray(rng.normal(size=(2, lq, 64)), jnp.float32)
    mask = jnp.asarray(np.arange(lq)[None, :] >= np.array([[0], [5]]))

    def mixer(layer, x):
        heard = set()
        with attention.routes_into(heard):
            out = seqrec._attention(layer, x, mask, p, "gqa", None, False)
        assert heard == {route}
        return out

    def dense(layer, x):
        positions = jnp.arange(lq)
        q, k = (rope(seqrec._norm((x @ layer[w]).reshape(2, lq, -1, width),
                                  layer[n], p), positions, p.rope_theta)
                for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
        v = (x @ layer["wv"]).reshape(2, lq, -1, width)
        k, v = (jnp.repeat(t, 4, axis=2) for t in (k, v))
        att = mha(q, k, v, causal=True, key_mask=mask)
        return att.reshape(2, lq, -1) @ layer["wo"]

    tol = 2e-2 if route == "pallas" else 1e-5
    with jax.default_matmul_precision("highest"):
        got, want = mixer(layer, x), dense(layer, x)
        np.testing.assert_allclose(got, want,
                                   atol=tol * float(jnp.abs(want).max()))
        w = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
        grads = jax.grad(lambda *a: (mixer(*a) * w).sum(), (0, 1))(layer, x)
        wants = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1))(layer, x)
    for g, want_g in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        np.testing.assert_allclose(
            g, want_g, atol=tol * float(jnp.abs(want_g).max()))


def test_the_attention_gate_is_a_field_and_gated_by_default():
    """The Qwen cell's spec names no `attention_gate` and keeps its
    `wq_gate`; without the gate the projection has the queries' half."""
    gated = small_spec(attention_gate=True)
    assert seqrec.SeqRecParams().attention_gate is True
    a = seqrec.init_params(np.random.default_rng(0), 20, gated)["layers"][1]
    b = seqrec.init_params(np.random.default_rng(0), 20,
                           small_spec())["layers"][1]
    assert a["wq_gate"].shape == (64, 2 * 64) and "wq" not in a
    assert b["wq"].shape == (64, 64) and "wq_gate" not in b
    assert gated.spec_key() != small_spec().spec_key()


def test_the_routers_normaliser_by_hand():
    """Gates s_e / (sum of the chosen s + eps): 1e-6 in this family,
    1e-20 by default (what every older spec computes)."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.float32)
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.0, 1.5]],
                    jnp.float32)
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(w)))
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    got = moe.route(x, w, bias, 2, norm_eps=0.5)
    assert got.experts.tolist() == [[2, 0], [2, 3]]     # score + bias
    np.testing.assert_allclose(
        got.gates, [s[0, [2, 0]] / (s[0, [2, 0]].sum() + 0.5),
                    s[1, [2, 3]] / (s[1, [2, 3]].sum() + 0.5)], rtol=1e-6)
    old = moe.route(x, w, bias, 2)
    np.testing.assert_allclose(old.gates.sum(-1), [1.0, 1.0], rtol=1e-6)
    # the spec's epsilon reaches the layer
    p = small_spec(router_norm_eps=0.5)
    layer = weights(p)["layers"][2]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(1, L, 64)),
                    jnp.float32)
    y_half, _ = seqrec._moe(layer, h, p)
    y_tiny, _ = seqrec._moe(layer, h, small_spec())
    assert float(jnp.abs(y_half).max()) < float(jnp.abs(y_tiny).max())


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips of two experts each: their parts of an expert layer
    add up to what the uncut reference gives for the whole layer of 16
    experts. Nothing is counted once: there is no shared expert, and a
    chip that holds none of a token's experts adds zeros."""
    p = small_spec()
    params = weights(p)
    layer = params["layers"][2]
    assert "shared" not in layer
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, L, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, load = ref.expert_layer(layer, x[0], ref_spec(p))
        nothing = ref.expert_layer(layer, x[0],
                                   ref_spec(p, held_experts=(0, 0)))[0]
        parts = []
        for lo in range(0, 16, 2):
            share = dataclasses.replace(p, held_experts=(lo, lo + 2))
            held = dict(layer, experts=jax.tree.map(
                lambda w: w[lo:lo + 2], layer["experts"]))
            y, stats = seqrec._moe(held, x, share)
            parts.append(y[0])
            assert np.array_equal(stats["load"], load)
            assert np.array_equal(stats["held_tokens"], load[lo:lo + 2])
            assert int(stats["dropped"]) == 0
    assert int(load.sum()) == 4 * L
    assert not np.asarray(nothing).any()
    np.testing.assert_allclose(sum(parts), whole, atol=2e-6)


def test_a_step_adds_what_the_references_adamw_and_bias_update_add(model):
    """A router's group holds the selection bias, moved by its layer's
    load and left alone by adamw: the bias itself, and the layers the
    step reports by mixer."""
    after, stats, _, load, _ = \
        cases.a_step_adds_what_the_references_adamw_adds(
            model, "layer3.short_conv")
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"conv": 4, "gqa": 1}
    assert np.array_equal(stats["load"], load)
    assert np.asarray(stats["load"]).shape == (4, 16)
    for n, (before, layer) in enumerate(zip(model.params["layers"][1:],
                                            after["layers"][1:])):
        np.testing.assert_allclose(
            layer["router_bias"], ref.bias_after_step(
                np.asarray(before["router_bias"]), load[n], 0.001),
            atol=1e-7)
    assert "router_bias" not in after["layers"][0]


def test_a_train_steps_record_against_the_reference_and_the_int8_control(
        model):
    cases.a_train_steps_record_against_the_reference_and_the_int8_control(
        model, ("attention", "experts", "ffn"))


def test_the_tied_heads_gradient_reaches_the_table_from_both_ends(model):
    """Under `remat` and token blocks: the table's gradient is the sum
    of what the lookups and what the head send it. The lookups' part is
    0 in the rows of items that are no input; the head's part reaches
    every row."""
    params = model.params
    seqs, targets = batch(seed=9)
    _, tied = model.loss_and_grads(seqs, targets)
    _, lookups = model.of(tied_head=False).loss_and_grads(
        seqs, targets, params={**params, "head": params["emb"].T})
    head = lookups.pop("head")
    np.testing.assert_allclose(tied["emb"], lookups["emb"] + head.T,
                               rtol=1e-4, atol=1e-7)
    unseen = np.setdiff1d(np.arange(VOCAB), seqs.ravel())
    assert len(unseen) and not np.asarray(lookups["emb"])[unseen].any()
    assert np.asarray(head.T)[unseen].any(axis=1).all()
    assert "head" not in params


def test_a_mixer_sequence_as_long_as_the_layers_with_a_leading_dense_layer():
    """No period from layer 0: a dense convolution layer, then attention,
    convolution x 3. The sequence names each layer; it is part of a
    run's identity and comes back from a list as from a tuple."""
    p = small_spec()
    assert p.mixer_kinds() == PATTERN
    assert [p.ffn_kind(i) for i in range(5)] == ["swiglu"] + ["moe"] * 4
    assert small_spec(mixer=list(PATTERN)).spec_key() == p.spec_key()
    assert p.spec_key() != small_spec(
        mixer=("gqa", "conv", "conv", "conv", "conv")).spec_key()
    hash(p.spec_key())
    params = seqrec.init_params(np.random.default_rng(0), 20, p)
    assert sorted(params["layers"][0]) == sorted(
        ["ln1", "ln2", "conv_in", "conv_taps", "conv_out", "w_gate", "w_up",
         "w_down"])
    assert sorted(params["layers"][1]) == sorted(
        ["ln1", "ln2", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "router",
         "router_bias", "experts"])
    assert sorted(params["layers"][4]) == sorted(
        ["ln1", "ln2", "conv_in", "conv_taps", "conv_out", "router",
         "router_bias", "experts"])
    assert params["layers"][2]["conv_in"].shape == (64, 192)
    assert params["layers"][2]["conv_taps"].shape == (3, 64)
    assert params["layers"][0]["w_gate"].shape == (64, 96)
    assert "head" not in params and "pos" not in params
    # norm weights start at 1 (the plain RMS norm), the bias at 0
    assert np.asarray(params["layers"][1]["q_norm"]["scale"] == 1).all()
    assert not np.asarray(params["layers"][1]["router_bias"]).any()
    # drawn on the device the shapes are the same
    on_device = seqrec.init_params(None, 20, small_spec(device_init=True))
    assert jax.tree.map(jnp.shape, on_device) == jax.tree.map(jnp.shape,
                                                              params)


def test_a_train_counts_its_positions_by_mixer():
    """`pio_train_seqrec_mixer_tokens_total{mixer="conv"}`: positions of
    the trained batches times the convolution layers the step ran; the
    attention route's counter counts the one attention layer's
    positions, and a model of convolutions alone counts nothing there."""
    counted = cases.counted
    series = [("pio_train_seqrec_mixer_tokens_total", {"mixer": "conv"}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "gqa"}),
              ("pio_train_seqrec_attention_tokens_total", {"impl": "xla"})]
    sessions = cases.sessions(4)
    positions = 2 * 2 * L
    before = [counted(name, **labels) for name, labels in series]
    model = seqrec.train_seqrec(None, sessions, small_spec(
        epochs=1, batch_size=2, device_init=True))
    assert len(model.record["loss"]) == 2
    assert "layer0.short_conv" in model.record["grad_norm"][0]
    assert "layer0.short_conv" in model.record["update_norm"][0]
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    assert gained == [4 * positions, positions, positions]
    before = [counted(name, **labels) for name, labels in series]
    seqrec.train_seqrec(None, sessions, small_spec(
        epochs=1, batch_size=2, mixer="conv", n_layers=2))
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    assert gained == [2 * positions, 0, 0]


def test_recommend_next_through_the_tied_head():
    p = small_spec(epochs=0, batch_size=2)
    sessions = [[f"i{(s + j) % 30:02d}" for j in range(L + 1)]
                for s in range(6)]                        # items i00..i29
    model = seqrec.train_seqrec(None, sessions, p)
    assert "head" not in model.params
    scores = dict(model.recommend_next(["i01", "i02"], 30))
    assert len(scores) == 28                  # 30 items, two seen
    dev, hidden_of = model._device_params()
    seq = np.zeros((1, L), np.int32)
    seq[0, -2:] = [model.item_code("i01"), model.item_code("i02")]
    hidden = np.asarray(hidden_of(dev, jnp.asarray(seq)))[0, -1]
    want = hidden @ model.params["emb"].T
    assert scores["i07"] == pytest.approx(float(want[8]), rel=1e-4,
                                          abs=1e-5)


def test_the_model_trains_and_serves_from_an_engine_json(tmp_path):
    """The new keys of the layer spec: `conv_kernel`, `attention_gate`,
    `router_norm_eps`, the `conv` kind."""
    params, trained = cases.the_model_trains_and_serves_from_an_engine_json(
        CASE, tmp_path, "Conv")
    assert params["mixer"] == list(PATTERN)
    assert trained.hyper.mixer_kinds() == PATTERN
    assert (trained.hyper.conv_kernel, trained.hyper.attention_gate,
            trained.hyper.router_norm_eps) == (3, False, 1e-6)


@pytest.mark.parametrize("over,match", [
    ({"mixer": ("conv", "flash")}, "unknown mixer"),
    ({"mixer": "conv", "positions": "learned"}, "learned"),
    ({"mixer": "conv", "norm": "layer"}, "layer"),
    ({"conv_kernel": 0}, "conv_kernel"),
    ({"conv_kernel": -3}, "conv_kernel"),
    ({"n_kv_heads": 3}, "divisor"),
    ({"rotary_dim": 10}, "rotary_dim"),
    ({"shared_expert_gate": True}, "shared_expert_gate"),
])
def test_check_refuses_the_combinations_that_do_not_exist(over, match):
    with pytest.raises(ValueError, match=match):
        small_spec(**over).check()


def test_a_seq_mesh_is_refused_where_a_mixer_does_not_ring(mesh8):
    cases.a_seq_mesh_is_refused_where_a_mixer_does_not_ring(CASE)


def test_the_step_under_a_mesh_is_the_step(model, mesh8):
    """A convolution's `conv_in` by column, its `conv_out` by row."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                axis_names=("data", "model"))
    sharded, _, _ = cases.the_step_under_a_mesh_is_the_step(model, mesh)
    layer = sharded["layers"][2]
    assert layer["conv_in"].sharding.spec == P(None, "model")
    assert layer["conv_out"].sharding.spec == P("model", None)
    assert layer["conv_taps"].sharding.spec == P()


def _on_conv_passes(monkeypatch):
    """`gated_short_conv` as a v5e would route it, the passes
    interpreted."""
    from predictionio_tpu.ops import attention_pallas, short_conv_pallas

    monkeypatch.setattr(linear_attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    passes = short_conv_pallas.gated_short_conv_pallas
    monkeypatch.setattr(short_conv_pallas, "gated_short_conv_pallas",
                        lambda bcu, taps, **kw: passes(bcu, taps, True, **kw))


def test_a_step_says_which_route_its_short_convolution_was_traced_on(
        monkeypatch):
    """The step's `short_conv_pallas` is what `gated_short_conv` chose
    at trace time from the device's kind, the devices and the shapes: on
    the CPU False; with the passes' route forced (and interpreted) at a
    width of whole lane tiles and a length of whole row blocks True, the
    loss and every gradient norm the plain chain's; under a mesh of two
    devices False again; at the small spec's 64 columns False whatever
    the device; and a step without a convolution layer says nothing."""
    from jax.sharding import Mesh

    p = small_spec(d_model=128, max_len=128, learning_rate=1e-3)
    optimizer = seqrec.make_optimizer(p)
    rng = np.random.default_rng(5)
    seqs = rng.integers(1, VOCAB, size=(2, 129))
    seqs[0, :40] = 0                        # a left-padded session
    seqs, targets = (jnp.asarray(t, jnp.int32)
                     for t in (seqs[:, :-1], seqs[:, 1:]))

    def step(spec, mesh=None):
        # (float32 products: the layer then asks the passes for a float32
        # gradient too, `_qkv_grad_dtype`, and the two routes are one
        # mathematics to rounding; at the default the passes round the
        # projection's gradient to bfloat16 where the TPU's products
        # would, which the CPU's float32 products do not repeat)
        params = weights(spec)
        with jax.default_matmul_precision("float32"):
            return seqrec.make_train_step(mesh, spec, optimizer)(
                params, optimizer.init(params), seqs[:, :spec.max_len],
                targets[:, :spec.max_len])[2]

    plain = step(p)
    assert "short_conv_pallas" in plain and not plain["short_conv_pallas"]
    _on_conv_passes(monkeypatch)
    for mesh, pallas in (
            (None, True),
            (Mesh(np.asarray(jax.devices()[:2]), ("data",)), False)):
        stats = step(p, mesh)
        assert bool(stats["short_conv_pallas"]) is pallas
        assert not bool(stats["linear_attention_pallas"])
        assert abs(float(stats["loss"]) - float(plain["loss"])) \
            < 2e-6 * float(plain["loss"])
        # (adamw's first step is a sign where a gradient is not 0: an
        # update's norm moves by the entries that lie within its epsilon)
        for key, within in (("grad_norm", 2e-5), ("update_norm", 2e-3)):
            for group, norm in plain[key].items():
                assert abs(float(stats[key][group]) - float(norm)) \
                    < within * float(norm), (key, group)
    assert not step(small_spec())["short_conv_pallas"]
    no_conv = step(small_spec(mixer="gqa", n_layers=2, max_len=L))
    assert "short_conv_pallas" not in no_conv


@pytest.mark.parametrize("ambient,grad_dtype", [
    (None, jnp.bfloat16), ("bfloat16", jnp.bfloat16), ("highest", None),
    ("float32", None)])
def test_the_projections_gradient_is_rounded_where_its_products_round_it(
        ambient, grad_dtype, monkeypatch):
    """The `conv` mixer asks the passes for a bfloat16 gradient of `x @
    conv_in` only while that product, and so its two backward products,
    take their operands in one bfloat16 pass: the product carries no
    precision of its own (the default's) AND the default is the TPU's;
    under a higher default it asks for none, and the gradient stays
    float32."""
    from predictionio_tpu.ops import short_conv_pallas

    _on_conv_passes(monkeypatch)
    asked = []
    passes = short_conv_pallas.gated_short_conv_pallas
    monkeypatch.setattr(
        short_conv_pallas, "gated_short_conv_pallas",
        lambda *a, grad_dtype=None: asked.append(grad_dtype) or passes(
            *a, grad_dtype=grad_dtype))
    rng = np.random.default_rng(2)
    layer = {name: jnp.asarray(rng.normal(size=shape), jnp.float32)
             for name, shape in (("conv_in", (128, 384)),
                                 ("conv_taps", (3, 128)),
                                 ("conv_out", (128, 128)))}
    x = jnp.asarray(rng.normal(size=(1, 128, 128)), jnp.float32)
    mask = jnp.ones((1, 128), bool)

    def traced():
        return jax.make_jaxpr(
            lambda layer, x: seqrec._short_conv(layer, x, mask))(layer, x)

    if ambient is None:
        jaxpr = traced()
    else:
        with jax.default_matmul_precision(ambient):
            jaxpr = traced()
    assert asked == [grad_dtype]
    products = [e for e in jaxpr.jaxpr.eqns if e.primitive.name
                == "dot_general"]
    assert len(products) == 2       # x @ conv_in, y @ conv_out
    if ambient is None:     # (under a default, the product takes it)
        assert all(e.params["precision"] is None for e in products)
