"""The sequence model under a pattern of gated short-convolution layers
and ungated grouped-query attention, a leading dense layer, sigmoid-routed
experts behind a selection bias with no shared expert, and a tied head,
against the plain reference the benchmark brings
(benchmarks/checks/seqrec_conv_reference.py), on seeded random weights at
a small size; and the pieces the spec is made of against their
hand-computed values."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import seqrec_conv_reference as ref
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import linear_attention, moe
from predictionio_tpu.ops.attention import mha, rope

VOCAB, L = 97, 24
PATTERN = ("conv", "gqa", "conv", "conv", "conv")


def small_spec(**over) -> seqrec.SeqRecParams:
    """d 64; a convolution layer with the dense feed-forward, then one
    period of a gqa layer (8 query heads of 8 = d / heads over 2
    key/value heads, rotary on the whole head, no gate) and three
    convolution layers of 3 taps, 16 experts top-4 by sigmoid + bias in
    each, no shared expert; the head tied."""
    base = dict(
        d_model=64, n_heads=8, n_layers=5, max_len=L, seed=11,
        mixer=PATTERN, ffn="moe", first_dense_layers=1, ffn_width=96,
        norm="rms", norm_eps=1e-5, positions="rope", rope_theta=1e6,
        tied_head=True, n_kv_heads=2, head_dim=8, rotary_dim=8,
        attention_gate=False, conv_kernel=3, n_routed_experts=16,
        held_experts=(0, 16), experts_per_token=4, moe_width=24,
        router_scoring="sigmoid", router_norm_eps=1e-6,
        bias_update_rate=0.001, remat=True)
    return seqrec.SeqRecParams(**{**base, **over})


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks small enough that a session of 24 takes three attention
    blocks and a step's 48 tokens four token blocks."""
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 8)
    monkeypatch.setattr(seqrec, "TOKEN_BLOCK", 12)


def batch(seed=0, rows=2, pad=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(1, VOCAB, size=(rows, L + 1))
    s[:, :pad] = 0
    return s[:, :-1].astype(np.int32), s[:, 1:].astype(np.int32)


def weights(p, seed=3, vocab_multiple=1):
    """The spec's draws, with every norm's weight moved off 1 and every
    selection bias off 0, so that they matter."""
    params = seqrec.init_params(np.random.default_rng(seed), VOCAB - 1, p,
                                vocab_multiple)
    rng = np.random.default_rng(seed + 1)
    moved = {"ln1": 0.1, "ln2": 0.1, "ln_f": 0.1, "q_norm": 0.1,
             "k_norm": 0.1, "router_bias": 0.05}

    def move(path, w):
        for k in path:
            if getattr(k, "key", None) in moved:
                return w + jnp.asarray(
                    rng.normal(size=w.shape) * moved[k.key], jnp.float32)
        return w

    return jax.tree_util.tree_map_with_path(move, params)


def ref_spec(p, **over):
    return ref.Spec.of(dataclasses.asdict(p), **over)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(pad):
    """float32 on both sides, on the CPU; the orders of summation differ
    (blocked attention, grouped experts, token blocks), which costs a few
    float32 roundings a value: 2e-5 of each array's largest entry. A
    lower precision anywhere reads 1e-3 and more (the int8 case below)."""
    p = small_spec()
    params = weights(p)
    seqs, targets = batch(pad=pad)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(seqrec._loss_fn, has_aux=True)(
            params, jnp.asarray(seqs), jnp.asarray(targets), p)
    want_loss, want_grads, _ = ref.loss_and_grads(params, seqs, targets,
                                                  ref_spec(p))
    assert abs(float(loss) - want_loss) < 2e-6 * want_loss
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    assert set(got) == set(dict(
        jax.tree_util.tree_leaves_with_path(want_grads)))
    for path, want in jax.tree_util.tree_leaves_with_path(want_grads):
        assert rel(got[path], want) < 2e-5, jax.tree_util.keystr(path)
    # every group of the record: the convolution's own, the dense layer's,
    # no head (it is the table) and no shared expert
    groups = seqrec._group_norms(grads)
    assert set(groups) == set(ref.group_norms(want_grads))
    assert {"layer0.short_conv", "layer0.ffn", "layer1.attention",
            "layer1.experts", "layer4.short_conv", "embedding"} <= set(groups)
    assert not any("head" in g or "shared" in g for g in groups)
    # the selection bias takes no gradient
    assert not np.asarray(grads["layers"][2]["router_bias"]).any()
    # and the control: the reference's own int8 products
    _, low, _ = ref.loss_and_grads(params, seqs, targets,
                                   ref_spec(p, precision="int8"))
    for name in ("conv_in", "conv_out"):
        assert rel(low["layers"][3][name],
                   want_grads["layers"][3][name]) > 1e-3


def test_logits_match_the_reference():
    p = small_spec()
    params = weights(p)
    seqs, _ = batch(seed=5, rows=1, pad=3)
    with jax.default_matmul_precision("highest"):
        hidden = seqrec.forward(params, jnp.asarray(seqs), p)
        logits = hidden[0] @ seqrec.head_matrix(params)
        want = ref.hidden_states(params, seqs[0], ref_spec(p))[0] \
            @ params["emb"].T
    assert logits.shape == (L, VOCAB)
    assert rel(logits, want) < 1e-5      # float32 roundings


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


def test_a_left_padded_session_is_the_unpadded_one():
    """Every mixer of the pattern: a convolution's padding positions are
    zeros before the session, the attention layer masks its keys and
    rotates by distance."""
    p = small_spec()
    params = weights(p)
    seqs, _ = batch(seed=4, rows=1)
    short = seqs[:, 7:]
    padded = np.concatenate([np.zeros((1, 7), np.int32), short], axis=1)
    with jax.default_matmul_precision("highest"):
        whole = seqrec.forward(params, jnp.asarray(padded), p)
        alone = seqrec.forward(params, jnp.asarray(short),
                               dataclasses.replace(p, max_len=L - 7))
    assert not np.asarray(whole[0, :7]).any()
    np.testing.assert_allclose(whole[0, 7:], alone[0], atol=2e-5)


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


def test_a_step_adds_what_the_references_adamw_and_bias_update_add():
    """By parameter group, the norm of step 1's update against the
    reference's adamw step from its own gradients (a router's group holds
    the selection bias, moved by its layer's load and left alone by
    adamw), what a learning rate ten times off reads, the bias itself,
    and the layers the step reports by mixer."""
    p = small_spec(learning_rate=1e-3)
    params = weights(p)
    seqs, targets = batch(seed=2)
    _, grads, load = ref.loss_and_grads(params, seqs, targets, ref_spec(p))
    want = ref.first_update_norms(params, grads, load, ref_spec(p))
    off = ref.first_update_norms(params, grads, load,
                                 ref_spec(p, learning_rate=1e-2))
    optimizer = seqrec.make_optimizer(p)
    before = [np.asarray(layer["router_bias"])
              for layer in params["layers"][1:]]
    with jax.default_matmul_precision("highest"):
        after, _, stats = seqrec.make_train_step(None, p, optimizer)(
            jax.tree.map(jnp.copy, params), optimizer.init(params),
            jnp.asarray(seqs), jnp.asarray(targets))
    got = {k: float(v) for k, v in stats["update_norm"].items()}
    assert set(got) == set(want)
    for group, norm in want.items():
        assert abs(got[group] - norm) < 2e-4 * norm, group
    assert off["layer3.short_conv"] > 9 * got["layer3.short_conv"]
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"conv": 4, "gqa": 1}
    assert np.array_equal(stats["load"], load)
    assert np.asarray(stats["load"]).shape == (4, 16)
    for n, (b0, layer) in enumerate(zip(before, after["layers"][1:])):
        np.testing.assert_allclose(
            layer["router_bias"], ref.bias_after_step(b0, load[n], 0.001),
            atol=1e-7)
    assert "router_bias" not in after["layers"][0]


def test_the_tied_heads_gradient_reaches_the_table_from_both_ends():
    """Under `remat` and token blocks: the table's gradient is the sum
    of what the lookups and what the head send it. The lookups' part is
    0 in the rows of items that are no input; the head's part reaches
    every row."""
    p = small_spec()
    params = weights(p)
    seqs, targets = batch(seed=9)
    loss_of = lambda prm, head: seqrec._loss_fn(
        {**prm, "head": head}, jnp.asarray(seqs), jnp.asarray(targets),
        dataclasses.replace(p, tied_head=False))[0]
    with jax.default_matmul_precision("highest"):
        tied = jax.grad(lambda prm: seqrec._loss_fn(
            prm, jnp.asarray(seqs), jnp.asarray(targets), p)[0])(params)
        lookups, head = jax.grad(loss_of, (0, 1))(params, params["emb"].T)
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
    from predictionio_tpu.obs.registry import default_registry

    reg = default_registry()

    def counted(name, **labels):
        c = reg.get(name)
        return c.value(**labels) if c is not None else 0

    series = [("pio_train_seqrec_mixer_tokens_total", {"mixer": "conv"}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "gqa"}),
              ("pio_train_seqrec_attention_tokens_total", {"impl": "xla"})]
    sessions = [[f"i{(3 * s + j * (1 + s % 2)) % 50:02d}"
                 for j in range(L + 1)] for s in range(4)]
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
    """`pio train` and `pio deploy`'s predict from a variant file alone:
    the new keys of the layer spec (`conv_kernel`, `attention_gate`,
    `router_norm_eps`, the `conv` kind) reach the model like the old
    ones."""
    import datetime as dt

    from predictionio_tpu.core.params import engine_params_from_json
    from predictionio_tpu.data import Event
    from predictionio_tpu.data.eventstore import clear_cache
    from predictionio_tpu.engines.sessionrec import (
        AlgorithmParams, DataSourceParams, Query, engine,
    )
    from predictionio_tpu.storage import App, Storage
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.train import load_for_deploy

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "t.db")}},
        "repositories": {name: {"NAME": "pio", "SOURCE": "DB"}
                         for name in ("METADATA", "EVENTDATA", "MODELDATA")}})
    clear_cache()
    try:
        app_id = Storage.get_meta_data_apps().insert(App(id=0, name="Conv"))
        store = Storage.get_events()
        store.init_channel(app_id)
        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        store.insert_batch([
            Event(event="view", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{(u + j) % 15:02d}",
                  event_time=t0 + dt.timedelta(minutes=u * 100 + j))
            for u in range(40) for j in range(4 + u % 5)], app_id)
        spec = dataclasses.asdict(small_spec(max_len=16, epochs=30,
                                             batch_size=20,
                                             learning_rate=3e-3))
        variant = json.loads(json.dumps({
            "datasource": {"params": {"appName": "Conv"}},
            "algorithms": [{"name": "seqrec", "params": spec}]}))
        assert variant["algorithms"][0]["params"]["mixer"] == list(PATTERN)
        params = engine_params_from_json(
            variant, DataSourceParams, None, {"seqrec": AlgorithmParams})
        eng = engine()
        instance = run_train(eng, params)
        assert instance.status == "COMPLETED"
        result, _ = load_for_deploy(eng, instance)
        algo, model = result.algorithms[0], result.models[0]
        assert model.hyper.mixer_kinds() == PATTERN
        assert (model.hyper.conv_kernel, model.hyper.attention_gate,
                model.hyper.router_norm_eps) == (3, False, 1e-6)
        assert model.record["loss"][-1] < model.record["loss"][0]
        pred = algo.predict(model, Query(items=["i03", "i04", "i05"], num=3))
        items = [s.item for s in pred.item_scores]
        assert "i06" in items and "i05" not in items
    finally:
        Storage.reset()
        clear_cache()


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
    """The ring takes one key/value head a query head: a train over a
    mesh with a "seq" axis is refused before anything is traced."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                axis_names=("data", "seq"))
    with pytest.raises(ValueError, match="ring"):
        seqrec.train_seqrec(mesh, [["a", "b", "c"]] * 4, small_spec())


def test_the_step_under_a_mesh_is_the_step(mesh8):
    """Batch over "data", the projections' columns over "model" (a
    convolution's `conv_in` by column, its `conv_out` by row): the
    sharded step's loss and gradient norms are the one-device step's."""
    from jax.sharding import Mesh, PartitionSpec as P

    p = small_spec(learning_rate=1e-3)
    params = weights(p, vocab_multiple=2)
    seqs, targets = batch(seed=6, rows=4)
    optimizer = seqrec.make_optimizer(p)
    _, _, want = seqrec.make_train_step(None, p, optimizer)(
        jax.tree.map(jnp.copy, params), optimizer.init(params),
        jnp.asarray(seqs), jnp.asarray(targets))
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                axis_names=("data", "model"))
    sharded = seqrec.shard_params(jax.tree.map(jnp.copy, params), mesh)
    layer = sharded["layers"][2]
    assert layer["conv_in"].sharding.spec == P(None, "model")
    assert layer["conv_out"].sharding.spec == P("model", None)
    assert layer["conv_taps"].sharding.spec == P()
    _, _, got = seqrec.make_train_step(mesh, p, optimizer)(
        sharded, optimizer.init(sharded), jnp.asarray(seqs),
        jnp.asarray(targets))
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5
    for group, norm in want["grad_norm"].items():
        assert abs(float(got["grad_norm"][group]) - float(norm)) \
            < 2e-3 * float(norm), group


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
