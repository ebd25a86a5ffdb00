"""The sequence model under a period of linear-attention (gated delta
rule) and gated grouped-query layers with softmax-routed experts and a
gated shared expert, against the plain reference the benchmark brings
(benchmarks/checks/seqrec_hybrid_reference.py), on seeded random weights
at a small size; and the pieces the spec is made of against their
hand-computed values."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import seqrec_hybrid_reference as ref
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import linear_attention, moe
from predictionio_tpu.ops.attention import blockwise_attention, mha, rope

VOCAB, L = 97, 24


def small_spec(**over) -> seqrec.SeqRecParams:
    """d 64; a period of three gdn layers (4 key heads of 8 serving 8
    value heads of 8, a convolution of 4) and one gqa layer (4 query
    heads of 16 over 2 key/value heads, 4 rotary dimensions); 16 experts
    top-3 by softmax plus a gated shared one in every layer."""
    base = dict(
        d_model=64, n_heads=4, n_layers=4, max_len=L, seed=11,
        mixer=("gdn", "gdn", "gdn", "gqa"), ffn="moe",
        norm="rms_zero_centered", norm_eps=1e-6, positions="rope",
        rope_theta=1e7, tied_head=False, n_kv_heads=2, head_dim=16,
        rotary_dim=4, linear_key_heads=4, linear_value_heads=8,
        linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel=4,
        n_routed_experts=16, held_experts=(0, 16), experts_per_token=3,
        moe_width=24, n_shared_experts=1, shared_expert_gate=True,
        router_scoring="softmax", remat=True)
    return seqrec.SeqRecParams(**{**base, **over})


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks small enough that a session of 24 takes three attention
    blocks and three chunks of the delta rule, a step's 48 tokens four
    token blocks, and a linear layer its heads in two groups."""
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 8)
    monkeypatch.setattr(seqrec, "TOKEN_BLOCK", 12)
    monkeypatch.setattr(seqrec, "LINEAR_KEY_HEADS", 2)
    monkeypatch.setattr(linear_attention, "CHUNK", 8)


def batch(seed=0, rows=2, pad=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(1, VOCAB, size=(rows, L + 1))
    s[:, :pad] = 0
    return s[:, :-1].astype(np.int32), s[:, 1:].astype(np.int32)


def weights(p, seed=3, vocab_multiple=1):
    """The spec's draws, with every norm's weight moved off its start so
    that it matters."""
    params = seqrec.init_params(np.random.default_rng(seed), VOCAB - 1, p,
                                vocab_multiple)
    rng = np.random.default_rng(seed + 1)
    norms = ("ln1", "ln2", "ln_f", "q_norm", "k_norm", "o_norm")

    def move(path, w):
        if any(getattr(k, "key", None) in norms for k in path):
            return w + jnp.asarray(rng.normal(size=w.shape) * 0.1,
                                   jnp.float32)
        return w

    return jax.tree_util.tree_map_with_path(move, params)


def ref_spec(p, **over):
    return ref.Spec.of(dataclasses.asdict(p), **over)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(pad):
    """float32 on both sides, on the CPU. The orders of summation differ
    (the chunked rule against the recurrence, blocked attention, grouped
    experts, token blocks), and a linear layer's output is normed a head
    where it can be small, so a gradient's last digits are amplified on
    their way down: the same program under another chunk reads up to
    5e-4 of an array's largest entry from itself. A lower precision
    anywhere reads 1e-2 and more (the int8 case below)."""
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
    for path, want in jax.tree_util.tree_leaves_with_path(want_grads):
        assert rel(got[path], want) < 1e-3, jax.tree_util.keystr(path)
    # every group of the record, the linear layers' own among them
    groups = seqrec._group_norms(grads)
    assert set(groups) == set(ref.group_norms(want_grads))
    assert {"layer0.linear_attention", "layer3.attention",
            "layer2.shared_expert"} <= set(groups)
    # the selection bias is no parameter of this spec
    assert not np.asarray(grads["layers"][1]["router_bias"]).any()
    # and the control: the reference's own int8 products
    _, low, _ = ref.loss_and_grads(params, seqs, targets,
                                   ref_spec(p, precision="int8"))
    for name in ("w_out", "w_qkvz"):
        assert rel(low["layers"][2][name],
                   want_grads["layers"][2][name]) > 1e-2


def test_logits_match_the_reference():
    p = small_spec()
    params = weights(p)
    seqs, _ = batch(seed=5, rows=1, pad=3)
    with jax.default_matmul_precision("highest"):
        hidden = seqrec.forward(params, jnp.asarray(seqs), p)
        logits = hidden[0] @ seqrec.head_matrix(params)
        want = ref.hidden_states(params, seqs[0], ref_spec(p))[0] \
            @ params["head"]
    assert rel(logits, want) < 1e-5      # float32 roundings


@pytest.mark.parametrize("length", [8, 24, 29, 3])
def test_the_chunked_rule_is_the_recurrence(length):
    """Chunks of 8: one whole chunk, three, three and a part, a part;
    against the reference's rule, position by position."""
    rng = np.random.default_rng(length)
    b, h, dk, dv = 2, 3, 16, 8
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    operands = tuple(jnp.asarray(t, jnp.float32) for t in (
        unit(rng.normal(size=(b, length, h, dk))) * dk ** -0.5,
        unit(rng.normal(size=(b, length, h, dk))),
        rng.normal(size=(b, length, h, dv)),
        -rng.uniform(0, 3, size=(b, length, h)),
        rng.uniform(size=(b, length, h))))
    spec = ref_spec(small_spec())

    def by_position(*operands):      # the reference's, a head at a time
        one_head = lambda *a: ref.delta_rule(*a, spec)
        return jax.vmap(jax.vmap(one_head, in_axes=1, out_axes=1))(*operands)

    with jax.default_matmul_precision("highest"):
        got = linear_attention.gated_delta_rule(*operands)
        want = by_position(*operands)
        assert got.shape == (b, length, h, dv)
        assert rel(got, want) < 1e-5
        w = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
        grads = jax.grad(lambda *a: (linear_attention.gated_delta_rule(
            *a) * w).sum(), argnums=(0, 1, 2, 3, 4))(*operands)
        wants = jax.grad(lambda *a: (by_position(*a) * w).sum(),
                         argnums=(0, 1, 2, 3, 4))(*operands)
    for g, want_g in zip(grads, wants):
        assert rel(g, want_g) < 1e-4


def test_the_causal_convolution_by_hand():
    x = jnp.asarray(np.arange(10.0).reshape(1, 5, 2) - 4.0, jnp.float32)
    w = jnp.asarray([[1.0, 0.5], [0.0, -1.0], [2.0, 0.25]], jnp.float32)
    got = linear_attention.causal_conv(x, w)
    pre = np.zeros((5, 2))
    xs = np.asarray(x[0])
    for t in range(5):
        for j in range(3):
            if t - 2 + j >= 0:
                pre[t] += np.asarray(w)[j] * xs[t - 2 + j]
    np.testing.assert_allclose(got[0], pre / (1 + np.exp(-pre)), rtol=1e-5,
                               atol=1e-6)


def test_a_left_padded_session_is_the_unpadded_one():
    """Every mixer of the period: the linear layers write nothing at a
    padding position, the full layer masks its keys and rotates by
    distance."""
    p = small_spec(max_len=L)
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


def _gqa_case(lq, heads, kv_heads, width, seed):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.normal(size=(2, lq, h, width)), jnp.float32)
               for h in (heads, kv_heads, kv_heads))
    w = jnp.asarray(rng.normal(size=(2, lq, heads, width)), jnp.float32)
    mask = jnp.asarray(np.arange(lq)[None, :] >= np.array([[0], [5]]))
    return q, k, v, w, mask


@pytest.mark.parametrize("route", ["xla", "pallas"])
def test_grouped_query_attention_is_mha_over_repeated_heads(route,
                                                            monkeypatch):
    """`blockwise_attention` with 2 key/value heads under 8 query heads
    and rotary positions on a leading part of the width, against `mha`
    on the heads repeated, forward and gradient (a key/value head's is
    the sum over its query heads): the scan, and the kernels in the
    Pallas interpreter (their products take bfloat16 operands)."""
    from predictionio_tpu.ops import attention, attention_pallas

    lq, width = (256, 128) if route == "pallas" else (24, 16)
    if route == "pallas":
        monkeypatch.setattr(attention, "_device_kind",
                            lambda: attention_pallas.KINDS[0])
        kernels = attention_pallas.flash_attention_pallas
        monkeypatch.setattr(
            attention_pallas, "flash_attention_pallas",
            lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal,
                                                  True))
    q, k, v, w, mask = _gqa_case(lq, 8, 2, width, seed=len(route))
    positions = jnp.arange(lq)
    turn = lambda t: rope(t, positions, 1e7, width // 4)
    # the leading quarter turns, the rest passes
    assert np.array_equal(turn(q)[..., width // 4:], q[..., width // 4:])
    assert not np.allclose(turn(q)[:, 1:, :, :width // 4],
                           q[:, 1:, :, :width // 4])
    np.testing.assert_allclose(
        turn(q)[..., :width // 4],
        rope(q[..., :width // 4], positions, 1e7), atol=1e-6)

    def grouped(q, k, v):
        heard = set()
        with attention.routes_into(heard):
            out = blockwise_attention(turn(q), turn(k), v, block_k=8,
                                      causal=True, key_mask=mask)
        assert heard == {route}
        return out

    def dense(q, k, v):
        k, v = (jnp.repeat(t, 4, axis=2) for t in (turn(k), v))
        return mha(turn(q), k, v, causal=True, key_mask=mask)

    tol = 2e-2 if route == "pallas" else 1e-5
    got, want = grouped(q, k, v), dense(q, k, v)
    np.testing.assert_allclose(got, want,
                               atol=tol * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: (grouped(*a) * w).sum(), (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (dense(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, want_g in zip(grads, wants):
        assert g.shape == want_g.shape
        np.testing.assert_allclose(
            g, want_g, atol=tol * float(jnp.abs(want_g).max()))
    with pytest.raises(ValueError, match="query heads"):
        blockwise_attention(q, k[:, :, :1].repeat(3, axis=2),
                            v[:, :, :1].repeat(3, axis=2))


@pytest.mark.parametrize("name,over,rows", [
    # the hybrid's own form without its heads' norms: 4 heads of 256 on
    # 2, rotary on 64 of 256, the gate element by element (flat already)
    ("elementwise-gate", dict(head_dim=256, rotary_dim=64, qk_norm=False),
     True),
    # the state-space hybrid's: 4 heads of 128 on 1, no rotary, no gate
    ("no-rotary-no-gate", dict(head_dim=128, n_kv_heads=1, positions="none",
                               attention_gate=False, qk_norm=False,
                               norm="rms"), True),
    # a head's own norm runs on [B, L, H, D]: the layer stays head-first
    ("qk-norm", dict(head_dim=256, rotary_dim=64), False),
    # heads of 64 (the convolution hybrid's) are no whole lane tiles
    ("heads-of-64", dict(head_dim=64, rotary_dim=64, qk_norm=False), False),
])
def test_a_gqa_layer_on_the_token_first_route_is_the_head_first_one(
        monkeypatch, name, over, rows):
    """A `gqa` layer function on the kernels' route (the device's kind
    patched, the kernels interpreted) at 256 positions with a left-padded
    row: token-first where `attention_layout` answers "rows" AND the
    layer norms no head (`grouped_attention`), head-first everywhere
    else; where it is token-first, the head-first layer (`layout`
    patched) gives the same values and the same gradients of every leaf
    and of the input, to the tolerance the `mha` layer's two layouts are
    held to (tests/test_seqrec_looped.py)."""
    from predictionio_tpu.ops import attention, attention_pallas

    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 128)
    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    whole, grouped = (attention_pallas.flash_attention_pallas,
                      attention_pallas.grouped_attention_pallas)
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: whole(q, k, v, mask, causal, True))
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda *a, operand_dtype=None, packed=False: grouped(
            *a, True, operand_dtype, packed))
    p = small_spec(d_model=128, max_len=256, n_layers=1, mixer="gqa",
                   **over)
    layer = weights(p)["layers"][0]
    rng = np.random.default_rng(len(name))
    x, cot = (jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
              for _ in range(2))
    mask = jnp.asarray(np.arange(256)[None, :] >= np.array([[9], [0]]))

    def run():
        layouts = set()
        with attention.routes_into(set(), layouts):
            out, pull = jax.vjp(lambda w, x: seqrec._attention(
                w, x, mask, p, "gqa", None, False), layer, x)
        return layouts, out, pull(cot)

    layouts, got, (d_layer, d_x) = run()
    assert layouts == ({"rows"} if rows else {"heads"})
    if not rows:
        return
    monkeypatch.setattr(attention_pallas, "layout", lambda dk, dv: "heads")
    layouts, want, (want_layer, want_x) = run()
    assert layouts == {"heads"}
    assert rel(got, want) < 2e-3 and rel(d_x, want_x) < 5e-3
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(d_layer),
                            jax.tree.leaves(want_layer)):
        assert rel(g, w) < 5e-3, jax.tree_util.keystr(path)
    wq = "wq_gate" if p.attention_gate is True else "wq"
    for leaf in (wq, "wk", "wv", "wo"):
        assert float(jnp.abs(d_layer[leaf]).max()) > 0, leaf


def test_the_softmax_router_by_hand():
    x = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.float32)
    w = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 1.0, 1.5]],
                    jnp.float32)
    routing = moe.route(x, w, jnp.zeros(4), 2, scoring="softmax")
    p0 = np.exp([2.0, 1.0, 0.0, -1.0]) / np.exp([2.0, 1.0, 0.0, -1.0]).sum()
    p1 = np.exp([0.0, 1.0, 2.0, 3.0]) / np.exp([0.0, 1.0, 2.0, 3.0]).sum()
    np.testing.assert_allclose(routing.scores, [p0, p1], rtol=1e-6)
    assert routing.experts.tolist() == [[0, 1], [3, 2]]
    np.testing.assert_allclose(
        routing.gates, [p0[:2] / p0[:2].sum(), p1[[3, 2]] / p1[2:].sum()],
        rtol=1e-6)
    # unnormalised, the gates are the probabilities themselves; a bias
    # moves the choice and not the weights
    raw = moe.route(x, w, jnp.asarray([0.0, 0.0, 5.0, 0.0]), 2,
                    norm_topk=False, scoring="softmax")
    assert raw.experts.tolist() == [[2, 0], [2, 3]]
    np.testing.assert_allclose(raw.gates, [p0[[2, 0]], p1[[2, 3]]],
                               rtol=1e-6)
    # and the sigmoid router is what it was
    old = moe.route(x, w, jnp.zeros(4), 2)
    np.testing.assert_allclose(
        old.scores, 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(w))),
        rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of four experts each: their parts of an expert layer,
    with the gated shared expert (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer of 16 experts."""
    p = small_spec()
    params = weights(p)
    layer = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(8).normal(size=(1, L, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, load = ref.expert_layer(layer, x[0], ref_spec(p))
        shared = ref.expert_layer(
            layer, x[0], ref_spec(p, held_experts=(0, 0)))[0]
        parts = []
        for lo in range(0, 16, 4):
            share = dataclasses.replace(p, held_experts=(lo, lo + 4))
            held = dict(layer, experts=jax.tree.map(
                lambda w: w[lo:lo + 4], layer["experts"]))
            y, stats = seqrec._moe(held, x, share)
            parts.append(y[0] - shared)
            assert np.array_equal(stats["load"], load)
            assert np.array_equal(stats["held_tokens"], load[lo:lo + 4])
            assert int(stats["dropped"]) == 0
    assert int(load.sum()) == 3 * L
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-6)
    assert float(jnp.abs(shared).max()) > 1e-3      # the gate lets it by


def test_a_step_adds_what_the_references_adamw_adds():
    """By parameter group, the norm of step 1's update against the
    reference's adamw step from its own gradients (float32 both sides;
    adamw's first step is -lr g / (|g| + eps), and the few entries whose
    gradient is near eps = 1e-8 feel the gradients' last digits), and
    what a learning rate ten times off reads; the step reports the
    layers it ran by mixer."""
    p = small_spec(learning_rate=1e-3)
    params = weights(p)
    seqs, targets = batch(seed=2)
    _, grads, _ = ref.loss_and_grads(params, seqs, targets, ref_spec(p))
    want = ref.first_update_norms(params, grads, ref_spec(p))
    off = ref.first_update_norms(params, grads,
                                 ref_spec(p, learning_rate=1e-2))
    optimizer = seqrec.make_optimizer(p)
    with jax.default_matmul_precision("highest"):
        _, _, stats = seqrec.make_train_step(None, p, optimizer)(
            params, optimizer.init(params), jnp.asarray(seqs),
            jnp.asarray(targets))
    got = {k: float(v) for k, v in stats["update_norm"].items()}
    assert set(got) == set(want)
    for group, norm in want.items():
        assert abs(got[group] - norm) < 2e-4 * norm, group
    assert off["layer1.linear_attention"] > 9 * got["layer1.linear_attention"]
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"gdn": 3, "gqa": 1}
    assert np.asarray(stats["load"]).shape == (4, 16)


@pytest.mark.parametrize("pad", [0, 5])
def test_a_step_on_the_kernels_route_is_the_step_on_the_scans(monkeypatch,
                                                              pad):
    """A linear layer whose widths the kernels tile, on both routes (the
    device's kind patched, the kernels interpreted, their products'
    operands left float32): the same loss and gradient norms by group,
    and the step reports the route, which `gated_delta_chain` takes for
    the rule and the chain around it alike: the kernels' step runs the
    fused chain once a linear layer and no rule outside it."""
    from predictionio_tpu.ops import attention_pallas, linear_attention_pallas

    monkeypatch.setattr(linear_attention, "CHUNK", 64)
    p = small_spec(n_layers=2, mixer=("gdn", "gqa"), linear_key_heads=1,
                   linear_value_heads=2, linear_key_head_dim=128,
                   linear_value_head_dim=128)
    params = weights(p)
    optimizer = seqrec.make_optimizer(p)
    seqs, targets = batch(seed=4, pad=pad)

    def step():
        with jax.default_matmul_precision("highest"):
            return seqrec.make_train_step(None, p, optimizer)(
                jax.tree.map(jnp.copy, params), optimizer.init(params),
                jnp.asarray(seqs), jnp.asarray(targets))[2]

    scan = step()
    assert not scan["linear_attention_pallas"]
    monkeypatch.setattr(linear_attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    monkeypatch.setattr(linear_attention_pallas, "_BF16", jnp.float32)
    chain, calls = linear_attention_pallas.gated_delta_chain_pallas, []
    monkeypatch.setattr(
        linear_attention_pallas, "gated_delta_chain_pallas",
        lambda *a: calls.append(a[5]) or chain(*a, True))
    monkeypatch.setattr(linear_attention_pallas, "gated_delta_rule_pallas",
                        lambda *a: pytest.fail("the rule outside the chain"))
    kernels = step()
    assert kernels["linear_attention_pallas"]
    assert calls == [(1, 2, 128, 128)]
    assert abs(float(kernels["loss"]) - float(scan["loss"])) \
        < 2e-6 * float(scan["loss"])
    assert set(kernels["grad_norm"]) == set(scan["grad_norm"])
    for group, norm in scan["grad_norm"].items():
        assert abs(float(kernels["grad_norm"][group]) - float(norm)) \
            < 1e-4 * float(norm), group
    for group, norm in scan["update_norm"].items():
        assert abs(float(kernels["update_norm"][group]) - float(norm)) \
            < 1e-3 * float(norm), group


def test_a_train_counts_its_positions_by_mixer():
    """`pio_train_seqrec_mixer_tokens_total{mixer}`: positions of the
    trained batches times the layers of each kind the step ran; the
    attention route's counter keeps its meaning for the full layer."""
    from predictionio_tpu.obs.registry import default_registry

    reg = default_registry()

    def counted(name, **labels):
        c = reg.get(name)
        return c.value(**labels) if c is not None else 0

    series = [("pio_train_seqrec_mixer_tokens_total", {"mixer": "gdn"}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "gqa"}),
              ("pio_train_seqrec_attention_tokens_total", {"impl": "xla"}),
              # the chain around the rule follows the rule's route
              ("pio_train_seqrec_linear_attention_tokens_total",
               {"impl": "xla"}),
              ("pio_train_seqrec_linear_attention_chain_tokens_total",
               {"impl": "xla"}),
              ("pio_train_seqrec_linear_attention_chain_tokens_total",
               {"impl": "pallas"})]
    before = [counted(name, **labels) for name, labels in series]
    p = small_spec(epochs=1, batch_size=2, device_init=True)
    sessions = [[f"i{(3 * s + j * (1 + s % 2)) % 50:02d}"
                 for j in range(L + 1)] for s in range(4)]
    model = seqrec.train_seqrec(None, sessions, p)
    assert len(model.record["loss"]) == 2
    assert "layer0.linear_attention" in model.record["grad_norm"][0]
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    positions = 2 * 2 * L
    assert gained == [3 * positions, positions, positions, 3 * positions,
                      3 * positions, 0]


def test_recommend_next_through_the_hybrid():
    p = small_spec(epochs=0, batch_size=2)
    sessions = [[f"i{(s + j) % 30:02d}" for j in range(L + 1)]
                for s in range(6)]                        # items i00..i29
    model = seqrec.train_seqrec(None, sessions, p)
    model.params["head"] = np.zeros_like(model.params["head"])
    model.params["head"][:, 8] = 1.0
    scores = dict(model.recommend_next(["i01", "i02"], 30))
    assert len(scores) == 28                  # 30 items, two seen
    others = [s for it, s in scores.items() if it != "i07"]
    assert scores["i07"] != 0.0 and not any(others)


def test_the_hybrid_trains_and_serves_from_an_engine_json(tmp_path):
    """`pio train` and `pio deploy`'s predict from a variant file alone:
    the new keys of the layer spec reach the model like the old ones."""
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
        app_id = Storage.get_meta_data_apps().insert(App(id=0, name="Hyb"))
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
            "datasource": {"params": {"appName": "Hyb"}},
            "algorithms": [{"name": "seqrec", "params": spec}]}))
        assert variant["algorithms"][0]["params"]["mixer"] == \
            ["gdn", "gdn", "gdn", "gqa"]
        params = engine_params_from_json(
            variant, DataSourceParams, None, {"seqrec": AlgorithmParams})
        eng = engine()
        instance = run_train(eng, params)
        assert instance.status == "COMPLETED"
        result, _ = load_for_deploy(eng, instance)
        algo, model = result.algorithms[0], result.models[0]
        assert model.hyper.mixer_kinds() == ("gdn", "gdn", "gdn", "gqa")
        assert model.record["loss"][-1] < model.record["loss"][0]
        pred = algo.predict(model, Query(items=["i03", "i04", "i05"], num=3))
        items = [s.item for s in pred.item_scores]
        assert "i06" in items and "i05" not in items
    finally:
        Storage.reset()
        clear_cache()


@pytest.mark.parametrize("over,match", [
    ({"mixer": ("gdn", "flash")}, "unknown mixer"),
    ({"mixer": ()}, "names no kind"),
    ({"positions": "learned"}, "learned"),
    ({"norm": "layer"}, "layer"),
    ({"n_kv_heads": 3}, "divisor"),
    ({"head_dim": 0}, "head_dim"),
    ({"rotary_dim": 5}, "rotary_dim"),
    ({"rotary_dim": 32}, "rotary_dim"),
    ({"linear_key_heads": 3}, "linear_"),
    ({"linear_conv_kernel": 0}, "linear_"),
    ({"router_scoring": "tanh"}, "router_scoring"),
    ({"n_shared_experts": 0}, "shared_expert_gate"),
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


def test_a_mixer_is_a_name_or_one_period():
    """The old specs are the period of one; a period repeats over the
    layers and is part of a run's identity."""
    one = seqrec.SeqRecParams(mixer="mha", n_layers=3)
    assert one.mixer_kinds() == ("mha",) * 3
    p = small_spec(n_layers=6)
    assert p.mixer_kinds() == ("gdn", "gdn", "gdn", "gqa", "gdn", "gdn")
    p.check()
    assert p.spec_key() != small_spec(
        n_layers=6, mixer=("gdn", "gqa")).spec_key()
    hash(p.spec_key())
    listed = small_spec(mixer=["gdn", "gdn", "gdn", "gqa"], n_layers=6)
    assert listed.spec_key() == p.spec_key()
    params = seqrec.init_params(np.random.default_rng(0), 20, p)
    assert ["w_qkvz" in layer for layer in params["layers"]] == \
        [True, True, True, False, True, True]
    assert sorted(params["layers"][3]) == sorted(
        ["ln1", "ln2", "wq_gate", "wk", "wv", "q_norm", "k_norm", "wo",
         "router", "router_bias", "experts", "shared", "shared_gate"])
    # norms start at the identity: a zero-centred weight at 0, the linear
    # layer's output norm at 1; the decay's rate in (0, 16)
    assert not np.asarray(params["layers"][3]["q_norm"]["scale"]).any()
    assert np.asarray(params["layers"][0]["o_norm"]["scale"]).all()
    rate = np.exp(np.asarray(params["layers"][0]["A_log"]))
    assert ((rate > 0) & (rate < 16)).all()


def test_the_hybrid_step_under_a_mesh_is_the_step(mesh8):
    """Batch over "data", the projections' columns over "model": the
    sharded step's loss and gradient norms are the one-device step's."""
    from jax.sharding import Mesh

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
    assert sharded["layers"][0]["w_qkvz"].sharding.spec == \
        jax.sharding.PartitionSpec(None, "model")
    _, _, got = seqrec.make_train_step(mesh, p, optimizer)(
        sharded, optimizer.init(sharded), jnp.asarray(seqs),
        jnp.asarray(targets))
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5
    for group, norm in want["grad_norm"].items():
        assert abs(float(got["grad_norm"][group]) - float(norm)) \
            < 2e-3 * float(norm), group
