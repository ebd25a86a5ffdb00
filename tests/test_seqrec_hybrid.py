"""The sequence model under a period of linear-attention (gated delta
rule) and gated grouped-query layers with softmax-routed experts and a
gated shared expert, against the plain reference the benchmark brings
(benchmarks/checks/seqrec_hybrid_reference.py), on seeded random weights
at a small size; and the pieces the spec is made of against their
hand-computed values."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    batch, model, rel, small_blocks,
)

from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import linear_attention, moe
from predictionio_tpu.ops.attention import blockwise_attention, mha, rope

#: the record (tests/seqrec_cases.py): blocks small enough that a session
#: of 24 takes three attention blocks and three chunks of the delta rule,
#: a step's 48 tokens four token blocks, and a linear layer its heads in
#: two groups
CASE = cases.CASES["hybrid"]
ref, L = CASE.ref, CASE.length
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(model, pad):
    _, _, grads, _, _ = cases.loss_and_every_gradient_match_the_reference(
        model, pad)
    # the linear layers' own groups among the record's
    assert {"layer0.linear_attention", "layer3.attention",
            "layer2.shared_expert"} <= set(seqrec._group_norms(grads))
    # the selection bias is no parameter of this spec
    assert not np.asarray(grads["layers"][1]["router_bias"]).any()


def test_logits_match_the_reference(model):
    cases.logits_match_the_reference(model)


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


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


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


def test_a_step_adds_what_the_references_adamw_adds(model):
    """The shared test, and the layers the step reports by mixer."""
    _, stats, *_ = cases.a_step_adds_what_the_references_adamw_adds(
        model, "layer1.linear_attention")
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"gdn": 3, "gqa": 1}
    assert np.asarray(stats["load"]).shape == (4, 16)


@pytest.fixture(scope="module")
def both_routes():
    """A linear layer whose widths the kernels tile, its step compiled
    once on the scans and once on the kernels' route (the device's kind
    patched while it is traced, the kernels interpreted, their products'
    operands left float32) -> (the scans' model, the kernels', what the
    fused chain was called with while the kernels' step was traced)."""
    from predictionio_tpu.ops import attention_pallas, linear_attention_pallas

    case = dataclasses.replace(CASE, blocks=CASE.blocks[:-1] + (
        (linear_attention, "CHUNK", 64),))
    scan, kernels = (cases.Model(
        case, n_layers=2, mixer=("gdn", "gqa"), linear_key_heads=1,
        linear_value_heads=2, linear_key_head_dim=128,
        linear_value_head_dim=128) for _ in range(2))
    chain, calls = linear_attention_pallas.gated_delta_chain_pallas, []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(linear_attention, "_device_kind",
                      lambda: attention_pallas.KINDS[0])
        patch.setattr(linear_attention_pallas, "_BF16", jnp.float32)
        patch.setattr(
            linear_attention_pallas, "gated_delta_chain_pallas",
            lambda *a: calls.append(a[5]) or chain(*a, True))
        patch.setattr(linear_attention_pallas, "gated_delta_rule_pallas",
                      lambda *a: pytest.fail("the rule outside the chain"))
        kernels.step(*batch(seed=4))
    return scan, kernels, calls


@pytest.mark.parametrize("pad", [0, 5])
def test_a_step_on_the_kernels_route_is_the_step_on_the_scans(both_routes,
                                                              pad):
    """The same loss and gradient norms by group on both routes, and the
    step reports the route, which `gated_delta_chain` takes for the rule
    and the chain around it alike: the kernels' step runs the fused chain
    once a linear layer and no rule outside it. The padding is an input
    to the two programs."""
    scan, kernels, calls = both_routes
    scan, kernels = (m.step(*batch(seed=4, pad=pad))[1]
                     for m in (scan, kernels))
    assert not scan["linear_attention_pallas"]
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
    counted = cases.counted
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
    sessions = cases.sessions(4)
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
    params, trained = cases.the_model_trains_and_serves_from_an_engine_json(
        CASE, tmp_path, "Hyb")
    assert params["mixer"] == ["gdn", "gdn", "gdn", "gqa"]
    assert trained.hyper.mixer_kinds() == ("gdn", "gdn", "gdn", "gqa")


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
    cases.a_seq_mesh_is_refused_where_a_mixer_does_not_ring(CASE)


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


def test_the_hybrid_step_under_a_mesh_is_the_step(model, mesh8):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                axis_names=("data", "model"))
    sharded, _, _ = cases.the_step_under_a_mesh_is_the_step(model, mesh)
    assert sharded["layers"][0]["w_qkvz"].sharding.spec == \
        jax.sharding.PartitionSpec(None, "model")
