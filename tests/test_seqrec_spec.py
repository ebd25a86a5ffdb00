"""The sequence model under a layer spec (latent attention, routed
experts held in part, RMS norm, rotary positions, an untied head) against
the plain reference the benchmark brings (benchmarks/checks/
seqrec_reference.py), on seeded random weights at a small size; and the
pieces the spec is made of against their hand-computed values."""

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
from predictionio_tpu.ops import moe
from predictionio_tpu.ops.attention import blockwise_attention, mha, rope

#: the record (tests/seqrec_cases.py): blocks small enough that a session
#: of 24 takes three attention blocks and a step's 48 tokens four token
#: blocks
CASE = cases.CASES["spec"]
ref, L = CASE.ref, CASE.length
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(model, pad):
    _, _, grads, _, _ = cases.loss_and_every_gradient_match_the_reference(
        model, pad)
    # the selection bias takes no gradient
    assert not np.asarray(grads["layers"][1]["router_bias"]).any()


def test_logits_match_the_reference(model):
    p, params = model.p, model.params
    seqs, _ = batch(seed=5, rows=1)
    hidden = model.forward(seqs)
    with jax.default_matmul_precision("highest"):
        logits = hidden[0] @ seqrec.head_matrix(params)
        spec = ref_spec(p)
        h = params["emb"][seqs[0]]
        for i, layer in enumerate(params["layers"]):
            h = h + ref.attention(layer, ref.rms_norm(
                h, layer["ln1"]["scale"], 1e-5), seqs[0] != 0, spec)
            x = ref.rms_norm(h, layer["ln2"]["scale"], 1e-5)
            h = h + (ref.swiglu(layer, x, spec) if i < 1
                     else ref.expert_layer(layer, x, spec)[0])
        want = ref.rms_norm(h, params["ln_f"]["scale"], 1e-5) @ params["head"]
    assert rel(logits, want) < 1e-5      # float32 roundings, as above


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their parts of an expert layer,
    with the shared expert (which every chip computes alike) counted
    once, add up to what the reference gives for the layer whole."""
    p = small_spec()
    layer = weights(p)["layers"][1]
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, L, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, load, _ = ref.expert_layer(layer, x[0], ref_spec(p))
        shared = ref.swiglu(layer["shared"], x[0], ref_spec(p))
        total, held = 0.0, []
        for lo in range(0, 8, 2):
            share = dict(layer, experts=jax.tree.map(
                lambda w: w[lo:lo + 2], layer["experts"]))
            y, stats = seqrec._moe(share, x, dataclasses.replace(
                p, held_experts=(lo, lo + 2)))
            total = total + (y[0] - shared)
            held += np.asarray(stats["held_tokens"]).tolist()
            assert np.array_equal(stats["load"], load)   # all route alike
    assert rel(total + shared, whole) < 1e-5
    assert held == np.asarray(load).tolist() and sum(held) == 2 * L


@pytest.mark.parametrize("favoured, rows", [((3,), 59), ((2, 3), 96)])
def test_no_token_is_dropped_under_a_router_forced_onto_held_experts(
        favoured, rows):
    """Every token to one expert; and both of a token's experts held
    here. Either way the routed rows outnumber a step's 48 tokens, which
    is what the layer multiplies at a time: it takes a second pass."""
    p = small_spec(held_experts=(2, 4))
    layer = weights(p)["layers"][1]
    layer = dict(layer, router_bias=jnp.zeros(8).at[
        jnp.asarray(favoured)].set(100.0))
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, L, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, stats = seqrec._moe(layer, x, p)
        want = jnp.stack([ref.expert_layer(layer, row, ref_spec(p))[0]
                          for row in x])
    held = np.asarray(stats["held_tokens"])
    assert held[1] == 2 * L and held.sum() == rows
    assert int(stats["dropped"]) == 0
    assert rel(y, want) < 1e-5


def test_the_expert_layer_in_passes_and_its_count_of_dropped_tokens(
        monkeypatch):
    """Twelve passes of 8 rows give what one pass gives, gradients too;
    and the count of dropped tokens is read from the output: a loop that
    leaves five routed tokens at zero says five."""
    p = small_spec(held_experts=(2, 4))
    layer = weights(p)["layers"][1]
    ex = layer["experts"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(2 * L, 64)),
                    jnp.float32)
    routing = moe.route(x, layer["router"], jnp.zeros(8).at[
        jnp.asarray([2, 3])].set(100.0), 2, 2.446)

    def out(pass_rows, x, w_gate):
        y, _, dropped = moe.held_experts(x, w_gate, ex["w_up"], ex["w_down"],
                                         routing, 2, pass_rows)
        return jnp.sum(jnp.sin(y)), dropped

    with jax.default_matmul_precision("highest"):
        (a, dropped), da = jax.value_and_grad(
            lambda *w: out(8, *w), (0, 1), has_aux=True)(x, ex["w_gate"])
        (b, _), db = jax.value_and_grad(
            lambda *w: out(4 * L, *w), (0, 1), has_aux=True)(x, ex["w_gate"])
    assert int(dropped) == 0 and abs(float(a - b)) < 1e-4
    for g, w in zip(da, db):
        assert rel(g, w) < 1e-5
    whole = moe._grouped_experts
    monkeypatch.setattr(moe, "_grouped_experts",
                        lambda *a: whole(*a).at[:5].set(0.0))
    assert int(out(8, x, ex["w_gate"])[1]) == 5


def test_bias_update_and_balance_loss_by_hand():
    load = jnp.asarray([5, 1, 3, 3])
    got = moe.bias_update(jnp.asarray([0.0, 0.1, -0.1, 0.0]), load, 0.01)
    np.testing.assert_allclose(got, [-0.01, 0.11, -0.1, 0.0], atol=1e-7)
    np.testing.assert_allclose(
        ref.bias_after_step([0.0, 0.1, -0.1, 0.0], load, 0.01), got,
        atol=1e-7)
    # one sequence of two tokens, three experts, top-1: token 0 -> expert
    # 0, token 1 -> expert 2. f = (3/2) (1, 0, 1); P = mean of the
    # affinities normalised over the experts
    scores = jnp.asarray([[0.6, 0.3, 0.1], [0.2, 0.2, 0.4]])
    routing = moe.Routing(jnp.asarray([[0], [2]]), None, scores)
    p0 = (0.6 / 1.0 + 0.2 / 0.8) / 2
    p2 = (0.1 / 1.0 + 0.4 / 0.8) / 2
    np.testing.assert_allclose(moe.sequence_balance_loss(routing, 1),
                               1.5 * p0 + 1.5 * p2, rtol=1e-6)


def test_a_train_step_moves_the_bias_and_leaves_it_out_of_adamw(model):
    faster = model.of(bias_update_rate=0.01, learning_rate=1e-2)
    before = np.asarray(faster.params["layers"][1]["router_bias"])
    _, _, load = faster.reference()
    new, stats = faster.step(*batch())
    assert np.array_equal(stats["load"], load)
    np.testing.assert_allclose(
        new["layers"][1]["router_bias"],
        ref.bias_after_step(before, load[0], 0.01), atol=1e-7)
    assert set(stats["grad_norm"]) == set(stats["update_norm"]) == set(
        ref.group_norms(faster.params))


def test_blockwise_attention_with_unequal_widths_and_rope_matches_mha():
    rng = np.random.default_rng(9)
    q, k = (jnp.asarray(rng.normal(size=(2, 40, 3, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 40, 3, 16)), jnp.float32)
    mask = jnp.asarray(rng.random((2, 40)) > 0.2)
    pos = jnp.arange(40)

    def run(attend):
        def f(q, k, v):
            qr = jnp.concatenate([q[..., :16], rope(q[..., 16:], pos, 1e4)], -1)
            kr = jnp.concatenate([k[..., :16], rope(k[..., 16:], pos, 1e4)], -1)
            return attend(qr, kr, v)
        out, vjp = jax.vjp(f, q, k, v)
        return (out, *vjp(jnp.cos(out)))

    with jax.default_matmul_precision("highest"):
        got = run(lambda q, k, v: blockwise_attention(
            q, k, v, block_k=16, block_q=8, causal=True, key_mask=mask))
        want = run(lambda q, k, v: mha(q, k, v, causal=True, key_mask=mask))
    assert got[0].shape == (2, 40, 3, 16)
    for g, w in zip(got, want):     # float32, another order of summation
        assert rel(g, w) < 1e-5
    # rotary scores depend on the distance alone
    a = rope(q[:, :, :, :8], pos, 1e4)
    b = rope(q[:, :, :, :8], pos + 7, 1e4)
    np.testing.assert_allclose(
        jnp.einsum("blhd,bmhd->bhlm", a, a),
        jnp.einsum("blhd,bmhd->bhlm", b, b), atol=1e-4)


def test_the_default_spec_is_the_original_block():
    """The block this model had before it took a layer spec, written out
    here as it was, on the weights the default spec draws."""
    p = seqrec.SeqRecParams(d_model=32, n_heads=2, n_layers=2, max_len=16)
    params = seqrec.init_params(np.random.default_rng(7), 40, p)
    assert sorted(params) == ["emb", "layers", "ln_f", "pos"]
    assert sorted(params["layers"][0]) == ["ln1", "ln2", "w1", "w2", "wo",
                                           "wqkv"]
    # the host draws, in the original order
    rng = np.random.default_rng(7)
    first = jnp.asarray(rng.normal(size=(32, 96)) * 32 ** -0.5, jnp.float32)
    assert np.array_equal(params["layers"][0]["wqkv"], first)

    def ln(x, w):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-6) * w["scale"] + w["bias"]

    seqs = jnp.asarray(batch(seed=1, pad=3)[0][:, :16] % 41)
    h = params["emb"][seqs] + params["pos"][None, :16]
    for layer in params["layers"]:
        q, k, v = (t.reshape(2, 16, 2, 16) for t in
                   jnp.split(ln(h, layer["ln1"]) @ layer["wqkv"], 3, -1))
        att = mha(q, k, v, causal=True, key_mask=seqs != 0)
        h = h + att.reshape(2, 16, 32) @ layer["wo"]
        h = h + jax.nn.gelu(ln(h, layer["ln2"]) @ layer["w1"]) @ layer["w2"]
    want = jnp.where((seqs == 0)[..., None], 0.0, ln(h, params["ln_f"]))
    np.testing.assert_allclose(seqrec.forward(params, seqs, p), want,
                               atol=2e-6)
    assert seqrec.head_matrix(params).shape == (32, 41)


def test_recommend_next_reads_the_head_the_spec_names():
    p = small_spec(epochs=0, batch_size=2)
    sessions = [[f"i{(s + j) % 30:02d}" for j in range(L + 1)]
                for s in range(6)]                        # items i00..i29
    model = seqrec.train_seqrec(None, sessions, p)
    assert model.record == {"rows": [], "loss": [], "grad_norm": [],
                            "update_norm": []}
    # an untied head whose one column is item i07's (code 8): the tied
    # reading (the embeddings) would score every item
    model.params["head"] = np.zeros_like(model.params["head"])
    model.params["head"][:, 8] = 1.0
    scores = dict(model.recommend_next(["i01", "i02"], 30))
    assert len(scores) == 28                  # 30 items, two seen
    others = [s for it, s in scores.items() if it != "i07"]
    assert scores["i07"] != 0.0 and not any(others)


def test_a_train_records_its_steps():
    p = small_spec(epochs=2, batch_size=2, device_init=True)
    sessions = cases.sessions(4)
    model = seqrec.train_seqrec(None, sessions, p)
    rec = model.record
    assert len(rec["loss"]) == 4 and rec["loss"][-1] < rec["loss"][0]
    assert sorted(sum(rec["rows"][:2], [])) == [0, 1, 2, 3]
    assert np.asarray(rec["load"]).shape == (4, 2, 8)
    assert np.asarray(rec["load"]).sum(-1).tolist() == [[2 * 2 * L] * 2] * 4
    assert np.asarray(rec["held_tokens"]).shape == (4, 2, 8)
    assert not np.asarray(rec["dropped"]).any()
    assert "layer1.experts" in rec["grad_norm"][0]
    again = seqrec.train_seqrec(None, sessions, p)    # deterministic
    assert again.record["loss"] == rec["loss"]


def test_a_step_adds_what_the_references_adamw_adds(model):
    """The shared test at this record's 1e-4, and the router's group."""
    *_, want = cases.a_step_adds_what_the_references_adamw_adds(
        model, "layer1.attention")
    # a router's group holds its bias's own update: gamma x sign, 8 experts
    assert want["layer1.router"] ** 2 > 8 * 0.001 ** 2 * 0.99


def test_a_train_steps_record_against_the_reference_and_the_int8_control(
        model):
    cases.a_train_steps_record_against_the_reference_and_the_int8_control(
        model, ("attention", "experts", "ffn"))


def test_memory_settings_are_no_part_of_a_runs_identity():
    """A resume without recomputation, or with the sequence laid over a
    mesh, is the same run; under another learning rate or width it is
    not. The program's cache key tells them all apart."""
    vocab = np.asarray(["a", "b"], dtype=object)
    p = small_spec()
    same = dataclasses.replace(p, remat=False)
    assert seqrec.seqrec_fingerprint(vocab, p) == \
        seqrec.seqrec_fingerprint(vocab, same)
    assert p.spec_key() != same.spec_key()
    for other in (dataclasses.replace(p, learning_rate=3e-3),
                  dataclasses.replace(p, moe_width=64),
                  dataclasses.replace(p, device_init=True)):
        assert seqrec.seqrec_fingerprint(vocab, p) != \
            seqrec.seqrec_fingerprint(vocab, other)
