"""The sequence model under a layer spec of sliding-window layers beside
full ones -- head counts and rotary tables by kind, YaRN on the full
layers, a gate of one column a head, a leading dense layer, sigmoid-routed
experts beside a shared one at an expert share -- against the plain
reference the benchmark brings
(benchmarks/checks/seqrec_window_reference.py), on seeded random weights
at a small size; the band in the scan and in the interpreted kernels
against a dense masked softmax; the pair tables; YaRN by hand; the share
tied to the model. (What the program already ran: the one table of pins
in tests/test_seqrec_kinds.py.)"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    VOCAB, YARN, model, rel, small_blocks,
)

from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import attention, attention_pallas
from predictionio_tpu.ops.attention import YarnScaling

#: the record (tests/seqrec_cases.py): a session of 24 takes three
#: attention blocks (a window of 7 leaves the pair (2, 0) out), a step's
#: 48 tokens four token blocks
CASE = cases.CASES["window"]
ref, L, SWA = CASE.ref, CASE.length, CASE.spec["swa"]
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


# -- the band ----------------------------------------------------------------

def dense_band(q, k, v, mask, window):
    """A dense masked softmax: q [B, L, H, D] over k, v [B, L, Hkv, D],
    query t seeing the real keys s with t - window < s <= t (None: all
    up to its own)."""
    l, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = jnp.arange(l)
    seen = t[None, :] <= t[:, None]
    if window is not None:
        seen = seen & (t[None, :] > t[:, None] - window)
    seen = seen[None, None] & mask[:, None, None, :]
    top = jnp.max(jnp.where(seen, s, -jnp.inf), -1, keepdims=True)
    w = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = w.sum(-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", w / jnp.where(total == 0, 1.0,
                                                       total), v)


def band_case(l, pad, seed, heads=4, kv_heads=2, width=64):
    rng = np.random.default_rng(seed)
    draw = lambda h: jnp.asarray(rng.normal(size=(2, l, h, width)),
                                 jnp.float32)
    mask = jnp.asarray(np.arange(l)[None, :] >= np.array([[pad], [0]]))
    return draw(heads), draw(kv_heads), draw(kv_heads), mask, draw(heads)


BANDS = [("off-the-block", 300, 64, 0), ("left-padded", 256, 37, 20),
         ("window-of-one", 256, 1, 3), ("window-past-the-session", 256, 400, 5),
         ("a-block-wide", 384, 128, 0), ("two-blocks-wide", 384, 256, 9)]


@pytest.mark.parametrize("name,l,window,pad", BANDS)
def test_the_scans_band_is_a_dense_masked_softmax(name, l, window, pad):
    """Forward and the three gradients, for a length off the block, left
    padding, a window of one key and one past the whole session."""
    q, k, v, mask, w = band_case(l, pad, len(name))
    with jax.default_matmul_precision("highest"):
        banded = lambda q, k, v: attention.blockwise_attention(
            q, k, v, block_k=128, causal=True, key_mask=mask, window=window)
        got, d_got = jax.value_and_grad(
            lambda *a: (banded(*a) * w).sum(), (0, 1, 2))(q, k, v)
        want, d_want = jax.value_and_grad(
            lambda *a: (dense_band(*a, mask, window) * w).sum(),
            (0, 1, 2))(q, k, v)
        out = banded(q, k, v)
        if window >= l:         # the band is the whole causal triangle
            assert rel(out, attention.blockwise_attention(
                q, k, v, block_k=128, causal=True, key_mask=mask)) < 1e-6
    assert rel(out, dense_band(q, k, v, mask, window)) < 1e-5
    assert abs(float(got - want)) < 1e-4 * abs(float(want))
    for part, g, d in zip("qkv", d_got, d_want):
        assert float(jnp.abs(g - d).max()) < 1e-5 * max(
            float(jnp.abs(d).max()), 1.0), part


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("name,l,window,pad", BANDS[1:])
def test_the_kernels_band_is_a_dense_masked_softmax(monkeypatch, name, l,
                                                    window, pad, block):
    """The same through `window_attention_pallas` in the interpreter (its
    products take bfloat16 operands), at two blocks: a band inside one
    block, across two and across three."""
    monkeypatch.setattr(attention_pallas, "WINDOW_BLOCK", block)
    q, k, v, mask, w = band_case(l, pad, len(name))
    hf = lambda t: jnp.swapaxes(t, 1, 2)
    banded = lambda q, k, v: hf(attention_pallas.window_attention_pallas(
        hf(q), hf(k), hf(v), mask, window, True))
    got, d_got = jax.value_and_grad(
        lambda *a: (banded(*a) * w).sum(), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want, d_want = jax.value_and_grad(
            lambda *a: (dense_band(*a, mask, window) * w).sum(),
            (0, 1, 2))(q, k, v)
        assert rel(banded(q, k, v), dense_band(q, k, v, mask, window)) < 2e-2
    # (a gradient that is 0 by cancellation, dq and dk under a window of
    # one key, reads the two roundings of do . v apart: some sqrt(width))
    for part, g, d in zip("qkv", d_got, d_want):
        assert float(jnp.abs(g - d).max()) < 2e-2 * max(
            float(jnp.abs(d).max()), 8.0), part


@pytest.mark.parametrize("n,block,window", [
    (6, 128, 128), (6, 128, 129), (6, 128, 1), (8, 64, 200), (4, 256, 512),
    (4, 256, 5000), (32, 512, 512), (16, 1024, 512)])
def test_no_pair_outside_the_band_and_none_inside_missing(n, block, window):
    """Both tables hold exactly the block pairs with a (query, key) that
    `sees`: the scan walks the kernels' query-major table, the backward
    kernel the same pairs key-major."""
    t = np.arange(n * block)
    seen = np.asarray(attention_pallas.sees(t[:, None], t[None, :], True,
                                            window))
    want = {(i, j) for i in range(n) for j in range(n)
            if seen[i * block:(i + 1) * block,
                    j * block:(j + 1) * block].any()}
    by_query = attention_pallas._block_pairs(n, n, block, block, True, False,
                                             window)
    by_key = attention_pallas._block_pairs(n, n, block, block, True, True,
                                           window)
    assert set(map(tuple, by_query.tolist())) == want
    assert set(map(tuple, by_key.tolist())) == want
    assert len(by_query) == len(by_key) == len(want)
    assert by_query.tolist() == sorted(by_query.tolist())
    assert by_key.tolist() == sorted(by_key.tolist(), key=lambda p: p[::-1])
    assert np.array_equal(attention._block_pairs(n, n, block, block, True,
                                                 window), by_query)
    # without a window the tables are the causal triangle's, as they were
    causal = attention_pallas._block_pairs(n, n, block, block, True, False)
    assert len(causal) == n * (n + 1) // 2
    assert np.array_equal(attention._block_pairs(n, n, block, block, True),
                          causal)


def test_band_pairs_counts_the_band_and_the_blocks_its_route_visits():
    """16,384 positions under a window of 512: W (W + 1) / 2 + (L - W) W
    pairs count; the scan's blocks of 512 visit two pairs a query block
    but the first."""
    inside, visited = attention.band_pairs("cpu", 16384, 128, 128, 512, 512)
    assert inside == 512 * 513 // 2 + (16384 - 512) * 512 == 8_257_792
    assert visited == (2 * 32 - 1) * 512 * 512
    kernels = attention.band_pairs(attention_pallas.KINDS[0], 16384, 128, 128,
                                   512, 512)
    block = attention_pallas.WINDOW_BLOCK
    assert kernels == (inside, len(attention_pallas._block_pairs(
        16384 // block, 16384 // block, block, block, True, False, 512))
        * block * block)
    # a window past the session is the causal triangle
    assert attention.band_pairs("cpu", 24, 8, 8, 100, 8)[0] == 24 * 25 // 2


@pytest.mark.parametrize("route", ["scan", "kernels"])
def test_an_edge_off_by_one_reads_whole_numbers_where_the_sound_one_reads_0(
        route, monkeypatch):
    """The probe's edge case (`benchmarks/tools/seqrec_window_probe.py
    --edge`: scores that peak midway between the keys `window` - 1 and
    `window` back): under the window it was made for a query's output IS
    the value `window` - 1 back and `dv` the cotangent that far ahead; a
    band one key wider or narrower is off by whole numbers. What the
    check's rows cannot tell apart at the cell's size, this can on
    either route."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "seqrec_window_probe", os.path.join(
            os.path.dirname(__file__), "..", "benchmarks", "tools",
            "seqrec_window_probe.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    if route == "kernels":
        monkeypatch.setattr(attention_pallas, "WINDOW_BLOCK", 128)
        monkeypatch.setattr(attention, "_device_kind",
                            lambda: attention_pallas.KINDS[0])
        banded = attention_pallas.window_attention_pallas
        monkeypatch.setattr(
            attention_pallas, "window_attention_pallas",
            lambda q, k, v, mask, window: banded(q, k, v, mask, window,
                                                 True))
    read = lambda window: probe.edge_errors(jax, jnp, np, 384, 4, 2, 128,
                                            130, window)
    sound = read(130)
    assert sound["out_err"] < 0.05 and sound["dv_err"] < 0.05, sound
    for window in (129, 131):
        off = read(window)
        assert off["out_err"] > 0.5 and off["dv_err"] > 0.5, (window, off)


def test_a_window_is_causal_and_holds_the_querys_own_key():
    q, k, v, mask, _ = band_case(128, 0, 1)
    for causal, window in ((False, 8), (True, 0)):
        with pytest.raises(ValueError, match="window"):
            attention.blockwise_attention(q, k, v, causal=causal,
                                          key_mask=mask, window=window)


# -- YaRN ---------------------------------------------------------------------

def test_yarns_frequencies_by_hand():
    """The published record at a rotary width of 64, theta 500,000:
    dim(r) = 64 ln(4096 / (2 pi r)) / (2 ln 500000); dim(64) = 5.66 so
    low = 5, dim(1) = 15.80 so high = 16: pairs 0-5 keep theta^(-2j/64),
    pairs 16-31 have it over 64, pair 10 is 5/11 of the way."""
    yarn = YarnScaling(factor=64.0, original_max_len=4096, beta_fast=64.0,
                       beta_slow=1.0, attention_factor=1.4158883083359672)
    freq = np.asarray(yarn.frequencies(500000.0, 64), np.float64)
    kept = 500000.0 ** (-np.arange(32) / 32.0)
    assert freq.shape == (32,)
    np.testing.assert_allclose(freq[:6], kept[:6], rtol=1e-6)
    np.testing.assert_allclose(freq[16:], kept[16:] / 64.0, rtol=1e-6)
    np.testing.assert_allclose(
        freq[10], kept[10] * (5 / 11 / 64.0 + 6 / 11), rtol=1e-6)
    assert freq[0] == 1.0 and abs(freq[31] - 4.715e-8) < 1e-10
    assert yarn.amplitude() == 1.4158883083359672
    assert YarnScaling(64.0, 4096).amplitude() == pytest.approx(
        0.1 * np.log(64.0) + 1.0)
    np.testing.assert_allclose(freq, ref.yarn_inv_freq(
        500000.0, 64, dataclasses.asdict(yarn)), rtol=1e-6)
    # a vector turned at position 3: amplitude x the plain rotation at
    # the scaled frequency, the columns past the rotary width untouched
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 1, 128)),
                    jnp.float32)
    turned = np.asarray(attention.rope(x, jnp.arange(4), 500000.0, 64, yarn))
    assert np.array_equal(turned[..., 64:], np.asarray(x)[..., 64:])
    ang = 3 * freq
    x1, x2 = np.asarray(x)[0, 3, 0, :32], np.asarray(x)[0, 3, 0, 32:64]
    np.testing.assert_allclose(
        turned[0, 3, 0, :32],
        1.4158883083359672 * (x1 * np.cos(ang) - x2 * np.sin(ang)), atol=1e-5)
    # no scaling: the table it was
    plain = attention.rope(x, jnp.arange(4), 500000.0, 64)
    assert np.array_equal(np.asarray(plain), np.asarray(
        attention.rope(x, jnp.arange(4), 500000.0, 64, None)))
    assert not np.allclose(np.asarray(plain), turned)


# -- each attention kind alone --------------------------------------------------

def layer_case(kind, pad=4):
    p = small_spec()
    layer = weights(p)["layers"][1 if kind == "swa" else 0]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, L, 64)), jnp.float32)
    mask = jnp.asarray(np.arange(L)[None, :] >= np.array([[pad], [0]]))
    return p, layer, x, mask


@pytest.mark.parametrize("kind", ["gqa", "swa"])
def test_an_attention_layer_alone_matches_the_reference(kind):
    p, layer, x, mask = layer_case(kind)
    spec = ref_spec(p)
    own = layer["swa"] if kind == "swa" else layer
    cot = jnp.asarray(np.random.default_rng(6).normal(size=x.shape),
                      jnp.float32)
    with jax.default_matmul_precision("highest"):
        ours = lambda w, x: (p.held_kind(kind).apply(w, x, mask, p, None)
                             * cot).sum()
        theirs = lambda w, x: (jnp.stack([
            ref.attention(w, row, ok, spec, kind)
            for row, ok in zip(x, mask)]) * cot).sum()
        got, d_got = jax.value_and_grad(ours, (0, 1))(layer, x)
        want, d_want = jax.value_and_grad(theirs, (0, 1))(own, x)
    assert abs(float(got - want)) < 1e-4 * max(abs(float(want)), 1.0)
    d_own = d_got[0]["swa"] if kind == "swa" else d_got[0]
    for name in ("wq", "w_head_gate", "wk", "wv", "wo"):
        assert rel(d_own[name], d_want[0][name]) < 1e-4, name
    assert rel(d_got[1], d_want[1]) < 1e-4
    heads = 8 if kind == "swa" else 4
    assert own["w_head_gate"].shape == (64, heads)
    assert own["wq"].shape == (64, heads * 8)
    assert "wq_gate" not in own and "q_norm" not in own


def test_a_left_padded_session_is_the_unpadded_one_in_a_sliding_layer():
    """Positions are indices into the padded row, and a rotary score
    reads their difference alone: the real positions' outputs do not
    move with the padding in front."""
    p, layer, x, _ = layer_case("swa")
    band = p.held_kind("swa")
    short = x[:1, 5:]
    with jax.default_matmul_precision("highest"):
        padded = band.apply(layer, x[:1], jnp.arange(L)[None] >= 5, p, None)
        alone = band.apply(layer, short, jnp.ones((1, L - 5), bool), p, None)
    assert rel(padded[:, 5:], alone) < 1e-5


# -- the whole step -----------------------------------------------------------

@pytest.mark.parametrize("pad", [0, 5])
def test_loss_loads_and_every_gradient_match_the_reference(model, pad):
    _, (expert_layers, mixers, _), grads, _, want_load = \
        cases.loss_and_every_gradient_match_the_reference(model, pad)
    groups = seqrec._group_norms(grads)
    assert {"layer0.attention", "layer0.ffn", "layer1.window_attention",
            "layer3.window_attention", "layer4.attention", "layer1.router",
            "layer4.experts", "layer2.shared_expert",
            "layer2.norms"} <= set(groups)
    assert "layer1.attention" not in groups
    load = np.stack([np.asarray(s["load"]) for s in expert_layers])
    assert load.shape == (4, 16) and np.array_equal(load, want_load)
    assert {k: int(v) for k, v in mixers.items()} == {"gqa": 2, "swa": 3}


def test_logits_match_the_reference(model):
    cases.logits_match_the_reference(model)


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


def test_a_train_steps_record_against_the_reference_and_the_int8_control(
        model):
    after, stats, load, update_norms, by_expert = \
        cases.a_train_steps_record_against_the_reference_and_the_int8_control(
            model, ("attention", "experts", "ffn"))
    # the experts' update expert by expert; over a layer's held experts
    # it is the group's
    assert by_expert.shape == (4, 16)
    assert np.allclose(np.sqrt((by_expert ** 2).sum(-1)), [
        update_norms[f"layer{i}.experts"] for i in (1, 2, 3, 4)], rtol=1e-6)
    assert np.array_equal(stats["load"], load)
    assert "layer_passes" not in stats
    # the selection bias is no parameter of adamw's and its rate is 0
    assert not np.asarray(after["layers"][1]["router_bias"]).any()
    # one held expert left where it is: its layer's group and its own row
    grads = model.reference(2)[1]
    less, by_expert_less = model.update_norms(grads, load,
                                              expert_not_updated=(1, 3))
    assert by_expert_less[1, 3] == 0 and less["layer2.experts"] \
        < update_norms["layer2.experts"]
    assert less["layer1.experts"] == update_norms["layer1.experts"]


#: each planted fault of the reference and a gradient part it has to move
#: (at theta_0 on one batch; the loss moves with every one too)
FAULT_PARTS = {
    "window_ignored": "window_attention",
    "window_plus_one": "window_attention",
    "window_minus_one": "window_attention",
    "tables_swapped": "window_attention",
    "yarn_ramp_left_out": "attention",
    "attention_factor_left_out": "attention",
    "gate_left_out": "attention",
    "full_heads_everywhere": "window_attention",
    "scaling_factor_left_out": "experts",
}


def test_every_fault_has_a_part():
    assert set(FAULT_PARTS) == set(ref.FAULTS)


@pytest.mark.parametrize("fault", sorted(FAULT_PARTS))
def test_every_fault_control_of_the_reference_moves_its_part(model, fault):
    sound_loss, sound, _ = model.reference(7)
    loss, grads, _ = model.reference(7, fault=fault)
    assert abs(loss - sound_loss) > 1e-6 * sound_loss
    sound, broken = ref.group_norms(sound), ref.group_norms(grads)
    part = FAULT_PARTS[fault]
    assert max(abs(broken[g] - n) / n for g, n in sound.items()
               if g.endswith("." + part)) > 1e-3, part


def _interpreted(monkeypatch, calls):
    """The attention entries as a v5e would route them, the kernels in
    the Pallas interpreter; `calls` takes what each entry was asked for."""
    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    whole, banded, grouped = (attention_pallas.flash_attention_pallas,
                              attention_pallas.window_attention_pallas,
                              attention_pallas.grouped_attention_pallas)
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: calls.append(("whole", q.shape[1]))
        or whole(q, k, v, mask, causal, True))
    monkeypatch.setattr(
        attention_pallas, "window_attention_pallas",
        lambda q, k, v, mask, window: calls.append((window, q.shape[1]))
        or banded(q, k, v, mask, window, True))
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda q, k, v, gate, mask, tables, heads, shifts, window,
        operand_dtype=None, packed=False: calls.append(
            ("rows", window, heads, shifts))
        or grouped(q, k, v, gate, mask, tables, heads, shifts, window, True,
                   operand_dtype, packed))


@pytest.mark.parametrize("kind", ["gqa", "swa"])
def test_a_layer_on_the_token_first_route_is_the_head_first_one(monkeypatch,
                                                                kind):
    """A full layer (6 heads of 128 on 2, rotary on 64 of 128 under
    YaRN) and a sliding one (8 on 2, a window of 100, rotary on all 128),
    each with its gate of a column a head, on the kernels' route at 256
    positions with a left-padded row: the layer function token-first
    (`grouped_attention`: the widths' layout) equals the head-first one
    (`layout` patched: `rope` and the gate on [B, L, H, D], the
    transposes), values and the gradients of every leaf and of the
    input, to the tolerance the `mha` layer's two layouts are held to
    (tests/test_seqrec_looped.py): both round the same operands to
    bfloat16 for the same kernels."""
    monkeypatch.undo()
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 128)
    p = small_spec(d_model=128, n_heads=6, n_kv_heads=2, head_dim=128,
                   rotary_dim=64, max_len=256, n_layers=2,
                   mixer=("gqa", "swa"), first_dense_layers=1,
                   swa=dict(heads=8, window=100, rope_theta=10000.0,
                            rotary_dim=128))
    layer = weights(p)["layers"][1 if kind == "swa" else 0]
    rng = np.random.default_rng(7)
    x, cot = (jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
              for _ in range(2))
    mask = jnp.asarray(np.arange(256)[None, :] >= np.array([[9], [0]]))
    calls = []
    _interpreted(monkeypatch, calls)

    def run():
        del calls[:]
        layouts = set()
        with attention.routes_into(set(), layouts):
            out, pull = jax.vjp(lambda w, x: seqrec._attention(
                w, x, mask, p, kind, None, False), layer, x)
        return layouts, out, pull(cot)

    layouts, got, (d_layer, d_x) = run()
    window = 100 if kind == "swa" else None
    assert layouts == {"rows"} and calls == [
        ("rows", window, (8, 2) if kind == "swa" else (6, 2),
         (64,) if kind == "swa" else (96, 32))]
    monkeypatch.setattr(attention_pallas, "layout", lambda dk, dv: "heads")
    layouts, want, (want_layer, want_x) = run()
    assert layouts == {"heads"} and calls == [
        (100, 8) if kind == "swa" else ("whole", 6)]
    assert rel(got, want) < 2e-3 and rel(d_x, want_x) < 5e-3
    own = d_layer["swa"] if kind == "swa" else d_layer
    for name in ("wq", "w_head_gate", "wk", "wv", "wo"):
        assert float(jnp.abs(own[name]).max()) > 0, name
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(d_layer),
                            jax.tree.leaves(want_layer)):
        assert rel(g, w) < 5e-3, jax.tree_util.keystr(path)


@pytest.mark.parametrize("ambient,grad_dtype", [
    (None, jnp.bfloat16), ("bfloat16", jnp.bfloat16), ("highest", None),
    ("float32", None)])
def test_the_three_products_gradients_are_rounded_where_their_products_round(
        ambient, grad_dtype, monkeypatch):
    """The `swa` mixer (the `gqa` one's `apply`) asks the token-first
    route for bfloat16 gradients of `x @ wq`, `x @ wk`, `x @ wv` only
    while those products, and so their backward products, take their
    operands in one bfloat16 pass (`seqrec._qkv_grad_dtype`, the rule the
    `mha` mixer follows: tests/test_seqrec_looped.py); under a higher
    default it asks for none."""
    monkeypatch.undo()
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 128)
    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    asked = []
    grouped = attention_pallas.grouped_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda *a, operand_dtype=None, packed=False: asked.append(
            operand_dtype) or grouped(*a, True, operand_dtype, packed))
    p = small_spec(d_model=128, n_heads=2, n_kv_heads=1, head_dim=128,
                   rotary_dim=64, max_len=128, n_layers=2,
                   mixer=("gqa", "swa"), first_dense_layers=1,
                   swa=dict(heads=2, window=100, rope_theta=10000.0,
                            rotary_dim=128))
    layer = weights(p)["layers"][1]
    x = jnp.ones((1, 128, 128), jnp.float32)
    mask = jnp.ones((1, 128), bool)
    traced = lambda: jax.make_jaxpr(lambda w, x: seqrec._attention(
        w, x, mask, p, "swa", None, False))(layer, x)
    if ambient is None:
        traced()
    else:
        with jax.default_matmul_precision(ambient):
            traced()
    assert asked == [grad_dtype]


def test_a_step_on_the_kernels_route_is_the_step_on_the_scans(monkeypatch):
    """Heads of 128 at 256 positions, on both routes (the device's kind
    patched, the kernels interpreted): the same loss and gradient norms
    by group to the kernels' bfloat16 operands, both kinds of layer on
    the kernels token-first (`attention_rows`: the widths' layout), and
    head-first (`layout` patched) the sliding ones through the banded
    entry alone. A step says `attention_rows` only where BOTH kinds
    heard "rows": with the sliding layers alone head-first it does not."""
    monkeypatch.undo()
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 128)
    p = small_spec(d_model=128, n_heads=2, n_kv_heads=1, head_dim=128,
                   rotary_dim=64, max_len=256, n_layers=2,
                   mixer=("gqa", "swa"), first_dense_layers=1,
                   swa=dict(heads=3, window=100, rope_theta=10000.0,
                            rotary_dim=128))
    params = weights(p)
    optimizer = seqrec.make_optimizer(p)
    rng = np.random.default_rng(4)
    s = rng.integers(1, VOCAB, size=(1, 257))
    s[:, :9] = 0
    seqs, targets = s[:, :-1].astype(np.int32), s[:, 1:].astype(np.int32)

    def step():
        return seqrec.make_train_step(None, p, optimizer)(
            jax.tree.map(jnp.copy, params), optimizer.init(params),
            jnp.asarray(seqs), jnp.asarray(targets))[2]

    scan = step()
    assert not scan["attention_pallas"] and "attention_rows" not in scan
    calls = []
    _interpreted(monkeypatch, calls)
    rows = step()
    assert rows["attention_pallas"] and rows["attention_rows"]
    assert set(calls) == {("rows", 100, (3, 1), (64,)),
                          ("rows", None, (2, 1), (96, 32))}, calls
    del calls[:]
    layout = seqrec.attention_layout
    monkeypatch.setattr(
        seqrec, "attention_layout",
        lambda *sizes, window=None, **named: "heads"
        if window else layout(*sizes, window=window, **named))
    mixed = step()
    assert mixed["attention_pallas"] and "attention_rows" not in mixed
    assert set(calls) == {(100, 3), ("rows", None, (2, 1), (96, 32))}, calls
    del calls[:]
    monkeypatch.setattr(attention_pallas, "layout", lambda dk, dv: "heads")
    heads = step()
    assert heads["attention_pallas"] and "attention_rows" not in heads
    assert set(calls) == {(100, 3), ("whole", 2)}, calls
    for kernels in (rows, mixed, heads):
        assert abs(float(kernels["loss"]) - float(scan["loss"])) \
            < 2e-3 * float(scan["loss"])
        assert set(kernels["grad_norm"]) == set(scan["grad_norm"])
        for group, norm in scan["grad_norm"].items():
            assert abs(float(kernels["grad_norm"][group]) - float(norm)) \
                < 3e-2 * float(norm), group


# -- the share ------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """An expert layer's eight expert-parallel shares: each chip routes
    over all 16 experts and computes its own two's part; those parts and
    the shared expert ONCE are what the uncut reference gives."""
    p, layer, x, _ = layer_case("swa")
    spec = ref_spec(p)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([ref.expert_layer(layer, row, spec)[0]
                           for row in x])
        shared = jnp.stack([ref.swiglu(layer["shared"], row, spec)
                            for row in x])
        routed = 0.0
        for rank in range(8):
            held = dataclasses.replace(
                p, held_experts=(2 * rank, 2 * rank + 2))
            mine = dict(layer, experts=jax.tree.map(
                lambda w: w[2 * rank:2 * rank + 2], layer["experts"]))
            y, stats = seqrec._moe(mine, x, held)
            assert int(stats["dropped"]) == 0
            assert int(stats["held_tokens"].sum()) == int(
                stats["load"][2 * rank:2 * rank + 2].sum())
            # a chip's own result holds the shared expert whole
            routed = routed + (y - shared)
    assert rel(routed + shared, whole) < 1e-5
    assert float(jnp.abs(routed).max()) > 1e-2


# -- the spec -----------------------------------------------------------------

@pytest.mark.parametrize("over, message", [
    (dict(swa=dict(SWA, window=0)), "window >= 1"),
    (dict(swa=dict(SWA, heads=7)), "swa needs .* divisor of its heads: 8, 2, 7"),
    (dict(swa=dict(SWA, rotary_dim=5)), "rotary_dim 5 is no even part"),
    (dict(swa=dict(SWA, rotary_dim=16)),
     "rotary_dim 16 is no even part of head_dim 8"),
    (dict(swa=None), "window >= 1"),
    (dict(attention_gate="row"), "attention_gate 'row'"),
    (dict(n_heads=5), "gqa needs .* divisor of n_heads: 8, 2, 5"),
    (dict(positions="learned"), "positions 'learned' does not go with"),
    (dict(tensor_ways=2), "hold\\s+no share of their own"),
])
def test_check_refuses_what_the_new_fields_cannot_mean(over, message):
    with pytest.raises(ValueError, match=message):
        small_spec(**over).check()


def test_the_spec_by_layer_and_its_key():
    p = small_spec()
    p.check()
    assert p.mixer_kinds() == ("gqa", "swa", "swa", "swa", "gqa")
    assert [ffn for _, ffn in p.layer_kinds()] == ["swiglu"] + ["moe"] * 4
    full, band = p.held_kind("gqa"), p.held_kind("swa")
    assert (full.heads, full.window, full.rotary_dim, full.theta) \
        == (4, None, 4, 500000.0)
    assert full.scaling == YarnScaling(**YARN) and full.gate == "head"
    assert (band.heads, band.kv_heads, band.head_dim, band.window,
            band.rotary_dim, band.theta, band.scaling, band.gate) \
        == (8, 2, 8, 7, 8, 10000.0, None, "head")
    assert band.scope == "seqrec_window_attention" \
        and full.scope == "seqrec_attention"
    assert "seqrec_window_attention" in seqrec.STEP_SCOPES
    assert seqrec.KINDS["swa"].family == "attention"
    # the key is hashable and tells the records apart
    keys = {p.spec_key(), small_spec(swa=dict(SWA, window=8)).spec_key(),
            small_spec(rope_scaling=None).spec_key(),
            small_spec(attention_gate=True).spec_key()}
    assert len(keys) == 4
    # a "seq" mesh takes no band
    assert not seqrec.KINDS["swa"].ring
    # the older kinds' defaults are what they were
    old = seqrec.SeqRecParams(mixer="gqa", norm="rms", positions="rope",
                              n_kv_heads=1, head_dim=8, rotary_dim=4)
    kind = old.held_kind("gqa")
    assert (kind.gate, kind.scaling, kind.window, kind.theta) \
        == (True, None, None, 10000.0)


def test_a_train_counts_the_band_and_serves(tmp_path):
    """The mixer counter's new label, the band's pair counters, the
    attention route's counter over both kinds; `recommend_next` runs the
    windowed layers too."""
    counted = cases.counted
    series = [("pio_train_seqrec_mixer_tokens_total", {"mixer": "swa"}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "gqa"}),
              ("pio_train_seqrec_attention_tokens_total", {"impl": "xla"}),
              ("pio_train_seqrec_window_band_pairs_total", {}),
              ("pio_train_seqrec_window_block_pairs_total", {})]
    before = [counted(name, **labels) for name, labels in series]
    p = small_spec(epochs=1, batch_size=2, device_init=True)
    sessions = cases.sessions(4)
    model = seqrec.train_seqrec(None, sessions, p)
    record = model.record
    assert len(record["loss"]) == 2
    assert np.asarray(record["expert_update_norm"]).shape == (2, 4, 16)
    assert "layer1.window_attention" in record["grad_norm"][0]
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    positions = 2 * 2 * L
    # a window of 7 over 24 positions: 28 + 17 x 7 pairs inside; blocks of
    # 8 visit (0,0), (1,0), (1,1), (2,1), (2,2): the pair (2, 0) is out
    assert gained == [3 * positions, 2 * positions, positions,
                      4 * 3 * (28 + 17 * 7), 4 * 3 * 5 * 64]
    top = model.recommend_next(sessions[0][:10], 5)
    assert len(top) == 5 and all(np.isfinite(score) for _, score in top)
