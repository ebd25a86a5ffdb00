"""The sequence model under a layer spec of single-sub-layer layers --
state-space (Mamba-2) layers, grouped-query attention without positions,
sigmoid-routed two-matrix experts in a latent beside a shared expert --
with a multi-token-prediction module and a tensor share, against the
plain reference the benchmark brings
(benchmarks/checks/seqrec_ssm_reference.py), on seeded random weights at
a small size; the chunked scan against the position-by-position
recurrence; the share tied to the model. (What the program already ran:
the one table of pins in tests/test_seqrec_kinds.py.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    VOCAB, batch, model, rel, small_blocks,
)

from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import moe, state_space

#: the record (tests/seqrec_cases.py): a session of 24 takes three
#: attention blocks and three chunks of the scan, a step's 48 tokens four
#: token blocks
CASE = cases.CASES["ssm"]
ref, L = CASE.ref, CASE.length
PERIOD, SSM = CASE.spec["sublayers"], CASE.spec["ssm"]
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


# -- the scan --------------------------------------------------------------

def scan_case(seed, b, l, h, p, g, n, pad):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, size=(b, l, h)), jnp.float32)
    # left padding: positions that neither decay nor write
    dt = dt.at[:, :pad].set(0.0)
    rate = jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    return draw(b, l, h, p), dt, rate, draw(b, l, g, n), draw(b, l, g, n), \
        draw(h)


@pytest.mark.parametrize("name,l,chunk,pad", [
    ("whole-chunks", 32, 8, 0), ("a-chunk-and-a-bit", 37, 16, 0),
    ("under-a-chunk", 50, 128, 0), ("left-padded", 40, 8, 11),
    ("left-padded-off-the-chunk", 29, 8, 5)])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(
        name, l, chunk, pad):
    args = scan_case(len(name), 2, l, 6, 4, 3, 8, pad)
    with jax.default_matmul_precision("highest"):
        got = state_space.scan(*args, chunk)
        want = state_space.recurrence(*args)
        loss = lambda fn: lambda *a: (jnp.sin(fn(*a)) * 0.5).sum()
        d_got = jax.grad(loss(lambda *a: state_space.scan(*a, chunk)),
                         range(6))(*args)
        d_want = jax.grad(loss(state_space.recurrence), range(6))(*args)
    assert got.shape == want.shape == (2, l, 6, 4)
    assert rel(got, want) < 1e-5
    for part, g, w in zip("x dt rate b c skip".split(), d_got, d_want):
        assert rel(g, w) < 1e-4, part


def test_the_state_is_zero_at_a_sessions_start_and_padding_writes_nothing():
    """A left-padded session's outputs are the unpadded session's: the
    padding in front neither decays a state nor writes one, whatever its
    inputs hold."""
    x, dt, rate, b, c, skip = scan_case(5, 1, 24, 4, 4, 2, 8, 0)
    pad = 9
    front = lambda t: jnp.concatenate([jnp.full_like(t[:, :pad], 3.0), t], 1)
    padded = state_space.scan(front(x), front(dt).at[:, :pad].set(0.0), rate,
                              front(b), front(c), skip, 8)
    assert rel(padded[:, pad:], state_space.scan(x, dt, rate, b, c, skip,
                                                 8)) < 1e-5
    # and two sessions of a batch do not see each other
    both = state_space.scan(*(jnp.concatenate([t, t[:, ::-1]]) if t.ndim > 1
                              else t for t in (x, dt, rate, b, c, skip)), 8)
    assert rel(both[:1], state_space.scan(x, dt, rate, b, c, skip, 8)) < 1e-6


# -- each new layer alone ----------------------------------------------------

def layer_case(kind, pad=4):
    p = small_spec()
    params = weights(p)
    layer = params["layers"][PERIOD.index(kind)]
    x = jnp.asarray(np.random.default_rng(8).normal(size=(2, L, 64)),
                    jnp.float32)
    key_mask = jnp.ones((2, L), bool).at[0, :pad].set(False)
    return p, layer, x, key_mask


def layer_fns(kind, p, key_mask):
    """(the program's layer function, the reference's), both (layer, x)
    -> [B, L, D]."""
    spec = ref_spec(p)
    if kind == "ssm":
        return (lambda w, x: p.state_space().apply(w, x, key_mask, p, None),
                lambda w, x: jnp.stack([ref.state_space(
                    w["ssm"], row, ok, spec) for row, ok in zip(x, key_mask)]))
    if kind == "gqa":
        return (lambda w, x: seqrec._attention(w, x, key_mask, p, "gqa", None,
                                               False),
                lambda w, x: jnp.stack([ref.attention(w, row, ok, spec)
                                        for row, ok in zip(x, key_mask)]))
    return (lambda w, x: seqrec._moe(w, x, p)[0],
            lambda w, x: jnp.stack([ref.expert_layer(w, row, spec)[0]
                                    for row in x]))


@pytest.mark.parametrize("kind", ["ssm", "gqa", "moe"])
def test_a_new_layer_alone_matches_the_reference_forward_and_backward(kind):
    p, layer, x, key_mask = layer_case(kind)
    program, reference = layer_fns(kind, p, key_mask)
    real = key_mask[..., None]
    loss = lambda fn: lambda w, x: (jnp.sin(jnp.where(real, fn(w, x), 0.0))
                                    ).sum()
    with jax.default_matmul_precision("highest"):
        (got, d_got), (want, d_want) = (
            jax.jit(lambda w, x: (fn(w, x), jax.grad(loss(fn), (0, 1))(w, x))
                    )(layer, x) for fn in (program, reference))
    assert rel(jnp.where(real, got, 0.0), jnp.where(real, want, 0.0)) < 1e-5
    want_leaves = dict(jax.tree_util.tree_leaves_with_path(d_want))
    for path, g in jax.tree_util.tree_leaves_with_path(d_got):
        if getattr(path[-1], "key", None) == "router_bias":
            continue                         # no gradient on either side
        assert rel(g, want_leaves[path]) < 2e-4, jax.tree_util.keystr(path)


def test_an_attention_layer_without_positions_has_no_norms_and_no_gate():
    p, layer, x, key_mask = layer_case("gqa", pad=0)
    assert set(layer) == {"ln1", "wq", "wk", "wv", "wo"}
    # no position enters: the last position's output is the same when the
    # positions before it change places
    out = seqrec._attention(layer, x, key_mask, p, "gqa", None, False)
    swapped = x.at[:, [2, 5]].set(x[:, [5, 2]])
    other = seqrec._attention(layer, swapped, key_mask, p, "gqa", None, False)
    assert rel(other[:, -1], out[:, -1]) < 1e-5
    assert rel(other[:, 3], out[:, 3]) > 1e-3


def test_two_matrix_experts_in_passes_and_their_dropped_count():
    """Twelve passes of 8 rows give what one pass gives, gradients too,
    and what a dense loop over the held experts gives."""
    p, layer, x, _ = layer_case("moe")
    flat = x.reshape(-1, 64) @ layer["latent"]["w_dn"]
    ex = layer["experts"]
    routing = moe.route(x.reshape(-1, 64), layer["router"], jnp.zeros(16).at[
        jnp.asarray([2, 3])].set(100.0), 3, 5.0)

    def out(pass_rows, rows, w_up):
        y, counts, dropped = moe.held_experts(
            rows, None, w_up[2:6], ex["w_down"][2:6], routing, 2, pass_rows)
        return jnp.sum(jnp.sin(y)), (y, counts, dropped)

    def dense(rows, w_up):
        y = jnp.zeros_like(rows)
        for e in range(2, 6):
            gate = jnp.where(routing.experts == e, routing.gates, 0.0).sum(-1)
            y = y + gate[:, None] * (jnp.square(jax.nn.relu(
                rows @ w_up[e])) @ ex["w_down"][e])
        return jnp.sum(jnp.sin(y))

    with jax.default_matmul_precision("highest"):
        (a, (_, counts, dropped)), da = jax.value_and_grad(
            lambda *w: out(8, *w), (0, 1), has_aux=True)(flat, ex["w_up"])
        (b, _), db = jax.value_and_grad(
            lambda *w: out(4 * L, *w), (0, 1), has_aux=True)(flat, ex["w_up"])
        c, dc = jax.value_and_grad(dense, (0, 1))(flat, ex["w_up"])
    assert int(counts.sum()) > 8 * 8 and int(dropped) == 0
    assert abs(float(a - b)) < 1e-4 and abs(float(a - c)) < 1e-4
    for g, w, v in zip(da, db, dc):
        assert rel(g, w) < 1e-5 and rel(g, v) < 1e-4


# -- the whole step ----------------------------------------------------------

@pytest.mark.parametrize("pad", [0, 5])
def test_loss_both_heads_and_every_gradient_match_the_reference(model, pad):
    loss, (expert_layers, mixers, rest), grads, _, want = \
        cases.loss_and_every_gradient_match_the_reference(model, pad)
    assert abs(float(rest["mtp_loss"]) - want["mtp_loss"]) \
        < 2e-6 * want["mtp_loss"]
    # the module's loss is in the loss a tenth, and is no copy of the main
    main = float(loss) - 0.1 * float(rest["mtp_loss"])
    assert abs(main - float(rest["mtp_loss"])) > 1e-3
    assert {"layer0.attention", "layer1.latent_projection",
            "layer2.state_space", "layer1.shared_expert", "mtp",
            "mtp0.attention", "mtp1.experts", "mtp1.norms"} <= set(
                seqrec._group_norms(grads))
    # the expert loads, the module's layer after the stack's two
    load = np.stack([np.asarray(s["load"]) for s in expert_layers])
    assert load.shape == (3, 16) and np.array_equal(load, want["load"])
    assert {k: int(v) for k, v in mixers.items()} == {"gqa": 2, "ssm": 2}


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


def test_a_train_steps_record_against_the_reference_and_the_int8_control(
        model):
    after, stats, rest, update_norms, by_expert = \
        cases.a_train_steps_record_against_the_reference_and_the_int8_control(
            model, ("state_space", "attention", "experts"))
    # the experts' update expert by expert, the module's layer last; over
    # a layer's held experts it is the group's
    assert by_expert.shape == (3, 16)
    assert np.allclose(np.sqrt((by_expert ** 2).sum(-1)), [
        update_norms[g] for g in ("layer1.experts", "layer3.experts",
                                  "mtp1.experts")], rtol=1e-6)
    assert np.array_equal(stats["load"], rest["load"])
    assert {k: int(v) for k, v in stats["layer_passes"].items()} \
        == {"first": 7, "repeat": 0}
    # the selection bias is no parameter of adamw's and its rate is 0
    assert not np.asarray(after["layers"][1]["router_bias"]).any()
    assert not np.asarray(after["mtp"]["layers"][1]["router_bias"]).any()


@pytest.mark.parametrize("control", [
    {"decay_one": True}, {"skip_left_out": True},
    {"norm_gate_left_out": True}, {"latent_as_slice": True},
    {"dropped_head": 0}, {"relu_plain": True}, {"mtp_loss_weight": 0.0},
    {"mtp_wrong_item": True}])
def test_every_fault_control_of_the_reference_moves_the_loss(model, control):
    sound = model.reference(4, rows=1)[0]
    broken = model.reference(4, rows=1, **control)[0]
    assert abs(broken - sound) > 1e-4 * sound


# -- the share tied to the model ----------------------------------------------

WAYS = 4


def share_of(layer, kind, rank, p):
    """What tensor rank `rank` of `WAYS` holds of an uncut layer's
    weights: its query heads with the key/value head they read, its
    state-space heads with their group, its columns of the shared
    expert; the norms, router and latent projections whole."""
    def cols(w, lo, hi, axis=-1):
        return jax.lax.slice_in_dim(w, lo, hi, axis=axis)

    if kind == "gqa":
        hq, hd = p.n_heads // WAYS, p.head_dim
        kv = rank * hq // (p.n_heads // p.n_kv_heads)
        return {**layer,
                "wq": cols(layer["wq"], rank * hq * hd, (rank + 1) * hq * hd),
                "wk": cols(layer["wk"], kv * hd, (kv + 1) * hd),
                "wv": cols(layer["wv"], kv * hd, (kv + 1) * hd),
                "wo": cols(layer["wo"], rank * hq * hd, (rank + 1) * hq * hd,
                           0)}
    if kind == "ssm":
        whole, held = seqrec.StateSpaceMixer(**p.ssm), \
            seqrec.StateSpaceMixer(**p.ssm).held(WAYS)
        (hp_all, gn_all), (hp, gn) = whole.widths(), held.widths()
        w = layer["ssm"]
        heads = (rank * held.heads, (rank + 1) * held.heads)
        # [z | x | B | C]: this rank's columns of each of the four
        pick = np.concatenate([
            np.arange(rank * hp, (rank + 1) * hp),
            hp_all + np.arange(rank * hp, (rank + 1) * hp),
            2 * hp_all + np.arange(rank * gn, (rank + 1) * gn),
            2 * hp_all + gn_all + np.arange(rank * gn, (rank + 1) * gn)])
        return {**layer, "ssm": {
            "w_in": w["w_in"][:, pick], "w_dt": cols(w["w_dt"], *heads),
            "conv": w["conv"][:, pick[hp:] - hp_all],
            "conv_bias": w["conv_bias"][pick[hp:] - hp_all],
            "A_log": cols(w["A_log"], *heads),
            "dt_bias": cols(w["dt_bias"], *heads), "D": cols(w["D"], *heads),
            "norm": {"scale": cols(w["norm"]["scale"], rank * hp,
                                   (rank + 1) * hp)},
            "w_out": cols(w["w_out"], rank * hp, (rank + 1) * hp, 0)}}
    width = p.n_shared_experts * p.moe_width // WAYS
    return {**layer, "shared": {
        "w_up": cols(layer["shared"]["w_up"], rank * width,
                     (rank + 1) * width),
        "w_down": cols(layer["shared"]["w_down"], rank * width,
                       (rank + 1) * width, 0)}}


@pytest.mark.parametrize("kind", ["ssm", "gqa"])
def test_the_tensor_shares_of_a_mixer_add_up_to_the_uncut_reference(kind):
    """Every rank's held heads through its rows of the output projection
    is a partial sum: over all the ranks, the uncut layer."""
    p, layer, x, key_mask = layer_case(kind)
    held = dataclasses.replace(p, tensor_ways=WAYS)
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(layer_fns(kind, p, key_mask)[1])(layer, x)  # reference
        program = jax.jit(layer_fns(kind, held, key_mask)[0])
        parts = [program(share_of(layer, kind, rank, p), x)
                 for rank in range(WAYS)]
    real = key_mask[..., None]
    assert rel(jnp.where(real, sum(parts), 0.0),
               jnp.where(real, whole, 0.0)) < 1e-5
    assert rel(jnp.where(real, parts[0], 0.0),
               jnp.where(real, whole, 0.0)) > 0.1
    # and the weights a rank draws for itself have the shapes of its share
    drawn = seqrec.init_params(None, VOCAB - 1, dataclasses.replace(
        held, device_init=True))["layers"][PERIOD.index(kind)]
    assert jax.tree.map(jnp.shape, drawn) == jax.tree.map(
        jnp.shape, share_of(layer, kind, 1, p))


def test_the_expert_layers_shares_add_up_with_the_common_part_counted_once():
    """Four expert-parallel shares of the routed experts by four tensor
    shares of the shared expert: a chip computes router, W_dn and W_up
    alike, so the routed part adds up in the LATENT and goes through W_up
    once; the shared expert's column shares add up as they are."""
    p, layer, x, _ = layer_case("moe")
    spec = ref_spec(p)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([ref.expert_layer(layer, row, spec)[0]
                           for row in x])
        total = 0.0
        for rank in range(WAYS):
            held = dataclasses.replace(
                p, tensor_ways=WAYS,
                held_experts=(4 * rank, 4 * rank + 4))
            mine = share_of(layer, "moe", rank, p)
            mine["experts"] = jax.tree.map(lambda w: w[4 * rank:4 * rank + 4],
                                           layer["experts"])
            y, stats = seqrec._moe(mine, x, held)
            assert int(stats["dropped"]) == 0
            assert int(stats["held_tokens"].sum()) == int(
                stats["load"][4 * rank:4 * rank + 4].sum())
            total = total + y
    # (W_up is linear and has no bias, so the ranks' routed parts may as
    # well go through it one by one: nothing is in the sum twice)
    assert rel(total, whole) < 1e-5


# -- the spec ---------------------------------------------------------------

@pytest.mark.parametrize("over, message", [
    (dict(sublayers=("gqa", "mlp")), "unknown sublayers"),
    (dict(mtp_layers=("gqa", "rnn")), "unknown mtp_layers"),
    (dict(ssm=None), "ssm needs"),
    (dict(ssm={**SSM, "groups": 3}), "ssm needs"),
    (dict(ssm={**SSM, "state": 0}), "ssm needs"),
    (dict(positions="learned"), "does not go with"),
    (dict(norm="layer"), "does not go with"),
    (dict(sublayers=("mha", "moe"), n_heads=2), "positions 'none'"),
    (dict(tensor_ways=0), "must be >= 1"),
    (dict(tensor_ways=3), "do not divide"),
    (dict(tensor_ways=8), "ssm record's heads or groups"),
    (dict(tensor_ways=2, sublayers=("gqa", "swiglu")),
     "hold no share"),
    (dict(expert_act="gelu"), "unknown expert_act"),
    (dict(moe_latent_size=-1), "must be >= 0"),
    (dict(mtp_loss_weight=-0.1), "must be >= 0"),
    (dict(n_loops=2), "mtp_layers does not go with"),
    (dict(positions="rope", rotary_dim=3), "rotary_dim"),
    (dict(held_experts=(0, 17)), "held_experts"),
])
def test_check_refuses_what_the_new_fields_cannot_mean(over, message):
    with pytest.raises(ValueError, match=message):
        small_spec(**over).check()


def test_the_spec_by_layer_and_its_key(model):
    p = small_spec(n_layers=7)
    p.check()
    assert [p.mixer_kind(i) for i in range(7)] == [
        "gqa", None, "ssm", None, "ssm", "gqa", None]
    assert [p.ffn_kind(i) for i in range(7)] == [
        None, "moe", None, "moe", None, None, "moe"]
    assert p.mixer_kinds() == ("gqa", "ssm", "ssm", "gqa")
    assert p.layer_kinds()[-2:] == (("gqa", None), (None, "moe"))
    # the record of sizes is part of the key, and the key hashes
    other = small_spec(n_layers=7, ssm={**SSM, "chunk": 16})
    assert hash(p.spec_key()) != hash(other.spec_key())
    assert p.spec_key() == small_spec(n_layers=7).spec_key()
    # a layer of one sub-layer has one norm; a feed-forward's kind may
    # differ from layer to layer
    mixed_model = model.of(sublayers=("gqa", "swiglu", "ssm", "gelu", "moe"),
                           mtp_layers=(), ffn_width=48)
    mixed = mixed_model.p
    mixed.check()
    params = seqrec.init_params(None, VOCAB - 1, dataclasses.replace(
        mixed, device_init=True))
    layers = params["layers"]
    assert [sorted(k for k in layer if k.startswith("ln"))
            for layer in layers] == [["ln1"], ["ln2"], ["ln1"], ["ln2"],
                                     ["ln2"]]
    assert "w_gate" in layers[1] and "w1" in layers[3] \
        and "router" in layers[4]
    (loss, _), _ = mixed_model.loss_and_grads(*batch(), params=params)
    assert np.isfinite(float(loss))


def test_the_state_space_draws_are_the_familys():
    p = small_spec(device_init=True)
    w = seqrec.init_params(None, VOCAB - 1, p)["layers"][2]["ssm"]
    rate = np.exp(np.asarray(w["A_log"]))
    assert ((rate >= 1.0) & (rate <= 16.0)).all()
    step = np.log1p(np.exp(np.asarray(w["dt_bias"])))      # its softplus
    assert ((step >= 1e-4) & (step <= 0.1001)).all()
    assert np.array_equal(w["D"], np.ones(8)) and w["conv_bias"].shape == (
        64 + 2 * 64,)
    assert w["w_in"].shape == (64, 2 * 64 + 2 * 64) \
        and w["w_dt"].shape == (64, 8)


# -- through the train, the counters and serving ------------------------------

SESSIONS = [[f"i{(7 * s + 3 * j) % 53}" for j in range(25)] for s in range(4)]


def counter(name, **labels):
    from predictionio_tpu.obs.registry import default_registry

    for metric in default_registry().collect():
        if metric.name == name:
            return sum(v for lab, v in metric.samples()
                       if all(lab.get(k) == w for k, w in labels.items()))
    return 0.0


def test_a_train_records_the_module_counts_its_layers_and_serves_from_the_main_head():
    p = small_spec(epochs=2, batch_size=2, learning_rate=3e-3)
    before = {key: counter("pio_train_seqrec_mixer_tokens_total", mixer=key)
              for key in ("ssm", "gqa")}
    passes = counter("pio_train_seqrec_layer_pass_tokens_total",
                     **{"pass": "first"})
    model = seqrec.train_seqrec(None, SESSIONS, p)
    record = model.record
    assert len(record["mtp_loss"]) == len(record["loss"]) == 4
    assert record["loss"][-1] < record["loss"][0]
    assert np.asarray(record["load"]).shape == (4, 3, 16)
    positions = 4 * 2 * L
    assert counter("pio_train_seqrec_mixer_tokens_total", mixer="ssm") \
        - before["ssm"] == 2 * positions
    assert counter("pio_train_seqrec_mixer_tokens_total", mixer="gqa") \
        - before["gqa"] == 2 * positions            # the module's is one
    assert counter("pio_train_seqrec_layer_pass_tokens_total",
                   **{"pass": "first"}) - passes == 7 * positions
    assert counter("pio_train_seqrec_mtp_loss") == pytest.approx(
        record["mtp_loss"][-1])
    # serving reads the stack and the main head; the module is not run
    top = model.recommend_next(SESSIONS[0][:10], 5)
    assert len(top) == 5 and all(np.isfinite(score) for _, score in top)
    without = dataclasses.replace(model, params={
        k: v for k, v in model.params.items() if k != "mtp"})
    assert without.recommend_next(SESSIONS[0][:10], 5) == top
