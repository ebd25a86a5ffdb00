"""The sequence model as a looped decoder: a stack of sandwich-normed
multi-head-attention layers run `n_loops` times over the same weights,
the last norm after every pass, an exit gate after each pass and the
loss an expectation over the passes' exits, against the plain reference
the benchmark brings (benchmarks/checks/seqrec_looped_reference.py), on
seeded random weights at a small size. Only `mha` + `swiglu` is held to
a reference here; that the loop runs with the other mixers and with
expert layers is held by a step's shapes and counts alone."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    VOCAB, batch, model, rel, small_blocks,
)

from predictionio_tpu.models import seqrec
from predictionio_tpu.obs import profiler

#: the record (tests/seqrec_cases.py): a session of 24 takes three
#: attention blocks, a step's 48 tokens four token blocks and the loss's
#: 4 x 48 rows sixteen
CASE = cases.CASES["looped"]
ref, L = CASE.ref, CASE.length
R, N = CASE.spec["n_loops"], CASE.spec["n_layers"]
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("pad", [0, 5])
def test_loss_and_every_gradient_match_the_reference(model, pad, remat):
    """The shared test (among the orders of summation that differ here:
    the scan's sum of a weight's gradient over the passes), each pass's
    loss and share, and the groups of a looped stack."""
    _, (_, mixers, exits), grads, _, want = \
        cases.loss_and_every_gradient_match_the_reference(
            model if remat else model.of(remat=False), pad)
    np.testing.assert_allclose(exits["loop_loss"], want["loop_loss"],
                               rtol=2e-6)
    np.testing.assert_allclose(exits["exit_share"], want["exit_share"],
                               rtol=2e-6)
    assert float(exits["exit_share"].sum()) == pytest.approx(1.0, abs=1e-6)
    # every group of the record: the post norms with the norms, the gate
    assert set(seqrec._group_norms(grads)) == {
        "embedding", "head", "final_norm", "exit_gate",
        *(f"layer{i}.{part}" for i in range(N)
          for part in ("attention", "ffn", "norms"))}
    # a layer counts once a pass
    assert {k: int(v) for k, v in mixers.items()} == {"mha": R * N}


def test_every_passes_logits_match_the_reference(model):
    p, params = model.p, model.params
    seqs, _ = batch(seed=5, rows=1, pad=3)
    passes = model.passes(seqs)
    with jax.default_matmul_precision("highest"):
        states = ref.pass_states(params, seqs[0], ref_spec(p))
        assert passes.shape == (R, 1, L, 64) and len(states) == R
        for r in range(R):
            logits = passes[r, 0] @ seqrec.head_matrix(params)
            assert logits.shape == (L, VOCAB)
            assert rel(logits, states[r] @ params["head"]) < 1e-5, r
    # `forward`, what serving reads, is the last pass
    np.testing.assert_array_equal(model.forward(seqs), passes[-1])
    assert not np.asarray(passes[:, 0, :3]).any()      # padding reads 0


def test_a_left_padded_session_is_the_unpadded_one(model):
    cases.a_left_padded_session_is_the_unpadded_one(model)


def test_shared_passes_are_an_unshared_stack_of_copies(model):
    """R passes of N shared layers = an unshared model of R x N layers
    holding the same weights R times, and a shared weight's gradient =
    the sum of its R copies': the reference as the unshared twin (pass r
    takes layers [r N, (r + 1) N) of a list of R x N)."""
    p, params = model.p, model.params
    seqs, targets = batch(seed=7)
    (loss, _), grads = model.loss_and_grads(seqs, targets)
    copies = {**params, "layers": [
        jax.tree.map(jnp.copy, layer) for _ in range(R)
        for layer in params["layers"]]}
    twin_loss, twin, _ = ref.loss_and_grads(copies, seqs, targets,
                                            ref_spec(p, unshared=True))
    assert len(twin["layers"]) == R * N
    assert abs(float(loss) - twin_loss) < 2e-6 * twin_loss
    for i in range(N):
        for name, got in grads["layers"][i].items():
            parts = [twin["layers"][r * N + i][name] for r in range(R)]
            want = jax.tree.map(lambda *t: sum(t), *parts)
            for g, w, first in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want),
                                   jax.tree.leaves(parts[0])):
                assert rel(g, w) < 2e-5, (i, name)
                # and no one copy's: the passes all send something
                assert rel(g, first) > 1e-2, (i, name)
    for name in ("emb", "head"):
        assert rel(grads[name], twin[name]) < 2e-5


def test_the_scanned_loop_is_the_unrolled_one(model, monkeypatch):
    """One `lax.scan` over the passes against the stack written out four
    times: the same operations a pass, but the compiler fuses them
    otherwise and the backward pass adds a weight's gradient up in
    another order (the scan's carry against a tree of sums). The stated
    bound: the loss to 1e-6 of itself, every gradient to 5e-6 of its
    largest entry, float32 roundings both."""
    p, params = model.p, model.params
    seqs, targets = batch(seed=8, pad=2)

    def loops():
        """`while` loops in the forward pass's compiled program."""
        return jax.jit(lambda w: seqrec._forward(
            w, jnp.asarray(seqs), dataclasses.replace(p, remat=False))[0]
        ).lower(params).compile().as_text().count(" while(")

    (loss, _), grads = model.loss_and_grads(seqs, targets)
    scanned = loops()
    monkeypatch.setattr(seqrec, "LOOP_UNROLL", True)
    (unrolled, _), unrolled_grads = cases.Model(CASE).loss_and_grads(
        seqs, targets)           # the same record, traced written out
    assert float(loss) == pytest.approx(float(unrolled), rel=1e-6)
    for (path, g), u in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(unrolled_grads)):
        assert rel(g, u) < 5e-6, jax.tree_util.keystr(path)
    # the scanned program holds the stack, and so the loops inside its
    # attention layers, once inside the loop of passes; the unrolled one
    # R times and no loop around them
    assert scanned > 1 and (scanned - 1) * R == loops()


def test_one_pass_without_post_norms_and_gate_is_todays_program():
    """`n_loops` 1, `post_norm` and `exit_gate` off: the fields' defaults.
    The weights are the draws of a spec that never heard of them, bit for
    bit, and the looped spec's are those plus norms of 1 and a gate of 0
    (no draw is spent on them); the step's program holds no loop of
    passes, no gate and no extra number."""
    assert (seqrec.SeqRecParams().n_loops, seqrec.SeqRecParams().post_norm,
            seqrec.SeqRecParams().exit_gate) == (1, False, False)
    plain = small_spec(n_loops=1, post_norm=False, exit_gate=False)
    looped = small_spec()
    a = seqrec.init_params(np.random.default_rng(0), 40, plain)
    b = seqrec.init_params(np.random.default_rng(0), 40, looped)
    assert sorted(a) == ["emb", "head", "layers", "ln_f"]
    assert sorted(set(b) - set(a)) == ["exit_gate"]
    assert sorted(set(b["layers"][0]) - set(a["layers"][0])) == ["post1",
                                                                 "post2"]
    for path, leaf in jax.tree_util.tree_leaves_with_path(a):
        assert np.array_equal(leaf, dict(
            jax.tree_util.tree_leaves_with_path(b))[path])
    assert all(np.asarray(layer[n]["scale"] == 1).all()
               for layer in b["layers"] for n in ("post1", "post2"))
    assert not np.asarray(b["exit_gate"]["w"]).any()
    assert b["exit_gate"]["b"].shape == () and float(b["exit_gate"]["b"]) == 0
    # the leaves are each their own: a donated step takes each once
    assert len({id(leaf) for leaf in jax.tree.leaves(b)}) == len(
        jax.tree.leaves(b))
    seqs, targets = batch(seed=1)
    passes, _, mixers, _ = seqrec._forward(a, jnp.asarray(seqs), plain)
    assert isinstance(passes, tuple) and len(passes) == 1
    assert mixers == {"mha": N}
    optimizer = seqrec.make_optimizer(plain)
    _, _, stats = seqrec.make_train_step(None, plain, optimizer)(
        jax.tree.map(jnp.copy, a), optimizer.init(a), jnp.asarray(seqs),
        jnp.asarray(targets))
    assert not {"layer_passes", "loop_loss", "exit_share"} & set(stats)
    # the fields are architecture: part of a run's identity
    assert plain.spec_key() != looped.spec_key()
    assert small_spec(exit_entropy_beta=0.1).spec_key() != looped.spec_key()
    assert plain.spec_key(memory=False) != looped.spec_key(memory=False)


def test_the_exit_distribution_by_hand():
    """Four passes, two positions: p_1 = s_1, p_2 = s_2 (1 - s_1), p_3 =
    s_3 (1 - s_1)(1 - s_2), p_4 = (1 - s_1)(1 - s_2)(1 - s_3); they sum to
    1 whatever the gates say, and the last pass's own gate is not read."""
    z = np.asarray([[0.3, -2.0], [-1.0, 4.0], [2.0, 0.5], [9.0, -9.0]])
    s = 1 / (1 + np.exp(-z))
    want = np.stack([s[0], s[1] * (1 - s[0]),
                     s[2] * (1 - s[0]) * (1 - s[1]),
                     (1 - s[0]) * (1 - s[1]) * (1 - s[2])])
    logp = seqrec.exit_distribution(jnp.asarray(z, jnp.float32))
    np.testing.assert_allclose(np.exp(logp), want, rtol=1e-6)
    np.testing.assert_allclose(np.exp(logp).sum(0), [1.0, 1.0], rtol=1e-6)
    np.testing.assert_allclose(
        np.exp(ref.exit_log_probabilities(jnp.asarray(z, jnp.float32))),
        want, rtol=5e-6)
    grad = jax.grad(lambda t: (jnp.exp(seqrec.exit_distribution(t))
                               * jnp.arange(8.0).reshape(4, 2)).sum())(
        jnp.asarray(z, jnp.float32))
    assert not np.asarray(grad[-1]).any() and np.asarray(grad[:-1]).all()
    # two passes: p = (s_1, 1 - s_1)
    np.testing.assert_allclose(
        np.exp(seqrec.exit_distribution(jnp.asarray(z[:2], jnp.float32))),
        [s[0], 1 - s[0]], rtol=1e-6)


def test_the_last_passes_gate_gets_no_gradient_and_the_entropy_counts(model):
    """The loss does not read lambda_R: with the last pass's state alone
    feeding a gate of its own, that gate's gradient is 0 while the other
    passes' is not. The entropy term is beta x sum p log p a target."""
    p, params = model.p, model.params
    seqs, targets = batch(seed=4)

    def loss_of(gates):
        """A gate a pass: `gates` [R, d] in place of the one column."""
        passes, *_ = seqrec._forward(params, jnp.asarray(seqs), p)
        z = (passes.reshape(R, -1, 64) * gates[:, None]).sum(-1)
        logp = seqrec.exit_distribution(z)
        return (jnp.exp(logp) * jnp.arange(1.0, R + 1)[:, None]).sum()

    by_pass = jax.jit(jax.grad(loss_of))(
        jnp.tile(params["exit_gate"]["w"], (R, 1)))
    assert not np.asarray(by_pass[-1]).any()
    assert all(np.asarray(by_pass[r]).any() for r in range(R - 1))
    (with_h, _), _ = model.loss_and_grads(seqs, targets)
    (without, _), _ = model.of(exit_entropy_beta=0.0).loss_and_grads(
        seqs, targets)
    ref_without, _, _ = model.reference(4, exit_entropy_beta=0.0)
    assert float(without) == pytest.approx(ref_without, rel=2e-6)
    # 0.05 x H(p), H between 0 and log 4
    assert 0 < float(without - with_h) < 0.05 * np.log(R)


def test_a_step_adds_what_the_references_adamw_adds(model):
    """The shared test, the gradient norms, each pass's loss and share
    and the layer passes the step reports."""
    _, stats, grads, passes, want = \
        cases.a_step_adds_what_the_references_adamw_adds(
            model, "layer1.attention")
    assert "exit_gate" in want
    for group, norm in ref.group_norms(grads).items():
        assert abs(float(stats["grad_norm"][group]) - norm) < 2e-4 * norm
    np.testing.assert_allclose(stats["loop_loss"], passes["loop_loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(stats["exit_share"], passes["exit_share"],
                               rtol=1e-5)
    assert {k: int(v) for k, v in stats["layer_passes"].items()} == \
        {"first": N, "repeat": (R - 1) * N}
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"mha": R * N}


@pytest.mark.parametrize("fault,apart", [
    ({"n_loops": R - 1}, "loss"),            # a pass left out
    ({"last_pass_only": True}, "wqkv"),      # the earlier passes' part dropped
    ({"post_norm": False}, "loss"),
    ({"exit_entropy_beta": 0.0}, "loss"),
])
def test_the_references_fault_controls_are_faults(model, fault, apart):
    """Each control of the benchmark's check moves what it should: the
    loss (a pass or the post norms or the entropy left out) or the shared
    weights' gradient with the loss unmoved (the gradient through the
    last pass only)."""
    loss, grads, _ = model.reference(6)
    bad_loss, bad, passes = model.reference(6, **fault)
    if apart == "loss":
        assert abs(bad_loss - loss) > 1e-3 * loss
    else:
        assert bad_loss == pytest.approx(loss, rel=1e-6)
        assert rel(bad["layers"][0][apart], grads["layers"][0][apart]) > 0.05
        # nothing reaches the table but through the passes before the last
        assert not np.asarray(bad["emb"]).any()
    assert len(passes["loop_loss"]) == fault.get("n_loops", R)


def test_recommend_next_scores_with_the_last_pass():
    p = small_spec(epochs=0, batch_size=2)
    sessions = [[f"i{(s + j) % 30:02d}" for j in range(L + 1)]
                for s in range(6)]                        # items i00..i29
    model = seqrec.train_seqrec(None, sessions, p)
    model.params = weights(p)        # norms off 1: the passes differ
    model.params = {**model.params, **{
        k: v[:31] if k == "emb" else v[:, :31] for k, v in
        model.params.items() if k in ("emb", "head")}}
    scores = dict(model.recommend_next(["i01", "i02"], 30))
    assert len(scores) == 28                  # 30 items, two seen
    seq = np.zeros((1, L), np.int32)
    seq[0, -2:] = [model.item_code("i01"), model.item_code("i02")]
    with jax.default_matmul_precision("highest"):
        states = ref.pass_states(model.params, seq[0], ref_spec(p))
    last = np.asarray(states[-1][-1] @ model.params["head"])
    first = np.asarray(states[0][-1] @ model.params["head"])
    assert scores["i07"] == pytest.approx(float(last[8]), rel=1e-4, abs=1e-5)
    assert abs(float(first[8]) - float(last[8])) > 1e-3


def test_a_train_counts_its_layer_passes_and_reports_each_pass():
    """`pio_train_seqrec_layer_pass_tokens_total{pass}`: positions of the
    trained batches times the layers the compiled step ran in its first
    pass and in its repeats; a step of one pass counts 0 repeats. The
    gauges hold the last step's loss and share a pass, the record every
    step's."""
    counted = cases.counted
    series = [("pio_train_seqrec_layer_pass_tokens_total", {"pass": "first"}),
              ("pio_train_seqrec_layer_pass_tokens_total",
               {"pass": "repeat"}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "mha"})]
    sessions = cases.sessions(4)
    positions = 2 * 2 * L
    before = [counted(name, **labels) for name, labels in series]
    model = seqrec.train_seqrec(None, sessions, small_spec(
        epochs=1, batch_size=2, device_init=True))
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    assert gained == [N * positions, (R - 1) * N * positions,
                      R * N * positions]
    record = model.record
    assert len(record["loss"]) == 2
    assert np.asarray(record["loop_loss"]).shape == (2, R)
    assert np.asarray(record["exit_share"]).shape == (2, R)
    np.testing.assert_allclose(np.asarray(record["exit_share"]).sum(-1), 1.0,
                               rtol=1e-5)
    # the gate starts at 0: every pass but the last leaves half of what came
    np.testing.assert_allclose(record["exit_share"][0],
                               [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    assert "exit_gate" in record["grad_norm"][0]
    assert "exit_gate" in record["update_norm"][0]
    for key, name in (("loop_loss", "pio_train_seqrec_loop_loss"),
                      ("exit_share", "pio_train_seqrec_exit_share")):
        for loop in range(R):
            assert counted(name, loop=str(loop)) == pytest.approx(
                record[key][-1][loop])
    # one pass: all its layers are first passes
    before = [counted(name, **labels) for name, labels in series]
    seqrec.train_seqrec(None, sessions, small_spec(
        epochs=1, batch_size=2, n_loops=1, exit_gate=False))
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    assert gained == [N * positions, 0, N * positions]


def test_a_looped_stack_of_other_mixers_and_experts_runs():
    """`n_loops` with the other mixers and with expert layers: not held
    to a reference; the balance numbers come once a pass and layer (the
    first pass's layers first), a selection bias moves by its layer's
    tokens over all passes, and the gradients are finite."""
    p = seqrec.SeqRecParams(
        d_model=32, n_heads=4, n_layers=3, n_loops=2, max_len=L, seed=5,
        mixer=("conv", "gqa", "gdn"), ffn="moe", first_dense_layers=1,
        ffn_width=48, norm="rms", positions="rope", n_kv_heads=2, head_dim=8,
        rotary_dim=8, conv_kernel=3, linear_key_heads=2,
        linear_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
        linear_conv_kernel=4, n_routed_experts=8, held_experts=(0, 8),
        experts_per_token=2, moe_width=16, bias_update_rate=0.01,
        post_norm=True, remat=True)
    params = seqrec.init_params(np.random.default_rng(0), VOCAB - 1, p)
    seqs, targets = batch(seed=3)
    optimizer = seqrec.make_optimizer(p)
    after, _, stats = seqrec.make_train_step(None, p, optimizer)(
        jax.tree.map(jnp.copy, params), optimizer.init(params),
        jnp.asarray(seqs), jnp.asarray(targets))
    load = np.asarray(stats["load"])
    assert load.shape == (2 * 2, 8)              # two expert layers, twice
    assert (load.sum(-1) == 2 * 2 * L).all()
    assert {k: int(v) for k, v in stats["mixer_layers"].items()} == \
        {"conv": 2, "gqa": 2, "gdn": 2}
    assert {k: int(v) for k, v in stats["layer_passes"].items()} == \
        {"first": 3, "repeat": 3}
    assert all(np.isfinite(float(v)) and float(v) > 0
               for v in stats["grad_norm"].values())
    from predictionio_tpu.ops import moe

    for n, layer in enumerate(after["layers"][1:]):
        want = moe.bias_update(jnp.zeros(8), jnp.asarray(load[n] + load[2 + n]),
                               0.01)
        np.testing.assert_allclose(layer["router_bias"], want, atol=1e-7)


def test_the_scope_table_of_a_scanned_step_names_the_bodys_instructions(
        model):
    """The stack lies in the body of the scan's `while`, its feed-forward
    and loss blocks in loops inside that: the table reaches them all, an
    instruction of the body stands once however often it runs, and the
    join adds an instruction's seconds, which a capture gives summed
    over its events, to its scope and skips the loops themselves."""
    params = model.params
    seqs, targets = batch(seed=1)
    text = model.train_step().lower(
        params, model.optimizer.init(params), jnp.asarray(seqs),
        jnp.asarray(targets)).compile().as_text()
    module, table = profiler.parse_scope_table(text, seqrec.STEP_SCOPES)
    assert module.startswith("jit_step")
    _, _, computations = profiler._computations(text)
    loops = [ins for lines in computations.values()
             for ins in profiler._instructions(lines)
             if ins.opcode == "while"]
    assert loops and all("c" in table[ins.key][1] for ins in loops)
    bodies = {run for ins in loops for run in ins.runs}
    inside = {ins.key: table[ins.key] for name in bodies
              for ins in profiler._instructions(computations[name])
              if ins.key in table}
    scopes = {row[0] for row in inside.values()}
    assert {"seqrec_attention", "seqrec_ffn", "seqrec_norm",
            "seqrec_head_loss"} <= scopes
    # forward and backward instructions of the body
    assert any("t" in row[1] for row in inside.values())
    assert any("t" not in row[1] for row in inside.values())
    named = [key for key, row in inside.items()
             if row[0] == "seqrec_attention" and "c" not in row[1]]
    seconds = {key: 4 * 0.001 for key in named}     # four events, summed
    seconds[loops[0].key] = 1.0                     # the loop: skipped
    by_scope = profiler.scope_seconds(
        seconds, [{"family": "seqrec_train_step", "instructions": table}])
    assert by_scope["seqrec_train_step"] == {
        "seqrec_attention": pytest.approx(0.004 * len(named))}


def test_the_model_trains_and_serves_from_an_engine_json(tmp_path):
    """The new keys of the layer spec: `n_loops`, `post_norm`,
    `exit_gate`, `exit_entropy_beta`."""
    _, trained = cases.the_model_trains_and_serves_from_an_engine_json(
        CASE, tmp_path, "Loop")
    assert (trained.hyper.n_loops, trained.hyper.post_norm,
            trained.hyper.exit_gate, trained.hyper.exit_entropy_beta) == \
        (R, True, True, 0.05)
    assert len(trained.record["loop_loss"][-1]) == R
    assert isinstance(trained.params["exit_gate"]["w"], np.ndarray)


@pytest.mark.parametrize("over,match", [
    ({"n_loops": 0}, "n_loops"),
    ({"n_loops": -2}, "n_loops"),
    ({"n_loops": 1}, "exit_gate"),
    ({"norm": "layer", "positions": "learned"}, "post_norm"),
])
def test_check_refuses_what_does_not_exist(over, match):
    with pytest.raises(ValueError, match=match):
        small_spec(**over).check()
    # and lets through what does: a loop without a gate, a gate without
    # its entropy term, post norms on one pass
    small_spec(exit_gate=False).check()
    small_spec(exit_entropy_beta=0.0).check()
    small_spec(n_loops=1, exit_gate=False).check()


def test_a_loop_without_a_gate_reads_the_last_pass_alone(model):
    """`n_loops` without `exit_gate`: one head over the last pass's
    state, no gate among the weights, no per-pass numbers."""
    plain = model.of(exit_gate=False, post_norm=False)
    p, params = plain.p, plain.params
    assert "exit_gate" not in params
    seqs, targets = batch(seed=9)
    (loss, (_, _, exits)), grads = plain.loss_and_grads(seqs, targets)
    assert exits == {}
    with jax.default_matmul_precision("highest"):
        last = ref.pass_states(params, seqs[0], ref_spec(p))[-1]
    hidden = plain.forward(seqs)
    assert rel(hidden[0], last) < 1e-5
    logits = hidden.reshape(-1, 64) @ params["head"]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               jnp.asarray(targets).reshape(-1, 1), 1)
    assert float(loss) == pytest.approx(float(nll.mean()), rel=1e-5)
    assert all(np.asarray(g).any() for g in jax.tree.leaves(grads))


def test_the_looped_step_under_a_mesh_is_the_step(model, mesh8):
    """The shared test; the post norms and the gate replicate, and the
    per-pass numbers too are the one-device step's."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                axis_names=("data", "model"))
    sharded, got, want = cases.the_step_under_a_mesh_is_the_step(model, mesh)
    assert sharded["layers"][0]["post1"]["scale"].sharding.spec == P()
    assert sharded["exit_gate"]["w"].sharding.spec == P()
    assert sharded["layers"][0]["wqkv"].sharding.spec == P(None, "model")
    np.testing.assert_allclose(got["loop_loss"], want["loop_loss"],
                               rtol=1e-5)


def _on_attention_kernels(monkeypatch):
    """`blockwise_attention`, `rotary_attention` and `grouped_attention`
    as a v5e would route them, the kernels in the Pallas interpreter,
    with blocks a session of 128 fills."""
    from predictionio_tpu.ops import attention, attention_pallas

    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 512)
    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    kernels = attention_pallas.flash_attention_pallas
    rotary = attention_pallas.rotary_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "flash_attention_pallas",
        lambda q, k, v, mask, causal: kernels(q, k, v, mask, causal, True))
    monkeypatch.setattr(
        attention_pallas, "rotary_attention_pallas",
        lambda qkv, mask, heads, theta, causal, grad_dtype=None: rotary(
            qkv, mask, heads, theta, causal, True, grad_dtype))
    banded = attention_pallas.window_attention_pallas
    grouped = attention_pallas.grouped_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "window_attention_pallas",
        lambda q, k, v, mask, window: banded(q, k, v, mask, window, True))
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda *a, operand_dtype=None, packed=False: grouped(
            *a, True, operand_dtype, packed))


def test_the_looped_step_is_one_step_on_both_layouts(monkeypatch):
    """The looped `mha` spec at heads a lane tile wide (2 x 128), the
    kernels interpreted: its loss, each pass's loss and every gradient
    group with the kernels reading q, k, v token-first (the widths'
    layout: nothing between `x @ wqkv` and the kernels is relaid) and
    head-first (`layout` patched: the split, `rope` on [B, L, H, D], the
    transposes). Both round the same operands to bfloat16 for the same
    products; the rotation's float32 round-off moves a few of them to
    the neighbouring bfloat16."""
    from predictionio_tpu.ops import attention, attention_pallas

    _on_attention_kernels(monkeypatch)
    p = small_spec(d_model=256, n_heads=2, n_layers=1, n_loops=2,
                   max_len=128, ffn_width=128)
    params = weights(p)
    rng = np.random.default_rng(5)
    s = rng.integers(1, VOCAB, size=(1, 129))
    s[:, :9] = 0
    seqs, targets = s[:, :-1].astype(np.int32), s[:, 1:].astype(np.int32)

    def run():
        routes, layouts = set(), set()
        with attention.routes_into(routes, layouts):
            (loss, (_, _, exits)), grads = jax.value_and_grad(
                seqrec._loss_fn, has_aux=True)(
                    params, jnp.asarray(seqs), jnp.asarray(targets), p)
        assert routes == {"pallas"}
        return layouts, loss, exits, grads

    layouts, loss, exits, grads = run()
    assert layouts == {"rows"}
    monkeypatch.setattr(attention_pallas, "layout", lambda dk, dv: "heads")
    layouts, want_loss, want_exits, want_grads = run()
    assert layouts == {"heads"}
    assert abs(float(loss) - float(want_loss)) < 2e-5 * float(want_loss)
    np.testing.assert_allclose(exits["loop_loss"], want_exits["loop_loss"],
                               rtol=2e-5)
    got, want = seqrec._group_norms(grads), seqrec._group_norms(want_grads)
    assert set(got) == set(want) and "layer0.attention" in want
    for group, norm in want.items():
        assert abs(float(got[group]) - float(norm)) < 2e-3 * float(norm), \
            group
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert rel(g, w) < 5e-3, jax.tree_util.keystr(path)


@pytest.mark.parametrize("ambient,grad_dtype", [
    (None, jnp.bfloat16), ("bfloat16", jnp.bfloat16), ("highest", None),
    ("float32", None)])
def test_the_projections_gradient_is_rounded_where_its_products_round_it(
        ambient, grad_dtype, monkeypatch):
    """The `mha` mixer asks the token-first route for a bfloat16 gradient
    of `x @ wqkv` only while that product, and so its two backward
    products, take their operands in one bfloat16 pass: the product
    carries no precision of its own (the default's) AND the default is
    the TPU's; under a higher default it asks for none, and the op's
    gradient stays float32."""
    from predictionio_tpu.ops import attention, attention_pallas

    _on_attention_kernels(monkeypatch)
    asked = []
    rotary = attention_pallas.rotary_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "rotary_attention_pallas",
        lambda *a, grad_dtype=None: asked.append(grad_dtype) or rotary(
            *a, grad_dtype=grad_dtype))
    p = small_spec(d_model=256, n_heads=2, n_layers=1, n_loops=1,
                   max_len=128, exit_gate=False)
    rng = np.random.default_rng(2)
    layer = {"wqkv": jnp.asarray(rng.normal(size=(256, 768)), jnp.float32),
             "wo": jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(1, 128, 256)), jnp.float32)
    mask = jnp.ones((1, 128), bool)

    def traced():
        return jax.make_jaxpr(lambda layer, x: seqrec._attention(
            layer, x, mask, p, "mha", None, False))(layer, x)

    if ambient is None:
        jaxpr = traced()
    else:
        with jax.default_matmul_precision(ambient):
            jaxpr = traced()
    assert asked == [grad_dtype]
    products = [e for e in jaxpr.jaxpr.eqns if e.primitive.name
                == "dot_general"]
    assert len(products) == 2       # x @ wqkv, att @ wo
    if ambient is None:     # (under a default, the product takes it)
        assert all(e.params["precision"] is None for e in products)


def _tiny_spec(config, **over):
    """A benchmark configuration's tiny section, one layer pattern of it
    at widths the attention kernels tile."""
    import os

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", f"{config}.json")) as f:
        params = json.load(f)["tiny"]["algorithm_params"]
    return seqrec.SeqRecParams(**{**params, "max_len": 128, "batch_size": 1,
                                  "epochs": 1, "remat": False,
                                  "device_init": False, **over})


@pytest.mark.parametrize("config,over,layout", [
    # 2 heads of 128: whole lane tiles, the kernels read a projection's
    # columns
    ("seqrec-ouro-2.6b-pp8",
     dict(d_model=256, n_heads=2, n_layers=1, n_loops=2), "rows"),
    # q/k 128 + 64 = 192, v 128 (the cell's widths): head-first
    ("seqrec-kimi-vl-a3b-ep8",
     dict(n_heads=1, n_layers=2, qk_nope_head_dim=128, qk_rope_head_dim=64,
          v_head_dim=128), "heads"),
    # 2 query heads on 1 key/value head of 64 (the cell's width)
    ("seqrec-lfm2-24b-a2b-ep8",
     dict(n_heads=2, n_kv_heads=1, head_dim=64, rotary_dim=64, n_layers=2,
          mixer=["conv", "gqa"]), "heads"),
    # a full and a sliding layer at heads of 128, no norm of their own:
    # both kinds on three products' columns
    ("seqrec-laguna-xs2-ep8",
     dict(n_heads=2, n_kv_heads=1, head_dim=128, rotary_dim=64, n_layers=2,
          mixer=["gqa", "swa"],
          swa=dict(heads=3, window=100, rope_theta=10000.0,
                   rotary_dim=128)), "rows"),
    # ... and the sliding ones alone at another width would not do: a
    # step says "rows" only where every layer heard it (the full layers
    # normed a head: head-first)
    ("seqrec-laguna-xs2-ep8",
     dict(n_heads=2, n_kv_heads=1, head_dim=128, rotary_dim=64, n_layers=2,
          mixer=["gqa", "swa"], qk_norm=True,
          swa=dict(heads=3, window=100, rope_theta=10000.0,
                   rotary_dim=128)), "heads"),
    # 2 heads of 128 on 1 with a norm of their own (`qk_norm`): head-first
    ("seqrec-qwen3-next-80b-a3b-ep16",
     dict(n_heads=2, n_kv_heads=1, head_dim=128, rotary_dim=64, n_layers=2,
          mixer=["gdn", "gqa"]), "heads"),
])
def test_a_train_counts_where_its_attention_kernels_read_a_head(
        config, over, layout, monkeypatch):
    """`pio_train_seqrec_attention_layout_tokens_total{layout}`: every
    position of a train on the kernels' route, under where its compiled
    step says the kernels read a head; `impl="pallas"` either way. The
    tiny Ouro, Kimi, LFM2, Laguna and Qwen specs at widths the kernels
    tile."""
    _on_attention_kernels(monkeypatch)
    p, counted = _tiny_spec(config, **over), cases.counted
    names = ("pio_train_seqrec_attention_layout_tokens_total",
             "pio_train_seqrec_attention_tokens_total")
    before = {(name, label): counted(name, **{key: label})
              for name, key, labels in (
                  (names[0], "layout", ("rows", "heads")),
                  (names[1], "impl", ("pallas", "xla")))
              for label in labels}
    sessions = [[f"i{(s + j) % 13}" for j in range(100 + s)]
                for s in range(2)]
    model = seqrec.train_seqrec(None, sessions, p)
    assert np.isfinite(model.record["loss"]).all()
    gained = {key: counted(key[0], **{
        "layout" if key[0] == names[0] else "impl": key[1]}) - value
        for key, value in before.items()}
    positions = 2 * 128         # two steps of one session, padding too
    other = "heads" if layout == "rows" else "rows"
    assert gained == {(names[0], layout): positions, (names[0], other): 0,
                      (names[1], "pallas"): positions, (names[1], "xla"): 0}
