"""The sequence model on PACKED rows -- several whole sessions a row, a
query seeing the keys of its own session alone, positions that restart
with it -- under a period of sliding-window layers and a full one over
grouped query heads with softmax-routed experts at an expert share,
against the plain reference the benchmark brings
(benchmarks/checks/seqrec_packed_reference.py: every session ALONE, it
never packs), on seeded random weights at a small size; `pack_sessions`'
properties; a session's boundary in the scan and in the interpreted
kernels against a dense masked softmax, the three states of a block
pair, an edge case that reads whole numbers off; the share tied to the
model; the counters. (What the program already ran: the one table of pins
in tests/test_seqrec_kinds.py.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import seqrec_cases as cases
from seqrec_cases import (  # noqa: F401 (the fixtures: model, small_blocks)
    VOCAB, model, rel, small_blocks,
)

from benchmarks.checks import seqrec_packed_step as check
from benchmarks.events import sessions_packed
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import attention, attention_pallas

#: the record (tests/seqrec_cases.py): a row of 48 takes six attention
#: blocks, a step's 96 tokens eight token blocks
CASE = cases.CASES["packed"]
ref, L = CASE.ref, CASE.length
small_spec, weights, ref_spec = CASE.small_spec, CASE.weights, CASE.ref_spec
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "configs")

LENGTHS = (2, 3, 5, 9, 17, 30, 12, 40, 7, 3, 25, 2, 11)


def sessions_of(lengths=LENGTHS, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def alone(sessions, max_len=L):
    """[(inputs, targets)] of each session by itself, as the reference
    takes them."""
    return [(np.asarray(s[-(max_len + 1):][:-1], np.int32),
             np.asarray(s[-(max_len + 1):][1:], np.int32))
            for s in sessions if len(s) > 1]


def arrays(packed, rows=slice(None)):
    return tuple(jnp.asarray(t[rows]) for t in (
        packed.inputs, packed.targets, packed.ids, packed.positions))


# -- pack_sessions ---------------------------------------------------------

PACKS = [("the-tests", LENGTHS, L), ("one-long", (200, 3, 4), 32),
         ("all-fit-one-row", (2, 2, 3), 16), ("a-single-item", (1, 5, 1), 8),
         ("exact-fill", (9, 9, 17, 17), 16)]


@pytest.mark.parametrize("name,lengths,max_len", PACKS)
def test_a_packed_row_holds_whole_sessions(name, lengths, max_len):
    """Every session of two items or more whole in one row, a longer one
    as its last max_len + 1 items; no row over max_len; ids from 1 rising
    by one along a row and 0 its tail; positions from 0 in each session;
    inputs and targets each session's own shift."""
    sessions = sessions_of(lengths)
    packed = seqrec.pack_sessions(sessions, max_len)
    placed = sorted(i for row in packed.sessions for i in row)
    assert placed == [i for i, s in enumerate(sessions) if len(s) > 1]
    assert packed.inputs.shape[1] == max_len
    for row, members in enumerate(packed.sessions):
        at = 0
        for n, i in enumerate(members):
            s = sessions[i][-(max_len + 1):]
            span = slice(at, at + len(s) - 1)
            assert packed.inputs[row, span].tolist() == s[:-1]
            assert packed.targets[row, span].tolist() == s[1:]
            assert set(packed.ids[row, span].tolist()) == {n + 1}
            assert packed.positions[row, span].tolist() == list(
                range(len(s) - 1))
            at = span.stop
        assert at <= max_len
        for t in (packed.inputs, packed.targets, packed.ids,
                  packed.positions):
            assert not t[row, at:].any()


def test_packing_is_first_fit_over_decreasing_lengths_and_deterministic():
    sessions = sessions_of()
    packed = seqrec.pack_sessions(sessions, L)
    # positions 39, 29, 24, 16, 11, 10, 8, 6, 4, 2, 2, 1, 1 into rows of
    # 48: 39 + 8 + 1 | 29 + 16 + 2 + 1 | 24 + 11 + 10 + 2 | 6 + 4
    assert packed.sessions == ((7, 3, 0), (5, 4, 1, 11), (10, 6, 12, 9),
                               (8, 2))
    again = seqrec.pack_sessions(sessions, L)
    assert again.sessions == packed.sessions
    for name in ("inputs", "targets", "ids", "positions"):
        assert np.array_equal(getattr(packed, name), getattr(again, name))
    # the check's own few lines state the same rule
    spans = [len(s) - 1 for s in sessions]
    assert check.rows_of(spans, L) == [list(r) for r in packed.sessions]


def test_the_cells_sessions_pack_into_16_rows():
    """mellum2-a2.5b-ep4.train: 672 sessions of 126,648 events, the same
    multiset of lengths on every seed, 125,976 positions into 16 rows of
    8,192 at 96.1%, 3 to 209 sessions a row; rows 3 and 10 are the first
    step's (the configuration's seed), full both."""
    with open(os.path.join(CONFIGS,
                           "seqrec-mellum2-12b-a2.5b-ep4.json")) as f:
        cfg = json.load(f)
    lengths = sessions_packed.lengths(cfg)
    assert (len(lengths), int(lengths.sum())) == (672, 126_648)
    assert (int(np.median(lengths)), int(lengths.max()),
            int((lengths > 1024).sum()), int((lengths == 4096).sum())) \
        == (64, 4096, 22, 2)
    packed = seqrec.pack_sessions([[1] * n for n in lengths], 8192)
    filled = (packed.ids > 0).sum(axis=1)
    assert packed.inputs.shape == (16, 8192) and filled.sum() == 125_976
    per_row = [len(r) for r in packed.sessions]
    assert (min(per_row), max(per_row)) == (3, 209)
    first = check.epoch0_rows(cfg["algorithm_params"], 16)[:2].tolist()
    assert first == [3, 10] and filled[first].tolist() == [8192, 8192]
    assert [len(packed.sessions[r]) for r in first] == [6, 29]


# -- the boundary ------------------------------------------------------------

def row_ids(lengths, l):
    ids, positions, at = np.zeros(l, np.int32), np.zeros(l, np.int32), 0
    for n, size in enumerate(lengths):
        ids[at:at + size], positions[at:at + size] = n + 1, np.arange(size)
        at += size
    return ids, positions


def dense_sessions(q, k, v, ids, window):
    """Causal softmax attention inside sessions by the dense [L, L]
    mask: q [B, L, H, D], k, v [B, L, Hkv, D], ids [B, L]."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    t = np.arange(q.shape[1])
    ok = (ids[:, :, None] == ids[:, None, :]) & (ids[:, None, :] > 0) \
        & (t[None, :, None] >= t[None, None, :])
    if window:
        ok &= t[None, None, :] > t[None, :, None] - window
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(ok[:, None], s, -1e30), -1) * ok[:, None]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


BOUNDARIES = [
    ("sessions-across-blocks", 256, (130, 128 - 2), None),
    ("many-in-a-block", 256, (3, 2, 60, 1, 1, 100, 40), None),
    ("a-tail-of-padding", 384, (100, 28, 129), None),
    ("under-a-band", 256, (130, 100, 20), 40),
    ("a-band-wider-than-a-session", 384, (30, 200, 129, 25), 150)]


def boundary_case(l, lengths, seed=0, heads=4, kv_heads=2, width=64):
    rng = np.random.default_rng(seed)
    draw = lambda h: jnp.asarray(rng.normal(size=(2, l, h, width)),
                                 jnp.float32)
    other = tuple(reversed(lengths))
    ids = np.stack([row_ids(lengths, l)[0], row_ids(other, l)[0]])
    return draw(heads), draw(kv_heads), draw(kv_heads), ids


@pytest.mark.parametrize("name,l,lengths,window", BOUNDARIES)
def test_the_scans_sessions_are_a_dense_masked_softmax(name, l, lengths,
                                                       window):
    q, k, v, ids = boundary_case(l, lengths)
    with jax.default_matmul_precision("highest"):
        run = lambda q, k, v: attention.blockwise_attention(
            q, k, v, block_k=64, causal=True, key_mask=jnp.asarray(ids),
            window=window, packed=True)
        out, want = run(q, k, v), dense_sessions(q, k, v, ids, window)
        assert rel(out, want) < 1e-5
        w = jnp.asarray(np.random.default_rng(1).normal(size=out.shape),
                        jnp.float32)
        got = jax.grad(lambda *a: (run(*a) * w).sum(), (0, 1, 2))(q, k, v)
        wanted = jax.grad(lambda *a: (dense_sessions(*a, ids, window)
                                      * w).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, wanted):
        assert rel(a, b) < 1e-5


@pytest.mark.parametrize("entry", ["head-first", "token-first"])
@pytest.mark.parametrize("name,l,lengths,window", BOUNDARIES)
def test_the_kernels_sessions_are_a_dense_masked_softmax(
        monkeypatch, name, l, lengths, window, entry):
    """The interpreted kernels in blocks of 128 (two or three a row), the
    entry that takes heads apart and the one that takes the projections'
    columns (no rotary table here: the boundary alone), forward and
    backward, to the kernels' bfloat16 operands."""
    monkeypatch.setattr(attention_pallas, "BLOCK", 128)
    monkeypatch.setattr(attention_pallas, "WINDOW_BLOCK", 128)
    q, k, v, ids = boundary_case(l, lengths)
    b, _, h, d = q.shape
    flat = lambda t: t.reshape(b, l, -1)
    if entry == "head-first":
        run = lambda q, k, v: jnp.swapaxes(
            attention_pallas.packed_attention_pallas(
                *(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                jnp.asarray(ids), window, True), 1, 2)
    else:
        run = lambda q, k, v: attention_pallas.grouped_attention_pallas(
            flat(q), flat(k), flat(v), None, jnp.asarray(ids), (),
            (h, k.shape[2]), (), window, True, None, True).reshape(q.shape)
    rounded = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    want = lambda q, k, v: dense_sessions(rounded(q), rounded(k),
                                          rounded(v), ids, window)
    assert rel(run(q, k, v), want(q, k, v)) < 1e-2
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                    jnp.float32)
    got = jax.grad(lambda *a: (run(*a) * w).sum(), (0, 1, 2))(q, k, v)
    wanted = jax.grad(lambda *a: (want(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for a, c in zip(got, wanted):
        assert rel(a, c) < 2e-2


@pytest.mark.parametrize("lengths,l,block", [
    ((130, 126), 256, 64), ((3, 2, 60, 1, 1, 100, 40), 256, 32),
    ((100, 28, 129), 384, 128), ((64, 64, 64), 256, 64), ((), 128, 64)])
def test_a_block_pairs_three_states_against_the_dense_mask(lengths, l,
                                                           block):
    """`session_pair` from the blocks' first and last ids: a pair DOES
    products iff some query and some key of it are of one session (a
    causal pair: the table's), and needs NO mask iff every query and
    every key of it are of one and the same."""
    ids = row_ids(lengths, l)[0]
    first, last = attention_pallas.session_blocks(ids[None], block)
    same = (ids[:, None] == ids[None, :]) & (ids[None, :] > 0)
    n = l // block
    states = set()
    for i in range(n):
        for j in range(i + 1):
            does, one = attention_pallas.session_pair(
                first[0, i], last[0, i], first[0, j], last[0, j])
            inside = same[i * block:(i + 1) * block,
                          j * block:(j + 1) * block]
            assert bool(does) == bool(inside.any()), (i, j)
            assert bool(does and one) == bool(inside.all()), (i, j)
            states.add((bool(does), bool(does and one)))
    if lengths == (64, 64, 64):
        assert states == {(True, True), (False, False)}
    if lengths == (130, 126):
        assert states == {(True, True), (True, False), (False, False)}


def test_session_pairs_counts_the_sessions_and_the_blocks_multiplied():
    """Two rows of 256 in blocks of 128 on the kernels' route: row one
    130 + 126 (the pair (1, 0) holds the first session's tail: all three
    pairs multiply), row two 128 + 128 (the pair (1, 0) shares no
    session: two); under a window of 40 the band's pairs alone count.
    The scan masks and multiplies its whole table."""
    ids = np.stack([row_ids((130, 126), 256)[0], row_ids((128, 128), 256)[0]])
    tri = lambda n: n * (n + 1) // 2
    v5e = attention_pallas.KINDS[0]
    attention_pallas.BLOCK, was = 128, attention_pallas.BLOCK
    try:
        assert attention.session_pairs(v5e, ids, 128, 128) == (
            tri(130) + tri(126) + 2 * tri(128), 5 * 128 * 128)
    finally:
        attention_pallas.BLOCK = was
    band = lambda n: tri(40) + (n - 40) * 40
    assert attention.session_pairs("cpu", ids, 128, 128, 40, block_k=64) == (
        band(130) + band(126) + 2 * band(128), 2 * 7 * 64 * 64)


def sink_case(l, lengths):
    """Scores that put a query's whole weight on the FIRST key of its own
    session, values that are the key's place in the row: the output IS
    the place where the query's session starts."""
    ids, positions = row_ids(lengths, l)
    starts = jnp.asarray((positions == 0) & (ids > 0), jnp.float32)
    q = jnp.zeros((1, l, 2, 64), jnp.float32).at[..., 0].set(16.0)
    k = jnp.zeros((1, l, 1, 64), jnp.float32).at[0, :, 0, 0].set(
        16.0 * starts)
    v = jnp.broadcast_to(jnp.arange(l, dtype=jnp.float32)[None, :, None,
                                                          None],
                         (1, l, 1, 64))
    want = np.repeat(np.cumsum([0] + list(lengths))[:-1], lengths)
    return q, k, v, ids, want


@pytest.mark.parametrize("route", ["scan", "kernels"])
def test_a_boundary_one_position_off_reads_whole_numbers_off(route,
                                                             monkeypatch):
    """The probe's edge case: under the ids it was made for a query's
    output is the place its session starts, to a thousandth; with every
    boundary one position late (a session's first position given to the
    session before) or early, the positions at the boundaries read
    another session's start, whole numbers off. What the check's rows
    weigh against a step's rounding, this tells apart outright, on either
    route."""
    lengths = (130, 70, 56)
    q, k, v, ids, want = sink_case(256, lengths)
    if route == "kernels":
        monkeypatch.setattr(attention_pallas, "BLOCK", 128)
        run = lambda ids: jnp.swapaxes(
            attention_pallas.packed_attention_pallas(
                *(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                jnp.asarray(ids)[None], None, True), 1, 2)
    else:
        run = lambda ids: attention.blockwise_attention(
            q, k, v, block_k=64, causal=True,
            key_mask=jnp.asarray(ids)[None], packed=True)
    err = lambda ids: np.abs(np.asarray(run(ids))[0, :, 0, 0] - want)
    assert err(ids).max() < 0.05
    late = np.concatenate([ids[:1], ids[:-1]])
    early = np.concatenate([ids[1:], ids[-1:]])
    # late: query 130 sees the start of the session before beside its own
    # (halfway between the two, 65 off); early: query 129 sees itself
    # alone (129 off)
    for off, where, by in ((late, 130, 65.0), (early, 129, 129.0)):
        read = err(off)
        assert abs(read[where] - by) < 0.05, (where, read[where])
        assert (read > 0.5).sum() >= 2


def test_rotary_tables_are_read_by_the_position_in_the_session():
    """A session packed behind 7,000 other positions turns by the SAME
    angles as the same session alone, bit for bit; by its place in the
    row it would not (8,192 x a frequency carries 5e-4 rad of float32
    rounding: rotary positions are relative in exact arithmetic alone).
    A restart is a shift of q and k alike, so no edge case reads it off
    by whole numbers; the tables are where it is held."""
    ids, positions = row_ids((7000, 1000), 8192)
    scaling = attention.YarnScaling(16.0, 8192, 32.0, 1.0,
                                    1.2772588722239782)
    for s in (None, scaling):
        alone_, _ = attention.rotary_tables(1000, 128, 500000.0, scaling=s)
        packed_, shifts = attention.rotary_tables(
            8192, 128, 500000.0, scaling=s,
            positions=jnp.asarray(positions)[None])
        in_row, _ = attention.rotary_tables(8192, 128, 500000.0, scaling=s)
        assert shifts == (64,)
        for mine, theirs, rows in zip(packed_, alone_, in_row):
            assert mine.shape == (1, 8192, 128)
            assert np.array_equal(mine[0, 7000:8000], theirs)
            assert np.array_equal(mine[0, :7000], rows[:7000])
            assert not np.array_equal(rows[7000:8000], theirs)


# -- the model against the reference -------------------------------------------

def reference_of(p, params, sessions, n_positions, **over):
    spec = ref_spec(p, **over)
    loss, grads, load = ref.loss_and_grads(
        params, alone(sessions, p.max_len), spec, n_positions)
    return loss, grads, load, spec


@pytest.mark.parametrize("lengths", [LENGTHS, (49, 30, 18), (60, 2, 2, 2)])
def test_loss_loads_and_every_gradient_match_the_reference(model, lengths):
    """The packed rows' loss, every gradient leaf and the routed counts
    against every session run ALONE and added up: no key of the session
    before, no target across a boundary, positions from 0, a band inside
    a session; a row's padding tail routed on both sides."""
    sessions = sessions_of(lengths)
    packed = seqrec.pack_sessions(sessions, L)
    (loss, (expert_layers, _, _)), grads = model.loss_and_grads(
        *arrays(packed))
    want_loss, want, load, _ = reference_of(model.p, model.params, sessions,
                                            packed.inputs.size)
    assert abs(float(loss) - want_loss) < CASE.loss_tol * want_loss
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        if getattr(path[-1], "key", None) not in CASE.no_gradient:
            assert rel(g, w) < CASE.grad_tol, jax.tree_util.keystr(path)
    assert np.array_equal(np.stack([s["load"] for s in expert_layers]), load)


def test_sessions_one_a_row_and_packed_give_the_same_loss_and_gradients(
        model):
    """The same sessions trained a row each (left-padded, as every other
    configuration trains them) and packed: one loss, one gradient."""
    sessions = sessions_of()
    (loss, _), grads = model.loss_and_grads(
        *arrays(seqrec.pack_sessions(sessions, L)))
    (want_loss, _), want = model.of(packing=False).loss_and_grads(
        *seqrec.pad_sessions(sessions, L))
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        assert rel(g, w) < 1e-4, jax.tree_util.keystr(path)


def _interpreted(monkeypatch, calls):
    """The attention entries as a v5e would route them, the kernels in
    the Pallas interpreter; `calls` takes what each entry was asked for."""
    monkeypatch.setattr(attention, "_device_kind",
                        lambda: attention_pallas.KINDS[0])
    grouped = attention_pallas.grouped_attention_pallas
    monkeypatch.setattr(
        attention_pallas, "grouped_attention_pallas",
        lambda q, k, v, gate, mask, tables, heads, shifts, window,
        operand_dtype=None, packed=False: calls.append(
            ("rows", window, heads, shifts, packed, tables[0].ndim))
        or grouped(q, k, v, gate, mask, tables, heads, shifts, window, True,
                   operand_dtype, packed))


@pytest.mark.parametrize("route", ["scan", "kernels"])
def test_a_train_steps_record_against_the_reference(route, monkeypatch):
    """`make_train_step` on two packed rows of 256 at heads of 128: its
    loss, gradient norms by group, first update by group and expert by
    expert and its loads against the reference's, on the scan at the
    highest precision and on the interpreted kernels token-first (told
    the sessions, their tables a row's own) to the kernels' bfloat16
    operands."""
    monkeypatch.undo()
    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 128)
    monkeypatch.setattr(attention_pallas, "BLOCK", 128)
    monkeypatch.setattr(attention_pallas, "WINDOW_BLOCK", 128)
    p = small_spec(d_model=128, n_heads=2, n_kv_heads=1, head_dim=128,
                   rotary_dim=128, max_len=256, n_layers=2,
                   mixer=("swa", "gqa"), learning_rate=1e-3,
                   swa=dict(heads=2, window=100, rope_theta=500000.0,
                            rotary_dim=128))
    params = weights(p)
    sessions = sessions_of((150, 90, 60, 40, 130, 20, 8, 3))
    packed = seqrec.pack_sessions(sessions, 256)
    assert packed.inputs.shape == (2, 256)
    optimizer = seqrec.make_optimizer(p)
    calls = []
    tight = route == "scan"
    if not tight:
        _interpreted(monkeypatch, calls)
    with jax.default_matmul_precision("highest" if tight else "default"):
        stats = jax.device_get(seqrec.make_train_step(None, p, optimizer)(
            jax.tree.map(jnp.copy, params), optimizer.init(params),
            *arrays(packed))[2])
    if not tight:
        assert stats["attention_pallas"] and stats["attention_rows"]
        assert set(calls) == {("rows", 100, (2, 1), (64,), True, 3),
                              ("rows", None, (2, 1), (64,), True, 3)}, calls
    loss, grads, load, spec = reference_of(p, params, sessions, 512)
    update, by_expert = ref.first_update_norms(params, grads, spec)
    g_tol, u_tol = (2e-4, 2e-3) if tight else (4e-2, 4e-2)
    assert abs(float(stats["loss"]) - loss) < g_tol * loss
    norms = ref.group_norms(grads)
    assert set(norms) == set(stats["grad_norm"])
    for group, norm in norms.items():
        assert abs(float(stats["grad_norm"][group]) - norm) < g_tol * norm, \
            group
        assert abs(float(stats["update_norm"][group]) - update[group]) \
            < u_tol * update[group], group
    if tight:
        assert np.array_equal(stats["load"], load)
        assert rel(stats["expert_update_norm"], by_expert) < u_tol
    assert int(stats["dropped"].sum()) == 0


# -- the share ------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """An expert layer's four expert-parallel shares: each chip routes
    over all 16 experts (a softmax over all of them, the chosen gates
    over their sum) and computes its own four's part; those parts are
    what the uncut reference gives: nothing is computed by all alike, no
    shared expert."""
    p = small_spec(held_experts=(0, 16))
    params = weights(p)
    layer = params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, L, 64)),
                    jnp.float32)
    spec = ref_spec(p)
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([ref.expert_layer(layer, row, spec,
                                            spec.knobs())[0] for row in x])
        routed = 0.0
        for rank in range(4):
            held = dataclasses.replace(
                p, held_experts=(4 * rank, 4 * rank + 4))
            mine = dict(layer, experts=jax.tree.map(
                lambda w: w[4 * rank:4 * rank + 4], layer["experts"]))
            y, stats = seqrec._moe(mine, x, held)
            assert int(stats["dropped"]) == 0
            assert int(stats["held_tokens"].sum()) == int(
                stats["load"][4 * rank:4 * rank + 4].sum())
            routed = routed + y
    assert rel(routed, whole) < 1e-5
    assert float(jnp.abs(routed).max()) > 1e-2


# -- the spec -----------------------------------------------------------------

@pytest.mark.parametrize("over, message", [
    (dict(mixer="mha", positions="learned"), "packing goes with the mixers"),
    (dict(mixer=("gqa", "gdn"), linear_key_heads=2, linear_value_heads=2,
          linear_key_head_dim=8, linear_value_head_dim=8,
          linear_conv_kernel=4), "not \\['gdn'\\]"),
    (dict(mixer=("swa", "conv"), conv_kernel=3), "not \\['conv'\\]"),
    (dict(mtp_layers=("gqa",), mtp_loss_weight=0.1),
     "packing does not go with mtp_layers"),
])
def test_check_refuses_what_packing_cannot_mean(over, message):
    with pytest.raises(ValueError, match=message):
        small_spec(**over).check()


def test_a_ring_and_packing_are_refused_together():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "seq"))
    with pytest.raises(ValueError, match='"seq" axis'):
        seqrec.train_seqrec(mesh, [["a", "b", "c"]], small_spec())


def test_packing_is_part_of_a_runs_identity_only_where_it_is_set():
    """`packing` is one of `LATER_FIELDS`: a spec that does not set it
    has the key it had (an older run's checkpoints stay its own), one
    that does has another."""
    whole = small_spec(packing=False)
    assert "packing" in seqrec.LATER_FIELDS
    assert "packing" not in dict(whole.spec_key())
    assert dict(small_spec().spec_key())["packing"] is True
    assert whole.spec_key() != small_spec().spec_key()


# -- a train --------------------------------------------------------------------

def test_a_packed_train_counts_its_rows_sessions_and_pairs():
    """`train_seqrec` under `packing`: batches of rows, the record's
    `rows` the rows and `sessions` the sessions of each, the pack span,
    the rows, sessions and padding counters, and by attention kind the
    pairs inside sessions and the pairs of the blocks the scan
    multiplied; `recommend_next` serves the model."""
    from predictionio_tpu.obs.registry import default_registry

    reg, counted = default_registry(), cases.counted

    def packs():
        spans = reg.get("pio_span_duration_seconds")
        return 0 if spans is None else sum(
            spans.count(**labels) for labels, _ in spans.samples()
            if labels.get("span") == "seqrec_pack")

    series = [("pio_train_seqrec_rows_total", {}),
              ("pio_train_seqrec_packed_sessions_total", {}),
              ("pio_train_seqrec_tokens_total", {}),
              ("pio_train_seqrec_pad_tokens_total", {}),
              ("pio_train_seqrec_mixer_tokens_total", {"mixer": "swa"}),
              ("pio_train_seqrec_packed_attention_pairs_total", {}),
              ("pio_train_seqrec_packed_attention_block_pairs_total", {}),
              ("pio_train_seqrec_packed_window_pairs_total", {}),
              ("pio_train_seqrec_packed_window_block_pairs_total", {}),
              ("pio_train_seqrec_window_band_pairs_total", {})]
    before = [counted(name, **labels) for name, labels in series]
    packs_before = packs()
    p = small_spec(epochs=1, batch_size=2, device_init=True)
    lengths = (40, 30, 25, 17, 12, 9, 7, 5, 3, 3, 2, 2, 45, 20)
    sessions = [[f"i{(3 * s + j * (1 + s % 2)) % 50:02d}" for j in range(n)]
                for s, n in enumerate(lengths)]
    from predictionio_tpu.obs import tracing

    with tracing.adopt("train"):
        model = seqrec.train_seqrec(None, sessions, p)
    record = model.record
    spans = [n - 1 for n in lengths]
    rows = check.rows_of(spans, L)
    assert len(rows) == 5 and len(record["loss"]) == 2      # 5 // 2 steps
    trained = [r for step in record["rows"] for r in step]
    assert sorted(trained) == sorted(set(trained)) and len(trained) == 4
    assert record["sessions"] == [[rows[r] for r in step]
                                  for step in record["rows"]]
    inside = [spans[i] for r in trained for i in rows[r]]
    real = sum(inside)
    tri = lambda n: n * (n + 1) // 2
    band = lambda n: tri(min(n, 7)) + (n - min(n, 7)) * min(n, 7)
    gained = [counted(name, **labels) - b
              for (name, labels), b in zip(series, before)]
    assert gained == [
        4, len(inside), real, 4 * L - real, 3 * 4 * L,
        sum(map(tri, inside)), 4 * 21 * 64,
        3 * sum(map(band, inside)), 3 * 4 * 11 * 64, 0]
    assert packs() == packs_before + 1
    top = model.recommend_next(sessions[0][:10], 5)
    assert len(top) == 5 and all(np.isfinite(score) for _, score in top)
