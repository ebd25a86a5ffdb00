"""The expert layer's grouped products on the kernels' route
(ops/moe_pallas.py, interpreted on the CPU) against `lax.ragged_dot` at
float32-highest on operands rounded to bfloat16; the route and the tiles
as tables; what a step reports and `train_seqrec` counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import attention_pallas, moe, moe_pallas

V5E = attention_pallas.KINDS[0]
D, W = 128, 256


def on_kernels(monkeypatch):
    """`held_experts` as a v5e would route it, the kernels interpreted."""
    monkeypatch.setattr(moe, "_device_kind", lambda: V5E)
    for name in ("rows_by_matrix", "rows_by_matrix_t", "rows_t_by_rows"):
        kernel = getattr(moe_pallas, name)
        monkeypatch.setattr(
            moe_pallas, name,
            lambda a, b, plan, kernel=kernel: kernel(a, b, plan, True))


def rounded(a):
    return jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(
        jnp.float32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# name, rows, row tile, the groups' sizes: their sum may stop short of
# the rows, and what lies past it is NaN on the way in
PRODUCTS = [
    ("uneven", 256, 32, (70, 41, 5, 100, 40)),
    ("an-empty-group", 256, 32, (70, 0, 86, 0, 33)),
    ("groups-under-a-tile", 256, 64, (3, 1, 17, 9, 2)),
    ("one-group-has-it-all", 128, 16, (0, 0, 128, 0)),
    ("the-first-group-is-empty", 128, 8, (0, 64, 30, 7)),
    ("one-tile", 64, 64, (10, 20, 30)),
    ("nothing-routed", 64, 8, (0, 0, 0)),
]


@pytest.mark.parametrize("name,rows,tile,sizes", PRODUCTS)
def test_the_three_products_against_a_loop_over_groups(name, rows, tile,
                                                       sizes):
    """x W, dy W^T and x^T dy a group from the kernels, against numpy on
    the operands rounded to bfloat16; rows past the last group are NaN
    on the way in and reach no result."""
    rng = np.random.default_rng(len(name))
    groups, routed = len(sizes), sum(sizes)
    x = rng.standard_normal((rows, D)).astype(np.float32)
    dy = rng.standard_normal((rows, W)).astype(np.float32)
    x[routed:], dy[routed:] = np.nan, np.nan
    w = rng.standard_normal((groups, D, W)).astype(np.float32)
    xr, dyr, wr = (np.asarray(rounded(t), np.float64) for t in (x, dy, w))
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    spans = [slice(offsets[g], offsets[g + 1]) for g in range(groups)]
    plan = moe_pallas.schedule(jnp.asarray(sizes, jnp.int32), rows, tile)

    got = moe_pallas.rows_by_matrix(x, w, plan, True)
    assert got.dtype == jnp.float32 and got.shape == (rows, W)
    for g, span in enumerate(spans):
        np.testing.assert_allclose(got[span], xr[span] @ wr[g], atol=2e-4)
    got = moe_pallas.rows_by_matrix_t(dy, w, plan, True)
    assert got.dtype == jnp.float32 and got.shape == (rows, D)
    for g, span in enumerate(spans):
        np.testing.assert_allclose(got[span], dyr[span] @ wr[g].T, atol=2e-4)
    # bfloat16 rows are taken as they are
    for a, b in ((x, dy), (jnp.asarray(x, jnp.bfloat16),
                           jnp.asarray(dy, jnp.bfloat16))):
        got = moe_pallas.rows_t_by_rows(a, b, plan, True)
        assert got.dtype == jnp.float32 and got.shape == (groups, D, W)
        for g, span in enumerate(spans):
            np.testing.assert_allclose(got[g], xr[span].T @ dyr[span],
                                       atol=2e-4)


def _layer_case(seed, tokens, k, n_routed, held, heavy=None):
    """Tokens, a routing over `n_routed` experts of which [held) are held
    here (`heavy`: the expert most slots go to), the held experts'
    matrices and a cotangent."""
    rng = np.random.default_rng(seed)
    lo, hi = held
    x = rng.standard_normal((tokens, D)).astype(np.float32)
    odds = np.ones(n_routed)
    if heavy is not None:
        odds[heavy] = 3.0 * n_routed
    experts = np.stack([rng.choice(n_routed, k, replace=False,
                                   p=odds / odds.sum())
                        for _ in range(tokens)])
    # tokens routed to no held expert hold NaN: their rows are gathered
    # into the passes' tails and may reach nothing
    away = ~((experts >= lo) & (experts < hi)).any(-1)
    x[away] = np.nan
    gates = rng.uniform(0.2, 1.0, (tokens, k)).astype(np.float32)
    mats = [rng.standard_normal(shape).astype(np.float32) * scale
            for shape, scale in (((hi - lo, D, W), D ** -0.5),
                                 ((hi - lo, D, W), D ** -0.5),
                                 ((hi - lo, W, D), W ** -0.5))]
    cot = rng.standard_normal((tokens, D)).astype(np.float32)
    cot[away] = 0.0
    routing = moe.Routing(jnp.asarray(experts), jnp.asarray(gates), None)
    return jnp.asarray(x), routing, mats, jnp.asarray(cot), away


# name, tokens, k, routed experts, held range, pass_rows, heavy expert
LAYERS = [
    ("uneven-groups", 64, 2, 6, (1, 5), 128, None),
    ("an-empty-group", 48, 2, 8, (0, 8), 96, None),
    ("a-group-under-a-row-tile", 128, 2, 16, (0, 4), 256, 2),
    ("two-passes", 64, 2, 4, (0, 3), 64, 1),
    ("three-passes-of-a-tile", 32, 2, 4, (0, 4), 24, None),
]


@pytest.mark.parametrize("name,tokens,k,n_routed,held,pass_rows,heavy",
                         LAYERS)
def test_the_kernels_route_is_ragged_dot_on_rounded_operands(
        monkeypatch, name, tokens, k, n_routed, held, pass_rows, heavy):
    """`held_experts` forward and its five gradients on the kernels'
    route against the `ragged_dot` route at float32-highest with every
    product's operands rounded to bfloat16 where the kernels round them;
    every gradient is float32."""
    x, routing, mats, cot, away = _layer_case(
        len(name), tokens, k, n_routed, held, heavy)
    if name == "an-empty-group":
        experts = np.array(routing.experts)
        experts[experts == 3] = 4 + (np.arange((experts == 3).sum()) % 2) * 2
        # two slots of a token may now name one expert: both are computed
        routing = routing._replace(experts=jnp.asarray(experts))

    def loss(x, gates, w_gate, w_up, w_down):
        heard = set()
        with moe.routes_into(heard):
            y, counts, dropped = moe.held_experts(
                x, w_gate, w_up, w_down, routing._replace(gates=gates),
                held[0], pass_rows)
        return (jnp.where(away[:, None], 0.0, y) * cot).sum(), (
            y, counts, dropped, heard)

    args = (jnp.where(away[:, None], 0.0, x), routing.gates, *mats)
    with monkeypatch.context() as m:
        m.setattr(moe, "_operand", lambda rows, tile: rounded(rows))
        with jax.default_matmul_precision("highest"):
            (_, (y, counts, _, heard)), want = jax.value_and_grad(
                loss, (0, 1, 2, 3, 4), has_aux=True)(
                rounded(args[0]), args[1], *(rounded(w) for w in mats))
    assert heard == {"xla"}
    if name == "an-empty-group":
        assert int(counts[3]) == 0
    if name == "a-group-under-a-row-tile":
        assert 0 < int(counts.min()) < moe_pallas.tiles(pass_rows, D, W, 4)
    assert (int(counts.sum()) > pass_rows) == ("passes" in name)

    on_kernels(monkeypatch)
    (_, (got_y, got_counts, dropped, heard)), got = jax.value_and_grad(
        loss, (0, 1, 2, 3, 4), has_aux=True)(x, routing.gates, *mats)
    assert heard == {"pallas"}
    assert int(dropped) == 0 and np.array_equal(got_counts, counts)
    assert np.isfinite(got_y).all()
    assert rel(got_y, y) < 2e-3
    for part, g, w in zip(("x", "gates", "w_gate", "w_up", "w_down"), got,
                          want):
        assert g.dtype == jnp.float32, part
        assert np.isfinite(g).all(), part
        assert rel(g, w) < 4e-3, (part, rel(g, w))
    assert not np.asarray(got[0])[away].any()


@pytest.mark.parametrize("kind,rows,d,w,groups,devices,route", [
    # the three cells' passes
    (V5E, 16384, 2048, 1408, 8, 1, "pallas"),
    (V5E, 32768, 2048, 1536, 8, 1, "pallas"),
    (V5E, 16384, 2048, 512, 32, 1, "pallas"),
    # the compiler partitions no Mosaic kernel
    (V5E, 16384, 2048, 1408, 8, 4, "xla"),
    ("TPU v4", 16384, 2048, 1408, 8, 1, "xla"),
    ("cpu", 16384, 2048, 1408, 8, 1, "xla"),
    # widths off the lane tile, rows no tile divides, a matrix over VMEM
    (V5E, 16384, 2048, 1400, 8, 1, "xla"),
    (V5E, 48, 64, 48, 8, 1, "xla"),
    (V5E, 100, 128, 128, 2, 1, "xla"),
    (V5E, 16384, 4096, 2048, 8, 1, "xla"),
    (V5E, 24, 128, 128, 4, 1, "pallas"),
])
def test_grouped_product_route(kind, rows, d, w, groups, devices, route):
    assert moe.grouped_product_route(kind, rows, d, w, groups,
                                     devices) == route


@pytest.mark.parametrize("rows,d,w,groups,tile", [
    (16384, 2048, 1408, 8, 128),        # kimivl-a3b-ep8.train
    (32768, 2048, 1536, 8, 128),        # lfm2-a2b-ep8.train
    (16384, 2048, 512, 32, 128),        # qwen3next-a3b-ep16.train
    (16384, 2048, 512, 64, 64),
    (128, 128, 256, 4, 8),
    (24, 128, 128, 4, 8),               # no smaller tile: taken as it is
    (16384, 2048, 1400, 8, None),
    (100, 128, 128, 2, None),
    (16384, 4096, 2048, 8, None),
])
def test_tiles(rows, d, w, groups, tile):
    assert moe_pallas.tiles(rows, d, w, groups) == tile


def _expert_spec(**over):
    from predictionio_tpu.models import seqrec

    base = dict(
        d_model=128, n_heads=2, n_layers=2, max_len=32, batch_size=2,
        seed=5, epochs=1, mixer="mla", ffn="moe", norm="rms",
        positions="rope", tied_head=False, ffn_width=128,
        first_dense_layers=1, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, kv_lora_rank=32, n_routed_experts=8,
        held_experts=(0, 4), experts_per_token=2, moe_width=128,
        n_shared_experts=0, remat=True)
    return seqrec.SeqRecParams(**{**base, **over})


def test_a_step_says_which_route_its_grouped_products_took(monkeypatch):
    """The step's `expert_product_pallas` is what `held_experts` chose at
    trace time: False on the CPU, True with the kernels' route forced
    (and interpreted), False again for the same spec under a mesh of two
    devices; with the rows left float32, as the CPU leaves
    `ragged_dot`'s, the loss and the gradient norms are `ragged_dot`'s."""
    from jax.sharding import Mesh

    from predictionio_tpu.models import seqrec

    p = _expert_spec()
    optimizer = seqrec.make_optimizer(p)
    seqs = jnp.asarray(np.random.default_rng(0).integers(1, 9, (2, 32)),
                       jnp.int32)

    def step(mesh):
        params = seqrec.init_params(np.random.default_rng(0), 8, p)
        return seqrec.make_train_step(mesh, p, optimizer)(
            params, optimizer.init(params), seqs, seqs)[2]

    xla = step(None)
    assert not bool(xla["expert_product_pallas"])
    on_kernels(monkeypatch)
    monkeypatch.setattr(moe_pallas, "_BF16", jnp.float32)
    monkeypatch.setattr(moe, "_operand", lambda rows, tile: rows)
    for mesh, pallas in (
            (None, True),
            (Mesh(np.asarray(jax.devices()[:2]), ("data",)), False)):
        stats = step(mesh)
        assert bool(stats["expert_product_pallas"]) is pallas
        assert int(stats["dropped"].sum()) == 0
        assert np.array_equal(stats["held_tokens"], xla["held_tokens"])
        assert abs(float(stats["loss"]) - float(xla["loss"])) \
            < 1e-5 * float(xla["loss"])
        for group, norm in xla["grad_norm"].items():
            assert abs(float(stats["grad_norm"][group]) - float(norm)) \
                < 2e-3 * float(norm), group


def test_expert_product_tokens_are_counted_by_route(monkeypatch):
    """`pio_train_seqrec_expert_product_tokens_total{impl}` counts the
    routed slots the held experts multiplied under the route the
    compiled step reports; a model without experts counts nothing."""
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs.registry import default_registry

    reg = default_registry()

    def counted(name, **labels):
        c = reg.get(name)
        return c.value(**labels) if c is not None else 0

    def products(label):
        return counted("pio_train_seqrec_expert_product_tokens_total",
                       impl=label)

    def held():
        return counted("pio_train_seqrec_expert_tokens_total", layer="0")

    sessions = [[f"i{(s + j) % 11}" for j in range(20 + s)] for s in range(4)]
    before = {label: products(label) for label in ("xla", "pallas")}
    seqrec.train_seqrec(None, sessions, seqrec.SeqRecParams(
        d_model=16, n_heads=2, n_layers=1, max_len=8, batch_size=2))
    assert {label: products(label) for label in before} == before
    slots = held()
    seqrec.train_seqrec(None, sessions, _expert_spec())
    slots, xla_slots = held(), held() - slots
    assert xla_slots > 0
    assert products("xla") - before["xla"] == xla_slots
    assert products("pallas") == before["pallas"]

    on_kernels(monkeypatch)
    # another seed: another step than the cached one
    seqrec.train_seqrec(None, sessions, _expert_spec(seed=6))
    assert products("pallas") - before["pallas"] == held() - slots > 0
    assert products("xla") - before["xla"] == xla_slots
