"""Fused low-precision top-k scoring (ops/scoring.py).

Covers the ISSUE's acceptance paths:
  * randomized recall@k parity property — exact vs fused bf16/int8 vs
    two-stage across catalog sizes spanning tile boundaries;
  * the fused f32 kernel is EXACTLY the exact scorer (scores and ids),
    masked and unmasked, and quantized/two-stage modes return exact f32
    scores for the items they pick (the overfetch/shortlist rescore);
  * masked and unmasked lanes share one compile family, and the
    scoring ledger stays on the bucket ladder x mode bound;
  * the build-time parity gate demotes a badly-quantizing catalog to
    exact serving (and the counter says so);
  * exact-vs-fused output parity THROUGH the query server and the
    batchpredict lanes, not just the model layer;
  * knob precedence (env > engine.json "scorer" > server.json) and the
    mode-keyed dispatch-latency probe;
  * the similarproduct vectorized batch_predict riding the kernel.
"""

import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

import predictionio_tpu.models.als as als_mod
from predictionio_tpu.models.als import ALSModel
from predictionio_tpu.ops import scoring
from predictionio_tpu.ops.fn_cache import family_keys
from predictionio_tpu.ops.topk import host_topk
from predictionio_tpu.utils.server_config import ScorerConfig

pytestmark = pytest.mark.anyio

NONEXACT_MODES = ("fused", "fused_bf16", "fused_int8", "twostage")


@pytest.fixture(autouse=True)
def _reset_scorer_state():
    """Every test starts from lazy (env > server.json) resolution and a
    fresh dispatch-probe memo; nothing leaks process-pinned modes."""
    scoring.set_process_scorer_config(None)
    als_mod._DEVICE_ROUNDTRIP_S = None
    als_mod._DEVICE_ROUNDTRIP_MODE = None
    yield
    scoring.set_process_scorer_config(None)
    als_mod._DEVICE_ROUNDTRIP_S = None
    als_mod._DEVICE_ROUNDTRIP_MODE = None


def _factors(n, k=12, seed=0, decay=1.2):
    """ALS-like factors: gaussian rows under a geometrically decaying
    spectrum (trained factor Gramians decay — the structure the
    two-stage principal truncation uses)."""
    rng = np.random.default_rng(seed)
    spec = np.power(10.0, -decay * np.arange(k) / max(1, k - 1))
    return (rng.standard_normal((n, k)) * spec).astype(np.float32)


def _recall(exact_idx, got_idx):
    return np.mean([
        len(set(a.tolist()) & set(b.tolist())) / max(1, len(a))
        for a, b in zip(exact_idx, got_idx)])


# ---------------------------------------------------------------------------
# host_topk (satellite: partition without the negated full copy)
# ---------------------------------------------------------------------------

def test_host_topk_matches_full_sort_randomized():
    rng = np.random.default_rng(3)
    for b, n, k in [(1, 1, 1), (3, 40, 5), (5, 257, 10), (2, 64, 64),
                    (4, 100, 200), (2, 9, 0)]:
        scores = rng.standard_normal((b, n)).astype(np.float32)
        vals, idx = host_topk(scores, k)
        kk = min(k, n)
        assert vals.shape == (b, kk) and idx.shape == (b, kk)
        ref = np.argsort(-scores, axis=1)[:, :kk]
        assert (idx == ref).all()
        assert (vals == np.take_along_axis(scores, ref, axis=1)).all()


def test_host_topk_with_ties_and_infs():
    scores = np.array([[1.0, 1.0, -np.inf, 2.0, 1.0]], np.float32)
    vals, idx = host_topk(scores, 3)
    assert vals[0, 0] == 2.0 and idx[0, 0] == 3
    assert (vals[0, 1:] == 1.0).all()


# ---------------------------------------------------------------------------
# kernel parity (ItemScorer directly)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_items", [33, 128, 129, 391, 640])
def test_fused_f32_is_exact_across_tile_boundaries(n_items):
    """Sizes straddle the 128-item tile grid: under one tile, exactly
    one, one+1, a ragged middle, and a whole multiple."""
    V = _factors(n_items, seed=n_items)
    U = _factors(7, seed=n_items + 1)
    s = scoring.build_scorer(V, ScorerConfig(mode="fused",
                                             tile_items=128))
    sc_e, ix_e = host_topk(U @ V.T, 10)
    sc, ix = s.topk(U, 10)
    assert np.allclose(sc, sc_e, rtol=1e-5, atol=1e-6)
    assert (ix == ix_e).all()


@pytest.mark.parametrize("mode", ["fused_bf16", "fused_int8", "twostage"])
@pytest.mark.parametrize("n_items,seed", [(200, 1), (384, 2), (385, 3),
                                          (900, 4)])
def test_recall_parity_property(mode, n_items, seed):
    """The randomized recall@k property the bench asserts at scale,
    across catalog sizes spanning tile boundaries."""
    k = 10
    V = _factors(n_items, seed=seed)
    U = _factors(16, seed=seed + 100)
    s = scoring.build_scorer(
        V, ScorerConfig(mode=mode, tile_items=128, shortlist=64))
    assert s.active_mode == mode, \
        f"{mode} unexpectedly parity-demoted (probe {s.recall_probe})"
    _, ix_e = host_topk(U @ V.T, k)
    sc, ix = s.topk(U, k)
    assert _recall(ix_e, ix) >= 0.99
    # quantized + two-stage paths rescore exactly: picked items carry
    # their true f32 scores, not dequantized approximations
    expect = np.einsum("bk,bsk->bs", U, V[ix])
    assert np.allclose(sc, expect, rtol=1e-4, atol=1e-5)


def test_quantized_modes_halve_factor_bytes():
    V = _factors(512, k=16)
    for mode, factor in [("fused_bf16", 2), ("fused_int8", 2),
                         ("twostage", 2)]:
        s = scoring.build_scorer(V, ScorerConfig(mode=mode,
                                                 tile_items=128))
        assert s.factor_bytes * factor <= s.exact_bytes, (
            mode, s.factor_bytes, s.exact_bytes)


def test_twostage_truncates_scan_rank_on_decaying_spectrum():
    s = scoring.build_scorer(_factors(600, k=32, decay=1.5),
                             ScorerConfig(mode="twostage",
                                          tile_items=128))
    assert s.scan_rank < 32
    # a flat spectrum keeps (nearly) every column — graceful degrade
    rng = np.random.default_rng(0)
    flat = rng.standard_normal((600, 32)).astype(np.float32)
    s2 = scoring.build_scorer(flat, ScorerConfig(mode="twostage",
                                                 tile_items=128))
    assert s2.scan_rank >= 24


def test_masked_kernel_matches_masked_exact():
    """The mask folds into the tiles as a -inf sentinel; the fused f32
    kernel must reproduce the materialized masked scorer exactly."""
    n, b, k = 391, 6, 8
    V = _factors(n, seed=9)
    U = _factors(b, seed=10)
    rng = np.random.default_rng(11)
    mask = rng.random((b, n)) < 0.3
    scores_ref = U @ V.T
    scores_ref[mask] = -np.inf
    sc_e, ix_e = host_topk(scores_ref, k)
    for mode in ("fused", "fused_int8", "twostage"):
        s = scoring.build_scorer(
            V, ScorerConfig(mode=mode, tile_items=128, shortlist=64))
        sc, ix = s.topk(U, k, mask=mask)
        for r in range(b):
            assert not mask[r, ix[r][np.isfinite(sc[r])]].any(), mode
        if mode == "fused":
            assert (ix == ix_e).all() and np.allclose(sc, sc_e,
                                                      rtol=1e-5)
        else:
            assert _recall(ix_e, ix) >= 0.95, mode


def test_fully_masked_row_returns_no_finite_scores():
    V = _factors(130, seed=12)
    U = _factors(2, seed=13)
    mask = np.ones((2, 130), bool)
    for mode in ("fused", "fused_int8", "twostage"):
        s = scoring.build_scorer(
            V, ScorerConfig(mode=mode, tile_items=128, shortlist=32))
        sc, _ = s.topk(U, 5, mask=mask)
        assert not np.isfinite(sc).any(), mode


def test_masked_and_unmasked_share_one_family():
    """Satellite: one compile family for both lanes — the masked lane
    is the same tiled kernel with the sentinel input, not a separate
    materialized program."""
    V = _factors(300, seed=14)
    U = _factors(4, seed=15)
    s = scoring.build_scorer(V, ScorerConfig(mode="fused_int8",
                                             tile_items=128))
    before = set(family_keys(scoring.FUSED_FAMILY))
    s.topk(U, 5)
    s.topk(U, 5, mask=np.zeros((4, 300), bool))
    new = set(family_keys(scoring.FUSED_FAMILY)) - before
    assert len(new) == 2          # same family, masked flag in the key
    assert {k[-1] for k in new} == {True, False}


def test_compile_ledger_bounded_on_bucket_ladder():
    """Varying B and k must land on the power-of-two ladder, not one
    compile per observed shape."""
    V = _factors(300, seed=16)
    s = scoring.build_scorer(V, ScorerConfig(mode="fused_int8",
                                             tile_items=128))
    before = len(family_keys(scoring.FUSED_FAMILY))
    for b in (1, 2, 3, 4, 5, 7, 8):
        s.topk(_factors(b, seed=b), 10)
    delta = len(family_keys(scoring.FUSED_FAMILY)) - before
    assert delta <= 4             # buckets 1, 2, 4, 8 — not 7 shapes
    s2 = scoring.build_scorer(V, ScorerConfig(mode="twostage",
                                              tile_items=128))
    before = len(family_keys(scoring.TWOSTAGE_FAMILY))
    for b in (1, 2, 3, 4, 5, 7, 8):
        s2.topk(_factors(b, seed=b), 3)
        s2.topk(_factors(b, seed=b), 7)   # k does not shape the scan
    delta = len(family_keys(scoring.TWOSTAGE_FAMILY)) - before
    assert delta <= 4


def test_twostage_k_beyond_shortlist_widens_candidates():
    """A request wanting more than the configured shortlist must widen
    the per-tile candidate fetch, not truncate (regression: num > the
    effective shortlist width crashed recommend_batch / silently
    shorted similarproduct)."""
    n = 520
    V = _factors(n, seed=70)
    U = _factors(3, seed=71)
    # min_recall=0: a 20-wide shortlist can't pass the k=10 probe at
    # 0.99 (correctly), and THIS test is about width handling, not the
    # gate
    s = scoring.build_scorer(
        V, ScorerConfig(mode="twostage", tile_items=128, shortlist=16),
        min_recall=0.0)
    assert s.n_tiles * s.cand_per_tile < 100
    sc, ix = s.topk(U, 100)
    assert sc.shape == (3, 100) and ix.shape == (3, 100)
    assert np.isfinite(sc).all()
    _, ix_e = host_topk(U @ V.T, 100)
    assert _recall(ix_e, ix) >= 0.95
    # the whole catalog is a valid ask too
    sc, ix = s.topk(U, n)
    assert sc.shape == (3, n)
    assert len(set(ix[0].tolist())) == n
    # model layer end-to-end: num far past the shortlist serves fine
    model = _als_model(n_items=520, seed=72)
    scoring.set_process_scorer_config(ScorerConfig(
        mode="twostage", tile_items=128, shortlist=16, min_recall=0.5))
    out = model.recommend_batch([("u003", 200, (), None)])
    assert len(out[0]) == 200


def test_twostage_concentrated_whitelist_widens_per_tile():
    """A whitelist whose survivors all share ONE tile sentinels every
    other tile to -inf; the masked scan must emit k candidates PER TILE
    so the allowed tile alone can fill the answer (regression: the
    configured cand_per_tile returned fewer results than exact)."""
    n = 520
    V = _factors(n, seed=80)
    U = _factors(3, seed=81)
    s = scoring.build_scorer(
        V, ScorerConfig(mode="twostage", tile_items=128, shortlist=16),
        min_recall=0.0)
    assert s.cand_per_tile < 10
    mask = np.ones((3, n), bool)
    mask[:, 20:60] = False            # 40 allowed items, one tile
    scores_ref = U @ V.T
    scores_ref[mask] = -np.inf
    sc_e, ix_e = host_topk(scores_ref, 10)
    sc, ix = s.topk(U, 10, mask=mask)
    assert np.isfinite(sc).all()
    assert (ix == ix_e).all()
    assert np.allclose(sc, sc_e, rtol=1e-4)
    # model layer: whitelist query under twostage == exact answers
    model = _als_model(n_items=520, seed=82)
    allow = tuple(f"i{i:05d}" for i in range(20, 60))
    reqs = [("u003", 10, (), allow), ("u007", 10, (), allow)]
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    exact = model.recommend_batch(reqs)
    scoring.set_process_scorer_config(ScorerConfig(
        mode="twostage", tile_items=128, shortlist=16, min_recall=0.5))
    got = model.recommend_batch(reqs)
    assert _rounded(got) == _rounded(exact)


def test_parity_gate_demotes_bad_quantization():
    """A near-tie catalog (score gaps far under quantization noise)
    must fail the probe, fall back to exact serving, and count it."""
    from predictionio_tpu.obs.scoring_stats import scoring_metrics

    rng = np.random.default_rng(17)
    V = (np.ones((400, 8)) + 1e-5 * rng.standard_normal((400, 8))
         ).astype(np.float32)

    def fallback_count():
        return sum(v for lab, v in
                   scoring_metrics().parity_fallback.samples()
                   if lab.get("mode") == "fused_int8")

    before = fallback_count()
    s = scoring.build_scorer(V, ScorerConfig(mode="fused_int8",
                                             tile_items=128))
    assert s.active_mode == "exact" and not s.active
    assert s.recall_probe < 0.99
    assert fallback_count() == before + 1
    assert s.factor_bytes == 0    # demoted scorers hold no device copy


# ---------------------------------------------------------------------------
# model layer: _score_topk routing + dispatch probe
# ---------------------------------------------------------------------------

def _als_model(n_items=300, n_users=20, rank=12, seed=21):
    uv = np.sort(np.asarray([f"u{i:03d}" for i in range(n_users)],
                            dtype=object))
    iv = np.sort(np.asarray([f"i{i:05d}" for i in range(n_items)],
                            dtype=object))
    return ALSModel(user_vocab=uv, item_vocab=iv,
                    U=_factors(n_users, k=rank, seed=seed),
                    V=_factors(n_items, k=rank, seed=seed + 1))


REQS = [("u003", 5, (), None),
        ("u007", 3, ("i00002", "i00005"), None),          # blacklist
        ("missing", 4, (), None),                          # unknown user
        ("u012", 6, (), ("i00001", "i00004", "i00009"))]   # whitelist


def _rounded(recs):
    return [[(i, round(s, 4)) for i, s in r] for r in recs]


def test_model_fused_matches_exact_through_recommend_batch():
    model = _als_model()
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    exact = model.recommend_batch(REQS)
    scoring.set_process_scorer_config(ScorerConfig(mode="fused",
                                                   tile_items=128))
    assert _rounded(model.recommend_batch(REQS)) == _rounded(exact)
    # arrays lane (the batchpredict arrow assembly) agrees too
    items, scores, counts = model.recommend_batch_arrays(REQS)
    flat_exact = [(i, round(s, 4)) for r in exact for i, s in r]
    flat_got = [(i, round(float(s), 4))
                for i, s in zip(items.tolist(), scores.tolist())]
    assert flat_got == flat_exact
    assert counts.tolist() == [len(r) for r in exact]


@pytest.mark.parametrize("mode", ["fused_int8", "twostage"])
def test_model_quantized_recall_through_recommend_batch(mode):
    model = _als_model(n_items=500)
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    exact = model.recommend_batch(REQS)
    scoring.set_process_scorer_config(ScorerConfig(
        mode=mode, tile_items=128, shortlist=64))
    got = model.recommend_batch(REQS)
    for a, b in zip(exact, got):
        ia, ib = {i for i, _ in a}, {i for i, _ in b}
        assert len(ia & ib) >= len(ia) - 1, (mode, a, b)
    # picked scores are exact (the rescore), so overlapping items agree
    for a, b in zip(exact, got):
        sa, sb = dict(a), dict(b)
        for item in set(sa) & set(sb):
            assert abs(sa[item] - sb[item]) < 1e-4


def test_scorer_cache_keyed_on_v_identity_and_config():
    model = _als_model()
    scoring.set_process_scorer_config(ScorerConfig(mode="fused_int8",
                                                   tile_items=128))
    model.recommend_batch(REQS)
    first = model._scorer_cache[2]
    model.recommend_batch(REQS)
    assert model._scorer_cache[2] is first          # stable across calls
    # V swap (the fold-in item-apply shape) requantizes
    model.V = model.V.copy()
    model.recommend_batch(REQS)
    assert model._scorer_cache[2] is not first
    # config change rebuilds too
    scoring.set_process_scorer_config(ScorerConfig(mode="fused_int8",
                                                   tile_items=256))
    model.recommend_batch(REQS)
    assert model._scorer_cache[2].tile == 256


def test_pickling_drops_scorer_cache():
    import pickle

    model = _als_model()
    scoring.set_process_scorer_config(ScorerConfig(mode="fused_int8",
                                                   tile_items=128))
    model.recommend_batch(REQS)
    assert hasattr(model, "_scorer_cache")
    clone = pickle.loads(pickle.dumps(model))
    assert not hasattr(clone, "_scorer_cache")
    assert not hasattr(clone, "_resident")


def test_dispatch_probe_reprobes_on_mode_change(monkeypatch):
    """Satellite: the memoized device-roundtrip probe re-measures when
    the scorer mode flips, and the host path only competes in exact
    mode."""
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    first = als_mod.device_roundtrip_s()
    assert als_mod._DEVICE_ROUNDTRIP_MODE == "exact"
    # same mode: memoized, no re-probe (the value object is stable)
    assert als_mod.device_roundtrip_s() == first
    scoring.set_process_scorer_config(ScorerConfig(mode="fused"))
    als_mod.device_roundtrip_s()
    assert als_mod._DEVICE_ROUNDTRIP_MODE == "fused"
    # the forced-device override (tests/benches) pins across modes
    als_mod._DEVICE_ROUNDTRIP_MODE = None
    als_mod._DEVICE_ROUNDTRIP_S = 0.0
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    assert als_mod.device_roundtrip_s() == 0.0
    # tiny catalog: exact mode routes host, fused mode must not
    als_mod._DEVICE_ROUNDTRIP_S = None        # drop the forced override
    model = _als_model(n_items=20)
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    assert model._use_host(2, False)
    scoring.set_process_scorer_config(ScorerConfig(mode="fused",
                                                   tile_items=128))
    assert not model._use_host(2, False)


# ---------------------------------------------------------------------------
# config precedence
# ---------------------------------------------------------------------------

def test_scorer_config_precedence(monkeypatch, tmp_path):
    from predictionio_tpu.utils.server_config import scorer_config

    conf = tmp_path / "server.json"
    conf.write_text(json.dumps({
        "scorer": {"mode": "fused_bf16", "tileItems": 4096,
                   "shortlist": 256, "minRecall": 0.95}}))
    monkeypatch.setenv("PIO_SERVER_CONF", str(conf))
    cfg = scorer_config(None)
    assert (cfg.mode, cfg.tile_items, cfg.shortlist, cfg.min_recall) == \
        ("fused_bf16", 4096, 256, 0.95)
    # engine.json section beats the host file
    cfg = scorer_config({"mode": "twostage", "shortlist": 128})
    assert cfg.mode == "twostage"
    assert cfg.shortlist == 128
    assert cfg.tile_items == 4096          # per-knob inheritance
    # env beats both; malformed env is logged + ignored
    monkeypatch.setenv("PIO_SCORER_MODE", "fused_int8")
    monkeypatch.setenv("PIO_SCORER_TILE_ITEMS", "not-a-number")
    cfg = scorer_config({"mode": "twostage"})
    assert cfg.mode == "fused_int8"
    assert cfg.tile_items == 4096
    # a malformed file mode falls back to the default chain
    conf.write_text(json.dumps({"scorer": {"mode": "warp-speed"}}))
    monkeypatch.delenv("PIO_SCORER_MODE")
    assert scorer_config(None).mode == "exact"


def test_process_config_lazy_resolution(monkeypatch):
    monkeypatch.setenv("PIO_SCORER_MODE", "fused")
    scoring.set_process_scorer_config(None)
    assert scoring.process_scorer_config().mode == "fused"


# ---------------------------------------------------------------------------
# similarproduct: the vectorized batch_predict rides the kernel
# ---------------------------------------------------------------------------

def _sim_model(n_items=260, rank=8, seed=30):
    from predictionio_tpu.engines.common import Item
    from predictionio_tpu.engines.similarproduct import SimilarityModel

    V = _factors(n_items, k=rank, seed=seed)
    norms = np.linalg.norm(V, axis=1, keepdims=True)
    V = V / np.where(norms == 0, 1.0, norms)
    vocab = np.sort(np.asarray([f"p{i:04d}" for i in range(n_items)],
                               dtype=object))
    cats = {i: Item(categories=("a",) if i % 3 == 0 else ("b",))
            for i in range(n_items)}
    return SimilarityModel(item_vocab=vocab, V=V, items=cats)


def _sim_queries():
    from predictionio_tpu.engines.similarproduct import Query

    return [
        (0, Query(items=("p0003", "p0017"), num=5)),
        (1, Query(items=("p0042",), num=4, black_list=("p0050",))),
        (2, Query(items=("unknown",), num=3)),
    ]


def test_similarproduct_batch_predict_fused_parity():
    from predictionio_tpu.engines.similarproduct import ALSAlgorithm

    model = _sim_model()
    algo = ALSAlgorithm()
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    exact = algo.batch_predict(model, _sim_queries())
    scoring.set_process_scorer_config(ScorerConfig(mode="fused",
                                                   tile_items=128))
    got = algo.batch_predict(model, _sim_queries())
    assert hasattr(model, "_scorer_cache")     # it actually rode the kernel
    for (ie, re_), (ig, rg) in zip(exact, got):
        assert ie == ig
        assert [(s.item, round(s.score, 4)) for s in re_.item_scores] == \
            [(s.item, round(s.score, 4)) for s in rg.item_scores]


def test_similarproduct_unbounded_filters_keep_exact_path():
    """categories / whiteList can reject unboundedly many of the top
    hits, so those queries keep the full-score path — and answer
    identically in both modes."""
    from predictionio_tpu.engines.similarproduct import ALSAlgorithm, Query

    model = _sim_model()
    algo = ALSAlgorithm()
    queries = [(0, Query(items=("p0003",), num=4, categories=("a",))),
               (1, Query(items=("p0010",), num=3,
                         white_list=("p0021", "p0033", "p0045")))]
    scoring.set_process_scorer_config(ScorerConfig(mode="exact"))
    exact = algo.batch_predict(model, queries)
    scoring.set_process_scorer_config(ScorerConfig(mode="fused",
                                                   tile_items=128))
    got = algo.batch_predict(model, queries)
    assert not hasattr(model, "_scorer_cache")  # fused lane declined
    for (_, re_), (_, rg) in zip(exact, got):
        assert [(s.item, round(s.score, 4)) for s in re_.item_scores] == \
            [(s.item, round(s.score, 4)) for s in rg.item_scores]


# ---------------------------------------------------------------------------
# query-server lane (exact-vs-fused parity through HTTP, status echo)
# ---------------------------------------------------------------------------

def _query_server(scorer_cfg):
    from predictionio_tpu.core.engine import Engine, TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, DataSourceParams,
        RecommendationDataSource, RecommendationPreparator,
        RecommendationServing,
    )
    from predictionio_tpu.server.query_server import QueryServer
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.utils.server_config import (
        DeployConfig, ServingConfig,
    )

    model = _als_model(n_items=400, n_users=16, seed=40)
    result = TrainResult(
        models=[model],
        algorithms=[ALSAlgorithm(AlgorithmParams(rank=12))],
        serving=RecommendationServing(),
        engine_params=EngineParams(
            data_source_params=DataSourceParams(app_name="ScoringApp")))
    engine = Engine(
        data_source_classes=RecommendationDataSource,
        preparator_classes=RecommendationPreparator,
        algorithm_classes={"als": ALSAlgorithm},
        serving_classes=RecommendationServing)
    instance = EngineInstance(
        id="scoring-e2e", engine_id="scoring-engine", engine_version="1",
        engine_variant="default", status="COMPLETED")
    return QueryServer(
        engine, result, instance, ctx=None,
        serving_config=ServingConfig(batch_max=8, batch_linger_s=0.0),
        deploy_config=DeployConfig(warmup=False),
        scorer_config=scorer_cfg)


async def test_query_server_parity_and_status_echo():
    queries = [{"user": f"u{i:03d}", "num": 5} for i in (1, 3, 5, 9)]
    queries.append({"user": "u002", "num": 4,
                    "blackList": ["i00007", "i00011"]})
    answers = {}
    for mode in ("exact", "fused", "fused_int8"):
        qs = _query_server(ScorerConfig(mode=mode, tile_items=128))
        client = TestClient(TestServer(qs.app))
        await client.start_server()
        try:
            outs = []
            for q in queries:
                r = await client.post("/queries.json", json=q)
                assert r.status == 200, await r.text()
                outs.append(await r.json())
            answers[mode] = outs
            st = await (await client.get("/deploy/status.json")).json()
            assert st["scorer"]["mode"] == mode
            if mode != "exact":
                units = st["scorer"]["units"]
                assert len(units) == 1 and \
                    units[0]["activeMode"] == mode
                assert units[0]["quantization"] == (
                    "float32" if mode == "fused" else "int8")
        finally:
            await client.close()
    def rounded(outs):
        return [[(s["item"], round(s["score"], 4))
                 for s in o["itemScores"]] for o in outs]
    assert rounded(answers["fused"]) == rounded(answers["exact"])
    # int8 picks may reorder near-ties; assert per-query overlap
    for a, b in zip(rounded(answers["exact"]),
                    rounded(answers["fused_int8"])):
        ia, ib = {i for i, _ in a}, {i for i, _ in b}
        assert len(ia & ib) >= len(ia) - 1


# ---------------------------------------------------------------------------
# batchpredict lane (workflow/batch_predict.py)
# ---------------------------------------------------------------------------

def _bp_result():
    from predictionio_tpu.core.engine import TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing,
    )

    model = _als_model(n_items=350, n_users=30, seed=50)
    return TrainResult(
        models=[model], algorithms=[ALSAlgorithm(AlgorithmParams())],
        serving=RecommendationServing(), engine_params=EngineParams())


@pytest.mark.parametrize("exact_lane", ["host", "device"])
def test_batchpredict_lane_parity_exact_vs_fused(tmp_path, monkeypatch,
                                                 exact_lane):
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    # exact mode picks host BLAS or the device from two timing probes
    # (`ALSModel._use_host`); pin their results so that each lane is
    # compared with the fused kernel whatever this machine's speed (on a
    # throttled CPU the probes pick the device, and the bytes differ)
    if exact_lane == "host":
        monkeypatch.setattr(als_mod, "_HOST_FLOPS", 1e18)
        monkeypatch.setattr(als_mod, "_DEVICE_ROUNDTRIP_S", 1.0)
    else:
        monkeypatch.setattr(als_mod, "_DEVICE_ROUNDTRIP_S", 0.0)
    assert _bp_result().models[0]._use_host(16, False) \
        == (exact_lane == "host")
    inp = tmp_path / "queries.jsonl"
    with open(inp, "w") as f:
        for i in range(40):
            q = {"user": f"u{i % 32:03d}", "num": 3 + i % 3}
            if i % 6 == 0:
                q["blackList"] = [f"i{i % 9:05d}"]
            f.write(json.dumps(q) + "\n")
    outs = {}
    for mode in ("exact", "fused"):
        scoring.set_process_scorer_config(
            ScorerConfig(mode=mode, tile_items=128))
        out = tmp_path / f"preds-{mode}.jsonl"
        rep = run_batch_predict(None, None, str(inp), str(out),
                                chunk_size=16, loaded=(_bp_result(), None))
        assert rep.merged
        outs[mode] = open(out, "rb").read()
    if exact_lane == "host":
        # byte-identical output: the fused f32 kernel IS the exact scorer
        assert outs["fused"] == outs["exact"]
        return
    # exact mode's own device program sums in another order: the same
    # items in the same order, scores to float32 rounding
    fused, exact = ([json.loads(line) for line in outs[mode].splitlines()]
                    for mode in ("fused", "exact"))
    assert len(fused) == len(exact) == 40
    for f, e in zip(fused, exact):
        assert f["query"] == e["query"]
        fs, es = f["prediction"]["itemScores"], e["prediction"]["itemScores"]
        assert [s["item"] for s in fs] == [s["item"] for s in es]
        np.testing.assert_allclose([s["score"] for s in fs],
                                   [s["score"] for s in es], rtol=2e-6)
