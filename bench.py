"""Benchmark: the judged configs (BASELINE.md) as one fault-isolated suite.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Design:

* A chip belongs to one process at a time, so the chip is claimed ONCE:
  a single long-lived jax worker runs every config sequentially, fed one
  config name at a time over stdin by an orchestrator that never imports
  jax.
* Heartbeats: the worker stamps every phase (init, data-build, compile,
  train, query) to stderr; the orchestrator echoes them and keeps the
  tail, so a hang always leaves evidence of WHERE.
* Watchdogs: per-config budgets + an overall deadline (BENCH_DEADLINE_S)
  so the suite ends itself and always prints its final line; SIGTERM
  dumps partial results instead of dying silently.
* One platform per run: a worker that fails to start, or wedges, may be
  replaced once by a fresh worker on the SAME platform. The platform
  never changes within a run — no chip means a non-zero exit, and so
  does any config left in `failures`.
* Baselines are MEASURED single-process numpy runs of the same math (the
  stand-in for stock Spark-local; the reference publishes no numbers,
  BASELINE.md). They run in a SEPARATE no-jax subprocess, overlapped
  with the worker's start-up, and extrapolate from a measured iteration
  subset where flagged (`baseline_measured_iters`).
* MFU: an analytic FLOP model (als_model_flops) against the chip's bf16
  peak — an estimate (the math runs in f32), reported per config.

Configs (order = bank cheap+judged numbers first, riskiest last):
  als_ml100k        recommendation ALS kernel @ MovieLens-100K shape
  pipeline_ml100k   the judged path: 100k rate events -> sqlite event
                    store -> run_train workflow (`pio train` wall-clock)
                    -> deploy -> 1k HTTP /queries.json, p50/p99
  cooccurrence_ml1m similarproduct cooccurrence @ ML-1M shape
  naive_bayes_spam  classification NB, spam/ham scale
  ecommerce_implicit_als  implicit ALS (view+buy confidence) + top-N
  eval_sweep_grid   cross-validated ALS hyperparameter sweep: 3-fold x
                    12-candidate (ranks x regs) grid, sequential
                    per-candidate trains vs the device-batched
                    vectorized sweep (compile ledger == distinct ranks)
  serving_batching  query-server hot path: concurrent-client sweep
                    (1/8/64) over the bucketed, pipelined micro-batcher,
                    p50/p99 + mean batch size + compile-shape ledger
  deploy_swap       deploy lifecycle cutover: cold reload vs warm swap
                    first-traffic latency + post-swap compile counts
                    (warm must be ZERO — the deploy/ acceptance bar)
  ingest_write      event WRITE hot path: per-request inserts vs the
                    group-commit WriteBuffer on sqlite + parquet,
                    events/s + ack p99 (asserts >=5x and exactly-once)
  foldin_freshness  online fold-in loop: batched vs one-at-a-time
                    fold-ins/sec (asserts >=5x + bounded als_foldin
                    ledger) and open-loop event stream vs recommendation
                    probe, p50/p95 event->reflected seconds (asserts
                    p95 <= apply interval + one warm apply + slack)
  batch_predict     offline batch scoring: sequential-chunk loop vs the
                    pipelined reader->scorer->writer vs a 2-process
                    sharded fleet, queries/s (asserts >=4x best path,
                    byte-identical output, bounded compile ledger)
  als_ml20m         MovieLens-20M ALS on one chip: 20M ratings,
                    138k x 27k, string-id assignment + data build +
                    train + RMSE all timed (north star, BASELINE.md)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np

RANK, ITERS, REG = 10, 20, 0.01

T0 = time.time()


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def hb(phase: str) -> None:
    """Worker-side heartbeat: timestamped phase marker on stderr, echoed
    by the orchestrator — a killed worker's last heartbeat tells WHERE it
    hung."""
    print(f"HB {time.time() - T0:.1f} {phase}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Synthetic data + measured numpy baselines (no jax anywhere here)
# ---------------------------------------------------------------------------

def synthetic_ratings(n_users, n_items, nnz, seed=0, implicit=False):
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, nnz).astype(np.int32)
    items = rng.integers(0, n_items, nnz).astype(np.int32)
    latent_u = rng.normal(size=(n_users, 4))
    latent_v = rng.normal(size=(n_items, 4))
    raw = np.einsum("nk,nk->n", latent_u[users], latent_v[items])
    if implicit:
        ratings = (raw > 0).astype(np.float32) + 1.0
    else:
        ratings = np.clip(np.round(2.5 + raw), 1, 5).astype(np.float32)
    return users, items, ratings


def _np_half_sweep(F, seg, tgt, val, n_seg, rank, reg, implicit=False,
                   alpha=1.0, chunk=1_000_000):
    """One numpy half-sweep (same math as the device kernel), chunked so
    the [n, K, K] outer-product buffer stays bounded at 20M nnz."""
    gram = np.zeros((n_seg, rank, rank), np.float32)
    rhs = np.zeros((n_seg, rank), np.float32)
    cnt = np.zeros(n_seg, np.float32)
    for lo in range(0, len(seg), chunk):
        s, t, v = seg[lo:lo + chunk], tgt[lo:lo + chunk], val[lo:lo + chunk]
        f = F[t]
        if implicit:
            w = alpha * np.abs(v)                     # c - 1
            p = (v > 0).astype(np.float32)
            outer = np.einsum("nk,nl->nkl", f, f) * w[:, None, None]
            np.add.at(gram, s, outer)
            np.add.at(rhs, s, f * ((1.0 + w) * p)[:, None])
            np.add.at(cnt, s, w)
        else:
            outer = np.einsum("nk,nl->nkl", f, f)
            np.add.at(gram, s, outer)
            np.add.at(rhs, s, f * v[:, None])
            np.add.at(cnt, s, 1.0)
    if implicit:
        gram = gram + (F.T @ F)[None, :, :]
    A = gram + (reg * np.maximum(cnt, 1.0))[:, None, None] * \
        np.eye(rank, dtype=np.float32)
    return np.linalg.solve(A, rhs[..., None])[..., 0]


def numpy_als_baseline(users, items, ratings, nu, ni, rank, iters, reg=REG,
                       implicit=False, alpha=1.0, measure_iters=None,
                       seed=1):
    """MEASURED numpy ALS run (both sides per iteration). When
    `measure_iters` < iters, the measured iterations are extrapolated
    linearly (ALS iterations are uniform cost; flagged by the caller)."""
    rng = np.random.default_rng(seed)
    V = rng.normal(size=(ni, rank)).astype(np.float32) / np.sqrt(rank)
    run = min(measure_iters or iters, iters)
    t0 = time.perf_counter()
    for _ in range(run):
        U = _np_half_sweep(V, users, items, ratings, nu, rank, reg,
                           implicit, alpha)
        V = _np_half_sweep(U, items, users, ratings, ni, rank, reg,
                           implicit, alpha)
    dt = time.perf_counter() - t0
    return dt * (iters / run), run


def base_als_ml100k():
    nu, ni, nnz = 943, 1682, 100_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz)
    base, measured = numpy_als_baseline(users, items, ratings, nu, ni,
                                        RANK, ITERS, measure_iters=5)
    return {"baseline_s": round(base, 3), "baseline_measured_iters": measured}


def base_pipeline():
    """No-jax surrogate of the judged pipeline boundary: events already
    in a sqlite store -> read + id-assign + numpy ALS train (the `pio
    train` wall-clock analog; import and query latency are reported
    separately by the config, so the baseline matches its elapsed_s =
    train-only). Store setup/import is untimed, mirroring cfg_pipeline."""
    import tempfile

    from predictionio_tpu.data import Event
    from predictionio_tpu.storage import App, Storage

    nu, ni, nnz = 943, 1682, 100_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=11)
    with tempfile.TemporaryDirectory() as tmp:
        Storage.configure({
            "sources": {"DB": {"TYPE": "sqlite",
                               "PATH": os.path.join(tmp, "base.db")}},
            "repositories": {
                "METADATA": {"NAME": "pio", "SOURCE": "DB"},
                "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
                "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
            },
        })
        from predictionio_tpu.data.eventstore import clear_cache
        clear_cache()
        apps = Storage.get_meta_data_apps()
        app_id = apps.insert(App(id=0, name="BaseApp"))
        store = Storage.get_events()
        store.init_channel(app_id)
        batch = [Event(event="rate", entity_type="user", entity_id=str(u),
                       target_entity_type="item", target_entity_id=str(i),
                       properties={"rating": float(r)})
                 for u, i, r in zip(users, items, ratings)]
        for k in range(0, len(batch), 5000):
            store.insert_batch(batch[k:k + 5000], app_id)

        t0 = time.perf_counter()
        tbl = store.find_columnar(app_id, ordered=False)
        eid = np.asarray(tbl.column("entity_id"))
        tid = np.asarray(tbl.column("target_entity_id"))
        rr = np.asarray([json.loads(p)["rating"]
                         for p in tbl.column("properties").to_pylist()],
                        dtype=np.float32)
        uvocab, uidx = np.unique(eid, return_inverse=True)
        ivocab, iidx = np.unique(tid, return_inverse=True)
        read_s = time.perf_counter() - t0
        base, measured = numpy_als_baseline(
            uidx.astype(np.int32), iidx.astype(np.int32), rr,
            len(uvocab), len(ivocab), RANK, ITERS, measure_iters=5)
    return {"baseline_s": round(read_s + base, 3),
            "baseline_measured_iters": measured,
            "baseline_read_s": round(read_s, 3)}


def base_cooccurrence():
    nu, ni, nnz = 6040, 3706, 1_000_000
    users, items, _ = synthetic_ratings(nu, ni, nnz, seed=2)
    pairs = np.unique(
        users.astype(np.int64) * ni + items.astype(np.int64))
    users, items = (pairs // ni).astype(np.int32), (pairs % ni).astype(np.int32)
    n_top = 20
    t0 = time.perf_counter()
    a = np.zeros((nu, ni), np.float32)
    a[users, items] = 1.0
    c_np = a.T @ a
    np.fill_diagonal(c_np, 0.0)
    np.argpartition(-c_np, kth=n_top, axis=1)[:, :n_top]
    base = time.perf_counter() - t0
    return {"baseline_s": round(base, 3)}


def _nb_data():
    n_docs, vocab = 20_000, 2_000
    rng = np.random.default_rng(3)
    labels = np.where(rng.random(n_docs) < 0.4, "spam", "ham")
    X = rng.poisson(
        np.where((labels == "spam")[:, None],
                 rng.random(vocab) * 2.0, rng.random(vocab) * 1.2)
    ).astype(np.float32)
    return X, labels


def base_naive_bayes():
    X, labels = _nb_data()
    n_docs, vocab = X.shape
    t0 = time.perf_counter()
    lv, codes = np.unique(labels, return_inverse=True)
    counts = np.zeros((len(lv), vocab), np.float64)
    np.add.at(counts, codes, X)
    prior = np.log(np.bincount(codes) / n_docs)
    prob = np.log((counts + 1.0) / (counts + 1.0).sum(1, keepdims=True))
    (X @ prob.T.astype(np.float32) + prior[None, :]).argmax(1)
    base = time.perf_counter() - t0
    return {"baseline_s": round(base, 3)}


def base_ecommerce():
    nu, ni, nnz = 2000, 1500, 200_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=4,
                                              implicit=True)
    base, measured = numpy_als_baseline(users, items, ratings, nu, ni,
                                        RANK, 10, implicit=True,
                                        measure_iters=3)
    return {"baseline_s": round(base, 3), "baseline_measured_iters": measured}


def _eval_grid_shape():
    """The eval_sweep grid, shared by config + baseline (env-overridable
    so the smoke test can shrink both sides identically)."""
    nu = int(os.environ.get("BENCH_EVAL_USERS", 943))
    ni = int(os.environ.get("BENCH_EVAL_ITEMS", 1682))
    nnz = int(os.environ.get("BENCH_EVAL_NNZ", 100_000))
    k_fold = int(os.environ.get("BENCH_EVAL_FOLDS", 3))
    iters = int(os.environ.get("BENCH_EVAL_ITERS", 5))
    ranks = [int(r) for r in
             os.environ.get("BENCH_EVAL_RANKS", "8,12").split(",") if r]
    regs = [float(g) for g in os.environ.get(
        "BENCH_EVAL_REGS", "0.01,0.02,0.05,0.1,0.2,0.4").split(",") if g]
    return nu, ni, nnz, k_fold, iters, ranks, regs


def base_eval_sweep():
    nu, ni, nnz, k_fold, iters, ranks, regs = _eval_grid_shape()
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=5)
    fold_of = np.arange(nnz) % k_fold
    # one fold per rank measured, then extrapolated across folds x regs
    # (folds are uniform cost; reg does not change numpy ALS cost)
    t0 = time.perf_counter()
    for rank in ranks:
        tr = fold_of != 0
        numpy_als_baseline(users[tr], items[tr], ratings[tr], nu, ni,
                           rank, iters)
    base = (time.perf_counter() - t0) * k_fold * len(regs)
    return {"baseline_s": round(base, 3), "baseline_measured_folds": 1,
            "baseline_extrapolated_candidates": len(ranks) * len(regs)}


def base_als_ml20m():
    nu, ni, nnz = 138_000, 27_000, 20_000_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=20)
    cap = 4_000_000
    base_cap, measured = numpy_als_baseline(
        users[:cap], items[:cap], ratings[:cap], nu, ni, RANK, ITERS,
        measure_iters=1)
    base = base_cap * (nnz / cap)
    return {"baseline_s": round(base, 2), "baseline_measured_iters": measured,
            "baseline_extrapolated_from_nnz": cap}


BASELINES = {
    "als_ml100k": base_als_ml100k,
    "pipeline_ml100k": base_pipeline,
    "cooccurrence_ml1m": base_cooccurrence,
    "naive_bayes_spam": base_naive_bayes,
    "ecommerce_implicit_als": base_ecommerce,
    "eval_sweep_grid": base_eval_sweep,
    "als_ml20m": base_als_ml20m,
}


def worker_baselines(names) -> None:
    """No-jax subprocess: measure numpy baselines, one JSON line each (so
    a crash/timeout keeps everything already measured)."""
    for name in names:
        fn = BASELINES.get(name)
        if fn is None:
            continue
        hb(f"baseline-start {name}")
        try:
            out = fn()
        except Exception as e:      # one bad baseline must not eat the rest
            log(f"baseline {name} failed: {e!r}")
            continue
        print("BASELINE " + json.dumps({"name": name, **out}), flush=True)
    print("BASELINES_DONE", flush=True)


# ---------------------------------------------------------------------------
# FLOP model / MFU
# ---------------------------------------------------------------------------

def als_model_flops(nnz, nu, ni, rank, iters):
    """Analytic FLOPs of `iters` full ALS iterations: Gramian assembly
    (one K x K outer-accumulate per rating, both sides) + rhs + batched
    Cholesky solves (K^3/3 factor + 2 K^2 triangular solves/segment)."""
    gram = 2 * nnz * rank * rank * 2          # both sides, 2 flops/MAC
    rhs = 2 * nnz * rank * 2
    solve = (nu + ni) * (rank ** 3 / 3 + 2 * rank * rank) * 2
    return iters * (gram + rhs + solve)


_PEAK_BF16 = (  # (device_kind substring, peak bf16 FLOP/s per chip)
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
)


def peak_flops(device_kind: str):
    kind = (device_kind or "").lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    return None     # unknown chip / CPU: no MFU claim


# ---------------------------------------------------------------------------
# Worker-side backend setup
# ---------------------------------------------------------------------------

def setup_backend(platform: str):
    """Import jax pinned to `platform` (JAX itself raises when that
    platform has no device), with the persistent compilation cache on.
    The bench mesh is ONE device whatever the host holds; every config
    builds its data with n_shards=1."""
    from predictionio_tpu.utils.device import enable_compile_cache

    os.environ["JAX_PLATFORMS"] = platform
    enable_compile_cache()
    import jax

    devices = jax.devices()
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices)[:1], axis_names=("data",))
    return jax, devices, mesh


# ---------------------------------------------------------------------------
# Configs — each returns a detail dict
# ---------------------------------------------------------------------------

def timed_best(fn, repeats: int = 3):
    """min-of-N wall time for a sub-second timed region. Returns
    (best_seconds, last_result). min, not mean — stalls are additive
    noise, never speedups."""
    best, result = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def _als_device_data(jax, mesh, users, items, ratings, nu, ni):
    """ALSData built on host then committed to the mesh ONCE — the timed
    train consumes resident arrays, so host->device transfer time is
    reported separately (`transfer_s`) instead of polluting the train
    number."""
    from predictionio_tpu.models.als import ALSData

    t0 = time.perf_counter()
    data = ALSData.build(users, items, ratings, nu, ni, n_shards=1)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    data = data.put(mesh)
    transfer_s = time.perf_counter() - t0
    return data, build_s, transfer_s


def cfg_als_ml100k(jax, mesh, platform):
    """Config 1 kernel: recommendation ALS @ ML-100K shape."""
    from predictionio_tpu.models.als import ALSParams, train_als
    from predictionio_tpu.models.als import rmse as als_rmse

    nu, ni, nnz = 943, 1682, 100_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz)
    # default chunk_size = engine parity: pipeline_ml100k's run_train
    # then reuses THIS config's compiled program (same worker, same jit
    # cache), so its cold train measures work, not XLA compile
    params = ALSParams(rank=RANK, num_iterations=ITERS, reg=REG)
    hb("als_ml100k data-build")
    data, build_s, transfer_s = _als_device_data(
        jax, mesh, users, items, ratings, nu, ni)
    hb("als_ml100k compile+warmup")
    t0 = time.perf_counter()
    train_als(mesh, data, params)          # warm-up (compile + first run)
    warm_s = time.perf_counter() - t0
    hb("als_ml100k train")
    elapsed, (U, V) = timed_best(lambda: train_als(mesh, data, params))
    err = als_rmse(U, V, users, items, ratings)
    assert np.isfinite(err), "ALS diverged"
    flops = als_model_flops(nnz, nu, ni, RANK, ITERS)
    return {"elapsed_s": round(elapsed, 4),
            "build_s": round(build_s, 3),
            "transfer_s": round(transfer_s, 3),
            "compile_s": round(warm_s - elapsed, 3),
            "model_flops": flops,
            "note": f"train-RMSE {err:.3f}; best of 3"}


def cfg_pipeline_ml100k(jax, mesh, platform):
    """The judged workload boundary (BASELINE.md target metrics): events
    in the store -> `pio train` equivalent -> deploy -> HTTP query
    latency. Mirrors the reference quickstart
    (tests/pio_tests/scenarios/quickstart_test.py:33-95,
    CreateServer.scala:597-604)."""
    import asyncio
    import tempfile

    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.engines.recommendation import (
        default_engine_params, engine as engine_factory)
    from predictionio_tpu.storage import App, Storage
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.train import load_for_deploy

    nu, ni, nnz = 943, 1682, 100_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=11)

    with tempfile.TemporaryDirectory() as tmp:
        Storage.configure({
            "sources": {"DB": {"TYPE": "sqlite",
                               "PATH": os.path.join(tmp, "bench.db")}},
            "repositories": {
                "METADATA": {"NAME": "pio", "SOURCE": "DB"},
                "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
                "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
            },
        })
        from predictionio_tpu.data.eventstore import clear_cache
        clear_cache()
        apps = Storage.get_meta_data_apps()
        app_id = apps.insert(App(id=0, name="BenchApp"))
        store = Storage.get_events()
        store.init_channel(app_id)

        hb("pipeline import-events")
        t0 = time.perf_counter()
        batch = []
        for u, i, r in zip(users, items, ratings):
            batch.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float(r)})))
            if len(batch) >= 10_000:
                store.insert_batch(batch, app_id)
                batch = []
        if batch:
            store.insert_batch(batch, app_id)
        import_s = time.perf_counter() - t0

        engine = engine_factory()
        ep = default_engine_params("BenchApp", rank=RANK,
                                   num_iterations=ITERS)
        hb("pipeline train (cold: read+build+jit+train)")
        t0 = time.perf_counter()
        instance = run_train(
            engine, ep,
            engine_factory="predictionio_tpu.engines.recommendation:engine")
        train_s = time.perf_counter() - t0   # the `pio train` wall-clock

        # warm `pio train`: same workflow again — compile is cached, so
        # this separates XLA-compile cost from the steady-state train the
        # judge compares against Spark re-runs (VERDICT r3 item 3)
        hb("pipeline train (warm)")
        t0 = time.perf_counter()
        instance = run_train(
            engine, ep,
            engine_factory="predictionio_tpu.engines.recommendation:engine")
        train_warm_s = time.perf_counter() - t0

        hb("pipeline deploy")
        t0 = time.perf_counter()
        result, ctx = load_for_deploy(engine, instance)
        deploy_s = time.perf_counter() - t0

        from aiohttp.test_utils import TestClient, TestServer

        from predictionio_tpu.server.query_server import create_query_server

        server = create_query_server(engine, result, instance, ctx)
        lat = []

        hb("pipeline queries")

        async def drive():
            c = TestClient(TestServer(server.app))
            await c.start_server()
            try:
                for q in range(20):        # warm-up (compile + caches)
                    await c.post("/queries.json",
                                 json={"user": f"u{q % nu}", "num": 10})
                for q in range(1000):
                    t = time.perf_counter()
                    resp = await c.post(
                        "/queries.json",
                        json={"user": f"u{q % nu}", "num": 10})
                    assert resp.status == 200, await resp.text()
                    body = await resp.json()
                    assert len(body["itemScores"]) == 10
                    lat.append(time.perf_counter() - t)
            finally:
                await c.close()

        asyncio.run(drive())
        Storage.reset()
        clear_cache()

    lat_ms = np.asarray(lat) * 1e3
    p50, p99 = float(np.percentile(lat_ms, 50)), float(np.percentile(lat_ms, 99))
    return {
        "elapsed_s": round(train_s, 3),
        "baseline_s": None,
        "note": (f"import {import_s:.1f}s, pio-train {train_s:.2f}s "
                 f"(warm {train_warm_s:.2f}s), deploy {deploy_s:.2f}s, "
                 f"query p50 {p50:.2f}ms p99 {p99:.2f}ms over 1000 HTTP "
                 "queries"),
        "import_s": round(import_s, 2),
        "train_s": round(train_s, 3),
        "train_warm_s": round(train_warm_s, 3),
        "deploy_s": round(deploy_s, 3),
        "query_p50_ms": round(p50, 3),
        "query_p99_ms": round(p99, 3),
    }


def cfg_als_ml20m(jax, mesh, platform):
    """North-star shape (BASELINE.md): 20M ratings, 138k users x 27k
    items, trained end-to-end on one chip — string-id assignment, data
    build, transfer, train, RMSE all timed separately. A run started on
    the CPU uses a scaled-down shape (flagged `scaled_for_cpu`)."""
    from predictionio_tpu.data.bimap import assign_indices
    from predictionio_tpu.models.als import ALSParams, train_als
    from predictionio_tpu.models.als import rmse as als_rmse

    if platform == "cpu":
        nu, ni, nnz, iters, scaled = 30_000, 10_000, 2_000_000, 5, True
    else:
        nu, ni, nnz, iters, scaled = 138_000, 27_000, 20_000_000, ITERS, False
    hb("ml20m synth-data")
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=20)
    detail = {}
    if scaled:
        # the out-of-process baseline measured the FULL 20M/20-iter shape;
        # a scaled-down run must carry its own matched baseline or the
        # speedup would compare different workloads
        hb("ml20m scaled inline baseline")
        base, measured = numpy_als_baseline(
            users, items, ratings, nu, ni, RANK, iters, measure_iters=1)
        detail.update({"baseline_s": round(base, 2),
                       "baseline_measured_iters": measured,
                       "baseline_note": "matched to the scaled CPU shape"})

    # the BiMap.scala:126-128 hard part: string ids -> contiguous indices
    user_ids = users.astype("U8")
    item_ids = items.astype("U8")
    hb("ml20m id-assign")
    t0 = time.perf_counter()
    user_vocab, user_codes = assign_indices(user_ids)
    item_vocab, item_codes = assign_indices(item_ids)
    id_assign_s = time.perf_counter() - t0
    del user_ids, item_ids
    nu_r, ni_r = len(user_vocab), len(item_vocab)

    hb("ml20m data-build")
    data, build_s, transfer_s = _als_device_data(
        jax, mesh, user_codes, item_codes, ratings, nu_r, ni_r)
    params = ALSParams(rank=RANK, num_iterations=iters, reg=REG,
                       chunk_size=16384)
    hb("ml20m compile+warmup")
    t0 = time.perf_counter()
    train_als(mesh, data, params)               # warm-up compile
    warm_s = time.perf_counter() - t0
    hb("ml20m train")
    t0 = time.perf_counter()
    U, V = train_als(mesh, data, params)
    train_s = time.perf_counter() - t0
    hb("ml20m rmse")
    err = als_rmse(U, V, user_codes[:1_000_000], item_codes[:1_000_000],
                   ratings[:1_000_000])
    assert np.isfinite(err), "ALS diverged"
    flops = als_model_flops(nnz, nu_r, ni_r, RANK, iters)
    detail.update({
        "elapsed_s": round(train_s, 3),
        "model_flops": flops, "scaled_for_cpu": scaled,
        "nnz": nnz,
        "note": (f"{nnz / 1e6:.0f}M ratings {nu_r}x{ni_r}: id-assign "
                 f"{id_assign_s:.1f}s, build {build_s:.1f}s, transfer "
                 f"{transfer_s:.1f}s, train {train_s:.2f}s ({iters} "
                 f"iters, compile {warm_s - train_s:.1f}s), "
                 f"RMSE {err:.3f}"),
        "id_assign_s": round(id_assign_s, 2),
        "build_s": round(build_s, 2),
        "transfer_s": round(transfer_s, 2),
        "compile_s": round(warm_s - train_s, 2)})
    return detail


def cfg_cooccurrence(jax, mesh, platform):
    """Config 2: similarproduct cooccurrence @ ML-1M shape. The count
    matrix A^T A runs as ONE bf16 MXU matmul over the host-built
    user-item incidence matrix (models/cooccurrence.py)."""
    from predictionio_tpu.models.cooccurrence import (
        cooccurrence_topn, distinct_pairs)

    from predictionio_tpu.utils.profiling import collect_phases

    nu, ni, nnz = 6040, 3706, 1_000_000
    users, items, _ = synthetic_ratings(nu, ni, nnz, seed=2)
    users, items = distinct_pairs(users, items)
    n_top = 20

    hb("cooccurrence warmup")
    ph = {}
    with collect_phases(ph):       # cold call: host build + upload + compile
        t0 = time.perf_counter()
        cooccurrence_topn(mesh, users, items, nu, ni, n_top)
        cold = time.perf_counter() - t0
    hb("cooccurrence timed")
    elapsed, _ = timed_best(
        lambda: cooccurrence_topn(mesh, users, items, nu, ni, n_top))
    # matmul-dominated: A^T A is 2 * nu * ni^2 flops
    flops = 2.0 * nu * ni * ni
    build_s = ph.get("incidence_build", 0.0)
    transfer_s = ph.get("incidence_transfer", 0.0)
    if platform == "cpu":
        # a CPU run rebuilds + recomputes the
        # IDENTICAL BLAS gemm + top-k the numpy baseline runs (no
        # residency, no phase split — build_s/transfer_s are 0 here), so
        # ~1x is structural parity, not a regression — the headroom is
        # the MXU path
        note = (f"{len(users)} distinct pairs, best of 3 full recomputes; "
                f"CPU run = same BLAS as baseline (parity expected)")
    else:
        note = (f"{len(users)} distinct pairs; steady-state counts on "
                f"a resident incidence matrix, best of 3 (cold "
                f"build+upload+compile reported separately)")
    return {"elapsed_s": round(elapsed, 4),
            "build_s": round(build_s, 3),
            "transfer_s": round(transfer_s, 3),
            "compile_s": round(cold - elapsed - build_s - transfer_s, 3),
            "model_flops": flops,
            "note": note}


def cfg_naive_bayes(jax, mesh, platform):
    """Config 3: classification NaiveBayes, spam/ham-scale."""
    from predictionio_tpu.models.naive_bayes import train_multinomial_nb

    from predictionio_tpu.utils.profiling import collect_phases

    X, labels = _nb_data()
    hb("naive_bayes warmup")
    ph = {}
    with collect_phases(ph):       # cold call: compact + upload + compile
        model = train_multinomial_nb(X, labels, mesh=mesh)
        model.predict(X)           # compile the score matmul too
    hb("naive_bayes timed")
    train_s, model = timed_best(
        lambda: train_multinomial_nb(X, labels, mesh=mesh))
    predict_s, pred = timed_best(lambda: model.predict(X))
    elapsed = train_s + predict_s
    acc = float((pred == labels).mean())
    assert acc > 0.9, f"NB accuracy {acc}"
    return {"elapsed_s": round(elapsed, 4),
            "train_s": round(train_s, 4),
            "predict_s": round(predict_s, 4),
            "compact_s": round(ph.get("nb_compact", 0.0), 3),
            "transfer_s": round(ph.get("nb_transfer", 0.0), 3),
            "note": f"accuracy {acc:.3f}; steady-state train+predict on a "
                    f"resident X, each best of 3 (cold compact+upload "
                    f"reported separately)"}


def cfg_ecommerce(jax, mesh, platform):
    """Config 4: ecommerce implicit ALS (view+buy confidence) + top-N."""
    import jax.numpy as jnp

    from predictionio_tpu.models.als import ALSParams, train_als

    nu, ni, nnz = 2000, 1500, 200_000
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=4,
                                              implicit=True)
    iters = 10
    params = ALSParams(rank=RANK, num_iterations=iters, reg=REG,
                       implicit_prefs=True, alpha=1.0, chunk_size=16384)

    # pio: ignore[PIO001]: bench-local jit, one trace per process run
    @jax.jit
    def topn(u_all, v):
        return jax.lax.top_k(u_all @ v.T, 10)

    hb("ecommerce data-build")
    data, build_s, transfer_s = _als_device_data(
        jax, mesh, users, items, ratings, nu, ni)
    hb("ecommerce warmup")
    U, V = train_als(mesh, data, params)   # warm-up train ...
    jax.block_until_ready(topn(jnp.asarray(U), jnp.asarray(V)))
    hb("ecommerce timed")

    def run_once():
        U, V = train_als(mesh, data, params)
        out = topn(jnp.asarray(U), jnp.asarray(V))
        jax.block_until_ready(out)
        return out

    elapsed, _ = timed_best(run_once)
    flops = als_model_flops(nnz, nu, ni, RANK, iters)
    return {"elapsed_s": round(elapsed, 4), "model_flops": flops,
            "note": "implicit ALS + batch top-10; best of 3"}


def cfg_eval_sweep(jax, mesh, platform):
    """Config 5: cross-validated ALS hyperparameter sweep, 3-fold x
    12-candidate grid (ranks x regs), run BOTH ways:

      * sequential — the pre-PR reference shape (MetricEvaluator loop):
        per-fold data builds + one compiled train dispatch per
        (candidate, fold), P x K of them.
      * batched — the vectorized eval path (models/als_sweep): ONE
        fold-masked data build, the whole grid as one vmapped device
        program per distinct rank, held-out RMSE computed on device.

    Asserts the batched path's XLA compile ledger equals the number of
    distinct ranks (not grid size) and that both paths pick the same
    best candidate; reports candidates/sec for each side.
    """
    from predictionio_tpu.core.cross_validation import fold_assignments
    from predictionio_tpu.models.als import ALSData, ALSParams, train_als
    from predictionio_tpu.models.als_sweep import build_sweep_data, run_sweep
    from predictionio_tpu.ops import fn_cache

    nu, ni, nnz, k_fold, iters, ranks, regs = _eval_grid_shape()
    users, items, ratings = synthetic_ratings(nu, ni, nnz, seed=5)
    fold_of = fold_assignments(k_fold, nnz)
    candidates = [ALSParams(rank=r, num_iterations=iters, reg=g,
                            chunk_size=16384)
                  for r in ranks for g in regs]
    n_cand = len(candidates)

    def sweep_sequential():
        # fold data is rank-independent: build + commit each fold ONCE
        # per sweep and train every candidate on the resident arrays
        # (the CachedEvalRunner prefix-memoization semantics — already
        # generous to the sequential side)
        fold_data = []
        for f in range(k_fold):
            tr = fold_of != f
            fold_data.append(ALSData.build(
                users[tr], items[tr], ratings[tr], nu, ni,
                n_shards=1).put(mesh))
        out = []
        for p in candidates:
            se, nt = 0.0, 0
            for f in range(k_fold):
                te = fold_of == f
                U, V = train_als(mesh, fold_data[f], p)
                pred = np.einsum("nk,nk->n", U[users[te]], V[items[te]])
                se += float(((pred - ratings[te]) ** 2).sum())
                nt += int(te.sum())
            out.append((p.rank, p.reg, float(np.sqrt(se / nt))))
        return out

    def sweep_batched():
        data = build_sweep_data(users, items, ratings, fold_of, nu, ni)
        res = run_sweep(data, candidates)
        return [(c.params.rank, c.params.reg, c.heldout_rmse)
                for c in res.candidates]

    def best_of(scores):
        return min(scores, key=lambda t: t[2])

    hb(f"eval_sweep warmup sequential ({len(set(ranks))} rank compiles)")
    sweep_sequential()
    hb("eval_sweep timed sequential")
    seq_s, seq_scores = timed_best(sweep_sequential, repeats=2)

    hb("eval_sweep warmup batched")
    keys_before = len(fn_cache.family_keys("als_eval_sweep"))
    sweep_batched()
    compile_groups = len(fn_cache.family_keys("als_eval_sweep")) \
        - keys_before
    # the tentpole contract: the compile ledger is bounded by distinct
    # RANKS, not by the grid size
    assert compile_groups == len(set(ranks)), (
        f"batched sweep compiled {compile_groups} groups for "
        f"{len(set(ranks))} distinct ranks ({n_cand} candidates)")
    hb("eval_sweep timed batched")
    bat_s, bat_scores = timed_best(sweep_batched, repeats=2)

    assert best_of(seq_scores)[:2] == best_of(bat_scores)[:2], (
        f"best-candidate parity broken: sequential {best_of(seq_scores)} "
        f"vs batched {best_of(bat_scores)}")
    max_diff = max(abs(a[2] - b[2])
                   for a, b in zip(seq_scores, bat_scores))
    best_rank, best_reg, best_err = best_of(bat_scores)
    flops = sum(als_model_flops(nnz * (k_fold - 1) // k_fold, nu, ni,
                                p.rank, iters) * k_fold
                for p in candidates)
    speedup = seq_s / bat_s if bat_s else None
    return {"elapsed_s": round(bat_s, 4),
            "model_flops": flops,
            "grid_candidates": n_cand,
            "k_fold": k_fold,
            "sequential_s": round(seq_s, 4),
            "candidates_per_s_batched": round(n_cand / bat_s, 2),
            "candidates_per_s_sequential": round(n_cand / seq_s, 2),
            "speedup_batched_vs_sequential": round(speedup, 2),
            "compile_groups": compile_groups,
            "distinct_ranks": len(set(ranks)),
            "max_rmse_diff_vs_sequential": float(max_diff),
            "note": (f"{n_cand}-candidate x {k_fold}-fold grid: batched "
                     f"{n_cand / bat_s:.1f} cand/s vs sequential "
                     f"{n_cand / seq_s:.1f} cand/s ({speedup:.1f}x); "
                     f"{compile_groups} compile groups for "
                     f"{len(set(ranks))} ranks; best rank {best_rank} "
                     f"reg {best_reg} test-RMSE {best_err:.3f}, "
                     f"max |seq-batched| RMSE diff {max_diff:.1e}")}


def _als_kernel_shape():
    """The als_kernel sweep shape, env-overridable so the smoke test can
    shrink it. Defaults are the CPU-feasible judged shape; on TPU the
    same ranks run at whatever BENCH_ALS_* scale the round sets."""
    nu = int(os.environ.get("BENCH_ALS_USERS", 3000))
    ni = int(os.environ.get("BENCH_ALS_ITEMS", 800))
    nnz = int(os.environ.get("BENCH_ALS_NNZ", 120_000))
    iters = int(os.environ.get("BENCH_ALS_ITERS", 5))
    ranks = [int(r) for r in
             os.environ.get("BENCH_ALS_RANKS", "16,64,128").split(",") if r]
    block = int(os.environ.get("BENCH_ALS_BLOCK", 16))
    # block coordinate descent takes smaller steps per outer iteration, so
    # the subspace side runs factor x the iterations and parity is judged
    # at MATCHED HELD-OUT QUALITY (the iALS++ time-to-quality protocol,
    # arXiv:2110.14044 fig. 2) — throughput claims at equal iteration
    # counts but unequal quality would be fake
    factor = float(os.environ.get("BENCH_ALS_SUB_ITERS_FACTOR", 1.6))
    min_speedup = float(os.environ.get("BENCH_ALS_MIN_SPEEDUP", 2.0))
    slack = float(os.environ.get("BENCH_ALS_RMSE_SLACK", 0.03))
    return nu, ni, nnz, iters, ranks, block, factor, min_speedup, slack


def cfg_als_kernel(jax, mesh, platform):
    """Training-kernel face-off: full per-row solve vs subspace (iALS++)
    block coordinate descent, swept over ranks.

    For each rank the FULL solver trains `iters` outer iterations and the
    SUBSPACE solver `ceil(iters * factor)` — enough block sweeps to reach
    the same held-out RMSE (asserted within BENCH_ALS_RMSE_SLACK) — and
    the judged speedup is wall-to-matched-quality, best-of-2 each side.
    Asserts the >= BENCH_ALS_MIN_SPEEDUP floor at every rank >= 64 (the
    regime where the full solver's [S, K, K] batched-Cholesky bandwidth
    wall dominates) and that the als_train compile ledger stays at one
    entry per (rank, solver) family.
    """
    from predictionio_tpu.models.als import (
        ALSData, ALSParams, train_als, rmse as als_rmse,
    )
    from predictionio_tpu.ops import fn_cache

    nu, ni, nnz, iters, ranks, block, factor, min_speedup, slack = \
        _als_kernel_shape()
    rng = np.random.default_rng(7)
    users = rng.integers(0, nu, nnz).astype(np.int32)
    items = rng.integers(0, ni, nnz).astype(np.int32)
    # full-spectrum ground truth + noise: a noiseless low-rank synthetic
    # would let relative RMSE comparisons swing on a ~0 denominator
    lu = rng.normal(size=(nu, 32)) * (0.9 ** np.arange(32))
    lv = rng.normal(size=(ni, 32))
    ratings = (np.einsum("nk,nk->n", lu[users], lv[items]) / 3 + 3
               + 0.3 * rng.normal(size=nnz)).astype(np.float32)
    heldout = rng.random(nnz) < 0.1
    tr = ~heldout
    hb("als_kernel data-build")
    data = ALSData.build(users[tr], items[tr], ratings[tr], nu, ni,
                         n_shards=1).put(mesh)
    sub_iters = int(np.ceil(iters * factor))
    keys_before = len(fn_cache.family_keys("als_train"))

    detail = {}
    total_timed = 0.0
    notes = []
    for rank in ranks:
        sides = {}
        for solver, n_it in (("full", iters), ("subspace", sub_iters)):
            p = ALSParams(rank=rank, num_iterations=n_it, reg=0.05, seed=1,
                          solver=solver, block_size=block)
            hb(f"als_kernel r{rank} {solver} warmup")
            train_als(mesh, data, p)        # compile + first run
            hb(f"als_kernel r{rank} {solver} timed")
            elapsed, (U, V) = timed_best(
                lambda: train_als(mesh, data, p), repeats=2)
            err = als_rmse(U, V, users[heldout], items[heldout],
                           ratings[heldout])
            assert np.isfinite(err), f"{solver} diverged at rank {rank}"
            sides[solver] = (elapsed, err)
            total_timed += elapsed
        (t_full, e_full), (t_sub, e_sub) = sides["full"], sides["subspace"]
        speedup = t_full / t_sub if t_sub else float("inf")
        detail[f"train_s_full_r{rank}"] = round(t_full, 3)
        detail[f"train_s_subspace_r{rank}"] = round(t_sub, 3)
        detail[f"heldout_rmse_full_r{rank}"] = round(float(e_full), 5)
        detail[f"heldout_rmse_subspace_r{rank}"] = round(float(e_sub), 5)
        detail[f"iters_per_s_full_r{rank}"] = round(iters / t_full, 3)
        detail[f"iters_per_s_subspace_r{rank}"] = round(sub_iters / t_sub, 3)
        detail[f"speedup_r{rank}"] = round(speedup, 2)
        # held-out parity at matched quality — for EVERY rank
        assert e_sub <= e_full * (1.0 + slack), (
            f"rank {rank}: subspace heldout RMSE {e_sub:.4f} vs full "
            f"{e_full:.4f} exceeds {slack:.0%} slack")
        if rank >= 64:
            # the tentpole floor: the subspace solver must actually pay
            # off where the full solve's K^3 wall bites
            assert speedup >= min_speedup, (
                f"rank {rank}: subspace speedup {speedup:.2f}x under the "
                f"{min_speedup}x floor (full {t_full:.2f}s vs subspace "
                f"{t_sub:.2f}s)")
        notes.append(f"r{rank} {speedup:.1f}x")

    ledger = len(fn_cache.family_keys("als_train")) - keys_before
    assert ledger <= 2 * len(ranks), (
        f"als_train compiled {ledger} entries for {len(ranks)} ranks x 2 "
        "solvers — the (rank, block_size) family bound is broken")
    big = [r for r in ranks if r >= 64]
    headline = max((detail[f"speedup_r{r}"] for r in big), default=None)
    detail.update({
        "elapsed_s": round(total_timed, 3),
        "ranks": ranks,
        "block_size": block,
        "iters_full": iters,
        "iters_subspace": sub_iters,
        "rmse_slack": slack,
        "compile_ledger_delta": ledger,
        "speedup_headline": headline,
        "note": (f"full vs subspace(b={block}) at matched held-out "
                 f"quality, best-of-2: {', '.join(notes)}; "
                 f"ledger {ledger} <= {2 * len(ranks)}"),
    })
    return detail


def cfg_serving_batching(jax, mesh, platform):
    """Serving hot path under concurrent load: the bucketed, pipelined
    micro-batcher swept at 1/8/64 clients (BENCH_SERVING_CLIENTS),
    recording p50/p99 latency and the mean coalesced batch size per
    level, plus the compile-shape ledger the bucketing discipline bounds.

    No storage and no training — the model is synthetic factors, so the
    measurement isolates the serving stack (HTTP -> batcher -> jitted
    scorer). The device scorer is FORCED on (the host-BLAS crossover
    would hide the jit path on CPU) because the shape discipline under
    test is exactly the TPU-serving one. A single-in-flight, zero-linger
    re-run at the top client level gives the pipelining its
    before/after."""
    import asyncio

    import predictionio_tpu.models.als as als_mod
    from aiohttp.test_utils import TestClient, TestServer

    from predictionio_tpu.core.engine import Engine, TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing)
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.ops import bucketing, fn_cache
    from predictionio_tpu.server.query_server import create_query_server
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.utils.server_config import ServingConfig

    nu = int(os.environ.get("BENCH_SERVING_USERS", 5000))
    ni = int(os.environ.get("BENCH_SERVING_ITEMS", 2000))
    rank = 32
    per_level = int(os.environ.get("BENCH_SERVING_QUERIES", 512))
    clients = [int(c) for c in os.environ.get(
        "BENCH_SERVING_CLIENTS", "1,8,64").split(",") if c]
    max_batch = 64

    rng = np.random.default_rng(9)
    model = ALSModel(
        user_vocab=np.asarray([f"u{i:06d}" for i in range(nu)],
                              dtype=object),
        item_vocab=np.asarray([f"i{i:06d}" for i in range(ni)],
                              dtype=object),
        U=rng.normal(size=(nu, rank)).astype(np.float32),
        V=rng.normal(size=(ni, rank)).astype(np.float32))
    result = TrainResult(models=[model],
                         algorithms=[ALSAlgorithm(AlgorithmParams())],
                         serving=RecommendationServing(),
                         engine_params=EngineParams())
    instance = EngineInstance(id="bench-serving", engine_id="bench",
                              engine_variant="default")
    engine = Engine({}, {}, {"als": ALSAlgorithm}, {})

    async def run_level(c, n_clients, n_queries, lat):
        async def one(i):
            t = time.perf_counter()
            resp = await c.post("/queries.json", json={
                "user": f"u{i % nu:06d}", "num": 10})
            assert resp.status == 200, await resp.text()
            body = await resp.json()
            assert len(body["itemScores"]) == 10
            lat.append(time.perf_counter() - t)

        async def client(k, n):
            for j in range(n):
                await one(k * n + j)

        per_client = max(1, n_queries // n_clients)
        await asyncio.gather(*[client(k, per_client)
                               for k in range(n_clients)])

    def sweep(serving_config, levels, tag, slo_spec=None):
        # one server + one HTTP client span the whole sweep: app cleanup
        # shuts the server's predict executor, so apps are single-use
        server = create_query_server(engine, result, instance, None,
                                     serving_config=serving_config,
                                     slo_spec=slo_spec)
        size_hist = server.registry.get("pio_batch_size")
        out = {}

        async def run_all():
            c = TestClient(TestServer(server.app))
            await c.start_server()
            lat = []
            try:
                await run_level(c, 1, 8, lat)         # warm-up/compile
                for n_clients in levels:
                    hb(f"serving_batching {tag} {n_clients}c")
                    c0 = size_hist.total_count()
                    s0 = size_hist.total_sum()
                    lat.clear()
                    await run_level(c, n_clients, per_level, lat)
                    lat_ms = np.asarray(lat) * 1e3
                    dc = size_hist.total_count() - c0
                    mean_b = (size_hist.total_sum() - s0) / dc if dc \
                        else 0.0
                    out[n_clients] = {
                        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                        "mean_batch": round(float(mean_b), 2),
                    }
            finally:
                await c.close()

        asyncio.run(run_all())
        return out

    # the host-BLAS crossover would route this small model away from the
    # jitted scorer; force the device path so the compile ledger and the
    # bucketing discipline are what gets measured
    old_rt = als_mod._DEVICE_ROUNDTRIP_S
    als_mod._DEVICE_ROUNDTRIP_S = 0.0
    try:
        # compile every reachable bucket shape OUTSIDE the measured
        # window: steady-state latency is the judged number, and the
        # one-time compile cost is already bounded by the bucket set
        hb("serving_batching shape-warmup")
        b = 1
        while b <= max_batch:
            model.recommend_batch([(model.user_vocab[0], 10, (), None)] * b)
            b <<= 1
        t0 = time.perf_counter()
        piped = sweep(ServingConfig(batch_max=max_batch,
                                    batch_linger_s=None,
                                    batch_inflight=2), clients, "pipelined")
        elapsed = time.perf_counter() - t0
        # before/after: the pre-PR behavior (one batch in flight, no
        # linger) at the top concurrency level only
        single = sweep(ServingConfig(batch_max=max_batch,
                                     batch_linger_s=0.0,
                                     batch_inflight=1),
                       clients[-1:], "single-inflight")

        # observability overhead: tracing + flight recording + a live SLO
        # burn-rate engine (evaluating every 50ms) vs the obs-off state
        # (PIO_TRACING=0, no SLO engine — metrics stay on either way).
        # Alternating best-of-N p99 at the top client level; the plane
        # must cost within BENCH_OBS_OVERHEAD_PCT (default 5%) of the
        # obs-off p99 (+ a small absolute slack absorbing sub-ms noise).
        from predictionio_tpu.obs.slo import SLOEngine  # noqa: F401
        from predictionio_tpu.obs.slo import SLOObjective, SLOSpec, SLOWindow

        hb("serving_batching obs-overhead")
        obs_spec = SLOSpec(
            objectives=[
                SLOObjective("latency", "latency", threshold_s=0.256,
                             budget=0.01),
                SLOObjective("errors", "errors", budget=0.01)],
            # burn threshold astronomically high: the engine does its
            # full evaluation work but never flips (the flip path is
            # tested elsewhere; here we charge only its steady cost)
            windows=[SLOWindow(2.0, 1e12)],
            eval_interval_s=0.05)
        obs_cfg = lambda: ServingConfig(  # noqa: E731
            batch_max=max_batch, batch_linger_s=None, batch_inflight=2)
        repeats = int(os.environ.get("BENCH_OBS_REPEATS", 3))
        # pio: ignore[PIO006]: save/restore around the tracing A/B toggle
        old_tracing = os.environ.get("PIO_TRACING")
        on_p99, off_p99 = [], []
        # measure at the MID concurrency level: the top level runs queue-
        # saturated, where p99 is scheduling noise (3x run-to-run swings
        # on the same config) — a per-request overhead comparison needs
        # the stable regime. Alternating best-of-N bounds the tail noise.
        obs_level = [clients[1] if len(clients) > 1 else clients[-1]]
        try:
            for _ in range(repeats):
                os.environ["PIO_TRACING"] = "0"
                off_p99.append(
                    sweep(obs_cfg(), obs_level, "obs-off")
                    [obs_level[0]]["p99_ms"])
                os.environ["PIO_TRACING"] = "1"
                on_p99.append(
                    sweep(obs_cfg(), obs_level, "obs-on",
                          slo_spec=obs_spec)[obs_level[0]]["p99_ms"])
        finally:
            if old_tracing is None:
                os.environ.pop("PIO_TRACING", None)
            else:
                os.environ["PIO_TRACING"] = old_tracing
        obs_on_ms, obs_off_ms = min(on_p99), min(off_p99)
        overhead_pct = (100.0 * (obs_on_ms - obs_off_ms) / obs_off_ms
                        if obs_off_ms > 0 else 0.0)
        max_pct = float(os.environ.get("BENCH_OBS_OVERHEAD_PCT", 5.0))
        abs_slack_ms = float(os.environ.get(
            "BENCH_OBS_OVERHEAD_ABS_MS", 0.3))
        assert obs_on_ms <= obs_off_ms * (1 + max_pct / 100.0) \
            + abs_slack_ms, (
            f"observability overhead breached: p99 {obs_on_ms}ms with "
            f"tracing+SLO vs {obs_off_ms}ms obs-off "
            f"(+{overhead_pct:.1f}% > {max_pct}% + {abs_slack_ms}ms)")

        # anatomy overhead: the critical-path stage plane (per-member
        # stage histograms + exemplar stamping) on vs its kill switch,
        # with tracing ON both sides — so the comparison isolates the
        # anatomy cost itself, not the trace plane it rides. Same
        # alternating best-of-N p99 protocol at the same stable level.
        hb("serving_batching anatomy-overhead")
        # pio: ignore[PIO006]: save/restore around the anatomy A/B toggle
        old_anatomy = os.environ.get("PIO_ANATOMY")
        # pio: ignore[PIO006]: save/restore around the anatomy A/B toggle
        old_tracing = os.environ.get("PIO_TRACING")
        an_on_p99, an_off_p99 = [], []
        try:
            os.environ["PIO_TRACING"] = "1"
            for _ in range(repeats):
                os.environ["PIO_ANATOMY"] = "0"
                an_off_p99.append(
                    sweep(obs_cfg(), obs_level, "anatomy-off")
                    [obs_level[0]]["p99_ms"])
                os.environ["PIO_ANATOMY"] = "1"
                an_on_p99.append(
                    sweep(obs_cfg(), obs_level, "anatomy-on")
                    [obs_level[0]]["p99_ms"])
        finally:
            for name, old in (("PIO_ANATOMY", old_anatomy),
                              ("PIO_TRACING", old_tracing)):
                if old is None:
                    os.environ.pop(name, None)
                else:
                    os.environ[name] = old
        an_on_ms, an_off_ms = min(an_on_p99), min(an_off_p99)
        anatomy_pct = (100.0 * (an_on_ms - an_off_ms) / an_off_ms
                       if an_off_ms > 0 else 0.0)
        an_max_pct = float(os.environ.get("BENCH_ANATOMY_OVERHEAD_PCT",
                                          5.0))
        an_abs_ms = float(os.environ.get(
            "BENCH_ANATOMY_OVERHEAD_ABS_MS", 0.3))
        assert an_on_ms <= an_off_ms * (1 + an_max_pct / 100.0) \
            + an_abs_ms, (
            f"anatomy overhead breached: p99 {an_on_ms}ms with the "
            f"stage plane on vs {an_off_ms}ms off "
            f"(+{anatomy_pct:.1f}% > {an_max_pct}% + {an_abs_ms}ms)")
    finally:
        als_mod._DEVICE_ROUNDTRIP_S = old_rt

    # filter to THIS model's (catalog, rank): the bench worker is long-
    # lived and earlier configs may have registered their own ALS shapes
    shapes = sorted({k[0] for fam in ("als_topk", "als_topk_masked")
                     for k in fn_cache.family_keys(fam)
                     if k[2:] == (ni, rank)})
    bound = bucketing.bucket_count(max_batch)
    assert len(shapes) <= bound, (
        f"bucketing leak: {len(shapes)} compiled batch shapes {shapes} "
        f"> bound {bound}")
    top = clients[-1]
    detail = {
        "elapsed_s": round(elapsed, 3),
        "baseline_s": None,
        "queries_per_level": per_level,
        "distinct_compiled_batch_shapes": len(shapes),
        "compile_shape_bound": bound,
        "note": (f"{len(clients)}-level client sweep x {per_level} "
                 f"queries on synthetic {nu}x{ni} r{rank} factors, "
                 f"device scorer forced; {top}c p99 "
                 f"{piped[top]['p99_ms']}ms (single-in-flight "
                 f"{single[top]['p99_ms']}ms), mean batch "
                 f"{piped[top]['mean_batch']}; {len(shapes)} compiled "
                 f"batch shapes (bound {bound})"),
    }
    for n_clients, stats in piped.items():
        for key, val in stats.items():
            detail[f"{key}_{n_clients}c"] = val
    detail[f"p99_ms_{top}c_single_inflight"] = single[top]["p99_ms"]
    detail[f"mean_batch_{top}c_single_inflight"] = single[top]["mean_batch"]
    obs_c = obs_level[0]
    detail[f"p99_ms_{obs_c}c_obs_on"] = obs_on_ms
    detail[f"p99_ms_{obs_c}c_obs_off"] = obs_off_ms
    detail["obs_overhead_pct"] = round(overhead_pct, 2)
    detail[f"p99_ms_{obs_c}c_anatomy_on"] = an_on_ms
    detail[f"p99_ms_{obs_c}c_anatomy_off"] = an_off_ms
    detail["anatomy_overhead_pct"] = round(anatomy_pct, 2)
    detail["note"] += (f"; obs overhead {overhead_pct:+.1f}% at {obs_c}c "
                       f"(tracing+SLO p99 {obs_on_ms}ms vs obs-off "
                       f"{obs_off_ms}ms); anatomy overhead "
                       f"{anatomy_pct:+.1f}% ({an_on_ms}ms vs "
                       f"{an_off_ms}ms)")
    return detail


def cfg_deploy_swap(jax, mesh, platform):
    """Deploy lifecycle cutover: cold reload vs warm swap.

    A retrain must reach production without a compile stall — the warm
    path (deploy/warm.py) drives the candidate through the ops/bucketing
    shape ladder BEFORE cutover, so post-swap traffic hits only
    pre-compiled shapes. Measured per cycle, each with a FRESH catalog
    size (fresh shape keys => real compiles to pay somewhere):

      * cold: swap with warmup disabled, then time first-traffic bursts
        across the bucket ladder (they stall on serving-path compiles)
        and read the pio_jax_compile_total delta.
      * warm: same-shaped candidate warmed pre-cutover; same bursts.
        The compile delta across the swap MUST be zero (asserted — the
        acceptance criterion of the deploy subsystem).
    """
    import asyncio
    import functools

    import predictionio_tpu.models.als as als_mod
    from aiohttp.test_utils import TestClient, TestServer

    from predictionio_tpu.core.engine import Engine, TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.deploy.warm import ServingUnit, warmup_unit
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, Query, RecommendationServing)
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.obs.jax_stats import compile_counter
    from predictionio_tpu.obs.registry import default_registry
    from predictionio_tpu.server.query_server import create_query_server
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.utils.server_config import (
        DeployConfig, ServingConfig)

    nu = int(os.environ.get("BENCH_DEPLOY_USERS", 3000))
    ni_base = int(os.environ.get("BENCH_DEPLOY_ITEMS", 1500))
    cycles = int(os.environ.get("BENCH_DEPLOY_CYCLES", 3))
    rank, max_batch, num = 32, 16, 8
    rng = np.random.default_rng(17)

    def make_model(ni):
        return ALSModel(
            user_vocab=np.asarray([f"u{i:06d}" for i in range(nu)],
                                  dtype=object),
            item_vocab=np.asarray([f"i{i:06d}" for i in range(ni)],
                                  dtype=object),
            U=rng.normal(size=(nu, rank)).astype(np.float32),
            V=rng.normal(size=(ni, rank)).astype(np.float32))

    def make_unit(ni, tag):
        return ServingUnit(
            instance=EngineInstance(id=f"bench-{tag}-{ni}",
                                    engine_id="bench", engine_version="1",
                                    engine_variant="default"),
            result=TrainResult(models=[make_model(ni)],
                               algorithms=[ALSAlgorithm(AlgorithmParams())],
                               serving=RecommendationServing(),
                               engine_params=EngineParams()),
            ctx=None, vectorized=True)

    def total_compiles():
        return sum(v for _l, v in
                   compile_counter(default_registry()).samples())

    engine = Engine({}, {}, {"als": ALSAlgorithm}, {})
    server = create_query_server(
        engine, make_unit(ni_base, "incumbent").result,
        EngineInstance(id="bench-incumbent", engine_id="bench",
                       engine_version="1", engine_variant="default"),
        None,
        serving_config=ServingConfig(batch_max=max_batch,
                                     batch_linger_s=0.0, batch_inflight=2),
        deploy_config=DeployConfig(warmup=True, drain_timeout_s=5.0))

    ladder = [1, 2, 4, 8, 16]
    out = {"cold": [], "warm": []}

    async def burst(c, b, user_base):
        t0 = time.perf_counter()
        resp = await asyncio.gather(*[
            c.post("/queries.json",
                   json={"user": f"u{(user_base + i) % nu:06d}",
                         "num": num}) for i in range(b)])
        for r in resp:
            assert r.status == 200, await r.text()
            await r.json()
        return time.perf_counter() - t0

    async def cycle(c, ni, warm, tag):
        unit = make_unit(ni, tag)
        server._attach_batcher(unit)
        predict_batch = functools.partial(server._predict_batch_unit, unit)
        t0 = time.perf_counter()
        if warm:
            warmup_unit(unit, predict_batch, max_batch,
                        query=Query(user="u000000", num=num))
        prepare_s = time.perf_counter() - t0
        compiles_before = total_compiles()
        t0 = time.perf_counter()
        server._swap_to(unit, "warm" if warm else "cold", "bench")
        burst_s = [await burst(c, b, j * 101) for j, b in enumerate(ladder)]
        first_traffic_s = time.perf_counter() - t0
        return {
            "prepare_s": prepare_s,
            "first_traffic_s": first_traffic_s,
            "worst_burst_s": max(burst_s),
            "compile_delta": int(total_compiles() - compiles_before),
        }

    async def run_all():
        c = TestClient(TestServer(server.app))
        await c.start_server()
        try:
            await burst(c, 4, 0)           # incumbent warm-up / compile
            ni = ni_base
            for k in range(cycles):
                for mode in ("cold", "warm"):
                    ni += 7                # fresh catalog => fresh shapes
                    hb(f"deploy_swap cycle {k} {mode} ni={ni}")
                    out[mode].append(await cycle(c, ni, mode == "warm",
                                                 f"{mode}{k}"))
        finally:
            await c.close()

    # the host-BLAS crossover would hide the jit path on CPU; the shape
    # discipline under test is the TPU-serving one
    old_rt = als_mod._DEVICE_ROUNDTRIP_S
    als_mod._DEVICE_ROUNDTRIP_S = 0.0
    t0 = time.perf_counter()
    try:
        asyncio.run(run_all())
    finally:
        als_mod._DEVICE_ROUNDTRIP_S = old_rt
    elapsed = time.perf_counter() - t0

    warm_compiles = [c_["compile_delta"] for c_ in out["warm"]]
    assert all(d == 0 for d in warm_compiles), (
        f"warm swap paid post-cutover compiles: {warm_compiles}")
    cold_ms = 1e3 * float(np.mean(
        [c_["first_traffic_s"] for c_ in out["cold"]]))
    warm_ms = 1e3 * float(np.mean(
        [c_["first_traffic_s"] for c_ in out["warm"]]))
    detail = {
        "elapsed_s": round(elapsed, 3),
        "baseline_s": None,
        "cycles": cycles,
        "cold_first_traffic_ms": round(cold_ms, 3),
        "warm_first_traffic_ms": round(warm_ms, 3),
        "cold_worst_burst_ms": round(1e3 * float(np.max(
            [c_["worst_burst_s"] for c_ in out["cold"]])), 3),
        "warm_worst_burst_ms": round(1e3 * float(np.max(
            [c_["worst_burst_s"] for c_ in out["warm"]])), 3),
        "warm_prepare_ms": round(1e3 * float(np.mean(
            [c_["prepare_s"] for c_ in out["warm"]])), 3),
        "cold_post_swap_compiles": int(np.sum(
            [c_["compile_delta"] for c_ in out["cold"]])),
        "warm_post_swap_compiles": int(np.sum(warm_compiles)),
        "cutover_speedup": round(cold_ms / warm_ms, 2) if warm_ms else None,
        "note": (f"{cycles} cold vs {cycles} warm swap cycles on fresh "
                 f"{nu}x~{ni_base} r{rank} catalogs, ladder {ladder}; "
                 f"first-traffic {cold_ms:.0f}ms cold vs {warm_ms:.0f}ms "
                 "warm; warm pays its compiles pre-cutover "
                 f"(prepare {1e3 * float(np.mean([c_['prepare_s'] for c_ in out['warm']])):.0f}ms) "
                 "and ZERO after (asserted)"),
    }
    return detail


def cfg_train_ingest(jax, mesh, platform):
    """Training-ingest hot path: event store -> model-ready arrays, the
    old per-Event fold vs the columnar pipeline (find_columnar +
    vectorized aggregate/intern, data/ingest.py), swept over event
    counts (BENCH_INGEST_EVENTS). Reports rows/s for both paths plus the
    snapshot-digest cache-hit replay time. No device math — this measures
    the host-side layer between storage and XLA that used to dominate
    `pio train` (SURVEY §2.9 P2; the ALX flat-array ingest argument)."""
    import shutil
    import tempfile

    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.data.aggregator import (
        aggregate_properties as row_aggregate,
    )
    from predictionio_tpu.data.bimap import BiMap, assign_indices
    from predictionio_tpu.data.eventstore import EventStoreClient, clear_cache
    from predictionio_tpu.data.ingest import (
        event_columns, pair_counts, training_scan,
    )
    from predictionio_tpu.storage import App, Storage

    sizes = [int(s) for s in os.environ.get(
        "BENCH_INGEST_EVENTS", "20000,100000").split(",")]
    backends = os.environ.get(
        "BENCH_INGEST_BACKENDS", "parquet,sqlite").split(",")
    n_users, n_items = 2000, 500
    detail = {"sizes": sizes, "backends": backends}
    total_t0 = time.perf_counter()
    import datetime as dt

    UTC = dt.timezone.utc

    def seed_store(root, n, backend):
        if backend == "parquet":
            sources = {
                "DB": {"TYPE": "sqlite", "PATH": f"{root}/meta.db"},
                "PQ": {"TYPE": "parquet", "PATH": f"{root}/events"},
            }
            repos = {"METADATA": {"NAME": "pio", "SOURCE": "DB"},
                     "EVENTDATA": {"NAME": "pio", "SOURCE": "PQ"},
                     "MODELDATA": {"NAME": "pio", "SOURCE": "DB"}}
        else:
            sources = {"DB": {"TYPE": "sqlite",
                              "PATH": f"{root}/bench_ingest.db"}}
            repos = {r: {"NAME": "pio", "SOURCE": "DB"}
                     for r in ("METADATA", "EVENTDATA", "MODELDATA")}
        Storage.configure({"sources": sources, "repositories": repos})
        clear_cache()
        app_id = Storage.get_meta_data_apps().insert(
            App(id=0, name="BenchIngest"))
        store = Storage.get_events()
        store.init_channel(app_id)
        rng = np.random.default_rng(7)
        events = []
        t = 0
        for u in range(n_users):
            events.append(Event(
                event="$set", entity_type="user", entity_id=f"u{u}",
                properties=DataMap({"segment": int(u % 5)}),
                event_time=dt.datetime.fromtimestamp(
                    (t := t + 1) / 1000, tz=UTC)))
        ev_names = np.asarray(["rate", "buy"])[
            (rng.random(n) < 0.3).astype(np.int8)]
        us = rng.integers(0, n_users, n)
        its = rng.integers(0, n_items, n)
        rat = rng.integers(1, 6, n)
        for k in range(n):
            name = str(ev_names[k])
            events.append(Event(
                event=name, entity_type="user", entity_id=f"u{us[k]}",
                target_entity_type="item", target_entity_id=f"i{its[k]}",
                properties=(DataMap({"rating": float(rat[k])})
                            if name == "rate" else DataMap()),
                event_time=dt.datetime.fromtimestamp(
                    (t := t + 1) / 1000, tz=UTC)))
            if len(events) >= 10_000:
                store.insert_batch(events, app_id)
                events = []
        if events:
            store.insert_batch(events, app_id)

    def per_event_read():
        """The pre-columnar training read: per-Event iteration, python
        rating fold, dict-intern (collect + BiMap), row aggregate."""
        ratings = []
        for e in EventStoreClient.find(
                app_name="BenchIngest", entity_type="user",
                event_names=["rate", "buy"], target_entity_type="item"):
            v = (float(e.properties.get("rating")) if e.event == "rate"
                 else 4.0)
            ratings.append((e.entity_id, e.target_entity_id, v))
        u_map = BiMap.string_int(r[0] for r in ratings)
        i_map = BiMap.string_int(r[1] for r in ratings)
        u_codes = np.fromiter((u_map[r[0]] for r in ratings), np.int32,
                              len(ratings))
        i_codes = np.fromiter((i_map[r[1]] for r in ratings), np.int32,
                              len(ratings))
        users = row_aggregate(EventStoreClient.find(
            app_name="BenchIngest", entity_type="user",
            event_names=["$set", "$unset", "$delete"]))
        return len(ratings) + len(users), u_codes, i_codes

    def columnar_read(cache=False):
        """The columnar pipeline: one arrow scan, vectorized value fill,
        np.unique intern, columnar $set fold."""
        from predictionio_tpu.data.columnar import property_column

        scan = training_scan(
            "BenchIngest", entity_type="user",
            event_names=["rate", "buy"], target_entity_type="item",
            cache=cache,
            columns=("event", "entity_id", "target_entity_id",
                     "properties"))
        events, users, items = event_columns(
            scan.table, "event", "entity_id", "target_entity_id")
        is_rate = events == "rate"
        values = np.full(len(events), 4.0, np.float32)
        if is_rate.any():
            import pyarrow as pa

            values[is_rate] = property_column(
                scan.table.filter(pa.array(is_rate)), "rating")
        _, u_codes = assign_indices(users)
        _, i_codes = assign_indices(items)
        props = EventStoreClient.aggregate_properties("BenchIngest", "user")
        return len(values) + len(props), u_codes, i_codes

    for backend in backends:
        for n in sizes:
            root = tempfile.mkdtemp(prefix="pio_bench_ingest_")
            try:
                hb(f"train_ingest seed {backend} {n}")
                seed_store(root, n, backend)
                hb(f"train_ingest per-event {backend} {n}")
                # same best-of-3 discipline as the columnar side, so a
                # stray stall can never inflate the reported speedup
                pe_s, (rows_pe, upe, ipe) = timed_best(per_event_read)
                hb(f"train_ingest columnar {backend} {n}")
                col_s, (rows_col, uc, ic) = timed_best(
                    lambda: columnar_read(cache=False))
                # parity: both paths interned the identical code streams
                assert rows_col == rows_pe and np.array_equal(upe, uc) \
                    and np.array_equal(ipe, ic), "ingest paths disagree"
                columnar_read(cache=True)      # prime the digest cache
                hit_s, _ = timed_best(lambda: columnar_read(cache=True))
                k = f"{backend}_{n}"
                detail[f"rows_per_s_per_event_{k}"] = round(rows_pe / pe_s)
                detail[f"rows_per_s_columnar_{k}"] = round(rows_col / col_s)
                detail[f"speedup_{k}"] = round(pe_s / col_s, 2)
                detail[f"cache_hit_s_{k}"] = round(hit_s, 4)
            finally:
                Storage.reset()
                clear_cache()
                shutil.rmtree(root, ignore_errors=True)
    top = f"{backends[0]}_{sizes[-1]}"
    detail["elapsed_s"] = round(time.perf_counter() - total_t0, 2)
    detail["speedup_headline"] = detail[f"speedup_{top}"]
    detail["note"] = (
        f"columnar ingest {detail[f'speedup_{top}']}x per-event on "
        f"{backends[0]} at {sizes[-1]} events "
        f"({detail[f'rows_per_s_columnar_{top}']} vs "
        f"{detail[f'rows_per_s_per_event_{top}']} rows/s); cache-hit "
        f"replay {detail[f'cache_hit_s_{top}']}s; "
        + "; ".join(f"{b}: {detail[f'speedup_{b}_{sizes[-1]}']}x"
                    for b in backends))
    return detail


def cfg_ingest_write(jax, mesh, platform):
    """Event WRITE hot path: the per-request insert (one storage
    transaction per HTTP request — the pre-PR6 event server) vs the
    group-commit WriteBuffer (data/write_buffer.py: bounded queue +
    dedicated writer coalescing concurrent submits into few insert_batch
    flushes), on sqlite and parquet. Per-request drives C concurrent
    client threads (the aiohttp executor shape); grouped drives an
    open-loop submitter with a bounded outstanding window (the event
    loop + per-request futures shape) and measures ack latency
    submit->resolve. Asserts the tentpole bar: grouped sustains >=
    BENCH_INGEST_WRITE_MIN_SPEEDUP x the per-request events/s (default
    5) with bounded ack p99, and zero loss/duplication at bench scale
    (row count == submissions). No device math — this is the storage-SPI
    analog of what the reference delegated to HBase/ES.

    PR 17 adds the partition-scaling curve: the same open-loop submitter
    drives a PartitionedEvents store (storage/partitioned.py) through
    1/2/4 commit lanes (WriteBuffer partitions=P) under an injected
    per-flush commit wall (FaultyEvents latency on insert_batch). On a
    single-host bench the raw sqlite fsync is so short that the GIL
    serialises the lanes; production commit walls (fsync on real disks,
    object-store PUTs) are tens of ms, so the wall makes the bench
    latency-realistic AND lets lanes genuinely overlap. The injected
    floor is recorded in the detail dict (commit_floor_ms,
    commit_floor_injected) — same disclosure discipline as the device
    benches' scaled_for_cpu flag. Asserts >=
    BENCH_INGEST_WRITE_MIN_SCALING (default 2.5) sustained events/s at
    4 partitions vs 1, with exactly-once row counts per curve point."""
    import datetime as dt
    import shutil
    import tempfile
    import threading

    from predictionio_tpu.data.event import Event, UTC
    from predictionio_tpu.data.write_buffer import WriteBuffer
    from predictionio_tpu.obs.registry import MetricsRegistry

    n_grouped = int(os.environ.get("BENCH_INGEST_WRITE_EVENTS", 24576))
    clients = int(os.environ.get("BENCH_INGEST_WRITE_CLIENTS", 16))
    backends = os.environ.get(
        "BENCH_INGEST_WRITE_BACKENDS", "sqlite,parquet").split(",")
    min_speedup = float(os.environ.get("BENCH_INGEST_WRITE_MIN_SPEEDUP", 5))
    p99_bound_ms = float(os.environ.get("BENCH_INGEST_WRITE_P99_MS", 2000))
    detail = {"clients": clients, "events_grouped": n_grouped,
              "min_speedup": min_speedup}
    total_t0 = time.perf_counter()
    APP = 7

    def build_events(n, seed_off=0):
        base = dt.datetime(2026, 1, 1, tzinfo=UTC)
        return [Event(
            event="view", entity_type="user",
            entity_id=f"u{(seed_off + i) % 5000}",
            target_entity_type="item", target_entity_id=f"i{i % 800}",
            event_time=base + dt.timedelta(seconds=seed_off + i))
            for i in range(n)]

    def make_store(root, backend):
        if backend == "parquet":
            from predictionio_tpu.storage.parquet_events import (
                ParquetEvents, ParquetEventsClient)
            store = ParquetEvents(ParquetEventsClient(f"{root}/events"))
        else:
            from predictionio_tpu.storage.sqlite_backend import (
                SqliteClient, SqliteEvents)
            store = SqliteEvents(SqliteClient(f"{root}/events.db"))
        store.init_channel(APP)
        return store

    def run_per_request(store, events):
        """The old path: C concurrent requests, each one insert/txn."""
        lat, lock = [], threading.Lock()
        per = len(events) // clients

        def client(c):
            mine = []
            for k in range(per):
                t0 = time.perf_counter()
                store.insert(events[c * per + k], APP)
                mine.append(time.perf_counter() - t0)
            with lock:
                lat.extend(mine)

        t0 = time.perf_counter()
        # pio: ignore[PIO003]: load-generator clients; traces measured server-side
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat.sort()
        return per * clients / wall, lat[int(0.99 * len(lat))] * 1000

    def run_grouped(store, events, registry):
        """The new path: open-loop submits with a bounded outstanding
        window; ack latency is submit -> future resolved. The drive
        itself is the shared loadtest harness (loadtest/harness.py) —
        the same discipline the workload simulator storms with."""
        from predictionio_tpu.loadtest.harness import drive_open_loop

        buf = WriteBuffer(store_fn=lambda: store, flush_max=512,
                          linger_s=0.002, queue_max=1 << 20,
                          registry=registry)
        res = drive_open_loop(events, lambda e: buf.submit([e], APP),
                              max_outstanding=1024, timeout_s=600)
        buf.stop()
        assert not res.timed_out, "grouped ingest did not complete"
        assert res.dropped == 0 and res.failed == 0, (
            f"grouped ingest dropped={res.dropped} failed={res.failed}")
        return res.events_per_s(), res.ledger.percentile_ms(99)

    for backend in backends:
        # per-request side needs far fewer events for a stable rate —
        # and on parquet every one is a whole fragment file, which the
        # exactly-once row-count scan must re-read
        n_pr = max(clients, min(n_grouped // 8,
                                768 if backend == "parquet" else 4096))
        hb(f"ingest_write per-request {backend}")
        root_pr = tempfile.mkdtemp(prefix="pio_bench_ingw_pr_")
        root_g = tempfile.mkdtemp(prefix="pio_bench_ingw_g_")
        try:
            store = make_store(root_pr, backend)
            eps_pr, p99_pr = max(
                run_per_request(store, build_events(n_pr, i * n_pr))
                for i in range(2))
            # each round inserts per*clients (truncated division)
            assert store.find_columnar(APP).num_rows \
                == 2 * (n_pr // clients) * clients
            hb(f"ingest_write grouped {backend}")
            store_g = make_store(root_g, backend)
            half = n_grouped // 2
            reg = MetricsRegistry()
            eps_g, p99_g = max(
                run_grouped(store_g, build_events(half, i * half), reg)
                for i in range(2))
            # zero loss, zero duplication at bench scale
            assert store_g.find_columnar(APP).num_rows == 2 * half, \
                "grouped ingest lost or duplicated events"
            flushes = reg.get("pio_ingest_flush_size")
            speedup = eps_g / eps_pr
            detail[f"events_per_s_per_request_{backend}"] = round(eps_pr)
            detail[f"events_per_s_grouped_{backend}"] = round(eps_g)
            detail[f"p99_ms_per_request_{backend}"] = round(p99_pr, 1)
            detail[f"p99_ms_grouped_{backend}"] = round(p99_g, 1)
            detail[f"speedup_{backend}"] = round(speedup, 2)
            detail[f"mean_flush_{backend}"] = round(
                flushes.total_sum() / max(1, flushes.total_count()), 1)
            assert speedup >= min_speedup, (
                f"group commit on {backend}: {speedup:.1f}x < "
                f"{min_speedup}x over the per-request path")
            assert p99_g <= p99_bound_ms, (
                f"grouped ack p99 {p99_g:.0f}ms breaches the "
                f"{p99_bound_ms:.0f}ms bound on {backend}")
        finally:
            shutil.rmtree(root_pr, ignore_errors=True)
            shutil.rmtree(root_g, ignore_errors=True)

    # -- partition scaling curve (PR 17) ---------------------------------
    from predictionio_tpu.storage.faults import FaultyEvents
    from predictionio_tpu.storage.partitioned import (
        PartitionedEvents, SqlitePartitions)

    n_scale = int(os.environ.get("BENCH_INGEST_SCALING_EVENTS", 8192))
    floor_ms = float(os.environ.get("BENCH_INGEST_COMMIT_FLOOR_MS", 30))
    min_scaling = float(os.environ.get("BENCH_INGEST_WRITE_MIN_SCALING", 2.5))
    curve_points = tuple(
        int(p) for p in os.environ.get(
            "BENCH_INGEST_SCALING_PARTITIONS", "1,2,4").split(","))

    def run_partitioned(parts):
        """Open-loop batched submits against P commit lanes, every flush
        paying the injected commit wall. Returns sustained events/s."""
        root = tempfile.mkdtemp(prefix="pio_bench_ingw_part_")
        try:
            store = PartitionedEvents(
                SqlitePartitions(f"{root}/events.db"), initial_count=parts)
            store.init_channel(APP)
            walled = FaultyEvents(
                store, latency_s=floor_ms / 1000.0, ops=("insert_batch",))
            # flush_max caps what one lane can amortise per wall payment,
            # so the single-lane baseline is wall-limited (the production
            # regime) rather than GIL-limited (the 1-core bench artifact)
            buf = WriteBuffer(store_fn=lambda: walled, flush_max=256,
                              linger_s=0.004, queue_max=1 << 20,
                              partitions=parts, registry=MetricsRegistry())
            from predictionio_tpu.loadtest.harness import drive_open_loop

            events = build_events(n_scale)
            batches = [events[i:i + 256]
                       for i in range(0, n_scale, 256)]
            res = drive_open_loop(
                batches, lambda b: buf.submit(b, APP),
                max_outstanding=24, weight=len, timeout_s=600)
            buf.stop()
            assert not res.timed_out and res.dropped == 0 \
                and res.failed == 0, (
                    f"partitioned ingest (P={parts}) dropped="
                    f"{res.dropped} failed={res.failed} "
                    f"timed_out={res.timed_out}")
            # exactly-once at every curve point, through the lane split
            assert store.find_columnar(APP).num_rows == n_scale, \
                f"partitioned ingest (P={parts}) lost or duplicated events"
            store.close()
            return res.events_per_s()
        finally:
            shutil.rmtree(root, ignore_errors=True)

    curve = {}
    for parts in curve_points:
        hb(f"ingest_write partitions={parts}")
        curve[parts] = max(run_partitioned(parts) for _ in range(2))
        detail[f"partition_events_per_s_{parts}"] = round(curve[parts])
    base_p = curve_points[0]
    for parts in curve_points[1:]:
        detail[f"partition_scaling_{parts}x"] = round(
            curve[parts] / curve[base_p], 2)
    detail["commit_floor_ms"] = floor_ms
    detail["commit_floor_injected"] = floor_ms > 0
    detail["min_scaling"] = min_scaling
    top_p = curve_points[-1]
    scaling = curve[top_p] / curve[base_p]
    detail["scaling_headline"] = round(scaling, 2)
    assert scaling >= min_scaling, (
        f"partitioned ingest: {scaling:.2f}x at {top_p} partitions < "
        f"{min_scaling}x over {base_p} (commit floor {floor_ms}ms)")

    detail["elapsed_s"] = round(time.perf_counter() - total_t0, 2)
    detail["speedup_headline"] = detail[f"speedup_{backends[0]}"]
    detail["note"] = (
        "group-commit ingest vs per-request writes: "
        + "; ".join(
            f"{b}: {detail[f'speedup_{b}']}x "
            f"({detail[f'events_per_s_grouped_{b}']} vs "
            f"{detail[f'events_per_s_per_request_{b}']} ev/s, "
            f"ack p99 {detail[f'p99_ms_grouped_{b}']}ms)"
            for b in backends)
        + f"; partition lanes ({floor_ms}ms commit wall): "
        + " -> ".join(
            f"P={p} {detail[f'partition_events_per_s_{p}']} ev/s"
            for p in curve_points)
        + f" = {detail['scaling_headline']}x at {top_p} partitions")
    return detail


def cfg_foldin_freshness(jax, mesh, platform):
    """Online fold-in: the event→serving freshness loop (deploy/foldin.py).

    Two measurements:

    1. **fold-ins/sec, batched vs one-at-a-time** — the same
       `FoldInSolver` solves B pending user rows as ONE bucketed device
       program vs B single-row dispatches. The batched path's win is the
       tentpole bar (>= BENCH_FOLDIN_MIN_SPEEDUP, default 5x): per-row
       dispatch overhead is exactly what an online path cannot afford.
       Also asserts the `als_foldin` compile ledger stays inside the
       power-of-two bucket ladder.
    2. **p50/p95 event→reflected seconds** — an open-loop event stream
       (new users' rate events submitted through the group-commit
       WriteBuffer with the fold-in push tap armed) races a
       recommendation PROBE that polls the query server's predict path
       until each user appears; the controller applies on a timer
       thread at BENCH_FOLDIN_INTERVAL_S. Asserts the headline bound:
       p95 <= apply interval + one (warm) apply + slack.
    """
    import shutil
    import tempfile
    import threading

    from predictionio_tpu.core.engine import TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.data.datamap import DataMap
    from predictionio_tpu.data.event import Event, UTC
    from predictionio_tpu.data.write_buffer import WriteBuffer
    from predictionio_tpu.deploy.foldin import FoldInController
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, DataSourceParams, Query,
        RecommendationServing)
    from predictionio_tpu.models.als import ALSModel, ALSParams, FoldInSolver
    from predictionio_tpu.ops.bucketing import bucket_count
    from predictionio_tpu.ops.fn_cache import family_keys
    from predictionio_tpu.server.query_server import QueryServer
    from predictionio_tpu.storage.base import App, EngineInstance
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.utils.server_config import (
        DeployConfig, FoldinConfig, ServingConfig)
    import datetime as dt

    total_t0 = time.perf_counter()
    nu = int(os.environ.get("BENCH_FOLDIN_USERS", 3000))
    ni = int(os.environ.get("BENCH_FOLDIN_ITEMS", 1500))
    rank = int(os.environ.get("BENCH_FOLDIN_RANK", 32))
    solve_batch = int(os.environ.get("BENCH_FOLDIN_SOLVE_BATCH", 256))
    ratings_per = int(os.environ.get("BENCH_FOLDIN_EVENTS_PER_USER", 8))
    stream_users = int(os.environ.get("BENCH_FOLDIN_STREAM_USERS", 120))
    interval_s = float(os.environ.get("BENCH_FOLDIN_INTERVAL_S", 0.25))
    min_speedup = float(os.environ.get("BENCH_FOLDIN_MIN_SPEEDUP", 5))
    p95_slack = float(os.environ.get("BENCH_FOLDIN_P95_SLACK", 0.5))
    detail = {"rank": rank, "solve_batch": solve_batch,
              "apply_interval_s": interval_s,
              "stream_users": stream_users,
              "events_per_user": ratings_per}
    rng = np.random.default_rng(17)

    # ---- 1) batched vs one-at-a-time fold-ins/sec ------------------------
    hb("foldin solver warmup")
    V = rng.normal(size=(ni, rank)).astype(np.float32)
    params = ALSParams(rank=rank, reg=0.05)
    solver = FoldInSolver(V, params)
    rated = [rng.choice(ni, size=ratings_per, replace=False)
             for _ in range(solve_batch)]
    values = [np.clip(rng.normal(3.0, 1.0, ratings_per), 1, 5
                      ).astype(np.float32) for _ in range(solve_batch)]
    solver.solve(rated, values)                   # compile batched shape
    solver.solve(rated[:1], values[:1])           # compile B=1 shape
    hb("foldin solver timed")

    def time_batched():
        t0 = time.perf_counter()
        solver.solve(rated, values)
        return solve_batch / (time.perf_counter() - t0)

    def time_sequential():
        t0 = time.perf_counter()
        for r, v in zip(rated, values):
            solver.solve([r], [v])
        return solve_batch / (time.perf_counter() - t0)

    fps_batched = max(time_batched() for _ in range(2))
    fps_seq = max(time_sequential() for _ in range(2))
    speedup = fps_batched / fps_seq
    ledger = [k for k in family_keys("als_foldin")
              if k[0] == (ni, rank)]
    ledger_bound = 2 * bucket_count(solve_batch) + 2
    detail.update({
        "foldins_per_s_batched": round(fps_batched, 1),
        "foldins_per_s_sequential": round(fps_seq, 1),
        "speedup_batched": round(speedup, 2),
        "foldin_compiled_shapes": len(ledger),
        "foldin_shape_bound": ledger_bound,
    })
    assert 0 < len(ledger) <= ledger_bound, (len(ledger), ledger_bound)
    assert speedup >= min_speedup, (
        f"batched fold-in {speedup:.1f}x < {min_speedup}x over "
        "one-at-a-time")

    # ---- 2) open-loop event stream vs recommendation probe ---------------
    hb("foldin freshness loop")
    root = tempfile.mkdtemp(prefix="pio_bench_foldin_")
    try:
        Storage.configure({
            "sources": {"DB": {"TYPE": "sqlite",
                               "PATH": f"{root}/events.db"}},
            "repositories": {
                "METADATA": {"NAME": "pio", "SOURCE": "DB"},
                "EVENTDATA": {"NAME": "pio", "SOURCE": "DB"},
                "MODELDATA": {"NAME": "pio", "SOURCE": "DB"},
            }})
        app_id = Storage.get_meta_data_apps().insert(
            App(id=0, name="FoldinBench"))
        Storage.get_events().init_channel(app_id)
        model = ALSModel(
            user_vocab=np.asarray([f"u{i:06d}" for i in range(nu)],
                                  dtype=object),
            item_vocab=np.asarray([f"i{i:06d}" for i in range(ni)],
                                  dtype=object),
            U=rng.normal(size=(nu, rank)).astype(np.float32),
            V=V)
        result = TrainResult(
            models=[model],
            algorithms=[ALSAlgorithm(AlgorithmParams(rank=rank))],
            serving=RecommendationServing(),
            engine_params=EngineParams(
                data_source_params=DataSourceParams(
                    app_name="FoldinBench")))
        instance = EngineInstance(
            id="foldin-bench", engine_id="bench", engine_version="1",
            engine_variant="default", status="COMPLETED")
        server = QueryServer(
            None, result, instance, ctx=None,
            serving_config=ServingConfig(batch_max=16, batch_linger_s=0.0),
            deploy_config=DeployConfig(warmup=False))
        ctl = FoldInController(
            server, FoldinConfig(enabled=True,
                                 apply_interval_s=interval_s,
                                 max_pending=4 * stream_users),
            registry=server.registry)
        ctl.start()                       # arms the push tap (no loop)
        buf = WriteBuffer(linger_s=0.001, flush_max=256)

        stop = threading.Event()
        apply_s: list = []

        def apply_loop():
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    out = ctl.apply_pending()
                except Exception:
                    import traceback

                    traceback.print_exc()
                    out = None
                if out is not None:
                    apply_s.append(time.perf_counter() - t0)
                stop.wait(interval_s)

        applier = threading.Thread(target=apply_loop, daemon=True)
        applier.start()

        def stream_one(uid: str):
            when = dt.datetime.now(tz=UTC)
            items = rng.choice(ni, size=ratings_per, replace=False)
            evs = [Event(event="rate", entity_type="user", entity_id=uid,
                         target_entity_type="item",
                         target_entity_id=f"i{j:06d}",
                         properties=DataMap({"rating": 4.0}),
                         event_time=when) for j in items]
            buf.submit(evs, app_id)
            return time.monotonic()

        def probe_until(uid: str, deadline_s: float = 60.0):
            q = Query(user=uid, num=10)
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if server._predict(q).item_scores:
                    return time.monotonic()
                time.sleep(0.002)
            raise AssertionError(f"user {uid} never reflected")

        # warm the streaming shapes (first applies pay XLA compiles)
        for w in range(2):
            t0 = stream_one(f"warm{w:04d}")
            probe_until(f"warm{w:04d}")
        apply_s.clear()

        lat: list = []
        for n in range(stream_users):
            t_post = stream_one(f"fresh{n:05d}")
            # open loop: a new user every few ms, several per apply tick
            time.sleep(0.004)
            if n % 4 == 3:      # probe a sample of the stream, inline
                t_ref = probe_until(f"fresh{n:05d}")
                lat.append(t_ref - t_post)
        # drain: every streamed user must reflect
        t_ref = probe_until(f"fresh{stream_users - 1:05d}")
        stop.set()
        applier.join(timeout=10)
        ctl.stop_tap()
        buf.stop()
        lat.sort()
        p50 = lat[len(lat) // 2]
        p95 = lat[min(len(lat) - 1, int(0.95 * len(lat)))]
        max_apply = max(apply_s) if apply_s else 0.0
        bound = interval_s + max_apply + p95_slack
        detail.update({
            "p50_event_to_reflected_s": round(p50, 4),
            "p95_event_to_reflected_s": round(p95, 4),
            "max_warm_apply_s": round(max_apply, 4),
            "p95_bound_s": round(bound, 4),
            "applies": ctl.applies,
            "applied_user_rows": ctl.applied_users,
        })
        assert ctl.applied_users >= stream_users
        assert p95 <= bound, (
            f"p95 event->reflected {p95:.3f}s exceeds bound {bound:.3f}s "
            f"(interval {interval_s}s + apply {max_apply:.3f}s + slack)")
    finally:
        Storage.reset()
        shutil.rmtree(root, ignore_errors=True)
    detail["elapsed_s"] = round(time.perf_counter() - total_t0, 2)
    detail["speedup_headline"] = detail["speedup_batched"]
    detail["note"] = (
        f"online fold-in: batched solve {fps_batched:.0f} rows/s vs "
        f"{fps_seq:.0f} one-at-a-time ({speedup:.1f}x, B={solve_batch} "
        f"r{rank}); event->reflected p50 {p50 * 1000:.0f}ms / p95 "
        f"{p95 * 1000:.0f}ms at {interval_s}s apply interval "
        f"({stream_users} streamed users, {ctl.applies} applies); "
        f"{len(ledger)} compiled shapes (bound {ledger_bound})")
    return detail


def _batchpredict_result(nu, ni, rank, seed=11):
    """Synthetic trained recommendation engine (no storage, no train):
    the deterministic fixture shared by the parent bench AND the sharded
    worker children, so every process scores the identical model."""
    from predictionio_tpu.core.engine import TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing)
    from predictionio_tpu.models.als import ALSModel

    rng = np.random.default_rng(seed)
    model = ALSModel(
        user_vocab=np.asarray([f"u{i:06d}" for i in range(nu)],
                              dtype=object),
        item_vocab=np.asarray([f"i{i:06d}" for i in range(ni)],
                              dtype=object),
        U=rng.normal(size=(nu, rank)).astype(np.float32),
        V=rng.normal(size=(ni, rank)).astype(np.float32))
    return TrainResult(models=[model],
                       algorithms=[ALSAlgorithm(AlgorithmParams())],
                       serving=RecommendationServing(),
                       engine_params=EngineParams())


def _batchpredict_sequential(result, input_path, output_path, chunk_size):
    """Frozen replica of the pre-pipeline `run_batch_predict` (the
    66-line sequential-chunk loop this PR replaced): line-by-line JSON
    parse, per-chunk batch_predict, asdict/to_dict serialization and
    synchronous per-line writes, all interleaved on one thread. Kept
    here verbatim as the measured baseline — the shared engine kernels
    underneath are today's, so the ratio isolates the architecture
    (pipelining + columnar serialization + sharding), not kernel drift."""
    import dataclasses as _dc

    from predictionio_tpu.core.params import params_from_json
    from predictionio_tpu.server.query_server import _query_class

    qc = _query_class(result)

    def _to_jsonable(obj):
        if hasattr(obj, "to_dict"):
            return obj.to_dict()
        if _dc.is_dataclass(obj) and not isinstance(obj, type):
            return _dc.asdict(obj)
        return obj

    def _process_chunk(chunk, fout):
        queries = [params_from_json(q, qc) if qc else q for q in chunk]
        supplemented = [(i, result.serving.supplement(q))
                        for i, q in enumerate(queries)]
        per_algo = []
        for algo, model in zip(result.algorithms, result.models):
            per_algo.append(dict(algo.batch_predict(model, supplemented)))
        for i, (raw, q) in enumerate(zip(chunk, queries)):
            predictions = [preds[i] for preds in per_algo]
            served = result.serving.serve(q, predictions)
            fout.write(json.dumps(
                {"query": raw, "prediction": _to_jsonable(served)},
                sort_keys=True) + "\n")
        return len(chunk)

    n = 0
    # pio: ignore[PIO002]: measurement baseline in a run-local temp dir
    with open(input_path) as fin, open(output_path, "w") as fout:
        chunk = []
        for line in fin:
            line = line.strip()
            if not line:
                continue
            chunk.append(json.loads(line))
            if len(chunk) >= chunk_size:
                n += _process_chunk(chunk, fout)
                chunk = []
        if chunk:
            n += _process_chunk(chunk, fout)
    return n


def _batchpredict_worker():
    """Sharded child entry: `python -c "import bench;
    bench._batchpredict_worker()"` with the fixture shape in BENCH_BP_*
    env and the shard identity in PIO_PROCESS_ID / PIO_NUM_PROCESSES —
    exactly how an operator runs a batchpredict fleet, minus `pio`.

    Rendezvous files keep one-time process setup (interpreter + jax
    import, model restore, BLAS probe warmup) OUT of the parent's
    measured window: the child warms up, drops `<out>.ready-<rank>`,
    and scores only once `<out>.go` appears — the fleet analog of
    serving_batching compiling its shape ladder outside the timed
    sweep. Steady-state throughput is the judged number; spawn cost is
    one-time and reported by the parent as `shard_spawn_s`."""
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    result = _batchpredict_result(
        int(os.environ["BENCH_BP_USERS"]),
        int(os.environ["BENCH_BP_ITEMS"]),
        int(os.environ["BENCH_BP_RANK"]))
    out = os.environ["BENCH_BP_OUTPUT"]
    chunk = int(os.environ["BENCH_BP_CHUNK"])
    rank = os.environ["PIO_PROCESS_ID"]  # pio: ignore[PIO006]: spawned shard reads its own rank wiring
    warm_in = os.environ.get("BENCH_BP_WARM_INPUT")
    if warm_in:
        # rank-unique warm path: sharded children share BENCH_BP_OUTPUT,
        # and two warm passes racing the same file can unlink each other
        warm_out = f"{out}.warm-{rank}"
        run_batch_predict(None, None, warm_in, warm_out,
                          chunk_size=chunk, loaded=(result, None),
                          worker=(0, 1))
        os.unlink(warm_out)
    # pio: ignore[PIO002]: empty rendezvous sentinel, no content to tear
    with open(f"{out}.ready-{rank}", "w") as f:
        f.write("ready")
    deadline = time.time() + 120
    while not os.path.exists(f"{out}.go"):
        if time.time() > deadline:
            raise TimeoutError("no go signal from the bench parent")
        time.sleep(0.005)
    run_batch_predict(
        None, None, os.environ["BENCH_BP_INPUT"], out,
        chunk_size=chunk, loaded=(result, None))


def _assert_parquet_value_parity(parquet_path, jsonl_path):
    """The parquet output (structured wire columns OR the JSON-string
    layout) must carry exactly the sequential baseline's values, row for
    row: parse both sides back to plain objects and compare — the
    order-normalized byte-identity bar of the acceptance criteria, made
    format-agnostic."""
    import pyarrow.parquet as pq

    table = pq.read_table(parquet_path)
    queries = table.column("query").to_pylist()
    preds = table.column("prediction").to_pylist()
    with open(jsonl_path) as f:
        expect = [json.loads(line) for line in f if line.strip()]
    assert len(queries) == len(expect), (
        f"parquet row count {len(queries)} != baseline {len(expect)}")
    for i, (q, p, e) in enumerate(zip(queries, preds, expect)):
        if isinstance(p, str):
            p = json.loads(p)
        assert json.loads(q) == e["query"], f"query row {i} differs"
        assert p == e["prediction"], f"prediction row {i} differs"


def cfg_batch_predict(jax, mesh, platform):
    """Offline batch scoring: the pre-PR sequential-chunk loop vs the
    pipelined reader->scorer->writer, and vs a 2-process sharded fleet
    (contiguous row ranges + manifest merge) — queries/sec, best-of-2.

    Asserts the tentpole bar: byte-identical output across all three
    paths, the compile-shape ledger bounded by the bucket ladder when
    the device scorer is forced, and the throughput floor
    (BENCH_BP_MIN_SPEEDUP, default 4x) for the best parallel path over
    the sequential baseline. The workload is serialization-heavy
    (num=50 recommendations/query) — the regime offline exports live
    in, and the one the columnar lane + pipelining attack; the sharded
    side then scales the remaining per-process Python with the fleet,
    the way ALX lays offline factorization across chips."""
    import glob
    import tempfile

    import predictionio_tpu.models.als as als_mod
    from predictionio_tpu.ops import bucketing, fn_cache
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    nu = int(os.environ.get("BENCH_BP_USERS", 5000))
    ni = int(os.environ.get("BENCH_BP_ITEMS", 2000))
    rank = int(os.environ.get("BENCH_BP_RANK", 32))
    num = int(os.environ.get("BENCH_BP_NUM", 50))
    n_queries = int(os.environ.get("BENCH_BP_QUERIES", 40000))
    chunk = int(os.environ.get("BENCH_BP_CHUNK", 1024))
    shards = int(os.environ.get("BENCH_BP_SHARDS", 2))
    min_speedup = float(os.environ.get("BENCH_BP_MIN_SPEEDUP", 4.0))
    min_pipe = float(os.environ.get("BENCH_BP_MIN_PIPE", 1.1))

    result = _batchpredict_result(nu, ni, rank)
    work = tempfile.mkdtemp(prefix="bench_bp_")
    inp = os.path.join(work, "queries.jsonl")
    # pio: ignore[PIO002]: bench input fixture in a run-local temp dir
    with open(inp, "w") as f:
        for i in range(n_queries):
            f.write(json.dumps({"user": f"u{i % nu:06d}", "num": num})
                    + "\n")

    def read(path):
        with open(path) as f:
            return f.read()

    # warm the BLAS/crossover probes and caches outside every measured
    # window, symmetrically for both sides (a chunk-sized slice is
    # enough — the measured runs below then start hot)
    hb("batch_predict warmup")
    warm_in = os.path.join(work, "warm_in.jsonl")
    # pio: ignore[PIO002]: bench input fixture in a run-local temp dir
    with open(inp) as f, open(warm_in, "w") as g:
        for _ in range(min(n_queries, chunk + 1)):
            g.write(f.readline())
    _batchpredict_sequential(result, warm_in,
                             os.path.join(work, "warm1.jsonl"), chunk)
    run_batch_predict(None, None, warm_in,
                      os.path.join(work, "warm2.jsonl"),
                      chunk_size=chunk, loaded=(result, None))

    hb("batch_predict sequential baseline")
    seq_out = os.path.join(work, "seq.jsonl")
    seq_s, _ = timed_best(
        lambda: _batchpredict_sequential(result, inp, seq_out, chunk),
        repeats=2)

    hb("batch_predict pipelined")
    pipe_out = os.path.join(work, "pipe.jsonl")
    pipe_s, pipe_report = timed_best(
        lambda: run_batch_predict(None, None, inp, pipe_out,
                                  chunk_size=chunk, loaded=(result, None)),
        repeats=2)
    assert read(pipe_out) == read(seq_out), \
        "pipelined output differs from the sequential baseline"

    # columnar output: same pipeline, parquet sink fed by the engine's
    # arrow lane — scores leave as ONE structured column per chunk, no
    # per-row Python objects anywhere between top-k and the file. This
    # is the tentpole throughput path; its speedup rides the headline.
    hb("batch_predict pipelined parquet")
    cols_out = os.path.join(work, "pipe.parquet")
    cols_s, _ = timed_best(
        lambda: run_batch_predict(None, None, inp, cols_out,
                                  chunk_size=chunk, loaded=(result, None)),
        repeats=2)
    _assert_parquet_value_parity(cols_out, seq_out)

    # sharded fleet: N real processes over contiguous row ranges, merged
    # by manifest. One-time setup (spawn, jax import, model restore)
    # stays outside the window via the worker's ready/go rendezvous;
    # it is reported separately as shard_spawn_s. The shard children are
    # CPU processes (a chip belongs to ONE process, and this worker holds
    # it), so the leg runs only when this worker is on the CPU too: no
    # number from a CPU process sits in a row that carries a chip's
    # platform/device_kind.
    run_shards = platform == "cpu"
    hb(f"batch_predict sharded x{shards}"
       + ("" if run_shards else " SKIPPED (worker holds the chip)"))
    shard_out = os.path.join(work, "shard.parquet")
    repo_root = os.path.dirname(os.path.abspath(__file__))
    child_env = {**os.environ,
                 "JAX_PLATFORMS": "cpu",
                 "BENCH_BP_USERS": str(nu), "BENCH_BP_ITEMS": str(ni),
                 "BENCH_BP_RANK": str(rank), "BENCH_BP_CHUNK": str(chunk),
                 "BENCH_BP_INPUT": inp, "BENCH_BP_OUTPUT": shard_out,
                 "BENCH_BP_WARM_INPUT": warm_in,
                 "PIO_NUM_PROCESSES": str(shards)}
    spawn_s = [0.0]

    def run_sharded():
        for stale in glob.glob(shard_out + "*"):
            os.unlink(stale)
        t_spawn = time.perf_counter()
        procs = [subprocess.Popen(
            [sys.executable, "-c",
             "import bench; bench._batchpredict_worker()"],
            cwd=repo_root, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env={**child_env, "PIO_PROCESS_ID": str(p)})
            for p in range(shards)]
        try:
            deadline = time.time() + 300
            while not all(os.path.exists(f"{shard_out}.ready-{p}")
                          for p in range(shards)):
                for p in procs:
                    assert p.poll() is None, \
                        f"shard died in setup:\n{p.communicate()[1][-2000:]}"
                assert time.time() < deadline, "shard setup timed out"
                time.sleep(0.01)
            spawn_s[0] = time.perf_counter() - t_spawn
            t0 = time.perf_counter()
            # pio: ignore[PIO002]: rendezvous sentinel, no content to tear
            with open(f"{shard_out}.go", "w") as f:
                f.write("go")
            for p in procs:
                _out, err = p.communicate(timeout=600)
                assert p.returncode == 0, f"shard failed:\n{err[-2000:]}"
            elapsed = time.perf_counter() - t0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        assert os.path.exists(shard_out), "no merged shard output"
        shard_inner_s.append(elapsed)
        return elapsed

    shard_inner_s = []
    shard_s = None
    if run_shards:
        timed_best(run_sharded, repeats=2)
        # judge best-of-N of the INNER elapsed (go-signal to last exit):
        # the outer wall timed_best sees includes spawn/rendezvous waiting
        shard_s = min(shard_inner_s)
        _assert_parquet_value_parity(shard_out, seq_out)

    # compile-shape ledger: force the device scorer (the TPU-serving
    # path; host-BLAS crossover would hide it on CPU) over a slice that
    # exercises full AND partial chunks — distinct compiled batch shapes
    # must stay inside the bucket ladder of the maximal bucket.
    hb("batch_predict ledger check")
    slice_in = os.path.join(work, "slice.jsonl")
    # pio: ignore[PIO002]: bench input fixture in a run-local temp dir
    with open(inp) as f, open(slice_in, "w") as g:
        for _ in range(2 * chunk + 17):
            g.write(f.readline())
    old_rt = als_mod._DEVICE_ROUNDTRIP_S
    als_mod._DEVICE_ROUNDTRIP_S = 0.0
    try:
        run_batch_predict(None, None, slice_in,
                          os.path.join(work, "ledger.jsonl"),
                          chunk_size=chunk, loaded=(result, None))
    finally:
        als_mod._DEVICE_ROUNDTRIP_S = old_rt
    shapes = sorted({k[0] for fam in ("als_topk", "als_topk_masked")
                     for k in fn_cache.family_keys(fam)
                     if k[2:] == (ni, rank)})
    bound = bucketing.bucket_count(chunk)
    assert 0 < len(shapes) <= bound, (
        f"bucketing leak: {len(shapes)} compiled batch shapes {shapes} "
        f"> bound {bound}")

    qps_seq = n_queries / seq_s
    qps_pipe = n_queries / pipe_s
    qps_cols = n_queries / cols_s
    speedup_pipe = qps_pipe / qps_seq
    speedup_cols = qps_cols / qps_seq
    headline = max(speedup_pipe, speedup_cols)
    sharded = {}
    shard_note = f"{shards}-proc sharded leg not run (worker holds the chip)"
    if shard_s is not None:
        qps_shard = n_queries / shard_s
        speedup_shard = qps_shard / qps_seq
        headline = max(headline, speedup_shard)
        sharded = {
            f"qps_sharded_{shards}proc": round(qps_shard, 1),
            f"speedup_sharded_{shards}proc": round(speedup_shard, 2),
            "shard_spawn_s": round(spawn_s[0], 2),
        }
        shard_note = (f"{shards}-proc sharded {qps_shard:.0f} q/s "
                      f"({speedup_shard:.2f}x)")
    if min_pipe > 0:
        assert speedup_pipe >= min_pipe, (
            f"pipelined jsonl path only {speedup_pipe:.2f}x over the "
            f"sequential-chunk baseline (floor {min_pipe}x)")
    if min_speedup > 0:
        assert headline >= min_speedup, (
            f"best batchpredict path only {headline:.2f}x over the "
            f"sequential-chunk baseline (floor {min_speedup}x)")
    return {
        # judged pair: the tentpole columnar path vs the pre-PR
        # sequential loop on the SAME 40k queries -> the orchestrator's
        # derived speedup IS the headline ratio
        "elapsed_s": round(cols_s, 3),
        "baseline_s": round(seq_s, 3),
        "queries": n_queries,
        "qps_sequential": round(qps_seq, 1),
        "qps_pipelined": round(qps_pipe, 1),
        "qps_columnar": round(qps_cols, 1),
        "speedup_pipelined": round(speedup_pipe, 2),
        "speedup_columnar": round(speedup_cols, 2),
        **sharded,
        "speedup_headline": round(headline, 2),
        "pad_waste_rows": pipe_report.pad_waste,
        "distinct_compiled_batch_shapes": len(shapes),
        "compile_shape_bound": bound,
        "note": (f"{n_queries} queries (num={num}) on synthetic "
                 f"{nu}x{ni} r{rank} factors, chunk {chunk}: sequential "
                 f"{qps_seq:.0f} q/s, pipelined jsonl {qps_pipe:.0f} q/s "
                 f"({speedup_pipe:.2f}x), columnar parquet "
                 f"{qps_cols:.0f} q/s ({speedup_cols:.2f}x), "
                 f"{shard_note}; value-identical outputs; "
                 f"{len(shapes)} compiled batch shapes (bound {bound})"),
    }


def cfg_telemetry(jax, mesh, platform):
    """Durable telemetry (obs/tsdb.py + obs/telemetry.py): the three
    numbers that decide whether persistence may stay on in production.

    1. SERVING OVERHEAD — p99 at concurrent load with an aggressive
       scrape loop (50ms interval, ~200x the default cadence) vs
       PIO_TELEMETRY=0, alternating best-of-N, asserted within
       BENCH_TELEMETRY_OVERHEAD_PCT (default 5%) + a sub-ms absolute
       slack — the same discipline as the PR 10 tracing bench.
    2. WRITE THROUGHPUT — samples/s appending a 10k-series registry
       snapshot (BENCH_TELEMETRY_SERIES), the store's headline.
    3. RANGE-QUERY LATENCY — one-metric range read + a fleet
       quantile-over-time against that 10k-series store, in ms.
    """
    import asyncio
    import tempfile

    import predictionio_tpu.models.als as als_mod
    from aiohttp.test_utils import TestClient, TestServer

    from predictionio_tpu.core.engine import Engine, TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.engines.recommendation import (
        ALSAlgorithm, AlgorithmParams, RecommendationServing)
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.obs.registry import MetricsRegistry
    from predictionio_tpu.obs.telemetry import TelemetryRecorder
    from predictionio_tpu.obs.tsdb import TSDBReader
    from predictionio_tpu.server.query_server import create_query_server
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.utils.server_config import (
        ServingConfig, TelemetryConfig)

    nu, ni, rank = 2000, 1000, 16
    per_level = int(os.environ.get("BENCH_TELEMETRY_QUERIES", 384))
    n_clients = int(os.environ.get("BENCH_TELEMETRY_CLIENTS", 8))
    n_series = int(os.environ.get("BENCH_TELEMETRY_SERIES", 10000))
    ticks = int(os.environ.get("BENCH_TELEMETRY_TICKS", 12))
    repeats = int(os.environ.get("BENCH_TELEMETRY_REPEATS", 3))

    rng = np.random.default_rng(11)
    model = ALSModel(
        user_vocab=np.asarray([f"u{i:06d}" for i in range(nu)],
                              dtype=object),
        item_vocab=np.asarray([f"i{i:06d}" for i in range(ni)],
                              dtype=object),
        U=rng.normal(size=(nu, rank)).astype(np.float32),
        V=rng.normal(size=(ni, rank)).astype(np.float32))
    result = TrainResult(models=[model],
                         algorithms=[ALSAlgorithm(AlgorithmParams())],
                         serving=RecommendationServing(),
                         engine_params=EngineParams())
    instance = EngineInstance(id="bench-telemetry", engine_id="bench",
                              engine_variant="default")
    engine = Engine({}, {}, {"als": ALSAlgorithm}, {})

    async def run_level(c, lat):
        async def client(k, n):
            for j in range(n):
                i = k * n + j
                t = time.perf_counter()
                resp = await c.post("/queries.json", json={
                    "user": f"u{i % nu:06d}", "num": 10})
                assert resp.status == 200, await resp.text()
                lat.append(time.perf_counter() - t)

        per_client = max(1, per_level // n_clients)
        await asyncio.gather(*[client(k, per_client)
                               for k in range(n_clients)])

    def serve_p99(telemetry) -> float:
        server = create_query_server(
            engine, result, instance, None,
            serving_config=ServingConfig(batch_max=32,
                                         batch_linger_s=None,
                                         batch_inflight=2),
            telemetry=telemetry)

        async def run_all():
            c = TestClient(TestServer(server.app))
            await c.start_server()
            lat = []
            try:
                await run_level(c, [])          # warm-up
                lat.clear()
                await run_level(c, lat)
            finally:
                await c.close()
            return lat

        lat = asyncio.run(run_all())
        return round(float(np.percentile(np.asarray(lat) * 1e3, 99)), 3)

    old_rt = als_mod._DEVICE_ROUNDTRIP_S
    als_mod._DEVICE_ROUNDTRIP_S = 0.0
    t0 = time.perf_counter()
    on_p99, off_p99 = [], []
    try:
        b = 1
        while b <= 32:
            model.recommend_batch([(model.user_vocab[0], 10, (), None)] * b)
            b <<= 1
        for r in range(repeats):
            hb(f"telemetry serve-sweep {r + 1}/{repeats}")
            off_p99.append(serve_p99(None))
            root = tempfile.mkdtemp(prefix="bench-telemetry-")
            cfg = TelemetryConfig(dir=root, interval_s=0.05)
            rec = TelemetryRecorder("query_server", cfg).start(
                restore=False)
            try:
                on_p99.append(serve_p99(rec))
            finally:
                rec.stop()
    finally:
        als_mod._DEVICE_ROUNDTRIP_S = old_rt
    elapsed = time.perf_counter() - t0
    tel_on, tel_off = min(on_p99), min(off_p99)
    overhead_pct = (100.0 * (tel_on - tel_off) / tel_off
                    if tel_off > 0 else 0.0)
    max_pct = float(os.environ.get("BENCH_TELEMETRY_OVERHEAD_PCT", 5.0))
    abs_slack_ms = float(os.environ.get(
        "BENCH_TELEMETRY_OVERHEAD_ABS_MS", 0.3))
    assert tel_on <= tel_off * (1 + max_pct / 100.0) + abs_slack_ms, (
        f"telemetry overhead breached: p99 {tel_on}ms with a 50ms "
        f"scrape loop vs {tel_off}ms telemetry-off "
        f"(+{overhead_pct:.1f}% > {max_pct}% + {abs_slack_ms}ms)")

    # -- tsdb write throughput at n_series ----------------------------------
    hb(f"telemetry tsdb-write {n_series} series")
    reg = MetricsRegistry()
    wide = reg.counter("pio_bench_wide_total", "bench fanout", ("shard",),
                       max_series=n_series + 8)
    lat_hist = reg.histogram("pio_bench_lat_seconds", "bench latency",
                             ("shard",), buckets=(0.01, 0.1, 1.0),
                             max_series=1024)
    for i in range(n_series):
        wide.inc(float(i % 7 + 1), shard=f"s{i:05d}")
    root = tempfile.mkdtemp(prefix="bench-tsdb-")
    store_dir = os.path.join(root, "bench")
    from predictionio_tpu.obs.tsdb import TSDB

    db = TSDB(store_dir)
    t0 = time.perf_counter()
    written = 0
    for tick in range(ticks):
        for i in range(0, n_series, 97):
            wide.inc(1.0, shard=f"s{i:05d}")
        for i in range(128):
            lat_hist.observe(0.05 * (i % 3 + 1), shard=f"s{i % 64:05d}")
        written += db.append_snapshot(reg.to_snapshot(),
                                      ts_ms=1_700_000_000_000 + 1000 * tick)
    db.flush()
    write_s = time.perf_counter() - t0
    samples_per_s = written / write_s if write_s > 0 else 0.0

    # -- range-query latency over that store --------------------------------
    hb("telemetry range-query")
    reader = TSDBReader([store_dir])
    t0 = time.perf_counter()
    series = reader.series("pio_bench_lat_seconds")
    range_ms = 1e3 * (time.perf_counter() - t0)
    assert series and len(series[0].points) == ticks
    t0 = time.perf_counter()
    q99 = reader.quantile_over_time("pio_bench_lat_seconds", 0.99)
    quantile_ms = 1e3 * (time.perf_counter() - t0)
    assert q99 is not None
    rates = reader.rate("pio_bench_wide_total",
                        labels={"shard": "s00000"})
    assert rates and rates[0]["increase"] > 0

    return {
        "elapsed_s": round(elapsed + write_s, 3),
        "baseline_s": None,
        "p99_ms_telemetry_on": tel_on,
        "p99_ms_telemetry_off": tel_off,
        "telemetry_overhead_pct": round(overhead_pct, 2),
        "tsdb_series": n_series,
        "tsdb_samples_written": written,
        "tsdb_samples_per_s": round(samples_per_s, 1),
        "range_query_ms": round(range_ms, 2),
        "quantile_over_time_ms": round(quantile_ms, 2),
        "note": (f"serving p99 {tel_on}ms w/ 50ms scrape loop vs "
                 f"{tel_off}ms off ({overhead_pct:+.1f}%, bound "
                 f"{max_pct}%); tsdb {samples_per_s:,.0f} samples/s at "
                 f"{n_series} series x {ticks} ticks; range query "
                 f"{range_ms:.1f}ms, quantile-over-time "
                 f"{quantile_ms:.1f}ms"),
    }


def _topk_scoring_shape():
    """Judged defaults vs BENCH_TOPK_* smoke overrides — keeps one code
    path; CPU-judged scale streams a half-million-item catalog (the
    10M-item TPU target runs the same kernels at BENCH_TOPK_ITEMS=1e7;
    below ~300k items the exact matmul still fits caches well enough
    that the two-stage ratio is understated)."""
    ni = int(os.environ.get("BENCH_TOPK_ITEMS", 524_288))
    rank = int(os.environ.get("BENCH_TOPK_RANK", 64))
    batch = int(os.environ.get("BENCH_TOPK_BATCH", 8))
    batches = int(os.environ.get("BENCH_TOPK_BATCHES", 6))
    tile = int(os.environ.get("BENCH_TOPK_TILE", 16384))
    shortlist = int(os.environ.get("BENCH_TOPK_SHORTLIST", 384))
    min_speedup = float(os.environ.get("BENCH_TOPK_MIN_SPEEDUP", 2.0))
    min_recall = float(os.environ.get("BENCH_TOPK_MIN_RECALL", 0.99))
    return ni, rank, batch, batches, tile, shortlist, min_speedup, \
        min_recall


def cfg_topk_scoring(jax, mesh, platform):
    """Fused low-precision top-k scoring (ops/scoring) vs the exact
    materialize-then-top_k scorer, through the model's real batch path
    (`recommend_batch_arrays`, the batchpredict arrow lane).

    Synthetic factors carry a geometrically-decaying singular spectrum —
    the shape trained ALS factors actually have (the data is low-rank
    plus noise; the als_kernel config's ground truth uses the same decay)
    and the structure the two-stage scan's principal-column truncation
    exploits. Asserts: twostage >= BENCH_TOPK_MIN_SPEEDUP x exact
    queries/sec (the CPU-judged floor; the TPU target at 10M items is
    4x), every non-exact mode >= BENCH_TOPK_MIN_RECALL recall@10 vs
    exact, quantized modes halve device factor bytes, and the scoring
    compile ledger stays on the bucket ladder x mode families.
    """
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.ops import fn_cache, scoring
    from predictionio_tpu.utils.server_config import ScorerConfig

    ni, rank, batch, n_batches, tile, shortlist, min_speedup, \
        min_recall = _topk_scoring_shape()
    k = 10
    rng = np.random.default_rng(11)
    hb("topk_scoring data-build")
    spec = np.power(10.0, -1.5 * np.arange(rank) / max(1, rank - 1))
    V = (rng.standard_normal((ni, rank)) * spec).astype(np.float32)
    n_users = batch * n_batches
    U = (rng.standard_normal((n_users, rank)) * spec).astype(np.float32)
    user_vocab = np.array([f"u{i:06d}" for i in range(n_users)],
                          dtype=object)
    item_vocab = np.array([f"i{i:08d}" for i in range(ni)], dtype=object)
    model = ALSModel(user_vocab=user_vocab, item_vocab=item_vocab,
                     U=U, V=V)
    req_batches = [
        [(f"u{i:06d}", k, (), None)
         for i in range(b * batch, (b + 1) * batch)]
        for b in range(n_batches)
    ]

    def run_pass():
        outs = []
        for reqs in req_batches:
            outs.append(model.recommend_batch_arrays(reqs))
        return outs

    def items_of(outs):
        return [set(items[sum(counts[:j]):sum(counts[: j + 1])].tolist())
                for items, _scores, counts in outs
                for j in range(len(counts))]

    modes = ["exact", "fused", "fused_bf16", "fused_int8", "twostage"]
    ledger_before = (len(fn_cache.family_keys(scoring.FUSED_FAMILY))
                     + len(fn_cache.family_keys(scoring.TWOSTAGE_FAMILY)))
    detail = {}
    results = {}
    total = 0.0
    try:
        for mode in modes:
            scoring.set_process_scorer_config(ScorerConfig(
                mode=mode, tile_items=tile, shortlist=shortlist,
                min_recall=min_recall))
            if hasattr(model, "_scorer_cache"):
                del model._scorer_cache
            hb(f"topk_scoring {mode} warmup")
            outs = run_pass()             # compile + quantize + parity
            hb(f"topk_scoring {mode} timed")
            elapsed, outs = timed_best(run_pass, repeats=2)
            total += elapsed
            qps = batch * n_batches / elapsed
            results[mode] = (qps, items_of(outs))
            detail[f"qps_{mode}"] = round(qps, 1)
            if mode != "exact":
                status = model._scorer_cache[2].status()
                assert status["activeMode"] == mode, (
                    f"{mode} parity-demoted at bench scale: {status}")
                detail[f"factor_bytes_{mode}"] = status["factorBytes"]
                detail[f"recall_probe_{mode}"] = status["recallProbe"]
                if status["quantization"] != "float32":
                    assert status["factorBytes"] * 2 \
                        <= status["exactBytes"], (
                        f"{mode} factor bytes {status['factorBytes']} "
                        f"not halved vs exact {status['exactBytes']}")
    finally:
        # the worker process runs MORE configs after a failed one: a
        # pinned non-exact mode must never leak into their scoring
        scoring.set_process_scorer_config(None)

    qps_exact, exact_sets = results["exact"]
    for mode in modes[1:]:
        qps, sets = results[mode]
        hits = sum(len(a & b) for a, b in zip(exact_sets, sets))
        recall = hits / float(sum(len(a) for a in exact_sets))
        speedup = qps / qps_exact
        detail[f"recall_{mode}"] = round(recall, 4)
        detail[f"speedup_{mode}"] = round(speedup, 2)
        assert recall >= min_recall, (
            f"{mode} recall@{k} {recall:.4f} under the {min_recall} "
            "parity floor vs the exact scorer")
    # the tentpole floor: the two-stage scan->exact-rescore path must
    # actually pay off at CPU-judged scale (4x is the 10M-item TPU bar)
    assert detail["speedup_twostage"] >= min_speedup, (
        f"twostage {detail['speedup_twostage']}x under the "
        f"{min_speedup}x floor (exact {qps_exact:.0f} q/s)")
    ledger = (len(fn_cache.family_keys(scoring.FUSED_FAMILY))
              + len(fn_cache.family_keys(scoring.TWOSTAGE_FAMILY))
              - ledger_before)
    # one (B-bucket, k-bucket) program per fused mode + one shortlist
    # scan: the bucket-ladder x mode bound, with one spare rung
    bound = 2 * len(modes)
    assert ledger <= bound, (
        f"scoring ledger grew {ledger} entries for {len(modes)} modes — "
        f"the bucket-ladder x mode bound ({bound}) is broken")
    detail.update({
        "elapsed_s": round(total, 3),
        "items": ni, "rank": rank, "batch": batch,
        "tile_items": tile, "shortlist": shortlist,
        "compile_ledger_delta": ledger,
        "compile_ledger_bound": bound,
        "speedup_headline": detail["speedup_twostage"],
        "note": (f"{ni}x{rank} catalog, B={batch}: exact "
                 f"{qps_exact:.0f} q/s; twostage "
                 f"{detail['speedup_twostage']}x at recall@10 "
                 f"{detail['recall_twostage']}; int8 factor bytes "
                 f"{detail.get('factor_bytes_fused_int8', 0)} vs f32 "
                 f"{V.nbytes}; ledger {ledger} <= {bound}"),
    })
    return detail


def _fleet_shape():
    """Judged defaults vs BENCH_FLEET_* smoke overrides (one code
    path). The replica service time is INJECTED (each stub replica
    models `slots` serving lanes of `service_ms` each with a semaphore
    + sleep) — the leg judges the ROUTER tier's scaling, not a model's
    kernel time, and the injection is disclosed in the detail."""
    service_ms = float(os.environ.get("BENCH_FLEET_SERVICE_MS", 20.0))
    slots = int(os.environ.get("BENCH_FLEET_SLOTS", 1))
    clients_per = int(os.environ.get("BENCH_FLEET_CLIENTS_PER_REPLICA", 3))
    stage_s = float(os.environ.get("BENCH_FLEET_STAGE_S", 4.0))
    min_scaling = float(os.environ.get("BENCH_FLEET_MIN_SCALING", 3.0))
    p99_ratio = float(os.environ.get("BENCH_FLEET_P99_RATIO", 2.0))
    items = int(os.environ.get("BENCH_FLEET_ITEMS", 200_000))
    rank = int(os.environ.get("BENCH_FLEET_RANK", 64))
    shards = int(os.environ.get("BENCH_FLEET_SHARDS", 4))
    return service_ms, slots, clients_per, stage_s, min_scaling, \
        p99_ratio, items, rank, shards


def cfg_fleet_scaling(jax, mesh, platform):
    """The serving-fleet tentpole, CPU-judged: (1) QPS through the REAL
    router tier (server/router.py — error-diffusion spread, health
    probes, retry-on-other-replica) scales near-linearly 1 -> 2 -> 4
    replicas at flat p99, with offered load scaled per replica (the
    standard open-loop scaling method) and zero dropped queries; (2) a
    sharded catalog (ops/scoring.ShardedScorer) serves item factors
    LARGER than one device's simulated HBM budget with exact top-k
    parity to the unsharded scorer.

    Asserts: qps(4)/qps(1) >= BENCH_FLEET_MIN_SCALING (3x CPU floor),
    p99(4) <= p99(1) x BENCH_FLEET_P99_RATIO, dropped == 0 at every
    stage, max per-shard factor bytes <= budget < whole-catalog bytes,
    and sharded ids == unsharded ids exactly."""
    import asyncio

    from predictionio_tpu.ops.scoring import build_sharded_scorer
    from predictionio_tpu.ops.topk import host_topk
    from predictionio_tpu.utils.server_config import (
        RouterConfig, ScorerConfig,
    )

    service_ms, slots, clients_per, stage_s, min_scaling, p99_ratio, \
        items, rank, shards = _fleet_shape()
    t_start = time.perf_counter()
    detail = {"service_ms_injected": service_ms,
              "slots_per_replica": slots,
              "clients_per_replica": clients_per}

    # -- leg 1: router QPS scaling over stub replicas ------------------------
    async def start_replica():
        from aiohttp import web

        sem = asyncio.Semaphore(slots)

        async def queries(request):
            await request.read()
            async with sem:         # `slots` concurrent serving lanes
                await asyncio.sleep(service_ms / 1000.0)
            return web.json_response({"itemScores": []})

        async def slo(request):
            return web.json_response({"breached": False})

        async def status(request):
            return web.json_response({"active": {"releaseVersion": 1}})

        app = web.Application()
        app.router.add_post("/queries.json", queries)
        app.router.add_get("/slo.json", slo)
        app.router.add_get("/deploy/status.json", status)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        return runner, f"http://127.0.0.1:{port}"

    async def run_stage(n_replicas):
        import aiohttp
        from aiohttp.test_utils import TestClient, TestServer

        from predictionio_tpu.server.router import Router

        runners, urls = [], []
        for _ in range(n_replicas):
            runner, url = await start_replica()
            runners.append(runner)
            urls.append(url)
        router = Router(
            RouterConfig(health_interval_s=0.2, health_fail_after=2,
                         proxy_retries=1),
            replica_urls=urls)
        client = TestClient(TestServer(router.app))
        await client.start_server()
        for rank_ in list(router.replicas):
            assert await router.wait_replica_healthy(rank_, timeout_s=10)
        from predictionio_tpu.loadtest.harness import LatencyLedger

        ledger = LatencyLedger()     # the shared stage accounting
        done = 0
        deadline = time.perf_counter() + stage_s

        async def one_client():
            nonlocal done
            while time.perf_counter() < deadline:
                t0 = time.perf_counter()
                async with client.post(
                        "/queries.json", json={"user": "u1"}) as resp:
                    await resp.read()
                    assert resp.status == 200, resp.status
                ledger.record(time.perf_counter() - t0)
                done += 1

        clients = [one_client()
                   for _ in range(clients_per * n_replicas)]
        t0 = time.perf_counter()
        await asyncio.gather(*clients)
        elapsed = time.perf_counter() - t0
        dropped = sum(v for _, v in router._dropped.samples())
        spread = {rank_: router._requests.value(replica=str(rank_),
                                                status="200")
                  for rank_ in router.replicas}
        await client.close()
        for runner in runners:
            await runner.cleanup()
        qps = done / elapsed
        p99 = ledger.percentile_ms(99)
        return qps, p99, dropped, spread

    qps_by_n = {}
    for n in (1, 2, 4):
        hb(f"fleet_scaling router stage n={n}")
        qps, p99, dropped, spread = asyncio.run(run_stage(n))
        qps_by_n[n] = qps
        detail[f"qps_{n}"] = round(qps, 1)
        detail[f"p99_ms_{n}"] = round(p99, 2)
        assert dropped == 0, (
            f"{dropped} dropped queries at {n} replicas — the router "
            "must never fail a query while every replica is healthy")
        # the error-diffusion spread must be exact (±1 per replica)
        total = sum(spread.values())
        for rank_, served in spread.items():
            assert abs(served - total / n) <= 1.0, (
                f"replica {rank_} served {served}/{total} at {n} "
                "replicas — splitter spread is not exact")
    scaling = qps_by_n[4] / max(1e-9, qps_by_n[1])
    detail["scaling_4"] = round(scaling, 2)
    assert scaling >= min_scaling, (
        f"4-replica scaling {scaling:.2f}x under the {min_scaling}x "
        f"floor (qps {qps_by_n[1]:.0f} -> {qps_by_n[4]:.0f})")
    assert detail["p99_ms_4"] <= detail["p99_ms_1"] * p99_ratio + 5.0, (
        f"p99 not flat under scaling: {detail['p99_ms_1']}ms at 1 "
        f"replica vs {detail['p99_ms_4']}ms at 4 (bound "
        f"{p99_ratio}x + 5ms)")

    # -- leg 2: sharded catalog beyond one device's budget -------------------
    hb("fleet_scaling sharded-catalog build")
    rng = np.random.default_rng(13)
    spec = np.power(10.0, -1.5 * np.arange(rank) / max(1, rank - 1))
    V = (rng.standard_normal((items, rank)) * spec).astype(np.float32)
    U = (rng.standard_normal((16, rank)) * spec).astype(np.float32)
    # the simulated device budget: HALF the catalog — an unsharded
    # residency cannot fit, each of the `shards` shards trivially does
    budget = V.nbytes // 2
    scorer = build_sharded_scorer(
        V, ScorerConfig(mode="fused", tile_items=16384, shards=shards),
        shards=shards)
    status = scorer.status()
    detail["sharded_items"] = items
    detail["sharded_shards"] = shards
    detail["catalog_bytes"] = int(status["exactBytes"])
    detail["device_budget_bytes"] = int(budget)
    detail["max_shard_factor_bytes"] = int(status["maxShardFactorBytes"])
    assert status["maxShardFactorBytes"] <= budget < status["exactBytes"], (
        f"sharded residency {status['maxShardFactorBytes']}B must fit "
        f"the {budget}B budget the {status['exactBytes']}B catalog "
        "exceeds")
    hb("fleet_scaling sharded parity")
    ref_v, ref_i = host_topk(U @ V.T, 10)
    out_v, out_i = scorer.topk(U, 10)
    assert np.array_equal(np.asarray(out_i), ref_i), (
        "sharded top-k ids diverge from the unsharded scorer")
    assert np.allclose(np.asarray(out_v), ref_v, rtol=1e-5, atol=1e-5), (
        "sharded top-k scores diverge from the unsharded scorer")
    detail["sharded_parity"] = 1.0

    detail.update({
        "elapsed_s": round(time.perf_counter() - t_start, 3),
        "baseline_s": None,
        "speedup_headline": detail["scaling_4"],
        "service_floor_injected": True,
        "note": (f"router QPS {detail['qps_1']} -> {detail['qps_2']} -> "
                 f"{detail['qps_4']} over 1/2/4 replicas "
                 f"({scaling:.2f}x, floor {min_scaling}x) at p99 "
                 f"{detail['p99_ms_1']} -> {detail['p99_ms_4']}ms, zero "
                 f"drops (replica service {service_ms}ms x {slots} "
                 f"lanes INJECTED, load scaled per replica); sharded "
                 f"catalog {status['exactBytes'] >> 20}MB over "
                 f"{shards} shards fits a {budget >> 20}MB device "
                 f"budget with exact parity"),
    })
    return detail


def cfg_loadtest(jax, mesh, platform):
    """Workload simulator end-to-end (loadtest/): the whole paper's
    serving story under one sustained, mixed, incident-laden storm.

    Leg 1 (sustained): a LocalFleet — real event server (group-commit
    WriteBuffer, partitioned lanes), two QueryServer replicas with
    online fold-in, the router tier, and the continuous-training
    orchestrator — stormed at the largest CPU-feasible population
    (BENCH_LOADTEST_POPULATION lazy Zipfian users) with the 60/30/10
    events/queries/feedback mix on a diurnal arrival curve, while the
    orchestrator completes a FULL retrain-and-promote cycle mid-run and
    the router rolls the promoted release across the fleet. Asserts the
    runtime invariants live: zero dropped acks/queries, exactly-once
    ingest by post-run audit against the emitter's acked-id ledger, one
    LIVE release after the dust settles, retrain promoted mid-run, ack
    and query p99 under BENCH_LOADTEST_P99_MS, and fold-in freshness
    (rows applied, event->applied p95 bounded).

    Leg 2 (chaos, parquet): the same fleet on the parquet backend
    survives a replica kill + restart (router ejects with backed-off
    probes, re-admits on recovery) AND a compaction crash (storage kill
    point mid-rewrite, recovery rolls forward) mid-storm — with zero
    dropped acks and the exactly-once audit still clean."""
    import shutil
    import tempfile

    from predictionio_tpu.loadtest.fleet import LocalFleet
    from predictionio_tpu.loadtest.scenario import Scenario
    from predictionio_tpu.loadtest.simulator import run_storm

    population = int(os.environ.get("BENCH_LOADTEST_POPULATION", 200_000))
    items = int(os.environ.get("BENCH_LOADTEST_ITEMS", 20_000))
    duration_s = float(os.environ.get("BENCH_LOADTEST_DURATION_S", 24))
    rate = float(os.environ.get("BENCH_LOADTEST_RATE", 400))
    chaos_s = float(os.environ.get("BENCH_LOADTEST_CHAOS_DURATION_S", 16))
    chaos_rate = float(os.environ.get("BENCH_LOADTEST_CHAOS_RATE", 150))
    p99_bound_ms = float(os.environ.get("BENCH_LOADTEST_P99_MS", 2000))
    detail = {"population": population, "items": items,
              "duration_s": duration_s, "base_rate": rate,
              "p99_bound_ms": p99_bound_ms}
    t_start = time.perf_counter()

    def run_one(sc, label, **kw):
        root = tempfile.mkdtemp(prefix=f"pio_bench_lt_{label}_")
        fleet = LocalFleet(root, replicas=sc.replicas,
                           partitions=sc.partitions, backend=sc.backend)
        try:
            fleet.start()
            return run_storm(sc, fleet,
                             ack_p99_bound_ms=p99_bound_ms,
                             query_p99_bound_ms=p99_bound_ms, **kw)
        finally:
            fleet.stop()
            shutil.rmtree(root, ignore_errors=True)

    def fails(report):
        return [r for r in report["invariants"] if not r["ok"]]

    # -- leg 1: sustained mixed workload + mid-run retrain-and-promote ----
    hb("loadtest sustained storm")
    sustained = Scenario.from_dict({
        "name": "bench-sustained",
        "population": population, "items": items,
        "durationS": duration_s, "seed": 7,
        "baseRate": rate, "amplitude": 0.5,
        "mix": {"events": 0.6, "queries": 0.3, "feedback": 0.1},
        "replicas": 2, "partitions": 2, "backend": "sqlite",
        "maxOutstanding": 256,
        "incidents": [{"kind": "retrain", "atS": round(duration_s * 0.4, 1)}],
    })
    rep1 = run_one(sustained, "sustained")
    lanes = rep1["lanes"]
    detail["sustained_arrivals"] = rep1["arrivals"]
    detail["sustained_active_users"] = rep1["active_users"]
    detail["sustained_wall_s"] = rep1["wall_s"]
    for lane, res in lanes.items():
        detail[f"sustained_{lane}_acked"] = res["acked"]
        detail[f"sustained_{lane}_p99_ms"] = res["ack_p99_ms"]
    detail["sustained_audited_events"] = rep1["audit"]["expected"]
    detail["foldin_applied_rows"] = rep1["foldin_applied_rows"]
    ops_s = (sum(r["acked"] for r in lanes.values())
             / max(1e-9, rep1["wall_s"]))
    detail["sustained_ops_per_s"] = round(ops_s, 1)
    assert rep1["ok"], (
        f"sustained storm violated invariants: {fails(rep1)}")

    # -- leg 2: chaos storm on parquet (kill replica + kill compaction) ---
    hb("loadtest chaos storm")
    chaos = Scenario.from_dict({
        "name": "bench-chaos",
        "population": max(1000, population // 10),
        "items": max(200, items // 10),
        "durationS": chaos_s, "seed": 11,
        "baseRate": chaos_rate, "amplitude": 0.3,
        "mix": {"events": 0.7, "queries": 0.25, "feedback": 0.05},
        "replicas": 2, "partitions": 2, "backend": "parquet",
        "maxOutstanding": 128,
        "incidents": [
            {"kind": "kill_replica", "atS": round(chaos_s * 0.25, 1),
             "target": 1, "restartAfterS": round(chaos_s * 0.3, 1)},
            {"kind": "kill_compaction", "atS": round(chaos_s * 0.55, 1)},
        ],
    })
    # freshness is leg 1's assertion; the chaos leg is about survival
    rep2 = run_one(chaos, "chaos", check_freshness=False)
    detail["chaos_arrivals"] = rep2["arrivals"]
    detail["chaos_events_acked"] = rep2["lanes"]["events"]["acked"]
    detail["chaos_audited_events"] = rep2["audit"]["expected"]
    detail["chaos_audit_ok"] = rep2["audit"]["ok"]
    assert rep2["ok"], f"chaos storm violated invariants: {fails(rep2)}"

    detail.update({
        "elapsed_s": round(time.perf_counter() - t_start, 2),
        "baseline_s": None,
        "speedup_headline": detail["sustained_ops_per_s"],
        "note": (
            f"sustained storm: {rep1['arrivals']} arrivals over "
            f"{population} users, {detail['sustained_ops_per_s']} ops/s "
            f"acked (ack p99 "
            f"{detail['sustained_events_p99_ms']}ms), retrain promoted "
            f"mid-run, exactly-once over "
            f"{detail['sustained_audited_events']} events, "
            f"{detail['foldin_applied_rows']} rows folded in; chaos "
            f"storm (parquet): replica kill+restart and compaction "
            f"crash survived with zero dropped acks, exactly-once over "
            f"{detail['chaos_audited_events']} events"),
    })
    return detail


def cfg_multitenant(jax, mesh, platform):
    """Multi-tenant consolidation (server/multitenant.py): THREE engine
    families — recommendation (ALS user->item), similarproduct
    (item->item cosine), recommended_user (user->user follow graph) —
    served from ONE process behind per-tenant routes, under a device
    budget deliberately too small for all residencies at once.

    Three measurements, each an acceptance gate:

    * **p99 parity** — each tenant is first benched STANDALONE (its own
      QueryServer, same scorer config), then consolidated behind the
      MultiTenantServer gate with phased per-tenant traffic. The
      consolidated per-tenant p99 must stay within
      BENCH_MT_P99_SLACK (default 1.15x) of its standalone baseline —
      the gate + shared process must not tax the hot path.
    * **the eviction/reload cycle actually turns** — the undersized
      budget (BENCH_MT_BUDGET_FRACTION of the scorer-backed tenants'
      combined residency) forces warm LRU evictions at phase
      boundaries and warm reloads on the next hit; both counters must
      move, and every query must still answer 200.
    * **consolidation saves bytes** — post-run device-resident bytes
      across the host stay under the budget, which is itself under the
      sum of the standalone residencies (the whole point of
      consolidating).

    Per-tenant quantized residency rides along: the rec tenant serves
    int8 factors, the sim tenant bf16, in the SAME process — the
    per-holder scorer override the multi-tenant host stamps."""
    import asyncio
    import gc
    import shutil
    import tempfile

    import predictionio_tpu.models.als as als_mod
    from aiohttp.test_utils import TestClient, TestServer

    from predictionio_tpu.core.engine import Engine, TrainResult
    from predictionio_tpu.core.params import EngineParams
    from predictionio_tpu.deploy.releases import record_release
    from predictionio_tpu.engines import (
        recommendation as rec_mod,
        recommended_user as ru_mod,
        similarproduct as sp_mod,
    )
    from predictionio_tpu.engines.common import Item
    from predictionio_tpu.models.als import ALSModel
    from predictionio_tpu.server.multitenant import (
        MultiTenantServer, TenantSpec,
    )
    from predictionio_tpu.server.query_server import create_query_server
    from predictionio_tpu.storage import Model, Storage
    from predictionio_tpu.storage.base import EngineInstance
    from predictionio_tpu.utils.server_config import (
        DeployConfig, MultiTenantConfig, ScorerConfig, ServingConfig,
    )
    from predictionio_tpu.workflow.serialization import serialize_models

    n_items = int(os.environ.get("BENCH_MT_ITEMS", 20000))
    n_users = int(os.environ.get("BENCH_MT_USERS", 400))
    rank = int(os.environ.get("BENCH_MT_RANK", 64))
    per_tenant = int(os.environ.get("BENCH_MT_QUERIES", 300))
    passes = int(os.environ.get("BENCH_MT_PASSES", 2))
    slack = float(os.environ.get("BENCH_MT_P99_SLACK", 1.15))
    budget_fraction = float(
        os.environ.get("BENCH_MT_BUDGET_FRACTION", 0.8))

    rng = np.random.default_rng(23)
    serving_cfg = ServingConfig(batch_max=32, batch_linger_s=0.0)
    deploy_cfg = DeployConfig(warmup=False, drain_timeout_s=10.0)

    # -- three engine families, one synthetic catalog each ----------------
    rec_model = ALSModel(
        user_vocab=np.sort(np.asarray(
            [f"u{i:06d}" for i in range(n_users)], dtype=object)),
        item_vocab=np.sort(np.asarray(
            [f"i{i:06d}" for i in range(n_items)], dtype=object)),
        U=rng.normal(size=(n_users, rank)).astype(np.float32),
        V=rng.normal(size=(n_items, rank)).astype(np.float32))

    sp_V = rng.normal(size=(n_items, rank)).astype(np.float32)
    sp_V /= np.linalg.norm(sp_V, axis=1, keepdims=True)
    sp_model = sp_mod.SimilarityModel(
        item_vocab=np.sort(np.asarray(
            [f"i{i:06d}" for i in range(n_items)], dtype=object)),
        V=sp_V, items={i: Item(categories=None) for i in range(n_items)})

    # the follow graph gets catalog-scale factors too — every tenant's
    # steady state must be compute-bound, or p99 parity just measures
    # shared-process jitter against a sub-ms baseline
    ru_V = rng.normal(size=(n_items, rank)).astype(np.float32)
    ru_V /= np.linalg.norm(ru_V, axis=1, keepdims=True)
    ru_model = ru_mod.RecommendedUserModel(
        user_vocab=np.sort(np.asarray(
            [f"u{i:06d}" for i in range(n_items)], dtype=object)),
        V=ru_V, users={})

    tenants = [
        # (name, family, engine, model, algorithms, serving, scorer, query)
        ("rec", "recommendation",
         Engine(rec_mod.RecommendationDataSource,
                rec_mod.RecommendationPreparator,
                {"als": rec_mod.ALSAlgorithm},
                rec_mod.RecommendationServing),
         rec_model,
         [rec_mod.ALSAlgorithm(rec_mod.AlgorithmParams(rank=rank))],
         rec_mod.RecommendationServing(),
         ScorerConfig(mode="fused_int8"),
         lambda i: {"user": f"u{i % n_users:06d}", "num": 10}),
        ("sim", "similarproduct",
         Engine(sp_mod.SimilarProductDataSource,
                sp_mod.SimilarProductPreparator,
                {"als": sp_mod.ALSAlgorithm},
                sp_mod.SimilarProductServing),
         sp_model,
         [sp_mod.ALSAlgorithm()],
         sp_mod.SimilarProductServing(),
         ScorerConfig(mode="fused_bf16"),
         lambda i: {"items": [f"i{i % n_items:06d}"], "num": 10}),
        ("social", "recommended_user",
         Engine(ru_mod.RecommendedUserDataSource,
                ru_mod.RecommendedUserPreparator,
                {"als": ru_mod.ALSAlgorithm},
                ru_mod.RecommendedUserServing),
         ru_model,
         [ru_mod.ALSAlgorithm()],
         ru_mod.RecommendedUserServing(),
         None,
         lambda i: {"users": [f"u{i % n_items:06d}"], "num": 10}),
    ]

    root = tempfile.mkdtemp(prefix="pio_bench_mt_")
    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite",
                           "PATH": os.path.join(root, "mt.db")}},
        "repositories": {
            "METADATA": {"SOURCE": "DB", "NAMESPACE": "pio_meta"},
            "MODELDATA": {"SOURCE": "DB", "NAMESPACE": "pio_model"},
            "EVENTDATA": {"SOURCE": "DB", "NAMESPACE": "pio_event"},
        }})
    old_rt = als_mod._DEVICE_ROUNDTRIP_S
    als_mod._DEVICE_ROUNDTRIP_S = 0.0   # force the device scorer lane
    detail = {"tenants": [t[0] for t in tenants],
              "families": [t[1] for t in tenants],
              "items": n_items, "rank": rank,
              "queries_per_tenant": per_tenant, "p99_slack": slack}
    t_start = time.perf_counter()

    def build_spec(name, engine, model, algorithms, serving, scorer):
        instance = EngineInstance(
            id=f"bench-mt-{name}", status="COMPLETED",
            engine_id="bench-multitenant", engine_version="1",
            engine_variant=name,
            data_source_params=json.dumps({"app_name": f"{name}App"}),
            algorithms_params=json.dumps(
                [{"name": "als", "params": {"rank": rank}}]))
        Storage.get_meta_data_engine_instances().insert(instance)
        blob = serialize_models([model])
        Storage.get_model_data_models().insert(
            Model(id=instance.id, models=blob))
        release = record_release(instance, train_seconds=0.0, blob=blob)
        result = TrainResult(models=[model], algorithms=algorithms,
                             serving=serving,
                             engine_params=EngineParams())
        return TenantSpec(name=name, engine=engine, train_result=result,
                          instance=instance, ctx=None, release=release,
                          scorer_config=scorer,
                          serving_config=serving_cfg,
                          deploy_config=deploy_cfg)

    async def drive(client, path, mk_query, n, lat=None, base=0):
        for i in range(n):
            t0 = time.perf_counter()
            resp = await client.post(path, json=mk_query(base + i))
            assert resp.status == 200, (path, resp.status,
                                        await resp.text())
            await resp.json()
            if lat is not None:
                lat.append(time.perf_counter() - t0)

    def p99_ms(lat):
        return round(float(np.percentile(np.asarray(lat) * 1e3, 99)), 3)

    def p50_ms(lat):
        return round(float(np.percentile(np.asarray(lat) * 1e3, 50)), 3)

    try:
        specs = {t[0]: build_spec(t[0], t[2], t[3], t[4], t[5], t[6])
                 for t in tenants}

        # -- standalone baselines: one tenant, one process-worth ----------
        baseline_p99 = {}
        baseline_p50 = {}
        standalone_bytes = {}

        async def run_baseline(name, spec, mk_query):
            server = create_query_server(
                spec.engine, spec.train_result, spec.instance, None,
                serving_config=serving_cfg, deploy_config=deploy_cfg,
                scorer_config=spec.scorer_config, release=spec.release)
            c = TestClient(TestServer(server.app))
            await c.start_server()
            try:
                await drive(c, "/queries.json", mk_query, 32)  # warm/compile
                lat = []
                gc.collect()
                gc.disable()   # GC pauses scale with heap size, not with
                try:           # serving cost; keep them out of both tails
                    await drive(c, "/queries.json", mk_query, per_tenant,
                                lat=lat, base=32)
                finally:
                    gc.enable()
                baseline_p99[name] = p99_ms(lat)
                baseline_p50[name] = p50_ms(lat)
                standalone_bytes[name] = server.warm_bytes
            finally:
                await c.close()

        for name, _family, _eng, _model, _algos, _srv, _cfg, mk_q in tenants:
            hb(f"multitenant baseline {name}")
            asyncio.run(run_baseline(name, specs[name], mk_q))
        detail["baseline_p99_ms"] = dict(baseline_p99)
        detail["baseline_p50_ms"] = dict(baseline_p50)
        detail["standalone_resident_bytes"] = dict(standalone_bytes)
        standalone_total = sum(standalone_bytes.values())
        assert standalone_total > 0, standalone_bytes

        # -- consolidated host under an undersized budget -----------------
        # sized so the scorer-backed tenants cannot all stay resident:
        # phase transitions MUST evict and the next hit MUST warm-reload
        budget = int(budget_fraction * standalone_total)
        detail["budget_bytes"] = budget
        mt_p99 = {}
        mt_p50 = {}

        async def run_consolidated():
            host = MultiTenantServer(
                list(specs.values()),
                config=MultiTenantConfig(
                    budget_bytes=budget, reload_wait_s=30.0,
                    sweep_interval_s=3600.0, min_resident=1,
                    admission=False))
            c = TestClient(TestServer(host.app))
            await c.start_server()
            try:
                lat = {t[0]: [] for t in tenants}
                for p in range(passes):
                    for (name, _f, _e, _m, _a, _s, _cfg, mk_q) in tenants:
                        hb(f"multitenant pass {p} {name}")
                        # untimed warm leg, symmetric with the baseline
                        # methodology: the FIRST query here is the miss
                        # that drives the warm reload, so the reload +
                        # scorer-cache rebuild cost stays out of the
                        # steady-state parity sample (it is proven
                        # separately by the eviction/reload counters)
                        await drive(c, f"/t/{name}/queries.json", mk_q,
                                    16, base=100_000 + p * 16)
                        gc.collect()
                        gc.disable()
                        try:
                            await drive(c, f"/t/{name}/queries.json",
                                        mk_q, per_tenant, lat=lat[name],
                                        base=p * per_tenant)
                        finally:
                            gc.enable()
                        # deterministic sweep tick: all tenants START
                        # resident, so without this only the (disabled)
                        # background sweep would ever notice the budget
                        await host.enforce_budget()
                for name, samples in lat.items():
                    mt_p99[name] = p99_ms(samples)
                    mt_p50[name] = p50_ms(samples)
                # one registry serves every tenant: read the shared
                # counters ONCE (summing per tenant would triple-count)
                any_server = next(iter(host.tenants.values())).server
                evictions = any_server._evict_total.value(reason="budget")
                reloads = any_server._reload_total.value(
                    status="warm_reload")
                return {
                    "evictions": int(evictions),
                    "warm_reloads": int(reloads),
                    "resident_bytes_end": int(host.resident_bytes()),
                    "resident_tenants_end": sorted(
                        t.name for t in host.tenants.values()
                        if t.server.resident),
                }
            finally:
                await c.close()

        consolidated = asyncio.run(run_consolidated())
        detail.update(consolidated)
        detail["consolidated_p99_ms"] = dict(mt_p99)
        detail["consolidated_p50_ms"] = dict(mt_p50)

        # gate 1: the cycle actually turned under the undersized budget
        assert consolidated["evictions"] > 0, consolidated
        assert consolidated["warm_reloads"] > 0, consolidated
        # gate 2: consolidation saves bytes — end-state residency under
        # the budget, which is under the sum of standalone residencies
        assert consolidated["resident_bytes_end"] <= budget < \
            standalone_total, (consolidated, budget, standalone_total)
        # gate 3: steady-state p99 parity per tenant
        for name, base in baseline_p99.items():
            assert mt_p99[name] <= base * slack, (
                name, mt_p99[name], base, slack)

        detail.update({
            "elapsed_s": round(time.perf_counter() - t_start, 2),
            "baseline_s": None,
            "speedup_headline": round(
                standalone_total / max(1, consolidated[
                    "resident_bytes_end"]), 2),
            "note": (
                f"3 engine families consolidated: budget {budget}B vs "
                f"{standalone_total}B standalone "
                f"({consolidated['evictions']} evictions, "
                f"{consolidated['warm_reloads']} warm reloads); "
                f"per-tenant p99 consolidated/standalone: "
                + ", ".join(
                    f"{n} {mt_p99[n]:.1f}/{baseline_p99[n]:.1f}ms"
                    for n in baseline_p99)),
        })
        return detail
    finally:
        als_mod._DEVICE_ROUNDTRIP_S = old_rt
        Storage.reset()
        shutil.rmtree(root, ignore_errors=True)


def cfg_sleep_forever(jax, mesh, platform):
    """Test-only config (never in the default set): wedges the worker so
    the orchestrator's watchdog + same-platform respawn can be exercised
    on CPU."""
    hb("sleep_forever")
    while True:
        time.sleep(1)


#: name -> (fn, seconds budget measured from RUN dispatch to BENCH_DETAIL)
CONFIGS = {
    "als_ml100k": (cfg_als_ml100k, 240),
    "pipeline_ml100k": (cfg_pipeline_ml100k, 420),
    "cooccurrence_ml1m": (cfg_cooccurrence, 240),
    "naive_bayes_spam": (cfg_naive_bayes, 180),
    "ecommerce_implicit_als": (cfg_ecommerce, 240),
    "eval_sweep_grid": (cfg_eval_sweep, 420),
    "als_kernel": (cfg_als_kernel, 900),
    "serving_batching": (cfg_serving_batching, 240),
    "deploy_swap": (cfg_deploy_swap, 240),
    "train_ingest": (cfg_train_ingest, 240),
    "ingest_write": (cfg_ingest_write, 240),
    "foldin_freshness": (cfg_foldin_freshness, 240),
    "batch_predict": (cfg_batch_predict, 300),
    "telemetry": (cfg_telemetry, 240),
    "topk_scoring": (cfg_topk_scoring, 240),
    "fleet_scaling": (cfg_fleet_scaling, 300),
    "loadtest": (cfg_loadtest, 420),
    "multitenant": (cfg_multitenant, 420),
    "als_ml20m": (cfg_als_ml20m, 900),
}

#: wedge-simulator, reachable only via --only (watchdog/ladder testing)
CONFIGS["_sleep_forever"] = (cfg_sleep_forever, 15)

INIT_BUDGET_S = 420      # worker start-up (import, device claim, first dispatch)


# ---------------------------------------------------------------------------
# Worker: claims the device ONCE, then runs configs fed over stdin
# ---------------------------------------------------------------------------

def worker_loop(platform: str) -> None:
    hb(f"worker init-start platform={platform}")
    jax, devices, mesh = setup_backend(platform)
    import jax.numpy as jnp

    x = jnp.ones((256, 256))
    # pio: ignore[PIO001]: one-shot worker warmup probe, process-local
    jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    hb("worker first-dispatch ok")
    # the device as JAX reports it, never the name that was asked for
    platform = devices[0].platform
    print("DEVINFO " + json.dumps({
        "platform": platform, "n_devices": len(devices),
        "device_kind": devices[0].device_kind}), flush=True)
    for line in sys.stdin:
        name = line.strip()
        if not name or name == "QUIT":
            break
        fn, _budget = CONFIGS[name]
        hb(f"config-start {name}")
        t0 = time.perf_counter()
        try:
            detail = fn(jax, mesh, platform)
        except Exception as e:
            import traceback

            traceback.print_exc()
            print("CONFIG_FAILED " + json.dumps(
                {"name": name, "error": repr(e)}), flush=True)
            continue
        detail.update({
            "name": name, "platform": platform,
            "device_kind": devices[0].device_kind,
            "total_s": round(time.perf_counter() - t0, 2),
        })
        print("BENCH_DETAIL " + json.dumps(detail), flush=True)
    hb("worker done")


# ---------------------------------------------------------------------------
# Orchestrator (no jax in this process)
# ---------------------------------------------------------------------------

class WorkerHandle:
    """A worker subprocess + reader threads. stdout lines land in a
    queue; stderr lines are echoed to our stderr and kept (tail) for
    failure forensics."""

    def __init__(self, args, extra_env=None):
        import queue

        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)] + args,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, bufsize=1, env=env)
        self.lines: "queue.Queue[str]" = queue.Queue()
        self.err_tail = []
        # pio: ignore[PIO003]: subprocess stdout/stderr pumps, no request trace exists
        threading.Thread(target=self._pump_out, daemon=True).start()
        # pio: ignore[PIO003]: subprocess stdout/stderr pumps, no request trace exists
        threading.Thread(target=self._pump_err, daemon=True).start()

    def _pump_out(self):
        for line in self.proc.stdout:
            self.lines.put(line.rstrip("\n"))
        self.lines.put("__EOF__")

    def _pump_err(self):
        for line in self.proc.stderr:
            line = line.rstrip("\n")
            print(f"  | {line}", file=sys.stderr, flush=True)
            self.err_tail.append(line)
            del self.err_tail[:-40]

    def send(self, line: str) -> bool:
        try:
            self.proc.stdin.write(line + "\n")
            self.proc.stdin.flush()
            return True
        except (BrokenPipeError, OSError, ValueError):
            return False

    def read_until(self, prefixes, deadline):
        """Next line starting with any prefix, or None on timeout/EOF."""
        import queue

        while True:
            remain = deadline - time.monotonic()
            if remain <= 0:
                return None
            try:
                line = self.lines.get(timeout=min(remain, 5.0))
            except queue.Empty:
                continue
            if line == "__EOF__":
                return None
            for p in prefixes:
                if line.startswith(p):
                    return line

    def kill(self):
        try:
            self.proc.kill()
            self.proc.wait(timeout=10)
        except Exception:
            pass

    def alive(self) -> bool:
        return self.proc.poll() is None


def resolve_platform() -> str:
    override = os.environ.get("BENCH_PLATFORM")
    if override:
        log(f"platform forced to {override} via BENCH_PLATFORM")
        return override
    plat = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() or "tpu"
    return plat


class Suite:
    def __init__(self, names, deadline_s, partial=False):
        self.names = names
        self.partial = partial
        self.deadline = time.monotonic() + deadline_s
        self.details = []
        self.failures = []
        self.baselines = {}
        self.devinfo = {}
        self.done = set()
        self._emitted = False

    # -- workers ------------------------------------------------------------

    def start_worker(self, platform, extra_env=None):
        w = WorkerHandle(["--worker", "--platform", platform],
                         extra_env=extra_env)
        line = w.read_until(
            ("DEVINFO",),
            min(self.deadline - 30, time.monotonic() + INIT_BUDGET_S))
        if line is None:
            tail = w.err_tail[-3:]
            log(f"worker init on {platform} FAILED/hung "
                f"(last heartbeats: {tail})")
            # the artifact must explain on its own why nothing ran
            self.failures.append({
                "name": f"_worker_init_{platform}",
                "error": "backend init hung/failed (device claim)",
                "last_heartbeats": tail})
            w.kill()
            return None
        self.devinfo = json.loads(line[len("DEVINFO "):])
        log(f"worker up: {self.devinfo['n_devices']} x "
            f"{self.devinfo['device_kind']}")
        return w

    def run_config(self, w: WorkerHandle, name: str) -> bool:
        """True if the config produced a detail (or a clean in-worker
        failure); False if the worker must be presumed wedged."""
        _fn, budget = CONFIGS[name]
        deadline = min(self.deadline - 30, time.monotonic() + budget)
        if deadline - time.monotonic() < 10:
            self.failures.append({"name": name, "error": "suite deadline"})
            log(f"{name}: SKIPPED (deadline)")
            self.done.add(name)
            return True
        if not w.send(name):
            # worker died between configs: leave a trail (superseded if a
            # retry on a fresh worker succeeds)
            self.failures.append({"name": name,
                                  "error": "worker dead (stdin closed)",
                                  "last_heartbeats": w.err_tail[-5:]})
            log(f"{name}: worker dead before dispatch")
            return False
        line = w.read_until(("BENCH_DETAIL", "CONFIG_FAILED"), deadline)
        if line is None:
            self.failures.append({
                "name": name, "error": "timeout/worker-death",
                "last_heartbeats": w.err_tail[-5:]})
            log(f"{name}: TIMEOUT (last heartbeats: {w.err_tail[-3:]})")
            return False
        if line.startswith("CONFIG_FAILED"):
            info = json.loads(line[len("CONFIG_FAILED "):])
            self.failures.append(info)
            log(f"{name}: FAILED in-worker ({info.get('error')})")
            self.done.add(name)
            return True
        detail = json.loads(line[len("BENCH_DETAIL "):])
        self.finish_detail(detail)
        self.done.add(name)
        return True

    def finish_detail(self, detail):
        name = detail["name"]
        # a success supersedes earlier timeout entries for the same config
        # (a retry on a fresh worker after a wedge) — the artifact must
        # not report a config as both failed and measured
        self.failures = [f for f in self.failures if f.get("name") != name]
        base = self.baselines.get(name, {})
        # never clobber — or MIX METADATA INTO — a baseline the worker
        # measured itself (the scaled CPU ml20m run carries its own
        # matched baseline; the external entry describes a different
        # workload shape). Value check, not key presence: a config that
        # reports baseline_s=None is declaring "none of my own", not
        # vetoing the externally measured one
        if detail.get("baseline_s") is not None:
            base = {}
        else:
            detail.pop("baseline_s", None)
        detail.update({k: v for k, v in base.items()
                       if k != "name" and k not in detail})
        b, e = detail.get("baseline_s"), detail.get("elapsed_s")
        if b and e:
            detail["speedup"] = round(b / e, 2)
        peak = peak_flops(detail.get("device_kind", ""))
        if peak and detail.get("model_flops") and e:
            detail["mfu"] = round(detail["model_flops"] / e / peak, 5)
        elif detail.get("model_flops") and e:
            # a device with no peak in the table (the CPU included): no
            # MFU claim, only the achieved model-flop rate
            detail["achieved_gflops_per_s"] = round(
                detail["model_flops"] / e / 1e9, 2)
        detail.pop("model_flops", None)
        self.details.append(detail)
        log(f"{name}: {json.dumps(detail)}")

    # -- final output -------------------------------------------------------

    def emit(self):
        if self._emitted:        # SIGTERM during normal emit: print once
            return
        self._emitted = True
        total = sum(d.get("elapsed_s") or 0.0 for d in self.details)
        speedups = [d["speedup"] for d in self.details if d.get("speedup")]
        geomean = (float(np.exp(np.mean(np.log(speedups))))
                   if speedups else 0.0)
        mfus = {d["name"]: d["mfu"] for d in self.details if d.get("mfu")}
        pipeline = next(
            (d for d in self.details if d["name"] == "pipeline_ml100k"),
            None)
        per_cfg = ", ".join(
            f"{d['name']} {d.get('speedup', '-')}x"
            + (f"/mfu {d['mfu']:.1%}" if d.get("mfu") else "")
            for d in self.details)
        # label with the device the details ran on
        kinds = sorted({d.get("device_kind", "?") for d in self.details})
        unit = (f"seconds total across {len(self.details)}/"
                f"{len(self.names)} configs on "
                f"{' + '.join(kinds) if kinds else '?'}; "
                f"speedups [{per_cfg}]")
        if pipeline:
            unit += (f"; pio-train {pipeline['train_s']}s "
                     f"(warm {pipeline.get('train_warm_s', '?')}s), query "
                     f"p50 {pipeline['query_p50_ms']}ms p99 "
                     f"{pipeline['query_p99_ms']}ms")
        # --only (subset) runs must not clobber the canonical full-suite
        # artifact the judge reads — they get a .partial sibling
        name = ("BENCH_DETAILS.json" if not self.partial
                else "BENCH_DETAILS.partial.json")
        path = os.environ.get("BENCH_DETAILS_PATH") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name)
        try:
            # temp-write + rename: BENCH_DETAILS.json is a durable
            # artifact diffed across runs — never leave half of one
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({"devinfo": self.devinfo, "details": self.details,
                           "failures": self.failures, "mfu": mfus,
                           "baselines": self.baselines}, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            pass
        # perf trajectory: append every judged config run to its own
        # BENCH_<config>.json history file (timestamped entries, headline
        # numbers, environment fingerprint) — the record nine PRs of
        # bench work never kept. History lands next to BENCH_DETAILS_PATH
        # when overridden (tests write to tmp, not the repo).
        history_dir = os.path.dirname(path)
        for detail in self.details:
            try:
                append_bench_history(history_dir, detail,
                                     partial=self.partial)
            except OSError:
                pass
        print(json.dumps({
            "metric": "judged_suite_wallclock",
            "value": round(total, 3),
            "unit": unit,
            "vs_baseline": round(geomean, 2),
        }), flush=True)


def environment_fingerprint() -> dict:
    """Enough context to interpret a historical bench number: interpreter,
    machine shape, and every BENCH_* knob that shaped the run."""
    import platform as _platform

    return {
        "python": sys.version.split()[0],
        "machine": _platform.machine(),
        "system": _platform.system(),
        "cpus": os.cpu_count(),
        "bench_env": {k: v for k, v in sorted(os.environ.items())
                      if k.startswith("BENCH_")},
    }


def append_bench_history(history_dir: str, detail: dict,
                         partial: bool = False) -> str:
    """Append one judged run to BENCH_<config>.json (a JSON list; read,
    append, temp-write + atomic rename). Returns the history path."""
    import datetime as _dt

    name = detail.get("name", "unknown")
    path = os.path.join(history_dir, f"BENCH_{name}.json")
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
            if not isinstance(history, list):
                history = []
        except (OSError, ValueError):
            history = []
    history.append({
        "ts": _dt.datetime.now(_dt.timezone.utc).isoformat(
            timespec="seconds"),
        "partial": partial,
        "detail": {k: v for k, v in detail.items() if k != "name"},
        "env": environment_fingerprint(),
    })
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def orchestrate(names, partial=False) -> int:
    """Run the suite; returns the process exit code: 0 only when a worker
    came up on the requested platform, every config produced a detail,
    and the worker tore down cleanly."""
    # default covers the summed per-config budgets PLUS worker init
    # (INIT_BUDGET_S, possibly retried) so the tail config (als_ml20m,
    # the north star) is not skipped as "suite deadline" on a
    # slow-but-healthy chip; if an outer driver timeout fires first the
    # SIGTERM handler dumps partials
    deadline_s = float(os.environ.get("BENCH_DEADLINE_S", 4020))
    suite = Suite(names, deadline_s, partial=partial)
    worker = None

    def _sigterm(_sig, _frm):
        log("SIGTERM — dumping partial results")
        suite.emit()
        if worker is not None:
            worker.kill()
        sys.exit(1)

    signal.signal(signal.SIGTERM, _sigterm)

    # baselines measure in parallel with the worker's start-up (a pure
    # numpy process beside one that is importing jax and claiming the
    # device — the overlap is nearly free)
    base_proc = WorkerHandle(["--baselines", ",".join(
        n for n in names if n in BASELINES)])

    # ONE platform for the whole run: a worker that cannot start on it is
    # retried once on the same platform, and then the run fails
    platform = resolve_platform()
    worker = suite.start_worker(platform)
    if worker is None:
        log(f"retrying {platform} worker once")
        worker = suite.start_worker(platform)
    if worker is None:
        log(f"no worker could start on {platform}; not measuring "
            "anywhere else")
        base_proc.kill()
        suite.emit()
        return 1

    # drain baselines (they are much faster than the claim; give slack)
    base_deadline = min(suite.deadline,
                        time.monotonic() + 600)
    while True:
        line = base_proc.read_until(("BASELINE", "BASELINES_DONE"),
                                    base_deadline)
        if line is None or line == "BASELINES_DONE":
            break
        info = json.loads(line[len("BASELINE "):])
        suite.baselines[info["name"]] = info
    base_proc.kill()
    log(f"baselines measured: {sorted(suite.baselines)}")

    def replace_wedged_worker(old):
        """Kill a wedged worker and start a fresh one on the SAME
        platform (None when it cannot start). The judged kernels and the
        platform never change mid-suite; the per-config retry bound and
        the suite deadline bound how often this can happen."""
        old.kill()
        log("respawning worker after wedge")
        return suite.start_worker(platform)

    pending = list(names)
    while pending:
        name = pending.pop(0)
        retried = False
        while name not in suite.done:
            if worker is None or not suite.run_config(worker, name):
                if worker is not None:
                    worker = replace_wedged_worker(worker)
                if worker is None or retried:
                    # a config that wedged two workers (or no worker at
                    # all) is marked failed; run_config already recorded
                    # the timeout, so just move on
                    suite.done.add(name)
                    if worker is None:
                        for n in pending:
                            suite.failures.append(
                                {"name": n, "error": "no worker available"})
                        pending = []
                    break
                retried = True    # ONE more chance on the fresh worker
            # run_config marked it done (success or clean in-worker fail)

    if worker is not None:
        worker.send("QUIT")
        try:
            rc = worker.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            rc = None
            worker.kill()
        if rc != 0:
            suite.failures.append(
                {"name": "_worker_exit",
                 "error": f"worker teardown rc={rc}",
                 "last_heartbeats": worker.err_tail[-5:]})
    suite.emit()
    if suite.failures:
        log(f"{len(suite.failures)} failure(s): "
            f"{[f.get('name') for f in suite.failures]}")
        return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true",
                    help="jax worker: claims the device, runs configs "
                         "fed over stdin")
    ap.add_argument("--baselines", help="comma-separated baseline subset "
                                        "(no-jax numpy worker)")
    ap.add_argument("--config", help="single-shot: run one config and exit "
                                     "(debugging)")
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--only", help="comma-separated config subset")
    args = ap.parse_args()

    if args.worker:
        worker_loop(args.platform)
        return
    if args.baselines is not None:
        worker_baselines([n for n in args.baselines.split(",") if n])
        return
    if args.config:
        jax, devices, mesh = setup_backend(args.platform)
        detail = CONFIGS[args.config][0](jax, mesh, devices[0].platform)
        print("BENCH_DETAIL " + json.dumps(detail), flush=True)
        return

    names = [n for n in CONFIGS if not n.startswith("_")]
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in CONFIGS]
        if unknown:
            log(f"unknown config(s) {unknown}; known: {list(CONFIGS)}")
            sys.exit(2)
    sys.exit(orchestrate(names, partial=bool(args.only)))


if __name__ == "__main__":
    main()
