"""bench.py orchestrator contract tests.

The driver runs `python bench.py` and records the LAST stdout line as the
round's judged result — these tests lock that contract: exactly one final
JSON line with the required keys, produced even when a config wedges its
worker (the r03 failure mode: rc=124, no line, no diagnostics).
"""

import json
import os
import re
import subprocess
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")

#: configs whose judged shape is too heavy to re-run inside tier-1, with
#: the reason on record. Every OTHER config MUST have a `_run(...)` smoke
#: below — test_every_bench_config_has_smoke enforces it, so a future
#: config cannot ship unsmoked without an explicit entry here.
HEAVY_EXEMPT = {
    "als_ml100k": "pure ALS kernel, ~60s of train even shrunk; the same "
                  "kernel is driven by the eval_sweep_grid smoke",
    "pipeline_ml100k": "full store->train->deploy->HTTP pipeline, minutes "
                       "on CPU; covered piecewise by the e2e test suite",
    "cooccurrence_ml1m": "1M-pair incidence build dominates at any scale",
    "ecommerce_implicit_als": "full implicit ALS train; the implicit "
                              "kernel is unit-tested in test_als",
    "als_ml20m": "north-star scale; even the CPU-scaled variant is "
                 "minutes of numpy baseline + train",
}


def _run(only: str, deadline: str, timeout: int, tmp_path, extra_env=None):
    env = dict(os.environ)
    env.update({"BENCH_PLATFORM": "cpu", "BENCH_DEADLINE_S": deadline,
                # keep the repo's committed judged artifact untouched
                "BENCH_DETAILS_PATH": str(tmp_path / "details.json")})
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, BENCH, "--only", only],
        capture_output=True, text=True, timeout=timeout, env=env)
    return p


def test_bench_emits_single_json_line(tmp_path):
    p = _run("naive_bayes_spam", "300", timeout=280, tmp_path=tmp_path)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert set(out) == {"metric", "value", "unit", "vs_baseline"}
    assert out["metric"] == "judged_suite_wallclock"
    assert out["value"] > 0
    assert "naive_bayes_spam" in out["unit"]


def test_bench_serving_batching_smoke(tmp_path):
    """Smoke the serving_batching config at a shrunken scale so tier-1
    exercises the bucketed/pipelined hot path end to end: the config
    itself asserts the compile-shape bound, and the emitted detail must
    carry the per-level latency + batch-size fields the judged run
    records."""
    p = _run("serving_batching", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_SERVING_QUERIES": "48",
                        "BENCH_SERVING_CLIENTS": "1,8",
                        "BENCH_SERVING_USERS": "200",
                        "BENCH_SERVING_ITEMS": "150",
                        # the 5% obs-overhead bar is a judged-scale
                        # assertion: at 48-query smoke scale p99 is
                        # scheduling noise, so only the mechanism is
                        # exercised here, not the bound
                        "BENCH_OBS_REPEATS": "1",
                        "BENCH_OBS_OVERHEAD_PCT": "10000",
                        "BENCH_OBS_OVERHEAD_ABS_MS": "1000",
                        "BENCH_ANATOMY_OVERHEAD_PCT": "10000",
                        "BENCH_ANATOMY_OVERHEAD_ABS_MS": "1000"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "serving_batching" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "serving_batching")
    for key in ("p50_ms_1c", "p99_ms_8c", "mean_batch_8c",
                "p99_ms_8c_single_inflight",
                "p99_ms_8c_obs_on", "p99_ms_8c_obs_off",
                "obs_overhead_pct",
                "p99_ms_8c_anatomy_on", "p99_ms_8c_anatomy_off",
                "anatomy_overhead_pct",
                "distinct_compiled_batch_shapes", "compile_shape_bound"):
        assert key in detail, (key, detail)
    assert 0 < detail["distinct_compiled_batch_shapes"] \
        <= detail["compile_shape_bound"]
    # concurrency must actually coalesce: 8 clients -> batches > 1
    assert detail["mean_batch_8c"] > 1.0
    # the run was appended to the per-config perf-trajectory history,
    # next to the overridden BENCH_DETAILS_PATH (never the repo root
    # from tests)
    history = json.load(open(tmp_path / "BENCH_serving_batching.json"))
    assert len(history) == 1
    entry = history[0]
    assert entry["partial"] is True
    assert entry["detail"]["p99_ms_8c"] == detail["p99_ms_8c"]
    assert entry["env"]["bench_env"]["BENCH_SERVING_QUERIES"] == "48"
    assert "ts" in entry and "python" in entry["env"]


def test_bench_deploy_swap_smoke(tmp_path):
    """Smoke the deploy_swap config at a shrunken scale: the config
    itself asserts the warm path pays ZERO post-cutover compiles, and
    the emitted detail must carry the cold/warm cutover latencies and
    compile deltas the judged run records."""
    p = _run("deploy_swap", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_DEPLOY_USERS": "300",
                        "BENCH_DEPLOY_ITEMS": "200",
                        "BENCH_DEPLOY_CYCLES": "1"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "deploy_swap" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "deploy_swap")
    for key in ("cold_first_traffic_ms", "warm_first_traffic_ms",
                "cold_post_swap_compiles", "warm_post_swap_compiles",
                "warm_prepare_ms", "cutover_speedup"):
        assert key in detail, (key, detail)
    # the acceptance criterion, visible in the judged artifact: a warm
    # swap serves its first post-cutover batches with no new compiles,
    # while the cold path demonstrably compiles on the serving path
    assert detail["warm_post_swap_compiles"] == 0
    assert detail["cold_post_swap_compiles"] > 0


def test_bench_train_ingest_smoke(tmp_path):
    """Smoke the train_ingest config at a shrunken scale: the config
    itself asserts per-event/columnar parity (identical interned code
    streams), and the emitted detail must carry the rows/s + speedup +
    cache-replay fields the judged run records for every swept backend."""
    p = _run("train_ingest", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_INGEST_EVENTS": "4000",
                        "BENCH_INGEST_BACKENDS": "parquet,sqlite"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "train_ingest" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "train_ingest")
    for backend in ("parquet", "sqlite"):
        for key in (f"rows_per_s_per_event_{backend}_4000",
                    f"rows_per_s_columnar_{backend}_4000",
                    f"speedup_{backend}_4000",
                    f"cache_hit_s_{backend}_4000"):
            assert key in detail, (key, detail)
        assert detail[f"rows_per_s_columnar_{backend}_4000"] > 0
    # the columnar path must actually beat the per-event fold, even at
    # smoke scale (the judged 100k sweep asserts nothing weaker)
    assert detail["speedup_headline"] > 1.0, detail


def test_bench_eval_sweep_grid_smoke(tmp_path):
    """Smoke the eval_sweep_grid config at a shrunken grid: the config
    itself asserts the compile ledger equals the number of distinct
    ranks AND that the batched and sequential paths pick the same best
    candidate; the emitted detail must carry the candidates/sec and
    compile-group fields the judged run records."""
    p = _run("eval_sweep_grid", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_EVAL_USERS": "150",
                        "BENCH_EVAL_ITEMS": "100",
                        "BENCH_EVAL_NNZ": "6000",
                        "BENCH_EVAL_FOLDS": "2",
                        "BENCH_EVAL_ITERS": "3",
                        "BENCH_EVAL_RANKS": "4,6",
                        "BENCH_EVAL_REGS": "0.01,0.1"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "eval_sweep_grid" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "eval_sweep_grid")
    for key in ("candidates_per_s_batched", "candidates_per_s_sequential",
                "speedup_batched_vs_sequential", "compile_groups",
                "distinct_ranks", "max_rmse_diff_vs_sequential",
                "grid_candidates"):
        assert key in detail, (key, detail)
    # the tentpole contract, visible in the judged artifact: the compile
    # ledger is bounded by distinct ranks, not the 4-candidate grid
    assert detail["compile_groups"] == detail["distinct_ranks"] == 2
    assert detail["grid_candidates"] == 4
    assert detail["max_rmse_diff_vs_sequential"] < 1e-4
    assert detail["candidates_per_s_batched"] > 0


def test_bench_ingest_write_smoke(tmp_path):
    """Smoke the ingest_write config at a shrunken scale: the config
    itself asserts the grouped path beats the per-request path by the
    floor, bounded ack p99, and exactly-once row counts; the emitted
    detail must carry the events/s + p99 + flush-size fields the judged
    run records for both backends. The judged-scale speedup floor is 5x
    (the tentpole bar); the smoke floor is relaxed — small batches on a
    busy 2-core CI box measure mostly scheduler noise. PR 17: the detail
    must also carry the 1/2/4-partition scaling curve (commit-wall
    regime); the judged floor is 2.5x at 4 partitions, the smoke floor
    is relaxed for the same reason."""
    p = _run("ingest_write", "300", timeout=280, tmp_path=tmp_path,
             # the speedup floor is 1.25, not 1.5: on a fast-fsync box
             # (tmpfs/ext4 with write cache) the per-request denominator
             # is cheap and the true smoke-scale ratio sits near 1.5, so
             # a 1.5 floor is a coin flip on measurement noise. The
             # coalescing contract is separately pinned by mean_flush.
             extra_env={"BENCH_INGEST_WRITE_EVENTS": "3072",
                        "BENCH_INGEST_WRITE_CLIENTS": "8",
                        "BENCH_INGEST_WRITE_MIN_SPEEDUP": "1.25",
                        "BENCH_INGEST_WRITE_P99_MS": "5000",
                        "BENCH_INGEST_SCALING_EVENTS": "2048",
                        "BENCH_INGEST_WRITE_MIN_SCALING": "1.3"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "ingest_write" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "ingest_write")
    for backend in ("sqlite", "parquet"):
        for key in (f"events_per_s_per_request_{backend}",
                    f"events_per_s_grouped_{backend}",
                    f"p99_ms_grouped_{backend}",
                    f"speedup_{backend}",
                    f"mean_flush_{backend}"):
            assert key in detail, (key, detail)
        # group commit must actually coalesce and actually win
        assert detail[f"mean_flush_{backend}"] > 1.0
        assert detail[f"speedup_{backend}"] >= 1.25
    assert detail["speedup_headline"] >= 1.25
    # PR 17: the partition scaling curve is persisted with every run,
    # with the injected commit wall disclosed alongside the numbers
    for parts in (1, 2, 4):
        assert detail[f"partition_events_per_s_{parts}"] > 0, detail
    for key in ("partition_scaling_2x", "partition_scaling_4x",
                "commit_floor_ms", "commit_floor_injected",
                "scaling_headline"):
        assert key in detail, (key, detail)
    assert detail["commit_floor_injected"] is True
    assert detail["scaling_headline"] >= 1.3


def test_bench_telemetry_smoke(tmp_path):
    """Smoke the telemetry config at a shrunken scale: the config itself
    asserts serving p99 with an aggressive 50ms scrape loop stays
    within the overhead bound of telemetry-off and that the tsdb
    write/read path round-trips; the emitted detail must carry the
    overhead + throughput + query-latency fields the judged run
    records. The judged bound is 5%; the smoke bound is relaxed — a
    p99 over a few hundred requests on a busy 2-core CI box is mostly
    scheduler noise."""
    p = _run("telemetry", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_TELEMETRY_QUERIES": "128",
                        "BENCH_TELEMETRY_SERIES": "1500",
                        "BENCH_TELEMETRY_TICKS": "4",
                        "BENCH_TELEMETRY_REPEATS": "2",
                        "BENCH_TELEMETRY_OVERHEAD_PCT": "150",
                        "BENCH_TELEMETRY_OVERHEAD_ABS_MS": "5"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "telemetry" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "telemetry")
    for key in ("p99_ms_telemetry_on", "p99_ms_telemetry_off",
                "telemetry_overhead_pct", "tsdb_samples_per_s",
                "range_query_ms", "quantile_over_time_ms"):
        assert key in detail, (key, detail)
    assert detail["tsdb_samples_written"] > 0
    assert detail["tsdb_samples_per_s"] > 0
    assert detail["range_query_ms"] > 0


def test_bench_foldin_freshness_smoke(tmp_path):
    """Smoke the foldin_freshness config at a shrunken scale: the config
    itself asserts the batched-solve speedup floor, the bounded
    als_foldin compile ledger, and the p95 event→reflected bound; the
    emitted detail must carry the freshness + throughput fields the
    judged run records. The judged-scale speedup floor is 5x (the
    tentpole bar); the smoke floor is relaxed and the p95 slack widened
    — a busy 2-core CI box pays scheduler noise per apply tick."""
    p = _run("foldin_freshness", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_FOLDIN_USERS": "300",
                        "BENCH_FOLDIN_ITEMS": "150",
                        "BENCH_FOLDIN_RANK": "8",
                        "BENCH_FOLDIN_SOLVE_BATCH": "32",
                        "BENCH_FOLDIN_STREAM_USERS": "12",
                        "BENCH_FOLDIN_MIN_SPEEDUP": "1.5",
                        "BENCH_FOLDIN_P95_SLACK": "5.0"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "foldin_freshness" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "foldin_freshness")
    for key in ("foldins_per_s_batched", "foldins_per_s_sequential",
                "speedup_batched", "foldin_compiled_shapes",
                "foldin_shape_bound", "p50_event_to_reflected_s",
                "p95_event_to_reflected_s", "p95_bound_s", "applies",
                "applied_user_rows"):
        assert key in detail, (key, detail)
    # the tentpole contract, visible in the judged artifact: one
    # batched device program beats per-row dispatches and the solver's
    # compiled shapes stay inside the bucket ladder
    assert detail["speedup_batched"] >= 1.5
    assert 0 < detail["foldin_compiled_shapes"] \
        <= detail["foldin_shape_bound"]
    assert detail["p95_event_to_reflected_s"] <= detail["p95_bound_s"]
    assert detail["applied_user_rows"] >= 12


def test_bench_als_kernel_smoke(tmp_path):
    """Smoke the als_kernel config at a shrunken scale: the config itself
    asserts held-out RMSE parity at matched quality and the als_train
    compile-ledger bound; the emitted detail must carry the per-rank
    timing/RMSE/speedup fields the judged run records. The judged-scale
    speedup floor is 2x at rank >= 64 (the tentpole bar); the smoke floor
    is relaxed — at smoke scale the solve is too small for the full
    path's bandwidth wall to show above 2-core CI scheduler noise."""
    p = _run("als_kernel", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_ALS_USERS": "300",
                        "BENCH_ALS_ITEMS": "120",
                        "BENCH_ALS_NNZ": "9000",
                        "BENCH_ALS_ITERS": "4",
                        "BENCH_ALS_RANKS": "8,64",
                        "BENCH_ALS_BLOCK": "8",
                        "BENCH_ALS_MIN_SPEEDUP": "0",
                        "BENCH_ALS_RMSE_SLACK": "0.2"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "als_kernel" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "als_kernel")
    for rank in (8, 64):
        for key in (f"train_s_full_r{rank}", f"train_s_subspace_r{rank}",
                    f"heldout_rmse_full_r{rank}",
                    f"heldout_rmse_subspace_r{rank}",
                    f"speedup_r{rank}"):
            assert key in detail, (key, detail)
        assert detail[f"train_s_subspace_r{rank}"] > 0
    # one compiled program per (rank, solver) family, never per train call
    assert 0 < detail["compile_ledger_delta"] <= 4
    assert detail["speedup_headline"] is not None
    assert detail["iters_subspace"] >= detail["iters_full"]


def test_bench_batch_predict_smoke(tmp_path):
    """Smoke the batch_predict config at a shrunken scale: the config
    itself asserts byte-identical jsonl output, value-identical parquet
    output (single-process AND 2-shard merged), and the compile-shape
    ledger bound; the emitted detail must carry the per-path qps +
    speedup fields the judged run records. The judged-scale throughput
    floor is 4x (the tentpole bar); the smoke floors are disabled — at
    smoke scale fixed costs (spawn, first-chunk warmup) swamp the
    steady-state ratio on a busy 2-core CI box."""
    p = _run("batch_predict", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_BP_USERS": "400",
                        "BENCH_BP_ITEMS": "200",
                        "BENCH_BP_RANK": "8",
                        "BENCH_BP_QUERIES": "2000",
                        "BENCH_BP_CHUNK": "256",
                        "BENCH_BP_NUM": "10",
                        "BENCH_BP_MIN_SPEEDUP": "0",
                        "BENCH_BP_MIN_PIPE": "0"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "batch_predict" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "batch_predict")
    for key in ("qps_sequential", "qps_pipelined", "qps_columnar",
                "qps_sharded_2proc", "speedup_pipelined",
                "speedup_columnar", "speedup_sharded_2proc",
                "speedup_headline", "pad_waste_rows",
                "distinct_compiled_batch_shapes", "compile_shape_bound"):
        assert key in detail, (key, detail)
    assert detail["qps_columnar"] > 0
    # the tentpole contract, visible in the judged artifact: the batch
    # scorer's compiled shapes stay inside the bucket ladder
    assert 0 < detail["distinct_compiled_batch_shapes"] \
        <= detail["compile_shape_bound"]


def test_bench_topk_scoring_smoke(tmp_path):
    """Smoke the topk_scoring config at a shrunken catalog: the config
    itself asserts recall parity, the quantized factor-byte halving,
    and the scoring compile ledger; the speedup floor is relaxed — at
    16k items the exact matmul is nowhere near the bandwidth wall the
    judged 262k-item run measures against."""
    p = _run("topk_scoring", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_TOPK_ITEMS": "16384",
                        "BENCH_TOPK_RANK": "16",
                        "BENCH_TOPK_BATCH": "4",
                        "BENCH_TOPK_BATCHES": "2",
                        "BENCH_TOPK_TILE": "4096",
                        "BENCH_TOPK_SHORTLIST": "96",
                        "BENCH_TOPK_MIN_SPEEDUP": "0.05"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "topk_scoring" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "topk_scoring")
    for key in ("qps_exact", "qps_fused", "qps_fused_bf16",
                "qps_fused_int8", "qps_twostage", "speedup_twostage",
                "recall_fused", "recall_fused_int8", "recall_twostage",
                "factor_bytes_fused_int8", "compile_ledger_delta",
                "compile_ledger_bound"):
        assert key in detail, (key, detail)
    # the parity + memory + ledger contracts hold even at smoke scale
    assert detail["recall_twostage"] >= 0.99
    assert detail["factor_bytes_fused_int8"] * 2 <= 16384 * 16 * 4
    assert 0 < detail["compile_ledger_delta"] \
        <= detail["compile_ledger_bound"]
    # the run landed in the per-config perf-trajectory history
    history = json.load(open(tmp_path / "BENCH_topk_scoring.json"))
    assert len(history) == 1
    assert history[0]["detail"]["speedup_twostage"] == \
        detail["speedup_twostage"]


def test_bench_fleet_scaling_smoke(tmp_path):
    """Smoke the fleet_scaling config at a shrunken scale: the config
    itself asserts zero dropped queries, the exact error-diffusion
    spread, and the sharded catalog's budget-fit + exact parity; the
    emitted detail must carry the per-stage qps/p99 + sharded fields
    the judged run records. The judged-scale scaling floor is 3x at 4
    replicas (the tentpole bar); the smoke floor is relaxed — short
    stages on a busy 2-core CI box measure mostly scheduler noise."""
    p = _run("fleet_scaling", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_FLEET_SERVICE_MS": "15",
                        "BENCH_FLEET_STAGE_S": "1.2",
                        "BENCH_FLEET_MIN_SCALING": "1.5",
                        "BENCH_FLEET_P99_RATIO": "10",
                        "BENCH_FLEET_ITEMS": "20000",
                        "BENCH_FLEET_RANK": "16",
                        "BENCH_FLEET_SHARDS": "4"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "fleet_scaling" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "fleet_scaling")
    for key in ("qps_1", "qps_2", "qps_4", "p99_ms_1", "p99_ms_4",
                "scaling_4", "sharded_parity", "catalog_bytes",
                "device_budget_bytes", "max_shard_factor_bytes",
                "service_floor_injected"):
        assert key in detail, (key, detail)
    assert detail["scaling_4"] >= 1.5
    assert detail["service_floor_injected"] is True
    # the sharded catalog really exceeds the per-device budget its
    # shards individually fit, and parity to the unsharded scorer held
    assert detail["max_shard_factor_bytes"] <= \
        detail["device_budget_bytes"] < detail["catalog_bytes"]
    assert detail["sharded_parity"] == 1.0
    # the run landed in the per-config perf-trajectory history
    history = json.load(open(tmp_path / "BENCH_fleet_scaling.json"))
    assert len(history) == 1
    assert history[0]["detail"]["scaling_4"] == detail["scaling_4"]


def test_bench_loadtest_smoke(tmp_path):
    """Smoke the loadtest config end to end at a shrunken scale: both
    legs run real fleets — the sustained leg with a mid-run
    retrain-and-promote, the chaos leg (parquet) with a replica
    kill+restart and a compaction crash — and the config itself asserts
    every runtime invariant (zero dropped acks, exactly-once audit, one
    LIVE release). The emitted detail must carry the per-lane acked/p99
    fields and both legs' audit tallies the judged run records."""
    p = _run("loadtest", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_LOADTEST_POPULATION": "400",
                        "BENCH_LOADTEST_ITEMS": "80",
                        "BENCH_LOADTEST_DURATION_S": "8",
                        "BENCH_LOADTEST_RATE": "40",
                        "BENCH_LOADTEST_CHAOS_DURATION_S": "6",
                        "BENCH_LOADTEST_CHAOS_RATE": "25",
                        # p99 bounds are a judged-scale assertion; the
                        # smoke exercises the mechanism, not the bar
                        "BENCH_LOADTEST_P99_MS": "30000"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "loadtest" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "loadtest")
    for key in ("sustained_arrivals", "sustained_active_users",
                "sustained_events_acked", "sustained_events_p99_ms",
                "sustained_queries_acked", "sustained_queries_p99_ms",
                "sustained_feedback_acked", "sustained_audited_events",
                "sustained_ops_per_s", "foldin_applied_rows",
                "chaos_arrivals", "chaos_events_acked",
                "chaos_audited_events", "chaos_audit_ok"):
        assert key in detail, (key, detail)
    assert detail["sustained_events_acked"] > 0
    assert detail["sustained_queries_acked"] > 0
    assert detail["foldin_applied_rows"] > 0
    assert detail["chaos_audit_ok"] is True
    # the run landed on the per-config perf-trajectory history
    history = json.load(open(tmp_path / "BENCH_loadtest.json"))
    assert history[-1]["detail"]["sustained_ops_per_s"] > 0


def test_bench_multitenant_smoke(tmp_path):
    """Smoke the multitenant config end to end at a shrunken scale:
    three engine families (recommendation, similarproduct,
    recommended_user) consolidated behind one MultiTenantServer under a
    deliberately undersized device budget. The config itself asserts
    the judged gates (eviction+warm-reload cycle turns, end-state
    residency under the budget which is under the standalone sum,
    per-tenant p99 within slack of its standalone baseline) — the smoke
    exercises the mechanism at small scale with the p99 bar relaxed."""
    p = _run("multitenant", "300", timeout=280, tmp_path=tmp_path,
             extra_env={"BENCH_MT_ITEMS": "400",
                        "BENCH_MT_USERS": "80",
                        "BENCH_MT_RANK": "16",
                        "BENCH_MT_QUERIES": "60",
                        "BENCH_MT_PASSES": "2",
                        # p99 parity is a judged-scale assertion; smoke
                        # scale is dominated by per-request overhead
                        "BENCH_MT_P99_SLACK": "50.0"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE json line, got: {lines}"
    out = json.loads(lines[0])
    assert "multitenant" in out["unit"]
    detail = next(d for d in
                  json.load(open(tmp_path / "details.json"))["details"]
                  if d["name"] == "multitenant")
    assert detail["families"] == ["recommendation", "similarproduct",
                                  "recommended_user"]
    # the cycle turned: evictions happened AND warm reloads served
    assert detail["evictions"] > 0
    assert detail["warm_reloads"] > 0
    # consolidation saved bytes: end residency fits a budget that is
    # itself smaller than the standalone residencies summed
    standalone_total = sum(detail["standalone_resident_bytes"].values())
    assert detail["resident_bytes_end"] <= detail["budget_bytes"]
    assert detail["budget_bytes"] < standalone_total
    for name in ("rec", "sim", "social"):
        assert detail["consolidated_p99_ms"][name] > 0
        assert detail["baseline_p99_ms"][name] > 0
    # the run landed on the per-config perf-trajectory history
    history = json.load(open(tmp_path / "BENCH_multitenant.json"))
    assert history[-1]["detail"]["evictions"] > 0


def test_every_bench_config_has_smoke():
    """Static gate: every bench.py config must either have a `_run(...)`
    smoke in this file or a justified HEAVY_EXEMPT entry — future
    configs cannot ship unsmoked."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_module", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    configs = {n for n in bench.CONFIGS if not n.startswith("_")}

    with open(__file__) as f:
        src = f.read()
    smoked = set()
    for arg in re.findall(r'_run\(\s*"([^"]+)"', src):
        smoked.update(n for n in arg.split(",") if not n.startswith("_"))
    unknown = (smoked | set(HEAVY_EXEMPT)) - configs
    assert not unknown, f"smoke/exempt entries for unknown configs: {unknown}"
    uncovered = configs - smoked - set(HEAVY_EXEMPT)
    assert not uncovered, (
        f"bench configs with neither a smoke test nor a HEAVY_EXEMPT "
        f"entry: {sorted(uncovered)}")


def test_bench_survives_wedged_worker_and_reports_partial(tmp_path):
    """A config that hangs its worker (the hidden _sleep_forever wedge
    simulator, budget 15s) must not take down the suite: the next config
    still runs on a fresh worker of the SAME platform and the final line
    still prints — but a suite with a hole exits non-zero."""
    p = _run("_sleep_forever,naive_bayes_spam", "300", timeout=280,
             tmp_path=tmp_path)
    assert p.returncode != 0, p.stderr[-2000:]
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1
    out = json.loads(lines[0])
    assert "naive_bayes_spam" in out["unit"]      # measured despite wedge
    assert "1/2" in out["unit"]                   # and the hole is visible
    assert "TIMEOUT" in p.stderr
    doc = json.load(open(tmp_path / "details.json"))
    # both attempts (first worker + the one retry) are on record
    assert {f["name"] for f in doc["failures"]} == {"_sleep_forever"}
    # the platform never changed within the run
    assert {d["platform"] for d in doc["details"]} == {"cpu"}


def test_bench_without_the_requested_platform_fails_fast(tmp_path):
    """No device of the requested platform -> non-zero exit, nothing
    measured anywhere else (the old ladder carried on on the CPU and
    wrote the result under the same keys)."""
    p = _run("naive_bayes_spam", "300", timeout=120, tmp_path=tmp_path,
             extra_env={"BENCH_PLATFORM": "tpu"})
    assert p.returncode != 0
    doc = json.load(open(tmp_path / "details.json"))
    assert doc["details"] == []
    assert doc["failures"] and all(
        f["name"] == "_worker_init_tpu" for f in doc["failures"])
