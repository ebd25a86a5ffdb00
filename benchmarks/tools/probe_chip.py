#!/usr/bin/env python3
"""One-off facts from the chip that the harness is built against: what
memory_stats() holds, the precision of the program's f32 matmul, how a
device trace is laid out, and the probed lane crossover. Writes
chiprun_out/probe/. A development tool: no cell runs it."""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, HERE)
os.environ["JAX_PLATFORMS"] = "tpu"
OUT = os.path.join(HERE, "chiprun_out", "probe")
os.makedirs(OUT, exist_ok=True)

import jax
import jax.numpy as jnp
import numpy as np

report = {}
d = jax.devices()[0]
report["device"] = {"platform": d.platform, "kind": d.device_kind,
                    "count": len(jax.devices())}
report["memory_stats_start"] = d.memory_stats()

rng = np.random.default_rng(7)
N, K = 384_546, 128
V = rng.standard_normal((N, K), dtype=np.float32) / np.sqrt(K)
U = rng.standard_normal((4096, K), dtype=np.float32)
Vd = jax.device_put(V)


def topk(u, v, precision=None):
    return jax.lax.top_k(jnp.matmul(u, v.T, precision=precision), 16)


f_def = jax.jit(topk)
f_hi = jax.jit(lambda u, v: topk(u, v, jax.lax.Precision.HIGHEST))

# precision of the program's own expression (user_vecs @ V.T, no precision)
u64 = U[:64]
ref = u64.astype(np.float64) @ V.astype(np.float64).T
scale = np.linalg.norm(u64, axis=1)[:, None] * np.median(np.linalg.norm(V, axis=1))
prec = {}
for name, fn in (("default", f_def), ("highest", f_hi)):
    s, i = jax.device_get(fn(jnp.asarray(u64), Vd))
    true = np.take_along_axis(ref, i, axis=1)
    kth = np.sort(ref, axis=1)[:, -10][:, None]
    prec[name] = {
        "score_err_max": float(np.max(np.abs(s[:, :10] - true[:, :10]) / scale)),
        "rank_gap_max": float(np.max(np.maximum(kth - true[:, :10], 0) / scale)),
    }
# controls computed in numpy: bf16-rounded and int8-quantised operands
def bf16(x):
    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
def int8(x):
    s = np.max(np.abs(x), axis=1, keepdims=True) / 127.0
    return np.round(x / s) * s
for name, q in (("np_bf16", bf16), ("np_int8", int8)):
    s = (q(u64).astype(np.float64) @ q(V).astype(np.float64).T)
    i = np.argsort(-s, axis=1)[:, :10]
    sv = np.take_along_axis(s, i, axis=1)
    true = np.take_along_axis(ref, i, axis=1)
    kth = np.sort(ref, axis=1)[:, -10][:, None]
    prec[name] = {
        "score_err_max": float(np.max(np.abs(sv - true) / scale)),
        "rank_gap_max": float(np.max(np.maximum(kth - true, 0) / scale)),
    }
report["precision"] = prec

# kernel wall by bucket (blocked host clock, 5 calls) and memory after each
walls = {}
for b in (1, 8, 64, 256, 1024, 4096):
    ub = jnp.asarray(U[:b])
    jax.block_until_ready(f_def(ub, Vd))
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(f_def(ub, Vd))
    walls[b] = (time.perf_counter() - t0) / 5
    report[f"memory_stats_after_b{b}"] = {
        k: v for k, v in d.memory_stats().items() if "bytes" in k}
report["topk_wall_s_by_bucket"] = walls

# a small trace, kept as the recorded fixture of the reduction's test
tdir = os.path.join(OUT, "trace")
shutil.rmtree(tdir, ignore_errors=True)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
ub = jnp.asarray(U[:256])
jax.profiler.start_trace(tdir, profiler_options=opts)
for j in range(3):
    with jax.profiler.TraceAnnotation("bench_job", job=j):
        with jax.profiler.TraceAnnotation("bench_host:prepare"):
            time.sleep(0.02)
        jax.block_until_ready(f_def(ub, Vd))
        jax.block_until_ready(f_def(ub, Vd))
    time.sleep(0.01)
jax.profiler.stop_trace()
path = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))[0]
report["trace_file"] = {"path": os.path.relpath(path, HERE),
                        "bytes": os.path.getsize(path)}
shutil.copy(path, os.path.join(OUT, "small_trace.xplane.pb"))
pd = jax.profiler.ProfileData.from_file(path)
layout = []
for pl in pd.planes:
    for ln in pl.lines:
        evs = list(ln.events)
        layout.append({"plane": pl.name, "line": ln.name, "events": len(evs),
                       "first": [[e.name, e.start_ns, e.duration_ns,
                                  [[k, str(v)[:80]] for k, v in list(e.stats)[:6]]]
                                 for e in evs[:6]]})
report["trace_layout"] = layout

# the lane crossover as this process probes it
from predictionio_tpu.models import als
report["device_roundtrip_s"] = als.device_roundtrip_s()
report["host_flops"] = als._host_flops()
for name, n, k in (("ml20m", 26_744, 64), ("msd", 384_546, 128)):
    rows = None
    for b in (1, 2, 4, 8, 16, 32, 64, 128):
        if 2.0 * b * n * k / als._host_flops() >= als.device_roundtrip_s():
            rows = b
            break
    report[f"first_device_batch_{name}"] = rows
report["memory_stats_end"] = d.memory_stats()
with open(os.path.join(OUT, "probe.json"), "w") as f:
    json.dump(report, f, indent=1, default=str)
print(json.dumps({k: report[k] for k in (
    "device", "precision", "topk_wall_s_by_bucket", "trace_file",
    "device_roundtrip_s", "host_flops", "first_device_batch_ml20m",
    "first_device_batch_msd", "memory_stats_end")}, indent=1, default=str))
