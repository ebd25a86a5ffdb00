#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path runs on the chip.

Events in the store -> ``pio train`` -> release registered -> ``pio deploy``
-> ``/queries.json``, through the normal CLI entry points, with the
`recommendation` template (explicit ALS) at MovieLens-20M widths and
rank 64. Depth is cut (2M of 20M ratings, 3 of 20 iterations) so the
whole run, cold compile included, fits one chip call; widths are not.

    python3 chip_smoke.py            # needs a TPU; exits non-zero without
    python3 chip_smoke.py --tiny     # CPU rehearsal of the same control flow

This process never imports JAX: it writes data and config and runs one
child at a time (a chip belongs to one process), each with
``JAX_PLATFORMS`` naming the platform so that JAX itself raises when it
finds no such device. Every phase is a hard failure. On success stdout
carries two JSON lines: the run's report (also written to
``<out>/chip_smoke.json``), then, LAST, the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. On failure nothing is printed to stdout
and the exit code is non-zero. The numbers in the report are set-up facts
of one run, not performance claims.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: MovieLens-20M (GroupLens ml-20m README: 20,000,263 ratings, 138,493
#: users, 26,744 movies); explicit ALS as the reference recommendation
#: template configures it, at rank 64
FULL = {
    "source": "MovieLens-20M widths (GroupLens ml-20m README), "
              "recommendation template, explicit ALS",
    "n_users": 138_493, "n_items": 26_744, "rank": 64,
    "n_events": 2_000_000, "num_iterations": 3,
    "kernel_ranks": [10, 16, 64], "kernel_systems": 138_493,
    "reduced": {"n_events": "20,000,263 -> 2,000,000 rate events",
                "num_iterations": "20 -> 3"},
}
#: the CPU rehearsal (--tiny): same control flow, toy sizes
TINY = {
    "source": "toy sizes for the CPU rehearsal (--tiny)",
    "n_users": 300, "n_items": 200, "rank": 8,
    "n_events": 6_000, "num_iterations": 2,
    "kernel_ranks": [4, 10], "kernel_systems": 200,
    "reduced": {"everything": "toy sizes; not a configuration anyone runs"},
}

NUM = 10                 # itemScores asked for per query
N_SEQUENTIAL = 8
N_BURST = 64
APP = "chipsmoke"
ACCESS_KEY = "chipsmoke-stop"

_live = []               # Popen objects to kill on any exit path


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.time() - T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


class SmokeFailure(Exception):
    pass


def fail(msg: str):
    raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def child_env(platform: str, out: str) -> dict:
    """The environment every child runs in: the checkout on the path (no
    dependence on `pip install -e`), the platform pinned so JAX raises
    instead of choosing another, storage configured the way a user
    would (conf/pio-env.sh.template scheme, default backends)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = platform
    storage = os.path.join(out, "storage")
    env.update({
        "PIO_HOME": os.path.join(storage, "home"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": os.path.join(storage, "pio.db"),
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(storage, "models"),
    })
    return env


def run_child(name: str, argv, env, out: str, timeout: float,
              cwd=None) -> str:
    """Run one child to completion; its combined output lands in
    ``<out>/<name>.log`` and is returned. Non-zero exit or timeout fails
    the smoke."""
    log_path = os.path.join(out, f"{name}.log")
    t0 = time.time()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(argv, env=env, cwd=cwd or HERE, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        _live.append(proc)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            kill(proc)
            fail(f"{name}: no exit within {timeout:g}s (see {log_path})")
        _live.remove(proc)
    with open(log_path, errors="replace") as f:
        text = f.read()
    if rc != 0:
        fail(f"{name}: exit code {rc} after {time.time() - t0:.1f}s; "
             f"last output:\n{text[-3000:]}")
    return text


def kill(proc) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        proc.wait(timeout=30)
    if proc in _live:
        _live.remove(proc)


def pio(*args):
    return [sys.executable, "-m", "predictionio_tpu.cli.main", *args]


# ---------------------------------------------------------------------------
# phase bodies that run INSIDE a child (the only code here that may import
# jax); reached through the hidden --child argument
# ---------------------------------------------------------------------------

def child_probe() -> None:
    """Name the device as JAX reports it, and the compile cache directory
    the program's own function yields."""
    from predictionio_tpu.utils.device import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    print("PROBE " + json.dumps({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": cache_dir,
    }), flush=True)


def child_kernels(ranks, n_systems: int, seed: int, interpret: bool) -> None:
    """Compile and run every `pallas_call` the package ships at main-path
    shapes and hold each to its plain-JAX counterpart."""
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops.linalg import (
        cholesky_solve_pallas, cholesky_solve_vec,
    )

    @functools.partial(jax.jit, static_argnames=("s", "k"))
    def problem(key, s, k):
        ka, kb = jax.random.split(key)
        m = jax.random.normal(ka, (s, k, k), jnp.float32)
        # the ALS normal-equation shape: a Gramian plus a ridge
        a = jnp.einsum("sik,sjk->sij", m, m) / k \
            + jnp.eye(k, dtype=jnp.float32)
        return a, jax.random.normal(kb, (s, k), jnp.float32)

    @jax.jit
    def rel_residual(a, x, b):
        # full f32 products: the TPU's default matmul precision would
        # make the residual of an exact answer look like 1e-3
        ax = jnp.einsum("sij,sj->si", a, x,
                        precision=jax.lax.Precision.HIGHEST)
        return jnp.max(jnp.linalg.norm(ax - b, axis=1)
                       / jnp.linalg.norm(b, axis=1))

    rows = []
    for k in ranks:
        a, b = problem(jax.random.PRNGKey(seed + k), n_systems, k)
        jax.block_until_ready(a)
        t0 = time.perf_counter()
        x_p = cholesky_solve_pallas(a, b, interpret=interpret)
        jax.block_until_ready(x_p)
        first_s = time.perf_counter() - t0
        resid = float(rel_residual(a, x_p, b))
        x_v = np.asarray(cholesky_solve_vec(a, b))
        x_p = np.asarray(x_p)
        row = {
            "kernel": "cholesky_solve_pallas", "k": k, "systems": n_systems,
            "interpret": interpret,
            "first_call_s": round(first_s, 3),
            "finite": bool(np.isfinite(x_p).all()),
            "max_abs_diff_vs_vec": float(np.max(np.abs(x_p - x_v))),
            "max_rel_residual": resid,
        }
        # f32 tolerance for a well-conditioned K x K solve, both paths
        # running the same recurrence in another order
        row["ok"] = bool(row["finite"]
                         and np.allclose(x_p, x_v, rtol=2e-4, atol=2e-4)
                         and resid < 1e-4)
        rows.append(row)
        del a, b
    print("KERNELS " + json.dumps(rows), flush=True)
    if not all(r["ok"] for r in rows):
        sys.exit(1)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def write_events(path: str, cfg: dict, seed: int) -> None:
    """`rate` events from `seed`: every user and item id appears at least
    once (widths are not cut), item popularity is skewed, ratings are a
    low-rank signal plus noise on MovieLens' half-star scale."""
    import numpy as np

    rng = np.random.default_rng(seed)
    nu, ni, n = cfg["n_users"], cfg["n_items"], cfg["n_events"]
    cover = max(nu, ni)
    if n < cover:
        fail(f"{n} events cannot cover {nu} users x {ni} items")
    users = np.concatenate([np.arange(cover) % nu,
                            rng.integers(0, nu, n - cover)])
    items = np.concatenate([np.arange(cover) % ni,
                            (ni * rng.random(n - cover) ** 2).astype(np.int64)])
    lat_u = rng.normal(size=(nu, 4))
    lat_v = rng.normal(size=(ni, 4))
    raw = 3.0 + 0.7 * np.einsum("nk,nk->n", lat_u[users], lat_v[items]) \
        + 0.3 * rng.normal(size=n)
    ratings = np.clip(np.round(raw * 2) / 2, 0.5, 5.0)
    with open(path, "w") as f:
        for u, i, r in zip(users.tolist(), items.tolist(), ratings.tolist()):
            f.write(
                '{"event":"rate","entityType":"user","entityId":"%d",'
                '"targetEntityType":"item","targetEntityId":"%d",'
                '"properties":{"rating":%s},'
                '"eventTime":"2015-03-31T00:00:00.000Z"}\n'
                % (u + 1, i + 1, r))


# ---------------------------------------------------------------------------
# HTTP
# ---------------------------------------------------------------------------

def http(method: str, url: str, body=None, timeout: float = 120.0):
    data = json.dumps(body).encode() if body is not None else (
        b"" if method == "POST" else None)
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read()


def dispatch_seconds(base: str) -> dict:
    """``pio_device_dispatch_seconds_total`` per compiled family."""
    _, text = http("GET", f"{base}/metrics")
    out = {}
    for m in re.finditer(
            r'^pio_device_dispatch_seconds_total\{family="([^"]+)"\}\s+(\S+)',
            text.decode(), re.M):
        out[m.group(1)] = float(m.group(2))
    return out


def topk_dispatch(metrics: dict) -> float:
    return sum(v for k, v in metrics.items() if k.startswith("als_topk"))


def query(base: str, user: str, cfg: dict):
    """One query, checked: HTTP 200, NUM itemScores, finite, descending,
    distinct catalog items. Returns (seconds, itemScores)."""
    t0 = time.perf_counter()
    status, raw = http("POST", f"{base}/queries.json",
                       {"user": user, "num": NUM})
    dt = time.perf_counter() - t0
    if status != 200:
        fail(f"query user={user}: HTTP {status}: {raw[:300]!r}")
    scores = json.loads(raw).get("itemScores")
    if not isinstance(scores, list) or len(scores) != NUM:
        fail(f"query user={user}: wanted {NUM} itemScores, got {raw[:300]!r}")
    vals = [s["score"] for s in scores]
    if not all(isinstance(v, (int, float)) and math.isfinite(v)
               for v in vals):
        fail(f"query user={user}: non-finite score in {vals}")
    if any(a < b for a, b in zip(vals, vals[1:])):
        fail(f"query user={user}: scores not descending: {vals}")
    ids = [s["item"] for s in scores]
    if len(set(ids)) != NUM or not all(
            1 <= int(i) <= cfg["n_items"] for i in ids):
        fail(f"query user={user}: bad item ids {ids}")
    return dt, scores


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def cache_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path)) \
        if os.path.isdir(path) else 0


# ---------------------------------------------------------------------------
# the smoke
# ---------------------------------------------------------------------------

def smoke(args) -> dict:
    cfg = TINY if args.tiny else FULL
    platform = "cpu" if args.tiny else "tpu"
    out = os.path.abspath(args.out)
    storage = os.path.join(out, "storage")
    shutil.rmtree(storage, ignore_errors=True)
    os.makedirs(storage, exist_ok=True)
    env = child_env(platform, out)
    me = [sys.executable, os.path.abspath(__file__)]
    phases = {}

    @contextlib.contextmanager
    def timed(name):
        say(f"phase {name} ...")
        t0 = time.time()
        yield
        phases[name] = round(time.time() - t0, 3)
        say(f"phase {name} ok ({phases[name]:.1f}s)")

    # 0. the device, as JAX reports it — seconds, and fatal without a chip
    with timed("probe"):
        text = run_child("probe", me + ["--child", "probe"], env, out, 300)
        probe = json.loads(text[text.rindex("PROBE ") + 6:].splitlines()[0])
        if probe["platform"] != platform:
            fail(f"JAX reports platform {probe['platform']!r}, "
                 f"expected {platform!r}")
    cache_dir = probe["compile_cache_dir"]
    cache_before = cache_entries(cache_dir)

    # 1. events into the store
    events_path = os.path.join(storage, "events.jsonl")
    with timed("generate_events"):
        write_events(events_path, cfg, args.seed)
    with timed("app_new"):
        run_child("app_new", pio("app", "new", APP), env, out, 300)
    with timed("import"):
        text = run_child("import", pio("import", "--appname", APP,
                                       "--input", events_path),
                         env, out, 1800)
        if f"Imported {cfg['n_events']} events" not in text:
            fail(f"import did not report {cfg['n_events']} events:\n"
                 f"{text[-500:]}")
    os.unlink(events_path)

    # 2. every pallas_call the package ships, compiled for real
    with timed("kernels"):
        text = run_child(
            "kernels",
            me + ["--child", "kernels", "--seed", str(args.seed)]
            + (["--tiny"] if args.tiny else []), env, out, 1800)
        kernels = json.loads(
            text[text.rindex("KERNELS ") + 8:].splitlines()[0])

    # 3. pio train
    engine_dir = os.path.join(out, "engine")
    shutil.rmtree(engine_dir, ignore_errors=True)
    run_child("template_get", pio("template", "get", "recommendation",
                                  engine_dir), env, out, 300)
    variant_path = os.path.join(engine_dir, "engine.json")
    with open(variant_path) as f:
        variant = json.load(f)
    variant["datasource"]["params"]["app_name"] = APP
    # solver and scorer are left at their defaults (full, exact)
    variant["algorithms"][0]["params"].update(
        {"rank": cfg["rank"], "num_iterations": cfg["num_iterations"]})
    with open(variant_path, "w") as f:
        json.dump(variant, f, indent=2)
    with timed("train"):
        train_log = run_child("train", pio("train", "--variant",
                                           variant_path),
                              env, out, 3000, cwd=engine_dir)
    m = re.search(r"Training completed\. Engine instance: (\S+)", train_log)
    if not m:
        fail(f"train printed no instance id:\n{train_log[-1500:]}")
    instance_id = m.group(1)
    mesh_line = next((ln for ln in train_log.splitlines()
                      if "mesh: " in ln), "")
    if f"platform={platform} " not in mesh_line:
        fail(f"train's mesh line does not name platform={platform}: "
             f"{mesh_line!r}")
    releases = run_child("releases", pio("releases", "--variant",
                                         variant_path),
                         env, out, 300, cwd=engine_dir)
    if instance_id not in releases:
        fail(f"instance {instance_id} is not in the release registry:\n"
             f"{releases[-800:]}")

    # 4. pio deploy (background) — only COMPLETED instances deploy
    port = free_port()
    base = f"http://localhost:{port}"
    deploy_log = open(os.path.join(out, "deploy.log"), "wb")
    with timed("deploy_ready"):
        deploy = subprocess.Popen(
            pio("deploy", "--variant", variant_path, "--port", str(port),
                "--accesskey", ACCESS_KEY),
            env=env, cwd=engine_dir, stdout=deploy_log,
            stderr=subprocess.STDOUT, start_new_session=True)
        _live.append(deploy)
        deadline = time.time() + 900
        root = None
        while root is None:
            if deploy.poll() is not None:
                fail(f"deploy exited with code {deploy.returncode} before "
                     "it was ready; see deploy.log")
            if time.time() > deadline:
                fail("deploy not ready on / within 900s")
            try:
                status, raw = http("GET", f"{base}/", timeout=5)
                if status == 200:
                    root = json.loads(raw)
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(0.5)
    if root.get("engineInstance", {}).get("id") != instance_id:
        fail(f"deploy serves instance {root.get('engineInstance')}, "
             f"trained {instance_id}")

    # 5. queries through HTTP
    import numpy as np

    rng = np.random.default_rng(args.seed + 1)
    users = [str(int(u) + 1)
             for u in rng.choice(cfg["n_users"], N_BURST, replace=False)]
    with timed("queries_sequential"):
        d0 = dispatch_seconds(base)
        seq = [query(base, u, cfg) for u in users[:N_SEQUENTIAL]]
        d1 = dispatch_seconds(base)
    with timed("queries_burst"):
        with concurrent.futures.ThreadPoolExecutor(N_BURST) as pool:
            burst = list(pool.map(lambda u: query(base, u, cfg), users))
        d2 = dispatch_seconds(base)
    # the same user asked alone and inside the burst must get the same
    # answer, whichever lane served either (the device matmul runs at the
    # TPU's default precision, so order may differ among near-ties)
    agree = []
    for (_, alone), (_, batched) in zip(seq, burst):
        a = {s["item"] for s in alone}
        b = {s["item"] for s in batched}
        top_rel = abs(alone[0]["score"] - batched[0]["score"]) / max(
            abs(alone[0]["score"]), 1e-6)
        agree.append({"overlap": len(a & b), "top_score_rel_diff": top_rel})
        if len(a & b) < NUM // 2 or top_rel > 5e-2:
            fail(f"answers alone and in the burst disagree: {alone} vs "
                 f"{batched}")
    _, raw = http("GET", f"{base}/capacity.json")
    capacity = json.loads(raw)
    device_bytes = int(capacity["process"]["deviceBytes"])
    item_factor_bytes = cfg["n_items"] * cfg["rank"] * 4
    burst_dispatch = topk_dispatch(d2) - topk_dispatch(d1)
    lane = {
        # which lane answered the 8 single-user queries is the crossover's
        # own choice (ALSModel._use_host); only the burst must be device
        "sequential_lane": "device" if topk_dispatch(d1) > topk_dispatch(d0)
        else "host",
        "topk_dispatch_s_before": topk_dispatch(d0),
        "topk_dispatch_s_after_sequential": topk_dispatch(d1),
        "topk_dispatch_s_after_burst": topk_dispatch(d2),
        "dispatch_families_after_burst": d2,
        "deviceBytes": device_bytes,
        "item_factor_bytes": item_factor_bytes,
        "device_served_burst": burst_dispatch > 0
        and device_bytes >= item_factor_bytes,
    }
    if not args.tiny and not lane["device_served_burst"]:
        fail("the device did not serve the burst (host-BLAS lane answered "
             f"every query): {json.dumps(lane)}")

    # 6. stop
    with timed("stop"):
        http("POST", f"{base}/stop?accessKey={ACCESS_KEY}")
        try:
            rc = deploy.wait(timeout=120)
        except subprocess.TimeoutExpired:
            fail("deploy did not exit within 120s of POST /stop")
        _live.remove(deploy)
        if rc != 0:
            fail(f"deploy exited with code {rc} after POST /stop")
    deploy_log.close()

    later = sorted(dt for dt, _ in seq[1:])
    return {
        "platform": probe["platform"],
        "device_kind": probe["device_kind"],
        "n_devices": probe["n_devices"],
        "tiny": bool(args.tiny),
        "versions": {k: probe[k] for k in ("jax", "jaxlib", "libtpu")},
        "config": {k: cfg[k] for k in (
            "source", "n_users", "n_items", "rank", "n_events",
            "num_iterations", "reduced")},
        "seed": args.seed,
        "phase_seconds": phases,
        "kernels": kernels,
        "train": {"instance": instance_id, "status": "COMPLETED",
                  "mesh": mesh_line.split("mesh: ", 1)[1],
                  "device_bytes": [ln.split("] ", 2)[-1] for ln in
                                   train_log.splitlines()
                                   if "device bytes in use" in ln]},
        "queries": {
            "answered": len(seq) + len(burst),
            "first_query_s": round(seq[0][0], 4),
            "later_sequential_median_s": round(later[len(later) // 2], 4),
            "burst_wall_s": phases["queries_burst"],
            "agreement_alone_vs_burst": agree,
        },
        "lane": lane,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_entries(cache_dir)},
        "claim": None,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal: JAX_PLATFORMS=cpu, toy sizes, "
                         "Pallas in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="output directory (logs, chip_smoke.json, scratch storage)")
    ap.add_argument("--child", choices=("probe", "kernels"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child == "probe":
        child_probe()
        return 0
    if args.child == "kernels":
        cfg = TINY if args.tiny else FULL
        child_kernels(cfg["kernel_ranks"], cfg["kernel_systems"],
                      args.seed, interpret=args.tiny)
        return 0

    if not os.path.isdir(os.path.join(HERE, "predictionio_tpu")):
        print(f"chip_smoke: no predictionio_tpu package beside {__file__}; "
              "run it from a checkout", file=sys.stderr)
        return 2
    os.makedirs(args.out, exist_ok=True)
    report = None
    try:
        report = smoke(args)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    finally:
        for proc in list(_live):
            kill(proc)
        # the store and the model are scratch; logs and the JSON stay
        shutil.rmtree(os.path.join(os.path.abspath(args.out), "storage"),
                      ignore_errors=True)
    if report is None:
        return 1
    line = json.dumps(report)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    print(line)
    # the last line is the verdict alone, in exactly this shape
    print(json.dumps({"ok": True, "device": {
        "platform": report["platform"], "kind": report["device_kind"],
        "count": report["n_devices"]}}), flush=True)
    return 0


T0 = time.time()

if __name__ == "__main__":
    sys.exit(main())
