#!/usr/bin/env python3
"""What a softmax-attention layer does around its kernels, on the chip (a
builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/attention_layout_probe.py --profile
    chiprun -- python3 benchmarks/tools/attention_layout_probe.py --parent DIR

`--profile`: one `train_seqrec` of `--config` (the Ouro configuration) on
generated sessions, a second inside a `jax.profiler` capture; lists the
device operations a step under the scope `seqrec_attention` that are not
the attention kernels, each with its phase (forward, backward,
recomputed), its milliseconds a step, the bytes of the shapes its text
names and the text's head; products (an operation whose name says
`convolution`, XLA's name for a matrix product on this chip) apart from
the rest, and every operation summed by the stem of its name and its
phase; beside them the whole step's milliseconds by scope and, by stem,
the operations under none of the step's scopes (what
`scope_named_pct.train` leaves out) and under `seqrec_ffn`.

Without `--profile`: one process a tree (`--tree` runs one here), each
timing ms a call by the host's clock around `--calls` calls, the last
one blocked on: `layer_fwd_bwd`, a whole `seqrec._attention` of the
configuration under the block's `jax.checkpoint` (forward, the block's
recomputation, backward, from given cotangents) at one session of the
configuration's length, and `layer_fwd`, its forward pass alone. This
tree's first; with `--parent DIR` (a `git archive` of another commit in a
git-ignored directory) that tree's own modules after it. Off a v5e the
kernels would be interpreted: the probe refuses to time anything there;
`--tiny` rehearses on the CPU at the configuration's tiny section and
prints no time. One JSON line a reading; the last line repeats them all
and goes to chiprun_out/attention_layout_probe[.profile].json.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CONFIG = "seqrec-ouro-2.6b-pp8"
SCOPE = "seqrec_attention"
KERNEL = "flash_attention_pallas"
_SHAPE = re.compile(r"\b(pred|s8|u8|s32|u32|bf16|f16|f32)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--config", default=CONFIG)
    ap.add_argument("--parent", default=None,
                    help="another commit's checkout: its layer after ours")
    ap.add_argument("--tree", default=None, help="(internal) time one tree")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2_410_000_011)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.profile:
        return profile(args)
    if args.tree:
        return layer(args)
    # one process a tree: a chip belongs to one process at a time, and a
    # process imports one tree's modules
    readings = []
    for tree in [ROOT] + ([os.path.abspath(args.parent)]
                          if args.parent else []):
        cmd = [sys.executable, os.path.abspath(__file__), "--tree", tree,
               "--config", args.config, "--calls", str(args.calls),
               "--seed", str(args.seed)] + (["--tiny"] if args.tiny else [])
        done = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(done.stderr[-4000:])
        if done.returncode:
            return done.returncode
        for line in done.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                readings.append(json.loads(line))
    write("attention_layout_probe.json", {"readings": readings})
    return 0


def write(name: str, doc: dict) -> None:
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(doc, f)
    print(json.dumps(doc), flush=True)


def cell_params(name: str, tiny: bool):
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec

    cfg = manifest.load_config(manifest.load_benchmark(), name)
    if tiny:
        cfg = {**cfg, **cfg["tiny"]}
    return cfg, seqrec.SeqRecParams(**cfg["algorithm_params"])


def phase(flags: str) -> str:
    return "recomputed" if "r" in flags else \
        "backward" if "t" in flags else "forward"


def text_bytes(text: str) -> int:
    """The bytes of every shape an instruction's text names: its result
    and, where the text lists them, its operands."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def profile(args) -> int:
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax

    from benchmarks.events import sessions_longhist
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import profiler

    cfg, p = cell_params(args.config, args.tiny)
    _, truth = sessions_longhist.generate(cfg, args.seed)
    sessions = [[str(i) for i in row] for row in truth["sessions"].tolist()]
    t0 = time.perf_counter()
    steps = len(seqrec.train_seqrec(None, sessions, p).record["loss"])
    out = {"device": jax.devices()[0].device_kind, "config": cfg["name"],
           "first_train_s": time.perf_counter() - t0, "steps": steps}
    tables = [t for t in profiler.scope_tables()
              if t["family"] == "seqrec_train_step"]
    if not tables:
        out["error"] = "the step published no scope table"
        write("attention_layout_probe.profile.json", out)
        return 1
    trace_dir = tempfile.mkdtemp(prefix="pio-attention-layout-probe-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            seqrec.train_seqrec(None, sessions, p)
        finally:
            jax.profiler.stop_trace()
        out.update(read_capture(trace_dir, tables[-1], profiler))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    write("attention_layout_probe.profile.json", out)
    return 0


def read_capture(trace_dir: str, table: dict, profiler) -> dict:
    """The scope's operations of the capture: the kernels, the products
    and the rest, ms a step by phase; the rest's operations one by one."""
    import glob

    from jax.profiler import ProfileData

    rows = table["instructions"]
    by_module, programs = profiler.capture_ops(trace_dir)
    ops = by_module.get(table["module"], {})
    events, _ = programs.get(table["module"], (0, 0.0))
    if not ops:
        return {"note": "the capture holds no device operation of "
                        f"{table['module']}"}
    per_step = 1e3 / max(events, 1)
    texts = {}      # an "XLA Ops" event's name is its instruction's text
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == profiler.OPS_LINE:
                for e in line.events:
                    texts.setdefault(profiler.op_key(e.name), e.name[:600])
    inside = {k: s for k, s in ops.items()
              if k in rows and "c" not in rows[k][1] and rows[k][0] == SCOPE}
    sums = {"kernels": {}, "products": {}, "rest": {}}
    stems, rest = {}, []
    for k, s in inside.items():
        kind = "kernels" if KERNEL in k else \
            "products" if "convolution" in k else "rest"
        ph = phase(rows[k][1])
        sums[kind][ph] = sums[kind].get(ph, 0.0) + s * per_step
        cell = stems.setdefault(
            f"{re.sub(r'[.0-9]+$', '', k)} {ph}", [0, 0.0, 0])
        cell[0] += 1
        cell[1] += s * per_step
        cell[2] += text_bytes(texts.get(k, ""))
        if kind == "rest":
            rest.append((k, s))
    rest.sort(key=lambda kv: -kv[1])
    # the whole step by scope ("" = under none of the step's scopes), and
    # the operations under none and under the feed-forward's by stem: what
    # `scope_named_pct.train` and `step_scope_ms.ffn` are made of
    by_scope, others = {}, {"": {}, "seqrec_ffn": {}}
    for k, s in ops.items():
        scope, flags = rows.get(k, ("", ""))
        if "c" in flags:
            continue
        scope = scope or ""
        by_scope[scope] = by_scope.get(scope, 0.0) + s * per_step
        if scope in others:
            cell = others[scope].setdefault(
                f"{re.sub(r'[.0-9]+$', '', k)} {phase(flags)}", [0, 0.0])
            cell[0] += 1
            cell[1] += s * per_step
    return {
        "by_scope_ms": by_scope,
        "stems_outside_scopes": sorted(
            ([k, n, ms] for k, (n, ms) in others[""].items()),
            key=lambda row: -row[2])[:60],
        "stems_ffn": sorted(
            ([k, n, ms] for k, (n, ms) in others["seqrec_ffn"].items()),
            key=lambda row: -row[2])[:40],
        "steps_in_capture": events,
        "scope_ms": sum(inside.values()) * per_step,
        "by_kind_ms": sums, "operations": len(inside),
        "by_stem": sorted(([k, n, ms, nbytes] for k, (n, ms, nbytes)
                           in stems.items()), key=lambda row: -row[2]),
        "rest": [{"op": k, "phase": phase(rows[k][1]), "flags": rows[k][1],
                  "ms": s * per_step, "bytes": text_bytes(texts.get(k, "")),
                  "text": texts.get(k, "")[:320]} for k, s in rest[:80]],
        "rest_beyond_ms": sum(s for _, s in rest[80:]) * per_step,
    }


def layer(args) -> int:
    sys.path.insert(0, args.tree)       # this tree's modules, or a parent's
    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, attention_pallas

    device = jax.devices()[0].device_kind
    if not args.tiny and device not in attention_pallas.KINDS:
        raise SystemExit(f"no {attention_pallas.KINDS[0]} here ({device}): "
                         "the kernels would be interpreted and no time "
                         "mean anything; --tiny rehearses")
    _, p = cell_params(args.config, args.tiny)
    kind = next(m for m in p.mixer_kinds() if m in ("mha", "gqa", "mla"))
    rng = np.random.default_rng(args.seed)
    weights = seqrec.init_params(np.random.default_rng(args.seed), 64, p)[
        "layers"][p.mixer_kinds().index(kind)]
    x = jnp.asarray(rng.normal(size=(1, p.max_len, p.d_model)), jnp.float32)
    mask = jnp.ones((1, p.max_len), bool)

    def mixer(weights, x):
        return seqrec._attention(weights, x, mask, p, kind, None, False)

    def both(ct, weights, x):
        out, pull = jax.vjp(jax.checkpoint(mixer), weights, x)
        return out, pull(ct)

    for reading, fn, *operands in (("layer_fwd", mixer, weights, x),
                                   ("layer_fwd_bwd", both, x, weights, x)):
        fn = jax.jit(fn)
        routes = set()
        with attention.routes_into(routes):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        doc = {"tree": os.path.relpath(args.tree, ROOT), "reading": reading,
               "mixer": kind, "device": device, "routes": sorted(routes)}
        if args.tiny:           # no time off the chip means anything
            doc["rehearsal"] = True
        else:
            doc.update(ms=(time.perf_counter() - t0) / args.calls * 1e3,
                       first_call_s=first)
        print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
