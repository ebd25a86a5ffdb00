#!/usr/bin/env python3
"""What a linear-attention ("gdn") layer does around the gated delta
rule's kernels, at the Qwen cell's shapes (1 x 16,384 positions, d 2048,
16 key heads serving 32 value heads of 128, a convolution of 4), on the
chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/gdn_chain_probe.py
    chiprun -- python3 benchmarks/tools/gdn_chain_probe.py --profile

Without `--profile`: one process a variant (`--variant` runs one here),
each timing, ms a call by the host's clock around `--calls` calls with
the last one blocked on,

  front      projection's output [B, L, 12288] -> q, k (unit length, q
             scaled), v behind the convolution and SiLU, forward, and
             forward + backward;
  back       the head norm of o times SiLU(z), forward, and forward +
             backward;
  layer      `models/seqrec._linear_attention` whole (projections, gates,
             chain, rule, output product), forward + backward under the
             block's `jax.checkpoint` as a step runs it;

for the variants

  xla        the plain chain as XLA fuses it, q and k repeated to the
             value heads (the tree before PR 39); its `layer` is that
             tree's own, from a checkout of it given as `--parent DIR`
             (`git archive <commit> | tar -x -C DIR`, a directory
             `.gitignore` lists), and is left out without one;
  norepeat   the same chain with q and k left at the key heads (front
             and back; no tree runs a whole layer so);
  fused      the passes of `ops/linear_attention_pallas.py` themselves
             (`_front`, `_front_backward`, `_back`, `_back_backward`), and
             this tree's `layer`: what the kernels' route runs.

Beside each time the bytes of the part's contract (every array once each
way at the width the model gives it) and the share of the memory's peak
they make of the time.

`--profile`: one `train_seqrec` of the Qwen configuration on generated
sessions, a second inside a `jax.profiler` capture; lists the thirty
longest device operations a step under the scope
`seqrec_linear_attention` that are not the rule's kernels, each with its
phase (forward, backward, recomputed), its milliseconds a step and the
head of its instruction's text, and the scope's sums by phase.

Without `--tiny` the variants refuse to run off a v5e (the kernels'
`KINDS`): elsewhere the kernels would be interpreted. `--tiny` rehearses
small shapes on whatever device JAX finds, the kernels interpreted, and
prints no time. One JSON line a reading; the last line repeats them all
and goes to chiprun_out/gdn_chain_probe[.profile].json.
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

VARIANTS = ("xla", "norepeat", "fused")
CONFIG = "seqrec-qwen3-next-80b-a3b-ep16"
SCOPE = "seqrec_linear_attention"
RULE = "gated_delta_rule_pallas"
#: bytes a second of the chip's memory (benchmarks/peaks.json, "TPU v5
#: lite")
PEAK_BYTES_S = 819e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", choices=VARIANTS)
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2_390_000_011)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--parent", help="a checkout of the tree before PR 39, "
                    "for the `xla` variant's whole layer")
    args = ap.parse_args(argv)
    if args.profile:
        return profile(args)
    if args.variant:
        return variant(args)
    # one process a variant: a chip belongs to one process at a time, and
    # this one stays off JAX
    readings, rc = [], 0
    for name in VARIANTS:
        cmd = [sys.executable, os.path.abspath(__file__), "--variant", name,
               "--calls", str(args.calls), "--seed", str(args.seed)]
        done = subprocess.run(
            cmd + (["--tiny"] if args.tiny else [])
            + (["--parent", args.parent] if args.parent else []),
            stdout=subprocess.PIPE, text=True)
        rc = rc or done.returncode
        for line in done.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                readings.append(json.loads(line))
    write("gdn_chain_probe.json", {"readings": readings})
    return rc


def write(name: str, doc: dict) -> None:
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(doc, f)
    print(json.dumps(doc))


def cell_params(tiny: bool):
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec

    cfg = manifest.load_config(manifest.load_benchmark(), CONFIG)
    if tiny:
        cfg = {**cfg, **cfg["tiny"]}
    return cfg, seqrec.SeqRecParams(**cfg["algorithm_params"])


def phase(flags: str) -> str:
    return "recomputed" if "r" in flags else \
        "backward" if "t" in flags else "forward"


def profile(args) -> int:
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax

    from benchmarks.events import sessions_longhist
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import profiler

    cfg, p = cell_params(args.tiny)
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
        write("gdn_chain_probe.profile.json", out)
        return 1
    table = tables[-1]
    trace_dir = tempfile.mkdtemp(prefix="pio-gdn-chain-probe-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            seqrec.train_seqrec(None, sessions, p)
        finally:
            jax.profiler.stop_trace()
        out.update(read_capture(trace_dir, table, profiler))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    write("gdn_chain_probe.profile.json", out)
    return 0


def read_capture(trace_dir: str, table: dict, profiler) -> dict:
    """The scope's operations of the capture that are not the rule's
    kernels: the thirty longest, and the sums by phase, ms a step."""
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
    # an "XLA Ops" event's name is its instruction's whole text
    texts = {}
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == profiler.OPS_LINE:
                for e in line.events:
                    texts.setdefault(profiler.op_key(e.name), e.name[:400])
    inside = {k: s for k, s in ops.items()
              if k in rows and "c" not in rows[k][1] and rows[k][0] == SCOPE}
    by_phase, kernels = {}, {}
    for k, s in inside.items():
        cell = kernels if RULE in k else by_phase
        cell[phase(rows[k][1])] = cell.get(phase(rows[k][1]), 0.0) \
            + s * per_step
    chain = sorted(((k, s) for k, s in inside.items() if RULE not in k),
                   key=lambda kv: -kv[1])
    # every operation of the chain by the stem of its name and its phase
    stems = {}
    for k, s in chain:
        cell = stems.setdefault(
            f"{re.sub(r'[.0-9]+$', '', k)} {phase(rows[k][1])}", [0, 0.0])
        cell[0] += 1
        cell[1] += s * per_step
    return {
        "steps_in_capture": events,
        "scope_ms": sum(inside.values()) * per_step,
        "rule_kernels_ms": kernels, "around_the_kernels_ms": by_phase,
        "operations": len(chain),
        "longest": [{"op": k, "phase": phase(rows[k][1]),
                     "flags": rows[k][1], "ms": s * per_step,
                     "text": texts.get(k, "")} for k, s in chain[:30]],
        "the_rest_ms": sum(s for _, s in chain[30:]) * per_step,
        "by_stem": sorted(([k, n, ms] for k, (n, ms) in stems.items()),
                          key=lambda row: -row[2]),
        "next_longest": [[k, phase(rows[k][1]), s * per_step,
                          texts.get(k, "")[:200]] for k, s in chain[30:120]],
    }


def variant(args) -> int:
    if args.variant == "xla" and args.parent:
        # the whole layer as the tree before PR 39 runs it: that tree's
        # own modules, not a copy of them here
        sys.path.insert(0, os.path.abspath(args.parent))
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention_pallas
    from predictionio_tpu.ops import linear_attention as la
    from predictionio_tpu.ops import linear_attention_pallas as lap

    device = jax.devices()[0].device_kind
    if not args.tiny and device not in attention_pallas.KINDS:
        raise SystemExit(f"no {attention_pallas.KINDS[0]} here ({device}): "
                         "the kernels would be interpreted and no time "
                         "mean anything; --tiny rehearses")
    _, p = cell_params(False)
    p = dataclasses.replace(p, n_layers=1, mixer="gdn")
    length = p.max_len
    if args.tiny:
        p = dataclasses.replace(p, d_model=64, linear_key_heads=2,
                                linear_value_heads=4)
        length = 256
    heads = (p.linear_key_heads, p.linear_value_heads,
             p.linear_key_head_dim, p.linear_value_head_dim)
    hk, hv, dk, dv = heads
    cuts = [hk * dk, 2 * hk * dk, 2 * hk * dk + hv * dv]
    rng = np.random.default_rng(args.seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    layer = seqrec.init_params(np.random.default_rng(args.seed), 64, p)[
        "layers"][0]
    qkvz = draw(1, length, cuts[2] + hv * dv)
    x = draw(1, length, p.d_model)
    o = draw(1, length, hv * dv)
    scale = layer["o_norm"]["scale"]
    mask = jnp.ones((1, length), bool)
    name = args.variant

    def unit(t):
        t = t.reshape(1, length, -1, dk)
        return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    def front(qkvz, taps):              # the plain chain, XLA's to fuse
        q, k, v, _ = jnp.split(qkvz, cuts, axis=-1)
        q, k, v = (la.causal_conv(t, w) for t, w in zip(
            (q, k, v), jnp.split(taps, cuts[:2], axis=-1)))
        q, k = unit(q) * dk ** -0.5, unit(k)
        if name == "xla":
            q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
        return (q.reshape(1, length, -1), k.reshape(1, length, -1), v)

    def back(o, qkvz, scale):
        z = qkvz[..., cuts[2]:].reshape(1, length, hv, dv)
        return (seqrec._rms_norm(o.reshape(1, length, hv, dv), scale,
                                 p.norm_eps) * jax.nn.silu(z)).reshape(
                                     1, length, -1)

    def pulled(fn):
        """Forward, then backward from given cotangents: no loss's pass
        over the outputs is timed."""
        def both(ct, *operands):
            out, pull = jax.vjp(fn, *operands)
            return out, pull(ct)
        return both

    front_both, back_both = pulled(front), pulled(back)
    if name == "fused":
        # the passes themselves, as `gated_delta_chain_pallas` calls them
        front = lambda qkvz, taps: lap._front(qkvz, taps, heads, args.tiny)
        back = lambda o, qkvz, scale: lap._back(o, qkvz, scale, heads,
                                                p.norm_eps, args.tiny)
        front_both = lambda ct, qkvz, taps: (
            front(qkvz, taps),
            lap._front_backward(qkvz, taps, ct, None, heads, args.tiny))
        back_both = lambda ct, o, qkvz, scale: (
            back(o, qkvz, scale),
            lap._back_backward(o, qkvz, scale, ct, None, heads, p.norm_eps,
                               args.tiny))
        if args.tiny:           # the layer as a v5e would route it
            la._device_kind = lambda: attention_pallas.KINDS[0]
            chain = lap.gated_delta_chain_pallas
            lap.gated_delta_chain_pallas = lambda *a: chain(*a, True)

    def mixer(layer, x):
        return seqrec._linear_attention(layer, x, mask, p, 1)

    def timed(fn, *operands):
        fn = jax.jit(fn)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, first

    chain_bytes = length * cuts[2] * 4.0        # q, k, v at their widths
    gate_bytes = length * hv * dv * 4.0
    readings = [
        ("front_fwd", 2 * chain_bytes, front, qkvz, layer["conv"]),
        ("front_fwd_bwd", 5 * chain_bytes, front_both,
         tuple(jnp.ones(t.shape, t.dtype) for t in jax.eval_shape(
             front, qkvz, layer["conv"])), qkvz, layer["conv"]),
        ("back_fwd", 3 * gate_bytes, back, o, qkvz, scale),
        ("back_fwd_bwd", 8 * gate_bytes, back_both, o, o, qkvz, scale)]
    # the whole layer under the block's `jax.checkpoint`, as a step runs
    # it: this tree's, or for `xla` the tree's before PR 39 (`--parent`)
    if name == "fused" or (name == "xla" and args.parent):
        readings.append(("layer_fwd_bwd", None,
                         pulled(jax.checkpoint(mixer)), x, layer, x))
    for reading, nbytes, fn, *operands in readings:
        ms, first = timed(fn, *operands)
        doc = {"variant": name, "reading": reading, "device": device}
        if args.tiny:           # interpreted kernels: no time means anything
            doc["rehearsal"] = True
        else:
            doc.update(ms=ms, first_call_s=first)
            if nbytes:
                doc.update(contract_bytes=nbytes, memory_peak_pct=100.0
                           * nbytes / PEAK_BYTES_S / (ms * 1e-3))
        print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
