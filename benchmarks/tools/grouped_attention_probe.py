#!/usr/bin/env python3
"""A grouped-head attention layer around its kernels, kind by kind, on the
chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/grouped_attention_probe.py \\
        [--config seqrec-laguna-xs2-ep8] [--parent DIR]

For each softmax-attention kind of `--config` ("gqa", "swa": the Laguna
configuration has both) a whole `seqrec._attention` at one session of the
configuration's length: `layer_fwd`, its forward pass, and
`layer_fwd_bwd`, forward, the block's recomputation under
`jax.checkpoint` and backward from given cotangents; ms a call by the
host's clock around `--calls` calls, the last one blocked on, with the
layouts the calls were heard on. One process a tree: this tree's first,
then `--parent DIR`'s (a `git archive` of another commit in a git-ignored
directory). `--gates` times this tree's layers again, a process each, with
the per-head gate's two Pallas passes (`attention_pallas._gated`) replaced
by what ISSUE 45 offered beside them, left to XLA: "indicator", the gate's
H columns spread to H x D by a product with the heads' indicator rows
(`_head_sums`' matrix, float32-exact), "repeat", a broadcast and a
reshape, both with `delta` from the same sums, and "repeat_fwd", the
broadcast forward (where XLA may fuse it into `@ wo`'s operand) with the
Pallas pass backward (one read of the cotangent for both its results).
Whichever reads faster is the one the program keeps (PR 45: the Pallas
passes). Off a v5e the kernels would be interpreted: the probe refuses
to time anything there; `--tiny` rehearses on the CPU at the
configuration's tiny section and prints no time. One JSON line a reading; the last line
repeats them all and goes to chiprun_out/grouped_attention_probe.json.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CONFIG = "seqrec-laguna-xs2-ep8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=CONFIG)
    ap.add_argument("--parent", default=None,
                    help="another commit's checkout: its layers after ours")
    ap.add_argument("--tree", default=None, help="(internal) time one tree")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2_450_000_011)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--gates", action="store_true",
                    help="this tree's layers again with the gate left to XLA")
    ap.add_argument("--gate", default="pallas",
                    choices=("pallas", "indicator", "repeat", "repeat_fwd"),
                    help="(internal) the gate of one reading")
    args = ap.parse_args(argv)
    if args.tree:
        return layers(args)
    # one process a tree: a chip belongs to one process at a time, and a
    # process imports one tree's modules
    readings = []
    trees = [(ROOT, "pallas")] + ([(os.path.abspath(args.parent), "pallas")]
                                  if args.parent else [])
    if args.gates:
        trees += [(ROOT, gate)
                  for gate in ("indicator", "repeat", "repeat_fwd")]
    for tree, gate in trees:
        cmd = [sys.executable, os.path.abspath(__file__), "--tree", tree,
               "--config", args.config, "--calls", str(args.calls),
               "--seed", str(args.seed), "--gate", gate] \
            + (["--tiny"] if args.tiny else [])
        done = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(done.stderr[-4000:])
        if done.returncode:
            return done.returncode
        for line in done.stdout.splitlines():
            if line.startswith("{"):
                print(line, flush=True)
                readings.append(json.loads(line))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "grouped_attention_probe.json"), "w") as f:
        json.dump({"readings": readings}, f)
    print(json.dumps({"readings": readings}), flush=True)
    return 0


def gate_by_xla(form: str):
    """`attention_pallas._gated`'s contract with no kernel of its own:
    x [B, L, H x D] times s [B, L, H] spread over each head's width ->
    `dtype`; with `a`, each head's sum of x a too."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import attention_pallas

    pallas = attention_pallas._gated

    def gated(x, s, dtype, interpret, a=None):
        if form == "repeat_fwd" and a is not None:
            return pallas(x, s, dtype, interpret, a)
        h = s.shape[-1]
        d = x.shape[-1] // h
        ones = jnp.repeat(jnp.eye(h, dtype=jnp.float32), d, axis=1)
        spread = jnp.repeat(s, d, axis=-1) if form != "indicator" \
            else jnp.einsum("blh,hc->blc", s, ones,
                            precision=jax.lax.Precision.HIGHEST)
        y = (x * spread).astype(dtype)
        if a is None:
            return y
        return y, jnp.einsum("blc,hc->blh", x * a, ones,
                             precision=jax.lax.Precision.HIGHEST)

    return gated


def layers(args) -> int:
    sys.path.insert(0, args.tree)       # this tree's modules, or a parent's
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention, attention_pallas

    if args.gate != "pallas":
        attention_pallas._gated = gate_by_xla(args.gate)

    device = jax.devices()[0].device_kind
    if not args.tiny and device not in attention_pallas.KINDS:
        raise SystemExit(f"no {attention_pallas.KINDS[0]} here ({device}): "
                         "the kernels would be interpreted and no time "
                         "mean anything; --tiny rehearses")
    cfg = manifest.load_config(manifest.load_benchmark(), args.config)
    if args.tiny:
        cfg = {**cfg, **cfg["tiny"]}
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    rng = np.random.default_rng(args.seed)
    params = seqrec.init_params(np.random.default_rng(args.seed), 64, p)
    x = jnp.asarray(rng.normal(size=(1, p.max_len, p.d_model)), jnp.float32)
    mask = jnp.ones((1, p.max_len), bool)
    for kind in sorted(set(p.mixer_kinds()) & {"mha", "gqa", "swa", "mla"}):
        weights = params["layers"][p.mixer_kinds().index(kind)]

        def mixer(weights, x):
            return seqrec._attention(weights, x, mask, p, kind, None, False)

        def both(ct, weights, x):
            out, pull = jax.vjp(jax.checkpoint(mixer), weights, x)
            return out, pull(ct)

        for reading, fn, *operands in (("layer_fwd", mixer, weights, x),
                                       ("layer_fwd_bwd", both, x, weights,
                                        x)):
            fn = jax.jit(fn)
            routes, layouts = set(), set()
            with attention.routes_into(routes, layouts):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*operands))
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = fn(*operands)
            jax.block_until_ready(out)
            doc = {"tree": os.path.relpath(args.tree, ROOT), "mixer": kind,
                   "reading": reading, "device": device, "gate": args.gate,
                   "routes": sorted(routes), "layouts": sorted(layouts)}
            if args.tiny:           # no time off the chip means anything
                doc["rehearsal"] = True
            else:
                doc.update(ms=(time.perf_counter() - t0) / args.calls * 1e3,
                           first_call_s=first)
            print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
