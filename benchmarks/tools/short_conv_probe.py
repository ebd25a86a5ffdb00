#!/usr/bin/env python3
"""What a short-convolution ("conv") layer does between and around its two
products, on the chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/short_conv_probe.py --profile [--parent DIR]
    chiprun -- python3 benchmarks/tools/short_conv_probe.py [--parent DIR]

`--profile`: `attention_layout_probe.py`'s capture of a real step (one
`train_seqrec` of the LFM2 configuration, a second inside a
`jax.profiler` capture) read for the scope `seqrec_short_conv`: the
operations that are neither the passes of `ops/short_conv_pallas.py` nor
a product, by phase (forward, backward, recomputed), with their
milliseconds a step, the bytes their text names and the text's head;
the whole step by scope beside them.

Without `--profile`: ms a call by the host's clock around `--calls`
calls, the last one blocked on: `layer_fwd_bwd`, a whole
`seqrec._short_conv` of the configuration under the block's
`jax.checkpoint` (forward, the block's recomputation, backward, from
given cotangents) at one session of the configuration's length, and
`layer_fwd`, its forward pass alone; where the tree has the passes,
`chain_fwd` and `chain_fwd_bwd`, `gated_short_conv` alone on both routes,
and `chain_fwd_bwd_bf16_grad`, the passes writing the projection's
gradient in bfloat16 (what the layer asks for at the default precision).

Either way one process a tree: this tree's first, then with `--parent
DIR` (a `git archive` of another commit in a git-ignored directory) that
tree's own modules. Off a v5e the passes would be interpreted: the probe
refuses to time anything there; `--tiny` rehearses on the CPU at the
configuration's tiny section and prints no time. One JSON line a
reading; the last line repeats them all and goes to
chiprun_out/short_conv_probe[.profile].json.
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
sys.path.insert(0, HERE)

import attention_layout_probe as capture      # noqa: E402  (a sibling tool)

CONFIG = "seqrec-lfm2-24b-a2b-ep8"
capture.SCOPE = "seqrec_short_conv"
capture.KERNEL = "short_conv_chain"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--parent", default=None,
                    help="another commit's checkout: read after this one")
    ap.add_argument("--tree", default=None, help="(internal) read one tree")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=2_420_000_011)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    args.config = CONFIG
    if args.tree:
        # this tree's modules, or a parent's, before the sibling's ROOT
        sys.path.insert(0, args.tree)
        if not args.profile:
            return layer(args)
        capture.write = lambda name, doc: print(json.dumps(
            {"tree": os.path.relpath(args.tree, ROOT), **doc}), flush=True)
        return capture.profile(args)
    # one process a tree: a chip belongs to one process at a time, and a
    # process imports one tree's modules
    readings = []
    for tree in [ROOT] + ([os.path.abspath(args.parent)]
                          if args.parent else []):
        cmd = [sys.executable, os.path.abspath(__file__), "--tree", tree,
               "--calls", str(args.calls), "--seed", str(args.seed)] \
            + [flag for flag in ("--tiny", "--profile")
               if getattr(args, flag[2:])]
        done = subprocess.run(cmd, capture_output=True, text=True)
        sys.stderr.write(done.stderr[-4000:])
        if done.returncode:
            return done.returncode
        for line in done.stdout.splitlines():
            if line.startswith("{"):
                readings.append(json.loads(line))
                if not args.profile:
                    print(line, flush=True)
    capture.write("short_conv_probe.profile.json" if args.profile
                  else "short_conv_probe.json", {"readings": readings})
    return 0


def layer(args) -> int:
    import inspect

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import attention_pallas, linear_attention

    device = jax.devices()[0].device_kind
    if not args.tiny and device not in attention_pallas.KINDS:
        raise SystemExit(f"no {attention_pallas.KINDS[0]} here ({device}): "
                         "the passes would be interpreted and no time "
                         "mean anything; --tiny rehearses")
    _, p = capture.cell_params(args.config, args.tiny)
    rng = np.random.default_rng(args.seed)
    weights = seqrec.init_params(np.random.default_rng(args.seed), 64, p)[
        "layers"][p.mixer_kinds().index("conv")]
    weights = {k: weights[k] for k in ("conv_in", "conv_taps", "conv_out")}
    x = jnp.asarray(rng.normal(size=(1, p.max_len, p.d_model)), jnp.float32)
    mask = jnp.ones((1, p.max_len), bool)
    # (the parent's mixer takes no `devices`)
    more = (1,) if "devices" in inspect.signature(
        seqrec._short_conv).parameters else ()

    def mixer(weights, x):
        return seqrec._short_conv(weights, x, mask, *more)

    def both(fn):
        def run(ct, *operands):
            out, pull = jax.vjp(jax.checkpoint(fn), *operands)
            return out, pull(ct)
        return run

    readings = [("layer_fwd", mixer, None, weights, x),
                ("layer_fwd_bwd", both(mixer), None, x, weights, x)]
    if hasattr(linear_attention, "gated_short_conv"):
        bcu = jnp.asarray(rng.normal(size=(1, p.max_len, 3 * p.d_model)),
                          jnp.float32)
        y, taps = x, weights["conv_taps"]

        def chain(grad_dtype=None):
            # (a function of its own a reading: `jax.checkpoint` too finds
            # a function's last trace again)
            return lambda bcu, taps: linear_attention.gated_short_conv(
                bcu, taps, grad_dtype=grad_dtype)

        for kind in (device, "cpu"):
            readings += [("chain_fwd", chain(), kind, bcu, taps),
                         ("chain_fwd_bwd", both(chain()), kind, y, bcu, taps)]
        readings.append(("chain_fwd_bwd_bf16_grad",
                         both(chain(jnp.bfloat16)), device, y, bcu, taps))
    kind_here = linear_attention._device_kind
    for reading, fn, kind, *operands in readings:
        # (a route is decided at trace time, the first call's: a function
        # of its own a reading, or `jit` finds the last one's trace)
        fn = jax.jit(lambda *operands, fn=fn: fn(*operands))
        routes = set()
        linear_attention._device_kind = kind_here if kind is None \
            else (lambda kind=kind: kind)
        listen = linear_attention.routes_into(set(), routes) if more \
            else linear_attention.routes_into(routes)
        with listen:
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        doc = {"tree": os.path.relpath(args.tree, ROOT), "reading": reading,
               "device": device, "routes": sorted(routes)}
        if args.tiny:           # no time off the chip means anything
            doc["rehearsal"] = True
        else:
            doc.update(ms=(time.perf_counter() - t0) / args.calls * 1e3,
                       first_call_s=first)
        print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
