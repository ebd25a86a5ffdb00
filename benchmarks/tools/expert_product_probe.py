#!/usr/bin/env python3
"""The expert layer's grouped products alone, kernel by kernel, at the
three sequence cells' shapes, in one process on the chip (a builder's
tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/expert_product_probe.py

For each cell's pass (rows x d x w x groups, so many rows routed, the
groups' sizes uneven as the cell's loads are) and each of a pass's
product shapes (`gate`: x W_gate; `down`: h W_down; `dy_wt`: dy W_down^T;
`xt_dy`: x^T dy a group), the time of a call of:

  xla            `lax.ragged_dot` as ops/moe.py's "xla" route calls it,
                 float32 operands, with the `ragged_dot_tiling` its
                 compiled text shows;
  xla_bf16       the same on operands cast to bfloat16 (the casts
                 outside the timed call), float32 result;
  pallas_<tile>  ops/moe_pallas.py's kernel at that row tile, rows
                 handed over in bfloat16 (`_f32`: in float32, rounded in
                 VMEM); the matrices float32 and rounded in VMEM;
  megablox       `jax.experimental.pallas.ops.tpu.megablox` `gmm` /
                 `tgmm` on bfloat16 operands at tiles (512, 512, whole
                 width) (`_f32`: float32 operands, which it multiplies
                 in float32 passes);
  dense          x @ w of the same operations on the routed rows, one
                 bfloat16 pass: the yardstick.

Each reading: ms a call (the host's clock around `--calls` calls, the
last one blocked on), the bytes the tiling moves over the routed rows,
and the share of the chip's peak on the routed rows' operations.
`--layer` times `ops/moe.held_experts` forward and forward + backward on
both routes at each cell's shape instead (`--profile`: and lists the
device's operations of the forward + backward calls by time). `--tiny` runs small shapes on
whatever device JAX finds (the kernels interpreted). Prints one JSON
line a reading; the last line repeats them all and goes to
chiprun_out/expert_product_probe.json.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: cell -> rows of a pass, d, w, groups, routed rows, busiest group over
#: the mean, tokens, top-k, routed experts (ledger, PR 35, and PERF.md
#: section 4)
CELLS = {
    "kimivl-a3b-ep8.train": (16384, 2048, 1408, 8, 12288, 3.25, 16384, 6, 64),
    "lfm2-a2b-ep8.train": (32768, 2048, 1536, 8, 16384, 1.29, 32768, 4, 64),
    "qwen3next-a3b-ep16.train": (16384, 2048, 512, 32, 10240, 2.54, 16384,
                                 10, 512),
}
TINY = {"tiny": (256, 128, 256, 4, 200, 2.0, 128, 2, 8)}
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # benchmarks/peaks.json, v5e


def group_sizes(groups, routed, max_over_mean):
    """Uneven sizes that sum to `routed`: the busiest group
    `max_over_mean` times the mean, the others falling off evenly."""
    import numpy as np

    top = max_over_mean * routed / groups
    rest = (routed - top) / max(groups - 1, 1)
    ramp = np.linspace(1.5, 0.5, groups - 1) * rest
    sizes = np.concatenate([[top], ramp]).astype(np.int64)
    sizes[-1] += routed - sizes.sum()
    return sizes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--layer", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="with --layer: trace the forward + backward calls "
                         "and list the device's operations by time")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops import attention_pallas, moe, moe_pallas

    # the package's `gmm` attribute is its differentiable wrapper; the
    # module of that name holds both kernels
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    device = jax.devices()[0]
    interpret = device.platform != "tpu"
    if interpret and not args.tiny:
        raise SystemExit(f"no TPU here ({device.platform}); --tiny rehearses")
    cells = TINY if args.tiny else {
        name: CELLS[name] for name in (args.cells or CELLS)}
    readings = []

    def say(**doc):
        readings.append(doc)
        print(json.dumps(doc), flush=True)

    def timed(fn, *operands):
        """ms a call, or the error's first line."""
        try:
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*operands).compile()
            compile_s = time.perf_counter() - t0
            jax.block_until_ready(compiled(*operands))
            jax.block_until_ready(compiled(*operands))
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = compiled(*operands)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.calls * 1e3
            return ms, compile_s, compiled
        except Exception as e:                  # a tiling Mosaic refuses
            return None, (str(e).strip() or repr(e)).splitlines()[0][:300], None

    def profile(call, calls=4):
        """The device's operations over `calls` calls, by time."""
        from benchmarks.lib import trace_reduce

        trace_dir = os.path.join(ROOT, "chiprun_out", "probe_trace")
        jax.profiler.start_trace(trace_dir)
        for _ in range(calls):
            out = call()
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        try:
            reduced = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)), top=40)
            return dict(calls=calls, busy_s=reduced["busy_s"],
                        device_ops=reduced["device_ops_top"])
        except ValueError as e:             # the CPU has no device plane
            return dict(error=str(e))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    def ragged_tiling(compiled):
        found = re.search(r'ragged_dot_tiling="([0-9,]+)"',
                          compiled.as_text())
        return [int(t) for t in found.group(1).split(",")] if found else None

    bf16 = lambda t: t.astype(jnp.bfloat16)
    for cell, (rows, d, w, groups, routed, ratio, tokens, k,
               n_routed) in cells.items():
        rng = np.random.default_rng(37)
        sizes_np = group_sizes(groups, routed, ratio)
        sizes = jnp.asarray(sizes_np, jnp.int32)
        ends = np.cumsum(sizes_np)
        base = dict(cell=cell, rows=rows, d=d, w=w, groups=groups,
                    routed=routed, device=device.device_kind)

        if args.layer:
            x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
            mats = [jnp.asarray(rng.standard_normal(s) * s[1] ** -0.5,
                                jnp.float32)
                    for s in ((groups, d, w), (groups, d, w), (groups, w, d))]
            # the held experts are the first `groups`; a slot is routed
            # here with the cell's odds, the held groups uneven
            odds = np.concatenate([
                sizes_np / (tokens * k),
                np.full(n_routed - groups,
                        (1 - routed / (tokens * k)) / (n_routed - groups))])
            experts = jnp.asarray(rng.choice(
                n_routed, (tokens, k), p=odds / odds.sum()), jnp.int32)
            gates = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)),
                                jnp.float32)
            routing = moe.Routing(experts, gates, None)

            kinds = attention_pallas.KINDS
            for route in ("xla", "pallas"):
                if args.tiny and route == "pallas":
                    continue
                attention_pallas.KINDS = kinds if route == "pallas" else ()

                # a function of its own a route: `jit` keeps a trace by
                # the function it was made from
                def forward(x, *mats):
                    return moe.held_experts(x, *mats, routing, 0, rows)

                def both(x, *mats):
                    return jax.grad(lambda *a: jnp.sum(
                        forward(*a)[0] ** 2), (0, 1, 2, 3))(x, *mats)

                try:
                    for name, fn in (("forward", forward),
                                     ("forward_backward", both)):
                        ms, note, compiled = timed(fn, x, *mats)
                        text = compiled.as_text() if compiled else ""
                        say(**base, layer=name, route=route, ms=ms,
                            note=note,
                            kernel_calls=text.count("grouped_product_pallas"),
                            held_slots=int(compiled(x, *mats)[1].sum())
                            if name == "forward" and compiled else None)
                    if args.profile and compiled:
                        say(**base, layer="profile", route=route,
                            **profile(lambda: compiled(x, *mats)))
                finally:
                    attention_pallas.KINDS = kinds
            continue

        x = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
        h = jnp.asarray(rng.standard_normal((rows, w)), jnp.float32)
        w_up = jnp.asarray(rng.standard_normal((groups, d, w)), jnp.float32)
        w_down = jnp.asarray(rng.standard_normal((groups, w, d)), jnp.float32)
        tiles = [128, 256, 512] + ([1024] if groups <= 8 else [])
        if args.tiny:
            tiles = [32]
        # product -> (a, b, contraction x columns (of xt_dy: a group's
        # result), xla's call, the kernel, megablox's call)
        products = {
            "gate": (x, w_up, (d, w),
                     lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                     moe_pallas.rows_by_matrix,
                     lambda a, b, t: megablox.gmm(
                         a, b, sizes, jnp.float32, t, interpret=interpret)),
            "down": (h, w_down, (w, d),
                     lambda a, b: jax.lax.ragged_dot(a, b, sizes),
                     moe_pallas.rows_by_matrix,
                     lambda a, b, t: megablox.gmm(
                         a, b, sizes, jnp.float32, t, interpret=interpret)),
            "dy_wt": (x, w_down, (d, w),
                      lambda a, b: jax.lax.ragged_dot_general(
                          a, b, sizes, moe._ROWS_T),
                      moe_pallas.rows_by_matrix_t,
                      lambda a, b, t: megablox.gmm(
                          a, b, sizes, jnp.float32, t, transpose_rhs=True,
                          interpret=interpret)),
            "xt_dy": (x, h, (d, w),
                      lambda a, b: jax.lax.ragged_dot_general(
                          a, b, sizes, moe._GROUPS),
                      moe_pallas.rows_t_by_rows,
                      lambda a, b, t: megablox.tgmm(
                          a.T, b, sizes, jnp.float32, t,
                          interpret=interpret)),
        }
        for product, (a, b, (kk, nn), xla, kernel, blox) in products.items():
            ops = 2.0 * routed * kk * nn
            a_bytes, b_bytes = routed * a.shape[1] * 4.0, groups * kk * nn * 4.0
            if product == "xt_dy":
                b_bytes, out_bytes = routed * b.shape[1] * 4.0, \
                    groups * kk * nn * 4.0
            else:
                out_bytes = routed * nn * 4.0

            def reading(variant, ms, note, nbytes=None, **more):
                say(**base, product=product, variant=variant, ms=ms,
                    note=note, gbytes=nbytes and nbytes / 1e9,
                    ms_at_bytes=nbytes and nbytes / PEAK_BYTES * 1e3,
                    peak_pct=ms and 100 * ops / PEAK_FLOPS / (ms / 1e3),
                    **more)

            ms, note, compiled = timed(xla, a, b)
            tiling = compiled and ragged_tiling(compiled)
            nbytes = None
            if tiling and product != "xt_dy":
                tm, tk, tn = tiling
                visits = int(np.ceil(ends[-1] / tm)) + groups - 1
                nbytes = a_bytes * np.ceil(nn / tn) \
                    + visits * kk * nn * 4.0 + out_bytes
            reading("xla", ms, note, nbytes, ragged_dot_tiling=tiling)
            ms, note, compiled = timed(xla, bf16(a), bf16(b))
            reading("xla_bf16", ms, note,
                    ragged_dot_tiling=compiled and ragged_tiling(compiled))
            once = a_bytes + b_bytes + out_bytes
            half = once - (a_bytes + (b_bytes if product == "xt_dy" else 0)) / 2
            for tile in tiles:
                ms, note, _ = timed(
                    lambda a, b: kernel(
                        a, b, moe_pallas.schedule(sizes, rows, tile), interpret),
                    bf16(a), bf16(b) if product == "xt_dy" else b)
                reading(f"pallas_{tile}", ms, note, half)
            mid = moe_pallas.tiles(rows, d, w, groups)
            ms, note, _ = timed(
                lambda a, b: kernel(
                    a, b, moe_pallas.schedule(sizes, rows, mid), interpret), a, b)
            reading(f"pallas_{mid}_f32", ms, note, once)
            # megablox tiles the contraction too: (rows, contraction,
            # columns) as its gmm names them; tgmm's are (rows, k, n)
            blox_tiles = (32, 128, 128) if args.tiny else (512, 512, nn)
            ms, note, _ = timed(lambda a, b: blox(a, b, blox_tiles),
                                bf16(a), bf16(b))
            reading("megablox", ms, note, tiles_mkn=blox_tiles)
            ms, note, _ = timed(lambda a, b: blox(a, b, blox_tiles), a, b)
            reading("megablox_f32", ms, note, tiles_mkn=blox_tiles)
            dense_a = jnp.asarray(rng.standard_normal((routed, kk)),
                                  jnp.float32)
            dense_b = jnp.asarray(rng.standard_normal((kk, nn)), jnp.float32)
            ms, note, _ = timed(lambda a, b: jnp.dot(
                bf16(a), bf16(b), preferred_element_type=jnp.float32),
                dense_a, dense_b)
            reading("dense", ms, note, (routed * (kk + nn) + kk * nn) * 4.0)

    out = {"probe": "expert_product_probe", "calls": args.calls,
           "readings": readings}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "expert_product_probe.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
