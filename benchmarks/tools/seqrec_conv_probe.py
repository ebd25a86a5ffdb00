#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_conv_step.py` were set from,
the step's time and memory, and the attention kernels alone at the
cell's narrow heads, in one process on the chip (a builder's tool; no
cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_conv_probe.py --seeds 3

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, gradient norms and
expert loads: the sound reading), the reference at the highest precision
on the same weights and batch, and the controls put in the program's
place and held to the configuration's own limits (`failed` names the
rows by which each comes out not correct): the reference with every
matrix product's operands rounded to int8 and, on the first seed, with
one held expert left out, with the convolution's first gate left out,
with the norms of queries and keys left out, with a learning rate ten
times off, the selection bias moved the wrong way, and a train that
returns its state unchanged. `--micro` times the attention alone at 1 x
32 (8 key/value) heads x 32,768 x 64, forward and forward + backward:
the kernels with v as it is (a block's trailing dimension the array's 64),
with v filled up to 128 in the caller, at other blocks, and the scan of
XLA operations. `--overlay` lays other parameters over the algorithm's
(`--reference 0`: the steps' times alone), `--profile` lists the device's
operations by time. `--tiny` runs the configuration's tiny section on
whatever device JAX finds. Prints one JSON line a reading; the last line
repeats them all and goes to chiprun_out/seqrec_conv_probe.json.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CONFIG = "seqrec-lfm2-24b-a2b-ep8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_330_000_017)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--micro", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="trace the last two steps and list the device's "
                         "operations by time")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--faults", type=int, default=1,
                    help="0: the int8 control only, no fault on the "
                         "first seed")
    ap.add_argument("--overlay", default="{}",
                    help="JSON laid over algorithm_params")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_conv_reference as ref
    from benchmarks.checks import seqrec_conv_step as conv_step
    from benchmarks.checks import seqrec_step
    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec

    cfg = manifest.load_config(manifest.load_benchmark(), CONFIG)
    if args.tiny:
        cfg = {**cfg, **cfg["tiny"]}
    params_json = {**cfg["algorithm_params"], **json.loads(args.overlay)}
    p = seqrec.SeqRecParams(**params_json)
    spec = ref.Spec.of(params_json, recompute=True)
    device = jax.devices()[0]
    out = {"device": device.device_kind, "readings": []}
    no_limits = collections.defaultdict(lambda: math.inf)
    limits = cfg.get("limits") or no_limits

    def say(**doc):
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)

    if args.micro:
        micro(say, jax, jnp, np, p)

    optimizer = seqrec.make_optimizer(p)
    step = seqrec.make_train_step(None, p, optimizer)
    order = seqrec_step.epoch0_rows(params_json, cfg["n_users"])
    theta0 = None
    for n in range(args.seeds):
        seed = args.first_seed + 1_000_003 * n
        _, truth = sessions_longhist.generate(cfg, seed)
        batches = [seqrec_step.coded_batch(
            truth["sessions"], order[lo:lo + p.batch_size], p.max_len)
            for lo in range(0, min(args.steps * p.batch_size,
                                  len(order) - p.batch_size + 1),
                          p.batch_size)]
        t0 = time.perf_counter()
        params = seqrec.init_params(None, cfg["n_items"], p)
        jax.block_until_ready(params)
        init_s = time.perf_counter() - t0
        if theta0 is None:
            theta0 = jax.tree.map(np.asarray, params)
        opt_state = optimizer.init(params)
        walls, losses, loads, first = [], [], [], None
        for i, (seqs, targets) in enumerate(batches):
            if args.profile and i == len(batches) - 2:
                trace_dir = os.path.join(ROOT, "chiprun_out", "probe_trace")
                jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            params, opt_state, stats = step(params, opt_state,
                                            jnp.asarray(seqs),
                                            jnp.asarray(targets))
            jax.block_until_ready(params)
            walls.append(time.perf_counter() - t0)
            stats = jax.device_get(stats)
            losses.append(float(stats["loss"]))
            loads.append(np.asarray(stats["load"]))
            first = first or stats
        if args.profile:
            from benchmarks.lib import trace_reduce

            jax.profiler.stop_trace()
            try:
                reduced = trace_reduce.reduce(trace_reduce.load(
                    trace_reduce.find_xplane(trace_dir)), top=60)
                say(what="profile of two steps", seed=seed,
                    busy_s=reduced["busy_s"], window_s=reduced["window_s"],
                    device_ops=reduced["device_ops_top"])
            except ValueError as e:        # the CPU has no device plane
                say(what="profile of two steps", seed=seed, error=str(e))
            shutil.rmtree(trace_dir, ignore_errors=True)   # 100 MB and more
        mem = device.memory_stats() or {}
        record = {"dropped": np.asarray(first["dropped"]), "loss": losses,
                  "load": loads}
        biases = {"layers": [{k: np.asarray(v) for k, v in layer.items()
                              if k == "router_bias"}
                             for layer in params["layers"]]}
        bias_err = seqrec_step.router_bias_err(biases, record, spec)
        del params, opt_state
        program = {"loss": float(first["loss"]),
                   **{key: {k: float(v) for k, v in first[key].items()}
                      for key in ("grad_norm", "update_norm")},
                   "load": np.asarray(first["load"])}
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses, held_tokens=np.asarray(
                first["held_tokens"]).sum(-1).tolist(),
            mixer_layers={k: int(v) for k, v in
                          first["mixer_layers"].items()},
            attention_pallas=bool(first["attention_pallas"]),
            router_bias_err=bias_err,
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0,
                 bias_err=bias_err):
            return seqrec_step.compare(numbers, reference, record, unmoved,
                                       bias_err, limits)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, seqs, targets, spec)
        reference = conv_step.reference_numbers(theta0, seqs, targets, spec,
                                                sound_grads)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            failed=[r[0] for r in rows(program, reference, limits)
                    if not r[3]],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = [("int8", {"precision": "int8"}, None)]
        if n == 0 and args.faults:
            lo, hi = spec.held_experts
            controls += [
                ("expert_left_out", {"held_experts": (lo, hi - 1)}, None),
                ("conv_gate_left_out", {"conv_gate": False}, None),
                ("qk_norm_left_out", {"qk_norm": False}, None),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads)]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = conv_step.reference_numbers(
                theta0, seqs, targets, dataclasses.replace(spec, **over),
                grads_of)
            held = rows(control, reference, limits)
            say(what=name, seed=seed, control_s=time.perf_counter() - t0,
                failed=[r[0] for r in held if not r[3]],
                **{r[0]: r[1] for r in held})
        if n == 0 and args.faults:
            wrong = seqrec_step.router_bias_err(
                {"layers": [{k: -v for k, v in layer.items()}
                            for layer in biases["layers"]]}, record, spec)
            held = rows(program, reference, limits, bias_err=wrong)
            say(what="bias_moved_the_wrong_way", seed=seed,
                seqrec_router_bias_err=wrong,
                failed=[r[0] for r in held if not r[3]])
            held = rows(program, reference, limits,
                        unmoved=len(reference["grad_norm"]))
            say(what="state_unchanged", seed=seed,
                failed=[r[0] for r in held if not r[3]])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "seqrec_conv_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


def timed(fn, *operands, calls=5):
    """Milliseconds a call, after one that compiles."""
    import jax

    jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    for _ in range(calls):
        result = fn(*operands)
    jax.block_until_ready(result)
    return (time.perf_counter() - t0) / calls * 1000.0


def micro(say, jax, jnp, np, p) -> None:
    """Attention alone at the cell's shapes: milliseconds a call, forward
    and forward + backward, by layout of v, by block and on the scan."""
    from predictionio_tpu.ops import attention, attention_pallas
    from predictionio_tpu.ops.attention import blockwise_attention

    rng = np.random.default_rng(0)
    l, width = p.max_len, p.head_dim
    q, k, v = (jnp.asarray(rng.normal(size=(1, l, h, width)), jnp.float32)
               for h in (p.n_heads, p.n_kv_heads, p.n_kv_heads))
    shape = [1, l, p.n_heads, p.n_kv_heads, width]

    def read(what, attend, **doc):
        # a function of its own each time: jit keeps a program by the
        # function it was made from, whatever `BLOCK` and `KINDS` say now
        say(what=what, shape=shape, **doc,
            forward_ms=timed(jax.jit(lambda *a: attend(*a)), q, k, v),
            forward_backward_ms=timed(jax.jit(jax.grad(
                lambda *a: (attend(*a) ** 2).sum(), argnums=(0, 1, 2))),
                q, k, v))

    def as_it_is(q, k, v):
        return blockwise_attention(q, k, v, causal=True)

    def filled_up(q, k, v):      # v of 128: zeros beside the 64
        pad = ((0, 0), (0, 0), (0, 0), (0, 128 - width))
        return blockwise_attention(q, k, jnp.pad(v, pad),
                                   causal=True)[..., :width]

    route = attention.attention_route(attention._device_kind(), l, l, width,
                                      width)
    read("attention alone, v as it is", as_it_is, route=route,
         block=attention_pallas.BLOCK)
    if route == "pallas":
        read("attention alone, v filled up to 128", filled_up,
             block=attention_pallas.BLOCK)
        block = attention_pallas.BLOCK
        for other in (512, 2048):
            attention_pallas.BLOCK = other
            try:
                read("attention alone, v as it is", as_it_is, block=other)
            finally:
                attention_pallas.BLOCK = block
        kinds, attention_pallas.KINDS = attention_pallas.KINDS, ()
        try:
            read("attention alone, the scan", as_it_is,
                 block=512)
        finally:
            attention_pallas.KINDS = kinds


if __name__ == "__main__":
    sys.exit(main())
