#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_step.py` were set from, and
the step's time and memory, in one process on the chip (a builder's tool;
no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_limits_probe.py --seeds 3

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, gradient norms and
expert loads: the sound reading), the reference at the highest precision
on the same weights and batch, and the controls put in the program's
place and held to the configuration's own limits (`failed` names the
rows by which each comes out not correct): the reference with every
matrix product's operands rounded to int8 and, on the first seed, with
one held expert left out, with a learning rate ten times off, and a
train that returns its state unchanged. `--overlay` lays other memory
settings over the algorithm's parameters (`--reference 0`: the steps'
times alone). `--tiny` runs the configuration's tiny section on whatever
device JAX finds. Prints one JSON line a reading; the last line repeats
them all and goes to chiprun_out/seqrec_limits_probe.json.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_270_000_011)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="trace the last two steps and list the device's "
                         "operations by time")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--overlay", default="{}",
                    help="JSON laid over algorithm_params")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_reference as ref
    from benchmarks.checks import seqrec_step
    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec

    cfg = manifest.load_config(manifest.load_benchmark(),
                               "seqrec-kimi-vl-a3b-ep8")
    if args.tiny:
        cfg = {**cfg, **cfg["tiny"]}
    params_json = {**cfg["algorithm_params"], **json.loads(args.overlay)}
    p = seqrec.SeqRecParams(**params_json)
    spec = ref.Spec.of(params_json, recompute=True)
    device = jax.devices()[0]
    out = {"device": device.device_kind, "readings": []}
    no_limits = collections.defaultdict(lambda: math.inf)

    def say(**doc):
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)

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
        mem = device.memory_stats() or {}
        record = {"dropped": np.asarray(first["dropped"]), "loss": losses,
                  "load": loads}
        bias_err = seqrec_step.router_bias_err(
            {"layers": [{k: np.asarray(v) for k, v in layer.items()
                         if k == "router_bias"}
                        for layer in params["layers"]]}, record, spec)
        del params, opt_state
        program = {"loss": float(first["loss"]),
                   **{key: {k: float(v) for k, v in first[key].items()}
                      for key in ("grad_norm", "update_norm")},
                   "load": np.asarray(first["load"])}
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses, held_tokens=np.asarray(
                first["held_tokens"]).tolist(),
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0):
            return seqrec_step.compare(numbers, reference, record, unmoved,
                                       bias_err, limits)

        def numbers(spec, grads_of=None):
            loss, grads, load = grads_of or ref.loss_and_grads(
                theta0, seqs, targets, spec)
            return {"loss": loss, "grad_norm": ref.group_norms(grads),
                    "update_norm": ref.first_update_norms(
                        theta0, grads, load, spec), "load": load}, (
                loss, grads, load)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        reference, sound_grads = numbers(spec)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = [("int8", {"precision": "int8"}, None)]
        if n == 0:
            lo, hi = spec.held_experts
            controls += [
                ("expert_left_out", {"held_experts": (lo, hi - 1)}, None),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads)]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control, _ = numbers(dataclasses.replace(spec, **over), grads_of)
            held = rows(control, reference, cfg["limits"])
            say(what=name, seed=seed, control_s=time.perf_counter() - t0,
                failed=[r[0] for r in held if not r[3]],
                **{r[0]: r[1] for r in held})
        if n == 0:
            held = rows(program, reference, cfg["limits"],
                        unmoved=len(reference["grad_norm"]))
            say(what="state_unchanged", seed=seed,
                failed=[r[0] for r in held if not r[3]])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "seqrec_limits_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
