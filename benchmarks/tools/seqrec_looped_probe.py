#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_looped_step.py` were set
from, the step's time, memory and set-up under either form of the loop of
passes, in one process on the chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_looped_probe.py --seeds 3

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, each pass's loss and
exit share, gradient and update norms: the sound reading), the reference
at the highest precision on the same weights and batch, and the controls
put in the program's place and held to the configuration's own limits
(`failed` names the rows by which each comes out not correct): on the
first `--int8-seeds` seeds the reference with every matrix product's
operands rounded to int8 and, on the first seed, with one pass left out
(three for four), with the gradient taken through the last pass only,
with the post norms left out, with the entropy term left out, with a
learning rate ten times off, and a train that returns its state
unchanged. `--loop-unroll` writes the stack out `n_loops` times instead of
scanning it (`models/seqrec.LOOP_UNROLL`); `--reload` clears JAX's caches
after the steps and times one more first step, which then loads the
executable from the persistent cache: what a process's first train pays
with the cache warm. `--overlay` lays other parameters over the
algorithm's (`--reference 0`: the steps' times alone), `--profile` lists
the device's operations by time. `--tiny` runs the configuration's tiny
section on whatever device JAX finds. Prints one JSON line a reading; the
last line repeats them all and goes to
chiprun_out/seqrec_looped_probe<--tag>.json.
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

CONFIG = "seqrec-ouro-2.6b-pp8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_380_000_017)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--loop-unroll", action="store_true",
                    help="the stack written out a pass after another")
    ap.add_argument("--reload", action="store_true",
                    help="one more first step from the persistent cache")
    ap.add_argument("--profile", action="store_true",
                    help="trace the last two steps and list the device's "
                         "operations by time")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--int8-seeds", type=int, default=3)
    ap.add_argument("--faults", type=int, default=1,
                    help="0: no fault control on the first seed")
    ap.add_argument("--overlay", default="{}",
                    help="JSON laid over algorithm_params")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_looped_reference as ref
    from benchmarks.checks import seqrec_looped_step as looped_step
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
    if args.loop_unroll:
        seqrec.LOOP_UNROLL = True
    device = jax.devices()[0]
    out = {"device": device.device_kind, "loop_unroll": seqrec.LOOP_UNROLL,
           "readings": []}
    no_limits = collections.defaultdict(lambda: math.inf)
    limits = cfg.get("limits") or no_limits

    def say(**doc):
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)

    optimizer = seqrec.make_optimizer(p)
    step = seqrec.make_train_step(None, p, optimizer)
    order = seqrec_step.epoch0_rows(params_json, cfg["n_users"])
    theta0 = None

    def first_step_of(batch):
        """Fresh weights through one step -> (its wall, its numbers)."""
        params = seqrec.init_params(None, cfg["n_items"], p)
        opt_state = optimizer.init(params)
        jax.block_until_ready((params, opt_state))
        t0 = time.perf_counter()
        params, opt_state, stats = step(params, opt_state,
                                        *map(jnp.asarray, batch))
        jax.block_until_ready(params)
        return time.perf_counter() - t0, jax.device_get(stats)

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
        walls, losses, first = [], [], None
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
        del params, opt_state
        program = {"loss": float(first["loss"]),
                   **{key: {k: float(v) for k, v in first[key].items()}
                      for key in ("grad_norm", "update_norm")},
                   **{key: np.asarray(first[key])
                      for key in ("loop_loss", "exit_share")}}
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses, loop_loss=program["loop_loss"].tolist(),
            exit_share=program["exit_share"].tolist(),
            mixer_layers={k: int(v) for k, v in
                          first["mixer_layers"].items()},
            layer_passes={k: int(v) for k, v in
                          first["layer_passes"].items()},
            attention_pallas=bool(first["attention_pallas"]),
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))
        if n == 0:
            try:
                analysis = step.lower(
                    *jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype), (
                        theta0, jax.eval_shape(optimizer.init, theta0))),
                    *map(jnp.asarray, batches[0])).compile(
                    ).memory_analysis()
                say(what="the step's program", seed=seed,
                    argument_bytes=analysis.argument_size_in_bytes,
                    temp_bytes=analysis.temp_size_in_bytes,
                    code_bytes=analysis.generated_code_size_in_bytes)
            except Exception as e:          # a reading, not a requirement
                say(what="the step's program", seed=seed, error=repr(e))
        if args.reload and n == 0:
            jax.clear_caches()
            wall, _ = first_step_of(batches[0])
            say(what="first step, executable from the persistent cache",
                seed=seed, wall_s=wall)

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0):
            return looped_step.compare(numbers, reference, unmoved, limits)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, seqs, targets, spec)
        reference = looped_step.reference_numbers(theta0, seqs, targets,
                                                  spec, sound_grads)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            reference_loop_loss=np.asarray(reference["loop_loss"]).tolist(),
            failed=[r[0] for r in rows(program, reference, limits)
                    if not r[3]],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = []
        if n < args.int8_seeds:
            controls.append(("int8", {"precision": "int8"}, None))
        if n == 0 and args.faults:
            controls += [
                ("pass_left_out", {"n_loops": spec.n_loops - 1}, None),
                ("gradient_through_the_last_pass_only",
                 {"last_pass_only": True}, None),
                ("post_norms_left_out", {"post_norm": False}, None),
                ("entropy_left_out", {"exit_entropy_beta": 0.0}, None),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads)]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = looped_step.reference_numbers(
                theta0, seqs, targets, dataclasses.replace(spec, **over),
                grads_of)
            held = rows(control, reference, limits)
            say(what=name, seed=seed, control_s=time.perf_counter() - t0,
                failed=[r[0] for r in held if not r[3]],
                **{r[0]: r[1] for r in held})
        if n == 0 and args.faults:
            held = rows(program, reference, limits,
                        unmoved=len(reference["grad_norm"]))
            say(what="state_unchanged", seed=seed,
                failed=[r[0] for r in held if not r[3]])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"seqrec_looped_probe{args.tag}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
