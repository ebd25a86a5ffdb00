#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_ssm_step.py` were set from,
and the step's time and memory, in one process on the chip (a builder's
tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_ssm_probe.py --seeds 6

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, the module's loss,
gradient and update norms, the tokens each held expert got: the sound
reading), the
reference at the highest precision on the same weights and batch, and the
controls put in the program's place and held to the configuration's own
limits (`failed` names the rows by which each comes out not correct): on
the first `--int8-seeds` seeds the reference with every matrix product's
operands rounded to int8 and, on the first seed, each new mechanism left
out or broken in turn (the decay a set to 1; D x left out; the gated
norm's gate left out; W_dn / W_up replaced by a slice; the module's loss
weight 0; the module scored against item t + 1; one held state-space
head dropped; the squared ReLU as a plain ReLU), a learning rate ten times off, one held expert left where it is by
the first update (the first expert layer's, the one with the median of
its held experts' tokens), and a train that returns its state unchanged
(`--faults` names the ones to run). `--more-seeds` reads further seeds,
sound only. `--overlay` lays other parameters over the algorithm's
(`--reference 0`: the steps' times alone), `--profile` lists the device's
operations by time. `--tiny` runs the configuration's tiny section on
whatever device JAX finds. Prints one JSON line a reading; the last line
repeats them all and goes to chiprun_out/seqrec_ssm_probe<--tag>.json.
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

CONFIG = "seqrec-nemotron3-super-120b-a12b-tp8ep64"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_380_000_017)
    ap.add_argument("--more-seeds", default="",
                    help="seeds to read after the --seeds from --first-seed, "
                         "comma-separated (a run's whose reading is asked "
                         "about)")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="trace the last two steps and list the device's "
                         "operations by time")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--int8-seeds", type=int, default=3)
    ap.add_argument("--faults", default="all",
                    help="the fault controls on the first seed: all, none "
                         "or their names, comma-separated")
    ap.add_argument("--overlay", default="{}",
                    help="JSON laid over algorithm_params")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_ssm_reference as ref
    from benchmarks.checks import seqrec_ssm_step as ssm_step
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

    optimizer = seqrec.make_optimizer(p)
    step = seqrec.make_train_step(None, p, optimizer)
    order = seqrec_step.epoch0_rows(params_json, cfg["n_users"])
    theta0 = None

    seeds = [args.first_seed + 1_000_003 * n for n in range(args.seeds)] \
        + [int(seed) for seed in args.more_seeds.split(",") if seed]
    for n, seed in enumerate(seeds):
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
                   "mtp_loss": float(first["mtp_loss"]),
                   "expert_update_norm": np.asarray(
                       first["expert_update_norm"]),
                   "load": np.asarray(first["load"])}
        record = {"dropped": np.asarray(first["dropped"]),
                  "loss": losses}
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses, mtp_loss=program["mtp_loss"],
            held_tokens=np.asarray(first["held_tokens"]).tolist(),
            dropped=int(record["dropped"].sum()),
            mixer_layers={k: int(v) for k, v in
                          first["mixer_layers"].items()},
            layer_passes={k: int(v) for k, v in
                          first["layer_passes"].items()},
            attention_pallas=bool(first["attention_pallas"]),
            expert_product_pallas=bool(first["expert_product_pallas"]),
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

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0):
            return ssm_step.compare(numbers, reference, record, unmoved,
                                    limits)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, seqs, targets, spec)
        reference = ssm_step.reference_numbers(theta0, seqs, targets, spec,
                                               sound_grads)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            reference_mtp_loss=reference["mtp_loss"],
            reference_held_tokens=reference["held_load"].tolist(),
            experts_update_norm_by_layer={
                group: [program["update_norm"][group], norm]
                for group, norm in reference["update_norm"].items()
                if group.endswith(".experts")},
            expert_update_norm=[program["expert_update_norm"].tolist(),
                                reference["expert_update_norm"].tolist()],
            failed=[r[0] for r in rows(program, reference, limits)
                    if not r[3]],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = []
        if n < args.int8_seeds:
            controls.append(("int8", {"precision": "int8"}, None))
        if n == 0:
            faults = [
                ("decay_one", {"decay_one": True}, None),
                ("skip_left_out", {"skip_left_out": True}, None),
                ("norm_gate_left_out", {"norm_gate_left_out": True}, None),
                ("latent_as_slice", {"latent_as_slice": True}, None),
                ("mtp_loss_weight_0", {"mtp_loss_weight": 0.0}, None),
                ("mtp_wrong_item", {"mtp_wrong_item": True}, None),
                ("state_space_head_dropped", {"dropped_head": 0}, None),
                ("relu_plain", {"relu_plain": True}, None),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads),
                ("expert_not_updated", {"expert_not_updated": (0, int(
                    np.argsort(reference["held_load"][0], kind="stable")[
                        (reference["held_load"].shape[1] - 1) // 2]))},
                 sound_grads)]
            controls += [f for f in faults if args.faults == "all"
                         or f[0] in args.faults.split(",")]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = ssm_step.reference_numbers(
                theta0, seqs, targets, dataclasses.replace(spec, **over),
                grads_of)
            held = rows(control, reference, limits)
            say(what=name, seed=seed, control_s=time.perf_counter() - t0,
                failed=[r[0] for r in held if not r[3]],
                **{r[0]: r[1] for r in held})
        if n == 0 and args.faults != "none":
            held = rows(program, reference, limits,
                        unmoved=len(reference["grad_norm"]))
            say(what="state_unchanged", seed=seed,
                failed=[r[0] for r in held if not r[3]])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"seqrec_ssm_probe{args.tag}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
