#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_hybrid_step.py` were set
from, the step's time and memory, and the two mixers' kernels alone, in
one process on the chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_hybrid_probe.py --seeds 3

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, gradient norms and
expert loads: the sound reading), the reference at the highest precision
on the same weights and batch, and the controls put in the program's
place and held to the configuration's own limits (`failed` names the
rows by which each comes out not correct): the reference with every
matrix product's operands rounded to int8 and, on the first seed, with
one held expert left out, with one linear layer's decay left out, with
the attention's output gate left out, with a learning rate ten times
off, and a train that returns its state unchanged. `--micro` times the
chunked delta rule alone at 1 x 32 heads x 16,384 x 128 and the
attention kernels alone at 1 x 16 (2 key/value) heads x 16,384 x 256,
forward and forward + backward. `--key-heads` sets the heads a
linear-attention layer takes at a time (the constant
`seqrec.LINEAR_KEY_HEADS`), `--chunk` the rule's chunk, `--overlay` lays
other parameters over the algorithm's (`--reference 0`: the steps' times
alone). `--tiny` runs the configuration's tiny section on whatever device
JAX finds. Prints one JSON line a reading; the last line repeats them all
and goes to chiprun_out/seqrec_hybrid_probe.json.
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

CONFIG = "seqrec-qwen3-next-80b-a3b-ep16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_310_000_019)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--micro", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="trace the last two steps and list the device's "
                         "operations by time")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--key-heads", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--overlay", default="{}",
                    help="JSON laid over algorithm_params")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_hybrid_reference as ref
    from benchmarks.checks import seqrec_hybrid_step as hybrid_step
    from benchmarks.checks import seqrec_step
    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import linear_attention

    if args.key_heads:
        seqrec.LINEAR_KEY_HEADS = args.key_heads
    if args.chunk:
        linear_attention.CHUNK = args.chunk
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
        record = {"dropped": np.asarray(first["dropped"]), "loss": losses}
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
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0):
            return hybrid_step.compare(numbers, reference, record, unmoved,
                                       limits)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, seqs, targets, spec)
        reference = hybrid_step.reference_numbers(theta0, seqs, targets,
                                                  spec, sound_grads)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            failed=[r[0] for r in rows(program, reference, limits)
                    if not r[3]],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = [("int8", {"precision": "int8"}, None)]
        if n == 0:
            lo, hi = spec.held_experts
            controls += [
                ("expert_left_out", {"held_experts": (lo, hi - 1)}, None),
                ("decay_left_out", {"zero_decay_layer": 1}, None),
                ("attention_gate_left_out", {"attention_gate": False}, None),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads)]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = hybrid_step.reference_numbers(
                theta0, seqs, targets, dataclasses.replace(spec, **over),
                grads_of)
            held = rows(control, reference, limits)
            say(what=name, seed=seed, control_s=time.perf_counter() - t0,
                failed=[r[0] for r in held if not r[3]],
                **{r[0]: r[1] for r in held})
        if n == 0:
            held = rows(program, reference, limits,
                        unmoved=len(reference["grad_norm"]))
            say(what="state_unchanged", seed=seed,
                failed=[r[0] for r in held if not r[3]])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "seqrec_hybrid_probe.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


def micro(say, jax, jnp, np, p) -> None:
    """The chunked rule and the attention kernels alone at the cell's
    shapes: milliseconds a call, forward and forward + backward."""
    from predictionio_tpu.ops import linear_attention
    from predictionio_tpu.ops.attention import blockwise_attention

    def timed(fn, *operands, calls=5):
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(calls):
            result = fn(*operands)
        jax.block_until_ready(result)
        return (time.perf_counter() - t0) / calls * 1000.0

    rng = np.random.default_rng(0)
    l, hv = p.max_len, p.linear_value_heads
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(1, l, hv, p.linear_key_head_dim))) \
        * p.linear_key_head_dim ** -0.5
    k = unit(rng.normal(size=(1, l, hv, p.linear_key_head_dim)))
    v = rng.normal(size=(1, l, hv, p.linear_value_head_dim))
    g = -rng.uniform(0.0, 4.0, size=(1, l, hv))
    beta = rng.uniform(size=(1, l, hv))
    operands = tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))
    rule = jax.jit(linear_attention.gated_delta_rule)
    rule_grad = jax.jit(jax.grad(
        lambda *a: (linear_attention.gated_delta_rule(*a) ** 2).sum(),
        argnums=(0, 1, 2, 3, 4)))
    forward_ms = timed(rule, *operands)
    forward_backward_ms = timed(rule_grad, *operands)
    # the same with the scan's body kept whole instead of recomputed: the
    # rule alone uses `jax.checkpoint` nowhere else
    checkpoint, jax.checkpoint = jax.checkpoint, lambda f, **kw: f
    try:
        kept_ms = timed(jax.jit(jax.grad(
            lambda *a: (linear_attention.gated_delta_rule(*a) ** 2).sum(),
            argnums=(0, 1, 2, 3, 4))), *operands)
    finally:
        jax.checkpoint = checkpoint
    say(what="delta rule alone", shape=[1, l, hv, p.linear_key_head_dim],
        chunk=linear_attention.CHUNK, forward_ms=forward_ms,
        forward_backward_ms=forward_backward_ms,
        forward_backward_body_kept_ms=kept_ms)

    q, k, v = (jnp.asarray(rng.normal(size=(1, l, h, p.head_dim)),
                           jnp.float32)
               for h in (p.n_heads, p.n_kv_heads, p.n_kv_heads))
    attend = lambda q, k, v: blockwise_attention(q, k, v, causal=True)
    say(what="attention kernels alone",
        shape=[1, l, p.n_heads, p.n_kv_heads, p.head_dim],
        forward_ms=timed(jax.jit(attend), q, k, v),
        forward_backward_ms=timed(jax.jit(jax.grad(
            lambda *a: (attend(*a) ** 2).sum(), argnums=(0, 1, 2))),
            q, k, v))


if __name__ == "__main__":
    sys.exit(main())
