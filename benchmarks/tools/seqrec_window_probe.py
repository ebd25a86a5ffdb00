#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_window_step.py` were set
from, the step's time and memory, and the banded attention kernels alone
by block, in one process on the chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_window_probe.py --sweep
    chiprun --timeout 3300 -- python3 benchmarks/tools/seqrec_window_probe.py --seeds 6

`--sweep` times `window_attention_pallas` alone at the cell's sliding
layers' shape (1 session x 64 query heads over 8 key/value heads x 16,384
positions x 128, window 512), forward and forward + backward, at blocks
of 128, 256, 512 and 1024 (the constant `attention_pallas.WINDOW_BLOCK`
set in turn), beside the whole-causal call at 64 and at 48 heads: the
table `WINDOW_BLOCK` was chosen from.

For each seed: the sessions events/sessions_longhist.py makes, the batch
the program's first step trains on, that step through the program's own
`make_train_step` from the seeded weights (its loss, gradient norms,
expert loads and the experts' update by expert: the sound reading), the
reference at the highest precision on the same weights and batch, and
the controls put in the program's place and held to the configuration's
own limits (`failed` names the rows by which each comes out not
correct): on the first `--int8-seeds` seeds the reference with every
matrix product's operands rounded to int8; on the first seed each new
mechanism broken in turn (`seqrec_window_reference.FAULTS`: the window
ignored, off by one either way, the two kinds' rotary tables swapped,
YaRN's ramp left out, the attention factor left out, the gate left out,
48 heads where 64 belong, the scaling factor left out), a learning rate
ten times off, the first expert layer's median held expert left where it
is, and a train that returns its state unchanged. `--faults name,name`
runs those alone, `none` none; `--reference 0` times the steps alone;
`--profile` traces the last two steps and lists the device's operations
by time. `--edge` holds the band's trailing edge on this device at the
cell's sliding layers' shape, where the reference cannot (an edge off by
one key of 512 moves every row of the check by less than the program's
own rounding does): operands made so that a query's score is largest,
by 25, at the keys 511 and 512 positions back, through
`blockwise_attention`'s route here (`edge_case`); the output has to be
the value 511 back and `dv` the cotangent 511 ahead, and an edge off by
one either way reads 0.5 or more where the sound one reads 0. `--tiny`
runs the configuration's tiny section on whatever device JAX finds. Prints one JSON line a reading; the last line repeats
them all and goes to chiprun_out/seqrec_window_probe<tag>.json.
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

CONFIG = "seqrec-laguna-xs2-ep8"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_440_000_019)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--int8-seeds", type=int, default=3)
    ap.add_argument("--faults", default="all")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--edge", action="store_true")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_step
    from benchmarks.checks import seqrec_window_reference as ref
    from benchmarks.checks import seqrec_window_step as window_step
    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec

    cfg = manifest.load_config(manifest.load_benchmark(), CONFIG)
    if args.tiny:
        cfg = {**cfg, **cfg["tiny"]}
    params_json = cfg["algorithm_params"]
    p = seqrec.SeqRecParams(**params_json)
    spec = ref.Spec.of(params_json, recompute=True)
    device = jax.devices()[0]
    out = {"device": device.device_kind, "readings": []}
    no_limits = collections.defaultdict(lambda: math.inf)
    limits = cfg.get("limits") if isinstance(cfg.get("limits"), dict) \
        else no_limits

    def say(**doc):
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)

    if args.sweep:
        sweep(say, jax, jnp, np, p, args.tiny)
    if args.edge:
        band = p.held_kind("swa")
        for window in (band.window - 1, band.window, band.window + 1):
            # the operands are made for the sound window every time
            say(what="the band's edge", window=window,
                **edge_errors(jax, jnp, np, p.max_len, band.heads,
                              band.kv_heads, band.head_dim, band.window,
                              window))

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
                   "expert_update_norm": np.asarray(
                       first["expert_update_norm"]),
                   "load": np.asarray(first["load"])}
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses,
            held_tokens=np.asarray(first["held_tokens"]).sum(-1).tolist(),
            dropped=int(record["dropped"].sum()),
            mixer_layers={k: int(v) for k, v in
                          first["mixer_layers"].items()},
            attention_pallas=bool(first["attention_pallas"]),
            expert_product_pallas=bool(first["expert_product_pallas"]),
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))

        if not args.reference:
            continue

        def rows(numbers, reference, limits=no_limits, unmoved=0):
            return window_step.compare(numbers, reference, record, unmoved,
                                       limits)

        seqs, targets = batches[0]
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, seqs, targets, spec)
        reference = window_step.reference_numbers(theta0, seqs, targets,
                                                  spec, sound_grads)
        say(what="sound", seed=seed, reference_s=time.perf_counter() - t0,
            reference_loss=reference["loss"],
            reference_held_tokens=reference["held_load"].tolist(),
            experts_update_norm_by_layer={
                group: [program["update_norm"][group], norm]
                for group, norm in reference["update_norm"].items()
                if group.endswith(".experts")},
            failed=[r[0] for r in rows(program, reference, limits)
                    if not r[3]],
            **{r[0]: r[1] for r in rows(program, reference)})
        controls = []
        if n < args.int8_seeds:
            controls.append(("int8", {"precision": "int8"}, None))
        if n == 0:
            median = int(np.argsort(reference["held_load"][0], kind="stable")[
                (reference["held_load"].shape[1] - 1) // 2])
            faults = [(fault, {"fault": fault}, None)
                      for fault in ref.FAULTS] + [
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads),
                ("expert_not_updated", {"expert_not_updated": (0, median)},
                 sound_grads)]
            controls += [f for f in faults if args.faults == "all"
                         or f[0] in args.faults.split(",")]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = window_step.reference_numbers(
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
                           f"seqrec_window_probe{args.tag}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


def edge_case(np, length, heads, kv_heads, width, window, seed=0):
    """q [1, L, H, D], k, v [1, L, Hkv, D] whose scores depend on the
    distance query - key alone and peak, 25 above every other distance a
    band can hold, midway between `window` - 1 and `window`: q_t . k_s =
    a sum over D / 2 frequencies of cos(w_j (t - s - (window - 0.5))).
    Under the sound band a query's softmax is its key `window` - 1 back
    and nothing else; one key more splits it with the next, one key
    fewer hands it to the key before."""
    pairs = np.arange(1, width // 2 + 1)
    omega = 2 * np.pi * pairs / (4 * window)
    peak = lambda x: np.cos(omega * x).sum()
    amp = 25.0 * np.sqrt(width) / (peak(0.5) - peak(1.5))
    at = np.arange(length)[:, None] * omega

    def turned(phase, scale):
        rows = np.stack([np.cos(at + phase), np.sin(at + phase)], -1)
        return scale * rows.reshape(1, length, 1, width)

    q = np.broadcast_to(turned(-omega * (window - 0.5), amp),
                        (1, length, heads, width))
    k = np.broadcast_to(turned(0.0, 1.0), (1, length, kv_heads, width))
    v = np.random.default_rng(seed).normal(
        size=(1, length, kv_heads, width))
    return tuple(np.asarray(t, np.float32) for t in (q, k, v))


def edge_errors(jax, jnp, np, length, heads, kv_heads, width, made_for,
                window):
    """The largest |out_t - v_(t - made_for + 1)| and |dv_s - the group's
    summed do_(s + made_for - 1)| over the positions that have such a
    partner (but the session's first keys, which its first queries,
    with no key that far back, fall on), `blockwise_attention` run under
    `window`: 0 to rounding where `window` is the one the operands were
    made for."""
    from predictionio_tpu.ops.attention import blockwise_attention

    q, k, v = map(jnp.asarray, edge_case(np, length, heads, kv_heads, width,
                                         made_for))
    do = jnp.asarray(np.random.default_rng(1).normal(size=q.shape),
                     jnp.float32)
    back = made_for - 1
    out, pull = jax.vjp(lambda v: blockwise_attention(
        q, k, v, causal=True, window=window), v)
    dv, = pull(do)
    group = heads // kv_heads
    want_out = jnp.repeat(v, group, axis=2)[:, :length - back]
    want_dv = do[:, back:].reshape(1, length - back, kv_heads, group,
                                   width).sum(3)
    return {"out_err": float(jnp.abs(out[:, back:] - want_out).max()),
            "dv_err": float(jnp.abs(dv[:, back:length - back]
                                    - want_dv[:, back:]).max())}


def sweep(say, jax, jnp, np, p, tiny: bool) -> None:
    """The banded kernels alone at the cell's sliding layers' shape by
    block, beside the whole-causal call: milliseconds a call, forward
    and forward + backward."""
    from predictionio_tpu.ops import attention_pallas
    from predictionio_tpu.ops.attention import band_pairs, blockwise_attention

    def timed(fn, *operands, calls=5):
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(calls):
            result = fn(*operands)
        jax.block_until_ready(result)
        return (time.perf_counter() - t0) / calls * 1000.0

    band = p.held_kind("swa")
    rng = np.random.default_rng(0)
    l = p.max_len
    kind = jax.devices()[0].device_kind

    def operands(heads):
        return tuple(jnp.asarray(rng.normal(size=(1, l, h, band.head_dim)),
                                 jnp.float32)
                     for h in (heads, band.kv_heads, band.kv_heads))

    def both(attend, heads):
        q, k, v = operands(heads)
        return (timed(jax.jit(attend), q, k, v),
                timed(jax.jit(jax.grad(lambda *a: (attend(*a) ** 2).sum(),
                                       argnums=(0, 1, 2))), q, k, v))

    chosen = attention_pallas.WINDOW_BLOCK
    for block in (128, 256, 512, 1024):
        if tiny and block > 128:
            continue
        attention_pallas.WINDOW_BLOCK = block
        try:
            # (a new function a block: the constant is read at trace time)
            forward_ms, both_ms = both(
                lambda q, k, v: blockwise_attention(
                    q, k, v, causal=True, window=band.window), band.heads)
            inside, visited = band_pairs(kind, l, band.head_dim,
                                         band.head_dim, band.window)
            say(what="banded kernels alone", block=block,
                shape=[1, l, band.heads, band.kv_heads, band.head_dim],
                window=band.window, forward_ms=forward_ms,
                forward_backward_ms=both_ms,
                block_fill_pct=100.0 * inside / visited)
        except Exception as e:      # a block Mosaic refuses is a reading
            say(what="banded kernels alone", block=block, error=repr(e)[:400])
    attention_pallas.WINDOW_BLOCK = chosen
    for heads in (band.heads, p.n_heads):
        forward_ms, both_ms = both(
            lambda q, k, v: blockwise_attention(q, k, v, causal=True), heads)
        say(what="whole-causal kernels alone",
            shape=[1, l, heads, band.kv_heads, band.head_dim],
            forward_ms=forward_ms, forward_backward_ms=both_ms)


if __name__ == "__main__":
    sys.exit(main())
