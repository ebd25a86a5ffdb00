#!/usr/bin/env python3
"""The readings the limits of `checks/seqrec_packed_step.py` were set
from, the packed step's time and memory, and the banded attention
kernels alone by block at this cell's shape, in one process on the chip
(a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/seqrec_packed_probe.py --sweep --seeds 0
    chiprun --timeout 3300 -- python3 benchmarks/tools/seqrec_packed_probe.py --seeds 3

`--sweep` times the banded kernels alone at the cell's sliding layers'
shape (2 rows x 32 query heads over 4 key/value heads x 8,192 positions
x 128, window 1,024), forward and forward + backward, at blocks of 512
and 1024 (the constant `attention_pallas.WINDOW_BLOCK` set in turn), a
session a row and on the first step's packed rows, beside the
whole-causal kernels on the same two.

For each seed: the sessions events/sessions_packed.py makes, the rows the
program's first steps train on (`seqrec.pack_sessions` over the sessions
in the data source's order), the first step through the program's own
`make_train_step` from the seeded weights (the sound reading), the
reference at the highest precision on the same weights and the same
sessions EACH ALONE (checks/seqrec_packed_step.first_batch: the check's
own packing rule), and the controls held to the configuration's own
limits (`failed` names the rows by which each comes out not correct):
on the first `--int8-seeds` seeds the reference with every matrix
product's operands rounded to int8; on the first seed
  * what a reference that sees one session at a time can break, as
    numbers handed to the one compiled reference
    (`seqrec_packed_reference.FAULTS`: a window of 512, YaRN's factor
    64, YaRN on the sliding layers, sigmoid scores, the chosen gates not
    normalised), a learning rate ten times off, the first expert layer's
    median held expert left where it is, a state returned unchanged;
  * what only a program that packs can break, planted in the PROGRAM's
    step and read against the sound reference: in its inputs (the same
    compiled step) every boundary one position late or early
    ("boundary_late", "boundary_early"), positions that do not restart
    ("positions_not_restarted"), no boundary at all
    ("boundary_ignored"); in its layers (a step compiled again) the
    boundary ignored in the full layer alone or in the sliding layers
    alone ("boundary_ignored_full", "boundary_ignored_sliding").
`--faults name,name` runs those alone, `none` none; `--reference 0` times
the steps alone. `--tiny` runs the configuration's tiny section on
whatever device JAX finds. Prints one JSON line a reading; the last line
repeats them all and goes to chiprun_out/seqrec_packed_probe<tag>.json.
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

CONFIG = "seqrec-mellum2-12b-a2.5b-ep4"
INPUT_FAULTS = ("boundary_late", "boundary_early", "positions_not_restarted",
                "boundary_ignored")
LAYER_FAULTS = ("boundary_ignored_full", "boundary_ignored_sliding")


def faulted_inputs(np, fault, ids, positions):
    """A packed batch's (ids, positions) with one mechanism broken."""
    if fault == "boundary_late":      # a session's first position: the last's
        ids = np.concatenate([ids[:, :1], ids[:, :-1]], axis=1)
    elif fault == "boundary_early":   # a session's last position: the next's
        ids = np.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)
    elif fault == "positions_not_restarted":
        positions = np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32),
                                    ids.shape)
    elif fault == "boundary_ignored":
        ids = (ids > 0).astype(np.int32)
    return ids, np.ascontiguousarray(positions)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_470_000_019)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--int8-seeds", type=int, default=3)
    ap.add_argument("--faults", default="all")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--reference", type=int, default=1,
                    help="0: the program's steps only")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.checks import seqrec_packed_reference as ref
    from benchmarks.checks import seqrec_packed_step as packed_step
    from benchmarks.events import sessions_packed
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
    wanted = lambda name: args.faults == "all" \
        or name in args.faults.split(",")

    out_path = os.path.join(ROOT, "chiprun_out",
                            f"seqrec_packed_probe{args.tag}.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def say(**doc):
        # (written again a reading: a call cut short keeps what it read)
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)
        with open(out_path, "w") as f:
            json.dump(out, f)

    def batches_of(seed):
        """(the generated sessions, the program's packed rows, the rows
        of epoch 0's batches)."""
        _, truth = sessions_packed.generate(cfg, seed)
        coded = [np.concatenate([inputs, targets[-1:]]).tolist()
                 for inputs, targets in packed_step.coded_sessions(
                     truth["sessions"], p.max_len)]
        packed = seqrec.pack_sessions(coded, p.max_len)
        order = packed_step.epoch0_rows(params_json, len(packed.inputs))
        return truth["sessions"], packed, [
            order[lo:lo + p.batch_size] for lo in range(
                0, min(args.steps * p.batch_size,
                       len(order) - p.batch_size + 1), p.batch_size)]

    if args.sweep:
        _, packed, batches = batches_of(args.first_seed)
        sweep(say, jax, jnp, np, p, packed.ids[batches[0]], args.tiny)

    optimizer = seqrec.make_optimizer(p)
    step = seqrec.make_train_step(None, p, optimizer)

    def first_step(step, packed, rows, fault=None):
        """The step's numbers from the seeded weights on these rows."""
        params = seqrec.init_params(None, cfg["n_items"], p)
        ids, positions = faulted_inputs(np, fault, packed.ids[rows],
                                        packed.positions[rows])
        stats = jax.device_get(step(
            params, optimizer.init(params), jnp.asarray(packed.inputs[rows]),
            jnp.asarray(packed.targets[rows]), jnp.asarray(ids),
            jnp.asarray(positions))[2])
        return numbers(np, stats), stats

    theta0 = None
    for n in range(args.seeds):
        seed = args.first_seed + 1_000_003 * n
        sessions, packed, batches = batches_of(seed)
        t0 = time.perf_counter()
        params = seqrec.init_params(None, cfg["n_items"], p)
        jax.block_until_ready(params)
        init_s = time.perf_counter() - t0
        if theta0 is None:
            theta0 = jax.tree.map(np.asarray, params)
        opt_state = optimizer.init(params)
        walls, losses, first = [], [], None
        for rows in batches:
            t0 = time.perf_counter()
            params, opt_state, stats = step(
                params, opt_state, *(jnp.asarray(t[rows]) for t in (
                    packed.inputs, packed.targets, packed.ids,
                    packed.positions)))
            jax.block_until_ready(params)
            walls.append(time.perf_counter() - t0)
            stats = jax.device_get(stats)
            losses.append(float(stats["loss"]))
            first = first or stats
        mem = device.memory_stats() or {}
        record = {"dropped": np.asarray(first["dropped"]), "loss": losses}
        del params, opt_state
        program = numbers(np, first)
        say(what="program", seed=seed, init_s=init_s, step_walls_s=walls,
            losses=losses, rows=[r.tolist() for r in batches],
            sessions_a_row=[len(packed.sessions[r]) for r in batches[0]],
            held_tokens=np.asarray(first["held_tokens"]).sum(-1).tolist(),
            dropped=int(record["dropped"].sum()),
            attention_pallas=bool(first["attention_pallas"]),
            attention_rows=bool(first.get("attention_rows", False)),
            expert_product_pallas=bool(first["expert_product_pallas"]),
            peak_bytes_in_use=mem.get("peak_bytes_in_use"),
            peak_bytes_reserved=mem.get("peak_bytes_reserved"))
        if not args.reference:
            continue

        def rows_of(numbers_, reference, limits=no_limits, unmoved=0):
            return packed_step.compare(numbers_, reference, record, unmoved,
                                       limits)

        def held(what, numbers_, reference, **more):
            rows = rows_of(numbers_, reference, limits)
            say(what=what, seed=seed, **more,
                failed=[r[0] for r in rows if not r[3]],
                **{r[0]: r[1] for r in rows})

        alone, n_positions = packed_step.first_batch(cfg, sessions)
        t0 = time.perf_counter()
        sound_grads = ref.loss_and_grads(theta0, alone, spec, n_positions)
        reference = packed_step.reference_numbers(theta0, alone, n_positions,
                                                  spec, sound_grads)
        held("sound", program, reference,
             reference_s=time.perf_counter() - t0,
             reference_loss=reference["loss"],
             sessions=[len(inputs) for inputs, _ in alone])
        controls = []
        if n < args.int8_seeds:
            controls.append(("int8", {"precision": "int8"}, None))
        if n == 0 and args.faults != "none":
            median = int(np.argsort(reference["held_load"][0], kind="stable")[
                (reference["held_load"].shape[1] - 1) // 2])
            controls += [f for f in [
                *((fault, {"fault": fault}, None) for fault in ref.FAULTS),
                ("learning_rate_x10",
                 {"learning_rate": 10 * spec.learning_rate}, sound_grads),
                ("expert_not_updated", {"expert_not_updated": (0, median)},
                 sound_grads)] if wanted(f[0])]
        for name, over, grads_of in controls:
            t0 = time.perf_counter()
            control = packed_step.reference_numbers(
                theta0, alone, n_positions,
                dataclasses.replace(spec, **over), grads_of)
            held(name, control, reference,
                 control_s=time.perf_counter() - t0)
        if n or args.faults == "none":
            continue
        rows = rows_of(program, reference, limits,
                       unmoved=len(reference["grad_norm"]))
        say(what="state_unchanged", seed=seed,
            failed=[r[0] for r in rows if not r[3]])
        for fault in filter(wanted, INPUT_FAULTS):
            held(fault, first_step(step, packed, batches[0], fault)[0],
                 reference)
        for fault in filter(wanted, LAYER_FAULTS):
            t0 = time.perf_counter()
            with boundary_ignored_in(seqrec, jnp,
                                     full=fault.endswith("full")):
                broken = seqrec.make_train_step(None, p, optimizer)
                faulted = first_step(broken, packed, batches[0])[0]
            held(fault, faulted, reference,
                 control_s=time.perf_counter() - t0)
    print(json.dumps(out), flush=True)
    return 0


def numbers(np, stats) -> dict:
    """A step's numbers as `compare` reads a release's record."""
    return {"loss": float(stats["loss"]),
            **{key: {k: float(v) for k, v in stats[key].items()}
               for key in ("grad_norm", "update_norm")},
            "expert_update_norm": np.asarray(stats["expert_update_norm"]),
            "load": np.asarray(stats["load"])}


class boundary_ignored_in:
    """While it is open, the full layers' (or the sliding layers')
    attention of a step traced inside it takes a row for one session: the
    ids it is handed say only what is padding."""

    def __init__(self, seqrec, jnp, full: bool):
        self.kind, self.jnp, self.full = seqrec.GroupedQueryAttention, jnp, \
            full

    def __enter__(self):
        sound, jnp, full = self.kind.apply, self.jnp, self.full
        self.sound = sound

        def apply(record, w, x, key_mask, p, mesh, positions=None):
            if (record.window is None) == full:
                key_mask = (key_mask > 0).astype(jnp.int32)
            return sound(record, w, x, key_mask, p, mesh, positions)

        self.kind.apply = apply

    def __exit__(self, *exc):
        self.kind.apply = self.sound


def sweep(say, jax, jnp, np, p, ids, tiny: bool) -> None:
    """The banded kernels alone at the cell's sliding layers' shape by
    block, beside the whole-causal ones: milliseconds a call, forward and
    forward + backward, a session a row and on the packed rows `ids`."""
    from predictionio_tpu.ops import attention_pallas
    from predictionio_tpu.ops.attention import (blockwise_attention,
                                                session_pairs)

    def timed(fn, *operands, calls=5):
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(calls):
            result = fn(*operands)
        jax.block_until_ready(result)
        return (time.perf_counter() - t0) / calls * 1000.0

    band = p.held_kind("swa")
    rng = np.random.default_rng(0)
    b, l = ids.shape
    kind = jax.devices()[0].device_kind
    q, k, v = (jnp.asarray(rng.normal(size=(b, l, h, band.head_dim)),
                           jnp.float32)
               for h in (band.heads, band.kv_heads, band.kv_heads))

    def both(attend):
        return (timed(jax.jit(attend), q, k, v),
                timed(jax.jit(jax.grad(lambda *a: (attend(*a) ** 2).sum(),
                                       argnums=(0, 1, 2))), q, k, v))

    def attend(window, packed):
        named = {"key_mask": jnp.asarray(ids), "packed": True} if packed \
            else {}
        return lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, window=window, **named)

    chosen = attention_pallas.WINDOW_BLOCK
    for block in (512, 1024):
        if tiny and block > 512:
            continue
        attention_pallas.WINDOW_BLOCK = block
        for packed in (False, True):
            try:
                # (a new function a block: the constant is read at trace
                # time)
                forward_ms, both_ms = both(attend(band.window, packed))
                inside, multiplied = session_pairs(
                    kind, ids if packed else np.ones_like(ids),
                    band.head_dim, band.head_dim, band.window)
                say(what="banded kernels alone", block=block, packed=packed,
                    shape=[b, l, band.heads, band.kv_heads, band.head_dim],
                    window=band.window, forward_ms=forward_ms,
                    forward_backward_ms=both_ms,
                    score_fill_pct=100.0 * inside / multiplied)
            except Exception as e:  # a block Mosaic refuses is a reading
                say(what="banded kernels alone", block=block, packed=packed,
                    error=repr(e)[:400])
    attention_pallas.WINDOW_BLOCK = chosen
    for packed in (False, True):
        forward_ms, both_ms = both(attend(None, packed))
        inside, multiplied = session_pairs(
            kind, ids if packed else np.ones_like(ids), band.head_dim,
            band.head_dim)
        say(what="whole-causal kernels alone", packed=packed,
            shape=[b, l, band.heads, band.kv_heads, band.head_dim],
            forward_ms=forward_ms, forward_backward_ms=both_ms,
            score_fill_pct=100.0 * inside / multiplied)


if __name__ == "__main__":
    sys.exit(main())
