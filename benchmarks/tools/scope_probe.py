#!/usr/bin/env python3
"""Does a compiled train step's own text name what a capture's device
operations are called, and what does reading it cost? A sequence
configuration's step after a real train, in one process on the chip (a
builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/scope_probe.py

One `train_seqrec` of the configuration on generated sessions (its first
step compiles and publishes the step's scope table, `ops/fn_cache`), then
a second train inside a `jax.profiler` capture. Reports:

  (a) `table`: the seconds of `lower`, `compile`, `text`, `parse` and
      `write` after the first call, the executables the compiler
      built or loaded meanwhile (`compiled`, 0 where the lookup hit) and
      `pio_jax_backend_compile_total` by function;
  (b) `instructions`: how many the table holds, how many carry a scope,
      how many fusions are `mixed`, by phase (forward, `t` backward, `r`
      recomputed);
  (c) `capture`: the "XLA Ops" names of the capture inside the step's
      program that the table does not know (count, seconds, the first
      few), and the step by scope in milliseconds: scope x phase, the
      time in `mixed` fusions and under an inherited scope, the largest
      unnamed operations, and the scopes' sum beside the program's own
      events ("XLA Modules").

`--tiny` runs the configuration's tiny section on whatever device JAX
finds (no device plane on a CPU: (c) is empty there). Prints one JSON
line a reading; the last line repeats them all and goes to
chiprun_out/scope_probe.<config>.json.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="seqrec-kimi-vl-a3b-ep8")
    ap.add_argument("--seed", type=int, default=2_350_000_011)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax

    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.obs import jax_stats, profiler
    from predictionio_tpu.obs.registry import default_registry

    cfg = manifest.load_config(manifest.load_benchmark(), args.config)
    if args.tiny:
        cfg = {**cfg, **cfg["tiny"]}
    p = seqrec.SeqRecParams(**cfg["algorithm_params"])
    out = {"device": jax.devices()[0].device_kind, "config": cfg["name"],
           "readings": []}

    def say(**doc):
        out["readings"].append(doc)
        print(json.dumps(doc), flush=True)

    _, truth = sessions_longhist.generate(cfg, args.seed)
    sessions = [[str(i) for i in row] for row in truth["sessions"].tolist()]
    jax_stats.listen_to_compiler()
    t0 = time.perf_counter()
    model = seqrec.train_seqrec(None, sessions, p)
    steps = len(model.record["loss"])
    say(reading="train", seconds=time.perf_counter() - t0, steps=steps)
    del model

    tables = [t for t in profiler.scope_tables()
              if t["family"] == "seqrec_train_step"]
    if not tables:
        say(reading="table", error="the step published no scope table")
        return 1
    table = tables[-1]
    compiles = default_registry().get(jax_stats.BACKEND_COMPILE_COUNTER)
    say(reading="table", module=table["module"], seconds=table["seconds"],
        compiled=table.get("compiled"),
        file_bytes=os.path.getsize(table["path"]),
        backend_compiles={labels["fun"]: n for labels, n in
                          compiles.samples() if "step" in labels["fun"]})
    rows = table["instructions"]
    events = {k: v for k, v in rows.items() if "c" not in v[1]}
    say(reading="instructions", n=len(rows), containers=len(rows) - len(events),
        scoped=sum(1 for v in events.values() if v[0]),
        fusions=sum(1 for k in events if "fusion" in k),
        mixed=sum(1 for v in events.values() if "m" in v[1]),
        by_phase={ph: sum(1 for v in events.values() if phase(v[1]) == ph)
                  for ph in ("forward", "backward", "recomputed")})

    # (c) a second train inside a capture
    trace_dir = tempfile.mkdtemp(prefix="pio-scope-probe-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            seqrec.train_seqrec(None, sessions, p)
        finally:
            jax.profiler.stop_trace()
        say(reading="capture", **read_capture(trace_dir, table, steps,
                                              profiler))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"scope_probe.{cfg['name']}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out))
    return 0


def phase(flags: str) -> str:
    return "recomputed" if "r" in flags else \
        "backward" if "t" in flags else "forward"


def read_capture(trace_dir: str, table: dict, steps: int, profiler) -> dict:
    """The capture's operations inside the step's program against the
    step's table: what the table does not know, and the step by scope."""
    rows = table["instructions"]
    by_module, programs = profiler.capture_ops(trace_dir)
    ops = by_module.get(table["module"], {})
    module_events, module_s = programs.get(table["module"], (0, 0.0))
    other_modules_s = sum(s for name, (_, s) in programs.items()
                          if name != table["module"])
    if not ops:
        return {"note": "the capture holds no device operation of "
                        f"{table['module']}"}
    per_step = 1e3 / max(module_events, 1)
    unknown = {k: v for k, v in ops.items() if k not in rows}
    known = {k: s for k, s in ops.items()
             if k in rows and "c" not in rows[k][1]}
    by_scope_phase, mixed_s, inherited_s = {}, 0.0, 0.0
    for k, s in known.items():
        scope, flags = rows[k]
        cell = by_scope_phase.setdefault(scope or "(unnamed)", {})
        cell[phase(flags)] = cell.get(phase(flags), 0.0) + s * per_step
        if "m" in flags:
            mixed_s += s
        if "i" in flags:
            inherited_s += s
    joined = profiler.scope_seconds(ops, [table])
    total = sum(known.values())
    unnamed = sorted(((k, s) for k, s in known.items() if not rows[k][0]),
                     key=lambda kv: -kv[1])
    return {
        "steps_in_capture": module_events, "op_names": len(ops),
        "other_modules_s": other_modules_s,
        "unknown_names": len(unknown),
        "unknown_s": sum(unknown.values()),
        "unknown_first": sorted(unknown, key=lambda k: -unknown[k])[:12],
        "step_device_ms": total * per_step,
        "step_module_ms": module_s * per_step,
        "named_pct": 100.0 * sum(s for k, s in known.items() if rows[k][0])
        / total if total else None,
        "mixed_ms": mixed_s * per_step,
        "inherited_ms": inherited_s * per_step,
        "scope_ms": {(k or "(unnamed)"): v * per_step for k, v in
                     sorted(joined[table["family"]].items(),
                            key=lambda kv: -kv[1])},
        "scope_phase_ms": by_scope_phase,
        "unnamed_first": [[k, s * per_step] for k, s in unnamed[:25]],
        "steps_expected": steps,
    }


if __name__ == "__main__":
    sys.exit(main())
