#!/usr/bin/env python3
"""What a release's way from the device to the model store costs, piece
by piece, on a sequence configuration's weights after a real train, in
one process on the chip (a builder's tool; no cell runs it):

    chiprun -- python3 benchmarks/tools/persist_probe.py --rounds 3

One `train_seqrec` of the configuration on generated sessions, then, a
round, on fresh device copies of the trained weights each time, into the
local-fs model store a cell uses (as `workflow.train._persist` writes):

  (a) `fetch`: every leaf to numpy and nothing else (what `seqrec_fetch`
      was);
  (b) `persist_host`: the persist alone, the model holding (a)'s arrays;
  (c) `persist_device`: `copy_to_host_async()` on every leaf, then the
      persist of the model holding the device arrays: fetch and write as
      one;
  (d) `persist_device_cold`: the same without starting the copies: the
      pickler fetches each leaf when it reaches it and nothing is ahead;
  (e) `arrival`: every copy started, each leaf read in order, nothing
      written: when the first leaf and each quarter of the bytes arrive;
  (f) `persist_device_ahead`: as (d), the copies started `--ahead` leaves
      in front of the pickler.

Each reading carries the writer's and the hash thread's seconds and the
pickler's wait for leaves; the digests of (b), (c) and (d) have to agree.
`--tiny` runs the configuration's tiny section on whatever device JAX
finds. Prints one JSON line a reading; the last line repeats them all and
goes to chiprun_out/persist_probe.json.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
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
    ap.add_argument("--seed", type=int, default=2_340_000_011)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--ahead", type=int, nargs="*", default=[1, 4, 16],
                    help="leaves the copies run in front of the pickler")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    work = tempfile.mkdtemp(prefix="pio-persist-probe-")
    os.environ.update({
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "FS",
        "PIO_STORAGE_SOURCES_FS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_FS_PATH": os.path.join(work, "models"),
    })
    try:
        return probe(args)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def probe(args) -> int:
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.events import sessions_longhist
    from benchmarks.lib import manifest
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.workflow import serialization

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
    t0 = time.perf_counter()
    model = seqrec.train_seqrec(None, sessions, p)
    seconds = time.perf_counter() - t0

    def in_use():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use")

    after_train = in_use()
    # the trained weights on the host, and nothing of the train left on
    # the device: every reading puts its own copies there
    trained = jax.tree.map(np.array, model.params)
    model = dataclasses.replace(model, params=trained)
    gc.collect()
    nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(trained))
    say(reading="train", seconds=seconds, weight_bytes=nbytes,
        leaves=len(jax.tree.leaves(trained)), loss=model.record["loss"],
        device_bytes_after_train=after_train,
        device_bytes_weights_dropped=in_use())
    store = Storage.get_model_data_models()

    def fresh():
        """Device copies no host copy hangs on yet."""
        gc.collect()    # the last reading's pickler class held its leaves
        copies = jax.tree.map(jnp.asarray, trained)
        jax.block_until_ready(copies)
        return copies

    def persist(name, params, start_copies, ahead=None):
        """`workflow.train._persist`'s three lines, for the writer's
        numbers, through a pickler that notes each leaf's wait and, with
        `ahead`, starts the copies that many leaves in front of itself."""
        leaves = jax.tree.leaves(params)
        waits = []

        class Noting(serialization._ReleasePickler):
            def reducer_override(self, obj):
                if isinstance(obj, jax.Array):
                    if ahead is not None:
                        for leaf in leaves[len(waits):len(waits) + ahead + 1]:
                            leaf.copy_to_host_async()
                    before = self.wait_seconds
                    out = super().reducer_override(obj)
                    waits.append(self.wait_seconds - before)
                    return out
                return super().reducer_override(obj)

        t0 = time.perf_counter()
        if start_copies:
            for leaf in leaves:
                leaf.copy_to_host_async()
        started = time.perf_counter() - t0
        device_bytes_started = in_use()
        with store.open_write(name) as f:
            with serialization.DigestingWriter(f) as writer:
                pickler = Noting(writer)
                pickler.dump([dataclasses.replace(model, params=params)])
        wall = time.perf_counter() - t0
        store.delete(name)
        longest = sorted(range(len(waits)), key=waits.__getitem__)[-4:]
        return {"seconds": wall, "start_copies_s": started,
                "device_bytes_copies_started": device_bytes_started,
                "bytes": writer.size, "digest": writer.hexdigest()[:16],
                "write_s": writer.write_seconds,
                "hash_s": writer.hash_seconds,
                "device_bytes": pickler.device_bytes,
                "fetch_wait_s": pickler.wait_seconds,
                "first_leaf_wait_s": waits[0] if waits else None,
                "longest_waits": [[i, leaves[i].nbytes, waits[i]]
                                  for i in longest]}

    def arrival(params):
        """Every copy started, then each leaf read in order and nothing
        written: when a quarter, a half, ... of the bytes were there."""
        leaves = jax.tree.leaves(params)
        t0 = time.perf_counter()
        for leaf in leaves:
            leaf.copy_to_host_async()
        started = time.perf_counter() - t0
        marks, got, first = {}, 0, None
        for leaf in leaves:
            np.asarray(leaf)
            got += leaf.nbytes
            now = time.perf_counter() - t0
            first = now if first is None else first
            for share in (0.25, 0.5, 0.75, 1.0):
                if got >= share * nbytes and share not in marks:
                    marks[share] = now
        return {"start_copies_s": started, "first_leaf_s": first,
                "first_leaf_bytes": leaves[0].nbytes,
                "arrived_s": marks}

    for n in range(args.rounds):
        params = fresh()
        t0 = time.perf_counter()
        host = jax.tree.map(np.asarray, params)
        say(reading="fetch", round=n, seconds=time.perf_counter() - t0,
            gb_per_s=nbytes / (time.perf_counter() - t0) / 1e9)
        del params
        say(reading="persist_host", round=n, **persist(f"b{n}", host, False))
        del host
        say(reading="persist_device", round=n,
            **persist(f"c{n}", fresh(), True))
        say(reading="persist_device_cold", round=n,
            **persist(f"d{n}", fresh(), False))
        say(reading="arrival", round=n, **arrival(fresh()))
        for ahead in args.ahead:
            say(reading="persist_device_ahead", ahead=ahead, round=n,
                **persist(f"e{n}", fresh(), False, ahead))

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "persist_probe.json"),
              "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
