#!/usr/bin/env python3
"""The process that fills the event store of a run: started by child.py
for a `train` cell while JAX reaches the chip, it makes the
configuration's events from the seed (events/<name>.py), writes them
into the sqlite store in bulk, and leaves what the generator returned
beside the columns ("the truth") in a file for the configuration's check.

A process of its own, not a thread of the child: two million rows of
Python objects, and the generator's arrays, made and freed in the
process that trains leave its heap in a state that the program's own
large buffers then feel (PERF.md section 6, PR 26: `train_id_assign`
read 0.31 s or 0.38-0.53 s a train by nothing but how the rows had been
built). A `pio train` starts on a fresh heap; so do the window's trains.
It never imports JAX.

    python3 benchmarks/fill.py '<spec JSON>' <app name> <truth file>
"""

from __future__ import annotations

import importlib
import itertools
import json
import os
import pickle
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the event columns a generator (events/<name>.py) returns, in the order
#: of the store's row; each is one value for every row or one per row
EVENT_COLUMNS = ("event", "entity_type", "entity_id", "target_entity_type",
                 "target_entity_id", "properties", "event_time_ms")


def fill_event_store(columns: dict, seed: int, app_name: str,
                     block: int = 250_000) -> int:
    """Event columns into the sqlite event store in bulk: the rows
    `insert_batch` would write for the same events (its columns and
    encodings: ids as text, no target type where there is no target,
    properties as the JSON text given or NULL, times in UTC milliseconds
    with offset 0, creation time = event time, no tags, no prId), handed
    to the store's own connection in a few `executemany` calls. Building
    2M Event objects for `insert_batch` takes a minute of host Python
    that every run of every check would pay (tests/test_datagen.py holds
    the two paths to the same rows). Returns the number of rows written."""
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.storage.sqlite_backend import event_table_name

    if set(columns) != set(EVENT_COLUMNS):
        raise SystemExit(f"event columns {sorted(columns)} are not "
                         f"{sorted(EVENT_COLUMNS)}")
    def one_value(col) -> bool:
        return isinstance(col, (str, int, type(None)))

    lengths = {len(c) for c in columns.values() if not one_value(c)}
    if len(lengths) != 1:
        raise SystemExit(f"event columns of lengths {sorted(lengths)}: "
                         "one column at least is per row, all of one length")
    n = lengths.pop()

    def cells(name: str, lo: int):
        col = columns[name]
        if one_value(col):
            return itertools.repeat(col)
        part = col[lo:lo + block]
        part = part.tolist() if hasattr(part, "tolist") else list(part)
        if not name.endswith("_id"):
            return part
        # the store holds ids as text: a whole number is its decimal text
        if getattr(col, "dtype", None) is not None and col.dtype.kind in "iu":
            return map(str, part)
        return [None if v is None else str(v) for v in part]

    app = Storage.get_meta_data_apps().get_by_name(app_name)
    store = Storage.get_events()
    table = event_table_name(app.id, None)
    for lo in range(0, n, block):
        rows = [(f"{seed & 0xFFFFFFFF:08x}{i:024x}", ev, et, eid,
                 tt if tid is not None else None, tid,
                 props, ms, 0, None, None, ms, 0)
                for i, ev, et, eid, tt, tid, props, ms in zip(
                    range(lo, min(lo + block, n)),
                    *(cells(name, lo) for name in EVENT_COLUMNS))]
        with store.client.write_lock():
            store.client.conn().executemany(
                f"INSERT INTO {table} VALUES (?,?,?,?,?,?,?,?,?,?,?,?,?)",
                rows)
            store.client.conn().commit()
    return n


def main(argv=None) -> int:
    spec_json, app_name, truth_path = (argv or sys.argv)[1:4]
    t0 = time.perf_counter()
    spec = json.loads(spec_json)
    events = importlib.import_module(
        f"benchmarks.events.{spec['config']['events']}")
    columns, truth = events.generate(spec["config"], spec["seed"])
    n = fill_event_store(columns, spec["seed"], app_name)
    with open(truth_path, "wb") as f:
        pickle.dump(truth, f, protocol=pickle.HIGHEST_PROTOCOL)
    print(f"[fill] {n} events written in {time.perf_counter() - t0:.2f} s",
          file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
