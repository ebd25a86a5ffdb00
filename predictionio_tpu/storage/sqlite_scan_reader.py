"""One reader process of a fanned-out sqlite training scan.

`storage/sqlite_scan.py` starts this file BY PATH (never imported by the
package, never through `multiprocessing`), once per rowid window of one
`SqliteEvents.find_columnar` read, and the process ends with that read.
It imports `sqlite3` and `pyarrow` and nothing else of weight: no JAX, no
engine, no CLI, and not NumPy either, which `pyarrow` would otherwise pull in
for a third of a second that every reader of every scan would pay.

Protocol, lines on stdin and stdout, then one binary answer:

    argv[1]          the database file
    -> "ready"       imports done, connection open
    <- {"op": "pin"} roll back any transaction, BEGIN, read once: the
                     connection now holds one WAL snapshot
    -> "pinned"
    <- {"op": "scan", "sql", "params", "columns": [[name, type]]}
    -> "table <n>"   and n bytes of Arrow IPC stream, then exit 0

A "pin" may come again (the parent saw a commit between its window and
the pins). End of input at any point ends the process. Any error is a
traceback on stderr and a non-zero exit; the parent falls back to its
serial scan.
"""

import sys

sys.modules.setdefault("numpy", None)   # pyarrow runs without it

import gc          # noqa: E402
import json        # noqa: E402
import sqlite3     # noqa: E402

import pyarrow as pa   # noqa: E402

_TYPES = {"string": pa.string(), "int64": pa.int64()}


def rows_to_table(rows, columns) -> "pa.Table":
    """`data/columnar.rows_to_event_table` for this process: the same
    columns from the same rows, an empty `properties` as null."""
    schema = pa.schema([(name, _TYPES[kind]) for name, kind in columns])
    if not rows:
        return pa.table({n: [] for n in schema.names}, schema=schema)
    data = dict(zip(schema.names, zip(*rows)))
    if "properties" in data:
        data["properties"] = [p if p else None for p in data["properties"]]
    return pa.table(data, schema=schema)


def main(argv) -> int:
    gc.disable()       # short-lived: nothing here outlives the scan
    out = sys.stdout.buffer
    conn = sqlite3.connect(argv[1], isolation_level=None)
    out.write(b"ready\n")
    out.flush()
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["op"] == "pin":
            if conn.in_transaction:
                conn.execute("ROLLBACK")
            conn.execute("BEGIN")
            conn.execute("SELECT rootpage FROM sqlite_master LIMIT 1"
                         ).fetchall()
            out.write(b"pinned\n")
            out.flush()
            continue
        rows = conn.execute(msg["sql"], msg["params"]).fetchall()
        table = rows_to_table(rows, msg["columns"])
        del rows
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, table.schema) as writer:
            writer.write_table(table)
        buf = sink.getvalue()
        out.write(b"table %d\n" % buf.size)
        out.write(memoryview(buf))
        out.flush()
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
