"""A sqlite training scan fanned out over rowid windows.

The serial scan (`SqliteEvents.find_columnar`: one SELECT, `fetchall`, one
arrow table) spends a tenth of its time inside sqlite and the rest making
and collecting row objects under the GIL of the process that holds the
chip. Here the same read is cut into N contiguous rowid windows, each read
by a short-lived process (`sqlite_scan_reader.py`) that runs the SQL
`_find_sql` produces for that window and answers with an Arrow IPC stream;
the parent concatenates the N tables in window order and makes no per-row
object. It is what the reference does for this read
(JDBCPEvents.scala:89-101: numeric range partitions of one JdbcRDD).

One answer, as one SELECT at one instant would give it: the parent reads
`PRAGMA data_version` and the rowid window, every reader begins a read
transaction and pins its WAL snapshot, the parent reads `data_version`
again. Equal: no other connection committed in between, so every reader
holds the state the window was taken from and keeps it however long the
scan takes. Different: pin again, a few times, then scan serially. The
readers are started BEFORE that exchange, so the vulnerable interval is the
pinning alone.

Whether to fan out is read from the store and the host, never from a knob:
`planned_readers`. `fan_out` returns None whenever the serial path has to
answer (a window too small, a snapshot that would not hold still, a reader
that died, timed out or answered short), having reaped every process it
started; nothing but pipes carries the answer, so no file is left.
"""

from __future__ import annotations

import fcntl
import json
import logging
import os
import selectors
import sqlite3
import subprocess
import sys
import time
from typing import Callable, List, Optional, Sequence, Tuple

log = logging.getLogger("pio.storage")

READER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "sqlite_scan_reader.py")

#: one reader per this many rowids of the window, so that a reader's share
#: of the scan outweighs its start-up (interpreter, pyarrow, connection)
ROWIDS_PER_READER = 250_000
#: on the chip host's 13 CPUs 12 readers read 2 M rows less than a tenth
#: faster than 8 (PERF.md section 6, PR 25: call 1's sweep)
MAX_READERS = 8
#: pins tried before the read goes serial; each costs milliseconds
PIN_ATTEMPTS = 4
#: a reader that has not said "ready" or "pinned" by then is broken
HANDSHAKE_TIMEOUT_S = 30.0

_PIPE_BYTES = 1 << 20


def usable_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:          # not Linux
        return os.cpu_count() or 1


def planned_readers(span: int) -> int:
    """Readers for a window of `span` rowids; 1 means the serial scan."""
    return max(1, min(span // ROWIDS_PER_READER, MAX_READERS, usable_cpus()))


def scan_timeout_s(span: int) -> float:
    """A serial scan reads some 400,000 rows a second; a reader twenty
    times slower than that is not coming back."""
    return 60.0 + span / 20_000.0


def rowid_window(conn: sqlite3.Connection, table: str) -> Tuple[int, int]:
    """[lo, hi) over every rowid of the table. Two statements, so that
    sqlite takes each from the b-tree's edge; MIN and MAX in one
    statement walk the table."""
    lo = conn.execute(f"SELECT MIN(rowid) FROM {table}").fetchone()[0]
    hi = conn.execute(f"SELECT MAX(rowid) FROM {table}").fetchone()[0]
    return (lo or 0), (hi or 0) + 1


def data_version(conn: sqlite3.Connection) -> int:
    """Moves whenever a connection other than `conn` commits."""
    return conn.execute("PRAGMA data_version").fetchone()[0]


# ---------------------------------------------------------------------------
# metrics (OBSERVABILITY.md, ingest inventory)
# ---------------------------------------------------------------------------

def _registry():
    from predictionio_tpu.obs.registry import default_registry

    return default_registry()


def count_readers(n: int) -> None:
    _registry().histogram(
        "pio_ingest_scan_readers",
        "Reader processes a sqlite columnar scan used (1: the serial scan "
        "in the calling process)",
        buckets=(1, 2, 4, 8, 16)).observe(n)


def _fallbacks():
    return _registry().counter(
        "pio_ingest_scan_fallback_total",
        "Fanned-out sqlite scans that fell back to the serial scan: the "
        "snapshot would not hold still, or a reader died, timed out or "
        "answered short", labelnames=("reason",))


def _retries():
    return _registry().counter(
        "pio_ingest_scan_snapshot_retries_total",
        "Times a fanned-out sqlite scan pinned its readers again because "
        "another connection committed between the window and the pins")


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

class ReaderFailed(Exception):
    pass


class _Readers:
    """N reader processes and what they have written so far. Leaving the
    `with` block kills and reaps whatever still runs."""

    def __init__(self, path: str, n: int):
        self.procs: List[subprocess.Popen] = []
        self.bufs = [bytearray() for _ in range(n)]
        self.eof = [False] * n
        self._sel = selectors.DefaultSelector()
        try:
            for i in range(n):
                p = subprocess.Popen(
                    [sys.executable, READER, path], stdin=subprocess.PIPE,
                    stdout=subprocess.PIPE, bufsize=0, close_fds=True)
                self.procs.append(p)
                _widen(p.stdout.fileno())
                self._sel.register(p.stdout, selectors.EVENT_READ, i)
        except BaseException:
            self.close()
            raise

    def __enter__(self) -> "_Readers":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            for f in (p.stdin, p.stdout):
                try:
                    f.close()
                except OSError:
                    pass
            p.wait()
        self._sel.close()

    def tell(self, i: int, msg: dict) -> None:
        try:
            self.procs[i].stdin.write(json.dumps(msg).encode() + b"\n")
        except OSError as ex:
            raise ReaderFailed(f"reader {i} is gone: {ex}") from ex

    def tell_all(self, msg: dict) -> None:
        for i in range(len(self.procs)):
            self.tell(i, msg)

    def wait_until(self, done: Callable[[int], bool], timeout: float,
                   what: str) -> None:
        """Read from whichever reader writes until `done(i)` holds for
        every reader; a reader that ends first, or the deadline, fails
        the scan."""
        pending = {i for i in range(len(self.procs)) if not done(i)}
        for i in pending:
            if self.eof[i]:
                raise ReaderFailed(f"reader {i} ended before {what}")
        deadline = time.monotonic() + timeout
        while pending:
            left = deadline - time.monotonic()
            if left <= 0:
                raise ReaderFailed(f"no {what} from reader(s) "
                                   f"{sorted(pending)} in {timeout:g} s")
            for key, _ in self._sel.select(left):
                i = key.data
                chunk = os.read(key.fd, _PIPE_BYTES)
                if chunk:
                    self.bufs[i] += chunk
                else:
                    self.eof[i] = True
                    self._sel.unregister(key.fileobj)
                if done(i):
                    pending.discard(i)
                elif self.eof[i]:
                    raise ReaderFailed(
                        f"reader {i} ended (exit code "
                        f"{self.procs[i].wait()}) before {what}")

    def expect_line(self, word: bytes, timeout: float) -> None:
        """Every reader's next line is `word`; consume it."""
        self.wait_until(lambda i: b"\n" in self.bufs[i], timeout,
                        repr(word.decode()))
        for i, buf in enumerate(self.bufs):
            line, _, rest = bytes(buf).partition(b"\n")
            if line != word:
                raise ReaderFailed(f"reader {i} said {line[:40]!r}, "
                                   f"not {word!r}")
            self.bufs[i] = bytearray(rest)

    def tables(self, schema, timeout: float):
        """Every reader's answer, in reader order: the header line, that
        many bytes of Arrow IPC of a table of `schema`, the end of its
        output."""
        import pyarrow as pa

        sizes: List[Optional[int]] = [None] * len(self.procs)

        def answered(i: int) -> bool:
            if sizes[i] is None:
                head, nl, _ = bytes(self.bufs[i][:64]).partition(b"\n")
                if not nl:
                    return False
                word, _, n = head.partition(b" ")
                if word != b"table" or not n.isdigit():
                    raise ReaderFailed(f"reader {i} said {head[:40]!r}")
                sizes[i] = len(head) + 1 + int(n)
            return self.eof[i]

        self.wait_until(answered, timeout, "a table")
        out = []
        for i, buf in enumerate(self.bufs):
            if len(buf) != sizes[i]:
                raise ReaderFailed(f"reader {i} answered {len(buf)} bytes "
                                   f"of {sizes[i]}")
            if self.procs[i].wait() != 0:
                raise ReaderFailed(f"reader {i} exited with code "
                                   f"{self.procs[i].returncode}")
            body = memoryview(buf)[buf.index(b"\n") + 1:]
            table = pa.ipc.open_stream(pa.py_buffer(body)).read_all()
            if table.schema != schema:
                raise ReaderFailed(f"reader {i} answered {table.schema}")
            out.append(table)
        return out


def _widen(fd: int) -> None:
    """Ask for a pipe of 1 MiB instead of 64 KiB: a reader's answer is
    megabytes, and every read of it is a system call of the parent."""
    try:
        fcntl.fcntl(fd, fcntl.F_SETPIPE_SZ, _PIPE_BYTES)
    except (AttributeError, OSError):
        pass


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

def fan_out(conn: sqlite3.Connection, path: str,
            window: Callable[[], Tuple[int, int]],
            sql_of: Callable[[Tuple[int, int, Tuple[int, int]]],
                             Tuple[str, Sequence]],
            schema):
    """The rows of `window()` as one arrow table of `schema`, read by
    `planned_readers` processes, or None when the serial scan has to
    answer. `window()` reads the [lo, hi) rowid window on `conn`;
    `sql_of((i, n, (lo, hi)))` is the (sql, params) of sub-window i of n.
    A sqlite3 error of `conn`'s own reads (a missing table) is the
    caller's to name."""
    import pyarrow as pa

    n = planned_readers(_span(window()))
    if n < 2:
        return None
    fallbacks = _fallbacks()
    for reason in ("snapshot", "reader_failed"):
        fallbacks.inc(0, reason=reason)
    columns = [[f.name, str(f.type)] for f in schema]
    try:
        with _Readers(path, n) as readers:
            readers.expect_line(b"ready", HANDSHAKE_TIMEOUT_S)
            for attempt in range(PIN_ATTEMPTS):
                if attempt:
                    _retries().inc()
                before = data_version(conn)
                lo_hi = window()
                readers.tell_all({"op": "pin"})
                readers.expect_line(b"pinned", HANDSHAKE_TIMEOUT_S)
                if data_version(conn) == before:
                    break
            else:
                log.warning("sqlite scan over %d readers falls back to the "
                            "serial scan: the store was committed to "
                            "during each of %d pins", n, PIN_ATTEMPTS)
                fallbacks.inc(reason="snapshot")
                return None
            for i in range(n):
                sql, params = sql_of((i, n, lo_hi))
                readers.tell(i, {"op": "scan", "sql": sql,
                                 "params": list(params),
                                 "columns": columns})
            tables = readers.tables(schema, scan_timeout_s(_span(lo_hi)))
    except (ReaderFailed, OSError, pa.ArrowException) as ex:
        log.warning("sqlite scan over %d readers falls back to the serial "
                    "scan: %s", n, ex)
        fallbacks.inc(reason="reader_failed")
        return None
    count_readers(n)
    return pa.concat_tables(tables)


def _span(lo_hi: Tuple[int, int]) -> int:
    return max(0, lo_hi[1] - lo_hi[0])
