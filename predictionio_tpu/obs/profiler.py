"""On-demand device profiling: bounded jax.profiler captures, the host's
time around each compile family's dispatches, and device time by the
program's own ``jax.named_scope`` names.

"Which compiled family is eating the TPU" must be answerable in
production without redeploying instrumented code. Three mechanisms:

* **dispatch attribution** — ``ops.fn_cache`` wraps every cached
  compiled function so the host's wall time around each Python call of
  it lands in ``pio_device_dispatch_seconds_total{family}``. JAX
  returns from a dispatch before the device has run the program and
  nothing here waits for it, so this is what the calling thread spent
  dispatching (tracing and compiling on a first call, enqueueing
  after), not device time and not utilization; device time is the
  capture's ``scopes`` below. Always cheap (one perf_counter pair + a
  counter add per dispatch); ``PIO_DISPATCH_ATTRIBUTION=0`` disables
  the timing.

* **bounded trace capture** — :func:`capture` runs ``jax.profiler``
  (Python tracer off: the host plane holds the program's ``pio:<span>``
  annotations beside the runtime's events, on the device lines' time
  base) for a capped duration and returns the trace directory, exposed
  as ``POST /debug/profile`` on the query server and ``pio profile``.
  One capture at a time (a second request gets a busy error), duration
  clamped to :data:`MAX_CAPTURE_S` — an operator can never wedge a
  serving box with an unbounded profile.

* **scope tables** — a device operation in a capture carries its HLO
  instruction's text and not the ``jax.named_scope`` it was traced
  under; the compiled program knows both. A family that names its
  scopes (``fn_cache.mesh_cached_fn(..., scopes=...)``) publishes, once
  per compiled program, the table *instruction -> scope*
  (:func:`parse_scope_table`, :func:`publish_scope_table`), and
  :func:`scope_seconds` joins device seconds by instruction with it.
  :func:`capture` returns the join of the capture it took as
  ``scopes``; the benchmark's reader calls the same function.
"""

from __future__ import annotations

import bisect
import glob
import hashlib
import json
import os
import re
import tempfile
import threading
import time
from typing import (
    Dict, Hashable, Iterable, List, NamedTuple, Optional, Sequence, Tuple,
)

from predictionio_tpu.obs.registry import MetricsRegistry, default_registry

DISPATCH_ENV = "PIO_DISPATCH_ATTRIBUTION"
DISPATCH_COUNTER = "pio_device_dispatch_seconds_total"

MAX_CAPTURE_S = 60.0

SCOPE_TABLE_INFO = "pio_jax_scope_table_info"
SCOPE_TABLE_SECONDS = "pio_jax_scope_table_seconds_total"
#: the lines of a device plane a capture's join reads
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: operations that span the operations of the computations they call:
#: their bodies are counted under their own names
CONTAINER_OPCODES = ("while", "conditional", "call")
#: instructions that never run as an operation of their own
_NO_EVENT_OPCODES = ("parameter", "constant", "get-tuple-element", "tuple",
                     "bitcast")

_capture_lock = threading.Lock()
#: {path of the table's file: table} of this process's compiled programs
#: that named their scopes, the newest `MAX_TABLES_PER_FAMILY` a family
#: (`fn_cache.MAX_PER_FAMILY`: a server retraining on growing data
#: compiles a new program each time)
_scope_tables: Dict[str, dict] = {}
MAX_TABLES_PER_FAMILY = 8
_scope_lock = threading.Lock()


def dispatch_attribution_enabled() -> bool:
    return os.environ.get(DISPATCH_ENV, "1").strip().lower() not in (
        "0", "false", "no", "off")


def dispatch_counter(registry: Optional[MetricsRegistry] = None):
    """The family-labelled device-dispatch seconds counter."""
    return (registry or default_registry()).counter(
        DISPATCH_COUNTER,
        "Host wall seconds around the Python calls of compiled functions, "
        "per fn_cache family: nothing is waited for, so neither device "
        "time nor utilization (device time by scope: `pio profile`)",
        labelnames=("family",))


def dispatch_table(registry: Optional[MetricsRegistry] = None
                   ) -> Dict[str, float]:
    """Seconds per family, highest first — the \"who is eating the
    device\" answer."""
    metric = (registry or default_registry()).get(DISPATCH_COUNTER)
    if metric is None:
        return {}
    table = {labels.get("family", "?"): value
             for labels, value in metric.samples()}
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


# -- scope tables: HLO instruction -> jax.named_scope -------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")
#: the computations an instruction runs as operations of their own (a
#: fusion's `calls` is its inside, a reduction's `to_apply` a scalar rule)
_RUNS = re.compile(r"\b(?:body|condition|calls|true_computation|"
                   r"false_computation)=%?([^\s,)}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
#: an instruction named where another reads it (not `calls=%..`)
_OPERAND = re.compile(r"(?<![=\w])%([^\s,(){}]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([^\s=]+)")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")


def op_key(hlo: str) -> str:
    """What names an instruction in a table and an operation in a
    capture: the instruction's name, and for a custom call the name
    followed by ``_`` and its target (``flash_attention_pallas_bwd.11
    _tpu_custom_call``). An "XLA Ops" event's name is the instruction's
    whole text, a line of the executable's text is the same text: both
    go through here."""
    m = _NAME.match(hlo)
    name = m.group(1) if m else hlo[:60]
    t = _TARGET.search(hlo)
    if t and t.group(1) not in name:
        name = f"{name}_{t.group(1)}"
    return name


def _scope_of(op_name: str, scopes: frozenset) -> str:
    """The innermost of the family's scopes on an instruction's
    ``op_name`` path, whatever wraps it (``jit(..)``, ``jvp(..)``,
    ``transpose(..)``, ``checkpoint``, ``rematted_computation``)."""
    for word in reversed(_WORD.findall(op_name)):
        if word in scopes:
            return word
    return ""


class _Instruction(NamedTuple):
    key: str                       # `op_key` of the line
    name: str                      # the instruction's own name
    opcode: str
    op_name: Optional[str]         # its metadata's path, if any
    operands: List[str]            # the instructions it reads
    fused: Optional[str]           # a fusion's inside
    runs: List[str]                # the computations it runs as operations


def _computations(hlo_text: str) -> Tuple[str, str, Dict[str, List[str]]]:
    """-> (the module's name, the entry's, {computation: its lines})."""
    module = entry = ""
    computations: Dict[str, List[str]] = {}
    lines: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        if lines is not None:
            if line.startswith("}"):
                lines = None
            else:
                lines.append(line)
        elif line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line.endswith("{"):
            m = _COMPUTATION.match(line)
            if m:
                lines = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
    return module, entry, computations


def _instructions(lines: List[str]) -> List[_Instruction]:
    rows = []
    for line in lines:
        # a kernel's call carries its body as text, a hundred times the
        # rest of the line; nothing read here lies behind it
        cut = line.find(", backend_config=")
        if cut >= 0:
            line = line[:cut]
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        opcode = _OPCODE.search(rest)
        opcode = opcode.group(1) if opcode else ""
        meta = _OP_NAME.search(rest)
        calls = _CALLS.search(rest) if opcode == "fusion" else None
        runs = [] if opcode == "fusion" else _RUNS.findall(rest) + [
            name.strip().lstrip("%") for group in _BRANCHES.findall(rest)
            for name in group.split(",")]
        rows.append(_Instruction(
            op_key(line), m.group(1), opcode,
            meta.group(1) if meta else None, _OPERAND.findall(rest),
            calls.group(1) if calls else None, runs))
    return rows


def parse_scope_table(hlo_text: str, scopes: Iterable[str]
                      ) -> Tuple[str, Dict[str, List[str]]]:
    """An executable's text (``compiled.as_text()``) -> (the HLO module's
    name, ``{instruction key: [scope, flags]}``) for every instruction
    of every computation whose instructions run as operations of their
    own: the entry, loop bodies and conditions, branches and called
    computations, not the insides of fusions nor a reduction's rule.

    ``scope`` is the innermost of `scopes` on the instruction's
    ``op_name`` path, ``""`` where there is none. An instruction the
    compiler made and the program's trace did not (no ``op_name``, or
    one that is no path of the program: a layout's ``copy``, XLA's
    ``ragged-dot-none`` kernel call for a ``lax.ragged_dot``) inherits:
    the scope its users in its computation agree on, else the one its
    operands agree on, else none.

    ``flags`` holds ``t`` where the path holds ``transpose(`` (the
    backward pass), ``r`` where it holds ``rematted_computation`` (the
    forward pass repeated under ``remat``), ``m`` for a fusion whose
    fused instructions disagree on the scope (it is attributed whole by
    its own metadata), ``i`` where scope and phase were inherited and
    ``c`` for a container (`CONTAINER_OPCODES`)."""
    names = frozenset(scopes)
    module, entry, computations = _computations(hlo_text)
    # only what runs is read in full; of a fusion's inside, most of the
    # text, only the scopes its instructions name
    reached: Dict[str, List[_Instruction]] = {}
    queue = [entry]
    while queue:
        name = queue.pop()
        if name in reached or name not in computations:
            continue
        reached[name] = _instructions(computations[name])
        queue.extend(run for ins in reached[name] for run in ins.runs)

    def scopes_inside(fused: str) -> set:
        paths = (_OP_NAME.search(line) for line in computations.get(fused, ()))
        return {_scope_of(m.group(1), names) for m in paths if m}

    table: Dict[str, List[str]] = {}
    for rows in reached.values():
        #: instruction -> (scope, phase flags), by its own metadata
        found: Dict[str, Tuple[str, str]] = {}
        made_by_compiler = []
        for ins in rows:
            path = ins.op_name or ""
            if path.startswith("jit("):
                found[ins.name] = (
                    _scope_of(path, names),
                    ("t" if "transpose(" in path else "")
                    + ("r" if "rematted_computation" in path else ""))
            elif ins.opcode not in _NO_EVENT_OPCODES:
                made_by_compiler.append(ins)
        users: Dict[str, List[str]] = {}
        for ins in rows:
            for operand in ins.operands:
                users.setdefault(operand, []).append(ins.name)
        operands = {ins.name: ins.operands for ins in made_by_compiler}
        inherited = set()
        # users first and last to first, so that a chain of copies takes
        # what its end feeds; then operands, first to last
        for neighbours, order in ((users, reversed(made_by_compiler)),
                                  (operands, made_by_compiler)):
            for ins in order:
                if found.get(ins.name, ("",))[0]:
                    continue
                near = {found[n] for n in neighbours.get(ins.name, ())
                        if found.get(n, ("",))[0]}
                if len({scope for scope, _ in near}) == 1:
                    # the phase too, where the neighbours agree on it
                    found[ins.name] = near.pop() if len(near) == 1 \
                        else (near.pop()[0], "")
                    inherited.add(ins.name)
        for ins in rows:
            if ins.opcode in _NO_EVENT_OPCODES:
                continue
            scope, flags = found.get(ins.name, ("", ""))
            if ins.fused is not None and len(scopes_inside(ins.fused)) > 1:
                flags += "m"
            if ins.name in inherited:
                flags += "i"
            if ins.opcode in CONTAINER_OPCODES:
                flags += "c"
            table[ins.key] = [scope, flags]
    return module, table


def scope_table_path(family: str, key: Hashable) -> str:
    """The one file of a family's compiled program under a key: a fixed
    name under the compile cache's directory, so a recompile overwrites
    it and nothing accumulates."""
    from predictionio_tpu.utils.device import compile_cache_dir

    digest = hashlib.sha256(repr(key).encode()).hexdigest()[:16]
    return os.path.join(compile_cache_dir(), "scope_tables",
                        f"{family}-{digest}.json")


def publish_scope_table(family: str, key: Hashable, scopes: Sequence[str],
                        hlo_text: str, seconds: Dict[str, float]) -> dict:
    """Parse a compiled program's text into its table, keep it for this
    process's captures, write it as one JSON file and publish
    ``pio_jax_scope_table_info{family, module, path}`` = instructions
    and ``pio_jax_scope_table_seconds_total{family}`` (`seconds`, what
    the caller spent getting the text, plus the parse and the write;
    the file holds them too, all but its own write)."""
    t0 = time.perf_counter()
    module, instructions = parse_scope_table(hlo_text, scopes)
    path = scope_table_path(family, key)
    table = {"family": family, "module": module, "scopes": list(scopes),
             "path": path,
             "seconds": {**seconds, "parse": time.perf_counter() - t0},
             "instructions": instructions}
    t0 = time.perf_counter()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(table, f, separators=(",", ":"))
    os.replace(tmp, path)
    table["seconds"]["write"] = time.perf_counter() - t0
    with _scope_lock:
        _scope_tables.pop(path, None)
        _scope_tables[path] = table
        # as many as fn_cache keeps compiled programs of a family
        mine = [p for p, t in _scope_tables.items() if t["family"] == family]
        for old in mine[:-MAX_TABLES_PER_FAMILY]:
            del _scope_tables[old]
    reg = default_registry()
    reg.gauge(SCOPE_TABLE_INFO,
              "Instructions in the scope table (HLO instruction -> "
              "jax.named_scope) of a compiled program; `path` is the "
              "table's JSON file",
              labelnames=("family", "module", "path")).set(
        len(instructions), family=family, module=module, path=path)
    reg.counter(SCOPE_TABLE_SECONDS,
                "Seconds spent making scope tables (the executable's "
                "text, its parse, the file), per fn_cache family",
                labelnames=("family",)).inc(
        sum(table["seconds"].values()), family=family)
    return table


def scope_tables() -> List[dict]:
    """The tables of this process's compiled programs, oldest first."""
    with _scope_lock:
        return list(_scope_tables.values())


def scope_seconds(op_seconds: Dict[str, float], tables: Sequence[dict]
                  ) -> Dict[Optional[str], Dict[Optional[str], float]]:
    """Device seconds by instruction (`op_key` names) and the tables of
    the programs that ran -> ``{family: {scope: seconds}}``: ``""``
    holds the instructions of a tabled program under none of its scopes,
    and ``{None: {None: seconds}}`` the operations no table knows (other
    programs, transfers). Containers are skipped: their bodies count
    under their own names. A name two tables know goes to the first."""
    out: Dict[Optional[str], Dict[Optional[str], float]] = {
        t["family"]: {} for t in tables}
    for name, seconds in op_seconds.items():
        for t in tables:
            row = t["instructions"].get(name)
            if row is not None:
                if "c" not in row[1]:
                    by_scope = out[t["family"]]
                    by_scope[row[0]] = by_scope.get(row[0], 0.0) + seconds
                break
        else:
            unknown = out.setdefault(None, {})
            unknown[None] = unknown.get(None, 0.0) + seconds
    return out


def capture_ops(trace_dir: str) -> Tuple[
        Dict[Optional[str], Dict[str, float]], Dict[str, List[float]]]:
    """A capture's device operations by the program they ran inside:
    ({module or None: {`op_key`: seconds}}, {module: [events, seconds]}
    of the programs themselves), summed over the device planes. Each
    "XLA Ops" event goes to the "XLA Modules" event that encloses its
    start (`jit_step(<hash>)` -> `jit_step`), None outside every
    program."""
    from jax.profiler import ProfileData

    by_module: Dict[Optional[str], Dict[str, float]] = {}
    programs: Dict[str, List[float]] = {}
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return by_module, programs
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns,
             re.sub(r"\(\d+\)$", "", e.name))
            for e in (lines[MODULES_LINE].events
                      if MODULES_LINE in lines else ()))
        for lo, hi, name in modules:
            total = programs.setdefault(name, [0, 0.0])
            total[0] += 1
            total[1] += (hi - lo) * 1e-9
        starts = [m[0] for m in modules]
        for e in lines[OPS_LINE].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            inside = modules[i][2] if i >= 0 \
                and e.start_ns < modules[i][1] else None
            ops = by_module.setdefault(inside, {})
            key = op_key(e.name)
            ops[key] = ops.get(key, 0.0) + e.duration_ns * 1e-9
    return by_module, programs


def capture_scopes(trace_dir: str, tables: Sequence[dict]) -> dict:
    """Device seconds by family and scope of a capture; an operation
    joins the tables of the program it ran inside (`capture_ops`), so a
    name two programs share is told apart here.
    -> {"scopes": {family: {scope: seconds}}, "untabled_s": seconds of
    operations no table knows}."""
    scopes: Dict[str, Dict[str, float]] = {t["family"]: {} for t in tables}
    untabled = 0.0
    by_module = capture_ops(trace_dir)[0] if tables else {}
    for module, ops in by_module.items():
        joined = scope_seconds(
            ops, [t for t in tables if t["module"] == module])
        untabled += joined.pop(None, {}).get(None, 0.0)
        for family, by_scope in joined.items():
            for scope, seconds in by_scope.items():
                scopes[family][scope] = \
                    scopes[family].get(scope, 0.0) + seconds
    return {"scopes": scopes, "untabled_s": untabled}


class ProfileBusy(Exception):
    """A capture is already running; exactly one at a time."""


def capture(seconds: float, outdir: Optional[str] = None) -> dict:
    """Run a bounded jax.profiler trace; returns {traceDir, seconds,
    dispatch, scopes, untabledSeconds}: the dispatch table (host seconds
    around each family's calls) rides along, and `scopes` is the
    capture's device seconds by family and ``jax.named_scope`` for the
    programs that published a scope table (``""`` = under no scope);
    `untabledSeconds` the device seconds of every other operation.

    Raises :class:`ProfileBusy` when a capture is in flight and
    RuntimeError when jax's profiler is unavailable. The sleep happens
    INSIDE the trace window — callers run this off the event loop."""
    seconds = min(max(0.01, float(seconds)), MAX_CAPTURE_S)
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusy("a profile capture is already running")
    try:
        import jax

        trace_dir = outdir or tempfile.mkdtemp(prefix="pio-profile-")
        t0 = time.perf_counter()
        # the Python tracer would write an event per Python call of the
        # host loop and bury the program's own `pio:<span>` annotations
        # (obs/tracing.span), which are what names the host plane
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        seconds = round(time.perf_counter() - t0, 3)
        joined = capture_scopes(trace_dir, scope_tables())
        return {
            "traceDir": trace_dir,
            "seconds": seconds,
            "dispatch": dispatch_table(),
            "scopes": joined["scopes"],
            "untabledSeconds": joined["untabled_s"],
        }
    except ImportError as e:
        raise RuntimeError(f"jax profiler unavailable: {e}") from e
    finally:
        _capture_lock.release()
