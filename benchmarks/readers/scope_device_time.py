"""Device time of the traced train by the program's own `jax.named_scope`
names. The compiled train program publishes which HLO instruction belongs
to which scope (the program's `ops/fn_cache` and `obs/profiler`: one JSON
file a compiled program, named by `pio_jax_scope_table_info{family,
module, path}`); the reduced trace holds device seconds by instruction
name; `obs/profiler.scope_seconds` joins the two, here as in `pio
profile`. README.scopes.md is this reader's contract.

The metric file says what is read:
  `scope`       device seconds under that scope, over `per`:
                `program_event` = the tabled program's events in the
                traced window (a step each), `half_sweep` = 2 x
                `shapes.num_iterations`;
  `named_pct`   the tabled program's device time under any scope over all
                of its device time, in %.
`family` keeps the tables of one fn_cache family; without it every table
whose program ran in the traced window counts.

Nothing to read, so None (never an exception): no trace, no table series
(a program from before the tables), a table file that is gone, a program
without `scope_seconds`, no event of a tabled program in the window.
Also None where the join cannot be trusted: the reduced trace sums by
instruction name over the whole traced train and names repeat between
programs (`fusion.3` of the weights' initialiser and of the step), so up
to the device time of the other programs may sit under a wrong scope;
over `MAX_OTHER_SHARE` of the tabled programs' time the reader gives up.
"""

from __future__ import annotations

import json
import re

#: device time of programs without a table over the tabled programs'
MAX_OTHER_SHARE = 0.02
TABLE_INFO = "pio_jax_scope_table_info"


def _tables(evidence: dict, family):
    """The tables the program published, read from their files."""
    tables = []
    for labels, _ in evidence.get("registry_after", {}).get(TABLE_INFO, []):
        if family is not None and labels.get("family") != family:
            continue
        try:
            with open(labels["path"]) as f:
                tables.append(json.load(f))
        except (OSError, ValueError, KeyError):
            return []
    return tables


def _module(event_name: str) -> str:
    """`jit_step(1382380146442985092)` -> `jit_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def joined(evidence: dict, family=None):
    """(device seconds by scope of the tabled programs that ran in the
    traced window, their events there) or None."""
    trace = evidence.get("trace")
    if not trace:
        return None
    try:
        from predictionio_tpu.obs.profiler import scope_seconds
    except ImportError:
        return None
    modules = [(_module(n), d) for n, _, d in trace.get("modules", [])]
    ran = {m for m, _ in modules}
    tables = [t for t in _tables(evidence, family) if t.get("module") in ran]
    if not tables:
        return None
    tabled = {t["module"] for t in tables}
    events = sum(1 for m, _ in modules if m in tabled)
    tabled_s = sum(d for m, d in modules if m in tabled)
    other_s = sum(d for m, d in modules if m not in tabled)
    if not tabled_s or other_s > MAX_OTHER_SHARE * tabled_s:
        return None
    by_family = scope_seconds(
        {name: seconds for name, _, seconds in trace["ops"]}, tables)
    by_scope: dict = {}
    for fam, scopes in by_family.items():
        if fam is None:
            continue
        for scope, seconds in scopes.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    return by_scope, events


def read(evidence: dict, reader: dict):
    got = joined(evidence, reader.get("family"))
    if got is None:
        return None
    by_scope, events = got
    if reader.get("named_pct"):
        total = sum(by_scope.values())
        return 100.0 * (total - by_scope.get("", 0.0)) / total \
            if total else None
    if reader["per"] == "half_sweep":
        iterations = (evidence.get("shapes") or {}).get("num_iterations")
        units = 2.0 * iterations if iterations else 0
    else:
        units = events
    if not units:
        return None
    # a tabled program that ran with nothing under the scope spent no
    # device time there: 0, not nothing
    return by_scope.get(reader["scope"], 0.0) / units \
        * reader.get("scale", 1.0)
