"""`pio loadtest` — the whole-fleet workload simulator (ROADMAP item 5).

This package drives the subsystems *concurrently*: a
synthetic user population (population.py — Zipfian item popularity,
diurnal arrival curves, lazy per-user session state) emits mixed
traffic — events to the event server, queries through the router,
feedback closing the fold-in loop — in open-loop mode with one
latency-accounting discipline (harness.py), against an
in-process fleet (fleet.py) whose incidents a declarative scenario
file injects (scenario.py), while a runtime invariant engine
(invariants.py) turns the `pio check`-era guarantees into live
assertions: no dropped acks, exactly-once ingest (storage/audit.py),
the release registry converging to one LIVE, freshness holding while
the orchestrator retrains mid-storm.
"""

from predictionio_tpu.loadtest.harness import (  # noqa: F401
    LatencyLedger, OpenLoopResult, drive_open_loop,
)
from predictionio_tpu.loadtest.population import (  # noqa: F401
    Population, ZipfSampler, arrival_offsets, diurnal_rate,
)
from predictionio_tpu.loadtest.scenario import (  # noqa: F401
    Incident, Scenario, ScenarioError, TenantMix,
)
