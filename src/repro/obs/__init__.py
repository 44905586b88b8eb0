"""Round-trace observability layer (`repro.obs`).

Telemetry for the whole round path, strictly additive: phase spans
(monotonic wall times + profiler trace annotations), the online Eq. 2 gap
estimator (``‖ŝ − s‖²`` between the sampled and the full-participation
aggregate, observed per round), a schema-versioned JSONL event stream, and
a stdlib-threaded live metrics endpoint (JSON snapshot + Prometheus text
exposition).  With telemetry off only the spans' profiler annotations run
(no sync, no record) and every result is bit-for-bit unchanged (gated by
tests/test_obs.py).

Entry points: build an :class:`ObsConfig` and hand it to
``repro.sim.driver.run_simulation(obs=...)`` (or ``launch/train.py
--metrics-port/--diag-every/--trace-dir``); hold a :class:`Telemetry`
yourself when you need the endpoint to outlive the run (the CI obs-smoke
does).  See docs/observability.md for the event schema, the endpoint field
table and the trace-dir recipe.
"""

from repro.obs.events import OBS_SCHEMA, EventLog
from repro.obs.gap import GapStats, flat_gap_stats, gap_ratio, tree_gap_stats
from repro.obs.http import MetricsServer, render_prometheus
from repro.obs.log import get_logger
from repro.obs.telemetry import ObsConfig, Telemetry
from repro.obs.trace import PHASES, TraceWindow, span

__all__ = [
    "OBS_SCHEMA", "EventLog",
    "GapStats", "flat_gap_stats", "gap_ratio", "tree_gap_stats",
    "MetricsServer", "render_prometheus",
    "get_logger",
    "ObsConfig", "Telemetry",
    "PHASES", "TraceWindow", "span",
]
