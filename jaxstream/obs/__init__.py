"""Run telemetry: metric streams, health guards, sinks, spans, forensics.

The observability subsystem:
:mod:`.metrics` computes the Williamson invariant ladder *inside* the
jitted segment loop and accumulates it into a small device buffer
fetched once per segment; :mod:`.monitor` watches the fetched stream
for NaN/Inf blowups and CFL breaches with a configurable policy;
:mod:`.sink` writes the run manifest and per-segment records as JSONL
for ``scripts/telemetry_report.py`` (wired through ``Simulation`` by
the ``observability:`` block, off by default — enabling it must not
perturb the state carry, asserted bitwise in tests/test_obs.py).
:mod:`.trace` holds the program's host spans (on the profiler's
timeline and in the always-on ``SPANS`` table) and the per-request span
trees; :mod:`.flight` the always-on event ring and crash bundles;
:mod:`.registry` the ``/v1/metrics`` counters; :mod:`.perf` the cost
stamps and the device-memory watch.
"""

from .flight import (RECORDER, BundleWriter, FlightRecorder,
                     TornBundleError, latest_bundle, read_bundle)
from .metrics import (METRICS, MetricSet, MetricSpec, build_metric_set,
                      default_metrics, fetch_buffer)
from .monitor import GUARD_POLICIES, HealthError, HealthMonitor
from .perf import (CostStamp, MemoryWatcher, build_cost,
                   check_trajectory, load_bench_history, measure_cost)
from .registry import MetricsRegistry, parse_exposition
from .sink import (RECORD_KINDS, TelemetrySink, read_records,
                   validate_record)
from .trace import (RequestTrace, span_coverage, span_tree,
                    trace_id_for, tree_complete)

__all__ = [
    "METRICS", "MetricSet", "MetricSpec", "build_metric_set",
    "default_metrics", "fetch_buffer",
    "RECORDER", "BundleWriter", "FlightRecorder", "TornBundleError",
    "latest_bundle", "read_bundle",
    "GUARD_POLICIES", "HealthError", "HealthMonitor",
    "CostStamp", "MemoryWatcher", "build_cost", "check_trajectory",
    "load_bench_history", "measure_cost",
    "MetricsRegistry", "parse_exposition",
    "RECORD_KINDS", "TelemetrySink", "read_records", "validate_record",
    "RequestTrace", "span_coverage", "span_tree", "trace_id_for",
    "tree_complete",
]
