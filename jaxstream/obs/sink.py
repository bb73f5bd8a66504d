"""Structured telemetry sink: run manifest + per-segment JSONL records.

One run = one JSONL file.  Line 1 is the ``manifest`` record (static
run identity: config echo, devices, metric names); each compiled
segment then appends one ``segment`` record (step/time, the sampled
invariants, drift vs step 0, wall seconds and rates), guards append
``guard`` records, and benchmark harnesses append ``bench`` records.
The format is append-only plain JSONL so a crashed run's telemetry
survives to the last flushed line; ``scripts/telemetry_report.py``
turns a file into the drift table / rate timeline / guard-event
summary.

Schema discipline lives in :func:`validate_record` — the tests
round-trip records through a file and validate every line, so a field
rename here fails the tier-1 gate rather than silently breaking the
report CLI.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Iterable, List, Optional

__all__ = ["RECORD_KINDS", "TelemetrySink", "read_records",
           "validate_record", "run_manifest"]

#: kind -> required keys (beyond "kind").  Extra keys are legal — a
#: record may carry more.  Notable optional ``segment`` key (round 9):
#: ``host_wait_s``, the host-side I/O seconds that blocked the next
#: segment's dispatch (fetch resolution is excluded — it overlaps
#: compute under the async pipeline); the async-vs-sync comparison of
#: this column is how the io.async_pipeline overlap is made visible.
RECORD_KINDS: Dict[str, tuple] = {
    "manifest": ("schema_version", "created_unix", "metric_names",
                 "interval", "guards", "config", "devices"),
    "segment": ("step", "t", "steps", "wall_s", "steps_per_sec",
                "sim_days_per_sec_per_chip", "metrics", "drift"),
    "guard": ("event", "step", "t", "value", "policy",
              "last_good_step", "last_good_t"),
    "bench": ("metric", "value", "unit"),
    # One continuous-batching server segment (jaxstream.serve, round
    # 11): slot occupancy of the segment just run (active/B) and the
    # request-queue depth after refill — the columns
    # scripts/telemetry_report.py aggregates into the serving section.
    # Notable optional keys: "completed"/"evicted"/"refilled" per-
    # boundary counts, "member_steps" advanced this segment, "group";
    # round 12 adds "host_wait_s" (residual block on the health-stream
    # HostFetch — the d2h copy overlaps the boundary's host work) and,
    # under multi-chip placement, "placement"/"devices" plus per-
    # member-shard "chip_occupancy"/"chip_utilization" lists (the
    # telemetry_report per-chip columns).  Guard records appended by
    # the server carry "member" and — under placement — "chip".
    "serve": ("bucket", "occupancy", "queue_depth", "wall_s"),
    # One request's outcome at the network gateway (round 14,
    # jaxstream.gateway): completions carry "status" ok/evicted plus
    # "steps_run"/"nsteps"; typed admission sheds carry status
    # "shed_queue_full"/"shed_draining"/"shed_admission" with the
    # protocol "error" code.  telemetry_report aggregates latency
    # percentiles and shed counts from these.
    "gateway": ("id", "status", "latency_s"),
    # One request's CLIENT-side outcome from the load harness (round
    # 14, jaxstream.loadgen): written in trace order by one writer, so
    # two runs of the same trace are byte-comparable once wall-clock
    # fields ("latency_s"/"dispatched_at_s") are masked.  Optional:
    # "http_status", "steps_run", "segments", "error".
    "loadgen": ("id", "ic", "nsteps", "status", "latency_s"),
    # One live bucket-cap resize (round 14, EnsembleServer.resize —
    # the autoscaling policy's applied decisions; "reason" is
    # 'autoscale'/'autoscale_attach'/'manual').
    "autoscale": ("from_bucket", "to_bucket", "queue_depth",
                  "occupancy", "reason"),
    # One request-lifecycle span (round 17, jaxstream.obs.trace —
    # ``serve.trace: true``): the root span (parent_id null) carries
    # the request's terminal "status" and its end-to-end duration;
    # leaf spans tile the root interval (queue wait, pack, per-segment
    # compute/host-wait/boundary, finalize/fetch/flush — segment
    # leaves also carry "bucket"/"plan"/"chip"/"steps").  Span ids are
    # deterministic digests, so two runs of one trace byte-match once
    # the SPAN_TIMING_KEYS wall-clock fields are masked.
    "span": ("trace_id", "span_id", "parent_id", "id", "name",
             "start_s", "duration_s"),
    # One EnKF assimilation cycle (round 18, jaxstream.da): prior/
    # posterior area-RMS ensemble spread and ensemble-mean RMSE vs the
    # hidden truth, plus innovation statistics — the columns
    # telemetry_report's assimilation section and the dashboard's
    # cycle table/spread sparkline render.  "mode" is 'inprocess' or
    # 'gateway' (the round-18 client that cycles THROUGH the HTTP
    # front door).  Optional: "innovation_mean", "ens_mean_drift"
    # (the in-loop device-buffer statistic, in-process mode only),
    # "nobs", "wall_s".  Guard records appended by the DA guards carry
    # event 'spread_collapse' / 'filter_divergence' and a "cycle" key.
    "da": ("cycle", "step", "t", "mode", "spread", "rmse",
           "spread_post", "rmse_post", "innovation_rms"),
    # One device-memory poll (round 19, jaxstream.obs.perf.
    # MemoryWatcher — ``serve.memory_watch``): per-chip
    # bytes-in-use / peak / limit lists at segment-boundary cadence.
    # Backends with no allocator stats (CPU) emit ONE record with
    # empty lists and an "unavailable" reason instead of spamming or
    # vanishing.  The dashboard's memory panel and telemetry_report's
    # memory section render these.
    "memory": ("devices", "bytes_in_use", "peak_bytes", "limit_bytes"),
    # One compiled executable's cost stamp (round 19, jaxstream.obs.
    # perf.CostStamp — ``serve.cost_stamps``): the plan key it
    # implements, wall-clock compile seconds, and the XLA
    # memory_analysis byte dict (or its typed {"unavailable": reason}
    # fallback).  Optional: "bucket"/"group", "analytic"/"xla" cost
    # dicts, "flops_ratio"/"bytes_ratio"/"in_band" (the analytic
    # cross-check), "headroom_frac" (advisory static-footprint-vs-HBM
    # headroom of the bucket's placement).
    "perf": ("plan", "compile_seconds", "memory"),
    # The flight recorder's ring-dump summary (round 20, jaxstream.obs.
    # flight): written ONLY at crash-bundle dump time — never in steady
    # state, which is what keeps every pre-round-20 sink byte-identical
    # with the recorder always on.  Counts of the merged ring: events
    # dumped, threads that contributed sub-rings, events the bounded
    # rings dropped (a truncated timeline says so loudly).
    "flight": ("events", "threads", "dropped"),
    # One crash-bundle announcement (round 20): the bundle id, the
    # bundle directory on disk, and the dump reason (signal name /
    # 'health_error' / the unhandled exception's type).  The pointer
    # scripts/postmortem.py follows from a sink file to the bundle.
    "crash": ("bundle", "path", "reason"),
    # One warm-pool event (round 21, jaxstream.serve.warmpool —
    # ``serve.warm_pool``): every rung decision the degradation ladder
    # takes is typed, never silent.  "event" is 'hit' / 'miss' /
    # 'save' / 'corrupt' (torn entry detected, deleted, recompiled) /
    # 'fallback' (a rung refused — carries "reason"); "rung" is
    # 'aot' / 'stablehlo' / 'compile_cache' / 'cold'; "plan" is the
    # bucket's plan key (null for pool-level events).
    # Optional: "key" (the entry digest), "reason", "bytes".
    "warmpool": ("event", "rung", "plan"),
    # One headroom enforcement decision (round 21): resize() or the
    # speculative compiler refused a bucket whose stamped per-chip
    # footprint breaches ``serve.min_headroom_frac`` — the first
    # consumer of the round-19 advisory headroom_frac ("action" is
    # 'resize_refused' / 'speculate_refused').  Advisory stays
    # advisory for request admission; only scale-up enforces.
    "headroom": ("action", "bucket", "headroom_frac",
                 "min_headroom_frac"),
    # One resume-lineage stamp (round 20): a Simulation/server that
    # restarted from a checkpoint AND found a committed crash bundle
    # records which bundle it descends from and the checkpoint step it
    # resumed at — the lineage postmortem --diff cross-checks when it
    # byte-compares a resumed run against an uninterrupted one.  Only
    # written when a bundle exists, so bundle-less runs stay
    # byte-identical to round 19.
    "resume": ("bundle", "checkpoint_step", "step"),
}

SCHEMA_VERSION = 1


def validate_record(rec: dict) -> dict:
    """Raise ``ValueError`` unless ``rec`` is schema-valid; returns it."""
    kind = rec.get("kind")
    if kind not in RECORD_KINDS:
        raise ValueError(
            f"telemetry record kind {kind!r} unknown; valid: "
            f"{sorted(RECORD_KINDS)}")
    missing = sorted(k for k in RECORD_KINDS[kind] if k not in rec)
    if missing:
        raise ValueError(
            f"telemetry {kind!r} record missing keys {missing}")
    return rec


def run_manifest(metric_names=(), interval: int = 0, guards: str = "off",
                 config: Optional[dict] = None) -> dict:
    """The static run-identity record (line 1 of every sink file)."""
    import jax

    devs = jax.devices()
    return {
        "kind": "manifest",
        "schema_version": SCHEMA_VERSION,
        "created_unix": time.time(),
        "metric_names": list(metric_names),
        "interval": int(interval),
        "guards": guards,
        "config": config or {},
        "devices": {
            "platform": devs[0].platform,
            "count": len(devs),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
        },
        "jax_version": jax.__version__,
    }


class TelemetrySink:
    """JSONL writer for ONE run; validates every record on the way out.

    Flushes per record: telemetry's whole value is surviving the crash
    that truncates the run.  Opening a sink TRUNCATES an existing file
    — one file is one run (two manifests in a file would make the
    report CLI mix two runs' drift anchors); point ``observability.
    sink`` at a fresh path per attempt if you want to keep the old
    record.  Multihost runs should only open a sink on process 0
    (``Simulation`` enforces this).

    Threading: a sink is used from ONE thread at a time.  Under the
    async host pipeline every ``write`` is a queued task on the single
    background writer thread (FIFO with the history/checkpoint tasks),
    so the line order — and therefore the file — is identical to the
    synchronous path's.
    """

    def __init__(self, path: str, manifest: dict):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._fh = open(path, "w", buffering=1)
        self.n_written = 0
        self.write(manifest)

    def write(self, rec: dict) -> dict:
        validate_record(rec)
        self._fh.write(json.dumps(rec) + "\n")
        self.n_written += 1
        return rec

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_records(path: str, kind: Optional[str] = None) -> List[dict]:
    """Parse a sink file back; optionally filter to one record kind."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = validate_record(json.loads(line))
            if kind is None or rec["kind"] == kind:
                out.append(rec)
    return out
