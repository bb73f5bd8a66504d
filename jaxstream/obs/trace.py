"""Request-scoped tracing: one span tree per served scenario request.

The serving stack (rounds 11-14) records *aggregate* stats — occupancy,
host_wait, guard events — but no record can answer the first production
question: **where did request X spend its time?**  This module is the
answer's data model.  Every admitted request gets a deterministic
``trace_id``; its lifecycle phases (queue wait, pack-into-slot,
per-segment device compute, health-stream host wait, boundary work,
finalize wait, d2h result fetch, background-writer flush — plus the
gateway's ingress/egress on network submissions) become typed ``span``
records in the existing :mod:`jaxstream.obs.sink` JSONL stream, and the
spans of one request reassemble into a tree whose LEAF durations sum to
the request's reported end-to-end latency.

Design rules that make the sum property hold *by construction* rather
than by hope:

* **Marks, not paired start/stops.**  A :class:`RequestTrace` is an
  append-only list of ``(phase, timestamp)`` boundary marks; leaf k is
  the interval from mark k to mark k+1 (the last leaf ends at the
  finish timestamp).  Consecutive intervals telescope, so the leaf sum
  IS the root duration up to float rounding — no phase can be dropped
  or double-counted by an unbalanced stop.
* **The root interval is the latency interval.**  The trace starts at
  the same ``perf_counter`` stamp the server writes into
  ``submitted_wall`` and finishes at the instant the result's latency
  is stamped, so root duration == reported ``latency_s`` exactly.
* **Deterministic ids.**  ``trace_id`` is a digest of the request id
  and ``span_id`` a digest of ``(trace_id, name, seq)``, so two runs of
  the same trace produce byte-identical span records once wall-clock
  fields are masked (the replayability contract), and the gateway can
  parent its ingress/egress spans to the root WITHOUT any shared state
  — it recomputes the root span id from the request id alone.

Gateway-side spans (``gateway.ingress`` before admission,
``gateway.egress`` after result encode) sit just outside the server's
root interval; they are why the span-completeness check carries an
epsilon (:data:`EPSILON_ABS_S` + :data:`EPSILON_FRAC`) instead of
demanding exact equality.

A request's sink spans are written only with ``serve.trace`` on, and
never reach the profiler.  ``jax_compat.named_scope`` names *device*
ops only (the compiled ops of a scope carry its name).  Host phases
reach the profiler through :class:`span`, the program's one host-span
primitive: each span opens a ``jax.profiler.TraceAnnotation`` — so a
capture (the benchmark's, or ``POST /v1/profile``) shows the host phase
on the clock of the device ops — and adds its duration to the
process-wide :data:`SPANS` table, which is always on.  The forecast
loop (``sim.*``) and the serving loop (``serve.*``) open spans at each
layer boundary; the request marks reuse the spans' stamps, so there is
one set of timestamps per boundary.

The reassembly helpers need nothing but the standard library; the
stdlib-only ``scripts/`` tools mirror them (they cannot import this
package: ``jaxstream/__init__`` pulls jax).
"""

from __future__ import annotations

import hashlib
import json
import statistics
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

from ..utils import jax_compat

__all__ = [
    "EPSILON_ABS_S", "EPSILON_FRAC", "PHASE_OF", "SPAN_TIMING_KEYS",
    "ROOT", "GATEWAY_INGRESS", "QUEUE_WAIT", "PACK", "SEGMENT",
    "HOST_WAIT", "BOUNDARY", "FINALIZE_WAIT", "RESULT_FETCH",
    "WRITER_FLUSH", "GATEWAY_EGRESS",
    "SIM_RUN", "SIM_DISPATCH", "SIM_SYNC", "SIM_SEGMENT", "SIM_BOUNDARY",
    "SIM_IO", "SIM_COMPILES", "DISPATCH", "IC_BUILD", "IC_BASE",
    "FINALIZE", "REFILL", "INJECT", "INJECT_BYTES", "OBSERVE",
    "RESERVOIR", "SpanTable", "SPANS",
    "span", "record_span",
    "RequestTrace", "trace_id_for", "span_id_for", "root_span_id",
    "terminal_span", "spans_by_request", "span_tree", "leaf_sum_s",
    "tree_complete", "span_coverage", "masked_spans",
]

#: Declared measurement-overhead budget of the span-sum property: the
#: leaf durations of one request's tree must sum to its reported
#: latency within ``EPSILON_ABS_S + EPSILON_FRAC * latency``.  The
#: server-side leaves telescope exactly (see module docstring); the
#: slack covers the gateway's ingress/egress leaves (which sit outside
#: the latency interval) and sub-microsecond rounding of the recorded
#: durations.
EPSILON_ABS_S = 0.05
EPSILON_FRAC = 0.05

# ------------------------------------------------------------ span names
#: The root span: one per request, parent of every leaf.
ROOT = "request"
GATEWAY_INGRESS = "gateway.ingress"   # body decode + admission
QUEUE_WAIT = "queue.wait"             # admitted -> popped into a batch
PACK = "serve.pack"                   # IC build + stack/inject into slot
SEGMENT = "serve.segment"             # one compiled masked segment
HOST_WAIT = "serve.host_wait"         # health-stream d2h residual block
BOUNDARY = "serve.boundary"           # evict/extract/refill boundary work
FINALIZE_WAIT = "finalize.wait"       # queued behind the result writer
RESULT_FETCH = "result.fetch"         # d2h output fetch resolution
WRITER_FLUSH = "writer.flush"         # result build + output-store write
GATEWAY_EGRESS = "gateway.egress"     # result encode + stream handoff

#: leaf span name -> report/dashboard phase bucket.  scripts/
#: telemetry_report.py and scripts/telemetry_dashboard.py carry a
#: literal copy of this table (they must run with no jaxstream import);
#: tests/test_trace.py asserts the copies stay identical.
PHASE_OF: Dict[str, str] = {
    GATEWAY_INGRESS: "ingress",
    QUEUE_WAIT: "queue",
    PACK: "pack",
    SEGMENT: "compute",
    HOST_WAIT: "host_wait",
    BOUNDARY: "boundary",
    FINALIZE_WAIT: "egress",
    RESULT_FETCH: "egress",
    WRITER_FLUSH: "egress",
    GATEWAY_EGRESS: "egress",
}

#: Span-record fields carrying wall-clock time — masked for the
#: byte-determinism comparison of two runs of the same trace.
SPAN_TIMING_KEYS = ("start_s", "duration_s")

# --------------------------------------------------------- program spans
# Forecast loop (``Simulation``).  A segment is its dispatch plus the
# host's wait for it; the boundary is the host time between one
# segment's sync and the next dispatch on the same Simulation, across
# ``run()`` calls.
SIM_RUN = "sim.run"                   # one whole run() call
SIM_DISPATCH = "sim.dispatch"         # the segment function call
SIM_SYNC = "sim.sync"                 # the host's wait for the segment
SIM_SEGMENT = "sim.segment"           # dispatch start -> sync end
SIM_BOUNDARY = "sim.boundary"         # sync end -> next dispatch start
SIM_IO = "sim.io"                     # history / checkpoint work
SIM_COMPILES = "sim.compiles"         # counter: dispatches that compiled
# Serving loop (``EnsembleServer._run_batch``); PACK, HOST_WAIT,
# BOUNDARY, RESULT_FETCH and WRITER_FLUSH above name the same phases.
# BOUNDARY runs from the health fetch's end to the next dispatch and
# holds FINALIZE, REFILL and OBSERVE.
DISPATCH = "serve.dispatch"           # the masked segment's dispatch
IC_BUILD = "serve.ic_build"           # one request's initial state
IC_BASE = "serve.ic_base"             # a family's seed-free IC inputs
FINALIZE = "serve.finalize"           # extract + writer submit
REFILL = "serve.refill"               # late IC builds + inject
INJECT = "serve.inject"               # one member into its slot (refill)
INJECT_BYTES = "serve.inject_bytes"   # counter: host-to-device bytes the
                                      # injects upload (a member's h under
                                      # placement; 0 on one chip, where the
                                      # build uploads it)
OBSERVE = "serve.observe"             # stats, registry, sink, tick, bundle

#: Recent durations the span table keeps per name (for medians).
RESERVOIR = 4096


class _SpanStat:
    __slots__ = ("count", "total_s", "max_s", "recent")

    def __init__(self, reservoir: int):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.recent: deque = deque(maxlen=reservoir)


class SpanTable:
    """Per span name: count, total and longest duration, and the most
    recent ``(duration, attrs)`` pairs (at most ``reservoir``), so
    medians and per-attribute splits can be read; plus named counters.

    Written from any thread (the serving loop, the result writer) under
    one lock; :meth:`summary` returns plain dicts.
    """

    def __init__(self, reservoir: int = RESERVOIR):
        self.reservoir = int(reservoir)
        self._lock = threading.Lock()
        self._spans: Dict[str, _SpanStat] = {}
        self._counters: Dict[str, int] = {}

    def add(self, name: str, seconds: float,
            attrs: Optional[dict] = None) -> None:
        with self._lock:
            st = self._spans.get(name)
            if st is None:
                st = self._spans[name] = _SpanStat(self.reservoir)
            st.count += 1
            st.total_s += seconds
            if seconds > st.max_s:
                st.max_s = seconds
            st.recent.append((seconds, attrs or None))

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def clear(self) -> None:
        """Drop every span and counter (tests; a benchmark run reads
        the table of its own process)."""
        with self._lock:
            self._spans.clear()
            self._counters.clear()

    def summary(self) -> dict:
        """``{"spans": {name: {"count", "total_s", "max_s", "median_s",
        "recent_s", "recent_attrs"}}, "counters": {name: n}}``; the
        median is over the recent durations."""
        with self._lock:
            spans = {name: (st.count, st.total_s, st.max_s,
                            list(st.recent))
                     for name, st in self._spans.items()}
            counters = dict(self._counters)
        out = {}
        for name, (n, total, longest, recent) in spans.items():
            durs = [d for d, _ in recent]
            out[name] = {"count": n, "total_s": total, "max_s": longest,
                         "median_s": statistics.median(durs),
                         "recent_s": durs,
                         "recent_attrs": [a for _, a in recent]}
        return {"spans": out, "counters": counters}


#: The process-wide table every span adds to.  Always on.
SPANS = SpanTable()

# Bound once: a span's own cost is on every boundary of both loops.
_recording = jax_compat.profiler_recording
_clock = time.perf_counter


class span:
    """``with span(name, **attrs) as sp:`` — one program span.

    On entry it opens a profiler annotation (when a session records)
    and stamps ``sp.t0``, unless the caller passes a stamp it already
    took as ``t0``; on exit it stamps ``sp.t1``, sets ``sp.seconds``
    and adds the duration with ``attrs`` to :data:`SPANS`.  A caller
    that learns inside the span that it must stay out of the
    statistics (a segment that compiled) sets ``sp.keep = False``.
    """

    __slots__ = ("name", "attrs", "t0", "t1", "seconds", "keep", "_ann")

    def __init__(self, name: str, t0: Optional[float] = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = t0
        self.t1 = self.seconds = None
        self.keep = True

    def __enter__(self) -> "span":
        self._ann = ann = (
            jax_compat.trace_annotation(self.name, **self.attrs)
            if _recording() else None)
        if ann is not None:
            ann.__enter__()
        if self.t0 is None:
            self.t0 = _clock()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        t1 = self.t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        seconds = self.seconds = t1 - self.t0
        if self.keep:
            SPANS.add(self.name, seconds, self.attrs)
        return False


def record_span(name: str, t0: float, t1: float, **attrs) -> float:
    """A span from two stamps the caller already took: its duration
    goes to :data:`SPANS` (the profiler shows the interval as the gap
    between the spans around it).  Returns the duration."""
    SPANS.add(name, t1 - t0, attrs)
    return t1 - t0


def trace_id_for(request_id: str) -> str:
    """Deterministic 16-hex trace id of one request.

    A pure digest of the request id: byte-stable across runs and
    processes, and recomputable by every layer (gateway, loadgen
    client, report CLI) without plumbing the id through the protocol.
    """
    h = hashlib.sha256(("jaxstream-trace:" + request_id).encode("utf-8"))
    return h.hexdigest()[:16]


def span_id_for(trace_id: str, name: str, seq: int) -> str:
    """Deterministic 12-hex span id (digest of trace/name/ordinal)."""
    h = hashlib.sha256(f"{trace_id}/{name}/{int(seq)}".encode("utf-8"))
    return h.hexdigest()[:12]


def root_span_id(trace_id: str) -> str:
    """The root span's id — seq 0 by convention, so any layer that
    knows the request id can parent spans to the root."""
    return span_id_for(trace_id, ROOT, 0)


def terminal_span(request_id: str, status: str,
                  duration_s: float = 0.0, start_s: float = 0.0) -> dict:
    """A root-only tree for a request that never reached serving —
    typed sheds (``shed_queue_full``/``shed_draining``/
    ``shed_admission``) carry their terminal status here so a trace
    query answers 'what happened to request X' even when the answer is
    'the gateway refused it'."""
    tid = trace_id_for(request_id)
    return {
        "kind": "span", "trace_id": tid, "span_id": root_span_id(tid),
        "parent_id": None, "id": request_id, "name": ROOT, "seq": 0,
        "start_s": round(float(start_s), 6),
        "duration_s": round(float(duration_s), 6), "status": status,
    }


class RequestTrace:
    """One request's lifecycle marks -> its span records.

    Append-only and single-writer by construction: ``mark`` is called
    from the serving thread (queue/pack/segment phases) and then from
    the background writer thread (finalize phases) — the writer only
    takes over after the serving thread queued the finalization, so no
    two threads ever mark concurrently.
    """

    __slots__ = ("request_id", "trace_id", "t0", "marks")

    def __init__(self, request_id: str, t0: Optional[float] = None):
        self.request_id = request_id
        self.trace_id = trace_id_for(request_id)
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        #: (name, timestamp, attrs); the first mark opens queue.wait at
        #: the root start, so the leaves tile the root interval.
        self.marks: List[Tuple[str, float, dict]] = [
            (QUEUE_WAIT, self.t0, {})]

    def mark(self, name: str, t: Optional[float] = None, **attrs):
        """Open phase ``name`` at ``t`` (now), closing the previous one."""
        self.marks.append(
            (name, time.perf_counter() if t is None else float(t), attrs))

    def finish(self, status: str, t_end: Optional[float] = None
               ) -> List[dict]:
        """Close the trace at ``t_end``; returns root + leaf records.

        Leaf k spans ``marks[k] -> marks[k+1]`` (the last leaf ends at
        ``t_end``), so the durations telescope to the root's.  Negative
        intervals (a clock that cannot happen with monotonic marks, but
        a caller bug could produce) are clamped to 0 so a bad mark
        shows up as a missing-time epsilon breach, not a negative bar.
        """
        t_end = time.perf_counter() if t_end is None else float(t_end)
        rid = root_span_id(self.trace_id)
        records = [{
            "kind": "span", "trace_id": self.trace_id, "span_id": rid,
            "parent_id": None, "id": self.request_id, "name": ROOT,
            "seq": 0, "start_s": 0.0,
            "duration_s": round(t_end - self.t0, 6), "status": status,
        }]
        for i, (name, t, attrs) in enumerate(self.marks):
            t_next = (self.marks[i + 1][1] if i + 1 < len(self.marks)
                      else t_end)
            rec = {
                "kind": "span", "trace_id": self.trace_id,
                "span_id": span_id_for(self.trace_id, name, i + 1),
                "parent_id": rid, "id": self.request_id, "name": name,
                "seq": i + 1, "start_s": round(t - self.t0, 6),
                "duration_s": round(max(t_next - t, 0.0), 6),
            }
            rec.update(attrs)
            records.append(rec)
        return records


# ------------------------------------------------------------ reassembly
def spans_by_request(records: Iterable[dict]) -> Dict[str, List[dict]]:
    """Group ``span`` records by request id (sinks may interleave many
    requests and many files — the dashboard tails a fleet)."""
    out: Dict[str, List[dict]] = {}
    for rec in records:
        if rec.get("kind") != "span":
            continue
        out.setdefault(rec["id"], []).append(rec)
    return out


def span_tree(spans: List[dict]) -> dict:
    """One request's spans -> ``{"root": rec|None, "leaves": [recs]}``
    with leaves in ``seq`` order (their wall order)."""
    roots = [s for s in spans if s.get("parent_id") is None]
    leaves = sorted((s for s in spans if s.get("parent_id") is not None),
                    key=lambda s: s.get("seq", 0))
    return {"root": roots[0] if len(roots) == 1 else None,
            "n_roots": len(roots), "leaves": leaves}


def leaf_sum_s(spans: List[dict]) -> float:
    return sum(s["duration_s"] for s in spans
               if s.get("parent_id") is not None)


def tree_complete(spans: List[dict],
                  latency_s: Optional[float] = None
                  ) -> Tuple[bool, str]:
    """Is one request's span set a complete tree?

    Complete means: exactly one root; every leaf parented to it; at
    least one ``serve.segment`` leaf (the request demonstrably ran on
    a device); and — when the reported latency is given — leaf
    durations summing to it within the declared epsilon.  Returns
    ``(ok, reason)`` with a human-readable reason on failure.
    """
    tree = span_tree(spans)
    if tree["root"] is None:
        return False, f"{tree['n_roots']} root spans (need exactly 1)"
    rid = tree["root"]["span_id"]
    bad = [s["span_id"] for s in tree["leaves"]
           if s["parent_id"] != rid]
    if bad:
        return False, f"leaves parented outside the root: {bad}"
    if not any(s["name"] == SEGMENT for s in tree["leaves"]):
        return False, "no serve.segment leaf (request never ran)"
    if latency_s is not None:
        total = leaf_sum_s(spans)
        eps = EPSILON_ABS_S + EPSILON_FRAC * max(latency_s, 0.0)
        if abs(total - latency_s) > eps:
            return False, (f"leaf sum {total:.6f}s vs latency "
                           f"{latency_s:.6f}s exceeds eps {eps:.6f}s")
    return True, "ok"


def span_coverage(records: Iterable[dict],
                  latencies: Dict[str, float]) -> dict:
    """Fleet-level span completeness over one or many sink files.

    ``latencies`` maps request id -> reported end-to-end latency for
    every request that should carry a COMPLETE tree (completed or
    evicted requests; sheds carry a terminal root only and are not
    counted here).  Returns the ``spans_complete`` fraction the loadgen
    harness asserts and the bench ``serving_slo`` section stamps.
    """
    grouped = spans_by_request(records)
    failures = {}
    for req_id, lat in latencies.items():
        ok, why = tree_complete(grouped.get(req_id, []), lat)
        if not ok:
            failures[req_id] = why
    n = len(latencies)
    return {
        "checked": n,
        "complete": n - len(failures),
        "spans_complete": (n - len(failures)) / n if n else 1.0,
        "failures": failures,
    }


def masked_spans(records: Iterable[dict]) -> List[str]:
    """``span`` records as canonical JSON with wall-clock fields zeroed
    — the byte-determinism surface (two runs of one trace must compare
    equal; span ids, names, seqs, buckets and chips are all
    deterministic for a given packing)."""
    out = []
    for rec in records:
        if rec.get("kind") != "span":
            continue
        rec = dict(rec)
        for k in SPAN_TIMING_KEYS:
            if k in rec:
                rec[k] = 0.0
        out.append(json.dumps(rec, sort_keys=True))
    return out
