"""The performance observatory (round 19): per-plan cost stamps, live
device-memory telemetry, and the cross-round perf regression ledger.

Three layers, one module — this is the ONE definition of cost
accounting every surface routes through (``bench.py`` rooflines,
``scripts/perf_probe.py`` / ``scripts/perf_model.py``, the serving
bucket stamps, ``scripts/perf_ledger.py``):

* **Cost stamps** (:class:`CostStamp`): every stepper that carries a
  round-16 proof stamp now carries a ``cost`` stamp next to it
  (:func:`build_cost`, attached by ``jaxstream.plan.proof.
  attach_proof``).  The *analytic* half (hand-counted flops/bytes per
  step from :func:`jaxstream.utils.profiling.analytic_cov_step_cost`)
  is pure arithmetic and always present on dense covariant plans; the
  *measured* half (:func:`measure_cost` — XLA ``cost_analysis`` flops/
  bytes, ``memory_analysis`` footprint bytes, wall-clock compile
  seconds) is filled in wherever a compile actually happens (serve
  bucket warmup under ``serve.cost_stamps``, the bench ``perf``
  section, the probe CLIs).  The measured-vs-analytic flop ratio is
  recorded and a drift beyond :data:`FLOPS_RATIO_BAND` is a loud
  warning — XLA's *byte* count is recorded but never gated: "bytes
  accessed" counts every HLO buffer touch, not HBM traffic (the
  round-1 ~200x roofline lesson), and Pallas custom calls are
  invisible to the flop counter too (``xla_visible=False`` plans skip
  the band check and say so).

* **Live memory telemetry** (:class:`MemoryWatcher`): polls
  ``device.memory_stats()`` at segment boundaries — the same cadence
  as the autoscale tick, ZERO polling when off — into registry gauges
  (``jaxstream_device_memory_bytes_in_use`` / ``_peak_bytes`` /
  ``_limit_bytes`` per chip, scraped at ``/v1/metrics``) and typed
  ``memory`` sink records.  Backends with no allocator stats (CPU)
  degrade to ONE typed-unavailable record, not a crash and not a
  silent nothing.

* **The regression ledger**: :func:`load_bench_history` parses the
  full ``BENCH_r*.json`` archive (the driver envelope ``{"n", "tail",
  "parsed"}`` or a bare bench JSON line) into machine-normalized
  trajectory points — per section: sim-days/sec/chip, % of roof,
  footprint bytes, compile seconds — with the hardware class inferred
  from the recorded ``hardware`` field (new rounds) or the warmup log
  line (historic rounds).  :func:`check_trajectory` gates a candidate
  against the best recorded comparable point (same section, same
  hardware class): a throughput regression beyond the declared band or
  a silently-grown footprint fails the check.  CPU-smoke points are
  tagged ``reported_only`` and never gate — the enforced trajectory is
  the accelerator one.  ``scripts/perf_ledger.py`` is the CLI;
  ``bench.py`` stamps every run (full + ``--smoke``) with the check's
  verdict, asserted by ``tests/test_bench_smoke.py``.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..utils import jax_compat
from ..utils.logging import get_logger

__all__ = [
    "FLOPS_RATIO_BAND", "CostStamp", "build_cost",
    "measure_cost", "plan_analytic_cost", "analytic_cost",
    "roofline_json", "headroom_fraction",
    "MemoryWatcher", "device_memory_record",
    "parse_bench_point", "load_bench_history", "check_trajectory",
    "render_trajectory", "broken_bench_history",
    "write_broken_bench_history",
    "DEFAULT_MAX_REGRESSION", "DEFAULT_MAX_FOOTPRINT_GROWTH",
]

log = get_logger(__name__)

#: Declared band for the XLA-vs-analytic FLOP ratio on plans whose ops
#: XLA can see (classic jnp steppers).  Measured on this image: 1.27
#: (C24) to 1.61 (C8) — XLA counts the halo/seam arithmetic the
#: interior-only analytic model folds away, and the gap shrinks with
#: n.  The band is deliberately wide (the analytic count itself is
#: +-15%); a ratio outside it means one of the two models no longer
#: describes the stepper, which is the drift the stamp exists to
#: catch.
FLOPS_RATIO_BAND = (1.0 / 3.0, 3.0)

#: Ledger gates: a candidate section regressing more than this
#: fraction against the best recorded comparable point fails
#: ``check``; a footprint growing more than this fraction over the
#: smallest recorded comparable footprint fails too (a silently
#: fatter hot path is a regression even at equal throughput — it is
#: exactly what caps the C1536+ ensemble headroom story).
DEFAULT_MAX_REGRESSION = 0.10
DEFAULT_MAX_FOOTPRINT_GROWTH = 0.50

#: Tiers whose per-step arithmetic the covariant analytic model
#: describes (the TT tier's cost is rank-dependent; its stamp says so
#: instead of carrying a wrong number).
_ANALYTIC_TIERS = ("fused", "classic", "face", "face_block", "gspmd",
                  "cartesian_shard")


# --------------------------------------------------------------- stamps
@dataclasses.dataclass
class CostStamp:
    """One built stepper's cost accounting (rides next to its
    :class:`~jaxstream.plan.proof.ProofStamp`).

    ``analytic`` is per STEP (one batched step advances all ensemble
    members — flops and bytes both scale with B, intensity invariant);
    ``xla``/``memory``/``compile_seconds`` describe one compiled
    executable and are filled by :func:`measure_cost` where a compile
    happens (``steps`` tells the ratio check how many analytic steps
    that executable advances per call).  ``memory`` is either the
    ``jax_compat.memory_analysis`` byte dict or ``{"unavailable":
    reason}`` — the typed fallback, never a missing key.
    """
    plan_key: Optional[str] = None
    analytic: Optional[dict] = None      # per-step {"flops","bytes","ai"}
    xla: Optional[dict] = None           # measured {"flops","bytes","steps"}
    memory: dict = dataclasses.field(
        default_factory=lambda: {"unavailable": "not measured"})
    compile_seconds: Optional[float] = None
    flops_ratio: Optional[float] = None  # xla / (analytic * steps)
    bytes_ratio: Optional[float] = None  # recorded, never gated
    in_band: Optional[bool] = None       # None = not checkable
    xla_visible: bool = True             # False: Pallas custom calls

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self):
        mem = (f"{self.memory.get('total_bytes', 0)}B"
               if "total_bytes" in self.memory
               else self.memory.get("unavailable", "?"))
        ratio = ("-" if self.flops_ratio is None
                 else f"{self.flops_ratio:.2f}")
        cs = ("-" if self.compile_seconds is None
              else f"{self.compile_seconds:.2f}s")
        return (f"cost[{self.plan_key or '?'}] mem={mem} "
                f"flops_ratio={ratio} compile={cs}")


def plan_analytic_cost(plan) -> Optional[dict]:
    """Per-step analytic cost of one (duck-typed) capability plan.

    Pure arithmetic — no devices, no tracing — so ``scripts/plan.py
    explain`` can print it statically.  Returns None for tiers the
    covariant stencil model does not describe (TT: cost is
    rank-dependent).  Cartesian-formulation tiers carry the documented
    x1.4 scale of the bench roofline note.
    """
    tier = getattr(plan, "tier", None)
    n = int(getattr(plan, "n", 0) or 0)
    if tier not in _ANALYTIC_TIERS or n <= 0:
        return None
    from ..utils.profiling import analytic_cov_step_cost

    carry = getattr(plan, "carry", "f32")
    nu4 = None
    if getattr(plan, "nu4", False):
        mode = getattr(plan, "nu4_mode", "split")
        nu4 = mode if mode in ("split", "refused") else "split"
    precision = ("bf16" if getattr(plan, "stage", "f32") == "bf16"
                 else None)
    c = analytic_cov_step_cost(
        n, ensemble=max(1, int(getattr(plan, "ensemble", 1) or 1)),
        carry_bytes=(2 if carry in ("bf16", "mixed16") else None),
        nu4=nu4, precision=precision)
    scale = 1.4 if not getattr(plan, "covariant", True) else 1.0
    out = {
        "flops": c["flops"] * scale,
        "bytes": c["bytes"] * scale,
        "ai": c["ai"],
        "basis": ("analytic_cov_step_cost"
                  + ("_x1.4_cartesian" if scale != 1.0 else "")),
    }
    if c.get("bf16_flop_fraction"):
        out["bf16_flop_fraction"] = c["bf16_flop_fraction"]
    return out


def build_cost(plan, plan_key: Optional[str] = None) -> CostStamp:
    """The analytic-only cost stamp every built stepper carries (the
    measured half is filled wherever a compile happens)."""
    backend = str(getattr(plan, "backend", "jnp"))
    return CostStamp(
        plan_key=plan_key,
        analytic=plan_analytic_cost(plan),
        xla_visible=not backend.startswith("pallas"))


def measure_cost(fn, *args, plan_key: Optional[str] = None,
                 analytic: Optional[dict] = None, steps: int = 1,
                 xla_visible: bool = True,
                 stamp: Optional[CostStamp] = None,
                 band=FLOPS_RATIO_BAND, **kwargs) -> CostStamp:
    """Compile ``fn(*args)`` ahead-of-time and stamp what it costs.

    Times the lower+compile wall seconds, reads XLA's own
    ``cost_analysis`` (flops / bytes accessed) and
    ``memory_analysis`` (argument/output/temp/generated-code bytes;
    typed ``{"unavailable": reason}`` on backends that lack it), and
    cross-checks the flop count against ``analytic`` (a per-step dict;
    ``steps`` = how many analytic steps one call of ``fn`` advances).
    A flop ratio outside ``band`` logs a LOUD warning and sets
    ``in_band=False`` — unless ``xla_visible`` is False (Pallas custom
    calls hide their flops from XLA; the check would cry wolf on every
    fused plan).

    NOTE: the AOT compile is a real second compile when ``fn`` is a
    dispatch-cached jit already warmed elsewhere — callers opt in
    (``serve.cost_stamps``) where that matters for wall time.
    """
    import jax

    out = stamp if stamp is not None else CostStamp(plan_key=plan_key)
    if plan_key is not None:
        out.plan_key = plan_key
    if analytic is not None:
        out.analytic = analytic
    out.xla_visible = bool(xla_visible)
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    out.compile_seconds = round(time.perf_counter() - t0, 4)
    try:
        costs = compiled.cost_analysis()
        if isinstance(costs, list):            # older jax: [dict]
            costs = costs[0]
        out.xla = {"flops": float(costs.get("flops", 0.0)),
                   "bytes": float(costs.get("bytes accessed", 0.0)),
                   "steps": int(steps)}
    except Exception as e:
        out.xla = None
        log.warning("cost stamp %s: cost_analysis unavailable (%s: %s)",
                    out.plan_key, type(e).__name__, e)
    try:
        out.memory = jax_compat.memory_analysis(compiled)
    except RuntimeError as e:
        out.memory = {"unavailable": str(e)}
    ana = out.analytic
    if out.xla is not None and ana and ana.get("flops"):
        denom = ana["flops"] * max(1, int(steps))
        out.flops_ratio = round(out.xla["flops"] / denom, 4)
        if ana.get("bytes"):
            out.bytes_ratio = round(
                out.xla["bytes"] / (ana["bytes"] * max(1, int(steps))),
                4)
        if out.xla_visible:
            out.in_band = bool(band[0] <= out.flops_ratio <= band[1])
            if not out.in_band:
                log.warning(
                    "cost stamp %s: XLA/analytic flop ratio %.3f is "
                    "OUTSIDE the declared band [%.2f, %.2f] — the "
                    "analytic cost model no longer describes this "
                    "stepper (or XLA's counter changed); re-derive "
                    "before trusting any roofline built on it",
                    out.plan_key, out.flops_ratio, band[0], band[1])
    return out


def analytic_cost(n: int, **kwargs) -> dict:
    """The ONE analytic cost model, re-exported for the probe CLIs
    (``scripts/perf_probe.py`` / ``scripts/perf_model.py`` route here
    instead of carrying hand-expanded ``137 * 6 * n * n`` constants —
    the round-19 dedupe satellite; knob semantics documented on
    :func:`jaxstream.utils.profiling.analytic_cov_step_cost`)."""
    from ..utils.profiling import analytic_cov_step_cost

    return analytic_cov_step_cost(n, **kwargs)


def roofline_json(steps_per_sec: float, n: int, scale: float = 1.0,
                  bytes_scale: float = 1.0, ensemble: int = 1,
                  carry_bytes: Optional[int] = None,
                  nu4: Optional[str] = None,
                  precision: Optional[str] = None,
                  device_kind: Optional[str] = None) -> Optional[dict]:
    """Roofline numbers for one covariant-stepper rate, as JSON — the
    ONE implementation behind ``bench.py``'s per-variant entries and
    the probe CLIs (round-19 dedupe satellite; the knob semantics are
    documented on ``bench._roofline_json``, which now delegates here).

    ``device_kind`` (default: the first device's) picks the peaks from
    ``utils.profiling.DEVICE_ROOFS``; a CPU run returns None (no
    roofline share) and an unknown kind raises — callers decide how
    loudly to degrade.
    """
    from ..utils.profiling import (Roofline, analytic_cov_step_cost,
                                   device_roofs, mixed_vpu_roof)

    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    roofs = device_roofs(device_kind)
    if roofs is None:
        return None
    c = analytic_cov_step_cost(n, ensemble=ensemble,
                               carry_bytes=carry_bytes, nu4=nu4,
                               precision=precision)
    r = Roofline(c["flops"] * scale, c["bytes"] * scale * bytes_scale,
                 1.0 / steps_per_sec, roofs["vpu"])
    out = {
        "achieved_tflops": round(r.achieved_tflops, 3),
        "pct_of_compute_roof": round(
            100 * r.achieved_tflops / r.roof.peak_tflops, 1),
        "achieved_gbps": round(r.achieved_gbps, 1),
        "pct_of_hbm": round(100 * r.achieved_gbps / r.roof.hbm_gbps, 1),
        "ai": round(r.ai, 3),
    }
    if carry_bytes is not None and carry_bytes != 4:
        out["carry_bytes"] = carry_bytes
    if precision == "bf16":
        mroof = mixed_vpu_roof(c["bf16_flop_fraction"], roofs["vpu"],
                               roofs["vpu_bf16"])
        out["bf16_flop_fraction"] = round(c["bf16_flop_fraction"], 3)
        out["mixed_roof_tflops"] = round(mroof.peak_tflops, 2)
        out["pct_of_mixed_roof"] = round(
            100 * r.achieved_tflops / mroof.peak_tflops, 1)
    return out


# ------------------------------------------------------ memory watcher
def _read_stats(stats: dict, in_use_default: int = 0):
    in_use = int(stats.get("bytes_in_use", in_use_default))
    peak = int(stats.get("peak_bytes_in_use", in_use))
    limit = stats.get("bytes_limit",
                      stats.get("bytes_reservable_limit", 0))
    return in_use, peak, int(limit or 0)


class MemoryWatcher:
    """Per-chip device-memory polling at segment-boundary cadence.

    ``poll()`` reads ``device.memory_stats()`` for every watched
    device and publishes the result three ways: registry gauges
    (``jaxstream_device_memory_bytes_in_use`` / ``_peak_bytes`` /
    ``_limit_bytes``, labeled ``chip``), a typed ``memory`` sink
    record per poll, and ``self.last`` (the in-process snapshot
    ``/v1/stats`` serves).  On backends with no allocator stats the
    FIRST poll emits one typed-unavailable record and every later poll
    is a no-op returning None — the operator view says why there are
    no bars exactly once, and an unavailable watcher costs two
    attribute reads per boundary.

    ``stats_fn`` is injectable (tests feed deterministic fake stats;
    production uses ``jax_compat.device_memory_stats``).  Off == the
    watcher is never constructed — zero polling, sink byte-identical.
    """

    def __init__(self, devices=None, registry=None,
                 sink_write: Optional[Callable] = None,
                 stats_fn: Optional[Callable] = None):
        if devices is None:
            import jax

            devices = jax.devices()
        self.devices = list(devices)
        self.registry = registry
        self._sink_write = sink_write
        self._stats_fn = stats_fn or jax_compat.device_memory_stats
        self.polls = 0
        self.available: Optional[bool] = None   # unknown until polled
        self.last: Optional[dict] = None
        self._unavailable_reported = False
        if registry is not None:
            registry.gauge("jaxstream_device_memory_bytes_in_use",
                           "per-chip device memory currently in use")
            registry.gauge("jaxstream_device_memory_peak_bytes",
                           "per-chip peak device memory in use")
            registry.gauge("jaxstream_device_memory_limit_bytes",
                           "per-chip device memory capacity")

    def poll(self) -> Optional[dict]:
        stats = [self._stats_fn(d) for d in self.devices]
        if all(s is None for s in stats):
            self.available = False
            if self._unavailable_reported:
                return None
            self._unavailable_reported = True
            rec = {
                "kind": "memory", "devices": len(self.devices),
                "bytes_in_use": [], "peak_bytes": [], "limit_bytes": [],
                "unavailable": (
                    "device.memory_stats() returned None for every "
                    "watched device — this backend keeps no per-device "
                    "allocator stats (CPU does not; TPU/GPU do)"),
            }
            self.last = rec
            if self._sink_write is not None:
                self._sink_write(rec)
            return rec
        self.available = True
        self.polls += 1
        in_use, peak, limit = [], [], []
        for s in stats:
            i, p, l = _read_stats(s or {})
            in_use.append(i)
            peak.append(p)
            limit.append(l)
        rec = {"kind": "memory", "devices": len(self.devices),
               "bytes_in_use": in_use, "peak_bytes": peak,
               "limit_bytes": limit}
        self.last = rec
        if self.registry is not None:
            g = self.registry.gauge_set
            for j in range(len(self.devices)):
                g("jaxstream_device_memory_bytes_in_use", in_use[j],
                  chip=str(j))
                g("jaxstream_device_memory_peak_bytes", peak[j],
                  chip=str(j))
                g("jaxstream_device_memory_limit_bytes", limit[j],
                  chip=str(j))
        if self._sink_write is not None:
            self._sink_write(rec)
        return rec

    def limit_bytes(self) -> Optional[int]:
        """Smallest per-device capacity seen (None when unknown) —
        the denominator of the advisory headroom fraction."""
        if not self.last:
            return None
        limits = [v for v in self.last.get("limit_bytes", []) if v]
        return min(limits) if limits else None


def device_memory_record(devices=None, stats_fn=None) -> dict:
    """One-shot device-memory snapshot (the bench ``perf`` section) —
    a throwaway watcher's single poll, always returning a record."""
    w = MemoryWatcher(devices=devices, stats_fn=stats_fn)
    rec = w.poll()
    assert rec is not None           # first poll always reports
    return rec


def headroom_fraction(footprint_bytes: Optional[float],
                      limit_bytes: Optional[float]) -> Optional[float]:
    """Advisory per-device headroom: 1 - footprint/limit.

    ``footprint_bytes`` must be a PER-DEVICE figure — which is what
    ``Compiled.memory_analysis()`` already reports for sharded
    executables (verified on this image: a sharded argument bills each
    device its shard, not the global array), so callers must NOT
    divide by the device count again.  ``None`` when either side is
    unknown (no memory analysis, or a backend with no capacity
    stats).  Advisory THIS round: recorded in the bucket plans,
    placement report and telemetry — no admission behavior change
    (docs/DESIGN.md "Performance observatory").
    """
    if not footprint_bytes or not limit_bytes:
        return None
    return round(1.0 - float(footprint_bytes) / float(limit_bytes), 4)


# -------------------------------------------------------------- ledger
_HW_RE = re.compile(r"\bon (tpu|gpu|cpu)\b")


def _hardware_class(hardware: str) -> str:
    if hardware in ("tpu", "gpu"):
        return "accelerator"
    if hardware == "cpu":
        return "cpu"
    return "unknown"


def parse_bench_point(obj: dict, label: str = "?") -> dict:
    """One BENCH round -> one machine-normalized trajectory point.

    Accepts the driver envelope (``{"n", "cmd", "rc", "tail",
    "parsed"}``) or a bare bench stdout record.  Normalization rules
    (docs/DESIGN.md): the hardware id comes from the record's own
    ``hardware`` field (round 19+) or the warmup log line in the
    envelope tail (historic rounds; ``unknown`` when neither exists);
    smoke records and every non-accelerator point are
    ``reported_only``; section values are sim-days/sec/chip with
    variant entries read from either the round-4 scalar or the
    round-6+ ``{"sim_days_per_sec": ...}`` dict form; zero/suppressed
    entries are dropped (a gate breach is not a trajectory point).
    """
    parsed = obj.get("parsed", obj) if isinstance(obj, dict) else {}
    if not isinstance(parsed, dict):
        parsed = {}
    tail = str(obj.get("tail", "")) if isinstance(obj, dict) else ""
    smoke = bool(parsed.get("smoke"))
    hardware = parsed.get("hardware")
    if not hardware:
        m = _HW_RE.search(tail)
        if m:
            hardware = m.group(1)
        elif not smoke and parsed.get("value"):
            # Historic envelopes (r01-r05) predate the recorded
            # ``hardware`` field, and the driver's tail keeps only the
            # LAST stderr lines — the warmup "on tpu" line survives in
            # some rounds (r01) and scrolls out in others (r05).
            # Normalization rule: a full (non-smoke) bench whose
            # headline gated green IS the driver's accelerator run —
            # the C384 gates cannot complete on CPU in the driver's
            # budget — unless the tail explicitly says otherwise.
            hardware = "tpu"
        else:
            hardware = "unknown"
    hw_class = _hardware_class(hardware)
    point = {
        "label": label,
        "round": obj.get("n") if isinstance(obj, dict) else None,
        "hardware": hardware,
        "hardware_class": hw_class,
        "smoke": smoke,
        "reported_only": smoke or hw_class != "accelerator",
        "sections": {},
        "pct_of_roof": (parsed.get("roofline") or {}).get(
            "pct_of_compute_roof"),
        "footprint_bytes": None,
        "compile_seconds": None,
        "dt60_equivalent": parsed.get("dt60_equivalent"),
    }
    secs = point["sections"]
    value = parsed.get("value")
    if isinstance(value, (int, float)) and value > 0:
        secs["headline"] = float(value)
    for name, v in (parsed.get("variants") or {}).items():
        val = v.get("sim_days_per_sec") if isinstance(v, dict) else v
        if isinstance(val, (int, float)) and val > 0:
            secs[f"variant:{name}"] = float(val)
    ens = parsed.get("ensemble") or {}
    if isinstance(ens, dict):
        for k, v in ens.items():
            if (k.startswith("B") and isinstance(v, dict)
                    and isinstance(v.get("sim_days_per_sec"),
                                   (int, float))
                    and v["sim_days_per_sec"] > 0):
                secs[f"ensemble:{k}"] = float(v["sim_days_per_sec"])
    srv = parsed.get("serving") or {}
    packed = srv.get("packed") if isinstance(srv, dict) else None
    if (isinstance(packed, dict)
            and isinstance(packed.get("agg_sim_days_per_sec_per_chip"),
                           (int, float))):
        secs["serving:packed"] = float(
            packed["agg_sim_days_per_sec_per_chip"])
    # Round 21 (warm pools): the cold_start bench section lands as
    # warm-over-cold SPEEDUP ratios (higher is better, like every
    # other section), so future rounds gate scale-up latency the way
    # throughput is gated today.
    cold = parsed.get("cold_start") or {}
    if isinstance(cold, dict):
        for src, name in (("warm_speedup", "cold_start:warm_speedup"),
                          ("resize_speedup",
                           "cold_start:resize_speedup")):
            val = cold.get(src)
            if isinstance(val, (int, float)) and val > 0:
                secs[name] = float(val)
    perf = parsed.get("perf") or {}
    cost = perf.get("cost") or {}
    mem = cost.get("memory") or {}
    if isinstance(mem.get("total_bytes"), (int, float)):
        point["footprint_bytes"] = int(mem["total_bytes"])
    if isinstance(cost.get("compile_seconds"), (int, float)):
        point["compile_seconds"] = float(cost["compile_seconds"])
    # The stamped stepper rung (cov_fused vs classic): footprints are
    # only comparable within one rung — a Pallas-compile fallback must
    # not be gated against a fused footprint (or vice versa).
    point["rung"] = perf.get("rung")
    return point


def load_bench_history(root: str) -> List[dict]:
    """Every ``BENCH_r*.json`` under ``root``, as trajectory points in
    round order."""
    points = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        with open(path) as fh:
            obj = json.load(fh)
        points.append(parse_bench_point(
            obj, label=os.path.basename(path).rsplit(".", 1)[0]))
    return points


def check_trajectory(points: Sequence[dict],
                     max_regression: float = DEFAULT_MAX_REGRESSION,
                     max_footprint_growth: float =
                     DEFAULT_MAX_FOOTPRINT_GROWTH) -> dict:
    """Gate the LAST point against the best comparable history.

    Comparable = same section name, same hardware class.  An enforced
    candidate additionally requires the historical point to be
    enforced itself (a smoke window must never set the bar); a
    reported-only candidate (CPU smoke) compares against ANY
    same-class point and produces advisories, never failures —
    ``enforced`` says which mode ran, so a CI consumer can distinguish
    "passed" from "nothing to gate".
    """
    if not points:
        raise ValueError("check_trajectory needs at least one point")
    cand = points[-1]
    same_class = [p for p in points[:-1]
                  if p["hardware_class"] == cand["hardware_class"]]
    enforced = not cand["reported_only"]
    # An ENFORCED candidate only gates against enforced history (a
    # smoke window must never set the bar); a reported-only candidate
    # still gets ADVISORIES against any same-class point — a CPU
    # smoke trend that halves should say so, even if it cannot gate.
    prior = ([p for p in same_class if not p["reported_only"]]
             if enforced else same_class)
    regressions, advisories = [], []
    sink = regressions if enforced else advisories
    compared = 0
    for name, val in sorted(cand["sections"].items()):
        best = max((p["sections"][name] for p in prior
                    if name in p["sections"]), default=None)
        if best is None:
            continue
        compared += 1
        floor = best * (1.0 - max_regression)
        if val < floor:
            sink.append({
                "section": name, "value": round(val, 4),
                "best": round(best, 4),
                "change_pct": round(100.0 * (val / best - 1.0), 1),
                "detail": (
                    f"{name}: {val:.4f} sim-days/sec/chip is "
                    f"{100 * (1 - val / best):.1f}% below the best "
                    f"recorded {cand['hardware_class']} point "
                    f"({best:.4f}) — beyond the "
                    f"{100 * max_regression:.0f}% band"),
            })
    fp = cand.get("footprint_bytes")
    # Footprints only compare within one stamped rung: the classic
    # fallback's executable is a structurally different program from
    # the fused one — gating across the rung flip would fail healthy
    # runs (and mask genuinely grown fused footprints).
    prior_fp = [p["footprint_bytes"] for p in prior
                if p.get("footprint_bytes")
                and p.get("rung") == cand.get("rung")]
    if fp and prior_fp:
        compared += 1
        smallest = min(prior_fp)
        if fp > smallest * (1.0 + max_footprint_growth):
            sink.append({
                "section": "footprint", "value": fp,
                "best": smallest,
                "change_pct": round(100.0 * (fp / smallest - 1.0), 1),
                "detail": (
                    f"footprint: {fp} bytes is "
                    f"{100 * (fp / smallest - 1):.0f}% above the "
                    f"smallest recorded comparable footprint "
                    f"({smallest}) — beyond the "
                    f"{100 * max_footprint_growth:.0f}% band (a "
                    f"silently fatter hot path is a regression)"),
            })
    return {
        "ok": not regressions,
        "enforced": enforced,
        #: A green ENFORCED verdict with compared_sections == 0 is a
        #: VACUOUS pass (no comparable history yet — e.g. the first
        #: accelerator run after a new section lands); CI consumers
        #: must read this count, not just ``ok``.
        "compared_sections": compared,
        "points": len(points),
        "candidate": cand["label"],
        "hardware_class": cand["hardware_class"],
        "max_regression_pct": round(100 * max_regression, 1),
        "max_footprint_growth_pct": round(
            100 * max_footprint_growth, 1),
        "regressions": regressions,
        "advisories": advisories,
    }


def render_trajectory(points: Sequence[dict]) -> str:
    """The human trend table (``scripts/perf_ledger.py`` default)."""
    lines = [f"{'round':<10} {'hw':<8} {'mode':<13} {'headline':>9} "
             f"{'dt60':>7} {'%roof':>6} {'footprint':>12} "
             f"{'compile':>8}  sections"]
    for p in points:
        head = p["sections"].get("headline")
        dt60 = p.get("dt60_equivalent")
        roof = p.get("pct_of_roof")
        fp = p.get("footprint_bytes")
        cs = p.get("compile_seconds")
        def cell(v, width, spec):
            return (format(v, spec) if v is not None
                    else format("-", f">{width}"))

        lines.append(
            f"{p['label']:<10} {p['hardware']:<8} "
            f"{'reported-only' if p['reported_only'] else 'enforced':<13} "
            f"{cell(head, 9, '>9.4f')} {cell(dt60, 7, '>7.4f')} "
            f"{cell(roof, 6, '>6.1f')} {cell(fp, 12, '>12d')} "
            f"{cell(cs, 8, '>8.2f')}  {len(p['sections'])}")
        for name in sorted(p["sections"]):
            if name == "headline":
                continue
            lines.append(f"  {'':<8} {name:<28} "
                         f"{p['sections'][name]:>9.4f}")
    return "\n".join(lines)


# ------------------------------------------------- seeded-broken fixture
def broken_bench_history() -> List[dict]:
    """The ledger's regression corpus (``analysis/fixtures.py``
    pattern): a clean accelerator round followed by a candidate with a
    30% throughput regression AND a silently-grown footprint.  The
    check MUST fail on it — tier-1 asserts the gate cannot lose its
    teeth (``perf_regression`` fixture + ``perf_ledger.py check``)."""
    good = {
        "n": 1, "cmd": "fixture", "rc": 0,
        "tail": "bench: warmup 10 steps (incl. compile) 7.6s on tpu",
        "parsed": {
            "metric": "sim_days_per_sec_per_chip_TC5_C384",
            "value": 3.0, "unit": "sim-days/sec/chip",
            "hardware": "tpu",
            "variants": {"mixed16_carry": 3.19},
            "perf": {"cost": {"compile_seconds": 20.0,
                              "memory": {"total_bytes": 1_000_000_000}}},
        },
    }
    bad = {
        "n": 2, "cmd": "fixture", "rc": 0,
        "tail": "bench: warmup 10 steps (incl. compile) 8.1s on tpu",
        "parsed": {
            "metric": "sim_days_per_sec_per_chip_TC5_C384",
            "value": 2.1, "unit": "sim-days/sec/chip",   # -30%
            "hardware": "tpu",
            "variants": {"mixed16_carry": 3.21},
            "perf": {"cost": {"compile_seconds": 21.0,
                              "memory": {"total_bytes": 1_600_000_000}}},
        },
    }
    return [good, bad]


def write_broken_bench_history(dirpath: str) -> List[str]:
    """Materialize the broken corpus as ``BENCH_r*.json`` files (for
    driving ``scripts/perf_ledger.py check`` end to end)."""
    paths = []
    for obj in broken_bench_history():
        p = os.path.join(dirpath, f"BENCH_r{obj['n']:02d}.json")
        with open(p, "w") as fh:
            json.dump(obj, fh)
        paths.append(p)
    return paths
