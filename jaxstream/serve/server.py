"""The continuous-batching ensemble server.

One :class:`EnsembleServer` deployment fixes the grid, dt and physics
(the ``grid:``/``time:``/``physics:``/``model:`` config sections) and
serves :class:`ScenarioRequest` traffic — IC family, perturbation
seed, run length, output subset — by packing requests into the member
axis of the round-7 batched steppers:

* **Shape-bucketed batching**: batch sizes come from a fixed bucket
  set (``serve.buckets``, default ``1,4,16``) and every bucket's
  masked-segment executable is compiled once and kept warm
  (the persistent compile cache keeps even that across restarts), so
  steady-state serving triggers ZERO recompiles —
  :meth:`EnsembleServer.compile_count` is the proof surface the tests
  assert on.
* **Per-member run-length masking** (:func:`jaxstream.stepping.
  integrate_masked`): requests of any length share a batch; a member
  that finishes mid-segment is frozen bit-for-bit at its own final
  step and its slot is refilled from the queue at the next segment
  boundary instead of idling until the slowest member drains.
* **Slot-refill invariant**: refills happen ONLY at segment boundaries
  — injections are ``dynamic_update_slice`` on the member axis of the
  live carry, so the carry layout (and therefore the compiled
  executable) never changes (docs/DESIGN.md "Continuous batching").
* **Mixed-orography batches** (round 12, the default): the TC5
  mountain rides the batch as a *traced* per-member field — zeros for
  the flat families — so tc2/tc5/tc6/galewsky requests pack into ONE
  bucket in strict queue FIFO order, bitwise-equal to the round-11
  baked-static stepper (tested).  ``serve.group_by_orography: true``
  restores the round-11 batching groups (orography a stepper static,
  group-local FIFO, fused member-fold kernels where they compile).
* **Health-guarded eviction**: a per-member nonfinite count rides the
  compiled segment; a failing member is evicted alone (guard event
  carries the member index — and its chip, under placement) while the
  rest of the batch keeps integrating, and admission control refuses
  NEW traffic once ``serve.max_guard_events`` trips have accumulated.
* **Async result streaming**: per-member extraction starts its
  device->host copies behind the next segment's dispatch
  (:class:`jaxstream.io.async_pipeline.HostFetch`) and lands on the
  bounded :class:`...BackgroundWriter` — results never stall the
  batch.  The health stream itself rides a :class:`HostFetch` too:
  while its d2h copy chases the segment's compute, the host
  pre-builds the incoming requests' initial states for the slots it
  already knows will free (completion is host arithmetic on ``rem``),
  and the residual block is recorded as ``host_wait_s`` in the serve
  sink records.

**Multi-chip serving** (round 12, ``serve.placement:``): one server
process drives a whole mesh.  ``mode: member`` shards the packed
member axis across a 1-D ``('member',)`` device mesh (zero wire
traffic; a B=16 bucket on 8 chips runs 2 members/chip).  With
``backend: jnp`` the SAME masked-segment program is compiled under
member-axis ``in_shardings`` (GSPMD partitions the vmapped stepper),
with slot refill a sharding-preserving ``dynamic_update_slice`` whose
incoming IC is ``device_put`` onto the mesh per refill.  A grouped
Pallas server runs the fused member fold per chip instead
(:class:`_PlacedBucket`: a ``shard_map`` of the local-batch fused
stepper, with per-chip stack, inject and extract and no collective
anywhere).  ``mode: panel`` spreads each
request's six faces over the 2-D ``('panel', 'member')`` mesh through
:func:`jaxstream.parallel.shard_cov.make_sharded_cov_ensemble_stepper`
(the PR-3 batched exchange — one ppermute per schedule stage carries
all members' strips — composing with the PR-1 overlap phase split).
Placement off is byte-for-byte the single-chip round-11 path.
"""

from __future__ import annotations

import copy
import dataclasses
import os
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..config import Config, load_config
from ..geometry.cubed_sphere import build_grid
from ..io.async_pipeline import BackgroundWriter, HostFetch
from ..obs import flight
from ..obs import perf as obs_perf
from ..obs import trace as obs_trace
from ..obs.monitor import HealthMonitor
from ..obs.registry import (HOST_WAIT_BUCKETS_S, LATENCY_BUCKETS_S,
                            WALL_BUCKETS_S, MetricsRegistry)
from ..obs.sink import TelemetrySink, run_manifest
from ..parallel.mesh import (available_devices, member_shard_map,
                             member_spec, setup_ensemble_sharding)
from ..physics import initial_conditions as ics
from ..stepping import SCHEMES, integrate_masked, vmap_ensemble
from ..utils import jax_compat
from ..utils.logging import get_logger
from .placement import PLACEMENT_MODES, BucketPlan, plan_placement
from . import warmpool as _warmpool
from .warmpool import HeadroomRefused
from ..plan import rules as _plan_rules
from ..plan.plan import serves_fused
from ..plan.rules import RULES_VERSION as _PLAN_RULES_VERSION
from .queue import (AdmissionRefused, QueueFull, RequestQueue,
                    ServerDraining)
from .request import SWE_FAMILIES, RequestResult, ScenarioRequest

__all__ = ["EnsembleServer", "serve_requests"]

log = get_logger(__name__)

#: Thread name of the server's background result writer.
SERVE_WRITER_THREAD_NAME = "jaxstream-serve-writer"


def _member_nonfinite(y, axes):
    """Per-member nonfinite count over the prognostic carry leaves:
    ``(B,)``.

    The on-device health stream of the serving loop — one small vector
    per segment, fetched at the boundary the refill already pays for.
    Under a placement mesh this is a plain GSPMD reduction: the
    reduced axes are unsharded, so each member's count is computed
    entirely on the chip(s) that hold it and only the tiny ``(B,)``
    result crosses the wire.
    """
    total = None
    for k, ax in axes.items():
        a = y[k]
        bad = jnp.sum((~jnp.isfinite(a)).astype(jnp.int32),
                      axis=tuple(i for i in range(a.ndim) if i != ax))
        total = bad if total is None else total + bad
    return total


#: Member-axis position per leaf of the fused bucket's compact carry
#: (``models.shallow_water_cov.ENSEMBLE_CARRY_AXES``).
_FUSED_AXES = {"h": 0, "u": 1, "strips_sn": 0, "strips_we": 0}


def _take_member(y, idx, axes):
    """Member ``idx``'s interior state out of a batched carry."""
    return {k: jnp.take(y[k], idx, axis=axes[k]) for k in ("h", "u")}


def _with_member(y, idx, member, axes):
    """The batched carry with slot ``idx`` replaced by ``member``."""
    out = dict(y)
    for k, ax in axes.items():
        upd = jnp.expand_dims(member[k].astype(y[k].dtype), ax)
        out[k] = jax.lax.dynamic_update_slice_in_dim(
            y[k], upd, idx, axis=ax)
    return out


class _Slot:
    """One member slot's host bookkeeping."""

    def __init__(self, req: ScenarioRequest, chip: int = 0):
        self.req = req
        self.chip = chip                    # member shard holding it
        self.done = 0                       # steps executed so far

    @property
    def remaining(self) -> int:
        return self.req.nsteps - self.done


class _Bucket:
    """One (group, B) compiled runtime: segment/extract/inject jits.

    ``plan`` is the bucket's :class:`...placement.BucketPlan`;
    ``mesh``/``carry_sh``/``rep_sh`` are set when the plan is sharded
    (``stack``/``put_member``/``put_rem`` then pin their outputs to the
    mesh so every steady-state call hits the same executable)."""

    #: Whether request states reach this bucket as host arrays (its
    #: inject uploads them) rather than uploaded by their build.
    host_leaves = False

    def __init__(self, group: str, B: int, seg_fn, extract_fn, inject_fn,
                 axes, stack, member_carry, plan: BucketPlan,
                 mesh=None, carry_sh=None, rep_sh=None, proof=None,
                 cost=None):
        self.group = group
        self.B = B
        self.seg = seg_fn
        self.extract = extract_fn
        self.inject = inject_fn
        self.axes = axes
        self.plan = plan
        self.mesh = mesh
        #: Round 16: the bucket stepper's capability proof stamp
        #: (jaxstream.plan.proof) — surfaced in stats and telemetry.
        self.proof = proof
        #: Round 19: the bucket's cost stamp (jaxstream.obs.perf) —
        #: analytic per-step flops/bytes always; footprint bytes +
        #: XLA-vs-analytic flop ratio under ``serve.cost_stamps``;
        #: compile seconds from the warmup either way.
        self.cost = cost
        self._carry_sh = carry_sh
        self._rep = rep_sh
        self._stack = stack
        self._member_carry = member_carry

    def stack(self, trees):
        """Member trees -> the (device-placed) batch carry."""
        carry = self._stack(trees)
        if self._carry_sh is not None:
            carry = jax.device_put(carry, self._carry_sh)
        return carry

    def put_member(self, tree):
        """One member tree -> the inject operand.  Under GSPMD member
        placement (``backend: jnp``) the incoming IC is ``device_put``
        replicated on the bucket's mesh, so one inject executable
        serves every slot; the fused member-placed bucket
        (:class:`_PlacedBucket`) puts it on its slot's chip alone."""
        member = self._member_carry(tree)
        if self._rep is not None:
            member = jax.device_put(
                member, jax.tree_util.tree_map(lambda _: self._rep,
                                               member))
        return member

    def put_rem(self, rem):
        op = jnp.asarray(rem, jnp.int32)
        if self._rep is not None:
            op = jax.device_put(op, self._rep)
        return op

    def jits(self):
        return (self.seg, self.extract, self.inject)

    def chip_of(self, i: int) -> int:
        """The member shard (chip) that holds slot ``i``."""
        return i // self.plan.members_per_shard

    def warm_slots(self) -> tuple:
        """Slots whose extract and inject the warm-up probes: one per
        executable the steady state runs."""
        return (0,)

    def inject_slot(self, carry, i: int, tree):
        """``carry`` with slot ``i`` refilled from member ``tree``.  On
        one chip the tree's ``h`` was uploaded by its build, so the
        inject adds no host-to-device bytes."""
        obs_trace.SPANS.count(obs_trace.INJECT_BYTES, 0)
        return self.inject(carry, jnp.int32(i), self.put_member(tree))

    def extract_slot(self, carry, i: int):
        """Slot ``i``'s interior state (device arrays)."""
        return self.extract(carry, jnp.int32(i))


class _PlacedBucket(_Bucket):
    """A fused bucket split over a ``('member',)`` mesh, ``B // chips``
    members per chip (``serve.placement.mode: member`` on a grouped
    Pallas server).

    The masked segment is one ``shard_map`` program: each chip steps
    its own members with the fused member-fold kernels at the local
    batch, keeps its own slice of ``rem`` and of the health vector, and
    issues no collective.  Everything else runs on the chip that owns
    the slot (``i // (B // chips)``): the carry is assembled from
    per-chip arrays, a refill uploads the member's host-built leaves to
    that chip alone and injects them there (the strip pack inside the
    inject), and an extract reads that chip only.  ``stack``,
    ``extract`` and ``inject`` are single-chip jits, compiled once per
    chip by the warm-up.  A member tree's device leaves (a family's
    cached ``u``, built once per server) are copied to each chip once
    and kept; a request's own leaves come from the host."""

    host_leaves = True

    def __init__(self, group: str, B: int, seg_fn, stack_fn, extract_fn,
                 inject_fn, plan: BucketPlan, mesh, proof=None,
                 cost=None):
        super().__init__(group, B, seg_fn, extract_fn, inject_fn,
                         _FUSED_AXES, stack_fn, None, plan, mesh=mesh,
                         proof=proof, cost=cost)
        self._devices = list(mesh.devices.flat)
        self._per = plan.members_per_shard
        self._carry_sh = {k: NamedSharding(mesh, member_spec(ax))
                          for k, ax in self.axes.items()}
        self._rem_sh = NamedSharding(mesh, member_spec(0))
        self._kept: Dict[int, tuple] = {}

    def jits(self):
        return (self.seg, self.extract, self.inject, self._stack)

    def warm_slots(self) -> tuple:
        return tuple(range(0, self.B, self._per))

    def _on_chip(self, a, j: int):
        """``(leaf on chip j, host bytes uploaded)``."""
        if isinstance(a, jax.Array):
            # Keyed by identity and holding the array, so the key
            # cannot be reused while the copies live.
            kept = self._kept.setdefault(id(a), (a, {}))[1]
            if j not in kept:
                kept[j] = jax.device_put(a, self._devices[j])
            return kept[j], 0
        a = np.asarray(a)
        return jax.device_put(a, self._devices[j]), a.nbytes

    def _local(self, carry, j: int) -> dict:
        dev = self._devices[j]
        return {k: next(s.data for s in v.addressable_shards
                        if s.device == dev)
                for k, v in carry.items()}

    def _assemble(self, parts) -> dict:
        """Per-chip carries (in mesh order) -> the bucket's carry."""
        out = {}
        for k, ax in self.axes.items():
            arrs = [p[k] for p in parts]
            shape = list(arrs[0].shape)
            shape[ax] *= len(arrs)
            out[k] = jax.make_array_from_single_device_arrays(
                tuple(shape), self._carry_sh[k], arrs)
        return out

    def stack(self, trees):
        per = self._per
        return self._assemble([
            self._stack([{k: self._on_chip(v, j)[0] for k, v in t.items()}
                         for t in trees[j * per:(j + 1) * per]])
            for j in range(len(self._devices))])

    def _put_on_chip(self, tree, j: int):
        """Member tree -> (its leaves on chip ``j``, host bytes
        uploaded)."""
        member, nbytes = {}, 0
        for k, v in tree.items():
            member[k], b = self._on_chip(v, j)
            nbytes += b
        return member, nbytes

    def put_rem(self, rem):
        return jax.device_put(np.asarray(rem, np.int32), self._rem_sh)

    def inject_slot(self, carry, i: int, tree):
        j, k = divmod(i, self._per)
        member, nbytes = self._put_on_chip(tree, j)
        obs_trace.SPANS.count(obs_trace.INJECT_BYTES, nbytes)
        parts = [self._local(carry, c) for c in range(len(self._devices))]
        parts[j] = self.inject(parts[j], np.int32(k), member)
        return self._assemble(parts)

    def extract_slot(self, carry, i: int):
        j, k = divmod(i, self._per)
        return self.extract(self._local(carry, j), np.int32(k))


class EnsembleServer:
    """Config -> warm bucketed steppers -> packed request serving.

    ``config`` is the standard :class:`jaxstream.config.Config` surface
    (grid/time/physics/model + the ``serve:`` block); ``on_result`` is
    called with each :class:`RequestResult` from the background writer
    thread (after its fields are on host).  ``on_segment`` (round 14,
    the gateway's streaming hook) is called from the SERVING thread at
    every segment boundary with a list of per-slot progress dicts
    (``id``/``steps_done``/``nsteps``/``t``/``bucket``/``done`` — no
    wall-clock fields), strictly before any of that boundary's
    finalizations are queued, so a subscriber can never observe a
    request's result before its last segment event.  Use as a context
    manager, or call :meth:`close` when done.
    """

    def __init__(self, config=None,
                 on_result: Optional[Callable] = None,
                 on_segment: Optional[Callable] = None):
        self.config: Config = load_config(config)
        cfg = self.config
        s = cfg.serve
        if cfg.model.numerics != "dense":
            _plan_rules.fail("serve-dense")
        if cfg.model.name != "shallow_water_cov":
            # 'auto' would make the same config's Simulation build the
            # CARTESIAN model for tc2/tc5 — a server that silently
            # swapped models would break the documented B=1
            # bitwise-vs-Simulation contract.
            _plan_rules.fail("serve-covariant")
        if (cfg.precision.stage != "f32"
                or cfg.precision.strips not in ("auto", "f32")
                or cfg.precision.carry != "f32"):
            _plan_rules.fail("serve-f32")
        if cfg.parallelization.temporal_block > 1:
            _plan_rules.fail("serve-no-temporal-block")
        if (cfg.parallelization.use_shard_map
                or cfg.parallelization.tiles_per_edge > 1):
            _plan_rules.fail("serve-placement-not-shard-flags")
        if s.guards not in ("off", "evict", "halt"):
            raise ValueError(
                f"serve.guards={s.guards!r}; valid: 'off', 'evict', "
                "'halt'")
        try:
            self.buckets = tuple(sorted(
                {int(b) for b in str(s.buckets).split(",") if b.strip()}))
        except ValueError:
            raise ValueError(
                f"serve.buckets={s.buckets!r} must be a comma-separated "
                "list of positive ints") from None
        if not self.buckets or min(self.buckets) < 1:
            raise ValueError(
                f"serve.buckets={s.buckets!r} must name at least one "
                "positive batch size")
        if s.segment_steps < 1:
            raise ValueError(
                f"serve.segment_steps must be >= 1, got {s.segment_steps}")

        # ------------------------------------------------ placement plan
        self._grouping = bool(s.group_by_orography)
        p = s.placement
        if p.mode not in PLACEMENT_MODES:
            raise ValueError(
                f"serve.placement.mode={p.mode!r}; valid: "
                f"{PLACEMENT_MODES}")
        self._devices = None
        if p.mode != "off":
            devs = available_devices(p.device_type)
            n_dev = p.num_devices or len(devs)
            if n_dev > len(devs):
                raise ValueError(
                    f"serve.placement.num_devices={n_dev} but only "
                    f"{len(devs)} {p.device_type} devices exist. For "
                    f"CPU testing, start Python with XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_dev}.")
            if (p.mode == "member" and cfg.model.backend != "jnp"
                    and not serves_fused(cfg)):
                _plan_rules.fail("serve-member-jnp")
            if p.mode == "panel":
                if not self._grouping:
                    _plan_rules.fail("serve-panel-grouping")
                if cfg.time.scheme != "ssprk3":
                    _plan_rules.fail("serve-panel-ssprk3")
            self._plans: Dict[int, BucketPlan] = plan_placement(
                self.buckets, n_dev, p.mode)
            self._devices = list(devs[:n_dev])
        else:
            self._plans = plan_placement(self.buckets, 1, "off")

        halo = cfg.grid.halo
        if cfg.model.scheme == "ppm":
            halo = max(halo, 3)
        dtype = {"float32": jnp.float32, "float64": jnp.float64,
                 "bfloat16": jnp.bfloat16}[cfg.grid.dtype]
        self.grid = build_grid(cfg.grid.n, halo=halo,
                               radius=cfg.grid.radius, dtype=dtype,
                               metrics=cfg.grid.metrics)
        self.queue = RequestQueue(s.queue_capacity)
        self.monitor = (HealthMonitor(
            (), policy="warn" if s.guards == "evict" else "halt")
            if s.guards != "off" else None)
        self.on_result = on_result
        self.on_segment = on_segment
        self.results: Dict[str, RequestResult] = {}
        self.stats = {
            "submitted": 0, "refused": 0, "completed": 0, "evicted": 0,
            "batches": 0, "segments": 0, "refills": 0,
            "member_steps": 0, "occupancy_sum": 0.0,
            "utilization_sum": 0.0, "warmup_compiles": 0,
            "host_wait_s": 0.0, "resizes": 0, "last_occupancy": 0.0,
        }
        #: Live-resize state (round 14): the ACTIVE bucket cap.  The
        #: full configured bucket set stays warm; packing only uses
        #: buckets <= the cap, so autoscaling swaps among compiled
        #: executables and can never trigger a recompile.
        self._active_max = max(self.buckets)
        self._draining = False
        self._models: Dict[str, object] = {}
        self._ics: Dict[str, tuple] = {}
        self._ic_bases: Dict[str, tuple] = {}
        self._b_zero = None
        self._b_oro = None
        self._buckets: Dict[tuple, _Bucket] = {}
        self._setups: Dict[tuple, object] = {}
        self._writer: Optional[BackgroundWriter] = None
        #: Round 17: request-scoped tracing (serve.trace).  One
        #: RequestTrace per in-flight admitted request; span records
        #: land in the serve sink at finalize — or, on SINK-LESS
        #: servers only, are retained in ``trace_spans`` (bounded by
        #: the caller's request count; a sinked deployment must read
        #: its sink, not this dict).
        self._trace_on = bool(s.trace)
        self._traces: Dict[str, obs_trace.RequestTrace] = {}
        self.trace_spans: Dict[str, List[dict]] = {}
        #: The sink gains a second writer when tracing is on (span
        #: records from the background writer thread, serve/guard
        #: records from the serving thread) — serialize the two.
        self._sink_lock = threading.Lock()
        #: Round 17: the scrapeable metrics registry (obs.registry) —
        #: updated at segment boundaries on the serving thread, latency
        #: observations on the writer thread, shed counters by the
        #: gateway; rendered by ``GET /v1/metrics``.
        self.metrics = MetricsRegistry()
        self._init_metrics()
        #: Round 19 (performance observatory): the per-bucket compile
        #: counters' last-seen totals (jaxstream_compiles_total moves
        #: when a bucket's jit cache grows — a steady-state recompile
        #: shows up on the scrape, not only in tests) and, under
        #: ``serve.memory_watch``, the device-memory watcher polled at
        #: every segment boundary.  Both live on the serving thread
        #: (the registry's one-writer-per-name rule).
        self._compiles_seen: Dict[tuple, int] = {}
        self._cost_stamps = bool(s.cost_stamps)
        self.memory_watcher = None
        if s.memory_watch:
            self.memory_watcher = obs_perf.MemoryWatcher(
                devices=(self._devices if self._devices is not None
                         else jax.devices()[:1]),
                registry=self.metrics,
                sink_write=self._sink_write)
        self._sink = None
        if s.sink:
            manifest_cfg = {
                "serving": True, "grid_n": cfg.grid.n,
                "dt": cfg.time.dt, "buckets": list(self.buckets),
                "segment_steps": s.segment_steps,
                "queue_capacity": s.queue_capacity,
                "guards": s.guards,
                "placement": p.mode,
                "group_by_orography": self._grouping,
                # Round 16: rule-table version the bucket proof
                # stamps were minted against (each 'serve' record
                # then names its bucket's plan + verdict).
                "rules_version": _PLAN_RULES_VERSION,
            }
            if self._trace_on:
                # Only stamped when tracing is ON, so an untraced
                # run's manifest stays byte-identical to round 14's.
                manifest_cfg["trace"] = True
            # Same contract for the round-19 observatory knobs: the
            # manifest names them only when they are on, so a
            # default-config run's sink stays byte-identical.
            if s.memory_watch:
                manifest_cfg["memory_watch"] = True
            if s.cost_stamps:
                manifest_cfg["cost_stamps"] = True
            self._sink = TelemetrySink(s.sink, run_manifest(
                config=manifest_cfg))
        self._fault_fired = False
        self._closed = False
        #: Round 20 (flight recorder): the serving blackbox.  SIGKILL
        #: cannot be trapped, so when a flight dir is configured the
        #: server keeps a LIVE crash bundle — atomically re-committed
        #: at segment boundaries (throttled) and forced on every admit
        #: — whose open-request manifest always names every admitted-
        #: but-unfinished request.  ``self._resident`` mirrors the
        #: batch loop's local resident list so the bundle can see what
        #: is packed, not just what is queued.
        self._resident: List[str] = []
        self._blackbox: Optional[flight.BundleWriter] = None
        self._flight_last = 0.0
        self._flight_min_interval = 0.25
        #: Latched by flight_dump: once a terminal reason (signal,
        #: HealthError, ...) has been committed, the live re-commits
        #: that keep running through a graceful drain must not revert
        #: the bundle's reason to "live".
        self._flight_reason = "live"
        fdir = flight.resolve_flight_dir(cfg)
        if fdir:
            self._blackbox = flight.BundleWriter(fdir)
        #: Round 21 (warm pools): the disk-backed executable pool
        #: (``serve.warm_pool``), the persistent compile cache rung
        #: (``serve.compile_cache``), and the speculative
        #: compiler (``serve.speculate``).  The build lock serializes
        #: first-use bucket builds between the serving thread and the
        #: speculator thread; the deployment digest folds the config
        #: fields the plan key does NOT carry (dt, segment steps, nu4,
        #: dtype, donation, ...) into every pool entry key.
        self._deploy_digest = _warmpool.deployment_digest(cfg)
        self._build_lock = threading.RLock()
        self._warmpool: Optional[_warmpool.WarmPool] = None
        self._speculator = None
        #: Entries only persist when ``serve.warm_pool`` names a
        #: directory; a compile-cache-only deployment still gets a pool
        #: object (it owns the compile_cache rung) but load/save stay
        #: off.
        self._pool_entries = bool(s.warm_pool)
        if s.warm_pool or s.compile_cache:
            pool_dir = s.warm_pool or s.compile_cache + ".pool"
            self._warmpool = _warmpool.WarmPool(
                pool_dir, compile_cache=s.compile_cache,
                sink_write=self._sink_write,
                counter_inc=self.metrics.counter_inc)
            if s.compile_cache:
                self._warmpool.enable_compile_cache()
        if s.speculate:
            if not self._pool_entries:
                raise ValueError(
                    "serve.speculate requires serve.warm_pool — a "
                    "speculative compile is only worth its thread when "
                    "the executable persists for the next process too")
            self._speculator = _warmpool.SpeculativeCompiler(self)

    # --------------------------------------------------- flight recorder
    def _open_requests(self) -> dict:
        """Queued + in-flight request ids with trace ids — the crash
        bundle's admitted-but-unfinished manifest."""
        return flight.open_request_manifest(self.queue.snapshot(),
                                            list(self._resident))

    def flight_commit(self, force: bool = False,
                      reason: str = "live") -> None:
        """(Re-)commit the live crash bundle.  Throttled unless forced;
        never raises out of the serving loop."""
        bb = self._blackbox
        if bb is None:
            return
        if reason != "live":
            self._flight_reason = reason
        reason = self._flight_reason
        now = time.perf_counter()
        if not force and now - self._flight_last < self._flight_min_interval:
            return
        self._flight_last = now
        try:
            bb.commit(
                reason,
                config={"serving": True, "grid_n": self.config.grid.n,
                        "buckets": list(self.buckets),
                        "segment_steps": self.config.serve.segment_steps},
                proofs=self.bucket_proofs(),
                cost_stamps=self.bucket_costs(),
                device_memory=self.memory_snapshot(),
                open_requests=self._open_requests(),
                extra={"stats": {k: v for k, v in self.stats.items()
                                 if isinstance(v, int)}})
        except Exception as e:     # forensics must never kill serving
            log.warning("flight bundle commit failed (%s: %s)",
                        type(e).__name__, e)

    def flight_dump(self, reason: str) -> None:
        """Force one bundle commit (crash/signal path) and announce it
        in the serve sink as typed ``flight`` + ``crash`` records."""
        if self._blackbox is None:
            return
        self.flight_commit(force=True, reason=reason)
        try:
            events, threads, dropped = flight.RECORDER.dump()
            self._sink_write({"kind": "flight", "events": len(events),
                              "threads": len(threads),
                              "dropped": dropped})
            self._sink_write({"kind": "crash",
                              "bundle": self._blackbox.bundle_id,
                              "path": self._blackbox.path,
                              "reason": reason})
        except Exception as e:
            log.warning("flight dump sink records failed (%s: %s)",
                        type(e).__name__, e)

    def _init_metrics(self):
        """Declare the scrape surface up front (names, types, bucket
        ladders and HELP text are part of the operator contract —
        present from the first scrape, not from first traffic)."""
        m = self.metrics
        m.counter("jaxstream_requests_submitted_total",
                  "requests admitted by submit()")
        m.counter("jaxstream_requests_completed_total",
                  "requests that reached a final state, by status")
        m.counter("jaxstream_requests_shed_total",
                  "typed admission refusals, by shed status")
        m.counter("jaxstream_segments_total",
                  "compiled masked segments executed")
        m.counter("jaxstream_member_steps_total",
                  "member-steps of work advanced")
        m.counter("jaxstream_guard_events_total",
                  "health-guard trips (member evictions)")
        m.counter("jaxstream_compiles_total",
                  "compiled executables per plan key (warmup included; "
                  "a moving counter at steady state is a recompile)")
        m.counter("jaxstream_warmpool_hits_total",
                  "warm-pool entry loads, by rung")
        m.counter("jaxstream_warmpool_misses_total",
                  "warm-pool misses, by reason")
        m.counter("jaxstream_warmpool_saves_total",
                  "warm-pool entries persisted, by rung")
        m.gauge("jaxstream_queue_depth", "request queue depth")
        m.gauge("jaxstream_queue_capacity", "request queue bound")
        m.gauge("jaxstream_active_bucket_cap",
                "largest batch-size bucket packing may use")
        m.gauge("jaxstream_occupancy",
                "slot occupancy of the last segment (active/B)")
        m.gauge("jaxstream_chip_occupancy",
                "per-member-shard slot occupancy of the last segment")
        m.gauge("jaxstream_chip_utilization",
                "per-member-shard advanced-step fraction of the last "
                "segment")
        m.histogram("jaxstream_request_latency_seconds",
                    LATENCY_BUCKETS_S,
                    "submit-to-result end-to-end latency")
        m.histogram("jaxstream_segment_wall_seconds", WALL_BUCKETS_S,
                    "wall seconds per compiled masked segment")
        m.histogram("jaxstream_host_wait_seconds", HOST_WAIT_BUCKETS_S,
                    "residual health-stream d2h block per boundary")
        m.gauge_set("jaxstream_queue_depth", 0)
        m.gauge_set("jaxstream_queue_capacity",
                    self.config.serve.queue_capacity)
        m.gauge_set("jaxstream_active_bucket_cap", self._active_max)

    def _sink_write(self, rec: dict) -> None:
        """Serialized sink write (serving thread + writer thread when
        tracing; the lock is uncontended otherwise)."""
        if self._sink is None:
            return
        with self._sink_lock:
            self._sink.write(rec)

    # ------------------------------------------------------------ lifecycle
    def close(self):
        """Drain the result writer and close the telemetry sink."""
        if self._closed:
            return
        self._closed = True
        if self._speculator is not None:
            sp, self._speculator = self._speculator, None
            sp.close()
        if self._writer is not None:
            w, self._writer = self._writer, None
            w.close()
        if self._sink is not None:
            self._sink.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Graceful-drain entry (round 14): close admissions NOW —
        every later :meth:`submit` raises :class:`ServerDraining` —
        while already-admitted requests keep serving to their own
        final step (:meth:`serve_forever` exits once the queue is
        empty).  Nothing is re-queued or dropped."""
        self._draining = True
        flight.record("serve.drain", queue_depth=len(self.queue))

    # ------------------------------------------------------- live resize
    @property
    def active_buckets(self) -> tuple:
        """The bucket sizes packing may currently use (resize scales
        the cap; the smallest bucket always stays available)."""
        active = tuple(b for b in self.buckets if b <= self._active_max)
        return active or (min(self.buckets),)

    def resize(self, max_bucket: int, reason: str = "",
               queue_depth: Optional[int] = None,
               occupancy: Optional[float] = None) -> int:
        """Live-resize the active bucket cap (round 14 autoscaling).

        ``max_bucket`` must be a CONFIGURED bucket — every legal cap
        maps to a warm executable, so a resize never compiles (the
        zero-steady-state-recompiles-after-resize criterion is by
        construction).  Takes effect at the next batch.  Under
        ``serve.placement`` this is also the placement lever: each
        bucket's plan spans a fixed device count, so raising the cap
        engages more chips.  Returns the previous cap; records an
        ``autoscale`` event in the serve sink.  Thread-safe in the
        only way that matters: the cap is a single attribute read once
        per batch by the serving thread.
        """
        if max_bucket not in self.buckets:
            raise ValueError(
                f"resize target {max_bucket} is not a configured "
                f"bucket {list(self.buckets)} — resizes must land on "
                "warm executables (add the size to serve.buckets)")
        if max_bucket > self._active_max:
            # Round 21 headroom enforcement (the first consumer of the
            # round-19 advisory): a scale-UP to a bucket whose stamped
            # footprint breaches serve.min_headroom_frac is refused
            # with a typed record.  Scale-downs free memory and are
            # never refused; unstamped plans are never refused.
            refusal = self.headroom_refusal(max_bucket)
            if refusal is not None:
                self.record_headroom_refusal(refusal,
                                             action="resize_refused")
                raise HeadroomRefused(
                    f"resize target {max_bucket} refused: stamped "
                    f"headroom {refusal['headroom_frac']:.4f} < "
                    f"serve.min_headroom_frac "
                    f"{refusal['min_headroom_frac']:.4f}")
        old, self._active_max = self._active_max, int(max_bucket)
        self.metrics.gauge_set("jaxstream_active_bucket_cap",
                               self._active_max)
        if old != max_bucket:
            self.stats["resizes"] += 1
            log.info("serve: resized active bucket cap %d -> %d%s",
                     old, max_bucket, f" ({reason})" if reason else "")
        flight.record("serve.resize", from_bucket=old,
                      to_bucket=int(max_bucket),
                      reason=reason or "manual")
        if self._sink is not None:
            self._sink_write({
                "kind": "autoscale", "from_bucket": old,
                "to_bucket": int(max_bucket),
                "queue_depth": (len(self.queue) if queue_depth is None
                                else int(queue_depth)),
                "occupancy": round(
                    self.stats["last_occupancy"] if occupancy is None
                    else float(occupancy), 4),
                "reason": reason or "manual",
            })
        if self._speculator is not None:
            self._speculator.nudge(self._active_max)
        return old

    # ------------------------------------------------------------- building
    def _group(self, req: ScenarioRequest) -> str:
        """The request's batching group: its orography group under
        ``group_by_orography: true``, the single ``'any'`` group (all
        families pack, strict FIFO) otherwise."""
        return req.group if self._grouping else "any"

    def _pop(self, group: str) -> Optional[ScenarioRequest]:
        return self.queue.pop(group if self._grouping else None)

    def _ic(self, family: str):
        """Cached base IC fields ``(h_ext, v_ext, b_ext)`` per family."""
        if family not in self._ics:
            p, m, g = self.config.physics, self.config.model, self.grid
            b_ext = None
            if family == "tc2":
                h, v = ics.williamson_tc2(g, p.gravity, p.omega,
                                          alpha_rot=m.ic_angle)
            elif family == "tc5":
                h, v, b_ext = ics.williamson_tc5(g, p.gravity, p.omega)
            elif family == "tc6":
                h, v = ics.williamson_tc6(g, p.gravity, p.omega)
            else:
                h, v = ics.galewsky(g, p.gravity, p.omega)
            self._ics[family] = (h, v, b_ext)
        return self._ics[family]

    def _b_ext(self, family: str):
        """The request's traced orography field (mixed batches): the
        TC5 mountain for 'tc5', cached zeros for the flat families.

        The mountain is ghost-filled through the SAME halo exchange
        ``SWEBase.__init__`` applies to a baked static — the analytic
        IC ghosts differ from the exchanged (continuation-resampled)
        ones, and bitwise parity with the round-11 stepper depends on
        feeding the stencils identical ghost values."""
        if family == "tc5":
            if self._b_oro is None:
                self._b_oro = self._model("any").exchange(
                    self._ic("tc5")[2])
            return self._b_oro
        if self._b_zero is None:
            self._b_zero = jnp.zeros_like(self.grid.sqrtg)
        return self._b_zero

    def _model(self, group: str):
        """Cached model per batching group.  'oro' bakes the TC5
        orography (the ``group_by_orography: true`` parity mode);
        'flat' and the mixed-batch 'any' group are flat-bottom — the
        mountain then rides the carry as a traced field."""
        if group not in self._models:
            from ..models.shallow_water_cov import CovariantShallowWater

            cfg = self.config
            p, m = cfg.physics, cfg.model
            b_ext = self._ic("tc5")[2] if group == "oro" else None
            self._models[group] = CovariantShallowWater(
                self.grid, gravity=p.gravity, omega=p.omega, b_ext=b_ext,
                scheme=m.scheme, limiter=m.limiter,
                nu4=p.hyperdiffusion, backend=m.backend)
        return self._models[group]

    def _ic_base(self, family: str):
        """Cached seed-free request inputs per family, built once as a
        ``serve.ic_base`` span: the float64 perturbation inputs
        ``(h64, rhat64, href)`` (:func:`...perturbation_base`) and the
        unperturbed interior state.  Seeded requests share its ``u``:
        the recipe perturbs height only, and stack and inject copy
        their operands."""
        base = self._ic_bases.get(family)
        if base is None:
            with obs_trace.span(obs_trace.IC_BASE, family=family):
                h, v, _ = self._ic(family)
                group = SWE_FAMILIES[family] if self._grouping else "any"
                base = (ics.perturbation_base(self.grid, h),
                        self._model(group).initial_state(h, v))
            obs_trace.SPANS.count(obs_trace.IC_BASE)
            self._ic_bases[family] = base
        return base

    def _request_state(self, req: ScenarioRequest, host: bool = False):
        """A request's interior initial state (deterministic in seed).

        A seeded request runs :func:`...perturbed_member` on the
        family's cached base (:meth:`_ic_base`) in float64, casts and
        slices the interior on the host and uploads ``h`` once.
        ``ic: 'array'`` requests carry the interior state themselves
        (round 18): the arrays go on device as-is — byte-preserving,
        so a checkpointed member or an EnKF analysis state resubmitted
        through the gateway continues bitwise (validated at admission
        by :meth:`validate_request`).  With ``host`` (a bucket whose
        inject uploads, :attr:`_Bucket.host_leaves`) the per-request
        arrays stay on the host."""
        put = np.ascontiguousarray if host else jnp.asarray
        if req.ic == "array":
            return {k: put(v) for k, v in req.state.items()}
        (h64, rhat64, href), state = self._ic_base(req.ic)
        if req.seed < 0 or req.amplitude == 0.0:
            return dict(state)
        h = ics.perturbed_member(h64, rhat64, href,
                                 np.random.default_rng(req.seed),
                                 req.amplitude)
        h = self.grid.interior(h.astype(state["h"].dtype))
        return {"h": put(h), "u": state["u"]}

    def validate_request(self, req: ScenarioRequest) -> None:
        """Admission-time deployment validation (raises ValueError).

        The dataclass validated everything grid-independent; this
        checks what only the deployment knows — an ``ic: 'array'``
        state's shapes and dtype against the serving grid.  Runs in
        :meth:`submit` so a mismatched array is a typed 400 at the
        gateway, never a shape error mid-batch on the serving thread.
        """
        if req.ic != "array":
            return
        n = self.grid.n
        dtype = str(np.dtype(self.config.grid.dtype))
        expect = {"h": (6, n, n), "u": (2, 6, n, n)}
        for k, shape in expect.items():
            a = req.state[k]
            if tuple(a.shape) != shape:
                raise ValueError(
                    f"request {req.id!r}: ic 'array' field {k!r} has "
                    f"shape {tuple(a.shape)}; this deployment serves "
                    f"C{n} interior states of shape {shape}")
            if str(a.dtype) != dtype:
                raise ValueError(
                    f"request {req.id!r}: ic 'array' field {k!r} has "
                    f"dtype {a.dtype}; this deployment serves "
                    f"{dtype} states (byte-preserving continuations "
                    f"need the exact dtype)")

    def _member_tree(self, req: ScenarioRequest, host: bool = False):
        """The request's member tree: interior state, plus its traced
        orography leaf on the mixed-batch path."""
        st = self._request_state(req, host)
        if not self._grouping:
            st = dict(st)
            st["b"] = self._b_ext(req.ic)
        return st

    def _setup_for(self, plan: BucketPlan):
        """The (cached) mesh/ShardingSetup of one sharded plan."""
        key = (plan.mode, plan.num_devices)
        if key not in self._setups:
            ptype = self.config.serve.placement.device_type
            layout = ("member" if plan.mode == "member"
                      else "panel_member")
            self._setups[key] = setup_ensemble_sharding(
                {"parallelization": {
                    "num_devices": plan.num_devices,
                    "device_type": ptype,
                    "overlap_exchange":
                        self.config.parallelization.overlap_exchange,
                }},
                members=plan.bucket, layout=layout)
        return self._setups[key]

    def _build_bucket(self, group: str, B: int, impl: str) -> _Bucket:
        cfg = self.config
        model = self._model(group)
        dt, seg = cfg.time.dt, cfg.serve.segment_steps
        plan = self._plans[B]
        setup = self._setup_for(plan) if plan.sharded else None
        proof, cost = self._bucket_stamps(impl, plan, B)
        if impl == "fused" and plan.mode == "member":
            return self._placed_bucket(group, B, model, plan, setup.mesh,
                                       proof, cost)

        if impl == "fused":
            step = model.make_fused_step(dt, ensemble=B)
            axes = _FUSED_AXES
            member_carry = model.compact_state
            stack = (lambda trees:
                     model.ensemble_compact_state(
                         model.stack_ensemble(trees)))
        elif impl == "vmap":
            base = model.make_step(dt, cfg.time.scheme)
            axes = {"h": 0, "u": 1}
            step = vmap_ensemble(base, axes)
            member_carry = lambda st: st
            stack = model.stack_ensemble
        elif impl == "vmap_b":
            # Mixed-orography batches: the mountain is a traced
            # per-member carry leaf read by a per-step model rebind —
            # bitwise-equal to the baked-static stepper (the add/grad
            # ops are identical, only constant-ness changes; tested).
            axes = {"h": 0, "u": 1, "b": 0}
            scheme_fn = SCHEMES[cfg.time.scheme]

            def one(y, t, _m=model, _dt=dt):
                mm = copy.copy(_m)
                mm.b_ext = y["b"]
                out = scheme_fn(mm.rhs, {"h": y["h"], "u": y["u"]},
                                t, _dt)
                return {"h": out["h"], "u": out["u"], "b": y["b"]}

            step = vmap_ensemble(one, axes)
            member_carry = lambda st: st

            def stack(trees):
                return {"h": jnp.stack([tr["h"] for tr in trees]),
                        "u": jnp.stack([tr["u"] for tr in trees],
                                       axis=1),
                        "b": jnp.stack([tr["b"] for tr in trees])}
        elif impl == "panel":
            from ..parallel.shard_cov import (
                make_sharded_cov_ensemble_stepper)

            axes = {"h": 0, "u": 1}
            step = make_sharded_cov_ensemble_stepper(
                model, setup, dt, B, wrap_jit=False)
            member_carry = lambda st: st
            stack = model.stack_ensemble
        else:
            raise ValueError(f"unknown bucket impl {impl!r}")

        mesh = carry_sh = rep = None
        if setup is not None:
            mesh = setup.mesh
            carry_sh = {k: setup.ensemble_sharding_for(ax + 4)
                        for k, ax in axes.items()}
            from jax.sharding import NamedSharding, PartitionSpec as P

            rep = NamedSharding(mesh, P())

        # The health stream counts the prognostics only — the traced
        # orography leaf is constant per member.
        nf_axes = {k: axes[k] for k in ("h", "u")}

        def seg_body(y, rem):
            y, _, rem = integrate_masked(step, y, 0.0, rem, seg, dt,
                                         axes, sharding=carry_sh)
            return y, rem, _member_nonfinite(y, nf_axes)

        def extract_body(y, idx):
            return _take_member(y, idx, axes)

        def inject_body(y, idx, member):
            return _with_member(y, idx, member, axes)

        donate = (0,) if cfg.serve.donate else ()
        if mesh is None:
            seg_j = jax.jit(seg_body, donate_argnums=donate)
            ex_j = jax.jit(extract_body)
            inj_j = jax.jit(inject_body)
        else:
            member_sh = {k: rep for k in axes}
            seg_j = jax.jit(seg_body, donate_argnums=donate,
                            in_shardings=(carry_sh, rep),
                            out_shardings=(carry_sh, rep, rep))
            ex_j = jax.jit(extract_body,
                           in_shardings=(carry_sh, rep),
                           out_shardings={"h": rep, "u": rep})
            inj_j = jax.jit(inject_body,
                            in_shardings=(carry_sh, rep, member_sh),
                            out_shardings=carry_sh)
        return _Bucket(group, B, seg_j, ex_j, inj_j, axes, stack,
                       member_carry, plan, mesh=mesh,
                       carry_sh=carry_sh, rep_sh=rep, proof=proof,
                       cost=cost)

    def _placed_bucket(self, group: str, B: int, model, plan: BucketPlan,
                       mesh, proof, cost) -> "_PlacedBucket":
        """The fused bucket split over the member mesh: the masked
        segment of the local-batch fused stepper under ``shard_map``
        (see :class:`_PlacedBucket`)."""
        cfg = self.config
        dt, seg = cfg.time.dt, cfg.serve.segment_steps
        step = model.make_fused_step(dt, ensemble=B // plan.member_shards)
        axes = _FUSED_AXES
        nf_axes = {k: axes[k] for k in ("h", "u")}

        def seg_body(y, rem):
            y, _, rem = integrate_masked(step, y, 0.0, rem, seg, dt, axes)
            return y, rem, _member_nonfinite(y, nf_axes)

        def stack_body(trees):
            return model.ensemble_compact_state(model.stack_ensemble(trees))

        def extract_body(y, idx):
            return _take_member(y, idx, axes)

        def inject_body(y, idx, member):
            return _with_member(y, idx, model.compact_state(member), axes)

        donate = (0,) if cfg.serve.donate else ()
        seg_j = jax.jit(member_shard_map(seg_body, mesh, (axes, 0),
                                         (axes, 0, 0)),
                        donate_argnums=donate)
        return _PlacedBucket(group, B, seg_j, jax.jit(stack_body),
                             jax.jit(extract_body), jax.jit(inject_body),
                             plan, mesh, proof=proof, cost=cost)

    def _bucket_stamps(self, impl: str, plan: BucketPlan, B: int):
        """The bucket's capability proof stamp (round 16: which plan
        its compiled masked segment implements, and whether the static
        matrix covers it) and its cost stamp (round 19: analytic
        per-step flops/bytes now, measured fields at warmup)."""
        from ..plan.plan import CapabilityPlan
        from ..plan.proof import build_proof
        from ..plan.rules import normalize as plan_normalize

        cfg = self.config
        tier = {"fused": "fused", "vmap": "classic",
                "vmap_b": "classic", "panel": "face"}[impl]
        if plan.mode == "member" and impl != "fused":
            tier = "gspmd"
        splan = plan_normalize(CapabilityPlan(
            tier=tier, n=cfg.grid.n, halo=self.grid.halo,
            scheme=cfg.time.scheme, ensemble=B,
            overlap=(cfg.parallelization.overlap_exchange
                     and plan.mode == "panel"),
            donate=cfg.serve.donate, serving=True,
            placement=("off" if plan.mode == "single" else plan.mode),
            serve_grouping=self._grouping,
            num_devices=plan.num_devices,
            backend=("pallas" if impl == "fused"
                     else cfg.model.backend),
            covariant=True))
        proof = build_proof(splan)
        return proof, obs_perf.build_cost(splan, plan_key=proof.plan_key)

    def _impl_for(self, group: str, plan: BucketPlan) -> str:
        """The stepper impl of one bucket.  Panel-sharded plans run the
        shard_map ensemble stepper; mixed-orography servers run the
        traced-b vmapped classic; grouped pallas servers run the fused
        member-fold, on one chip or member-placed per chip (a
        :class:`_PlacedBucket`) — and a build failure there raises
        instead of dropping to the vmapped classic path.  Member-
        placed ``backend: jnp`` plans run the GSPMD-partitioned vmap
        (the gate in __init__ refused member placement with Pallas
        wherever the fused fold does not apply)."""
        if plan.mode == "panel":
            return "panel"
        if not self._grouping:
            return "vmap_b"
        return "fused" if serves_fused(self.config) else "vmap"

    def _bucket(self, group: str, B: int) -> _Bucket:
        """The warm (group, B) runtime — built, compiled and probed on
        first use (the probe run IS the warmup).  Under a configured
        ``serve.warm_pool`` the three executables route through the
        disk pool first (round 21): on a full-AOT hit the probe run
        below executes pre-loaded executables — ZERO XLA compiles.
        The build lock serializes first-use builds between the serving
        thread and the speculative compiler (dict reads stay lock-free
        for the warm steady state)."""
        key = (group, B)
        bk = self._buckets.get(key)
        if bk is not None:
            return bk
        with self._build_lock:
            bk = self._buckets.get(key)
            if bk is not None:      # raced the speculator; it won
                return bk
            plan = self._plans[B]
            impl = self._impl_for(group, plan)
            bk = self._build_bucket(group, B, impl)
            t_warm = time.perf_counter()
            self._warm_via_pool(bk)
            self._warm_bucket(bk)
            bk.cost.compile_seconds = round(
                time.perf_counter() - t_warm, 4)
            self._stamp_bucket(bk)
            self._buckets[key] = bk
            self.stats["warmup_compiles"] = self.compile_count()
            log.info("serve: bucket (%s, B=%d) warm (%s stepper, "
                     "placement %s x%d)", group, B, impl, plan.mode,
                     plan.num_devices)
            return bk

    def _warm_member_tree(self, group: str):
        family = "tc5" if group == "oro" else "tc2"
        st = dict(self._ic_base(family)[1])
        if not self._grouping:
            st["b"] = self._b_ext(family)
        return st

    def _warm_bucket(self, bk: _Bucket):
        """One dummy masked segment + extract + inject: compiles (and
        probes) every executable the bucket will ever run."""
        st = self._warm_member_tree(bk.group)
        carry = bk.stack([st] * bk.B)
        rem = np.zeros(bk.B, np.int64)
        rem[0] = self.config.serve.segment_steps
        carry, _, nf = bk.seg(carry, bk.put_rem(rem))
        jax.block_until_ready(nf)
        for i in bk.warm_slots():
            ex = bk.extract_slot(carry, i)
            carry = bk.inject_slot(carry, i, st)
        jax.block_until_ready((ex["h"], carry["h"]))

    def _warm_via_pool(self, bk: _Bucket) -> Optional[str]:
        """Route the bucket's three executables through the warm pool
        (round 21).  On a hit the jits are REPLACED by the pool-loaded
        executables before the warmup probe runs — zero XLA compiles on
        the full-AOT rung; on a miss each is compiled ahead-of-time
        exactly once (the AOT ``Compiled`` becomes the bucket's
        callable, so the warmup probe never compiles again) and
        persisted.  Sharded buckets are a typed miss this round — a
        serialized executable is bound to one device assignment, and
        revalidating that across processes is future work.  Returns
        the rung of the SEGMENT executable (the expensive one), or
        None (pool off / sharded)."""
        pool = self._warmpool
        if pool is None or not self._pool_entries:
            return None
        plan_key = bk.proof.plan_key if bk.proof is not None else None
        if bk.mesh is not None:
            pool._record("miss", "cold", plan_key,
                         reason="sharded_unsupported")
            return None
        st = self._warm_member_tree(bk.group)
        carry = bk.stack([st] * bk.B)
        rem = np.zeros(bk.B, np.int64)
        rem[0] = self.config.serve.segment_steps
        donate = (0,) if self.config.serve.donate else ()
        specs = (
            ("seg", bk.seg, (carry, bk.put_rem(rem)), donate),
            ("extract", bk.extract, (carry, jnp.int32(0)), ()),
            ("inject", bk.inject,
             (carry, jnp.int32(0), bk.put_member(st)), ()),
        )
        fingerprint = (bk.proof.schedule_fingerprint
                       if bk.proof is not None else None)
        rules_version = (bk.proof.rules_version
                         if bk.proof is not None
                         else _PLAN_RULES_VERSION)
        # The proof's plan key names the STRATEGY (tier, scheme,
        # placement) but not which bucket or batching group compiled
        # under it — and every bucket/group pair is a different
        # program (different B in every shape, oro groups carry the
        # orography field).  Fold both in or B=2 stale-hits B=1's
        # entry and dies on the shape check.
        ident = f"{plan_key or 'unplanned'}/{bk.group}/B{bk.B}"
        seg_rung = None
        loaded = {}
        for name, jitted, args, dn in specs:
            ekey = _warmpool.entry_key(
                ident, fingerprint,
                rules_version, self._deploy_digest, name)
            warm = pool.load(ekey, ident)
            if warm is None:
                # Lowering never consumes donated buffers — donation
                # only matters at execution, so the example args stay
                # valid for every spec.
                compiled = jitted.lower(*args).compile()
                rung = pool.save(ekey, jitted, compiled, args,
                                 plan_key=ident, donate=dn)
                warm = _warmpool.WarmExecutable(
                    compiled, rung or "fresh", compiles=1)
            # The original jit surface rides along so the round-19
            # cost stamp can still lower+measure (measure_cost needs
            # .lower; an AOT Compiled has none).
            warm._jitted = jitted
            loaded[name] = warm
            if name == "seg":
                seg_rung = warm.rung
        bk.seg, bk.extract, bk.inject = (
            loaded["seg"], loaded["extract"], loaded["inject"])
        return seg_rung

    def _stamp_bucket(self, bk: _Bucket) -> None:
        """Round 19: fill the bucket cost stamp's measured fields.

        Under ``serve.cost_stamps`` the segment executable is compiled
        ONCE MORE ahead-of-time — the timed compile becomes the
        recorded ``compile_seconds`` (replacing the warmup wall, which
        includes a probe execution), XLA's cost/memory analysis fills
        the footprint bytes and the flops-vs-analytic ratio, and the
        advisory ``headroom_frac`` lands on the bucket plan when the
        memory watcher knows the per-chip capacity.  One typed 'perf'
        sink record per stamped bucket.  Off = analytic half + warmup
        wall only (zero extra compiles, sink untouched)."""
        if not self._cost_stamps:
            return
        seg = self.config.serve.segment_steps
        try:
            st = self._warm_member_tree(bk.group)
            carry = bk.stack([st] * bk.B)
            rem = np.zeros(bk.B, np.int64)
            rem[0] = seg
            obs_perf.measure_cost(
                # Under the warm pool bk.seg is a WarmExecutable; the
                # stamp lowers through the original jit surface it
                # carries (stamping is the documented one-extra-compile
                # opt-in either way).
                getattr(bk.seg, "_jitted", bk.seg), carry,
                bk.put_rem(rem),
                analytic=bk.cost.analytic, steps=seg,
                xla_visible=bk.cost.xla_visible, stamp=bk.cost)
        except Exception as e:
            bk.cost.memory = {
                "unavailable": f"measure failed "
                               f"({type(e).__name__}: {e})"}
            log.warning("serve: cost stamp for bucket (%s, B=%d) "
                        "unavailable (%s: %s)", bk.group, bk.B,
                        type(e).__name__, e)
        limit = None
        if self.memory_watcher is not None:
            if self.memory_watcher.last is None:
                self.memory_watcher.poll()
            limit = self.memory_watcher.limit_bytes()
        footprint = bk.cost.memory.get("total_bytes")
        if footprint and limit:
            bk.plan = bk.plan.with_headroom(footprint, limit)
            # placement_summary reads the shared per-B plan table;
            # buckets of different groups share a B entry — last
            # stamped wins there, each bucket's own value stays in
            # bucket_costs().
            self._plans[bk.B] = bk.plan
        if self._sink is not None:
            self._sink_write({
                "kind": "perf", "plan": bk.cost.plan_key,
                "bucket": bk.B, "group": bk.group,
                "compile_seconds": bk.cost.compile_seconds,
                "memory": bk.cost.memory,
                "analytic": bk.cost.analytic, "xla": bk.cost.xla,
                "flops_ratio": bk.cost.flops_ratio,
                "bytes_ratio": bk.cost.bytes_ratio,
                "in_band": bk.cost.in_band,
                "headroom_frac": bk.plan.headroom_frac,
            })

    def warmup(self, groups=("flat",), buckets=None):
        """Pre-compile the bucket set so the first real traffic hits
        warm executables (steady-state = zero recompiles).  ``groups``:
        which batching groups to warm ('flat' and/or 'oro'; on the
        mixed-orography default every name maps to the single packed
        group)."""
        for g in groups:
            if g not in ("flat", "oro", "any"):
                raise ValueError(f"unknown batching group {g!r}")
            if not self._grouping:
                g = "any"
            for B in (buckets or self.buckets):
                self._bucket(g, B)
        # Publish the warmup compiles on the scrape before any
        # traffic (the serving thread has not started — sequential,
        # so the one-writer-per-name rule holds).
        self._observe_perf()
        return self.compile_count()

    def compile_count(self) -> int:
        """Total compiled executables across every bucket's jits — the
        zero-steady-state-recompile assertion surface (-1 when the jax
        build exposes no cache-size introspection; the introspection
        itself is the shared ``jax_compat.compile_count`` helper the
        round-19 compile-event counters also read)."""
        total = 0
        for bk in self._buckets.values():
            for f in bk.jits():
                cs = jax_compat.compile_count(f)
                if cs is None:
                    return -1
                total += cs
        return total

    def placement_summary(self) -> Optional[dict]:
        """The resolved per-bucket placement (None when placement is
        off) — the CLI/bench surface of the planner."""
        p = self.config.serve.placement
        if p.mode == "off":
            return None
        return {
            "mode": p.mode,
            "device_type": p.device_type,
            "devices": len(self._devices),
            "buckets": {str(b): dataclasses.asdict(pl)
                        for b, pl in sorted(self._plans.items())},
        }

    def bucket_proofs(self) -> Dict[str, Optional[dict]]:
        """Per warm bucket: the capability proof stamp of its compiled
        masked segment (round 16) — plan key, canonical schedule
        fingerprint, rules version, matrix-coverage verdict."""
        return {f"{g}/B{B}": (bk.proof.to_json()
                              if bk.proof is not None else None)
                for (g, B), bk in sorted(self._buckets.items())}

    def bucket_costs(self) -> Dict[str, Optional[dict]]:
        """Per warm bucket: the cost stamp of its compiled masked
        segment (round 19) — analytic flops/bytes, footprint bytes (or
        the typed unavailable reason), compile seconds, the
        XLA-vs-analytic flop ratio, and the plan's advisory headroom.
        Surfaced by ``/v1/stats`` and ``scripts/serve.py``."""
        out: Dict[str, Optional[dict]] = {}
        for (g, B), bk in sorted(self._buckets.items()):
            if bk.cost is None:
                out[f"{g}/B{B}"] = None
                continue
            d = bk.cost.to_json()
            d["headroom_frac"] = bk.plan.headroom_frac
            out[f"{g}/B{B}"] = d
        return out

    def memory_snapshot(self) -> Optional[dict]:
        """The memory watcher's latest per-chip record (None when
        ``serve.memory_watch`` is off or nothing polled yet)."""
        return (self.memory_watcher.last
                if self.memory_watcher is not None else None)

    # ------------------------------------------------- warm pool (round 21)
    def warm_groups(self) -> tuple:
        """Batching groups the speculative compiler should warm: the
        groups that already have buckets (a live server speculates
        along the traffic it has seen), else the deployment's default
        group."""
        groups = {g for (g, _B) in self._buckets}
        if not groups:
            groups = {"any" if not self._grouping else "flat"}
        return tuple(sorted(groups))

    def headroom_refusal(self, B: int) -> Optional[dict]:
        """The typed refusal record for scaling to bucket ``B``, or
        None (= allowed).  Refuses ONLY when the bucket's plan carries
        a stamped ``headroom_frac`` (round 19 cost stamps) below
        ``serve.min_headroom_frac`` — an unstamped plan is never
        refused (enforcement needs evidence), and the default threshold
        0.0 only refuses footprints that already exceed capacity."""
        plan = self._plans.get(int(B))
        hf = getattr(plan, "headroom_frac", None)
        if hf is None:
            return None
        mn = self.config.serve.min_headroom_frac
        if hf >= mn:
            return None
        return {"kind": "headroom", "action": "", "bucket": int(B),
                "headroom_frac": round(float(hf), 4),
                "min_headroom_frac": float(mn)}

    def record_headroom_refusal(self, refusal: dict,
                                action: str) -> None:
        """Write one headroom refusal as a typed sink record + flight
        event (``action``: 'resize_refused' / 'speculate_refused')."""
        rec = dict(refusal)
        rec["action"] = action
        self._sink_write(rec)
        flight.record("serve.headroom_refused", bucket=rec["bucket"],
                      action=action,
                      headroom_frac=rec["headroom_frac"])

    def warmpool_summary(self) -> Optional[dict]:
        """The warm pool's ``/v1/stats`` surface (None = pool off):
        hit/miss/save/corrupt counters, per-rung hit counts, and what
        the speculative compiler built/skipped."""
        if self._warmpool is None:
            return None
        out = self._warmpool.summary()
        if self._speculator is not None:
            out["speculative_built"] = [
                list(t) for t in self._speculator.built]
            out["speculative_skipped"] = len(self._speculator.skipped)
        return out

    # ------------------------------------------------------------ admission
    def refusal_reasons(self) -> List[str]:
        """Why a :meth:`submit` would be refused right now ([] =
        admissible).  The ONE definition both admission and readiness
        probes consume (the gateway's ``/v1/ready``), so a new refusal
        condition can never update one without the other.  Note
        ``queue_full`` is advisory for blocking submits — ``submit(
        block=True)`` waits a full queue out instead of refusing."""
        reasons = []
        if self._draining:
            reasons.append("draining")
        mx = self.config.serve.max_guard_events
        if (mx > 0 and self.monitor is not None
                and len(self.monitor.events) >= mx):
            reasons.append("admission_refused")
        if len(self.queue) >= self.queue.capacity:
            reasons.append("queue_full")
        return reasons

    def submit(self, req: ScenarioRequest, block: bool = False,
               timeout: Optional[float] = None) -> None:
        """Admit one request (raises :class:`QueueFull` at capacity,
        :class:`AdmissionRefused` when the health monitor has recorded
        ``serve.max_guard_events`` guard trips, :class:`ServerDraining`
        after :meth:`begin_drain`)."""
        if self._closed:
            raise RuntimeError("EnsembleServer is closed")
        self.validate_request(req)
        reasons = self.refusal_reasons()
        if "draining" in reasons:
            self.stats["refused"] += 1
            raise ServerDraining(
                f"server refused {req.id!r}: draining — admissions are "
                "closed while in-flight requests run to completion")
        if "admission_refused" in reasons:
            self.stats["refused"] += 1
            raise AdmissionRefused(
                f"server refused {req.id!r}: {len(self.monitor.events)} "
                f"guard events >= serve.max_guard_events="
                f"{self.config.serve.max_guard_events} — the "
                "deployment is unhealthy; investigate before admitting "
                "more traffic")
        # queue_full is the queue's own call: a blocking submit waits
        # it out, a non-blocking one gets QueueFull from queue.submit.
        req.submitted_wall = time.perf_counter()
        if self._trace_on:
            # The trace's root interval IS the latency interval: t0 is
            # the same stamp latency_s is measured from, so the leaf
            # sum telescopes to the reported latency by construction.
            # Registered BEFORE the queue publishes the request: the
            # serving thread may pop it the instant submit returns,
            # and a mark on an unregistered id is silently dropped —
            # an incomplete tree, found by review.
            self._traces[req.id] = obs_trace.RequestTrace(
                req.id, t0=req.submitted_wall)
        try:
            self.queue.submit(req, block=block, timeout=timeout)
        except Exception:
            self._traces.pop(req.id, None)
            raise
        if self._draining and self.queue.remove(req):
            self._traces.pop(req.id, None)
            # begin_drain raced the enqueue: serve_forever may already
            # have observed (empty queue, draining) and exited, which
            # would strand this request admitted-but-never-served.
            # Either we take it back out here and refuse it, or the
            # serving loop already popped it and will finish it.
            self.stats["refused"] += 1
            raise ServerDraining(
                f"server refused {req.id!r}: draining began during "
                "admission — the request was withdrawn, not stranded")
        self.stats["submitted"] += 1
        self.metrics.counter_inc("jaxstream_requests_submitted_total")
        # Forced (unthrottled) bundle re-commit on EVERY admission: the
        # last committed bundle must name every admitted-but-unfinished
        # request, so a SIGKILL at any instant leaves a manifest whose
        # open-request set includes this one.
        self.flight_commit(force=True)

    # -------------------------------------------------------------- serving
    def serve(self):
        """Drain the queue: pack -> masked segments -> refill, batch by
        batch, until no requests remain.  Returns ``self.results``."""
        try:
            while True:
                req = self.queue.pop()
                if req is None:
                    break
                self._run_batch(req)
        except BaseException as e:
            # Crash forensics (round 20): commit the black box and
            # stamp the sink BEFORE the writer flush below — a second
            # failure during flush must not cost us the bundle.
            self.flight_dump(reason=type(e).__name__)
            raise
        finally:
            if self._writer is not None:
                self._writer.flush()
        return self.results

    def serve_forever(self, stop=None, idle_wait: float = 0.01,
                      tick: Optional[Callable] = None,
                      idle_tick_s: float = 0.25):
        """Network-serving loop (round 14): drain batches until ``stop``
        (a ``threading.Event``) is set, parking ``idle_wait`` seconds
        between empty polls.  After :meth:`begin_drain`, exits once the
        queue is empty and every admitted request reached its final
        state (the writer is flushed on the way out, so results are
        delivered when this returns).  An escaping exception dumps the
        flight ring (crash bundle + ``flight``/``crash`` sink records)
        before propagating.

        ``tick``, when given, is called as ``tick(self)`` at every
        SEGMENT boundary — the autoscale hook: it observes queue depth
        + last-segment occupancy and may call :meth:`resize`.  Running
        it on the serving thread makes scaling decisions deterministic
        given queue state (no racing sampler thread); when a resize
        changes the active cap away from the running batch's bucket,
        that batch stops REFILLING — its in-flight members run to
        their own final step on the warm old-bucket executable — and
        packing resumes at the new cap with the very next batch (the
        live-resize migration path; no member is ever interrupted or
        re-queued).  While IDLE the hook runs at most once per
        ``idle_tick_s`` seconds, not once per poll: the policy's
        patience/cooldown counts are observations, and idle polls at
        ``idle_wait`` cadence would turn a few milliseconds of
        inter-burst silence into a full scale-down — exactly the flap
        the hysteresis exists to prevent.
        """
        last_idle_tick = float("-inf")
        try:
            while stop is None or not stop.is_set():
                req = self.queue.pop()
                if req is None and self._draining:
                    # A submit may have enqueued between the pop above
                    # and this flag read (its post-enqueue unwind then
                    # saw draining=False and kept the request): only
                    # exit the drain when the queue is confirmed empty
                    # AFTER the draining flag was observed.
                    req = self.queue.pop()
                    if req is None:
                        break
                if req is None:
                    # An idle server occupies zero slots; without this
                    # a final full segment would pin last_occupancy at
                    # 1.0 and block scale-down forever.
                    self.stats["last_occupancy"] = 0.0
                    now = time.monotonic()
                    if now - last_idle_tick >= idle_tick_s:
                        last_idle_tick = now
                        self._tick(tick)
                    time.sleep(idle_wait)
                    continue
                self._run_batch(req, tick=tick)
                last_idle_tick = float("-inf")
                if self._writer is not None:
                    self._writer.flush()
        except BaseException as e:
            self.flight_dump(reason=type(e).__name__)
            raise
        finally:
            if self._writer is not None:
                self._writer.flush()
        return self.results

    def _ensure_writer(self) -> BackgroundWriter:
        if self._writer is None or not self._writer.alive:
            self._writer = BackgroundWriter(
                max_pending=8, name=SERVE_WRITER_THREAD_NAME)
        return self._writer

    def _observe_perf(self) -> None:
        """Segment-boundary observability (round 19): the per-plan
        compile-event counters and — under ``serve.memory_watch`` —
        one device-memory poll.  Runs on the serving thread at the
        same cadence as the autoscale tick; the counter pass is a few
        dict/attribute reads when nothing compiled, and ZERO memory
        polling happens when the watcher is off."""
        # list(): the speculative compiler may insert a bucket
        # mid-iteration (round 21).
        for key, bk in list(self._buckets.items()):
            counts = [jax_compat.compile_count(f) for f in bk.jits()]
            cur = sum(c for c in counts if c is not None)
            prev = self._compiles_seen.get(key, 0)
            if cur > prev:
                self._compiles_seen[key] = cur
                self.metrics.counter_inc(
                    "jaxstream_compiles_total", cur - prev,
                    plan=(bk.proof.plan_key if bk.proof is not None
                          else f"{key[0]}/B{key[1]}"))
                flight.record(
                    "compile", delta=cur - prev,
                    plan=(bk.proof.plan_key if bk.proof is not None
                          else f"{key[0]}/B{key[1]}"))
        if self.memory_watcher is not None:
            rec = self.memory_watcher.poll()
            if rec is not None and rec.get("bytes_in_use"):
                flight.record("memory.watermark",
                              bytes_in_use=max(rec["bytes_in_use"]),
                              peak_bytes=max(rec["peak_bytes"] or [0]))

    def _tick(self, tick) -> None:
        """Boundary observers + the autoscale hook; a policy bug must
        not kill serving."""
        self._observe_perf()
        if tick is None:
            return
        try:
            tick(self)
        except Exception as e:
            log.warning("serve: autoscale tick failed (%s: %s)",
                        type(e).__name__, e)

    def _run_batch(self, first: ScenarioRequest, tick=None):
        """One batch's life: pack up to the best bucket, then segment /
        evict / extract / refill until every slot drains.  With a
        ``tick`` hook, a live resize away from this batch's bucket
        stops the refill so the batch winds down and serve_forever
        re-packs at the new cap."""
        cfg = self.config
        s, dt = cfg.serve, cfg.time.dt
        group = self._group(first)
        # The resize cap is read ONCE per batch (cap0): the packing
        # decision and the later wind-down comparison both derive from
        # the same read, so a resize from another thread between them
        # cannot be silently ignored.
        cap0 = self._active_max
        active = (tuple(b for b in self.buckets if b <= cap0)
                  or (min(self.buckets),))
        batch: List[ScenarioRequest] = [first]
        while len(batch) < max(active):
            r = self._pop(group)
            if r is None:
                break
            batch.append(r)
        B = next(b for b in active if b >= len(batch))
        bk = self._bucket(group, B)
        plan = bk.plan
        plan_key = bk.proof.plan_key if bk.proof is not None else None
        self.stats["batches"] += 1

        with obs_trace.span(obs_trace.PACK, bucket=B) as sp:
            if self._trace_on:
                # queue.wait ends (and serve.pack opens) for the whole
                # initial batch at one stamp — the IC builds + the
                # single stack below are the batch's shared packing
                # work.
                for r in batch:
                    self._mark(r.id, obs_trace.PACK, sp.t0)
            trees = [self._ic_build(r, "pack", bk.host_leaves)
                     for r in batch]
            carry = bk.stack(trees + [trees[0]] * (B - len(batch)))
        slots: List[Optional[_Slot]] = (
            [_Slot(r, bk.chip_of(i)) for i, r in enumerate(batch)]
            + [None] * (B - len(batch)))
        rem = np.zeros(B, np.int64)
        rem[:len(batch)] = [r.nsteps for r in batch]
        seg = s.segment_steps
        m_shards = plan.member_shards
        per_shard = B // m_shards
        chips = ([i // per_shard for i in range(B)]
                 if m_shards > 1 else None)
        # Live-resize wind-down (round 14): when the tick hook resizes
        # the cap away from this batch's packing decision (cap0,
        # above), the batch stops refilling — in-flight members finish
        # on the warm executable, then serve_forever re-packs at the
        # new cap.
        allow_refill = True

        while any(sl is not None for sl in slots):
            with obs_trace.span(obs_trace.DISPATCH, bucket=B) as sp:
                active_mask = [sl is not None for sl in slots]
                active_before = sum(active_mask)
                resident = [(i, sl.req.id) for i, sl in enumerate(slots)
                            if sl is not None]
                # The black box's in-flight view: updated BEFORE the
                # segment dispatches, so the crash bundle committed at
                # this boundary names exactly the members a kill during
                # the segment would strand.
                self._resident = [rid for _, rid in resident]
                carry, _, nf = bk.seg(carry, bk.put_rem(rem))
            w0 = sp.t0
            # The health stream rides a HostFetch: its d2h copy chases
            # the segment's compute while the host does the boundary
            # work that does NOT depend on it — completion is pure
            # arithmetic on `rem`, so the incoming requests' initial
            # states can be built now, overlapping the device.
            nf_fetch = HostFetch(nf)
            new_rem = np.maximum(rem - seg, 0)
            n_free_pred = sum(
                1 for i, sl in enumerate(slots)
                if sl is not None and new_rem[i] == 0)
            prepped: List[tuple] = []
            for _ in range(n_free_pred if allow_refill else 0):
                r = self._pop(group)
                if r is None:
                    break
                prepped.append(
                    (r, self._ic_build(r, "prefetch", bk.host_leaves)))
            with obs_trace.span(obs_trace.HOST_WAIT) as sp:
                nf_host = np.asarray(nf_fetch.resolve(),
                                     np.float64).reshape(-1)
            hw0, hw1 = sp.t0, sp.t1
            host_wait = hw1 - hw0
            wall = hw1 - w0
            # The boundary: from the health fetch's end to this
            # iteration's end, where the next dispatch follows.
            with obs_trace.span(obs_trace.BOUNDARY, t0=hw1, bucket=B):
                steps_by_slot = rem - new_rem
                member_steps = int(np.sum(steps_by_slot))
                rem = new_rem
                for i, sl in enumerate(slots):
                    if sl is not None:
                        sl.done = sl.req.nsteps - int(rem[i])
                if self._trace_on:
                    # Three leaves per resident request per segment, at the
                    # SHARED boundary stamps (w0/hw0/hw1): device compute,
                    # health-stream host wait, then boundary work (evict/
                    # extract/refill) which the next segment mark — or the
                    # finalize mark — closes.  Segment leaves carry the
                    # operator attribution: bucket, plan key, chip, steps.
                    for i, rid in resident:
                        self._mark(rid, obs_trace.SEGMENT, w0, bucket=B,
                                   plan=plan_key,
                                   chip=(chips[i] if chips is not None
                                         else 0),
                                   steps=int(steps_by_slot[i]))
                        self._mark(rid, obs_trace.HOST_WAIT, hw0)
                        self._mark(rid, obs_trace.BOUNDARY, hw1)
                # Per-segment progress stream (round 14, the gateway's
                # hook): one event per slot active during this segment,
                # emitted BEFORE any finalization from this boundary is
                # queued — no wall-clock fields, so the stream is
                # deterministic for a given packing.
                if self.on_segment is not None:
                    progress = [
                        {"id": sl.req.id, "steps_done": sl.done,
                         "nsteps": sl.req.nsteps, "t": sl.done * dt,
                         "bucket": B, "done": bool(rem[i] == 0)}
                        for i, sl in enumerate(slots) if sl is not None]
                    try:
                        self.on_segment(progress)
                    except Exception as e:   # a subscriber bug must not
                        log.warning(         # kill the batch
                            "serve: on_segment hook failed (%s: %s)",
                            type(e).__name__, e)
                # Testing hook: host-side injection into the health STREAM
                # (never the state), mirroring observability.fault_step.
                fi = s.fault_member
                if (fi >= 0 and cfg.observability.fault_step >= 0
                        and not self._fault_fired and fi < B
                        and slots[fi] is not None
                        and slots[fi].done >= cfg.observability.fault_step):
                    nf_host[fi] = max(nf_host[fi], 1.0)
                    self._fault_fired = True
                completed = evicted = 0
                if self.monitor is not None:
                    counts = np.where(
                        [sl is not None for sl in slots], nf_host, 0.0)
                    steps = [sl.done if sl is not None else 0 for sl in slots]
                    ts = [d * dt for d in steps]
                    # 'halt' policy raises here (HealthError) — the writer
                    # flush in serve()'s finally still lands prior
                    # results, and the speculatively popped refill
                    # requests go back to the queue head (they were
                    # admitted; a guard trip must not lose them).
                    try:
                        events = self.monitor.check_members(
                            steps, ts, counts, chips=chips)
                    except BaseException:
                        if prepped:
                            self.queue.requeue(r for r, _ in prepped)
                        raise
                    for ev in events:
                        i = ev["member"]
                        self._finish(slots[i], "evicted", None, ev)
                        rem[i] = 0
                        slots[i] = None
                        evicted += 1
                        if self._sink is not None:
                            # The event is already a schema-valid 'guard'
                            # record; under placement it names the chip.
                            self._sink_write(ev)
                    if events:
                        self.metrics.counter_inc(
                            "jaxstream_guard_events_total", len(events))
                with obs_trace.span(obs_trace.FINALIZE):
                    for i, sl in enumerate(slots):
                        if sl is not None and rem[i] == 0:
                            fetch = HostFetch(bk.extract_slot(carry, i))
                            self._finish(sl, "ok", fetch)
                            slots[i] = None
                            completed += 1
                with obs_trace.span(obs_trace.REFILL):
                    refilled = 0
                    if allow_refill:
                        for i in range(B):
                            if slots[i] is not None:
                                continue
                            if prepped:
                                r, tree = prepped.pop(0)
                            else:
                                r = self._pop(group)
                                if r is None:
                                    break
                                tree = self._ic_build(r, "late",
                                                      bk.host_leaves)
                            with obs_trace.span(obs_trace.INJECT,
                                                chip=bk.chip_of(i),
                                                slot=i):
                                carry = bk.inject_slot(carry, i, tree)
                            rem[i] = r.nsteps
                            slots[i] = _Slot(r, bk.chip_of(i))
                            refilled += 1
                # Prepped requests can never be left over: free slots >=
                # predicted completions (eviction only adds frees) and the
                # refill loop scans every slot, consuming prepped first.
                # A popped request silently dropped would be a lost-
                # traffic bug, so the invariant fails loudly.
                assert not prepped, (
                    "serve refill invariant broken: speculatively popped "
                    f"requests left unslotted: {[r.id for r, _ in prepped]}")
                with obs_trace.span(obs_trace.OBSERVE):
                    st = self.stats
                    st["segments"] += 1
                    st["last_occupancy"] = active_before / B
                    st["refills"] += refilled
                    st["member_steps"] += member_steps
                    st["occupancy_sum"] += active_before / B
                    st["utilization_sum"] += member_steps / (B * seg)
                    st["completed"] += completed
                    st["evicted"] += evicted
                    st["host_wait_s"] += host_wait
                    m = self.metrics
                    m.counter_inc("jaxstream_segments_total")
                    if member_steps:
                        m.counter_inc("jaxstream_member_steps_total",
                                      member_steps)
                    if completed:
                        m.counter_inc("jaxstream_requests_completed_total",
                                      completed, status="ok")
                    if evicted:
                        m.counter_inc("jaxstream_requests_completed_total",
                                      evicted, status="evicted")
                    m.gauge_set("jaxstream_queue_depth", len(self.queue))
                    m.gauge_set("jaxstream_occupancy", active_before / B)
                    m.observe("jaxstream_segment_wall_seconds", wall,
                              buckets=WALL_BUCKETS_S)
                    m.observe("jaxstream_host_wait_seconds", host_wait,
                              buckets=HOST_WAIT_BUCKETS_S)
                    for j in range(m_shards):
                        occ_j = (sum(active_mask[j * per_shard:
                                                 (j + 1) * per_shard])
                                 / per_shard)
                        util_j = (float(np.sum(
                            steps_by_slot[j * per_shard:(j + 1) * per_shard]))
                            / (per_shard * seg))
                        m.gauge_set("jaxstream_chip_occupancy", occ_j,
                                    chip=str(j))
                        m.gauge_set("jaxstream_chip_utilization", util_j,
                                    chip=str(j))
                    if self._sink is not None:
                        rec = {
                            "kind": "serve", "bucket": B, "group": group,
                            "plan": (bk.proof.plan_key
                                     if bk.proof is not None else None),
                            "proof_verdict": (bk.proof.verdict
                                              if bk.proof is not None
                                              else None),
                            "occupancy": round(active_before / B, 4),
                            "utilization": round(member_steps / (B * seg), 4),
                            "queue_depth": len(self.queue),
                            "wall_s": round(wall, 6),
                            "host_wait_s": round(host_wait, 6),
                            "completed": completed, "evicted": evicted,
                            "refilled": refilled, "member_steps": member_steps,
                        }
                        if self._trace_on:
                            # Which requests this segment advanced (slot
                            # order) — the dashboard's live in-flight view.
                            rec["trace_ids"] = [
                                obs_trace.trace_id_for(rid)
                                for _, rid in resident]
                        if plan.sharded:
                            rec["placement"] = plan.mode
                            rec["devices"] = plan.num_devices
                            rec["chip_occupancy"] = [
                                round(sum(active_mask[j * per_shard:
                                                      (j + 1) * per_shard])
                                      / per_shard, 4)
                                for j in range(m_shards)]
                            rec["chip_utilization"] = [
                                round(float(np.sum(
                                    steps_by_slot[j * per_shard:
                                                  (j + 1) * per_shard]))
                                    / (per_shard * seg), 4)
                                for j in range(m_shards)]
                        self._sink_write(rec)
                    flight.record("serve.boundary", bucket=B,
                                  active=active_before, completed=completed,
                                  evicted=evicted, refilled=refilled,
                                  queue_depth=len(self.queue))
                    # Autoscale hook, once per segment boundary — queue depth
                    # and last_occupancy are fresh here.  A resize ends this
                    # batch's refill (see cap0 note above).
                    self._tick(tick)
                    # Post-boundary resident set (completions/evictions above
                    # freed slots; refill re-occupied some) before the live
                    # bundle re-commit — throttled, so a fast segment cadence
                    # costs at most ~4 commits/second.
                    self._resident = [sl.req.id for sl in slots
                                      if sl is not None]
                    self.flight_commit()
                if allow_refill and self._active_max != cap0:
                    allow_refill = False
                    log.info("serve: active cap resized %d -> %d mid-"
                             "batch; batch (B=%d) winds down without "
                             "refilling", cap0, self._active_max, B)

    def _ic_build(self, req: ScenarioRequest, where: str, host: bool):
        """One request's member tree as a ``serve.ic_build`` span;
        ``where`` is ``pack`` (the batch's initial pack), ``prefetch``
        (built while the segment runs) or ``late`` (inside the
        boundary).  A refill's build opens its ``serve.pack`` mark; the
        initial pack marks the whole batch at the pack span's start.
        ``host``: the bucket's :attr:`_Bucket.host_leaves`."""
        with obs_trace.span(obs_trace.IC_BUILD, where=where) as sp:
            if where != "pack" and self._trace_on:
                self._mark(req.id, obs_trace.PACK, sp.t0)
            tree = self._member_tree(req, host=host)
        obs_trace.SPANS.count(f"{obs_trace.IC_BUILD}.{where}")
        return tree

    def _mark(self, rid: str, name: str, t: Optional[float] = None,
              **attrs) -> None:
        """Add one trace mark for an in-flight request (no-op for
        untraced ids — e.g. requests admitted before a restart)."""
        tr = self._traces.get(rid)
        if tr is not None:
            tr.mark(name, t, **attrs)

    def _finish(self, slot: _Slot, status: str,
                fetch: Optional[HostFetch], event: Optional[dict] = None):
        """Queue one request's finalization on the background writer —
        the d2h copies (already in flight) resolve there, overlapping
        the next segment's compute.  The latency stamp moved (round
        17) from here to :meth:`_finalize`'s result-ready instant, so
        the reported latency covers the writer-queue wait and the d2h
        result fetch — the same interval the request's span tree
        tiles."""
        if self._trace_on:
            self._mark(slot.req.id, obs_trace.FINALIZE_WAIT)
        self._ensure_writer().submit(
            self._finalize, slot.req, status, slot.done, fetch, event,
            slot.chip)

    def _finalize(self, req: ScenarioRequest, status: str, done: int,
                  fetch: Optional[HostFetch], event: Optional[dict],
                  chip: int = 0):
        tr = self._traces.pop(req.id, None) if self._trace_on else None
        with obs_trace.span(obs_trace.RESULT_FETCH) as sp:
            if tr is not None:
                tr.mark(obs_trace.RESULT_FETCH, sp.t0)
            fields = {}
            if fetch is not None:
                host = fetch.resolve()
                fields = {k: host[k] for k in req.outputs if k in host}
        t_final = done * self.config.time.dt
        with obs_trace.span(obs_trace.WRITER_FLUSH) as sp:
            if tr is not None:
                tr.mark(obs_trace.WRITER_FLUSH, sp.t0)
            out_dir = self.config.serve.output_dir
            if out_dir and fields:
                from ..io.history import HistoryWriter

                hw = HistoryWriter(
                    os.path.join(out_dir, req.id),
                    attrs={"request": req.id, "ic": req.ic,
                           "nsteps": req.nsteps, "status": status})
                hw.append(fields, t_final)
        # The result-ready instant: latency_s and the trace root close
        # on the SAME stamp, so the span tree's leaf sum telescopes to
        # the reported latency exactly (obs.trace module docstring).
        t_end = sp.t1
        latency = (t_end - req.submitted_wall
                   if req.submitted_wall is not None else 0.0)
        res = RequestResult(
            id=req.id, ic=req.ic, nsteps=req.nsteps, status=status,
            t_final=t_final, steps_run=done, latency_s=latency,
            fields=fields, guard_event=event, chip=chip)
        if tr is not None:
            spans = tr.finish(status, t_end)
            if self._sink is not None:
                for sp in spans:
                    self._sink_write(sp)
            else:
                # Only sink-less (direct/embedded) servers retain the
                # spans in memory — a sinked deployment already
                # persisted them, and retaining every request's spans
                # forever would grow without bound under continuous
                # traffic (review finding).
                self.trace_spans[req.id] = spans
        self.metrics.observe("jaxstream_request_latency_seconds",
                             latency, buckets=LATENCY_BUCKETS_S,
                             status=status)
        self.results[req.id] = res
        if self.on_result is not None:
            self.on_result(res)

    # ------------------------------------------------------------ reporting
    @property
    def occupancy_mean(self) -> float:
        n = self.stats["segments"]
        return self.stats["occupancy_sum"] / n if n else 0.0

    @property
    def utilization_mean(self) -> float:
        n = self.stats["segments"]
        return self.stats["utilization_sum"] / n if n else 0.0

    def latencies(self) -> np.ndarray:
        return np.asarray(sorted(
            r.latency_s for r in self.results.values()))


def serve_requests(config, requests, warm_groups=None):
    """One-call serving: build a server, admit ``requests`` (blocking
    at the queue bound), drain, close.  Returns the server (results in
    ``server.results``, counters in ``server.stats``)."""
    server = EnsembleServer(config)
    try:
        if warm_groups:
            server.warmup(groups=warm_groups)
        pending = list(requests)
        while pending:
            # Admit what fits, serve a batch, repeat — producer-side
            # backpressure without a second thread.
            while pending:
                try:
                    server.submit(pending[0])
                except QueueFull:
                    break
                pending.pop(0)
            req = server.queue.pop()
            if req is not None:
                server._run_batch(req)
        server.serve()
    finally:
        server.close()
    return server
