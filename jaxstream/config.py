"""YAML-driven configuration.

Source-compatible superset of the reference's config surface: the deck's
``config.yaml`` has a ``parallelization:`` block with ``tiles_per_edge``,
``num_devices``, ``device_type`` (screenshot deck p.8; consumed with
``.get`` defaults at ``/root/reference/JAX-DevLab-Examples.py:21-24``).
We keep those keys and defaults verbatim and add the sections the full
framework needs (grid, physics, time, io) — SURVEY.md §5 "Config / flag
system" rebuild note.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any

import yaml

__all__ = [
    "GridConfig",
    "ParallelConfig",
    "PhysicsConfig",
    "TimeConfig",
    "AsyncPipelineConfig",
    "IOConfig",
    "EnsembleConfig",
    "ObservabilityConfig",
    "PrecisionConfig",
    "PlacementConfig",
    "ServeConfig",
    "DAConfig",
    "Config",
    "load_config",
]

EARTH_RADIUS = 6.37122e6
EARTH_OMEGA = 7.292e-5
EARTH_GRAVITY = 9.80616


@dataclasses.dataclass(frozen=True)
class GridConfig:
    n: int = 48                      # cells per panel edge (C{n})
    halo: int = 2                    # >=2 for PLR, >=3 for PPM
    radius: float = EARTH_RADIUS
    dtype: str = "float32"
    metrics: str = "eager"           # 'eager' (precomputed f64) | 'lazy' (fused)


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    # Reference-compatible keys + defaults (JAX-DevLab-Examples.py:21-24).
    tiles_per_edge: int = 1
    num_devices: int = 6
    device_type: str = "cpu"         # 'cpu' (virtual devices) | 'tpu' | 'gpu'
    # Extension: explicit shard_map+ppermute stepping (needs num_devices=6,
    # one face per device) instead of the GSPMD-inferred path.  Honored by
    # jaxstream.parallel.sharded_model.make_stepper_for.
    use_shard_map: bool = False
    # Overlapped halo exchange (explicit shard_map paths + the sharded
    # factored tier): issue every ppermute stage up front, run the
    # interior-only RHS kernel while the collectives are in flight, and
    # finish with the boundary-band pass on the received strips.  The
    # split path is parity-tested against the serialized default on all
    # tiers; default off so the serialized exchange stays the reference.
    overlap_exchange: bool = False
    # Donate the state carry to the compiled segment loops (XLA aliases
    # input/output state instead of double-buffering every prognostic).
    # On accelerators a donated buffer is CONSUMED: references a caller
    # holds to sim.state (or a previous run()'s return value) become
    # invalid once the next segment runs.  Set false to keep every
    # intermediate state alive at the cost of one extra state copy of
    # HBM residency.
    donate_state: bool = True
    # Temporal halo blocking: run `temporal_block` SSPRK3 steps per
    # compiled block.  On the explicit one-face-per-device tier this is
    # the deep-halo form — ONE exchange of width 3*k*halo strips per
    # block, then 3*k exchange-free RK stages on shrinking windows
    # (redundant ghost-band compute instead of collectives; seam values
    # are then face-local continuations, consistent to the stencil's own
    # O(d^2) — see docs/USAGE.md "Temporal halo blocking" for when k > 1
    # loses).  On the single-device fused, block-mesh, and factored TT
    # tiers the k steps are fused exactly (unchanged exchange data, one
    # dispatch per block).  Default 1 = the serialized reference path.
    temporal_block: int = 1


@dataclasses.dataclass(frozen=True)
class PhysicsConfig:
    gravity: float = EARTH_GRAVITY
    omega: float = EARTH_OMEGA
    hyperdiffusion: float = 0.0      # nu4 coefficient (m^4/s)
    divergence_damping: float = 0.0  # nondimensional d2 coefficient
    diffusivity: float = 1.0e5       # kappa (m^2/s) for the diffusion model


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "auto"               # 'auto' | 'shallow_water' | 'advection' | 'diffusion'
    initial_condition: str = "tc2"   # tc1/cosine_bell | checkerboard | tc2 | tc5 | tc6 | galewsky
    scheme: str = "plr"              # 'plr' | 'ppm' reconstruction
    limiter: str = "mc"              # 'minmod' | 'mc' | 'vanleer' | 'none'
    backend: str = "jnp"             # 'jnp' | 'pallas' RHS stencils
    ic_angle: float = 0.0            # flow-orientation angle (TC1/TC2 alpha)
    # The deck's "Numerics (TT)" pipeline stage (pdf p.7): 'tt' runs the
    # factored-panel solver tier (jaxstream.tt.sphere*) — every panel
    # field a rank-`tt_rank` factor pair, nothing (n, n) materialized.
    numerics: str = "dense"          # 'dense' | 'tt'
    tt_rank: int = 16                # factored-state rank when numerics='tt'
    # In-step Laplacian dissipation on the factored SWE's velocity
    # components (m^2/s; numerics='tt' + shallow_water only) — ordinary
    # explicit viscosity for the factored tier.  0 disables.
    tt_kappa: float = 0.0
    # Factored-step rounding: 'auto' picks 'svd' (exact truncation —
    # the stability tier; forced nonlinear flows NaN within a sim-day
    # under 'aca', DESIGN.md stability envelope) for shallow-water runs
    # and 'aca' (cross approximation — the speed tier, no
    # factorization kernels in the step) for advection/diffusion.
    tt_rounding: str = "auto"        # 'auto' | 'aca' | 'svd'
    # del^4 filter placement on the fused covariant path (nu4 > 0):
    # 'split' (the round-5 once-per-step filter kernel — the reference),
    # 'refused' (round 10: filter fused into the stage-1 kernel, 3
    # kernels + 3 routes/step; trajectories equal to split up to one
    # endpoint filter application, Galewsky day-6 gated), or 'stage'
    # (the round-4 in-stage kernel pair, kept as the parity oracle).
    nu4_mode: str = "split"          # 'split' | 'refused' | 'stage'


@dataclasses.dataclass(frozen=True)
class TimeConfig:
    dt: float = 600.0
    scheme: str = "ssprk3"
    duration_days: float = 1.0       # total integration length ...
    nsteps: int = 0                  # ... or an explicit step count (wins if > 0)


@dataclasses.dataclass(frozen=True)
class AsyncPipelineConfig:
    """Async host pipeline (``io.async_pipeline:`` block) — default off,
    and when off the run is bit-for-bit today's synchronous behavior.
    With ``enabled: true`` the segment loop double-buffers: segment k+1
    is dispatched before segment k's host work resolves, device->host
    copies start with ``copy_to_host_async`` behind the next dispatch,
    and history appends / checkpoint saves / telemetry JSONL records
    drain on a bounded background writer thread (docs/USAGE.md "Async
    host pipeline").  Written outputs are bitwise identical to the
    synchronous path — only the overlap changes."""
    enabled: bool = False
    # Backpressure bound: the writer queue blocks the main thread when
    # it already holds this many pending segments of tasks.  Host-side
    # snapshot memory stays bounded at max_pending_segments queued + 1
    # writing + 1 unresolved fetch (= 4 segments at the default)
    # regardless of how far the device runs ahead.  Must be >= 1.
    max_pending_segments: int = 2


@dataclasses.dataclass(frozen=True)
class IOConfig:
    history_path: str = "history"
    history_stride: int = 0          # steps between snapshots; 0 = off
    history_tt_rank: int = 0         # >0: TT-compress snapshots (lossy)
    checkpoint_path: str = "checkpoints"
    checkpoint_stride: int = 0
    async_pipeline: AsyncPipelineConfig = AsyncPipelineConfig()


@dataclasses.dataclass(frozen=True)
class EnsembleConfig:
    """Perturbed-IC ensemble block — the many-concurrent-simulations
    workload (Williamson TC5 / Galewsky perturbed ensembles).  With
    ``members > 1`` the run advances all members per step through the
    batched steppers (member axis folded into the kernel grid on the
    fused path; one ppermute carries every member's halo strips on the
    sharded tiers — docs/USAGE.md "Ensembles")."""
    members: int = 1          # ensemble size (1 = plain single run)
    seed: int = 0             # perturbation generator seed (deterministic)
    # Relative height-perturbation amplitude of members 1..B-1 (member 0
    # stays unperturbed): dh = amplitude * mean|h| * smooth mode.
    amplitude: float = 1.0e-3
    # Device-mesh layout for multi-device ensemble runs (round 12):
    # 'auto' = the 2-D ('panel', 'member') mesh (num_devices must be a
    # multiple of 6 — faces exchange over 'panel', members scatter over
    # 'member'); 'member' = a 1-D ('member',) mesh sharding ONLY the
    # member axis (any device count that divides `members`; zero wire
    # traffic, GSPMD path only — use_shard_map needs the panel axis).
    layout: str = "auto"      # 'auto' | 'panel_member' | 'member'


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """In-loop run telemetry (jaxstream.obs) — off by default, and when
    off the run is bit-for-bit today's behavior.  With ``interval > 0``
    the compiled segment loops compute the configured invariant ladder
    on device every ``interval`` steps into a small buffer fetched with
    ONE device->host transfer per segment (docs/USAGE.md
    "Observability")."""
    # Comma-separated metric names (jaxstream.obs.metrics.METRICS), or
    # 'default' for the model family's ladder — SWE: mass, energy,
    # [enstrophy,] h_min, h_max, max_speed, cfl, nonfinite_count.
    metrics: str = "default"
    interval: int = 0         # steps between in-loop samples; 0 = off
    sink: str = ""            # JSONL path for manifest/segment records; '' = none
    # Guard policy on a NaN/Inf sample or CFL breach:
    # 'off' | 'warn' | 'checkpoint_and_raise' | 'halt'.
    guards: str = "off"
    cfl_limit: float = 2.0    # local-CFL guard threshold
    # Testing hook: inject NaN into the metric STREAM (never the state)
    # at this global step (must be a sampled step); -1 = disabled.
    fault_step: int = -1
    # Round 20: where crash-forensics bundles land (jaxstream.obs.
    # flight).  The in-memory flight recorder is ALWAYS on (bounded
    # ring, zero sink writes in steady state); a non-empty directory
    # here additionally flushes an atomic crash bundle on HealthError /
    # unhandled exception (and the serving stack keeps a live bundle
    # re-committed at segment boundaries, so a SIGKILL still leaves a
    # readable one).  '' = no bundle dumping — byte-identical on-disk
    # behavior to round 19.  scripts/serve.py and scripts/gateway.py
    # derive a default next to their sinks (--flight-dir overrides).
    flight_dir: str = ""


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Per-stage dtype policy for the fused covariant stepper (round
    10; ``jaxstream.ops.pallas.precision`` holds the op-level
    semantics).  Defaults are all-f32 = bit-for-bit today's behavior.

    ``stage: bf16`` runs the stage kernels' flux face-average
    velocities, the PLR limiter algebra, and the strip router's
    rotation multiplies in bfloat16 — every accumulator and every
    metric term stays f32.  ``strips`` sets the inter-stage
    strip/ghost storage dtype ('auto' follows ``stage``); 16-bit
    strips halve strip HBM/wire traffic and keep exact mass
    conservation (one shared symmetrized edge value per physical
    edge).  ``carry`` selects the between-step HBM storage encoding —
    'bf16' (h and u bf16) or 'mixed16' (h int16 fixed-point about a
    static offset + u bf16, the bench's gated encoding); orthogonal to
    ``stage`` (arithmetic vs storage), the two stack.  See
    docs/USAGE.md "Precision" for measured budgets and the
    when-it-loses caveats."""
    stage: str = "f32"        # 'f32' | 'bf16' stage-kernel arithmetic
    strips: str = "auto"      # 'auto' | 'f32' | 'bf16' strip storage
    carry: str = "f32"        # 'f32' | 'bf16' | 'mixed16' carry storage


@dataclasses.dataclass(frozen=True)
class PlacementConfig:
    """Multi-chip serving placement (``serve.placement:`` block, round
    12) — default off, and when off the server is bit-for-bit the
    single-chip round-11 path.  ``mode: member`` shards the packed
    member axis across a 1-D ``('member',)`` device mesh (a B=16
    bucket on 8 chips runs 2 members/chip; zero wire traffic): the
    GSPMD-partitioned classic stepper under ``backend: jnp``, the
    fused member-fold kernels per chip on a grouped Pallas server);
    ``mode: panel`` spreads each request's 6 faces over the 2-D
    ``('panel', 'member')`` mesh through the batched-exchange ensemble
    stepper (large grids; num_devices must be a multiple of 6;
    composes with ``parallelization.overlap_exchange``).  See
    docs/USAGE.md "Serving" (multi-chip) for when each mode wins."""
    mode: str = "off"         # 'off' | 'member' | 'panel'
    # Devices the server may span; 0 = every available device of
    # device_type.  Buckets that cannot use the whole pool (the plan
    # needs equal members per chip) use the largest fitting subset.
    num_devices: int = 0
    device_type: str = "cpu"  # 'cpu' (virtual devices) | 'tpu' | 'gpu'


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Continuous-batching ensemble server (``jaxstream.serve``, round
    11) — scenario requests packed into the member axis the way LLM
    servers pack prompts into a batch.  The server keeps one compiled
    masked-segment stepper warm per batch-size bucket (steady-state
    serving triggers ZERO recompiles once the bucket set is warm; the
    persistent compile cache makes the warmup itself cheap across
    restarts) and refills a finished member's slot from the bounded
    request queue at the next segment boundary (docs/USAGE.md
    "Serving")."""
    # Comma-separated batch-size buckets.  A batch's size is the
    # smallest bucket >= the number of packable requests, so the whole
    # serving life of a deployment compiles len(buckets) segment
    # steppers per scenario group and nothing else.
    buckets: str = "1,4,16"
    # Steps per compiled masked segment — the refill granularity: a
    # finished member idles at most segment_steps - 1 steps before its
    # slot is refilled.  Smaller = tighter packing, more host
    # boundaries.
    segment_steps: int = 8
    # Bounded request queue (admission control): submit raises
    # QueueFull at capacity instead of buffering unboundedly.
    queue_capacity: int = 64
    # Per-request zarr result stores are written under this directory
    # (streamed through the async BackgroundWriter); '' = results are
    # only retained in memory (server.results).
    output_dir: str = ""
    # Serving telemetry JSONL (obs.sink format: 'serve' records with
    # slot occupancy + queue depth); '' = none.
    sink: str = ""
    # On a member's nonfinite state: 'evict' (default — fail only that
    # request, refill the slot, keep the batch alive), 'halt' (raise,
    # stopping the server), 'off' (no per-member guard).
    guards: str = "evict"
    # Admission control driven by the HealthMonitor: once this many
    # guard events have been recorded the server refuses NEW requests
    # (AdmissionRefused) — a deployment that keeps blowing up members
    # should fail fast, not accept more traffic.  0 disables.
    max_guard_events: int = 16
    # Testing hook (pairs with observability.fault_step): mark this
    # member's health count bad when its own step count reaches
    # fault_step — injected into the monitor STREAM on the host, never
    # the state — so the evict->refill path is testable without
    # integrating a real blowup.  -1 = disabled.
    fault_member: int = -1
    # Donate the segment carry (XLA aliases input/output state).
    donate: bool = True
    # Round 17: request-scoped tracing (jaxstream.obs.trace).  Every
    # admitted request gets a deterministic trace id and its lifecycle
    # phases (queue wait, pack, per-segment compute/host-wait,
    # finalize/fetch/flush) land as typed 'span' records in the serve
    # sink, reassemblable into a tree whose leaf durations sum to the
    # request's end-to-end latency (docs/USAGE.md "Operator view").
    # Default off = the sink stream is byte-identical to the untraced
    # round-14 records (no span records, no trace fields).
    trace: bool = False
    # Round 19 (performance observatory): poll device.memory_stats()
    # at every segment boundary (the autoscale-tick cadence) into the
    # per-chip jaxstream_device_memory_* gauges on /v1/metrics and
    # typed 'memory' sink records.  Off = the watcher is never
    # constructed — zero polling, sink byte-identical to round 18.
    memory_watch: bool = False
    # Round 19: measure every warm bucket's segment executable with
    # XLA's cost/memory analysis (ahead-of-time compile) so its cost
    # stamp carries real footprint bytes + the flops-vs-analytic
    # ratio, and the bucket plan gains the advisory headroom_frac.
    # COSTS one extra XLA compile per bucket at warmup (the measured
    # compile IS the recorded compile_seconds); off = stamps carry
    # the analytic half + warmup wall seconds only.
    cost_stamps: bool = False
    # Round 12: orography (the TC5 mountain) rides the batch as a
    # traced per-member field (zeros for the flat families), so
    # tc2/tc5/tc6/galewsky requests pack into ONE bucket in strict
    # queue FIFO order (bitwise-equal to the baked-static stepper,
    # tested).  `true` restores the round-11 batching groups (orography
    # baked as a stepper static; group-local FIFO; the fused
    # member-fold kernels apply where they compile) — the parity mode,
    # and required by placement mode 'panel' (the shard_map stepper
    # bakes orography per device).
    group_by_orography: bool = False
    # Round 21 (warm pools): directory of disk-backed serialized bucket
    # executables (jaxstream.serve.warmpool).  A restarted or freshly
    # spawned server LOADS its masked-segment executables from here
    # instead of recompiling — the degradation ladder is full AOT
    # executable -> serialized StableHLO -> persistent compile cache ->
    # cold compile, every rung a typed 'warmpool' sink record.  '' =
    # off (byte-identical warmup to round 20).
    warm_pool: str = ""
    # Round 21: jax persistent-compilation-cache directory, the warm
    # pool's third rung.  A JAX_COMPILATION_CACHE_DIR set from outside
    # wins: the cache stays there and this only switches the rung on.
    # '' = rung disabled.
    compile_cache: str = ""
    # Round 21: background speculative compilation of ADJACENT plans
    # (the next configured bucket up/down from the active cap) on a
    # worker thread, nudged by resize()/autoscale — a later resize to
    # a not-yet-warm bucket stops paying jit at a segment boundary.
    # Requires warm_pool (the speculated executables persist there).
    speculate: bool = False
    # Round 21: the first CONSUMER of the round-19 advisory
    # headroom_frac — resize() and speculative compilation REFUSE a
    # bucket whose stamped per-chip footprint would leave less than
    # this headroom fraction (HeadroomRefused + a typed 'headroom'
    # sink record).  The default 0.0 refuses only footprints that
    # exceed per-chip capacity outright; advisory stays advisory for
    # request admission.  Enforcement needs a stamped plan
    # (serve.cost_stamps + serve.memory_watch) — unstamped plans are
    # never refused.
    min_headroom_frac: float = 0.0
    # Multi-chip placement sub-block (round 12; default mode 'off' =
    # the single-chip path, byte-for-byte).
    placement: PlacementConfig = PlacementConfig()


@dataclasses.dataclass(frozen=True)
class DAConfig:
    """Ensemble data assimilation (``jaxstream.da``, round 18) — the
    EnKF cycle on the batched ensemble steppers.  ``cycles: 0`` (the
    default) disables cycling entirely; with ``cycles > 0`` the
    drivers (:func:`jaxstream.da.run_cycle` in-process,
    :func:`jaxstream.da.run_cycle_gateway` through the HTTP gateway,
    ``scripts/assimilate.py``) run that many forecast->observe->
    analyze rounds against a hidden truth run.  Ensemble size/seed/
    amplitude come from the ``ensemble:`` block; the plan layer
    rejects illegal compositions statically (members >= 2, dense f32
    single-device tiers, no temporal blocking — docs/USAGE.md "Data
    assimilation")."""
    cycles: int = 0           # assimilation cycles; 0 = da off
    cycle_steps: int = 8      # forecast steps between analyses
    nstations: int = 64       # seeded h-observing stations
    obs_seed: int = 7         # station draw + obs noise seed
    obs_sigma: float = 1.0    # observation error std (m of h)
    inflation: float = 1.05   # multiplicative prior inflation
    # Gaspari-Cohn localization half-width in km; 0 = OFF (the pure
    # B x B ensemble-space solve — fine for dense networks/large B,
    # spurious at small B; see USAGE "when EnKF loses").
    localization_km: float = 0.0
    # Ensemble-statistics guards over the cycle (spread collapse /
    # filter divergence): 'off' | 'warn' | 'halt'.
    guards: str = "warn"
    # Posterior spread below this fraction of the INITIAL spread
    # trips the spread_collapse guard.  A healthy analysis contracts
    # spread a lot (to ~ the posterior error) — the guard is for the
    # runaway contraction that leaves the filter rejecting all future
    # observations, hence the deliberately low default.
    spread_collapse_factor: float = 0.01
    # Prior RMSE above this multiple of prior spread trips the
    # filter_divergence guard.
    divergence_ratio: float = 10.0
    sink: str = ""            # JSONL path for per-cycle 'da' records


@dataclasses.dataclass(frozen=True)
class Config:
    grid: GridConfig = GridConfig()
    parallelization: ParallelConfig = ParallelConfig()
    physics: PhysicsConfig = PhysicsConfig()
    model: ModelConfig = ModelConfig()
    time: TimeConfig = TimeConfig()
    io: IOConfig = IOConfig()
    ensemble: EnsembleConfig = EnsembleConfig()
    observability: ObservabilityConfig = ObservabilityConfig()
    precision: PrecisionConfig = PrecisionConfig()
    serve: ServeConfig = ServeConfig()
    da: DAConfig = DAConfig()


_SECTIONS = {
    "grid": GridConfig,
    "parallelization": ParallelConfig,
    "physics": PhysicsConfig,
    "model": ModelConfig,
    "time": TimeConfig,
    "io": IOConfig,
    "ensemble": EnsembleConfig,
    "observability": ObservabilityConfig,
    "precision": PrecisionConfig,
    "serve": ServeConfig,
    "da": DAConfig,
}


#: Dataclass-typed fields nested inside a section (config sub-blocks);
#: their YAML value is a mapping built recursively by _build_section.
_NESTED_SECTIONS = {
    "AsyncPipelineConfig": AsyncPipelineConfig,
    "PlacementConfig": PlacementConfig,
}


def _suggest(unknown, valid) -> str:
    """Did-you-mean tail for unknown-key errors: the closest valid
    name per typo (difflib ratio), so a plan-layer config mistake
    (``overlap_exchang``, ``temporal_blocks``) names its fix."""
    import difflib

    hints = []
    for k in sorted(unknown):
        close = difflib.get_close_matches(k, valid, n=1, cutoff=0.6)
        if close:
            hints.append(f"{k!r} -> did you mean {close[0]!r}?")
    return (" (" + "; ".join(hints) + ")") if hints else ""


def _build_section(cls, data: dict):
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(
            f"unknown {cls.__name__} keys {sorted(unknown)}; valid: "
            f"{sorted(fields)}{_suggest(unknown, fields)}"
        )
    # Coerce to the declared field types: YAML 1.1 parses exponent
    # literals without a sign ("1.0e14") as *strings*, and users write
    # "6" where an int is declared — both must land as numbers.
    coerced = {}
    for k, v in data.items():
        ftype = fields[k].type
        ftype = getattr(ftype, "__name__", ftype)  # str or type object
        if ftype in _NESTED_SECTIONS:
            # Recurse OUTSIDE the coercion try: a bad key/value inside
            # the nested mapping must surface _build_section's own
            # message (which names the unknown key and the valid set),
            # not a generic "expects a <section>" rewrap.
            nested = _NESTED_SECTIONS[ftype]
            if isinstance(v, nested):
                pass
            elif isinstance(v, dict) or v is None:
                v = _build_section(nested, v or {})
            else:
                raise ValueError(
                    f"{cls.__name__}.{k} expects a {ftype} mapping, "
                    f"got {v!r}"
                )
            coerced[k] = v
            continue
        try:
            if ftype == "float" and not isinstance(v, float):
                v = float(v)
            elif ftype == "int" and not isinstance(v, (int, bool)):
                v = int(v)
        except (TypeError, ValueError):
            raise ValueError(
                f"{cls.__name__}.{k} expects a {ftype}, got {v!r}"
            ) from None
        coerced[k] = v
    return cls(**coerced)


def load_config(source: Any = None) -> Config:
    """Build a Config from a YAML path, a YAML string, a dict, or None."""
    if source is None:
        return Config()
    if isinstance(source, Config):
        return source
    if isinstance(source, dict):
        data = source
    else:
        text = str(source)
        if os.path.exists(text):
            with open(text) as fh:
                data = yaml.safe_load(fh) or {}
        else:
            loaded = yaml.safe_load(text)
            if not isinstance(loaded, dict):
                raise ValueError(
                    f"config source {text!r} is neither an existing file path "
                    f"nor a YAML mapping"
                )
            data = loaded
    kwargs = {}
    unknown = set(data) - set(_SECTIONS)
    if unknown:
        raise ValueError(
            f"unknown config sections {sorted(unknown)}; valid: "
            f"{sorted(_SECTIONS)}{_suggest(unknown, _SECTIONS)}"
        )
    for name, cls in _SECTIONS.items():
        if name in data:
            kwargs[name] = _build_section(cls, data[name] or {})
    return Config(**kwargs)
