"""End-to-end simulation driver: the framework shell around the solvers.

This is the rebuilt form of the reference's implied top-level run loop
(SURVEY.md §3.4): ``load config.yaml -> geometry [zarr] -> initial
conditions -> setup_sharding() -> timestep loop (no recompilation) with
periodic history [zarr] / restart [Orbax] -> analysis``.  The reference
shows only the ``setup_sharding`` method of its unseen driver class
(``/root/reference/JAX-DevLab-Examples.py:19-85``); :class:`Simulation`
is that class built out in full, config-driven end to end.

Design notes (TPU-first):
  * The inner loop is segments of ``lax.fori_loop`` under one cached
    ``jit`` — host contact only at history/checkpoint boundaries, so the
    per-step path is pure device execution ("no recompilation during
    timestepping", deck p.10).
  * Sharding is transparent: with ``num_devices > 1`` the state is
    device_put with a ``('panel','y','x')`` NamedSharding (GSPMD path) or
    stepped inside ``shard_map`` with explicit ``lax.ppermute`` halos
    (``use_shard_map: true``); the numerics are byte-identical either way.
  * Restart is automatic: if the checkpoint directory has a saved step,
    the run resumes from it (sharding-aware restore).
"""

from __future__ import annotations

import functools
import logging
import math
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .config import Config, load_config
from .geometry.cubed_sphere import build_grid
from .io.async_pipeline import BackgroundWriter, HostFetch
from .io.checkpoint import CheckpointManager
from .io.history import HistoryWriter, save_geometry
from .models.advection import TracerAdvection
from .models.diffusion import ThermalDiffusion
from .models.shallow_water import ShallowWater
from .obs import flight
from .obs import metrics as obs_metrics
from .obs import trace as obs_trace
from .obs.monitor import HealthMonitor
from .obs.sink import TelemetrySink, run_manifest
from .parallel.mesh import (setup_ensemble_sharding, setup_sharding,
                            shard_ensemble_state, shard_state)
from .parallel.sharded_model import make_stepper_for
from .plan import build_proof, plan_for
from .plan import rules as plan_rules
from .physics import initial_conditions as ics
from .stepping import (integrate, integrate_with_metrics, jit_integrate,
                       time_carry)
from .utils import diagnostics as diag
from .utils import jax_compat
from .utils.logging import get_logger

__all__ = ["Simulation", "run_from_config"]

#: The prognostic keys of every dense state family — what the in-loop
#: metric functions see (fused-stepper strip carries are dropped first).
_PROG_KEYS = ("h", "u", "v", "q", "T")


class _ObsRuntime:
    """Per-Simulation telemetry wiring (built by ``_build_obs``)."""

    def __init__(self, cfg, metric_set, metric_fn, monitor, sink, ref):
        self.cfg = cfg                  # the ObservabilityConfig block
        self.ms = metric_set
        self.metric_fn = metric_fn      # fn(loop_carry, t) -> (k,) vector
        self.monitor = monitor
        self.sink = sink
        self.ref = ref                  # step-0 metric values (np, (k,))
        self.wrote_initial = False

log = get_logger(__name__)


def _run_tasks(tasks):
    """One async-pipeline boundary's writes, in order, as ONE writer
    task — so the queue bound counts segments, and a failure mid-list
    aborts the boundary's remaining writes (fail-stop within the
    boundary, matching the writer's fail-stop across boundaries)."""
    for fn, args in tasks:
        fn(*args)


_DTYPES = {"float32": jnp.float32, "float64": jnp.float64, "bfloat16": jnp.bfloat16}

#: initial-condition name -> model family it drives
IC_FAMILY = {
    "tc1": "advection",
    "cosine_bell": "advection",
    "checkerboard": "diffusion",
    "tc2": "shallow_water",
    "tc5": "shallow_water",
    "tc6": "shallow_water",
    "galewsky": "shallow_water",
}


class Simulation:
    """Config -> grid -> model+IC -> sharding -> run loop -> outputs."""

    def __init__(self, config: Any = None):
        self.config: Config = load_config(config)
        cfg = self.config
        # Round 16: resolve the capability plan FIRST — illegal
        # feature compositions are rejected statically by the
        # declarative rule table (jaxstream.plan.rules), before any
        # grid build, device placement or trace, with the same pointer
        # messages the legacy scattered raises carried.
        self.plan = plan_for(cfg)
        self.proof = None
        dtype = _DTYPES[cfg.grid.dtype]
        mcfg = cfg.model
        halo = cfg.grid.halo
        if mcfg.scheme == "ppm":
            halo = max(halo, 3)
        self.grid = build_grid(
            cfg.grid.n, halo=halo, radius=cfg.grid.radius, dtype=dtype,
            metrics=cfg.grid.metrics,
        )
        # The deck's "Numerics (TT)" tier (pdf p.7): factored-panel
        # solvers behind the same config/IO surface.
        self._tt_keys = None
        self._tt_hs = None
        self.t = 0.0
        self.step_count = 0
        self.setup = None
        self.members = cfg.ensemble.members
        self._classic_run = None
        if self.members < 1:
            raise ValueError(
                f"ensemble.members must be >= 1, got {self.members}")
        if self.members > 1 and cfg.model.numerics != "dense":
            raise ValueError(
                "ensemble.members > 1 runs the dense tier only; set "
                "model.numerics: dense (the factored TT state has no "
                "batched stepper yet)")
        # (Round 11: ensemble history/checkpoints are supported — the
        # member-batched arrays are written as-is and extracted
        # per-member via io.history.extract_member /
        # HistoryWriter.read_member / CheckpointManager.restore_member;
        # member 0 byte-matches an unbatched run on the vmapped path.)
        if cfg.model.numerics == "tt":
            self.model = None
            self.state, self._step = self._build_tt()
        elif cfg.model.numerics != "dense":
            raise ValueError(
                f"model.numerics={cfg.model.numerics!r}; valid: 'dense' "
                "(production solvers) or 'tt' (factored-panel tier)")
        else:
            self.model, self.state = self._build_model_and_state()

            par = cfg.parallelization
            # The sharded tiers run f32 numerics: hand them the
            # precision spec ONLY when they are the executing path
            # (num_devices > 1) so make_stepper_for rejects a non-f32
            # policy with its pointer.  Single-device runs ride the
            # fused stepper below (the classic _step built here is its
            # fallback, and the fused-or-raise check at the end of this
            # constructor guards that case).
            pspec = ({"stage": cfg.precision.stage,
                      "strips": cfg.precision.strips}
                     if par.num_devices > 1 else None)
            if self.members > 1:
                self.state = self._build_ensemble_state()
                if par.num_devices > 1:
                    # ensemble.layout (round 12): 'auto' = the 2-D
                    # ('panel', 'member') mesh; 'member' = the 1-D
                    # member-only mesh (any device count dividing the
                    # ensemble; GSPMD path, zero wire traffic) — the
                    # same layout the serving tier's member-parallel
                    # placement runs on.
                    self.setup = setup_ensemble_sharding(
                        cfg, self.members, layout=cfg.ensemble.layout)
                    self.state = shard_ensemble_state(self.setup,
                                                      self.state)
                self._step = make_stepper_for(
                    self.model, self.setup, self.state, cfg.time.dt,
                    cfg.time.scheme, temporal_block=par.temporal_block,
                    ensemble=self.members, precision=pspec,
                )
            else:
                if par.num_devices > 1:
                    self.setup = setup_sharding(cfg)
                    self.state = shard_state(self.setup, self.state)
                self._step = make_stepper_for(
                    self.model, self.setup, self.state, cfg.time.dt,
                    cfg.time.scheme, temporal_block=par.temporal_block,
                    precision=pspec,
                )
        # Single-device Pallas SWE runs use the fused extended-state
        # SSPRK3 stepper (the bench flagship): extend/restrict happen once
        # per compiled segment, so the strip carry stays on device between
        # I/O strides.  Sharded runs are handled by make_stepper_for.
        self._fused_step = None
        self._fused_prep = None
        # Decode hook for 16-bit carry encodings (precision.carry):
        # applied to every restrict_state exit so self.state, history,
        # checkpoints, diagnostics and the in-loop metrics all see
        # absolute f32 fields; None = identity (the f32 carry).
        self._fused_post = None
        m = self.model
        # nu4 > 0 is fused only where the model declares support (the
        # covariant model's two-kernel del^4 stage pair).
        tb = cfg.parallelization.temporal_block
        pkw, p_enc, p_dec = self._resolve_precision()
        if (self.members > 1 and self.setup is None
                and cfg.time.scheme == "ssprk3"
                and getattr(m, "backend", "").startswith("pallas")
                and getattr(m, "nu4", 0.0) == 0.0
                and hasattr(m, "ensemble_compact_state")):
            # Batched ensemble fast path: the member axis folds into the
            # stage kernels' grid, so all B members ride one kernel
            # launch per stage (jaxstream.ops.pallas.swe_cov).  A build
            # failure raises: a pallas run never silently drops to the
            # vmapped classic path.
            self._fused_step = m.make_fused_step(
                cfg.time.dt, temporal_block=tb, ensemble=self.members,
                **pkw)
            if p_enc is not None:
                # Strip narrowing only (carry encodings are
                # rejected for ensembles in _resolve_precision).
                self._fused_prep = (
                    lambda s, _e=p_enc: _e(m.ensemble_compact_state(s)))
            else:
                self._fused_prep = m.ensemble_compact_state
            log.info("using batched ensemble fused SSPRK3 stepper "
                     "(%d members per kernel launch)", self.members)
        elif (self.members == 1 and self.setup is None
                and cfg.time.scheme == "ssprk3"
                and getattr(m, "backend", "").startswith("pallas")
                and (getattr(m, "nu4", 0.0) == 0.0
                     or getattr(m, "fused_supports_nu4", False))
                and hasattr(m, "make_fused_step")):
            # The stepper and its carry-prep are a matched pair: pick
            # both here so they cannot drift apart.
            def _mk_fused():
                """Fused step honoring temporal_block where the
                model knows the knob (covariant multistep factory);
                exact k-step fusion via stepping.blocked otherwise."""
                try:
                    return m.make_fused_step(cfg.time.dt,
                                             temporal_block=tb, **pkw)
                except TypeError:
                    if pkw:
                        # The precision/nu4_mode kwargs have no
                        # generic fallback — a model that doesn't
                        # know them can't honor the config.
                        raise
                    step = m.make_fused_step(cfg.time.dt)
                    if tb > 1:
                        from .stepping import blocked

                        step = blocked(step, tb, cfg.time.dt)
                        step.steps_per_call = tb
                    return step

            if hasattr(m, "compact_state"):
                self._fused_step = _mk_fused()
                if p_enc is not None:
                    self._fused_prep = (
                        lambda s, _e=p_enc: _e(m.compact_state(s)))
                    self._fused_post = p_dec
                else:
                    self._fused_prep = m.compact_state
                log.info("using compact fused SSPRK3 stepper "
                         "(interior-only carry)")
            else:
                if pkw.get("precision") or p_enc is not None:
                    raise ValueError(
                        "precision: block needs the compact-carry "
                        "fused stepper (this model only has the "
                        "extended-state form)")
                self._fused_step = _mk_fused()
                self._fused_prep = functools.partial(
                    m.extend_state, with_strips=True)
                log.info("using fused extended-state SSPRK3 stepper")
        if (pkw or p_enc is not None) and self._fused_step is None:
            plan_rules.fail("precision-needs-fused-path")
        # The run's proof stamp: rules verdict + schedule fingerprint +
        # enumerated-matrix coverage for the stepper that will actually
        # execute (re-resolve the tier where the gate above picked no
        # fused stepper, so the stamp is honest).
        actual = self.plan
        if actual.tier == "fused" and self._fused_step is None:
            import dataclasses as _dc

            actual = plan_rules.normalize(
                _dc.replace(actual, tier="classic"))
        self.proof = build_proof(actual)
        self._segment_cache: Dict[int, Callable] = {}

        # Async host pipeline (io.async_pipeline, round 9): the writer
        # thread is created lazily on the first async run(); _host_wait
        # accumulates the host-side I/O seconds that blocked the next
        # dispatch since the last telemetry record (both modes report
        # it, so the overlap is visible in the sink).
        self._writer: Optional[BackgroundWriter] = None
        self._host_wait = 0.0
        self._t_carry = None
        # End stamp of the last segment's sim.sync: the next dispatch
        # closes the sim.boundary it opens, across run() calls.
        self._sync_end: Optional[float] = None

        io = cfg.io
        self.history: Optional[HistoryWriter] = None
        self.checkpoints: Optional[CheckpointManager] = None
        if io.history_stride > 0:
            save_geometry(io.history_path + ".geometry", self.grid)
            hist_rank = io.history_tt_rank or None
            if self._tt_keys is not None and hist_rank:
                log.info("numerics='tt': state snapshots are already "
                         "factored; ignoring io.history_tt_rank")
                hist_rank = None
            self.history = HistoryWriter(
                io.history_path,
                attrs={"model": mcfg.name, "ic": mcfg.initial_condition,
                       "numerics": mcfg.numerics,
                       # Marks the fields member-batched so read_member
                       # can slice the right axis (round 11).
                       "members": self.members},
                tt_rank=hist_rank,
            )
        # Crash forensics (round 20): dump-once latch + resume lineage.
        # Lineage is recorded only when this run actually resumed from
        # a checkpoint AND a committed crash bundle exists in the
        # configured flight dir — the prior incarnation's black box.
        self._flight_dumped = False
        self._resume_lineage: Optional[dict] = None
        if io.checkpoint_stride > 0:
            self.checkpoints = CheckpointManager(io.checkpoint_path)
            self._maybe_resume()
            if self.step_count > 0:
                self._resume_lineage = self._find_lineage()
        # Telemetry last: the metric reference must see the post-resume
        # state, and the guard's postmortem callback needs the
        # checkpoint manager.
        self._obs = self._build_obs()

    # ------------------------------------------------------------------ build
    def _build_obs(self):
        """Wire the ``observability:`` block into this run (or None).

        Builds the resolved :class:`jaxstream.obs.metrics.MetricSet`,
        the loop-carry metric function the instrumented segments trace,
        the :class:`HealthMonitor` (policy != 'off') and the JSONL sink
        (process 0 only), and records the step-0 reference values the
        drift columns are measured against (on a resumed run that
        reference is the resume point).
        """
        o = self.config.observability
        if o.interval <= 0:
            return None
        if self._tt_keys is not None:
            raise ValueError(
                "observability.interval > 0 requires model.numerics: "
                "dense (the factored TT state has no in-loop metric "
                "path; eager Simulation.diagnostics() still works)")
        tb = self.config.parallelization.temporal_block
        if o.interval % tb:
            raise ValueError(
                f"observability.interval={o.interval} must be a multiple "
                f"of parallelization.temporal_block={tb} (samples are "
                "taken at stepper-call boundaries)")
        # Segments are gcd(history_stride, checkpoint_stride) steps long
        # (Simulation.run); an interval longer than that would truncate
        # every segment's sample count to ZERO and silently disable the
        # metrics AND the guards the user just configured — reject the
        # misconfiguration instead.
        io = self.config.io
        strides = [s for s in (io.history_stride, io.checkpoint_stride)
                   if s > 0]
        seg = math.gcd(*strides) if strides else 0
        if seg and o.interval > seg:
            raise ValueError(
                f"observability.interval={o.interval} exceeds the "
                f"compiled segment length {seg} (= gcd of "
                f"io.history_stride/io.checkpoint_stride): every segment "
                "would take zero samples and the guards could never "
                "fire; lower the interval or raise the io strides")
        p, tc = self.config.physics, self.config.time
        ex = {k: v for k, v in self.state.items() if k in _PROG_KEYS}
        # Ensemble runs with guards on get one nonfinite row PER member
        # appended, so a guard event (and the postmortem checkpoint it
        # triggers) names the offending member instead of only an
        # all-member count (round 11).
        ms = obs_metrics.build_metric_set(
            self.grid, self.model, ex, o.metrics, tc.dt, p.gravity,
            member_rows=(self.members > 1 and o.guards != "off"))
        if self._fused_step is not None:
            m = self.model
            if self._fused_post is not None:
                # 16-bit carry: metrics must see absolute f32 fields.
                loop_prep = (lambda y, _m=m, _p=self._fused_post:
                             _p(_m.restrict_state(y)))
            else:
                loop_prep = m.restrict_state
        else:
            def loop_prep(y):
                return {k: v for k, v in y.items() if k in _PROG_KEYS}

        def metric_fn(y, t):
            del t
            return ms.values(loop_prep(y))

        monitor = None
        if o.guards != "off":
            monitor = HealthMonitor(ms.names, o.guards, o.cfl_limit,
                                    on_breach=self._postmortem_checkpoint)
        sink = None
        if o.sink and jax.process_index() == 0:
            cfg = self.config
            manifest = run_manifest(
                ms.names, o.interval, o.guards,
                config={
                    "grid_n": cfg.grid.n, "dtype": cfg.grid.dtype,
                    "dt": tc.dt, "scheme": tc.scheme,
                    "initial_condition": cfg.model.initial_condition,
                    "numerics": cfg.model.numerics,
                    "members": self.members,
                    "num_devices": cfg.parallelization.num_devices,
                    "use_shard_map": cfg.parallelization.use_shard_map,
                    "temporal_block": tb,
                    # Round 16: the run's capability plan + proof
                    # verdict ride the manifest so telemetry names the
                    # verified execution strategy.
                    "plan": self.plan.key(),
                    "proof": (self.proof.to_json()
                              if self.proof is not None else None),
                })
            sink = TelemetrySink(o.sink, manifest)
            if self._resume_lineage is not None:
                # Typed lineage stamp (round 20): this run descends
                # from the named crash bundle's incident; the
                # postmortem CLI joins the two files on it.  Only
                # written when a resume really happened AND a committed
                # bundle exists — otherwise the sink stays
                # byte-identical to round 19.
                sink.write({
                    "kind": "resume",
                    "bundle": self._resume_lineage["bundle"],
                    "checkpoint_step":
                        self._resume_lineage["checkpoint_step"],
                    "step": self.step_count,
                    "path": self._resume_lineage["path"],
                })
        # Step-0 reference for the drift columns: one eager evaluation
        # of the metric vector on the initial (or resumed) state.
        ref = np.asarray(jax.device_get(jax.jit(ms.values)(ex)))
        log.info("observability: %d metrics every %d steps (guards=%s%s)",
                 ms.k, o.interval, o.guards,
                 f", sink={o.sink}" if o.sink else "")
        return _ObsRuntime(o, ms, metric_fn, monitor, sink, ref)

    def _resolve_precision(self):
        """``precision:`` + ``model.nu4_mode`` config -> fused-stepper
        kwargs and carry encode/decode hooks.

        Returns ``(kwargs, encode, decode)``: ``kwargs`` feed
        ``make_fused_step`` (``precision=`` stage/strips policy,
        ``nu4_mode=``, and the ``carry_dtype``/``h_offset``/``h_scale``
        encoding triple); ``encode`` wraps the carry prep, ``decode``
        every carry exit (both None for the f32 carry).  All-default
        config returns ``({}, None, None)`` — the stepper factories are
        called exactly as before, bit-for-bit.  The mixed16 offset is
        the initial state's h mid-range, the same choice bench.py's
        gated mixed16 variant makes; re-encoding at segment boundaries
        is idempotent (round-to-grid of an on-grid value), so segment
        length never changes the trajectory.
        """
        from .ops.pallas.precision import (encode_strips,
                                           resolve_stage_precision)

        pcfg = self.config.precision
        kw = {}
        if self.config.model.nu4_mode != "split":
            kw["nu4_mode"] = self.config.model.nu4_mode
        if pcfg.stage != "f32" or pcfg.strips not in ("auto", "f32"):
            kw["precision"] = {"stage": pcfg.stage, "strips": pcfg.strips}
        # Under a 16-bit strips policy the stage kernels EMIT bf16
        # strips, so the initial carry's strips must be narrowed before
        # the jitted segment loop (fori_loop carry types are fixed);
        # composed below with the carry encoding when both are on.
        pol = resolve_stage_precision(kw.get("precision"))
        narrow = ((lambda y, _p=pol: encode_strips(y, _p))
                  if pol is not None and pol.strips == "bf16" else None)
        if pcfg.carry == "f32":
            return kw, narrow, None
        if pcfg.carry not in ("bf16", "mixed16"):
            raise ValueError(
                f"precision.carry={pcfg.carry!r}; valid: 'f32', 'bf16', "
                "'mixed16'")
        if self.members > 1:
            plan_rules.fail("carry-needs-single-member")
        m = self.model
        if m is None or not hasattr(m, "encode_carry"):
            plan_rules.fail("carry-needs-covariant")
        import jax.numpy as jnp

        h = self.state["h"]
        if pcfg.carry == "mixed16":
            # bench.py's gated encoding, ONE shared definition.
            from .ops.pallas.precision import mixed16_encoding

            cd, off, hs = mixed16_encoding(h)
        else:
            # bf16 h-anomaly + bf16 u: the wider-mass-band encoding
            # (demoted from bench's default gate; kept for experiments).
            off = float(0.5 * (float(jnp.min(h)) + float(jnp.max(h))))
            cd, hs = (jnp.bfloat16, jnp.bfloat16), 1.0
        kw.update(carry_dtype=cd, h_offset=off, h_scale=hs)
        if narrow is not None:
            enc = lambda s: narrow(m.encode_carry(s, cd, off, hs))
        else:
            enc = lambda s: m.encode_carry(s, cd, off, hs)
        dec = lambda s: m.decode_carry(s, off, hs)
        return kw, enc, dec

    def _postmortem_checkpoint(self, event=None):
        """'checkpoint_and_raise' breach callback: save the CURRENT
        (possibly corrupt) state for inspection — the HealthError's
        last-good step is the restart target, this save is evidence.
        ``event``: the guard event (the monitor passes it when the
        callback accepts one); its ``member`` attribution — when the
        breach names one ensemble member — is recorded in the
        checkpoint's ``meta`` so the postmortem says WHICH member blew
        up (round 11).

        Async-pipeline aware: queued background saves are drained FIRST
        (the Orbax manager is used serially — writer FIFO, then this),
        and under the async loop ``self.state`` is the latest
        *dispatched* segment's output, possibly still in flight — the
        save blocks on it, which is exactly what "current state" means
        once the pipeline runs ahead."""
        if self.checkpoints is None:
            log.warning(
                "guard policy 'checkpoint_and_raise' with no checkpoint "
                "manager (io.checkpoint_stride is 0) — raising without "
                "a postmortem save")
            return
        if self._writer is not None and self._writer.alive:
            try:
                self._writer.flush()
            except Exception as e:  # the postmortem save must still run
                log.warning("async writer flush before postmortem failed "
                            "(%s: %s)", type(e).__name__, e)
        t = self.t
        if self._t_carry is not None:
            try:
                t = float(jax.device_get(self._t_carry))
            except Exception:
                pass
        member = (event or {}).get("member")
        self.checkpoints.save(
            self.step_count, self.state, t,
            meta={"postmortem": True, "member": member})
        log.warning("guard breach: postmortem checkpoint saved at step "
                    "%d%s", self.step_count,
                    f" (member {member})" if member is not None else "")

    # ------------------------------------------------- crash forensics
    def _find_lineage(self) -> Optional[dict]:
        """The latest committed crash bundle in the configured flight
        dir, verified readable — the prior incarnation this resumed
        run descends from.  None when no flight dir is configured, no
        bundle exists, or the newest bundle is torn (a torn black box
        must not block the restart; the postmortem CLI reports it)."""
        fdir = flight.resolve_flight_dir(self.config)
        bdir = flight.latest_bundle(fdir) if fdir else None
        if bdir is None:
            return None
        try:
            manifest, _ = flight.read_bundle(bdir)
        except flight.TornBundleError as e:
            log.warning("resume: latest crash bundle %s is torn (%s); "
                        "resuming without lineage", bdir, e)
            return None
        return {"bundle": manifest["bundle_id"], "path": bdir,
                "checkpoint_step": self.step_count}

    def _flight_dump(self, reason: str) -> None:
        """Flush the flight ring as an atomic crash bundle + typed
        ``flight``/``crash`` sink records.  Once per incident (the
        first failure's evidence must not be overwritten by unwind
        noise); no-op without ``observability.flight_dir``; never
        raises (forensics must not mask the in-flight exception)."""
        if self._flight_dumped:
            return
        fdir = flight.resolve_flight_dir(self.config)
        if not fdir:
            return
        self._flight_dumped = True
        try:
            from .utils import jax_compat

            cfg = self.config
            ckpt = None
            if self.checkpoints is not None:
                step = self.checkpoints.latest_step()
                if step is not None:
                    ckpt = {"step": step, "path": self.checkpoints.path}
            writer = flight.BundleWriter(fdir)
            writer.commit(
                reason,
                config={"grid_n": cfg.grid.n, "dt": cfg.time.dt,
                        "members": self.members,
                        "step": self.step_count,
                        "guards": cfg.observability.guards},
                proofs={"run": (self.proof.to_json()
                                if self.proof is not None else None)},
                device_memory=jax_compat.device_memory_stats(
                    jax.devices()[0]),
                checkpoint=ckpt)
            obs = self._obs
            if obs is not None and obs.sink is not None:
                events, threads, dropped = flight.RECORDER.dump()
                obs.sink.write({
                    "kind": "flight", "events": len(events),
                    "threads": len(threads), "dropped": dropped})
                obs.sink.write({
                    "kind": "crash", "bundle": writer.bundle_id,
                    "path": writer.path, "reason": reason})
        except Exception as e:
            log.warning("flight bundle dump failed (%s: %s)",
                        type(e).__name__, e)

    def _ensure_writer(self) -> BackgroundWriter:
        if self._writer is None or not self._writer.alive:
            self._writer = BackgroundWriter(
                self.config.io.async_pipeline.max_pending_segments)
        return self._writer

    def close(self):
        """Release background resources: drain and join the async
        writer thread, close the telemetry sink.  Idempotent.  Call it
        (or use the Simulation as a context manager) when done with a
        run whose ``io.async_pipeline.enabled`` is true — the writer is
        a daemon thread, so skipping close leaks no process, but the
        thread-hygiene tests hold this to zero."""
        if self._writer is not None:
            w, self._writer = self._writer, None
            w.close()
        if self._obs is not None and self._obs.sink is not None:
            self._obs.sink.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _build_model_and_state(self):
        cfg = self.config
        m, p, g = cfg.model, cfg.physics, self.grid
        name = m.initial_condition
        family = IC_FAMILY.get(name)
        if family is None:
            raise ValueError(
                f"unknown initial_condition {name!r}; valid: {sorted(IC_FAMILY)}"
            )
        allowed = {"auto", family}
        if family == "shallow_water":
            allowed.add("shallow_water_cov")
        if m.name not in allowed:
            raise ValueError(
                f"model.name={m.name!r} is incompatible with "
                f"initial_condition={name!r} (which drives {family!r})"
            )
        fields = self._ic_fields(name, family)
        if family == "advection":
            model = TracerAdvection(g, fields["wind"], scheme=m.scheme,
                                    limiter=m.limiter)
            return model, model.initial_state(fields["q"])
        if family == "diffusion":
            model = ThermalDiffusion(g, kappa=p.diffusivity)
            return model, model.initial_state(fields["T"])
        cls = ShallowWater
        if m.name == "shallow_water_cov":
            from .models.shallow_water_cov import CovariantShallowWater

            cls = CovariantShallowWater
        model = cls(
            g, gravity=p.gravity, omega=p.omega, b_ext=fields["b_ext"],
            scheme=m.scheme, limiter=m.limiter, nu4=p.hyperdiffusion,
            backend=m.backend,
        )
        return model, model.initial_state(fields["h"], fields["v"])

    def _ic_fields(self, name: str, family: str):
        """The extended IC fields for one IC-family — the single
        dispatch shared by the dense and TT tiers, so their initial
        states can never drift apart (the dense twin is the TT parity
        oracle)."""
        cfg = self.config
        m, p, g = cfg.model, cfg.physics, self.grid
        if family == "advection":
            u0 = 2 * math.pi * g.radius / (12 * 86400.0)
            return {"wind": ics.solid_body_wind(g, u0, alpha_rot=m.ic_angle),
                    "q": ics.cosine_bell(g)}
        if family == "diffusion":
            return {"T": ics.checkerboard(g)}
        b_ext = None
        if name == "tc2":
            h, v = ics.williamson_tc2(g, p.gravity, p.omega,
                                      alpha_rot=m.ic_angle)
        elif name == "tc5":
            h, v, b_ext = ics.williamson_tc5(g, p.gravity, p.omega)
        elif name == "tc6":
            h, v = ics.williamson_tc6(g, p.gravity, p.omega)
        else:
            h, v = ics.galewsky(g, p.gravity, p.omega)
        return {"h": h, "v": v, "b_ext": b_ext}

    def _build_ensemble_state(self):
        """Batched perturbed-IC ensemble state ``{"h": (B, 6, n, n),
        "u"|"v": (c, B, 6, n, n)}`` — member 0 unperturbed, members
        1..B-1 from :func:`...initial_conditions.perturbed_ensemble`
        (height-only, deterministic in ``ensemble.seed``)."""
        cfg = self.config
        ens = cfg.ensemble
        name = cfg.model.initial_condition
        family = IC_FAMILY.get(name)
        if family != "shallow_water":
            raise ValueError(
                f"ensemble.members > 1 supports the shallow-water family "
                f"(tc2/tc5/tc6/galewsky); initial_condition={name!r} "
                f"drives {family!r}")
        fields = self._ic_fields(name, family)
        h_b = ics.perturbed_ensemble(self.grid, fields["h"], ens.members,
                                     seed=ens.seed,
                                     amplitude=ens.amplitude)
        states = [self.model.initial_state(h_b[i], fields["v"])
                  for i in range(ens.members)]
        vkey = "u" if "u" in states[0] else "v"
        return {"h": jnp.stack([s["h"] for s in states]),
                vkey: jnp.stack([s[vkey] for s in states], axis=1)}

    def _build_tt(self):
        """The factored-panel ("Numerics (TT)", pdf p.7) solver tier.

        Single-device research numerics: every prognostic is a rank-r
        factor pair stored in the state dict as ``name__ttA`` /
        ``name__ttB`` (the same naming the TT history/checkpoint layers
        use), so history snapshots and Orbax checkpoints are compressed
        for free.  Returns ``(state, step)`` with ``step(y, t) -> y``
        over the flat dict.
        """
        from .tt.sphere import factor_panels, make_tt_sphere_advection
        from .tt.sphere_diffusion import make_tt_sphere_diffusion
        from .tt.sphere_swe import (
            covariant_from_cartesian, make_tt_sphere_swe,
        )

        cfg = self.config
        m, p, g, tc = cfg.model, cfg.physics, self.grid, cfg.time
        par = cfg.parallelization
        sharded = par.num_devices > 1 or par.use_shard_map
        if sharded and par.num_devices != 6:
            hint = (" (or set use_shard_map: false for the "
                    "single-device tier)" if par.num_devices == 1
                    else "")
            raise ValueError(
                "model.numerics='tt' shards one face per device over a "
                "6-device ('panel',) mesh (jaxstream.tt.shard); set "
                "parallelization.num_devices: 6"
                f"{hint} — got {par.num_devices}")
        if sharded and par.tiles_per_edge > 1:
            raise ValueError(
                "model.numerics='tt' supports tiles_per_edge: 1 only "
                "(the factored state is O(n r) per panel; intra-panel "
                f"tiling is not meaningful) — got {par.tiles_per_edge}")
        if g.halo < 1:
            raise ValueError(
                "model.numerics='tt' needs grid.halo >= 1 (the factored "
                "edge statics read the innermost ghost cell at index "
                f"halo-1; with halo={g.halo} that wraps to the opposite "
                "panel edge); set grid.halo: 1 or higher")
        if tc.scheme not in ("ssprk3", "euler"):
            raise ValueError(
                f"model.numerics='tt' supports time.scheme 'ssprk3' or "
                f"'euler', not {tc.scheme!r}")
        if p.hyperdiffusion != 0.0:
            raise ValueError(
                "model.numerics='tt' has no nu4 hyperdiffusion; set "
                "physics.hyperdiffusion: 0 (or run numerics: dense)")
        rank = m.tt_rank
        if not 0 < rank <= g.n:
            raise ValueError(
                f"model.tt_rank={rank} must be in [1, grid.n={g.n}] "
                "(the SVD factors cap at bond dim n, but the step's "
                "rounding rank is exactly tt_rank — a larger value "
                "would break the integration carry shapes)")
        name = m.initial_condition
        family = IC_FAMILY.get(name)
        if family is None:
            raise ValueError(
                f"unknown initial_condition {name!r}; valid: "
                f"{sorted(IC_FAMILY)}")
        if m.name not in ("auto", family):
            raise ValueError(
                f"model.name={m.name!r} is incompatible with "
                f"initial_condition={name!r} (which drives {family!r}; "
                "the TT tier has no model-name variants — use 'auto')")
        if (m.scheme, m.limiter, m.backend) != ("plr", "mc", "jnp"):
            log.info("numerics='tt' uses its own centered factored "
                     "discretization; model.scheme/limiter/backend are "
                     "ignored")
        fac = lambda q: factor_panels(np.asarray(q, np.float64), rank)
        fields = self._ic_fields(name, family)

        mesh = None
        if sharded:
            from .parallel.mesh import _pick_devices
            from .tt.shard import (
                make_tt_sphere_advection_sharded,
                make_tt_sphere_diffusion_sharded,
                make_tt_sphere_swe_sharded, panel_mesh)

            mesh = panel_mesh(_pick_devices(par.device_type, 6))

        rounding = m.tt_rounding
        if rounding == "auto":
            # Forced nonlinear flows need a near-optimal-truncation
            # tier (DESIGN.md stability envelope); the linear families
            # keep the cheaper cross rounding.  Exact svd is CPU-only
            # — TPU f32 QR/eigh lose orthogonality at production bond
            # sizes (cross.svd_lowrank docstring) — so accelerators
            # get the matmul-only rsvd tier instead.
            if family == "shallow_water":
                # The platform the step will EXECUTE on: a sharded run
                # is pinned to its mesh's devices; a single-device run
                # lands on the process default backend regardless of
                # device_type (nothing pins it).
                import jax

                if sharded and par.device_type != "default":
                    exec_backend = par.device_type
                else:
                    exec_backend = jax.default_backend()
                if exec_backend == "cpu":
                    rounding = "svd"
                else:
                    # Accelerators cannot run the exact tier (f32
                    # QR/eigh are measured-broken on the v5e,
                    # cross.svd_lowrank docstring) — but round 5's
                    # matmul-only rsvd tier is near-optimal (<=1.04x
                    # the exact truncation, tests/
                    # test_tt_rounding_tiers.py) and TPU-validated:
                    # mountain-forced TC5 C96 integrates 5+ sim-days
                    # finite on the real chip at the exact tier's f32
                    # error level (DESIGN.md stability envelope,
                    # round-5 addendum).  'aca' would NaN TC5 within
                    # half a sim-day; never auto-select it here.
                    rounding = "rsvd"
            else:
                rounding = "aca"
        elif rounding not in ("aca", "svd", "rsvd", "host_svd"):
            raise ValueError(
                f"model.tt_rounding={rounding!r}: use 'auto', 'aca', "
                "'svd', 'rsvd' or 'host_svd'")
        if (rounding in ("svd", "rsvd", "host_svd")
                and family != "shallow_water"):
            raise ValueError(
                f"model.tt_rounding={rounding!r} applies to the "
                "shallow-water family only (advection/diffusion run "
                "'aca'); set tt_rounding: auto")
        if m.tt_kappa != 0.0 and family != "shallow_water":
            raise ValueError(
                "model.tt_kappa (in-step velocity dissipation) applies "
                "to the shallow-water family only; set tt_kappa: 0 for "
                f"{family!r} runs")

        if family == "advection":
            if sharded:
                tt_step = make_tt_sphere_advection_sharded(
                    g, fields["wind"], tc.dt, rank, mesh,
                    scheme=tc.scheme)
            else:
                tt_step = make_tt_sphere_advection(
                    g, fields["wind"], tc.dt, rank, scheme=tc.scheme)
            tt_step = self._tt_block(tt_step, par.temporal_block)
            keys = ("q",)
            pairs = (fac(g.interior(fields["q"])),)
            single = True
        elif family == "diffusion":
            if sharded:
                tt_step = make_tt_sphere_diffusion_sharded(
                    g, p.diffusivity, tc.dt, rank, mesh,
                    scheme=tc.scheme)
            else:
                tt_step = make_tt_sphere_diffusion(
                    g, p.diffusivity, tc.dt, rank, scheme=tc.scheme)
            tt_step = self._tt_block(tt_step, par.temporal_block)
            keys = ("T",)
            pairs = (fac(g.interior(fields["T"])),)
            single = True
        else:
            b_ext = fields["b_ext"]
            kw = dict(hs=b_ext, omega=p.omega, gravity=p.gravity,
                      scheme=tc.scheme, kappa=m.tt_kappa,
                      rounding=rounding,
                      temporal_block=par.temporal_block)
            tt_step = (make_tt_sphere_swe_sharded(
                           g, tc.dt, rank, mesh,
                           overlap_exchange=par.overlap_exchange, **kw)
                       if sharded else
                       make_tt_sphere_swe(g, tc.dt, rank, **kw))
            ua, ub = covariant_from_cartesian(g, fields["v"])
            keys = ("h", "ua", "ub")
            pairs = (fac(g.interior(fields["h"])), fac(ua), fac(ub))
            single = False
            self._tt_hs = b_ext
        self._tt_keys = keys
        log.info("using factored (TT) %s tier, rank %d%s%s", family, rank,
                 f", rounding {rounding}" if family == "shallow_water"
                 else "",
                 ", panel-sharded over 6 devices" if sharded else "")

        state = {}
        for k, (A, B) in zip(keys, pairs):
            state[k + "__ttA"] = A
            state[k + "__ttB"] = B
        if sharded:
            from .tt.shard import shard_factored_state

            state = shard_factored_state(state, mesh)

        def step(y, t):
            del t
            ps = tuple((y[k + "__ttA"], y[k + "__ttB"]) for k in keys)
            out = tt_step(ps[0]) if single else tt_step(ps)
            if single:
                out = (out,)
            return {kk + s: pair[i]
                    for kk, pair in zip(keys, out)
                    for i, s in ((0, "__ttA"), (1, "__ttB"))}

        # The SWE factory fuses temporal_block steps internally (and
        # _tt_block does it for the linear families), so the flat-dict
        # wrapper advances that many steps per call.
        if par.temporal_block > 1:
            step.steps_per_call = par.temporal_block
        return state, step

    @staticmethod
    def _tt_block(tt_step, k: int):
        """Exact k-step fusion of a single-pair TT step (the linear
        families' form of ``parallelization.temporal_block`` — the SWE
        factories take the knob natively)."""
        if k <= 1:
            return tt_step

        def block(pair):
            for _ in range(k):
                pair = tt_step(pair)
            return pair

        return block

    def _tt_dense(self, key: str):
        """Reconstruct one factored prognostic to a dense (6, n, n)."""
        from .tt.sphere import unfactor_panels

        return unfactor_panels((self.state[key + "__ttA"],
                                self.state[key + "__ttB"]))

    # ---------------------------------------------------------------- running
    def _maybe_resume(self):
        step = self.checkpoints.latest_step()
        if step is None:
            return
        # Host-side restore: inspect (and possibly regrid) before any
        # device placement — a sharded-state restart must never
        # materialize the full arrays on one device.
        from .io.regrid import infer_resolution, regrid_state

        state, self.t = self.checkpoints.restore_host(step)
        n_new = self.config.grid.n
        ckpt_tt = any(k.endswith("__ttA") for k in state)
        run_tt = self._tt_keys is not None
        if ckpt_tt != run_tt:
            raise ValueError(
                "checkpoint/run numerics mismatch: the checkpoint is "
                f"{'factored (TT)' if ckpt_tt else 'dense'} but the run is "
                f"{'factored (TT)' if run_tt else 'dense'}; set "
                "model.numerics to match, or convert with "
                "jaxstream.tt.store.compress_state/decompress_state")
        if run_tt:
            want = {k + s for k in self._tt_keys
                    for s in ("__ttA", "__ttB")}
            if set(state) != want:
                raise ValueError(
                    f"TT checkpoint prognostics {sorted(state)} do not "
                    f"match this run's {sorted(want)}: the checkpoint "
                    "was written by a different model family — point "
                    "io.checkpoint_path somewhere else")
            n_ckpt = next(np.asarray(v).shape[1] for k, v in state.items()
                          if k.endswith("__ttA"))
            r_ckpt = next(np.asarray(v).shape[2] for k, v in state.items()
                          if k.endswith("__ttA"))
            if n_ckpt != n_new:
                raise ValueError(
                    f"TT checkpoint is C{n_ckpt} but the run is C{n_new}: "
                    "cross-resolution resume is dense-only — restart "
                    "dense, or decompress_state + regrid manually")
            if r_ckpt != self.config.model.tt_rank:
                raise ValueError(
                    f"TT checkpoint rank {r_ckpt} != run tt_rank "
                    f"{self.config.model.tt_rank}: set model.tt_rank: "
                    f"{r_ckpt}, or re-factor the state manually")
            self.state = jax.tree_util.tree_map(jnp.asarray, state)
            self.step_count = step
            log.info("resumed factored (TT) state from checkpoint step %d "
                     "(t=%.0f s)", step, self.t)
            return
        if self.members > 1:
            # Ensemble resume (round 11): the checkpoint holds the
            # member-batched arrays; validate the batch shape against
            # this run and place directly (cross-resolution regrid is
            # dense-unbatched-only).
            hb = np.asarray(state["h"]) if "h" in state else None
            if hb is None or hb.ndim != 4:
                raise ValueError(
                    "ensemble.members > 1 but the checkpoint state is "
                    "not member-batched — it was written by an "
                    "unbatched run; point io.checkpoint_path elsewhere")
            if hb.shape[0] != self.members:
                raise ValueError(
                    f"checkpoint has {hb.shape[0]} ensemble members but "
                    f"the run configures {self.members}; set "
                    f"ensemble.members: {hb.shape[0]} (per-member resume: "
                    "CheckpointManager.restore_member)")
            if hb.shape[-1] != n_new:
                raise ValueError(
                    f"ensemble checkpoint is C{hb.shape[-1]} but the run "
                    f"is C{n_new}: cross-resolution resume is "
                    "unbatched-dense-only")
            if self.setup is not None and self.setup.mesh is not None:
                state = shard_ensemble_state(self.setup, state)
            else:
                state = jax.tree_util.tree_map(jnp.asarray, state)
            self.state = state
            self.step_count = step
            log.info("resumed %d-member ensemble state from checkpoint "
                     "step %d (t=%.0f s)", self.members, step, self.t)
            return
        n_ckpt = infer_resolution(state)   # raises clearly on ambiguity
        if n_ckpt != n_new:
            # Resolution-aware resume (SURVEY.md §5): conservative
            # area-weighted regrid of every state field onto the run's
            # grid (io/regrid.py), then shard for the run's mesh.
            log.info("resuming across resolutions: checkpoint C%d -> "
                     "run C%d (conservative regrid)", n_ckpt, n_new)
            state = regrid_state(state, n_new,
                                 dtype=self.grid.area.dtype)
        if self.setup is not None and self.setup.mesh is not None:
            from .parallel.mesh import shard_state

            state = shard_state(self.setup, state)
        else:
            state = jax.tree_util.tree_map(jnp.asarray, state)
        self.state = state
        self.step_count = step
        log.info("resumed from checkpoint step %d (t=%.0f s)", step, self.t)

    def _build_segment_fn(self, k: int):
        """Compile the ``k``-step segment callable (cached per ``k``).

        Without observability this is the historical pair of paths
        (fused-carry / classic ``jit_integrate``), signature
        ``fn(y, t) -> (y, t)``.  With ``observability.interval > 0``
        and at least one sample landing inside the segment, the loop is
        :func:`jaxstream.stepping.integrate_with_metrics` instead —
        same state ops in the same order — with signature
        ``fn(y, t, step0) -> (y, t, buf)`` and an ``obs_samples``
        attribute carrying the buffer's column count.  A callable that
        wraps its jit carries it as ``jitted``, whose compile count
        :meth:`_dispatch` reads.

        Known trade: the metric buffer's ``(k_metrics, samples)`` shape
        is static, so instrumented segments compile once per DISTINCT
        segment length instead of the classic tier's single
        traced-nsteps executable.  A run has at most two distinct
        lengths (the stride gcd and the final remainder), so this is
        one extra compile per run at worst.
        """
        dt = self.config.time.dt
        active = (self._fused_step if self._fused_step is not None
                  else self._step)
        # Temporal blocking: a blocked stepper advances
        # steps_per_call steps per call, so the integrator runs
        # k/spc calls of span spc*dt each (t advances identically
        # — the block's sub-step times are sequential dt adds).
        spc = getattr(active, "steps_per_call", 1)
        if k % spc:
            raise ValueError(
                f"segment of {k} steps is not a multiple of "
                f"parallelization.temporal_block={spc}; make "
                "io.history_stride/io.checkpoint_stride and the "
                "total step count multiples of temporal_block")
        # Both paths DONATE the state carry (round-7 satellite,
        # parallelization.donate_state to opt out): segments are
        # ping-pong by construction (self.state is always replaced
        # by the result), so XLA aliases the input and output state
        # instead of double-buffering every prognostic array for
        # the whole loop.  Accelerator callers holding their own
        # reference to sim.state across run() calls must copy it
        # (np.asarray) first — donation consumes the buffers.
        donate = self.config.parallelization.donate_state
        obs = self._obs
        samples = 0
        if obs is not None:
            every = obs.cfg.interval // spc
            samples = (k // spc) // every
        if samples > 0:
            mfn, fault = obs.metric_fn, obs.cfg.fault_step
            if self._fused_step is not None:
                m, fused, prep = self.model, self._fused_step, \
                    self._fused_prep
                post = self._fused_post or (lambda s: s)

                def fn(y, t, step0, _n=k // spc, _dt=dt * spc,
                       _e=every, _s=samples):
                    y_c = prep(y)
                    y_c, t, buf = integrate_with_metrics(
                        fused, y_c, t, _n, _dt, mfn, _e, _s, step0,
                        steps_per_call=spc, fault_step=fault)
                    return post(m.restrict_state(y_c)), t, buf
            else:
                step = self._step

                def fn(y, t, step0, _n=k // spc, _dt=dt * spc,
                       _e=every, _s=samples):
                    return integrate_with_metrics(
                        step, y, t, _n, _dt, mfn, _e, _s, step0,
                        steps_per_call=spc, fault_step=fault)
            jfn = jax.jit(fn, donate_argnums=(0,) if donate else ())

            def call(y, t, step0, _f=jfn):
                return _f(y, t, step0)

            call.obs_samples = samples
            call.jitted = jfn
            return call
        if self._fused_step is not None:
            m, fused = self.model, self._fused_step

            prep = self._fused_prep
            post = self._fused_post or (lambda s: s)

            def fn(y, t, _k=k // spc, _dt=dt * spc):
                y_c = prep(y)
                y_c, t = integrate(fused, y_c, t, _k, _dt)
                return post(m.restrict_state(y_c)), t

            return jax.jit(fn, donate_argnums=(0,) if donate else ())
        # unroll=1: the generic tiers' steps are ms-scale (TT
        # roundings, classic jnp), where the while-carry's
        # ~us-scale copies are invisible but a 4x-traced step
        # graph would multiply compile time.  One jit_integrate
        # executable serves every segment length (nsteps rides
        # as a traced operand).
        if self._classic_run is None:
            self._classic_run = jit_integrate(
                self._step, dt * spc, unroll=1, donate=donate)
        run = self._classic_run

        def fn(y, t, _k=k // spc):
            return run(y, t, _k)

        fn.jitted = run
        return fn

    def _segment_fn(self, k: int) -> Callable:
        fn = self._segment_cache.get(k)
        if fn is None:
            fn = self._build_segment_fn(k)
            self._segment_cache[k] = fn
        return fn

    def _dispatch(self, fn: Callable, k: int, *args):
        """One segment dispatch as a ``sim.dispatch`` span; returns
        ``(outputs, phases)``.

        A dispatch that grew the segment jit's compile count compiled:
        it counts in ``sim.compiles``, leaves a flight ``compile`` event
        naming the step, and stays out of the span statistics, as does
        its segment.  ``phases`` carries the dispatch's start stamp and
        seconds, the ``sim.boundary`` before it (None on a
        Simulation's first dispatch) and the compile flag."""
        jitted = getattr(fn, "jitted", fn)
        n0 = jax_compat.compile_count(jitted)
        with obs_trace.span(obs_trace.SIM_DISPATCH, steps=k) as sp:
            out = fn(*args)
            n1 = jax_compat.compile_count(jitted)
            compiled = n0 is not None and n1 is not None and n1 > n0
            sp.keep = not compiled
        boundary = None
        if self._sync_end is not None:
            boundary = sp.t0 - self._sync_end
            if not compiled:
                obs_trace.record_span(obs_trace.SIM_BOUNDARY,
                                      self._sync_end, sp.t0)
        if compiled:
            obs_trace.SPANS.count(obs_trace.SIM_COMPILES, n1 - n0)
            flight.record("compile", step=self.step_count, k=k,
                          delta=n1 - n0, seconds=round(sp.seconds, 6))
        return out, {"t0": sp.t0, "dispatch_s": sp.seconds,
                     "boundary_s": boundary, "compiled": compiled}

    def _close_segment(self, ph: dict, sync, k: int, step_end: int,
                       **fields) -> float:
        """Close one segment at its ``sim.sync`` span: the
        ``sim.segment`` statistics (unless it compiled), the next
        boundary's start stamp and the flight ``segment`` event with
        its phase split.  Returns the segment's seconds."""
        seconds = sync.t1 - ph["t0"]
        if not ph["compiled"]:
            obs_trace.record_span(obs_trace.SIM_SEGMENT, ph["t0"],
                                  sync.t1, steps=k)
        self._sync_end = sync.t1
        b = ph["boundary_s"]
        flight.record("segment", step=step_end, k=k, **fields,
                      dispatch_s=round(ph["dispatch_s"], 6),
                      sync_s=round(sync.seconds, 6),
                      boundary_s=None if b is None else round(b, 6),
                      compiled=ph["compiled"])
        return seconds

    def _run_segment(self, k: int):
        fn = self._segment_fn(k)
        step0, t0 = self.step_count, self.t
        instrumented = getattr(fn, "obs_samples", 0) > 0
        with obs_trace.span(obs_trace.SIM_SEGMENT, steps=k) as seg:
            # The profiler's sim.segment; its statistics come from the
            # dispatch and sync stamps (_close_segment).
            seg.keep = False
            if instrumented:
                # Instrumented segment: the metric buffer rides the
                # compiled loop and is fetched with ONE device->host
                # transfer, which also waits for the segment.
                (self.state, t, buf), ph = self._dispatch(
                    fn, k, self.state, self.t, jnp.asarray(step0))
            else:
                (self.state, t), ph = self._dispatch(
                    fn, k, self.state, self.t)
            with obs_trace.span(obs_trace.SIM_SYNC) as sync:
                sync.keep = not ph["compiled"]
                host = (obs_metrics.fetch_buffer(buf) if instrumented
                        else None)
                self.t = float(t)
        self.step_count += k
        wall = self._close_segment(ph, sync, k, self.step_count)
        if instrumented:
            self._ingest_telemetry(host, step0, t0, k, wall,
                                   self.step_count, self.t)

    def _ingest_telemetry(self, host, step0: int, t0: float, k: int,
                          wall: float, step_end: int, t_end: float,
                          emit: Optional[Callable] = None):
        """One fetched segment buffer -> sink record + guard check.

        ``host``: the ``(k_metrics, samples)`` numpy buffer; sample j
        is global step ``step0 + (j+1)*interval``.  Writes the segment
        record first so a guard raise leaves the evidence on disk, then
        runs the monitor (guard events are flushed even when the policy
        raises).  ``emit`` overrides the record destination — the async
        pipeline routes records through its background writer (FIFO
        with the history/checkpoint tasks) instead of writing inline.
        The record's ``host_wait_s`` is the host-side I/O time that
        blocked the next dispatch since the previous record (the
        quantity the async pipeline exists to shrink).
        """
        obs = self._obs
        if emit is None and obs.sink is not None:
            emit = obs.sink.write
        interval = obs.cfg.interval
        names = obs.ms.names
        samples = host.shape[1]
        steps = step0 + interval * np.arange(1, samples + 1)
        dt = self.config.time.dt
        ts = t0 + interval * dt * np.arange(1, samples + 1)
        drift = {}
        for i, n in enumerate(names):
            if n in obs_metrics.CONSERVED:
                v0 = float(obs.ref[i])
                d = float(host[i, -1]) - v0
                drift[n] = d / abs(v0) if v0 else d
        if emit is not None:
            rate = k / wall if wall > 0 else float("inf")
            chips = (self.config.parallelization.num_devices
                     if self.setup is not None else 1)
            host_wait, self._host_wait = self._host_wait, 0.0
            emit({
                "kind": "segment",
                "step": step_end, "t": t_end, "steps": k,
                "wall_s": wall, "steps_per_sec": rate,
                "sim_days_per_sec_per_chip":
                    rate * dt / 86400.0 / chips,
                "host_wait_s": host_wait,
                "metrics": {n: float(host[i, -1])
                            for i, n in enumerate(names)},
                "drift": drift,
                "samples": {"step": steps.tolist(),
                            **{n: host[i].tolist()
                               for i, n in enumerate(names)}},
            })
        if obs.monitor is not None:
            n0 = len(obs.monitor.events)
            try:
                obs.monitor.check(steps, ts, host)
            finally:
                if emit is not None:
                    for ev in obs.monitor.events[n0:]:
                        emit(ev)

    def _emit(self):
        if self.history is not None:
            self.history.append(
                {k: np.asarray(v) for k, v in self.state.items()}, self.t
            )
        # The per-emit log lines cost real host time (a diagnostics
        # compute + one blocking device_get) — only pay it when the
        # lines will actually be shown.  bench.py's io section relies
        # on this to compare sync vs async on identical I/O work.
        if log.isEnabledFor(logging.INFO):
            for k, v in self.diagnostics().items():
                log.info("step %-8d t=%10.0fs  %s=%.10g",
                         self.step_count, self.t, k, v)

    @staticmethod
    def _fetch_scalars(out) -> Dict[str, float]:
        """One host transfer for a whole dict of device scalars.

        The invariants are stacked on device (exact widening to the
        common dtype — an f32 value converts to the identical f64, so
        the returned floats are bitwise what per-metric ``float(x)``
        calls produced) and fetched with a SINGLE ``jax.device_get``:
        one blocking round trip per :meth:`diagnostics` call instead of
        one per metric.
        """
        if not out:
            return {}
        vals = [jnp.asarray(v) for v in out.values()]
        common = jnp.result_type(*[v.dtype for v in vals])
        host = np.asarray(
            jax.device_get(jnp.stack([v.astype(common) for v in vals])))
        return {k: float(host[i]) for i, k in enumerate(out)}

    def diagnostics(self) -> Dict[str, float]:
        """Scalar invariants for the current state (model-appropriate).

        All invariants are computed on device and fetched with one
        batched transfer (:meth:`_fetch_scalars`)."""
        g, s = self.grid, self.state
        out: Dict[str, Any] = {}
        if self._tt_keys is not None:
            from .tt.diagnostics import tt_total_mass

            pair = lambda k: (s[k + "__ttA"], s[k + "__ttB"])
            if self._tt_keys == ("q",):
                out["tracer_mass"] = tt_total_mass(g, pair("q"))
                out["tracer_max"] = jnp.max(self._tt_dense("q"))
            elif self._tt_keys == ("T",):
                out["heat"] = tt_total_mass(g, pair("T"))
            else:
                h = self._tt_dense("h")
                ua = self._tt_dense("ua")
                ub = self._tt_dense("ub")
                out["mass"] = diag.total_mass(g, h)
                sl = slice(g.halo, g.halo + g.n)
                aa = jnp.asarray(g.a_a)[:, :, sl, sl]
                ab = jnp.asarray(g.a_b)[:, :, sl, sl]
                v = aa * ua[None] + ab * ub[None]
                b_int = (g.interior(jnp.asarray(self._tt_hs))
                         if self._tt_hs is not None else 0.0)
                p = self.config.physics
                out["energy"] = diag.total_energy(g, h, v, p.gravity,
                                                  b_int)
            return self._fetch_scalars(out)
        if "h" in s and self.members > 1:
            # Member-0 invariants plus the ensemble's height spread (the
            # quantity a perturbed-IC run exists to grow): per-cell
            # cross-member std, reported at its max.
            p = self.config.physics
            vkey = "u" if "u" in s else "v"
            s0 = {"h": s["h"][0], vkey: s[vkey][:, 0]}
            out["mass_m0"] = diag.total_mass(g, s0["h"])
            b = self.model.b_ext
            b_int = g.interior(b) if b is not None else 0.0
            v = s0["v"] if "v" in s0 else self.model.to_cartesian(s0)
            out["energy_m0"] = diag.total_energy(g, s0["h"], v,
                                                 p.gravity, b_int)
            out["h_spread_max"] = jnp.max(jnp.std(
                s["h"].astype(jnp.float32), axis=0))
            return self._fetch_scalars(out)
        if "h" in s:
            p = self.config.physics
            out["mass"] = diag.total_mass(g, s["h"])
            b = self.model.b_ext
            b_int = g.interior(b) if b is not None else 0.0
            # Covariant models carry "u"; energy wants the Cartesian vector.
            v = s["v"] if "v" in s else self.model.to_cartesian(s)
            out["energy"] = diag.total_energy(g, s["h"], v, p.gravity,
                                              b_int)
        elif "q" in s:
            out["tracer_mass"] = diag.total_mass(g, s["q"])
            out["tracer_max"] = jnp.max(s["q"])
        elif "T" in s:
            out["heat"] = diag.total_mass(g, s["T"])
        return self._fetch_scalars(out)

    def total_steps(self) -> int:
        tc = self.config.time
        if tc.nsteps > 0:
            return tc.nsteps
        return int(round(tc.duration_days * 86400.0 / tc.dt))

    def run(self, nsteps: Optional[int] = None):
        """Integrate to ``nsteps`` total (default: the config's duration).

        Returns the final state.  History/checkpoints fire on their
        configured strides; everything between strides is one compiled
        device loop.  The returned state is ``self.state`` itself and —
        with the default ``parallelization.donate_state: true`` — will
        be CONSUMED by the first segment of any later ``run()`` on an
        accelerator: copy it (``np.asarray``) before continuing the
        simulation if you need to keep it.
        """
        with obs_trace.span(obs_trace.SIM_RUN) as sp:
            start = self.step_count
            self._run(self.total_steps() if nsteps is None else nsteps)
        ran = self.step_count - start
        days = ran * self.config.time.dt / 86400.0
        log.info(
            "ran %d steps (%.2f sim-days) in %.2fs wall -> %.2f sim-days/sec",
            ran, days, sp.seconds,
            days / sp.seconds if sp.seconds > 0 else float("inf"),
        )
        return self.state

    def _io(self, fn: Callable, *args) -> None:
        """History or checkpoint work as a ``sim.io`` span, counted in
        the host wait the next telemetry record reports."""
        with obs_trace.span(obs_trace.SIM_IO) as sp:
            fn(*args)
        self._host_wait += sp.seconds

    def _run(self, total: int):
        io = self.config.io
        strides = [s for s in (io.history_stride, io.checkpoint_stride) if s > 0]
        seg = math.gcd(*strides) if strides else 0
        if self.step_count == 0 and self.history is not None:
            self._emit()  # record the initial condition
        obs = self._obs
        if (obs is not None and obs.sink is not None
                and not obs.wrote_initial):
            # Step-0 record: the drift columns' reference values, so the
            # report CLI's drift table has its anchor in-file.
            obs.sink.write({
                "kind": "segment", "step": self.step_count, "t": self.t,
                "steps": 0, "wall_s": 0.0, "steps_per_sec": 0.0,
                "sim_days_per_sec_per_chip": 0.0,
                "metrics": {n: float(obs.ref[i])
                            for i, n in enumerate(obs.ms.names)},
                "drift": {n: 0.0 for n in obs.ms.names
                          if n in obs_metrics.CONSERVED},
            })
            obs.wrote_initial = True
        try:
            if io.async_pipeline.enabled:
                self._run_loop_async(total, seg, io)
            else:
                while self.step_count < total:
                    k = (min(seg, total - self.step_count) if seg
                         else total - self.step_count)
                    self._run_segment(k)
                    if (io.history_stride
                            and self.step_count % io.history_stride == 0):
                        self._io(self._emit)
                    if (
                        self.checkpoints is not None
                        and self.step_count % io.checkpoint_stride == 0
                    ):
                        self._io(self.checkpoints.save, self.step_count,
                                 self.state, self.t)
                        flight.record("checkpoint",
                                      step=self.step_count)
        except BaseException as e:
            # HealthError / unhandled exception: flush the black box
            # BEFORE unwinding (the sink records ride the same open
            # sink; the bundle commit is atomic on its own).
            self._flight_dump(type(e).__name__)
            raise
        jax.block_until_ready(self.state)

    # ------------------------------------------------------- async pipeline
    def _run_loop_async(self, total: int, seg: int, io):
        """The ``io.async_pipeline`` form of the segment loop.

        Double-buffered: segment k+1 is dispatched with segment k's
        boundary still unresolved — its device->host copies were
        started (``copy_to_host_async`` via :class:`HostFetch`) right
        behind segment k's own dispatch, and only after segment k+1 is
        in flight does the host block on them.  Resolved boundaries
        hand their history appends / checkpoint saves / telemetry
        records to the bounded background writer; at the queue bound
        (``max_pending_segments``) ``submit`` blocks, which is the
        pipeline's backpressure — host snapshots stay at a small
        constant (``max_pending_segments`` queued + 1 being written
        + 1 unresolved fetch).  Written bytes are identical to
        the synchronous path: one writer thread, FIFO, same values
        (the time scalar stays on device between segments via
        ``stepping.time_carry`` — bitwise the same float the sync
        path round-trips through python).

        On ANY exception the writer is still flushed before the
        exception propagates (guaranteed flush-on-exception), so a
        guard's sink records and the ``checkpoint_and_raise``
        postmortem land on disk.
        """
        obs = self._obs
        writer = None
        if (self.history is not None or self.checkpoints is not None
                or (obs is not None and obs.sink is not None)):
            writer = self._ensure_writer()
        self._t_carry = time_carry(self.t)
        self._seg_anchor = time.perf_counter()
        t_host = self.t              # resolved host time (trails one seg)
        pending = None
        raised = False
        try:
            while self.step_count < total:
                k = (min(seg, total - self.step_count) if seg
                     else total - self.step_count)
                fn = self._segment_fn(k)
                samples = getattr(fn, "obs_samples", 0)
                step0 = self.step_count
                buf = None
                if samples > 0:
                    (self.state, self._t_carry, buf), ph = self._dispatch(
                        fn, k, self.state, self._t_carry,
                        jnp.asarray(step0))
                else:
                    (self.state, self._t_carry), ph = self._dispatch(
                        fn, k, self.state, self._t_carry)
                self.step_count += k
                want_hist = bool(
                    io.history_stride
                    and self.step_count % io.history_stride == 0
                    and self.history is not None)
                want_ckpt = bool(
                    self.checkpoints is not None and io.checkpoint_stride
                    and self.step_count % io.checkpoint_stride == 0)
                # The boundary snapshot must be a DISTINCT device
                # buffer: the next dispatch donates self.state, and jax
                # marks a donated input deleted at dispatch (python-side
                # bookkeeping, every backend) — fetching the original
                # after that raises.  jnp.copy dispatches an on-device
                # copy asynchronously; its d2h fetch then rides behind
                # the next segment.  One state copy per history/
                # checkpoint boundary, nothing per plain segment.
                snap = None
                if want_hist or want_ckpt:
                    snap = jax.tree_util.tree_map(jnp.copy, self.state)
                b = {
                    "k": k, "step0": step0, "step_end": self.step_count,
                    "samples": samples,
                    "t": HostFetch(self._t_carry),
                    "buf": HostFetch(buf) if samples > 0 else None,
                    "state": HostFetch(snap) if snap is not None else None,
                    "hist": want_hist, "ckpt": want_ckpt, "phases": ph,
                }
                # The double buffer: only now — with this segment's
                # dispatch in flight — resolve the previous boundary.
                # (pending is popped BEFORE resolving so a raise inside
                # the resolve can never double-resolve it from the
                # unwind path below.)
                prev, pending = pending, None
                if prev is not None:
                    t_host = self._resolve_boundary(prev, t_host, writer)
                pending = b
            prev, pending = pending, None
            if prev is not None:
                t_host = self._resolve_boundary(prev, t_host, writer)
        except BaseException:
            raised = True
            # A still-pending boundary is fully computed on device — the
            # sync path would have written it before dispatching the
            # segment that just raised, so land its I/O (best-effort,
            # never masking the in-flight exception) before unwinding.
            if pending is not None:
                try:
                    self._resolve_boundary(pending, t_host, writer)
                except Exception:
                    log.warning("could not land the in-flight boundary "
                                "during exception unwind", exc_info=True)
                pending = None
            raise
        finally:
            if writer is not None:
                try:
                    writer.flush()
                except Exception:
                    # Flush-on-exception must not MASK the in-flight
                    # exception; on the success path a writer failure
                    # is the run's failure.
                    if not raised:
                        raise
                    log.warning("async writer flush failed during "
                                "exception unwind", exc_info=True)
        self.t = float(jax.device_get(self._t_carry))

    def _resolve_boundary(self, b, t_prev: float, writer) -> float:
        """Resolve one dispatched segment's host copies and hand its
        boundary I/O to the background writer.  Called with the NEXT
        segment already dispatched; returns the boundary's host time.

        All of a boundary's writes ride ONE queued task, in sync-path
        order (segment record, guard events, history append, checkpoint
        save) — so the writer's FIFO produces byte-identical files AND
        the queue bound counts whole segments, which is what
        ``max_pending_segments`` promises.  A guard raise inside the
        telemetry ingest still submits the records gathered so far
        (segment record + guard events land on disk) but skips the
        history/checkpoint writes, exactly like the synchronous loop,
        which raises before reaching them."""
        ph = b["phases"]
        with obs_trace.span(obs_trace.SIM_SYNC) as sync:
            sync.keep = not ph["compiled"]
            t_host = float(np.asarray(b["t"].resolve()))
        self.t = t_host
        wall = sync.t1 - self._seg_anchor
        self._seg_anchor = sync.t1
        self._close_segment(ph, sync, b["k"], b["step_end"],
                            wall_s=round(wall, 6))
        host_state = (b["state"].resolve() if b["state"] is not None
                      else None)
        tasks = []
        try:
            if b["samples"] > 0:
                host = b["buf"].resolve()
                emit = None
                obs = self._obs
                if obs is not None and obs.sink is not None:
                    sink_write = obs.sink.write
                    emit = lambda rec: tasks.append((sink_write, (rec,)))
                self._ingest_telemetry(host, b["step0"], t_prev, b["k"],
                                       wall, b["step_end"], t_host,
                                       emit=emit)
            if b["hist"]:
                tasks.append((self.history.append, (host_state, t_host)))
            if b["ckpt"]:
                flight.record("checkpoint", step=b["step_end"])
                tasks.append((self.checkpoints.save,
                              (b["step_end"], host_state, t_host)))
        finally:
            if tasks:
                self._io(writer.submit, _run_tasks, tasks)
        return t_host


def run_from_config(source: Any, nsteps: Optional[int] = None):
    """One-call entry: build a Simulation from ``source`` and run it."""
    sim = Simulation(source)
    sim.run(nsteps)
    return sim
