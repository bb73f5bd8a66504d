"""Headline benchmark: simulated-days/sec/chip, Williamson TC5 at C384.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline (BASELINE.json north star): >=1000 simulated-days/sec on a
v5p-256 pod => 1000/256 = 3.90625 sim-days/sec/chip. ``vs_baseline`` is
our per-chip rate divided by that.  Acceptance gates run first (stderr
only) and force value 0 on any breach: TC2 C48 5-day l1/l2/linf height
errors + mass conservation, and TC5 C96 15-day stability (finite,
physical h range, mass conservation) — thresholds justified against the
measured f64 truncation of this discretization (see accuracy_gates).
The timed C384 run itself is additionally gated (finite, physical h
range, mass drift < 1e-3 over its own ~26 simulated days).

The timed step is dt=75 s — matched to the worst-cell CFL the C96 gate
config has always run at; the verification evidence (15-day stability,
temporal error at the f32 roundoff floor) is in ``bench_tc5``'s
docstring and DESIGN.md "The time step".  The ``variants`` JSON field
records the mixed16-carry rate (h int16 + u bf16, default gate band),
the dt=90 empirical-max-stable rate (own 15-day gate each run), and
the Galewsky-nu4 rate (day-6 physics gate); the dt=60-equivalent rate
is a top-level field.  The ``ensemble`` field reports the batched
perturbed-IC ensemble section (``bench_ensemble``, TC5 C96 at the
CFL-matched dt=300 — the members-x-moderate-resolution regime where
batching pays): aggregate sim-days/sec/chip at B in {1, 4, 16} with
B-scaled rooflines and the B=1 bitwise acceptance check.  The ``io``
field (round 9) reports the async-host-pipeline section
(``bench_io``): steps/s with history+checkpoint+telemetry on, async
vs sync, against the io-off baseline, plus the per-mode
``host_wait_s`` totals from the runs' own telemetry.  The ``serving``
field (round 11) reports the continuous-batching ensemble server
section (``bench_serving``): packed heterogeneous-run-length traffic
vs serial B=1 aggregate sim-days/sec/chip, slot occupancy, request
latency p50/p99, warmup compile count and the zero-steady-state-
recompile check, plus the >= 0.9x floor vs the static-B=16 ensemble
rate.  The ``serving_multichip`` field (round 12,
``bench_serving_multichip``) measures one server process driving a
whole device mesh through ``serve.placement``: aggregate
member-steps/s at equal per-chip batch vs the single-device packed
rate, with the >= 0.8x-of-ideal N-chip scaling floor enforced on real
accelerators (reported-only on fake CPU meshes), the
single-vs-multichip packed-h byte-parity check, and zero steady-state
recompiles per placement mode.  The ``serving_slo`` field (round 14,
``bench_serving_slo``) replays a deterministic heavy-tailed mixed-IC
trace through the asyncio HTTP gateway over loopback with live
autoscaling and enforces the SLO floors: request p50/p99 latency,
goodput >= 0.5x the packed serving rate, completed + typed-shed ==
submitted, >= 1 autoscale resize, zero steady-state recompiles after
the resize.  The ``perf`` field (round 19, ``bench_perf``) is the performance
observatory's section — hardware identity, a full cost stamp of the
bench stepper (XLA memory_analysis footprint bytes, compile seconds,
the flops-vs-analytic cross-check on XLA-visible rungs) and a live
device-memory snapshot — and the ``perf_ledger`` field
(``bench_perf_ledger``) gates this run against the recorded
``BENCH_r*.json`` trajectory (enforced on accelerators, reported-only
for CPU smoke; ``scripts/perf_ledger.py`` renders/checks the same
history offline).
``python bench.py --smoke`` runs the C24 bitrot canary instead (no gates;
wired into tier-1 via tests/test_bench_smoke.py); ``python bench.py
--compile-report`` prints cold-vs-warm compile seconds for the
persistent compile cache the package places on import; ``python bench.py
--precision-report`` prints the round-10 precision ladder (f32 /
bf16-stage / mixed16-carry / stacked measured side by side at C384
with precision-corrected rooflines — ``bench_precision_report``).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

BASELINE_PER_CHIP = 1000.0 / 256.0  # sim-days/sec/chip
BENCH_DT = 75.0  # timed step (s); CFL-matched, see bench_tc5 docstring


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _device_count() -> int:
    """In-process device count (1 when jax is unavailable/broken)."""
    try:
        import jax

        return len(jax.devices())
    except Exception:
        return 1


def _platform() -> str:
    """Device platform id ('unknown' when jax is unavailable) — the
    hardware tag the perf ledger classes trajectory points by."""
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return "unknown"


def _argv_value(flag: str) -> str:
    """Value following ``flag`` in argv, or '' (no argparse: the JSON
    contract is one stdout line and the flag surface is tiny)."""
    argv = sys.argv[1:]
    if flag in argv:
        i = argv.index(flag)
        if i + 1 < len(argv):
            return argv[i + 1]
    return ""


def _open_telemetry(path: str):
    """Structured-sink handle for ``--telemetry PATH`` (jaxstream.obs).

    The benchmark's rates land as schema-valid ``bench`` records in the
    same JSONL format Simulation emits, so scripts/telemetry_report.py
    reads either.  Never fails the benchmark — a sink problem logs to
    stderr and returns None.
    """
    if not path:
        return None
    try:
        from jaxstream.obs.sink import TelemetrySink, run_manifest

        return TelemetrySink(path, run_manifest(
            config={"harness": "bench.py", "argv": sys.argv[1:]}))
    except Exception as e:
        log(f"bench: telemetry sink unavailable ({type(e).__name__}: {e})")
        return None


def _roofline_json(steps_per_sec: float, n: int, scale: float = 1.0,
                   bytes_scale: float = 1.0, ensemble: int = 1,
                   carry_bytes: int = None, nu4: str = None,
                   precision: str = None, device_kind: str = None):
    """Roofline numbers for one covariant-fused-stepper rate, as JSON.

    The analytic kernel count against the VPU roof (Pallas custom calls
    are invisible to XLA's cost model — see bench_tc5's roofline note);
    ``scale`` adjusts flops AND bytes for non-covariant rungs, while
    ``bytes_scale`` adjusts bytes alone (kept for ad-hoc callers).
    ``ensemble = B``: ``steps_per_sec`` counts BATCHED ensemble steps
    (each advancing all B members) and the analytic cost scales flops
    AND bytes by B together — intensity unchanged — so ensemble
    variants report truthful throughput instead of a B-inflated AI
    (jaxstream.utils.profiling.analytic_cov_step_cost's ensemble note).

    Round-10 accounting satellite — the three precision-aware knobs
    thread straight into ``analytic_cov_step_cost``:

    * ``carry_bytes=2``: 16-bit carry encodings.  Replaces the old
      coarse ``bytes_scale=0.5``, which billed the orography re-read at
      2 bytes too and so OVERSTATED both the byte savings and the AI of
      the 16-bit-carry variants; the reported ``ai`` is now the
      corrected one.
    * ``nu4='split'|'refused'``: del^4-filter variants get the
      re-derived 210 flops/cell/step filter count plus the per-placement
      byte traffic (6 extra f32 field passes split, 3 re-fused) instead
      of the old flops-AND-bytes ``scale=4/3``.
    * ``precision='bf16'``: the stage-policy variants additionally
      report ``bf16_flop_fraction`` and their percentage of the
      harmonic-blend ``mixed_vpu_roof`` (bf16 ops pack 2x per VPU
      lane); ``pct_of_compute_roof`` stays the f32 roof so rows remain
      comparable across variants.

    Round 19: the arithmetic itself moved to
    ``jaxstream.obs.perf.roofline_json`` — the ONE definition of cost
    accounting the probe CLIs and the serving cost stamps share; this
    wrapper keeps bench's never-fail-a-variant contract.  Returns None
    when the profiling helpers are unavailable, and on a CPU run (no
    roofline share; the peaks are keyed by ``device_kind``).
    """
    try:
        from jaxstream.obs.perf import roofline_json

        return roofline_json(steps_per_sec, n, scale=scale,
                             bytes_scale=bytes_scale,
                             ensemble=ensemble,
                             carry_bytes=carry_bytes, nu4=nu4,
                             precision=precision, device_kind=device_kind)
    except Exception as e:
        log(f"bench: variant roofline unavailable ({e})")
        return None


def _variant_entry(sim_days_per_sec: float, steps_per_sec: float, n: int,
                   scale: float = 1.0, bytes_scale: float = 1.0,
                   ensemble: int = 1, carry_bytes: int = None,
                   nu4: str = None, precision: str = None, **extra):
    """One ``variants`` JSON entry: rate + its own roofline numbers
    (round-6 satellite: the roofline is reported per variant, not just
    for the headline run).  ``scale`` adjusts the analytic covariant
    step cost for variants whose step does more work; ``carry_bytes``/
    ``nu4``/``precision`` are the precision-aware accounting knobs
    (see :func:`_roofline_json`); ``ensemble=B`` marks
    ``steps_per_sec`` as batched B-member steps (the roofline bills B
    members of flops AND bytes per step — truthful intensity) and
    ``sim_days_per_sec`` as AGGREGATE across members.  Every entry
    carries its ``dt60_equivalent`` (steps/s x 60 s) so cross-round
    rate comparisons never depend on the variant's own dt."""
    e = {"sim_days_per_sec": round(sim_days_per_sec, 4),
         "steps_per_sec": round(steps_per_sec, 2),
         "vs_baseline": round(sim_days_per_sec / BASELINE_PER_CHIP, 4),
         "dt60_equivalent": round(
             steps_per_sec * ensemble * 60.0 / 86400.0, 4)}
    if ensemble > 1:
        e["members"] = ensemble
        e["member_steps_per_sec"] = round(steps_per_sec * ensemble, 2)
    rl = _roofline_json(steps_per_sec, n, scale, bytes_scale, ensemble,
                        carry_bytes=carry_bytes, nu4=nu4,
                        precision=precision)
    if rl is not None:
        e["roofline"] = rl
    e.update(extra)
    return e


def _run_case(n, case, days, dt):
    """Integrate a Williamson case with the covariant formulation — the
    same discretization the benchmark times (fused Pallas stepper when it
    compiles, classic jnp otherwise).  Returns (grid, h0, h1) interior
    height fields as f64 numpy."""
    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.physics.initial_conditions import (williamson_tc2,
                                                      williamson_tc5)
    from jaxstream.stepping import integrate

    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    if case == "tc2":
        h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
        b_ext = None
    else:
        h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY,
                                             EARTH_OMEGA)
    nsteps = int(days * 86400 / dt)
    try:
        model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                      omega=EARTH_OMEGA, b_ext=b_ext,
                                      backend="pallas")
        step = model.make_fused_step(dt)
        state = model.initial_state(h_ext, v_ext)
        y = model.compact_state(state)
        run = jax.jit(lambda y: integrate(step, y, 0.0, nsteps, dt))
        out, _ = run(y)
        jax.block_until_ready(out["h"])
    except Exception as e:
        log(f"gate: fused stepper unavailable ({type(e).__name__}); "
            "using classic path")
        model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                      omega=EARTH_OMEGA, b_ext=b_ext)
        state = model.initial_state(h_ext, v_ext)
        out, _ = model.run(state, nsteps, dt)
    return (grid, np.asarray(state["h"], np.float64),
            np.asarray(out["h"], np.float64))


def accuracy_gates():
    """The Williamson-suite acceptance gates, at the standard the repo
    cites (SURVEY.md §4; BASELINE.md "L2 parity" row).  Thresholds are
    the measured f64-CPU truncation values of THIS discretization with
    a ~2x margin (the f32-TPU fused path reproduces them to 3-4
    digits; DESIGN.md "Acceptance gates"):

      TC2 C48, 5 days, dt=600 — the CFL-MATCHED step to the timed
      C384/dt=75 configuration (dt scales with n: 75 x 384/48), so
      this gate ties the benchmark's own Courant number to an
      ANALYTIC-truth error norm (TC2's steady state), round-5 VERDICT
      ask #5.  dt=600 vs dt=300 error norms agree to 3 digits in both
      f64 and f32 (spatial truncation dominates; SSPRK3 temporal error
      invisible at either step — f64-CPU dt=600: l1 9.93e-4,
      l2 1.372e-3, linf 7.20e-3; f32-TPU fused dt=600: 9.89e-4 /
      1.3716e-3 / 7.22e-3, dt=300: 9.86e-4 / 1.3713e-3 / 7.23e-3),
      and halving the steps halves the gate's wall time (VERDICT
      ask #7):
        l1 < 2e-3, l2 < 2.5e-3, linf < 1.4e-2
        mass drift < 2e-4   (measured f32 1.9e-5 over 720 steps;
                             f64 conserves to roundoff)
      TC5 C96, 15 days, dt=300 i.e. 4 320 steps — CFL-matched to the
      timed config by the same scaling (75 x 384/96 = 300; measured at
      this exact config on the v5e: h in [3 727, 5 953] m from initial
      [3 777, 5 960]; mass drift 1.04e-4):
        all finite, 3 000 < h < 6 500 m, mass drift < 1e-3

    Returns True iff every gate holds (each result logged to stderr).
    """
    ok = True

    grid, h0, h1 = _run_case(48, "tc2", days=5.0, dt=600.0)
    area = np.asarray(grid.interior(grid.area), np.float64)
    dh = h1 - h0
    l1 = np.sum(area * np.abs(dh)) / np.sum(area * np.abs(h0))
    l2 = np.sqrt(np.sum(area * dh**2) / np.sum(area * h0**2))
    linf = np.max(np.abs(dh)) / np.max(np.abs(h0))
    mass = abs(np.sum(area * h1) - np.sum(area * h0)) / np.sum(area * h0)
    log(f"gate TC2 C48 5d dt=600 (CFL-matched to the timed C384 "
        f"dt=75): l1={l1:.3e} (<2e-3) l2={l2:.3e} (<2.5e-3) "
        f"linf={linf:.3e} (<1.4e-2) mass_drift={mass:.3e} (<2e-4)")
    if not (l1 < 2e-3 and l2 < 2.5e-3 and linf < 1.4e-2 and mass < 2e-4):
        log("gate TC2: FAILED")
        ok = False

    grid5, h0, h1 = _run_case(96, "tc5", days=15.0, dt=300.0)
    area5 = np.asarray(grid5.interior(grid5.area), np.float64)
    finite = bool(np.all(np.isfinite(h1)))
    mass5 = (abs(np.sum(area5 * h1) - np.sum(area5 * h0))
             / np.sum(area5 * h0))
    log(f"gate TC5 C96 15d: finite={finite} "
        f"h_range=[{h1.min():.0f},{h1.max():.0f}] (in (3000,6500)) "
        f"mass_drift={mass5:.3e} (<1e-3)")
    if not (finite and h1.min() > 3000.0 and h1.max() < 6500.0
            and mass5 < 1e-3):
        log("gate TC5: FAILED")
        ok = False
    return ok


def bench_tc5(n=384, dt=BENCH_DT, warm_steps=10, timed_steps=24000,
              with_variants=True):
    """Timed run at dt=75 s — the CFL-matched time step (round 4).

    dt was 60 s through round 3; that configuration ran the C384 grid at
    a worst-cell 2-D CFL of 1.45 while this benchmark's own TC5 C96
    acceptance gate (dt=300, 15 days, re-proven stable on every bench
    run) runs the same discretization at 1.81.  dt=75 puts C384 at the
    gate's own CFL (1.816 vs 1.810, computed per-cell from
    sqrt(g h) + |v| and the metric cell spacings).  Verified on the v5e
    before adoption (round-4 evidence, DESIGN.md "The time step"):

    * 15-day C384 TC5 run at dt=75: finite, h in [3681, 5956] m, mass
      drift 4.1e-4 (dt=60: [3682, 5957], 5.2e-4).
    * Temporal accuracy: day-1 h l2-difference vs a dt=15 reference is
      1.15e-4 (dt=75) vs 1.09e-4 (dt=60) — flat in dt, i.e. BOTH are at
      the f32 roundoff floor; the SSPRK3 dt^3 truncation is invisible
      at either step.  At day 15 the difference vs a dt=30 reference is
      6.7e-4 vs 5.7e-4 with ratio 1.19 where pure time truncation would
      give (75/60)^3 = 1.95 — trajectory decorrelation, not scheme
      error, dominates both.
    * The timed windows below integrate ~26 simulated days of TC5 and
      the final state is gated (finite, physical h range, mass drift
      < 1e-3) every run — the dt=75 claim re-proves itself.

    The metric (sim-days/sec/chip) is dt-aware by construction: a
    larger stable-and-accurate step is a legitimate solver property,
    the same axis on which implicit/semi-Lagrangian dynamical cores
    compete.  The dt=60 equivalent is still printed each run for
    cross-round comparability.
    """
    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water import ShallowWater
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.physics.initial_conditions import williamson_tc5
    from jaxstream.stepping import integrate

    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)

    # Fastest-first ladder, probing one real step of each candidate so a
    # Mosaic compile failure (VMEM/shape limits, CPU bench runs) falls
    # through instead of crashing:
    #   1. covariant fused stepper (3 fields, rotation strips; ~1.4x the
    #      Cartesian fused stepper at C384),
    #   2. Cartesian fused stepper (in-kernel exchange),
    #   3. classic jnp SSPRK3.
    state = step = None
    rung = None
    try:
        model = CovariantShallowWater(
            grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext,
            backend="pallas")
        step = model.make_fused_step(dt)
        y = model.compact_state(model.initial_state(h_ext, v_ext))
        jax.block_until_ready(jax.jit(step)(y, jnp.float32(0.0)))
        state = y
        rung = "cov_fused"
        log("bench: using covariant compact fused SSPRK3 stepper "
            "(interior-only carry, rotation strips)")
    except Exception as e:
        log(f"bench: covariant fused stepper unavailable "
            f"({type(e).__name__}: {e})")
    if state is None:
        try:
            model = ShallowWater(grid, gravity=EARTH_GRAVITY,
                                 omega=EARTH_OMEGA, b_ext=b_ext,
                                 backend="pallas")
            step = model.make_fused_step(dt, in_kernel_exchange=True)
            y = model.extend_state(model.initial_state(h_ext, v_ext),
                                   with_strips=True)
            jax.block_until_ready(jax.jit(step)(y, jnp.float32(0.0)))
            state = y
            rung = "cart_fused"
            log("bench: using Cartesian fused SSPRK3 stepper "
                "(in-kernel exchange)")
        except Exception as e:
            log(f"bench: Cartesian fused stepper unavailable "
                f"({type(e).__name__}: {e})")
    if state is None:
        # Classic stepper; plain Pallas RHS kernel if it compiles (the
        # fused stage kernels have stricter VMEM/shape needs), jnp last.
        try:
            model = ShallowWater(grid, gravity=EARTH_GRAVITY,
                                 omega=EARTH_OMEGA, b_ext=b_ext,
                                 backend="pallas")
            state = model.initial_state(h_ext, v_ext)
            jax.block_until_ready(model.rhs(state, 0.0)["h"])
            rung = "pallas_rhs"
            log("bench: using classic stepper with pallas RHS kernel")
        except Exception as e:
            log(f"bench: pallas RHS unavailable ({type(e).__name__}); "
                f"using jnp")
            model = ShallowWater(grid, gravity=EARTH_GRAVITY,
                                 omega=EARTH_OMEGA, b_ext=b_ext)
            state = model.initial_state(h_ext, v_ext)
        step = model.make_step(dt, "ssprk3")

    # One compiled executable for any step count: nsteps rides the carry as
    # a traced bound (fori_loop lowers to a while), so the timed region is
    # pure device execution — no recompile between warmup and timing (the
    # reference's "no recompilation during timestepping" invariant, deck
    # p.10, applied to the benchmark harness itself).
    run = jax.jit(
        lambda y, nsteps: integrate(step, y, 0.0, nsteps, dt), donate_argnums=0
    )

    t0 = time.perf_counter()
    state_w, _ = run(state, warm_steps)
    jax.block_until_ready(state_w)
    log(f"bench: warmup {warm_steps} steps (incl. compile) "
        f"{time.perf_counter() - t0:.1f}s on {jax.devices()[0].platform}")

    from jaxstream.utils.profiling import steady_state_rate

    # steady_state_rate wants run(y, k) -> y; adapt integrate's (y, t).
    k1 = timed_steps // 4
    steps_per_sec, out = steady_state_rate(
        lambda y, k: run(y, k)[0], state_w, k1=k1, k2=timed_steps)

    # The timed window doubles as a >15-simulated-day stability gate on
    # the exact benchmarked configuration (this is what re-proves the
    # CFL-matched dt every run — see the docstring).  The carry's h is
    # extended on the cart_fused rung — gate on the interior either way.
    area_w = np.asarray(grid.interior(grid.area), np.float64)
    h0_f64 = np.asarray(grid.interior(h_ext), np.float64)
    mass0 = np.sum(area_w * h0_f64)

    def tc5_gate(h, label, mass_tol=1e-3):
        """Shared TC5 C384 stability gate: finite, physical h range,
        mass conserved vs the initial state.  Returns ok (logged).
        ``mass_tol``: every current caller uses the 1e-3 default
        (round 5 — the mixed16 carry holds mass at the default band);
        the override is kept for ad-hoc variants with a documented
        wider band, as the demoted bf16 line had."""
        if h.shape[-1] != grid.n:
            h = grid.interior(h)
        h = np.asarray(h, np.float64)
        finite = bool(np.all(np.isfinite(h)))
        mass_drift = abs(np.sum(area_w * h) - mass0) / mass0
        ok = (finite and 3000.0 < h.min() and h.max() < 6500.0
              and mass_drift < mass_tol)
        log(f"bench gate C{n} TC5 {label}: finite={finite} "
            f"h_range=[{h.min():.0f},{h.max():.0f}] (in (3000,6500)) "
            f"mass_drift={mass_drift:.3e} (<{mass_tol:g})")
        return ok

    # Total integration reaching `out`: warmup + both measurement
    # windows (k1 then timed_steps; retries would add more).
    sim_days_run = (warm_steps + k1 + timed_steps) * dt / 86400.0
    if not tc5_gate(out["h"], f"{sim_days_run:.1f}d (the timed run)"):
        raise RuntimeError(f"bench timed-run gate breached at dt={dt}")
    sim_days_per_sec = steps_per_sec * dt / 86400.0
    log(f"bench: C{n} TC5 windows {k1}/{timed_steps} steps -> "
        f"{steps_per_sec:.1f} steps/s (dt={dt}s, dispatch-overhead-free "
        "two-window differencing, utils.profiling.steady_state_rate)")
    log(f"bench: dt=60 equivalent (round-1..3 comparable): "
        f"{steps_per_sec * 60.0 / 86400.0:.4f} sim-days/sec/chip")
    try:  # roofline context (deck p.19's analysis frame; best-effort)
        from jaxstream.utils.profiling import (
            Roofline, analytic_cov_step_cost, device_roofs, roofline)

        roofs = device_roofs(jax.devices()[0].device_kind)
        if roofs is None:
            log("bench: no roofline share on cpu")
        elif rung in ("cov_fused", "cart_fused", "pallas_rhs"):
            # These rungs' math lives in Pallas kernels — invisible to
            # XLA's cost_analysis (round 1 printed a ~200x-off roofline
            # that way).  Use the hand-counted static-stencil cost
            # against the VPU roof (the stencils never touch the MXU);
            # consistent with DESIGN.md's stage-kernel bisection.  The
            # Cartesian-formulation rungs carry 4 fields + 3-vector
            # algebra: ~1.4x the covariant flops (DESIGN.md throughput
            # ladder) — scale the count and say so.
            scale = 1.0 if rung == "cov_fused" else 1.4
            c = analytic_cov_step_cost(n)
            r = Roofline(c["flops"] * scale, c["bytes"] * scale,
                         1.0 / steps_per_sec, roofs["vpu"])
            tag = ("" if rung == "cov_fused"
                   else f" (x{scale} Cartesian-formulation estimate)")
            log("bench: analytic kernel count "
                f"({c['flops_per_cell_stage']:.0f} flops/cell/stage, "
                f"+-15%{tag}; XLA cost_analysis excludes Pallas custom "
                "calls) " + r.report())
        else:  # pure-jnp rung: XLA sees every op, cost_analysis is real
            r = roofline(jax.jit(step), out, jnp.float32(0.0),
                         seconds=1.0 / steps_per_sec, roof=roofs["mxu"])
            log("bench: XLA-cost_analysis roofline " + r.report())
    except Exception as e:
        log(f"bench: roofline unavailable ({e})")

    variants = {"dt60_equivalent": round(steps_per_sec * 60.0 / 86400.0, 4)}
    if with_variants and rung == "cov_fused":
        # mixed16-carry variant (round 5; replaces the DEMOTED bf16
        # line): h stored int16 fixed-point (1/16 m quanta about a
        # static offset, magic-constant rounding) + u stored bf16.
        # Mass lives entirely in h, so this keeps the bf16 encoding's
        # u-side DMA speed (+5.4% of the +6.8%) while holding mass at
        # the int16 level — measured 4.8e-5 over 10.4 days at C384
        # (bf16 h-anomaly leaked 1.3e-3/day and needed its own 3e-2
        # band, the round-4 weakness).  Gate band here: THE DEFAULT
        # 1e-3.  Remaining trade: u's bf16 ulp puts TC2 C48 5-day l2
        # at 2.2e-3 vs f32's 1.37e-3 (passes the 2.5e-3 gate;
        # DESIGN.md carry ladder).
        try:
            from jaxstream.ops.pallas.precision import mixed16_encoding

            st0 = model.initial_state(h_ext, v_ext)
            cd, off, hs = mixed16_encoding(st0["h"])
            step16 = model.make_fused_step(dt, carry_dtype=cd,
                                           h_offset=off, h_scale=hs)
            y16 = model.encode_carry(model.compact_state(st0), cd, off,
                                     hs)
            run16 = jax.jit(
                lambda y, k: integrate(step16, y, 0.0, k, dt)[0],
                donate_argnums=0)
            y16 = run16(y16, warm_steps)
            jax.block_until_ready(y16["h"])
            rate16, out16 = steady_state_rate(
                lambda y, k: run16(y, k), y16, k1=3000, k2=12000)
            h16 = model.decode_carry(out16, h_offset=off, h_scale=hs)["h"]
            if not tc5_gate(h16, "mixed16 timed run"):
                raise RuntimeError("mixed16 variant gate breached")
            v16 = rate16 * dt / 86400.0
            # carry_bytes=2: the h int16 + u bf16 carry halves the
            # carry field-pass DMA; the orography re-read stays f32
            # (the old bytes_scale=0.5 billed b at 2 bytes too,
            # overstating the variant's AI — round-10 accounting
            # satellite, analytic_cov_step_cost's carry_bytes note).
            variants["mixed16_carry"] = _variant_entry(
                v16, rate16, n, carry_bytes=2, dt=dt)
            log(f"bench variant mixed16-carry: {rate16:.1f} steps/s -> "
                f"{v16:.4f} sim-days/sec/chip "
                f"({v16 / BASELINE_PER_CHIP:.4f}x baseline; h int16 + "
                "u bf16, mass at default band; DESIGN.md carry ladder)")
        except Exception as e:
            log(f"bench variant mixed16-carry unavailable "
                f"({type(e).__name__}: {e})")
        # bf16-stage variant (round 10): reduced precision IN the stage
        # arithmetic — flux face-average velocities, PLR limiter
        # algebra and router rotations in bfloat16, every accumulator
        # and metric term f32, bf16 inter-stage strips
        # (jaxstream.ops.pallas.precision; measured error budgets in
        # tests/test_precision.py and DESIGN.md "Precision ladder").
        # Own 15-day TC5 gate at the DEFAULT mass band: warm + 3000 +
        # 14400 steps at dt=75 integrates 15.1 simulated days, so the
        # timed windows ARE the gate integration.
        try:
            from jaxstream.ops.pallas.precision import encode_strips

            stepbf = model.make_fused_step(dt, precision="bf16")
            ybf = encode_strips(
                model.compact_state(model.initial_state(h_ext, v_ext)),
                "bf16")
            runbf = jax.jit(
                lambda y, k: integrate(stepbf, y, 0.0, k, dt)[0],
                donate_argnums=0)
            ybf = runbf(ybf, warm_steps)
            jax.block_until_ready(ybf["h"])
            ratebf, outbf = steady_state_rate(
                lambda y, k: runbf(y, k), ybf, k1=3000, k2=14400)
            if not tc5_gate(outbf["h"], "15.1d bf16-stage timed run"):
                raise RuntimeError("bf16-stage variant gate breached")
            vbf = ratebf * dt / 86400.0
            variants["bf16_stage"] = _variant_entry(
                vbf, ratebf, n, precision="bf16", dt=dt)
            log(f"bench variant bf16-stage: {ratebf:.1f} steps/s -> "
                f"{vbf:.4f} sim-days/sec/chip "
                f"({vbf / BASELINE_PER_CHIP:.4f}x baseline; bf16 "
                "flux/recon/router arithmetic, f32 accumulators + "
                "metric terms, own 15-day gate at the default band)")
        except Exception as e:
            log(f"bench variant bf16-stage unavailable "
                f"({type(e).__name__}: {e})")
        # dt=90 variant: the empirical max-stable step (round 4: 15-day
        # stable at dt=90 and 82.5; NaN at 100/110/120, so ~10% below
        # the blowup edge — too thin a margin for the default, which
        # stays at the CFL-matched 75).  Day-1 temporal error at dt=90
        # is 1.20e-4 vs a dt=15 reference — same roundoff-floor
        # plateau as dt=60/75, so accuracy is unchanged.  steps/s is
        # dt-independent (dt is a kernel constant), so the rate below
        # reuses the timed measurement; the 15-day stability gate is
        # re-proven here on every bench run.
        try:
            step90 = model.make_fused_step(90.0)
            y90 = model.compact_state(model.initial_state(h_ext, v_ext))
            run90 = jax.jit(
                lambda y, k: integrate(step90, y, 0.0, k, 90.0)[0],
                donate_argnums=0)
            h90 = run90(y90, 14400)["h"]
            if tc5_gate(h90, "15d at dt=90"):
                v90 = steps_per_sec * 90.0 / 86400.0
                variants["dt90_max_stable"] = _variant_entry(
                    v90, steps_per_sec, n, dt=90.0)
                log(f"bench variant dt90-max-stable: {v90:.4f} "
                    f"sim-days/sec/chip ({v90 / BASELINE_PER_CHIP:.4f}x"
                    " baseline; empirical stability edge ~dt=100, "
                    "margin rationale in DESIGN.md)")
            else:
                log("bench variant dt90: stability gate FAILED — "
                    "not reported")
        except Exception as e:
            log(f"bench variant dt90 unavailable "
                f"({type(e).__name__}: {e})")
        # Combined variant (round 5): the two trades above are
        # orthogonal — mixed16 trades u-ulp accuracy for rate, dt=90
        # trades stability margin for sim-days/step — so their product
        # is a legitimate gated configuration.  Requires BOTH parents'
        # gates green this run, plus its own 15-day integration gate
        # at the default mass band.
        if "mixed16_carry" in variants and "dt90_max_stable" in variants:
            try:
                # st0/off/cd/hs are the mixed16 parent's own values —
                # the guard above proves that block completed, so the
                # combined gate tests EXACTLY the reported encoding.
                s9016 = model.make_fused_step(90.0, carry_dtype=cd,
                                              h_offset=off, h_scale=hs)
                y9016 = model.encode_carry(model.compact_state(st0), cd,
                                           off, hs)
                run9016 = jax.jit(
                    lambda y, k: integrate(s9016, y, 0.0, k, 90.0)[0],
                    donate_argnums=0)
                out9016 = run9016(y9016, 14400)          # 15 days
                h9016 = model.decode_carry(out9016, h_offset=off,
                                           h_scale=hs)["h"]
                if tc5_gate(h9016, "15d at dt=90 + mixed16"):
                    # rate: the mixed16 steps/s (dt-independent) — the
                    # RAW measurement, not the display-rounded JSON
                    # field, so presentation rounding cannot skew it.
                    v = rate16 * 90.0 / 86400.0
                    variants["mixed16_dt90"] = _variant_entry(
                        v, rate16, n, carry_bytes=2, dt=90.0)
                    log(f"bench variant mixed16+dt90: {v:.4f} "
                        f"sim-days/sec/chip "
                        f"({v / BASELINE_PER_CHIP:.4f}x baseline; both "
                        "parent trades documented, own 15-day gate)")
                else:
                    log("bench variant mixed16+dt90: gate FAILED — "
                        "not reported")
            except Exception as e:
                log(f"bench variant mixed16+dt90 unavailable "
                    f"({type(e).__name__}: {e})")
        # Stacked variant (round 10): bf16 stage arithmetic + mixed16
        # carry + dt=90 — ALL three orthogonal trades at once
        # (arithmetic dtype / storage dtype / step size).  Requires all
        # three parents' gates green this run, then its own 15-day
        # integration at the default mass band (the three trades have
        # never been proven jointly stable by their parents — the
        # stacked gate is the evidence).  Its rate is measured on its
        # OWN stepper: it runs arithmetic neither parent runs.
        if ("bf16_stage" in variants and "mixed16_carry" in variants
                and "dt90_max_stable" in variants):
            try:
                from jaxstream.ops.pallas.precision import encode_strips

                sstk = model.make_fused_step(
                    90.0, precision="bf16", carry_dtype=cd,
                    h_offset=off, h_scale=hs)
                ystk = encode_strips(model.encode_carry(
                    model.compact_state(st0), cd, off, hs), "bf16")
                runstk = jax.jit(
                    lambda y, k: integrate(sstk, y, 0.0, k, 90.0)[0],
                    donate_argnums=0)
                outstk = runstk(ystk, 14400)          # 15 days
                hstk = model.decode_carry(outstk, h_offset=off,
                                          h_scale=hs)["h"]
                if tc5_gate(hstk, "15d bf16-stage + mixed16 at dt=90"):
                    ratestk, outstk2 = steady_state_rate(
                        lambda y, k: runstk(y, k), outstk,
                        k1=3000, k2=12000)
                    # The timing windows integrate ~16 MORE days on a
                    # stack never proven stable past its 15-day gate —
                    # re-gate the post-timing state like every sibling
                    # so a late blowup can't publish a rate.
                    hstk2 = model.decode_carry(
                        outstk2, h_offset=off, h_scale=hs)["h"]
                    if not tc5_gate(hstk2, "post-timing stacked (31d)"):
                        raise RuntimeError(
                            "stacked variant breached its gate during "
                            "the timing windows")
                    v = ratestk * 90.0 / 86400.0
                    variants["bf16_mixed16_dt90"] = _variant_entry(
                        v, ratestk, n, carry_bytes=2, precision="bf16",
                        dt=90.0)
                    log(f"bench variant bf16+mixed16+dt90 (stacked): "
                        f"{ratestk:.1f} steps/s -> {v:.4f} "
                        f"sim-days/sec/chip "
                        f"({v / BASELINE_PER_CHIP:.4f}x baseline; "
                        f"dt60-equivalent "
                        f"{ratestk * 60.0 / 86400.0:.4f}; all three "
                        "parent trades gated green this run + own "
                        "15-day gate)")
                else:
                    log("bench variant bf16+mixed16+dt90: gate FAILED "
                        "— not reported")
            except Exception as e:
                log(f"bench variant bf16+mixed16+dt90 unavailable "
                    f"({type(e).__name__}: {e})")
        # temporal_block variant (round 6): k=4 fused SSPRK3 steps per
        # dispatch (make_fused_ssprk3_cov_multistep — bitwise-identical
        # to the headline stepper, so the timed-run gate transfers; its
        # own ~2.8-day window is still gated below).  On one chip this
        # measures dispatch amortization; the exchanges/step and
        # redundant-compute numbers of the multichip deep-halo form
        # (same k) ride along from comm_probe.temporal_block_plan so
        # the JSON line carries the full temporal-blocking story.
        try:
            from jaxstream.utils.comm_probe import temporal_block_plan

            ktb = 4
            steptb = model.make_fused_step(dt, temporal_block=ktb)
            ytb = model.compact_state(model.initial_state(h_ext, v_ext))
            runtb = jax.jit(
                lambda y, kk: integrate(steptb, y, 0.0, kk, dt * ktb)[0],
                donate_argnums=0)
            ytb = runtb(ytb, max(1, warm_steps // ktb))
            jax.block_until_ready(ytb["h"])
            ratetb, outtb = steady_state_rate(
                lambda y, kk: runtb(y, kk), ytb, k1=3000 // ktb,
                k2=12000 // ktb)
            ratetb *= ktb                       # blocks/s -> steps/s
            if not tc5_gate(outtb["h"], f"temporal_block={ktb} timed run"):
                raise RuntimeError("temporal_block variant gate breached")
            vtb = ratetb * dt / 86400.0
            plan = temporal_block_plan(n, 2, ktb)
            variants["temporal_block"] = _variant_entry(
                vtb, ratetb, n, dt=dt, temporal_block=ktb,
                exchanges_per_step=plan["ppermutes_per_step"],
                serialized_exchanges_per_step=plan[
                    "serialized_ppermutes_per_step"],
                redundant_compute_fraction=round(
                    plan["redundant_compute_fraction"], 4))
            log(f"bench variant temporal_block k={ktb}: {ratetb:.1f} "
                f"steps/s -> {vtb:.4f} sim-days/sec/chip "
                f"({vtb / BASELINE_PER_CHIP:.4f}x baseline; "
                f"deep-halo plan: {plan['ppermutes_per_step']:.1f} "
                f"exchanges/step vs 12, redundant compute "
                f"{plan['redundant_compute_fraction']:.3f})")
        except Exception as e:
            log(f"bench variant temporal_block unavailable "
                f"({type(e).__name__}: {e})")
    return sim_days_per_sec, variants


def bench_galewsky(n=384, dt=60.0, nu4=1.0e14, nu4_mode="split"):
    """Galewsky C384 with the del^4 filter stepper — the variant line
    for the flagship validation case.  ``nu4_mode='split'`` is the
    round-5 once-per-step filter kernel (three plain RK stage kernels
    + one filter kernel, 1.90x the round-4 in-stage pair; BASELINE.md
    ladder config #5); ``'refused'`` is the round-10 re-fusion — the
    filter commuted into the stage-1 kernel, 3 kernels + 3 routes per
    step vs split's 4 + 4 (ops/pallas/swe_cov.py re-fusion note).

    Runs the jet to day 6 (8 640 steps) and gates on the instability's
    physics before reporting a rate: finite fields, physical h range,
    mass conservation, day-6 vorticity filaments in the documented band
    (max |zeta| ~1.5e-4 s^-1, docs/galewsky_c384_day6_vorticity.png),
    and a QUIESCENT southern hemisphere (measured 8e-7 vs the north's
    1.5e-4 — any spurious noise source trips this 180x separation).
    The re-fused line runs the IDENTICAL day-6 gate: the two forms'
    trajectories differ by one endpoint filter application (O(damp)),
    so passing the same physics bands is the equivalence evidence.
    dt=60: the jet adds ~80 m/s to the gravity-wave speed, so TC5's
    CFL-matched 75 s does not transfer.  Returns
    ``(sim-days/sec/chip, steps/s)`` — ``(0.0, 0.0)`` on gate breach.
    """
    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.ops.fv import vorticity_cov
    from jaxstream.physics.initial_conditions import galewsky
    from jaxstream.stepping import integrate
    from jaxstream.utils.profiling import steady_state_rate

    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext = galewsky(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA, backend="pallas",
                                  nu4=nu4)
    step = model.make_fused_step(dt, nu4_mode=nu4_mode)
    st = model.initial_state(h_ext, v_ext)
    area = np.asarray(grid.interior(grid.area), np.float64)
    h0 = np.asarray(st["h"], np.float64)
    m0 = np.sum(area * h0)
    run = jax.jit(lambda y, k: integrate(step, y, 0.0, k, dt)[0],
                  donate_argnums=0)

    y = run(model.compact_state(st), 8640)          # day 6
    h = np.asarray(y["h"], np.float64)
    zeta = np.asarray(vorticity_cov(grid, model._fill_u(y["u"])),
                      np.float64)
    lat = np.asarray(grid.interior(grid.lat))
    zN = np.abs(zeta)[lat > 0.2].max()
    zS = np.abs(zeta)[lat < -0.2].max()
    mass = abs(np.sum(area * h) - m0) / m0
    ok = (bool(np.all(np.isfinite(h))) and 8500.0 < h.min()
          and h.max() < 10800.0 and mass < 1e-3
          and 5e-5 < zN < 5e-4 and zS < 5e-6)
    log(f"gate Galewsky C{n} nu4 ({nu4_mode}) day-6: "
        f"finite={np.all(np.isfinite(h))} "
        f"h_range=[{h.min():.0f},{h.max():.0f}] (in (8500,10800)) "
        f"mass_drift={mass:.2e} (<1e-3) max|zeta| N={zN:.2e} "
        f"(in (5e-5,5e-4)) S={zS:.2e} (<5e-6, quiescent hemisphere)")
    if not ok:
        log("gate Galewsky: FAILED — variant reported as 0")
        return 0.0, 0.0

    rate, out = steady_state_rate(lambda y, k: run(y, k), y,
                                  k1=2000, k2=8000)
    if not np.all(np.isfinite(np.asarray(out["h"]))):
        log("bench variant galewsky: non-finite after timing — 0")
        return 0.0, 0.0
    v = rate * dt / 86400.0
    log(f"bench variant galewsky-nu4 ({nu4_mode}): {rate:.1f} steps/s "
        f"-> {v:.4f} sim-days/sec/chip ({v / BASELINE_PER_CHIP:.4f}x "
        f"baseline; {nu4_mode} del^4 filter stepper, dt=60)")
    return v, rate


def bench_ensemble(n=96, dt=300.0, members=(1, 4, 16), warm=6,
                   k1=2000, k2=8000, gates=True, bitwise_check=True):
    """Batched ensemble section: aggregate throughput for B members.

    The many-concurrent-simulations workload (perturbed-IC TC5
    ensembles): one batched stepper call advances all B members, the
    member axis folded into the fused stage kernels' grid
    (make_fused_ssprk3_cov_compact(ensemble=B)) so small per-member
    grids stop paying per-call dispatch/DMA glue once per member.

    Default configuration: **C96 at the CFL-matched dt=300** — the TC5
    gate config — not the headline C384.  Ensembles are a
    members-x-moderate-resolution workload by nature, and that is
    where batching pays: at C96 the per-member step is small enough
    that fixed per-call glue (dispatch, DMA setup, router op dispatch)
    is a large step-time fraction, so folding B members into one
    launch buys aggregate throughput; at C384 a single member already
    fills the VPU and B mostly amortizes the residual glue.  (Pass
    n=384 to measure that regime explicitly.)
    Reports, per B: batched ensemble-steps/s, member-steps/s, AGGREGATE
    sim-days/sec/chip (the serving metric — total simulated days
    delivered across members), and a B-scaled roofline (flops AND bytes
    x B: truthful intensity).  Also records the B=1 batched-vs-unbatched
    bitwise check (the batching acceptance criterion) and the batched-
    exchange payload accounting for the largest B.  Falls back to the
    vmapped classic stepper (impl tag) where the fused kernels don't
    compile, so the section runs end-to-end on any backend; ``gates``
    off skips the physical-range checks (the --smoke mode).
    ``bitwise_check`` off skips the standalone B=1 batched-vs-unbatched
    jit (one full stepper compile, ~15 s on this CPU): the smoke tier
    leaves that exact parity to
    tests/test_ensemble.py::test_b1_batched_bitwise_vs_unbatched, which
    runs in the same gate — the full bench keeps it inline.
    """
    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.physics.initial_conditions import (perturbed_ensemble,
                                                      williamson_tc5)
    from jaxstream.stepping import integrate
    from jaxstream.utils.profiling import steady_state_rate

    out = {"dt": dt, "case": "tc5", "members": list(members)}
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)

    impl = "fused_kernel"
    step1j = y1 = None
    stepB_cache = {}
    try:
        model = CovariantShallowWater(
            grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext,
            backend="pallas")
        # The compile IS the availability probe: on CPU the fused
        # pallas kernels construct fine and only fail here ("Only
        # interpret mode is supported"), which is what routes the
        # section to the vmapped classic stepper.
        step1j = jax.jit(model.make_fused_step(dt))
        y1 = model.compact_state(model.initial_state(h_ext, v_ext))
        jax.block_until_ready(step1j(y1, jnp.float32(0.0)))
    except Exception as e:
        log(f"bench ensemble: fused stepper unavailable "
            f"({type(e).__name__}: {e}); using vmapped classic stepper")
        impl = "vmap_classic"
        model = CovariantShallowWater(
            grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext)
    out["impl"] = impl

    if impl == "fused_kernel" and bitwise_check:
        # B=1 batched path must be bitwise-identical to the unbatched
        # stepper (the acceptance criterion of the member-axis fold).
        # The B=1 stepper is cached for the rate loop below; one jitted
        # unbatched stepper serves warm-up and check (the relay pays
        # ~1-40 s per compile — don't trace twice).
        try:
            stepB_cache[1] = model.make_fused_step(dt, ensemble=1)
            yb1 = model.ensemble_compact_state(
                model.stack_ensemble([model.initial_state(h_ext, v_ext)]))
            ob = jax.jit(stepB_cache[1])(yb1, jnp.float32(0.0))
            o1 = step1j(y1, jnp.float32(0.0))
            same = all(bool(jnp.all(
                (ob[k][:, 0] if k == "u" else ob[k][0]) == o1[k]))
                for k in o1)
            out["b1_bitwise"] = bool(same)
            log(f"bench ensemble: B=1 batched vs unbatched "
                f"bitwise={same}")
        except Exception as e:
            out["b1_bitwise"] = f"unavailable ({type(e).__name__}: {e})"

    h_b = perturbed_ensemble(grid, h_ext, max(members), seed=0,
                             amplitude=1e-3)

    def mk_run(stepB):
        return jax.jit(lambda y, k: integrate(stepB, y, 0.0, k, dt)[0],
                       donate_argnums=0)

    rates = {}
    for B in members:
        try:
            states = [model.initial_state(h_b[i], v_ext)
                      for i in range(B)]

            def build_carry():
                # Fresh carry per measurement attempt: runB DONATES its
                # input, so a retry can never reuse consumed buffers
                # (the per-member `states` are untouched by stacking).
                b = model.stack_ensemble(states)
                return (model.ensemble_compact_state(b)
                        if impl == "fused_kernel" else b)

            if impl == "fused_kernel":
                stepB = stepB_cache.get(B)
                if stepB is None:
                    stepB = model.make_fused_step(dt, ensemble=B)
            else:
                from jaxstream.parallel.sharded_model import \
                    make_stepper_for

                stepB = make_stepper_for(model, None, build_carry(), dt,
                                         "ssprk3", ensemble=B)
            runB = mk_run(stepB)
            yB = runB(build_carry(), warm)
            jax.block_until_ready(yB["h"])
            try:
                rate, outB = steady_state_rate(
                    lambda y, k: runB(y, k), yB, k1=k1, k2=k2)
            except Exception:
                # Tiny smoke windows can land t2 <= t1; one plain
                # window (on a rebuilt, re-warmed carry — yB was
                # donated by the failed attempt) is accurate enough
                # for a bitrot canary.
                yB = runB(build_carry(), warm)
                jax.block_until_ready(yB["h"])
                t0 = time.perf_counter()
                outB = runB(yB, k2)
                jax.block_until_ready(outB["h"])
                rate = k2 / (time.perf_counter() - t0)
            hB = np.asarray(outB["h"], np.float64)
            finite = bool(np.all(np.isfinite(hB)))
            ok = finite and (not gates
                             or (3000.0 < hB.min() and hB.max() < 6500.0))
            if not ok:
                log(f"bench ensemble B={B}: gate breached (finite="
                    f"{finite}, h=[{hB.min():.0f},{hB.max():.0f}]) — "
                    "entry reported as 0")
                rates[B] = None
                out[f"B{B}"] = {"sim_days_per_sec": 0.0}
                continue
            agg = rate * B * dt / 86400.0
            rates[B] = agg
            out[f"B{B}"] = _variant_entry(agg, rate, n, ensemble=B,
                                          dt=dt)
            log(f"bench ensemble B={B}: {rate:.2f} ensemble-steps/s "
                f"({rate * B:.1f} member-steps/s) -> {agg:.4f} "
                "aggregate sim-days/sec/chip")
        except Exception as e:
            log(f"bench ensemble B={B} unavailable "
                f"({type(e).__name__}: {e})")
            rates[B] = None
            out[f"B{B}"] = {"skipped": f"{type(e).__name__}: {e}"}
    b0, bN = members[0], members[-1]
    if rates.get(b0) and rates.get(bN):
        out["agg_speedup"] = {"vs": f"B{bN}/B{b0}",
                              "x": round(rates[bN] / rates[b0], 4)}
        log(f"bench ensemble: aggregate throughput B{bN}/B{b0} = "
            f"{rates[bN] / rates[b0]:.3f}x")
    try:
        from jaxstream.utils.comm_probe import batched_exchange_plan

        out["batched_exchange_plan"] = batched_exchange_plan(n, 2, bN)
    except Exception as e:
        log(f"bench ensemble: exchange plan unavailable ({e})")
    return out


def bench_serving(n=96, dt=300.0, bucket=16, n_requests=48, seg=8,
                  backend="pallas", lengths=None, ic="tc5", gates=True):
    """Serving section: continuous batching vs serial B=1 (round 11).

    The throughput headline of the ensemble server (jaxstream.serve):
    ``n_requests`` heterogeneous-run-length scenario requests (same IC
    family, distinct perturbation seeds, lengths cycling a ragged
    ladder so members finish mid-batch and slots refill continuously)
    are served twice —

      * **packed**: one bucket of size ``bucket`` — requests ride the
        member axis, per-member masking + boundary refill keep the
        slots busy;
      * **serial_B1**: the same trace through a B=1 bucket — the
        no-batching reference every request-at-a-time deployment runs.

    Reports per mode: aggregate member-steps/s and sim-days/sec/chip
    (the serving metric), slot occupancy and step utilization, request
    latency p50/p99 (requests are all admitted up front, so the serial
    tail latency IS the queue wait the packed mode removes), warmup
    compile count, and the steady-state recompile count (must be 0 —
    the shape-bucketing claim).  ``main()`` divides the packed
    member-steps/s by the ensemble section's static-B=16 rate: the
    acceptance floor is >= 0.9x (masking + refill overhead must stay
    under 10% of the PR-3 batched rate).  Warmup/compile time is
    excluded from the timed window (steady-state serving).  Never
    raises (returns ``{"skipped": ...}``).
    """
    try:
        from jaxstream.serve import EnsembleServer, ScenarioRequest

        if lengths is None:
            lengths = (seg * 3, seg * 5 + 3, seg * 2 + 1, seg * 7,
                       seg * 4 + 5)
        out = {"n": n, "dt": dt, "bucket": bucket,
               "n_requests": n_requests, "segment_steps": seg,
               "ic": ic, "lengths": list(lengths)}
        group = "oro" if ic == "tc5" else "flat"

        def mk_requests():
            return [ScenarioRequest(
                id=f"r{i}", ic=ic, nsteps=lengths[i % len(lengths)],
                seed=i, amplitude=1e-3)
                for i in range(n_requests)]

        def run_mode(b):
            # group_by_orography: true pins the round-11 code path
            # (orography a stepper static, fused member-fold where it
            # compiles) so this section's numbers stay byte-for-byte
            # comparable across rounds; the single-family trace never
            # exercises mixed batches anyway.  The mixed and multichip
            # paths are measured by bench_serving_multichip.
            cfg = {"grid": {"n": n, "halo": 2, "dtype": "float32"},
                   "time": {"dt": dt},
                   "model": {"name": "shallow_water_cov",
                             "backend": backend},
                   "serve": {"buckets": str(b), "segment_steps": seg,
                             "queue_capacity": n_requests + 1,
                             "group_by_orography": True}}
            srv = EnsembleServer(cfg)
            try:
                srv.warmup(groups=(group,))       # compiles excluded
                for r in mk_requests():
                    srv.submit(r)
                t0 = time.perf_counter()
                srv.serve()
                wall = time.perf_counter() - t0
                lat = srv.latencies()
                ms = srv.stats["member_steps"]
                entry = {
                    "completed": srv.stats["completed"],
                    "evicted": srv.stats["evicted"],
                    "segments": srv.stats["segments"],
                    "refills": srv.stats["refills"],
                    "occupancy_mean": round(srv.occupancy_mean, 4),
                    "utilization_mean": round(srv.utilization_mean, 4),
                    "member_steps": ms,
                    "member_steps_per_sec": round(ms / wall, 2),
                    "agg_sim_days_per_sec_per_chip": round(
                        ms * dt / 86400.0 / wall, 4),
                    "latency_p50_s": round(
                        float(np.percentile(lat, 50)), 4),
                    "latency_p99_s": round(
                        float(np.percentile(lat, 99)), 4),
                    "warmup_compiles": srv.stats["warmup_compiles"],
                    "steady_recompiles": (
                        srv.compile_count()
                        - srv.stats["warmup_compiles"]),
                    "impl": srv._impl_for(group, srv._plans[b]),
                    "wall_s": round(wall, 3),
                }
                if srv.stats["completed"] != n_requests:
                    raise RuntimeError(
                        f"serving B={b}: only {srv.stats['completed']}"
                        f"/{n_requests} requests completed")
                if gates:
                    for r in srv.results.values():
                        h = np.asarray(r.fields["h"], np.float64)
                        if not (np.all(np.isfinite(h))
                                and 3000.0 < h.min()
                                and h.max() < 6500.0):
                            raise RuntimeError(
                                f"serving B={b}: request {r.id} gate "
                                f"breached (h=[{h.min():.0f},"
                                f"{h.max():.0f}])")
                return entry
            finally:
                srv.close()

        out["packed"] = run_mode(bucket)
        out["serial_B1"] = run_mode(1)
        p, s = (out["packed"]["member_steps_per_sec"],
                out["serial_B1"]["member_steps_per_sec"])
        out["packed_vs_serial"] = round(p / s, 4) if s else None
        log(f"bench serving C{n} {ic} {n_requests} reqs "
            f"(bucket {bucket}, seg {seg}): packed "
            f"{p:.1f} member-steps/s (occ "
            f"{out['packed']['occupancy_mean']:.2f}, p50/p99 "
            f"{out['packed']['latency_p50_s']:.2f}/"
            f"{out['packed']['latency_p99_s']:.2f}s, "
            f"{out['packed']['steady_recompiles']} steady recompiles) "
            f"vs serial-B1 {s:.1f} -> {out['packed_vs_serial']}x")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench serving: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_serving_multichip(n=96, dt=300.0, per_chip=4, seg=8,
                            reqs_per_chip=6, mode="member",
                            devices=0, backend="jnp", ic="tc2",
                            lengths=None, gates=True):
    """Multi-chip serving section (round 12): N-device scaling floor.

    The acceptance measurement of serve.placement: the SAME ragged
    per-chip traffic is served twice at equal per-chip batch —

      * **single**: one device, bucket ``per_chip`` (placement off —
        the round-11 executable, byte-for-byte);
      * **multichip**: all ``N`` devices, bucket ``per_chip * N``
        under the requested placement mode, with ``N`` x the request
        count (so each chip sees the same steady-state load).

    Reports per mode: aggregate member-steps/s and sim-days/sec (the
    serving metric — NOT per chip: the whole point is that one server
    process now delivers N chips' worth), occupancy/utilization,
    steady-recompile counts, and the scaling ratio
    ``aggregate_multichip / (N * aggregate_single)``.  The acceptance
    floor ``>= 0.8`` (``meets_0p8_floor``) is ENFORCED — reported as a
    gate breach — only on real accelerators: on the fake-device CPU
    mesh (the MULTICHIP-gate test environment, also used by the smoke
    canary) all N "devices" share one host's cores, so the ratio
    measures XLA's partitioned-executable overhead, not scaling, and
    is reported with ``floor_enforced: false``.  The single-device
    parity claim IS asserted everywhere: packed h results must be
    byte-identical between the modes (u carries the established
    <= 1e-6 member-batching budget) — ``bitwise_h_ok``.

    Never raises (returns ``{"skipped": ...}``) — e.g. when fewer than
    2 devices exist in-process.
    """
    try:
        import jax

        from jaxstream.serve import EnsembleServer, ScenarioRequest

        n_dev = devices or len(jax.devices())
        if n_dev < 2:
            return {"skipped": f"needs >= 2 devices, have {n_dev}"}
        platform = jax.devices()[0].platform
        enforce = platform not in ("cpu",)
        if lengths is None:
            lengths = (seg * 3, seg * 5 + 3, seg * 2 + 1, seg * 4)
        out = {"n": n, "dt": dt, "per_chip": per_chip,
               "segment_steps": seg, "devices": n_dev, "mode": mode,
               "platform": platform, "ic": ic,
               "floor_enforced": bool(enforce)}

        def run_mode(bucket, placement, n_requests):
            cfg = {"grid": {"n": n, "halo": 2, "dtype": "float32"},
                   "time": {"dt": dt},
                   "model": {"name": "shallow_water_cov",
                             "backend": backend},
                   "serve": {"buckets": str(bucket),
                             "segment_steps": seg,
                             "queue_capacity": n_requests + 1,
                             # panel placement bakes orography per
                             # device (grouped mode); both runs use
                             # the same flag so the parity compare is
                             # stepper-for-stepper.
                             "group_by_orography": mode == "panel"}}
            if placement is not None:
                cfg["serve"]["placement"] = placement
            srv = EnsembleServer(cfg)
            try:
                srv.warmup(groups=("flat",))      # compiles excluded
                for i in range(n_requests):
                    srv.submit(ScenarioRequest(
                        id=f"r{i}", ic=ic,
                        nsteps=lengths[i % len(lengths)],
                        seed=i % reqs_per_chip, amplitude=1e-3,
                        outputs=("h", "u")))
                t0 = time.perf_counter()
                srv.serve()
                wall = time.perf_counter() - t0
                ms = srv.stats["member_steps"]
                if srv.stats["completed"] != n_requests:
                    raise RuntimeError(
                        f"only {srv.stats['completed']}/{n_requests} "
                        f"requests completed")
                entry = {
                    "completed": srv.stats["completed"],
                    "segments": srv.stats["segments"],
                    "refills": srv.stats["refills"],
                    "occupancy_mean": round(srv.occupancy_mean, 4),
                    "utilization_mean": round(srv.utilization_mean, 4),
                    "member_steps": ms,
                    "member_steps_per_sec": round(ms / wall, 2),
                    "agg_sim_days_per_sec": round(
                        ms * dt / 86400.0 / wall, 4),
                    "host_wait_s": round(srv.stats["host_wait_s"], 4),
                    "steady_recompiles": (
                        srv.compile_count()
                        - srv.stats["warmup_compiles"]),
                    "wall_s": round(wall, 3),
                }
                if placement is not None:
                    entry["placement"] = srv.placement_summary()
                results = {rid: r.fields for rid, r in
                           srv.results.items()}
                return entry, results
            finally:
                srv.close()

        # Equal per-chip batch and load: the single-device reference
        # serves reqs_per_chip requests through a per_chip bucket; the
        # multichip run serves N x as many through a per_chip*N bucket.
        out["single"], res1 = run_mode(per_chip, None, reqs_per_chip)
        out["multichip"], resN = run_mode(
            per_chip * n_dev,
            {"mode": mode, "num_devices": n_dev,
             "device_type": "default" if platform != "cpu" else "cpu"},
            reqs_per_chip * n_dev)

        # Parity on the shared request ids (same seed + length).
        # Member mode runs the SAME program GSPMD-partitioned: h must
        # be byte-identical across placements, u within the 2e-6
        # packed-vs-packed member-batching budget.  Panel mode runs a
        # DIFFERENT RHS implementation (shard_map per-face kernels +
        # strip exchange vs the classic oracle): both fields carry the
        # established cross-tier <= 1e-6 budget instead — bitwise is
        # not the contract there (docs/USAGE.md "Multi-chip serving").
        bitwise = True
        h_rel_max = u_rel_max = 0.0
        for rid, f1 in res1.items():
            fN = resN.get(rid)
            if fN is None:
                continue
            if np.asarray(f1["h"]).tobytes() != \
                    np.asarray(fN["h"]).tobytes():
                bitwise = False
            for key in ("h", "u"):
                a = np.asarray(fN[key], np.float64)
                b = np.asarray(f1[key], np.float64)
                rel = float(np.abs(a - b).max() / np.abs(b).max())
                if key == "h":
                    h_rel_max = max(h_rel_max, rel)
                else:
                    u_rel_max = max(u_rel_max, rel)
        out["bitwise_h_ok"] = bool(bitwise)
        out["h_rel_max"] = h_rel_max
        out["u_rel_max"] = u_rel_max
        sm, ss = (out["multichip"]["member_steps_per_sec"],
                  out["single"]["member_steps_per_sec"])
        ratio = sm / (n_dev * ss) if ss else None
        out["scaling_vs_ideal"] = (round(ratio, 4)
                                   if ratio is not None else None)
        out["meets_0p8_floor"] = (bool(ratio >= 0.8)
                                  if ratio is not None else None)
        out["zero_steady_recompiles"] = bool(
            out["single"]["steady_recompiles"] == 0
            and out["multichip"]["steady_recompiles"] == 0)
        log(f"bench serving_multichip C{n} {mode} x{n_dev} "
            f"({platform}): {sm:.1f} member-steps/s aggregate vs "
            f"single {ss:.1f} -> {out['scaling_vs_ideal']}x of ideal "
            f"N-chip scaling (floor 0.8 "
            f"{'ENFORCED' if enforce else 'reported only — fake CPU mesh'}"
            f"), bitwise_h={out['bitwise_h_ok']}, "
            f"h_rel={h_rel_max:.2e}, u_rel={u_rel_max:.2e}, "
            f"{out['multichip']['steady_recompiles']} steady recompiles")
        if gates:
            if mode == "panel":
                if max(h_rel_max, u_rel_max) > 1e-6:
                    raise RuntimeError(
                        f"serving_multichip: panel-sharded parity "
                        f"h={h_rel_max:.3e} u={u_rel_max:.3e} exceeds "
                        f"the cross-tier 1e-6 budget")
            else:
                if not out["bitwise_h_ok"]:
                    raise RuntimeError(
                        "serving_multichip: packed h diverged between "
                        "single-device and member-parallel placements")
                # Each packed run sits within 1e-6 of the solo
                # trajectory (the member-batching budget); two packed
                # runs at different bucket sizes are therefore within
                # 2e-6 of each other (triangle inequality — observed
                # ~1e-8).
                if u_rel_max > 2e-6:
                    raise RuntimeError(
                        f"serving_multichip: u rel {u_rel_max:.3e} "
                        f"exceeds the 2e-6 packed-vs-packed budget")
            if not out["zero_steady_recompiles"]:
                raise RuntimeError(
                    "serving_multichip: steady-state serving "
                    "recompiled under placement")
            if enforce and not out["meets_0p8_floor"]:
                raise RuntimeError(
                    f"serving_multichip: {out['scaling_vs_ideal']}x of "
                    f"ideal N-chip scaling breaches the 0.8 floor")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench serving_multichip: unavailable "
            f"({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_serving_slo(n=96, dt=300.0, n_requests=64, seed=1404,
                      buckets="1,4,16", seg=8, backend="jnp",
                      queue_capacity=24, lengths=None,
                      mean_gap_s=0.02, tail_alpha=1.4,
                      max_workers=8, p99_floor_s=None,
                      goodput_floor_frac=0.5, packed_msps=None,
                      gates=True):
    """Serving-SLO section (round 14): the network front door under a
    closed-loop heavy-tailed load, with enforced floors.

    An in-process :class:`jaxstream.gateway.Gateway` binds loopback; a
    deterministic mixed-IC trace (tc2/tc5/tc6/galewsky, ragged
    lengths, heavy-tailed Pareto arrivals — ``jaxstream.loadgen``) is
    replayed against it over real HTTP by a bounded worker pool while
    the autoscale policy resizes the active bucket cap live from queue
    depth + occupancy.  This measures what the throughput sections
    cannot: REQUEST latency percentiles (submit-to-result wall time
    through admission, queueing, packing, streaming), goodput
    (member-steps of completed work per second), and the overload
    contract (every request completes or sheds as a typed 429/503).

    Floors (``gates=True``; breaches surface as ``skipped`` with the
    reason, like the sibling serving sections):

      * accounting exactness — completed + typed-shed == submitted,
        zero untyped errors;
      * >= 1 live autoscale resize (the burst must trip the policy);
      * ZERO steady-state recompiles after warmup, resizes included
        (every level maps to a warm bucket by construction — this
        asserts it);
      * goodput >= ``goodput_floor_frac`` x the packed serving rate
        (``packed_msps``, member-steps/s from ``bench_serving`` —
        main() threads it through; the HTTP+streaming front door may
        cost at most half the engine's rate at this scale);
      * request p99 <= ``p99_floor_s`` when given (absolute SLO for
        the calibrated TPU config; None = reported only).

    Never raises (returns ``{"skipped": ...}``).
    """
    try:
        import os
        import shutil
        import tempfile

        from jaxstream.gateway import Gateway
        from jaxstream.gateway.client import get_text
        from jaxstream.loadgen import (AutoscaleController,
                                       AutoscalePolicy, generate_trace,
                                       run_load)
        from jaxstream.obs.registry import parse_exposition

        levels = tuple(sorted({int(b) for b in buckets.split(",")
                               if b.strip()}))
        if lengths is None:
            lengths = (seg * 2, seg * 3 + 1, seg, seg * 5 + 3)
        out = {"n": n, "dt": dt, "n_requests": n_requests,
               "buckets": buckets, "segment_steps": seg, "seed": seed,
               "lengths": list(lengths),
               "queue_capacity": queue_capacity}
        # Round 17: the section runs with request tracing ON and
        # certifies trace coverage — every completed request must
        # reassemble into a full span tree (spans_complete == 1.0),
        # and /v1/metrics must serve a parseable Prometheus payload.
        sink_dir = tempfile.mkdtemp(prefix="jaxstream_slo_")
        serve_sink = os.path.join(sink_dir, "serve.jsonl")
        gw_sink = os.path.join(sink_dir, "gateway.jsonl")
        cfg = {"grid": {"n": n, "halo": 2, "dtype": "float32"},
               "time": {"dt": dt},
               "model": {"name": "shallow_water_cov",
                         "backend": backend},
               "serve": {"buckets": buckets, "segment_steps": seg,
                         "queue_capacity": queue_capacity,
                         "sink": serve_sink, "trace": True}}
        ctrl = AutoscaleController(AutoscalePolicy(
            levels=levels, queue_high=3, queue_low=0, occ_low=0.6,
            patience=2, cooldown=2))
        trace = generate_trace(n_requests, seed,
                               mean_gap_s=mean_gap_s,
                               tail_alpha=tail_alpha, lengths=lengths)
        out["families"] = sorted({e["ic"] for e in trace})
        gw = Gateway(cfg, host="127.0.0.1", port=0, autoscale=ctrl,
                     sink=gw_sink)
        try:
            gw.start()
            out["warm_compiles"] = gw.warm_compiles
            summary = run_load("127.0.0.1", gw.port, trace,
                               time_scale=1.0, max_workers=max_workers,
                               dt=dt, trace_spans=True,
                               span_sinks=[serve_sink, gw_sink])
            out["slo"] = summary
            out["autoscale"] = ctrl.summary()
            out["steady_recompiles"] = (gw.server.compile_count()
                                        - gw.warm_compiles)
            out["resizes"] = len(ctrl.events)
            # Scrape the live gateway: the payload must parse as text
            # exposition 0.0.4 (the structural checks — +Inf buckets,
            # monotone cumulative counts — live in the parser).
            status, ctype, text = get_text("127.0.0.1", gw.port,
                                           "/v1/metrics")
            parsed = parse_exposition(text)
            out["metrics_scrape"] = {
                "status": status,
                "content_type": ctype,
                "families": len(parsed["types"]),
                "samples": sum(len(v)
                               for v in parsed["samples"].values()),
                "submitted": parsed["samples"].get(
                    "jaxstream_requests_submitted_total", {}).get(""),
                "ok": bool(status == 200
                           and "version=0.0.4" in ctype
                           and parsed["types"]),
            }
        finally:
            gw.close()
            shutil.rmtree(sink_dir, ignore_errors=True)
        msps = summary["goodput_member_steps_per_sec"]
        if packed_msps:
            out["goodput_vs_packed"] = round(msps / packed_msps, 4)
            out["meets_goodput_floor"] = bool(
                msps >= goodput_floor_frac * packed_msps)
        if p99_floor_s is not None:
            out["p99_floor_s"] = p99_floor_s
            out["meets_p99_floor"] = bool(
                summary["latency_p99_s"] is not None
                and summary["latency_p99_s"] <= p99_floor_s)
        log(f"bench serving_slo C{n} {n_requests} reqs over HTTP "
            f"loopback (buckets {buckets}): "
            f"{summary['completed']} completed / {summary['shed']} "
            f"shed / {summary['errors']} errors; p50/p99 "
            f"{summary['latency_p50_s']}/{summary['latency_p99_s']}s; "
            f"goodput {msps} member-steps/s; {out['resizes']} "
            f"autoscale resize(s); {out['steady_recompiles']} steady "
            f"recompiles; spans_complete "
            f"{summary.get('spans_complete')} over "
            f"{summary.get('spans_checked')} trees; metrics scrape "
            f"{out['metrics_scrape']['families']} families")
        if gates:
            if not summary["accounting_exact"]:
                raise RuntimeError(
                    f"serving_slo: overload contract broken — "
                    f"{summary['errors']} untyped outcomes of "
                    f"{summary['n_requests']} (completed "
                    f"{summary['completed']}, shed {summary['shed']})")
            if out["resizes"] < 1:
                raise RuntimeError(
                    "serving_slo: the heavy-tailed burst tripped no "
                    "autoscale resize — the closed loop is not "
                    "exercising the policy")
            if out["steady_recompiles"] != 0:
                raise RuntimeError(
                    f"serving_slo: {out['steady_recompiles']} steady-"
                    f"state recompiles after warmup/resizes — the "
                    "warm-bucket claim is broken")
            if summary.get("spans_complete") != 1.0:
                raise RuntimeError(
                    f"serving_slo: trace coverage broken — "
                    f"spans_complete {summary.get('spans_complete')} "
                    f"over {summary.get('spans_checked')} requests "
                    f"(failures: {summary.get('span_failures')})")
            if not out["metrics_scrape"]["ok"]:
                raise RuntimeError(
                    f"serving_slo: /v1/metrics scrape is not valid "
                    f"Prometheus text exposition: "
                    f"{out['metrics_scrape']}")
            if packed_msps and not out["meets_goodput_floor"]:
                raise RuntimeError(
                    f"serving_slo: goodput {msps} member-steps/s is "
                    f"below {goodput_floor_frac} x the packed serving "
                    f"rate ({packed_msps})")
            if p99_floor_s is not None and not out["meets_p99_floor"]:
                raise RuntimeError(
                    f"serving_slo: p99 {summary['latency_p99_s']}s "
                    f"breaches the {p99_floor_s}s floor")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench serving_slo: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_assimilation(n=48, dt=300.0, members=8, cycles=4,
                       cycle_steps=8, nstations=128, obs_sigma=1.0,
                       amplitude=1.0e-3, gates=True):
    """Assimilation section (round 18): the forecast claim.

    Runs the in-process EnKF cycle (jaxstream.da) on the Galewsky jet
    — a hidden truth run observed through ``nstations`` seeded
    stations every ``cycle_steps`` steps, a ``members``-member
    perturbed ensemble pulled toward the observations by the
    stochastic B x B ensemble-space analysis — and the FREE ensemble
    under identical seeds as the baseline.  The headline is the gated
    forecast claim: the cycled ensemble-mean RMSE vs the hidden truth
    must BEAT the free-running ensemble's (``beats_free_run``); the
    calibrated config must also finish with zero guard events (a
    spread collapse or filter divergence here means the defaults
    regressed).  Never raises (returns ``{"skipped": ...}``).
    """
    try:
        from jaxstream.da import run_cycle

        cfg = {
            "grid": {"n": n},
            "time": {"dt": dt},
            "model": {"name": "shallow_water_cov", "backend": "jnp",
                      "initial_condition": "galewsky"},
            "parallelization": {"num_devices": 1},
            "ensemble": {"members": members, "seed": 5,
                         "amplitude": amplitude},
            "da": {"cycles": cycles, "cycle_steps": cycle_steps,
                   "nstations": nstations, "obs_sigma": obs_sigma},
        }
        t0 = time.perf_counter()
        cycled = run_cycle(cfg)
        free = run_cycle(cfg, assimilate=False)
        out = {
            "n": n, "dt": dt, "members": members, "cycles": cycles,
            "cycle_steps": cycle_steps, "nstations": nstations,
            "obs_sigma": obs_sigma,
            "plan": cycled["plan"],
            "proof_verdict": cycled["proof_verdict"],
            "cycled_final_rmse": cycled["final_rmse"],
            "cycled_mean_rmse": round(cycled["mean_rmse"], 6),
            "cycled_final_spread": cycled["final_spread"],
            "free_final_rmse": free["final_rmse"],
            "free_mean_rmse": round(free["mean_rmse"], 6),
            "rmse_reduction": round(
                free["final_rmse"] - cycled["final_rmse"], 6),
            "beats_free_run": bool(
                cycled["final_rmse"] < free["final_rmse"]),
            "guard_events": len(cycled["guard_events"]),
            "cycle_records": cycled["cycles"],
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        log(f"bench assimilation C{n} galewsky B={members} "
            f"({cycles} cycles x {cycle_steps} steps, {nstations} "
            f"stations): cycled rmse {out['cycled_final_rmse']:.4f} "
            f"vs free {out['free_final_rmse']:.4f} "
            f"({'BEATS' if out['beats_free_run'] else 'LOSES TO'} "
            f"the free run; {out['guard_events']} guard events)")
        if gates:
            if not out["beats_free_run"]:
                raise RuntimeError(
                    f"assimilation: cycled final RMSE "
                    f"{out['cycled_final_rmse']} does not beat the "
                    f"free ensemble's {out['free_final_rmse']} — the "
                    f"forecast claim is the section's headline gate")
            if out["guard_events"]:
                raise RuntimeError(
                    f"assimilation: {out['guard_events']} guard "
                    f"event(s) on the calibrated config — filter "
                    f"health regressed")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench assimilation: unavailable "
            f"({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_io(n=48, dt=600.0, nsteps=96, stride=12, warm=12, ic="tc2",
             gates=True):
    """IO-overlap section: history+telemetry cost, async vs sync vs off.

    The async-host-pipeline acceptance measurement (round 9): the same
    Simulation config is integrated three ways — no IO at all (the
    baseline the device loop can reach), history+checkpoint+telemetry
    with the synchronous boundary stalls, and the same IO under
    ``io.async_pipeline`` (double-buffered fetches + background
    writers).  Reports steps/s for each, the IO overhead of both modes
    relative to the io-off baseline, and the per-segment
    ``host_wait_s`` totals from the runs' own telemetry files (the
    async column is the overlap made visible).  ``gates``: the final
    height field must stay finite in every mode — a pipeline that
    corrupts the carry must not report a rate.  Never raises (returns
    ``{"skipped": ...}``) — the headline metric does not depend on it.

    Fairness: the simulation logger is held at WARNING for every mode,
    which suppresses the sync path's per-emit diagnostics log lines
    (a diagnostics compute + blocking device_get per boundary that the
    async loop never performs).  Both modes therefore do identical I/O
    work — history append + checkpoint save + telemetry record — and
    the sync/async delta measures *overlap*, not dropped work.
    """
    import logging
    import shutil
    import tempfile

    from jaxstream.obs.sink import read_records
    from jaxstream.simulation import Simulation

    out = {"n": n, "dt": dt, "nsteps": nsteps, "stride": stride,
           "ic": ic}

    def run_mode(mode):
        d = tempfile.mkdtemp(prefix=f"bench_io_{mode}_")
        cfg = {
            "grid": {"n": n, "halo": 2, "dtype": "float32"},
            "model": {"initial_condition": ic},
            "time": {"dt": dt, "nsteps": warm + nsteps},
            "parallelization": {"num_devices": 1},
        }
        if mode != "off":
            cfg["io"] = {
                "history_path": d + "/hist", "history_stride": stride,
                "checkpoint_path": d + "/ckpt",
                "checkpoint_stride": stride,
                "async_pipeline": {"enabled": mode == "async"},
            }
            cfg["observability"] = {"interval": stride,
                                    "sink": d + "/telemetry.jsonl"}
        sim = Simulation(cfg)
        try:
            sim.run(warm)                      # compile + first strides
            t0 = time.perf_counter()
            if mode == "off":
                # No strides -> one run() call would jit a SECOND,
                # different-length segment inside the timed window
                # (deflating the baseline that io_overhead_pct divides
                # by).  Advance in warm-sized calls so the timed window
                # reuses the already-compiled k=warm segment, like the
                # strided modes reuse theirs.
                s = warm
                while s < warm + nsteps:
                    s = min(s + warm, warm + nsteps)
                    sim.run(s)
            else:
                sim.run(warm + nsteps)
            wall = time.perf_counter() - t0
            h = np.asarray(sim.state["h"], np.float64)
            finite = bool(np.all(np.isfinite(h)))
            if gates and not finite:
                raise RuntimeError(f"bench io mode={mode}: non-finite h")
            entry = {"steps_per_sec": round(nsteps / wall, 2),
                     "wall_s": round(wall, 3)}
            if mode != "off":
                segs = read_records(d + "/telemetry.jsonl",
                                    kind="segment")
                entry["host_wait_s_total"] = round(
                    sum(s.get("host_wait_s", 0.0) for s in segs
                        if s["step"] > warm), 4)
            return entry
        finally:
            sim.close()
            shutil.rmtree(d, ignore_errors=True)

    sim_log = logging.getLogger("jaxstream.simulation")
    old_level = sim_log.level
    sim_log.setLevel(logging.WARNING)
    try:
        for mode in ("off", "sync", "async"):
            out[mode] = run_mode(mode)
        base = out["off"]["steps_per_sec"]
        for mode in ("sync", "async"):
            r = out[mode]["steps_per_sec"]
            out[mode]["io_overhead_pct"] = round(100.0 * (base / r - 1.0),
                                                 2)
        out["async_overhead_smaller"] = (
            out["async"]["io_overhead_pct"]
            < out["sync"]["io_overhead_pct"])
        log(f"bench io C{n} {ic} {nsteps} steps (stride {stride}): "
            f"off {base:.1f} steps/s; "
            f"sync {out['sync']['steps_per_sec']:.1f} "
            f"(+{out['sync']['io_overhead_pct']:.1f}% overhead, host "
            f"wait {out['sync']['host_wait_s_total']:.3f}s); "
            f"async {out['async']['steps_per_sec']:.1f} "
            f"(+{out['async']['io_overhead_pct']:.1f}% overhead, host "
            f"wait {out['async']['host_wait_s_total']:.3f}s)")
    except Exception as e:  # never fail the headline metric on this
        log(f"bench io: unavailable ({type(e).__name__}: {e})")
        out["skipped"] = f"{type(e).__name__}: {e}"
    finally:
        sim_log.setLevel(old_level)
    return out


def compile_report(n=24):
    """``--compile-report``: cold vs warm compile seconds, one JSON line.

    Measures the persistent compilation cache the package placed on
    import (``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``):
    compile a representative stepper executable cold, drop jax's
    in-memory caches (``jax.clear_caches()``), compile again — warm
    hits the persistent cache when enabled, recompiles when not, so the
    cold/warm ratio IS the cache's value.  Every executable is cached
    for the report, however quick its compile.
    """
    import os

    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water import ShallowWater
    from jaxstream.physics.initial_conditions import williamson_tc2
    from jaxstream.stepping import integrate
    from jaxstream.utils.jax_compat import enable_compile_cache

    enabled = bool(jax.config.jax_enable_compilation_cache)
    cache_dir = enable_compile_cache(jax.config.jax_compilation_cache_dir)
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = ShallowWater(grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA)
    state = model.initial_state(h_ext, v_ext)
    step = model.make_step(600.0, "ssprk3")
    fn = jax.jit(lambda y, k: integrate(step, y, 0.0, k, 600.0))

    t0 = time.perf_counter()
    fn.lower(state, 8).compile()
    cold = time.perf_counter() - t0
    jax.clear_caches()
    t0 = time.perf_counter()
    fn.lower(state, 8).compile()
    warm = time.perf_counter() - t0
    rec = {
        "metric": "compile_report",
        "cache_dir": cache_dir,
        "cache_enabled": enabled,
        "n_cache_entries": len(os.listdir(cache_dir)),
        "cold_compile_s": round(cold, 3),
        "warm_compile_s": round(warm, 3),
        "speedup": round(cold / warm, 2) if warm > 0 else None,
    }
    log(f"compile report (C{n} classic SSPRK3 segment): cold {cold:.2f}s "
        f"-> warm {warm:.2f}s "
        + (f"(persistent cache at {cache_dir}, "
           f"{rec['n_cache_entries']} entries)" if enabled
           else "(cache disabled: warm = plain recompile)"))
    print(json.dumps(rec))
    return 0


def bench_precision_report(n=384, dt=BENCH_DT, interpret=False,
                           warm=10, k1=1500, k2=6000):
    """``--precision-report``: the precision ladder measured side by
    side on one grid/IC/dt, so each column isolates ONE knob.

    Rows (round 10; jaxstream.ops.pallas.precision semantics):

      ``f32``           all-f32 reference (the headline stepper)
      ``bf16_stage``    bf16 stage ARITHMETIC (flux/recon/router ops;
                        f32 accumulators + metric terms, bf16 strips)
      ``mixed16_carry`` 16-bit carry STORAGE (h int16 + u bf16), f32
                        arithmetic — the round-5 encoding
      ``stacked``       both: bf16 stage arithmetic + 16-bit carry

    Each row reports steps/s, sim-days/sec/chip, speedup vs the f32
    row, and the precision-corrected roofline (``carry_bytes`` bytes
    model, bf16 flop fraction + mixed-roof percentage) — the honest-
    accounting half of the round-10 satellite.  TC5 ICs; NO physics
    gates here (the gated rates live in the ``variants`` section; this
    is the ladder comparison).  ``interpret=True`` runs the kernels in
    Pallas interpret mode with whatever windows the caller passes —
    the ``--smoke`` structural canary, not a measurement.
    """
    import jax
    import jax.numpy as jnp

    from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.ops.pallas.precision import (encode_strips,
                                                mixed16_encoding)
    from jaxstream.physics.initial_conditions import williamson_tc5
    from jaxstream.stepping import integrate
    from jaxstream.utils.profiling import steady_state_rate

    out = {"n": n, "dt": dt, "interpret": bool(interpret), "rows": {}}
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(
        grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext,
        backend="pallas_interpret" if interpret else "pallas")
    st0 = model.initial_state(h_ext, v_ext)
    cd, off, hs = mixed16_encoding(st0["h"])

    def carry16(y):
        return model.encode_carry(y, cd, off, hs)

    def dec16(y):
        return model.decode_carry(y, h_offset=off, h_scale=hs)

    # (name, stepper kwargs, carry encode, carry decode, roofline kwargs)
    # — decode maps a row's carry back to absolute f32 h/u so the
    # non-finite guard sees real field values (an int16 h is ALWAYS
    # "finite"; bf16 u NaNs must be checked post-decode).
    rows = [
        ("f32", {}, lambda y: y, lambda y: y, {}),
        ("bf16_stage", {"precision": "bf16"},
         lambda y: encode_strips(y, "bf16"), lambda y: y,
         {"precision": "bf16"}),
        ("mixed16_carry",
         {"carry_dtype": cd, "h_offset": off, "h_scale": hs},
         carry16, dec16, {"carry_bytes": 2}),
        ("stacked",
         {"precision": "bf16", "carry_dtype": cd, "h_offset": off,
          "h_scale": hs},
         lambda y: encode_strips(carry16(y), "bf16"), dec16,
         {"precision": "bf16", "carry_bytes": 2}),
    ]
    def fresh_carry(enc):
        # Donation consumes the carry, and compact_state's output
        # aliases st0's buffers — copy every leaf so each row (and the
        # fallback window) starts from live arrays.
        return enc({k: jnp.copy(v)
                    for k, v in model.compact_state(st0).items()})

    for name, kw, enc, dec, rl_kw in rows:
        try:
            step = model.make_fused_step(dt, **kw)
            if interpret:
                # Smoke path: eager stage-kernel calls.  Wrapping the
                # loop in jit(integrate) costs ~35 s/row of interpret-
                # mode lowering vs ~9 s for the kernels alone (measured
                # C12 CPU) and adds no structural coverage — the jitted
                # donation loop is the measurement path below.
                y = fresh_carry(enc)
                for _ in range(warm):
                    y = step(y, 0.0)
                jax.block_until_ready(y["h"])
                t0 = time.perf_counter()
                outy = y
                for _ in range(k2):
                    outy = step(outy, 0.0)
                jax.block_until_ready(outy["h"])
                rate = k2 / (time.perf_counter() - t0)
            else:
                run = jax.jit(
                    lambda y, k, _s=step: integrate(_s, y, 0.0, k, dt)[0],
                    donate_argnums=0)
                y = run(fresh_carry(enc), warm)
                jax.block_until_ready(y["h"])
                try:
                    rate, outy = steady_state_rate(
                        lambda y, k: run(y, k), y, k1=k1, k2=k2)
                except Exception:
                    # Windows can land t2 <= t1 on a noisy host; one
                    # plain window on a rebuilt carry still reports.
                    y = run(fresh_carry(enc), warm)
                    jax.block_until_ready(y["h"])
                    t0 = time.perf_counter()
                    outy = run(y, k2)
                    jax.block_until_ready(outy["h"])
                    rate = k2 / (time.perf_counter() - t0)
            outd = dec(outy)
            if not (bool(jnp.all(jnp.isfinite(
                        outd["h"].astype(jnp.float32))))
                    and bool(jnp.all(jnp.isfinite(
                        outd["u"].astype(jnp.float32))))):
                raise RuntimeError("non-finite h/u after the rate window")
            row = {"steps_per_sec": round(rate, 2),
                   "sim_days_per_sec": round(rate * dt / 86400.0, 4),
                   "dt60_equivalent": round(rate * 60.0 / 86400.0, 4)}
            rl = _roofline_json(rate, n, **rl_kw)
            if rl is not None:
                row["roofline"] = rl
            out["rows"][name] = row
        except Exception as e:
            log(f"bench precision row {name} unavailable "
                f"({type(e).__name__}: {e})")
            out["rows"][name] = {"skipped": f"{type(e).__name__}: {e}"}
    base = out["rows"].get("f32", {}).get("steps_per_sec")
    hdr = (f"precision report C{n} dt={dt:g}"
           + (" [interpret smoke — NOT a measurement]" if interpret
              else ""))
    log(hdr)
    log(f"  {'row':<14} {'steps/s':>9} {'sd/s/chip':>10} "
        f"{'vs f32':>7} {'AI':>6} {'roof%':>6}")
    for name, row in out["rows"].items():
        if "skipped" in row:
            log(f"  {name:<14} skipped: {row['skipped']}")
            continue
        if base:
            row["speedup_vs_f32"] = round(row["steps_per_sec"] / base, 4)
        rl = row.get("roofline", {})
        pct = rl.get("pct_of_mixed_roof", rl.get("pct_of_compute_roof"))
        log(f"  {name:<14} {row['steps_per_sec']:>9.2f} "
            f"{row['sim_days_per_sec']:>10.4f} "
            f"{row.get('speedup_vs_f32', 1.0):>6.3f}x "
            f"{rl.get('ai', float('nan')):>6.3f} "
            f"{pct if pct is not None else float('nan'):>5}%")
    return out


def bench_perf(n=96, dt=300.0, probe_pallas=True):
    """Performance-observatory section (round 19): hardware identity,
    a full cost stamp of the bench stepper, and a live device-memory
    snapshot — the fields the cross-round regression ledger
    machine-normalizes (``scripts/perf_ledger.py``).

    The stamped stepper mirrors bench's own rung ladder: the covariant
    fused Pallas stepper where it compiles (its flops are INVISIBLE to
    XLA's counter, so the stamp skips the analytic band check and says
    so — the footprint/compile fields are still real), the classic jnp
    stepper otherwise (XLA sees every op; the flops-vs-analytic ratio
    is the cross-check).  The stamp's AOT compile is the recorded
    ``compile_seconds``.  Never raises (returns ``{"skipped": ...}``).
    """
    try:
        import jax
        import jax.numpy as jnp

        from jaxstream.config import (EARTH_GRAVITY, EARTH_OMEGA,
                                      EARTH_RADIUS)
        from jaxstream.geometry.cubed_sphere import build_grid
        from jaxstream.models.shallow_water_cov import \
            CovariantShallowWater
        from jaxstream.obs import perf as obs_perf
        from jaxstream.physics.initial_conditions import williamson_tc2

        out = {"hardware": jax.devices()[0].platform, "n": n}
        out["memory"] = obs_perf.device_memory_record()
        grid = build_grid(n, halo=2, radius=EARTH_RADIUS,
                          dtype=jnp.float32)
        h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
        rung, step, y = None, None, None
        if probe_pallas:
            try:
                m = CovariantShallowWater(
                    grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA,
                    backend="pallas")
                step = m.make_fused_step(dt)
                y = m.compact_state(m.initial_state(h_ext, v_ext))
                jax.block_until_ready(jax.jit(step)(y,
                                                    jnp.float32(0.0)))
                rung = "cov_fused"
            except Exception as e:
                log(f"bench perf: fused stepper unavailable "
                    f"({type(e).__name__}); stamping the classic rung")
        if rung is None:
            m = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                      omega=EARTH_OMEGA)
            step = m.make_step(dt, "ssprk3")
            y = m.initial_state(h_ext, v_ext)
            rung = "classic"
        stamp = obs_perf.measure_cost(
            step, y, jnp.float32(0.0),
            plan_key=f"bench:{rung}_C{n}",
            analytic=obs_perf.analytic_cost(n),
            xla_visible=(rung == "classic"))
        out["rung"] = rung
        out["cost"] = stamp.to_json()
        log(f"bench perf: {stamp} (hardware {out['hardware']}, "
            f"memory "
            + ("unavailable" if out["memory"].get("unavailable")
               else f"{out['memory']['bytes_in_use']} in use of "
                    f"{out['memory']['limit_bytes']}") + ")")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench perf: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_perf_ledger(rec):
    """Round-19 CI satellite: every bench run (full + ``--smoke``)
    carries the regression ledger's verdict — the assembled record is
    appended to the recorded ``BENCH_r*.json`` trajectory as the
    candidate point and gated against the best comparable history
    (same section, same hardware class; ``jaxstream.obs.perf.
    check_trajectory``).  CPU-smoke candidates are reported-only
    (``enforced: false``); an accelerator run that regressed beyond
    the band stamps ``ok: false`` LOUDLY for the driver.  Never raises
    (reports ``skipped``); asserted by ``tests/test_bench_smoke.py``.
    """
    import os

    try:
        from jaxstream.obs import perf as obs_perf

        root = os.path.dirname(os.path.abspath(__file__))
        points = obs_perf.load_bench_history(root)
        points.append(obs_perf.parse_bench_point(
            {"parsed": rec}, label="candidate"))
        res = obs_perf.check_trajectory(points)
        mode = "ENFORCED" if res["enforced"] else "reported-only"
        log(f"bench perf ledger [{mode}]: {res['points']} points, "
            f"{res['compared_sections']} section(s) compared, "
            f"{len(res['regressions'])} regression(s), "
            f"{len(res['advisories'])} advisory(ies)"
            + ("" if res["ok"] else " — PERF REGRESSION")
            + ("" if res["compared_sections"] or not res["enforced"]
               else " — VACUOUS (no comparable history)"))
        for r in res["regressions"] + res["advisories"]:
            log(f"bench perf ledger: {r['detail']}")
        return res
    except Exception as e:  # never fail the headline metric on this
        log(f"bench perf ledger: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_flight_overhead(n=12, dt=600.0, k=4, windows=12, repeats=9):
    """Round-20 black-box satellite: the always-on flight recorder's
    steady-state cost, measured where it actually runs — per-segment
    ``flight.record`` calls riding a REAL compiled stepping window,
    recorder enabled vs ``flight.disabled()``.  The arms run paired
    back-to-back ``repeats`` times (alternating order) and the
    quietest paired ratio is stamped — see the inline rationale;
    the stamped ``overhead_pct`` is the acceptance
    number behind the "always-on costs < 3%" claim, asserted by
    ``tests/test_bench_smoke.py``.  Smoke windows on CPU, but
    ``record()`` is pure-Python ring bookkeeping, so the ratio
    transfers.  Never raises (returns ``{"skipped": ...}``).
    """
    try:
        import jax
        import jax.numpy as jnp

        from jaxstream.config import (EARTH_GRAVITY, EARTH_OMEGA,
                                      EARTH_RADIUS)
        from jaxstream.geometry.cubed_sphere import build_grid
        from jaxstream.models.shallow_water_cov import \
            CovariantShallowWater
        from jaxstream.obs import flight
        from jaxstream.physics.initial_conditions import williamson_tc2

        grid = build_grid(n, halo=2, radius=EARTH_RADIUS,
                          dtype=jnp.float32)
        h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
        m = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA)
        step = jax.jit(m.make_step(dt, "ssprk3"))
        y0 = m.initial_state(h_ext, v_ext)
        jax.block_until_ready(step(y0, jnp.float32(0.0)))      # warm

        def window():
            # One serving-shaped window: k compiled steps then the
            # segment-boundary record pair (boundary mark + memory
            # watermark) — the exact steady-state call pattern the
            # server/Simulation loops emit.
            y = y0
            t0 = time.perf_counter()
            for w in range(windows):
                for _ in range(k):
                    y = step(y, jnp.float32(0.0))
                flight.record("segment", step=(w + 1) * k, k=k)
                flight.record("memory.watermark", bytes_in_use=0)
            jax.block_until_ready(y)
            return time.perf_counter() - t0

        # Burn-in: one untimed window per arm, so first-call effects
        # (allocator warmup, cache fill) land on neither timed arm.
        window()
        with flight.disabled():
            window()
        # The recorder's cost is deterministic and tiny (~µs of ring
        # bookkeeping per window) while the stepping wall wanders by
        # whole percents with CPU frequency/scheduler state, so a
        # min-per-arm difference mostly measures that wander.  Pair
        # the arms back-to-back inside each repeat (drift is smallest
        # there), alternate which goes first, and stamp the QUIETEST
        # paired ratio: any repeat where noise hit the arms
        # asymmetrically only moves its ratio away from the true one.
        t_on = t_off = float("inf")
        ratios = []
        for i in range(repeats):
            if i % 2 == 0:
                on = window()
                with flight.disabled():
                    off = window()
            else:
                with flight.disabled():
                    off = window()
                on = window()
            t_on, t_off = min(t_on, on), min(t_off, off)
            ratios.append(on / off)
        overhead = max(0.0, (min(ratios) - 1.0) * 100.0)
        out = {"t_on_s": round(t_on, 5), "t_off_s": round(t_off, 5),
               "overhead_pct": round(overhead, 3),
               "records_per_window": 2 * windows,
               "windows": windows, "k": k, "n": n}
        log(f"bench flight overhead: on {t_on:.4f}s / off "
            f"{t_off:.4f}s -> {overhead:.2f}% "
            f"({windows} windows x {k} steps, best of {repeats})")
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench flight overhead: unavailable "
            f"({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_cold_start(n=8, dt=600.0, buckets="1,2", seg=2, gates=True):
    """Round-21 warm-pool satellite: the compile tax, measured.

    Three arms over one tiny serving config (C{n}, buckets {buckets}):
    a COLD server (no pool — every bucket pays jit), an untimed
    POPULATE pass (pool on, fresh dir — pays the saves), then a WARM
    server restarted against the populated pool.  Stamps server
    cold-start-to-first-result and resize-to-new-bucket wall seconds
    for the cold and warm arms plus their ratios — the numbers the
    perf ledger tracks as ``cold_start:warm_speedup`` /
    ``cold_start:resize_speedup``.

    Gates (acceptance criteria, enforced on every image incl. smoke —
    the margins are ~5x on CPU): both speedups >= 3x, the warm path
    performs ZERO XLA compiles (``compile_count``), and the
    warm-loaded first-segment result byte-equals the fresh-compiled
    one.  Never raises (returns ``{"skipped": ...}``).
    """
    import shutil
    import tempfile

    try:
        import jax

        from jaxstream.serve import EnsembleServer, ScenarioRequest

        blist = sorted({int(b) for b in str(buckets).split(",")})
        b_hi = blist[-1]
        base = {"grid": {"n": n}, "time": {"dt": dt},
                "model": {"name": "shallow_water_cov"},
                "serve": {"buckets": buckets, "segment_steps": seg}}

        def arm(pool_dir):
            cfg = json.loads(json.dumps(base))
            if pool_dir:
                cfg["serve"]["warm_pool"] = pool_dir
            # Each arm starts from an empty jit cache: the cold arm
            # must actually compile even though earlier bench sections
            # warmed similar programs in this process.
            jax.clear_caches()
            t0 = time.perf_counter()
            srv = EnsembleServer(cfg)
            srv.submit(ScenarioRequest(id="r0", ic="tc2", nsteps=seg))
            res = srv.serve()
            first_s = time.perf_counter() - t0
            h = np.asarray(res["r0"].fields["h"])
            t0 = time.perf_counter()
            srv._bucket("any", b_hi)
            resize_s = time.perf_counter() - t0
            out = (first_s, resize_s, h, srv.compile_count(),
                   srv.warmpool_summary())
            srv.close()
            return out

        pdir = tempfile.mkdtemp(prefix="jaxstream_warmpool_")
        try:
            cold_first, cold_resize, h_cold, _, _ = arm(None)
            arm(pdir)                      # populate (untimed)
            (warm_first, warm_resize, h_warm, warm_compiles,
             pool) = arm(pdir)
        finally:
            shutil.rmtree(pdir, ignore_errors=True)

        warm_speedup = cold_first / warm_first if warm_first else 0.0
        resize_speedup = (cold_resize / warm_resize
                          if warm_resize else 0.0)
        byte_equal = h_cold.tobytes() == h_warm.tobytes()
        failures = []
        if gates:
            if warm_speedup < 3.0:
                failures.append(
                    f"cold-start speedup {warm_speedup:.2f}x < 3x")
            if resize_speedup < 3.0:
                failures.append(
                    f"resize speedup {resize_speedup:.2f}x < 3x")
            if warm_compiles != 0:
                failures.append(
                    f"warm path performed {warm_compiles} XLA "
                    "compiles (expected 0)")
            if not byte_equal:
                failures.append(
                    "warm-loaded first segment != fresh-compiled")
        out = {
            "cold_first_result_s": round(cold_first, 3),
            "warm_first_result_s": round(warm_first, 3),
            "warm_speedup": round(warm_speedup, 2),
            "cold_resize_s": round(cold_resize, 3),
            "warm_resize_s": round(warm_resize, 3),
            "resize_speedup": round(resize_speedup, 2),
            "warm_compiles": warm_compiles,
            "byte_equal": bool(byte_equal),
            "hits": pool["hits"] if pool else 0,
            "misses": pool["misses"] if pool else 0,
            "rungs": pool["rungs"] if pool else {},
            "n": n, "buckets": buckets, "segment_steps": seg,
            "ok": not failures,
        }
        if failures:
            out["failures"] = failures
        log(f"bench cold start: first result {cold_first:.2f}s cold / "
            f"{warm_first:.2f}s warm ({warm_speedup:.1f}x), resize "
            f"{cold_resize:.2f}s cold / {warm_resize:.2f}s warm "
            f"({resize_speedup:.1f}x), warm compiles {warm_compiles}, "
            f"byte_equal {byte_equal}"
            + (f" — FAILED: {'; '.join(failures)}" if failures else ""))
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench cold start: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_smoke(n=24, dt=600.0, telemetry=""):
    """``--smoke``: C24, a handful of steps, NO accuracy gates.

    A cheap end-to-end pass through bench's machinery — grid + TC5 ICs,
    rung probing with fallback, the batched ensemble section at
    B in {1, 2}, variant/roofline JSON assembly, exchange-plan
    accounting — wired into a non-slow test (tests/test_bench_smoke.py)
    so bench bitrot is caught by the tier-1 gate instead of the next
    offline TPU run.  Prints exactly ONE JSON line, like main().
    """
    t0 = time.perf_counter()
    try:
        ens = bench_ensemble(n=n, dt=dt, members=(1, 2), warm=1,
                             k1=2, k2=6, gates=False,
                             bitwise_check=False)
    except Exception as e:
        log(f"bench smoke: ensemble section failed "
            f"({type(e).__name__}: {e})")
        ens = {"skipped": f"{type(e).__name__}: {e}"}
    # IO-overlap canary: tiny async-vs-sync-vs-off triple so the async
    # pipeline's bench plumbing is exercised by the tier-1 gate (the
    # rates are smoke windows, NOT measurements — no gate on overhead).
    # nsteps == warm keeps every mode's segment loop on ONE compiled
    # body (the off mode would otherwise jit a second, different-k
    # plain loop for the timed window — pure compile cost, no coverage:
    # both history/checkpoint boundaries and both telemetry records
    # still fire at steps 2 and 4).
    io_sec = bench_io(n=12, dt=dt, nsteps=2, stride=2, warm=2,
                      gates=False)
    # Serving canary (round 11): the continuous-batching server end to
    # end at C16 — packing, per-member masking, boundary refill, the
    # zero-steady-state-recompile bucket claim and the packed-vs-serial
    # comparison all exercised through the REAL bench_serving code path
    # (vmapped classic steppers; rates are smoke windows, NOT
    # measurements).  Asserted by tests/test_bench_smoke.py.
    serving = bench_serving(n=16, dt=dt, bucket=2, n_requests=4, seg=2,
                            backend="jnp", lengths=(4, 7, 2, 5),
                            ic="tc2", gates=False)
    # Multi-chip serving canary (round 12): the member-parallel
    # placement end to end on a 6-fake-device CPU mesh at C12 —
    # sharded masked segments, sharding-preserving refill, the
    # single-vs-multichip h byte-parity and the zero-steady-recompile
    # claim all through the REAL bench_serving_multichip code path.
    # Rates are smoke windows; the 0.8x scaling floor is only enforced
    # on real accelerators (all fake devices share this host's cores).
    serving_mc = bench_serving_multichip(
        n=12, dt=dt, per_chip=1, seg=2, reqs_per_chip=2, mode="member",
        devices=min(6, _device_count()), backend="jnp", ic="tc2",
        lengths=(3, 5), gates=True)
    # Serving-SLO canary (round 14): the network front door end to
    # end on loopback at C8 — real HTTP admission + NDJSON streaming,
    # the closed-loop load harness, live autoscale resizes between the
    # warm {1,2} buckets, and the typed-overload accounting, all
    # through the REAL bench_serving_slo code path.  Latencies are
    # smoke numbers, NOT measurements; the structural floors
    # (accounting exact, >= 1 resize, zero steady recompiles) ARE
    # enforced and asserted by tests/test_bench_smoke.py.
    serving_slo = bench_serving_slo(
        n=8, dt=dt, n_requests=10, seed=714, buckets="1,2", seg=2,
        backend="jnp", queue_capacity=16, lengths=(1, 2, 3, 5),
        mean_gap_s=0.002, tail_alpha=1.4, max_workers=6, gates=True)
    # Assimilation canary (round 18): the EnKF forecast loop end to
    # end at C12 — truth run, seeded station network, batched
    # forecast with the in-loop h_spread stream, the B x B analysis,
    # the free-ensemble baseline — through the REAL
    # bench_assimilation code path.  Rates are smoke windows; the
    # forecast claim (cycled RMSE beats free, zero guard events) IS
    # enforced and asserted by tests/test_bench_smoke.py — this
    # config is calibrated (C12, B=4, 48 stations, sigma 1 m) and
    # measured ~10x RMSE reduction, so the gate is structural, not
    # marginal.
    assimilation = bench_assimilation(
        n=12, dt=dt, members=4, cycles=2, cycle_steps=4,
        nstations=48, obs_sigma=1.0, gates=True)
    # Precision-ladder canary: all four rows (f32 / bf16_stage /
    # mixed16_carry / stacked) through the REAL report code path in
    # interpret mode — structural coverage of the row builders, carry
    # encoders and the precision-corrected roofline JSON; the rates are
    # interpret-mode smoke windows, NOT measurements (the table the
    # driver reads comes from ``--precision-report`` on the TPU host).
    try:
        prec = bench_precision_report(n=12, dt=dt, interpret=True,
                                      warm=1, k1=1, k2=2)
    except Exception as e:
        log(f"bench smoke: precision report failed "
            f"({type(e).__name__}: {e})")
        prec = {"skipped": f"{type(e).__name__}: {e}"}
    # Contract-check stamp (round 13): the static analyzer over the
    # full composition matrix — the tier-1 gate asserts it is both
    # present and CLEAN, so a schedule/stepper invariant breach fails
    # the same gate that runs the parity tests.  smoke=True keeps the
    # stamp trace-only; the compile-level checks run in
    # tests/test_analysis.py within the same gate.
    contract = bench_contract_check(smoke=True)
    # Performance-observatory canary (round 19): the cost stamp +
    # memory snapshot at C12 through the REAL bench_perf code path
    # (classic rung on CPU — XLA sees every op, so the
    # flops-vs-analytic band check runs; memory_stats degrades to the
    # typed unavailable record on CPU), then the regression-ledger
    # stamp over the recorded BENCH_r*.json history with THIS record
    # as the (reported-only, CPU-smoke) candidate — both asserted by
    # tests/test_bench_smoke.py.
    perf = bench_perf(n=12, dt=dt)
    # Flight-recorder overhead stamp (round 20): recorder-on vs
    # recorder-off stepping windows; the envelope carries the number
    # behind the always-on claim (< 3%, asserted by
    # tests/test_bench_smoke.py).
    flight_overhead = bench_flight_overhead(n=12, dt=dt)
    # Warm-pool cold-start canary (round 21): cold vs populated-pool
    # server start and resize-to-new-bucket through the REAL
    # bench_cold_start code path at C8.  The >= 3x speedup, the
    # zero-warm-compiles proof and the byte-equality parity gate ARE
    # enforced (the margins are ~5x even on CPU); asserted by
    # tests/test_bench_smoke.py.  Runs LAST among the jax sections:
    # its arms call jax.clear_caches(), which must not cool any other
    # section's warm executables.
    cold_start = bench_cold_start(n=8, dt=dt, buckets="1,2", seg=2,
                                  gates=True)
    b1 = ens.get("B1", {})
    ok = isinstance(b1, dict) and b1.get("sim_days_per_sec", 0.0) > 0.0
    rec = {
        "metric": f"bench_smoke_TC5_C{n}",
        "smoke": True,
        "value": b1.get("sim_days_per_sec", 0.0)
                 if isinstance(b1, dict) else 0.0,
        "unit": "sim-days/sec (B=1, smoke window — NOT a benchmark)",
        "ok": bool(ok),
        "hardware": perf.get("hardware") or _platform(),
        "ensemble": ens,
        "io": io_sec,
        "serving": serving,
        "serving_multichip": serving_mc,
        "serving_slo": serving_slo,
        "assimilation": assimilation,
        "precision_report": prec,
        "contract_check": contract,
        "perf": perf,
        "flight_overhead": flight_overhead,
        "cold_start": cold_start,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    rec["perf_ledger"] = bench_perf_ledger(rec)
    sink = _open_telemetry(telemetry)
    if sink is not None:
        for key in ("B1", "B2"):
            b = ens.get(key, {})
            if isinstance(b, dict) and "sim_days_per_sec" in b:
                sink.write({"kind": "bench",
                            "metric": f"{rec['metric']}_{key}",
                            "value": b["sim_days_per_sec"],
                            "unit": "sim-days/sec (smoke window)",
                            "steps_per_sec": b.get("steps_per_sec")})
        sink.write({"kind": "bench", "metric": rec["metric"],
                    "value": rec["value"], "unit": rec["unit"],
                    "ok": rec["ok"], "wall_s": rec["wall_s"]})
        sink.close()
    print(json.dumps(rec))
    return 0 if ok else 1


def bench_contract_check(smoke=False):
    """Round-13 CI satellite: every bench run carries a contract-check
    stamp — ``scripts/analyze.py --json`` over the current composition
    matrix (exchange-schedule totality/coverage/depth, traced
    collective counts vs the comm_probe analytic plans, overlap
    windows, precision/donation/callback invariants; see
    jaxstream.analysis).  Runs the CLI's importable ``run()``
    in-process when >= 6 CPU devices exist (the pytest conftest's and
    any flag-started host's pool); otherwise a SUBPROCESS so the
    virtual-host-device flag never touches this process's backends —
    the same policy as bench_multichip.  ``smoke=True`` passes
    ``--no-compile`` (trace-only): the donation-aliasing and
    member-parallel-HLO compiles are covered by tests/test_analysis.py
    in the same tier-1 gate, so the smoke stamp skips their ~35 s while
    the offline full bench keeps every check.  Never raises (reports
    ``skipped``); a non-empty ``violations`` list means the run's
    schedules/steppers broke a proven invariant, and the smoke test
    fails the tier-1 gate on it.
    """
    import os
    import subprocess
    import sys as _sys

    argv = ["--json"] + (["--no-compile"] if smoke else [])
    try:
        import jax

        if len(jax.devices("cpu")) >= 6:
            scripts = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "scripts")
            if scripts not in _sys.path:
                _sys.path.insert(0, scripts)
            import analyze

            code, result, _report = analyze.run(argv)
            result["exit_code"] = code
        else:
            script = os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "scripts", "analyze.py")
            # A structural CPU check: pinned to the CPU, so the child
            # never reaches for the chip this process holds.
            r = subprocess.run(
                [_sys.executable, script] + argv,
                capture_output=True, text=True, timeout=1800,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            if r.returncode not in (0, 1) or not r.stdout.strip():
                tail = "\n".join((r.stdout + r.stderr).splitlines()[-5:])
                return {"skipped": f"analyze subprocess failed: {tail}"}
            result = json.loads(r.stdout.strip().splitlines()[-1])
            result["exit_code"] = r.returncode
        # The per-check pass list (~480 entries) is CLI/debug detail;
        # the stamp keeps counts + violations + facts so the bench
        # JSON line and sink records stay readable.
        result.pop("passes", None)
        space = (result.get("facts") or {}).get("plan_space") or {}
        log(f"bench contract check: {result['checks_run']} checks, "
            f"{result['violation_count']} violation(s)"
            + (f"; plan space {space['size']} plans "
               f"(rules v{space['rules_version']})" if space else "")
            + ("" if result["ok"] else " — CONTRACT BROKEN"))
        for v in result.get("violations", [])[:10]:
            log(f"bench contract check: FAIL [{v['check']}] "
                f"{v['subject']}: {v['detail']}")
        return result
    except Exception as e:  # never fail the headline metric on this
        log(f"bench contract check: unavailable "
            f"({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def bench_multichip():
    """Multichip steady-state section: per-stage exchange latency and
    steps/s with the overlapped exchange off vs on, on a 6-device
    ``(panel, 1, 1)`` mesh running the explicit covariant ppermute
    stepper (jaxstream.utils.comm_probe methodology —
    chained-dependency ppermute ping for the per-stage numbers,
    steady-state windows for the rates).  Uses the default platform's
    devices in-process when >= 6 exist (a real slice measures real
    ICI); otherwise runs the structural CPU smoke in a SUBPROCESS
    (scripts/comm_probe.py) so the virtual-host-device flag never
    touches this process's backends — the headline gates and timed run
    keep the exact environment all prior rounds measured in.  Returns
    the dict for the JSON ``multichip`` field; never raises (reports
    ``skipped``).
    """
    import os
    import subprocess
    import sys as _sys

    try:
        import jax

        if len(jax.devices()) >= 6:
            from jaxstream.utils import comm_probe

            cpu = jax.devices()[0].platform == "cpu"
            # temporal_block 2 on the CPU smoke (n=16 fits 3*2*2=12-deep
            # halos), 4 at the real-slice n=96; batched-ensemble rate at
            # a small B either way (one extra stepper compile).
            out = comm_probe.run_default_probe(
                iters=30 if cpu else 100, steps=10 if cpu else 50,
                temporal_block=2 if cpu else 4, members=2 if cpu else 4)
        else:
            script = os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "scripts", "comm_probe.py")
            r = subprocess.run(
                [_sys.executable, script, "--iters", "30", "--steps",
                 "10", "--temporal-block", "2", "--members", "2",
                 "--json"],
                capture_output=True, text=True, timeout=1200,
                env=dict(os.environ, JAX_PLATFORMS="cpu"))
            if r.returncode != 0:
                tail = "\n".join((r.stdout + r.stderr).splitlines()[-5:])
                return {"skipped": f"cpu-smoke subprocess failed: {tail}"}
            out = json.loads(r.stdout.strip().splitlines()[-1])
        from jaxstream.utils.comm_probe import format_report

        for line in format_report(out).splitlines():
            log("bench multichip: " + line)
        return out
    except Exception as e:  # never fail the headline metric on this
        log(f"bench multichip: unavailable ({type(e).__name__}: {e})")
        return {"skipped": f"{type(e).__name__}: {e}"}


def main():
    telemetry = _argv_value("--telemetry")
    if "--compile-report" in sys.argv[1:]:
        raise SystemExit(compile_report())
    if "--precision-report" in sys.argv[1:]:
        # Standalone ladder mode: the four rows measured side by side
        # at the headline grid (ONE JSON line, like main()).  Kept out
        # of the default full run — rows re-measure steppers the
        # variants section already times under gates.
        rep = bench_precision_report()
        print(json.dumps(rep))
        raise SystemExit(
            0 if "skipped" not in rep["rows"].get("f32", {}) else 1)
    if "--smoke" in sys.argv[1:]:
        raise SystemExit(bench_smoke(telemetry=telemetry))
    gates_ok = accuracy_gates()
    value, variants = bench_tc5()
    multichip = bench_multichip()
    contract = bench_contract_check()
    io_section = bench_io(n=96, dt=300.0, nsteps=480, stride=48, warm=48,
                          ic="tc5")
    try:
        ensemble = bench_ensemble()
    except Exception as e:  # never fail the headline metric on this
        log(f"bench ensemble: unavailable ({type(e).__name__}: {e})")
        ensemble = {"skipped": f"{type(e).__name__}: {e}"}
    # Serving section (round 11): packed heterogeneous traffic through
    # the continuous-batching server at the ensemble section's config
    # (C96, dt=300, B=16).  The acceptance floor: the packed rate must
    # recover >= 0.9x the static-B=16 ensemble rate measured above —
    # masking + refill overhead under 10%.
    serving = bench_serving()
    # Multi-chip serving section (round 12): aggregate scaling of one
    # server process driving every device, vs the single-device packed
    # rate at equal per-chip batch.  The >= 0.8x-of-ideal floor is
    # enforced on real accelerators; on a CPU pool the section still
    # proves parity + zero recompiles (floor reported only).
    serving_multichip = bench_serving_multichip()
    # Serving-SLO section (round 14): the network front door under
    # closed-loop heavy-tailed load over loopback HTTP — request
    # latency p50/p99, goodput, typed-shed accounting, live autoscale
    # resizes.  Floors: accounting exact, >= 1 resize, zero steady
    # recompiles, goodput >= 0.5x the packed serving rate measured
    # above, p99 <= 120 s at this calibrated config.
    serving_slo = bench_serving_slo(
        packed_msps=(serving.get("packed", {})
                     .get("member_steps_per_sec")
                     if isinstance(serving, dict) else None),
        p99_floor_s=120.0)
    # Assimilation section (round 18): the EnKF cycle vs the free
    # ensemble on the Galewsky jet — the gated forecast claim.
    assimilation = bench_assimilation()
    # Performance observatory (round 19): the headline stepper's cost
    # stamp (footprint bytes, compile seconds, flops-vs-analytic
    # cross-check on XLA-visible rungs) + live device-memory snapshot.
    perf = bench_perf(n=384, dt=BENCH_DT)
    if isinstance(ensemble, dict) and "packed" in serving:
        msps = (ensemble.get("B16") or {}).get("member_steps_per_sec")
        if msps:
            ratio = serving["packed"]["member_steps_per_sec"] / msps
            serving["vs_static_B16"] = round(ratio, 4)
            serving["meets_0p9_floor"] = bool(ratio >= 0.9)
            log(f"bench serving: packed/static-B16 = {ratio:.3f}x "
                f"(floor 0.9: "
                f"{'OK' if ratio >= 0.9 else 'BREACHED'})")
    try:
        vg, rg = bench_galewsky()
        # nu4='split': the re-derived 210 flops/cell/step filter count
        # plus the split placement's ~6 extra f32 field passes (the old
        # scale=4/3 billed the filter as one extra 137-flop stage, ~35%
        # under — round-10 accounting satellite).  Gate breach keeps
        # the entry shape (every variant is a dict).
        variants["galewsky_nu4_C384"] = (
            _variant_entry(vg, rg, 384, nu4="split", dt=60.0)
            if rg > 0 else {"sim_days_per_sec": 0.0})
    except Exception as e:
        log(f"bench variant galewsky unavailable ({type(e).__name__}: {e})")
    try:
        # Re-fused del^4 line (round 10): the filter commuted into the
        # stage-1 kernel — 3 kernels + 3 routes per step vs split's
        # 4 + 4 — behind the IDENTICAL day-6 physics gate (vorticity
        # bands, quiescent hemisphere, mass) so the equivalence claim
        # is re-proven on every bench run.
        vgr, rgr = bench_galewsky(nu4_mode="refused")
        variants["galewsky_nu4_refused_C384"] = (
            _variant_entry(vgr, rgr, 384, nu4="refused", dt=60.0)
            if rgr > 0 else {"sim_days_per_sec": 0.0})
    except Exception as e:
        log(f"bench variant galewsky-refused unavailable "
            f"({type(e).__name__}: {e})")
    if not gates_ok:
        # Variants were measured on the same breached discretization —
        # publish none of them (gate log lines on stderr remain).
        log("bench: ACCURACY/STABILITY GATE BREACH — reporting value 0 "
            "and suppressing all variant lines")
        value = 0.0
        variants = {}
        ensemble = {"suppressed": "accuracy/stability gate breach"}
        serving = {"suppressed": "accuracy/stability gate breach"}
        serving_multichip = {"suppressed":
                             "accuracy/stability gate breach"}
        serving_slo = {"suppressed": "accuracy/stability gate breach"}
        assimilation = {"suppressed": "accuracy/stability gate breach"}
    # dt is part of the metric's definition (sim-days/sec = steps/s * dt);
    # emit it top-level, with the dt=60-equivalent rate adjacent, so
    # cross-round comparisons of `value` are self-describing.
    dt60 = variants.pop("dt60_equivalent", round(value * 60.0 / BENCH_DT, 4))
    # Warm-pool cold start (round 21): cold vs populated-pool server
    # start-to-first-result and resize-to-new-bucket, with the >= 3x
    # speedup / zero-warm-compiles / byte-equality gates enforced.
    # Runs LAST among the jax sections: its arms clear the jit caches,
    # which must not cool any timed executable above.
    cold_start = bench_cold_start(n=8, dt=600.0, buckets="1,2", seg=2,
                                  gates=True)
    sink = _open_telemetry(telemetry)
    if sink is not None:
        sink.write({"kind": "bench",
                    "metric": "sim_days_per_sec_per_chip_TC5_C384",
                    "value": round(value, 4),
                    "unit": "sim-days/sec/chip", "dt": BENCH_DT,
                    "gates_ok": bool(gates_ok)})
        for name, v in variants.items():
            if isinstance(v, dict) and "sim_days_per_sec" in v:
                sink.write({"kind": "bench", "metric": f"variant_{name}",
                            "value": v["sim_days_per_sec"],
                            "unit": "sim-days/sec/chip",
                            "steps_per_sec": v.get("steps_per_sec")})
        if isinstance(serving, dict) and "packed" in serving:
            p = serving["packed"]
            sink.write({
                "kind": "bench", "metric": "serving_packed",
                "value": p["agg_sim_days_per_sec_per_chip"],
                "unit": "aggregate sim-days/sec/chip",
                "member_steps_per_sec": p["member_steps_per_sec"],
                "occupancy_mean": p["occupancy_mean"],
                "latency_p50_s": p["latency_p50_s"],
                "latency_p99_s": p["latency_p99_s"],
                "vs_static_B16": serving.get("vs_static_B16")})
        if (isinstance(serving_multichip, dict)
                and "multichip" in serving_multichip):
            m = serving_multichip["multichip"]
            sink.write({
                "kind": "bench", "metric": "serving_multichip",
                "value": m["agg_sim_days_per_sec"],
                "unit": "aggregate sim-days/sec (whole mesh)",
                "devices": serving_multichip["devices"],
                "mode": serving_multichip["mode"],
                "scaling_vs_ideal":
                    serving_multichip.get("scaling_vs_ideal"),
                "meets_0p8_floor":
                    serving_multichip.get("meets_0p8_floor")})
        if (isinstance(assimilation, dict)
                and "cycled_final_rmse" in assimilation):
            sink.write({
                "kind": "bench", "metric": "assimilation",
                "value": assimilation["rmse_reduction"],
                "unit": "m RMSE reduction vs free ensemble",
                "cycled_final_rmse":
                    assimilation["cycled_final_rmse"],
                "free_final_rmse": assimilation["free_final_rmse"],
                "beats_free_run": assimilation["beats_free_run"],
                "members": assimilation["members"],
                "cycles": assimilation["cycles"]})
        if isinstance(serving_slo, dict) and "slo" in serving_slo:
            slo = serving_slo["slo"]
            sink.write({
                "kind": "bench", "metric": "serving_slo",
                "value": slo["goodput_member_steps_per_sec"],
                "unit": "member-steps/sec goodput (HTTP loopback)",
                "latency_p50_s": slo["latency_p50_s"],
                "latency_p99_s": slo["latency_p99_s"],
                "completed": slo["completed"], "shed": slo["shed"],
                "resizes": serving_slo.get("resizes"),
                "goodput_vs_packed":
                    serving_slo.get("goodput_vs_packed"),
                "meets_goodput_floor":
                    serving_slo.get("meets_goodput_floor"),
                "meets_p99_floor":
                    serving_slo.get("meets_p99_floor")})
        if isinstance(cold_start, dict) and "warm_speedup" in cold_start:
            sink.write({"kind": "bench", "metric": "cold_start",
                        "value": cold_start["warm_speedup"],
                        "unit": "warm-over-cold start speedup (x)",
                        "resize_speedup": cold_start["resize_speedup"],
                        "warm_compiles": cold_start["warm_compiles"],
                        "byte_equal": cold_start["byte_equal"]})
        sink.close()
    record = {
        "metric": "sim_days_per_sec_per_chip_TC5_C384",
        "value": round(value, 4),
        "unit": "sim-days/sec/chip",
        "vs_baseline": round(value / BASELINE_PER_CHIP, 4),
        "dt": BENCH_DT,
        "dt60_equivalent": dt60,
        "hardware": perf.get("hardware") or _platform(),
        "roofline": (_roofline_json(value * 86400.0 / BENCH_DT, 384)
                     if value > 0 else None),
        "variants": variants,
        "ensemble": ensemble,
        "serving": serving,
        "serving_multichip": serving_multichip,
        "serving_slo": serving_slo,
        "assimilation": assimilation,
        "io": io_section,
        "multichip": multichip,
        "contract_check": contract,
        "perf": perf,
        "cold_start": cold_start,
    }
    # The regression-ledger stamp gates THIS record against the
    # recorded BENCH_r*.json trajectory (enforced on accelerator
    # hardware; the smoke path stamps reported-only).
    record["perf_ledger"] = bench_perf_ledger(record)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
