#!/usr/bin/env python3
"""Chip smoke: the main path once on a TPU, through the user entry points.

    python chip_smoke.py              # one chip: forecast, galewsky,
                                      # reference, serve
    python chip_smoke.py --multichip  # four chips: the sharded TC5 C384
                                      # tier vs one chip, nothing else

Every phase builds its run the way ``python -m jaxstream run`` does
(``Simulation`` from a config dict or the shipped YAML) or the way a
serving deployment does (``EnsembleServer.submit``/``serve``), checks
its result by the repo's own gates, and prints one line.  The last
line of standard output is ``{"ok": true, "device": {...}}`` and is
printed only when every phase passed; any failure exits non-zero.
Without a TPU the script stops before any phase.

Rates printed here come from a short smoke window, not a benchmark:
no warmup discipline, host syncs between segments, one sample.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

#: TC5 C384 at the bench's CFL-matched dt: the headline forecast.
TC5_C384 = {
    "grid": {"n": 384, "halo": 2, "dtype": "float32"},
    "model": {"name": "shallow_water_cov", "initial_condition": "tc5",
              "scheme": "plr", "limiter": "mc", "backend": "pallas"},
    "time": {"dt": 75.0},
    "parallelization": {"num_devices": 1, "device_type": "tpu"},
}
#: Steps per compiled segment.  Every run() below advances whole
#: segments of this length, so one compiled executable serves the
#: forecast, its smoke window and the reference comparison.
SEGMENT = 20
#: Segments in the forecast (SEGMENT * FORECAST_SEGMENTS steps).
FORECAST_SEGMENTS = 16
#: Galewsky steps (examples/configs/galewsky_c384.yaml, nsteps cut).
GALEWSKY_STEPS = 48
#: Fused vs plain-jnp agreement: atol = REF_ATOL_REL * max|field|, the
#: tolerance of the repo's fused-vs-jnp parity tests
#: (tests/test_cov_swe.py::test_cov_fused_step_parity,
#: tests/test_fused_step.py::test_fused_step_parity).
REF_ATOL_REL = 2e-4
#: The serving deployment: one B=4 bucket of TC5 C96 scenarios on the
#: fused member-fold kernels (orography-grouped batches).
SERVE_C96 = {
    "grid": {"n": 96, "dtype": "float32"},
    "model": {"name": "shallow_water_cov", "backend": "pallas"},
    "time": {"dt": 300.0},
    "parallelization": {"num_devices": 1, "device_type": "tpu"},
    "serve": {"buckets": "4", "segment_steps": 8, "queue_capacity": 8,
              "group_by_orography": True},
}
#: bench.py's TC5 gate: relative mass drift, and h bounds in metres.
MASS_TOL = 1e-3
TC5_H_RANGE = (3000.0, 6500.0)
GALEWSKY_H_RANGE = (8500.0, 10800.0)


def _tpu_devices(count: int):
    """The first ``count`` TPU devices, or exit: no phase ever runs on
    another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is "
              f"{devs[0].platform!r}); refusing to run on another "
              "platform", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < count:
        print(f"chip_smoke: needs {count} TPU chips, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs


def _cfg(base, **over):
    cfg = json.loads(json.dumps(base))
    for section, block in over.items():
        cfg.setdefault(section, {}).update(block)
    return cfg


def _interior_mass(sim, h):
    import numpy as np

    area = np.asarray(sim.grid.interior(sim.grid.area), np.float64)
    return float(np.sum(area * np.asarray(h, np.float64)))


def _gate(name, sim, h, mass0, h_range):
    """Finite, physical h, mass held — the bench's stability gate."""
    import numpy as np

    h = np.asarray(h, np.float64)
    if not np.all(np.isfinite(h)):
        raise AssertionError(f"{name}: non-finite h")
    lo, hi = float(h.min()), float(h.max())
    if not (h_range[0] < lo and hi < h_range[1]):
        raise AssertionError(f"{name}: h in [{lo:.1f}, {hi:.1f}] outside "
                             f"{h_range}")
    drift = abs(_interior_mass(sim, h) - mass0) / mass0
    if not drift < MASS_TOL:
        raise AssertionError(f"{name}: mass drift {drift:.3e} >= {MASS_TOL}")
    return {"h_min": lo, "h_max": hi, "mass_drift": drift}


def _assert_fused(name, sim):
    tier = sim.proof.plan_key.split("+")[0]
    if sim._fused_step is None or tier != "fused":
        raise AssertionError(f"{name}: the fused stepper did not run "
                             f"(proof tier {tier!r})")


def _snapshot(state):
    import numpy as np

    return {k: np.array(v) for k, v in state.items()}


def _max_err(a, b):
    """max|a - b| and the parity budget REF_ATOL_REL * max|a| per field."""
    import numpy as np

    out = {}
    for k in ("h", "u"):
        x = np.asarray(a[k], np.float64)
        y = np.asarray(b[k], np.float64)
        err = float(np.max(np.abs(x - y)))
        atol = REF_ATOL_REL * float(np.max(np.abs(x)))
        if not np.all(np.isfinite(y)) or not err <= atol:
            raise AssertionError(f"{k}: max|diff| {err:.3e} > atol "
                                 f"{atol:.3e}")
        out[k] = {"max_abs_err": err, "atol": atol}
    return out


# ------------------------------------------------------------ one chip
def phase_forecast(ctx):
    """TC5 C384 for FORECAST_SEGMENTS * SEGMENT steps on the compact
    fused SSPRK3 Pallas stepper."""
    from jaxstream.simulation import Simulation

    with Simulation(_cfg(TC5_C384)) as sim:
        _assert_fused("forecast", sim)
        mass0 = _interior_mass(sim, sim.state["h"])
        t0 = time.perf_counter()
        sim.run(SEGMENT)
        first = time.perf_counter() - t0
        # The reference phase compares against this state.
        ctx["fused_after_segment"] = _snapshot(sim.state)
        t0 = time.perf_counter()
        for i in range(2, FORECAST_SEGMENTS + 1):
            sim.run(i * SEGMENT)
        window = time.perf_counter() - t0
        rate = (FORECAST_SEGMENTS - 1) * SEGMENT / window
        gate = _gate("forecast", sim, sim.state["h"], mass0, TC5_H_RANGE)
        return {"steps": sim.step_count, "tier": "fused",
                "smoke_window_not_a_benchmark": {
                    "steps_per_s": rate,
                    "first_segment_s_incl_compile": first,
                    "compile_s_est": first - SEGMENT / rate},
                **gate}


def phase_galewsky(ctx):
    """examples/configs/galewsky_c384.yaml as shipped (split del^4
    filter kernels), nsteps cut and the output paths moved."""
    import yaml

    from jaxstream.simulation import Simulation

    with open(os.path.join(REPO, "examples", "configs",
                           "galewsky_c384.yaml")) as fh:
        cfg = yaml.safe_load(fh)
    out = os.path.join(ctx["out"], "galewsky")
    cfg["time"]["nsteps"] = GALEWSKY_STEPS
    cfg["io"]["history_path"] = os.path.join(out, "history")
    cfg["io"]["checkpoint_path"] = os.path.join(out, "ckpt")
    with Simulation(cfg) as sim:
        _assert_fused("galewsky", sim)
        mass0 = _interior_mass(sim, sim.state["h"])
        t0 = time.perf_counter()
        sim.run()
        wall = time.perf_counter() - t0
        gate = _gate("galewsky", sim, sim.state["h"], mass0,
                     GALEWSKY_H_RANGE)
        return {"steps": sim.step_count, "tier": "fused",
                "nu4": sim.config.physics.hyperdiffusion,
                "wall_s_incl_compile": wall, **gate}


def phase_reference(ctx):
    """The fused state after SEGMENT steps vs the plain f32 jnp classic
    stepper (backend: jnp, make_step(dt, 'ssprk3')) from the same TC5
    C384 initial state, both on the chip."""
    from jaxstream.simulation import Simulation

    if "fused_after_segment" not in ctx:
        raise AssertionError("reference: the forecast phase produced no "
                             "fused state to compare")
    cfg = _cfg(TC5_C384, model={"backend": "jnp"})
    with Simulation(cfg) as sim:
        tier = sim.proof.plan_key.split("+")[0]
        if sim._fused_step is not None or tier != "classic":
            raise AssertionError(f"reference: expected the classic jnp "
                                 f"tier, got {tier!r}")
        sim.run(SEGMENT)
        errs = _max_err(sim.state, ctx["fused_after_segment"])
    return {"steps": SEGMENT, "atol_rel": REF_ATOL_REL, **errs}


def phase_serve(ctx):
    """EnsembleServer: 4 seeded TC5 C96 requests of different lengths
    through the B=4 fused member-fold bucket; no compile after
    warmup."""
    import numpy as np

    from jaxstream.serve import EnsembleServer, ScenarioRequest

    srv = EnsembleServer(_cfg(SERVE_C96))
    try:
        warm = srv.warmup(groups=("oro",))
        for i, nsteps in enumerate((8, 16, 24, 40)):
            srv.submit(ScenarioRequest(id=f"r{i}", ic="tc5", seed=i + 1,
                                       nsteps=nsteps))
        t0 = time.perf_counter()
        results = srv.serve()
        wall = time.perf_counter() - t0
        after = srv.compile_count()
        proofs = srv.bucket_proofs()
    finally:
        srv.close()
    if after != warm:
        raise AssertionError(f"serve: {after - warm} compiles after "
                             "warmup")
    plans = sorted({(p or {}).get("plan_key", "?") for p in proofs.values()})
    if not all(k.split("+")[1:2] == ["fused"] for k in plans):
        raise AssertionError(f"serve: bucket plans {plans}, expected the "
                             "fused member fold")
    for rid in ("r0", "r1", "r2", "r3"):
        res = results.get(rid)
        if res is None or res.status != "ok":
            raise AssertionError(f"serve: {rid} did not complete "
                                 f"({getattr(res, 'status', None)})")
        if not np.all(np.isfinite(np.asarray(res.fields["h"]))):
            raise AssertionError(f"serve: {rid} returned non-finite h")
    return {"completed": len(results), "warmup_compiles": warm,
            "compiles_after_warmup": after - warm, "plans": plans,
            "serve_wall_s": wall}


# --------------------------------------------------------- four chips
def phase_multichip(ctx):
    """TC5 C384 on the GSPMD mesh panel=2 y=1 x=2 (4 chips) vs one
    chip, SEGMENT steps each, in this process."""
    from jaxstream.simulation import Simulation

    par4 = {"num_devices": 4, "tiles_per_edge": 2, "use_shard_map": False}
    with Simulation(_cfg(TC5_C384)) as one:
        _assert_fused("multichip single-chip run", one)
        one.run(SEGMENT)
        ref = _snapshot(one.state)
    backend, note = "pallas", ""
    sim = Simulation(_cfg(TC5_C384, parallelization=par4))
    try:
        sim.run(SEGMENT)
    except NotImplementedError as e:
        # GSPMD has no partitioning rule for a Mosaic kernel: record
        # it and run the tier on the plain jnp RHS.
        sim.close()
        note = (f"backend pallas refused under GSPMD "
                f"({type(e).__name__}: {str(e)[:300]}); ran backend jnp")
        backend = "jnp"
        sim = Simulation(_cfg(TC5_C384, parallelization=par4,
                              model={"backend": "jnp"}))
        sim.run(SEGMENT)
    with sim:
        setup = sim.setup
        mesh = dict(zip(setup.mesh.axis_names, setup.mesh.devices.shape))
        h = sim.state["h"]
        shard_devs = {s.device.id for s in h.addressable_shards}
        if len(shard_devs) != 4 or len(h.sharding.device_set) != 4:
            raise AssertionError(f"multichip: h shards on devices "
                                 f"{sorted(shard_devs)}, expected 4")
        tier = sim.proof.plan_key.split("+")[0]
        errs = _max_err(ref, sim.state)
    return {"mesh": mesh, "tier": tier, "model_backend": backend,
            "shard_devices": sorted(shard_devs), "steps": SEGMENT,
            "atol_rel": REF_ATOL_REL, **errs,
            **({"note": note} if note else {})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: the sharded TC5 C384 tier vs one "
                         "chip, and no other phase")
    ap.add_argument("--out", default=os.path.join(REPO, "out",
                                                  "chip_smoke"),
                    help="directory for the galewsky history/checkpoints")
    args = ap.parse_args(argv)
    count = 4 if args.multichip else 1
    devs = _tpu_devices(count)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print("device: " + json.dumps(device), flush=True)
    phases = ([phase_multichip] if args.multichip else
              [phase_forecast, phase_galewsky, phase_reference,
               phase_serve])
    ctx = {"out": args.out}
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t0 = time.perf_counter()
        try:
            res = phase(ctx)
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            print(f"{name}: FAILED {type(e).__name__}: {e}", flush=True)
            continue
        res["phase_s"] = time.perf_counter() - t0
        print(f"{name}: ok " + json.dumps(res), flush=True)
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
