"""Single-chip perf probe: steady-state step rate of fused-stepper variants.

The measurement methodology of DESIGN.md ("Step-time methodology"): jit a
``fori_loop`` of the step, size the window for multi-second runs, time
the second call.  Usage::

    python scripts/perf_probe.py [n] [variant ...] [--stamp]

Variants: ``mc`` / ``minmod`` / ``none`` / ``vanleer`` (limiter choice
on the compact covariant stepper), ``bf16`` (bf16 carry, h stored as
anomaly), ``int16`` (int16 fixed-point carry, magic-constant rounding),
``mixed16`` (h int16 fixed-point + u bf16 — mass-neutral 16-bit),
``noseam`` (seam imposition ablated — measurement only, breaks
conservation).  Default: ``mc``.

Round 19: every variant line carries its roofline from the SAME cost
accounting bench uses (``jaxstream.obs.perf.roofline_json`` — one
definition; 16-bit carries billed at the corrected ``carry_bytes=2``
model, not the old ``bytes * 0.5``).  ``--stamp`` additionally
compiles each variant's step ahead-of-time and prints its full cost
stamp (footprint bytes, compile seconds, XLA-vs-analytic flop ratio —
``measure_cost``; one extra XLA compile per variant).
"""

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water_cov import CovariantShallowWater
from jaxstream.physics.initial_conditions import williamson_tc5
from jaxstream.stepping import integrate


def measure(step, y, dt, k1=3000, k2=15000):
    """Dispatch-overhead-free steady-state rate (shared methodology:
    :func:`jaxstream.utils.profiling.steady_state_rate`)."""
    from jaxstream.utils.profiling import steady_state_rate

    run = jax.jit(lambda y, k: integrate(step, y, 0.0, k, dt),
                  donate_argnums=0)
    y, _ = run(y, 10)
    jax.block_until_ready(y["h"])
    rate, y = steady_state_rate(lambda y, k: run(y, k)[0], y, k1=k1, k2=k2)
    assert np.all(np.isfinite(np.asarray(y["h"])))
    return rate


def main():
    args = sys.argv[1:]
    stamp = "--stamp" in args
    args = [a for a in args if a != "--stamp"]
    n = int(args[0]) if args and args[0].isdigit() else 384
    variants = [a for a in args if not a.isdigit()] or ["mc"]
    dt = 60.0

    from jaxstream.obs import perf as obs_perf

    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)

    for v in variants:
        limiter = v if v in ("mc", "minmod", "none", "vanleer") else "mc"
        kw = {}
        if v == "noseam":
            kw["_ablate_seam"] = True
        model = CovariantShallowWater(
            grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext,
            backend="pallas", limiter=limiter)
        st = model.initial_state(h_ext, v_ext)
        if v in ("bf16", "int16", "mixed16"):
            # mixed16 (round 5): h int16 fixed-point (mass stays at the
            # accuracy-neutral int16 level — the bf16 h-anomaly's mass
            # leak lives entirely in h) + u bf16 (the native-convert
            # encoding that carried the round-2 ladder's speed).
            off = float(0.5 * (jnp.min(st["h"]) + jnp.max(st["h"])))
            cd = {"bf16": (jnp.bfloat16,) * 2,
                  "int16": (jnp.int16,) * 2,
                  "mixed16": (jnp.int16, jnp.bfloat16)}[v]
            hs = 1.0 if v == "bf16" else 0.0625
            us = float(grid.radius) / 256.0 if v == "int16" else 1.0
            kw.update(carry_dtype=cd, h_offset=off, h_scale=hs, u_scale=us)
            step = model.make_fused_step(dt, **kw)
            y = model.encode_carry(model.compact_state(st), cd, off, hs, us)
        else:
            step = model.make_fused_step(dt, **kw)
            y = model.compact_state(st)
        rate = measure(step, y, dt)
        print(f"C{n} {v:8s}: {rate:8.1f} steps/s  "
              f"({rate * dt / 86400.0:.3f} sim-days/s)")
        # Round 19: the roofline from the ONE cost-accounting
        # definition (obs.perf; bench's variant entries use the same
        # helper) — 16-bit carries at the corrected carry_bytes=2.
        carry_bytes = 2 if v in ("bf16", "int16", "mixed16") else None
        try:
            rl = obs_perf.roofline_json(rate, n,
                                        carry_bytes=carry_bytes)
            if rl is None:
                print("    roofline: none on cpu")
                continue
            print(f"    roofline: {rl['achieved_tflops']} TFLOP/s "
                  f"({rl['pct_of_compute_roof']}% of VPU roof), "
                  f"{rl['achieved_gbps']} GB/s "
                  f"({rl['pct_of_hbm']}% of HBM), AI {rl['ai']}")
        except Exception as e:
            print(f"    roofline unavailable ({type(e).__name__}: {e})")
        if stamp:
            st_cost = obs_perf.measure_cost(
                step, y, jnp.float32(0.0),
                plan_key=f"perf_probe:{v}_C{n}",
                analytic=obs_perf.analytic_cost(
                    n, carry_bytes=carry_bytes),
                xla_visible=False)   # fused Pallas: XLA can't see it
            print(f"    {st_cost}")


if __name__ == "__main__":
    main()
