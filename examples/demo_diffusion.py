"""Lima-flag thermal diffusion demo (reference deck p.12/p.17).

Checkerboard 1-1000 K heat source on the north panel, diffused for a few
weeks; prints conservation and symmetry diagnostics.  Runs on whatever the
default JAX device is (the TPU on a chip host; CPU elsewhere).
"""

import sys
import time

import numpy as np
import jax

sys.path.insert(0, ".")

from jaxstream.config import EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.diffusion import ThermalDiffusion
from jaxstream.physics.initial_conditions import checkerboard
from jaxstream.utils.diagnostics import total_mass


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 48
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS)
    kappa = 1.0e7  # m^2/s, exaggerated for a visible few-week spread
    model = ThermalDiffusion(grid, kappa)
    state = model.initial_state(checkerboard(grid, face=4))
    t0_heat = float(total_mass(grid, state["T"]))

    dt = 0.2 * (EARTH_RADIUS * grid.dalpha) ** 2 / kappa  # diffusive CFL
    days = 26.7
    nsteps = int(days * 86400 / dt)
    print(f"C{n}, kappa={kappa:.1e} m^2/s, dt={dt:.0f}s, {nsteps} steps "
          f"({days} days) on {jax.devices()[0].platform}")
    wall = time.time()
    state, t = model.run(state, nsteps, dt, scheme="rk4")
    jax.block_until_ready(state)
    wall = time.time() - wall

    T = np.asarray(state["T"])
    heat = float(total_mass(grid, state["T"]))
    print(f"wall {wall:.1f}s ({nsteps / wall:.0f} steps/s)")
    print(f"T range [{T.min():.2f}, {T.max():.2f}] K (started [1, 1000])")
    print(f"heat conservation drift: {(heat - t0_heat) / t0_heat:.2e}")
    print("per-face mean K:", np.round(T.mean(axis=(1, 2)), 2))
    adj = T.mean(axis=(1, 2))[:4]
    print(f"equatorial-face symmetry spread: {adj.max() - adj.min():.2e} K")


if __name__ == "__main__":
    main()
