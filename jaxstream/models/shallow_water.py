"""Shallow-water equations on the cubed sphere — the flagship solver.

The reference framework's end goal: "FV Cubed-Sphere Shallow Water Solver"
(``/root/reference/README.md:4``; deck p.4-7; SURVEY.md §2.2 "FV-PLR
numerics ... SWE").  The reference ships no numerics; this is a TPU-first
design:

  * **Vector-invariant form with Cartesian 3-vector velocity**:
    dh/dt = -div(h v),
    dv/dt = -(zeta + f) k x v - grad(g (h + b) + |v|^2 / 2),
    with v kept tangent to the sphere by projection.  Carrying velocity as
    a Cartesian vector makes panel-edge exchange a plain componentwise
    copy — the reference's proven "Cartesian Velocity Exchange" (deck
    p.18) — and removes all panel-edge rotation special cases from the hot
    loop.  (A great-circle-rotation exchange for panel-local (u,v)
    components is provided separately in
    :mod:`jaxstream.parallel.vector_halo` for parity with the north-star
    formulation.)
  * **Two halo exchanges per RHS** (h and v); the Bernoulli function
    g(h+b)+K is formed on the already-filled extended fields so its
    gradient needs no third exchange.
  * Flux-form continuity with PLR/PPM upwinding -> exact mass
    conservation; vorticity/gradient centered 2nd order.
  * Optional del^4 hyperdiffusion (Galewsky/TC6 need it) via iterated
    conservative Laplacian with a ghost refill between applications.

Everything traces into one XLA computation under the step ``jit``; no
data-dependent Python control flow.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..geometry.cubed_sphere import CubedSphereGrid
from ..ops.fv import (
    flux_divergence,
    gradient,
    kinetic_energy,
    laplacian,
    vorticity,
)
from .base import Model, State

__all__ = ["SWEBase", "ShallowWater"]


def _cross(a, b):
    return jnp.stack([
        a[1] * b[2] - a[2] * b[1],
        a[2] * b[0] - a[0] * b[2],
        a[0] * b[1] - a[1] * b[0],
    ])


class SWEBase(Model):
    """Shared SWE setup: scheme/backend validation, Coriolis, topography.

    Subclasses (Cartesian :class:`ShallowWater`, covariant
    :class:`jaxstream.models.shallow_water_cov.CovariantShallowWater`)
    provide ``_make_pallas_rhs(interpret)`` returning their fused RHS
    callable, or raise if no kernel exists for the formulation.
    """

    def __init__(
        self,
        grid: CubedSphereGrid,
        gravity: float,
        omega: float,
        b_ext: Optional[jnp.ndarray] = None,
        scheme: str = "plr",
        limiter: str = "mc",
        nu4: float = 0.0,
        backend: str = "jnp",
    ):
        super().__init__(grid)
        if scheme == "ppm" and grid.halo < 3:
            raise ValueError("PPM fluxes need a grid built with halo >= 3")
        self.gravity = gravity
        self.omega = omega
        self.scheme = scheme
        self.limiter = limiter
        self.nu4 = nu4
        # backend='pallas' fuses the whole stencil section of the RHS into
        # one TPU kernel per face; 'jnp' is the reference implementation
        # and parity oracle.
        if backend not in ("jnp", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown backend {backend!r}")
        self._pallas_rhs = None
        if backend.startswith("pallas"):
            if grid.sqrtg.dtype != jnp.float32:
                raise ValueError(
                    f"backend='pallas' supports float32 grids only (the TPU "
                    f"kernel is f32); got grid dtype {grid.sqrtg.dtype}. Use "
                    f"backend='jnp' or build the grid with dtype=float32."
                )
            self._pallas_rhs = self._make_pallas_rhs(
                interpret=(backend == "pallas_interpret")
            )
        self.backend = backend
        # Coriolis parameter f = 2 Omega sin(lat) at interior centers.
        self.fcor = 2.0 * omega * jnp.sin(grid.interior(grid.lat))
        # Bottom topography, extended; ghosts must be valid (analytic ICs
        # evaluate there; otherwise we fill them once here).
        if b_ext is None:
            b_ext = jnp.zeros_like(grid.sqrtg)
        # One compiled exchange, not ~30 op-by-op dispatches that each
        # compile on first use (a server's cold start pays them all).
        self.b_ext = jax.jit(self.exchange)(b_ext)

    def _make_pallas_rhs(self, interpret: bool):  # pragma: no cover
        raise NotImplementedError


class ShallowWater(SWEBase):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.khat_int = self.grid.interior(self.grid.khat)

    def _make_pallas_rhs(self, interpret: bool):
        from ..ops.pallas.swe_rhs import make_swe_rhs_pallas

        grid = self.grid
        return make_swe_rhs_pallas(
            grid.n, grid.halo, grid.dalpha, grid.radius,
            self.gravity, self.omega, scheme=self.scheme,
            limiter=self.limiter, interpret=interpret,
        )

    def initial_state(self, h_ext, v_ext) -> State:
        return {
            "h": self.grid.interior(h_ext),
            "v": self.grid.interior(v_ext),
        }

    # -- fused extended-state fast path (TPU) -------------------------------
    def extend_state(self, state: State, with_strips: bool = False) -> State:
        """Interior state -> extended state (ghosts zeroed; filled on use).

        ``with_strips=True`` adds the canonical edge-strip carry
        (``sh``/``sv``) used by the in-kernel-exchange stepper.
        """
        from ..ops.fv import embed_interior

        g = self.grid
        y = {k: embed_interior(g, v) for k, v in state.items()}
        if with_strips:
            from ..ops.pallas.swe_step import raw_strips

            y["sh_sn"], y["sh_we"] = raw_strips(y["h"], g.n, g.halo)
            y["sv_sn"], y["sv_we"] = raw_strips(y["v"], g.n, g.halo)
        return y

    def restrict_state(self, y_ext: State) -> State:
        """Extended state -> interior state (strip carries dropped)."""
        return {k: self.grid.interior(v) for k, v in y_ext.items()
                if k in ("h", "v")}

    def make_fused_step(self, dt: float, in_kernel_exchange: bool = True):
        """SSPRK3 step over *extended* state, one fused kernel per stage.

        Each stage reads the ghost-filled state once from HBM and writes
        the combined next-stage state once (RHS + stage axpy fused in
        VMEM; :mod:`jaxstream.ops.pallas.swe_step`) — the minimum-traffic
        formulation of the step for the memory-bound FV numerics (deck
        p.19).  With ``in_kernel_exchange`` (default) the halo fill also
        happens inside the kernel via the strip carry (state pytree
        ``{"h","v","sh_sn","sh_we","sv_sn","sv_we"}``; build with
        ``extend_state(state, with_strips=True)``); otherwise a
        concat-layout jnp exchange runs between kernels.  Requires
        ``backend='pallas'`` and ``nu4 == 0`` (the hyperdiffusion refill
        pattern is a different dataflow); use :meth:`make_step` otherwise.
        """
        if self._pallas_rhs is None:
            raise ValueError("make_fused_step requires backend='pallas'")
        if self.nu4 != 0.0:
            raise ValueError("make_fused_step does not support nu4 > 0")
        g = self.grid
        interpret = self.backend == "pallas_interpret"
        if in_kernel_exchange:
            from ..ops.pallas.swe_step import make_fused_ssprk3_step_inkernel

            return make_fused_ssprk3_step_inkernel(
                g.n, g.halo, g.dalpha, g.radius, self.gravity, self.omega,
                dt, self.b_ext, scheme=self.scheme, limiter=self.limiter,
                interpret=interpret,
            )
        from ..ops.pallas.swe_step import make_fused_ssprk3_step
        from ..parallel.halo import make_concat_exchanger

        # Concat-layout exchange: one read + one write per field instead
        # of a 48-update scatter chain (the dominant cost once the RHS and
        # stage combination are fused).
        exchange = make_concat_exchanger(g.n, g.halo)
        return make_fused_ssprk3_step(
            g.n, g.halo, g.dalpha, g.radius, self.gravity, self.omega,
            dt, exchange, self.b_ext,
            scheme=self.scheme, limiter=self.limiter,
            interpret=interpret,
        )

    def _hyperdiffuse(self, q_ext):
        """-nu4 del^4 q (interior), with a ghost refill between Laplacians."""
        l1 = laplacian(self.grid, q_ext)
        return -self.nu4 * laplacian(self.grid, self.fill(l1))

    def rhs(self, state: State, t) -> State:
        grid = self.grid
        k = self.khat_int

        h_ext = self.fill(state["h"])
        v_ext = self.fill(state["v"])

        if self._pallas_rhs is not None:
            dh, dv = self._pallas_rhs(h_ext, v_ext, self.b_ext)
            if self.nu4 > 0.0:
                dh = dh + self._hyperdiffuse(h_ext)
                dv_hyp = self._hyperdiffuse(v_ext)
                kk = self.khat_int
                dv_hyp = dv_hyp - kk * jnp.sum(dv_hyp * kk, axis=0)
                dv = dv + dv_hyp
            return {"h": dh, "v": dv}

        # Continuity: dh/dt = -div(h v).
        dh = -flux_divergence(
            grid, h_ext, v_ext, scheme=self.scheme, limiter=self.limiter
        )

        # Momentum, vector-invariant.
        zeta = vorticity(grid, v_ext)
        bern_ext = (
            self.gravity * (h_ext + self.b_ext) + kinetic_energy(v_ext)
        )
        grad_b = gradient(grid, bern_ext)

        v_int = grid.interior(v_ext)
        # Tangentialize before use so any radial drift cannot feed back.
        v_int = v_int - k * jnp.sum(v_int * k, axis=0)
        kxv = _cross(k, v_int)
        dv = -(zeta + self.fcor) * kxv - grad_b

        if self.nu4 > 0.0:
            dh = dh + self._hyperdiffuse(h_ext)
            # Batched over the component axis (laplacian/exchange operate on
            # trailing axes).  Componentwise Laplacian of a tangent field is
            # not tangent on the sphere — add BEFORE the projection below.
            dv = dv + self._hyperdiffuse(v_ext)

        # Project the full tendency onto the tangent plane.
        dv = dv - k * jnp.sum(dv * k, axis=0)
        return {"h": dh, "v": dv}
