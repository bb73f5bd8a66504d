"""Build a stepper from a :class:`CapabilityPlan` — the ONE recipe.

``jaxstream.analysis.contracts`` traces and audits every enumerated
plan through this builder, and ``tests/test_plan.py`` executes its
generated parity assertions through the same builder — so the thing
the analyzer proves and the thing the parity tests run can never be
two different constructions of "the plan's stepper".

:class:`PlanContext` owns the (lazily built, cached) grid / models /
states a build needs at one ``(n, halo, dt)``;
:func:`build_stepper` dispatches on the plan and returns a
:class:`BuiltStepper` whose ``step``/``example`` pair is directly
traceable (``jax.make_jaxpr``-style) and executable.  Every returned
stepper carries its proof stamp (:func:`jaxstream.plan.proof.
attach_proof` runs inside the factories this dispatches to, or here
for the composed serving segments).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

from .proof import attach_proof

__all__ = ["PlanContext", "BuiltStepper", "build_stepper"]

_TT_RANK = 4


@dataclasses.dataclass
class BuiltStepper:
    plan: Any
    step: Any                      # the callable
    example: Tuple                 # example args for step(*example)
    steps_per_call: int = 1
    kind: str = "state_t"          # 'state_t' | 'tt_pairs' | 'masked'

    @property
    def proof(self):
        return getattr(self.step, "proof", None)


class PlanContext:
    """Lazily-built shared fixtures for one ``(n, halo, dt)``."""

    def __init__(self, n: int = 12, halo: int = 2, dt: float = 300.0):
        self.n, self.halo, self.dt = n, halo, dt
        self._cache = {}

    def _get(self, key, builder):
        if key not in self._cache:
            self._cache[key] = builder()
        return self._cache[key]

    # -- geometry / models / states ------------------------------------
    @property
    def grid(self):
        def mk():
            import jax.numpy as jnp

            from ..config import EARTH_RADIUS
            from ..geometry.cubed_sphere import build_grid

            return build_grid(self.n, halo=self.halo,
                              radius=EARTH_RADIUS, dtype=jnp.float32)
        return self._get("grid", mk)

    def model(self, backend: str = "jnp"):
        def mk():
            from ..config import EARTH_GRAVITY, EARTH_OMEGA
            from ..models.shallow_water_cov import CovariantShallowWater

            return CovariantShallowWater(
                self.grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA,
                backend=backend)
        return self._get(("model", backend), mk)

    @property
    def state(self):
        """Interior covariant TC2 state, pinned f32 (the precision
        contract under audit is the steppers', not the IC builders' —
        the test conftest runs ambient x64)."""
        def mk():
            import jax
            import jax.numpy as jnp

            from ..config import EARTH_GRAVITY, EARTH_OMEGA
            from ..physics.initial_conditions import williamson_tc2

            h_ext, v_ext = williamson_tc2(self.grid, EARTH_GRAVITY,
                                          EARTH_OMEGA)
            st = self.model().initial_state(h_ext, v_ext)
            return jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float32), st)
        return self._get("state", mk)

    def batched_state(self, B: int):
        def mk():
            import jax.numpy as jnp

            st = self.state
            return {"h": jnp.stack([st["h"]] * B),
                    "u": jnp.stack([st["u"]] * B, axis=1)}
        return self._get(("bstate", B), mk)

    @property
    def tt_factors(self):
        def mk():
            import numpy as np

            from ..config import EARTH_GRAVITY, EARTH_OMEGA
            from ..ops.fv import covariant_components
            from ..physics.initial_conditions import williamson_tc2
            from ..tt.sphere import factor_panels

            g = self.grid
            h_ext, v_ext = williamson_tc2(g, EARTH_GRAVITY,
                                          EARTH_OMEGA)
            ua, ub = covariant_components(g, v_ext)
            return tuple(
                factor_panels(np.asarray(g.interior(x), np.float32),
                              _TT_RANK)
                for x in (h_ext, ua, ub))
        return self._get("tt_factors", mk)

    # -- sharding setups -----------------------------------------------
    def setup(self, overlap: bool = False, shard_map: bool = True):
        def mk():
            import dataclasses as _dc

            from ..parallel.mesh import setup_sharding

            su = setup_sharding({"parallelization": {
                "num_devices": 6, "device_type": "cpu",
                "use_shard_map": shard_map}})
            return (_dc.replace(su, overlap_exchange=True)
                    if overlap else su)
        return self._get(("setup", overlap, shard_map), mk)

    def ensemble_setup(self, members: int, layout: str,
                       num_devices: int):
        def mk():
            from ..parallel.mesh import setup_ensemble_sharding

            return setup_ensemble_sharding(
                {"parallelization": {"num_devices": num_devices,
                                     "device_type": "cpu"}},
                members=members, layout=layout)
        return self._get(("esetup", members, layout, num_devices), mk)

    @property
    def tt_mesh(self):
        def mk():
            import jax

            from ..tt.shard import panel_mesh

            return panel_mesh(jax.devices("cpu")[:6])
        return self._get("tt_mesh", mk)


def _ens_arg(plan) -> int:
    return plan.ensemble if plan.ensemble > 1 else 0


def build_stepper(plan, ctx: PlanContext) -> BuiltStepper:
    """The single config-plan-stepper pipeline's last stage."""
    import jax.numpy as jnp

    dt = ctx.dt
    t0 = jnp.float32(0.0)
    if plan.serving:
        return _build_serving(plan, ctx)
    if plan.tier == "fused":
        from ..ops.pallas.precision import encode_strips

        m = ctx.model("pallas_interpret")
        pol = plan.stage if plan.stage != "f32" else None
        step = m.make_fused_step(dt, precision=pol,
                                 temporal_block=plan.temporal_block,
                                 ensemble=_ens_arg(plan))
        if plan.ensemble > 1:
            y0 = m.ensemble_compact_state(
                ctx.batched_state(plan.ensemble))
        else:
            y0 = m.compact_state(ctx.state)
        y0 = encode_strips(y0, pol)
        return BuiltStepper(plan, step, (y0, t0),
                            steps_per_call=plan.temporal_block)
    if plan.tier in ("face", "face_block", "gspmd", "classic",
                     "cartesian_shard"):
        from ..parallel.sharded_model import make_stepper_for

        m = ctx.model()
        if plan.tier == "face":
            su = ctx.setup(overlap=plan.overlap)
        elif plan.tier == "gspmd":
            su = ctx.setup(shard_map=False)
        elif plan.tier == "classic":
            su = None
        else:
            raise NotImplementedError(
                f"tier {plan.tier!r} is schedule-verified only (its "
                "mesh cannot trace on the in-process device pool)")
        step = make_stepper_for(m, su, ctx.state, dt,
                                temporal_block=plan.temporal_block,
                                ensemble=_ens_arg(plan))
        y0 = (ctx.batched_state(plan.ensemble) if plan.ensemble > 1
              else ctx.state)
        return BuiltStepper(plan, step, (y0, t0),
                            steps_per_call=getattr(
                                step, "steps_per_call", 1))
    if plan.tier in ("tt", "tt_sharded"):
        from ..tt.shard import make_tt_sphere_swe_sharded
        from ..tt.sphere_swe import make_tt_sphere_swe

        if plan.tier == "tt_sharded":
            step = make_tt_sphere_swe_sharded(
                ctx.grid, dt, _TT_RANK, ctx.tt_mesh,
                overlap_exchange=plan.overlap,
                temporal_block=plan.temporal_block)
        else:
            step = make_tt_sphere_swe(
                ctx.grid, dt, _TT_RANK,
                temporal_block=plan.temporal_block)
        step = attach_proof(step, plan)
        return BuiltStepper(plan, step, (ctx.tt_factors,),
                            steps_per_call=plan.temporal_block,
                            kind="tt_pairs")
    raise NotImplementedError(f"no builder for tier {plan.tier!r}")


def _build_serving(plan, ctx: PlanContext) -> BuiltStepper:
    """The serving placements' masked-segment programs, composed the
    way :class:`jaxstream.serve.server.EnsembleServer._build_bucket`
    composes them (panel: shard_map ensemble stepper; member/single:
    the vmapped classic, or the grouped fused member fold — per chip
    under ``shard_map`` when member-placed)."""
    import jax.numpy as jnp

    from .. import stepping
    from ..models.shallow_water_cov import (ENSEMBLE_CARRY_AXES,
                                            ENSEMBLE_STATE_AXES)

    B, dt, seg = plan.ensemble, ctx.dt, 2
    rem0 = jnp.asarray([seg] * B, jnp.int32)
    if plan.tier == "fused":
        # The grouped fused member-fold bucket (round-11 parity mode):
        # the member axis rides the stage kernels' grid inside the
        # masked segment.
        m = ctx.model("pallas_interpret")
        axes = ENSEMBLE_CARRY_AXES
        carry = m.ensemble_compact_state(ctx.batched_state(B))
        if plan.placement == "member":
            from ..parallel.mesh import member_shard_map

            mesh = ctx.ensemble_setup(B, "member", plan.num_devices).mesh
            local = m.make_fused_step(dt, ensemble=B // plan.num_devices)

            def seg_local(y, rem, _s=local, _ax=axes):
                y, _, rem = stepping.integrate_masked(
                    _s, y, 0.0, rem, seg, dt, _ax)
                return y, rem

            seg_fn = attach_proof(
                member_shard_map(seg_local, mesh, (axes, 0), (axes, 0)),
                plan)
            return BuiltStepper(plan, seg_fn, (carry, rem0),
                                kind="masked")
        pstep = m.make_fused_step(dt, ensemble=B)
    else:
        m = ctx.model()
        axes = ENSEMBLE_STATE_AXES
        carry = ctx.batched_state(B)
        if plan.placement == "panel":
            from ..parallel.shard_cov import (
                make_sharded_cov_ensemble_stepper)

            esetup = ctx.ensemble_setup(B, "panel_member", 6)
            pstep = make_sharded_cov_ensemble_stepper(
                m, esetup, dt, B, wrap_jit=False)
        else:
            pstep = stepping.vmap_ensemble(m.make_step(dt),
                                           ENSEMBLE_STATE_AXES)

    def seg_fn(y, rem, _s=pstep, _ax=axes):
        return stepping.integrate_masked(_s, y, 0.0, rem, seg, dt,
                                         _ax)

    seg_fn = attach_proof(seg_fn, plan)
    return BuiltStepper(plan, seg_fn, (carry, rem0), kind="masked")
