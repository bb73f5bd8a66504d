"""Run a whole model step inside ``shard_map`` (explicit-collective path).

This composes :mod:`jaxstream.parallel.shard_halo` with the model layer:
the full SSPRK3 step — ghost fills via ``lax.ppermute``, FV stencils on
local blocks — executes as one SPMD program over the ``('panel','y','x')``
mesh, under a single top-level ``jit``.  This is the "hand-scheduled
collectives preserving the reference's race-free staging" design
(SURVEY.md §2.6) as opposed to the GSPMD-inferred path used by default.

Mechanics: every face-indexed array the model owns (grid metric terms,
Coriolis, topography, ...) is passed into ``shard_map`` as a sharded
argument; inside, a shallow-copied model is rebound to the local shards
and its unchanged ``rhs`` runs on ``(..., 1, M, M)`` blocks — the numerics
code is identical between the single-device, GSPMD, and explicit paths
(one source of truth, three execution strategies).
"""

from __future__ import annotations

import copy
import dataclasses

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..geometry.cubed_sphere import CubedSphereGrid
from ..stepping import SCHEMES
from .mesh import ShardingSetup
from .shard_halo import make_block_halo_program, make_shard_halo_program

__all__ = ["make_sharded_stepper", "make_stepper_for", "shard_params"]


def make_stepper_for(model, setup, example_state, dt: float,
                     scheme: str = "ssprk3", temporal_block: int = None,
                     ensemble: int = 0, donate: bool = False,
                     precision=None):
    """Dispatch on the config's ``use_shard_map`` flag.

    Explicit ppermute path when requested (and the mesh fits), otherwise
    the GSPMD path: plain ``jit`` over the model step — sharded inputs
    make XLA infer the collectives (the reference's implicit model).

    ``temporal_block = k > 1`` (default: the setup's) returns a stepper
    advancing k steps per call (its ``steps_per_call`` attribute says
    how many): the deep-halo blocked stepper on the covariant face tier
    (ONE 3*k*halo-deep exchange per block), exact k-step fusion
    elsewhere.  Callers that count steps must honor ``steps_per_call``.

    ``ensemble = B > 0``: the returned stepper advances a member-batched
    state (``{"h": (B, 6, n, n), "u": (2, B, 6, n, n)}``-layout) — the
    explicit covariant face tier uses the batched-exchange ensemble
    stepper (one ppermute per schedule stage for ALL members), the
    GSPMD path vmaps the model step over the member axis and lets XLA
    batch the inferred collectives.  ``donate=True`` donates the state
    carry at the top-level jit (callers must then treat each input
    state as consumed).

    ``precision`` (round 10): the per-stage dtype policy is wired for
    the single-device fused covariant stepper
    (``CovariantShallowWater.make_fused_step(precision=...)``, where it
    composes with temporal blocking, ensembles and donation); the
    steppers this dispatcher builds run the classic jnp RHS inside
    shard_map / GSPMD, which has no bf16 stage form — a non-f32 policy
    is rejected here with that pointer rather than silently ignored.
    The sharded tiers' 16-bit-strip *wire accounting* is available
    without a stepper change: ``scripts/comm_probe.py --strip-dtype
    bf16`` / ``comm_probe.temporal_block_plan(strip_dtype_bytes=2)``.
    """
    from ..ops.pallas.precision import resolve_stage_precision
    from ..plan import rules as plan_rules
    from ..plan.proof import attach_proof

    if resolve_stage_precision(precision) is not None:
        # One source of truth for the pointer prose: the plan-layer
        # rule table (the same rule plan_for rejects the config with,
        # statically, before any trace).
        plan_rules.fail("stage-policy-needs-fused")
    if temporal_block is None:
        k = 1 if setup is None else getattr(setup, "temporal_block", 1)
    else:
        k = temporal_block

    mesh = getattr(setup, "mesh", None)
    n_dev = int(mesh.devices.size) if mesh is not None else 1

    def _stamped(step, tier):
        return attach_proof(step, _args_plan(
            model, tier, overlap=bool(getattr(setup, "overlap_exchange",
                                              False)),
            temporal_block=k, ensemble=ensemble, scheme=scheme,
            num_devices=n_dev))

    if setup is not None and setup.use_shard_map:
        if hasattr(model, "exchange_u"):
            # Covariant formulation: its explicit paths carry the
            # rotation exchange + seam symmetrization as ppermute strips
            # and run the Pallas RHS kernel per device (SSPRK3 only) —
            # one face per device, or sub-panel blocks (tiles_per_edge
            # > 1) on the (6, s, s) mesh.
            from .shard_cov import (make_sharded_cov_ensemble_stepper,
                                    make_sharded_cov_stepper)
            from .shard_cov_block import make_sharded_cov_block_stepper

            blocked_mesh = (setup.panel == 6 and setup.sy == setup.sx
                            and setup.sy > 1)
            if scheme != "ssprk3":
                plan_rules.fail("explicit-cov-ssprk3", plan=None,
                                scheme=scheme)
            if ensemble:
                if setup.sy * setup.sx != 1:
                    plan_rules.fail("ensemble-face-tier")
                return _stamped(make_sharded_cov_ensemble_stepper(
                    model, setup, dt, ensemble, temporal_block=k,
                    donate=donate), "face")
            if blocked_mesh:
                return _stamped(make_sharded_cov_block_stepper(
                    model, setup, dt, temporal_block=k,
                    donate=donate), "face_block")
            return _stamped(make_sharded_cov_stepper(
                model, setup, dt, temporal_block=k, donate=donate),
                "face")
        if ensemble:
            plan_rules.fail("ensemble-needs-cov-or-gspmd")
        if k > 1:
            plan_rules.fail("temporal-block-cartesian")
        return _stamped(
            make_sharded_stepper(model, setup, example_state, dt,
                                 scheme), "cartesian_shard")
    single = setup is None or setup.mesh is None
    tier = "classic" if single else "gspmd"
    base = model.make_step(dt, scheme)
    if ensemble:
        # GSPMD/single-device ensemble: vmap the model step over the
        # member axis; XLA batches any inferred collectives across
        # members for free.  Layout rule (the ENSEMBLE_STATE_AXES
        # convention): vector fields ("u" covariant / "v" Cartesian)
        # keep their component axis first, member second; scalars lead
        # with the member axis.
        from ..stepping import blocked, vmap_ensemble

        axes = {kk: (1 if kk in ("u", "v") else 0)
                for kk in example_state}
        vstep = vmap_ensemble(base, axes)
        if k > 1:
            vstep = blocked(vstep, k, dt)
        jitted = jax.jit(vstep, donate_argnums=(0,) if donate else ())

        def step(y, t):
            return jitted(y, t)

        step.ensemble = int(ensemble)
        if k > 1:
            step.steps_per_call = k
        return _stamped(step, tier)
    if k > 1:
        # GSPMD path: exact k-step fusion under one jit — one dispatch
        # per block, collectives unchanged (XLA may still pipeline
        # across the fused steps).
        from ..stepping import blocked

        jitted = jax.jit(blocked(base, k, dt),
                         donate_argnums=(0,) if donate else ())

        def step(y, t):
            return jitted(y, t)

        step.steps_per_call = k
        return _stamped(step, tier)
    return _stamped(jax.jit(base, donate_argnums=(0,) if donate else ()),
                    tier)


def _args_plan(model, tier: str, overlap: bool, temporal_block: int,
               ensemble: int, scheme: str, num_devices: int):
    """A :class:`~jaxstream.plan.plan.CapabilityPlan` reconstructed
    from direct factory arguments (the proof-stamp source for callers
    that bypass ``plan_for``'s config resolution)."""
    from ..plan.plan import CapabilityPlan
    from ..plan.rules import normalize

    grid = getattr(model, "grid", None)
    return normalize(CapabilityPlan(
        tier=tier,
        n=getattr(grid, "n", 0), halo=getattr(grid, "halo", 2),
        scheme=scheme, overlap=overlap,
        temporal_block=max(1, temporal_block or 1),
        ensemble=max(1, int(ensemble or 1)),
        nu4=getattr(model, "nu4", 0.0) != 0.0,
        num_devices=num_devices,
        use_shard_map=tier in ("face", "face_block",
                               "cartesian_shard"),
        backend=getattr(model, "backend", "jnp") or "jnp",
        covariant=hasattr(model, "exchange_u")))


def _grid_arrays(grid: CubedSphereGrid):
    """jax.Array attributes of a grid (dense dataclass or lazy plain class)."""
    if dataclasses.is_dataclass(grid):
        names = [f.name for f in dataclasses.fields(grid)]
    else:  # LazyCubedSphereGrid stores 1-D coords + (3, 6, 1, 1) frames
        names = list(vars(grid))
    out = {}
    for name in names:
        v = getattr(grid, name)
        if isinstance(v, jax.Array):
            out[name] = v
    return out


def _rebind(obj, updates):
    """dataclasses.replace for dataclasses; copy+setattr otherwise."""
    if dataclasses.is_dataclass(obj):
        return dataclasses.replace(obj, **updates)
    new = copy.copy(obj)
    for k, v in updates.items():
        setattr(new, k, v)
    return new


def _face_spec(a) -> P:
    """PartitionSpec for an array whose trailing axes are (6, ny, nx)."""
    if a.ndim <= 1:  # 1-D coordinate vectors (lazy grid): replicate
        return P(*((None,) * a.ndim))
    if a.ndim == 2:  # (6, 4) per-device parameter tables
        return P("panel", None)
    return P(*((None,) * (a.ndim - 3) + ("panel", "y", "x")))


def shard_params(setup: ShardingSetup, tree):
    """device_put a pytree of face-axis arrays with P('panel', ...)."""
    mesh = setup.mesh
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, NamedSharding(mesh, _face_spec(a))), tree
    )


def _to_blocks(a, n_loc: int, halo: int, s: int):
    """Extended ``(..., 6, M, M)`` -> per-device ``(..., 6, s, s, m_loc,
    m_loc)`` blocks (overlapping halo slices — NamedSharding cannot
    express the overlap, so the blocks are materialized host-side once at
    setup; they are static geometry, not per-step state)."""
    m_loc = n_loc + 2 * halo
    return jnp.stack([
        jnp.stack([
            a[..., by * n_loc : by * n_loc + m_loc,
              bx * n_loc : bx * n_loc + m_loc]
            for bx in range(s)
        ], axis=-3)
        for by in range(s)
    ], axis=-4)


def _is_extended(a, m: int) -> bool:
    return a.ndim >= 2 and a.shape[-2:] == (m, m)


def make_sharded_stepper(model, setup: ShardingSetup, example_state,
                         dt: float, scheme: str = "ssprk3"):
    """Build ``step(state, t) -> state`` running fully inside shard_map.

    Mesh shapes supported: panel axis of size 6 with a square ``s x s``
    sub-panel block grid (``sy == sx == s``, ``n % s == 0``) — ``s = 1``
    is the flagship one-face-per-device layout, ``s > 1`` the reference's
    planned ``tiles_per_edge`` scaling run through the explicit
    block-halo program.  State arrays are the usual interior ``(6, n, n)``
    / ``(3, 6, n, n)`` pytrees sharded over (panel, y, x).
    ``example_state`` is only read for its tree structure/ranks.
    """
    grid = model.grid
    if hasattr(model, "exchange_u"):
        raise ValueError(
            "this explicit shard_map path only rebinds the scalar/Cartesian "
            "exchanger; covariant-component models (exchange_u) use "
            "jaxstream.parallel.shard_cov.make_sharded_cov_stepper (the "
            "make_stepper_for dispatcher picks it automatically), or the "
            "GSPMD path via parallelization.use_shard_map: false."
        )
    if (setup.mesh is None or setup.panel != 6 or setup.sy != setup.sx
            or grid.n % setup.sy):
        raise ValueError(
            f"explicit shard_map path needs mesh (panel=6, y=s, x=s) with "
            f"s dividing n={grid.n}; got panel={setup.panel}, y={setup.sy}, "
            f"x={setup.sx}. Use the GSPMD path (jax.jit over NamedSharding) "
            f"for other layouts."
        )
    mesh = setup.mesh
    s = setup.sy
    blocked = s > 1
    if blocked:
        if not dataclasses.is_dataclass(grid):
            raise ValueError(
                "block-mesh explicit path needs an eager CubedSphereGrid "
                "(metrics='eager'); lazy grids are only wired for s=1."
            )
        n_loc = grid.n // s
        program, local_exchange = make_block_halo_program(
            grid.n, grid.halo, s
        )
    else:
        n_loc = grid.n
        program, local_exchange = make_shard_halo_program(grid.n, grid.halo)
    m_ext = grid.m

    def pack(a):
        """Array + its PartitionSpec, block-slicing extended arrays."""
        if blocked and _is_extended(a, m_ext):
            blocks = _to_blocks(a, n_loc, grid.halo, s)
            spec = P(*((None,) * (blocks.ndim - 5)
                       + ("panel", "y", "x", None, None)))
            return blocks, spec
        return a, _face_spec(a)

    garrs = _grid_arrays(grid)
    aux = {k: v for k, v in vars(model).items()
           if isinstance(v, jax.Array) and v.ndim >= 3}
    packed = {
        "grid": {k: pack(v) for k, v in garrs.items()},
        "aux": {k: pack(v) for k, v in aux.items()},
    }
    params = {g: {k: v[0] for k, v in d.items()} for g, d in packed.items()}
    specs = {g: {k: v[1] for k, v in d.items()} for g, d in packed.items()}
    params["halo"] = dict(program.params)
    specs["halo"] = {
        k: (P("panel", "y", "x", None) if blocked else P("panel", None))
        for k in params["halo"]
    }
    params = {
        g: {k: jax.device_put(v, NamedSharding(mesh, specs[g][k]))
            for k, v in d.items()}
        for g, d in params.items()
    }
    stepper = SCHEMES[scheme]

    def unblock(a):
        # (..., 1, 1, 1, m_loc, m_loc) -> (..., 1, m_loc, m_loc)
        return a.reshape(a.shape[:-5] + (1,) + a.shape[-2:])

    def local_step(p, state, t):
        updates = {}
        for k, v in p["grid"].items():
            updates[k] = unblock(v) if (blocked and v.ndim >= 5
                                        and v.shape[-2] == n_loc + 2 * grid.halo
                                        and v.shape[-4] == 1) else v
        if blocked:
            grid_l = _rebind(grid, dict(updates, n=n_loc))
        else:
            grid_l = _rebind(grid, updates)
        m = copy.copy(model)
        m.grid = grid_l
        # Inside shard_map the RHS runs on (1, m_loc, m_loc) local blocks;
        # the 6-face Pallas kernel doesn't apply — use the jnp path (the
        # parity oracle, numerics-identical).
        m._pallas_rhs = None
        m_loc = n_loc + 2 * grid.halo
        for k, v in p["aux"].items():
            setattr(m, k, unblock(v) if (blocked and v.ndim >= 5
                                         and v.shape[-2] == m_loc
                                         and v.shape[-4] == 1) else v)
        if blocked:
            es, rs, ac = (p["halo"]["edge_sel"], p["halo"]["rev_sel"],
                          p["halo"]["active"])
            m.exchange = lambda f: local_exchange(f, es, rs, ac)
        else:
            es, rs = p["halo"]["edge_sel"], p["halo"]["rev_sel"]
            m.exchange = lambda f: local_exchange(f, es, rs)
        return stepper(m.rhs, state, t, dt)

    state_specs = jax.tree_util.tree_map(_face_spec, example_state)
    in_specs = (specs, state_specs, P())

    smapped = shard_map(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=state_specs,
        check_vma=False,
    )

    @jax.jit
    def step(state, t):
        return smapped(params, state, t)

    return step
