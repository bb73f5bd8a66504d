"""Overlapped halo exchange (parallelization.overlap_exchange) parity.

The interior/boundary split lets every ppermute stage go on the wire
before the RHS kernel starts: the interior-only kernel computes the
ghost-free (n-2h)^2 core under the in-flight collectives, and the
boundary-band pass consumes the received strips.  The serialized
exchange stays the reference — these tests pin the split path to it on
the face tier and the factored TT tier in-process (6 virtual devices);
the 24-device block tier runs the same check in the slow subprocess
parity (tests/cov_block_worker.py).

Tolerances: the TT tier is bitwise (the batched exchange ships the
identical strips).  The dense tiers are ulp-level — the interior/band
tiling reproduces the fused kernel's arithmetic cell for cell (asserted
bitwise at the default halo=2 in
test_interior_band_split_matches_full_kernel under one jit; at other
halos XLA's fusion of the differently-shaped band subgraphs already
moves single ulps), and re-fusion around the kernels moves single f32
ulps per step; over the 5-step runs here that stays within the 1e-6
relative budget.  (The budget is a property of THIS direct-stepping
configuration: an ulp seed can flip an MC-limiter branch and amplify
locally, so differently-fused contexts — e.g. steps inside
integrate()'s unrolled loop — show larger, still-benign divergence.
All of it is deterministic per XLA version, so these assertions are
stable, not statistical.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water_cov import CovariantShallowWater
from jaxstream.parallel.mesh import setup_sharding, shard_state
from jaxstream.parallel.shard_cov import make_sharded_cov_stepper
from jaxstream.physics.initial_conditions import (williamson_tc2,
                                                  williamson_tc5)


def _needs6():
    if len(jax.devices("cpu")) < 6:
        pytest.skip("needs 6 virtual CPU devices")


def _setup(overlap=False):
    return setup_sharding({"parallelization": {
        "num_devices": 6, "device_type": "cpu", "use_shard_map": True,
        "overlap_exchange": overlap}})


@pytest.mark.slow
def test_interior_band_split_matches_full_kernel():
    """Single-device, single jit: interior kernel + band pass tile the
    fused external-sym kernel BITWISE on every face (the arithmetic
    claim the overlapped steppers rest on)."""
    from jaxstream.geometry.cubed_sphere import FACE_AXES
    from jaxstream.ops.fv import embed_interior
    from jaxstream.ops.pallas.swe_cov import (make_cov_rhs_band_local,
                                              make_cov_rhs_interior_local,
                                              make_cov_rhs_pallas,
                                              sym_edge_normals)
    from jaxstream.ops.pallas.swe_rhs import coord_rows

    n, halo = 16, 2
    grid = build_grid(n, halo=halo, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA, b_ext=b_ext)
    st = model.initial_state(h_ext, v_ext)
    h_e = model.exchange(embed_interior(grid, st["h"]))
    u_e = model.exchange_u(embed_interior(grid, st["u"]))
    ssn, swe = sym_edge_normals(grid, u_e)

    rhs_full = make_cov_rhs_pallas(grid, EARTH_GRAVITY, EARTH_OMEGA,
                                   interpret=True, n_faces=1,
                                   external_sym=True)
    rhs_int = make_cov_rhs_interior_local(
        n, halo, float(grid.dalpha), float(grid.radius),
        EARTH_GRAVITY, EARTH_OMEGA, interpret=True)
    band = make_cov_rhs_band_local(
        n, halo, float(grid.dalpha), float(grid.radius),
        EARTH_GRAVITY, EARTH_OMEGA)
    xr, xfr, yc, yfc, _ = coord_rows(n, halo)
    xi, xfi = xr[:, halo:halo + n], xfr[:, halo:halo + n]
    yi, yfi = yc[halo:halo + n], yfc[halo:halo + n]
    fz_all = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)
    b_e = model.b_ext

    @jax.jit
    def split_vs_full(f):
        sl = lambda a, ax: jax.lax.dynamic_slice_in_dim(a, f, 1, ax)
        fz, hf, uf = sl(fz_all, 0), sl(h_e, 0), sl(u_e, 1)
        bf, sf, wf = sl(b_e, 0), sl(ssn, 0), sl(swe, 0)
        dh0, du0 = rhs_full(fz, hf, uf, bf, sf, wf)
        dhc, duc = rhs_int(
            fz, xi, xfi, yi, yfi,
            hf[:, halo:halo + n, halo:halo + n],
            uf[:, :, halo:halo + n, halo:halo + n],
            bf[:, halo:halo + n, halo:halo + n])
        dh1, du1 = band(fz, xr, xfr, yc, yfc, hf, uf, bf, sf, wf,
                        dhc, duc)
        return dh0, du0, dh1, du1

    for f in range(6):
        dh0, du0, dh1, du1 = split_vs_full(f)
        assert bool(jnp.all(dh1 == dh0)), f"dh face {f}"
        assert bool(jnp.all(du1 == du0)), f"du face {f}"


@pytest.mark.slow
def test_face_tier_overlap_matches_serialized_tc2():
    """5-step TC2 run, overlap on vs off: <= 1e-6 relative."""
    _needs6()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA)
    setup = _setup()
    ss = shard_state(setup, model.initial_state(h_ext, v_ext))
    step0 = make_sharded_cov_stepper(model, setup, 600.0, overlap=False)
    step1 = make_sharded_cov_stepper(model, setup, 600.0, overlap=True)
    a, b = ss, ss
    for _ in range(5):
        a = step0(a, 0.0)
        b = step1(b, 0.0)
    for k in ("h", "u"):
        x = np.asarray(a[k], np.float64)
        y = np.asarray(b[k], np.float64)
        rel = np.abs(x - y).max() / (np.abs(x).max() + 1e-300)
        assert rel <= 1e-6, (k, rel)


def test_face_tier_overlap_matches_serialized_tc5():
    """5-step TC5 (mountain-forced) run at the CFL-matched dt=300:
    <= 1e-6 relative, and mass conserved like the serialized path."""
    _needs6()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA, b_ext=b_ext)
    setup = _setup()
    s0 = model.initial_state(h_ext, v_ext)
    ss = shard_state(setup, s0)
    step0 = make_sharded_cov_stepper(model, setup, 300.0, overlap=False)
    step1 = make_sharded_cov_stepper(model, setup, 300.0, overlap=True)
    a, b = ss, ss
    for _ in range(5):
        a = step0(a, 0.0)
        b = step1(b, 0.0)
    for k in ("h", "u"):
        x = np.asarray(a[k], np.float64)
        y = np.asarray(b[k], np.float64)
        rel = np.abs(x - y).max() / (np.abs(x).max() + 1e-300)
        assert rel <= 1e-6, (k, rel)
    # The band pass imposes the same symmetrized seam fluxes, so the
    # overlapped path conserves mass to the same f32 budget.
    area = np.asarray(grid.interior(grid.area), np.float64)
    m0 = float(np.sum(area * np.asarray(s0["h"], np.float64)))
    m1 = float(np.sum(area * np.asarray(b["h"], np.float64)))
    assert abs(m1 - m0) / abs(m0) < 2e-6


def test_overlap_flag_threads_from_config():
    """setup_sharding reads parallelization.overlap_exchange and the
    dispatcher's default picks it up."""
    _needs6()
    setup = _setup(overlap=True)
    assert setup.overlap_exchange
    assert not _setup().overlap_exchange


def test_overlap_issues_same_ppermute_schedule():
    """Structural check at the jaxpr level (no compile): both schedules
    trace to exactly 4 ppermute stages x 3 RK stages — the split did
    not silently drop or duplicate exchanges.  (HLO-text counts are NOT
    comparable across the two: the async start/done lowering differs
    with the overlap restructure — which is the point.)"""
    _needs6()
    grid = build_grid(8, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA)
    setup = _setup(overlap=True)
    ss = shard_state(setup, model.initial_state(h_ext, v_ext))
    step0 = make_sharded_cov_stepper(model, setup, 600.0, overlap=False)
    step1 = make_sharded_cov_stepper(model, setup, 600.0, overlap=True)
    count = lambda s: str(jax.make_jaxpr(
        lambda y, t: s(y, t))(ss, jnp.float32(0.0))).count(" ppermute")
    c0, c1 = count(step0), count(step1)
    assert c0 == 12, c0
    assert c1 == 12, c1


def test_tt_batched_exchange_matches_per_field():
    """The batched up-front TT exchange (one 4-stage schedule for all
    fields) ships strips bitwise-identical to four per-field
    exchanges — the claim the overlapped factored tier rests on."""
    _needs6()
    from jax.sharding import PartitionSpec as P

    from jaxstream.tt.shard import (make_tt_strip_exchange,
                                    make_tt_strip_exchange_many,
                                    panel_mesh, shard_factored_state)
    from jaxstream.tt.sphere import factor_panels
    from jax import shard_map

    rng = np.random.default_rng(11)
    n, rank = 16, 6
    mesh = panel_mesh(jax.devices("cpu")[:6])
    pairs = [factor_panels(rng.standard_normal((6, n, n)), r)
             for r in (rank, rank + 2, 3)]
    pairs = [shard_factored_state(p, mesh) for p in pairs]

    one = make_tt_strip_exchange()
    many = make_tt_strip_exchange_many()
    spec = P("panel")
    f_one = jax.jit(shard_map(
        lambda *ps: tuple(one(p) for p in ps), mesh=mesh,
        in_specs=spec, out_specs=spec, check_vma=False))
    f_many = jax.jit(shard_map(
        lambda *ps: tuple(many(list(ps))), mesh=mesh,
        in_specs=spec, out_specs=spec, check_vma=False))
    a = f_one(*pairs)
    b = f_many(*pairs)
    for ga, gb in zip(a, b):
        for xa, xb in zip(ga, gb):
            assert (np.asarray(xa) == np.asarray(xb)).all()


@pytest.mark.slow
def test_tt_tier_overlap_bitwise():
    """Factored TT tier: the batched up-front exchange ships identical
    strips, so overlap on vs off is bitwise over a 3-step TC5 run."""
    _needs6()
    from jaxstream.tt.shard import (make_tt_sphere_swe_sharded,
                                    panel_mesh, shard_factored_state)
    from jaxstream.tt.sphere import factor_panels, unfactor_panels
    from jaxstream.tt.sphere_swe import covariant_from_cartesian

    # Slow tier: compiling the sharded SWE step twice is ~1.5 min even
    # at this small n/rank (the per-rounding sweeps dominate tracing).
    # Fast-tier coverage of the same wiring: the exchange-primitive
    # bitwise test above, plus the MULTICHIP dryrun gate's one-step
    # factored-TT overlap parity (run by the driver every round).
    n, rank = 8, 4
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    h0 = np.asarray(grid.interior(h_ext), np.float64)
    ua0, ub0 = covariant_from_cartesian(grid, v_ext)
    mesh = panel_mesh(jax.devices("cpu")[:6])
    kw = dict(hs=b_ext, omega=EARTH_OMEGA, gravity=EARTH_GRAVITY)
    s0 = jax.jit(make_tt_sphere_swe_sharded(grid, 300.0, rank, mesh, **kw))
    s1 = jax.jit(make_tt_sphere_swe_sharded(grid, 300.0, rank, mesh,
                                            overlap_exchange=True, **kw))
    p = shard_factored_state(
        tuple(factor_panels(x, rank) for x in (h0, ua0, ub0)), mesh)
    a, b = p, p
    for _ in range(3):
        a = s0(a)
        b = s1(b)
    for i, k in enumerate(("h", "ua", "ub")):
        x = np.asarray(unfactor_panels(a[i]))
        y = np.asarray(unfactor_panels(b[i]))
        assert (x == y).all(), k
