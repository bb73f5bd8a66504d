"""Panel-sharded TT tier: exchange and step parity vs single-device.

Runs on 6 of the 8 virtual CPU devices (conftest).  The sharded tier
must reproduce the single-device factored tier exactly: the ppermute
strip exchange is the same routing as sphere.tt_strip_ghosts, and the
per-face math is the same code on (1, n, r) slices.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.physics import initial_conditions as ics
from jaxstream.tt.shard import (
    make_tt_sphere_advection_sharded,
    make_tt_sphere_diffusion_sharded,
    make_tt_sphere_swe_sharded,
    make_tt_strip_exchange,
    panel_mesh,
    shard_factored_state,
)
from jaxstream.tt.sphere import (
    factor_panels,
    make_tt_sphere_advection,
    tt_strip_ghosts,
    unfactor_panels,
)
from jaxstream.tt.sphere_diffusion import make_tt_sphere_diffusion
from jaxstream.tt.sphere_swe import (
    covariant_from_cartesian,
    make_tt_sphere_swe,
)

jax.config.update("jax_enable_x64", True)


def _mesh():
    devs = jax.devices("cpu")
    if len(devs) < 6:
        pytest.skip("needs 6 virtual CPU devices (conftest XLA_FLAGS)")
    return panel_mesh(devs)


def _smooth_field(grid):
    x, y, z = (np.asarray(c, np.float64) for c in grid.xyz)
    h = grid.halo
    sl = slice(h, h + grid.n)
    return (1.0 + x * y + 0.3 * z**2)[:, sl, sl]


def test_sharded_strip_exchange_matches_global():
    """The ppermute exchange reproduces tt_strip_ghosts (same routing,
    flips, placement; to f64 matmul-reassociation level — the factor
    contractions compile in different fusion contexts)."""
    mesh = _mesh()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    q = factor_panels(_smooth_field(grid), 8)
    ref = tt_strip_ghosts(q, 1)

    exchange = make_tt_strip_exchange()
    sharded = shard_map(
        exchange, mesh=mesh,
        in_specs=jax.sharding.PartitionSpec("panel"),
        out_specs=jax.sharding.PartitionSpec("panel"))
    out = sharded(shard_factored_state(q, mesh))
    for got, want, name in zip(out, ref, ("gS", "gN", "gW", "gE")):
        g, w = np.asarray(got), np.asarray(want)
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < 1e-14, (name, err)


def test_sharded_advection_step_parity():
    mesh = _mesh()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    u0 = 2 * np.pi * grid.radius / (12 * 86400.0)
    wind = ics.solid_body_wind(grid, u0)
    q = factor_panels(np.asarray(grid.interior(ics.cosine_bell(grid))), 8)

    step1 = jax.jit(make_tt_sphere_advection(grid, wind, 600.0, 8))
    step6 = jax.jit(make_tt_sphere_advection_sharded(
        grid, wind, 600.0, 8, mesh))
    p1, p6 = q, shard_factored_state(q, mesh)
    for _ in range(3):
        p1 = step1(p1)
        p6 = step6(p6)
    d1 = np.asarray(unfactor_panels(p1))
    d6 = np.asarray(unfactor_panels(jax.tree.map(np.asarray, p6)))
    err = np.max(np.abs(d1 - d6)) / np.max(np.abs(d1))
    assert err < 1e-12, err


def test_sharded_diffusion_step_parity():
    mesh = _mesh()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    q = factor_panels(_smooth_field(grid), 8)

    step1 = jax.jit(make_tt_sphere_diffusion(grid, 1.0e6, 600.0, 8))
    step6 = jax.jit(make_tt_sphere_diffusion_sharded(
        grid, 1.0e6, 600.0, 8, mesh))
    p1, p6 = q, shard_factored_state(q, mesh)
    for _ in range(3):
        p1 = step1(p1)
        p6 = step6(p6)
    d1 = np.asarray(unfactor_panels(p1))
    d6 = np.asarray(unfactor_panels(jax.tree.map(np.asarray, p6)))
    err = np.max(np.abs(d1 - d6)) / np.max(np.abs(d1))
    assert err < 1e-12, err


def test_sharded_swe_step_parity_with_kappa_and_topography():
    """Full SWE: topography + in-step dissipation, 6-device vs the
    single-device factored run.  Compared at FULL rank with tight
    coefficient tolerance: truncated-rank runs are not comparable
    device-count-wise (the rounding's pivot/basis choices are
    reassociation-sensitive and the truncation error differences
    compound chaotically), but at full rank the rounding is exact and
    the two tiers are the same discretization."""
    mesh = _mesh()
    n = 16
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    h_ext, v_ext, b_ext = ics.williamson_tc5(grid, EARTH_GRAVITY,
                                             EARTH_OMEGA)
    h0 = np.asarray(grid.interior(h_ext))
    ua0, ub0 = covariant_from_cartesian(grid, v_ext)
    # rounding='svd': exact truncation is deterministic and
    # well-conditioned, so full-rank parity holds to reassociation
    # level (full-rank ACA is exact in exact arithmetic but its
    # sequential pivoting amplifies roundoff to ~1e-6 here).
    kw = dict(hs=b_ext, kappa=3e5, coeff_tol=1e-13, rounding="svd")

    step1 = jax.jit(make_tt_sphere_swe(grid, 300.0, n, **kw))
    step6 = jax.jit(make_tt_sphere_swe_sharded(grid, 300.0, n, mesh,
                                               **kw))
    p1 = tuple(factor_panels(x, n) for x in (h0, ua0, ub0))
    p6 = shard_factored_state(p1, mesh)
    for _ in range(3):
        p1 = step1(p1)
        p6 = step6(p6)
    for i, name in enumerate(("h", "ua", "ub")):
        d1 = np.asarray(unfactor_panels(p1[i]))
        d6 = np.asarray(unfactor_panels(jax.tree.map(np.asarray, p6[i])))
        err = np.max(np.abs(d1 - d6)) / np.max(np.abs(d1))
        assert err < 1e-10, (name, err)


def test_sharded_swe_svd_rounding_runs():
    """The stability-tier rounding ('svd') compiles and steps under the
    panel-sharded path (QR/SVD inside shard_map)."""
    mesh = _mesh()
    grid = build_grid(16, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    h_ext, v_ext, b_ext = ics.williamson_tc5(grid, EARTH_GRAVITY,
                                             EARTH_OMEGA)
    h0 = np.asarray(grid.interior(h_ext))
    ua0, ub0 = covariant_from_cartesian(grid, v_ext)
    rank = 8
    step6 = jax.jit(make_tt_sphere_swe_sharded(
        grid, 300.0, rank, mesh, hs=b_ext, rounding="svd"))
    p6 = shard_factored_state(
        tuple(factor_panels(x, rank) for x in (h0, ua0, ub0)), mesh)
    for _ in range(2):
        p6 = step6(p6)
    h = np.asarray(unfactor_panels(jax.tree.map(np.asarray, p6[0])))
    assert np.isfinite(h).all()
    assert 1000.0 < h.min() and h.max() < 8000.0
