"""Initial conditions: Williamson test suite, Galewsky jet, demo fields.

The reference's "Initial Conditions (Physics)" pipeline stage (deck p.6)
with its two demo ICs — the checkerboard "Lima Flag" heat source (p.12/17)
and the equatorial cosine bell (p.13/18) — plus the formal Williamson
(1992) cases TC1/TC2/TC5/TC6 and the Galewsky (2004) jet pinned by
``BASELINE.json``.

All fields are evaluated analytically at *extended* cell centers where
useful (prescribed winds fill their own ghosts exactly — no exchange
needed), in float64 NumPy, cast to the grid dtype on the way out.
Velocities are Cartesian 3-vectors ``(3, 6, M, M)`` tangent to the sphere.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..config import EARTH_RADIUS
from ..geometry.cubed_sphere import CubedSphereGrid

__all__ = [
    "solid_body_wind",
    "zonal_meridional_to_cartesian",
    "cosine_bell",
    "checkerboard",
    "williamson_tc2",
    "williamson_tc5",
    "williamson_tc6",
    "galewsky",
    "perturbation_base",
    "perturbed_member",
    "perturbed_ensemble",
]


def _np(x):
    return np.asarray(x, dtype=np.float64)


def solid_body_wind(grid: CubedSphereGrid, u0: float, alpha_rot: float = 0.0):
    """Solid-body rotation wind, W x r with the axis tilted by alpha_rot.

    Williamson TC1/TC2 wind: u = u0 (cos(lat) cos(a) + sin(lat) cos(lon)
    sin(a)).  Exact at every extended cell center (ghosts included).
    Returns (3, 6, M, M) in grid dtype.
    """
    xyz = _np(grid.xyz)  # (3, 6, M, M), |.| = radius
    a = grid.radius
    w = (u0 / a) * np.array([-np.sin(alpha_rot), 0.0, np.cos(alpha_rot)])
    v = np.stack([
        w[1] * xyz[2] - w[2] * xyz[1],
        w[2] * xyz[0] - w[0] * xyz[2],
        w[0] * xyz[1] - w[1] * xyz[0],
    ])
    return jnp.asarray(v, dtype=grid.sqrtg.dtype)


def zonal_meridional_to_cartesian(grid: CubedSphereGrid, u, v):
    """(u zonal, v meridional) at extended centers -> Cartesian (3,6,M,M)."""
    lon = _np(grid.lon)
    lat = _np(grid.lat)
    e_lon = np.stack([-np.sin(lon), np.cos(lon), np.zeros_like(lon)])
    e_lat = np.stack([
        -np.sin(lat) * np.cos(lon),
        -np.sin(lat) * np.sin(lon),
        np.cos(lat),
    ])
    vec = _np(u) * e_lon + _np(v) * e_lat
    return jnp.asarray(vec, dtype=grid.sqrtg.dtype)


def _great_circle(grid, lon_c, lat_c):
    lon = _np(grid.lon)
    lat = _np(grid.lat)
    c = np.sin(lat_c) * np.sin(lat) + np.cos(lat_c) * np.cos(lat) * np.cos(lon - lon_c)
    return grid.radius * np.arccos(np.clip(c, -1.0, 1.0))


def cosine_bell(
    grid: CubedSphereGrid,
    h0: float = 1000.0,
    lon_c: float = 3 * np.pi / 2,
    lat_c: float = 0.0,
    radius_frac: float = 1.0 / 3.0,
):
    """Williamson TC1 cosine bell (the deck's advection demo IC, p.13/18).

    Returns the *extended* scalar (6, M, M); slice with ``grid.interior``
    for the prognostic state.
    """
    r = _great_circle(grid, lon_c, lat_c)
    R = radius_frac * grid.radius
    h = np.where(r < R, 0.5 * h0 * (1.0 + np.cos(np.pi * r / R)), 0.0)
    return jnp.asarray(h, dtype=grid.sqrtg.dtype)


def checkerboard(
    grid: CubedSphereGrid,
    face: int = 4,
    lo: float = 1.0,
    hi: float = 1000.0,
    tiles: int = 6,
):
    """The deck's "Lima Flag" checkerboard heat source on one panel
    (p.12/17): alternating lo/hi blocks on ``face``, ``lo`` elsewhere.
    Returns extended (6, M, M)."""
    m = grid.m
    jj, ii = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    block = max(1, grid.n // tiles)
    pattern = np.where(((jj - grid.halo) // block + (ii - grid.halo) // block) % 2 == 0, hi, lo)
    field = np.full((6, m, m), lo)
    field[face] = pattern
    return jnp.asarray(field, dtype=grid.sqrtg.dtype)


def williamson_tc2(
    grid: CubedSphereGrid,
    gravity: float,
    omega: float,
    u0: float = 2 * np.pi * EARTH_RADIUS / (12 * 86400),
    gh0: float = 2.94e4,
    alpha_rot: float = 0.0,
):
    """TC2 steady geostrophic flow: returns (h_ext, v_ext).

    gh = gh0 - (a*Omega*u0 + u0^2/2) * (-cos(lon)cos(lat)sin(a) +
    sin(lat)cos(a))^2; exact steady state of the SWE.
    """
    lon = _np(grid.lon)
    lat = _np(grid.lat)
    a = grid.radius
    mu = -np.cos(lon) * np.cos(lat) * np.sin(alpha_rot) + np.sin(lat) * np.cos(alpha_rot)
    gh = gh0 - (a * omega * u0 + 0.5 * u0 * u0) * mu * mu
    h = jnp.asarray(gh / gravity, dtype=grid.sqrtg.dtype)
    v = solid_body_wind(grid, u0, alpha_rot)
    return h, v


def williamson_tc5(
    grid: CubedSphereGrid,
    gravity: float,
    omega: float,
    u0: float = 20.0,
    h0: float = 5960.0,
    mountain_h: float = 2000.0,
    lon_c: float = 3 * np.pi / 2,
    lat_c: float = np.pi / 6,
    mountain_r: float = np.pi / 9,
):
    """TC5 zonal flow over an isolated mountain: returns (h_ext, v_ext,
    b_ext) where b is the mountain surface height and h the *fluid depth*
    (so the free surface is h + b)."""
    lon = _np(grid.lon)
    lat = _np(grid.lat)
    a = grid.radius
    # Zonal balanced height for alpha=0 solid-body flow.
    gh = gravity * h0 - (a * omega * u0 + 0.5 * u0 * u0) * np.sin(lat) ** 2
    # Mountain: b = b0 (1 - r/R) with r the *angular* distance, clipped.
    dlon = np.arctan2(np.sin(lon - lon_c), np.cos(lon - lon_c))
    r = np.sqrt(np.minimum(mountain_r**2, dlon**2 + (lat - lat_c) ** 2))
    b = mountain_h * (1.0 - r / mountain_r)
    h = gh / gravity - b
    v = solid_body_wind(grid, u0, 0.0)
    dt = grid.sqrtg.dtype
    return jnp.asarray(h, dtype=dt), v, jnp.asarray(b, dtype=dt)


def williamson_tc6(
    grid: CubedSphereGrid,
    gravity: float,
    omega: float,
    omega_w: float = 7.848e-6,
    k_w: float = 7.848e-6,
    h0: float = 8000.0,
    r_w: int = 4,
):
    """TC6 Rossby-Haurwitz wave: returns (h_ext, v_ext)."""
    lon = _np(grid.lon)
    th = _np(grid.lat)
    a = grid.radius
    R = r_w
    cos = np.cos(th)
    sin = np.sin(th)

    u = a * omega_w * cos + a * k_w * cos ** (R - 1) * (
        R * sin * sin - cos * cos
    ) * np.cos(R * lon)
    v = -a * k_w * R * cos ** (R - 1) * sin * np.sin(R * lon)

    A = 0.5 * omega_w * (2 * omega + omega_w) * cos**2 + 0.25 * k_w**2 * cos ** (
        2 * R
    ) * ((R + 1) * cos**2 + (2 * R**2 - R - 2) - 2 * R**2 * cos ** (-2))
    B = (
        2 * (omega + omega_w) * k_w / ((R + 1) * (R + 2)) * cos**R
        * ((R**2 + 2 * R + 2) - (R + 1) ** 2 * cos**2)
    )
    C = 0.25 * k_w**2 * cos ** (2 * R) * ((R + 1) * cos**2 - (R + 2))
    gh = gravity * h0 + a * a * (A + B * np.cos(R * lon) + C * np.cos(2 * R * lon))

    h = jnp.asarray(gh / gravity, dtype=grid.sqrtg.dtype)
    vec = zonal_meridional_to_cartesian(grid, u, v)
    return h, vec


def perturbation_base(grid: CubedSphereGrid, h_ext):
    """The float64 inputs of :func:`perturbed_member` that do not depend
    on the seed: ``(h64, rhat64, href)`` — the base height and the unit
    position vectors ``xyz / radius`` at extended cell centers, both
    upcast from what the grid and ``h_ext`` hold (the grid dtype: f32 on
    the chip), and the perturbation scale ``mean|h|``."""
    h64 = _np(h_ext)
    rhat64 = _np(grid.xyz) / grid.radius             # (3, 6, M, M)
    return h64, rhat64, float(np.mean(np.abs(h64)))


def perturbed_member(h64, rhat64, href: float, rng, amplitude: float):
    """One perturbed height member ``(6, M, M)`` in float64: the shared
    recipe of :func:`perturbed_ensemble` and the serving tier.

    Draws a random unit-normalized combination of three ``l = 1``
    spherical modes ``ghat_j . rhat`` from ``rng`` (nine then three
    standard normals), max-normalizes it over the extended grid, ghosts
    included, and returns ``h64 + amplitude * href * mode``.  The
    inputs are :func:`perturbation_base`'s.
    """
    g = rng.standard_normal((3, 3))
    g /= np.linalg.norm(g, axis=1, keepdims=True)
    w = rng.standard_normal(3)
    mode = np.einsum("jk,k...->...", g * w[:, None], rhat64)
    mode /= max(float(np.abs(mode).max()), 1e-300)
    return h64 + amplitude * href * mode


def perturbed_ensemble(
    grid: CubedSphereGrid,
    h_ext,
    members: int,
    seed: int = 0,
    amplitude: float = 1.0e-3,
):
    """Perturbed-IC height ensemble for batched runs: ``(B, 6, M, M)``.

    Member 0 is the unperturbed ``h_ext``; members ``1..B-1`` add a
    smooth large-scale perturbation ``amplitude * mean|h| * mode`` with
    ``mode`` a random unit-normalized combination of three ``l = 1``
    spherical modes ``ghat_j . rhat`` (the gentlest fields that still
    decorrelate trajectories — the standard perturbed-IC recipe for TC5
    / Galewsky spread studies).  The grid's ``xyz`` and ``h_ext`` are
    read in the grid dtype (f32 on the chip) and upcast to float64
    (:func:`perturbation_base`); each member is then
    :func:`perturbed_member` in float64 at extended cell centers, ghosts
    included, drawing from one deterministic ``numpy`` generator in
    member order, so a given ``(seed, members)`` pair reproduces
    bit-identical ICs across runs and processes; member 1 of ``seed`` is
    the height the serving tier builds for a request of that seed.
    The wind is left unperturbed — height-only perturbations keep
    members balanced to the same order as the base state, so no member
    needs its own spin-up.
    """
    if members < 1:
        raise ValueError(f"members must be >= 1, got {members}")
    h64, rhat64, href = perturbation_base(grid, h_ext)
    rng = np.random.default_rng(seed)
    out = [h64] + [perturbed_member(h64, rhat64, href, rng, amplitude)
                   for _ in range(members - 1)]
    return jnp.asarray(np.stack(out), dtype=grid.sqrtg.dtype)


def galewsky(
    grid: CubedSphereGrid,
    gravity: float,
    omega: float,
    u_max: float = 80.0,
    h_mean: float = 10158.0,
    lat0: float = np.pi / 7,
    lat1: float = np.pi / 2 - np.pi / 7,
    perturb: bool = True,
    h_hat: float = 120.0,
    alpha_p: float = 1.0 / 3.0,
    beta_p: float = 1.0 / 15.0,
    lat2: float = np.pi / 4,
):
    """Galewsky et al. (2004) barotropic-instability jet: (h_ext, v_ext).

    The balanced height is integrated numerically (fine trapezoid in
    float64) from gh'(lat) = -a u (f + u tan(lat)/a).
    """
    a = grid.radius
    en = np.exp(-4.0 / (lat1 - lat0) ** 2)

    def u_of(phi):
        inside = (phi > lat0) & (phi < lat1)
        safe = np.where(inside, (phi - lat0) * (phi - lat1), -1.0)
        return np.where(inside, u_max / en * np.exp(1.0 / safe), 0.0)

    # Fine latitude grid for the balance integral.
    phi_f = np.linspace(-np.pi / 2, np.pi / 2, 20001)
    u_f = u_of(phi_f)
    integrand = a * u_f * (2 * omega * np.sin(phi_f) + u_f * np.tan(phi_f) / a)
    gh_f = -np.concatenate([[0.0], np.cumsum(
        0.5 * (integrand[1:] + integrand[:-1]) * np.diff(phi_f)
    )])
    # Normalize to the prescribed global-mean-ish level.
    gh_f = gh_f - gh_f.mean() + gravity * h_mean

    lat = _np(grid.lat)
    lon = _np(grid.lon)
    gh = np.interp(lat, phi_f, gh_f)
    h = gh / gravity
    if perturb:
        lonp = np.arctan2(np.sin(lon), np.cos(lon))  # wrap to (-pi, pi)
        h = h + h_hat * np.cos(lat) * np.exp(-((lonp / alpha_p) ** 2)) * np.exp(
            -(((lat2 - lat) / beta_p) ** 2)
        )

    u = u_of(lat)
    vec = zonal_meridional_to_cartesian(grid, u, np.zeros_like(u))
    return jnp.asarray(h, dtype=grid.sqrtg.dtype), vec
