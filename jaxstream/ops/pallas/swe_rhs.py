"""Fused Pallas TPU kernel for the shallow-water RHS.

The reference's numerics are memory-bound ("Traditional FV-PLR ... AI ~0.25
flops/byte", deck p.19), so the TPU-native answer is *fusion*: one Pallas
kernel per cubed-sphere face computes the complete SWE right-hand side —
contravariant face velocities, PLR-upwind fluxes, divergence, vorticity,
Bernoulli gradient, Coriolis — in VMEM, reading the (already ghost-filled)
state exactly once from HBM and writing only the tendencies.  No stencil
intermediate ever round-trips through HBM.

Geometry is not read from memory at all: the equiangular metric is rank-1
separable (see :class:`jaxstream.geometry.cubed_sphere.LazyCubedSphereGrid`),
so the kernel rebuilds every basis vector from two (1, M) gnomonic
coordinate rows plus a per-face 3x3 frame in SMEM — a few dozen VPU flops
per cell in exchange for ~100 MB/step of HBM traffic.

Numerics are identical (to f32 roundoff) to the pure-JAX path in
:mod:`jaxstream.ops.fv` — the PLR/PPM reconstructions are literally the
same code (:mod:`jaxstream.ops.reconstruct` is axis-agnostic jnp and traces
fine inside a Pallas kernel).  The pure-JAX path stays the reference
implementation and the parity-test oracle (SURVEY.md §7: Pallas kernels
"flag-switched, numerics-identical").
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...geometry.cubed_sphere import FACE_AXES, extended_coords
from ..reconstruct import plr_face_states, ppm_face_states

__all__ = ["make_swe_rhs_pallas", "rhs_core", "rhs_core_fast", "coord_rows",
           "pick_recon"]


def _frame_scalars(ref, k):
    """Read one 3-vector of a face frame from SMEM as Python-level scalars."""
    return ref[0, k, 0], ref[0, k, 1], ref[0, k, 2]


def _basis(xr, yc, c0, cx, cy, radius, need):
    """Metric quantities on the grid xr x yc (broadcast (1,mx) x (my,1)).

    ``c0``/``cx``/``cy`` are tuples of 3 scalars (the face frame).  Returns
    a dict restricted to ``need`` — everything is closed-form in
    X = tan(alpha), Y = tan(beta) (same math as LazyCubedSphereGrid._basis,
    specialized to scalar frame components so it vectorizes on the VPU
    without a leading component axis).
    """
    one = jnp.float32(1.0)
    R = jnp.float32(radius)
    x2 = xr * xr
    y2 = yc * yc
    rho2 = one + x2 + y2
    # rsqrt + reciprocal-multiply forms throughout: TPU VPU divides and
    # sqrts are multi-cycle, and this basis is recomputed per RK stage
    # (cheaper than streaming 20+ precomputed metric fields from HBM, but
    # only if the transcendental count stays minimal).
    inv_rho = jax.lax.rsqrt(rho2)
    inv_rho2 = inv_rho * inv_rho
    dxda = one + x2
    dydb = one + y2

    out = {}
    p = [c0[i] + xr * cx[i] + yc * cy[i] for i in range(3)]
    rhat = [p[i] * inv_rho for i in range(3)]
    if "rhat" in need:
        out["rhat"] = rhat
    if "sqrtg" in need:
        out["sqrtg"] = R * R * dxda * dydb * inv_rho * inv_rho2
    if "e" in need or "a" in need:
        pcx = rhat[0] * cx[0] + rhat[1] * cx[1] + rhat[2] * cx[2]
        pcy = rhat[0] * cy[0] + rhat[1] * cy[1] + rhat[2] * cy[2]
        fa = R * dxda * inv_rho
        fb = R * dydb * inv_rho
        e_a = [fa * (cx[i] - rhat[i] * pcx) for i in range(3)]
        e_b = [fb * (cy[i] - rhat[i] * pcy) for i in range(3)]
        if "e" in need:
            out["e_a"] = e_a
            out["e_b"] = e_b
        if "a" in need:
            # Closed-form 2x2 inverse metric of the equiangular map.
            R2 = R * R
            inv_rho4 = inv_rho2 * inv_rho2
            gcom = R2 * dxda * dydb * inv_rho4
            gaa = gcom * dxda
            gbb = gcom * dydb
            gab = -gcom * xr * yc
            inv_det = one / (gaa * gbb - gab * gab)
            inv_aa = gbb * inv_det
            inv_ab = -gab * inv_det
            inv_bb = gaa * inv_det
            out["a_a"] = [inv_aa * e_a[i] + inv_ab * e_b[i] for i in range(3)]
            out["a_b"] = [inv_ab * e_a[i] + inv_bb * e_b[i] for i in range(3)]
    return out


def pick_recon(scheme: str, halo: int, n: int, limiter: str):
    """Face-state reconstruction for the kernels (PLR default, PPM option)."""
    if scheme == "ppm":
        return functools.partial(ppm_face_states, h=halo, n=n)
    return functools.partial(plr_face_states, h=halo, n=n, limiter=limiter)


def coord_rows(n: int, halo: int):
    """Gnomonic coordinate rows/cols for kernel broadcast, plus face frames.

    Returns ``(x_row, xf_row, x_col, xf_col, frames)`` — the (1, M)/(M, 1)
    tan-coordinate arrays and the (6, 3, 3) face-frame table (same source
    of truth as the grid builders).
    """
    ac, af, _ = extended_coords(n, halo)
    x_row = jnp.asarray(np.tan(ac), jnp.float32)[None, :]     # (1, M)
    xf_row = jnp.asarray(np.tan(af), jnp.float32)[None, :]    # (1, M)
    x_col = jnp.asarray(np.tan(ac), jnp.float32)[:, None]     # (M, 1)
    xf_col = jnp.asarray(np.tan(af), jnp.float32)[:, None]    # (M, 1)
    frames = jnp.asarray(FACE_AXES, jnp.float32)              # (6, 3, 3)
    return x_row, xf_row, x_col, xf_col, frames


def rhs_core(frame_ref, xr, xfr, yc, yfc, hf, v, bf, *,
             n, halo, d, radius, gravity, omega, recon):
    """One face's complete SWE right-hand side, as traceable kernel math.

    ``hf``/``bf`` are (M, M) values, ``v`` a list of 3 (M, M) components
    (ghosts filled); returns ``(dh, [dv0, dv1, dv2])`` interior (n, n)
    tendencies.  Shared by the plain-RHS kernel and the fused SSPRK3 stage
    kernel (:mod:`jaxstream.ops.pallas.swe_step`).
    """
    h0, h1 = halo, halo + n
    inv2d = 1.0 / (2.0 * d)
    c0 = _frame_scalars(frame_ref, 0)
    cx = _frame_scalars(frame_ref, 1)
    cy = _frame_scalars(frame_ref, 2)
    g = jnp.float32(gravity)
    two_omega = jnp.float32(2.0 * omega)

    # ---- continuity: dh = -div(h v), PLR-upwind flux form ------------
    # x-faces i = h0..h1 on interior rows: coords (xf cols, center rows).
    bx = _basis(xfr[:, h0:h1 + 1], yc[h0:h1], c0, cx, cy, radius,
                need=("a", "sqrtg"))
    vxf = [0.5 * (v[i][h0:h1, h0 - 1:h1] + v[i][h0:h1, h0:h1 + 1])
           for i in range(3)]
    ux = (vxf[0] * bx["a_a"][0] + vxf[1] * bx["a_a"][1]
          + vxf[2] * bx["a_a"][2])                       # (n, n+1)
    qx = hf[h0:h1, :]                                    # (n, M)
    qL, qR = recon(qx, -1)
    fx = bx["sqrtg"] * (jnp.maximum(ux, 0.0) * qL
                        + jnp.minimum(ux, 0.0) * qR)     # (n, n+1)

    # y-faces.
    by = _basis(xr[:, h0:h1], yfc[h0:h1 + 1], c0, cx, cy, radius,
                need=("a", "sqrtg"))
    vyf = [0.5 * (v[i][h0 - 1:h1, h0:h1] + v[i][h0:h1 + 1, h0:h1])
           for i in range(3)]
    uy = (vyf[0] * by["a_b"][0] + vyf[1] * by["a_b"][1]
          + vyf[2] * by["a_b"][2])                       # (n+1, n)
    qy = hf[:, h0:h1]                                    # (M, n)
    qL, qR = recon(qy, -2)
    fy = by["sqrtg"] * (jnp.maximum(uy, 0.0) * qL
                        + jnp.minimum(uy, 0.0) * qR)     # (n+1, n)

    bc = _basis(xr[:, h0:h1], yc[h0:h1], c0, cx, cy, radius,
                need=("rhat", "sqrtg", "a"))
    inv_sg = 1.0 / bc["sqrtg"]
    inv_sg_d = inv_sg * jnp.float32(1.0 / d)
    dh = -((fx[:, 1:] - fx[:, :-1]) + (fy[1:, :] - fy[:-1, :])) * inv_sg_d

    # ---- momentum: vector-invariant with Cartesian velocity ----------
    # Band = interior +- 1 ring, for the centered first derivatives.
    b0, b1 = h0 - 1, h1 + 1
    bb = _basis(xr[:, b0:b1], yc[b0:b1], c0, cx, cy, radius, need=("e",))
    vb_band = [v[i][b0:b1, b0:b1] for i in range(3)]     # (n+2, n+2)
    va = (vb_band[0] * bb["e_a"][0] + vb_band[1] * bb["e_a"][1]
          + vb_band[2] * bb["e_a"][2])
    vbeta = (vb_band[0] * bb["e_b"][0] + vb_band[1] * bb["e_b"][1]
             + vb_band[2] * bb["e_b"][2])
    # zeta = (d vbeta/d alpha - d va/d beta) / sqrtg, interior cells.
    dvb_da = (vbeta[1:-1, 2:] - vbeta[1:-1, :-2]) * jnp.float32(inv2d)
    dva_db = (va[2:, 1:-1] - va[:-2, 1:-1]) * jnp.float32(inv2d)
    zeta = (dvb_da - dva_db) * inv_sg

    # Bernoulli function on the band: g (h + b) + |v|^2 / 2.
    ke = 0.5 * (vb_band[0] * vb_band[0] + vb_band[1] * vb_band[1]
                + vb_band[2] * vb_band[2])
    bern = g * (hf[b0:b1, b0:b1] + bf[b0:b1, b0:b1]) + ke
    dpa = (bern[1:-1, 2:] - bern[1:-1, :-2]) * jnp.float32(inv2d)
    dpb = (bern[2:, 1:-1] - bern[:-2, 1:-1]) * jnp.float32(inv2d)

    k = bc["rhat"]                                       # interior khat
    fcor = two_omega * k[2]
    absv = zeta + fcor

    vi = [v[i][h0:h1, h0:h1] for i in range(3)]
    # Tangentialize, then k x v, then assemble and re-project.
    vdotk = vi[0] * k[0] + vi[1] * k[1] + vi[2] * k[2]
    vt = [vi[i] - k[i] * vdotk for i in range(3)]
    kxv = [k[1] * vt[2] - k[2] * vt[1],
           k[2] * vt[0] - k[0] * vt[2],
           k[0] * vt[1] - k[1] * vt[0]]
    a_a, a_b = bc["a_a"], bc["a_b"]
    dv = [-absv * kxv[i] - (a_a[i] * dpa + a_b[i] * dpb)
          for i in range(3)]
    dvdotk = dv[0] * k[0] + dv[1] * k[1] + dv[2] * k[2]
    return dh, [dv[i] - k[i] * dvdotk for i in range(3)]


def _fast_frame(xr, yc, radius):
    """Scalar metric fields from orthonormal-frame closed forms.

    The face frames (c0, cx, cy) are orthonormal, which collapses the
    general basis algebra: ``rhat.cx = X/rho``, ``rhat.cy = Y/rho``,
    ``rhat.c0 = 1/rho``, and the inverse metric is closed-form
    (``g^aa = rho^2/(R^2 (1+X^2))``, ``g^bb = rho^2/(R^2 (1+Y^2))``,
    ``g^ab = X Y rho^2/(R^2 (1+X^2)(1+Y^2))``; derived from
    ``det g = (sqrtg)^2`` with ``(1+X^2)(1+Y^2) = rho^2 + X^2 Y^2``).
    Everything divides only on the 1-D coordinate rows/cols (negligible),
    so the per-cell cost is ~a dozen mul/adds plus one rsqrt — ~5x fewer
    VPU flops than the general :func:`_basis` path, which matters because
    the fused kernels recompute the metric every RK stage.

    ``xr``: (1, mx) row of X = tan(alpha); ``yc``: (my, 1) col of Y.
    """
    one = jnp.float32(1.0)
    R = jnp.float32(radius)
    R2 = R * R
    x2r = xr * xr
    y2c = yc * yc
    dxda_r = one + x2r                       # (1, mx) rows
    dydb_c = one + y2c                       # (my, 1) cols
    rho2 = dxda_r + y2c                      # 1 + X^2 + Y^2
    inv_rho = jax.lax.rsqrt(rho2)
    inv_rho2 = inv_rho * inv_rho
    inv_R2dxda_r = one / (R2 * dxda_r)       # 1-D divides only
    inv_dydb_c = one / dydb_c
    sg_row = R2 * dxda_r
    return {
        "x": xr, "y": yc,
        "inv_rho": inv_rho, "inv_rho2": inv_rho2,
        "fa": (R * dxda_r) * inv_rho,
        "fb": (R * dydb_c) * inv_rho,
        "inv_aa": rho2 * inv_R2dxda_r,
        "inv_bb": (rho2 * inv_R2dxda_r) * (dxda_r * inv_dydb_c),
        "inv_ab": rho2 * ((xr * inv_R2dxda_r) * (yc * inv_dydb_c)),
        "sqrtg": (sg_row * dydb_c) * (inv_rho2 * inv_rho),
        "inv_sqrtg": ((one / sg_row) * inv_dydb_c) * (rho2 * rho2 * inv_rho),
        # Flux-form (sqrtg-folded) inverse metric: the continuity flux
        # needs sqrtg * g^ij, whose closed forms are *cheaper* than either
        # factor — sqrtg g^aa = (1+Y^2)/rho, sqrtg g^bb = (1+X^2)/rho,
        # sqrtg g^ab = X Y / rho.  (Unused entries are pruned at trace
        # time, so the extra entries cost nothing where not consumed.)
        "fg_aa": dydb_c * inv_rho,
        "fg_bb": dxda_r * inv_rho,
        "fg_ab": (xr * yc) * inv_rho,
    }


def rhs_core_fast(frame_ref, xr, xfr, yc, yfc, hf, v, bf, *,
                  n, halo, d, radius, gravity, omega, recon):
    """Flop-lean twin of :func:`rhs_core` (same discretization).

    Identical stencils and upwinding; the metric algebra runs through
    :func:`_fast_frame` scalar forms (v.e_a, v.a_a etc. as scalar
    combinations of the three constant-frame dot products) instead of
    materializing 3-vector bases.  Agreement with :func:`rhs_core` is
    f32 op-reordering roundoff (tests/test_fused_step.py::test_fast_core_parity
    compares the two cores directly; the oracle-path parity tests cover it
    end to end).
    """
    h0, h1 = halo, halo + n
    inv2d = jnp.float32(1.0 / (2.0 * d))
    c0 = _frame_scalars(frame_ref, 0)
    cx = _frame_scalars(frame_ref, 1)
    cy = _frame_scalars(frame_ref, 2)
    g = jnp.float32(gravity)
    two_omega = jnp.float32(2.0 * omega)

    def dots(vl):
        """(v.c0, v.cx, v.cy) — the only 3-vector contractions needed."""
        return (
            vl[0] * c0[0] + vl[1] * c0[1] + vl[2] * c0[2],
            vl[0] * cx[0] + vl[1] * cx[1] + vl[2] * cx[2],
            vl[0] * cy[0] + vl[1] * cy[1] + vl[2] * cy[2],
        )

    def covariant(F, d0, dxx, dyy):
        """(v.e_a, v.e_b, v.P) from the frame dots."""
        vp = d0 + F["x"] * dxx + F["y"] * dyy
        u = vp * F["inv_rho2"]
        vea = F["fa"] * (dxx - F["x"] * u)
        veb = F["fb"] * (dyy - F["y"] * u)
        return vea, veb, vp

    # ---- continuity ------------------------------------------------------
    Fx = _fast_frame(xfr[:, h0:h1 + 1], yc[h0:h1], radius)
    vxf = [0.5 * (v[i][h0:h1, h0 - 1:h1] + v[i][h0:h1, h0:h1 + 1])
           for i in range(3)]
    d0, dxx, dyy = dots(vxf)
    vea, veb, _ = covariant(Fx, d0, dxx, dyy)
    ux = Fx["inv_aa"] * vea + Fx["inv_ab"] * veb       # v . a_a
    qL, qR = recon(hf[h0:h1, :], -1)
    fx = Fx["sqrtg"] * (jnp.maximum(ux, 0.0) * qL
                        + jnp.minimum(ux, 0.0) * qR)

    Fy = _fast_frame(xr[:, h0:h1], yfc[h0:h1 + 1], radius)
    vyf = [0.5 * (v[i][h0 - 1:h1, h0:h1] + v[i][h0:h1 + 1, h0:h1])
           for i in range(3)]
    d0, dxx, dyy = dots(vyf)
    vea, veb, _ = covariant(Fy, d0, dxx, dyy)
    uy = Fy["inv_ab"] * vea + Fy["inv_bb"] * veb       # v . a_b
    qL, qR = recon(hf[:, h0:h1], -2)
    fy = Fy["sqrtg"] * (jnp.maximum(uy, 0.0) * qL
                        + jnp.minimum(uy, 0.0) * qR)

    Fc = _fast_frame(xr[:, h0:h1], yc[h0:h1], radius)
    inv_sg_d = Fc["inv_sqrtg"] * jnp.float32(1.0 / d)
    dh = -((fx[:, 1:] - fx[:, :-1]) + (fy[1:, :] - fy[:-1, :])) * inv_sg_d

    # ---- momentum --------------------------------------------------------
    b0, b1 = h0 - 1, h1 + 1
    Fb = _fast_frame(xr[:, b0:b1], yc[b0:b1], radius)
    vb = [v[i][b0:b1, b0:b1] for i in range(3)]
    d0, dxx, dyy = dots(vb)
    va, vbeta, _ = covariant(Fb, d0, dxx, dyy)
    dvb_da = (vbeta[1:-1, 2:] - vbeta[1:-1, :-2]) * inv2d
    dva_db = (va[2:, 1:-1] - va[:-2, 1:-1]) * inv2d
    zeta = (dvb_da - dva_db) * Fc["inv_sqrtg"]

    ke = 0.5 * (vb[0] * vb[0] + vb[1] * vb[1] + vb[2] * vb[2])
    bern = g * (hf[b0:b1, b0:b1] + bf[b0:b1, b0:b1]) + ke
    dpa = (bern[1:-1, 2:] - bern[1:-1, :-2]) * inv2d
    dpb = (bern[2:, 1:-1] - bern[:-2, 1:-1]) * inv2d

    # grad = (a_a dpa + a_b dpb) expressed in the constant frame:
    # A cx + B cy + C c0 with scalar coefficient fields.
    ca = Fc["inv_aa"] * dpa + Fc["inv_ab"] * dpb
    cb = Fc["inv_ab"] * dpa + Fc["inv_bb"] * dpb
    uu = ca * Fc["fa"]
    ww = cb * Fc["fb"]
    tt = (uu * Fc["x"] + ww * Fc["y"]) * Fc["inv_rho2"]
    A = uu - tt * Fc["x"]
    B = ww - tt * Fc["y"]
    C = -tt
    grad = [A * cx[i] + B * cy[i] + C * c0[i] for i in range(3)]

    # rhat at centers, componentwise from the frame.
    ir = Fc["inv_rho"]
    k = [ir * (c0[i] + Fc["x"] * cx[i] + Fc["y"] * cy[i]) for i in range(3)]
    fcor = two_omega * k[2]
    absv = zeta + fcor

    vi = [v[i][h0:h1, h0:h1] for i in range(3)]
    vdotk = vi[0] * k[0] + vi[1] * k[1] + vi[2] * k[2]
    vt = [vi[i] - k[i] * vdotk for i in range(3)]
    kxv = [k[1] * vt[2] - k[2] * vt[1],
           k[2] * vt[0] - k[0] * vt[2],
           k[0] * vt[1] - k[1] * vt[0]]
    dv = [-absv * kxv[i] - grad[i] for i in range(3)]
    dvdotk = dv[0] * k[0] + dv[1] * k[1] + dv[2] * k[2]
    return dh, [dv[i] - k[i] * dvdotk for i in range(3)]


def make_swe_rhs_pallas(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
):
    """Build ``rhs(h_ext, v_ext, b_ext) -> (dh, dv)`` as one fused kernel.

    Inputs are extended ``(6, M, M)`` / ``(3, 6, M, M)`` fields with ghosts
    already filled; outputs are interior tendencies ``(6, n, n)`` /
    ``(3, 6, n, n)`` — drop-in for the stencil section of
    :meth:`jaxstream.models.shallow_water.ShallowWater.rhs`.
    """
    m = n + 2 * halo
    d = float(dalpha)
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, frames = coord_rows(n, halo)

    def kernel(frame_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, h_ref, v_ref,
               b_ref, dh_ref, dv_ref):
        hf = h_ref[0]                        # (M, M)
        v = [v_ref[0, 0], v_ref[1, 0], v_ref[2, 0]]
        bf = b_ref[0]
        dh, dv = rhs_core(
            frame_ref, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, v, bf, n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )
        dh_ref[0] = dh
        for i in range(3):
            dv_ref[i, 0] = dv[i]

    grid_spec = pl.GridSpec(
        grid=(6,),
        in_specs=[
            pl.BlockSpec((1, 3, 3), lambda f: (f, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 1, n, n), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )

    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((3, 6, n, n), jnp.float32),
        ],
        # Whole-face blocks at C384 need ~26 MB of scoped VMEM for the
        # stencil intermediates — above the compiler's 16 MB default but
        # well inside the chip's 128 MB VMEM.  (C768+ would need row-band
        # tiling instead.)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def rhs(h_ext, v_ext, b_ext) -> Tuple[jax.Array, jax.Array]:
        dh, dv = call(frames, x_row, xf_row, x_col, xf_col,
                      h_ext, v_ext, b_ext)
        return dh, dv

    return rhs
