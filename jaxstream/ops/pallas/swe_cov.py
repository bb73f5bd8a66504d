"""Fused TPU kernels for the covariant-component SWE formulation.

The covariant twin of :mod:`swe_rhs`/:mod:`swe_step`: one kernel per face
computes the complete vector-invariant RHS from the prognostic
``(h, u_a, u_b)`` — three (M, M) fields instead of the Cartesian path's
four, and the metric work collapses to the closed-form scalar fields of
:func:`jaxstream.ops.pallas.swe_rhs._fast_frame` (no 3-vector bases, dot
or cross products at all; the only frame data left is the three z-
components needed for the Coriolis parameter).

Panel-seam conservation: the two panels sharing an edge raise the index
through different covariant components/metrics, so their edge-face normal
velocities differ at truncation level (see
:func:`jaxstream.ops.fv.covariant_face_normal_velocity`).  The kernels
therefore take per-face *symmetrized edge-normal strips* — computed once
per physical edge outside the kernel (:func:`sym_edge_normals`) and
written over the boundary face values with iota-mask selects — so both
panels use bitwise-identical edge velocities and mass is conserved to
roundoff, matching the jnp oracle's ``symmetrize=True`` arithmetic
exactly.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.jax_compat import named_scope

from ...geometry.connectivity import (
    EDGE_E,
    EDGE_N,
    EDGE_S,
    EDGE_W,
    build_connectivity,
    edge_pairs,
)
from ...geometry.cubed_sphere import FACE_AXES
from .precision import StagePrecision, resolve_stage_precision
from .swe_rhs import _fast_frame, coord_rows, pick_recon

__all__ = [
    "sym_edge_normals",
    "rhs_core_cov",
    "pick_recon_precision",
    "make_cov_rhs_pallas",
    "make_cov_rhs_interior_local",
    "make_cov_rhs_band_local",
    "make_cov_strip_router",
    "make_cov_strip_router_linear",
    "make_cov_strip_router_split",
    "pack_strips_cov",
    "pack_strips_cov_split",
    "make_cov_stage_inkernel",
    "make_fused_ssprk3_cov_inkernel",
    "make_cov_stage_compact",
    "make_fused_ssprk3_cov_compact",
    "make_fused_ssprk3_cov_multistep",
    "lap_core",
    "make_cov_stage_nu4",
    "make_fused_ssprk3_cov_nu4",
    "make_cov_nu4_filter",
    "make_fused_ssprk3_cov_split_nu4",
    "make_fused_ssprk3_cov_refused_nu4",
]


def pick_recon_precision(scheme: str, halo: int, n: int, limiter: str,
                         precision: StagePrecision | None = None):
    """Reconstruction for the stage kernels under a precision policy.

    Policy off: plain :func:`pick_recon` — the bitwise historical path.
    ``compute='bf16'`` + PLR: cell differences are formed in f32, the
    limiter algebra (the slope-candidate min/max chain — most of the
    reconstruction's VPU ops, and on TPU a 2x-wide lane mix in bf16)
    runs in bfloat16, and the face state is assembled as ``f32 cell
    value +- f32(bf16 half-slope)``.  Quantization lands on the *slope*
    — the O(dx) correction term — never on the cell value, so the
    face-state error is O(2^-9) of the local gradient: truncation-class
    with no anomaly offset (a direct bf16 cast of h ~ 5e3 m would be a
    ~16 m quantum; this form is ~4e-2 m per m/cell of slope).  Measured
    budgets: tests/test_precision.py.

    PPM + a bf16 compute policy is REJECTED, not half-run: the policy's
    op split (and the roofline's ``bf16_flop_fraction``) is defined on
    the PLR limiter algebra — silently running f32 reconstruction under
    a 'bf16' label would publish wrong mixed-roof accounting.
    """
    if precision is None or precision.compute != "bf16":
        return pick_recon(scheme, halo, n, limiter)
    if scheme == "ppm":
        raise ValueError(
            "the bf16 stage policy is defined for the PLR "
            "reconstruction (its op split and mixed-roof accounting "
            "assume the limiter algebra); PPM has no bf16 form — drop "
            "the precision policy or use scheme='plr'")
    import functools

    from ...ops.reconstruct import plr_face_states

    # ONE definition of PLR (ops/reconstruct.py) — the policy only
    # selects the slope dtype, so limiter/stencil fixes propagate to
    # both paths.
    return functools.partial(plr_face_states, h=halo, n=n,
                             limiter=limiter, slope_dtype=jnp.bfloat16)

_OUT_SIGN = {EDGE_S: -1.0, EDGE_W: -1.0, EDGE_N: 1.0, EDGE_E: 1.0}

# Slot order of the vectorized routers' per-face edge tables.
_EORDER = (EDGE_S, EDGE_N, EDGE_W, EDGE_E)
_SLOT = {e: s for s, e in enumerate(_EORDER)}


def _pair_sym_tables(grid):
    """Shared static tables of the routers' edge-normal symmetrization.

    Returns ``(M0, M1, link_rows, back_rows, rev, sga, sgb, sym_src)``:
    the (1, 4, n) edge-face inverse-metric rows per slot (face-independent
    on the equiangular grid; (iab, ibb) for S/N, (iaa, iab) for W/E —
    covariant_face_normal_velocity's pairs), the 12 physical edges' row
    selections into the (24, n) local-normal table, reversal/sign
    columns, and the scatter order back to (face*4 + slot) rows.
    """
    import numpy as np

    n, halo = grid.n, grid.halo
    i0, i1 = halo, halo + n
    adj = build_connectivity()
    met = {
        EDGE_W: (grid.ginv_aa_xf[0, i0:i1, i0], grid.ginv_ab_xf[0, i0:i1, i0]),
        EDGE_E: (grid.ginv_aa_xf[0, i0:i1, i1], grid.ginv_ab_xf[0, i0:i1, i1]),
        EDGE_S: (grid.ginv_ab_yf[0, i0, i0:i1], grid.ginv_bb_yf[0, i0, i0:i1]),
        EDGE_N: (grid.ginv_ab_yf[0, i1, i0:i1], grid.ginv_bb_yf[0, i1, i0:i1]),
    }
    M0 = jnp.stack([jnp.asarray(met[e][0]) for e in _EORDER])[None]
    M1 = jnp.stack([jnp.asarray(met[e][1]) for e in _EORDER])[None]

    links = [lk for lk, _ in edge_pairs(adj)]
    backs = [bk for _, bk in edge_pairs(adj)]
    link_rows = jnp.asarray([lk.face * 4 + _SLOT[lk.edge] for lk in links])
    back_rows = jnp.asarray([bk.face * 4 + _SLOT[bk.edge] for bk in backs])
    rev = jnp.asarray([[lk.reversed_] for lk in links])
    sga = jnp.asarray([[_OUT_SIGN[lk.edge]] for lk in links], jnp.float32)
    sgb = jnp.asarray([[_OUT_SIGN[bk.edge]] for bk in backs], jnp.float32)
    sym_src = np.empty(24, np.int64)
    for i, (lk, bk) in enumerate(zip(links, backs)):
        sym_src[lk.face * 4 + _SLOT[lk.edge]] = i
        sym_src[bk.face * 4 + _SLOT[bk.edge]] = 12 + i
    return (M0, M1, link_rows, back_rows, rev, sga, sgb,
            jnp.asarray(sym_src))


def _pair_symmetrize(I_u, gadj_a, gadj_b, tables):
    """Vectorized :func:`_symmetrized_strips` algebra on (6, 4, n) rows.

    ``I_u``: (2, 6, 4, n) interior boundary-adjacent covariant rows;
    ``gadj_*``: (6, 4, n) edge-adjacent ghost rows (rotated).  Returns the
    per-face sym strips as (6, 4, n) in slot order — operand order matches
    the loop implementation exactly (bitwise, tested).
    """
    M0, M1, link_rows, back_rows, rev, sga, sgb, sym_src = tables
    ubar0 = 0.5 * (I_u[0] + gadj_a)
    ubar1 = 0.5 * (I_u[1] + gadj_b)
    L = (M0 * ubar0 + M1 * ubar1).reshape(24, -1)
    la = jnp.take(L, link_rows, axis=0)
    lb = jnp.take(L, back_rows, axis=0)
    lb = jnp.where(rev, jnp.flip(lb, -1), lb)
    avg = 0.5 * (sga * la - sgb * lb)
    na = sga * avg
    nb = sgb * (-avg)
    nb = jnp.where(rev, jnp.flip(nb, -1), nb)
    return jnp.take(jnp.concatenate([na, nb], axis=0), sym_src,
                    axis=0).reshape(6, 4, -1)


def _local_edge_normal(grid, u_ext, face: int, edge: int):
    """This panel's own normal velocity at one edge's boundary faces.

    Returns the stored +alpha (W/E) or +beta (S/N) face value as a
    canonical along-edge ``(n,)`` strip — the same arithmetic (same
    operand order) as :func:`jaxstream.ops.fv.covariant_face_normal_velocity`
    restricted to that edge, so replacing the kernel's values with the
    paired averages reproduces the oracle bitwise.
    """
    h, n = grid.halo, grid.n
    i0, i1 = h, h + n
    if edge in (EDGE_W, EDGE_E):
        fi = i0 if edge == EDGE_W else i1
        ub_a = 0.5 * (u_ext[0, face, i0:i1, fi - 1] + u_ext[0, face, i0:i1, fi])
        ub_b = 0.5 * (u_ext[1, face, i0:i1, fi - 1] + u_ext[1, face, i0:i1, fi])
        iaa = grid.ginv_aa_xf[face, i0:i1, fi]
        iab = grid.ginv_ab_xf[face, i0:i1, fi]
        return iaa * ub_a + iab * ub_b
    fi = i0 if edge == EDGE_S else i1
    ub_a = 0.5 * (u_ext[0, face, fi - 1, i0:i1] + u_ext[0, face, fi, i0:i1])
    ub_b = 0.5 * (u_ext[1, face, fi - 1, i0:i1] + u_ext[1, face, fi, i0:i1])
    iab = grid.ginv_ab_yf[face, fi, i0:i1]
    ibb = grid.ginv_bb_yf[face, fi, i0:i1]
    return iab * ub_a + ibb * ub_b


def _symmetrized_strips(local_normal):
    """Average the two panels' edge normals and distribute to both sides.

    ``local_normal(face, edge) -> (n,)`` is each panel's own stored
    +alpha/+beta edge-face value in canonical along-edge order.  Applies
    the ``_symmetrize_edge_fluxes`` outward-sign/reversal algebra once per
    physical edge, so both faces receive bitwise-identical values; the
    single implementation keeps the non-fused RHS path and the fused
    stepper's router seam-consistent by construction.  Returns
    ``(sym_sn (6, 2, n), sym_we (6, n, 2))`` — W/E strips stored with the
    pair axis last so kernels can slice lane-cheap (n, 1) columns.
    """
    sn = [[None, None] for _ in range(6)]
    we = [[None, None] for _ in range(6)]

    def put(face, edge, strip):
        if edge == EDGE_S:
            sn[face][0] = strip
        elif edge == EDGE_N:
            sn[face][1] = strip
        elif edge == EDGE_W:
            we[face][0] = strip
        else:
            we[face][1] = strip

    for link, back in edge_pairs(build_connectivity()):
        s_a = local_normal(link.face, link.edge)
        s_b = local_normal(back.face, back.edge)
        if link.reversed_:
            s_b = jnp.flip(s_b, axis=-1)
        out_a = _OUT_SIGN[link.edge] * s_a
        out_b = _OUT_SIGN[back.edge] * s_b
        avg = 0.5 * (out_a - out_b)
        new_a = _OUT_SIGN[link.edge] * avg
        new_b = _OUT_SIGN[back.edge] * (-avg)
        if link.reversed_:
            new_b = jnp.flip(new_b, axis=-1)
        put(link.face, link.edge, new_a)
        put(back.face, back.edge, new_b)

    sym_sn = jnp.stack([jnp.stack(rows) for rows in sn])        # (6, 2, n)
    sym_we = jnp.stack([jnp.stack(cols, axis=-1) for cols in we])  # (6, n, 2)
    return sym_sn, sym_we


def sym_edge_normals(grid, u_ext):
    """Symmetrized panel-edge normal velocities for the covariant kernels.

    ``u_ext``: (2, 6, M, M) covariant components with ghosts filled.
    Returns ``(sym_sn, sym_we)`` per :func:`_symmetrized_strips`, with
    each panel's local values from the grid's stored face metric
    (bitwise-equal to the jnp oracle's symmetrize path).
    """
    return _symmetrized_strips(
        lambda f, e: _local_edge_normal(grid, u_ext, f, e)
    )


def rhs_core_cov(fz, xr, xfr, yc, yfc, hf, ua, ub, bf, sym_sn, sym_we, *,
                 n, halo, d, radius, gravity, omega, recon,
                 seam_scratch=None, sym_prescaled=False,
                 seam_edges=(True, True, True, True), precision=None):
    """One face's covariant-SWE right-hand side as traceable kernel math.

    ``fz = (c0z, cxz, cyz)`` are the face frame's z-components (scalars,
    for the Coriolis parameter 2 Omega rhat_z); ``hf``/``bf`` (M, M),
    ``ua``/``ub`` (M, M) covariant components, ghosts filled.
    ``sym_sn`` (2, n) / ``sym_we`` (n, 2) are the symmetrized edge
    normals imposed on the panel-boundary faces (pass ``None`` for both
    to keep the local values — single-panel tests).  Returns
    ``(dh, dua, dub)`` interior (n, n) tendencies.

    Rectangular windows (the interior/boundary split of the overlapped
    exchange path): pass ``n=(ny, nx)`` with operand windows extended by
    ``halo`` on every side, and ``recon=(recon_y, recon_x)`` partials
    built for the matching extents.  ``seam_edges = (S, N, W, E)`` gates
    each seam imposition individually — a window whose edge is NOT a
    panel/block seam must leave that flux row/column at its local value
    (the full-face call imposes all four).  Every arithmetic operation
    on a given output cell is identical (same operand windows, same op
    order) to the square full-face call, so a tiling of rectangular
    calls reproduces the full kernel at the trace level; the compiled
    equality is ulp-level in general (execution-context fusion — see
    the interior/boundary split section comment).
    """
    ny, nx = (n, n) if isinstance(n, int) else n
    recon_y, recon_x = recon if isinstance(recon, tuple) else (recon, recon)
    eS, eN, eW, eE = seam_edges
    h0y, h1y = halo, halo + ny
    h0x, h1x = halo, halo + nx
    inv2d = jnp.float32(1.0 / (2.0 * d))
    g = jnp.float32(gravity)
    two_omega = jnp.float32(2.0 * omega)
    # Precision policy (see ops/pallas/precision.py): `lo` casts the
    # flux face-average VELOCITY operands to bf16 — the policy's "flux
    # arithmetic" half (the reconstruction half rides `recon`, built by
    # pick_recon_precision).  A bf16 value multiplied into the f32
    # metric promotes back to f32, so every accumulator (flux products,
    # divergence, gradients, RK combine) stays f32; with the policy off
    # `lo` is identity and the trace is bitwise the historical one.
    if precision is not None and precision.compute == "bf16":
        lo = lambda x: x.astype(jnp.bfloat16)
    else:
        lo = lambda x: x

    # ---- continuity ------------------------------------------------------
    # Flux-form velocities U = sqrtg u^perp directly via the folded metric
    # (fg_*: sqrtg g^ij is cheaper than either factor, see _fast_frame) —
    # the upwind flux then needs no separate sqrtg multiply.  Symmetrized
    # seam normals are imposed as sqrtg_edge * sym: both panels multiply
    # the identical sym strip by the identical edge sqrtg (the equiangular
    # sqrtg is even in the along-edge coordinate), so cross-seam flux
    # equality — hence exact mass conservation — is preserved.
    Fx = _fast_frame(xfr[:, h0x:h1x + 1], yc[h0y:h1y], radius)
    uba = 0.5 * (lo(ua[h0y:h1y, h0x - 1:h1x]) + lo(ua[h0y:h1y, h0x:h1x + 1]))
    ubb = 0.5 * (lo(ub[h0y:h1y, h0x - 1:h1x]) + lo(ub[h0y:h1y, h0x:h1x + 1]))
    ux = Fx["fg_aa"] * uba + Fx["fg_ab"] * ubb      # sqrtg u^a, (ny, nx+1)
    if sym_we is not None and (eW or eE):
        # Seam imposition: replace the two boundary flux-velocity
        # columns/rows with the symmetrized-edge values.  The in-kernel
        # edge-sqrtg evals are tiny (n, 1)-shaped op chains — expensive
        # per-op on the VPU — so the fused path pre-scales the sym rows
        # in the strip ROUTER (vectorized across faces, sym_prescaled)
        # and the kernel only merges.  Merge via VMEM scratch ref
        # slice-stores when provided; iota-select otherwise (concat
        # assembly was no cheaper, misaligned lane-dim concat and
        # value-level dynamic_update_slice are rejected by Mosaic).
        if sym_prescaled:
            uW, uE = sym_we[:, 0:1], sym_we[:, 1:2]
        else:
            sgW = (_fast_frame(xfr[:, h0x:h0x + 1], yc[h0y:h1y],
                               radius)["sqrtg"] if eW else None)
            sgE = (_fast_frame(xfr[:, h1x:h1x + 1], yc[h0y:h1y],
                               radius)["sqrtg"] if eE else None)
            uW = sgW * sym_we[:, 0:1] if eW else None
            uE = sgE * sym_we[:, 1:2] if eE else None
        if seam_scratch is not None:
            sx = seam_scratch[0]
            sx[:, :] = ux
            if eW:
                sx[:, 0:1] = uW
            if eE:
                sx[:, nx:nx + 1] = uE
            ux = sx[:, :]
        else:
            colx = jax.lax.broadcasted_iota(jnp.int32, (ny, nx + 1), 1)
            if eW:
                ux = jnp.where(colx == 0, uW, ux)
            if eE:
                ux = jnp.where(colx == nx, uE, ux)
    qL, qR = recon_x(hf[h0y:h1y, :], -1)
    fx = jnp.maximum(ux, 0.0) * qL + jnp.minimum(ux, 0.0) * qR

    Fy = _fast_frame(xr[:, h0x:h1x], yfc[h0y:h1y + 1], radius)
    vba = 0.5 * (lo(ua[h0y - 1:h1y, h0x:h1x]) + lo(ua[h0y:h1y + 1, h0x:h1x]))
    vbb = 0.5 * (lo(ub[h0y - 1:h1y, h0x:h1x]) + lo(ub[h0y:h1y + 1, h0x:h1x]))
    uy = Fy["fg_ab"] * vba + Fy["fg_bb"] * vbb      # sqrtg u^b, (ny+1, nx)
    if sym_sn is not None and (eS or eN):
        if sym_prescaled:
            uS, uN = sym_sn[0:1, :], sym_sn[1:2, :]
        else:
            sgS = (_fast_frame(xr[:, h0x:h1x], yfc[h0y:h0y + 1],
                               radius)["sqrtg"] if eS else None)
            sgN = (_fast_frame(xr[:, h0x:h1x], yfc[h1y:h1y + 1],
                               radius)["sqrtg"] if eN else None)
            uS = sgS * sym_sn[0:1, :] if eS else None
            uN = sgN * sym_sn[1:2, :] if eN else None
        if seam_scratch is not None:
            sy = seam_scratch[1]
            sy[:, :] = uy
            if eS:
                sy[0:1, :] = uS
            if eN:
                sy[ny:ny + 1, :] = uN
            uy = sy[:, :]
        else:
            rowy = jax.lax.broadcasted_iota(jnp.int32, (ny + 1, nx), 0)
            if eS:
                uy = jnp.where(rowy == 0, uS, uy)
            if eN:
                uy = jnp.where(rowy == ny, uN, uy)
    qL, qR = recon_y(hf[:, h0x:h1x], -2)
    fy = jnp.maximum(uy, 0.0) * qL + jnp.minimum(uy, 0.0) * qR

    # ---- momentum (vector-invariant, covariant components) ---------------
    # The cell-center frame Fc is the interior slice of the band frame Fb:
    # every _fast_frame output is an elementwise function of the same
    # coordinate-row values, so slicing is bitwise-identical to
    # recomputing — and saves a full (n, n) metric evaluation per stage.
    b0y, b1y = h0y - 1, h1y + 1
    b0x, b1x = h0x - 1, h1x + 1
    Fb = _fast_frame(xr[:, b0x:b1x], yc[b0y:b1y], radius)
    Fc = {k: v[-1:, 1:-1] if v.shape[-2] == 1 else
             (v[1:-1, -1:] if v.shape[-1] == 1 else v[1:-1, 1:-1])
          for k, v in Fb.items()}
    inv_sg_d = Fc["inv_sqrtg"] * jnp.float32(1.0 / d)
    dh = -((fx[:, 1:] - fx[:, :-1]) + (fy[1:, :] - fy[:-1, :])) * inv_sg_d
    uab = ua[b0y:b1y, b0x:b1x]
    ubb_ = ub[b0y:b1y, b0x:b1x]
    uca = Fb["inv_aa"] * uab + Fb["inv_ab"] * ubb_        # u^alpha, band
    ucb = Fb["inv_ab"] * uab + Fb["inv_bb"] * ubb_        # u^beta, band
    ke = 0.5 * (uca * uab + ucb * ubb_)
    bern = g * (hf[b0y:b1y, b0x:b1x] + bf[b0y:b1y, b0x:b1x]) + ke
    dba = (bern[1:-1, 2:] - bern[1:-1, :-2]) * inv2d
    dbb = (bern[2:, 1:-1] - bern[:-2, 1:-1]) * inv2d

    dub_da = (ub[h0y:h1y, h0x + 1:h1x + 1]
              - ub[h0y:h1y, h0x - 1:h1x - 1]) * inv2d
    dua_db = (ua[h0y + 1:h1y + 1, h0x:h1x]
              - ua[h0y - 1:h1y - 1, h0x:h1x]) * inv2d

    # (zeta + f) sqrtg expanded: zeta sqrtg is just the covariant curl
    # (zeta = curl / sqrtg), so only the Coriolis part needs the metric —
    # two fewer full-field multiplies and no inv_sqrtg/sqrtg pair.
    # f = 2 Omega rhat_z, rhat_z = (c0z + X cxz + Y cyz)/rho.
    rz = (fz[0] + Fc["x"] * fz[1] + Fc["y"] * fz[2]) * Fc["inv_rho"]
    absv = (dub_da - dua_db) + (two_omega * rz) * Fc["sqrtg"]

    dua = absv * ucb[1:-1, 1:-1] - dba
    dub = -absv * uca[1:-1, 1:-1] - dbb
    return dh, dua, dub


def make_cov_rhs_pallas(
    grid,
    gravity: float,
    omega: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    n_faces: int = 6,
    external_sym: bool = False,
):
    """Build ``rhs(h_ext, u_ext, b_ext) -> (dh, du)`` as one fused kernel.

    Drop-in for the stencil section of
    :meth:`jaxstream.models.shallow_water_cov.CovariantShallowWater.rhs`:
    extended inputs with ghosts filled, interior tendencies out
    (``du`` stacked (2, 6, n, n)).  The symmetrized edge normals are
    computed outside the kernel from the same ``u_ext`` (they read the
    grid's stored face metric, keeping them bitwise-equal to the oracle).

    ``n_faces=1`` + ``external_sym=True`` is the shard_map-local variant
    (one face per device): the returned function has signature
    ``rhs(fz, h_ext, u_ext, b_ext, sym_sn, sym_we)`` with the per-face
    frame z-components ``fz (1, 1, 3)`` and symmetrized edge normals
    supplied by the caller (the explicit ppermute exchange computes them).
    """
    n, halo = grid.n, grid.halo
    m = n + 2 * halo
    d = float(grid.dalpha)
    radius = float(grid.radius)
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    import numpy as np

    # (6, 1, 3): Mosaic requires the block's last two dims to equal the
    # array's, so keep a unit middle axis rather than a (6, 3) table.
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)

    def kernel(fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, h_ref, u_ref,
               b_ref, ssn_ref, swe_ref, dh_ref, du_ref):
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            h_ref[0], u_ref[0, 0], u_ref[1, 0], b_ref[0],
            ssn_ref[0], swe_ref[0], n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )
        dh_ref[0] = dh
        du_ref[0, 0] = dua
        du_ref[1, 0] = dub

    nf = n_faces
    grid_spec = pl.GridSpec(
        grid=(nf,),
        in_specs=[
            pl.BlockSpec((1, 1, 3), lambda f: (f, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, 2), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, n, n), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )

    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nf, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, nf, n, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if external_sym:
        def rhs_ext(fz, h_ext, u_ext, b_ext, sym_sn, sym_we):
            return tuple(call(fz, x_row, xf_row, x_col, xf_col,
                              h_ext, u_ext, b_ext, sym_sn, sym_we))

        return rhs_ext

    def rhs(h_ext, u_ext, b_ext) -> Tuple[jax.Array, jax.Array]:
        sym_sn, sym_we = sym_edge_normals(grid, u_ext)
        dh, du = call(frames_z, x_row, xf_row, x_col, xf_col,
                      h_ext, u_ext, b_ext, sym_sn, sym_we)
        return dh, du

    return rhs


def make_cov_rhs_pallas_local(
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
    """Covariant RHS for ONE local block with runtime coordinates.

    The sub-panel (block-mesh) twin of ``make_cov_rhs_pallas(n_faces=1,
    external_sym=True)``: here the gnomonic coordinate rows/columns are
    *runtime operands* too, because each device's block covers a
    different patch of its face.  Signature::

        rhs(fz, xr, xfr, yc, yfc, h_ext, u_ext, b_ext, sym_sn, sym_we)
            -> (dh (1, n, n), du (2, 1, n, n))

    with ``xr``/``xfr`` (1, m) rows, ``yc``/``yfc`` (m, 1) columns of
    the block's extended tan-coordinates, ``fz`` (1, 1, 3) the face
    frame z-components, and sym strips imposed at all four block edges
    (panel seams get the pair-symmetrized values; intra-panel seams the
    plain shared face normal — both sides bitwise-equal either way, so
    cross-device flux telescoping is exact).
    """
    m = n + 2 * halo
    d = float(dalpha)
    recon = pick_recon(scheme, halo, n, limiter)

    def kernel(fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, h_ref, u_ref,
               b_ref, ssn_ref, swe_ref, dh_ref, du_ref):
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            h_ref[0], u_ref[0, 0], u_ref[1, 0], b_ref[0],
            ssn_ref[0], swe_ref[0], n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )
        dh_ref[0] = dh
        du_ref[0, 0] = dua
        du_ref[1, 0] = dub

    grid_spec = pl.GridSpec(
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, 1, 3), lambda f: (f, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, 2), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, n, n), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )

    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 1, n, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def rhs(fz, xr, xfr, yc, yfc, h_ext, u_ext, b_ext, sym_sn, sym_we):
        return tuple(call(fz, xr, xfr, yc, yfc,
                          h_ext, u_ext, b_ext, sym_sn, sym_we))

    return rhs


# ---------------------------------------------------------------------------
# Interior/boundary split of the covariant RHS — the overlapped-exchange
# building blocks (parallelization.overlap_exchange).
#
# A halo of depth h is exactly the stencil radius of one RHS evaluation,
# so the tendency of any interior cell at distance >= h from the panel
# (or block) boundary reads NO ghost value: that "interior of the
# interior" — an (n-2h)^2 core out of n^2 cells, 97.9% of the face at
# C384 — is computable before any exchange completes (Putman & Lin 2007
# make the same observation for ghost-cell fills).  The sharded steppers
# therefore issue their ppermute stages FIRST, run the interior-only
# kernel below while XLA's async collectives are in flight, and finish
# with the boundary-band pass on the received strips.
#
# The band pass is four rectangular rhs_core_cov windows (S/N full-width
# rows, W/E the remaining columns: an exact disjoint tiling of the ring)
# kept as traced jnp rather than a fourth Pallas variant: the band is
# O(h*n) work — ~2% of the face at C384 — and leaving it to XLA lets the
# scheduler start it the moment the last receive lands, with no
# custom-call boundary in between.  Both passes slice the SAME operand
# windows in the SAME op order as the full-face kernel; at the default
# halo=2 the interior+band tiling reproduces the serialized path
# bitwise under one jit (tested), and the general contract is
# ulp-level — XLA may fuse the differently-shaped band subgraphs with
# different FMA/reassociation choices (measured: single-ulp band drift
# at halo=3) — the same budget the multi-step overlap parities carry.
# ---------------------------------------------------------------------------


def make_cov_rhs_interior_local(
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
    """Interior-pass covariant RHS for ONE local block, no ghosts read.

    Signature::

        rhs(fz, xr, xfr, yc, yfc, h_int, u_int, b_int)
            -> (dh (1, ni, ni), du (2, 1, ni, ni)),  ni = n - 2*halo

    ``h_int`` (1, n, n) / ``u_int`` (2, 1, n, n) are the block's plain
    interior fields (exactly the sharded state — no embed, no exchange);
    ``b_int`` the (1, n, n) interior window of the orography;
    ``xr``/``xfr`` (1, n), ``yc``/``yfc`` (n, 1) the INTERIOR coordinate
    windows (extended coords sliced ``[halo : halo+n]``).  The interior
    field plays the role of the extended array for the core window: its
    outer ``halo`` ring is the stencil halo of the ``ni x ni`` output.
    No seam strips exist this deep inside a block, so the seam machinery
    is off entirely.
    """
    ni = n - 2 * halo
    if ni <= 0:
        raise ValueError(
            f"interior split needs n > 2*halo (got n={n}, halo={halo}): "
            "with no ghost-free core the serialized exchange is the "
            "whole kernel")
    d = float(dalpha)
    recon = pick_recon(scheme, halo, ni, limiter)

    def kernel(fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, h_ref, u_ref,
               b_ref, dh_ref, du_ref):
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            h_ref[0], u_ref[0, 0], u_ref[1, 0], b_ref[0],
            None, None, n=ni, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )
        dh_ref[0] = dh
        du_ref[0, 0] = dua
        du_ref[1, 0] = dub

    grid_spec = pl.GridSpec(
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, 1, 3), lambda f: (f, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((n, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, n, n), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, ni, ni), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, 1, ni, ni), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )

    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, ni, ni), jnp.float32),
            jax.ShapeDtypeStruct((2, 1, ni, ni), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def rhs(fz, xr, xfr, yc, yfc, h_int, u_int, b_int):
        return tuple(call(fz, xr, xfr, yc, yfc, h_int, u_int, b_int))

    return rhs


def make_cov_rhs_band_local(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    scheme: str = "plr",
    limiter: str = "mc",
):
    """Boundary-band covariant RHS + stitch for ONE local block.

    Signature::

        band(fz, xr, xfr, yc, yfc, h_ext, u_ext, b_ext, sym_sn, sym_we,
             dh_core, du_core) -> (dh (1, n, n), du (2, 1, n, n))

    Operands as :func:`make_cov_rhs_pallas_local` (extended block with
    ghosts filled by the completed exchange, sym strips for all four
    edges) plus the interior pass's core tendencies, which are stitched
    into the full interior output.  Four rectangular windows tile the
    depth-``halo`` ring exactly: S/N rows over the full width (they own
    the corners), W/E the remaining ``n - 2h`` rows.  Each window's
    seam flags impose exactly the strip rows the full-face kernel
    imposes there — sym values at a window edge that is NOT the block
    edge are never touched, and every imposition site is covered by
    exactly one window.  Traced jnp by design (see the section comment).
    """
    h = halo
    ni = n - 2 * h
    if ni <= 0:
        raise ValueError(
            f"band split needs n > 2*halo (got n={n}, halo={halo})")
    m = n + 2 * h
    d = float(dalpha)
    recon_n = pick_recon(scheme, h, n, limiter)
    recon_h = pick_recon(scheme, h, h, limiter)
    recon_i = pick_recon(scheme, h, ni, limiter)
    kw = dict(halo=h, d=d, radius=radius, gravity=gravity, omega=omega)

    def band(fz, xr, xfr, yc, yfc, h_ext, u_ext, b_ext, sym_sn, sym_we,
             dh_core, du_core):
        fz3 = (fz[0, 0, 0], fz[0, 0, 1], fz[0, 0, 2])
        hf, ua, ub, bf = h_ext[0], u_ext[0, 0], u_ext[1, 0], b_ext[0]
        ssn, swe = sym_sn[0], sym_we[0]            # (2, n) / (n, 2)

        def win(r0, r1, c0, c1):
            sl = (slice(r0, r1), slice(c0, c1))
            return (xr[:, c0:c1], xfr[:, c0:c1], yc[r0:r1], yfc[r0:r1],
                    hf[sl], ua[sl], ub[sl], bf[sl])

        # S/N bands: (h, n) outputs over the full width.
        dS = rhs_core_cov(fz3, *win(0, 3 * h, 0, m), ssn, swe[0:h],
                          n=(h, n), recon=(recon_h, recon_n),
                          seam_edges=(True, False, True, True), **kw)
        dN = rhs_core_cov(fz3, *win(m - 3 * h, m, 0, m), ssn,
                          swe[n - h:n], n=(h, n),
                          recon=(recon_h, recon_n),
                          seam_edges=(False, True, True, True), **kw)
        # W/E bands: (ni, h) outputs on the remaining rows.
        dW = rhs_core_cov(fz3, *win(h, n + h, 0, 3 * h), None,
                          swe[h:n - h], n=(ni, h),
                          recon=(recon_i, recon_h),
                          seam_edges=(False, False, True, False), **kw)
        dE = rhs_core_cov(fz3, *win(h, n + h, m - 3 * h, m), None,
                          swe[h:n - h], n=(ni, h),
                          recon=(recon_i, recon_h),
                          seam_edges=(False, False, False, True), **kw)

        def stitch(i, core):
            mid = jnp.concatenate([dW[i], core, dE[i]], axis=-1)
            return jnp.concatenate([dS[i], mid, dN[i]], axis=-2)

        dh = stitch(0, dh_core[0])[None]
        du = jnp.stack([stitch(1, du_core[0, 0])[None],
                        stitch(2, du_core[1, 0])[None]])
        return dh, du

    return band


# ---------------------------------------------------------------------------
# Fused SSPRK3 with in-kernel exchange — the covariant TPU fast path.
#
# Mirrors jaxstream.ops.pallas.swe_step's strip-carry design with two
# covariant-specific twists: (1) velocity strips carry raw covariant
# components in the SOURCE panel's basis; the inter-stage router applies
# precomputed per-ghost-slot 2x2 rotations (the strip-sized form of the
# vector_halo exchange) while routing; (2) the router also produces the
# symmetrized panel-edge normal-velocity strips from the same carry, so
# each stage kernel's edge fluxes agree bitwise across seams (exact mass
# conservation without any cross-face traffic beyond the strips).
# ---------------------------------------------------------------------------


# Packed strip layout: ONE (6, 12*halo, n) tensor holds every boundary
# strip of the 3-field state — for each field fi in (h, u_a, u_b), base =
# fi*4*halo, rows [base, base+halo) = S block, [+halo, +2halo) = N block,
# [+2halo, +3halo) = W column block transposed depth-major, [+3halo,
# +4halo) = E ditto.  Rationale: (a) lane-major everywhere (an (n, 2)
# strip stores as 8-byte HBM rows — thousands of tiny DMAs per step);
# (b) ONE kernel operand instead of eight — each extra per-face block
# costs fixed DMA setup per grid step, and those fixed costs, not the
# RHS math, dominate the fused step (measured: an empty-body stage costs
# the same as the full RHS).  The routed-ghost input tensor uses the
# same 12*halo rows (placed layout) plus 4 trailing rows: the
# symmetrized edge normals for S, N and (transposed) W, E.


def _strip_base(fi: int, halo: int) -> int:
    return fi * 4 * halo


def pack_strips_cov(h_ext, u_ext, n: int, halo: int):
    """Boundary strips of extended (h, u) as one ``(6, 12*halo, n)``."""
    i0, i1 = halo, halo + n
    fields = (h_ext, u_ext[0], u_ext[1])
    rows = []
    for f in range(6):
        per_face = []
        for q in fields:
            per_face += [
                q[f, i0 : i0 + halo, i0:i1],
                q[f, i1 - halo : i1, i0:i1],
                jnp.swapaxes(q[f, i0:i1, i0 : i0 + halo], 0, 1),
                jnp.swapaxes(q[f, i0:i1, i1 - halo : i1], 0, 1),
            ]
        rows.append(jnp.concatenate(per_face, axis=0))
    return jnp.stack(rows)


def _rotation_tables(grid):
    """Per-ghost-slot covariant rotation tensors in *canonical* layout.

    For every ghost slot, ``T[i*2+j][f, e] = e_i^local(ghost cell) .
    a_j^src(source cell)`` — the same rotation as
    ``make_vector_halo_exchanger(components='covariant')`` — indexed by
    the receiving face's (face, edge) in canonical (depth, along) strip
    order with the pair's reversal already folded into the source side,
    so it multiplies the router's post-reversal canonical strips
    elementwise.  Returned packed as one float32 ``(4, 6, 4, halo, n)``
    tensor (i*2+j major): four separate well-tiled slices rather than a
    trailing ``(..., 2, 2)``, which would cost ~512x in (8, 128) tile
    padding.
    """
    import numpy as np

    from ...parallel.vector_halo import _strip_indices

    n, halo, m = grid.n, grid.halo, grid.m
    adj = build_connectivity()
    src_idx, dst_idx = _strip_indices(n, halo)
    e_b = np.stack([np.moveaxis(np.asarray(grid.e_a, np.float64), 0, -1),
                    np.moveaxis(np.asarray(grid.e_b, np.float64), 0, -1)])
    a_b = np.stack([np.moveaxis(np.asarray(grid.a_a, np.float64), 0, -1),
                    np.moveaxis(np.asarray(grid.a_b, np.float64), 0, -1)])
    ef = e_b.reshape(2, 6 * m * m, 3)
    af = a_b.reshape(2, 6 * m * m, 3)

    out = np.zeros((4, 6, 4, halo, n), np.float32)
    for f in range(6):
        for e in range(4):
            link = adj[f][e]
            src = src_idx[link.nbr_edge].reshape(halo, n)
            if link.reversed_:
                src = src[:, ::-1]
            src = src.reshape(-1) + link.nbr_face * m * m
            dst = dst_idx[e] + f * m * m
            for i in range(2):
                for j in range(2):
                    out[i * 2 + j, f, e] = np.einsum(
                        "...k,...k->...", ef[i][dst], af[j][src]
                    ).reshape(halo, n)
    return jnp.asarray(out)


def make_cov_strip_router(grid):
    """Build ``route(strips) -> ghosts`` over the packed strip layout.

    ``strips``: (6, 12*halo, n) per :func:`pack_strips_cov` — raw
    covariant components in each source panel's basis.  Returns the
    packed ghost tensor (6, 12*halo + 4, n): the same row layout holding
    the *placed* ghost blocks (u rotated into each destination panel's
    basis), followed by the four symmetrized edge-normal rows (S, N,
    then W, E transposed) — computed once per physical edge so both
    faces' flux inputs are bitwise-identical.
    """
    n, halo = grid.n, grid.halo
    i0, i1 = halo, halo + n
    h = halo
    Tc = _rotation_tables(grid)                     # (4, 6, 4, halo, n)
    adj = build_connectivity()

    # Edge-face metric rows (the equiangular metric is face-independent).
    met = {
        EDGE_W: (jnp.asarray(grid.ginv_aa_xf[0, i0:i1, i0]),
                 jnp.asarray(grid.ginv_ab_xf[0, i0:i1, i0])),
        EDGE_E: (jnp.asarray(grid.ginv_aa_xf[0, i0:i1, i1]),
                 jnp.asarray(grid.ginv_ab_xf[0, i0:i1, i1])),
        EDGE_S: (jnp.asarray(grid.ginv_ab_yf[0, i0, i0:i1]),
                 jnp.asarray(grid.ginv_bb_yf[0, i0, i0:i1])),
        EDGE_N: (jnp.asarray(grid.ginv_ab_yf[0, i1, i0:i1]),
                 jnp.asarray(grid.ginv_bb_yf[0, i1, i0:i1])),
    }
    # Within-field row offsets: S, N, W(T), E(T) blocks of `halo` rows.
    off = {EDGE_S: 0, EDGE_N: h, EDGE_W: 2 * h, EDGE_E: 3 * h}

    def raw_block(strips, fi, f, e):
        b = _strip_base(fi, h) + off[e]
        return strips[f, b : b + h, :]

    def canonical(strips, fi, f, e):
        """Face f / edge e's canonical ghost source (depth 0 nearest)."""
        link = adj[f][e]
        c = raw_block(strips, fi, link.nbr_face, link.nbr_edge)
        if link.nbr_edge in (EDGE_N, EDGE_E):
            c = jnp.flip(c, axis=-2)
        if link.reversed_:
            c = jnp.flip(c, axis=-1)
        return c

    def place(c, e):
        """Canonical ghost strip -> the slot layout the kernel stores."""
        return jnp.flip(c, axis=-2) if e in (EDGE_S, EDGE_W) else c

    def route(strips):
        ghost_rows = [[None] * 12 for _ in range(6)]
        g_adj = {}
        for f in range(6):
            for e in range(4):
                ch = place(canonical(strips, 0, f, e), e)
                cu = [canonical(strips, 1 + c_, f, e) for c_ in range(2)]
                ru = [Tc[0, f, e] * cu[0] + Tc[1, f, e] * cu[1],
                      Tc[2, f, e] * cu[0] + Tc[3, f, e] * cu[1]]
                slot = {EDGE_S: 0, EDGE_N: 1, EDGE_W: 2, EDGE_E: 3}[e]
                ghost_rows[f][slot] = ch
                ghost_rows[f][4 + slot] = place(ru[0], e)
                ghost_rows[f][8 + slot] = place(ru[1], e)
                # Edge-adjacent ghost row (placed: S/W blocks are depth-
                # flipped so the adjacent row is h-1; N/E it is 0).
                k = h - 1 if e in (EDGE_S, EDGE_W) else 0
                g_adj[(f, e)] = jnp.stack(
                    [place(ru[0], e)[k], place(ru[1], e)[k]])

        def local_normal(f, e):
            ui = jnp.stack([raw_block(strips, 1 + c_, f, e)[
                h - 1 if e in (EDGE_N, EDGE_E) else 0] for c_ in range(2)])
            ubar = 0.5 * (ui + g_adj[(f, e)])
            m0, m1 = met[e]
            return m0 * ubar[0] + m1 * ubar[1]

        sym_sn, sym_we = _symmetrized_strips(local_normal)

        out = []
        for f in range(6):
            out.append(jnp.concatenate(
                ghost_rows[f] + [sym_sn[f], jnp.swapaxes(sym_we[f], 0, 1)],
                axis=0))
        return jnp.stack(out)

    return route


def make_cov_strip_router_linear(grid):
    """Vectorized twin of :func:`make_cov_strip_router` — same output.

    The loop router emits hundreds of strip-sized XLA ops per call (per
    face/edge slices, flips, rotation multiplies, concats); at C384 that
    op-dispatch overhead is ~36 us x 3 routes/step, a quarter of the whole
    fused step.  But every router output row is a *linear* function of the
    packed strip rows, so the whole thing collapses to a handful of
    tensor-sized ops: one lane flip, one static row-gather (placement +
    orientation, both row permutations), two elementwise multiply-adds
    (the per-slot 2x2 covariant rotations), and a short vectorized
    pair-average for the symmetrized edge normals.  Arithmetic per element
    is kept in the loop router's operand order, so results are bitwise
    identical (tested) and seam conservation is preserved by construction
    (one sym value per physical edge, distributed by exact permutation).
    """
    import numpy as np

    n, halo = grid.n, grid.halo
    h = halo
    R = 12 * h
    adj = build_connectivity()
    off = {EDGE_S: 0, EDGE_N: h, EDGE_W: 2 * h, EDGE_E: 3 * h}

    # Rotation tables in *placed* layout, slot-ordered (4, 6, 4, halo, n):
    # place() depth-flips the S and W ghost blocks, and commutes with the
    # elementwise rotation, so flipping the canonical tables once here lets
    # the routed strips be multiplied in placed layout directly.
    Tc = np.asarray(_rotation_tables(grid))          # (4, 6, 4, h, n) by EDGE_*
    Tp = np.stack([Tc[:, :, e] for e in _EORDER], axis=2)
    for s, e in enumerate(_EORDER):
        if e in (EDGE_S, EDGE_W):
            Tp[:, :, s] = Tp[:, :, s, ::-1]
    Tp = jnp.asarray(Tp)

    # Row-gather index: output C row (fi, f, slot, k) <- packed strip row,
    # offset by 6*R when the pair is lane-reversed (gathers from the
    # flipped copy).  Folds place() (depth flip for S/W destinations) and
    # canonicalization (depth flip for N/E sources) into the permutation.
    idx = np.empty((3, 6, 4, h), np.int64)
    for f in range(6):
        for s, e in enumerate(_EORDER):
            link = adj[f][e]
            for k in range(h):
                kc = (h - 1 - k) if e in (EDGE_S, EDGE_W) else k
                kr = ((h - 1 - kc)
                      if link.nbr_edge in (EDGE_N, EDGE_E) else kc)
                row = link.nbr_face * R + off[link.nbr_edge] + kr
                for fi in range(3):
                    src = row + fi * 4 * h
                    idx[fi, f, s, k] = src + (6 * R if link.reversed_ else 0)
    # 48 more rows: each face/edge's own interior boundary-adjacent row of
    # (u_a, u_b) — raw canonical order, never reversed — for the edge
    # normals.  Nearest-to-edge depth is h-1 for N/E blocks, 0 for S/W.
    idx_int = np.empty((2, 6, 4), np.int64)
    for f in range(6):
        for s, e in enumerate(_EORDER):
            k = h - 1 if e in (EDGE_N, EDGE_E) else 0
            for c in range(2):
                idx_int[c, f, s] = f * R + (1 + c) * 4 * h + off[e] + k
    idx_all = jnp.asarray(np.concatenate([idx.reshape(-1),
                                          idx_int.reshape(-1)]))

    sym_tables = _pair_sym_tables(grid)

    # Adjacent ghost row of each placed (h, n) block: S/W blocks are
    # depth-flipped so the edge-adjacent row is h-1; N/E it is row 0.
    adj_k = [h - 1, 0, h - 1, 0]

    def route(strips):
        s_flat = strips.reshape(6 * R, n)
        s_all = jnp.concatenate([s_flat, jnp.flip(s_flat, -1)], axis=0)
        rows = jnp.take(s_all, idx_all, axis=0)
        C = rows[: 3 * 24 * h].reshape(3, 6, 4, h, n)
        I_u = rows[3 * 24 * h :].reshape(2, 6, 4, n)

        G_h = C[0]
        G_ua = Tp[0] * C[1] + Tp[1] * C[2]
        G_ub = Tp[2] * C[1] + Tp[3] * C[2]

        gadj_a = jnp.stack([G_ua[:, s, adj_k[s]] for s in range(4)], axis=1)
        gadj_b = jnp.stack([G_ub[:, s, adj_k[s]] for s in range(4)], axis=1)
        sym = _pair_symmetrize(I_u, gadj_a, gadj_b, sym_tables)

        return jnp.concatenate(
            [G_h.reshape(6, 4 * h, n), G_ua.reshape(6, 4 * h, n),
             G_ub.reshape(6, 4 * h, n), sym], axis=1)

    return route


def make_cov_stage_inkernel(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    dt: float,
    a: float,
    b: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    precision=None,
):
    """One fused covariant RK stage with the halo fill inside the kernel.

    ``a == 0``: ``stage(hc, uc, ghosts, b_ext)``; else
    ``stage(h0, u0, hc, uc, ghosts, b_ext)``.  ``ghosts`` is the packed
    (6, 12*halo + 4, n) tensor from :func:`make_cov_strip_router` (placed
    ghost blocks + symmetrized edge-normal rows).  Returns ``(h, u,
    strips)`` — the combined state plus its packed boundary strips
    (:func:`pack_strips_cov` layout).  Ghost corners stay stale (never
    read by the dimension-split stencils).

    ``precision``: compute half of the stage policy only (bf16
    flux/recon arithmetic); this legacy extended-carry layout keeps its
    packed strips f32 — 16-bit strip storage lives on the compact path.
    """
    import numpy as np

    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(dalpha)
    g_dt = b * dt
    precision = resolve_stage_precision(precision)
    if precision is not None and precision.strips == "bf16":
        raise ValueError(
            "the extended-carry (in-kernel exchange) stepper keeps f32 "
            "strips; 16-bit strip storage needs the compact carry "
            "(make_cov_stage_compact / make_fused_ssprk3_cov_compact)")
    recon = pick_recon_precision(scheme, halo, n, limiter, precision)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)
    with_y0 = a != 0.0
    h = halo
    R = 12 * halo

    def fill_ghosts(scratch, face_val, gi, fi):
        # Ghost blocks arrive packed and lane-major; W/E un-transpose is
        # a supported, cheap Mosaic op.
        base = _strip_base(fi, h)
        scratch[:] = face_val
        scratch[0:h, i0:i1] = gi[base : base + h]
        scratch[i1 : i1 + h, i0:i1] = gi[base + h : base + 2 * h]
        scratch[i0:i1, 0:h] = jnp.swapaxes(gi[base + 2 * h : base + 3 * h],
                                           0, 1)
        scratch[i0:i1, i1 : i1 + h] = jnp.swapaxes(
            gi[base + 3 * h : base + 4 * h], 0, 1)
        return scratch[:]

    def kernel(*refs):
        if with_y0:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             h0_ref, u0_ref, hc_ref, uc_ref, gi_ref, b_ref,
             ho_ref, uo_ref, so_ref, *scratch) = refs
        else:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             hc_ref, uc_ref, gi_ref, b_ref,
             ho_ref, uo_ref, so_ref, *scratch) = refs

        gi = gi_ref[0]
        hf = fill_ghosts(scratch[0], hc_ref[0], gi, 0)
        ua = fill_ghosts(scratch[1], uc_ref[0, 0], gi, 1)
        ub = fill_ghosts(scratch[2], uc_ref[1, 0], gi, 2)
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        ssn = gi[R : R + 2]
        swe = jnp.swapaxes(gi[R + 2 : R + 4], 0, 1)

        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, ua, ub, b_ref[0], ssn, swe,
            n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
            precision=precision,
        )

        fa = jnp.float32(a)
        fb = jnp.float32(b)
        fg = jnp.float32(g_dt)
        if with_y0:
            out_h = fa * h0_ref[0] + fb * hf
            out_u = [fa * u0_ref[i, 0] + fb * (ua if i == 0 else ub)
                     for i in range(2)]
        else:
            out_h = hf if b == 1.0 else fb * hf
            out_u = ([ua, ub] if b == 1.0
                     else [fb * ua, fb * ub])

        def emit(val, tend, out_ref, fi, lead=()):
            int_new = val[i0:i1, i0:i1] + fg * tend
            out_ref[lead + (0,)] = val
            out_ref[lead + (0, slice(i0, i1), slice(i0, i1))] = int_new
            base = _strip_base(fi, h)
            so_ref[0, base : base + h] = int_new[0:h, :]
            so_ref[0, base + h : base + 2 * h] = int_new[n - h : n, :]
            so_ref[0, base + 2 * h : base + 3 * h] = jnp.swapaxes(
                int_new[:, 0:h], 0, 1)
            so_ref[0, base + 3 * h : base + 4 * h] = jnp.swapaxes(
                int_new[:, n - h : n], 0, 1)

        emit(out_h, dh, ho_ref, 0)
        emit(out_u[0], dua, uo_ref, 1, lead=(0,))
        emit(out_u[1], dub, uo_ref, 2, lead=(1,))

    fz_spec = pl.BlockSpec((1, 1, 3), lambda f: (f, 0, 0),
                           memory_space=pltpu.SMEM)
    coord_specs = [
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
    ]
    h_blk = pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM)
    u_blk = pl.BlockSpec((2, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM)
    gi_blk = pl.BlockSpec((1, R + 4, n), lambda f: (f, 0, 0),
                          memory_space=pltpu.VMEM)
    so_blk = pl.BlockSpec((1, R, n), lambda f: (f, 0, 0),
                          memory_space=pltpu.VMEM)

    in_specs = [fz_spec] + coord_specs
    if with_y0:
        in_specs += [h_blk, u_blk]
    in_specs += [h_blk, u_blk, gi_blk, h_blk]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=in_specs,
            out_specs=[h_blk, u_blk, so_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(3)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((6, R, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage(h0, u0, hc, uc, ghosts, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              h0, u0, hc, uc, ghosts, b_ext))
    else:
        def stage(hc, uc, ghosts, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              hc, uc, ghosts, b_ext))
    return stage


def make_fused_ssprk3_cov_inkernel(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    precision=None,
):
    """``step(y, t) -> y`` over ``y = {h, u, strips}``.

    The covariant minimum-HBM-traffic step: three fused stage kernels plus
    three strip-routing shuffles (rotations + symmetrized edge normals on
    one packed strip tensor).  Initialise the carry with
    :meth:`CovariantShallowWater.extend_state(state, with_strips=True)`.
    ``precision``: compute-half policy only (see
    :func:`make_cov_stage_inkernel`).
    """
    from .swe_step import SSPRK3_COEFFS

    n, halo = grid.n, grid.halo
    route = make_cov_strip_router_linear(grid)
    mk = lambda a, b: make_cov_stage_inkernel(
        n, halo, float(grid.dalpha), float(grid.radius), gravity, omega,
        dt, a, b, scheme=scheme, limiter=limiter, interpret=interpret,
        precision=precision,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    def step(y, t):
        del t
        h0, u0 = y["h"], y["u"]
        h1, u1, s1 = stage1(h0, u0, route(y["strips"]), b_ext)
        h2, u2, s2 = stage2(h0, u0, h1, u1, route(s1), b_ext)
        h3, u3, s3 = stage3(h0, u0, h2, u2, route(s2), b_ext)
        return {"h": h3, "u": u3, "strips": s3}

    return step


# ---------------------------------------------------------------------------
# Compact fused stage: interior-only state in HBM, split-orientation strips.
#
# Two layout changes over the in-kernel stepper above:
#
# 1. Interior-only carry.  Extended (M, M) fields, M = n + 2h, are (388,
#    388) blocks at C384: the lane dimension pads to 4x128 = 512, so every
#    field DMA moves ~32% dead lanes, and the kernel writes each face
#    twice (full block + interior overwrite).  The carry holds only the
#    (n, n) interiors — perfectly (8, 128)-tiled at production sizes —
#    and the stage kernel assembles the extended field in VMEM scratch
#    from the interior block and the routed ghosts.
#
# 2. Split-orientation strips.  The single packed strip tensor stores W/E
#    strips transposed, so the kernel pays ~13 thin (h, n)<->(n, h)
#    transposes per face per stage (measured ~7 us/stage at C384 — Mosaic
#    lowers them to sublane/lane shuffle chains).  Instead the S/N strips
#    and sym rows live in a row-major tensor and the W/E strips and sym
#    cols in a column-major tensor; the kernel reads/writes both natively
#    with zero transposes, and the router (already a handful of big XLA
#    ops) absorbs the orientation change in its one static row-gather
#    plus a single whole-tensor transpose each way.  The (6, n, 6h+2)
#    column tensor DMAs with lane padding (6h+2 -> 128), ~1 MB extra per
#    stage — noise next to the transpose savings.
#
# Arithmetic is unchanged: interiors are bitwise-identical to the
# extended-carry stepper (tested).  Ghost corners in scratch are
# uninitialized garbage; the dimension-split stencils never read them
# (the only corner touches are produced-then-sliced-away bern band
# cells, see rhs_core_cov).
# ---------------------------------------------------------------------------


def pack_strips_cov_split(h_int, u_int, n: int, halo: int):
    """Boundary strips of interior fields, split by orientation.

    Returns ``(strips_sn, strips_we)``: ``strips_sn`` is ``(6, 6h, n)``
    holding, per field in (h, u_a, u_b), the raw S rows then N rows;
    ``strips_we`` is ``(6, n, 6h)`` holding, per field, the raw W columns
    then E columns.  Raw = interior values in storage order (row 0 / col 0
    nearest the S/W edge; row h-1 / col h-1 nearest the N/E edge).
    """
    h = halo
    fields = (h_int, u_int[0], u_int[1])
    sn = jnp.concatenate(
        [blk for q in fields for blk in (q[:, 0:h, :], q[:, n - h : n, :])],
        axis=1)
    we = jnp.concatenate(
        [blk for q in fields for blk in (q[:, :, 0:h], q[:, :, n - h : n])],
        axis=2)
    return sn, we


def make_cov_strip_router_split(grid, prescale_sym: bool = False,
                                precision: StagePrecision | None = None):
    """Linear router over the split-orientation strip layout.

    ``route(strips_sn, strips_we) -> (ghosts_sn, ghosts_we)`` with
    ``ghosts_sn`` ``(6, 6h+2, n)`` (placed S/N ghost blocks per field +
    the two symmetrized S/N edge-normal rows) and ``ghosts_we``
    ``(6, n, 6h+2)`` (placed W/E ghost columns + sym W/E columns).  Same
    algebra as :func:`make_cov_strip_router_linear` (bitwise-identical
    ghost/sym values); only the storage orientation differs, so the stage
    kernel never transposes.

    ``prescale_sym``: multiply the sym rows by the static edge sqrtg
    here (vectorized over faces) so the stage kernel imposes them
    directly — the in-kernel (n, 1)-shaped sqrtg evals were measured at
    several us/stage of VPU time (``rhs_core_cov`` ``sym_prescaled``).

    ``precision`` (ops/pallas/precision.py): with ``compute='bf16'``
    AND ``strips='bf16'`` the 2x2 rotation multiply-adds — the router's
    arithmetic — run in bfloat16 (tables cast once at build; against
    f32 strip operands they would promote to f32 and only round the
    coefficients, so the cast is gated on both knobs); with
    ``strips='bf16'`` inputs are taken (and ghost/sym outputs emitted)
    in bfloat16, halving the strip HBM/wire traffic.  The symmetrized edge normals are computed
    in f32 from the (widened) strip rows and rounded ONCE per physical
    edge before distribution, so both faces receive the identical
    16-bit value — cross-seam flux equality, hence exact mass
    conservation, is dtype-independent.  Policy off = the bitwise
    historical route (identity casts).
    """
    import numpy as np

    n, halo = grid.n, grid.halo
    h = halo
    adj = build_connectivity()
    F = 2 * 6 * 6 * h          # sn section + weT section row count

    def src_row(fi: int, g: int, e: int, depth: int) -> int:
        """Flat source row of face g / edge e / field fi at canonical
        ``depth`` (0 = nearest the edge), in [sn ; weT] order."""
        kr = depth if e in (EDGE_S, EDGE_W) else h - 1 - depth
        sec = 0 if e in (EDGE_S, EDGE_N) else 6 * 6 * h
        pair = 0 if e in (EDGE_S, EDGE_W) else h
        return sec + g * 6 * h + fi * 2 * h + pair + kr

    # Ghost-block gather: output (fi, f, epos, k) in placed layout.  The
    # placed depth flip applies to S and W destinations (their edge-
    # adjacent slot is the last row/col of the ghost block).
    def ghost_idx(edges):
        out = np.empty((3, 6, 2, h), np.int64)
        for fi in range(3):
            for f in range(6):
                for p, e in enumerate(edges):
                    link = adj[f][e]
                    for k in range(h):
                        dep = (h - 1 - k) if e in (EDGE_S, EDGE_W) else k
                        r = src_row(fi, link.nbr_face, link.nbr_edge, dep)
                        out[fi, f, p, k] = r + (F if link.reversed_ else 0)
        return out

    idx_sn = ghost_idx((EDGE_S, EDGE_N))
    idx_we = ghost_idx((EDGE_W, EDGE_E))
    # Interior boundary-adjacent rows of (u_a, u_b) for the edge normals.
    idx_int = np.empty((2, 6, 4), np.int64)
    for c in range(2):
        for f in range(6):
            for s, e in enumerate(_EORDER):
                idx_int[c, f, s] = src_row(1 + c, f, e, 0)
    idx_all = jnp.asarray(np.concatenate(
        [idx_sn.reshape(-1), idx_we.reshape(-1), idx_int.reshape(-1)]))
    n_sn = idx_sn.size
    n_we = idx_we.size

    # Placed rotation tables, split by orientation: (4, 6, 2, h, n).
    Tc = np.asarray(_rotation_tables(grid))
    T_sn = jnp.asarray(np.stack(
        [Tc[:, :, EDGE_S, ::-1], Tc[:, :, EDGE_N]], axis=2))
    T_we = jnp.asarray(np.stack(
        [Tc[:, :, EDGE_W, ::-1], Tc[:, :, EDGE_E]], axis=2))
    pol = precision
    sdt = jnp.float32 if pol is None else pol.strips_dtype
    if (pol is not None and pol.compute == "bf16"
            and pol.strips == "bf16"):
        # bf16 rotation algebra: tables cast once at build, products and
        # adds ride the 2x-wide bf16 lanes.  Gated on 16-bit strips as
        # well as compute: against f32 strip operands the multiplies
        # would promote to f32 anyway (no lane packing), so bf16 tables
        # would round the rotation coefficients for zero benefit.
        T_sn = T_sn.astype(jnp.bfloat16)
        T_we = T_we.astype(jnp.bfloat16)

    sym_tables = _pair_sym_tables(grid)
    adj_k = [h - 1, 0]          # placed edge-adjacent row: S/W flip, N/E not

    sym_scale = None
    if prescale_sym:
        # Static edge sqrtg rows in [S, N, W, E] order — identical for
        # all faces (the equiangular metric is face-independent), same
        # closed forms the kernel would otherwise evaluate per stage.
        x_row, xf_row, x_col, xf_col, _ = coord_rows(n, h)
        h0, h1 = h, h + n
        r = float(grid.radius)
        sgS = _fast_frame(x_row[:, h0:h1], xf_col[h0:h0 + 1], r)["sqrtg"]
        sgN = _fast_frame(x_row[:, h0:h1], xf_col[h1:h1 + 1], r)["sqrtg"]
        sgW = _fast_frame(xf_row[:, h0:h0 + 1], x_col[h0:h1], r)["sqrtg"]
        sgE = _fast_frame(xf_row[:, h1:h1 + 1], x_col[h0:h1], r)["sqrtg"]
        sym_scale = jnp.stack([sgS.reshape(n), sgN.reshape(n),
                               sgW.reshape(n), sgE.reshape(n)])[None]

    def route(strips_sn, strips_we):
        # The input casts absorb an f32 initial carry under a 16-bit
        # strips policy (and are no-ops thereafter — the stage kernels
        # emit strips in sdt); every cast below is identity with the
        # policy off, keeping that path bitwise the historical route.
        s_src = jnp.concatenate(
            [strips_sn.astype(sdt).reshape(6 * 6 * h, n),
             jnp.transpose(strips_we.astype(sdt),
                           (0, 2, 1)).reshape(6 * 6 * h, n)],
            axis=0)
        s_all = jnp.concatenate([s_src, jnp.flip(s_src, -1)], axis=0)
        rows = jnp.take(s_all, idx_all, axis=0)
        C_sn = rows[:n_sn].reshape(3, 6, 2, h, n)
        C_we = rows[n_sn : n_sn + n_we].reshape(3, 6, 2, h, n)
        # Sym inputs widen to f32: the pair-symmetrization algebra is
        # the conservation-critical path and stays full precision.
        I_u = rows[n_sn + n_we :].reshape(2, 6, 4, n).astype(jnp.float32)

        G_sn = [C_sn[0],
                T_sn[0] * C_sn[1] + T_sn[1] * C_sn[2],
                T_sn[2] * C_sn[1] + T_sn[3] * C_sn[2]]
        G_we = [C_we[0],
                T_we[0] * C_we[1] + T_we[1] * C_we[2],
                T_we[2] * C_we[1] + T_we[3] * C_we[2]]

        gadj_a = jnp.stack(
            [G_sn[1][:, 0, adj_k[0]], G_sn[1][:, 1, adj_k[1]],
             G_we[1][:, 0, adj_k[0]], G_we[1][:, 1, adj_k[1]]],
            axis=1).astype(jnp.float32)
        gadj_b = jnp.stack(
            [G_sn[2][:, 0, adj_k[0]], G_sn[2][:, 1, adj_k[1]],
             G_we[2][:, 0, adj_k[0]], G_we[2][:, 1, adj_k[1]]],
            axis=1).astype(jnp.float32)
        sym = _pair_symmetrize(I_u, gadj_a, gadj_b, sym_tables)
        if sym_scale is not None:
            sym = sym * sym_scale
        # Rounded ONCE per physical edge, then distributed — both faces
        # get the identical sdt value, so seam conservation is exact at
        # any strips dtype.
        sym = sym.astype(sdt)

        gsn = jnp.concatenate(
            [jnp.concatenate([g.reshape(6, 2 * h, n).astype(sdt)
                              for g in G_sn], axis=1),
             sym[:, 0:2]], axis=1)
        gwe_rows = jnp.concatenate(
            [jnp.concatenate([g.reshape(6, 2 * h, n).astype(sdt)
                              for g in G_we], axis=1),
             sym[:, 2:4]], axis=1)
        return gsn, jnp.transpose(gwe_rows, (0, 2, 1))

    return route


def _cov_blockspecs(n, halo, groups: int = 6):
    """The shared BlockSpec set of the compact-carry stage kernels.

    ``groups``: total kernel-grid extent.  The default 6 is the plain
    one-face-per-grid-step layout; the batched ensemble steppers fold
    the member axis into the face axis (``groups = 6 * B``, member-major
    ``(B, 6) -> B*6``) so ONE kernel launch sweeps every member's faces
    — the per-call dispatch/DMA-setup glue is paid once per ensemble
    step instead of once per member.  Static per-face operands (frame
    z-components, orography) stay 6-deep in HBM and index ``f % 6``;
    per-member state indexes ``f`` directly.
    """
    m = n + 2 * halo
    h = halo
    face = (lambda f: (f, 0, 0)) if groups == 6 else \
        (lambda f: (f % 6, 0, 0))
    fz_spec = pl.BlockSpec((1, 1, 3), face,
                           memory_space=pltpu.SMEM)
    coord_specs = [
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
    ]
    hi_blk = pl.BlockSpec((1, n, n), lambda f: (f, 0, 0),
                          memory_space=pltpu.VMEM)
    ui_blk = pl.BlockSpec((2, 1, n, n), lambda f: (0, f, 0, 0),
                          memory_space=pltpu.VMEM)
    be_blk = pl.BlockSpec((1, m, m), face,
                          memory_space=pltpu.VMEM)
    gsn_blk = pl.BlockSpec((1, 6 * h + 2, n), lambda f: (f, 0, 0),
                           memory_space=pltpu.VMEM)
    gwe_blk = pl.BlockSpec((1, n, 6 * h + 2), lambda f: (f, 0, 0),
                           memory_space=pltpu.VMEM)
    ssn_blk = pl.BlockSpec((1, 6 * h, n), lambda f: (f, 0, 0),
                           memory_space=pltpu.VMEM)
    swe_blk = pl.BlockSpec((1, n, 6 * h), lambda f: (f, 0, 0),
                           memory_space=pltpu.VMEM)
    return fz_spec, coord_specs, hi_blk, ui_blk, be_blk, gsn_blk, gwe_blk, \
        ssn_blk, swe_blk


def _make_fill(n, halo, i0, i1, corners: bool = False,
               interior: bool = True, base=(0, 0),
               precision: StagePrecision | None = None):
    """Shared in-kernel ghost fill / strip emit over the split layout.

    ``interior=False`` skips the interior store (the manual-DMA stage
    kernels land the interior in the scratch straight from HBM; only
    the ghost bands need the VPU).  ``base=(by, bx)`` shifts the whole
    extended window inside a larger scratch — the manual-DMA layout
    puts the interior at a (8, 128)-tile-aligned offset because Mosaic
    only accepts tile-aligned DMA destination windows, which parks the
    extended window's top-left at ``(8 - halo, 128 - halo)``.

    ``precision``: under a 16-bit strips policy the routed ghost blocks
    arrive in bfloat16 and are widened to f32 on the scratch store (the
    extended frame the stencils read is always f32), and the emitted
    boundary strips are narrowed to the strips dtype on the way out —
    the two casts that bound the 16-bit region to strip storage."""
    h = halo
    by, bx = base
    m = n + 2 * h
    if precision is not None and precision.strips == "bf16":
        gc = lambda x: x.astype(jnp.float32)
        sc = lambda x: x.astype(jnp.bfloat16)
    else:
        gc = sc = lambda x: x

    def fill_ghosts(scratch, int_val, gsn, gwe, fi):
        if interior:
            scratch[by + i0 : by + i1, bx + i0 : bx + i1] = int_val
        scratch[by : by + h, bx + i0 : bx + i1] = \
            gc(gsn[fi * 2 * h : fi * 2 * h + h])
        scratch[by + i1 : by + i1 + h, bx + i0 : bx + i1] = \
            gc(gsn[fi * 2 * h + h : (fi + 1) * 2 * h])
        scratch[by + i0 : by + i1, bx : bx + h] = \
            gc(gwe[:, fi * 2 * h : fi * 2 * h + h])
        scratch[by + i0 : by + i1, bx + i1 : bx + i1 + h] = \
            gc(gwe[:, fi * 2 * h + h : (fi + 1) * 2 * h])
        if corners:
            # The Laplacian's cross-derivative faces read the h x h ghost
            # corners (unlike the dimension-split advective stencils).
            # Same edge-ghost averaging as parallel.halo._fill_corners —
            # purely face-local, no extra communication.
            half = jnp.float32(0.5)
            scratch[by : by + h, bx : bx + h] = half * (
                scratch[by : by + h, bx + i0 : bx + i0 + 1]
                + scratch[by + i0 : by + i0 + 1, bx : bx + h])
            scratch[by : by + h, bx + i1 : bx + i1 + h] = half * (
                scratch[by : by + h, bx + i1 - 1 : bx + i1]
                + scratch[by + i0 : by + i0 + 1, bx + i1 : bx + i1 + h])
            scratch[by + i1 : by + i1 + h, bx : bx + h] = half * (
                scratch[by + i1 : by + i1 + h, bx + i0 : bx + i0 + 1]
                + scratch[by + i1 - 1 : by + i1, bx : bx + h])
            scratch[by + i1 : by + i1 + h, bx + i1 : bx + i1 + h] = half * (
                scratch[by + i1 : by + i1 + h, bx + i1 - 1 : bx + i1]
                + scratch[by + i1 - 1 : by + i1, bx + i1 : bx + i1 + h])
        if (by, bx) == (0, 0):
            return scratch[:]
        # Manual-DMA path: hand back the REF, not a loaded value — the
        # caller wraps it in an _OffsetView and every consumer loads just
        # its own shifted window.  (A full load would materialize the
        # padding lanes; a ref *window* at the misaligned base is
        # rejected by Mosaic; per-site shifted loads are fine.)
        return scratch

    def emit_strips(ssn_ref, swe_ref, int_new, fi):
        ssn_ref[0, fi * 2 * h : fi * 2 * h + h] = sc(int_new[0:h, :])
        ssn_ref[0, fi * 2 * h + h : (fi + 1) * 2 * h] = \
            sc(int_new[n - h : n, :])
        swe_ref[0, :, fi * 2 * h : fi * 2 * h + h] = sc(int_new[:, 0:h])
        swe_ref[0, :, fi * 2 * h + h : (fi + 1) * 2 * h] = \
            sc(int_new[:, n - h : n])

    return fill_ghosts, emit_strips


class _OffsetView:
    """Presents a padded 2-D value as if it were the (m, m) extended
    frame at a static offset ``(by, bx)`` inside it.

    Only the slice forms :func:`rhs_core_cov` uses are supported:
    non-negative starts/stops or ``None``, no steps.  Mosaic rejects
    ref windows whose offsets are not tile-aligned, so the manual-DMA
    stage kernels never materialize the (m, m) window — every consumer
    slices through this view and gets a plain shifted value slice.
    """

    __slots__ = ("v", "by", "bx", "m")

    def __init__(self, v, by, bx, m):
        self.v, self.by, self.bx, self.m = v, by, bx, m

    def __getitem__(self, idx):
        r, c = idx

        def sh(s, off, size):
            if isinstance(s, slice):
                if s.step not in (None, 1):
                    raise ValueError("_OffsetView: slice steps are "
                                     "unsupported")
                start = (s.start or 0)
                stop = size if s.stop is None else s.stop
                if start < 0 or stop < 0:
                    raise ValueError("_OffsetView: negative slice bounds")
                return slice(start + off, stop + off)
            if s < 0:
                raise ValueError("_OffsetView: negative integer indices")
            return s + off

        return self.v[sh(r, self.by, self.m), sh(c, self.bx, self.m)]


def make_cov_stage_compact(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    dt: float,
    a: float,
    b: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    carry_dtype=jnp.float32,
    h_offset: float = 0.0,
    h_scale: float = 1.0,
    u_scale: float = 1.0,
    seam: bool = True,
    sym_prescaled: bool = False,
    manual_dma: bool | None = None,
    groups: int = 6,
    precision: StagePrecision | None = None,
):
    """One fused covariant RK stage over interior-only state.

    ``a == 0``: ``stage(hc, uc, gsn, gwe, b_ext)``; else
    ``stage(h0, u0, hc, uc, gsn, gwe, b_ext)``.  Prognostic fields are
    interior ``(6, n, n)`` / ``(2, 6, n, n)``; ``b_ext`` stays extended
    (static, needs its one-deep ring for the Bernoulli band); ``gsn`` /
    ``gwe`` per :func:`make_cov_strip_router_split`.  Returns
    ``(h, u, strips_sn, strips_we)``.  No transposes anywhere in the
    kernel: every strip read/write is in its storage orientation.

    ``carry_dtype``: HBM storage dtype of the prognostic carry — one
    dtype for both fields or ``(h_dtype, u_dtype)``.  Compute is always
    f32 in-VMEM; strips stay f32.  16-bit storage halves that field's
    carry DMA — see DESIGN.md for the measured speed/accuracy ladder.
    ``h_offset`` stores h as an anomaly about a static offset (the
    stored value is ``h - h_offset``), shrinking 16-bit quantization by
    the ratio ``|h| / |h - h_offset|`` — the RK combine is affine with
    coefficients summing to 1, so anomalies combine exactly.
    ``u_scale`` stores u divided by a static scale (use ~grid.radius to
    bring covariant components to O(wind speed)) so ``float16`` storage
    neither overflows nor wastes exponent range; fp16's 10-bit mantissa
    then makes u quantization ~8x finer than bf16.  ``seam=False``
    ablates the symmetrized-seam imposition (measurement only: breaks
    cross-panel conservation).

    ``groups``: kernel-grid extent (see :func:`_cov_blockspecs`) — 6 for
    the single-state stepper, ``6 * B`` for the batched ensemble carry
    with the member axis folded into the face axis.  The kernel body is
    identical per grid step either way, so the ``B = 1`` batched stage
    is bitwise-equal to the plain one.

    ``manual_dma`` (measurement knob, default OFF — measured a dead
    end on v5e): the h/u carry arrives as ANY-space refs and each
    face's interior is DMA'd from HBM *directly into the extended
    scratch's interior window* (``True``: double-buffered one face
    ahead; ``"single"``: one static buffer, issue-and-wait).  The goal
    was deleting the in-kernel VPU interior copy (measured 18 us/step
    at C384: block fetch writes VMEM once, the placement copy reads +
    writes it again).  Measured at C384 (bitwise-identical outputs):
    block 303-310 us/step, manual double-buffered 314.5, manual single
    370.8.  The interior-window DMA destination is a strided row
    window of the padded halo frame and runs at ~70 GB/s effective
    (per-row descriptor overhead), so un-overlapped it stalls ~26
    us/stage, and even fully overlapped it loses ~10 us/step of
    HBM/VMEM bandwidth to the extra traffic — Pallas's compact tiled
    block bursts + VPU placement copy are the better structure on this
    chip.  Kept (parity-tested) because the DMA/VPU balance shifts per
    TPU generation.  Requires a plain f32 carry.
    """
    import numpy as np

    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(dalpha)
    g_dt = b * dt
    precision = resolve_stage_precision(precision)
    sdt = jnp.float32 if precision is None else precision.strips_dtype
    # Widen sym rows to f32 at extraction under a 16-bit strips policy
    # (the seam imposition stores into f32 seam scratch / iota-selects
    # against the f32 flux tensor); identity with the policy off.
    wide = ((lambda x: x.astype(jnp.float32))
            if sdt != jnp.float32 else (lambda x: x))
    recon = pick_recon_precision(scheme, halo, n, limiter, precision)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)
    with_y0 = a != 0.0
    h = halo
    cdt_h, cdt_u = ((jnp.dtype(carry_dtype[0]), jnp.dtype(carry_dtype[1]))
                    if isinstance(carry_dtype, (tuple, list))
                    else (jnp.dtype(carry_dtype),) * 2)
    h_offset = float(h_offset)
    with_off = h_offset != 0.0
    if with_off and ((with_y0 and abs(a + b - 1.0) > 1e-9)
                     or (not with_y0 and b != 1.0)):
        raise ValueError("h_offset needs stage coefficients summing to 1 "
                         "(anomaly combine is only exact then); got "
                         f"a={a}, b={b}")

    u_scale = float(u_scale)
    h_scale = float(h_scale)
    with_scale = u_scale != 1.0
    with_hscale = h_scale != 1.0

    plain_f32 = (cdt_h == jnp.float32 and cdt_u == jnp.float32
                 and not with_off and not with_scale and not with_hscale)
    if groups < 6 or groups % 6:
        raise ValueError(
            f"groups must be a positive multiple of 6 (6 * ensemble "
            f"members), got {groups}")
    if manual_dma is None:
        manual_dma = False
    elif manual_dma and not plain_f32:
        raise ValueError("manual_dma needs a plain f32 carry (the DMA "
                         "engine cannot widen or rescale)")
    if manual_dma and precision is not None:
        raise ValueError("manual_dma needs the plain f32 precision "
                         "policy (its scratch DMA layout is f32-only); "
                         "drop precision or manual_dma")
    if manual_dma and groups != 6:
        raise ValueError("manual_dma is wired for the single-state "
                         "stepper only (its fetch-ahead hardcodes the "
                         "6-face grid); use the block pipeline for "
                         "ensemble carries")
    if manual_dma and n % 128 != 0:
        raise ValueError(
            f"manual_dma needs n % 128 == 0 (got n={n}): the ANY-space "
            "carry's per-face slices must span whole 128-lane tiles")

    def f32h(x):
        # jnp scalars must be born inside the kernel trace (a captured
        # module-level constant is rejected by pallas_call).
        x = x if cdt_h == jnp.float32 else x.astype(jnp.float32)
        if with_hscale:
            x = x * jnp.float32(h_scale)
        return x + jnp.float32(h_offset) if with_off else x

    def f32u(x):
        x = x if cdt_u == jnp.float32 else x.astype(jnp.float32)
        return x * jnp.float32(u_scale) if with_scale else x

    def store(x, cdt):
        """Round-to-nearest for integer storage (truncation toward zero
        would bias every increment); plain cast for float storage.

        Rounding via the magic-constant trick ``(x + 1.5*2^23) - 1.5*2^23``
        (exact round-to-nearest-even for |x| < 2^22, which the int16
        encodings guarantee by construction): two VPU adds, measured
        ~2x cheaper than ``lax.round``'s lowering.
        """
        if cdt == jnp.float32:
            return x
        if jnp.issubdtype(cdt, jnp.integer):
            c = jnp.float32(1.5 * 2.0**23)
            return ((x + c) - c).astype(cdt)
        return x.astype(cdt)
    # Manual-DMA scratch layout: interior window at (8, 128) — the
    # smallest (sublane, lane)-tile-aligned offset that leaves room for
    # the ghost bands above/left of it.
    _OY, _OX = 8, 128
    fill_ghosts, emit_strips = _make_fill(
        n, halo, i0, i1, interior=not manual_dma,
        base=(_OY - halo, _OX - halo) if manual_dma else (0, 0),
        precision=precision)

    def kernel(*refs):
        if with_y0:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             h0_ref, u0_ref, hc_ref, uc_ref, gsn_ref, gwe_ref, b_ref,
             ho_ref, uo_ref, ssn_ref, swe_ref, *scratch) = refs
        else:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             hc_ref, uc_ref, gsn_ref, gwe_ref, b_ref,
             ho_ref, uo_ref, ssn_ref, swe_ref, *scratch) = refs

        gsn = gsn_ref[0]
        gwe = gwe_ref[0]
        if manual_dma:
            # The carry is ANY-space: DMA each face's interior from HBM
            # straight into the extended scratch's interior window,
            # double-buffered one face ahead (the hand-rolled version of
            # the block pipeline's fetch-ahead, minus the VPU placement
            # copy).  Buffer parity alternates per face; face f-1 is
            # fully consumed before face f starts (the TPU grid is
            # sequential), so re-targeting its buffer is race-free.
            sh2, sa2, sb2 = scratch[0], scratch[1], scratch[2]
            sems = scratch[-1]
            f = pl.program_id(0)
            dsy, dsx = pl.ds(_OY, n), pl.ds(_OX, n)

            def copies(face, buf):
                return (
                    pltpu.make_async_copy(
                        hc_ref.at[face], sh2.at[buf, dsy, dsx],
                        sems.at[buf, 0]),
                    pltpu.make_async_copy(
                        uc_ref.at[0, face], sa2.at[buf, dsy, dsx],
                        sems.at[buf, 1]),
                    pltpu.make_async_copy(
                        uc_ref.at[1, face], sb2.at[buf, dsy, dsx],
                        sems.at[buf, 2]),
                )

            if manual_dma == "single":
                for c in copies(f, 0):
                    c.start()
                buf = 0
            else:
                @pl.when(f == 0)
                def _():
                    for c in copies(0, 0):
                        c.start()

                @pl.when(f + 1 < 6)
                def _():
                    for c in copies(f + 1, (f + 1) % 2):
                        c.start()

                buf = f % 2
            for c in copies(f, buf):
                c.wait()
            ov = lambda v: _OffsetView(v, _OY - halo, _OX - halo, m)
            hf = ov(fill_ghosts(sh2.at[buf], None, gsn, gwe, 0))
            ua = ov(fill_ghosts(sa2.at[buf], None, gsn, gwe, 1))
            ub = ov(fill_ghosts(sb2.at[buf], None, gsn, gwe, 2))
            hc_int = hf[i0:i1, i0:i1]
            ua_int = ua[i0:i1, i0:i1]
            ub_int = ub[i0:i1, i0:i1]
        else:
            hf = fill_ghosts(scratch[0], f32h(hc_ref[0]), gsn, gwe, 0)
            ua = fill_ghosts(scratch[1], f32u(uc_ref[0, 0]), gsn, gwe, 1)
            ub = fill_ghosts(scratch[2], f32u(uc_ref[1, 0]), gsn, gwe, 2)
            hc_int = hc_ref[0]
            ua_int = uc_ref[0, 0]
            ub_int = uc_ref[1, 0]
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        ssn = wide(gsn[6 * h : 6 * h + 2]) if seam else None
        swe = wide(gwe[:, 6 * h : 6 * h + 2]) if seam else None

        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, ua, ub, b_ref[0], ssn, swe,
            n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
            seam_scratch=(scratch[3], scratch[4]) if seam else None,
            sym_prescaled=sym_prescaled, precision=precision,
        )

        fa = jnp.float32(a)
        fb = jnp.float32(b)
        fg = jnp.float32(g_dt)

        def emit(int_old, y0, tend, out_ref, fi, lead=(), is_h=False):
            # The combine runs in STORED space (h: the anomaly, u: the
            # scaled-down value): exact because the stage coefficients
            # sum to 1 and scaling is linear — the tendency constant
            # absorbs 1/u_scale at trace time.  Only the emitted strips
            # need the absolute value back.
            cdt = cdt_h if is_h else cdt_u
            up = ((lambda x: x) if cdt == jnp.float32
                  else (lambda x: x.astype(jnp.float32)))
            scale = h_scale if is_h else u_scale
            fgf = fg if scale == 1.0 else jnp.float32(g_dt / scale)
            if with_y0:
                int_new = (fa * up(y0) + fb * up(int_old)) + fgf * tend
            elif b == 1.0:
                int_new = up(int_old) + fgf * tend
            else:
                int_new = fb * up(int_old) + fgf * tend
            out_ref[lead + (0,)] = store(int_new, cdt)
            sval = int_new
            if scale != 1.0:
                sval = sval * jnp.float32(scale)
            if is_h and with_off:
                sval = sval + jnp.float32(h_offset)
            emit_strips(ssn_ref, swe_ref, sval, fi)

        if with_y0:
            emit(hc_int, h0_ref[0], dh, ho_ref, 0, is_h=True)
            emit(ua_int, u0_ref[0, 0], dua, uo_ref, 1, lead=(0,))
            emit(ub_int, u0_ref[1, 0], dub, uo_ref, 2, lead=(1,))
        else:
            emit(hc_int, None, dh, ho_ref, 0, is_h=True)
            emit(ua_int, None, dua, uo_ref, 1, lead=(0,))
            emit(ub_int, None, dub, uo_ref, 2, lead=(1,))

    (fz_spec, coord_specs, hi_blk, ui_blk, be_blk, gsn_blk, gwe_blk,
     ssn_blk, swe_blk) = _cov_blockspecs(n, halo, groups)

    in_specs = [fz_spec] + coord_specs
    if with_y0:
        in_specs += [hi_blk, ui_blk]
    if manual_dma:
        any_spec = pl.BlockSpec(memory_space=pltpu.ANY)
        in_specs += [any_spec, any_spec, gsn_blk, gwe_blk, be_blk]
    else:
        in_specs += [hi_blk, ui_blk, gsn_blk, gwe_blk, be_blk]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(groups,),
            in_specs=in_specs,
            out_specs=[hi_blk, ui_blk, ssn_blk, swe_blk],
            scratch_shapes=(
                # Logical shape rounded up to whole (8, 128) tiles:
                # slicing the buffer dim needs tile-aligned trailing
                # SHAPES, not just offsets.
                ([pltpu.VMEM((2, -(-(_OY + n + halo) // 8) * 8,
                              -(-(_OX + n + halo) // 128) * 128),
                             jnp.float32) for _ in range(3)]
                 if manual_dma else
                 [pltpu.VMEM((m, m), jnp.float32) for _ in range(3)])
                + [pltpu.VMEM((n, n + 1), jnp.float32),
                   pltpu.VMEM((n + 1, n), jnp.float32)]
                + ([pltpu.SemaphoreType.DMA((2, 3))]
                   if manual_dma else [])),
        ),
        out_shape=[
            jax.ShapeDtypeStruct((groups, n, n), cdt_h),
            jax.ShapeDtypeStruct((2, groups, n, n), cdt_u),
            jax.ShapeDtypeStruct((groups, 6 * h, n), sdt),
            jax.ShapeDtypeStruct((groups, n, 6 * h), sdt),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage(h0, u0, hc, uc, gsn, gwe, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              h0, u0, hc, uc, gsn, gwe, b_ext))
    else:
        def stage(hc, uc, gsn, gwe, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              hc, uc, gsn, gwe, b_ext))
    return stage


def make_fused_ssprk3_cov_compact(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    carry_dtype=jnp.float32,
    h_offset: float = 0.0,
    h_scale: float = 1.0,
    u_scale: float = 1.0,
    seam: bool = True,
    ensemble: int = 0,
    precision=None,
):
    """``step(y, t) -> y`` over ``y = {h, u, strips_sn, strips_we}``.

    The production stepper: three compact stage kernels (interior-only
    fields, orientation-native strips) plus three linear strip routes.
    Initialise the carry with :meth:`CovariantShallowWater.compact_state`
    (encode ``h``/``u`` per ``carry_dtype``/``h_offset`` — see
    :meth:`CovariantShallowWater.encode_carry`).

    ``precision`` (ops/pallas/precision.py): the per-stage dtype policy
    — bf16 flux/reconstruction/router arithmetic with f32 accumulators
    and metric terms, optionally bf16 strip storage.  Orthogonal to
    ``carry_dtype`` (in-stage arithmetic vs between-step storage); the
    two stack.  ``None`` is bitwise the historical f32 path.  A 16-bit
    strips policy accepts an f32 initial strip carry (the first route
    narrows it).

    ``ensemble = B > 0``: the carry gains a leading member axis —
    ``{h: (B, 6, n, n), u: (2, B, 6, n, n), strips_sn: (B, 6, 6h, n),
    strips_we: (B, 6, n, 6h)}`` — and each stage runs as ONE kernel
    launch over a ``6 * B`` grid (the member axis folded into the face
    axis, :func:`_cov_blockspecs`), with the strip router vmapped over
    members (its gathers/rotations batch into single whole-ensemble XLA
    ops).  Per-member arithmetic is the plain stepper's, op for op, so
    the ``B = 1`` batched step is bitwise-identical to the unbatched one
    (tested); what changes is dispatch and DMA-setup amortization —
    small per-member grids stop paying the fixed per-call glue that
    dominates below ~C128.  Initialise with
    :meth:`CovariantShallowWater.ensemble_compact_state`.
    """
    from .swe_step import SSPRK3_COEFFS

    B = int(ensemble)
    precision = resolve_stage_precision(precision)
    route = make_cov_strip_router_split(grid, prescale_sym=seam,
                                        precision=precision)
    if B:
        # Member-mapped router: the static row-gather and 2x2 rotation
        # multiply-adds batch into single whole-ensemble XLA ops.
        route = jax.vmap(route)
    mk = lambda a, b: make_cov_stage_compact(
        grid.n, grid.halo, float(grid.dalpha), float(grid.radius), gravity,
        omega, dt, a, b, scheme=scheme, limiter=limiter, interpret=interpret,
        carry_dtype=carry_dtype, h_offset=h_offset, h_scale=h_scale,
        u_scale=u_scale, seam=seam, sym_prescaled=seam,
        groups=6 * max(B, 1), precision=precision,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    if not B:
        def step(y, t):
            del t
            h0, u0 = y["h"], y["u"]
            with named_scope("rk_stage1"):
                gsn, gwe = route(y["strips_sn"], y["strips_we"])
                h1, u1, sn1, we1 = stage1(h0, u0, gsn, gwe, b_ext)
            with named_scope("rk_stage2"):
                gsn, gwe = route(sn1, we1)
                h2, u2, sn2, we2 = stage2(h0, u0, h1, u1, gsn, gwe, b_ext)
            with named_scope("rk_stage3"):
                gsn, gwe = route(sn2, we2)
                h3, u3, sn3, we3 = stage3(h0, u0, h2, u2, gsn, gwe, b_ext)
            return {"h": h3, "u": u3, "strips_sn": sn3, "strips_we": we3}

        return step

    # Batched ensemble step: fold (B, 6) -> B*6 around the stage kernels
    # (free reshapes — leading axes are contiguous), unfold for the
    # vmapped router.  ONE pallas_call per stage sweeps all members.
    def fold(x, lead=0):
        s = x.shape
        return x.reshape(s[:lead] + (B * 6,) + s[lead + 2:])

    def unfold(x, lead=0):
        s = x.shape
        return x.reshape(s[:lead] + (B, 6) + s[lead + 1:])

    def step(y, t):
        del t
        h0, u0 = fold(y["h"]), fold(y["u"], 1)
        with named_scope("rk_stage1"):
            gsn, gwe = route(y["strips_sn"], y["strips_we"])
            h1, u1, sn1, we1 = stage1(h0, u0, fold(gsn), fold(gwe), b_ext)
        with named_scope("rk_stage2"):
            gsn, gwe = route(unfold(sn1), unfold(we1))
            h2, u2, sn2, we2 = stage2(h0, u0, h1, u1, fold(gsn),
                                      fold(gwe), b_ext)
        with named_scope("rk_stage3"):
            gsn, gwe = route(unfold(sn2), unfold(we2))
            h3, u3, sn3, we3 = stage3(h0, u0, h2, u2, fold(gsn),
                                      fold(gwe), b_ext)
        return {"h": unfold(h3), "u": unfold(u3, 1),
                "strips_sn": unfold(sn3), "strips_we": unfold(we3)}

    step.ensemble = B
    return step


def make_fused_ssprk3_cov_multistep(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    temporal_block: int,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    carry_dtype=jnp.float32,
    h_offset: float = 0.0,
    h_scale: float = 1.0,
    u_scale: float = 1.0,
    seam: bool = True,
    ensemble: int = 0,
    precision=None,
):
    """``block(y, t) -> y`` running ``temporal_block`` fused SSPRK3 steps.

    The temporal-blocking form of :func:`make_fused_ssprk3_cov_compact`
    (``parallelization.temporal_block``): one traced block = k steps
    back-to-back, sharing ONE set of stage kernels and one router.  On a
    single device every strip route is face-local and exact, so the k
    steps are *bitwise-identical* to k separate compact steps — the k=1
    path stays the reference by construction; what changes is dispatch
    granularity (one call per k steps) and that the whole k-step chain
    of strip/state intermediates is one XLA liveness region (nothing is
    re-packed at step boundaries — the carry never round-trips through
    the caller).  The exchange-count story (deep halos, redundant band
    compute) lives in the sharded tiers
    (:func:`jaxstream.parallel.shard_cov.make_sharded_cov_stepper` with
    ``temporal_block > 1``) where strip routes are collectives.
    """
    if temporal_block < 1:
        raise ValueError(
            f"temporal_block must be >= 1, got {temporal_block}")
    step1 = make_fused_ssprk3_cov_compact(
        grid, gravity, omega, dt, b_ext, scheme=scheme, limiter=limiter,
        interpret=interpret, carry_dtype=carry_dtype, h_offset=h_offset,
        h_scale=h_scale, u_scale=u_scale, seam=seam, ensemble=ensemble,
        precision=precision,
    )
    if temporal_block == 1:
        return step1
    from ...stepping import blocked

    # stepping.blocked threads t with sequential dt adds — the compact
    # step ignores t today, but the shared helper keeps the sub-step
    # times right if it ever reads them (and keeps one k-loop, not
    # three copies across the temporal_block call sites).
    block = blocked(step1, temporal_block, dt)
    block.steps_per_call = temporal_block
    if ensemble:
        block.ensemble = int(ensemble)
    return block


# ---------------------------------------------------------------------------
# Fused hyperdiffusion (del^4) stepper: two kernels + two routes per stage.
#
# The Galewsky jet — the flagship validation case — needs a del^4 filter
# (nu4 > 0), which the single-kernel stages above cannot provide: del^4
# is two chained Laplacians with a ghost refill between them (the
# second Laplacian reads the FIRST one's halo, which lives on the
# neighbor panel).  Rather than widening halos (the 2-ring band near
# cube corners would need corner ghosts, which the cubed sphere does
# not have), each RK stage runs the existing strip machinery twice:
#
#   route(state strips) -> kernel A: fill state ghosts, advective RHS,
#       partial stage combine y_adv = (a y0 + b yc) + b dt L_adv, and
#       l1 = lap(h), lap(u_a), lap(u_b); emits l1 boundary strips
#   route(l1 strips)    -> kernel B: fill l1 ghosts (the same rotation
#       tables apply — lap of covariant components IS a covariant pair),
#       l2 = lap(l1), y_new = y_adv - b dt nu4 l2; emits state strips
#
# This reproduces the classic path's fill(lap(fill(lap)))) structure
# (jaxstream/models/shallow_water_cov.py rhs, nu4 branch) with closed-
# form in-kernel metrics; agreement is op-reordering roundoff (tested).
# ---------------------------------------------------------------------------


def lap_core(xr, xfr, yc, yfc, psi, *, n, halo, d, radius, ring=0):
    """Laplace-Beltrami of one ghost-filled (M, M) face.

    The kernel-math twin of :func:`jaxstream.ops.fv.laplacian` (same
    conservative flux form and stencils, cross-shaped and corner-free),
    with face metrics from the sqrtg-folded closed forms.

    ``ring``: how many ghost rings to INCLUDE in the output — 0 gives
    the interior ``(n, n)``; ``ring=g`` gives ``(n+2g, n+2g)``,
    evaluating the operator on the innermost ``g`` ghost rings too
    (their stencils read ghosts to depth ``g+1``, so ``g <= halo - 1``;
    the cross-derivative faces additionally read the corner-filled
    ghost corners).  The split-nu4 filter uses ``ring=1`` so the
    second Laplacian can consume the first one's ring without a
    mid-filter exchange — the ring values are face-local evaluations
    at the neighbor's physical points, consistent to the stencil's
    own O(d^2) (the same class of seam approximation as the ghost
    resampling itself).
    """
    if not 0 <= ring <= halo - 1:
        raise ValueError(f"lap_core: ring={ring} needs 0 <= ring <= "
                         f"halo-1 (halo={halo}; the ring stencil reads "
                         "ghosts to depth ring+1)")
    h0, h1 = halo - ring, halo + n + ring
    invd = jnp.float32(1.0 / d)
    inv2d = jnp.float32(0.5 / d)

    pr = psi[h0:h1, :]
    dpa = (pr[:, h0:h1 + 1] - pr[:, h0 - 1:h1]) * invd
    dpb_c = (psi[h0 + 1:h1 + 1, :] - psi[h0 - 1:h1 - 1, :]) * inv2d
    dpb_f = 0.5 * (dpb_c[:, h0 - 1:h1] + dpb_c[:, h0:h1 + 1])
    Fx = _fast_frame(xfr[:, h0:h1 + 1], yc[h0:h1], radius)
    fx = Fx["fg_aa"] * dpa + Fx["fg_ab"] * dpb_f

    pc = psi[:, h0:h1]
    dpb = (pc[h0:h1 + 1, :] - pc[h0 - 1:h1, :]) * invd
    dpa_c = (psi[:, h0 + 1:h1 + 1] - psi[:, h0 - 1:h1 - 1]) * inv2d
    dpa_f = 0.5 * (dpa_c[h0 - 1:h1, :] + dpa_c[h0:h1 + 1, :])
    Fy = _fast_frame(xr[:, h0:h1], yfc[h0:h1 + 1], radius)
    fy = Fy["fg_bb"] * dpb + Fy["fg_ab"] * dpa_f

    Fc = _fast_frame(xr[:, h0:h1], yc[h0:h1], radius)
    return ((fx[:, 1:] - fx[:, :-1]) + (fy[1:, :] - fy[:-1, :])) * (
        Fc["inv_sqrtg"] * invd)


def make_cov_stage_nu4(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    a: float,
    b: float,
    nu4: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
):
    """One covariant RK stage with del^4 filtering, as a kernel pair.

    Returns ``(stage_a, stage_b)``:
      * ``stage_a(y0h, y0u, hc, uc, gsn, gwe, b_ext) -> (h_adv, u_adv,
        l1h, l1u, sn_l1, we_l1)`` (``y0*`` omitted when ``a == 0``),
      * ``stage_b(h_adv, u_adv, l1h, l1u, gsn, gwe) -> (h, u, sn, we)``.
    """
    import numpy as np

    n, halo = grid.n, grid.halo
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(grid.dalpha)
    radius = float(grid.radius)
    g_dt = b * dt
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)
    with_y0 = a != 0.0
    h = halo
    fill_ghosts, emit_strips = _make_fill(n, halo, i0, i1, corners=True)
    (fz_spec, coord_specs, hi_blk, ui_blk, be_blk, gsn_blk, gwe_blk,
     ssn_blk, swe_blk) = _cov_blockspecs(n, halo)

    lap = lambda xr, xfr, ycol, yfcol, psi: lap_core(
        xr, xfr, ycol, yfcol, psi, n=n, halo=halo, d=d, radius=radius)

    def kernel_a(*refs):
        if with_y0:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             h0_ref, u0_ref, hc_ref, uc_ref, gsn_ref, gwe_ref, b_ref,
             ha_ref, ua_ref, l1h_ref, l1u_ref, ssn_ref, swe_ref,
             *scratch) = refs
        else:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             hc_ref, uc_ref, gsn_ref, gwe_ref, b_ref,
             ha_ref, ua_ref, l1h_ref, l1u_ref, ssn_ref, swe_ref,
             *scratch) = refs

        gsn = gsn_ref[0]
        gwe = gwe_ref[0]
        hf = fill_ghosts(scratch[0], hc_ref[0], gsn, gwe, 0)
        ua = fill_ghosts(scratch[1], uc_ref[0, 0], gsn, gwe, 1)
        ub = fill_ghosts(scratch[2], uc_ref[1, 0], gsn, gwe, 2)
        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        ssn = gsn[6 * h : 6 * h + 2]
        swe = gwe[:, 6 * h : 6 * h + 2]

        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, ua, ub, b_ref[0], ssn, swe,
            n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )

        fa = jnp.float32(a)
        fb = jnp.float32(b)
        fg = jnp.float32(g_dt)

        def combine(int_old, y0, tend):
            if with_y0:
                return (fa * y0 + fb * int_old) + fg * tend
            if b == 1.0:
                return int_old + fg * tend
            return fb * int_old + fg * tend

        if with_y0:
            ha_ref[0] = combine(hc_ref[0], h0_ref[0], dh)
            ua_ref[0, 0] = combine(uc_ref[0, 0], u0_ref[0, 0], dua)
            ua_ref[1, 0] = combine(uc_ref[1, 0], u0_ref[1, 0], dub)
        else:
            ha_ref[0] = combine(hc_ref[0], None, dh)
            ua_ref[0, 0] = combine(uc_ref[0, 0], None, dua)
            ua_ref[1, 0] = combine(uc_ref[1, 0], None, dub)

        for fi, (psi, ref, lead) in enumerate(
                ((hf, l1h_ref, ()), (ua, l1u_ref, (0,)), (ub, l1u_ref, (1,)))):
            l1 = lap(xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:], psi)
            ref[lead + (0,)] = l1
            emit_strips(ssn_ref, swe_ref, l1, fi)

    def kernel_b(*refs):
        (xr_ref, xfr_ref, yc_ref, yfc_ref,
         ha_ref, ua_ref, l1h_ref, l1u_ref, gsn_ref, gwe_ref,
         ho_ref, uo_ref, ssn_ref, swe_ref, *scratch) = refs

        gsn = gsn_ref[0]
        gwe = gwe_ref[0]
        damp = jnp.float32(g_dt * nu4)
        for fi, (int_ref, lead, adv_ref, out_ref) in enumerate(
                ((l1h_ref, (), ha_ref, ho_ref),
                 (l1u_ref, (0,), ua_ref, uo_ref),
                 (l1u_ref, (1,), ua_ref, uo_ref))):
            l1f = fill_ghosts(scratch[fi], int_ref[lead + (0,)], gsn, gwe, fi)
            l2 = lap(xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:], l1f)
            int_new = adv_ref[lead + (0,)] - damp * l2
            out_ref[lead + (0,)] = int_new
            emit_strips(ssn_ref, swe_ref, int_new, fi)

    in_a = [fz_spec] + coord_specs
    if with_y0:
        in_a += [hi_blk, ui_blk]
    in_a += [hi_blk, ui_blk, gsn_blk, gwe_blk, be_blk]
    call_a = pl.pallas_call(
        kernel_a,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=in_a,
            out_specs=[hi_blk, ui_blk, hi_blk, ui_blk, ssn_blk, swe_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(3)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 6 * h, n), jnp.float32),
            jax.ShapeDtypeStruct((6, n, 6 * h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    call_b = pl.pallas_call(
        kernel_b,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=coord_specs + [hi_blk, ui_blk, hi_blk, ui_blk,
                                    gsn_blk, gwe_blk],
            out_specs=[hi_blk, ui_blk, ssn_blk, swe_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(3)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 6 * h, n), jnp.float32),
            jax.ShapeDtypeStruct((6, n, 6 * h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage_a(h0, u0, hc, uc, gsn, gwe, b_ext):
            return tuple(call_a(frames_z, x_row, xf_row, x_col, xf_col,
                                h0, u0, hc, uc, gsn, gwe, b_ext))
    else:
        def stage_a(hc, uc, gsn, gwe, b_ext):
            return tuple(call_a(frames_z, x_row, xf_row, x_col, xf_col,
                                hc, uc, gsn, gwe, b_ext))

    def stage_b(h_adv, u_adv, l1h, l1u, gsn, gwe):
        return tuple(call_b(x_row, xf_row, x_col, xf_col,
                            h_adv, u_adv, l1h, l1u, gsn, gwe))

    return stage_a, stage_b


def _nu4_filtered_value(xr, xfr, yc, yfc, psi, iv, *, n, halo, d,
                        radius, damp):
    """``q - damp * lap(lap q)`` for one face — the ONE definition of
    the del^4 filter arithmetic (ring-1 first Laplacian on the
    halo-deep extended frame ``psi``, halo-1 second Laplacian on l1's
    ``(n+2)^2`` window whose ``[1:n+1]`` maps to the interior),
    shared by the split filter kernel (:func:`make_cov_nu4_filter`)
    and the re-fused stage-1 kernel
    (:func:`make_cov_stage_refused_nu4`) so a stencil/window fix
    propagates to both placements.  ``iv`` is the face's unfiltered
    interior values."""
    m = n + 2 * halo
    h = halo
    l1 = lap_core(xr, xfr, yc, yfc, psi, n=n, halo=halo, d=d,
                  radius=radius, ring=1)                # (n+2, n+2)
    l2 = lap_core(xr[:, h - 1:m - h + 1], xfr[:, h - 1:m - h + 2],
                  yc[h - 1:m - h + 1, :], yfc[h - 1:m - h + 2, :],
                  l1, n=n, halo=1, d=d, radius=radius)
    return iv - damp * l2


def make_cov_nu4_filter(
    grid,
    nu4: float,
    dt_eff: float,
    interpret: bool = False,
    precision=None,
):
    """Once-per-step del^4 filter as ONE kernel (round 5).

    ``filter(h, u, gsn, gwe) -> (h', u', sn, we)`` applying
    ``q -= dt_eff nu4 lap(lap q)`` to the three prognostics.  The
    in-stage pair (:func:`make_cov_stage_nu4`) refills the first
    Laplacian's ghosts from the neighbor panel between the two
    Laplacians; here the first Laplacian is instead evaluated on the
    extended ring (``lap_core(ring=1)``, legal at halo >= 2 with the
    in-kernel corner fill) so the second one needs no exchange.  The
    ring values are face-local evaluations at the neighbor's physical
    points — an O(d^2) seam approximation on a damp-scaled (~1e-3
    relative) term; the Galewsky day-6 physics gate (vorticity band,
    quiescent hemisphere, mass) is the acceptance test
    (bench_galewsky), plus interpret-mode split-vs-stage parity in
    tests/test_cov_swe.py::test_cov_split_nu4_matches_stage.

    Splitting the filter out of the RK stages (standard dycore
    practice: hyperdiffusion applied once per step, first-order in
    time like any split filter) removes 12 of the in-stage path's 18
    Laplacian evaluations and 3 of its 6 routes — measured budget in
    DESIGN.md "Galewsky/nu4 step budget".
    """
    n, halo = grid.n, grid.halo
    if halo < 2:
        raise ValueError(f"split nu4 filter needs halo >= 2 (ring-1 "
                         f"first Laplacian), got halo={halo}")
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(grid.dalpha)
    radius = float(grid.radius)
    h = halo
    precision = resolve_stage_precision(precision)
    sdt = jnp.float32 if precision is None else precision.strips_dtype
    # The filter arithmetic itself is always f32 (a damp-scaled 4th-
    # order operator is exactly where low-precision differencing bites);
    # the policy only narrows the strip storage at the boundary.
    fill_ghosts, emit_strips = _make_fill(n, halo, i0, i1, corners=True,
                                          precision=precision)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    (fz_spec, coord_specs, hi_blk, ui_blk, be_blk, gsn_blk, gwe_blk,
     ssn_blk, swe_blk) = _cov_blockspecs(n, halo)

    def kernel(*refs):
        (xr_ref, xfr_ref, yc_ref, yfc_ref,
         hc_ref, uc_ref, gsn_ref, gwe_ref,
         ho_ref, uo_ref, ssn_ref, swe_ref, *scratch) = refs

        gsn = gsn_ref[0]
        gwe = gwe_ref[0]
        damp = jnp.float32(dt_eff * nu4)
        for fi, (int_ref, lead, out_ref) in enumerate(
                ((hc_ref, (), ho_ref),
                 (uc_ref, (0,), uo_ref),
                 (uc_ref, (1,), uo_ref))):
            psi = fill_ghosts(scratch[fi], int_ref[lead + (0,)],
                              gsn, gwe, fi)
            int_new = _nu4_filtered_value(
                xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:], psi,
                int_ref[lead + (0,)], n=n, halo=halo, d=d,
                radius=radius, damp=damp)
            out_ref[lead + (0,)] = int_new
            emit_strips(ssn_ref, swe_ref, int_new, fi)

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=coord_specs + [hi_blk, ui_blk, gsn_blk, gwe_blk],
            out_specs=[hi_blk, ui_blk, ssn_blk, swe_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(3)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 6 * h, n), sdt),
            jax.ShapeDtypeStruct((6, n, 6 * h), sdt),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def filt(hc, uc, gsn, gwe):
        return tuple(call(x_row, xf_row, x_col, xf_col,
                          hc, uc, gsn, gwe))

    return filt


def make_fused_ssprk3_cov_split_nu4(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    nu4: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    interval: int = 1,
    precision=None,
):
    """``step(y, t) -> y``: three PLAIN compact RK stages + one del^4
    filter kernel per step (4 kernels + 4 routes, vs the in-stage
    pair's 6 + 6 with twice the Laplacian count).

    The split form is first-order in time in the filter term — the
    standard operator-split treatment of hyperdiffusion in dynamical
    cores — so trajectories differ from the in-stage path at the
    damp-scale; the Galewsky day-6 physics gate is the equivalence
    standard (see :func:`make_cov_nu4_filter`).  Carry/router identical
    to :func:`make_fused_ssprk3_cov_compact`; the stage kernels ARE
    that stepper's (un-prescaled router shared with the filter).

    ``interval``: apply the filter every ``interval``-th step with an
    ``interval x`` coefficient (filter-cycling, the same split-filter
    logic one level up).  The explicit del^4 stability bound is miles
    away (nu4 dt interval / dx^4 ~ 0.03 at C384/interval=2), so the
    arbiter is the physics gate, not stability.  Step counting rides an
    integer ``"filter_k"`` counter in the carry — seed it with
    ``jnp.int32(0)`` alongside :meth:`compact_state`'s fields.  (It
    must NOT be reconstructed as ``round(t/dt)``: ``t`` is accumulated
    in f32 one ``+ dt`` at a time, and for a dt whose multiples are not
    exactly representable the accumulated rounding makes ``round(t/dt)``
    skip or repeat an index — double- or un-applied filter steps.)
    """
    from .swe_step import SSPRK3_COEFFS

    precision = resolve_stage_precision(precision)
    route = make_cov_strip_router_split(grid, precision=precision)
    mk = lambda a, b: make_cov_stage_compact(
        grid.n, grid.halo, float(grid.dalpha), float(grid.radius),
        gravity, omega, dt, a, b, scheme=scheme, limiter=limiter,
        interpret=interpret, seam=True, sym_prescaled=False,
        precision=precision,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)
    filt = make_cov_nu4_filter(grid, nu4, dt * interval,
                               interpret=interpret, precision=precision)

    def step(y, t):
        del t
        h0, u0 = y["h"], y["u"]
        with named_scope("rk_stage1"):
            gsn, gwe = route(y["strips_sn"], y["strips_we"])
            h1, u1, sn1, we1 = stage1(h0, u0, gsn, gwe, b_ext)
        with named_scope("rk_stage2"):
            gsn, gwe = route(sn1, we1)
            h2, u2, sn2, we2 = stage2(h0, u0, h1, u1, gsn, gwe, b_ext)
        with named_scope("rk_stage3"):
            gsn, gwe = route(sn2, we2)
            h3, u3, sn3, we3 = stage3(h0, u0, h2, u2, gsn, gwe, b_ext)
        if interval == 1:
            with named_scope("nu4_filter"):
                gsn, gwe = route(sn3, we3)
                hf, uf, snf, wef = filt(h3, u3, gsn, gwe)
            return {"h": hf, "u": uf, "strips_sn": snf, "strips_we": wef}

        if "filter_k" not in y:
            raise ValueError(
                "the interval > 1 filter-cycling carry needs an integer "
                "'filter_k' step counter; seed it as "
                "dict(model.compact_state(state), filter_k=jnp.int32(0))")
        k = y["filter_k"]

        def do_filter(args):
            h3, u3, sn3, we3 = args
            gsn, gwe = route(sn3, we3)
            return filt(h3, u3, gsn, gwe)

        hf, uf, snf, wef = jax.lax.cond(
            k % interval == interval - 1,
            do_filter, lambda args: args, (h3, u3, sn3, we3))
        return {"h": hf, "u": uf, "strips_sn": snf, "strips_we": wef,
                "filter_k": (k + 1) % interval}

    return step


def make_fused_ssprk3_cov_nu4(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    nu4: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
):
    """``step(y, t) -> y`` with del^4 filtering, over the compact carry.

    Six kernels + six routes per step (two per RK stage); same carry and
    router as :func:`make_fused_ssprk3_cov_compact`.
    """
    from .swe_step import SSPRK3_COEFFS

    route = make_cov_strip_router_split(grid)
    mk = lambda a, b: make_cov_stage_nu4(
        grid, gravity, omega, dt, a, b, nu4,
        scheme=scheme, limiter=limiter, interpret=interpret,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    s1a, s1b = mk(a1, b1)
    s2a, s2b = mk(a2, b2)
    s3a, s3b = mk(a3, b3)

    def half_stage(sa, sb, args):
        ha, uadv, l1h, l1u, sn1, we1 = sa(*args)
        gsn, gwe = route(sn1, we1)
        return sb(ha, uadv, l1h, l1u, gsn, gwe)

    def step(y, t):
        del t
        h0, u0 = y["h"], y["u"]
        gsn, gwe = route(y["strips_sn"], y["strips_we"])
        h1, u1, sn, we = half_stage(s1a, s1b, (h0, u0, gsn, gwe, b_ext))
        gsn, gwe = route(sn, we)
        h2, u2, sn, we = half_stage(
            s2a, s2b, (h0, u0, h1, u1, gsn, gwe, b_ext))
        gsn, gwe = route(sn, we)
        h3, u3, sn, we = half_stage(
            s3a, s3b, (h0, u0, h2, u2, gsn, gwe, b_ext))
        return {"h": h3, "u": u3, "strips_sn": sn, "strips_we": we}

    return step


# ---------------------------------------------------------------------------
# Re-fused del^4 (round 10): the filter folded INTO the stage-1 kernel.
#
# The split filter (round 5) pays one extra kernel launch + one extra
# strip route per step — 4 + 4 against the plain stepper's 3 + 3 — and
# on the blocked tiers that fourth route is exactly the exchange the
# temporal block (PR 2) exists to amortize away.  The re-fusion
# observes that the split step's last op (filter y using route(y's
# strips)) and the NEXT step's first op (stage 1 using the same
# route(y's strips)) consume the identical routed ghosts: commuting the
# filter to the head of the step makes them one kernel.  Per step:
#
#   split:    route S1 route S2 route S3 route FILT     (4 kernels, 4 routes)
#   re-fused: route [FILT+S1] route S2 route S3         (3 kernels, 3 routes)
#
# Operator sequence: split is (F R)^k y0, re-fused is (R F)^k y0 — the
# identical infinite product shifted by half a split step, so the two
# trajectories differ by one filter application at the endpoints (an
# O(damp) ~ 1e-3-relative perturbation on the filter term, the same
# class as the split form's own first-order splitting).  Seam detail:
# the in-kernel filter can only produce the FILTERED interior (filtered
# ghosts would need depth-6 strips), so the advective stencils near the
# boundary read filtered interior + unfiltered ghost values — an
# O(damp) seam inconsistency on a damp-scaled term, the same class as
# the split filter's own ring-1 seam approximation.  Mass conservation
# is exact regardless: the symmetrized edge normals come from the
# router (one shared value per physical edge, both faces identical), so
# cross-seam flux equality never depends on ghost consistency.
# Equivalence standard: the Galewsky day-6 physics gate
# (bench_galewsky, refused line) + the damp-scale parity smoke in
# tests/test_precision.py.
# ---------------------------------------------------------------------------


def make_cov_stage_refused_nu4(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    nu4: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    precision=None,
):
    """Stage-1 kernel with the del^4 filter fused in front of the RHS.

    ``stage1f(hc, uc, gsn, gwe, b_ext) -> (h1, u1, h0f, u0f, sn, we)``:
    fills ghosts once (corner-filled — the Laplacian ring needs them;
    the advective stencils never read corners so their arithmetic is
    unchanged), applies ``q -= dt nu4 lap(lap q)`` to the three
    prognostics' interiors (ring-1 first Laplacian, exactly
    :func:`make_cov_nu4_filter`'s arithmetic), overwrites the scratch
    interiors with the filtered fields, and runs the plain stage-1
    advective RHS + combine on the result.  Emits the filtered base
    state ``(h0f, u0f)`` so stages 2/3 combine against the same y0 the
    split stepper would have produced.
    """
    import numpy as np

    n, halo = grid.n, grid.halo
    if halo < 2:
        raise ValueError(f"re-fused nu4 needs halo >= 2 (ring-1 first "
                         f"Laplacian), got halo={halo}")
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(grid.dalpha)
    radius = float(grid.radius)
    h = halo
    precision = resolve_stage_precision(precision)
    sdt = jnp.float32 if precision is None else precision.strips_dtype
    wide = ((lambda x: x.astype(jnp.float32))
            if sdt != jnp.float32 else (lambda x: x))
    recon = pick_recon_precision(scheme, halo, n, limiter, precision)
    fill_ghosts, emit_strips = _make_fill(n, halo, i0, i1, corners=True,
                                          precision=precision)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)
    (fz_spec, coord_specs, hi_blk, ui_blk, be_blk, gsn_blk, gwe_blk,
     ssn_blk, swe_blk) = _cov_blockspecs(n, halo)

    def kernel(*refs):
        (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
         hc_ref, uc_ref, gsn_ref, gwe_ref, b_ref,
         ho_ref, uo_ref, h0f_ref, u0f_ref, ssn_ref, swe_ref,
         *scratch) = refs

        gsn = gsn_ref[0]
        gwe = gwe_ref[0]
        damp = jnp.float32(dt * nu4)

        filt = []
        exts = []
        for fi, (int_ref, lead) in enumerate(
                ((hc_ref, ()), (uc_ref, (0,)), (uc_ref, (1,)))):
            iv = int_ref[lead + (0,)]
            fill_ghosts(scratch[fi], iv, gsn, gwe, fi)
            fv = _nu4_filtered_value(
                xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
                scratch[fi][:], iv, n=n, halo=halo, d=d,
                radius=radius, damp=damp)
            # Filtered interior + unfiltered ghosts: the O(damp) seam
            # inconsistency documented in the section comment.
            scratch[fi][i0:i1, i0:i1] = fv
            filt.append(fv)
            exts.append(scratch[fi][:])

        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        ssn = wide(gsn[6 * h : 6 * h + 2])
        swe = wide(gwe[:, 6 * h : 6 * h + 2])
        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            exts[0], exts[1], exts[2], b_ref[0], ssn, swe,
            n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
            seam_scratch=(scratch[3], scratch[4]),
            sym_prescaled=True, precision=precision,
        )

        fg = jnp.float32(dt)                 # stage 1: a = 0, b = 1
        for fi, (tend, out_ref, base_ref, lead) in enumerate(
                ((dh, ho_ref, h0f_ref, ()),
                 (dua, uo_ref, u0f_ref, (0,)),
                 (dub, uo_ref, u0f_ref, (1,)))):
            int_new = filt[fi] + fg * tend
            out_ref[lead + (0,)] = int_new
            base_ref[lead + (0,)] = filt[fi]
            emit_strips(ssn_ref, swe_ref, int_new, fi)

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=[fz_spec] + coord_specs
                     + [hi_blk, ui_blk, gsn_blk, gwe_blk, be_blk],
            out_specs=[hi_blk, ui_blk, hi_blk, ui_blk, ssn_blk, swe_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(3)]
                           + [pltpu.VMEM((n, n + 1), jnp.float32),
                              pltpu.VMEM((n + 1, n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 6 * h, n), sdt),
            jax.ShapeDtypeStruct((6, n, 6 * h), sdt),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def stage1f(hc, uc, gsn, gwe, b_ext):
        return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                          hc, uc, gsn, gwe, b_ext))

    return stage1f


def make_fused_ssprk3_cov_refused_nu4(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    nu4: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    precision=None,
):
    """``step(y, t) -> y``: the re-fused del^4 stepper — 3 kernels + 3
    routes per step (the split form's 4 + 4 with the filter commuted
    into stage 1; see the section comment for the equivalence story).
    Carry/router identical to :func:`make_fused_ssprk3_cov_compact`
    (prescaled sym rows); composes with the stage precision policy, and
    with temporal blocking via the caller's generic exact-fusion wrap
    (``stepping.blocked`` — the filter is inside the stage, so blocking
    adds no extra routes).  No ``interval`` support: filter-cycling
    stays on the split path.
    """
    from .swe_step import SSPRK3_COEFFS

    precision = resolve_stage_precision(precision)
    route = make_cov_strip_router_split(grid, prescale_sym=True,
                                        precision=precision)
    stage1f = make_cov_stage_refused_nu4(
        grid, gravity, omega, dt, nu4, scheme=scheme, limiter=limiter,
        interpret=interpret, precision=precision)
    mk = lambda a, b: make_cov_stage_compact(
        grid.n, grid.halo, float(grid.dalpha), float(grid.radius),
        gravity, omega, dt, a, b, scheme=scheme, limiter=limiter,
        interpret=interpret, seam=True, sym_prescaled=True,
        precision=precision,
    )
    (_, _), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    def step1(y, t):
        del t
        with named_scope("rk_stage1_nu4"):
            gsn, gwe = route(y["strips_sn"], y["strips_we"])
            h1, u1, h0f, u0f, sn1, we1 = stage1f(y["h"], y["u"],
                                                 gsn, gwe, b_ext)
        with named_scope("rk_stage2"):
            gsn, gwe = route(sn1, we1)
            h2, u2, sn2, we2 = stage2(h0f, u0f, h1, u1, gsn, gwe, b_ext)
        with named_scope("rk_stage3"):
            gsn, gwe = route(sn2, we2)
            h3, u3, sn3, we3 = stage3(h0f, u0f, h2, u2, gsn, gwe, b_ext)
        return {"h": h3, "u": u3, "strips_sn": sn3, "strips_we": we3}

    return step1


