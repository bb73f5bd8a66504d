"""Whole-step fused covariant SWE kernel: SSPRK3 in ONE pallas_call.

The compact stepper (swe_cov.py) is three stage kernels + three XLA
routes; between stages the full state makes an HBM round trip and the
RK prior y0 is re-read per stage — ~75 MB/step of traffic whose only
purpose is crossing kernel boundaries.  Here the entire step is one
``pallas_call`` with grid ``(3 stages x (1 router + 6 faces),)``: y0
and b are fetched once as pinned full blocks, the evolving state and
its boundary strips live in VMEM scratch across the whole step, and
HBM sees one read of the carry and one write of the result.

The inter-stage router runs as a dedicated grid step.  Its data
movements (static row-gather of strips, along-edge reversals) are
expressed as one-hot / anti-identity matmuls at ``Precision.HIGHEST``
— bitwise-exact permutations on the MXU (the trick validated by the
neighbor-read experiment in swe_cov.py) — followed by the same
rotation multiply-adds and pair-symmetrization algebra as the XLA
routers (reversal selection via exact 0/1 masks), so the ghosts are
bitwise-identical to :func:`make_cov_strip_router_split` and the whole
step to the compact stepper (tested).

Per-stage variation (RK combine coefficients) is data in SMEM indexed
by the stage id; the program is uniform over the grid apart from one
``pl.when`` router/face branch.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..geometry.connectivity import (
    EDGE_E,
    EDGE_N,
    EDGE_S,
    EDGE_W,
    build_connectivity,
    edge_pairs,
)
from ..geometry.cubed_sphere import FACE_AXES
from ..ops.pallas.swe_cov import (
    _EORDER,
    _OUT_SIGN,
    _SLOT,
    _rotation_tables,
    rhs_core_cov,
)
from ..ops.pallas.swe_rhs import coord_rows, pick_recon

__all__ = ["make_fused_ssprk3_cov_mega"]

HIGH = jax.lax.Precision.HIGHEST


def _gather_matrix(grid):
    """One-hot gather: every router input row as P @ [S ; S J].

    ``S`` is the flat strip tensor (sn rows then weT rows, 12*6h rows);
    outputs are the placed S/N ghost rows, placed W/E (row-form) ghost
    rows, and the interior boundary-adjacent u rows — the same row set
    as make_cov_strip_router_split's gather, as a dense 0/1 matrix.
    """
    n, halo = grid.n, grid.halo
    h = halo
    adj = build_connectivity()
    F = 2 * 6 * 6 * h

    def src_row(fi, g, e, depth):
        kr = depth if e in (EDGE_S, EDGE_W) else h - 1 - depth
        sec = 0 if e in (EDGE_S, EDGE_N) else 6 * 6 * h
        pair = 0 if e in (EDGE_S, EDGE_W) else h
        return sec + g * 6 * h + fi * 2 * h + pair + kr

    rows = []

    def ghost_rows(edges):
        for fi in range(3):
            for f in range(6):
                for e in edges:
                    link = adj[f][e]
                    for k in range(h):
                        dep = (h - 1 - k) if e in (EDGE_S, EDGE_W) else k
                        rows.append((src_row(fi, link.nbr_face,
                                             link.nbr_edge, dep),
                                     link.reversed_))

    ghost_rows((EDGE_S, EDGE_N))
    n_sn = len(rows)
    ghost_rows((EDGE_W, EDGE_E))
    n_we = len(rows) - n_sn
    for c in range(2):
        for f in range(6):
            for e in _EORDER:
                rows.append((src_row(1 + c, f, e, 0), False))

    P = np.zeros((len(rows), 2 * F), np.float32)
    for i, (r, rev) in enumerate(rows):
        P[i, r + (F if rev else 0)] = 1.0
    return P, n_sn, n_we


def _sym_mats():
    """Selection/scatter matrices + masks of the pair symmetrization."""
    adj = build_connectivity()
    links = [lk for lk, _ in edge_pairs(adj)]
    backs = [bk for _, bk in edge_pairs(adj)]
    SEL_A = np.zeros((12, 24), np.float32)
    SEL_B = np.zeros((12, 24), np.float32)
    SC_A = np.zeros((24, 12), np.float32)
    SC_B = np.zeros((24, 12), np.float32)
    sga = np.zeros((12, 1), np.float32)
    sgb = np.zeros((12, 1), np.float32)
    rev = np.zeros((12, 1), np.float32)
    for i, (lk, bk) in enumerate(zip(links, backs)):
        SEL_A[i, lk.face * 4 + _SLOT[lk.edge]] = 1.0
        SEL_B[i, bk.face * 4 + _SLOT[bk.edge]] = 1.0
        SC_A[lk.face * 4 + _SLOT[lk.edge], i] = 1.0
        SC_B[bk.face * 4 + _SLOT[bk.edge], i] = 1.0
        sga[i] = _OUT_SIGN[lk.edge]
        sgb[i] = _OUT_SIGN[bk.edge]
        rev[i] = 1.0 if lk.reversed_ else 0.0
    return SEL_A, SEL_B, SC_A, SC_B, sga, sgb, rev


def make_fused_ssprk3_cov_mega(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
):
    """``step(y, t) -> y`` over the compact split-strip carry, one kernel.

    Same carry and bitwise-identical results as the compact stepper
    (tested); the difference is purely where data lives between stages.
    """
    from ..ops.pallas.swe_step import SSPRK3_COEFFS

    n, halo = grid.n, grid.halo
    h = halo
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(grid.dalpha)
    radius = float(grid.radius)
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, _ = coord_rows(n, halo)
    frames_z = jnp.asarray(np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)

    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    AB = jnp.asarray([[0.0, 1.0, b1 * dt],
                      [a2, b2, b2 * dt],
                      [a3, b3, b3 * dt]], jnp.float32)

    P_np, n_sn, n_we = _gather_matrix(grid)
    P = jnp.asarray(P_np)
    F = P_np.shape[1] // 2
    J = jnp.asarray(np.eye(n, dtype=np.float32)[::-1])

    Tc = np.asarray(_rotation_tables(grid))
    T_sn = jnp.asarray(np.stack(
        [Tc[:, :, EDGE_S, ::-1], Tc[:, :, EDGE_N]], axis=2))
    T_we = jnp.asarray(np.stack(
        [Tc[:, :, EDGE_W, ::-1], Tc[:, :, EDGE_E]], axis=2))

    mats = [jnp.asarray(x) for x in _sym_mats()]
    SEL_A, SEL_B, SC_A, SC_B, sga, sgb, rev = mats

    M0 = jnp.stack([jnp.asarray({
        EDGE_W: grid.ginv_aa_xf[0, i0:i1, i0],
        EDGE_E: grid.ginv_aa_xf[0, i0:i1, i1],
        EDGE_S: grid.ginv_ab_yf[0, i0, i0:i1],
        EDGE_N: grid.ginv_ab_yf[0, i1, i0:i1]}[e]) for e in _EORDER])
    M1 = jnp.stack([jnp.asarray({
        EDGE_W: grid.ginv_ab_xf[0, i0:i1, i0],
        EDGE_E: grid.ginv_ab_xf[0, i0:i1, i1],
        EDGE_S: grid.ginv_bb_yf[0, i0, i0:i1],
        EDGE_N: grid.ginv_bb_yf[0, i1, i0:i1]}[e]) for e in _EORDER])

    SNR = 6 * 6 * h          # rows in the sn section of flat S

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   precision=HIGH,
                                   preferred_element_type=jnp.float32)

    def kernel(AB_ref, fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
               y0h_ref, y0u_ref, sn_in_ref, we_in_ref, b_ref,
               P_ref, J_ref, Tsn_ref, Twe_ref,
               SELA_ref, SELB_ref, SCA_ref, SCB_ref,
               sga_ref, sgb_ref, rev_ref, M0_ref, M1_ref,
               ho_ref, uo_ref, sno_ref, weo_ref,
               cur_h, cur_u, sn_s, we_s, gsn_s, gwe_s, w0, w1, w2):
        p = pl.program_id(0)
        stage = p // 7
        sub = p % 7

        @pl.when(sub == 0)
        def _router():
            @pl.when(p == 0)
            def _init():
                cur_h[:] = y0h_ref[:]
                cur_u[:] = y0u_ref[:]
                sn_s[:] = sn_in_ref[:]
                we_s[:] = we_in_ref[:]

            S = jnp.concatenate(
                [sn_s[:].reshape(SNR, n),
                 jnp.swapaxes(we_s[:], 1, 2).reshape(SNR, n)], axis=0)
            S_all = jnp.concatenate([S, dot(S, J_ref[:])], axis=0)
            rows = dot(P_ref[:], S_all)
            C_sn = rows[:n_sn].reshape(3, 6, 2, h, n)
            C_we = rows[n_sn:n_sn + n_we].reshape(3, 6, 2, h, n)
            I_u = rows[n_sn + n_we:].reshape(2, 6, 4, n)

            Tsn = Tsn_ref[:]
            Twe = Twe_ref[:]
            G_sn = [C_sn[0],
                    Tsn[0] * C_sn[1] + Tsn[1] * C_sn[2],
                    Tsn[2] * C_sn[1] + Tsn[3] * C_sn[2]]
            G_we = [C_we[0],
                    Twe[0] * C_we[1] + Twe[1] * C_we[2],
                    Twe[2] * C_we[1] + Twe[3] * C_we[2]]

            ka, kb = h - 1, 0          # placed edge-adjacent rows (S/W, N/E)
            gadj_a = jnp.stack(
                [G_sn[1][:, 0, ka], G_sn[1][:, 1, kb],
                 G_we[1][:, 0, ka], G_we[1][:, 1, kb]], axis=1)
            gadj_b = jnp.stack(
                [G_sn[2][:, 0, ka], G_sn[2][:, 1, kb],
                 G_we[2][:, 0, ka], G_we[2][:, 1, kb]], axis=1)
            ubar0 = 0.5 * (I_u[0] + gadj_a)
            ubar1 = 0.5 * (I_u[1] + gadj_b)
            L = (M0_ref[:][None] * ubar0 + M1_ref[:][None] * ubar1
                 ).reshape(24, n)

            la = dot(SELA_ref[:], L)
            lb = dot(SELB_ref[:], L)
            rv = rev_ref[:]
            one = jnp.float32(1.0)
            lb = rv * dot(lb, J_ref[:]) + (one - rv) * lb
            avg = 0.5 * (sga_ref[:] * la - sgb_ref[:] * lb)
            na = sga_ref[:] * avg
            nb = sgb_ref[:] * (-avg)
            nb = rv * dot(nb, J_ref[:]) + (one - rv) * nb
            sym = (dot(SCA_ref[:], na) + dot(SCB_ref[:], nb)
                   ).reshape(6, 4, n)

            gsn_s[:] = jnp.concatenate(
                [jnp.concatenate([g.reshape(6, 2 * h, n) for g in G_sn],
                                 axis=1), sym[:, 0:2]], axis=1)
            gwe_s[:] = jnp.swapaxes(jnp.concatenate(
                [jnp.concatenate([g.reshape(6, 2 * h, n) for g in G_we],
                                 axis=1), sym[:, 2:4]], axis=1), 1, 2)

        @pl.when(sub > 0)
        def _face():
            f = sub - 1
            gsn = gsn_s[f]
            gwe = gwe_s[f]

            def fill(scratch, int_val, fi):
                scratch[i0:i1, i0:i1] = int_val
                scratch[0:h, i0:i1] = gsn[fi * 2 * h:fi * 2 * h + h]
                scratch[i1:i1 + h, i0:i1] = gsn[fi * 2 * h + h:
                                                (fi + 1) * 2 * h]
                scratch[i0:i1, 0:h] = gwe[:, fi * 2 * h:fi * 2 * h + h]
                scratch[i0:i1, i1:i1 + h] = gwe[:, fi * 2 * h + h:
                                                (fi + 1) * 2 * h]
                return scratch[:]

            hf = fill(w0, cur_h[f], 0)
            ua = fill(w1, cur_u[0, f], 1)
            ub = fill(w2, cur_u[1, f], 2)
            fz = (fz_ref[f, 0, 0], fz_ref[f, 0, 1], fz_ref[f, 0, 2])
            ssn = gsn[6 * h:6 * h + 2]
            swe = gwe[:, 6 * h:6 * h + 2]

            dh, dua, dub = rhs_core_cov(
                fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
                hf, ua, ub, b_ref[f], ssn, swe,
                n=n, halo=halo, d=d, radius=radius,
                gravity=gravity, omega=omega, recon=recon,
            )

            A = AB_ref[stage, 0]
            B = AB_ref[stage, 1]
            C = AB_ref[stage, 2]

            def emit(y0_f, cur_ref, idx, tend, fi):
                int_new = (A * y0_f + B * cur_ref[idx]) + C * tend
                cur_ref[idx] = int_new
                sn_s[f, fi * 2 * h:fi * 2 * h + h] = int_new[0:h, :]
                sn_s[f, fi * 2 * h + h:(fi + 1) * 2 * h] = (
                    int_new[n - h:n, :])
                we_s[f, :, fi * 2 * h:fi * 2 * h + h] = int_new[:, 0:h]
                we_s[f, :, fi * 2 * h + h:(fi + 1) * 2 * h] = (
                    int_new[:, n - h:n])

            emit(y0h_ref[f], cur_h, f, dh, 0)
            emit(y0u_ref[0, f], cur_u, (0, f), dua, 1)
            emit(y0u_ref[1, f], cur_u, (1, f), dub, 2)

            @pl.when(p == 20)
            def _writeback():
                ho_ref[:] = cur_h[:]
                uo_ref[:] = cur_u[:]
                sno_ref[:] = sn_s[:]
                weo_ref[:] = we_s[:]

    def pin(shape):
        nd = len(shape)
        return pl.BlockSpec(shape, lambda p, _nd=nd: (0,) * _nd,
                            memory_space=pltpu.VMEM)

    G_rows = P_np.shape[0]
    in_specs = [
        pl.BlockSpec((3, 3), lambda p: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((6, 1, 3), lambda p: (0, 0, 0),
                     memory_space=pltpu.SMEM),
        pin((1, m)), pin((1, m)), pin((m, 1)), pin((m, 1)),
        pin((6, n, n)), pin((2, 6, n, n)),
        pin((6, 6 * h, n)), pin((6, n, 6 * h)),
        pin((6, m, m)),
        pin((G_rows, 2 * F)), pin((n, n)),
        pin((4, 6, 2, h, n)), pin((4, 6, 2, h, n)),
        pin((12, 24)), pin((12, 24)), pin((24, 12)), pin((24, 12)),
        pin((12, 1)), pin((12, 1)), pin((12, 1)),
        pin((4, n)), pin((4, n)),
    ]
    out_specs = [
        pin((6, n, n)), pin((2, 6, n, n)),
        pin((6, 6 * h, n)), pin((6, n, 6 * h)),
    ]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(21,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((6, n, n), jnp.float32),
                pltpu.VMEM((2, 6, n, n), jnp.float32),
                pltpu.VMEM((6, 6 * h, n), jnp.float32),
                pltpu.VMEM((6, n, 6 * h), jnp.float32),
                pltpu.VMEM((6, 6 * h + 2, n), jnp.float32),
                pltpu.VMEM((6, n, 6 * h + 2), jnp.float32),
                pltpu.VMEM((m, m), jnp.float32),
                pltpu.VMEM((m, m), jnp.float32),
                pltpu.VMEM((m, m), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, n, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 6 * h, n), jnp.float32),
            jax.ShapeDtypeStruct((6, n, 6 * h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=120 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    def step(y, t):
        del t
        h3, u3, sn3, we3 = call(
            AB, frames_z, x_row, xf_row, x_col, xf_col,
            y["h"], y["u"], y["strips_sn"], y["strips_we"], b_ext,
            P, J, T_sn, T_we, SEL_A, SEL_B, SC_A, SC_B,
            sga, sgb, rev, M0, M1)
        return {"h": h3, "u": u3, "strips_sn": sn3, "strips_we": we3}

    return step
