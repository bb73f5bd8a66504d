"""EXPERIMENTAL: neighbor-read fused covariant stage (measured dead end).

Quarantined from :mod:`jaxstream.ops.pallas.swe_cov` (VERDICT r1 weak #7):
a documented negative experiment — measured 2.8x SLOWER than the
strip-router stepper on TPU v5e at C384 — kept because the design is
instructive and the trade may flip on chips with a different MXU-latency/
DMA-overhead balance (see the design banner below and DESIGN.md
"Failed/negative experiments").  Parity-tested (opt-in, slow-marked) in
tests/test_cov_swe.py::test_cov_nbr_step_parity.
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
from ..ops.pallas.swe_cov import (
    _OUT_SIGN,
    _rotation_tables,
    rhs_core_cov,
)
from ..ops.pallas.swe_rhs import (
    FACE_AXES,
    _fast_frame,
    coord_rows,
    pick_recon,
)

__all__ = ["make_cov_stage_nbr", "make_fused_ssprk3_cov_nbr"]

# ---------------------------------------------------------------------------
# Neighbor-read fused stage: zero strip traffic, zero inter-stage router.
#
# EXPERIMENTAL ALTERNATIVE — measured SLOWER than the strip-router stepper
# on TPU v5e at C384 (870 vs 2020 steps/s): the in-kernel costs of the
# orientation workarounds (MXU flip-matmuls and transposes on the ghost
# critical path, 6-way pl.when branch bodies, full-array constant-block
# fetches) exceed the strip-router's small-XLA-op overhead they remove.
# Kept because the design is instructive and the trade may flip on chips
# with different MXU latency / DMA overhead ratios; parity-tested against
# the oracle (tests/test_cov_swe.py::test_cov_nbr_step_parity).
#
# Design: each stage kernel receives the full (6, M, M) state as
# *constant* VMEM blocks (index_map pinned to 0, so Mosaic fetches them
# once per launch) alongside the usual per-face blocks, and every face
# fills its own ghost ring directly from its neighbors' interior rows with
# static slices inside a 6-way pl.when branch.  The three
# Mosaic-unsupported data movements are replaced by supported ones:
#   * along-edge reversal -> matmul with the anti-identity on the MXU at
#     Precision.HIGHEST, which is bitwise-exact for a permutation matrix;
#   * W/E orientation     -> 2-D transpose (supported);
#   * depth reversal      -> static sublane re-concatenation (halo rows).
# The symmetrized panel-edge normal velocities are also computed in-kernel:
# both faces of an edge evaluate the identical expression on the identical
# operands (each can see both panels' data), so their edge fluxes agree
# bitwise and mass conservation is preserved without any cross-kernel
# communication.  The integration carry shrinks to plain {h, u} extended
# fields, and per-step HBM traffic is exactly the field reads/writes.
# ---------------------------------------------------------------------------


def _edge_metric_rows(xr, yc, n, halo, radius):
    """(m0, m1) closed-form inverse-metric rows at each edge's faces.

    Face-independent (the equiangular metric depends only on |X|, |Y|);
    the across-edge coordinate is exactly +-1 (X = tan(+-pi/4)) and the
    along-edge coordinate row is the same one the RHS uses.  Returns dict
    edge -> (m0_row, m1_row), canonical along-edge order as (1, n) rows,
    with the (iaa, iab) pair for W/E and (iab, ibb) for S/N, matching
    covariant_face_normal_velocity.
    """
    h0, h1 = halo, halo + n
    out = {}
    # W/E edges: x-face at X = -1 / +1, along-edge coord = Y (rows).
    for edge, xe in ((EDGE_W, -1.0), (EDGE_E, 1.0)):
        F = _fast_frame(jnp.full((1, 1), xe, jnp.float32), yc[h0:h1], radius)
        # (n, 1) columns -> transpose to (1, n) rows.
        out[edge] = (jnp.swapaxes(F["inv_aa"], 0, 1),
                     jnp.swapaxes(F["inv_ab"], 0, 1))
    # S/N edges: y-face at Y = -1 / +1, along-edge coord = X (cols).
    for edge, ye in ((EDGE_S, -1.0), (EDGE_N, 1.0)):
        F = _fast_frame(xr[:, h0:h1], jnp.full((1, 1), ye, jnp.float32),
                        radius)
        out[edge] = (F["inv_ab"], F["inv_bb"])
    return out


def _depth_flip(strip, halo):
    """Reverse the (sublane) depth axis of a (halo, n) strip, statically."""
    return jnp.concatenate([strip[k:k + 1] for k in reversed(range(halo))],
                           axis=0)


def _nbr_tables(grid):
    """(T_sn_full, T_we_full, P_rev) for the neighbor-read kernels.

    Placed-layout rotation tables — (4, 6, 2, halo, n) for S/N ghost
    blocks and (4, 6, 2, n, halo) for W/E — derived from the canonical
    :func:`_rotation_tables` by the ``place_strip`` transforms, plus the
    (n, n) anti-identity used for exact MXU reversals.
    """
    Tc = _rotation_tables(grid)                     # (4, 6, 4, halo, n)
    t_sn = jnp.stack([jnp.flip(Tc[:, :, EDGE_S], axis=-2),
                      Tc[:, :, EDGE_N]], axis=2)    # (4, 6, 2, halo, n)
    t_we = jnp.stack([
        jnp.swapaxes(jnp.flip(Tc[:, :, EDGE_W], axis=-2), -1, -2),
        jnp.swapaxes(Tc[:, :, EDGE_E], -1, -2),
    ], axis=2)                                      # (4, 6, 2, n, halo)
    return (t_sn, t_we,
            jnp.asarray(np.eye(grid.n, dtype=np.float32)[::-1]))


def make_cov_stage_nbr(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    a: float,
    b: float,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    tables=None,
):
    """One neighbor-read fused covariant RK stage (see section banner).

    ``a == 0``: ``stage(hc, uc, b_ext) -> (h, u)``; else
    ``stage(h0, u0, hc, uc, b_ext) -> (h, u)``.  All fields extended;
    output ghosts are finite but stale (next stage refills in-kernel).
    ``tables`` is the optional ``(T_sn_full, T_we_full, P_rev)`` triple so
    the stepper builds the rotation tables once for all three stages.
    """
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

    adj = build_connectivity()
    pair_of = {}
    for link, back in edge_pairs(adj):
        pair_of[(link.face, link.edge)] = (link, back, True)
        pair_of[(back.face, back.edge)] = (link, back, False)

    if tables is None:
        tables = _nbr_tables(grid)
    T_sn_full, T_we_full, P_rev = tables

    HIGH = jax.lax.Precision.HIGHEST

    def lane_flip(strip, p_ref):
        """Exact along-edge reversal of a (k, n) strip on the MXU."""
        return jax.lax.dot_general(
            strip, p_ref[:], (((1,), (0,)), ((), ())),
            precision=HIGH, preferred_element_type=jnp.float32)

    def raw_block(ref, face, edge, lead=()):
        """Neighbor ``face``'s interior boundary block for ``edge``."""
        if edge == EDGE_S:
            return ref[lead + (face, slice(i0, i0 + h), slice(i0, i1))]
        if edge == EDGE_N:
            return ref[lead + (face, slice(i1 - h, i1), slice(i0, i1))]
        if edge == EDGE_W:
            return ref[lead + (face, slice(i0, i1), slice(i0, i0 + h))]
        return ref[lead + (face, slice(i0, i1), slice(i1 - h, i1))]

    def canon_block(blk, edge):
        """Raw boundary block -> canonical (halo, n), depth 0 nearest."""
        if edge == EDGE_S:
            return blk
        if edge == EDGE_N:
            return _depth_flip(blk, h)
        t = jnp.swapaxes(blk, 0, 1)          # (halo, n), depth = cols
        if edge == EDGE_W:
            return t
        return _depth_flip(t, h)             # E: nearest is the last col

    def place_block(strip, edge):
        """Canonical (halo, n) -> the local ghost block's layout."""
        if edge == EDGE_S:
            return _depth_flip(strip, h)
        if edge == EDGE_N:
            return strip
        if edge == EDGE_W:
            return jnp.swapaxes(_depth_flip(strip, h), 0, 1)
        return jnp.swapaxes(strip, 0, 1)

    def ghost_canonical(ref, f, e, p_ref, lead=()):
        """Canonical-(halo, n) ghost data for face ``f``/edge ``e``."""
        link = adj[f][e]
        c = canon_block(raw_block(ref, link.nbr_face, link.nbr_edge,
                                  lead=lead), link.nbr_edge)
        if link.reversed_:
            c = lane_flip(c, p_ref)
        return c

    def store_ghost(scratch, e, placed):
        if e == EDGE_S:
            scratch[0:h, i0:i1] = placed
        elif e == EDGE_N:
            scratch[i1:i1 + h, i0:i1] = placed
        elif e == EDGE_W:
            scratch[i0:i1, 0:h] = placed
        else:
            scratch[i0:i1, i1:i1 + h] = placed

    def t_rows_adj(tsn_ref, twe_ref, f, e, j):
        """(1, n) T[i*2+j] rotation row at face f / edge e's adjacent
        ghost slot, canonical along order."""
        if e == EDGE_S:
            return tsn_ref[j, f, 0, h - 1:h, :]
        if e == EDGE_N:
            return tsn_ref[j, f, 1, 0:1, :]
        if e == EDGE_W:
            return jnp.swapaxes(twe_ref[j, f, 0, :, h - 1:h], 0, 1)
        return jnp.swapaxes(twe_ref[j, f, 1, :, 0:1], 0, 1)

    def int_adj_row(ref, f, e, lead=()):
        """(1, n) interior edge-adjacent row of face f, canonical order."""
        if e == EDGE_S:
            return ref[lead + (f, slice(i0, i0 + 1), slice(i0, i1))]
        if e == EDGE_N:
            return ref[lead + (f, slice(i1 - 1, i1), slice(i0, i1))]
        if e == EDGE_W:
            return jnp.swapaxes(
                ref[lead + (f, slice(i0, i1), slice(i0, i0 + 1))], 0, 1)
        return jnp.swapaxes(
            ref[lead + (f, slice(i0, i1), slice(i1 - 1, i1))], 0, 1)

    def ghost_adj_rows(u_ref, tsn_ref, twe_ref, f, e, p_ref):
        """Edge-adjacent ghost covariant components of face f in f's
        basis, canonical (1, n) rows — the other panel's adjacent
        interior row rotated through the adjacent-slot T entries."""
        link = adj[f][e]
        raws = []
        for comp in range(2):
            row = int_adj_row(u_ref, link.nbr_face, link.nbr_edge,
                              lead=(comp,))
            if link.reversed_:
                row = lane_flip(row, p_ref)
            raws.append(row)
        return [t_rows_adj(tsn_ref, twe_ref, f, e, 0) * raws[0]
                + t_rows_adj(tsn_ref, twe_ref, f, e, 1) * raws[1],
                t_rows_adj(tsn_ref, twe_ref, f, e, 2) * raws[0]
                + t_rows_adj(tsn_ref, twe_ref, f, e, 3) * raws[1]]

    def local_normal_rows(u_ref, tsn_ref, twe_ref, f, e, met, p_ref):
        """(1, n) face-f local edge-normal velocity, canonical order."""
        gi = ghost_adj_rows(u_ref, tsn_ref, twe_ref, f, e, p_ref)
        ii = [int_adj_row(u_ref, f, e, lead=(c,)) for c in range(2)]
        lower_is_ghost = e in (EDGE_S, EDGE_W)
        ub0 = 0.5 * ((gi[0] + ii[0]) if lower_is_ghost else (ii[0] + gi[0]))
        ub1 = 0.5 * ((gi[1] + ii[1]) if lower_is_ghost else (ii[1] + gi[1]))
        m0, m1 = met[e]
        return m0 * ub0 + m1 * ub1

    def kernel(*refs):
        if with_y0:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, p_ref,
             tsn_ref, twe_ref, h0_ref, u0_ref, hfull_ref, ufull_ref, b_ref,
             ho_ref, uo_ref, s_h, s_ua, s_ub, s_ssn, s_swe) = refs
        else:
            (fz_ref, xr_ref, xfr_ref, yc_ref, yfc_ref, p_ref,
             tsn_ref, twe_ref, hfull_ref, ufull_ref, b_ref,
             ho_ref, uo_ref, s_h, s_ua, s_ub, s_ssn, s_swe) = refs

        met = _edge_metric_rows(xr_ref[:], yc_ref[:], n, halo, radius)
        pid = pl.program_id(0)

        for f in range(6):
            @pl.when(pid == f)
            def _(f=f):
                # --- ghost fill, all-static slices for this face --------
                s_h[:] = hfull_ref[f]
                s_ua[:] = ufull_ref[0, f]
                s_ub[:] = ufull_ref[1, f]
                for e in range(4):
                    gh = ghost_canonical(hfull_ref, f, e, p_ref)
                    store_ghost(s_h, e, place_block(gh, e))
                    raw = [ghost_canonical(ufull_ref, f, e, p_ref,
                                           lead=(c,)) for c in range(2)]
                    # Full-depth T tables at this face's ghost slots,
                    # un-placed back to canonical (halo, n) layout
                    # (place/unplace are involutive per edge).
                    if e == EDGE_S:
                        Ts = [_depth_flip(tsn_ref[j, f, 0], h)
                              for j in range(4)]
                    elif e == EDGE_N:
                        Ts = [tsn_ref[j, f, 1] for j in range(4)]
                    elif e == EDGE_W:
                        Ts = [_depth_flip(jnp.swapaxes(twe_ref[j, f, 0],
                                                       0, 1), h)
                              for j in range(4)]
                    else:
                        Ts = [jnp.swapaxes(twe_ref[j, f, 1], 0, 1)
                              for j in range(4)]
                    ca = Ts[0] * raw[0] + Ts[1] * raw[1]
                    cb = Ts[2] * raw[0] + Ts[3] * raw[1]
                    store_ghost(s_ua, e, place_block(ca, e))
                    store_ghost(s_ub, e, place_block(cb, e))
                # --- symmetrized edge normals ---------------------------
                for e in range(4):
                    link, back, is_link = pair_of[(f, e)]
                    nl = local_normal_rows(ufull_ref, tsn_ref, twe_ref,
                                           link.face, link.edge, met, p_ref)
                    nb = local_normal_rows(ufull_ref, tsn_ref, twe_ref,
                                           back.face, back.edge, met, p_ref)
                    if link.reversed_:
                        nb = lane_flip(nb, p_ref)
                    out_a = jnp.float32(_OUT_SIGN[link.edge]) * nl
                    out_b = jnp.float32(_OUT_SIGN[back.edge]) * nb
                    avg = 0.5 * (out_a - out_b)
                    if is_link:
                        mine = jnp.float32(_OUT_SIGN[link.edge]) * avg
                    else:
                        mine = jnp.float32(_OUT_SIGN[back.edge]) * (-avg)
                        if link.reversed_:
                            mine = lane_flip(mine, p_ref)
                    if e == EDGE_S:
                        s_ssn[0:1, :] = mine
                    elif e == EDGE_N:
                        s_ssn[1:2, :] = mine
                    elif e == EDGE_W:
                        s_swe[:, 0:1] = jnp.swapaxes(mine, 0, 1)
                    else:
                        s_swe[:, 1:2] = jnp.swapaxes(mine, 0, 1)

        fz = (fz_ref[0, 0, 0], fz_ref[0, 0, 1], fz_ref[0, 0, 2])
        hf = s_h[:]
        ua = s_ua[:]
        ub = s_ub[:]
        dh, dua, dub = rhs_core_cov(
            fz, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, ua, ub, b_ref[0], s_ssn[:], s_swe[:],
            n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
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
            out_u = [ua, ub] if b == 1.0 else [fb * ua, fb * ub]

        ho_ref[0] = out_h
        ho_ref[0, i0:i1, i0:i1] = out_h[i0:i1, i0:i1] + fg * dh
        for i, tend in ((0, dua), (1, dub)):
            uo_ref[i, 0] = out_u[i]
            uo_ref[i, 0, i0:i1, i0:i1] = (out_u[i][i0:i1, i0:i1]
                                          + fg * tend)

    fz_spec = pl.BlockSpec((1, 1, 3), lambda f: (f, 0, 0),
                           memory_space=pltpu.SMEM)
    coord_specs = [
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
    ]
    p_spec = pl.BlockSpec((n, n), lambda f: (0, 0), memory_space=pltpu.VMEM)
    tsn_spec = pl.BlockSpec((4, 6, 2, h, n), lambda f: (0, 0, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    twe_spec = pl.BlockSpec((4, 6, 2, n, h), lambda f: (0, 0, 0, 0, 0),
                            memory_space=pltpu.VMEM)
    h_blk = pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM)
    u_blk = pl.BlockSpec((2, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM)
    hfull_spec = pl.BlockSpec((6, m, m), lambda f: (0, 0, 0),
                              memory_space=pltpu.VMEM)
    ufull_spec = pl.BlockSpec((2, 6, m, m), lambda f: (0, 0, 0, 0),
                              memory_space=pltpu.VMEM)

    in_specs = [fz_spec] + coord_specs + [p_spec, tsn_spec, twe_spec]
    if with_y0:
        in_specs += [h_blk, u_blk]
    in_specs += [hfull_spec, ufull_spec, h_blk]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=in_specs,
            out_specs=[h_blk, u_blk],
            scratch_shapes=[
                pltpu.VMEM((m, m), jnp.float32),
                pltpu.VMEM((m, m), jnp.float32),
                pltpu.VMEM((m, m), jnp.float32),
                pltpu.VMEM((2, n), jnp.float32),
                pltpu.VMEM((n, 2), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((2, 6, m, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage(h0, u0, hc, uc, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              P_rev, T_sn_full, T_we_full,
                              h0, u0, hc, uc, b_ext))
    else:
        def stage(hc, uc, b_ext):
            return tuple(call(frames_z, x_row, xf_row, x_col, xf_col,
                              P_rev, T_sn_full, T_we_full, hc, uc, b_ext))
    return stage


def make_fused_ssprk3_cov_nbr(
    grid,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
):
    """``step(y, t) -> y`` over plain extended state ``y = {h, u}``.

    Three neighbor-read stage kernels and nothing else — no strip carry,
    no inter-stage ops at all.
    """
    from ..ops.pallas.swe_step import SSPRK3_COEFFS

    tables = _nbr_tables(grid)
    mk = lambda a, b: make_cov_stage_nbr(
        grid, gravity, omega, dt, a, b,
        scheme=scheme, limiter=limiter, interpret=interpret, tables=tables,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    def step(y, t):
        del t
        h0, u0 = y["h"], y["u"]
        h1, u1 = stage1(h0, u0, b_ext)
        h2, u2 = stage2(h0, u0, h1, u1, b_ext)
        h3, u3 = stage3(h0, u0, h2, u2, b_ext)
        return {"h": h3, "u": u3}

    return step
