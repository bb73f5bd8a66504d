"""Explicit block-mesh shard_map stepper for the covariant formulation.

Completes the explicit tier's matrix (DESIGN.md "formulation ×
parallelism"): the covariant flagship on a ``(panel, y, x)`` =
``(6, s, s)`` mesh — the reference's planned ``tiles_per_edge`` scaling
(``/root/reference/JAX-DevLab-Examples.py:31-37``, annotated "3 → 54
tiles" on the config screenshot, deck p.8) with the rotation-form
vector exchange instead of the Cartesian componentwise one.

Structure per SSPRK3 stage, per device (one sub-panel block each):

* **Intra-panel ghosts**: 4 neighbor ``ppermute``s over the 'y'/'x'
  axes carrying one ``(3, halo, n_loc)`` payload (h + both covariant
  components — same basis on both sides, no rotation).
* **Cube edges**: the 4 race-free stages as joint ``ppermute``s over
  the full device product axis (only face-boundary blocks participate);
  receivers rotate the velocity strips through per-device slices of the
  face-level rotation tables (the same ``_rotation_tables`` source of
  truth as every other covariant path).
* **Seam normals**: every block edge gets an imposed edge-normal strip.
  Panel seams use the canonical (link, back) symmetrization algebra on
  the exchanged adjacent rows (bitwise-equal on both sides, as in
  :mod:`.shard_cov`); intra-panel seams need no pair algebra at all —
  ``0.5 * (mine + theirs)`` is bitwise-commutative and both sides scale
  by identical stored-metric rows, so the shared value is exact by
  construction.  Cross-device flux telescoping (mass conservation) is
  therefore exact in both directions.

The per-block RHS runs :func:`...swe_cov.make_cov_rhs_pallas_local`
with the block's own coordinate rows as runtime operands (each device
covers a different patch of its face's gnomonic coordinates).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..geometry.connectivity import (
    EDGE_E,
    EDGE_N,
    EDGE_S,
    EDGE_W,
    build_connectivity,
    build_schedule,
)
from ..geometry.cubed_sphere import FACE_AXES, extended_coords
from .halo import read_strip, write_strip
from .shard_cov import (
    CUBE_ROW_NAMES,
    apply_cov_cube_recv,
    pallas_interpret_for,
    ssprk3_sharded_body,
)
from .shard_halo import _block_coords

__all__ = ["CovBlockProgram", "make_cov_block_exchange",
           "make_cov_block_exchange_phases",
           "make_cov_block_exchange_batched",
           "make_sharded_cov_block_stepper"]

_OUT_SIGN = {EDGE_S: -1.0, EDGE_W: -1.0, EDGE_N: 1.0, EDGE_E: 1.0}


class CovBlockProgram:
    """Static schedule + per-device tables for the covariant block mesh.

    All ``(6, s, s, ...)`` tables shard ``P('panel', 'y', 'x')``; the
    SPMD program is uniform and reads its own rows.
    """

    def __init__(self, grid, s: int, axis_names=("panel", "y", "x")):
        n, halo = grid.n, grid.halo
        if n % s:
            raise ValueError(f"n={n} not divisible by blocks-per-edge {s}")
        n_loc = n // s
        if n_loc < halo:
            raise ValueError(f"local block {n_loc} smaller than halo {halo}")
        self.s = s
        self.n_loc = n_loc
        self.halo = halo
        self.axis_names = tuple(axis_names)
        ax_panel, ax_y, ax_x = self.axis_names
        adj = build_connectivity()
        schedule = build_schedule(adj)
        nst = len(schedule)
        i0f, i1f = halo, halo + n          # face-extended interior range

        # ---- intra-panel neighbor shifts --------------------------------
        fwd = [(i, i + 1) for i in range(s - 1)]
        bwd = [(i + 1, i) for i in range(s - 1)]
        self.intra_perms = [
            (ax_x, fwd, EDGE_E, EDGE_W),
            (ax_x, bwd, EDGE_W, EDGE_E),
            (ax_y, fwd, EDGE_N, EDGE_S),
            (ax_y, bwd, EDGE_S, EDGE_N),
        ]

        # ---- cube-edge stages (joint permutes over boundary blocks) -----
        def lin(f, iy, ix):
            return (f * s + iy) * s + ix

        stage_of = {}
        self.cube_perms = []
        for t, stage in enumerate(schedule):
            perm = []
            for link, back in stage:
                for lk, other, isl in ((link, back, True),
                                       (back, link, False)):
                    for k in range(s):
                        kk = s - 1 - k if lk.reversed_ else k
                        src = lin(lk.face, *_block_coords(lk.edge, k, s))
                        dst = lin(lk.nbr_face,
                                  *_block_coords(lk.nbr_edge, kk, s))
                        perm.append((src, dst))
                        iy, ix = _block_coords(lk.edge, k, s)
                        stage_of[(lk.face, iy, ix, lk.edge)] = (
                            t, link, back, isl, k, kk)
            assert len(set(d for _, d in perm)) == len(perm)
            self.cube_perms.append(perm)

        # ---- per-device tables ------------------------------------------
        from ..ops.pallas.swe_cov import _rotation_tables

        T_all = np.asarray(_rotation_tables(grid))   # (4, 6, 4, halo, n)
        gaa_xf = np.asarray(grid.ginv_aa_xf)
        gab_xf = np.asarray(grid.ginv_ab_xf)
        gab_yf = np.asarray(grid.ginv_ab_yf)
        gbb_yf = np.asarray(grid.ginv_bb_yf)

        def met_seg(face, edge, iy, ix):
            """(2, n_loc) metric rows of block (iy, ix)'s ``edge``."""
            if edge in (EDGE_W, EDGE_E):
                fi = i0f + (ix if edge == EDGE_W else ix + 1) * n_loc
                r0, r1 = i0f + iy * n_loc, i0f + (iy + 1) * n_loc
                return np.stack([gaa_xf[face, r0:r1, fi],
                                 gab_xf[face, r0:r1, fi]])
            fi = i0f + (iy if edge == EDGE_S else iy + 1) * n_loc
            c0, c1 = i0f + ix * n_loc, i0f + (ix + 1) * n_loc
            return np.stack([gab_yf[face, fi, c0:c1],
                             gbb_yf[face, fi, c0:c1]])

        edge_sel = np.zeros((6, s, s, nst), np.int32)
        active = np.zeros((6, s, s, nst), np.float32)
        rev_sel = np.zeros((6, s, s, nst), np.float32)
        is_link = np.zeros((6, s, s, nst), np.float32)
        s_link = np.zeros((6, s, s, nst), np.float32)
        s_back = np.zeros((6, s, s, nst), np.float32)
        T_mine = np.zeros((6, s, s, nst, 4, halo, n_loc), np.float32)
        T_oadj = np.zeros((6, s, s, nst, 4, n_loc), np.float32)
        met_mine = np.zeros((6, s, s, nst, 2, n_loc), np.float32)
        met_oth = np.zeros((6, s, s, nst, 2, n_loc), np.float32)
        met_edge = np.zeros((6, s, s, 4, 2, n_loc), np.float32)

        for f in range(6):
            for iy in range(s):
                for ix in range(s):
                    for e in range(4):
                        met_edge[f, iy, ix, e] = met_seg(f, e, iy, ix)

        for (f, iy, ix, e), (t, link, back, isl, k, kk) in stage_of.items():
            other = back if isl else link
            seg = slice(k * n_loc, (k + 1) * n_loc)
            oseg = slice(kk * n_loc, (kk + 1) * n_loc)
            edge_sel[f, iy, ix, t] = e
            active[f, iy, ix, t] = 1.0
            rev_sel[f, iy, ix, t] = float(link.reversed_)
            is_link[f, iy, ix, t] = float(isl)
            s_link[f, iy, ix, t] = _OUT_SIGN[link.edge]
            s_back[f, iy, ix, t] = _OUT_SIGN[back.edge]
            T_mine[f, iy, ix, t] = T_all[:, f, e][:, :, seg]
            T_oadj[f, iy, ix, t] = T_all[:, other.face, other.edge][
                :, 0, oseg]
            met_mine[f, iy, ix, t] = met_edge[f, iy, ix, e]
            oy, ox = _block_coords(other.edge, kk, s)
            met_oth[f, iy, ix, t] = met_seg(other.face, other.edge, oy, ox)

        # ---- corner-ghost routing (nu4 / Laplacian support) -------------
        # The Laplace-Beltrami cross-terms read the h x h ghost corners.
        # On the block mesh every corner ghost is the END PATCH of some
        # neighbor's already-filled edge-ghost strip: the x-neighbor's
        # S/N strip end for interior columns, the y-neighbor's W/E strip
        # end on the panel-edge columns (where the x-neighbor is across
        # a cube edge and the strip itself already carries the rotated
        # data), and the face-local average at true cube corners —
        # exactly the whole-face oracle's structure.  One-hot source
        # masks per corner in [SW, SE, NW, NE] order:
        use_x = np.zeros((6, s, s, 4), np.float32)
        use_y = np.zeros((6, s, s, 4), np.float32)
        use_avg = np.zeros((6, s, s, 4), np.float32)
        for iy in range(s):
            for ix in range(s):
                for c, (xdir, ydir) in enumerate(
                        [(-1, -1), (+1, -1), (-1, +1), (+1, +1)]):
                    has_x = (ix > 0) if xdir < 0 else (ix < s - 1)
                    has_y = (iy > 0) if ydir < 0 else (iy < s - 1)
                    if has_x:
                        use_x[:, iy, ix, c] = 1.0
                    elif has_y:
                        use_y[:, iy, ix, c] = 1.0
                    else:
                        use_avg[:, iy, ix, c] = 1.0

        # ---- per-device coordinates and frames --------------------------
        ac, af, _ = extended_coords(n, halo)
        xr = np.zeros((6, s, s, 1, n_loc + 2 * halo), np.float32)
        xfr = np.zeros_like(xr)
        yc = np.zeros((6, s, s, n_loc + 2 * halo, 1), np.float32)
        yfc = np.zeros_like(yc)
        for iy in range(s):
            for ix in range(s):
                cseg = slice(ix * n_loc, ix * n_loc + n_loc + 2 * halo)
                rseg = slice(iy * n_loc, iy * n_loc + n_loc + 2 * halo)
                xr[:, iy, ix, 0, :] = np.tan(ac[cseg])
                xfr[:, iy, ix, 0, :] = np.tan(af[cseg])
                yc[:, iy, ix, :, 0] = np.tan(ac[rseg])
                yfc[:, iy, ix, :, 0] = np.tan(af[rseg])
        fz = np.broadcast_to(
            np.asarray(FACE_AXES, np.float32)[:, None, None, None, :, 2],
            (6, s, s, 1, 3)).copy()

        self.tables = {
            "edge_sel": jnp.asarray(edge_sel),
            "active": jnp.asarray(active),
            "rev_sel": jnp.asarray(rev_sel),
            "is_link": jnp.asarray(is_link),
            "s_link": jnp.asarray(s_link),
            "s_back": jnp.asarray(s_back),
            "T_mine": jnp.asarray(T_mine),
            "T_oadj": jnp.asarray(T_oadj),
            "met_mine": jnp.asarray(met_mine),
            "met_oth": jnp.asarray(met_oth),
            "met_edge": jnp.asarray(met_edge),
            "xr": jnp.asarray(xr),
            "xfr": jnp.asarray(xfr),
            "yc": jnp.asarray(yc),
            "yfc": jnp.asarray(yfc),
            "fz": jnp.asarray(fz),
            "corner_use_x": jnp.asarray(use_x),
            "corner_use_y": jnp.asarray(use_y),
            "corner_use_avg": jnp.asarray(use_avg),
        }


def _flip(row, rev):
    return jnp.where(rev > 0.5, jnp.flip(row, axis=-1), row)


def make_cov_block_exchange_phases(program: CovBlockProgram):
    """``(start, finish)`` — the block exchange split at the wire.

    Every payload (intra-panel neighbor shifts AND cube-edge stages) is
    a function of the block's pre-exchange boundary strips, read once —
    so ``start`` issues all of them immediately and ``finish`` applies
    the ghost writes plus both seam-normal algebras.  The overlapped
    stepper runs the interior-only RHS kernel between the two (see
    :func:`jaxstream.parallel.shard_cov.make_cov_shard_exchange_phases`
    for the face-tier twin).
    """
    n, halo = program.n_loc, program.halo
    joint = program.axis_names

    def start(h_blk, u_blk, t):
        def tt(name):
            v = t[name]
            return v.reshape(v.shape[3:])      # drop (1, 1, 1) device dims

        hs = jnp.stack([read_strip(h_blk, 0, e, halo, n)
                        for e in range(4)])                  # (4, halo, n)
        us = jnp.stack([read_strip(u_blk, 0, e, halo, n)
                        for e in range(4)], axis=1)          # (2, 4, halo, n)

        intra = []
        for axname, perm, e_send, e_recv in program.intra_perms:
            if not perm:
                continue
            payload = jnp.concatenate(
                [hs[e_send][None], us[:, e_send]])           # (3, halo, n)
            intra.append((e_recv, lax.ppermute(payload, axname, perm)))

        cube = []
        for st, perm in enumerate(program.cube_perms):
            rows = tuple(tt(name)[st] for name in CUBE_ROW_NAMES)
            e_s, rev = rows[0], rows[1]
            act = tt("active")[st]
            u_send = jnp.take(us, e_s, axis=1)
            payload = _flip(jnp.concatenate(
                [jnp.take(hs, e_s, axis=0)[None], u_send]), rev)
            cube.append((lax.ppermute(payload, joint, perm),
                         u_send, rows, act))
        return us, intra, cube

    def finish(h_blk, u_blk, t, phase):
        def tt(name):
            v = t[name]
            return v.reshape(v.shape[3:])

        us, intra, cube = phase
        sym = jnp.zeros((4, n), jnp.float32)
        met_edge = tt("met_edge")                            # (4, 2, n)

        # ---- intra-panel neighbors (same basis; no rotation) ------------
        writers = [lambda b, st, e=e: write_strip(b, 0, e, st)
                   for e in range(4)] + [lambda b, st: b]
        for e_recv, recv in intra:
            blk3 = jnp.concatenate([h_blk[None], u_blk], axis=0)
            blk3 = writers[e_recv](blk3, recv)
            h_blk = blk3[0]
            u_blk = blk3[1:3]
            # Shared seam normal: 0.5*(mine + theirs) is commutative, so
            # both sides compute the identical value with identical
            # metric rows — no pair algebra needed off the cube edges.
            ubar = 0.5 * (us[:, e_recv, 0, :] + recv[1:3, 0, :])
            n_seam = (met_edge[e_recv, 0] * ubar[0]
                      + met_edge[e_recv, 1] * ubar[1])
            sym = jnp.where((jnp.arange(4) == e_recv)[:, None],
                            n_seam[None], sym)

        # ---- cube-edge stages (shared seam algebra, shard_cov.py) -------
        for recv, u_send, rows, act in cube:
            e_s = rows[0]
            h_blk, u_blk, mine = apply_cov_cube_recv(
                h_blk, u_blk, u_send, recv, rows,
                jnp.where(act > 0.5, e_s, 4))
            sym = jnp.where(
                ((jnp.arange(4) == e_s) & (act > 0.5))[:, None],
                mine[None], sym)

        sym_sn = jnp.stack([sym[EDGE_S], sym[EDGE_N]])[None]     # (1, 2, n)
        sym_we = jnp.stack([sym[EDGE_W], sym[EDGE_E]], axis=-1)[None]
        return h_blk, u_blk, sym_sn, sym_we

    return start, finish


def make_cov_block_exchange(program: CovBlockProgram):
    """``exchange(h_blk, u_blk, t) -> (h_blk, u_blk, sym_sn, sym_we)``.

    Local function for ``shard_map`` over the ``(6, s, s)`` mesh; the
    blocks are local ``(1, m_loc, m_loc)`` / ``(2, 1, m_loc, m_loc)``
    and ``t`` holds this device's table rows (leading dims 1).
    """
    start, finish = make_cov_block_exchange_phases(program)

    def exchange(h_blk, u_blk, t):
        return finish(h_blk, u_blk, t, start(h_blk, u_blk, t))

    return exchange


def make_cov_block_exchange_batched(program: CovBlockProgram):
    """Batched ensemble form of :func:`make_cov_block_exchange`.

    ``exchange(h_blk, u_blk, t) -> (h_blk, u_blk, sym_sn, sym_we)`` over
    member-batched local blocks ``(B, 1, m_loc, m_loc)`` /
    ``(2, B, 1, m_loc, m_loc)`` — ``jax.vmap`` of the single-member
    block exchange, so every intra-panel neighbor shift AND cube-edge
    schedule stage issues ONE ``ppermute`` carrying all members' strips
    stacked ``(B, 3, halo, n_loc)``.  Per-member ghosts/seam normals are
    bitwise the per-member loop's (the receive algebra vmaps
    elementwise); the collective launch count per ensemble step drops
    B-fold at unchanged per-member wire bytes — the block-mesh face of
    the batched-exchange design (see shard_cov.py's twin).
    """
    exchange1 = make_cov_block_exchange(program)
    return jax.vmap(exchange1, in_axes=(0, 1, None),
                    out_axes=(0, 1, 0, 0))


def make_block_corner_fill(program: CovBlockProgram):
    """``corner_fill(blk3, t) -> blk3`` — fill the four h x h ghost
    corners of a stacked ``(3, m_loc, m_loc)`` block (h, u_a, u_b) from
    the neighbors' edge-ghost strip end patches (see the corner-routing
    tables in :class:`CovBlockProgram`).  Requires the edge ghosts to be
    filled first; needed only by corner-reading stencils (the nu4
    Laplacians — the dimension-split advective stencils never look)."""
    n, h = program.n_loc, program.halo
    i0, i1 = h, h + n
    _, ax_y, ax_x = program.axis_names
    # Same intra-panel shift perms the main exchange uses (s >= 2 is
    # enforced by the stepper factory).
    fwd = [(i, i + 1) for i in range(program.s - 1)]
    bwd = [(i + 1, i) for i in range(program.s - 1)]

    def corner_fill(blk3, t):
        def tt(name):
            v = t[name]
            return v.reshape(v.shape[3:])

        ux = tt("corner_use_x")          # (4,) one-hot per corner
        uy = tt("corner_use_y")
        ua = tt("corner_use_avg")

        S = blk3[:, 0:h, i0:i1]
        N = blk3[:, i1:i1 + h, i0:i1]
        W = blk3[:, i0:i1, 0:h]
        E = blk3[:, i0:i1, i1:i1 + h]
        # E-ends of my S/N strips -> (ix+1)'s west corners, etc.
        rx_w = lax.ppermute(jnp.stack([S[:, :, n - h:],
                                       N[:, :, n - h:]]), ax_x, fwd)
        rx_e = lax.ppermute(jnp.stack([S[:, :, :h],
                                       N[:, :, :h]]), ax_x, bwd)
        ry_s = lax.ppermute(jnp.stack([W[:, n - h:, :],
                                       E[:, n - h:, :]]), ax_y, fwd)
        ry_n = lax.ppermute(jnp.stack([W[:, :h, :],
                                       E[:, :h, :]]), ax_y, bwd)

        # Face-local averages (the oracle's cube-corner treatment; same
        # formulas as ops.pallas.swe_cov._make_fill corners=True).
        a_sw = 0.5 * (blk3[:, 0:h, i0:i0 + 1] + blk3[:, i0:i0 + 1, 0:h])
        a_se = 0.5 * (blk3[:, 0:h, i1 - 1:i1] + blk3[:, i0:i0 + 1, i1:i1 + h])
        a_nw = 0.5 * (blk3[:, i1:i1 + h, i0:i0 + 1] + blk3[:, i1 - 1:i1, 0:h])
        a_ne = 0.5 * (blk3[:, i1:i1 + h, i1 - 1:i1]
                      + blk3[:, i1 - 1:i1, i1:i1 + h])

        cands = [
            (0, slice(0, h), slice(0, h), rx_w[0], ry_s[0], a_sw),
            (1, slice(0, h), slice(i1, i1 + h), rx_e[0], ry_s[1], a_se),
            (2, slice(i1, i1 + h), slice(0, h), rx_w[1], ry_n[0], a_nw),
            (3, slice(i1, i1 + h), slice(i1, i1 + h), rx_e[1], ry_n[1],
             a_ne),
        ]
        for c, rs, cs, xv, yv, av in cands:
            val = ux[c] * xv + uy[c] * yv + ua[c] * av
            blk3 = blk3.at[:, rs, cs].set(val)
        return blk3

    return corner_fill


def make_sharded_cov_block_stepper(model, setup, dt: float, overlap=None,
                                   temporal_block: int = 1,
                                   donate: bool = False):
    """``step(state, t) -> state`` for the covariant model on (6, s, s).

    State is the usual interior pytree ``{"h": (6, n, n),
    "u": (2, 6, n, n)}`` sharded over all three mesh axes.  ``nu4 > 0``
    runs the exchange-lap-exchange-lap del^4 structure of the face tier
    (shard_cov.py), with the Laplacians' corner ghosts delivered by
    :func:`make_block_corner_fill` (neighbor strip end-patches; cube
    corners averaged face-locally like the oracle).

    ``overlap`` (default: the setup's ``overlap_exchange`` flag): issue
    every neighbor/cube-edge ppermute first, run the interior-only RHS
    kernel on the block's ghost-free (n_loc-2h)^2 core while the
    collectives are in flight, and finish with the boundary-band pass
    (interior/band split of :mod:`jaxstream.ops.pallas.swe_cov`, same
    schedule as the face tier).  Requires ``n_loc > 2*halo``.

    ``temporal_block = k > 1``: k steps fused inside ONE shard_map body
    per call (``steps_per_call`` attribute set) — one SPMD dispatch per
    k steps, exchange data unchanged.  Exact by construction (same ops
    per step; XLA cross-step re-fusion moves single ulps, the same
    <= 1e-6 multi-step budget as the overlap split), unlike the face
    tier's deep-halo form: the block mesh's sub-panel seams would be
    exact under redundant recompute, but its cube-edge blocks carry the
    panel-seam O(d^2) continuation problem plus an along-edge widening
    of every deep strip into the neighbor blocks — the fused form keeps
    this tier in the bitwise-reference family instead (composes with
    ``overlap``, which already hides most of the per-stage latency).
    """
    if temporal_block < 1:
        raise ValueError(
            f"temporal_block must be >= 1, got {temporal_block}")
    grid = model.grid
    s = setup.sy
    if setup.mesh is None or setup.panel != 6 or setup.sy != setup.sx \
            or s < 2:
        raise ValueError(
            f"covariant block path needs a (panel=6, s, s) mesh with "
            f"s >= 2; got panel={setup.panel}, y={setup.sy}, x={setup.sx}"
        )
    if overlap is None:
        overlap = getattr(setup, "overlap_exchange", False)
    mesh = setup.mesh
    halo = grid.halo
    program = CovBlockProgram(grid, s)
    n_loc = program.n_loc
    exchange = make_cov_block_exchange(program)
    interpret = pallas_interpret_for(mesh.devices.flat[0].platform)

    from ..ops.pallas.swe_cov import make_cov_rhs_pallas_local

    rhs_local = make_cov_rhs_pallas_local(
        n_loc, halo, float(grid.dalpha), float(grid.radius),
        model.gravity, model.omega, scheme=model.scheme,
        limiter=model.limiter, interpret=interpret,
    )
    if overlap:
        from ..ops.pallas.swe_cov import (make_cov_rhs_band_local,
                                          make_cov_rhs_interior_local)

        ex_start, ex_finish = make_cov_block_exchange_phases(program)
        rhs_interior = make_cov_rhs_interior_local(
            n_loc, halo, float(grid.dalpha), float(grid.radius),
            model.gravity, model.omega, scheme=model.scheme,
            limiter=model.limiter, interpret=interpret)
        rhs_band = make_cov_rhs_band_local(
            n_loc, halo, float(grid.dalpha), float(grid.radius),
            model.gravity, model.omega, scheme=model.scheme,
            limiter=model.limiter)

    axes = mesh.axis_names
    pstate = {"h": P(*axes), "u": P(None, *axes)}
    ptab = {k: P(axes[0], axes[1], axes[2])
            for k in program.tables}

    # Static per-block b: overlapping extended blocks cannot come from
    # plain sharding, so pre-slice them host-side into a (6, s, s,
    # m_loc, m_loc) table sharded like everything else.
    m_loc = n_loc + 2 * halo
    b_np = np.asarray(model.b_ext)
    b_blocks = np.zeros((6, s, s, m_loc, m_loc), np.float32)
    for iy in range(s):
        for ix in range(s):
            b_blocks[:, iy, ix] = b_np[
                :, iy * n_loc : iy * n_loc + m_loc,
                ix * n_loc : ix * n_loc + m_loc]
    b_blocks = jnp.asarray(b_blocks)

    def embed(x):
        pad = [(0, 0)] * (x.ndim - 2) + [(halo, halo), (halo, halo)]
        return jnp.pad(x, pad)

    nu4 = float(getattr(model, "nu4", 0.0))
    if nu4 != 0.0:
        from ..ops.pallas.swe_cov import lap_core

        corner_fill = make_block_corner_fill(program)

    def body(state, tabs, b_loc):
        fz = tabs["fz"].reshape(1, 1, 3)
        xr = tabs["xr"].reshape(1, m_loc)
        xfr = tabs["xfr"].reshape(1, m_loc)
        yc = tabs["yc"].reshape(m_loc, 1)
        yfc = tabs["yfc"].reshape(m_loc, 1)
        b_e = b_loc.reshape(1, m_loc, m_loc)

        def f(h_int, u_int):
            h_e = embed(h_int)
            u_e = embed(u_int)
            if overlap:
                # Wire first: every payload is a function of the
                # pre-exchange strips, so the interior kernel overlaps
                # all in-flight collectives; the band pass consumes the
                # received strips afterwards.
                phase = ex_start(h_e, u_e, tabs)
                i0, i1 = halo, halo + n_loc
                dh_c, du_c = rhs_interior(
                    fz, xr[:, i0:i1], xfr[:, i0:i1], yc[i0:i1],
                    yfc[i0:i1], h_int, u_int, b_e[:, i0:i1, i0:i1])
                h_e, u_e, ssn, swe = ex_finish(h_e, u_e, tabs, phase)
                dh, du = rhs_band(fz, xr, xfr, yc, yfc, h_e, u_e, b_e,
                                  ssn, swe, dh_c, du_c)
            else:
                h_e, u_e, ssn, swe = exchange(h_e, u_e, tabs)
                dh, du = rhs_local(fz, xr, xfr, yc, yfc, h_e, u_e, b_e,
                                   ssn, swe)
            if nu4 != 0.0:
                # del^4 = lap(lap(.)) with an exchanged refill between —
                # the face tier's structure (shard_cov.py), per-block
                # runtime coordinates, corners from the neighbor-patch
                # pass (lap of a covariant pair IS a covariant pair, so
                # the same exchange applies to l1).
                def lap3(he, ue):
                    blk3 = corner_fill(
                        jnp.concatenate([he, ue[:, 0]], axis=0), tabs)
                    lap = lambda a: lap_core(
                        xr, xfr, yc, yfc, a, n=n_loc, halo=halo,
                        d=float(grid.dalpha), radius=float(grid.radius))
                    return (lap(blk3[0])[None],
                            jnp.stack([lap(blk3[1])[None],
                                       lap(blk3[2])[None]]))

                l1h, l1u = lap3(h_e, u_e)
                l1h_e, l1u_e, _, _ = exchange(embed(l1h), embed(l1u),
                                              tabs)
                l2h, l2u = lap3(l1h_e, l1u_e)
                dh = dh - nu4 * l2h
                du = du - nu4 * l2u
            return dh, du

        for _ in range(temporal_block):
            state = ssprk3_sharded_body(f, state, dt)
        return state

    shard_body = shard_map(
        body, mesh=mesh,
        in_specs=(pstate, ptab, P(*axes)),
        out_specs=pstate,
        check_vma=False,
    )

    tables = {
        k: jax.device_put(v, NamedSharding(mesh, ptab[k]))
        for k, v in program.tables.items()
    }
    b_sh = jax.device_put(b_blocks, NamedSharding(mesh, P(*axes)))

    jitted = jax.jit(lambda state: shard_body(state, tables, b_sh),
                     donate_argnums=(0,) if donate else ())

    def step(state, t):
        del t
        return jitted(state)

    if temporal_block > 1:
        step.steps_per_call = temporal_block
    return step
