"""Explicit shard_map stepper for the covariant SWE formulation.

The multi-chip form of the flagship path: one cube face per device on the
``'panel'`` mesh axis, the fused covariant Pallas RHS kernel running
per-device, and the halo exchange hand-scheduled as the reference's four
race-free stages (deck p.9), each ONE bijective ``lax.ppermute`` over ICI
carrying a single ``(3, halo, n)`` payload — the h strip and both
covariant velocity components together.

Covariant components transform between panel bases, so the receiver
rotates the incoming velocity strips through precomputed per-ghost-slot
2x2 entries (``T[i][j] = e_i^local . a_j^nbr`` — the strip form of
``jaxstream.parallel.vector_halo``'s rotation, built from the same grid
bases, hence bitwise-equal ghosts).  Per-device variation (which edge
exchanges in which stage, reversal flags, rotation entries, edge metric
rows) is carried as *data* sharded ``P('panel')``; the SPMD program is
uniform (same technique as :mod:`jaxstream.parallel.shard_halo`).

Panel-seam conservation: each device also reconstructs, from the same
exchanged strips, BOTH panels' edge-normal velocities and applies the
canonical (link, back) symmetrization algebra of
:func:`jaxstream.ops.pallas.swe_cov._symmetrized_strips` — both sides of
an edge evaluate identical expressions on identical operands, so their
edge fluxes agree bitwise and mass is conserved to roundoff across
devices, matching the single-device fused stepper.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.jax_compat import named_scope

from ..geometry.connectivity import (
    EDGE_E,
    EDGE_N,
    EDGE_S,
    EDGE_W,
    build_connectivity,
    build_schedule,
)
from ..geometry.cubed_sphere import FACE_AXES
from .halo import read_strip, write_strip

__all__ = ["CovShardProgram", "make_cov_shard_exchange",
           "make_cov_shard_exchange_phases",
           "make_cov_shard_exchange_batched",
           "make_sharded_cov_stepper", "make_sharded_cov_deep_stepper",
           "make_sharded_cov_ensemble_stepper", "deep_extend_static",
           "pallas_interpret_for"]

_OUT_SIGN = {EDGE_S: -1.0, EDGE_W: -1.0, EDGE_N: 1.0, EDGE_E: 1.0}


class CovShardProgram:
    """Static ppermute schedule + per-device parameter tables.

    All ``(6, ...)`` tables shard ``P('panel')`` so each device reads its
    own face's rows; everything else about the program is uniform.

    Tables (nstages = 4; leading axis = face):
      edge_sel (6, 4) i32      — my edge exchanging in stage s
      rev_sel  (6, 4) f32 0/1  — pair reverses along-edge order
      is_link  (6, 4) f32 0/1  — am I the pair's canonical 'link' side
      s_link / s_back (6, 4)   — OUT_SIGN of the link / back edge
      T_mine   (6, 4, 4, halo, n) — ghost rotation entries (i*2+j),
                 canonical layout, input = received (my-order) raw comps
      T_oadj   (6, 4, 4, n)    — the OTHER face's adjacent-slot entries
      met_mine / met_oth (6, 4, 2, n) — (m0, m1) edge-face inverse-metric
                 rows of my / the other edge, from the grid's stored
                 metric (oracle-bitwise normals)
    """

    def __init__(self, grid, axis_name: str = "panel"):
        n, halo, m = grid.n, grid.halo, grid.m
        i0, i1 = halo, halo + n
        adj = build_connectivity()
        schedule = build_schedule(adj)
        self.axis_name = axis_name
        self.n, self.halo = n, halo

        self.perms = []
        stage_of = {}
        for s, stage in enumerate(schedule):
            perm = []
            for link, back in stage:
                perm.append((link.face, link.nbr_face))
                perm.append((back.face, back.nbr_face))
                stage_of[(link.face, link.edge)] = (s, link, back, True)
                stage_of[(back.face, back.edge)] = (s, link, back, False)
            self.perms.append(perm)

        # One source of truth for the rotation convention: the fused
        # stepper's canonical tables, sliced per (face, edge).
        from ..ops.pallas.swe_cov import _rotation_tables

        T_all = np.asarray(_rotation_tables(grid))   # (4, 6, 4, halo, n)

        gaa_xf = np.asarray(grid.ginv_aa_xf)
        gab_xf = np.asarray(grid.ginv_ab_xf)
        gab_yf = np.asarray(grid.ginv_ab_yf)
        gbb_yf = np.asarray(grid.ginv_bb_yf)

        def met_of(face, edge):
            if edge in (EDGE_W, EDGE_E):
                fi = i0 if edge == EDGE_W else i1
                return np.stack([gaa_xf[face, i0:i1, fi],
                                 gab_xf[face, i0:i1, fi]])
            fi = i0 if edge == EDGE_S else i1
            return np.stack([gab_yf[face, fi, i0:i1],
                             gbb_yf[face, fi, i0:i1]])

        nst = len(schedule)
        edge_sel = np.zeros((6, nst), np.int32)
        rev_sel = np.zeros((6, nst), np.float32)
        is_link = np.zeros((6, nst), np.float32)
        s_link = np.zeros((6, nst), np.float32)
        s_back = np.zeros((6, nst), np.float32)
        T_mine = np.zeros((6, nst, 4, halo, n), np.float32)
        T_oadj = np.zeros((6, nst, 4, n), np.float32)
        met_mine = np.zeros((6, nst, 2, n), np.float32)
        met_oth = np.zeros((6, nst, 2, n), np.float32)

        for (f, e), (s, link, back, mine_is_link) in stage_of.items():
            other = back if mine_is_link else link
            edge_sel[f, s] = e
            rev_sel[f, s] = float(link.reversed_)
            is_link[f, s] = float(mine_is_link)
            s_link[f, s] = _OUT_SIGN[link.edge]
            s_back[f, s] = _OUT_SIGN[back.edge]
            T_mine[f, s] = T_all[:, f, e]
            T_oadj[f, s] = T_all[:, other.face, other.edge][:, 0, :]
            met_mine[f, s] = met_of(f, e)
            met_oth[f, s] = met_of(other.face, other.edge)

        self.tables = {
            "edge_sel": jnp.asarray(edge_sel),
            "rev_sel": jnp.asarray(rev_sel),
            "is_link": jnp.asarray(is_link),
            "s_link": jnp.asarray(s_link),
            "s_back": jnp.asarray(s_back),
            "T_mine": jnp.asarray(T_mine),
            "T_oadj": jnp.asarray(T_oadj),
            "met_mine": jnp.asarray(met_mine),
            "met_oth": jnp.asarray(met_oth),
        }


def _maybe_flip(row, rev):
    return jnp.where(rev > 0.5, jnp.flip(row, axis=-1), row)


#: Table-row order expected by :func:`apply_cov_cube_recv`.
CUBE_ROW_NAMES = ("edge_sel", "rev_sel", "is_link", "s_link", "s_back",
                  "T_mine", "T_oadj", "met_mine", "met_oth")


def apply_cov_cube_recv(h_blk, u_blk, u_send, recv, rows, write_idx):
    """Shared cube-edge receive: rotate, write ghosts, symmetrize.

    The bitwise-critical half of a cube-edge exchange stage, common to
    the one-face-per-device and block-mesh paths (one source of truth
    for the seam-conservation algebra).  ``rows`` are this device's
    table values in :data:`CUBE_ROW_NAMES` order; ``write_idx`` selects
    the ghost edge to write (4 = inactive no-op, used by boundary
    gating on the block mesh).  Returns ``(h_blk, u_blk, mine)`` with
    ``mine`` the symmetrized edge-normal strip of this stage's edge —
    both sides of the physical edge compute it bitwise-equal.
    """
    e_s, rev, isl, sl, sb, Tm, To, mm, mo = rows
    del e_s

    gu0 = Tm[0] * recv[1] + Tm[1] * recv[2]
    gu1 = Tm[2] * recv[1] + Tm[3] * recv[2]
    writers = [functools.partial(write_strip, face=0, edge=e)
               for e in range(4)] + [lambda b, strip: b]
    ghost = jnp.stack([recv[0], gu0, gu1])           # (3, halo, n)
    blk3 = jnp.concatenate([h_blk[None], u_blk], axis=0)
    blk3 = lax.switch(
        write_idx, [lambda b, st, w=w: w(b, strip=st) for w in writers],
        blk3, ghost,
    )
    h_blk = blk3[0]
    u_blk = blk3[1:3]

    # --- symmetrized edge normal (bitwise on both sides) ----------------
    int_adj = u_send[:, 0, :]                # my adjacent row, my order
    ghost_adj = jnp.stack([gu0[0], gu1[0]])
    ubar = 0.5 * (int_adj + ghost_adj)
    n_mine = mm[0] * ubar[0] + mm[1] * ubar[1]

    # The other panel's own normal, in ITS canonical order.
    oth_int = _maybe_flip(recv[1:3, 0, :], rev)      # back to its order
    my_adj_f = _maybe_flip(int_adj, rev)             # as it received
    oth_ghost = jnp.stack([
        To[0] * my_adj_f[0] + To[1] * my_adj_f[1],
        To[2] * my_adj_f[0] + To[3] * my_adj_f[1],
    ])
    obar = 0.5 * (oth_int + oth_ghost)
    n_oth = mo[0] * obar[0] + mo[1] * obar[1]

    n_link = jnp.where(isl > 0.5, n_mine, n_oth)
    n_back_lo = jnp.where(isl > 0.5, _maybe_flip(n_oth, rev),
                          _maybe_flip(n_mine, rev))
    avg = 0.5 * (sl * n_link - sb * n_back_lo)
    mine = jnp.where(isl > 0.5, sl * avg,
                     _maybe_flip(sb * (-avg), rev))
    return h_blk, u_blk, mine


def ssprk3_sharded_body(f, state, dt):
    """The explicit paths' shared SSPRK3 stage combination."""
    from ..ops.pallas.swe_step import SSPRK3_COEFFS

    (_, _), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    h0, u0 = state["h"], state["u"]
    dh, du = f(h0, u0)
    h1 = h0 + dt * dh
    u1 = u0 + dt * du
    dh, du = f(h1, u1)
    h2 = a2 * h0 + b2 * (h1 + dt * dh)
    u2 = a2 * u0 + b2 * (u1 + dt * du)
    dh, du = f(h2, u2)
    return {"h": a3 * h0 + b3 * (h2 + dt * dh),
            "u": a3 * u0 + b3 * (u2 + dt * du)}


def make_cov_shard_exchange_phases(program: CovShardProgram):
    """``(start, finish)`` — the cube-edge exchange split at the wire.

    ``start(h_blk, u_blk, t)`` reads the canonical boundary strips ONCE
    (the stages write only the ghost ring, so every payload is a
    function of the pre-exchange state) and issues all four stage
    ``ppermute``s immediately; ``finish(h_blk, u_blk, recvs)`` rotates
    the received strips into ghosts and runs the seam symmetrization.
    Nothing between ``start`` and ``finish`` depends on the collectives,
    so the overlapped stepper runs the interior RHS kernel there and
    XLA's async collectives fly under it.  The serialized
    :func:`make_cov_shard_exchange` is ``finish(.., start(..))`` —
    one exchange implementation, two schedules.
    """
    n, halo = program.n, program.halo
    axis = program.axis_name

    def start(h_blk, u_blk, t):
        # Canonical strips for every edge, read once: the stages write
        # only the ghost ring, so the interior strips are loop-invariant.
        with named_scope("exchange_start"):
            hs = jnp.stack([read_strip(h_blk, 0, e, halo, n)
                            for e in range(4)])              # (4, halo, n)
            us = jnp.stack([read_strip(u_blk, 0, e, halo, n)
                            for e in range(4)], axis=1)      # (2, 4, halo, n)
            recvs = []
            for s, perm in enumerate(program.perms):
                rows = tuple(t[name][0, s] for name in CUBE_ROW_NAMES)
                e_s, rev = rows[0], rows[1]
                h_send = jnp.take(hs, e_s, axis=0)
                u_send = jnp.take(us, e_s, axis=1)
                payload = jnp.concatenate([h_send[None], u_send])
                payload = _maybe_flip(payload, rev)        # (3, halo, n)
                recvs.append(
                    (lax.ppermute(payload, axis, perm), u_send, rows))
            return recvs

    def finish(h_blk, u_blk, recvs):
        with named_scope("exchange_finish"):
            sym = jnp.zeros((4, n), jnp.float32)
            for recv, u_send, rows in recvs:
                e_s = rows[0]
                h_blk, u_blk, mine = apply_cov_cube_recv(
                    h_blk, u_blk, u_send, recv, rows, e_s)
                sym = jnp.where(
                    (jnp.arange(4) == e_s)[:, None], mine[None], sym)

            sym_sn = jnp.stack([sym[EDGE_S], sym[EDGE_N]])[None]  # (1, 2, n)
            sym_we = jnp.stack([sym[EDGE_W], sym[EDGE_E]], axis=-1)[None]
            return h_blk, u_blk, sym_sn, sym_we

    return start, finish


def make_cov_shard_exchange(program: CovShardProgram):
    """``exchange(h_blk, u_blk, t) -> (h_blk, u_blk, sym_sn, sym_we)``.

    Local function for use inside ``shard_map`` (one face per device).
    ``h_blk``: (1, M, M); ``u_blk``: (2, 1, M, M) covariant components in
    this panel's basis; ``t`` the device's table rows (leading axis 1).
    Fills cube-edge ghosts in 4 ppermute stages and returns the
    symmetrized edge-normal strips ``sym_sn (1, 2, n) / sym_we (1, n, 2)``
    for the RHS kernel.
    """
    start, finish = make_cov_shard_exchange_phases(program)

    def exchange(h_blk, u_blk, t):
        return finish(h_blk, u_blk, start(h_blk, u_blk, t))

    return exchange


def make_cov_shard_exchange_batched(program: CovShardProgram):
    """Batched ensemble form of :func:`make_cov_shard_exchange`.

    ``exchange(h_blk, u_blk, t) -> (h_blk, u_blk, sym_sn, sym_we)`` over
    a LOCAL member-batched face block — ``h_blk (B, 1, M, M)``, ``u_blk
    (2, B, 1, M, M)`` — implemented as ``jax.vmap`` of the single-member
    exchange over the member axis.  The payload of each of the 4
    schedule stages batches into ONE ``lax.ppermute`` carrying all
    members' strips stacked as ``(B, 3, halo, n)`` (vmap's collective
    batching rule — verified as exactly 4 ppermute eqns in the jaxpr),
    so the per-stage ICI latency chain is paid once per ensemble step
    instead of once per member: collective launch latency amortizes
    B-fold at unchanged per-member wire bytes.  The receive algebra
    (rotations, ghost writes, seam symmetrization) is mapped per member
    with identical per-element arithmetic, so every member's ghosts and
    sym strips are bitwise-equal to a per-member exchange loop (tested
    in tests/test_ensemble.py).
    """
    return jax.vmap(make_cov_shard_exchange(program),
                    in_axes=(0, 1, None), out_axes=(0, 1, 0, 0))


def deep_extend_static(grid, field_ext, depth: int):
    """Re-extend a static ``(6, M, M)`` field to ghost ``depth``.

    The deep-halo blocked stepper's orography prep: interior values are
    re-embedded at the deeper ring, edge ghosts filled by the plain
    copy exchange at ``depth`` (the same continuation-point assignment
    the state exchange uses), corners by the face-local average.  Pure
    and cheap; run once at stepper-build time.
    """
    from .halo import make_halo_exchanger

    n = grid.n
    if field_ext is None:
        return jnp.zeros((6, n + 2 * depth, n + 2 * depth), jnp.float32)
    b_int = grid.interior(field_ext)
    pad = [(0, 0)] * (b_int.ndim - 2) + [(depth, depth), (depth, depth)]
    return make_halo_exchanger(n, depth)(jnp.pad(b_int, pad))


def make_sharded_cov_deep_stepper(model, setup, dt: float,
                                  temporal_block: int, overlap=None,
                                  donate: bool = False):
    """Temporal halo blocking on the one-face-per-device tier.

    ``block(state, t) -> state`` advancing ``temporal_block = k`` SSPRK3
    steps per call with ONE deep halo exchange per block: the 4
    race-free ppermute stages ship ``(3, 3*k*halo, n)`` strips (same
    wire bytes per simulated step as the serialized path — 3k h-deep
    exchanges collapse into one 3kh-deep exchange — but the per-stage
    ICI latency chain is paid once per k steps instead of 12 times per
    step), and the 3k RK stages then run exchange-free on shrinking
    windows: stage i computes a ``(n + 2*(D - (i+1)h))^2`` window from
    the ``(n + 2*(D - i*h))^2`` one, ``D = 3*k*halo`` — redundant
    ghost-band compute instead of collectives (Putman & Lin 2007's
    ghost-consumption argument applied across stages).

    Composes with ``parallelization.overlap_exchange``: the block's one
    deep exchange is issued through the start/finish phase split, and
    with the flag on, stage 0's ghost-free ``(n-2h)^2`` interior core
    is computed between the phases (it reads no exchanged value), so
    the 4-ppermute chain flies under it; the rest of stage 0 is then
    four rectangular ring windows stitched around the core — the PR-1
    interior/band tiling generalized to the deep window (ulp-level vs
    the single-window evaluation, the established split budget).

    Approximation contract (why this tier is opt-in while the fused
    k-step tiers are exact): panel-seam ghosts are face-local
    *continuations* — the deep copy assigns neighbor values to
    continuation points whose mismatch grows with depth, the band then
    evolves under THIS panel's metric, and the bitwise seam
    symmetrization is dropped (each side would compute it from its own
    drifting band copy anyway).  All three effects are the same O(d^2)
    class as the k=1 path's own ghost resampling, so the blocked
    trajectory is consistent to truncation — but NOT to the 1e-6
    ulp-budget the exact tiers hold, and cross-seam mass conservation
    degrades from roundoff to truncation level.  Corner patches (three
    panels meet; no unique continuation exists) use the face-local
    edge-ghost average of :func:`jaxstream.parallel.halo._fill_corners`
    at depth D.  docs/USAGE.md "Temporal halo blocking" quantifies the
    redundant-compute fraction ``((n + 2*3kh)^2 - n^2) / n^2`` per
    first stage and when k > 1 loses.
    """
    from ..geometry.cubed_sphere import build_grid
    from ..ops.pallas.swe_cov import rhs_core_cov
    from ..ops.pallas.swe_rhs import coord_rows, pick_recon
    from ..ops.pallas.swe_step import SSPRK3_COEFFS
    from .halo import _fill_corners

    grid = model.grid
    n, h = grid.n, grid.halo
    k = int(temporal_block)
    if k < 2:
        raise ValueError(
            f"deep stepper needs temporal_block >= 2, got {k} "
            "(k=1 is make_sharded_cov_stepper's serialized path)")
    S = 3  # SSPRK3 stages per step; each consumes `halo` of validity
    D = S * k * h
    if n < D:
        raise ValueError(
            f"temporal_block={k} needs n >= 3*k*halo = {D} (deep strips "
            f"are read from the interior), got n={n}")
    if float(getattr(model, "nu4", 0.0)) != 0.0:
        raise ValueError(
            "temporal_block > 1 on the face tier supports nu4 = 0 only "
            "(the del^4 refill would need its own deep exchange)")
    if setup.mesh is None or setup.panel != 6 or setup.sy * setup.sx != 1:
        raise ValueError(
            f"deep blocked stepper needs a (panel=6, 1, 1) mesh; got "
            f"panel={setup.panel}, y={setup.sy}, x={setup.sx}")
    mesh = setup.mesh

    # Deep-grid program: CovShardProgram is depth-agnostic — built on a
    # halo=D grid it yields D-deep rotation tables and the same 4-stage
    # schedule, so the exchange phases are reused verbatim.
    gdeep = build_grid(n, halo=D, radius=float(grid.radius),
                       dtype=jnp.float32)
    program = CovShardProgram(gdeep)
    ex_start, ex_finish = make_cov_shard_exchange_phases(program)

    xr, xfr, yc, yfc, _ = coord_rows(n, D)
    b_deep = deep_extend_static(grid, model.b_ext, D)
    frames_z = jnp.asarray(
        np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)

    d = float(grid.dalpha)
    kw = dict(halo=h, d=d, radius=float(grid.radius),
              gravity=model.gravity, omega=model.omega)
    # One reconstruction partial per stage output size (3k shrinking
    # windows); all windows are square so one recon serves both axes.
    recons = [pick_recon(model.scheme, h, n + 2 * (D - (i + 1) * h),
                         model.limiter) for i in range(S * k)]
    if overlap is None:
        overlap = bool(getattr(setup, "overlap_exchange", False))
    if overlap:
        # Stage-0 split extents: interior core (n-2h)^2 plus the ring's
        # S/N rows (depth D, full width) and W/E columns.
        no = n + 2 * (D - h)
        recon_core = pick_recon(model.scheme, h, n - 2 * h, model.limiter)
        recon_D = pick_recon(model.scheme, h, D, model.limiter)
        recon_no = pick_recon(model.scheme, h, no, model.limiter)
    (_, _), (a2, b2), (a3, b3) = SSPRK3_COEFFS

    axes = mesh.axis_names
    pstate = {"h": P(axes[0]), "u": P(None, axes[0])}
    ptab = {kk: P(axes[0]) for kk in program.tables}

    def crop(x, c):
        return x[..., c:x.shape[-2] - c, c:x.shape[-1] - c]

    def body(state, tabs, fz, b_loc):
        def embed(x):
            pad = [(0, 0)] * (x.ndim - 2) + [(D, D), (D, D)]
            return jnp.pad(x, pad)

        h_e = embed(state["h"])              # (1, n+2D, n+2D)
        u_e = embed(state["u"])
        fz3 = (fz[0, 0, 0], fz[0, 0, 1], fz[0, 0, 2])
        b_l = b_loc[0]
        core = None
        if overlap:
            # Wire first: stage 0's ghost-free (n-2h)^2 core reads only
            # interior data, so it runs under the in-flight deep
            # exchange (the PR-1 overlap schedule, once per block).
            recvs = ex_start(h_e, u_e, tabs)
            sl_i = slice(D, D + n)
            core = rhs_core_cov(
                fz3, xr[:, sl_i], xfr[:, sl_i], yc[sl_i], yfc[sl_i],
                state["h"][0], state["u"][0, 0], state["u"][1, 0],
                b_l[sl_i, sl_i], None, None,
                n=(n - 2 * h, n - 2 * h), recon=recon_core, **kw)
            h_e, u_e, _, _ = ex_finish(h_e, u_e, recvs)
        else:
            h_e, u_e, _, _ = ex_finish(h_e, u_e,
                                       ex_start(h_e, u_e, tabs))
        h_e = _fill_corners(h_e, D, n)
        u_e = _fill_corners(u_e, D, n)

        hc, uac, ubc = h_e[0], u_e[0, 0], u_e[1, 0]
        stage = 0

        def rhs_win(hf, ua, ub, i):
            # Validity entering stage i is D - i*h: the operand window
            # is the whole current array; coordinates/orography slice to
            # the matching deep-extended offsets.
            off = i * h
            m_in = n + 2 * (D - i * h)
            nv = m_in - 2 * h
            sl = slice(off, off + m_in)
            return rhs_core_cov(
                fz3, xr[:, sl], xfr[:, sl], yc[sl], yfc[sl],
                hf, ua, ub, b_l[sl, sl], None, None,
                n=(nv, nv), recon=recons[i], **kw)

        def rhs_stage0_ring(hf, ua, ub):
            # Finish stage 0 around the precomputed core: four
            # rectangular windows tile the deep ring exactly (S/N rows
            # own the corners; W/E take the remaining rows), stitched
            # into the full (n + 2*(D-h))^2 stage-0 tendency — the
            # make_cov_rhs_band_local tiling at deep width.
            def win(r0, r1, c0, c1, ry, rx):
                # r0..c1 are OUTPUT ranges in deep coordinates; the
                # operand window extends `h` beyond on every side.
                sr = slice(r0 - h, r1 + h)
                sc = slice(c0 - h, c1 + h)
                return rhs_core_cov(
                    fz3, xr[:, sc], xfr[:, sc], yc[sr], yfc[sr],
                    hf[sr, sc], ua[sr, sc], ub[sr, sc], b_l[sr, sc],
                    None, None, n=(r1 - r0, c1 - c0), recon=(ry, rx),
                    **kw)

            r_lo, r_hi = D + h, D + n - h       # core output rows
            dS = win(h, D + h, h, h + no, recon_D, recon_no)
            dN = win(D + n - h, n + 2 * D - h, h, h + no,
                     recon_D, recon_no)
            dW = win(r_lo, r_hi, h, D + h, recon_core, recon_D)
            dE = win(r_lo, r_hi, D + n - h, n + 2 * D - h,
                     recon_core, recon_D)

            def stitch(i):
                mid = jnp.concatenate([dW[i], core[i], dE[i]], axis=-1)
                return jnp.concatenate([dS[i], mid, dN[i]], axis=-2)

            return stitch(0), stitch(1), stitch(2)

        for j in range(k):
            h0, ua0, ub0 = hc, uac, ubc
            if j == 0 and overlap:
                dh, dua, dub = rhs_stage0_ring(hc, uac, ubc)
            else:
                dh, dua, dub = rhs_win(hc, uac, ubc, stage)
            hc = crop(h0, h) + dt * dh
            uac = crop(ua0, h) + dt * dua
            ubc = crop(ub0, h) + dt * dub
            stage += 1
            dh, dua, dub = rhs_win(hc, uac, ubc, stage)
            hc = a2 * crop(h0, 2 * h) + b2 * (crop(hc, h) + dt * dh)
            uac = a2 * crop(ua0, 2 * h) + b2 * (crop(uac, h) + dt * dua)
            ubc = a2 * crop(ub0, 2 * h) + b2 * (crop(ubc, h) + dt * dub)
            stage += 1
            dh, dua, dub = rhs_win(hc, uac, ubc, stage)
            hc = a3 * crop(h0, 3 * h) + b3 * (crop(hc, h) + dt * dh)
            uac = a3 * crop(ua0, 3 * h) + b3 * (crop(uac, h) + dt * dua)
            ubc = a3 * crop(ub0, 3 * h) + b3 * (crop(ubc, h) + dt * dub)
            stage += 1

        return {"h": hc[None], "u": jnp.stack([uac[None], ubc[None]])}

    shard_body = shard_map(
        body, mesh=mesh,
        in_specs=(pstate, ptab, P(axes[0]), P(axes[0])),
        out_specs=pstate,
        check_vma=False,
    )

    tables = {
        kk: jax.device_put(v, NamedSharding(mesh, P(axes[0])))
        for kk, v in program.tables.items()
    }
    fz_sh = jax.device_put(frames_z, NamedSharding(mesh, P(axes[0])))
    b_sh = jax.device_put(b_deep, NamedSharding(mesh, P(axes[0])))

    jitted = jax.jit(lambda state: shard_body(state, tables, fz_sh, b_sh),
                     donate_argnums=(0,) if donate else ())

    def step(state, t):
        del t
        return jitted(state)

    step.steps_per_call = k
    return step


def pallas_interpret_for(platform: str) -> bool:
    """Whether the sharded tiers' Pallas kernels run interpreted.

    Explicit per platform: compiled Mosaic on ``'tpu'``, the Pallas
    interpreter on ``'cpu'`` (the test tier), and an error anywhere
    else — a device the kernels were never written for must not
    silently run the interpreter.
    """
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"the covariant shard tiers' Pallas kernels run on 'tpu' "
        f"(compiled) or 'cpu' (interpret mode); got platform "
        f"{platform!r}")


def _make_cov_face_rhs(model, grid, program: CovShardProgram, overlap,
                       platform):
    """Per-face local RHS closure of the explicit face tier.

    Returns ``f(h_int, u_int, tabs, fz, b_loc) -> (dh, du)`` — embed,
    4-stage ppermute exchange (phase-split under ``overlap``), fused
    covariant Pallas RHS kernel, optional del^4 — the single source of
    the face-tier stage arithmetic, shared by the serialized/overlapped
    stepper and the batched ensemble stepper (which vmaps it over the
    member axis: the ppermutes batch into single all-member collectives
    and the per-member math stays op-identical).
    """
    halo, n = grid.halo, grid.n
    exchange = make_cov_shard_exchange(program)
    from ..ops.pallas.swe_cov import make_cov_rhs_pallas

    interpret = pallas_interpret_for(platform)
    rhs_local = make_cov_rhs_pallas(
        grid, model.gravity, model.omega, scheme=model.scheme,
        limiter=model.limiter, interpret=interpret,
        n_faces=1, external_sym=True,
    )
    if overlap:
        from ..ops.pallas.swe_cov import (make_cov_rhs_band_local,
                                          make_cov_rhs_interior_local)
        from ..ops.pallas.swe_rhs import coord_rows

        ex_start, ex_finish = make_cov_shard_exchange_phases(program)
        rhs_interior = make_cov_rhs_interior_local(
            n, halo, float(grid.dalpha), float(grid.radius),
            model.gravity, model.omega, scheme=model.scheme,
            limiter=model.limiter, interpret=interpret)
        rhs_band = make_cov_rhs_band_local(
            n, halo, float(grid.dalpha), float(grid.radius),
            model.gravity, model.omega, scheme=model.scheme,
            limiter=model.limiter)
        xr_f, xfr_f, yc_f, yfc_f, _ = coord_rows(n, halo)
        xr_i, xfr_i = xr_f[:, halo:halo + n], xfr_f[:, halo:halo + n]
        yc_i, yfc_i = yc_f[halo:halo + n], yfc_f[halo:halo + n]

    def embed(x):
        pad = [(0, 0)] * (x.ndim - 2) + [(halo, halo), (halo, halo)]
        return jnp.pad(x, pad)

    nu4 = float(getattr(model, "nu4", 0.0))
    if nu4 != 0.0:
        from ..ops.pallas.swe_cov import lap_core
        from ..ops.pallas.swe_rhs import coord_rows
        from .halo import _fill_corners

        x_row, xf_row, x_col, xf_col, _ = coord_rows(grid.n, halo)
        lap1 = functools.partial(
            lap_core, x_row, xf_row, x_col, xf_col,
            n=grid.n, halo=halo, d=float(grid.dalpha),
            radius=float(grid.radius))

    def f(h_int, u_int, tabs, fz, b_loc):
        h_e = embed(h_int)
        u_e = embed(u_int)
        if overlap:
            # Wire first: all 4 stage ppermutes are functions of the
            # pre-exchange strips.  The interior kernel depends on
            # none of them, so the async collectives overlap it; the
            # band pass then consumes the received strips.
            recvs = ex_start(h_e, u_e, tabs)
            with named_scope("rhs_interior"):
                dh_c, du_c = rhs_interior(
                    fz, xr_i, xfr_i, yc_i, yfc_i, h_int, u_int,
                    b_loc[:, halo:halo + n, halo:halo + n])
            h_e, u_e, ssn, swe = ex_finish(h_e, u_e, recvs)
            with named_scope("rhs_band"):
                dh, du = rhs_band(fz, xr_f, xfr_f, yc_f, yfc_f,
                                  h_e, u_e, b_loc, ssn, swe, dh_c, du_c)
        else:
            h_e, u_e, ssn, swe = exchange(h_e, u_e, tabs)
            with named_scope("rhs_face"):
                dh, du = rhs_local(fz, h_e, u_e, b_loc, ssn, swe)
        if nu4 != 0.0:
            # del^4 = lap(lap(.)) with an exchanged refill between,
            # exactly the fused nu4 stepper's structure: the same
            # strip exchange applies (lap of a covariant pair is a
            # covariant pair), and the Laplace-Beltrami cross-terms
            # need the ghost corners (face-local averaging).
            def lap3(he, ue):
                he = _fill_corners(he, halo, grid.n)
                ue = _fill_corners(ue, halo, grid.n)
                return (lap1(he[0])[None],
                        jnp.stack([lap1(ue[0, 0])[None],
                                   lap1(ue[1, 0])[None]]))
            l1h, l1u = lap3(h_e, u_e)
            l1h_e, l1u_e, _, _ = exchange(embed(l1h), embed(l1u), tabs)
            l2h, l2u = lap3(l1h_e, l1u_e)
            dh = dh - nu4 * l2h
            du = du - nu4 * l2u
        return dh, du

    return f


def make_sharded_cov_stepper(model, setup, dt: float, overlap=None,
                             temporal_block: int = 1,
                             donate: bool = False):
    """``step(state, t) -> state`` for the covariant model under shard_map.

    Requires a ``(panel=6, 1, 1)`` mesh (one face per device).  State is
    the usual interior pytree ``{"h": (6, n, n), "u": (2, 6, n, n)}``
    sharded over the panel axis.  Each SSPRK3 stage = one explicit
    4-ppermute exchange + the fused covariant Pallas RHS kernel on the
    local face (interpret mode on CPU) + the stage combination.

    ``overlap`` (default: the setup's ``overlap_exchange`` flag): issue
    the 4 ppermute stages first, run the interior-only RHS kernel (the
    ghost-free (n-2h)^2 core) while the collectives are in flight, then
    consume the received strips in the boundary-band pass — the
    interior/band split of :mod:`jaxstream.ops.pallas.swe_cov`.  The
    split tiles the exact arithmetic of the fused kernel; compiled
    states agree at the ulp level (XLA re-fuses the differently-shaped
    kernels' surroundings — <= 1e-6 relative over the multi-step parity
    runs in tests/test_overlap_exchange.py); only the collective/compute
    overlap differs.

    ``temporal_block = k > 1`` dispatches to
    :func:`make_sharded_cov_deep_stepper`: k steps per call behind ONE
    3*k*halo-deep exchange (see its docstring for the approximation
    contract; the k=1 path here stays the bitwise reference).  The
    ``overlap`` argument is forwarded — there it schedules stage-0's
    ghost-free core under the deep exchange.
    """
    if temporal_block > 1:
        return make_sharded_cov_deep_stepper(model, setup, dt,
                                             temporal_block,
                                             overlap=overlap,
                                             donate=donate)
    grid = model.grid
    if setup.mesh is None or setup.panel != 6 or setup.sy * setup.sx != 1:
        raise ValueError(
            f"explicit covariant shard path needs a (panel=6, 1, 1) mesh; "
            f"got panel={setup.panel}, y={setup.sy}, x={setup.sx}. Use the "
            f"GSPMD path (use_shard_map: false) for other layouts."
        )
    if overlap is None:
        overlap = getattr(setup, "overlap_exchange", False)
    mesh = setup.mesh
    program = CovShardProgram(grid)
    platform = mesh.devices.flat[0].platform
    f_loc = _make_cov_face_rhs(model, grid, program, overlap, platform)
    frames_z = jnp.asarray(
        np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)

    axes = mesh.axis_names                      # ('panel', 'y', 'x')
    pstate = {"h": P(axes[0]), "u": P(None, axes[0])}
    ptab = {k: P(axes[0]) for k in program.tables}

    def body(state, tabs, fz, b_loc):
        return ssprk3_sharded_body(
            lambda h, u: f_loc(h, u, tabs, fz, b_loc), state, dt)

    shard_body = shard_map(
        body, mesh=mesh,
        in_specs=(pstate, ptab, P(axes[0]), P(axes[0])),
        out_specs=pstate,
        check_vma=False,
    )

    tables = {
        k: jax.device_put(v, NamedSharding(mesh, P(axes[0])))
        for k, v in program.tables.items()
    }
    fz_sh = jax.device_put(frames_z, NamedSharding(mesh, P(axes[0])))
    b_sh = jax.device_put(model.b_ext, NamedSharding(mesh, P(axes[0])))

    # donate=True aliases the ping-pong state carry (donate_argnums)
    # so XLA stops double-buffering every prognostic; default off
    # because parity/test callers legitimately step one initial state
    # through several steppers (a donated buffer dies on first use).
    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(state, t):
        del t
        return shard_body(state, tables, fz_sh, b_sh)

    return step


def make_sharded_cov_ensemble_stepper(model, setup, dt: float,
                                      members: int, overlap=None,
                                      temporal_block: int = 1,
                                      donate: bool = False,
                                      wrap_jit: bool = True):
    """Batched ensemble stepper on the explicit covariant face tier.

    ``step(state, t) -> state`` over the batched interior state
    ``{"h": (B, 6, n, n), "u": (2, B, 6, n, n)}`` (member-before-face
    layout), advancing all ``B = members`` perturbed-IC members one
    SSPRK3 step (or ``temporal_block`` exactly-fused steps) per call.

    Execution: the single-member face-tier stage closure
    (:func:`_make_cov_face_rhs` — the serialized/overlapped stepper's
    own arithmetic) is ``jax.vmap``-ed over the member axis inside the
    ``shard_map`` body.  Collective batching turns each of the 4
    schedule stages' ppermutes into ONE collective carrying all local
    members' strips stacked ``(B_loc, 3, halo, n)`` — per-stage launch
    latency is paid once per ensemble step instead of once per member,
    per-member wire bytes unchanged — and the per-face Pallas RHS
    kernel batches into a single launch with a leading member grid
    axis.  Per-member values are bitwise-equal to the single-member
    stepper run B times (vmap maps, it does not reassociate).

    Meshes: the plain face tier ``(panel=6, 1, 1)`` (members stacked
    locally per device) or :func:`..mesh.setup_ensemble_sharding`'s 2-D
    ``('panel', 'member')`` mesh, where each device carries
    ``members / setup.member`` members and the member axis adds zero
    wire traffic.  ``temporal_block = k > 1`` fuses k steps in one
    SPMD dispatch (exact — the face tier's deep-halo approximation is
    NOT applied here; the batched exchange already amortizes the
    latency the deep form trades accuracy for).

    ``wrap_jit=False`` (round 12) returns the raw (untraced) step so a
    caller can compose it inside its OWN compiled loop — the
    continuous-batching server's panel-sharded masked segment traces
    it under one ``jax.jit`` around ``stepping.integrate_masked``,
    where a nested jit boundary would block carry donation and
    sharding propagation; the serving loop's per-member nonfinite
    stream is then a plain GSPMD reduction over the shard_map outputs.
    The closed-over program tables/orography stay the device-put
    ``P('panel')`` constants either way (``donate`` only applies to
    the wrapped jit).
    """
    grid = model.grid
    if setup.mesh is None or setup.panel != 6 or setup.sy * setup.sx != 1:
        raise ValueError(
            f"ensemble face stepper needs a (panel=6, ...) face mesh "
            f"(optionally x member); got panel={setup.panel}, "
            f"y={setup.sy}, x={setup.sx}")
    if members < 1:
        raise ValueError(f"members must be >= 1, got {members}")
    mshard = getattr(setup, "member", 1)
    if members % mshard:
        raise ValueError(
            f"members={members} not divisible by the mesh's member-"
            f"shard count {mshard}")
    if temporal_block < 1:
        raise ValueError(
            f"temporal_block must be >= 1, got {temporal_block}")
    if overlap is None:
        overlap = getattr(setup, "overlap_exchange", False)
    mesh = setup.mesh
    program = CovShardProgram(grid)
    platform = mesh.devices.flat[0].platform
    f_loc = _make_cov_face_rhs(model, grid, program, overlap, platform)
    frames_z = jnp.asarray(
        np.asarray(FACE_AXES)[:, None, :, 2], jnp.float32)

    axes = mesh.axis_names
    member_ax = "member" if "member" in axes else None
    pstate = {"h": P(member_ax, "panel"),
              "u": P(None, member_ax, "panel")}
    ptab = {k: P("panel") for k in program.tables}
    maxes = {"h": 0, "u": 1}

    def body(state, tabs, fz, b_loc):
        def one(st):
            for _ in range(temporal_block):
                st = ssprk3_sharded_body(
                    lambda h, u: f_loc(h, u, tabs, fz, b_loc), st, dt)
            return st

        return jax.vmap(one, in_axes=(maxes,), out_axes=maxes)(state)

    shard_body = shard_map(
        body, mesh=mesh,
        in_specs=(pstate, ptab, P("panel"), P("panel")),
        out_specs=pstate,
        check_vma=False,
    )

    tables = {
        k: jax.device_put(v, NamedSharding(mesh, P("panel")))
        for k, v in program.tables.items()
    }
    fz_sh = jax.device_put(frames_z, NamedSharding(mesh, P("panel")))
    b_sh = jax.device_put(model.b_ext, NamedSharding(mesh, P("panel")))

    if wrap_jit:
        jitted = jax.jit(
            lambda state: shard_body(state, tables, fz_sh, b_sh),
            donate_argnums=(0,) if donate else ())

        def step(state, t):
            del t
            return jitted(state)
    else:
        def step(state, t):
            del t
            return shard_body(state, tables, fz_sh, b_sh)

    step.ensemble = int(members)
    if temporal_block > 1:
        step.steps_per_call = temporal_block
    return step
