"""Fused SSPRK3 stage kernels: RHS + stage combination in one HBM pass.

The headline bottleneck is HBM bandwidth (SURVEY.md §6: FV numerics are
memory-bound, deck p.19).  The straightforward step — embed interior ->
exchange -> RHS kernel -> tree_map axpy per RK stage — moves each field
through HBM several extra times per stage (the embed pad, the tendency
array, and the axpy read-modify-write are all full-field passes).

This module removes all of them.  State is carried *extended* (ghosts
included, ``(6, M, M)`` / ``(3, 6, M, M)``) across the whole integration,
and each SSPRK3 stage

    y_out = a * y0 + b * y_c + (b * dt) * f(y_c)

is ONE Pallas kernel per face that reads the ghost-filled stage state,
computes the complete SWE right-hand side in VMEM
(:func:`jaxstream.ops.pallas.swe_rhs.rhs_core`), and writes the combined
next-stage state directly — tendencies never touch HBM, and the only
other per-stage traffic is the halo strip writes.  Ghost cells of the
output are written as ``a*y0 + b*y_c`` (finite, cheap) and are refilled
by the next exchange before anything reads them.

Shu-Osher coefficients: stage 1 (a=0, b=1), stage 2 (a=3/4, b=1/4),
stage 3 (a=1/3, b=2/3).  Stage 1 has ``a == 0`` and is built without the
``y0`` inputs at all so their blocks are never fetched.

The pure-JAX path (:mod:`jaxstream.stepping` over
:meth:`ShallowWater.rhs`) remains the parity oracle; see
tests/test_fused_step.py.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...geometry.connectivity import (
    EDGE_E,
    EDGE_N,
    EDGE_S,
    EDGE_W,
    build_connectivity,
)
from .swe_rhs import coord_rows, pick_recon, rhs_core, rhs_core_fast

__all__ = [
    "make_swe_stage_pallas",
    "make_fused_ssprk3_step",
    "make_swe_stage_inkernel",
    "make_fused_ssprk3_step_inkernel",
    "raw_strips",
    "route_strips",
]

SSPRK3_COEFFS = ((0.0, 1.0), (0.75, 0.25), (1.0 / 3.0, 2.0 / 3.0))


def make_swe_stage_pallas(
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
    fast: bool = True,
):
    """Build one fused RK-stage call with static coefficients ``(a, b)``.

    Returns ``stage(hc, vc, b_ext) -> (h_out, v_out)`` when ``a == 0``
    (stage 1: no dependence on the step-start state), else
    ``stage(h0, v0, hc, vc, b_ext) -> (h_out, v_out)``.  All fields are
    extended; outputs have valid interiors and finite-but-stale ghosts.
    """
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(dalpha)
    g_dt = b * dt  # tendency multiplier: y_out = a*y0 + b*yc + (b*dt)*f(yc)
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, frames = coord_rows(n, halo)
    with_y0 = a != 0.0

    def kernel(*refs):
        if with_y0:
            (frame_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             h0_ref, v0_ref, hc_ref, vc_ref, b_ref, ho_ref, vo_ref) = refs
        else:
            (frame_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             hc_ref, vc_ref, b_ref, ho_ref, vo_ref) = refs

        hf = hc_ref[0]                       # (M, M)
        v = [vc_ref[0, 0], vc_ref[1, 0], vc_ref[2, 0]]
        bf = b_ref[0]

        dh, dv = (rhs_core_fast if fast else rhs_core)(
            frame_ref, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, v, bf, n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )

        fa = jnp.float32(a)
        fb = jnp.float32(b)
        fg = jnp.float32(g_dt)
        if with_y0:
            out_h = fa * h0_ref[0] + fb * hf
            out_v = [fa * v0_ref[i, 0] + fb * v[i] for i in range(3)]
        else:
            # a == 0: no y0 term, but honor b (stage 1 of SSPRK3 has b=1,
            # other schemes may not).
            out_h = hf if b == 1.0 else fb * hf
            out_v = v if b == 1.0 else [fb * v[i] for i in range(3)]
        # Full-block write (keeps ghosts finite), then the interior gets
        # the tendency added on top — both stores stay in VMEM until the
        # block flushes, so HBM sees each output exactly once.
        ho_ref[0] = out_h
        ho_ref[0, i0:i1, i0:i1] = out_h[i0:i1, i0:i1] + fg * dh
        for i in range(3):
            vo_ref[i, 0] = out_v[i]
            vo_ref[i, 0, i0:i1, i0:i1] = out_v[i][i0:i1, i0:i1] + fg * dv[i]

    scalar_specs = [
        pl.BlockSpec((1, 3, 3), lambda f: (f, 0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
    ]
    h_spec = pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                          memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((3, 1, m, m), lambda f: (0, f, 0, 0),
                          memory_space=pltpu.VMEM)
    state_specs = [h_spec, v_spec]
    in_specs = scalar_specs + (state_specs if with_y0 else []) + \
        state_specs + [h_spec]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(grid=(6,), in_specs=in_specs,
                              out_specs=[h_spec, v_spec]),
        out_shape=[
            jax.ShapeDtypeStruct((6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((3, 6, m, m), jnp.float32),
        ],
        # Same scoped-VMEM story as the RHS kernel (swe_rhs.py): whole-face
        # stencil intermediates at C384 exceed the 16 MB default.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage(h0, v0, hc, vc, b_ext) -> Tuple[jax.Array, jax.Array]:
            return tuple(call(frames, x_row, xf_row, x_col, xf_col,
                              h0, v0, hc, vc, b_ext))
    else:
        def stage(hc, vc, b_ext) -> Tuple[jax.Array, jax.Array]:
            return tuple(call(frames, x_row, xf_row, x_col, xf_col,
                              hc, vc, b_ext))
    return stage


def make_fused_ssprk3_step(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    dt: float,
    exchange,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    fast: bool = True,
):
    """Build ``step(y_ext, t) -> y_ext`` over extended-state pytrees.

    ``y_ext = {"h": (6, M, M), "v": (3, 6, M, M)}`` with ghosts in any
    state (stale is fine: every stage exchanges before it reads).
    ``exchange`` is a scalar/vector halo exchanger over extended arrays
    (leading axes carried through).
    """
    mk = lambda a, b: make_swe_stage_pallas(
        n, halo, dalpha, radius, gravity, omega, dt, a, b,
        scheme=scheme, limiter=limiter, interpret=interpret, fast=fast,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    def step(y, t):
        del t  # the SWE RHS is autonomous
        h0 = exchange(y["h"])
        v0 = exchange(y["v"])
        h1, v1 = stage1(h0, v0, b_ext)
        h1 = exchange(h1)
        v1 = exchange(v1)
        h2, v2 = stage2(h0, v0, h1, v1, b_ext)
        h2 = exchange(h2)
        v2 = exchange(v2)
        h3, v3 = stage3(h0, v0, h2, v2, b_ext)
        return {"h": h3, "v": v3}

    return step


# ---------------------------------------------------------------------------
# In-kernel exchange: the whole step with zero standalone exchange passes.
#
# Each stage kernel emits, besides the combined next-stage state, the RAW
# boundary strips of its face (4 static slices, no data transforms — the
# Mosaic TPU lowering has no `rev`, so flips stay out of kernels).  A tiny
# jnp "router" between stages turns every face's raw strips into its
# neighbors' ghost data — the full cube topology (canonical frames,
# along-edge reversals, W/E transposes) applied to ~74 KB of strip
# tensors.  The next stage kernel then fills its ghost ring with 4 static
# writes.  Net: the halo exchange costs strip traffic only; full fields
# move through HBM exactly once per stage.  The strips ride the
# integration carry: y = {h, v, sh_sn, sh_we, sv_sn, sv_we}.
# ---------------------------------------------------------------------------


def raw_strips(field, n: int, halo: int):
    """Raw boundary strips of an extended field, kernel-output layout.

    Returns ``(sn, we)``: ``sn = (..., 6, 2, halo, n)`` holding the
    untransformed S/N interior rows, ``we = (..., 6, 2, n, halo)`` the W/E
    interior columns.  Carry initialisation for the in-kernel-exchange
    stepper (afterwards the kernels maintain the strips themselves).
    """
    i0, i1 = halo, halo + n
    sn = jnp.stack([
        jnp.stack([field[..., f, i0 : i0 + halo, i0:i1],
                   field[..., f, i1 - halo : i1, i0:i1]], axis=-3)
        for f in range(6)
    ], axis=-4)
    we = jnp.stack([
        jnp.stack([field[..., f, i0:i1, i0 : i0 + halo],
                   field[..., f, i0:i1, i1 - halo : i1]], axis=-3)
        for f in range(6)
    ], axis=-4)
    return sn, we


def route_strips(sn, we):
    """Raw strips -> placed ghost tensors (the cube-edge communication).

    Input: the output of :func:`raw_strips` (any leading axes).  Output
    ``(gsn, gwe)`` with ``gsn[..., f, 0] = (halo, n)`` rows to write at
    face ``f``'s S ghost ``[0:halo, halo:halo+n]``, ``gsn[..., f, 1]``
    the N ghost rows, and ``gwe[..., f, 0/1] = (n, halo)`` the W/E ghost
    columns.  All canonical-frame math (depth ordering, along-edge
    reversal, transposes — jaxstream.parallel.halo read/write_strip
    conventions) happens here, on strip-sized arrays.
    """
    from ...parallel.halo import canonicalize_strip, place_strip

    adj = build_connectivity()

    def ghost(f, e):
        link = adj[f][e]
        ne = link.nbr_edge
        if ne in (EDGE_S, EDGE_N):
            raw = sn[..., link.nbr_face, 0 if ne == EDGE_S else 1, :, :]
        else:
            raw = we[..., link.nbr_face, 0 if ne == EDGE_W else 1, :, :]
        s = canonicalize_strip(ne, raw)
        if link.reversed_:
            s = jnp.flip(s, axis=-1)
        return place_strip(e, s)

    gsn = jnp.stack([
        jnp.stack([ghost(f, EDGE_S), ghost(f, EDGE_N)], axis=-3)
        for f in range(6)
    ], axis=-4)
    gwe = jnp.stack([
        jnp.stack([ghost(f, EDGE_W), ghost(f, EDGE_E)], axis=-3)
        for f in range(6)
    ], axis=-4)
    return gsn, gwe


def make_swe_stage_inkernel(
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
    fast: bool = True,
):
    """One fused RK stage with the halo fill inside the kernel.

    ``a == 0``: ``stage(hc, vc, ghosts, b_ext)``; else
    ``stage(h0, v0, hc, vc, ghosts, b_ext)``; ``ghosts`` is the routed
    4-tuple ``(gsn, gwe, vgsn, vgwe)`` from :func:`route_strips`.
    Returns ``(h, v, sn, we, vsn, vwe)`` — the combined state plus its
    raw boundary strips.  Ghost corners are left stale — the
    dimension-split stencils never read them (see halo._fill_corners).
    """
    m = n + 2 * halo
    i0, i1 = halo, halo + n
    d = float(dalpha)
    g_dt = b * dt
    recon = pick_recon(scheme, halo, n, limiter)
    x_row, xf_row, x_col, xf_col, frames = coord_rows(n, halo)
    with_y0 = a != 0.0
    h = halo

    def fill_ghosts(scratch, face_val, gsn, gwe):
        """Ghost-filled face via a VMEM scratch buffer.

        Mosaic TPU lowers neither ``scatter`` nor value-level
        ``dynamic_update_slice`` nor lane-misaligned ``concatenate``, but
        *ref stores with static slices* are first-class: copy the face
        into scratch, overwrite the 4 ghost strips, read it back.  Ghost
        corners keep the previous stage's (finite, never-read) values.
        """
        scratch[:] = face_val
        scratch[0:h, i0:i1] = gsn[0]
        scratch[i1 : i1 + h, i0:i1] = gsn[1]
        scratch[i0:i1, 0:h] = gwe[0]
        scratch[i0:i1, i1 : i1 + h] = gwe[1]
        return scratch[:]

    def kernel(*refs):
        if with_y0:
            (frame_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             h0_ref, v0_ref, hc_ref, vc_ref,
             gsn_ref, gwe_ref, vgsn_ref, vgwe_ref, b_ref,
             ho_ref, vo_ref, sno_ref, weo_ref, vsno_ref, vweo_ref,
             *scratch) = refs
        else:
            (frame_ref, xr_ref, xfr_ref, yc_ref, yfc_ref,
             hc_ref, vc_ref,
             gsn_ref, gwe_ref, vgsn_ref, vgwe_ref, b_ref,
             ho_ref, vo_ref, sno_ref, weo_ref, vsno_ref, vweo_ref,
             *scratch) = refs

        hf = fill_ghosts(scratch[0], hc_ref[0], gsn_ref[0], gwe_ref[0])
        v = [fill_ghosts(scratch[1 + i], vc_ref[i, 0],
                         vgsn_ref[i, 0], vgwe_ref[i, 0])
             for i in range(3)]
        bf = b_ref[0]

        dh, dv = (rhs_core_fast if fast else rhs_core)(
            frame_ref, xr_ref[:], xfr_ref[:], yc_ref[:], yfc_ref[:],
            hf, v, bf, n=n, halo=halo, d=d, radius=radius,
            gravity=gravity, omega=omega, recon=recon,
        )

        fa = jnp.float32(a)
        fb = jnp.float32(b)
        fg = jnp.float32(g_dt)
        if with_y0:
            out_h = fa * h0_ref[0] + fb * hf
            out_v = [fa * v0_ref[i, 0] + fb * v[i] for i in range(3)]
        else:
            out_h = hf if b == 1.0 else fb * hf
            out_v = list(v) if b == 1.0 else [fb * v[i] for i in range(3)]

        def emit(val, tend, out_ref, sn_ref, we_ref, lead=()):
            """Store combined state: full block, then the tendency-updated
            interior on top (both stores flush from VMEM once), plus the
            raw boundary strips of the *final* interior."""
            int_new = val[i0:i1, i0:i1] + fg * tend
            out_ref[lead + (0,)] = val
            out_ref[lead + (0, slice(i0, i1), slice(i0, i1))] = int_new
            sn_ref[lead + (0, 0)] = int_new[0:h, :]
            sn_ref[lead + (0, 1)] = int_new[n - h : n, :]
            we_ref[lead + (0, 0)] = int_new[:, 0:h]
            we_ref[lead + (0, 1)] = int_new[:, n - h : n]

        emit(out_h, dh, ho_ref, sno_ref, weo_ref)
        for i in range(3):
            emit(out_v[i], dv[i], vo_ref, vsno_ref, vweo_ref, lead=(i,))

    frame_spec = pl.BlockSpec((1, 3, 3), lambda f: (f, 0, 0),
                              memory_space=pltpu.SMEM)
    coord_specs = [
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, m), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((m, 1), lambda f: (0, 0), memory_space=pltpu.VMEM),
    ]
    h_blk = pl.BlockSpec((1, m, m), lambda f: (f, 0, 0),
                         memory_space=pltpu.VMEM)
    v_blk = pl.BlockSpec((3, 1, m, m), lambda f: (0, f, 0, 0),
                         memory_space=pltpu.VMEM)
    sn_blk = pl.BlockSpec((1, 2, h, n), lambda f: (f, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    we_blk = pl.BlockSpec((1, 2, n, h), lambda f: (f, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    vsn_blk = pl.BlockSpec((3, 1, 2, h, n), lambda f: (0, f, 0, 0, 0),
                           memory_space=pltpu.VMEM)
    vwe_blk = pl.BlockSpec((3, 1, 2, n, h), lambda f: (0, f, 0, 0, 0),
                           memory_space=pltpu.VMEM)

    in_specs = [frame_spec] + coord_specs
    if with_y0:
        in_specs += [h_blk, v_blk]
    in_specs += [h_blk, v_blk, sn_blk, we_blk, vsn_blk, vwe_blk, h_blk]

    call = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=(6,),
            in_specs=in_specs,
            out_specs=[h_blk, v_blk, sn_blk, we_blk, vsn_blk, vwe_blk],
            scratch_shapes=[pltpu.VMEM((m, m), jnp.float32)
                            for _ in range(4)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((3, 6, m, m), jnp.float32),
            jax.ShapeDtypeStruct((6, 2, h, n), jnp.float32),
            jax.ShapeDtypeStruct((6, 2, n, h), jnp.float32),
            jax.ShapeDtypeStruct((3, 6, 2, h, n), jnp.float32),
            jax.ShapeDtypeStruct((3, 6, 2, n, h), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )

    if with_y0:
        def stage(h0, v0, hc, vc, ghosts, b_ext):
            return tuple(call(frames, x_row, xf_row, x_col, xf_col,
                              h0, v0, hc, vc, *ghosts, b_ext))
    else:
        def stage(hc, vc, ghosts, b_ext):
            return tuple(call(frames, x_row, xf_row, x_col, xf_col,
                              hc, vc, *ghosts, b_ext))
    return stage


def make_fused_ssprk3_step_inkernel(
    n: int,
    halo: int,
    dalpha: float,
    radius: float,
    gravity: float,
    omega: float,
    dt: float,
    b_ext,
    scheme: str = "plr",
    limiter: str = "mc",
    interpret: bool = False,
    fast: bool = True,
):
    """``step(y, t) -> y``, ``y = {h, v, sh_sn, sh_we, sv_sn, sv_we}``.

    The minimum-HBM-traffic step: three kernel launches plus three
    strip-routing shuffles, no standalone exchange or axpy passes.
    Initialise the strip carry with :func:`raw_strips`; ``h``/``v`` ghost
    rings are maintained by the kernels (corners stay stale — never read
    by the stencils).
    """
    mk = lambda a, b: make_swe_stage_inkernel(
        n, halo, dalpha, radius, gravity, omega, dt, a, b,
        scheme=scheme, limiter=limiter, interpret=interpret, fast=fast,
    )
    (a1, b1), (a2, b2), (a3, b3) = SSPRK3_COEFFS
    stage1 = mk(a1, b1)
    stage2 = mk(a2, b2)
    stage3 = mk(a3, b3)

    def ghosts_of(sn, we, vsn, vwe):
        # Direct small-op routing: measured faster on TPU than a
        # one-big-gather formulation (trace route_strips over index
        # arrays, replay as one jnp.take) — arbitrary-index gathers are
        # expensive on TPU; the 2xN strip shuffles fuse well.
        return route_strips(sn, we) + route_strips(vsn, vwe)

    def step(y, t):
        del t
        h0, v0 = y["h"], y["v"]
        g0 = ghosts_of(y["sh_sn"], y["sh_we"], y["sv_sn"], y["sv_we"])
        h1, v1, *s1 = stage1(h0, v0, g0, b_ext)
        h2, v2, *s2 = stage2(h0, v0, h1, v1, ghosts_of(*s1), b_ext)
        h3, v3, *s3 = stage3(h0, v0, h2, v2, ghosts_of(*s2), b_ext)
        return {"h": h3, "v": v3, "sh_sn": s3[0], "sh_we": s3[1],
                "sv_sn": s3[2], "sv_we": s3[3]}

    return step
