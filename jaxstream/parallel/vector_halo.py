"""Vector halo exchange with great-circle (panel-basis) rotation.

The reference demonstrably exchanged vector fields in Cartesian components
("Cosine Bell Advection ... Cartesian Velocity Exchange", deck p.18) —
that path is the flagship one here too (:mod:`jaxstream.parallel.halo`
carries a leading component axis through untouched).  The north star's
alternative formulation carries velocity as *panel-local contravariant
components* ``(u^alpha, u^beta)`` and rotates them between panel bases at
each edge; this module implements that exchange.

The rotation is exact relative to the Cartesian route: a ghost cell's
value is the neighbor's vector re-expressed in the local panel's
(halo-extended) dual basis,

    T[i][j] = a_i^local(x_ghost) . e_j^nbr(x_src),

so ``T @ (u^a', u^b')_nbr = a^local . v_cartesian`` identically — the two
exchange formulations agree to roundoff (tested).  The 2x2 strips are
precomputed once at setup from the grid's stored bases; the hot path is
24 gathers + small elementwise FMAs + 24 scatters, fully fused under the
step ``jit``.

Layout: ``(2, 6, M, M)`` — component axis leading, like Cartesian vectors.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..geometry.connectivity import build_connectivity, build_schedule
from ..geometry.cubed_sphere import CubedSphereGrid
from .halo import _fill_corners, read_strip, write_strip

__all__ = ["make_vector_halo_exchanger", "to_contravariant", "to_cartesian"]


def to_contravariant(grid: CubedSphereGrid, v):
    """Cartesian ``(3, 6, M, M)`` -> contravariant ``(2, 6, M, M)``."""
    return jnp.stack([
        jnp.sum(v * grid.a_a, axis=0),
        jnp.sum(v * grid.a_b, axis=0),
    ])


def to_cartesian(grid: CubedSphereGrid, uv):
    """Contravariant ``(2, 6, M, M)`` -> Cartesian ``(3, 6, M, M)``."""
    return uv[0][None] * grid.e_a + uv[1][None] * grid.e_b


def _strip_indices(n: int, halo: int):
    """Index maps from canonical strip frame to flat (M*M) positions.

    Returns ``(src_idx, dst_idx)``: ``src_idx[edge]`` flat positions (in
    one face's (M, M)) of the interior boundary strip read by
    :func:`read_strip` in canonical (depth, along) order, and
    ``dst_idx[edge]`` the ghost-ring positions written by
    :func:`write_strip` for a canonical strip.
    """
    m = n + 2 * halo

    @jax.jit
    def probe(flat, blank, marker):
        # One compiled program for all 8 probes, not ~20 op-by-op
        # dispatches that each compile on first use.
        return ([read_strip(flat, 0, e, halo, n) for e in range(4)],
                [write_strip(blank, 0, e, marker)[0] for e in range(4)])

    reads, writes = probe(np.arange(m * m).reshape(1, m, m),
                          np.full((1, m, m), -1),
                          np.arange(halo * n).reshape(halo, n))
    src_idx, dst_idx = [], []
    for s, out in zip(reads, writes):
        src_idx.append(np.asarray(s).reshape(halo * n))
        out = np.asarray(out)
        pos = np.argsort(out.ravel())[m * m - halo * n:]  # where out >= 0
        order = out.ravel()[pos]
        dst = np.empty(halo * n, dtype=np.int64)
        dst[order] = pos
        dst_idx.append(dst)
    return src_idx, dst_idx


def make_vector_halo_exchanger(
    grid: CubedSphereGrid,
    fill_corners: bool = True,
    components: str = "contravariant",
) -> Callable:
    """Build ``exchange(uv) -> uv`` for panel-local ``(2, 6, M, M)``.

    Ghost values are the neighbor's components rotated into the local
    panel's extended basis (see module docstring).  For contravariant
    components ``(u^a, u^b)`` the rotation is
    ``T[i][j] = a_i^local(ghost) . e_j^nbr(src)``; for covariant
    components ``(u_a, u_b) = (v.e_a, v.e_b)`` the roles of the two bases
    swap: ``T[i][j] = e_i^local(ghost) . a_j^nbr(src)`` (both follow from
    re-expressing the same Cartesian vector in the local basis).  Pure
    function; trace it inside the step ``jit``.
    """
    if components not in ("contravariant", "covariant"):
        raise ValueError(f"unknown components {components!r}")
    n, halo = grid.n, grid.halo
    m = grid.m
    adj = build_connectivity()
    schedule = build_schedule(adj)
    src_idx, dst_idx = _strip_indices(n, halo)

    # Basis arrays as host numpy for the precompute, in grid dtype.
    e_a = np.moveaxis(np.asarray(grid.e_a), 0, -1).reshape(6, m * m, 3)
    e_b = np.moveaxis(np.asarray(grid.e_b), 0, -1).reshape(6, m * m, 3)
    a_a = np.moveaxis(np.asarray(grid.a_a), 0, -1).reshape(6, m * m, 3)
    a_b = np.moveaxis(np.asarray(grid.a_b), 0, -1).reshape(6, m * m, 3)

    copies = []
    for stage in schedule:
        for pair in stage:
            for link in pair:
                src_flat = src_idx[link.nbr_edge].reshape(halo, n)
                if link.reversed_:
                    src_flat = src_flat[:, ::-1]
                src_flat = src_flat.reshape(-1)
                dst_flat = dst_idx[link.edge]
                # Contravariant: T[k,i,j] = a_i^local(ghost k).e_j^nbr(src k);
                # covariant: e_i^local . a_j^nbr.
                loc = (a_a, a_b) if components == "contravariant" else (e_a, e_b)
                nbr = (e_a, e_b) if components == "contravariant" else (a_a, a_b)
                al = np.stack([loc[0][link.face, dst_flat],
                               loc[1][link.face, dst_flat]], axis=1)  # (hn,2,3)
                en = np.stack([nbr[0][link.nbr_face, src_flat],
                               nbr[1][link.nbr_face, src_flat]], axis=2)  # (hn,3,2)
                T = al @ en  # (hn, 2, 2)
                copies.append((
                    link.face,
                    link.nbr_face,
                    jnp.asarray(src_flat),
                    jnp.asarray(dst_flat),
                    jnp.asarray(T.astype(np.asarray(grid.e_a).dtype)),
                ))

    def exchange(uv):
        if uv.shape != (2, 6, m, m):
            raise ValueError(
                f"vector halo exchanger built for n={n}, halo={halo} expects "
                f"(2, 6, {m}, {m}), got {uv.shape}"
            )
        flatuv = uv.reshape(2, 6, m * m)
        # All reads against the pre-exchange field (ghost targets are never
        # strip sources, so staging order is irrelevant here).
        updates = []
        for dst_f, src_f, s_idx, d_idx, T in copies:
            comp = flatuv[:, src_f, :][:, s_idx]          # (2, h*n)
            rot = jnp.einsum("kij,jk->ik", T, comp)        # (2, h*n)
            updates.append((dst_f, d_idx, rot))
        for dst_f, d_idx, rot in updates:
            flatuv = flatuv.at[:, dst_f, d_idx].set(rot)
        out = flatuv.reshape(2, 6, m, m)
        if fill_corners:
            out = _fill_corners(out, halo, n)
        return out

    return exchange
