"""Cross/ACA rounding (the LANL method, deck p.14): accuracy + wiring."""

import numpy as np

import jax
import jax.numpy as jnp

from jaxstream.tt.cross import aca_lowrank


def _smooth(n, m):
    x = np.linspace(0, 2 * np.pi, n)
    y = np.linspace(0, 2 * np.pi, m)
    X, Y = np.meshgrid(x, y, indexing="ij")
    return ((1 + 0.5 * np.sin(X) * np.cos(Y))
            * (2 + np.cos(2 * X) * np.sin(Y) + 0.1 * np.sin(5 * X)))


def test_aca_near_svd_optimal_on_smooth_operand():
    M = _smooth(256, 192)
    u, s, vt = np.linalg.svd(M, full_matrices=False)
    P = jnp.asarray(u * s)          # implicit full-rank factorization
    Q = jnp.asarray(vt)
    nrm = np.linalg.norm(M)
    for k in (4, 8, 12):
        U, V = jax.jit(aca_lowrank, static_argnums=2)(P, Q, k)
        err = np.linalg.norm(np.asarray(U @ V) - M) / nrm
        opt = np.sqrt((s[k:] ** 2).sum()) / nrm
        # ACA quasi-optimality: within a small factor of the SVD floor.
        assert err < max(50 * opt, 1e-13), (k, err, opt)


def test_aca_recovers_exact_low_rank():
    rng = np.random.default_rng(3)
    P = jnp.asarray(rng.standard_normal((100, 5)))
    Q = jnp.asarray(rng.standard_normal((5, 80)))
    U, V = aca_lowrank(P, Q, 5)
    np.testing.assert_allclose(np.asarray(U @ V), np.asarray(P @ Q),
                               rtol=0, atol=1e-10)
    # Overshooting the true rank must not inject garbage (dead pivots
    # write zeros).
    U, V = aca_lowrank(P, Q, 9)
    np.testing.assert_allclose(np.asarray(U @ V), np.asarray(P @ Q),
                               rtol=0, atol=1e-9)


def test_swe_cross_rounding_tracks_dense():
    """The eigh/SVD-free cross pipeline tracks the dense stencil oracle
    on the nonlinear SWE (small N, fast)."""
    from jaxstream.tt.swe2d import (make_dense_swe_stepper,
                                    make_tt_swe_stepper, sw_factor,
                                    sw_unfactor)

    N, rank, nsteps = 128, 12, 25
    L = 1.0e6
    dx = dy = L / N
    g = 9.81
    x = np.linspace(0, 2 * np.pi, N, endpoint=False)
    X, Y = np.meshgrid(x, x, indexing="ij")
    h0 = 1000.0 + 5.0 * np.exp(
        -((np.cos(X) - 0.3) ** 2 + np.cos(Y) ** 2) * 8)
    u0 = 0.5 * np.sin(X) * np.cos(Y)
    v0 = -0.5 * np.cos(X) * np.sin(Y)
    dt = 0.2 * dx / np.sqrt(g * 1005)
    nu = 0.01 * dx * dx / dt

    dense = tuple(jnp.asarray(a) for a in (h0, u0, v0))
    dstep = jax.jit(make_dense_swe_stepper(dx, dy, dt, g, nu=nu))
    s = dense
    for _ in range(nsteps):
        s = dstep(s)
    h_ref = np.asarray(s[0])

    for mode in ("cross", "cross_fused"):
        tstep = jax.jit(make_tt_swe_stepper(N, N, dx, dy, dt, g, rank,
                                            nu=nu, rounding=mode))
        q = tuple(sw_factor(a, rank) for a in dense)
        for _ in range(nsteps):
            q = tstep(q)
        h_tt = np.asarray(sw_unfactor(q[0]))
        err = np.max(np.abs(h_tt - h_ref)) / np.max(np.abs(h_ref))
        assert err < 1e-6, (mode, err)


def test_host_svd_lowrank_gates_unsupported_backends():
    """host_svd_lowrank is a jax.pure_callback host round trip; plugin
    backends without host-callback support must be refused at BUILD
    time with remediation text, not fail obscurely mid-run."""
    import jax.numpy as jnp
    import pytest

    from jaxstream.tt.cross import host_svd_lowrank

    P = jnp.ones((6, 3), jnp.float32)
    Q = jnp.ones((3, 6), jnp.float32)
    with pytest.raises(NotImplementedError, match="host callbacks"):
        host_svd_lowrank(P, Q, 2, backend="noplugin")
    # The supported platforms still build and run (CPU here).
    A, B = host_svd_lowrank(P, Q, 2, backend="cpu")
    assert A.shape == (6, 2) and B.shape == (2, 6)
