"""Cross / ACA low-rank approximation of implicitly-factored operands.

The LANL route to TT-compressed nonlinear terms (Danis et al. 2024,
arXiv:2408.03483 — deck p.14): instead of projecting the full operand
(randomized sketch) or forming Gram matrices (exact rounding, one eigh/
SVD per product), **adaptive cross approximation** builds a rank-k
skeleton from k actual rows and columns of the operand, chosen by
partial pivoting on the residual.  Everything is matvecs, slicing, and
argmax — no factorization kernels at all — which matters because the
N-independent eigh/SVD calls were measured to eat ~2/3 of the TT step
at N=1024 (DESIGN.md "Tensor-Train numerics"): cross removes that floor
from the quadratic-term roundings.

``aca_lowrank(P, Q, k)`` approximates ``M = P @ Q`` (never formed, with
``P (n, R)``, ``Q (R, m)`` — e.g. the Khatri-Rao factors of a product
of two rank-r fields, R = r^2) by the classic partially-pivoted ACA:

    for t < k:
        c   = M[:, j] - U V[:, j]          (residual column at pivot j)
        i   = argmax |c|   (excluding used rows)
        r   = M[i, :] - U[i] V             (residual row at pivot i)
        U[:, t] = c / r[j];  V[t] = r
        j   = argmax |r|   (excluding used columns)

After k steps ``U V ~ M`` with the standard ACA quasi-optimality (error
~ the (k+1)-th singular value up to a k-dependent factor, tight for the
smooth fields this layer carries).  All shapes static; pivot selection
is data-dependent but jit-safe (argmax + dynamic slices in a fori_loop).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["aca_lowrank", "aca_lowrank_many", "svd_lowrank",
           "rsvd_lowrank", "host_svd_lowrank"]


def svd_lowrank(P, Q, k: int, backend: str | None = None):
    """EXACT best rank-``k`` truncation of ``M = P @ Q`` (never formed):
    thin QR of ``P`` then SVD of the small ``(R, m)`` product —
    O(n R^2 + R^2 m + R m min(R, m)), one QR + one SVD per call.

    The quality tier above :func:`aca_lowrank`: ACA is quasi-optimal
    (error ~ sigma_{k+1} up to a k-dependent factor) and its pivoted
    skeleton can inject perturbations far above optimal on operands
    with slowly-decaying spectra.  Measured consequence (round 4,
    DESIGN.md stability envelope): the factored sphere SWE on
    mountain-forced TC5 C96 NaNs within 0.17-0.5 sim-days under ACA
    rounding at EVERY rank/dissipation tried, but integrates 5+ days
    with physical fields under this exact rounding — the
    "non-dissipative perturbation" that destabilized the flow was
    dominated by ACA's excess over optimal truncation, not by optimal
    truncation itself.  Factors are balanced ``sqrt(s)`` per side (the
    layer's convention).

    Backend status (round 4, measured): CPU f32/f64 run the QR+SVD
    path (LAPACK; TC5 C96 stable 8+ sim-hours in f32, 5 days in f64).
    On ACCELERATOR f32 the QR+SVD path NaNs the TC5 run within 4-8
    sim-hours — with AND without pinned matmul precision (TPU f32 QR
    loses orthogonality on near-rank-deficient operands, the same
    failure qtt.py:418-432 hit) — so that combination routes to the
    masked-Gram-eigh path below (qtt's proven f32 construction).  The
    v5e's f32 ``eigh`` then ALSO degrades at production bond sizes
    (garbage eigenbasis at bond ~100, followed by a TPU-worker crash;
    correct at bond ~20), so the svd stability tier is currently
    CPU-validated only; the TPU path stays in place as the
    best-known-construction for when TPU linalg robustness improves
    (Simulation's 'auto' picks it only for CPU runs).

    ``backend``: the platform this rounding will execute on ('cpu' /
    'tpu' / ...).  Callers that place computation explicitly (the
    panel-sharded tier's CPU mesh inside a TPU-enabled process) MUST
    pass it — the default consults the process-global
    ``jax.default_backend()``, which is where an un-pinned jit runs.
    """
    if backend is None:
        backend = jax.default_backend()
    if P.dtype == jnp.float32 and backend != "cpu":
        return _svd_lowrank_gram(P, Q, k)
    with jax.default_matmul_precision("highest"):
        Qf, Rf = jnp.linalg.qr(P)
        U, s, Vt = jnp.linalg.svd(Rf @ Q, full_matrices=False)
        kk = min(k, s.shape[0])
        rs = jnp.sqrt(s[:kk])
        A = Qf @ (U[:, :kk] * rs[None])
        B = rs[:, None] * Vt[:kk]
        if kk < k:  # zero-pad to exactly rank k (the gram path's contract)
            A = jnp.pad(A, ((0, 0), (0, k - kk)))
            B = jnp.pad(B, ((0, k - kk), (0, 0)))
        return A, B


def _svd_lowrank_gram(P, Q, k: int):
    """f32 exact-truncation path: two masked Gram eighs, no QR/SVD.

    ``M = P Q``; eigh of ``Q Q^T`` gives ``Q = S W`` with orthonormal
    rows ``W`` (masked against zero modes), so ``M = (P S) W`` and the
    best rank-k of ``M`` is the best rank-k of ``T = P S`` against
    ``W``; eigh of ``T^T T`` then yields the singular pairs.  Balanced
    ``sqrt(sigma)`` per side; zero-padded to exactly rank k."""
    fi = jnp.finfo(P.dtype)
    with jax.default_matmul_precision("highest"):
        lam_q, Eq = jnp.linalg.eigh(Q @ Q.T)            # ascending
        keep_q = lam_q > fi.eps * lam_q[-1] + fi.tiny
        sq = jnp.sqrt(jnp.where(keep_q, lam_q, 1.0))
        W = jnp.where(keep_q, 1.0 / sq, 0.0)[:, None] * (Eq.T @ Q)
        T = P @ (Eq * jnp.where(keep_q, sq, 0.0)[None, :])
        lam, E = jnp.linalg.eigh(T.T @ T)
        lam, E = lam[::-1], E[:, ::-1]
        kk = min(k, T.shape[1])
        keep = lam[:kk] > fi.eps * jnp.maximum(lam[0], 0.0) + fi.tiny
        s = jnp.sqrt(jnp.where(keep, lam[:kk], 1.0))    # sigma_i of M
        root = jnp.sqrt(s)
        A = T @ (E[:, :kk] * jnp.where(keep, root / s, 0.0)[None, :])
        B = jnp.where(keep, root, 0.0)[:, None] * (E[:, :kk].T @ W)
        if kk < k:
            A = jnp.pad(A, ((0, 0), (0, k - kk)))
            B = jnp.pad(B, ((0, k - kk), (0, 0)))
        return A, B


def _ns_orth(X, iters: int = 90):
    """Orthonormalize the columns of ``X (n, l)`` by Newton-Schulz
    polar iteration — **matmul-only**, no QR/eigh/SVD primitives.

    The cubic map ``X <- 1.5 X - 0.5 X (X^T X)`` drives every singular
    value of the Frobenius-prenormalized operand toward 1 (monotone on
    (0, sqrt(3)); ~1.5x growth per sweep for small values, quadratic
    contraction near the fixed point), so the limit is the orthogonal
    polar factor of ``X`` — same column span, orthonormal columns.
    This is the v5e-robust replacement for the f32 ``jnp.linalg.qr``
    whose orthogonality loss on near-rank-deficient operands NaN'd the
    svd rounding tier on TPU (see :func:`svd_lowrank` backend notes):
    matmuls carry none of the Householder pivoting that breaks there,
    and exactly-zero columns (rank-deficient operands, zero-padded
    factors) stay exactly zero instead of poisoning the basis.
    """
    fi = jnp.finfo(X.dtype)
    X = X / (jnp.sqrt(jnp.sum(X * X)) + fi.tiny)
    with jax.default_matmul_precision("highest"):
        def body(_, Y):
            return 1.5 * Y - 0.5 * (Y @ (Y.T @ Y))

        return jax.lax.fori_loop(0, iters, body, X)


_SKETCH_SEED = 7031  # fixed: rounding is deterministic run to run


def _balanced(A, B, k: int):
    """Rescale mode ``j`` so each side carries ``sqrt(sigma_j)`` (the
    layer's factor convention; ``sigma_j ~ |A_j| |B_j|``), zero dead
    modes, and zero-pad to exactly width ``k``.  The product ``A B`` is
    unchanged on live modes."""
    fi = jnp.finfo(A.dtype)
    na = jnp.sqrt(jnp.sum(A * A, axis=0))
    nb = jnp.sqrt(jnp.sum(B * B, axis=1))
    s = na * nb
    keep = s > fi.tiny
    root = jnp.sqrt(jnp.where(keep, s, 1.0))
    A = A * jnp.where(keep, root / jnp.maximum(na, fi.tiny), 0.0)[None, :]
    B = jnp.where(keep, root / jnp.maximum(nb, fi.tiny), 0.0)[:, None] * B
    w = A.shape[1]
    if w < k:
        A = jnp.pad(A, ((0, 0), (0, k - w)))
        B = jnp.pad(B, ((0, k - w), (0, 0)))
    return A, B


def rsvd_lowrank(P, Q, k: int, oversample: int = 8, power: int = 2,
                 subspace_iters: int = 6, ns_iters: int = 90,
                 compute_dtype=None):
    """Near-optimal rank-``k`` truncation of ``M = P @ Q`` using ONLY
    matrix multiplies — the TPU-viable stability tier (round 5).

    The exact tier (:func:`svd_lowrank`) is measured-blocked on v5e
    f32: QR loses orthogonality and ``eigh`` returns garbage at
    production bond sizes (its docstring).  This tier replaces every
    factorization primitive with Newton-Schulz polar orthogonalization
    (:func:`_ns_orth`) inside a two-stage randomized-SVD:

    1. **Range finder** (Halko-Martinsson-Tropp): a deterministic
       Gaussian sketch of width ``l = k + oversample`` gives
       ``Y = P (Q Om)``; ``power`` subspace iterations with NS
       re-orthogonalization tighten the basis ``U`` toward the top-l
       left singular space.  Oversampling keeps the *top-k* angle
       small even where the spectrum is flat at the cutoff.
    2. **Core truncation**: project ``C = (U^T P) Q`` (small,
       ``(l, m)``) and extract its top-k right basis ``V`` by NS-
       orthogonalized subspace iteration on the explicit core — cheap,
       so ``subspace_iters`` can be generous.  ``M ~ (U C V) V^T``.

    Error ~ sigma_{k+1} times a modest factor (measured against the
    exact tier in tests/test_tt_rounding_tiers.py); deterministic
    (fixed sketch key) and jit/vmap-safe.  Factors balanced
    ``sqrt(sigma)`` per side, zero-padded to exactly ``k``.
    """
    out_dtype = P.dtype
    if compute_dtype is not None:
        P = P.astype(compute_dtype)
        Q = Q.astype(compute_dtype)
    n, R = P.shape
    m = Q.shape[1]
    rmax = min(n, m, R)
    l = min(k + oversample, rmax)
    with jax.default_matmul_precision("highest"):
        # Distinct subkeys for the two independent draws: the range
        # sketch Om and the core-truncation subspace initializer V must
        # not share randomness (with one key, V's k columns replicate
        # the first k columns' pattern of Om's draw — a correlated
        # start the subspace iteration then has to work away from).
        key_om, key_v = jax.random.split(jax.random.PRNGKey(_SKETCH_SEED))
        Om = jax.random.normal(key_om, (m, l), P.dtype)
        U = _ns_orth(P @ (Q @ Om), ns_iters)
        for _ in range(power):
            Z = Q.T @ (P.T @ U)                       # (m, l)
            U = _ns_orth(P @ (Q @ Z), ns_iters)
        C = (U.T @ P) @ Q                             # (l, m)
        if l <= k:  # the basis already spans rank(M): exact, just pad
            A, B = _balanced(U, C, k)
            return A.astype(out_dtype), B.astype(out_dtype)
        V = jax.random.normal(key_v, (m, k), P.dtype)
        for _ in range(subspace_iters):
            V = _ns_orth(C.T @ (C @ V), ns_iters)
        A = U @ (C @ V)                               # (n, k)
        A, B = _balanced(A, V.T, k)
        return A.astype(out_dtype), B.astype(out_dtype)


#: Platforms whose runtimes are known to execute ``jax.pure_callback``.
#: Out-of-tree PJRT plugin backends may lack host-callback support
#: entirely and fail at RUN time with an opaque runtime error, hence the
#: explicit build-time gate below.
_HOST_CALLBACK_PLATFORMS = frozenset({"cpu", "gpu", "cuda", "rocm", "tpu"})


def host_svd_lowrank(P, Q, k: int, backend: str | None = None):
    """EXACT rank-``k`` truncation with the small factorization on the
    HOST (numpy/LAPACK, f64) via ``jax.pure_callback`` — the guaranteed
    stopgap rung for backends whose on-device linalg is unreliable.
    Bit-identical quality to the CPU svd tier; costs one host round
    trip per call (measured cost line in DESIGN.md).  Supports leading
    batch dims (numpy stacked linalg), so it vmaps via broadcast.

    .. warning:: **Requires host-callback support in the executing
       runtime.**  ``pure_callback`` is a host round trip per call: the
       device runtime must be able to pause the program and call back
       into Python.  Standard CPU/GPU/TPU runtimes can; out-of-tree
       PJRT plugin backends often cannot, and without this gate the
       failure surfaces as an obscure runtime error mid-run.  Pass
       ``backend`` (the platform this rounding will execute on — same
       contract as :func:`svd_lowrank`) when placing computation
       explicitly; the default consults ``jax.default_backend()``.
    """
    import numpy as np

    if backend is None:
        backend = jax.default_backend()
    if backend not in _HOST_CALLBACK_PLATFORMS:
        raise NotImplementedError(
            f"host_svd_lowrank executes a jax.pure_callback host round "
            f"trip, and the {backend!r} backend is not known to support "
            f"host callbacks (supported: "
            f"{sorted(_HOST_CALLBACK_PLATFORMS)}). Use rounding='rsvd' "
            f"(matmul-only, runs anywhere) or place this rounding on a "
            f"CPU mesh."
        )

    dt = P.dtype
    m = Q.shape[-1]

    def _host(p, q):
        p = np.asarray(p, np.float64)
        q = np.asarray(q, np.float64)
        Qf, Rf = np.linalg.qr(p)
        U, s, Vt = np.linalg.svd(Rf @ q, full_matrices=False)
        kk = min(k, s.shape[-1])
        rs = np.sqrt(s[..., :kk])
        A = Qf @ (U[..., :, :kk] * rs[..., None, :])
        B = rs[..., :, None] * Vt[..., :kk, :]
        if kk < k:
            pad = [(0, 0)] * (A.ndim - 1)
            A = np.pad(A, pad + [(0, k - kk)])
            B = np.pad(B, pad[:-1] + [(0, k - kk), (0, 0)])
        return (np.ascontiguousarray(A, dtype=dt),
                np.ascontiguousarray(B, dtype=dt))

    out = (jax.ShapeDtypeStruct(P.shape[:-1] + (k,), dt),
           jax.ShapeDtypeStruct(Q.shape[:-2] + (k, m), dt))
    return jax.pure_callback(_host, out, P, Q,
                             vmap_method="broadcast_all")


def aca_lowrank(P, Q, k: int):
    """Rank-``k`` cross approximation ``(U, V)`` of ``M = P @ Q``.

    ``P (n, R)``, ``Q (R, m)`` -> ``U (n, k)``, ``V (k, m)`` with
    ``U @ V ~ P @ Q``.  O(k (n + m) (R + k)) flops, no eigh/SVD/QR.
    The factors are balanced per direction (each ACA term is
    ``c_t r_t / pivot``; we split the pivot as ``1/sqrt|pivot|`` on each
    side to keep both factors at comparable scale — the same balancing
    convention as ``solver._round_factored``).
    """
    n, R = P.shape
    R2, m = Q.shape
    assert R == R2, (P.shape, Q.shape)
    dt = P.dtype
    # Factor-accumulation strategy, resolved at trace time (same
    # backend-gating convention as sphere_swe's batch_rounding).
    onehot = jax.default_backend() != "cpu"

    def body(t, carry):
        U, V, j, used_r, used_c = carry
        # Residual column at pivot column j.
        c = P @ jax.lax.dynamic_slice_in_dim(Q, j, 1, axis=1)[:, 0] \
            - U @ jax.lax.dynamic_slice_in_dim(V, j, 1, axis=1)[:, 0]
        c_m = jnp.where(used_r, 0.0, jnp.abs(c))
        i = jnp.argmax(c_m)
        # Residual row at pivot row i.
        r = jax.lax.dynamic_slice_in_dim(P, i, 1, axis=0)[0] @ Q \
            - jax.lax.dynamic_slice_in_dim(U, i, 1, axis=0)[0] @ V
        piv = r[j]
        # Dead pivot (exactly-representable operand of lower rank):
        # write zero vectors instead of dividing by ~0.
        ok = jnp.abs(piv) > jnp.finfo(dt).tiny * 16
        inv = jnp.where(ok, 1.0 / jnp.sqrt(jnp.abs(
            jnp.where(ok, piv, 1.0))), 0.0)
        sgn = jnp.where(piv < 0, -1.0, 1.0)
        u_t = c * inv
        v_t = r * (inv * sgn)
        if onehot:
            # One-hot outer-product accumulation: bitwise-identical to
            # the DUS (each column/row is written exactly once onto
            # zeros), measured 1.8x faster per vmapped call on TPU —
            # the 17.5 us/iteration DUS was the largest op family in
            # the batched factored-SWE step's device trace.  On CPU the
            # k-fold extra factor traffic measures 9-16% SLOWER, hence
            # the backend gate.
            oh = (jnp.arange(k, dtype=jnp.int32) == t).astype(dt)
            U = U + u_t[:, None] * oh[None, :]
            V = V + oh[:, None] * v_t[None, :]
        else:
            U = jax.lax.dynamic_update_slice_in_dim(U, u_t[:, None], t,
                                                    axis=1)
            V = jax.lax.dynamic_update_slice_in_dim(V, v_t[None, :], t,
                                                    axis=0)
        used_r = used_r.at[i].set(True)
        used_c = used_c.at[j].set(True)
        j_next = jnp.argmax(jnp.where(used_c, 0.0, jnp.abs(r)))
        return U, V, j_next, used_r, used_c

    U0 = jnp.zeros((n, k), dt)
    V0 = jnp.zeros((k, m), dt)
    # First pivot column: the one with the largest column of Q-energy
    # proxy (cheap, deterministic): argmax of column norms of Q summed
    # through P's column scales.
    col_proxy = jnp.einsum("ij,j->i", jnp.abs(Q.T), jnp.sum(jnp.abs(P), 0))
    j0 = jnp.argmax(col_proxy)
    carry = (U0, V0, j0, jnp.zeros((n,), bool), jnp.zeros((m,), bool))
    U, V, _, _, _ = jax.lax.fori_loop(0, k, body, carry)
    return U, V


def aca_lowrank_many(ops, k: int):
    """Round MANY independent face-batched operands in ONE ACA sweep.

    ``ops``: list of stacked factor pairs ``(A (F, n, R_i), B (F, R_i,
    n))`` with differing bond ranks ``R_i``.  Zero-pads every operand to
    ``max R_i`` (zero bond columns leave ``P @ Q`` unchanged, so the
    rounding is identical), stacks to one ``(len(ops) * F, ...)`` batch,
    and runs a single vmapped :func:`aca_lowrank`.  Returns the list of
    rounded ``(U (F, n, k), V (F, k, n))`` pairs.

    This is the TT analogue of kernel-launch batching: on TPU the
    factored SWE step was measured latency-bound on its ~36 *sequential*
    vmapped ACA loops (DESIGN.md "Round 2 (cont.)"); independent
    roundings grouped here run as one fori_loop instead of one per
    operand.
    """
    if not ops:
        return []
    R = max(A.shape[-1] for A, _ in ops)
    F = ops[0][0].shape[0]
    if any(A.shape[0] != F or B.shape[0] != F for A, B in ops):
        raise ValueError(
            "aca_lowrank_many needs a common face/batch count; got "
            f"{[(A.shape[0], B.shape[0]) for A, B in ops]}")
    padded_A = [jnp.pad(A, ((0, 0), (0, 0), (0, R - A.shape[-1])))
                for A, _ in ops]
    padded_B = [jnp.pad(B, ((0, 0), (0, R - B.shape[-2]), (0, 0)))
                for _, B in ops]
    As = jnp.concatenate(padded_A, axis=0)
    Bs = jnp.concatenate(padded_B, axis=0)
    U, V = jax.vmap(lambda a, b: aca_lowrank(a, b, k))(As, Bs)
    return [(U[i * F:(i + 1) * F], V[i * F:(i + 1) * F])
            for i in range(len(ops))]
