"""Profiling/roofline subsystem tests (deck p.19 analysis frame as code)."""

import jax.numpy as jnp
import pytest

from jaxstream.utils.profiling import (
    TPU_V4_CLASS,
    Roofline,
    cost_analysis,
    roofline,
)


def test_ridge_matches_deck():
    # Deck p.19: 275 TFLOP/s / 900 GB/s = 305.6 flops/byte.
    assert TPU_V4_CLASS.ridge == pytest.approx(305.6, abs=0.1)


def test_cost_analysis_counts_matmul_flops():
    a = jnp.ones((256, 256), jnp.float32)

    def f(x):
        return x @ x

    c = cost_analysis(f, a)
    # 2*N^3 flops for a square matmul, modulo small compiler accounting.
    assert c["flops"] == pytest.approx(2 * 256**3, rel=0.5)
    assert c["bytes"] > 0
    assert c["ai"] == c["flops"] / c["bytes"]


def test_roofline_bound_classification():
    memory_pt = Roofline(flops=1e9, bytes=1e9, seconds=1.0, roof=TPU_V4_CLASS)
    assert memory_pt.bound == "memory"
    assert memory_pt.ai == 1.0
    # At AI=1, the roof is BW-limited: 900 GB/s * 1 flops/byte = 0.9 TFLOP/s.
    assert memory_pt.roof_tflops == pytest.approx(0.9)

    compute_pt = Roofline(flops=1e15, bytes=1e9, seconds=1.0, roof=TPU_V4_CLASS)
    assert compute_pt.bound == "compute"
    assert compute_pt.roof_tflops == pytest.approx(275.0)


def test_roofline_from_measurement():
    x = jnp.ones((64, 64), jnp.float32)
    r = roofline(lambda v: (v * 2.0).sum(), x, seconds=1e-3)
    assert r.bound == "memory"  # elementwise+reduce is far below the ridge
    assert 0.0 <= r.efficiency


def test_analytic_cov_step_cost_matches_design_bisection():
    """The hand count must agree with DESIGN.md's measured stage-kernel
    bisection: ~150 flops/cell/stage (+-15%) and a byte model whose DMA
    time at C384 lands near the measured ~40 us/stage machinery floor."""
    from jaxstream.utils.profiling import TPU_V5E_VPU, analytic_cov_step_cost

    c = analytic_cov_step_cost(384)
    assert 120 <= c["flops_per_cell_stage"] <= 175
    cells = 6 * 384 * 384
    assert c["flops"] == pytest.approx(
        c["flops_per_cell_stage"] * cells * 3)
    # ~9 field passes/stage * 4 B -> per-stage DMA at 819 GB/s in the
    # 35-55 us window (the measured floor is ~40 us/stage).
    per_stage_bytes = c["bytes"] / 3
    dma_us = per_stage_bytes / 819e9 * 1e6
    assert 25 < dma_us < 60
    # Limiter choice moves the count in the right direction.
    assert (analytic_cov_step_cost(384, limiter="none")["flops"]
            < c["flops"])
    # At the measured ~3050 steps/s the binding label must be compute
    # (VPU), matching the bisection — not the ridge-side "memory" label.
    r = Roofline(c["flops"], c["bytes"], seconds=1.0 / 3050.0,
                 roof=TPU_V5E_VPU)
    assert r.binding == "compute"
    assert 1.0 < r.achieved_tflops < 3.5
    assert "compute-bound" in r.report()
