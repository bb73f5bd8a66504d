"""Test harness: fabricate 8 virtual CPU XLA devices before JAX backend init.

This replicates (and fixes) the reference's multi-device-without-a-cluster
testing tier: it sets ``XLA_FLAGS=--xla_force_host_platform_device_count``
via config *after* JAX may already be initialized
(``/root/reference/JAX-DevLab-Examples.py:64-73`` — a latent ordering bug,
SURVEY.md §7).  Here the flags are set in conftest, before any test module
imports jax.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_parallel_codegen_split_count" not in _flags:
    # The fast tier is COMPILE-dominated (hundreds of jit compiles of
    # big unrolled stepper graphs) and CI boxes are small: splitting
    # LLVM codegen into parallel modules is numerics-neutral (pure
    # compile-time partitioning) and measured ~8% off a compile-heavy
    # module even on a 2-core container (round 9).
    _flags = (_flags + " --xla_cpu_parallel_codegen_split_count=8").strip()
os.environ["XLA_FLAGS"] = _flags

# Make the repo root importable regardless of pytest rootdir config.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The suite runs on the CPU: 8 virtual devices, Pallas kernels in
# interpret mode (backend='pallas_interpret').  The driver also sets
# JAX_PLATFORMS=cpu; pinning it here keeps a bare `pytest` off the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The package places jax's persistent compilation cache on import
# (jaxstream.utils.jax_compat.place_compile_cache); the suite keeps it
# OFF so its behaviour does not depend on what an earlier run cached.
# The env var reaches the worker processes tests spawn as well.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (must import after XLA_FLAGS is set)

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
# Tests use float64 oracles (SURVEY.md §7: "f64-on-CPU oracle");
# library code is dtype-explicit so this only sharpens test math.
jax.config.update("jax_enable_x64", True)
# Hold the package logger at WARNING for the gate: Simulation's
# per-emit INFO diagnostics lines each cost a diagnostics compile
# + a blocking device_get (simulation._emit gates on
# isEnabledFor), and across the suite's many history-enabled runs
# that is tens of seconds of the fixed tier-1 budget spent
# formatting log lines no test asserts on.  Tests that DO assert
# on log records set their own level (caplog.at_level).
import logging  # noqa: E402

logging.getLogger("jaxstream").setLevel(logging.WARNING)
