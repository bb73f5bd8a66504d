"""The per-layer metrics read from the program's span table: a traced
run at C16 on the CPU prints them as finite numbers, and each reader
returns None on an empty table (as with a program that keeps none)."""

import importlib
import math

import pytest

CELLS = {
    "tc5_c384.forecast": ("host_boundary_ms", "segment_excess_share"),
    "tc5_c384.serve_ens": ("host_boundary_ms", "serve_ic_build_ms"),
}
READERS = ("host_boundary_ms", "segment_excess_share", "serve_ic_build_ms")


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_span_metrics_print(run_small, workload):
    from jaxstream.obs import trace

    trace.SPANS.clear()
    for name in READERS:
        assert importlib.import_module("metrics." + name).read({}) is None
    res = run_small(workload, trace=True)
    assert res["correct"], res["checks"]
    for name in CELLS[workload]:
        v = res["metrics"][name]["value"]
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
