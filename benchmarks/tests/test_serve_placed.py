"""The four-chip served cell at C16 on four virtual CPU devices: a
traced run through ``run.run_cell`` comes out correct, prints a finite
``serve_inject_ms``, checks a refilled member on every chip, uploads
one member's ``h`` per inject and hands the per-layer readers the
member-steps per chip.  It is not correct with one cell of every
extracted answer moved by 1%, nor with the heights that refills inject
on chip 2 alone scaled by 1.01.

Each run is a subprocess: the device count has to be set before JAX
starts, and this directory's conftest does not set it.
"""

import json
import math
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "tc5_c384.serve_ens_4chip"

SCRIPT = r'''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import run
run._prepare_environment()
import numpy as np
from drivers import serve_placed

fault = sys.argv[3]
seen = {}
outer = serve_placed.run


def placed_run(ctx):
    out = outer(ctx)
    seen["layer_steps"] = out["layer"].get("member_steps")
    for k in ("traced_member_steps", "checked", "inject_bytes_per_inject"):
        seen[k] = out["notes"].get(k)
    return out


serve_placed.run = placed_run
if fault == "inject2":
    from jaxstream.serve.server import _PlacedBucket

    inject_slot = _PlacedBucket.inject_slot

    def scaled(self, carry, i, tree):
        # A request's own h arrives from the host; the warm-up's does not.
        if i // self._per == 2 and isinstance(tree["h"], np.ndarray):
            tree = dict(tree, h=tree["h"] * np.float32(1.01))
        return inject_slot(self, carry, i, tree)
    _PlacedBucket.inject_slot = scaled
if fault == "extract":
    from jaxstream.serve.server import EnsembleServer

    build = EnsembleServer._placed_bucket

    def placed_bucket(self, *a, **kw):
        bk = build(self, *a, **kw)
        extract = bk.extract

        def bumped(carry, idx):
            out = extract(carry, idx)
            h = out["h"]
            return dict(out, h=h.at[(0,) * h.ndim].multiply(1.01))
        bk.extract = bumped
        return bk
    EnsembleServer._placed_bucket = placed_bucket
over = {"config": {"grid": {"n": 16},
                   "model": {"backend": "pallas_interpret"},
                   "time": {"dt": 1800.0},
                   "parallelization": {"device_type": "cpu"},
                   "serve": {"buckets": "8", "segment_steps": 2,
                             "placement": {"device_type": "cpu"}}},
        "traffic": {"streams": 16}}
res = run.run_cell(sys.argv[4], 1234567890123, 6.0, True, overrides=over,
                   require_chip=False)
print(json.dumps(dict(res, seen=seen)))
'''


def _run(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, ROOT, BENCH, mode, CELL],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    return _run("sound")


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["count"] == 4


def test_inject_metric_prints(sound):
    v = sound["metrics"]["serve_inject_ms"]["value"]
    assert v is not None and math.isfinite(v) and v > 0


def test_member_steps_per_chip(sound):
    seen = sound["seen"]
    assert seen["traced_member_steps"] > 0
    assert seen["layer_steps"] == seen["traced_member_steps"] / 4


def test_refilled_member_checked_on_every_chip(sound):
    """Every chip has a checked member that came in by a refill (not
    one of the first pack, streams 0-7 at k = 0)."""
    first_pack = {f"s{s}r0" for s in range(8)}
    checked = sound["seen"]["checked"]
    assert {chip for rid, _, chip in checked
            if rid not in first_pack} == {0, 1, 2, 3}


def test_inject_uploads_one_member_height(sound):
    assert sound["seen"]["inject_bytes_per_inject"] == 6 * 16 * 16 * 4


def test_altered_answer_is_not_correct():
    assert not _run("extract")["correct"]


def test_altered_inject_on_one_chip_is_not_correct():
    assert not _run("inject2")["correct"]
