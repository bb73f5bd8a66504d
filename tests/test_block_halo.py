"""Block-mesh (sub-panel tiled) explicit halo exchange.

The reference declared ``tiles_per_edge > 1`` future work
(/root/reference/JAX-DevLab-Examples.py:31-37); this is its realization:
a (6, s, s) device mesh with intra-panel neighbor ppermutes plus the
4-stage cube-edge schedule as joint ppermutes.  Structural invariants run
in-process; the 24-device execution tests run in a subprocess (conftest
pins this process to 8 virtual devices).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from jaxstream.geometry.connectivity import build_connectivity
from jaxstream.parallel.shard_halo import BlockHaloProgram

# This repo's face layout (cubed_sphere.py): 0-3 equatorial at lon
# 0/90/180/270, 4 north, 5 south -> antipodal pairs (0,2), (1,3), (4,5).
ANTIPODAL = {0: 2, 2: 0, 1: 3, 3: 1, 4: 5, 5: 4}


@pytest.mark.parametrize("s", [1, 2, 3])
def test_block_program_invariants(s):
    prog = BlockHaloProgram(s)
    nd = 6 * s * s

    def face_of(lin):
        return lin // (s * s)

    for perm in prog.cube_perms:
        srcs = [p[0] for p in perm]
        dsts = [p[1] for p in perm]
        # 3 edge pairs x 2 directions x s blocks, all distinct endpoints.
        assert len(perm) == 6 * s
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        assert all(0 <= i < nd for i in srcs + dsts)
        for src, dst in perm:
            fs, fd = face_of(src), face_of(dst)
            assert fs != fd, "no self-exchange"
            assert ANTIPODAL[fs] != fd, "antipodal faces never exchange"
    # Every block of every face-boundary edge participates exactly 4x
    # (once per its face's edge per stage); interior blocks never.
    act = np.asarray(prog.active)
    for f in range(6):
        for iy in range(s):
            for ix in range(s):
                on_boundary = iy in (0, s - 1) or ix in (0, s - 1)
                assert act[f, iy, ix].any() == on_boundary


def _run_sub(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=24"
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_block_exchange_matches_reference_24dev():
    """s=2 block exchange under shard_map == global-array exchange.

    Slow-marked (suite-budget reclaim): the 24-virtual-device
    subprocess pays a fresh JAX import + 24-way compile (~1 min wall),
    and the same exchange is covered at full depth by the other
    24-device parities already in the slow tier.  (The multi-process
    Gloo pod test was audited for the same treatment and has carried
    the slow mark since it landed.)
    """
    out = _run_sub(r"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jaxstream.parallel.halo import make_halo_exchanger
from jaxstream.parallel.shard_halo import make_block_halo_program
from jax import shard_map

n, halo, s = 8, 2, 2
n_loc = n // s
m = n + 2 * halo
rng = np.random.default_rng(3)
devs = np.array(jax.devices('cpu')[:24]).reshape(6, s, s)
mesh = Mesh(devs, ('panel', 'y', 'x'))
program, local_exchange = make_block_halo_program(n, halo, s)

for lead in [(), (3,)]:
    field = jnp.asarray(rng.normal(size=lead + (6, m, m)), jnp.float32)
    ref = make_halo_exchanger(n, halo)(field)

    # Interior -> per-device extended blocks (ghosts zero, filled by the
    # exchange; ghost corners are averaged on both paths).
    h = halo
    interior = field[..., h:h+n, h:h+n]
    pspec = P(*((None,) * len(lead) + ('panel', 'y', 'x')))
    tspec = P('panel', 'y', 'x', None)

    def embed_local(x):
        pad = [(0, 0)] * (x.ndim - 2) + [(h, h), (h, h)]
        return jnp.pad(x, pad)

    def run(x, es, rs, ac):
        return local_exchange(embed_local(x), es, rs, ac)

    es, rs, ac = (program.edge_sel, program.rev_sel, program.active)
    smapped = shard_map(
        run, mesh=mesh,
        in_specs=(pspec, tspec, tspec, tspec),
        out_specs=pspec, check_vma=False)
    blocks = jax.jit(smapped)(interior, es, rs, ac)

    # Gather device blocks back to the global extended layout and compare
    # interiors + ghost rings (excluding corners, averaged vs exact
    # diagonal data at interior block seams).
    got = np.asarray(blocks)
    want = np.asarray(ref)
    # The out spec partitions the last two axes over (y, x), so the
    # stitched global shape is (..., 6, s*m_l, s*m_l) of extended blocks.
    m_l = n_loc + 2 * h
    assert got.shape[-2:] == (s * m_l, s * m_l), got.shape
    for f in range(6):
        for by in range(s):
            for bx in range(s):
                blk = got[..., f, by*m_l:(by+1)*m_l, bx*m_l:(bx+1)*m_l]
                wnt = want[..., f, by*n_loc:by*n_loc+m_l,
                           bx*n_loc:bx*n_loc+m_l]
                # compare everything except the halo x halo corners
                mask = np.ones((m_l, m_l), bool)
                for cy in (slice(0, h), slice(m_l-h, m_l)):
                    for cx in (slice(0, h), slice(m_l-h, m_l)):
                        mask[cy, cx] = False
                np.testing.assert_allclose(
                    blk[..., mask], wnt[..., mask], atol=1e-6,
                    err_msg=f'face {f} block ({by},{bx}) lead {lead}')
print('OK block exchange == reference')
""")
    assert "OK block exchange == reference" in out


@pytest.mark.slow
def test_block_sharded_stepper_matches_single_24dev():
    """Full SWE SSPRK3 step on the 24-device block mesh == single device."""
    out = _run_sub(r"""
import jax
jax.config.update('jax_platforms', 'cpu')
import numpy as np
import jax.numpy as jnp
from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water import ShallowWater
from jaxstream.physics.initial_conditions import williamson_tc2
from jaxstream.parallel.mesh import ShardingSetup, shard_state
from jaxstream.parallel.sharded_model import make_sharded_stepper
from jax.sharding import Mesh

n, halo, s = 12, 2, 2
grid = build_grid(n, halo=halo, radius=EARTH_RADIUS, dtype=jnp.float32)
model = ShallowWater(grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA)
h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
state = model.initial_state(h_ext, v_ext)
dt = 600.0

ref = state
step_ref = model.make_step(dt, 'ssprk3')
for i in range(2):
    ref = step_ref(ref, i * dt)

devs = np.array(jax.devices('cpu')[:24]).reshape(6, s, s)
mesh = Mesh(devs, ('panel', 'y', 'x'))
setup = ShardingSetup(mesh=mesh, num_devices=24, panel=6, sy=s, sx=s,
                      use_shard_map=True)
step = make_sharded_stepper(model, setup, state, dt)
y = shard_state(setup, state)
t = 0.0
for i in range(2):
    y = step(y, jnp.float32(i * dt))
for k in ('h', 'v'):
    a = np.asarray(ref[k], dtype=np.float64)
    b = np.asarray(y[k], dtype=np.float64)
    scale = np.max(np.abs(a)) + 1e-300
    np.testing.assert_allclose(b, a, atol=1e-5 * scale, err_msg=k)
print('OK block sharded stepper == single device')
""")
    assert "OK block sharded stepper == single device" in out
