"""Explicit shard_map + ppermute halo path vs the global (GSPMD) path.

The two execution strategies share one numerics source, so results must
match to roundoff; this is the rebuild's version of the reference's
"prove sharding works" validation (deck p.12, p.18) as an exact test.
Runs on 6 of the 8 virtual CPU devices fabricated in conftest.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water import ShallowWater
from jaxstream.parallel.halo import make_halo_exchanger
from jaxstream.parallel.mesh import setup_sharding, shard_state
from jaxstream.parallel.shard_halo import make_shard_halo_program
from jaxstream.parallel.sharded_model import (
    _face_spec,
    make_sharded_stepper,
    shard_params,
)
from jaxstream.physics.initial_conditions import williamson_tc2

CONF = {"parallelization": {"num_devices": 6, "device_type": "cpu",
                            "tiles_per_edge": 1}}


@pytest.fixture(scope="module")
def setup6():
    return setup_sharding(CONF)


def _exchange_via_shard_map(setup, field, n, halo):
    program, lex = make_shard_halo_program(n, halo)
    params = shard_params(setup, dict(program.params))
    pspecs = jax.tree_util.tree_map(_face_spec, params)
    fspec = _face_spec(field)
    fn = shard_map(
        lambda p, f: lex(f, p["edge_sel"], p["rev_sel"]),
        mesh=setup.mesh, in_specs=(pspecs, fspec), out_specs=fspec,
        check_vma=False,
    )
    fld = jax.device_put(field, NamedSharding(setup.mesh, fspec))
    return jax.jit(fn)(params, fld)


@pytest.mark.parametrize("lead", [(), (3,)])
def test_shard_halo_matches_global(setup6, lead):
    n, halo = 16, 2
    m = n + 2 * halo
    rng = np.random.default_rng(7)
    field = jnp.asarray(rng.normal(size=lead + (6, m, m)))
    ref = make_halo_exchanger(n, halo)(field)
    out = _exchange_via_shard_map(setup6, field, n, halo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=0, atol=0)


def test_sharded_swe_step_matches_single_device(setup6):
    n = 12
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    model = ShallowWater(grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA)
    h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    state = model.initial_state(h_ext, v_ext)
    dt = 300.0

    ref_step = jax.jit(model.make_step(dt))
    ref = ref_step(state, 0.0)

    sstep = make_sharded_stepper(model, setup6, state, dt)
    sstate = shard_state(setup6, state)
    out = sstep(sstate, 0.0)

    for k in ref:
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(ref[k]), rtol=1e-12, atol=1e-12,
            err_msg=f"state field {k}",
        )


def test_sharded_stepper_rejects_wrong_mesh():
    setup1 = setup_sharding({"parallelization": {"num_devices": 1,
                                                 "device_type": "cpu"}})
    grid = build_grid(8, halo=2, dtype=jnp.float64)
    model = ShallowWater(grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA)
    with pytest.raises(ValueError, match="panel=6"):
        make_sharded_stepper(model, setup1, {"h": grid.sqrtg}, 60.0)
