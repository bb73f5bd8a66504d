"""The main-path kernels compile for a described TPU v5e — no chip needed.

Interpret-mode parity proves what the kernels compute, not that Mosaic
accepts them: a slice off the tiling or too much VMEM only fails in the
TPU compiler.  The TPU compiler is installed here and compiles for a
chip that is described, not attached
(``jax.experimental.topologies``), so each test lowers one main-path
stepper at its real width for one chip of a ``v5e:2x2`` topology and
checks that the Pallas kernel is in the program and that it fits the
chip's 16 GiB.  A compile that passes is not a chip run: what the
kernels compute on the chip is ``chip_smoke.py``'s job.

The topology is described in a module fixture, never at import: only
one process at a time may load the TPU library, and under xdist every
worker imports this file (see the on-chip-measurement guide, section 2).
Keep every such compile in THIS file, so one worker holds the library.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water_cov import CovariantShallowWater
from jaxstream.physics.initial_conditions import williamson_tc5

#: One v5e chip's HBM (Google Cloud documentation, "TPU v5e": 16 GB).
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, compiled for in f32 with the persistent
    cache off (a topology compile cannot be read back without a chip,
    and x64 index types are not legal in Mosaic)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved = {n: getattr(jax.config, n) for n in
             ("jax_enable_x64", "jax_enable_compilation_cache")}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_x64", False)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            for n, v in saved.items():
                jax.config.update(n, v)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described host."""
    return SingleDeviceSharding(topo.devices[0])


def _model(n, halo=2, **kw):
    grid = build_grid(n, halo=halo, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA, b_ext=b_ext,
                                  backend="pallas", **kw)
    return model, model.initial_state(h_ext, v_ext)


def _fused(n, halo=2, dt=75.0, **kw):
    """The single-chip Simulation stepper and its carry's shapes."""
    model, state = _model(n, halo, **kw)
    return (model.make_fused_step(dt),
            jax.eval_shape(model.compact_state, state))


def _ensemble(n, members):
    """The batched-ensemble stepper (member axis folded into the stage
    kernels' grid) and its batched carry's shapes."""
    model, state = _model(n)
    batched = {"h": jnp.stack([state["h"]] * members),
               "u": jnp.stack([state["u"]] * members, axis=1)}
    return (model.make_fused_step(75.0, ensemble=members),
            jax.eval_shape(model.ensemble_compact_state, batched))


CASES = {
    # TC5 C384, the headline forecast: compact PLR stage kernels.
    "compact_plr_c384": lambda: _fused(384),
    # PPM reconstruction needs halo 3: its own strip widths.
    "compact_ppm_c384": lambda: _fused(384, halo=3, scheme="ppm"),
    # examples/configs/galewsky_c384.yaml: the split del^4 filter
    # kernel after the three stage kernels.
    "galewsky_nu4_split_c384": lambda: _fused(384, dt=60.0, nu4=1.0e14),
    # The serving tier's kernel fold: B=4 members per launch at C96.
    "ensemble_fold_b4_c96": lambda: _ensemble(96, 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_main_path_compiles_for_v5e(one_chip, case):
    step, carry = CASES[case]()
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=one_chip), carry)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = jax.jit(step).lower(args, t).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, (case, total)


def test_member_placed_serving_segment_compiles_for_v5e_2x2(topo):
    """The member-placed fused bucket's masked segment (the serving
    tier's ``shard_map`` of the local-batch member fold, 2 members per
    chip at C96) compiles for the four chips of the described host,
    holds the stage kernels and no collective, and its per-chip
    inject and extract compile for one chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from jaxstream.parallel.mesh import member_shard_map, member_spec
    from jaxstream.serve.server import (_FUSED_AXES, _member_nonfinite,
                                        _with_member, _take_member)
    from jaxstream.stepping import integrate_masked

    mesh = Mesh(np.array(topo.devices), ("member",))
    chips, per = len(topo.devices), 2
    model, state = _model(96)
    step = model.make_fused_step(75.0, ensemble=per)
    axes = _FUSED_AXES

    def seg_body(y, rem):
        y, _, rem = integrate_masked(step, y, 0.0, rem, 4, 75.0, axes)
        return y, rem, _member_nonfinite(y, {"h": 0, "u": 1})

    B = chips * per
    carry = jax.eval_shape(model.ensemble_compact_state, {
        "h": jnp.stack([state["h"]] * B),
        "u": jnp.stack([state["u"]] * B, axis=1)})
    args = {k: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=NamedSharding(mesh, member_spec(axes[k])))
        for k, s in carry.items()}
    rem = jax.ShapeDtypeStruct((B,), jnp.int32,
                               sharding=NamedSharding(mesh, member_spec(0)))
    seg = jax.jit(member_shard_map(seg_body, mesh, (axes, 0), (axes, 0, 0)))
    text = seg.lower(args, rem).compile().as_text()
    assert "tpu_custom_call" in text
    collectives = ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter")
    assert [c for c in collectives if c in text] == []

    one = SingleDeviceSharding(topo.devices[1])
    local = {k: jax.ShapeDtypeStruct(
        s.shape[:axes[k]] + (per,) + s.shape[axes[k] + 1:], s.dtype,
        sharding=one) for k, s in carry.items()}
    member = {k: jax.ShapeDtypeStruct(state[k].shape, jnp.float32,
                                      sharding=one) for k in ("h", "u")}
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    for fn, a in ((lambda y, i, m: _with_member(
                       y, i, model.compact_state(m), axes),
                   (local, idx, member)),
                  (lambda y, i: _take_member(y, i, axes), (local, idx))):
        text = jax.jit(fn).lower(*a).compile().as_text()
        assert [c for c in collectives if c in text] == []
