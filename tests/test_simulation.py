"""End-to-end driver tests: config -> Simulation -> run -> outputs.

Covers the reference's implied top-level loop (SURVEY.md §3.4) — config
load, IC dispatch, sharded vs single-device parity, history output,
checkpoint/restart resume — on tiny grids.
"""

import json
import math

import numpy as np
import pytest

from jaxstream.simulation import Simulation, run_from_config


def _cfg(tmp_path=None, **over):
    cfg = {
        "grid": {"n": 12, "halo": 2, "dtype": "float64"},
        "model": {"initial_condition": "tc2"},
        "time": {"dt": 600.0, "nsteps": 4},
        "parallelization": {"num_devices": 1},
    }
    for k, v in over.items():
        cfg.setdefault(k, {}).update(v)
    if tmp_path is not None:
        cfg["io"] = {
            "history_path": str(tmp_path / "hist"),
            "history_stride": 2,
            "checkpoint_path": str(tmp_path / "ckpt"),
            "checkpoint_stride": 2,
            **cfg.get("io", {}),
        }
    return cfg


def test_tc2_run_conserves_mass():
    sim = Simulation(_cfg())
    m0 = sim.diagnostics()["mass"]
    sim.run()
    assert sim.step_count == 4
    assert sim.t == pytest.approx(4 * 600.0)
    d = sim.diagnostics()
    assert math.isfinite(d["energy"])
    assert d["mass"] == pytest.approx(m0, rel=1e-12)  # flux-form exactness


def test_duration_days_sets_total_steps():
    sim = Simulation(_cfg(time={"nsteps": 0, "duration_days": 0.5, "dt": 3600.0}))
    assert sim.total_steps() == 12


@pytest.mark.parametrize("ic,key", [("tc1", "q"), ("checkerboard", "T")])
def test_other_model_families(ic, key):
    sim = Simulation(_cfg(model={"initial_condition": ic}))
    sim.run()
    out = np.asarray(sim.state[key])
    assert np.all(np.isfinite(out))


def test_incompatible_model_name_rejected():
    with pytest.raises(ValueError, match="incompatible"):
        Simulation(_cfg(model={"name": "diffusion", "initial_condition": "tc2"}))


def test_unknown_ic_rejected():
    with pytest.raises(ValueError, match="initial_condition"):
        Simulation(_cfg(model={"initial_condition": "nope"}))


@pytest.mark.slow
def test_history_and_checkpoint_resume(tmp_path):
    cfg = _cfg(tmp_path)
    sim = Simulation(cfg)
    sim.run()
    # History: IC + records at steps 2 and 4.
    from jaxstream.io.zarrlite import open_group

    g = open_group(str(tmp_path / "hist"))
    assert g["time"].shape[0] == 3
    assert g["h"].shape[0] == 3

    # A fresh Simulation resumes from the step-4 checkpoint and continues.
    sim2 = Simulation(cfg)
    assert sim2.step_count == 4
    assert sim2.t == pytest.approx(sim.t)
    np.testing.assert_allclose(
        np.asarray(sim2.state["h"]), np.asarray(sim.state["h"])
    )
    sim2.run(6)
    assert sim2.step_count == 6


def test_regrid_operator_conserves_mass():
    """Unit level: overlap rows partition, constants pass through, and
    the area-weighted transfer conserves mass in the model's measure to
    the midpoint-rule O(dalpha^2) (both directions)."""
    import jax.numpy as jnp

    from jaxstream.config import EARTH_RADIUS
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.io.regrid import overlap_matrix, regrid_state

    W = overlap_matrix(24, 36)  # non-integer ratio
    np.testing.assert_allclose(W.sum(axis=1), 1.0, rtol=1e-12)

    g24 = build_grid(24, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    g48 = build_grid(48, halo=2, radius=EARTH_RADIUS, dtype=jnp.float64)
    a24 = np.asarray(g24.interior(g24.area), np.float64)
    a48 = np.asarray(g48.interior(g48.area), np.float64)

    xyz = (np.asarray(g24.interior(g24.xyz), np.float64)
           / EARTH_RADIUS)                               # unit sphere
    h = 1000.0 + 100.0 * xyz[2] + 20.0 * xyz[0] * xyz[1]
    state = {"h": jnp.asarray(h), "u": jnp.asarray(
        np.stack([xyz[0], xyz[1]]))}

    up = regrid_state(state, 48)
    assert np.shape(up["h"]) == (6, 48, 48)
    assert np.shape(up["u"]) == (2, 6, 48, 48)
    m24 = np.sum(a24 * h)
    m48 = np.sum(a48 * np.asarray(up["h"], np.float64))
    assert abs(m48 - m24) / abs(m24) < 1e-12     # exact in model measure

    down = regrid_state({"h": up["h"]}, 24)
    m24b = np.sum(a24 * np.asarray(down["h"], np.float64))
    assert abs(m24b - m24) / abs(m24) < 1e-12

    # Constants pick up only the documented O(dalpha^2) area ripple.
    const = regrid_state({"h": jnp.full((6, 24, 24), 7.5)}, 48)
    np.testing.assert_allclose(np.asarray(const["h"]), 7.5, rtol=5e-4)


def test_resume_across_resolutions(tmp_path):
    """SURVEY.md §5: restart must be resolution-aware — a C12 checkpoint
    resumes into a C24 run via the conservative regrid and keeps
    integrating with mass preserved."""
    cfg12 = _cfg(tmp_path)
    sim = Simulation(cfg12)
    sim.run()
    m12 = sim.diagnostics()["mass"]

    # Same checkpoint dir (the resume source); history gets its own
    # store — snapshot shapes change with resolution.
    cfg24 = _cfg(tmp_path, grid={"n": 24},
                 io={"history_path": str(tmp_path / "hist24")})
    sim2 = Simulation(cfg24)
    assert sim2.step_count == 4            # resumed from the checkpoint
    assert np.shape(sim2.state["h"]) == (6, 24, 24)
    m24 = sim2.diagnostics()["mass"]
    assert abs(m24 - m12) / abs(m12) < 1e-10
    sim2.run(6)                            # and it keeps integrating
    assert sim2.step_count == 6
    assert np.all(np.isfinite(np.asarray(sim2.state["h"])))


def test_resume_across_resolutions_non_swe_state(tmp_path):
    """Resolution inference must not assume an 'h' key — advection
    states carry 'q' (regression guard)."""
    cfg = _cfg(tmp_path, model={"initial_condition": "tc1"})
    Simulation(cfg).run()
    sim2 = Simulation(_cfg(tmp_path, model={"initial_condition": "tc1"},
                           grid={"n": 24},
                           io={"history_path": str(tmp_path / "h24")}))
    assert sim2.step_count == 4
    assert np.shape(sim2.state["q"]) == (6, 24, 24)
    sim2.run(6)
    assert np.all(np.isfinite(np.asarray(sim2.state["q"])))


@pytest.mark.slow
def test_sharded_matches_single_device():
    ref = Simulation(_cfg())
    ref.run()
    for shard_map in (False, True):
        sh = Simulation(_cfg(parallelization={
            "num_devices": 6, "device_type": "cpu", "use_shard_map": shard_map,
        }))
        sh.run()
        np.testing.assert_allclose(
            np.asarray(sh.state["h"]), np.asarray(ref.state["h"]),
            rtol=1e-12, atol=1e-9,
        )


@pytest.mark.slow
def test_lazy_grid_shard_map_matches_single_device():
    """The TPU-production combination: lazy metrics inside shard_map."""
    grid = {"n": 12, "halo": 2, "dtype": "float64", "metrics": "lazy"}
    ref = Simulation(_cfg(grid=grid))
    ref.run()
    sh = Simulation(_cfg(grid=grid, parallelization={
        "num_devices": 6, "device_type": "cpu", "use_shard_map": True,
    }))
    sh.run()
    np.testing.assert_allclose(
        np.asarray(sh.state["h"]), np.asarray(ref.state["h"]),
        rtol=1e-12, atol=1e-9,
    )


def test_pallas_backend_rejects_non_f32_grid():
    with pytest.raises(ValueError, match="float32"):
        Simulation(_cfg(model={"backend": "pallas"}))  # f64 grid in _cfg


def test_cli_run_and_info(tmp_path, capsys):
    from jaxstream.__main__ import main

    cfgfile = tmp_path / "cfg.yaml"
    import yaml

    cfgfile.write_text(yaml.safe_dump(_cfg()))
    main(["run", str(cfgfile)])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["steps"] == 4

    main(["info", str(cfgfile)])
    assert "grid: C12" in capsys.readouterr().out

    main(["schedule"])
    text = capsys.readouterr().out
    assert text.count("stage") == 4


def test_yaml_exponent_literals_coerce_to_float():
    """YAML 1.1 parses '1.0e14' (no sign) as a string; the loader must
    coerce to the declared field type — the form every example config
    uses for physics.hyperdiffusion."""
    from jaxstream.config import load_config

    cfg = load_config(
        "physics:\n  hyperdiffusion: 1.0e14\ntime:\n  dt: '300'\n"
    )
    assert cfg.physics.hyperdiffusion == 1.0e14
    assert isinstance(cfg.physics.hyperdiffusion, float)
    assert cfg.time.dt == 300.0

    import pytest

    with pytest.raises(ValueError, match="expects a float"):
        load_config("physics:\n  hyperdiffusion: banana\n")


@pytest.mark.slow
def test_simulation_uses_fused_stepper_for_pallas_swe():
    """Single-device pallas SWE sims run the fused extended-state path
    and match the classic jnp path to f32 roundoff."""
    base = {
        "grid": {"n": 16, "halo": 2},
        "model": {"name": "shallow_water_cov", "initial_condition": "tc5"},
        "time": {"dt": 600.0, "nsteps": 6},
        "parallelization": {"num_devices": 1, "device_type": "cpu"},
        "io": {},
    }
    ref = Simulation({**base})
    ref.run(6)

    cfg = {**base, "model": {**base["model"], "backend": "pallas_interpret"}}
    sim = Simulation(cfg)
    assert sim._fused_step is not None
    sim.run(6)

    a = np.asarray(ref.state["h"], dtype=np.float64)
    b = np.asarray(sim.state["h"], dtype=np.float64)
    scale = np.max(np.abs(a))
    np.testing.assert_allclose(b, a, atol=2e-4 * scale)


# ------------------------------------------------------- no hidden fallback
def _pallas_cfg(members=1):
    return {
        "grid": {"n": 8, "halo": 2, "dtype": "float32"},
        "model": {"name": "shallow_water_cov", "initial_condition": "tc5",
                  "backend": "pallas"},
        "time": {"dt": 600.0, "nsteps": 2},
        "ensemble": {"members": members},
        "parallelization": {"num_devices": 1},
    }


@pytest.mark.parametrize("members", [1, 2])
def test_pallas_fused_build_failure_raises(monkeypatch, members):
    """A run that asked for backend: pallas never drops silently to the
    ~2x slower classic tier: a fused-stepper build failure raises."""
    from jaxstream.models.shallow_water_cov import CovariantShallowWater

    def refuse(self, *a, **kw):
        raise RuntimeError("mosaic refused the stage kernel")

    monkeypatch.setattr(CovariantShallowWater, "make_fused_step", refuse)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        Simulation(_pallas_cfg(members))


def test_fused_first_call_failure_surfaces(monkeypatch):
    """make_fused_step only builds; Mosaic compiles at the first call.
    A failure there propagates out of run() — nothing swallows it."""
    from jaxstream.models.shallow_water_cov import CovariantShallowWater

    def lazy_refusal(self, dt, **kw):
        def step(y, t):
            raise RuntimeError("Mosaic failed to compile the kernel")
        return step

    monkeypatch.setattr(CovariantShallowWater, "make_fused_step",
                        lazy_refusal)
    sim = Simulation(_pallas_cfg())
    assert sim._fused_step is not None
    assert sim.proof.plan_key.split("+")[0] == "fused"
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        sim.run(2)


def test_available_devices_has_no_fallback():
    """'tpu' means jax.devices('tpu'): a CPU-only host raises instead
    of landing on another backend; unknown kinds are refused."""
    from jaxstream.parallel.mesh import available_devices

    assert available_devices("cpu")[0].platform == "cpu"
    with pytest.raises(RuntimeError, match="no tpu devices"):
        available_devices("tpu")
    with pytest.raises(ValueError, match="unknown device_type"):
        available_devices("npu")
