"""Batched ensemble execution: member-axis parity across the stack.

The batching acceptance contract (round 7):

* **B=1 bitwise**: the batched fused stepper with one member is
  bitwise-identical to the unbatched compact stepper — the member-axis
  fold (kernel grid ``6*B``, vmapped router) adds NO arithmetic.
* **Batched exchange bitwise**: one ppermute carrying all members'
  stacked strips ships per-member ghosts/sym values bitwise-equal to a
  per-member exchange loop, on the dense face tier and the factored TT
  wrapper (a ppermute of stacked payloads IS the stack of per-member
  ppermutes).
* **B>1 member parity is ulp-level, not bitwise**: per-member values of
  the kernel-batched stepper match the vmapped reference (and separate
  single-member runs) to single f32 ulps — XLA contracts mul+add chains
  into FMAs shape-dependently, so the (B, ...)-shaped router/kernel
  subgraphs round a few last bits differently than the (6, ...)-shaped
  ones (first visible in u's rotation chains; the tail feeds h from
  step 2 on).  Same budget class as the overlap/temporal split tiers.

Plumbing (mesh factoring, comm accounting, config wiring, Simulation
end-to-end) rides along in the fast tier; kernel parities beyond the
B=1 acceptance are slow-marked with the other interpret-mode parities.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jaxstream.config import EARTH_GRAVITY, EARTH_OMEGA, EARTH_RADIUS
from jaxstream.geometry.cubed_sphere import build_grid
from jaxstream.models.shallow_water_cov import (ENSEMBLE_CARRY_AXES,
                                                CovariantShallowWater)
from jaxstream.physics.initial_conditions import (perturbed_ensemble,
                                                  williamson_tc5)


def _needs6():
    if len(jax.devices("cpu")) < 6:
        pytest.skip("needs 6 virtual CPU devices")


def _model(n=8, backend="pallas_interpret"):
    grid = build_grid(n, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, v_ext, b_ext = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(
        grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA, b_ext=b_ext,
        backend=backend)
    return grid, model, h_ext, v_ext


def _member(y, k, i):
    return y[k][:, i] if k == "u" else y[k][i]


# ------------------------------------------------------ fused stepper


def test_b1_batched_bitwise_vs_unbatched():
    """THE acceptance criterion: ensemble=1 batched step == unbatched
    compact step, every carry leaf exactly equal (interpret mode)."""
    grid, model, h_ext, v_ext = _model()
    dt = 600.0
    st = model.initial_state(h_ext, v_ext)
    out1 = jax.jit(model.make_fused_step(dt))(
        model.compact_state(st), jnp.float32(0.0))
    yb = model.ensemble_compact_state(model.stack_ensemble([st]))
    outb = jax.jit(model.make_fused_step(dt, ensemble=1))(
        yb, jnp.float32(0.0))
    for k in out1:
        assert bool(jnp.all(_member(outb, k, 0) == out1[k])), k


@pytest.mark.slow
def test_ensemble_kernel_matches_vmap_reference():
    """B=3 kernel-batched vs the vmapped reference and vs separate
    single-member runs over 3 steps: ulp-level per member (see module
    docstring), h bitwise; temporal_block composes exactly."""
    grid, model, h_ext, v_ext = _model()
    dt = 600.0
    B = 3
    h_b = perturbed_ensemble(grid, h_ext, B, seed=1, amplitude=1e-3)
    states = [model.initial_state(h_b[i], v_ext) for i in range(B)]
    yb = model.ensemble_compact_state(model.stack_ensemble(states))

    stepk = jax.jit(model.make_fused_step(dt, ensemble=B))
    stepv = jax.jit(model.make_fused_step(dt, ensemble=B,
                                          ensemble_impl="vmap"))
    step1 = jax.jit(model.make_fused_step(dt))
    ok, ov = yb, yb
    singles = [model.compact_state(s) for s in states]
    for _ in range(3):
        ok = stepk(ok, jnp.float32(0.0))
        ov = stepv(ov, jnp.float32(0.0))
        singles = [step1(s, jnp.float32(0.0)) for s in singles]

    for i in range(B):
        for k in singles[0]:
            a = np.asarray(_member(ok, k, i), np.float64)
            for ref in (np.asarray(_member(ov, k, i), np.float64),
                        np.asarray(singles[i][k], np.float64)):
                scale = np.abs(ref).max() + 1e-300
                rel = np.abs(a - ref).max() / scale
                # 1e-6 is ~10 f32 ulps: catches any cross-member leak
                # (members differ by 1e-3 relative) while allowing the
                # shape-dependent FMA tail to accumulate over 3 steps.
                assert rel <= 1e-6, (k, i, rel)

    # The vmapped reference carries the same shape-dependent FMA tail
    # once compiled (vmap maps semantics; XLA still contracts the
    # batched subgraphs its own way) — same ulp budget.
    for i in range(B):
        for k in singles[0]:
            a = np.asarray(_member(ov, k, i), np.float64)
            ref = np.asarray(singles[i][k], np.float64)
            rel = np.abs(a - ref).max() / (np.abs(ref).max() + 1e-300)
            assert rel <= 1e-6, ("vmap", k, i, rel)

    # Exact k-step fusion: temporal_block=3 block == 3 batched steps.
    blk = jax.jit(model.make_fused_step(dt, ensemble=B,
                                        temporal_block=3))
    ob = blk(yb, jnp.float32(0.0))
    for k in ob:
        assert bool(jnp.all(ob[k] == ok[k])), k


def test_ensemble_make_fused_step_validation():
    _, model, _, _ = _model()
    with pytest.raises(ValueError, match="compact"):
        model.make_fused_step(600.0, compact=False, ensemble=2)
    with pytest.raises(ValueError, match="ensemble_impl"):
        model.make_fused_step(600.0, ensemble=2, ensemble_impl="nope")
    grid = build_grid(8, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    nu4_model = CovariantShallowWater(
        grid, gravity=EARTH_GRAVITY, omega=EARTH_OMEGA,
        backend="pallas_interpret", nu4=1e14)
    with pytest.raises(ValueError, match="nu4"):
        nu4_model.make_fused_step(600.0, ensemble=2)


# ------------------------------------------------- batched exchange


def test_batched_face_exchange_bitwise_vs_loop():
    """Dense face tier: the vmapped batched exchange (one ppermute per
    schedule stage for ALL members) ships ghosts + sym strips bitwise-
    equal to a per-member exchange loop, and its jaxpr carries exactly
    4 ppermutes for the whole ensemble."""
    _needs6()
    from jax.sharding import PartitionSpec as P

    from jaxstream.parallel.mesh import setup_sharding
    from jaxstream.parallel.shard_cov import (
        CovShardProgram, make_cov_shard_exchange,
        make_cov_shard_exchange_batched)
    from jax import shard_map

    grid = build_grid(8, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    setup = setup_sharding({"parallelization": {
        "num_devices": 6, "device_type": "cpu", "use_shard_map": True}})
    mesh = setup.mesh
    program = CovShardProgram(grid)
    tables = program.tables
    axes = mesh.axis_names
    B, m = 3, grid.m
    tspec = {k: P(axes[0]) for k in tables}

    exb = make_cov_shard_exchange_batched(program)
    sb = shard_map(exb, mesh=mesh,
                   in_specs=(P(None, axes[0]), P(None, None, axes[0]),
                             tspec),
                   out_specs=(P(None, axes[0]), P(None, None, axes[0]),
                              P(None, axes[0]), P(None, axes[0])),
                   check_vma=False)
    ex1 = make_cov_shard_exchange(program)
    s1 = shard_map(ex1, mesh=mesh,
                   in_specs=(P(axes[0]), P(None, axes[0]), tspec),
                   out_specs=(P(axes[0]), P(None, axes[0]),
                              P(axes[0]), P(axes[0])),
                   check_vma=False)

    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.normal(size=(B, 6, m, m)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, B, 6, m, m)), jnp.float32)
    ho, uo, ssn, swe = jax.jit(lambda h, u: sb(h, u, tables))(h, u)
    f1 = jax.jit(lambda h, u: s1(h, u, tables))
    for b in range(B):
        h1, u1, n1, w1 = f1(h[b], u[:, b])
        assert bool(jnp.all(h1 == ho[b]))
        assert bool(jnp.all(u1 == uo[:, b]))
        assert bool(jnp.all(n1 == ssn[b]))
        assert bool(jnp.all(w1 == swe[b]))

    jx = str(jax.make_jaxpr(lambda h, u: sb(h, u, tables))(h, u))
    assert jx.count(" ppermute") == 4


def test_tt_ensemble_exchange_bitwise_vs_loop():
    """TT wrapper: one flattened exchange_many schedule for B members'
    factor pairs == per-member exchange calls, bitwise."""
    _needs6()
    from jax.sharding import PartitionSpec as P

    from jaxstream.tt.shard import (make_tt_ensemble_exchange,
                                    make_tt_strip_exchange, panel_mesh,
                                    shard_factored_state)
    from jaxstream.tt.sphere import factor_panels
    from jax import shard_map

    rng = np.random.default_rng(3)
    n, rank, B = 16, 5, 3
    mesh = panel_mesh(jax.devices("cpu")[:6])
    members = [[factor_panels(rng.standard_normal((6, n, n)), r)
                for r in (rank, rank + 1)] for _ in range(B)]
    members = [[shard_factored_state(p, mesh) for p in mem]
               for mem in members]

    one = make_tt_strip_exchange()
    ens = make_tt_ensemble_exchange()
    spec = P("panel")
    flat = [p for mem in members for p in mem]

    def run_ens(*ps):
        mems = [list(ps[i * 2:(i + 1) * 2]) for i in range(B)]
        out = ens(mems)
        return tuple(g for mem in out for pair in mem for g in pair)

    def run_loop(*ps):
        return tuple(g for p in ps for g in one(p))

    f_e = jax.jit(shard_map(run_ens, mesh=mesh, in_specs=spec,
                            out_specs=spec, check_vma=False))
    f_l = jax.jit(shard_map(run_loop, mesh=mesh, in_specs=spec,
                            out_specs=spec, check_vma=False))
    a = f_e(*flat)
    b = f_l(*flat)
    assert len(a) == len(b) == B * 2 * 4
    for xa, xb in zip(a, b):
        assert (np.asarray(xa) == np.asarray(xb)).all()


@pytest.mark.slow
def test_sharded_ensemble_stepper_matches_single():
    """Face-tier batched ensemble stepper (vmapped body, one ppermute
    per stage for all members): per-member bitwise vs the single-member
    explicit stepper over 2 steps, and 12 ppermutes per step for the
    whole ensemble in the jaxpr."""
    _needs6()
    from jaxstream.parallel.mesh import (setup_sharding,
                                         shard_ensemble_state,
                                         shard_state)
    from jaxstream.parallel.shard_cov import (
        make_sharded_cov_ensemble_stepper, make_sharded_cov_stepper)

    grid, model, h_ext, v_ext = _model(n=8, backend="jnp")
    dt = 600.0
    B = 2
    setup = setup_sharding({"parallelization": {
        "num_devices": 6, "device_type": "cpu", "use_shard_map": True}})
    h_b = perturbed_ensemble(grid, h_ext, B, seed=2, amplitude=1e-3)
    states = [model.initial_state(h_b[i], v_ext) for i in range(B)]
    batched = shard_ensemble_state(setup, model.stack_ensemble(states))

    stepe = make_sharded_cov_ensemble_stepper(model, setup, dt, B)
    step1 = make_sharded_cov_stepper(model, setup, dt)
    out = batched
    singles = [shard_state(setup, s) for s in states]
    for _ in range(2):
        out = stepe(out, 0.0)
        singles = [step1(s, 0.0) for s in singles]
    for i in range(B):
        for k in ("h", "u"):
            a = _member(out, k, i)
            assert bool(jnp.all(a == singles[i][k])), (k, i)

    jx = str(jax.make_jaxpr(
        lambda y: stepe(y, jnp.float32(0.0)))(batched))
    assert jx.count(" ppermute") == 12

    # overlap_exchange composes: batched phase-split vs serialized at
    # the established ulp budget of the interior/band split.
    stepo = make_sharded_cov_ensemble_stepper(model, setup, dt, B,
                                              overlap=True)
    oo = stepo(batched, 0.0)
    oe = stepe(batched, 0.0)
    for k in ("h", "u"):
        a = np.asarray(oo[k], np.float64)
        b = np.asarray(oe[k], np.float64)
        rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-300)
        assert rel <= 1e-6, (k, rel)


# ------------------------------------------------------ mesh + probes


def test_ensemble_mesh_factoring_and_errors():
    _needs6()
    from jaxstream.parallel.mesh import setup_ensemble_sharding

    setup = setup_ensemble_sharding({"parallelization": {
        "num_devices": 6, "device_type": "cpu"}}, members=4)
    assert setup.panel == 6 and setup.member == 1
    assert setup.mesh.axis_names == ("panel", "member")
    spec = setup.ensemble_spec_for(4)
    assert spec == jax.sharding.PartitionSpec("member", "panel",
                                              None, None)
    with pytest.raises(ValueError, match="multiple of 6"):
        setup_ensemble_sharding({"parallelization": {
            "num_devices": 4, "device_type": "cpu"}}, members=4)
    single = setup_ensemble_sharding({"parallelization": {
        "num_devices": 1}}, members=8)
    assert single.mesh is None


def test_batched_exchange_plan_accounting():
    from jaxstream.utils.comm_probe import (batched_exchange_plan,
                                            format_report,
                                            run_default_probe)

    p1 = batched_exchange_plan(96, 2, 1)
    p16 = batched_exchange_plan(96, 2, 16)
    # Same 12 collectives per ensemble step regardless of B...
    assert p1["ppermutes_per_step"] == p16["ppermutes_per_step"] == 12.0
    # ...so per-member launches drop B-fold...
    assert p16["ppermutes_per_member_step"] == 12.0 / 16
    assert p16["launch_latency_ratio"] == 1.0 / 16
    # ...while per-member wire bytes are invariant (stacked payloads).
    assert (p16["wire_bytes_per_member_step"]
            == p1["wire_bytes_per_member_step"])
    assert (p16["payload_bytes_per_ppermute"]
            == 16 * p1["payload_bytes_per_ppermute"])
    with pytest.raises(ValueError, match="members"):
        batched_exchange_plan(96, 2, 0)

    class FakeDev:
        platform = "tpu"

    out = run_default_probe(devices=[FakeDev()] * 8, members=16,
                            plan_only=True)
    assert out["batched_exchange_plan"]["members"] == 16
    rep = format_report(out)
    assert "batched exchange B=16" in rep


def test_analytic_cost_ensemble_scaling():
    """Roofline accounting: B scales flops AND bytes together — the
    intensity must NOT inflate with B (the truthful-roofline
    satellite)."""
    from jaxstream.utils.profiling import analytic_cov_step_cost

    c1 = analytic_cov_step_cost(96)
    c8 = analytic_cov_step_cost(96, ensemble=8)
    assert c8["flops"] == 8 * c1["flops"]
    assert c8["bytes"] == 8 * c1["bytes"]
    assert c8["ai"] == c1["ai"]
    with pytest.raises(ValueError, match="ensemble"):
        analytic_cov_step_cost(96, ensemble=0)


# ------------------------------------------------- ICs + simulation


def test_perturbed_ensemble_fields():
    grid = build_grid(8, halo=2, radius=EARTH_RADIUS, dtype=jnp.float32)
    h_ext, _, _ = williamson_tc5(grid, EARTH_GRAVITY, EARTH_OMEGA)
    hb = perturbed_ensemble(grid, h_ext, 4, seed=5, amplitude=1e-3)
    assert hb.shape == (4,) + h_ext.shape
    # member 0 unperturbed; others perturbed at the relative amplitude
    assert bool(jnp.all(hb[0] == jnp.asarray(h_ext, hb.dtype)))
    href = float(np.mean(np.abs(np.asarray(h_ext, np.float64))))
    for i in (1, 2, 3):
        d = np.abs(np.asarray(hb[i], np.float64)
                   - np.asarray(h_ext, np.float64))
        # 1e-3 * href bound with slack for the f32 cast of hb's leaves.
        assert 0.0 < d.max() <= 1e-3 * href * 1.001, i
    # deterministic in the seed
    hb2 = perturbed_ensemble(grid, h_ext, 4, seed=5, amplitude=1e-3)
    assert bool(jnp.all(hb == hb2))
    assert not bool(jnp.all(
        hb == perturbed_ensemble(grid, h_ext, 4, seed=6,
                                 amplitude=1e-3)))


def test_simulation_ensemble_end_to_end():
    """Config-driven ensemble run (vmapped classic path on CPU): the
    batched state advances all members, member 0 exactly reproduces a
    single-member run, and diagnostics report the ensemble spread."""
    from jaxstream.simulation import Simulation

    base = {
        "grid": {"n": 12},
        "model": {"name": "shallow_water_cov",
                  "initial_condition": "tc5"},
        "time": {"dt": 600.0, "nsteps": 2},
    }
    cfg = dict(base, ensemble={"members": 3, "seed": 9,
                               "amplitude": 1e-3})
    sim = Simulation(cfg)
    assert sim.members == 3
    assert sim.state["h"].shape[0] == 3
    sim.run()
    d = sim.diagnostics()
    assert "h_spread_max" in d and d["h_spread_max"] > 0.0
    assert np.isfinite(d["mass_m0"]) and np.isfinite(d["energy_m0"])
    h_ens = np.asarray(sim.state["h"], np.float64)
    assert np.all(np.isfinite(h_ens))

    ref = Simulation(base)
    ref.run()
    # member 0 is the unperturbed member: bitwise the single run
    # (vmap adds no arithmetic on this path).
    np.testing.assert_array_equal(h_ens[0], np.asarray(ref.state["h"],
                                                       np.float64))


def test_simulation_ensemble_cartesian_model():
    """The member-axis rule covers the Cartesian state too ("v" keeps
    its component axis first, member second)."""
    from jaxstream.simulation import Simulation

    sim = Simulation({
        "grid": {"n": 8},
        "model": {"initial_condition": "tc2"},
        "time": {"dt": 600.0, "nsteps": 1},
        "ensemble": {"members": 2, "amplitude": 1e-3},
    })
    assert sim.state["h"].shape == (2, 6, 8, 8)
    assert sim.state["v"].shape == (3, 2, 6, 8, 8)
    sim.run()
    assert np.all(np.isfinite(np.asarray(sim.state["h"])))
    d = sim.diagnostics()
    assert d["h_spread_max"] > 0.0


def test_jit_integrate_donates_and_matches():
    """stepping.jit_integrate: same trajectory as plain integrate, one
    executable across window lengths, and the state carry actually
    donated (the no-double-buffering satellite)."""
    from jaxstream.stepping import (integrate, jit_integrate,
                                    jit_integrate_with_history,
                                    make_stepper)

    rhs = lambda y, t: {"y": -0.5 * y["y"]}
    step = make_stepper(rhs, 0.1, "ssprk3")
    y0 = {"y": jnp.ones(8, jnp.float32)}
    ref, tref = jax.jit(
        lambda y: integrate(step, y, 0.0, 7, 0.1, unroll=1))(y0)

    run = jit_integrate(step, 0.1, unroll=1)
    yin = {"y": jnp.ones(8, jnp.float32)}
    out, t = run(yin, 0.0, 7)
    np.testing.assert_array_equal(np.asarray(out["y"]),
                                  np.asarray(ref["y"]))
    assert float(t) == float(tref)
    if yin["y"].is_deleted():  # backends that enforce donation
        with pytest.raises(Exception):
            run(yin, 0.0, 7)
    # one executable serves other window lengths (nsteps is traced)
    out2, _ = run(out, 0.0, 3)
    assert np.all(np.isfinite(np.asarray(out2["y"])))

    hist_run = jit_integrate_with_history(
        step, 0.1, stride=2, snapshot=lambda y: y["y"][0])
    yh, th, hist = hist_run({"y": jnp.ones(8, jnp.float32)}, 0.0, 6)
    assert hist.shape == (3,)
    assert np.all(np.isfinite(np.asarray(hist)))


def test_simulation_ensemble_validation():
    from jaxstream.simulation import Simulation

    with pytest.raises(ValueError, match="shallow-water"):
        Simulation({"model": {"initial_condition": "tc1"},
                    "ensemble": {"members": 2}})
    with pytest.raises(ValueError, match="dense"):
        Simulation({"model": {"initial_condition": "tc5",
                              "numerics": "tt"},
                    "ensemble": {"members": 2}})


def test_ensemble_history_checkpoint_member_extraction(tmp_path):
    """Round-11 satellite: ensemble runs write history/checkpoints
    (the old rejection is gone), and member 0's extraction is BYTE-
    identical to an equivalent unbatched run — the first blocker
    ROADMAP item 1 named.  Also covers the ensemble resume branch and
    the postmortem meta plumbing (member id round-trips through the
    checkpoint store)."""
    from jaxstream.io.history import HistoryWriter, extract_member
    from jaxstream.simulation import Simulation

    base = {"grid": {"n": 8},
            "model": {"name": "shallow_water_cov",
                      "initial_condition": "tc5"},
            "time": {"dt": 600.0, "nsteps": 4},
            "parallelization": {"num_devices": 1}}
    cfg = dict(base,
               ensemble={"members": 2, "seed": 9, "amplitude": 1e-3},
               io={"history_path": str(tmp_path / "eh"),
                   "history_stride": 2,
                   "checkpoint_path": str(tmp_path / "ec"),
                   "checkpoint_stride": 2})
    sim = Simulation(cfg)
    sim.run()
    ref = dict(base, io={"history_path": str(tmp_path / "rh"),
                         "history_stride": 2,
                         "checkpoint_path": str(tmp_path / "rc"),
                         "checkpoint_stride": 2})
    rsim = Simulation(ref)
    rsim.run()

    hw, rw = HistoryWriter(str(tmp_path / "eh")), \
        HistoryWriter(str(tmp_path / "rh"))
    assert len(hw) == len(rw) == 3          # IC + 2 strides
    # Member 0 is the unperturbed member and the vmapped classic path
    # adds no arithmetic: its history is byte-equal to the B=1 run's.
    np.testing.assert_array_equal(hw.read_member("h", 0), rw.read("h"))
    np.testing.assert_array_equal(hw.read_member("u", 0), rw.read("u"))
    assert hw.read_member("h", 1).shape == rw.read("h").shape
    with pytest.raises(ValueError, match="member-batched"):
        rw.read_member("h", 0)              # unbatched store rejects

    # Checkpoint: per-member extraction equals the B=1 run's save.
    st0, t0 = sim.checkpoints.restore_member(0)
    rst, rt = rsim.checkpoints.restore_host()
    assert t0 == rt
    np.testing.assert_array_equal(st0["h"], rst["h"])
    np.testing.assert_array_equal(st0["u"], rst["u"])
    # extract_member applies the same axis rule on a live state dict.
    ex = extract_member({k: np.asarray(v) for k, v in sim.state.items()},
                        0)
    assert ex["h"].shape == (6, 8, 8) and ex["u"].shape == (2, 6, 8, 8)

    # Resume: a new ensemble Simulation picks up the batched state.
    sim2 = Simulation(cfg)
    assert sim2.step_count == 4
    assert sim2.state["h"].shape == (2, 6, 8, 8)
    sim2.run(6)
    assert np.all(np.isfinite(np.asarray(sim2.state["h"])))

    # Postmortem meta: the member id a guard event attributes is
    # recorded beside the checkpoint (numeric-only payload).
    sim.checkpoints.save(99, sim.state, sim.t,
                         meta={"postmortem": True, "member": 1})
    meta = sim.checkpoints.restore_meta(99)
    assert meta == {"postmortem": 1, "member": 1}

    # Member-count mismatch on resume is rejected with a pointer.
    bad = dict(cfg, ensemble={"members": 3, "seed": 9,
                              "amplitude": 1e-3})
    with pytest.raises(ValueError, match="ensemble.members"):
        Simulation(bad)
