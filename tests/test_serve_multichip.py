"""Multi-chip serving (serve.placement, round 12).

Acceptance criteria of the placement tier, all tier-1 on the
conftest's 8 in-process virtual CPU devices (check_tiers rules 6 and 7
keep this module fast and in-process):

  * the placement planner maps buckets onto device pools correctly
    (counts 1 / 6 / 8 / 12, both modes, pure arithmetic);
  * member-parallel packed results match the single-device packed run
    — h BYTE-identical, u at the repo's established <= 1e-6
    member-batching budget (shape-dependent XLA FMA contraction,
    DESIGN.md "Batched ensemble execution") — and placement off is the
    round-11 code path;
  * slot refill under sharding is deterministic (two identical
    member-placement servers produce byte-identical results) and
    sharding-preserving (zero steady-state recompiles through refills);
  * per-member eviction works on the sharded nonfinite stream, and the
    guard event names the failing member's chip;
  * the panel-sharded mode serves through the shard_map
    batched-exchange ensemble stepper (6-device mesh) at the
    established cross-tier <= 1e-6 budget, zero steady recompiles;
  * a grouped Pallas server under member placement runs the fused
    member fold per chip (plan ``serve_member+fused``, verified):
    agreement with the single-chip fused bucket and with the classic
    jnp stepper run solo, no collective in its executables, per-chip
    inject/extract, zero compiles after warm-up.

Configs are tiny (C8, jnp backend) — the real throughput floors
(>= 0.8x N-chip scaling) are asserted by bench.py's
``serving_multichip`` section on real accelerators; this module
certifies the machinery.
"""

import numpy as np
import pytest

import jax

from jaxstream.serve import EnsembleServer, ScenarioRequest
from jaxstream.serve.placement import (plan_bucket, plan_placement,
                                       plan_exchange_bytes_per_step,
                                       placement_report)

N, DT = 8, 600.0


def _cfg(**over):
    cfg = {
        "grid": {"n": N},
        "time": {"dt": DT},
        "model": {"name": "shallow_water_cov", "backend": "jnp"},
        "parallelization": {"num_devices": 1},
        "serve": {"buckets": "4", "segment_steps": 2,
                  "queue_capacity": 16},
    }
    for k, v in over.items():
        if k == "placement":
            cfg["serve"]["placement"] = v
        else:
            cfg.setdefault(k, {}).update(v)
    return cfg


def _needs(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")


# ------------------------------------------------------------- planner
def test_placement_plans_across_device_counts():
    """The planner's device-count policies at pools 1/6/8/12."""
    # 1 device: everything degrades to the single-chip executable.
    for mode in ("off", "member"):
        pl = plan_bucket(16, 1, mode)
        assert pl.mode == "single" and pl.num_devices == 1

    # 6 devices, member mode: largest bucket divisor <= 6.
    plans = plan_placement((1, 4, 16), 6, "member")
    assert plans[1].mode == "single"
    assert plans[4].member_shards == 4 and plans[4].num_devices == 4
    assert plans[16].member_shards == 4    # 16 % 6 != 0 -> 4 shards
    # 6 devices, panel mode: every bucket spreads its faces.
    plans = plan_placement((1, 4, 16), 6, "panel")
    for b, pl in plans.items():
        assert pl.mode == "panel" and pl.panel_shards == 6
        assert pl.num_devices == 6 and pl.members_per_shard == b

    # 8 devices: B=16 runs 2 members/chip (the ISSUE headline case);
    # panel mode needs a multiple of 6 and says so.
    plans = plan_placement((1, 4, 16), 8, "member")
    assert plans[16].member_shards == 8
    assert plans[16].members_per_shard == 2
    assert plans[4].member_shards == 4
    with pytest.raises(ValueError, match="multiple of 6"):
        plan_placement((1, 4, 16), 8, "panel")

    # 12 devices, panel mode: (panel=6, member=2) where the bucket
    # divides, 6 devices otherwise (B=1).
    plans = plan_placement((1, 4, 16), 12, "panel")
    assert plans[16].member_shards == 2 and plans[16].num_devices == 12
    assert plans[16].members_per_shard == 8
    assert plans[1].member_shards == 1 and plans[1].num_devices == 6

    # Exchange accounting: member-parallel is wire-free; panel ships
    # the face tier's 12 ppermutes/step at the batched payload.
    assert plan_exchange_bytes_per_step(plans[16], N, 2) == \
        16 * 12 * 3 * 2 * N * 4
    assert plan_exchange_bytes_per_step(
        plan_bucket(16, 8, "member"), N, 2) == 0.0

    rep = placement_report((1, 4, 16), 8, N, 2)
    assert "skipped" in rep["modes"]["panel"]
    rows = {r["bucket"]: r for r in rep["modes"]["member"]["buckets"]}
    assert rows[16]["members_per_shard"] == 2
    assert rows[16]["exchange_bytes_per_step"] == 0.0

    with pytest.raises(ValueError, match="mode"):
        plan_bucket(4, 4, "tile")


# --------------------------------------------- member-parallel serving
LENGTHS = (3, 5, 2, 4, 7, 1)     # ragged: none a segment multiple


def _serve_trace(placement=None, **over):
    serve_over = {}
    if placement is not None:
        serve_over["placement"] = placement
    cfg = _cfg(serve=serve_over) if not over else _cfg(
        serve=serve_over, **over)
    srv = EnsembleServer(cfg)
    for i, ns in enumerate(LENGTHS):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=ns,
                                   seed=i, amplitude=1e-3,
                                   outputs=("h", "u")))
    srv.serve()
    srv.close()
    return srv


@pytest.fixture(scope="module")
def member_parallel_pair():
    _needs(4)
    single = _serve_trace()
    sharded = _serve_trace(placement={"mode": "member",
                                      "num_devices": 4})
    return single, sharded


def test_member_parallel_matches_single_device(member_parallel_pair):
    """The B=4 bucket sharded over 4 chips (1 member/chip) serves the
    same ragged trace as the single-device packed server: h is
    byte-identical, u carries the established member-batching budget,
    and refills happened under sharding."""
    single, sharded = member_parallel_pair
    plan = sharded._plans[4]
    assert plan.mode == "member" and plan.num_devices == 4
    assert set(sharded.results) == set(single.results)
    for rid, rs in single.results.items():
        rm = sharded.results[rid]
        assert rs.status == rm.status == "ok"
        assert rs.steps_run == rm.steps_run
        np.testing.assert_array_equal(
            np.asarray(rm.fields["h"]), np.asarray(rs.fields["h"]),
            err_msg=rid)
        a = np.asarray(rm.fields["u"], np.float64)
        b = np.asarray(rs.fields["u"], np.float64)
        rel = np.abs(a - b).max() / np.abs(b).max()
        assert rel <= 1e-6, (rid, rel)
    # The trace is bigger than the bucket, so slots were refilled
    # under sharding; behavioral counters agree across placements.
    assert sharded.stats["refills"] >= 2
    assert sharded.stats["refills"] == single.stats["refills"]
    assert sharded.stats["member_steps"] == single.stats["member_steps"]
    assert sharded.stats["segments"] == single.stats["segments"]


def test_member_parallel_zero_steady_recompiles(member_parallel_pair):
    """Sharding-preserving refill: injections (device_put member IC +
    traced-index dynamic_update_slice under out_shardings) never
    change the executable population after warmup."""
    _, sharded = member_parallel_pair
    warm = sharded.stats["warmup_compiles"]
    assert warm > 0
    assert sharded.compile_count() == warm


def test_refill_under_sharding_is_deterministic():
    """Two identical member-placement servers produce byte-identical
    packed results (the round-11 determinism claim, now under
    sharding)."""
    _needs(4)
    a = _serve_trace(placement={"mode": "member", "num_devices": 4})
    b = _serve_trace(placement={"mode": "member", "num_devices": 4})
    for rid, ra in a.results.items():
        rb = b.results[rid]
        assert ra.status == rb.status == "ok"
        for k in ("h", "u"):
            np.testing.assert_array_equal(np.asarray(ra.fields[k]),
                                          np.asarray(rb.fields[k]),
                                          err_msg=(rid, k))


def test_sharded_eviction_names_member_and_chip(tmp_path):
    """The per-member nonfinite stream is a GSPMD reduction over the
    sharded carry; eviction under placement evicts only the failing
    member, and its guard event carries the owning chip (member-shard
    index) — the per-chip attribution satellite."""
    _needs(4)
    sink = str(tmp_path / "mc.jsonl")
    cfg = _cfg(serve={"placement": {"mode": "member", "num_devices": 4},
                      "fault_member": 2, "max_guard_events": 1,
                      "sink": sink},
               observability={"fault_step": 2})
    srv = EnsembleServer(cfg)
    for i, ns in enumerate((6, 6, 6, 4)):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=ns,
                                   seed=i))
    srv.serve()
    srv.close()
    ev = srv.results["r2"].guard_event
    assert srv.results["r2"].status == "evicted"
    assert ev["member"] == 2
    # 4 slots over 4 shards: slot 2 lives on chip 2.
    assert ev["chip"] == 2
    for rid in ("r0", "r1", "r3"):
        assert srv.results[rid].status == "ok"
        assert np.all(np.isfinite(np.asarray(
            srv.results[rid].fields["h"])))
    assert srv.stats["evicted"] == 1 and srv.stats["completed"] == 3

    # The sink's serve records carry the per-chip columns and the
    # guard record carries the chip; telemetry_report aggregates both.
    from jaxstream.obs.sink import read_records
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import telemetry_report

    recs = read_records(sink)              # schema-validates every line
    serves = [r for r in recs if r["kind"] == "serve"]
    assert serves and all(r["placement"] == "member" for r in serves)
    assert all(len(r["chip_occupancy"]) == 4 for r in serves)
    assert all("host_wait_s" in r for r in serves)
    guards = [r for r in recs if r["kind"] == "guard"]
    assert guards and guards[0]["chip"] == 2
    s = telemetry_report.summarize(recs)
    sv = s["serving"]
    assert sv["devices"] == 4
    assert sv["placement_modes"] == ["member"]
    assert len(sv["chip_occupancy_mean"]) == 4
    assert all(0.0 <= v <= 1.0 for v in sv["chip_occupancy_mean"])
    assert sv["host_wait_total_s"] >= 0.0


# ------------------------------------------------- panel-sharded serving
def test_panel_sharded_serving_matches_single_device():
    """A 6-device ('panel', 'member') mesh serves through the
    shard_map batched-exchange ensemble stepper: results match the
    single-device packed server at the established cross-tier <= 1e-6
    budget (different RHS implementation — per-face Pallas kernel +
    strip exchange vs the classic jnp oracle), with zero steady-state
    recompiles.  Panel placement requires the grouped (baked-
    orography) mode."""
    _needs(6)
    base = {"serve": {"group_by_orography": True, "buckets": "2",
                      "segment_steps": 2, "queue_capacity": 8}}

    def run(placement):
        cfg = _cfg()
        cfg["serve"].update(base["serve"])
        if placement:
            cfg["serve"]["placement"] = placement
        srv = EnsembleServer(cfg)
        for i, ns in enumerate((3, 2, 4)):
            srv.submit(ScenarioRequest(id=f"p{i}", ic="tc2", nsteps=ns,
                                       seed=i, outputs=("h", "u")))
        srv.serve()
        srv.close()
        return srv

    ref = run(None)
    panel = run({"mode": "panel", "num_devices": 6})
    plan = panel._plans[2]
    assert plan.mode == "panel" and plan.num_devices == 6
    warm = panel.stats["warmup_compiles"]
    assert warm > 0 and panel.compile_count() == warm
    assert panel.stats["refills"] >= 1
    for rid, rr in ref.results.items():
        rp = panel.results[rid]
        assert rr.status == rp.status == "ok"
        for k in ("h", "u"):
            a = np.asarray(rp.fields[k], np.float64)
            b = np.asarray(rr.fields[k], np.float64)
            rel = np.abs(a - b).max() / (np.abs(b).max() + 1e-300)
            assert rel <= 1e-6, (rid, k, rel)


# ------------------------------------- member-placed fused member fold
#: The fused cells' CPU stand-in: C16, Pallas interpreted, one bucket of
#: 8 slots — 2 members per chip on 4 of the conftest's virtual devices.
FUSED_N, FUSED_DT = 16, 600.0
#: Agreement with the classic jnp stepper run solo: the fused kernels
#: and the classic XLA stepper evaluate the same discretisation in
#: different code, so they part at f32 rounding (~1e-7 per step) and
#: the gap grows through the dynamics: <= 3.7e-6 relative measured over
#: these 2-8 step requests, where the bf16 stage arithmetic reads
#: >= 1.7e-4 (h) and >= 3.7e-3 (u).
FUSED_VS_CLASSIC = 1e-5


def _fused_cfg(placed, backend="pallas_interpret", **serve):
    cfg = {"grid": {"n": FUSED_N}, "time": {"dt": FUSED_DT},
           "model": {"name": "shallow_water_cov", "backend": backend,
                     "initial_condition": "tc5"},
           "parallelization": {"num_devices": 1},
           "serve": dict({"buckets": "8", "segment_steps": 1,
                          "queue_capacity": 32,
                          "group_by_orography": True}, **serve)}
    if placed:
        cfg["serve"]["placement"] = {"mode": "member", "num_devices": 4,
                                     "device_type": "cpu"}
    return cfg


def _fused_serve(placed, lengths, **serve):
    srv = EnsembleServer(_fused_cfg(placed, **serve))
    srv.warmup(groups=("oro",))
    warm = srv.compile_count()
    reqs = [ScenarioRequest(id=f"f{i}", ic="tc5", nsteps=ns, seed=40 + i,
                            amplitude=1e-3, outputs=("h", "u"))
            for i, ns in enumerate(lengths)]
    for r in reqs:
        srv.submit(r)
    srv.serve()
    srv.close()
    return srv, warm, reqs


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture(scope="module")
def fused_pair():
    """One short ragged trace (12 requests of 1-2 steps on 8 slots, so
    slots refill) through the single-chip fused bucket and the
    member-placed one."""
    _needs(4)
    lengths = (1, 2, 1, 2, 2, 1, 2, 1, 1, 2, 2, 1)
    return _fused_serve(False, lengths), _fused_serve(True, lengths)


def test_member_placed_fused_matches_single_chip_bucket(fused_pair):
    """(a) Every member served by the placed bucket agrees with the
    single-chip fused bucket of 8 within the member-fold budget, 1e-6
    relative (docs/DESIGN.md "Batched ensemble execution").  The
    per-chip programs are the same kernels at the local batch, but on
    the CPU the interpreted kernels' XLA code inside ``shard_map``
    contracts the RK stage combination differently (one ulp in stage
    2, measured kernel by kernel), and the dynamics grow that seed by
    about 3e-7 relative in u per step at this size — past the budget
    after three steps.  Hence requests of 1-2 steps here, and the solo reference
    of the next test for longer ones."""
    (single, _, reqs), (placed, _, _) = fused_pair
    assert single.bucket_proofs()["oro/B8"]["plan_key"] == (
        "serve_single+fused")
    proof = placed.bucket_proofs()["oro/B8"]
    assert proof["plan_key"] == "serve_member+fused"
    assert proof["verdict"] == "verified"
    assert placed.stats["refills"] == single.stats["refills"] == 4
    for r in reqs:
        a, b = placed.results[r.id], single.results[r.id]
        assert a.status == b.status == "ok"
        assert a.steps_run == b.steps_run == r.nsteps
        for k in ("h", "u"):
            assert _rel(a.fields[k], b.fields[k]) <= 1e-6, (r.id, k)


def test_member_placed_fused_matches_solo_classic_stepper():
    """(b) Longer requests (2-8 steps, 2-step segments) served member-
    placed agree with the same request run solo on the plain f32
    classic jnp stepper through ``Simulation`` within
    FUSED_VS_CLASSIC; the bf16 stage arithmetic, run solo the same
    way, fails that tolerance on every request."""
    import jax.numpy as jnp

    from jaxstream.simulation import Simulation

    _needs(4)
    lengths = (8, 2, 4, 8, 4, 2, 8, 2, 4, 8)
    srv, _, reqs = _fused_serve(True, lengths, segment_steps=2)

    def solo_runner(backend, precision=None):
        cfg = _fused_cfg(False, backend=backend)
        del cfg["serve"]
        if precision:
            cfg["precision"] = {"stage": precision}
        sim = Simulation(cfg)

        def run(req):
            st = srv._request_state(req)
            sim.state = {k: jnp.array(st[k], jnp.float32, copy=True)
                         for k in ("h", "u")}
            sim.step_count, sim.t = 0, 0.0
            sim.run(req.nsteps)
            return {k: np.asarray(sim.state[k]) for k in ("h", "u")}
        return sim, run

    sim_c, classic = solo_runner("jnp")
    sim_b, bf16 = solo_runner("pallas_interpret", "bf16")
    try:
        for r in reqs:
            got = srv.results[r.id]
            assert got.status == "ok" and got.steps_run == r.nsteps
            ref, low = classic(r), bf16(r)
            for k in ("h", "u"):
                assert _rel(got.fields[k], ref[k]) <= FUSED_VS_CLASSIC, (
                    r.id, k)
            assert max(_rel(low[k], ref[k]) for k in ("h", "u")) > (
                FUSED_VS_CLASSIC), r.id
    finally:
        sim_c.close()
        sim_b.close()


_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
                "collective-permute", "reduce-scatter",
                "collective-broadcast", "all_gather", "all_reduce",
                "all_to_all", "collective_permute", "reduce_scatter",
                "collective_broadcast")


def _collectives(text):
    return [c for c in _COLLECTIVES if c in text]


def test_member_placed_executables_hold_no_collective(fused_pair):
    """(c) The placed bucket's masked segment (one ``shard_map``
    program over the 4-chip mesh), inject and extract (one chip each)
    hold no collective, lowered or compiled; the segment's carry,
    ``rem`` and health vector stay split along the member axis."""
    _, (placed, _, _) = fused_pair
    bk = placed._buckets[("oro", 8)]
    st = placed._warm_member_tree("oro")
    carry = bk.stack([st] * 8)
    rem = bk.put_rem(np.arange(8))
    lowered = bk.seg.lower(carry, rem)
    assert _collectives(lowered.as_text()) == []
    assert _collectives(lowered.compile().as_text()) == []
    out, rem2, nf = bk.seg(carry, rem)
    for a in (rem2, nf, out["h"], out["strips_we"]):
        assert len(a.sharding.device_set) == 4
        assert a.sharding.spec[0] == "member"
    assert out["u"].sharding.spec[1] == "member"
    local = bk._local(out, 1)
    member = bk._put_on_chip(st, 1)[0]
    for fn, args in ((bk.inject, (local, np.int32(0), member)),
                     (bk.extract, (local, np.int32(1)))):
        low = fn.lower(*args)
        assert _collectives(low.as_text()) == []
        assert _collectives(low.compile().as_text()) == []


def test_member_placed_refills_are_per_chip_and_compile_free(fused_pair):
    """(d) Zero compiles after warm-up through a trace whose refills
    cycle every slot, and a direct round of injects into every slot:
    each member is read back unchanged from its own slot, on the chip
    that owns it, each inject uploads one member's ``h`` alone, and
    each result names its chip."""
    from jaxstream.obs import trace as obs_trace

    _, (placed, warm, reqs) = fused_pair
    assert warm == placed.stats["warmup_compiles"] > 0
    assert placed.compile_count() == warm
    # Each result names the chip whose slot served it: the first pack
    # fills slots 0-7 (2 per chip), the 1-step members free slots 0, 2,
    # 5 and 7, and the four refills land there.
    assert [placed.results[r.id].chip for r in reqs] == [
        0, 0, 1, 1, 2, 2, 3, 3, 0, 1, 2, 3]
    bk = placed._buckets[("oro", 8)]
    assert bk.host_leaves
    trees = [placed._member_tree(r, host=True) for r in reqs[:8]]
    assert all(isinstance(t["h"], np.ndarray) for t in trees)
    carry = bk.stack([placed._warm_member_tree("oro")] * 8)
    before = obs_trace.SPANS.summary()["counters"].get(
        obs_trace.INJECT_BYTES, 0)
    for i, t in enumerate(trees):
        carry = bk.inject_slot(carry, i, t)
    added = obs_trace.SPANS.summary()["counters"][
        obs_trace.INJECT_BYTES] - before
    assert added == sum(t["h"].nbytes for t in trees)
    for i, t in enumerate(trees):
        got = bk.extract_slot(carry, i)
        chip = placed._devices[i // 2]
        assert bk.chip_of(i) == i // 2
        for k in ("h", "u"):
            assert got[k].devices() == {chip}, (i, k)
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(t[k]),
                                          err_msg=(i, k))
    assert placed.compile_count() == warm


def test_member_placed_fused_plan_is_enumerated_and_verified():
    """(e) ``serve_member+fused`` is in the enumerated plan space and a
    plan of it (as ``plan_for`` resolves the deployment) is minted
    ``verified``; the GSPMD member plan stays ``backend: jnp``'s."""
    from jaxstream.plan import enumerate_plans, plan_for
    from jaxstream.plan.proof import build_proof

    keys = {p.key() for p in enumerate_plans()}
    assert {"serve_member+fused", "serve_member+gspmd"} <= keys
    plan = plan_for(_fused_cfg(True), serving=True)
    assert plan.key() == "serve_member+fused"
    assert build_proof(plan).verdict == "verified"
    assert plan_for(_fused_cfg(True, backend="jnp"),
                    serving=True).key() == "serve_member+gspmd"


# ------------------------------------------------------------ validation
def test_placement_config_validation():
    with pytest.raises(ValueError, match="placement.mode"):
        EnsembleServer(_cfg(placement={"mode": "tiles"}))
    # member placement with Pallas runs the fused member fold per
    # chip, which needs the grouped server; ungrouped, the pointer
    # names the rule's conditions and the jnp way out.
    with pytest.raises(ValueError, match="group_by_orography") as ei:
        EnsembleServer(_cfg(placement={"mode": "member",
                                       "num_devices": 2},
                            model={"backend": "pallas",
                                   "name": "shallow_water_cov"}))
    assert ei.value.violations[0].rule == "serve-member-jnp"
    assert "model.backend: jnp" in str(ei.value)
    srv = EnsembleServer(_cfg(placement={"mode": "member",
                                         "num_devices": 2},
                              model={"backend": "pallas"},
                              serve={"group_by_orography": True}))
    assert srv._impl_for("oro", srv._plans[4]) == "fused"
    srv.close()
    # panel placement bakes orography per device: grouped mode only.
    with pytest.raises(ValueError, match="group_by_orography"):
        EnsembleServer(_cfg(placement={"mode": "panel",
                                       "num_devices": 6}))
    # More devices than exist: the XLA_FLAGS hint, not a crash later.
    with pytest.raises(ValueError, match="devices exist"):
        EnsembleServer(_cfg(placement={"mode": "member",
                                       "num_devices": 4096}))


def test_simulation_member_layout_mesh():
    """ensemble.layout: member — the 1-D member-only mesh behind the
    same helper the serving tier uses: any device count dividing the
    ensemble works (no multiple-of-6 constraint), and the spec shards
    only the member axis."""
    _needs(4)
    from jaxstream.parallel.mesh import setup_ensemble_sharding

    setup = setup_ensemble_sharding(
        {"parallelization": {"num_devices": 4, "device_type": "cpu"}},
        members=8, layout="member")
    assert setup.mesh.axis_names == ("member",)
    assert setup.member == 4 and setup.panel == 1
    assert setup.ensemble_spec_for(4) == jax.sharding.PartitionSpec(
        "member", None, None, None)
    assert setup.ensemble_spec_for(5) == jax.sharding.PartitionSpec(
        None, "member", None, None, None)
    with pytest.raises(ValueError, match="divide"):
        setup_ensemble_sharding(
            {"parallelization": {"num_devices": 4}}, members=6,
            layout="member")
    with pytest.raises(ValueError, match="use_shard_map"):
        setup_ensemble_sharding(
            {"parallelization": {"num_devices": 4,
                                 "use_shard_map": True}},
            members=8, layout="member")
    with pytest.raises(ValueError, match="layout"):
        setup_ensemble_sharding(
            {"parallelization": {"num_devices": 4}}, members=8,
            layout="tiles")
