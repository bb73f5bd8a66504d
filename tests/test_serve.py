"""Continuous-batching ensemble server (jaxstream.serve, round 11).

Acceptance criteria of the serving tier, all tier-1 (check_tiers rule
6 keeps this module fast):

  * per-member run-length masking freezes finished members bit-for-bit
    (stepping.integrate_masked unit);
  * a single request through the B=1 bucket is BITWISE identical to a
    plain unbatched ``Simulation`` run of the same scenario;
  * a seeded request's initial state, built from the family's cached
    base, is BITWISE the float64 perturbation recipe's (a verbatim
    oracle), as is each ``perturbed_ensemble`` member;
  * packing + boundary refill are deterministic (two identical servers
    produce byte-identical results) and each packed member's trajectory
    is exactly its own solo run;
  * a member whose state goes non-finite is EVICTED alone (guard event
    carries the member index) while the batch keeps serving, and the
    health monitor drives admission control;
  * the bounded queue raises at capacity (backpressure);
  * the shape-bucketed steppers compile during warmup and NEVER again
    (zero steady-state recompiles);
  * the serving telemetry sink's occupancy/queue-depth records are
    schema-valid and aggregated by scripts/telemetry_report.py.

Configs are tiny (C8, jnp backend: the vmapped classic stepper — the
fused-kernel member fold has its own parity suite in
tests/test_ensemble.py and cannot execute on CPU anyway).
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jaxstream.serve import (AdmissionRefused, EnsembleServer, QueueFull,
                             RequestQueue, ScenarioRequest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

N, DT = 8, 600.0


def _cfg(**over):
    cfg = {
        "grid": {"n": N},
        "time": {"dt": DT},
        "model": {"name": "shallow_water_cov", "backend": "jnp"},
        "parallelization": {"num_devices": 1},
        "serve": {"buckets": "2", "segment_steps": 2,
                  "queue_capacity": 8},
    }
    for k, v in over.items():
        cfg.setdefault(k, {}).update(v)
    return cfg


# --------------------------------------------------------------- units
def test_integrate_masked_freezes_members_bitwise():
    """Member i's state stops changing exactly when its remaining count
    hits zero; a member with rem >= nsteps matches plain stepping."""
    from jaxstream.stepping import integrate_masked

    step = lambda y, t: {"y": y["y"] * 2.0 + 1.0}
    y0 = {"y": jnp.ones((3, 2), jnp.float32)}
    run = jax.jit(lambda y, r: integrate_masked(
        step, y, 0.0, r, 4, 1.0, {"y": 0}))
    y, t, rem = run(y0, jnp.asarray([2, 4, 0], jnp.int32))
    # 1 -> 3 -> 7 -> 15 -> 31 under 4 steps; member 0 froze at 7,
    # member 2 never advanced.
    np.testing.assert_array_equal(
        np.asarray(y["y"]), [[7, 7], [31, 31], [1, 1]])
    np.testing.assert_array_equal(np.asarray(rem), [0, 0, 0])
    assert float(t) == 4.0


def test_request_queue_backpressure_and_group_fifo():
    q = RequestQueue(2)
    r = [ScenarioRequest(id=f"r{i}", ic=ic, nsteps=1)
         for i, ic in enumerate(["tc2", "tc5", "tc6"])]
    q.submit(r[0])
    q.submit(r[1])
    with pytest.raises(QueueFull):
        q.submit(r[2])                      # hard capacity bound
    # Group-local FIFO: popping the 'flat' group skips the queued tc5
    # request without disturbing its position.
    assert q.pop_group("flat").id == "r0"
    q.submit(r[2])
    assert q.pop_group("flat").id == "r2"
    assert q.pop().id == "r1"
    assert q.pop() is None
    # remove() takes a request back out by IDENTITY (the submit/drain
    # race unwind): False once it is no longer queued.
    q.submit(r[0])
    q.submit(r[1])
    assert q.remove(r[0]) is True
    assert q.remove(r[0]) is False        # already withdrawn
    assert q.pop() is r[1]


def test_request_validation():
    with pytest.raises(ValueError, match="unknown ic"):
        ScenarioRequest(id="x", ic="tc9", nsteps=1)
    with pytest.raises(ValueError, match="nsteps"):
        ScenarioRequest(id="x", ic="tc2", nsteps=0)
    with pytest.raises(ValueError, match="output fields"):
        ScenarioRequest(id="x", ic="tc2", nsteps=1, outputs=("zeta",))
    with pytest.raises(ValueError, match="unknown keys"):
        ScenarioRequest.from_dict({"id": "x", "ic": "tc2", "nsteps": 1,
                                   "color": "red"})
    r = ScenarioRequest.from_dict(
        {"id": "x", "ic": "tc5", "nsteps": 3, "outputs": ["h", "u"]})
    assert r.group == "oro" and r.outputs == ("h", "u")


# --------------------------------------------- the packed serving pair
LENGTHS = (3, 5, 2, 4)     # heterogeneous, none a segment multiple


def _run_trace(sink_path):
    cfg = _cfg(serve={"sink": sink_path})
    srv = EnsembleServer(cfg)
    for i, ns in enumerate(LENGTHS):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=ns,
                                   seed=i, amplitude=1e-3,
                                   outputs=("h", "u")))
    srv.serve()
    srv.close()
    return srv


@pytest.fixture(scope="module")
def served_pair(tmp_path_factory):
    d = tmp_path_factory.mktemp("serve")
    return (_run_trace(str(d / "a.jsonl")),
            _run_trace(str(d / "b.jsonl")), d)


def test_packing_and_refill_are_deterministic(served_pair):
    a, b, _ = served_pair
    assert set(a.results) == {f"r{i}" for i in range(len(LENGTHS))}
    for rid, ra in a.results.items():
        rb = b.results[rid]
        assert ra.status == rb.status == "ok"
        assert ra.steps_run == ra.nsteps
        for k in ("h", "u"):
            np.testing.assert_array_equal(np.asarray(ra.fields[k]),
                                          np.asarray(rb.fields[k]))
    # Four requests through two slots: at least two boundary refills,
    # and the slots stayed busy.
    assert a.stats["refills"] >= 2
    assert a.stats["batches"] == 1
    assert a.stats["member_steps"] == sum(LENGTHS)
    assert 0.5 < a.occupancy_mean <= 1.0
    assert 0.0 < a.utilization_mean <= 1.0


def test_packed_member_matches_its_solo_trajectory(served_pair):
    """Masked packed stepping = each member's own run: replay request
    r0 (3 steps, a non-multiple of the segment) step by step with the
    same classic stepper.  h is bitwise; u carries the repo's
    established B>1 per-member bound (<= 1e-6 rel — shape-dependent
    XLA FMA contraction under the member batching, DESIGN.md "Batched
    ensemble execution"; the bitwise claim belongs to the B=1 path,
    tested below)."""
    a, _, _ = served_pair
    req = ScenarioRequest(id="r0", ic="tc2", nsteps=3, seed=0,
                          amplitude=1e-3)
    model = a._model("flat")
    y = a._request_state(req)
    step = jax.jit(model.make_step(DT, "ssprk3"))
    t = 0.0
    for _ in range(req.nsteps):
        y = step(y, t)
        t += DT
    np.testing.assert_array_equal(np.asarray(a.results["r0"].fields["h"]),
                                  np.asarray(y["h"]))
    got = np.asarray(a.results["r0"].fields["u"], np.float64)
    want = np.asarray(y["u"], np.float64)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel <= 1e-6, rel


def test_zero_steady_state_recompiles(served_pair):
    """The shape-bucketing claim: every executable compiles during the
    bucket warmup (first use) and serving adds NONE."""
    a, _, _ = served_pair
    warm = a.stats["warmup_compiles"]
    assert warm > 0
    assert a.compile_count() == warm


def test_serve_sink_records_and_report(served_pair):
    a, _, d = served_pair
    from jaxstream.obs.sink import read_records

    recs = read_records(str(d / "a.jsonl"))       # schema-validates
    serves = [r for r in recs if r["kind"] == "serve"]
    assert len(serves) == a.stats["segments"]
    assert all(0.0 <= r["occupancy"] <= 1.0 for r in serves)

    import telemetry_report

    s = telemetry_report.summarize(recs)
    sv = s["serving"]
    assert sv["segments"] == a.stats["segments"]
    assert sv["completed"] == len(LENGTHS)
    assert sv["evicted"] == 0
    assert sv["refilled"] == a.stats["refills"]
    assert 0.0 < sv["occupancy_mean"] <= 1.0
    assert sv["queue_depth_max"] >= 0


# ------------------------------------------------- parity & resilience
def _oracle_ensemble(grid, h_ext, members, seed, amplitude):
    """The float64 perturbed-height recipe written out whole, apart from
    the library's split into a seed-free base and one member: the
    oracle the shared recipe and the server's cached build are held
    to, bit for bit."""
    h = np.asarray(h_ext, dtype=np.float64)
    rhat = np.asarray(grid.xyz, dtype=np.float64) / grid.radius
    rng = np.random.default_rng(seed)
    href = float(np.mean(np.abs(h)))
    out = [h]
    for _ in range(members - 1):
        g = rng.standard_normal((3, 3))
        g /= np.linalg.norm(g, axis=1, keepdims=True)
        w = rng.standard_normal(3)
        mode = np.einsum("jk,k...->...", g * w[:, None], rhat)
        mode /= max(float(np.abs(mode).max()), 1e-300)
        out.append(h + amplitude * href * mode)
    return jnp.asarray(np.stack(out), dtype=grid.sqrtg.dtype)


@pytest.fixture(scope="module")
def c16_server():
    srv = EnsembleServer(_cfg(grid={"n": 16}))
    yield srv
    srv.close()


@pytest.mark.parametrize("seed", [0, 1, 7, 123])
@pytest.mark.parametrize("family", ["tc5", "galewsky", "tc2"])
def test_request_state_bitwise_vs_oracle(c16_server, family, seed):
    """A seeded request's interior (h, u), built from the family's
    cached base, is bitwise the oracle's member 1 through
    ``initial_state``; ``perturbed_ensemble`` gives the oracle's
    members one by one."""
    from jaxstream.physics import initial_conditions as ics

    srv, amp = c16_server, 1e-3
    h, v, _ = srv._ic(family)
    want = srv._model("any").initial_state(
        _oracle_ensemble(srv.grid, h, 2, seed, amp)[1], v)
    got = srv._request_state(ScenarioRequest(
        id="p", ic=family, nsteps=1, seed=seed, amplitude=amp))
    for k in ("h", "u"):
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)
    members = ics.perturbed_ensemble(srv.grid, h, 4, seed=seed,
                                     amplitude=amp)
    oracle = _oracle_ensemble(srv.grid, h, 4, seed, amp)
    assert members.dtype == oracle.dtype
    for i in range(4):
        np.testing.assert_array_equal(np.asarray(members[i]),
                                      np.asarray(oracle[i]), err_msg=i)


def test_b1_request_bitwise_vs_plain_simulation(tmp_path):
    """A request served alone through the B=1 bucket is bitwise the
    unbatched Simulation run of the same scenario — the single-request
    acceptance criterion."""
    from jaxstream.simulation import Simulation

    base = {"grid": {"n": N},
            "time": {"dt": DT, "nsteps": 5},
            "model": {"name": "shallow_water_cov",
                      "initial_condition": "tc2", "backend": "jnp"},
            "parallelization": {"num_devices": 1}}
    ref = Simulation(base)
    ref.run()

    srv = EnsembleServer(_cfg(serve={"buckets": "1"}))
    srv.submit(ScenarioRequest(id="solo", ic="tc2", nsteps=5, seed=-1,
                               outputs=("h", "u")))
    srv.serve()
    srv.close()
    res = srv.results["solo"]
    assert res.status == "ok"
    np.testing.assert_array_equal(np.asarray(res.fields["h"]),
                                  np.asarray(ref.state["h"]))
    np.testing.assert_array_equal(np.asarray(res.fields["u"]),
                                  np.asarray(ref.state["u"]))
    assert res.t_final == 5 * DT


def test_eviction_under_injected_nan_keeps_batch_alive():
    """observability.fault_step + serve.fault_member mark one member's
    health stream bad: that member alone is evicted (guard event with
    its index), its slot refills, everyone else completes — and the
    accumulated guard events drive admission control."""
    cfg = _cfg(serve={"fault_member": 1, "max_guard_events": 1},
               observability={"fault_step": 2})
    srv = EnsembleServer(cfg)
    for i, ns in enumerate((6, 6, 4)):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=ns,
                                   seed=i))
    srv.serve()
    assert srv.results["r1"].status == "evicted"
    assert srv.results["r1"].guard_event["member"] == 1
    assert srv.results["r1"].steps_run < 6
    for rid in ("r0", "r2"):
        r = srv.results[rid]
        assert r.status == "ok"
        assert np.all(np.isfinite(np.asarray(r.fields["h"])))
    assert srv.stats["evicted"] == 1 and srv.stats["completed"] == 2
    assert srv.stats["refills"] >= 1          # the slot was reused
    # Admission control: 1 guard event >= max_guard_events=1.
    with pytest.raises(AdmissionRefused):
        srv.submit(ScenarioRequest(id="late", ic="tc2", nsteps=1))
    assert srv.stats["refused"] == 1
    srv.close()


def test_halt_guard_requeues_prepped_requests():
    """serve.guards: halt fires AFTER the boundary's refill prep has
    speculatively popped queued requests — they must go back to the
    queue head (admitted traffic is never lost to a guard trip)."""
    from jaxstream.obs.monitor import HealthError

    cfg = _cfg(serve={"guards": "halt", "fault_member": 0},
               observability={"fault_step": 2})
    srv = EnsembleServer(cfg)
    # r0 faults at its step 2; r1 completes at that same boundary, so
    # the prep path pops r2 before the health check raises.
    srv.submit(ScenarioRequest(id="r0", ic="tc2", nsteps=6, seed=0))
    srv.submit(ScenarioRequest(id="r1", ic="tc2", nsteps=2, seed=1))
    srv.submit(ScenarioRequest(id="r2", ic="tc2", nsteps=2, seed=2))
    with pytest.raises(HealthError):
        srv.serve()
    assert "r2" not in srv.results
    assert len(srv.queue) == 1
    assert srv.queue.pop().id == "r2"
    srv.close()


def test_monitor_member_attribution_and_breach_callback():
    """HealthMonitor names the offending member (nonfinite_m{i} rows)
    in events, HealthError, and the on_breach callback's event — the
    postmortem-records-the-member-id satellite at the monitor level."""
    from jaxstream.obs.monitor import HealthError, HealthMonitor

    seen = []
    names = ("mass", "nonfinite_count", "nonfinite_m0", "nonfinite_m1")
    mon = HealthMonitor(names, policy="checkpoint_and_raise",
                        on_breach=lambda ev: seen.append(ev))
    buf = np.array([[1.0], [1.0], [0.0], [2.0]])
    with pytest.raises(HealthError) as ei:
        mon.check([4], [2400.0], buf)
    assert ei.value.member == 1
    assert seen and seen[0]["member"] == 1
    assert mon.events[0]["member"] == 1

    # Zero-arg callbacks keep working, and a clean buffer advances the
    # last-good cursor without attribution.
    calls = []
    mon2 = HealthMonitor(names, policy="checkpoint_and_raise",
                         on_breach=lambda: calls.append(1))
    mon2.check([2], [1200.0], np.zeros((4, 1)))
    assert mon2.last_good_step == 2
    with pytest.raises(HealthError):
        mon2.check([4], [2400.0], buf)
    assert calls == [1]

    # check_members: one event PER failing member, warn never raises.
    mon3 = HealthMonitor((), policy="warn")
    evs = mon3.check_members([3, 7, 5], [0.0, 0.0, 0.0],
                             np.array([2.0, 0.0, np.nan]))
    assert [e["member"] for e in evs] == [0, 2]
    assert all(e["kind"] == "guard" for e in evs)


def test_server_config_validation():
    with pytest.raises(ValueError, match="buckets"):
        EnsembleServer(_cfg(serve={"buckets": "zero"}))
    with pytest.raises(ValueError, match="guards"):
        EnsembleServer(_cfg(serve={"guards": "retry"}))
    with pytest.raises(ValueError, match="dense"):
        EnsembleServer(_cfg(model={"numerics": "tt"}))
    # Multi-chip serving is the serve.placement block's job, not the
    # parallelization flags (those configure Simulation runs).
    with pytest.raises(ValueError, match="serve.placement"):
        EnsembleServer(_cfg(parallelization={"use_shard_map": True,
                                             "num_devices": 6}))
    # Knobs the serving tier does not thread must be REJECTED, never
    # silently ignored (the bitwise-vs-Simulation contract depends on
    # the model name; the precision policy must never silently run f32).
    with pytest.raises(ValueError, match="shallow_water_cov"):
        EnsembleServer(_cfg(model={"name": "auto"}))
    with pytest.raises(ValueError, match="precision"):
        EnsembleServer(_cfg(precision={"stage": "bf16"}))
    with pytest.raises(ValueError, match="temporal_block"):
        EnsembleServer(_cfg(parallelization={"temporal_block": 4}))


def test_mixed_orography_batch_packs_all_families():
    """The round-12 default: tc2/tc5/tc6 requests pack into ONE batch
    (orography a traced per-member field), every result matches the
    family's own baked-static solo run — h bitwise, u at the
    established B>1 member budget — and strict queue FIFO replaces the
    group-local restriction."""
    srv = EnsembleServer(_cfg(serve={"buckets": "4"}))
    reqs = [("m0", "tc2", 3), ("m1", "tc5", 4), ("m2", "tc6", 2),
            ("m3", "tc5", 3)]
    for rid, ic, ns in reqs:
        srv.submit(ScenarioRequest(id=rid, ic=ic, nsteps=ns, seed=-1,
                                   outputs=("h", "u")))
    srv.serve()
    srv.close()
    assert srv.stats["batches"] == 1          # one mixed batch
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.physics import initial_conditions as ics

    phys = srv.config.physics
    for rid, ic, ns in reqs:
        res = srv.results[rid]
        assert res.status == "ok", rid
        b = None
        if ic == "tc5":
            h, v, b = ics.williamson_tc5(srv.grid, phys.gravity,
                                         phys.omega)
        elif ic == "tc2":
            h, v = ics.williamson_tc2(srv.grid, phys.gravity, phys.omega)
        else:
            h, v = ics.williamson_tc6(srv.grid, phys.gravity, phys.omega)
        model = CovariantShallowWater(
            srv.grid, gravity=phys.gravity, omega=phys.omega, b_ext=b)
        y = model.initial_state(h, v)
        step = jax.jit(model.make_step(DT, "ssprk3"))
        t = 0.0
        for _ in range(ns):
            y = step(y, t)
            t += DT
        np.testing.assert_array_equal(np.asarray(res.fields["h"]),
                                      np.asarray(y["h"]), err_msg=rid)
        got = np.asarray(res.fields["u"], np.float64)
        want = np.asarray(y["u"], np.float64)
        rel = np.abs(got - want).max() / np.abs(want).max()
        assert rel <= 1e-6, (rid, rel)


def test_group_by_orography_parity_mode():
    """serve.group_by_orography: true restores the round-11 grouping:
    tc5 and tc2 never share a batch (two batches for a 2-slot bucket
    fed one of each), and the tc2 result is bitwise the mixed-mode
    server's (traced zeros orography == baked static, the round-12
    equivalence claim)."""
    def run(grouped):
        srv = EnsembleServer(_cfg(serve={"group_by_orography": grouped}))
        srv.submit(ScenarioRequest(id="a", ic="tc2", nsteps=3, seed=0,
                                   outputs=("h", "u")))
        srv.submit(ScenarioRequest(id="b", ic="tc5", nsteps=3, seed=1,
                                   outputs=("h", "u")))
        srv.serve()
        srv.close()
        return srv

    grouped = run(True)
    mixed = run(False)
    assert grouped.stats["batches"] == 2      # group-local packing
    assert mixed.stats["batches"] == 1        # one mixed batch
    for rid in ("a", "b"):
        assert grouped.results[rid].status == "ok"
        assert mixed.results[rid].status == "ok"
        np.testing.assert_array_equal(
            np.asarray(grouped.results[rid].fields["h"]),
            np.asarray(mixed.results[rid].fields["h"]))


def test_resize_and_drain_surface(tmp_path):
    """Round-14 serve hooks, compile-free: resize validates against
    the configured bucket set (every legal cap maps to a warm
    executable), records an 'autoscale' sink event, and scales the
    active packing cap; begin_drain closes admissions with the typed
    ServerDraining (an AdmissionRefused subclass) and serve_forever
    exits once the queue drains."""
    from jaxstream.serve import ServerDraining

    sink = str(tmp_path / "resize.jsonl")
    srv = EnsembleServer(_cfg(serve={"buckets": "1,2", "sink": sink}))
    assert srv.active_buckets == (1, 2)
    with pytest.raises(ValueError, match="not a configured bucket"):
        srv.resize(4)
    assert srv.resize(1, reason="autoscale",
                      queue_depth=5, occupancy=0.25) == 2
    assert srv.active_buckets == (1,)
    assert srv.stats["resizes"] == 1
    assert srv.resize(2) == 1              # back up, still warm-only
    assert srv.active_buckets == (1, 2)

    srv.begin_drain()
    assert srv.draining
    with pytest.raises(ServerDraining) as ei:
        srv.submit(ScenarioRequest(id="late", ic="tc2", nsteps=1))
    assert isinstance(ei.value, AdmissionRefused)   # typed hierarchy
    assert srv.stats["refused"] == 1
    # Draining + empty queue: serve_forever returns without serving.
    assert srv.serve_forever() == {}
    srv.close()

    from jaxstream.obs.sink import read_records

    autos = read_records(sink, kind="autoscale")
    assert [a["to_bucket"] for a in autos] == [1, 2]
    assert autos[0]["from_bucket"] == 2
    assert autos[0]["queue_depth"] == 5
    assert autos[0]["occupancy"] == 0.25
    assert autos[0]["reason"] == "autoscale"
    assert autos[1]["reason"] == "manual"


def test_serve_cli_summary(tmp_path):
    """scripts/serve.py end to end: YAML config + JSONL trace -> one
    JSON summary line + per-request zarr stores."""
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        f"grid: {{n: {N}}}\n"
        f"time: {{dt: {DT}}}\n"
        "model: {name: shallow_water_cov, backend: jnp}\n"
        "serve: {buckets: '2', segment_steps: 2, queue_capacity: 2}\n")
    trace = tmp_path / "reqs.jsonl"
    trace.write_text(
        '{"id": "a", "ic": "tc2", "nsteps": 3, "seed": 0}\n'
        '{"id": "b", "ic": "tc2", "nsteps": 2, "seed": 1}\n'
        '{"id": "c", "ic": "tc2", "nsteps": 4, "seed": 2}\n')

    import serve as serve_cli

    out_dir = str(tmp_path / "out")
    import io as _io
    from contextlib import redirect_stdout

    buf = _io.StringIO()
    with redirect_stdout(buf):
        rc = serve_cli.main([str(cfg), "--requests", str(trace),
                             "--output-dir", out_dir])
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 1, "CLI must print exactly ONE JSON line"
    summary = json.loads(lines[0])
    assert summary["completed"] == 3 and summary["evicted"] == 0
    assert summary["steady_recompiles"] == 0
    assert summary["requests"] == {"a": "ok", "b": "ok", "c": "ok"}
    # The capacity-2 queue forced interleaved admission (producer-side
    # backpressure), and every request landed a result store.
    from jaxstream.io.history import HistoryWriter

    for rid, ns in (("a", 3), ("b", 2), ("c", 4)):
        hw = HistoryWriter(os.path.join(out_dir, rid))
        assert len(hw) == 1
        h = hw.read("h")
        assert h.shape == (1, 6, N, N)
        assert np.all(np.isfinite(h))
        assert hw.times[0] == ns * DT
