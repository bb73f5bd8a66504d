"""Round-19 performance-observatory coverage (jaxstream.obs.perf).

The acceptance criteria of the cost-stamp / memory-telemetry /
regression-ledger layer, all CPU-runnable (check_tiers rule 13):
every proof-stamped stepper carries a cost stamp; ``measure_cost``
fills footprint bytes + compile seconds + the flops-vs-analytic band
check (typed ``unavailable`` fallback when memory_analysis is
missing); the MemoryWatcher publishes per-chip gauges + typed sink
records and degrades to ONE typed record on statless backends, with
the default-off config keeping the serve sink on the round-17/18
record set; the ledger passes the real BENCH_r01→ history and FAILS
the seeded 30%-regression fixture through every entry point
(``check_trajectory``, ``scripts/perf_ledger.py``,
``scripts/analyze.py --fixture perf_regression``); and the operator
tools render the new ``memory``/``perf`` kinds without tripping their
own loud unrendered-kinds footer.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: A synthetic BENCH_r01..r05 trajectory (same record shape as a bench
#: envelope; made-up numbers, no chip produced them).
HISTORY = os.path.join(REPO, "tests", "fixtures", "bench_history")
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from jaxstream.obs import perf as obs_perf              # noqa: E402
from jaxstream.obs.registry import (MetricsRegistry,    # noqa: E402
                                    parse_exposition)
from jaxstream.obs.sink import (read_records,           # noqa: E402
                                validate_record)
from jaxstream.utils import jax_compat                  # noqa: E402

FAKE_STATS = {"bytes_in_use": 3 << 20, "peak_bytes_in_use": 5 << 20,
              "bytes_limit": 16 << 30}

SERVE_CFG = {
    "grid": {"n": 8},
    "time": {"dt": 600.0, "scheme": "ssprk3"},
    "model": {"name": "shallow_water_cov", "backend": "jnp"},
    "serve": {"buckets": "1,2", "segment_steps": 2,
              "cost_stamps": True, "memory_watch": True},
}


@pytest.fixture(scope="module")
def cost_server(tmp_path_factory):
    """ONE served deployment with the full observatory on (C8, jnp,
    3 requests through the B=2 bucket) — every server-side assertion
    reads this fixture instead of compiling its own."""
    from jaxstream.serve import EnsembleServer, ScenarioRequest

    sink = str(tmp_path_factory.mktemp("perfobs") / "serve.jsonl")
    cfg = {**SERVE_CFG,
           "serve": {**SERVE_CFG["serve"], "sink": sink}}
    srv = EnsembleServer(cfg)
    srv.memory_watcher._stats_fn = lambda d: FAKE_STATS
    for i in range(3):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=4,
                                   seed=i, amplitude=1e-3))
    srv.serve()
    srv.close()
    return srv, sink


# ------------------------------------------------------------- stamps
def test_cost_stamp_rides_every_proof_stamped_stepper():
    """Fused + classic factory steppers carry ``cost`` next to
    ``proof`` (same plan key; analytic half filled, measured half the
    typed not-measured fallback until a compile happens)."""
    from jaxstream.config import (EARTH_GRAVITY, EARTH_OMEGA,
                                  EARTH_RADIUS)
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.parallel.sharded_model import make_stepper_for
    from jaxstream.physics.initial_conditions import williamson_tc2

    grid = build_grid(8, halo=2, radius=EARTH_RADIUS,
                      dtype=jnp.float32)
    h, v = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    classic = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                    omega=EARTH_OMEGA)
    st = classic.initial_state(h, v)
    step = make_stepper_for(classic, None, st, 600.0)
    assert step.proof is not None and step.cost is not None
    assert step.cost.plan_key == step.proof.plan_key
    ana = step.cost.analytic
    assert ana["flops"] > 0 and ana["bytes"] > 0 and ana["ai"] > 0
    assert step.cost.memory == {"unavailable": "not measured"}
    assert step.cost.xla_visible is True

    fused = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA,
                                  backend="pallas_interpret")
    fstep = fused.make_fused_step(600.0)
    assert fstep.cost is not None
    assert fstep.cost.plan_key == fstep.proof.plan_key
    assert fstep.cost.xla_visible is False     # Pallas hides flops
    # One batched step advances every member: flops AND bytes scale
    # with B together (intensity invariant) — the ensemble stamp
    # must reflect that, not a B-inflated AI.
    b2 = fused.make_fused_step(600.0, ensemble=2)
    assert b2.cost.analytic["flops"] == pytest.approx(
        2 * fstep.cost.analytic["flops"])
    assert b2.cost.analytic["ai"] == pytest.approx(
        fstep.cost.analytic["ai"])
    # to_json round-trips through the sink validator's json layer.
    json.loads(json.dumps(fstep.cost.to_json()))


def test_measure_cost_fields_and_drift_band():
    """The measured half: compile seconds, XLA flops/bytes, footprint
    bytes, and the analytic cross-check — in band quietly, out of
    band LOUDLY (ratio still recorded)."""
    f = lambda x: jnp.sin(x) @ x.T                       # noqa: E731
    x = jnp.ones((64, 64), jnp.float32)
    stamp = obs_perf.measure_cost(
        f, x, plan_key="toy",
        analytic={"flops": 5.25e5, "bytes": 8.2e4})
    assert stamp.compile_seconds > 0
    assert stamp.xla["flops"] > 0
    assert stamp.memory["total_bytes"] > 0
    assert stamp.memory["argument_bytes"] == 64 * 64 * 4
    assert stamp.in_band is True
    band = obs_perf.FLOPS_RATIO_BAND
    assert band[0] <= stamp.flops_ratio <= band[1]
    # The drift is LOUD: capture the module logger directly (it does
    # not propagate to root, so caplog cannot see it).
    import logging

    messages = []
    handler = logging.Handler()
    handler.emit = lambda rec: messages.append(rec.getMessage())
    lg = logging.getLogger("jaxstream.obs.perf")
    lg.addHandler(handler)
    try:
        bad = obs_perf.measure_cost(
            f, x, plan_key="toy-drift",
            analytic={"flops": 10.0, "bytes": 1.0})
    finally:
        lg.removeHandler(handler)
    assert bad.in_band is False
    assert bad.flops_ratio > band[1]
    assert any("OUTSIDE the declared band" in m for m in messages)
    # Pallas-style plans skip the band check instead of crying wolf.
    blind = obs_perf.measure_cost(
        f, x, plan_key="toy-blind",
        analytic={"flops": 10.0, "bytes": 1.0}, xla_visible=False)
    assert blind.in_band is None and blind.flops_ratio is not None


def test_memory_analysis_unavailable_typed_fallback(monkeypatch):
    """Backends without Compiled.memory_analysis degrade to the typed
    {"unavailable": reason} dict — never a crash, never a missing
    key."""
    def raiser(compiled):
        raise RuntimeError("unavailable: no memory analysis here")

    monkeypatch.setattr(jax_compat, "memory_analysis", raiser)
    stamp = obs_perf.measure_cost(lambda x: x + 1.0,
                                  jnp.ones(8), plan_key="fallback")
    assert stamp.memory == {
        "unavailable": "unavailable: no memory analysis here"}
    assert stamp.compile_seconds > 0       # the rest still measured
    assert stamp.xla["flops"] >= 0


# ----------------------------------------------------- memory watcher
def test_memory_watcher_gauges_records_roundtrip(tmp_path):
    """Fake per-device stats -> per-chip gauges (scrape parses as
    exposition 0.0.4) + schema-valid 'memory' records per poll."""
    reg = MetricsRegistry()
    written = []
    stats = {"d0": dict(FAKE_STATS),
             "d1": {"bytes_in_use": 1 << 20, "bytes_limit": 8 << 30}}
    w = obs_perf.MemoryWatcher(devices=["d0", "d1"], registry=reg,
                               sink_write=written.append,
                               stats_fn=lambda d: stats[d])
    rec = w.poll()
    assert w.available is True and w.polls == 1
    validate_record(rec)
    assert rec["bytes_in_use"] == [3 << 20, 1 << 20]
    # peak falls back to in_use when the backend keeps no watermark.
    assert rec["peak_bytes"] == [5 << 20, 1 << 20]
    assert rec["limit_bytes"] == [16 << 30, 8 << 30]
    parsed = parse_exposition(reg.render())
    samples = parsed["samples"]["jaxstream_device_memory_bytes_in_use"]
    assert samples['chip="0"'] == float(3 << 20)
    assert samples['chip="1"'] == float(1 << 20)
    assert ("jaxstream_device_memory_limit_bytes"
            in parsed["types"])
    w.poll()
    assert w.polls == 2 and len(written) == 2
    assert w.limit_bytes() == 8 << 30      # min over chips
    assert obs_perf.headroom_fraction(4 << 30, w.limit_bytes()) \
        == pytest.approx(0.5)
    assert obs_perf.headroom_fraction(None, w.limit_bytes()) is None


def test_memory_watcher_statless_backend_reports_once():
    """CPU-style backends (memory_stats() -> None): ONE typed record,
    then no-ops — and the real CPU devices behave exactly so."""
    written = []
    w = obs_perf.MemoryWatcher(devices=["d0"],
                               sink_write=written.append,
                               stats_fn=lambda d: None)
    rec = w.poll()
    assert w.available is False
    assert rec["bytes_in_use"] == [] and "unavailable" in rec
    validate_record(rec)
    assert w.poll() is None and len(written) == 1
    # The live CPU backend takes the same path (the rule-13
    # CPU-honesty contract: no accelerator required to test it).
    live = obs_perf.device_memory_record(devices=jax.devices()[:1])
    validate_record(live)


# ------------------------------------------------------------ serving
def test_serve_bucket_cost_stamps_full(cost_server):
    """Under serve.cost_stamps every warm bucket's stamp carries the
    measured footprint, compile seconds and an in-band flop ratio;
    the advisory headroom lands on the bucket plan."""
    srv, _ = cost_server
    costs = srv.bucket_costs()
    assert costs, "no warm buckets stamped"
    for key, stamp in costs.items():
        assert stamp["plan_key"] == "serve_single+classic", key
        assert stamp["memory"]["total_bytes"] > 0
        assert stamp["compile_seconds"] > 0
        assert stamp["analytic"]["flops"] > 0
        assert stamp["in_band"] is True, stamp
        assert 0.0 < stamp["headroom_frac"] <= 1.0
    plan = srv._plans[2]
    assert plan.headroom_frac == pytest.approx(1.0, abs=1e-3)


def test_serve_memory_and_perf_sink_records(cost_server):
    """The sink carries schema-valid 'memory' records at boundary
    cadence and one 'perf' record per stamped bucket."""
    _, sink = cost_server
    recs = read_records(sink)                 # validates every line
    mems = [r for r in recs if r["kind"] == "memory"]
    perfs = [r for r in recs if r["kind"] == "perf"]
    assert len(mems) >= 2                     # >= one per boundary
    assert all(m["bytes_in_use"] == [3 << 20] for m in mems)
    assert len(perfs) == 1
    assert perfs[0]["plan"] == "serve_single+classic"
    assert perfs[0]["memory"]["total_bytes"] > 0
    assert perfs[0]["headroom_frac"] is not None
    manifest = recs[0]
    assert manifest["config"]["memory_watch"] is True
    assert manifest["config"]["cost_stamps"] is True


def test_serve_scrape_carries_memory_and_compile_counters(cost_server):
    """/v1/metrics surface: per-chip device-memory gauges + the
    per-plan compile counter, all valid exposition."""
    srv, _ = cost_server
    parsed = parse_exposition(srv.metrics.render())
    mem = parsed["samples"]["jaxstream_device_memory_bytes_in_use"]
    assert mem['chip="0"'] == float(3 << 20)
    compiles = parsed["samples"]["jaxstream_compiles_total"]
    key = 'plan="serve_single+classic"'
    assert compiles[key] >= 3       # seg + extract + inject warmup
    # Steady-state serving moved the gauge, not the counter: the
    # compile total equals the server's own zero-recompile surface.
    assert compiles[key] == srv.compile_count()


def test_serve_default_off_keeps_round18_sink(tmp_path):
    """The PR-4/PR-13 contract: observatory off (the default) writes
    NO new record kinds, no new manifest keys, constructs no watcher
    — the sink stream is the round-17/18 one."""
    from jaxstream.serve import EnsembleServer, ScenarioRequest

    sink = str(tmp_path / "plain.jsonl")
    cfg = {"grid": {"n": 8},
           "time": {"dt": 600.0, "scheme": "ssprk3"},
           "model": {"name": "shallow_water_cov", "backend": "jnp"},
           "serve": {"buckets": "1", "segment_steps": 2,
                     "sink": sink}}
    srv = EnsembleServer(cfg)
    assert srv.memory_watcher is None
    assert srv.memory_snapshot() is None
    srv.submit(ScenarioRequest(id="p0", ic="tc2", nsteps=2, seed=0,
                               amplitude=1e-3))
    srv.serve()
    srv.close()
    recs = read_records(sink)
    assert {r["kind"] for r in recs} <= {"manifest", "serve"}
    assert "memory_watch" not in recs[0]["config"]
    assert "cost_stamps" not in recs[0]["config"]
    # The always-on half still stamps: analytic + warmup wall, with
    # the typed not-measured footprint.
    costs = srv.bucket_costs()
    (stamp,) = costs.values()
    assert stamp["analytic"]["flops"] > 0
    assert stamp["compile_seconds"] > 0
    assert stamp["memory"] == {"unavailable": "not measured"}


def test_gateway_stats_expose_bucket_costs():
    """/v1/stats (the in-process snapshot the handler serves) carries
    the bucket_costs surface."""
    pytest.importorskip("aiohttp")
    from jaxstream.gateway import Gateway

    gw = Gateway(SERVE_CFG, warm=False)     # never serves: no compiles
    try:
        snap = gw.snapshot()
        assert "bucket_costs" in snap
        assert snap["bucket_costs"] == {}   # nothing warm yet
    finally:
        gw.close()


# ------------------------------------------------------------- ledger
def test_ledger_parses_and_passes_recorded_history():
    pts = obs_perf.load_bench_history(HISTORY)
    assert len(pts) == 5
    by_label = {p["label"]: p for p in pts}
    assert by_label["BENCH_r01"]["hardware_class"] == "accelerator"
    assert by_label["BENCH_r05"]["reported_only"] is False
    assert by_label["BENCH_r05"]["sections"]["headline"] == 1.25
    assert ("variant:mixed16_carry"
            in by_label["BENCH_r05"]["sections"])
    res = obs_perf.check_trajectory(pts)
    assert res["ok"] is True and res["regressions"] == []
    # Smoke/CPU candidates are reported-only: advisories, never gates.
    smoke = obs_perf.parse_bench_point(
        {"parsed": {"smoke": True, "hardware": "cpu", "value": 0.01,
                    "metric": "bench_smoke"}}, label="smoke")
    assert smoke["reported_only"] is True
    res2 = obs_perf.check_trajectory(pts + [smoke])
    assert res2["ok"] is True and res2["enforced"] is False


def test_ledger_fixture_fails_loudly_everywhere(tmp_path, capsys):
    """The seeded 30%-regression + grown-footprint corpus fails the
    gate through every entry point — the ledger cannot lose its teeth
    unnoticed."""
    pts = [obs_perf.parse_bench_point(o, label=f"fx{o['n']}")
           for o in obs_perf.broken_bench_history()]
    res = obs_perf.check_trajectory(pts)
    assert res["ok"] is False and res["enforced"] is True
    # headline + variant:mixed16_carry + footprint all had a
    # comparable prior — a vacuous pass (compared_sections == 0)
    # could never report ok=False, so the count is part of the teeth.
    assert res["compared_sections"] == 3
    assert {r["section"] for r in res["regressions"]} == {
        "headline", "footprint"}
    # The CLI over materialized files...
    import perf_ledger

    paths = obs_perf.write_broken_bench_history(str(tmp_path))
    rc = perf_ledger.main(["check"] + paths + ["--json"])
    out = json.loads(capsys.readouterr().out.strip())
    assert rc == 1 and out["ok"] is False
    # ...its self-test mode...
    assert perf_ledger.main(["--fixture"]) == 1
    capsys.readouterr()
    # ...and the analyzer's fixture corpus.
    from jaxstream.analysis.fixtures import FIXTURES, run_fixture

    assert "perf_regression" in FIXTURES
    report = run_fixture("perf_regression")
    assert not report.passed
    import analyze

    code, result, _ = analyze.run(["--fixture", "perf_regression",
                                   "--json"])
    assert code == 1
    assert result["violation_count"] == 2
    # A widened band would come back clean — exactly what CI fails on.
    loose = obs_perf.check_trajectory(pts, max_regression=0.5,
                                      max_footprint_growth=2.0)
    assert loose["ok"] is True


def test_ledger_cli_renders_and_checks_repo_history(capsys):
    import glob

    import perf_ledger

    paths = sorted(glob.glob(os.path.join(HISTORY, "BENCH_r*.json")))
    assert perf_ledger.main(paths) == 0
    out = capsys.readouterr().out
    assert "BENCH_r01" in out and "BENCH_r05" in out
    assert "enforced" in out
    assert perf_ledger.main(["check"] + paths + ["--json"]) == 0
    res = json.loads(capsys.readouterr().out.strip())
    assert res["ok"] is True
    # The repo root's own records (BENCH_r06, a CPU smoke) render and
    # check clean too.
    assert perf_ledger.main(["check", "--json"]) == 0
    res = json.loads(capsys.readouterr().out.strip())
    assert res["ok"] is True


# ----------------------------------------------------- operator tools
def test_report_and_dashboard_render_observatory(cost_server, capsys):
    """telemetry_report + telemetry_dashboard render the new kinds —
    memory section/panel with peak watermarks, the cost-stamp table —
    and their loud unrendered-kinds footer stays EMPTY."""
    _, sink = cost_server
    import telemetry_dashboard
    import telemetry_report

    assert telemetry_report.main([sink, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out.strip())
    assert rep["unrendered_kinds"] == {}
    assert rep["memory"]["polls"] >= 2
    assert rep["memory"]["last_bytes_in_use"] == [3 << 20]
    assert rep["memory"]["peak_bytes"] == [5 << 20]
    stamps = rep["perf"]["stamps"]
    assert stamps[0]["plan"] == "serve_single+classic"
    assert stamps[0]["footprint_bytes"] > 0
    assert telemetry_report.main([sink]) == 0          # human render
    text = capsys.readouterr().out
    assert "device memory" in text and "plan cost stamps" in text
    assert "unrendered kinds" not in text

    assert telemetry_dashboard.main([sink, "--json"]) == 0
    frame = json.loads(capsys.readouterr().out.strip())
    assert frame["unrendered_kinds"] == {}
    assert frame["memory"]["bytes_in_use"] == [3 << 20]
    assert frame["memory"]["peak_bytes"] == [5 << 20]
    assert frame["perf"][0]["plan"] == "serve_single+classic"
    assert telemetry_dashboard.main([sink, "--once",
                                     "--no-color"]) == 0
    ansi = capsys.readouterr().out
    assert "device memory (peak watermark |)" in ansi
    assert "plan cost stamps:" in ansi
    bar = telemetry_dashboard.memory_bar(50, 75, 100, width=20)
    assert bar.count("█") == 10 and "|" in bar


def test_plan_explain_prints_cost_stamp(capsys):
    """scripts/plan.py explain prints the analytic cost next to the
    proof — statically, no devices."""
    import plan as plan_cli

    assert plan_cli.main(["explain", "grid:\n  n: 48\n",
                          "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip())
    assert out["ok"] is True
    cost = out["cost"]
    assert cost["analytic"]["flops"] > 0
    assert cost["memory"] == {"unavailable": "not measured"}
    assert plan_cli.main(["explain", "grid:\n  n: 48\n"]) == 0
    text = capsys.readouterr().out
    assert "cost:  analytic" in text and "GFLOP/step" in text


def test_roofline_one_definition():
    """bench's per-variant roofline and the probe CLIs now share ONE
    implementation (obs.perf.roofline_json)."""
    import bench

    v5e = "TPU v5 lite"
    ours = obs_perf.roofline_json(1000.0, 96, carry_bytes=2,
                                  device_kind=v5e)
    theirs = bench._roofline_json(1000.0, 96, carry_bytes=2,
                                  device_kind=v5e)
    assert ours == theirs
    bf = obs_perf.roofline_json(1000.0, 96, precision="bf16",
                                device_kind=v5e)
    assert 0.0 < bf["bf16_flop_fraction"] < 1.0
    assert bf["pct_of_mixed_roof"] > 0


def test_roofline_peaks_keyed_by_device_kind():
    """The peaks come from the device that ran: a CPU run gets no
    roofline share, and an unknown accelerator is an error, never v5e."""
    assert obs_perf.roofline_json(1000.0, 96) is None  # this CPU host
    assert obs_perf.roofline_json(1000.0, 96, device_kind="cpu") is None
    with pytest.raises(ValueError, match="no roofline peaks"):
        obs_perf.roofline_json(1000.0, 96, device_kind="TPU v9 mega")
