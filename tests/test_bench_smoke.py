"""bench.py --smoke wired into the tier-1 gate (round-7 CI satellite).

The full benchmark only runs offline on a TPU, so bench bitrot (an
import drift, a renamed helper, a JSON-assembly typo) historically
surfaced rounds later.  ``bench_smoke`` is the C24/no-gates canary:
this test drives it through ``main()``'s ``--smoke`` flag IN-PROCESS
(subprocess startup would pay ~15 s of interpreter+jax boot for no
extra coverage) and checks the one-line JSON contract the driver
scrapes.
"""

import io
import json
import sys

import numpy as np


def test_bench_smoke_runs_and_reports(monkeypatch, capsys, tmp_path):
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    import bench

    telemetry = str(tmp_path / "bench.jsonl")
    monkeypatch.setattr(sys, "argv", ["bench.py", "--smoke",
                                      "--telemetry", telemetry])
    code = None
    try:
        bench.main()
    except SystemExit as e:
        code = e.code
    assert code == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, "smoke must print exactly ONE JSON line"
    rec = json.loads(out[0])
    assert rec["smoke"] is True
    assert rec["ok"] is True
    assert rec["metric"].startswith("bench_smoke_TC5_C")
    ens = rec["ensemble"]
    assert ens["impl"] in ("fused_kernel", "vmap_classic")
    for key in ("B1", "B2"):
        assert ens[key]["sim_days_per_sec"] > 0.0, key
        assert np.isfinite(ens[key]["sim_days_per_sec"])
    # B=2 advances two members per step; a correct batched path beats
    # B=1 aggregate comfortably (measured ~2x on CPU).  The floor only
    # guards against a batched step that silently advances one member
    # (aggregate ratio ~0.5x, a hard arithmetic consequence) — wall-
    # clock noise on the tiny smoke windows must not flake this: the
    # old 0.9 floor flaked at 0.77x under load and 0.72 flaked at
    # 0.714x on a degraded 2-core CI box, so the floor sits at 0.6,
    # splitting the ~0.5x failure band from the observed >=0.71x
    # noise band.
    assert (ens["B2"]["sim_days_per_sec"]
            >= 0.6 * ens["B1"]["sim_days_per_sec"])
    assert ens["batched_exchange_plan"]["members"] == 2

    # The io (async-pipeline) section ran all three modes and kept the
    # carry finite; rates are smoke windows, so no overhead assertion.
    io_sec = rec["io"]
    assert "skipped" not in io_sec, io_sec
    for mode in ("off", "sync", "async"):
        assert io_sec[mode]["steps_per_sec"] > 0.0, mode
    assert "host_wait_s_total" in io_sec["sync"]
    assert "host_wait_s_total" in io_sec["async"]
    assert isinstance(io_sec["async_overhead_smaller"], bool)

    # The serving canary (round 11) ran the continuous-batching server
    # end to end at C16: every request completed, slots stayed
    # occupied, refills happened (the 4 request lengths are ragged vs
    # the 2-step segment), the request latencies are ordered sanely,
    # and — the bucket claim — serving compiled NOTHING after warmup.
    # Rates are smoke windows; no throughput assertion (the >= 0.9x
    # vs-static-B16 floor is asserted on the TPU bench run's JSON).
    srv = rec["serving"]
    assert "skipped" not in srv, srv
    for mode in ("packed", "serial_B1"):
        m = srv[mode]
        assert m["completed"] == srv["n_requests"], (mode, m)
        assert m["evicted"] == 0, (mode, m)
        assert m["steady_recompiles"] == 0, (mode, m)
        assert m["warmup_compiles"] > 0, (mode, m)
        assert 0.0 < m["occupancy_mean"] <= 1.0, (mode, m)
        assert 0.0 < m["utilization_mean"] <= 1.0, (mode, m)
        assert m["member_steps_per_sec"] > 0.0, (mode, m)
        assert 0.0 < m["latency_p50_s"] <= m["latency_p99_s"], (mode, m)
    assert srv["packed"]["refills"] > 0
    assert srv["packed"]["member_steps"] == srv["serial_B1"]["member_steps"]

    # The multi-chip serving canary (round 12) drove the member-
    # parallel placement on a 6-fake-device CPU mesh through the REAL
    # bench_serving_multichip code path: every request completed in
    # both runs, packed h results byte-matched between the
    # single-device and sharded servers, u stayed inside the packed-
    # vs-packed budget, and steady-state serving compiled NOTHING
    # under placement.  The 0.8x scaling floor is reported only (all
    # fake devices share this host's cores; it is enforced on real
    # accelerators by the full bench run).
    mc = rec["serving_multichip"]
    assert "skipped" not in mc, mc
    assert mc["devices"] >= 2
    assert mc["mode"] == "member"
    assert mc["floor_enforced"] is False        # fake CPU mesh
    assert mc["bitwise_h_ok"] is True
    assert mc["u_rel_max"] <= 2e-6
    assert mc["zero_steady_recompiles"] is True
    for m in ("single", "multichip"):
        assert mc[m]["completed"] > 0, m
        assert mc[m]["steady_recompiles"] == 0, m
        assert mc[m]["member_steps_per_sec"] > 0.0, m
    # Equal per-chip load: the multichip run served devices x the
    # single run's member-steps.
    assert (mc["multichip"]["member_steps"]
            == mc["devices"] * mc["single"]["member_steps"])
    assert mc["multichip"]["placement"]["mode"] == "member"
    assert isinstance(mc["scaling_vs_ideal"], float)

    # The serving-SLO canary (round 14) drove the network front door
    # end to end: 10 mixed-IC requests over REAL loopback HTTP through
    # the asyncio gateway under a heavy-tailed burst, with the
    # closed-loop harness measuring latency/goodput and the autoscale
    # policy resizing the active bucket cap live.  The structural
    # floors are enforced inside bench_serving_slo (gates=True):
    # accounting exactness (completed + typed-shed == submitted, zero
    # untyped errors), >= 1 live resize, and zero steady-state
    # recompiles after warmup INCLUDING the resizes — a breach
    # surfaces as "skipped" and fails here.  Latencies are smoke
    # numbers; only structure is asserted.
    slo = rec["serving_slo"]
    assert "skipped" not in slo, slo
    s = slo["slo"]
    assert s["n_requests"] == 10
    assert s["accounting_exact"] is True
    assert s["completed"] + s["shed"] == 10 and s["errors"] == 0
    assert s["goodput_member_steps_per_sec"] > 0.0
    assert 0.0 < s["latency_p50_s"] <= s["latency_p99_s"]
    assert slo["resizes"] >= 1
    assert slo["steady_recompiles"] == 0
    assert slo["warm_compiles"] > 0
    az = slo["autoscale"]
    assert az["levels"] == [1, 2]
    assert az["events"][0]["to_bucket"] == 2
    # The trace mixed IC families (seeded — deterministic).
    assert len(slo["families"]) >= 2
    # Round 17: the section runs with request tracing ON and the
    # spans_complete == 1.0 floor ENFORCED inside bench_serving_slo —
    # every completed request reassembled into a full span tree whose
    # leaf durations sum to its reported latency (a breach surfaces
    # as "skipped" and fails above).  The stamp is also asserted here
    # so the canary cannot silently stop checking it.
    assert s["spans_checked"] == s["completed"]
    assert s["spans_complete"] == 1.0
    assert s["span_failures"] == {}
    # ...and the live /v1/metrics scrape parsed as Prometheus text
    # exposition 0.0.4 (structure validated by parse_exposition —
    # +Inf buckets, monotone cumulative counts).
    scrape = slo["metrics_scrape"]
    assert scrape["ok"] is True
    assert scrape["status"] == 200
    assert "version=0.0.4" in scrape["content_type"]
    assert scrape["families"] >= 10
    assert scrape["submitted"] == s["n_requests"]

    # The assimilation canary (round 18) closed the forecast loop
    # through the REAL bench_assimilation code path: hidden truth run,
    # seeded 48-station network, B=4 batched Galewsky forecast with
    # the in-loop h_spread stream, the B x B stochastic analysis, and
    # the free-ensemble baseline under identical seeds.  The forecast
    # claim and filter health ARE enforced inside bench_assimilation
    # (gates=True) — a breach surfaces as "skipped" and fails here —
    # and re-asserted so the canary cannot silently stop checking.
    da = rec["assimilation"]
    assert "skipped" not in da, da
    assert da["beats_free_run"] is True
    assert da["cycled_final_rmse"] < da["free_final_rmse"]
    assert da["rmse_reduction"] > 0.0
    assert da["guard_events"] == 0
    assert da["plan"] == "classic+B4+da"
    assert da["proof_verdict"] == "verified"
    assert len(da["cycle_records"]) == da["cycles"]
    for c in da["cycle_records"]:
        assert c["spread"] > 0.0 and c["spread_post"] > 0.0
        assert c["nobs"] == da["nstations"]

    # The precision ladder (round 10) ran all four rows through the
    # real --precision-report code path: reduced-precision stage
    # kernels, carry encoders, and the precision-corrected roofline
    # JSON all compile and produce finite rates.  Rates are interpret-
    # mode smoke windows — only structure is asserted.
    prec = rec["precision_report"]
    assert "skipped" not in prec, prec
    assert set(prec["rows"]) == {"f32", "bf16_stage", "mixed16_carry",
                                 "stacked"}
    for name, row in prec["rows"].items():
        assert "skipped" not in row, (name, row)
        assert row["steps_per_sec"] > 0.0, name
        assert np.isfinite(row["steps_per_sec"]), name
        # A CPU run gets no roofline share: the peaks are keyed by
        # the device kind that ran (utils.profiling.DEVICE_ROOFS).
        assert "roofline" not in row, name
    # The corrected bytes model, on v5e's peaks at the rows' own rates:
    # a 16-bit carry moves fewer bytes per step at the same flop count,
    # so its AI must come out HIGHER than the f32 row's (but below the
    # old bytes*0.5 model, which billed the f32 orography re-read at 2
    # bytes too); bf16-stage rows carry the mixed-roof fields.
    from jaxstream.obs.perf import roofline_json

    kw = {"f32": {}, "mixed16_carry": {"carry_bytes": 2},
          "bf16_stage": {"precision": "bf16"},
          "stacked": {"precision": "bf16", "carry_bytes": 2}}
    rls = {name: roofline_json(prec["rows"][name]["steps_per_sec"], 12,
                               device_kind="TPU v5 lite", **kw[name])
           for name in kw}
    assert rls["mixed16_carry"]["ai"] > rls["f32"]["ai"]
    assert rls["mixed16_carry"]["carry_bytes"] == 2
    for name in ("bf16_stage", "stacked"):
        rl = rls[name]
        assert 0.0 < rl["bf16_flop_fraction"] < 1.0, (name, rl)
        assert rl["mixed_roof_tflops"] > 0.0, name
        assert "pct_of_mixed_roof" in rl, name

    # The contract-check stamp (round 13): every bench run carries the
    # static analyzer's verdict over the full composition matrix —
    # schedule totality/coverage/depth, traced collective counts vs
    # the comm_probe analytic plans, overlap windows, precision/
    # donation/callback invariants.  The smoke asserts it ran AND came
    # back clean, so a broken schedule fails this tier-1 gate even if
    # every runtime parity window happens to look plausible.
    cc = rec["contract_check"]
    assert "skipped" not in cc, cc
    assert cc["exit_code"] == 0
    assert cc["ok"] is True
    assert cc["violations"] == []
    assert cc["checks_run"] > 400
    facts = cc["facts"]
    assert facts["ok"] is True
    # The analytic plans and the traced schedules pin the same digest.
    from jaxstream.geometry.connectivity import schedule_fingerprint

    assert facts["schedule_fingerprint"] == schedule_fingerprint()
    assert facts["variants"]["face"]["ppermutes_per_step"] == 12.0
    # Round 16: the stamp records the enumerated plan-space size and
    # the rule-table version, so a silently shrinking legal space (a
    # feature flag dropping out of the verified matrix) fails THIS
    # tier-1 gate loudly.
    space = facts["plan_space"]
    from jaxstream.plan.rules import RULES_VERSION

    assert space["size"] >= 16
    assert space["size"] == len(space["keys"])
    assert space["rules_version"] == RULES_VERSION
    assert set(space["keys"]) <= set(facts["variants"])

    # The performance-observatory section (round 19): the smoke runs
    # the REAL bench_perf code path on the classic rung — XLA sees
    # every op, so the cost stamp carries real footprint bytes, a
    # positive compile time, and an in-band flops-vs-analytic ratio —
    # and device memory degrades to the typed unavailable record on
    # this CPU image (TPU/GPU fill the per-chip lists).
    assert rec["hardware"] == "cpu"
    perf = rec["perf"]
    assert "skipped" not in perf, perf
    assert perf["hardware"] == "cpu"
    assert perf["rung"] == "classic"
    cost = perf["cost"]
    assert cost["compile_seconds"] > 0
    assert cost["memory"]["total_bytes"] > 0
    assert cost["xla"]["flops"] > 0
    assert cost["in_band"] is True, cost
    mem = perf["memory"]
    assert mem["kind"] == "memory"
    assert mem["bytes_in_use"] == [] and "unavailable" in mem

    # ...and the regression-ledger stamp (round 19): the recorded
    # BENCH_r*.json trajectory parses, this CPU-smoke record lands as
    # a reported-only candidate (never gated — the enforced
    # trajectory is the accelerator one), and the check comes back
    # clean.  The seeded-broken fixture keeping the gate's teeth is
    # asserted in tests/test_perf_obs.py.
    pl = rec["perf_ledger"]
    assert "skipped" not in pl, pl
    assert pl["ok"] is True
    assert pl["enforced"] is False          # CPU smoke: reported-only
    assert pl["hardware_class"] == "cpu"
    assert pl["points"] >= 2                # r06 + this candidate
    # With BENCH_r06 (cpu) recorded, the CPU-smoke candidate has at
    # least one comparable section — the check is not vacuous.
    assert pl["compared_sections"] >= 1
    assert pl["regressions"] == []

    # The flight-recorder overhead stamp (round 20): the envelope
    # carries the recorder-on vs recorder-off stepping-window
    # comparison, and the number behind the always-on claim holds —
    # per-segment record() calls cost < 3% of a real serving window
    # (best-of-5 per arm; record() is pure-Python ring bookkeeping,
    # so the bound is comfortable, not marginal).
    fo = rec["flight_overhead"]
    assert "skipped" not in fo, fo
    assert fo["t_on_s"] > 0.0 and fo["t_off_s"] > 0.0
    assert fo["records_per_window"] > 0
    assert 0.0 <= fo["overhead_pct"] < 3.0, fo

    # The cold-start canary (round 21): the smoke runs the REAL
    # bench_cold_start code path with its gates ENFORCED (gates=True —
    # a breach surfaces as ok: False or "skipped" and fails here): a
    # warm-pool-loaded server reaches its first result and resizes to
    # a new bucket >= 3x faster than cold, performs ZERO XLA compiles,
    # and its first-segment results byte-match the cold server's.
    cs = rec["cold_start"]
    assert "skipped" not in cs, cs
    assert cs["ok"] is True, cs
    assert cs["warm_compiles"] == 0
    assert cs["byte_equal"] is True
    assert cs["warm_speedup"] >= 3.0, cs
    assert cs["resize_speedup"] >= 3.0, cs
    assert cs["hits"] > 0
    assert cs["cold_first_result_s"] > cs["warm_first_result_s"] > 0.0
    assert cs["cold_resize_s"] > cs["warm_resize_s"] > 0.0

    # --telemetry writes a schema-valid obs-sink file alongside the
    # stdout JSON (round-8 satellite: bench rides the structured sink).
    from jaxstream.obs.sink import read_records

    recs = read_records(telemetry)      # validates every line
    assert recs[0]["kind"] == "manifest"
    benches = [r for r in recs if r["kind"] == "bench"]
    names = {b["metric"] for b in benches}
    assert rec["metric"] in names
    assert any(m.endswith("_B1") for m in names)
