"""Trace-time contract checker coverage (round 13, tier-1).

The full stepper matrix — overlap x temporal_block x ensemble x
precision x serve placement — is traced ONCE per gate through the
CLI's importable entry point (``scripts/analyze.py run()``, the same
path ``bench.py``'s ``contract_check`` stamp uses) and every matrix
assertion reads the shared JSON facts; the schedule-verifier units and
the seeded-broken fixtures are pure and run in milliseconds.  Rule 8
of ``scripts/check_tiers.py`` keeps this module non-slow and
in-process by construction.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import analyze  # noqa: E402

from jaxstream.analysis import (  # noqa: E402
    ContractReport,
    face_seam_graph,
    verify_stage_perms,
)
from jaxstream.analysis import fixtures  # noqa: E402
from jaxstream.geometry.connectivity import (  # noqa: E402
    schedule_fingerprint,
    schedule_perms,
)


@pytest.fixture(scope="module")
def full_run():
    """One full-matrix run shared by every matrix assertion."""
    code, result, report = analyze.run(["--json"])
    return code, result


# ---------------------------------------------------------------------
# Seam graph + schedule verifier units (pure, fast)
# ---------------------------------------------------------------------

def test_seam_graph_structure():
    g = face_seam_graph()
    assert len(g["directed"]) == 24
    assert len(g["undirected"]) == 12
    assert len(g["corners"]) == 8
    # Octahedron adjacency: every face has exactly one antipode.
    assert len(g["antipodal"]) == 3
    for corner in g["corners"]:
        assert len(corner) == 3


def test_canonical_schedule_verifies_clean():
    report = ContractReport()
    verify_stage_perms(schedule_perms(), report, "canonical")
    assert report.passed, report.format()
    # Totality, symmetry, seam membership, coverage, corners all ran.
    checks = {c for c, _, _ in report._passes}
    assert {"schedule.total_permutation", "schedule.symmetric_pairs",
            "schedule.seam_graph_membership", "schedule.edge_coverage",
            "schedule.corner_stages"} <= checks


def test_fixture_dropped_pair_fails_loudly():
    rep = fixtures.run_fixture("dropped_pair")
    assert not rep.passed
    checks = {v.check for v in rep.violations}
    # The silent-ppermute failure class is named explicitly.
    assert "schedule.total_permutation" in checks
    assert "schedule.edge_coverage" in checks
    assert any("zero-fill" in v.detail for v in rep.violations)


def test_fixture_deep_depth_fails_loudly():
    rep = fixtures.run_fixture("deep_depth")
    assert not rep.passed
    assert {v.check for v in rep.violations} == {
        "schedule.deep_halo_depth"}
    assert any("3*k*halo" in v.detail for v in rep.violations)


def test_cli_fixture_modes_exit_nonzero(capsys):
    """Acceptance: the CLI exits nonzero on BOTH seeded-broken
    fixtures (a zero exit would mean the pass lost its teeth)."""
    for name in fixtures.FIXTURES:
        code = analyze.main(["--json", "--fixture", name])
        assert code == 1, name
        rec = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["ok"] is False and rec["violation_count"] > 0, name
        assert rec["mode"] == f"fixture:{name}"


def test_cli_schedules_only_clean(capsys):
    code = analyze.main(["--schedules-only", "--json"])
    assert code == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] is True and rec["checks_run"] > 200


def test_traced_broken_schedule_changes_fingerprint():
    """Jaxpr-side teeth: a dropped pair in an actually-traced ppermute
    program changes the traced fingerprint away from the plans'."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jaxstream.analysis.jaxpr_audit import audit_rounds, trace
    from jax import shard_map

    perms, _ = fixtures.broken_dropped_pair_perms()
    mesh = Mesh(jax.devices("cpu")[:6], ("panel",))

    def body(x):
        for perm in perms:
            x = x + jax.lax.ppermute(x, "panel", [tuple(p)
                                                  for p in perm])
        return x

    fn = shard_map(body, mesh=mesh, in_specs=P("panel"),
                   out_specs=P("panel"), check_vma=False)
    jx = trace(fn, jnp.zeros((6, 4), jnp.float32))
    rounds = audit_rounds(jx)
    traced = [list(p) for r in rounds for p in r.perms]
    assert schedule_fingerprint(traced) != schedule_fingerprint()


# ---------------------------------------------------------------------
# New seeded-broken fixtures (round 16): plan rules + proof stamps
# ---------------------------------------------------------------------

def test_fixture_illegal_plan_fails_loudly():
    rep = fixtures.run_fixture("illegal_plan")
    assert not rep.passed
    checks = {v.check for v in rep.violations}
    assert "plan.rules.stage-policy-needs-fused" in checks
    # The rejection carries the legacy pointer, not generic prose.
    assert any("comm_probe.py --strip-dtype" in v.detail
               for v in rep.violations)


def test_fixture_proof_fingerprint_fails_loudly():
    rep = fixtures.run_fixture("proof_fingerprint")
    assert not rep.passed
    assert {v.check for v in rep.violations} == {"proof.stamp"}
    assert any("does not describe this stepper" in v.detail
               for v in rep.violations)


# ---------------------------------------------------------------------
# Full composition matrix (the shared run, enumerated from the plan
# space — no hand-listed variants)
# ---------------------------------------------------------------------

def test_full_matrix_clean(full_run):
    """Acceptance: the checker is clean on EVERY plan in the
    enumerated legal space."""
    code, result = full_run
    assert result["violations"] == [], result["violations"]
    assert code == 0
    assert result["ok"] is True
    assert result["checks_run"] > 400
    facts = result["facts"]
    space = facts["plan_space"]
    # >= the 16 previously hand-listed variants, and every enumerated
    # plan actually audited (plus the segment-loop witness).
    assert space["size"] >= 16
    from jaxstream.plan.rules import RULES_VERSION

    assert space["rules_version"] == RULES_VERSION
    v = facts["variants"]
    assert set(space["keys"]) <= set(v)
    # The legacy 16-variant matrix, under its enumerated names —
    # nothing the hand list covered fell out of the space.
    assert {"face", "face+ov", "face+tb2", "face+ov+tb2",
            "face+B2", "face+ov+B2", "face+tb2+B2",
            "tt_sharded", "tt_sharded+ov", "gspmd", "fused",
            "fused+bf16", "fused+tb2+bf16", "segment_loop_face",
            "serve_panel+face", "serve_member+gspmd"} <= set(v)
    # ...and the combos hand-listing missed are now verified too.
    assert {"face+ov+tb2+B2", "fused+tb2", "gspmd+B2",
            "tt_sharded+tb2", "serve_single+classic",
            "serve_single+fused"} <= set(v)


def test_no_hand_enumerated_variant_list():
    """Acceptance: contracts.py contains no hand-enumerated variant
    list — the matrix is the plan-space enumeration."""
    import inspect

    from jaxstream.analysis import contracts

    src = inspect.getsource(contracts)
    assert "enumerate_plans" in src
    for legacy in ("face_serialized", "ensemble_B2", "fused_bf16_tb2",
                   "tt_serialized"):
        assert legacy not in src, legacy


def test_collective_counts_match_plans_exactly(full_run):
    """Acceptance: traced collective counts equal comm_probe's
    analytic plans exactly, per variant."""
    _, result = full_run
    facts = result["facts"]
    n, halo = facts["n"], facts["halo"]
    v = facts["variants"]

    from jaxstream.utils.comm_probe import (
        SERIALIZED_PPERMUTES_PER_STEP, batched_exchange_plan,
        temporal_block_plan)

    p1 = batched_exchange_plan(n, halo, 1)
    p2 = batched_exchange_plan(n, halo, 2)
    tb = temporal_block_plan(n, halo, 2)

    for name in ("face", "face+ov"):
        assert v[name]["ppermutes_per_step"] == \
            SERIALIZED_PPERMUTES_PER_STEP
        assert v[name]["payload_bytes_per_step"] == \
            p1["wire_bytes_per_member_step"]
    for name in ("face+tb2", "face+ov+tb2"):
        assert v[name]["ppermutes_per_step"] == \
            tb["ppermutes_per_step"]
        assert v[name]["payload_bytes_per_step"] == \
            tb["payload_bytes_per_step"]
        # One 3*k*halo-deep strip per stage, conserved wire bytes.
        assert v[name]["payload_shapes"] == [
            [3, tb["deep_halo_width"], n]]
    for name in ("face+B2", "face+ov+B2", "face+tb2+B2",
                 "face+ov+tb2+B2"):
        assert v[name]["ppermutes_per_step"] == \
            p2["ppermutes_per_step"]
        assert v[name]["payload_bytes_per_step"] == \
            p2["payload_bytes_per_ppermute"] * p2["ppermutes_per_step"]
        assert v[name]["payload_shapes"] == [[2, 3, halo, n]]
    # Exact temporal fusion: k x the per-step schedule in one call.
    assert v["face+tb2+B2"]["ppermutes_per_call"] == 24
    assert v["face+tb2+B2"]["rounds"] == [4] * 6
    # TT: depth-1 strips; overlap collapses 4 per-field exchanges
    # into one batched schedule per RK stage; temporal fusion scales
    # rounds by k at unchanged per-step counts.
    assert v["tt_sharded"]["rounds"] == [16, 16, 16]
    assert v["tt_sharded+ov"]["rounds"] == [4, 4, 4]
    assert v["tt_sharded+ov"]["payload_shapes"] == [[4, 1, n]]
    assert v["tt_sharded+tb2"]["rounds"] == [16] * 6
    assert (v["tt_sharded+tb2"]["ppermutes_per_step"]
            == v["tt_sharded"]["ppermutes_per_step"])
    # Serving placement vs the placement plan.
    assert v["serve_panel+face"]["ppermutes_per_step"] == 12
    assert (v["serve_panel+face"]["payload_bytes_per_step"]
            == v["serve_panel+face"]["plan_payload_bytes_per_step"])
    assert v["serve_member+gspmd"]["plan_exchange_bytes_per_step"] \
        == 0.0
    assert v["serve_member+gspmd"]["compiled_collective_permutes"] == 0
    assert v["serve_member+gspmd"]["compiled_all_to_alls"] == 0
    # GSPMD: schedule is compiler-inferred, nothing explicit to drop.
    assert v["gspmd"]["ppermutes_per_call"] == 0
    assert v["gspmd+B2"]["ppermutes_per_call"] == 0


def test_schedule_fingerprints_consistent(full_run):
    """The traced schedules and the analytic plans pin the SAME
    canonical fingerprint — the cross-check that stops the plans and
    the compiled schedules from silently diverging."""
    _, result = full_run
    facts = result["facts"]
    fp = schedule_fingerprint()
    assert facts["schedule_fingerprint"] == fp
    v = facts["variants"]
    for name in ("face", "face+ov", "face+tb2", "face+B2",
                 "face+tb2+B2", "tt_sharded", "tt_sharded+ov"):
        assert v[name]["schedule_fingerprint"] == fp, name

    from jaxstream.utils.comm_probe import (batched_exchange_plan,
                                            temporal_block_plan)

    assert temporal_block_plan(facts["n"], facts["halo"], 2)[
        "schedule_fingerprint"] == fp
    assert batched_exchange_plan(facts["n"], facts["halo"], 2)[
        "schedule_fingerprint"] == fp


def test_proof_stamps_verified_across_the_space(full_run):
    """Every enumerated plan's built stepper carried a proof stamp,
    the stamp's verdict is 'verified' (the matrix covers its class),
    and exchange-tier stamps were cross-checked against the TRACED
    schedule (the proof_fingerprint fixture keeps that check loud)."""
    _, result = full_run
    passes = {(p["check"], p["subject"]) for p in result["passes"]}
    space = result["facts"]["plan_space"]["keys"]
    for key in space:
        assert ("proof.stamp_present", key) in passes, key
        assert ("proof.verdict", key) in passes, key
    # Exchange tiers got the traced-schedule cross-check.
    for key in ("face", "face+ov+tb2+B2", "tt_sharded",
                "serve_panel+face"):
        assert ("proof.stamp", key) in passes, key
        assert ("proof.schedule_fingerprint", key) in passes, key


def test_precision_policy_conformance(full_run):
    """Policy off => zero bf16 ops anywhere in the trace (no leak
    outside ops/pallas/precision.py regions); policy on => bf16
    present with f32 accumulators still dominant."""
    _, result = full_run
    v = result["facts"]["variants"]
    assert v["fused"]["bf16_ops"] == 0
    assert v["fused+bf16"]["bf16_ops"] > 0
    assert v["fused+bf16"]["f32_ops"] > v["fused+bf16"]["bf16_ops"]
    # Composition: temporal blocking scales both censuses together.
    assert v["fused+tb2+bf16"]["bf16_ops"] == \
        2 * v["fused+bf16"]["bf16_ops"]


def test_donation_overlap_and_callback_checks_ran(full_run):
    """The invariants beyond counting ran on the right subjects:
    donation aliasing proven both ways, overlap windows proven on the
    overlapped variants (and absence proven on serialized), no host
    callbacks in any segment loop."""
    _, result = full_run
    passes = {(p["check"], p["subject"]) for p in result["passes"]}
    assert ("jaxpr.donation_aliases",
            "jit_integrate(donate=True)") in passes
    assert ("jaxpr.no_donation",
            "jit_integrate(donate=False)") in passes
    assert ("jaxpr.overlap_windows", "face+ov") in passes
    assert ("jaxpr.serialized_schedule", "face") in passes
    assert ("jaxpr.overlap_windows", "face+ov+B2") in passes
    # The combo hand-listing missed: overlap windows proven through
    # the exact temporal fusion too.
    assert ("jaxpr.overlap_windows", "face+ov+tb2+B2") in passes
    for subject in ("segment_loop_face", "serve_panel+face",
                    "serve_member+gspmd", "serve_single+classic",
                    "serve_single+fused"):
        assert ("jaxpr.no_host_callbacks", subject) in passes
    assert ("jaxpr.member_parallel_zero_wire", "serve_member") in passes
