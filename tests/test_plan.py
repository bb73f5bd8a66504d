"""Capability plans (round 16, tier-1).

Four proof surfaces of the config -> plan -> stepper pipeline:

* **Rejection parity** — every pointer message the legacy factories
  (``make_stepper_for``, ``make_fused_step``,
  ``Simulation._resolve_precision``, the serving layer) used to carry
  is now raised from the ONE rule table, and fires *statically* from
  ``plan_for(config)`` — pure config arithmetic, before any grid
  build, device placement or trace.
* **The enumerated space** — ``enumerate_plans`` walks the rule table
  and emits at least the 16 previously hand-listed variants (plus the
  combos hand-listing missed), deterministically.
* **Generated parity assertions** — for every executable dense plan
  in the space, the plan's own declared budget
  (:meth:`CapabilityPlan.parity`) is asserted against its reference
  plan through the one shared builder — no hand-written per-pair
  parity list.
* **Proof stamps** — steppers built by Simulation / the dispatcher /
  the fused factory carry verified stamps; plans outside the
  enumerated axes say so loudly instead of claiming coverage.

Rule 10 of ``scripts/check_tiers.py`` keeps this module non-slow and
in-process by construction.
"""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from jaxstream.config import load_config
from jaxstream.plan import (CapabilityPlan, PlanError, RULES_VERSION,
                            build_proof, enumerate_plans, plan_for,
                            plan_space_keys)
from jaxstream.plan.build import PlanContext, build_stepper
from jaxstream.plan.rules import check_plan, normalize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


# ---------------------------------------------------------------------
# Resolution + enumeration
# ---------------------------------------------------------------------

def test_ic_family_mirror_matches_simulation():
    from jaxstream.simulation import IC_FAMILY

    p = plan_for({"model": {"initial_condition": "tc1"}})
    assert p.family == IC_FAMILY["tc1"] == "advection"
    assert plan_for({"model": {"initial_condition": "galewsky"}}
                    ).family == "shallow_water"


def test_tier_resolution_mirrors_simulation_dispatch():
    cov = {"name": "shallow_water_cov"}
    assert plan_for({"model": cov,
                     "parallelization": {"num_devices": 1}}
                    ).tier == "classic"
    assert plan_for({"model": dict(cov, backend="pallas"),
                     "parallelization": {"num_devices": 1}}
                    ).tier == "fused"
    assert plan_for({"model": cov,
                     "parallelization": {"num_devices": 6,
                                         "use_shard_map": True}}
                    ).tier == "face"
    assert plan_for({"model": cov,
                     "parallelization": {"num_devices": 6}}
                    ).tier == "gspmd"
    assert plan_for({"model": {"numerics": "tt"},
                     "parallelization": {"num_devices": 1}}
                    ).tier == "tt"
    # Cartesian model under shard_map = the scalar-exchange path.
    assert plan_for({"model": {"name": "auto"},
                     "parallelization": {"num_devices": 6,
                                         "use_shard_map": True}}
                    ).tier == "cartesian_shard"


def test_enumerated_space_covers_the_hand_list_and_more():
    plans = enumerate_plans()
    keys = [p.key() for p in plans]
    assert len(keys) == len(set(keys))          # no duplicates
    assert len(plans) >= 16
    # The 16 previously hand-listed variants, under plan keys.
    legacy = {"face", "face+ov", "face+tb2", "face+ov+tb2", "face+B2",
              "face+ov+B2", "face+tb2+B2", "tt_sharded",
              "tt_sharded+ov", "gspmd", "fused", "fused+bf16",
              "fused+tb2+bf16", "serve_panel+face",
              "serve_member+gspmd"}
    assert legacy <= set(keys)
    # Combos the hand list missed are in the walk.
    assert {"face+ov+tb2+B2", "fused+tb2", "gspmd+B2",
            "serve_single+fused"} <= set(keys)
    # Every emitted plan is canonical and rule-clean by construction.
    for p in plans:
        assert normalize(p) == p, p.key()
        assert check_plan(p) == [], p.key()
    # Deterministic: a second walk is identical.
    assert [p.key() for p in enumerate_plans()] == keys


def test_enumeration_prunes_illegal_and_noncanonical():
    keys = plan_space_keys()
    # bf16 never escapes the fused tier into the class-key space...
    assert not any("face" in k and "bf16" in k for k in keys)
    # ...and inert overlap flags normalize away (no fused+ov class).
    assert not any(k.startswith("fused") and "ov" in k.split("+")
                   for k in keys)


# ---------------------------------------------------------------------
# Rejection parity: the legacy pointers, statically from plan_for
# ---------------------------------------------------------------------

_COV = {"name": "shallow_water_cov"}

REJECTIONS = [
    # (config, pointer-match) — one per legacy ValueError whose prose
    # moved into the rule table.
    ({"precision": {"stage": "bf16"},
      "model": _COV,
      "parallelization": {"num_devices": 6, "use_shard_map": True}},
     r"comm_probe\.py --strip-dtype"),
    ({"precision": {"stage": "bf16"}, "model": _COV,
      "parallelization": {"num_devices": 1}},
     r"single-device fused covariant stepper"),
    ({"precision": {"carry": "mixed16"},
      "model": dict(_COV, backend="pallas"),
      "ensemble": {"members": 2},
      "parallelization": {"num_devices": 1}},
     r"members: 1"),
    ({"precision": {"carry": "mixed16"},
      "model": {"backend": "pallas"},
      "parallelization": {"num_devices": 1}},
     r"covariant dense model"),
    ({"precision": {"stage": "bf16"}, "model": {"backend": "pallas"},
      "parallelization": {"num_devices": 1}},
     r"compact-carry fused stepper"),
    ({"model": _COV, "time": {"scheme": "euler"},
      "parallelization": {"num_devices": 6, "use_shard_map": True}},
     r"ssprk3 only"),
    ({"model": _COV, "ensemble": {"members": 2},
      "parallelization": {"num_devices": 24, "use_shard_map": True,
                          "tiles_per_edge": 2}},
     r"tiles_per_edge: 1"),
    ({"model": {"name": "auto"}, "ensemble": {"members": 2},
      "parallelization": {"num_devices": 6, "use_shard_map": True}},
     r"use_shard_map: false"),
    ({"model": {"name": "auto"},
      "parallelization": {"num_devices": 6, "use_shard_map": True,
                          "temporal_block": 2}},
     r"steps serially"),
    ({"model": {"numerics": "tt"},
      "parallelization": {"num_devices": 2}},
     r"6-device"),
    ({"model": {"numerics": "tt"},
      "physics": {"hyperdiffusion": 1e14},
      "parallelization": {"num_devices": 1}},
     r"hyperdiffusion: 0"),
    ({"model": {"numerics": "tt"}, "ensemble": {"members": 2},
      "parallelization": {"num_devices": 1}},
     r"dense tier only"),
    ({"model": {"numerics": "tt"},
      "observability": {"interval": 4},
      "parallelization": {"num_devices": 1}},
     r"numerics: dense"),
    ({"model": _COV, "observability": {"interval": 3},
      "parallelization": {"num_devices": 6, "use_shard_map": True,
                          "temporal_block": 2}},
     r"multiple of"),
    ({"model": {"initial_condition": "tc1"},
      "ensemble": {"members": 2},
      "parallelization": {"num_devices": 1}},
     r"shallow-water"),
    # Review hardening: the stage oracle rejects ANY active stage
    # policy, including a strips-only one (make_fused_step keys the
    # raise off the resolved policy being non-None, not stage alone).
    ({"model": {"name": "shallow_water_cov", "backend": "pallas",
                "nu4_mode": "stage"},
      "physics": {"hyperdiffusion": 1e14},
      "precision": {"stage": "f32", "strips": "bf16"},
      "parallelization": {"num_devices": 1}},
     r"parity oracle"),
    # Review hardening: nu4_mode != 'split' on ANY non-fused tier is a
    # static rejection too (Simulation's fused-or-raise would fire at
    # build time — the plan layer must not certify it first).
    ({"model": {"name": "shallow_water_cov", "nu4_mode": "stage"},
      "physics": {"hyperdiffusion": 1e14},
      "parallelization": {"num_devices": 6, "use_shard_map": True}},
     r"single-device fused covariant stepper"),
]

SERVE_REJECTIONS = [
    # Review hardening: a malformed bucket list is a static rejection
    # too, with the server's message (not a silent B=1 fallback).
    ({"model": _COV, "serve": {"buckets": "4,abc"}},
     r"comma-separated list"),
    ({"model": {"numerics": "tt"}}, r"dense"),
    ({"model": {"name": "auto"}}, r"shallow_water_cov"),
    ({"model": _COV, "precision": {"stage": "bf16"}},
     r"f32 numerics"),
    ({"model": _COV, "parallelization": {"temporal_block": 2}},
     r"temporal_block"),
    ({"model": _COV, "parallelization": {"use_shard_map": True}},
     r"use_shard_map"),
    ({"model": dict(_COV, backend="pallas"),
      "serve": {"placement": {"mode": "member"}}},
     r"model\.backend: jnp"),
    ({"model": _COV, "serve": {"placement": {"mode": "panel"}}},
     r"group_by_orography"),
]


@pytest.mark.parametrize("cfg,match", REJECTIONS)
def test_rejection_parity_static(cfg, match):
    """The pair fails BEFORE trace time: plan_for is pure config
    arithmetic (no grid, no devices), and the pointer survives."""
    with pytest.raises(ValueError, match=match):
        plan_for(cfg)


@pytest.mark.parametrize("cfg,match", SERVE_REJECTIONS)
def test_serve_rejection_parity_static(cfg, match):
    with pytest.raises(ValueError, match=match):
        plan_for(cfg, serving=True)


def test_rejections_are_plan_errors_with_rule_names():
    with pytest.raises(PlanError) as ei:
        plan_for({"precision": {"stage": "bf16"}, "model": _COV,
                  "parallelization": {"num_devices": 6,
                                      "use_shard_map": True}})
    assert ei.value.violations[0].rule == "stage-policy-needs-fused"
    assert ei.value.plan is not None
    assert ei.value.plan.key() == "face+bf16"


def test_factory_raises_come_from_the_same_table():
    """Direct factory calls raise the SAME table pointers (the prose
    cannot drift between plan_for and the build path)."""
    from jaxstream.config import (EARTH_GRAVITY, EARTH_OMEGA,
                                  EARTH_RADIUS)
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater
    from jaxstream.parallel.sharded_model import make_stepper_for

    with pytest.raises(PlanError, match="comm_probe.py --strip-dtype"):
        make_stepper_for(None, None, {}, 60.0, precision="bf16")

    grid = build_grid(8, halo=2, radius=EARTH_RADIUS,
                      dtype=jnp.float32)
    nu4 = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                omega=EARTH_OMEGA, nu4=1e12,
                                backend="pallas_interpret")
    with pytest.raises(PlanError, match="nu4 = 0 only"):
        nu4.make_fused_step(60.0, ensemble=2)
    with pytest.raises(PlanError, match="parity oracle"):
        nu4.make_fused_step(60.0, nu4_mode="stage", precision="bf16")
    with pytest.raises(PlanError, match="not supported on the nu4"):
        nu4.make_fused_step(60.0, carry_dtype=jnp.bfloat16)
    # Round-16 tightening (deliberate, review-hardened): the batched
    # carry has no encoding plumbing — the pair is rejected explicitly
    # with the same rule plan_for rejects the config with.
    clean = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA,
                                  backend="pallas_interpret")
    with pytest.raises(PlanError, match="members: 1"):
        clean.make_fused_step(60.0, ensemble=2,
                              carry_dtype=jnp.bfloat16)


# ---------------------------------------------------------------------
# Proof stamps
# ---------------------------------------------------------------------

def test_proof_stamp_fields_and_coverage():
    plan = plan_for({"model": _COV,
                     "parallelization": {"num_devices": 6,
                                         "use_shard_map": True}})
    stamp = build_proof(plan)
    assert stamp.verdict == "verified"
    assert stamp.jaxpr_audit == "matrix"
    assert stamp.rules_version == RULES_VERSION
    from jaxstream.geometry.connectivity import schedule_fingerprint

    assert stamp.schedule_fingerprint == schedule_fingerprint()
    # A legal plan OUTSIDE the enumerated axes says so loudly.
    exotic = normalize(CapabilityPlan(
        tier="fused", backend="pallas", covariant=True,
        carry="mixed16"))
    assert build_proof(exotic).verdict == "rules_only"
    assert build_proof(exotic).jaxpr_audit == "uncovered"
    # Review hardening: a strips-only 16-bit policy is its own program
    # class — the key must not collapse onto plain f32 coverage.
    strips_only = plan_for({"model": dict(_COV, backend="pallas"),
                            "precision": {"stage": "f32",
                                          "strips": "bf16"},
                            "parallelization": {"num_devices": 1}})
    assert strips_only.key() == "fused+strips_bf16"
    assert build_proof(strips_only).verdict == "rules_only"
    # Representative axis values stand for the class: B=16, k=4 map
    # onto the same verified class keys as B=2, k=2.
    big = normalize(CapabilityPlan(tier="face", ensemble=16,
                                   temporal_block=4, num_devices=6,
                                   use_shard_map=True))
    assert build_proof(big).verdict == "verified"
    assert big.class_key() in plan_space_keys()
    # Schedule-only tiers (the 24-device block mesh) are stamped as
    # schedule-verified, never as matrix-covered.
    block = normalize(CapabilityPlan(tier="face_block", num_devices=24,
                                     use_shard_map=True,
                                     tiles_per_edge=2))
    assert build_proof(block).verdict == "schedule_verified"


def test_simulation_carries_verified_proof():
    from jaxstream.simulation import Simulation

    sim = Simulation({"grid": {"n": 8}, "time": {"dt": 60.0},
                      "parallelization": {"num_devices": 1}})
    assert sim.plan.tier == "classic"
    assert sim.proof.verdict == "verified"
    assert sim.proof.rules_version == RULES_VERSION
    # The dispatcher-built stepper itself is stamped too.
    assert getattr(sim._step, "proof").plan_key == "classic"


def test_fused_factory_stamps_its_steppers():
    from jaxstream.config import (EARTH_GRAVITY, EARTH_OMEGA,
                                  EARTH_RADIUS)
    from jaxstream.geometry.cubed_sphere import build_grid
    from jaxstream.models.shallow_water_cov import CovariantShallowWater

    grid = build_grid(8, halo=2, radius=EARTH_RADIUS,
                      dtype=jnp.float32)
    m = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                              omega=EARTH_OMEGA,
                              backend="pallas_interpret")
    step = m.make_fused_step(60.0, temporal_block=2)
    assert step.proof.plan_key == "fused+tb2"
    assert step.proof.verdict == "verified"
    assert step.steps_per_call == 2       # attrs survive stamping


# ---------------------------------------------------------------------
# Generated parity assertions over the enumerated space
# ---------------------------------------------------------------------

def _leaves(plan, out):
    """(h, u) numpy leaves of one plan's output, member 0 for batched
    plans (identical-member batch => member 0 is THE trajectory)."""
    if plan.ensemble > 1:
        return (np.asarray(out["h"][0]), np.asarray(out["u"][:, 0]))
    return (np.asarray(out["h"]), np.asarray(out["u"]))


def test_generated_parity_over_enumerated_space():
    """B=1 bitwise / declared-budget parity assertions GENERATED over
    the enumerated space: each executable dense plan runs one block
    through the shared builder and lands within the tolerance its own
    plan declares, against the reference plan the plan itself names.
    (Fused-tier and TT runtime parities keep their dedicated feature
    modules — interpret-mode execution is priced out of this loop;
    their *structural* contracts ride the analysis matrix.)"""
    ctx = PlanContext(n=12, halo=2, dt=300.0)
    all_plans = [p for p in enumerate_plans(n=12)
                 if not p.serving and p.tier in ("face", "gspmd",
                                                 "classic")]
    by_key = {p.key(): p for p in enumerate_plans(n=12)}
    assert len(all_plans) >= 12

    # Execution subset (gate economy: every run here is a real XLA
    # compile): per tier, each SINGLE-knob plan plus the MAXIMAL combo
    # — the singles pin each knob's own budget, the maximal combo pins
    # their composition; the middle combos' structural contracts ride
    # the analysis matrix.  The subset is derived from the space, not
    # hand-listed.
    def knobs(p):
        return int(p.overlap) + int(p.temporal_block > 1) \
            + int(p.ensemble > 1)

    max_knobs = {t: max(knobs(p) for p in all_plans if p.tier == t)
                 for t in {p.tier for p in all_plans}}
    plans = [p for p in all_plans
             if knobs(p) <= 1 or knobs(p) == max_knobs[p.tier]]
    outputs = {}
    builds = {}        # plan key -> BuiltStepper (ONE compile each)

    def run_steps(plan, steps):
        built = builds.get(plan.key())
        if built is None:
            built = builds[plan.key()] = build_stepper(plan, ctx)
        y, t = built.example
        calls = steps // built.steps_per_call
        assert calls * built.steps_per_call == steps
        for _ in range(calls):
            y = built.step(y, t)
            t = t + ctx.dt * built.steps_per_call
        return y

    checked = 0
    for plan in plans:
        par = plan.parity()
        if par["reference"] is None:
            continue                       # the tier's base plan
        ref = by_key[par["reference"]]
        steps = plan.temporal_block
        got = _leaves(plan, run_steps(plan, steps))
        key = (ref.key(), steps)
        if key not in outputs:
            outputs[key] = _leaves(ref, run_steps(ref, steps))
        want = outputs[key]
        for g, w in zip(got, want):
            if par["budget"] == 0.0:
                assert np.array_equal(g, w), (plan.key(), "bitwise")
            else:
                rel = (np.max(np.abs(g - w))
                       / max(np.max(np.abs(w)), 1e-30))
                assert rel <= par["budget"], (plan.key(), rel)
        checked += 1
    # The generated surface really covered the knob space: every
    # single-knob plan and every tier's maximal combo ran.
    assert checked >= 8


# ---------------------------------------------------------------------
# Satellites: did-you-mean config errors, the plan CLI
# ---------------------------------------------------------------------

def test_unknown_key_did_you_mean():
    with pytest.raises(ValueError,
                       match=r"did you mean 'stage'"):
        load_config("precision:\n  stag: bf16\n")
    with pytest.raises(ValueError,
                       match=r"did you mean 'temporal_block'"):
        load_config("parallelization:\n  temporal_blocks: 2\n")
    with pytest.raises(ValueError,
                       match=r"did you mean 'precision'"):
        load_config("precison:\n  stage: bf16\n")
    # No near-miss => the plain error, no bogus suggestion.
    with pytest.raises(ValueError) as ei:
        load_config("grid:\n  zzqq: 1\n")
    assert "did you mean" not in str(ei.value)


def test_plan_cli_explain_and_enumerate(capsys):
    import json

    import plan as plan_cli

    code = plan_cli.main(
        ["explain", "model: {name: shallow_water_cov}", "--json"])
    rec = json.loads(capsys.readouterr().out.strip())
    assert code == 0
    assert rec["ok"] is True
    assert rec["plan"]["key"] == rec["proof"]["plan_key"]
    assert rec["proof"]["verdict"] == "verified"

    bad = ("precision: {stage: bf16}\n"
           "parallelization: {num_devices: 6, use_shard_map: true}\n"
           "model: {name: shallow_water_cov}")
    code = plan_cli.main(["explain", bad, "--json"])
    rec = json.loads(capsys.readouterr().out.strip())
    assert code == 2
    assert rec["ok"] is False
    assert rec["violations"][0]["rule"] == "stage-policy-needs-fused"

    code = plan_cli.main(["--enumerate", "--json"])
    rec = json.loads(capsys.readouterr().out.strip())
    assert code == 0
    assert rec["size"] >= 16
    assert rec["rules_version"] == RULES_VERSION
    assert "face+ov+tb2+B2" in rec["keys"]


def test_serve_plans_resolve():
    p = plan_for({"model": _COV, "serve": {"buckets": "1,4"}},
                 serving=True)
    assert p.serving and p.placement == "off"
    assert p.key() == "serve_single+classic"
    # Grouped + pallas resolves to the fused member-fold bucket —
    # mirroring EnsembleServer._impl_for, so scripts/plan.py explain
    # --serve names the plan the deployment's telemetry will log.
    pf = plan_for({"model": dict(_COV, backend="pallas_interpret"),
                   "serve": {"group_by_orography": True}},
                  serving=True)
    assert pf.key() == "serve_single+fused"
    pm = plan_for({"model": _COV,
                   "serve": {"placement": {"mode": "member"}}},
                  serving=True)
    assert pm.key() == "serve_member+gspmd"
