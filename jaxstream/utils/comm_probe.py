"""Per-stage cube-edge exchange latency probes (library half).

The race-free 4-stage schedule puts FOUR sequential ``ppermute``s on
every SSPRK3 stage's critical path; the overlapped-exchange redesign
(``parallelization.overlap_exchange``) exists to hide exactly that
chain under the interior RHS kernel.  These probes make the chain —
and the win — measurable, per stage:

  * :func:`probe_exchange` — for each of the 4 schedule stages, a
    jitted shard_map program chains ``iters`` back-to-back
    ``ppermute``s of a real-sized ``(3, halo, n)`` payload (each hop
    depends on the last, so wall time / iters is the per-stage
    wire+dispatch latency — the same methodology as a ping-pong
    NCCL/ICI probe), plus the production 4-stage exchange (rotation +
    seam symmetrization included) chained the same way.
  * :func:`probe_step_rates` — steady-state steps/s of the explicit
    covariant face stepper, serialized vs overlapped schedule.

Consumed by ``scripts/comm_probe.py`` (the CLI), ``bench.py``'s
multichip section, and the driver's MULTICHIP dryrun gate.  On CPU the
numbers characterize dispatch/copy structure, not ICI — the probes'
reason to exist is running unchanged on a real slice.
"""

from __future__ import annotations

from .profiling import median_chain_seconds

__all__ = ["temporal_block_plan", "batched_exchange_plan",
           "serve_placement_plan", "probe_exchange", "probe_step_rates",
           "run_default_probe", "format_report"]

#: ppermutes per SSPRK3 step of the serialized face-tier exchange:
#: 4 race-free schedule stages x 3 RK stages.
SERIALIZED_PPERMUTES_PER_STEP = 12


def temporal_block_plan(n: int, halo: int, temporal_block: int,
                        rk_stages: int = 3,
                        strip_dtype_bytes: int = 4) -> dict:
    """Static exchange/compute accounting of temporal halo blocking.

    Pure arithmetic — no devices, no jax — shared by the CLI report,
    ``bench.py``'s JSON, and the non-slow schedule test.  For a k-step
    block on the one-face-per-device tier the deep halo width is
    ``D = rk_stages * k * halo`` (each RK stage consumes ``halo`` of
    ghost validity) and stage ``i`` (0-based, of ``rk_stages*k``)
    computes an ``(n + 2*(D - (i+1)*halo))^2`` window:

    * ``ppermutes_per_step``: 4 schedule stages once per block / k
      steps, vs the serialized 12 per step.
    * ``payload_elems_per_step``: per-edge payload elements shipped per
      simulated step each way (3 fields x D-deep x n strips once per
      block) — equal to the serialized path's by construction (the k
      exchanges collapse, they don't shrink).
    * ``redundant_compute_fraction``: extra RHS cell-evaluations vs the
      k=1 path, averaged over the block's ``rk_stages*k`` windows —
      ``mean_i ((n + 2*(D - (i+1)h))^2 - n^2) / n^2``; the first-stage
      (worst) term is ``((n + 2*(D - h))^2 - n^2) / n^2``, bounded by
      the docs' headline ``((n + 2kh)^2 - n^2) / n^2`` with ``k``
      counting exchange-free RHS evaluations (``rk_stages *
      temporal_block``).

    ``strip_dtype_bytes`` (round 10): bytes per exchanged strip element
    — 4 (f32, the default) or 2 when the strips ride a 16-bit precision
    policy (``jaxstream.ops.pallas.precision.strip_dtype_bytes``).
    Sets ``payload_bytes_per_step`` and the reported
    ``wire_bytes_saving_vs_f32`` fraction; element counts are
    dtype-independent.

    ``schedule_fingerprint`` (round 13): the canonical digest of the
    4-stage race-free schedule this accounting assumes
    (:func:`jaxstream.geometry.connectivity.schedule_fingerprint`).
    ``jaxstream.analysis`` recomputes the fingerprint from the traced
    steppers' actual ``ppermute`` perms and cross-checks it against
    this field, so the analytic plan and the compiled schedule can
    never silently diverge.
    """
    from ..geometry.connectivity import schedule_fingerprint

    if temporal_block < 1:
        raise ValueError(
            f"temporal_block must be >= 1, got {temporal_block}")
    k = temporal_block
    D = rk_stages * k * halo
    stages = rk_stages * k
    windows = [n + 2 * (D - (i + 1) * halo) for i in range(stages)]
    redundant = [(w * w - n * n) / float(n * n) for w in windows]
    from ..plan.rules import RULES_VERSION

    return {
        "temporal_block": k,
        "schedule_fingerprint": schedule_fingerprint(),
        "rules_version": RULES_VERSION,
        "deep_halo_width": D,
        "fits": n >= D,
        "ppermutes_per_step": 4.0 / k,
        "serialized_ppermutes_per_step": float(
            SERIALIZED_PPERMUTES_PER_STEP),
        "exchange_latency_ratio": (4.0 / k)
            / SERIALIZED_PPERMUTES_PER_STEP,
        "payload_elems_per_step": 3 * D * n * 4 / k,
        "strip_dtype_bytes": strip_dtype_bytes,
        "payload_bytes_per_step": 3 * D * n * 4 * strip_dtype_bytes / k,
        "wire_bytes_saving_vs_f32": 1.0 - strip_dtype_bytes / 4.0,
        "redundant_compute_fraction": sum(redundant) / stages,
        "redundant_compute_fraction_first_stage": redundant[0],
    }


def batched_exchange_plan(n: int, halo: int, members: int,
                          rk_stages: int = 3,
                          dtype_bytes: int = 4) -> dict:
    """Static exchange accounting of the batched ensemble exchange.

    Pure arithmetic — no devices, no jax — the batched-exchange twin of
    :func:`temporal_block_plan`, shared by the CLI report, bench.py's
    ensemble section, and the non-slow plumbing test.  A B-member
    ensemble step on the face tier issues the SAME 12 ppermutes per
    step as a single member (4 schedule stages x ``rk_stages``) with
    every payload stacked ``(B, 3, halo, n)``; a per-member loop would
    issue ``12 * B``.  Per-member wire bytes are unchanged by
    construction — stacking amortizes collective LAUNCH latency, it
    does not compress anything.

    Keys: ``ppermutes_per_step`` (whole ensemble), ``ppermutes_per_
    member_step`` (12/B), ``serialized_ppermutes_per_member_step`` (12),
    ``launch_latency_ratio`` (1/B), ``payload_bytes_per_ppermute``
    (each way, per edge), ``wire_bytes_per_member_step`` (invariant
    in B).  ``dtype_bytes=2`` is the 16-bit-strips policy
    (round 10) — payload and wire bytes halve; the saving fraction is
    reported as ``wire_bytes_saving_vs_f32``.
    ``schedule_fingerprint`` (round 13): the canonical schedule digest
    the analyzer cross-checks against traced ppermute perms (see
    :func:`temporal_block_plan`).
    """
    from ..geometry.connectivity import schedule_fingerprint

    if members < 1:
        raise ValueError(f"members must be >= 1, got {members}")
    if halo < 1 or n < 1:
        raise ValueError(f"need n >= 1 and halo >= 1, got n={n}, "
                         f"halo={halo}")
    from ..plan.rules import RULES_VERSION

    B = members
    per_step = 4 * rk_stages
    payload = B * 3 * halo * n * dtype_bytes
    return {
        "members": B,
        "schedule_fingerprint": schedule_fingerprint(),
        "rules_version": RULES_VERSION,
        "ppermutes_per_step": float(per_step),
        "ppermutes_per_member_step": per_step / B,
        "serialized_ppermutes_per_member_step": float(per_step),
        "launch_latency_ratio": 1.0 / B,
        "payload_bytes_per_ppermute": payload,
        "wire_bytes_per_member_step": per_step * 3 * halo * n
            * dtype_bytes,
        "strip_dtype_bytes": dtype_bytes,
        "wire_bytes_saving_vs_f32": 1.0 - dtype_bytes / 4.0,
    }


def serve_placement_plan(buckets, num_devices: int, n: int,
                         halo: int = 2, dtype_bytes: int = 4) -> dict:
    """Static serving-placement accounting (round 12) — the
    ``comm_probe --serve`` report body.

    Pure arithmetic — no devices, no jax — a thin wrap of
    :func:`jaxstream.serve.placement.placement_report`: for each
    placement mode (member-parallel / panel-sharded), per batch-size
    bucket, the resolved device split and the halo-exchange bytes per
    step it would put on the wire (member mode: ZERO — members never
    communicate; panel mode: the face tier's 12 ppermutes/step at the
    batched-exchange payload).  ``dtype_bytes=2`` re-bills a 16-bit
    strips policy, like the other plans.
    The panel accounting assumes the canonical race-free schedule; its
    ``schedule_fingerprint`` is the analyzer's cross-check hook
    (round 13, see :func:`temporal_block_plan`).
    """
    from ..geometry.connectivity import schedule_fingerprint
    from ..serve.placement import placement_report

    from ..plan.rules import RULES_VERSION

    out = placement_report(buckets, num_devices, n, halo,
                           dtype_bytes=dtype_bytes)
    out["schedule_fingerprint"] = schedule_fingerprint()
    out["rules_version"] = RULES_VERSION
    return out


def run_default_probe(iters: int = 100, steps: int = 30, n: int = 0,
                      temporal_block: int = 0, members: int = 0,
                      devices=None, plan_only: bool = False,
                      strip_dtype_bytes: int = 4):
    """Full probe suite with the shared device/size policy.

    The one place the selection lives (CLI, bench multichip, dryrun
    gate all call through here): the DEFAULT platform's devices when at
    least 6 exist (a real slice measures real ICI), else 6 virtual CPU
    devices (structural dispatch-level numbers, platform-tagged in the
    report); face size ``n`` defaults to a production-ish 96 on real
    accelerators and 16 on the CPU smoke.  Returns the result dict
    (``n``, ``devices``, ``platform``, stage/exchange latencies, step
    rates, and — when ``temporal_block > 1`` — the blocked-vs-serialized
    rates plus the :func:`temporal_block_plan` accounting).

    ``devices``: explicit device list overriding the policy (tests pass
    fakes with a ``platform`` attribute).  ``plan_only=True`` stops
    after the device/size/schedule selection — everything that needs no
    compilation — so the plumbing is testable in milliseconds.

    ``strip_dtype_bytes``: bytes per exchanged strip element for the
    PLAN accounting (2 under a 16-bit strips policy — CLI
    ``--strip-dtype bf16``).  The measured latencies always ship f32
    strips: the sharded steppers run f32 numerics (the 16-bit wire is
    the single-device fused path's policy), so the plans report the
    savings a 16-bit exchange WOULD bank, explicitly tagged.
    """
    from ..geometry.connectivity import build_connectivity, build_schedule

    if devices is None:
        import jax

        devices = jax.devices()
    device_type = "default" if len(devices) >= 6 else "cpu"
    platform = (getattr(devices[0], "platform", "cpu")
                if device_type == "default" else "cpu")
    n = n or (96 if platform != "cpu" else 16)
    halo = 2
    result = {"n": n, "devices": 6, "platform": platform}
    result["schedule_stages"] = len(build_schedule(build_connectivity()))
    if temporal_block > 1:
        result["temporal_block_plan"] = temporal_block_plan(
            n, halo, temporal_block,
            strip_dtype_bytes=strip_dtype_bytes)
    if members > 1:
        result["batched_exchange_plan"] = batched_exchange_plan(
            n, halo, members, dtype_bytes=strip_dtype_bytes)
    if plan_only:
        return result

    import jax.numpy as jnp

    from ..config import EARTH_RADIUS
    from ..geometry.cubed_sphere import build_grid
    from ..parallel.mesh import setup_sharding

    setup = setup_sharding({"parallelization": {
        "num_devices": 6, "device_type": device_type,
        "use_shard_map": True}})
    platform = setup.mesh.devices.flat[0].platform
    result["platform"] = platform
    grid = build_grid(n, halo=halo, radius=EARTH_RADIUS, dtype=jnp.float32)
    result.update(probe_exchange(grid, setup.mesh, iters=iters))
    result.update(probe_step_rates(grid, setup, steps=steps,
                                   temporal_block=temporal_block,
                                   members=members))
    return result


def probe_exchange(grid, mesh, iters: int = 100):
    """Per-stage + full-exchange latency on a ``(panel=6,1,1)`` mesh.

    Returns ``{"stage_us": [4 floats], "exchange_us": float}`` —
    median microseconds per chained iteration.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.shard_cov import (CovShardProgram,
                                      make_cov_shard_exchange)
    from jax import shard_map

    n, halo, m = grid.n, grid.halo, grid.m
    program = CovShardProgram(grid)
    axis = program.axis_name
    axes = mesh.axis_names
    sh = NamedSharding(mesh, P(axes[0]))

    stage_us = []
    for s, perm in enumerate(program.perms):
        def chain(x, _perm=perm):
            for _ in range(iters):
                x = lax.ppermute(x, axis, _perm)
            return x

        fn = jax.jit(shard_map(
            chain, mesh=mesh, in_specs=P(axes[0]), out_specs=P(axes[0]),
            check_vma=False))
        x = jax.device_put(jnp.zeros((6, 3, halo, n), jnp.float32), sh)
        stage_us.append(1e6 * median_chain_seconds(fn, (x,), iters))

    # Full production exchange (ghost writes + rotations + seam sym),
    # chained through its own output so each iteration depends on the
    # last.
    exchange = make_cov_shard_exchange(program)
    tables = {k: jax.device_put(v, sh) for k, v in program.tables.items()}
    ex_iters = max(1, iters // 10)

    def chain_ex(h_blk, u_blk, t):
        for _ in range(ex_iters):
            h_blk, u_blk, ssn, swe = exchange(h_blk, u_blk, t)
            h_blk = h_blk + ssn[:, :1, :1]
        return h_blk

    fn = jax.jit(shard_map(
        chain_ex, mesh=mesh,
        in_specs=(P(axes[0]), P(None, axes[0]),
                  {k: P(axes[0]) for k in tables}),
        out_specs=P(axes[0]), check_vma=False))
    h_blk = jax.device_put(jnp.zeros((6, m, m), jnp.float32), sh)
    u_blk = jax.device_put(jnp.zeros((2, 6, m, m), jnp.float32),
                           NamedSharding(mesh, P(None, axes[0])))
    ex_us = 1e6 * median_chain_seconds(
        fn, (h_blk, u_blk, tables), ex_iters)
    return {"stage_us": [round(u, 2) for u in stage_us],
            "exchange_us": round(ex_us, 2)}


def probe_step_rates(grid, setup, dt: float = 300.0, steps: int = 50,
                     temporal_block: int = 0, members: int = 0):
    """Steady-state steps/s of the explicit covariant face stepper,
    serialized vs overlapped.  Returns ``{"serialized_steps_per_sec",
    "overlap_steps_per_sec", "overlap_speedup"}`` — plus, when
    ``temporal_block = k > 1`` fits the grid, the deep-halo blocked
    stepper's rate (``temporal_block_steps_per_sec`` counts SIMULATED
    steps: blocks/s x k) and its speedup over the serialized path, and,
    when ``members = B > 1``, the batched ensemble stepper's rate
    (``ensemble_member_steps_per_sec`` counts MEMBER-steps: calls/s x B
    — one call advances every member) with its per-member speedup over
    the serialized single-member path."""
    import jax
    import jax.numpy as jnp

    from ..config import EARTH_GRAVITY, EARTH_OMEGA
    from ..models.shallow_water_cov import CovariantShallowWater
    from ..parallel.mesh import shard_state
    from ..parallel.shard_cov import make_sharded_cov_stepper
    from ..physics.initial_conditions import williamson_tc2

    h_ext, v_ext = williamson_tc2(grid, EARTH_GRAVITY, EARTH_OMEGA)
    model = CovariantShallowWater(grid, gravity=EARTH_GRAVITY,
                                  omega=EARTH_OMEGA)
    ss = shard_state(setup, model.initial_state(h_ext, v_ext))

    variants = [("serialized", dict(overlap=False)),
                ("overlap", dict(overlap=True))]
    k = temporal_block
    with_blocked = k > 1 and grid.n >= 3 * k * grid.halo
    if with_blocked:
        variants.append(("temporal_block", dict(temporal_block=k)))

    rates = {}
    for key, kw in variants:
        step = make_sharded_cov_stepper(model, setup, dt, **kw)
        spc = getattr(step, "steps_per_call", 1)
        ncalls = max(1, steps // spc)

        # fori_loop, not a Python-unrolled window: the step traces ONCE
        # however long the window (at the real-slice configuration an
        # unrolled 50-step program is hundreds of kernels/ppermutes and
        # can take minutes to compile); the carry dependency preserves
        # the chained-latency methodology.
        @jax.jit
        def run(y, _step=step, _ncalls=ncalls):
            return jax.lax.fori_loop(
                0, _ncalls, lambda i, yy: _step(yy, jnp.float32(0.0)), y)

        sec = median_chain_seconds(run, (ss,), ncalls * spc, reps=3)
        rates[f"{key}_steps_per_sec"] = round(1.0 / sec, 2)
    rates["overlap_speedup"] = round(
        rates["overlap_steps_per_sec"]
        / rates["serialized_steps_per_sec"], 4)
    if with_blocked:
        rates["temporal_block_speedup"] = round(
            rates["temporal_block_steps_per_sec"]
            / rates["serialized_steps_per_sec"], 4)
    elif k > 1:
        rates["temporal_block_skipped"] = (
            f"n={grid.n} < 3*k*halo={3 * k * grid.halo}")

    B = members
    if B > 1:
        from ..parallel.shard_cov import make_sharded_cov_ensemble_stepper

        estep = make_sharded_cov_ensemble_stepper(model, setup, dt, B)
        ssb = {"h": jnp.stack([ss["h"]] * B),
               "u": jnp.stack([ss["u"]] * B, axis=1)}
        from ..parallel.mesh import shard_ensemble_state

        ssb = shard_ensemble_state(setup, ssb)
        ncalls = max(1, steps // 4)

        @jax.jit
        def runb(y):
            return jax.lax.fori_loop(
                0, ncalls, lambda i, yy: estep(yy, jnp.float32(0.0)), y)

        sec = median_chain_seconds(runb, (ssb,), ncalls, reps=3)
        rates["ensemble_members"] = B
        # One call advances every member: member-steps/s = B / call sec.
        rates["ensemble_member_steps_per_sec"] = round(B / sec, 2)
        rates["ensemble_per_member_speedup"] = round(
            (B / sec) / rates["serialized_steps_per_sec"], 4)
    return rates


def format_report(result: dict) -> str:
    """One human-readable line per measurement (CI-log friendly)."""
    plat = result.get("platform")
    tag = f" [{plat}]" if plat else ""
    lines = []
    st = result.get("stage_us")
    if st:
        lines.append(f"comm_probe{tag}: per-stage exchange latency "
                     + "  ".join(f"stage{i}={u:.1f}us"
                                 for i, u in enumerate(st))
                     + f"  full-exchange={result['exchange_us']:.1f}us")
    if "serialized_steps_per_sec" in result:
        line = (
            f"comm_probe{tag}: steps/s "
            f"serialized={result['serialized_steps_per_sec']:.1f} "
            f"overlap={result['overlap_steps_per_sec']:.1f} "
            f"(x{result['overlap_speedup']:.3f})")
        if "temporal_block_steps_per_sec" in result:
            line += (
                f" temporal_block="
                f"{result['temporal_block_steps_per_sec']:.1f} "
                f"(x{result['temporal_block_speedup']:.3f})")
        lines.append(line)
    if "ensemble_member_steps_per_sec" in result:
        lines.append(
            f"comm_probe{tag}: ensemble B={result['ensemble_members']} "
            f"member-steps/s="
            f"{result['ensemble_member_steps_per_sec']:.1f} "
            f"(x{result['ensemble_per_member_speedup']:.3f} per member "
            f"vs serialized)")
    be = result.get("batched_exchange_plan")
    if be:
        lines.append(
            f"comm_probe{tag}: batched exchange B={be['members']} "
            f"ppermutes/member-step="
            f"{be['ppermutes_per_member_step']:.2f} "
            f"(vs {be['serialized_ppermutes_per_member_step']:.0f}) "
            f"payload/ppermute={be['payload_bytes_per_ppermute']} B "
            f"wire/member-step={be['wire_bytes_per_member_step']} B"
            + (f" (16-bit strips: -"
               f"{100 * be['wire_bytes_saving_vs_f32']:.0f}% wire)"
               if be.get("wire_bytes_saving_vs_f32") else "")
            + (f" sched={be['schedule_fingerprint']}"
               if be.get("schedule_fingerprint") else "")
            + (f" rules=v{be['rules_version']}"
               if be.get("rules_version") else ""))
    sp = result.get("serve_placement_plan")
    if sp:
        if sp.get("schedule_fingerprint"):
            lines.append(
                f"comm_probe{tag}: serve placement panel exchange "
                f"assumes the canonical race-free schedule "
                f"sched={sp['schedule_fingerprint']}")
        for mode, info in sp["modes"].items():
            if "skipped" in info:
                lines.append(
                    f"comm_probe{tag}: serve placement {mode} on "
                    f"{sp['num_devices']} devices: skipped "
                    f"({info['skipped']})")
                continue
            for row in info["buckets"]:
                lines.append(
                    f"comm_probe{tag}: serve placement {mode} B="
                    f"{row['bucket']}: {row['mode']} on "
                    f"{row['devices']} device(s) "
                    f"({row['panel_shards']}x{row['member_shards']} "
                    f"mesh, {row['members_per_shard']} members/shard) "
                    f"exchange/step={row['exchange_bytes_per_step']:.0f} B")
    tb = result.get("temporal_block_plan")
    if tb:
        lines.append(
            f"comm_probe{tag}: temporal_block k={tb['temporal_block']} "
            f"deep_halo={tb['deep_halo_width']} "
            f"exchanges/step={tb['ppermutes_per_step']:.2f} "
            f"(vs {tb['serialized_ppermutes_per_step']:.0f}) "
            f"redundant_compute="
            f"{tb['redundant_compute_fraction']:.3f}"
            f" (first stage "
            f"{tb['redundant_compute_fraction_first_stage']:.3f})"
            + (f" payload/step={tb['payload_bytes_per_step']:.0f} B "
               f"(16-bit strips: -"
               f"{100 * tb['wire_bytes_saving_vs_f32']:.0f}% wire)"
               if tb.get("wire_bytes_saving_vs_f32") else "")
            + (f" sched={tb['schedule_fingerprint']}"
               if tb.get("schedule_fingerprint") else "")
            + (f" rules=v{tb['rules_version']}"
               if tb.get("rules_version") else ""))
    return "\n".join(lines)
