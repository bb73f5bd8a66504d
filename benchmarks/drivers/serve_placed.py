"""Serving mix over every chip of the cell: ``drivers.serve``'s closed
loop (the same traffic keys, window, latency and reference check) on a
server whose bucket is member-placed, with the checked sample and three
readings adjusted to the chips.

* The sample compared with the reference: the first completed among the
  longest, as in ``drivers.serve``, and on every chip one member that
  came in by a refill, so that each chip's inject path (the upload to
  that chip and the inject there) is checked in every run.  On chip
  ``j`` it is the ``picks[j]``-th refilled completion, ``picks`` drawn
  from the seed below ``PICK_RANGE``, or the chip's last refilled
  completion where the window holds fewer.  A member of the first pack
  (the bucket's first ``B`` requests) came in by the stack, not by a
  refill.
* ``memory_peak_bytes`` is the fullest chip's, read on every chip of
  the cell at the window's close.
* ``layer["member_steps"]`` (the traced window's member-steps) is per
  chip, the host's count divided by the cell's ``chips``:
  ``trace_reduce.reduce`` averages device time over the chips, so
  ``step_mfu``, ``pallas_kernel_roofline`` and
  ``xla_glue_us_per_step`` read per-chip shares with their readers
  unchanged.
* ``end_to_end["sim_days_per_s"]`` stays the whole host's.

Notes add the checked members' chips and the window's host-to-device
bytes per inject (``serve.inject_bytes`` over ``serve.inject`` spans;
one member's ``h`` where the upload is not replicated).
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

import work
from drivers import common
from drivers.serve import (PICK_RANGE, control_answers, expand_deck,
                           make_request, p95, reference_readings,
                           serve_config)


def _inject_table():
    """``(serve.inject spans, serve.inject_bytes)`` of the program's
    span table so far."""
    from jaxstream.obs import trace

    t = trace.SPANS.summary()
    return (t["spans"].get("serve.inject", {}).get("count", 0),
            t["counters"].get("serve.inject_bytes", 0))


def run(ctx: dict) -> dict:
    import jax

    from jaxstream.serve import EnsembleServer
    from jaxstream.serve.queue import QueueFull

    spec, seed, seconds = ctx["spec"], ctx["seed"], ctx["seconds"]
    config, traffic = spec["config"], spec["traffic"]
    chips = int(spec["cell"]["chips"])
    cfg = serve_config(config)
    dt = float(cfg["time"]["dt"])
    n = int(cfg["grid"]["n"])
    B = max(int(b) for b in str(cfg["serve"]["buckets"]).split(","))
    deck = expand_deck(traffic["deck"], dt)
    amp = float(traffic["amplitude"])
    outputs = tuple(traffic["outputs"])

    lock = threading.Lock()
    st = {"closed": False, "t0": None, "t_close": None}
    done, failed, refused = [], [], []
    submitted = {}

    def submit(stream: int, k: int):
        req = make_request(deck, seed, stream, k, amp, outputs)
        submitted[req.id] = (stream, k, req, time.perf_counter())
        try:
            srv.submit(req)
        except QueueFull:
            refused.append(req.id)

    # Fields are kept only for the requests the check compares: the
    # first to complete among the longest so far, and per chip the
    # refilled completion at its seeded position (the latest one on
    # that chip until the position is reached).
    picks = common.rng(seed, 6).integers(0, PICK_RANGE, chips).tolist()
    refilled_seen = [0] * chips
    kept = {"long": None, "chip": [None] * chips}

    def held(res):
        return res is kept["long"] or any(res is r for r in kept["chip"])

    def drop(res):
        if res is not None and not held(res):
            res.fields = {}

    def keep(res, refilled):
        j = res.chip
        if refilled and refilled_seen[j] <= picks[j]:
            old, kept["chip"][j] = kept["chip"][j], res
            drop(old)
        refilled_seen[j] += refilled
        if kept["long"] is None or res.nsteps > kept["long"].nsteps:
            old, kept["long"] = kept["long"], res
            drop(old)
        drop(res)

    def on_result(res):
        t = time.perf_counter()
        with lock:
            if st["closed"]:
                res.fields = {}
                return
            stream, k, _, t_sub = submitted[res.id]
            if res.status == "ok":
                keep(res, refilled=not (k == 0 and stream < B))
                done.append((res, t - t_sub))
            else:
                failed.append(res)
            submit(stream, k + 1)

    t_build = time.perf_counter()
    srv = EnsembleServer(cfg, on_result=on_result)
    t_build = time.perf_counter() - t_build
    t_warm = time.perf_counter()
    groups = tuple(sorted({("oro" if ic == "tc5" else "flat")
                           for ic, _ in deck}))
    srv.warmup(groups=groups)
    plans = sorted({(p or {}).get("plan_key", "?")
                    for p in srv.bucket_proofs().values()})
    want = config["expect"]["serve_impl"]
    if not all(k.split("+")[1:2] == [want] for k in plans):
        raise RuntimeError(f"bucket plans {plans}: the {want!r} bucket "
                           "did not run")
    t_warm = time.perf_counter() - t_warm
    devices = jax.devices()[:chips]
    compiles_warm = srv.compile_count()

    tw = common.TraceWindow(ctx["trace"], seconds)
    counters = {}

    def snapshot():
        s = srv.stats
        return {"member_steps": s["member_steps"], "segments": s["segments"],
                "occupancy_sum": s["occupancy_sum"]}

    stop = threading.Event()

    def tick(server):
        t = time.perf_counter()
        elapsed = t - st["t0"]
        if tw.due(elapsed, seconds):
            tw.start()
            counters["trace_start"] = snapshot()
        if elapsed >= seconds and not st["closed"]:
            with lock:
                st["closed"] = True
                st["t_close"] = t
            counters["close"] = snapshot()
            counters["inject_close"] = _inject_table()
            tw.stop()
            while server.queue.pop() is not None:
                pass
            stop.set()

    t_setup = time.perf_counter() - ctx["t_start"]
    counters["inject_open"] = _inject_table()
    st["t0"] = time.perf_counter()
    counters["open"] = snapshot()
    for s in range(int(traffic["streams"])):
        submit(s, 0)
    srv.serve_forever(stop=stop, idle_wait=0.001, tick=tick)
    t_close = st["t_close"]
    window_s = t_close - st["t0"]
    peak = common.memory_peak_bytes(devices)
    compiles_window = srv.compile_count() - compiles_warm
    srv.close()

    with lock:
        ok_lat = [d for _, d in done]
        bad = list(failed)
        del done[:]
    lat = ok_lat + [float("inf")] * (len(bad) + len(refused))
    c0, c1 = counters["open"], counters["close"]
    bad_steps = sum(r.steps_run for r in bad)
    member_steps = c1["member_steps"] - c0["member_steps"] - bad_steps
    segs = max(1, c1["segments"] - c0["segments"])
    (i0, b0), (i1, b1) = counters["inject_open"], counters["inject_close"]
    injects, inject_bytes = i1 - i0, b1 - b0
    layer = {"device_kind": devices[0].device_kind,
             "work": work.member_step(n, nu4=float(
                 cfg.get("physics", {}).get("hyperdiffusion", 0.0))),
             "occupancy": (c1["occupancy_sum"] - c0["occupancy_sum"]) / segs}
    notes = {}
    if tw.done:
        ts = counters["trace_start"]
        notes["traced_member_steps"] = c1["member_steps"] - ts[
            "member_steps"]
        layer["member_steps"] = notes["traced_member_steps"] / chips
        tr = tw.reduce(segment_program="seg_body")
        if tr is not None:
            layer["breakdown"] = tr.pop("breakdown")
            layer["trace"] = tr

    sample = [kept["long"]] if kept["long"] is not None else []
    sample += [r for r in kept["chip"]
               if r is not None and r is not kept["long"]]
    del srv
    gc.collect()
    requests = {rid: v[2] for rid, v in submitted.items()}
    if ctx["control"]:
        control_answers(config, sample, requests)
    t_ref = time.perf_counter()
    readings = reference_readings(config, sample, requests)
    ref_s = time.perf_counter() - t_ref
    return {
        "end_to_end": {
            "sim_days_per_s": member_steps * dt / 86400.0 / window_s,
            "request_p95_s": p95(lat),
            "setup_s": t_setup},
        "readings": readings,
        "attempted": len(lat),
        "failed": len(bad) + len(refused),
        "memory_peak_bytes": peak,
        "layer": layer,
        "notes": dict(notes, setup_build_s=t_build, setup_warm_s=t_warm,
                      completed=len(ok_lat), window_s=window_s,
                      latency_median_s=(float(np.median(ok_lat))
                                        if ok_lat else None),
                      latency_samples=len(lat),
                      member_steps=member_steps,
                      compiles_in_window=compiles_window,
                      checked=[[r.id, r.nsteps, r.chip] for r in sample],
                      refilled_per_chip=refilled_seen,
                      inject_bytes_per_inject=(inject_bytes / injects
                                               if injects else None),
                      work_flops_per_byte=(layer["work"]["flops"]
                                           / layer["work"]["bytes"]),
                      reference_s=ref_s),
    }
