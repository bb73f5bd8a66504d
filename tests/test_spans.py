"""Program spans: the forecast and serving loops on the profiler's host
timeline and in the always-on ``SPANS`` table (``jaxstream.obs.trace``).

Tier-1 (check_tiers rules 11 and 14: non-slow, in-process):

  * a CPU ``jax.profiler`` capture of a C8 ``Simulation`` over three
    segments holds host events ``sim.run``, ``sim.segment``,
    ``sim.dispatch`` and ``sim.sync``, the last two inside the first;
  * the table leaves the compiling first segment out, counts it in
    ``sim.compiles``, and the flight ring's ``segment`` events carry
    the phase split (``dispatch_s``, ``sync_s``, ``boundary_s``,
    ``compiled``);
  * an untraced ``EnsembleServer`` run gives one ``serve.boundary`` per
    segment and one ``serve.ic_build`` per packed request, split by
    where it was built, and its writer thread's spans land in the table;
  * seeded requests of one family share one ``serve.ic_base`` build of
    its seed-free inputs, and two seeds give two heights over one wind;
  * the table's reservoir stays bounded, its median is exact, and
    concurrent writers lose no update.
"""

import glob
import threading

import numpy as np
import pytest

import jax

from jaxstream.config import load_config
from jaxstream.obs import flight
from jaxstream.obs import trace as obs_trace
from jaxstream.serve.request import ScenarioRequest
from jaxstream.serve.server import EnsembleServer
from jaxstream.simulation import Simulation
from jaxstream.utils import jax_compat

SIM_CFG = {"grid": {"n": 8}, "model": {"initial_condition": "tc5"},
           "time": {"dt": 600.0}, "parallelization": {"num_devices": 1}}


def _three_segments(sim):
    """Three run() calls of two steps each: one segment per call."""
    for n in (2, 4, 6):
        sim.run(n)


def _host_events(path):
    from jax.profiler import ProfileData

    files = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
    assert files, "the capture wrote no trace"
    pd = ProfileData.from_file(files[-1])
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if not p.name.startswith("/device:")
            for line in p.lines for e in line.events]


def test_simulation_spans_on_the_profiler_timeline(tmp_path):
    sim = Simulation(SIM_CFG)
    jax_compat.start_profiler_trace(str(tmp_path))
    try:
        _three_segments(sim)
    finally:
        jax_compat.stop_profiler_trace()
    events = _host_events(str(tmp_path))
    by_name = {}
    for name, a, b in events:
        by_name.setdefault(name, []).append((a, b))
    for name in ("sim.run", "sim.segment", "sim.dispatch", "sim.sync"):
        assert len(by_name.get(name, [])) == 3, (name, sorted(by_name))
    # Each segment holds its own dispatch and sync, in that order.
    for (s0, s1), (d0, d1), (y0, y1) in zip(
            sorted(by_name["sim.segment"]), sorted(by_name["sim.dispatch"]),
            sorted(by_name["sim.sync"])):
        assert s0 <= d0 <= d1 <= y0 <= y1 <= s1


def test_simulation_span_table_and_flight_phases():
    sim = Simulation(SIM_CFG)
    obs_trace.SPANS.clear()
    flight.RECORDER.clear()
    _three_segments(sim)
    table = obs_trace.SPANS.summary()
    spans, counters = table["spans"], table["counters"]
    assert counters[obs_trace.SIM_COMPILES] >= 1
    # The first segment compiled: the statistics hold the other two.
    for name in ("sim.segment", "sim.dispatch", "sim.sync",
                 "sim.boundary"):
        assert spans[name]["count"] == 2, name
    assert spans["sim.segment"]["recent_attrs"] == [{"steps": 2}] * 2
    assert spans["sim.run"]["count"] == 3
    seg = spans["sim.segment"]
    assert seg["max_s"] >= seg["median_s"] > 0
    assert seg["total_s"] == pytest.approx(sum(seg["recent_s"]))

    events, _, _ = flight.RECORDER.dump()
    segs = [e for e in events if e["type"] == "segment"]
    assert [e["step"] for e in segs] == [2, 4, 6]
    assert [e["compiled"] for e in segs] == [True, False, False]
    for e in segs:
        assert e["dispatch_s"] >= 0 and e["sync_s"] >= 0
    # The first dispatch has no sync before it; the others' boundaries
    # are the ones the table holds (rounded to the microsecond).
    assert segs[0]["boundary_s"] is None
    assert [e["boundary_s"] for e in segs[1:]] == pytest.approx(
        spans["sim.boundary"]["recent_s"], abs=1e-6)
    compiles = [e for e in events if e["type"] == "compile"]
    assert compiles and compiles[0]["step"] == 0
    assert compiles[0]["k"] == 2


def _serve_cfg():
    return load_config({
        "grid": {"n": 8}, "time": {"dt": 600.0},
        "model": {"name": "shallow_water_cov", "backend": "jnp"},
        "serve": {"buckets": "2", "segment_steps": 2,
                  "queue_capacity": 16, "fault_member": 1},
        "observability": {"fault_step": 2},
    })


def test_server_spans_untraced():
    """``serve.trace`` off: the spans are there all the same.  Member 1
    is evicted at step 2, a completion nobody predicted, so its slot
    refills with a build inside the boundary (``late``)."""
    obs_trace.SPANS.clear()
    srv = EnsembleServer(_serve_cfg())
    assert srv.config.serve.trace is False
    lengths = (2, 6, 4, 2, 4, 2)
    for i, n in enumerate(lengths):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc2", nsteps=n,
                                   outputs=("h",)))
    srv.serve()
    srv.close()
    statuses = sorted(r.status for r in srv.results.values())
    assert statuses == ["evicted"] + ["ok"] * (len(lengths) - 1)

    table = obs_trace.SPANS.summary()
    spans, counters = table["spans"], table["counters"]
    segments = srv.stats["segments"]
    for name in ("serve.dispatch", "serve.host_wait", "serve.boundary",
                 "serve.finalize", "serve.refill", "serve.observe"):
        assert spans[name]["count"] == segments, name
    assert spans["serve.pack"]["count"] == srv.stats["batches"]
    builds = spans["serve.ic_build"]
    assert builds["count"] == len(lengths)
    where = {w: counters.get(f"serve.ic_build.{w}", 0)
             for w in ("pack", "prefetch", "late")}
    assert sum(where.values()) == len(lengths)
    assert where["pack"] == 2 and where["late"] >= 1, where
    assert sorted(a["where"] for a in builds["recent_attrs"]) == sorted(
        w for w, n in where.items() for _ in range(n))
    # The result writer's spans: one fetch and one flush per request.
    assert spans["result.fetch"]["count"] == len(lengths)
    assert spans["writer.flush"]["count"] == len(lengths)


def test_ic_base_built_once_per_family():
    """Seeded tc5 requests at C16 share one ``serve.ic_base`` build of
    the family's seed-free inputs; each request still opens its own
    ``serve.ic_build``, and two seeds give two heights over one wind."""
    obs_trace.SPANS.clear()
    srv = EnsembleServer(load_config({
        "grid": {"n": 16}, "time": {"dt": 600.0},
        "model": {"name": "shallow_water_cov", "backend": "jnp"},
        "serve": {"buckets": "2", "segment_steps": 2,
                  "queue_capacity": 16, "group_by_orography": True},
    }))
    built = {}
    member_tree = srv._member_tree

    def record(req, **kw):
        built[req.id] = tree = member_tree(req, **kw)
        return tree

    srv._member_tree = record
    lengths = (2, 4, 2, 6, 2, 4)
    for i, n in enumerate(lengths):
        srv.submit(ScenarioRequest(id=f"r{i}", ic="tc5", nsteps=n,
                                   seed=10 + i, amplitude=1e-3))
    srv.serve()
    srv.close()
    assert sorted(r.status for r in srv.results.values()) == (
        ["ok"] * len(lengths))

    table = obs_trace.SPANS.summary()
    spans, counters = table["spans"], table["counters"]
    assert counters[obs_trace.IC_BASE] == 1
    assert spans[obs_trace.IC_BASE]["recent_attrs"] == [{"family": "tc5"}]
    assert spans[obs_trace.IC_BUILD]["count"] == len(lengths)
    where = [a["where"] for a in spans[obs_trace.IC_BUILD]["recent_attrs"]]
    assert where.count("pack") == 2
    assert set(where) <= {"pack", "prefetch", "late"}
    a, b = built["r0"], built["r1"]
    assert not np.array_equal(np.asarray(a["h"]), np.asarray(b["h"]))
    np.testing.assert_array_equal(np.asarray(a["u"]), np.asarray(b["u"]))


def test_reservoir_bounded_and_median_exact():
    table = obs_trace.SpanTable(reservoir=4)
    for v in (9.0, 1.0, 7.0, 3.0, 5.0, 2.0):
        table.add("x", v)
    table.count("c")
    table.count("c", 2)
    s = table.summary()
    x = s["spans"]["x"]
    assert x["count"] == 6
    assert x["total_s"] == 27.0 and x["max_s"] == 9.0
    assert x["recent_s"] == [7.0, 3.0, 5.0, 2.0]      # the newest four
    assert x["median_s"] == 4.0                        # (3 + 5) / 2
    assert s["counters"] == {"c": 3}
    table.add("y", 1.5, {"where": "late"})
    assert table.summary()["spans"]["y"]["recent_attrs"] == [
        {"where": "late"}]
    table.clear()
    assert table.summary() == {"spans": {}, "counters": {}}


def test_spans_from_other_threads_land_in_the_table():
    """Many writer threads at once: no lost count, every span kept."""
    import sys

    obs_trace.SPANS.clear()
    n_threads, per_thread = 8, 300

    def work():
        for _ in range(per_thread):
            with obs_trace.span("t.span", where="writer"):
                pass
            obs_trace.SPANS.count("t.count")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    s = obs_trace.SPANS.summary()
    total = n_threads * per_thread
    assert s["spans"]["t.span"]["count"] == total
    assert s["counters"]["t.count"] == total
    assert len(s["spans"]["t.span"]["recent_s"]) == min(
        total, obs_trace.RESERVOIR)


def test_span_stamps_keep_flag_and_stamp_form():
    obs_trace.SPANS.clear()
    assert not jax_compat.profiler_recording()
    with obs_trace.span("a", t0=1.0) as sp:
        pass
    assert sp.t0 == 1.0 and sp.seconds == pytest.approx(sp.t1 - 1.0)
    with obs_trace.span("b") as sp:
        sp.keep = False
    assert sp.seconds >= 0
    assert obs_trace.record_span("c", 2.0, 2.5, steps=3) == 0.5
    spans = obs_trace.SPANS.summary()["spans"]
    assert "b" not in spans
    assert spans["a"]["count"] == 1
    assert spans["c"]["recent_s"] == [0.5]
    assert spans["c"]["recent_attrs"] == [{"steps": 3}]
