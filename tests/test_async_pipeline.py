"""Async host pipeline acceptance (round-9 tentpole).

The criteria, as tests:
  * async-on vs async-off runs write BITWISE-identical outputs —
    every history-store file byte-compared, checkpoints compared
    through restore, telemetry records equal modulo the wall-clock
    fields — and end in bitwise-identical states;
  * the background writer's bounded queue blocks ``submit`` at the
    configured bound (backpressure — host memory stays ~2 segments);
  * a writer-task failure is fail-stop and surfaces on the main
    thread;
  * a guard breach under the async loop still flushes its sink
    records and postmortem checkpoint before the ``HealthError``
    propagates (reusing ``observability.fault_step``);
  * no live worker threads after ``Simulation.close()``.

This module imports ``jaxstream.io.async_pipeline`` and therefore must
stay tier-1 (scripts/check_tiers.py rule 4): no slow markers here.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from jaxstream.config import load_config
from jaxstream.io.async_pipeline import (WRITER_THREAD_NAME,
                                         BackgroundWriter, HostFetch,
                                         WriterFailed)
from jaxstream.io.checkpoint import CheckpointManager
from jaxstream.obs.monitor import HealthError
from jaxstream.obs.sink import read_records
from jaxstream.simulation import Simulation

#: Telemetry fields that legitimately differ run-to-run (wall clock).
_VOLATILE = ("wall_s", "steps_per_sec", "sim_days_per_sec_per_chip",
             "host_wait_s", "created_unix")


def _cfg(d, async_on, nsteps=6, hist=2, ckpt=3, interval=1, **over):
    cfg = {
        "grid": {"n": 12, "halo": 2, "dtype": "float64"},
        "model": {"initial_condition": "tc2"},
        "time": {"dt": 600.0, "nsteps": nsteps},
        "parallelization": {"num_devices": 1},
        "io": {"history_path": str(d / "hist"), "history_stride": hist,
               "checkpoint_path": str(d / "ckpt"),
               "checkpoint_stride": ckpt,
               "async_pipeline": {"enabled": async_on}},
        "observability": {"interval": interval,
                          "sink": str(d / "telemetry.jsonl"),
                          "guards": "warn"},
    }
    for k, v in over.items():
        cfg.setdefault(k, {}).update(v)
    return cfg


def _files(root):
    out = {}
    for dirpath, _, names in os.walk(str(root)):
        for f in names:
            p = os.path.join(dirpath, f)
            out[os.path.relpath(p, str(root))] = p
    return out


def _records_sans_timing(path):
    out = []
    for rec in read_records(path):        # validates every line
        rec = {k: v for k, v in rec.items() if k not in _VOLATILE}
        out.append(rec)
    return out


# ----------------------------------------------------------- file parity
def test_async_outputs_bitwise_match_sync(tmp_path):
    """The tentpole acceptance: unequal segment cadence (gcd(2,3)=1 ->
    six compiled segments, mixed history/checkpoint boundaries), then
    every written artifact compared against the synchronous path.

    Also asserts the backpressure unit on the async run: all of a
    boundary's writes ride ONE queued task, so ``max_pending_segments``
    really counts segments (one submit per 1-step segment here) — and
    the thread-hygiene criterion: the worker thread exists while the
    async simulation is live and is joined by ``close()`` (no leaked
    ``jaxstream-io-writer`` threads after the ``with`` block)."""
    ds, da = tmp_path / "sync", tmp_path / "async"
    ds.mkdir(), da.mkdir()
    sims = {}
    submits = []
    orig_submit = BackgroundWriter.submit

    def counting(self, fn, *a, **k):
        submits.append(fn)
        return orig_submit(self, fn, *a, **k)

    BackgroundWriter.submit = counting
    try:
        for d, async_on in ((ds, False), (da, True)):
            with Simulation(_cfg(d, async_on)) as sim:
                sim.run()
                sims[async_on] = sim
                if async_on:
                    assert any(t.name == WRITER_THREAD_NAME
                               for t in threading.enumerate())
    finally:
        BackgroundWriter.submit = orig_submit
    leaked = [t for t in threading.enumerate()
              if t.name == WRITER_THREAD_NAME and t.is_alive()]
    assert not leaked, f"writer threads leaked: {leaked}"
    # interval=1 -> every segment emits a record: exactly one composite
    # writer task per segment boundary, none from the sync run.
    assert len(submits) == 6, [getattr(f, "__name__", f) for f in submits]

    # Final state + time: bitwise.
    for k in sims[False].state:
        a = np.asarray(sims[False].state[k])
        b = np.asarray(sims[True].state[k])
        assert a.dtype == b.dtype
        assert np.array_equal(a, b), f"state {k} diverged under async"
    assert sims[False].t == sims[True].t

    # History store: every file, byte for byte (incl. the .geometry
    # sidecar and all zarr metadata).
    fs, fa = _files(ds / "hist"), _files(da / "hist")
    assert sorted(fs) == sorted(fa)
    for rel in fs:
        with open(fs[rel], "rb") as f1, open(fa[rel], "rb") as f2:
            assert f1.read() == f2.read(), f"history byte diff: {rel}"

    # Checkpoints: same steps, restored (state, t) bitwise.
    cs = CheckpointManager(str(ds / "ckpt"))
    ca = CheckpointManager(str(da / "ckpt"))
    assert cs.latest_step() == ca.latest_step() == 6
    for step in (3, 6):
        s1, t1 = cs.restore_host(step)
        s2, t2 = ca.restore_host(step)
        assert t1 == t2
        assert sorted(s1) == sorted(s2)
        for k in s1:
            assert np.array_equal(np.asarray(s1[k]), np.asarray(s2[k])), \
                f"checkpoint {step}/{k} diverged under async"

    # Telemetry: record-for-record equal once the wall-clock fields are
    # masked (values, drift, per-sample series, ordering — all exact).
    rs = _records_sans_timing(str(ds / "telemetry.jsonl"))
    ra = _records_sans_timing(str(da / "telemetry.jsonl"))
    assert rs == ra


def test_async_without_io_matches_sync(tmp_path):
    """async_pipeline.enabled with no IO configured at all is a plain
    (writerless) run and must not perturb the carry."""
    base = {"grid": {"n": 12, "halo": 2, "dtype": "float64"},
            "model": {"initial_condition": "tc2"},
            "time": {"dt": 600.0, "nsteps": 4},
            "parallelization": {"num_devices": 1}}
    ref = Simulation(dict(base))
    ref.run()
    cfg = dict(base)
    cfg["io"] = {"async_pipeline": {"enabled": True}}
    with Simulation(cfg) as sim:
        sim.run()
        assert sim._writer is None          # nothing to write -> no thread
    for k in ref.state:
        assert np.array_equal(np.asarray(ref.state[k]),
                              np.asarray(sim.state[k])), k
    assert ref.t == sim.t


def test_async_pipeline_config_from_yaml():
    cfg = load_config(
        "io:\n  history_stride: 2\n  async_pipeline:\n"
        "    enabled: true\n    max_pending_segments: 3\n")
    assert cfg.io.async_pipeline.enabled is True
    assert cfg.io.async_pipeline.max_pending_segments == 3
    # Default off, and unknown nested keys are rejected like any other —
    # with the nested section's OWN message (names the bad key and the
    # valid set), not a generic "expects a AsyncPipelineConfig" rewrap.
    assert load_config(None).io.async_pipeline.enabled is False
    with pytest.raises(ValueError, match=r"\['turbo'\].*enabled"):
        load_config("io:\n  async_pipeline:\n    turbo: yes\n")
    # A non-mapping value is the one shape the outer message is for.
    with pytest.raises(ValueError, match="AsyncPipelineConfig mapping"):
        load_config("io:\n  async_pipeline: 5\n")


# ------------------------------------------------------ writer semantics
def test_writer_backpressure_blocks_at_bound():
    """submit() must block once max_pending tasks are queued — the
    memory bound of the pipeline.  A gated first task holds the worker;
    the queue then absorbs exactly max_pending more submits before the
    next one stalls until the gate opens."""
    gate = threading.Event()
    done = []
    w = BackgroundWriter(max_pending=2)
    try:
        w.submit(gate.wait)                 # occupies the worker
        time.sleep(0.05)                    # let the worker pick it up
        w.submit(done.append, 1)            # queue slot 1
        w.submit(done.append, 2)            # queue slot 2 — at the bound

        t0 = time.perf_counter()
        blocked = {}

        def overflow():
            blocked["entered"] = time.perf_counter()
            w.submit(done.append, 3)        # must block until gate opens
            blocked["exited"] = time.perf_counter()

        th = threading.Thread(target=overflow)
        th.start()
        time.sleep(0.25)
        assert "entered" in blocked and "exited" not in blocked, \
            "submit beyond the bound did not block"
        gate.set()
        th.join(timeout=5.0)
        assert "exited" in blocked
        w.flush()
        assert done == [1, 2, 3]            # FIFO preserved throughout
        assert blocked["exited"] - t0 >= 0.25 - 0.05
    finally:
        gate.set()
        w.close()


def test_writer_failure_is_fail_stop_and_surfaces():
    """A failed task skips the rest of the queue (no frame k+1 after a
    torn frame k) and re-raises on the next main-thread call."""
    ran = []

    def boom():
        raise OSError("disk full")

    w = BackgroundWriter(max_pending=4)
    w.submit(boom)
    w.submit(ran.append, 1)                 # must be SKIPPED
    with pytest.raises(WriterFailed, match="disk full"):
        w.flush()
    assert ran == []
    w.submit(ran.append, 2)                 # writer recovers after raise
    w.flush()
    assert ran == [2]
    w.close()
    assert not w.alive


def test_writer_close_is_idempotent_and_drains():
    out = []
    w = BackgroundWriter(max_pending=2)
    w.submit(out.append, 1)
    w.submit(out.append, 2)
    w.close()
    w.close()
    assert out == [1, 2]
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(out.append, 3)


def test_host_fetch_resolves_device_and_plain_leaves():
    import jax.numpy as jnp

    tree = {"a": jnp.arange(4.0), "b": np.arange(3), "t": 1.5}
    f = HostFetch(tree)
    out = f.resolve()
    assert isinstance(out["a"], np.ndarray)
    np.testing.assert_array_equal(out["a"], np.arange(4.0))
    np.testing.assert_array_equal(out["b"], np.arange(3))
    assert float(out["t"]) == 1.5
    assert f.resolve() is out               # cached


# ------------------------------------------------- guard + thread hygiene
def test_async_guard_flushes_sink_and_postmortem(tmp_path):
    """observability.fault_step under the async loop: the HealthError
    still carries the last-good sample, the guard record is on disk
    (flush-on-exception), and the postmortem checkpoint landed —
    labelled with the latest *dispatched* step, since the pipeline runs
    a segment ahead of the resolve that trips the guard."""
    cfg = _cfg(tmp_path, True, nsteps=8, hist=0, ckpt=2, interval=2,
               observability={"interval": 2, "guards":
                              "checkpoint_and_raise", "fault_step": 4})
    sim = Simulation(cfg)
    with pytest.raises(HealthError) as ei:
        sim.run()
    sim.close()
    assert ei.value.kind == "nan"
    assert ei.value.step == 4
    assert ei.value.last_good_step == 2
    # The fault is stream-only: the state never went non-finite.
    assert np.all(np.isfinite(np.asarray(sim.state["h"])))
    guards = read_records(str(tmp_path / "telemetry.jsonl"), kind="guard")
    assert len(guards) == 1
    assert guards[0]["event"] == "nan"
    assert guards[0]["last_good_step"] == 2
    cm = CheckpointManager(str(tmp_path / "ckpt"))
    assert cm.latest_step() is not None
    assert cm.latest_step() >= ei.value.step    # ran ahead of the breach


def test_dispatch_failure_lands_pending_boundary(tmp_path):
    """A raise while segment k+1 is being dispatched must not drop
    boundary k's already-computed I/O: the sync path would have written
    it before dispatching, so the async unwind lands it too."""
    cfg = _cfg(tmp_path, True, nsteps=6, hist=0, ckpt=2, interval=2)
    sim = Simulation(cfg)
    fn2 = sim._segment_fn(2)
    calls = {"n": 0}

    def failing_fn(*a, **k):
        calls["n"] += 1
        if calls["n"] == 2:          # segment 2's dispatch dies
            raise RuntimeError("XLA dispatch failed")
        return fn2(*a, **k)

    failing_fn.obs_samples = fn2.obs_samples
    sim._segment_cache[2] = failing_fn
    with pytest.raises(RuntimeError, match="XLA dispatch failed"):
        sim.run()
    sim.close()
    # Boundary 1 (step 2) resolved during unwind: its checkpoint and
    # telemetry record are on disk.
    cm = CheckpointManager(str(tmp_path / "ckpt"))
    assert cm.latest_step() == 2
    segs = read_records(str(tmp_path / "telemetry.jsonl"), kind="segment")
    assert [s["step"] for s in segs if s["steps"] > 0] == [2]


def test_segment_records_carry_host_wait(tmp_path):
    """Both modes stamp host_wait_s on segment records (the overlap
    measurement the telemetry report surfaces)."""
    with Simulation(_cfg(tmp_path, True, nsteps=4, hist=2, ckpt=0,
                         interval=2)) as sim:
        sim.run()
    segs = read_records(str(tmp_path / "telemetry.jsonl"),
                        kind="segment")
    timed = [s for s in segs if s["steps"] > 0]
    assert timed
    for s in timed:
        assert "host_wait_s" in s
        assert s["host_wait_s"] >= 0.0


# ------------------------------------------------------- compile cache
_CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cache_config():
    """Restore jax's compile-cache settings (and the use latch) after
    a test moves them; the suite itself runs with the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {n: getattr(jax.config, n) for n in _CACHE_FLAGS}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    jax.clear_caches()


def test_compile_cache_writes_and_reloads(tmp_path, cache_config):
    """With the cache on, ``enable_compile_cache`` populates the
    directory, and a clear_caches+recompile round trip still works."""
    import jax.numpy as jnp

    from jaxstream.utils.jax_compat import enable_compile_cache

    d = str(tmp_path / "cc")
    jax.config.update("jax_enable_compilation_cache", True)
    enable_compile_cache(d)
    fn = jax.jit(lambda x: jnp.sin(x) * 2.0 + jnp.cos(x))
    x = jnp.arange(128.0)
    fn.lower(x).compile()
    assert os.listdir(d), "no persistent cache entries written"
    jax.clear_caches()
    np.testing.assert_allclose(
        np.asarray(fn(x)),
        np.sin(np.arange(128.0)) * 2.0 + np.cos(np.arange(128.0)),
        rtol=1e-6)


@pytest.mark.parametrize("outside", [False, True])
def test_compile_cache_placement(tmp_path, cache_config, outside):
    """The package places the cache: a directory set from outside
    (``JAX_COMPILATION_CACHE_DIR``, which jax reads into its config)
    is kept, and otherwise the fixed ``<repo>/.jax_cache`` is used —
    never a temp name, a pid or the time."""
    from jaxstream.utils.jax_compat import (default_compile_cache_dir,
                                            place_compile_cache)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert default_compile_cache_dir() == os.path.join(repo, ".jax_cache")
    want = str(tmp_path / "outside") if outside else None
    jax.config.update("jax_compilation_cache_dir", want)
    got = place_compile_cache()
    assert got == (want or default_compile_cache_dir())
    assert jax.config.jax_compilation_cache_dir == got
    # Placing twice is a no-op, and enablement is never touched.
    assert place_compile_cache() == got
    assert jax.config.jax_enable_compilation_cache is False
