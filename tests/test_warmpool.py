"""Warm pools & the compile tax (jaxstream.serve.warmpool, round 21).

Acceptance criteria, all tier-1 (check_tiers rule 15 keeps this module
fast and in-process):

  * cache-key invalidation: a rules-version bump, a different plan
    key, a different deployment digest, or a different toolchain
    string (jax/jaxlib/backend/device count) each produce a DIFFERENT
    entry key — and a functional MISS, never a stale hit;
  * a truncated/corrupt entry is detected (sha256 + length), deleted,
    recorded as a typed ``corrupt`` event, and recompiled — never a
    crash, never a silent wrong answer;
  * a restarted server loads its warm pool: the second server performs
    ZERO XLA compiles and its first-segment results are BYTE-equal to
    the cold server's;
  * the compile_cache rung engages where configured and never moves a
    cache that ``JAX_COMPILATION_CACHE_DIR`` placed;
  * resize() and the speculative compiler REFUSE a scale-up whose
    stamped advisory ``headroom_frac`` breaches
    ``serve.min_headroom_frac`` (typed ``headroom`` record); the
    autoscale controller reverts its level on refusal instead of
    hammering the refused target.

Configs are tiny (C8, jnp backend) like tests/test_serve.py.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jaxstream.serve import (EnsembleServer, HeadroomRefused,
                             ScenarioRequest)
from jaxstream.serve.warmpool import (WarmPool, deployment_digest,
                                      entry_key)

N, DT = 8, 600.0

_ENV = {"jax": "0.9.0", "jaxlib": "0.9.0", "backend": "cpu",
        "device_count": 1}


def _cfg(pool="", **over):
    cfg = {
        "grid": {"n": N},
        "time": {"dt": DT},
        "model": {"name": "shallow_water_cov", "backend": "jnp"},
        "parallelization": {"num_devices": 1},
        "serve": {"buckets": "1", "segment_steps": 2,
                  "queue_capacity": 8, "warm_pool": pool},
    }
    for k, v in over.items():
        cfg.setdefault(k, {}).update(v)
    return cfg


# ----------------------------------------------------------- cache key
def test_entry_key_invalidation():
    """Every identity axis the docstring names must move the key:
    plan, proof fingerprint, rules version, deployment digest, fn
    name, and each toolchain field.  Same inputs -> same key."""
    base = dict(plan_key="serve/B2", proof_fingerprint="abc",
                rules_version=2, deploy_digest="d" * 16, fn="seg",
                environment=dict(_ENV))
    k0 = entry_key(**base)
    assert k0 == entry_key(**base)          # deterministic
    variants = [
        dict(base, plan_key="serve/B4"),
        dict(base, proof_fingerprint="def"),
        dict(base, proof_fingerprint=None),
        dict(base, rules_version=3),        # rule-table bump voids all
        dict(base, deploy_digest="e" * 16),
        dict(base, fn="extract"),
        dict(base, environment=dict(_ENV, jax="0.9.1")),
        dict(base, environment=dict(_ENV, jaxlib="0.9.1")),
        dict(base, environment=dict(_ENV, backend="tpu")),
        dict(base, environment=dict(_ENV, device_count=8)),
    ]
    keys = [entry_key(**v) for v in variants]
    assert k0 not in keys
    assert len(set(keys)) == len(keys)      # all pairwise distinct


def test_deployment_digest_moves_with_physics(tmp_path):
    """Two deployments differing in a field the plan key does NOT
    carry (dt here) must digest differently — a stale hit across them
    would be wrong physics, not a slow path."""
    from jaxstream.config import load_config

    a = load_config(_cfg())
    b = load_config(_cfg(time={"dt": 2 * DT}))
    assert deployment_digest(a) == deployment_digest(a)
    assert deployment_digest(a) != deployment_digest(b)


# ------------------------------------------------- pool load/save/torn
def _pool(tmp_path, **kw):
    recs = []
    kw.setdefault("sink_write", recs.append)
    return WarmPool(str(tmp_path / "pool"), **kw), recs


def _compiled_doubler():
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.arange(8.0)
    return fn, fn.lower(x).compile(), x


def test_pool_roundtrip_and_key_miss(tmp_path):
    pool, recs = _pool(tmp_path)
    fn, compiled, x = _compiled_doubler()
    key = entry_key("p/B1", "fp", 2, "d" * 16, "seg",
                    environment=_ENV)
    rung = pool.save(key, fn, compiled, (x,), plan_key="p/B1")
    assert rung in ("aot", "stablehlo")
    warm = pool.load(key, plan_key="p/B1")
    assert warm is not None and warm.rung == rung
    np.testing.assert_array_equal(np.asarray(warm(x)),
                                  np.asarray(x) * 2.0 + 1.0)
    if rung == "aot":
        # The zero-compile proof: a pool-loaded AOT executable
        # reports zero compiles through the compile_count surface.
        assert warm._cache_size() == 0
    # A rules-version bump is a clean MISS (reason 'absent'), never a
    # stale hit of the voided entry.
    bumped = entry_key("p/B1", "fp", 3, "d" * 16, "seg",
                       environment=_ENV)
    assert pool.load(bumped, plan_key="p/B1") is None
    # ... and so is a foreign jaxlib string.
    foreign = entry_key("p/B1", "fp", 2, "d" * 16, "seg",
                        environment=dict(_ENV, jaxlib="9.9.9"))
    assert pool.load(foreign, plan_key="p/B1") is None
    events = [(r["event"], r["rung"]) for r in recs]
    assert ("save", rung) in events
    assert ("hit", rung) in events
    assert events.count(("miss", "cold")) == 2
    assert pool.stats["hits"] == 1 and pool.stats["misses"] == 2
    # Every typed record is sink-schema-valid.
    from jaxstream.obs.sink import validate_record

    for r in recs:
        validate_record(r)


def test_torn_entry_detected_deleted_recompiled(tmp_path):
    """A payload that is short, digest-mismatched or missing is a torn
    entry: loud typed ``corrupt`` record, both files deleted, and the
    load reports a miss so the caller recompiles."""
    pool, recs = _pool(tmp_path)
    fn, compiled, x = _compiled_doubler()
    key = entry_key("p/B1", "fp", 2, "d" * 16, "seg",
                    environment=_ENV)
    assert pool.save(key, fn, compiled, (x,)) is not None
    ppath = pool._payload_path(key)
    with open(ppath, "rb") as fh:
        payload = fh.read()
    with open(ppath, "wb") as fh:
        fh.write(payload[: len(payload) // 2])      # truncate
    assert pool.load(key) is None
    assert pool.stats["corrupt"] == 1
    assert not os.path.exists(ppath)
    assert not os.path.exists(pool._meta_path(key))
    events = [r["event"] for r in recs]
    assert "corrupt" in events
    reasons = [r.get("reason") for r in recs if r["event"] == "miss"]
    assert "corrupt" in reasons
    # The slot is reusable: a fresh save + load round-trips again.
    assert pool.save(key, fn, compiled, (x,)) is not None
    assert pool.load(key) is not None
    # Meta pointing at a MISSING payload is the same torn path.
    os.unlink(ppath)
    assert pool.load(key) is None
    assert pool.stats["corrupt"] == 2


@pytest.fixture
def restore_cache_config():
    """Put jax's compile-cache settings back after a test moves them."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("outside", [False, True])
def test_compile_cache_rung_keeps_outside_dir(tmp_path, monkeypatch,
                                              restore_cache_config,
                                              outside):
    """``serve.compile_cache`` engages the rung directly, and a
    ``JAX_COMPILATION_CACHE_DIR`` set from outside keeps the cache
    where it is — the rung never moves it."""
    outside_dir = str(tmp_path / "outside")
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    pool, recs = _pool(tmp_path, compile_cache=str(tmp_path / "cc"))
    assert pool.enable_compile_cache() and pool.enable_compile_cache()
    want = outside_dir if outside else str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == want
    assert pool.summary()["compile_cache"] == want
    assert os.path.isdir(want)
    # A pool with no compile_cache leaves the rung off.
    bare, _ = _pool(tmp_path / "bare")
    assert not bare.enable_compile_cache()


# ------------------------------------------------- server warm restart
def test_server_warm_restart_zero_compiles_byte_equal(tmp_path):
    """The tentpole's parity gates, in-process: a second server on the
    same config + pool directory loads every executable (zero XLA
    compiles) and its results are byte-equal to the cold server's.
    Two configured buckets, and the warm pass builds BOTH — the proof
    plan key does not encode the bucket, so this is also the
    regression test for a B=2 lookup stale-hitting the B=1 entry."""
    pool_dir = str(tmp_path / "pool")

    def run(sink):
        cfg = _cfg(pool=pool_dir, serve={"buckets": "1,2",
                                         "sink": sink})
        srv = EnsembleServer(cfg)
        srv.submit(ScenarioRequest(id="r0", ic="tc2", nsteps=2))
        res = srv.serve()
        h = np.asarray(res["r0"].fields["h"])
        # Force the second bucket warm too: with colliding keys this
        # dies on the executable's shape check instead of compiling.
        srv._bucket("any", 2)
        count, summary = srv.compile_count(), srv.warmpool_summary()
        srv.close()
        return h, count, summary

    h_cold, _, s_cold = run(str(tmp_path / "a.jsonl"))
    assert s_cold["saves"] >= 6 and s_cold["hits"] == 0
    h_warm, warm_compiles, s_warm = run(str(tmp_path / "b.jsonl"))
    assert warm_compiles == 0           # the zero-compile proof
    assert s_warm["hits"] >= 6 and s_warm["corrupt"] == 0
    assert h_cold.tobytes() == h_warm.tobytes()
    # The warm server's sink carries schema-valid typed records.
    from jaxstream.obs.sink import read_records

    recs = read_records(str(tmp_path / "b.jsonl"))
    assert any(r["kind"] == "warmpool" and r["event"] == "hit"
               for r in recs)


# ------------------------------------------------- headroom enforcement
def _stamp_low_headroom(srv, bucket, frac=0.05):
    """Inject a stamped plan whose advisory headroom is ``frac`` (the
    round-19 stamp the real path writes from memory_analysis)."""
    plan = srv._plans[bucket]
    srv._plans[bucket] = plan.with_headroom(100.0 * (1.0 - frac),
                                            100.0)
    return srv._plans[bucket]


def test_resize_refuses_stamped_headroom_breach(tmp_path):
    sink = str(tmp_path / "s.jsonl")
    srv = EnsembleServer(_cfg(serve={
        "buckets": "1,2", "sink": sink, "min_headroom_frac": 0.2}))
    try:
        # Unstamped plans are NEVER refused (advisory stays advisory).
        srv.resize(1, reason="test")
        assert srv.resize(2, reason="test") == 1
        srv.resize(1, reason="test")
        stamped = _stamp_low_headroom(srv, 2, frac=0.05)
        assert stamped.headroom_frac == pytest.approx(0.05)
        with pytest.raises(HeadroomRefused, match="min_headroom_frac"):
            srv.resize(2, reason="test")
        # Scale-DOWN under the same stamp is never refused.
        assert srv.resize(1, reason="test") == 1
    finally:
        srv.close()
    from jaxstream.obs.sink import read_records

    recs = read_records(sink)
    refusals = [r for r in recs if r["kind"] == "headroom"]
    assert len(refusals) == 1
    assert refusals[0]["action"] == "resize_refused"
    assert refusals[0]["bucket"] == 2
    assert refusals[0]["headroom_frac"] == pytest.approx(0.05)


def test_autoscale_reverts_level_on_refusal():
    """The controller must not believe a resize the server refused:
    the level reverts, the event is marked refused, and the fresh
    cooldown stops it hammering the refused target every tick."""
    from jaxstream.loadgen.autoscale import (AutoscaleController,
                                             AutoscalePolicy)

    class _Stub:
        buckets = (1, 2)
        queue = [None] * 8
        stats = {"last_occupancy": 1.0}

        def __init__(self):
            self.resizes = 0

        def resize(self, target, **kw):
            self.resizes += 1
            raise HeadroomRefused(f"bucket {target} refused")

    ctrl = AutoscaleController(AutoscalePolicy(
        levels=(1, 2), patience=1, cooldown=2))
    stub = _Stub()
    assert ctrl(stub) is None
    assert stub.resizes == 1
    assert ctrl.state.level == 0            # reverted
    assert ctrl.events[-1]["refused"] is True
    assert ctrl.events[-1]["to_bucket"] == 2
    # Cooldown holds: the next two ticks do not retry the resize.
    assert ctrl(stub) is None and ctrl(stub) is None
    assert stub.resizes == 1


# --------------------------------------------------------- speculation
def test_speculate_requires_warm_pool():
    with pytest.raises(ValueError, match="warm_pool"):
        EnsembleServer(_cfg(serve={"speculate": True}))


def test_speculator_builds_adjacent_and_respects_headroom(tmp_path):
    """The speculative compiler warms the adjacent bucket through the
    server's own build path (so the pool gets the entry), and skips a
    headroom-refused target with the same typed record resize writes."""
    sink = str(tmp_path / "s.jsonl")
    srv = EnsembleServer(_cfg(pool=str(tmp_path / "pool"), serve={
        "buckets": "1,2", "sink": sink, "speculate": True,
        "min_headroom_frac": 0.2}))
    try:
        sp = srv._speculator
        assert sp is not None
        # A stamped breach is SKIPPED with the typed record...
        _stamp_low_headroom(srv, 2, frac=0.05)
        sp._build(2)
        assert sp.built == [] and len(sp.skipped) == 1
        assert ("any", 2) not in srv._buckets
        # ... and clearing the stamp lets the build through.
        srv._plans[2] = srv._plans[2].with_headroom(None, None)
        sp._build(2)
        assert ("any", 2) in sp.built
        assert ("any", 2) in srv._buckets
        summary = srv.warmpool_summary()
        assert summary["speculative_built"] == [["any", 2]]
        assert summary["speculative_skipped"] == 1
        # nudge() targets exactly the configured neighbors of the cap
        # (worker stopped first so the target list is inspectable
        # without racing the drain).
        sp.close()
        sp.nudge(1)
        with sp._lock:
            assert sp._targets == [2]
        sp.nudge(7)                         # not a configured bucket
        with sp._lock:
            assert sp._targets == [2]       # unchanged
    finally:
        srv.close()
    from jaxstream.obs.sink import read_records

    recs = read_records(sink)
    refusals = [r for r in recs if r["kind"] == "headroom"]
    assert [r["action"] for r in refusals] == ["speculate_refused"]
