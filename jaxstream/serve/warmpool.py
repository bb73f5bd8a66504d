"""Disk-backed warm pools: kill the compile tax (round 21).

Every scale-up event used to pay a multi-second jit compile — PR 5
measured 22.4 s cold vs 5.7 s warm, and the round-19 ``compile_seconds``
cost stamps measure it per plan.  This module converts that telemetry
into millisecond scale-up: each bucket's compiled masked-segment
executable (plus its extract/inject companions) is persisted to disk so
a restarted — or freshly spawned — :class:`~.server.EnsembleServer`
*loads* its warm pool instead of recompiling.

**Cache key.**  An entry is only reusable when the program AND the
environment match, so the key digests all of:

* the bucket's capability **plan key** (grid, tier, scheme, B,
  placement — ``jaxstream.plan``) and **proof fingerprint** (the
  canonical exchange-schedule digest; ``None`` hashes as such),
* the **rules version** the proof was minted against — a rule-table
  bump voids every stamp, so it must void every cached executable too,
* a **deployment digest** over the config fields the plan key does NOT
  carry (dt, segment steps, nu4, gravity, dtype, donation, grouping —
  a stale hit across any of these would be silently wrong *results*,
  not just a slow path),
* **jax + jaxlib version strings, backend platform, device count** —
  a serialized executable is an artifact of one exact toolchain.

**Degradation ladder** (:meth:`WarmPool.load` / :meth:`WarmPool.save`),
each rung a typed sink record, never a silent fallback:

1. ``aot`` — full compiled-executable serialization
   (``jax_compat.serialize_executable``): a load performs ZERO XLA
   compiles (the parity gate's ``compile_count`` proof).
2. ``stablehlo`` — ``jax.export`` StableHLO bytes: a load re-runs the
   backend compile but skips trace + lower.
3. ``compile_cache`` — jax's persistent compilation cache pointed at
   ``serve.compile_cache``, or kept where ``JAX_COMPILATION_CACHE_DIR``
   put it when that is set.  (Until PR 21 a subprocess probe gated this
   rung against an older jaxlib's segfault on cross-process cache
   reads; on jax 0.9 a cross-process warm run of the TT/serve/async/
   ensemble test mix read 550 cache entries cleanly, so the probe
   went.)
4. ``cold`` — plain jit compile (today's behavior).

**Atomicity.**  Entries commit in the PR-20 flight-recorder style:
payload bytes land via tmp + ``os.replace``; the small meta JSON —
naming the payload sha256 and byte length — is written LAST, so a
reader either sees a complete entry or no entry.  A meta that points
at missing/short/digest-mismatched payload bytes is a TORN entry:
detected, deleted, recorded (``event: "corrupt"``), recompiled.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
from typing import Callable, List, Optional

from ..utils import jax_compat
from ..utils.logging import get_logger

__all__ = ["WarmPool", "WarmExecutable", "HeadroomRefused",
           "entry_key", "deployment_digest",
           "SpeculativeCompiler", "RUNGS"]

log = get_logger(__name__)

#: The degradation ladder, best rung first.  ``cold`` is implicit —
#: the pool returning None IS the cold rung.
RUNGS = ("aot", "stablehlo", "compile_cache")


class HeadroomRefused(ValueError):
    """A resize/speculation target whose stamped per-chip footprint
    would breach ``serve.min_headroom_frac`` (the first CONSUMER of the
    round-19 advisory ``headroom_frac`` — advisory stays advisory for
    admission; only scale-up decisions enforce it)."""


# ------------------------------------------------------------- cache key
def deployment_digest(config) -> str:
    """Digest of the config fields the plan key does NOT carry.

    The plan key names grid size, tier, scheme, bucket and placement —
    but not dt, segment steps, hyperdiffusion, gravity, limiter or the
    carry dtype.  Two deployments differing in any of those compile
    DIFFERENT programs under the SAME plan key, so the warm-pool key
    must fold them in: a stale hit here would be wrong physics, not a
    slow path.
    """
    cfg = config
    ident = {
        "grid": {"n": cfg.grid.n, "halo": cfg.grid.halo,
                 "radius": cfg.grid.radius, "dtype": cfg.grid.dtype,
                 "metrics": cfg.grid.metrics},
        "time": {"dt": cfg.time.dt, "scheme": cfg.time.scheme},
        "physics": {"gravity": cfg.physics.gravity,
                    "omega": cfg.physics.omega,
                    "nu4": cfg.physics.hyperdiffusion,
                    "d2": cfg.physics.divergence_damping},
        "model": {"scheme": cfg.model.scheme,
                  "limiter": cfg.model.limiter,
                  "backend": cfg.model.backend,
                  "nu4_mode": cfg.model.nu4_mode,
                  "ic_angle": cfg.model.ic_angle},
        "precision": {"stage": cfg.precision.stage,
                      "strips": cfg.precision.strips,
                      "carry": cfg.precision.carry},
        "serve": {"segment_steps": cfg.serve.segment_steps,
                  "donate": cfg.serve.donate,
                  "group_by_orography": cfg.serve.group_by_orography},
    }
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def _environment_fields() -> dict:
    """The toolchain identity a serialized executable depends on."""
    import jax

    try:
        import jaxlib

        jaxlib_version = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = "unknown"
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def entry_key(plan_key: str, proof_fingerprint: Optional[str],
              rules_version: int, deploy_digest: str, fn: str,
              environment: Optional[dict] = None) -> str:
    """One warm-pool entry's content-addressed key (hex digest).

    ``fn`` names which of the bucket's executables the entry holds
    ('seg' / 'extract' / 'inject').  ``environment`` is injectable so
    the tier-1 invalidation tests can prove a jaxlib version-string
    change MISSES without installing a second jaxlib.
    """
    env = environment if environment is not None else _environment_fields()
    ident = {
        "plan_key": plan_key,
        "proof_fingerprint": proof_fingerprint,
        "rules_version": int(rules_version),
        "deploy": deploy_digest,
        "fn": fn,
        "env": {k: env.get(k) for k in
                ("jax", "jaxlib", "backend", "device_count")},
    }
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


# -------------------------------------------------------- warm callables
class WarmExecutable:
    """A pool-managed callable that keeps ``compile_count`` honest.

    The server's zero-steady-state-recompile proofs read
    ``fn._cache_size()`` through ``jax_compat.compile_count``; an AOT
    ``Compiled`` has no jit cache, so the wrapper reports the number of
    XLA compiles its construction actually performed: 0 for a
    pool-loaded executable (the warm path's zero-compile gate), 1 for
    a freshly AOT-compiled one.  ``stablehlo``-rung loads delegate to
    the inner jit's real cache (its first call IS one backend
    compile).
    """

    def __init__(self, call: Callable, rung: str,
                 compiles: Optional[int] = None):
        self._call = call
        self.rung = rung
        self._compiles = compiles

    def __call__(self, *args, **kwargs):
        return self._call(*args, **kwargs)

    def _cache_size(self) -> int:
        if self._compiles is not None:
            return self._compiles
        inner = jax_compat.compile_count(self._call)
        return 0 if inner is None else inner


# --------------------------------------------------------------- the pool
@dataclasses.dataclass
class _Entry:
    """On-disk layout of one committed entry (meta side)."""
    key: str
    rung: str
    sha256: str
    length: int
    plan_key: str
    donate: tuple


class WarmPool:
    """One directory of serialized bucket executables.

    ``sink_write`` receives the typed ``warmpool`` records (hit / miss
    / save / corrupt / fallback — never a silent rung change);
    ``counter_inc`` is the metrics hook (``jaxstream_warmpool_*`` on
    ``/v1/metrics``).  Thread-safe: the speculative compiler and the
    serving thread share one pool under ``self._lock``.
    """

    def __init__(self, path: str, compile_cache: str = "",
                 sink_write: Optional[Callable] = None,
                 counter_inc: Optional[Callable] = None):
        self.path = path
        os.makedirs(path, exist_ok=True)
        self.compile_cache = compile_cache
        self._sink_write = sink_write
        self._counter_inc = counter_inc
        self._lock = threading.Lock()
        self.stats = {"hits": 0, "misses": 0, "saves": 0,
                      "corrupt": 0, "rungs": {}}
        self._cache_enabled = False

    # ------------------------------------------------------------ records
    def _record(self, event: str, rung: str, plan: Optional[str],
                **extra) -> None:
        rec = {"kind": "warmpool", "event": event, "rung": rung,
               "plan": plan}
        rec.update(extra)
        if self._sink_write is not None:
            try:
                self._sink_write(rec)
            except Exception as e:  # telemetry must never kill serving
                log.warning("warmpool sink record failed (%s: %s)",
                            type(e).__name__, e)
        if self._counter_inc is not None:
            try:
                if event == "hit":
                    self._counter_inc("jaxstream_warmpool_hits_total",
                                      1, rung=rung)
                elif event == "miss":
                    self._counter_inc(
                        "jaxstream_warmpool_misses_total", 1,
                        reason=str(extra.get("reason", "absent")))
                elif event == "save":
                    self._counter_inc("jaxstream_warmpool_saves_total",
                                      1, rung=rung)
            except Exception:
                pass

    # ------------------------------------------------------------- paths
    def _payload_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.bin")

    def _meta_path(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.json")

    def _atomic_write(self, path: str, data: bytes) -> None:
        from ..obs.flight import _atomic_write_bytes

        _atomic_write_bytes(path, data)

    # ------------------------------------------------- compile cache rung
    def enable_compile_cache(self) -> bool:
        """Engage the ``compile_cache`` rung iff configured; returns
        whether the cache is on.  A ``JAX_COMPILATION_CACHE_DIR`` set
        from outside wins: the cache stays in that directory and
        ``serve.compile_cache`` only switches the rung on.  Idempotent.
        """
        if self._cache_enabled:
            return True
        if not self.compile_cache:
            return False
        path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or self.compile_cache)
        jax_compat.enable_compile_cache(path)
        self.compile_cache = path
        self._cache_enabled = True
        return True

    # ------------------------------------------------------------ loading
    def load(self, key: str, plan_key: Optional[str] = None):
        """One entry -> a :class:`WarmExecutable`, or None (= cold).

        Every outcome is typed: a clean absent entry is a ``miss``
        (reason 'absent'); a meta whose payload is missing, short, or
        digest-mismatched is a torn/corrupt entry — deleted, recorded
        (``corrupt``), and reported as a miss so the caller recompiles;
        a payload that fails deserialization (e.g. a foreign jaxlib's
        bytes that slipped past the key — should be impossible) is the
        same corrupt path, never a crash.
        """
        with self._lock:
            return self._load_locked(key, plan_key)

    def _load_locked(self, key: str, plan_key: Optional[str]):
        mpath, ppath = self._meta_path(key), self._payload_path(key)
        if not os.path.exists(mpath):
            self.stats["misses"] += 1
            self._record("miss", "cold", plan_key, key=key,
                         reason="absent")
            return None
        try:
            with open(mpath) as fh:
                meta = json.load(fh)
            with open(ppath, "rb") as fh:
                payload = fh.read()
            if len(payload) != int(meta["length"]):
                raise ValueError(
                    f"payload is {len(payload)}B, meta says "
                    f"{meta['length']}B")
            digest = hashlib.sha256(payload).hexdigest()
            if digest != meta["sha256"]:
                raise ValueError("payload sha256 mismatch")
            rung = meta["rung"]
            if rung == "aot":
                call = jax_compat.deserialize_executable(payload)
                warm = WarmExecutable(call, "aot", compiles=0)
            elif rung == "stablehlo":
                call = jax_compat.deserialize_stablehlo(
                    payload,
                    donate_argnums=tuple(meta.get("donate", ())))
                warm = WarmExecutable(call, "stablehlo")
            else:
                raise ValueError(f"unknown entry rung {rung!r}")
        except Exception as e:
            # Torn/corrupt entry: loud, deleted, recompiled.
            self.stats["corrupt"] += 1
            self.stats["misses"] += 1
            for p in (mpath, ppath):
                try:
                    os.unlink(p)
                except OSError:
                    pass
            log.warning(
                "warmpool: entry %s is torn/corrupt (%s: %s) — "
                "deleted; recompiling", key, type(e).__name__, e)
            self._record("corrupt", "cold", plan_key, key=key,
                         reason=f"{type(e).__name__}: {e}")
            self._record("miss", "cold", plan_key, key=key,
                         reason="corrupt")
            return None
        self.stats["hits"] += 1
        self.stats["rungs"][rung] = self.stats["rungs"].get(rung, 0) + 1
        self._record("hit", rung, plan_key, key=key)
        return warm

    # ------------------------------------------------------------- saving
    def save(self, key: str, jitted, compiled, example_args,
             plan_key: Optional[str] = None,
             donate: tuple = ()) -> Optional[str]:
        """Persist one freshly compiled executable at the best rung
        this build supports.  ``compiled`` is the AOT ``Compiled``
        (rung 1's payload); ``jitted`` + ``example_args`` feed the
        StableHLO export when rung 1 is unavailable.  Returns the rung
        saved at, or None (ladder exhausted — the typed ``fallback``
        records say which rungs refused and why)."""
        with self._lock:
            return self._save_locked(key, jitted, compiled,
                                     example_args, plan_key, donate)

    def _save_locked(self, key, jitted, compiled, example_args,
                     plan_key, donate):
        payload = rung = None
        # The aot/stablehlo rungs gate on API availability alone (a
        # corrupt payload degrades through the typed torn-entry path).
        if jax_compat.executable_serialization_available():
            try:
                payload = jax_compat.serialize_executable(compiled)
                rung = "aot"
            except RuntimeError as e:
                self._record("fallback", "aot", plan_key,
                             reason=str(e))
        else:
            self._record("fallback", "aot", plan_key,
                         reason="unavailable: no serialize_executable "
                                "in this jax build")
        if payload is None and jax_compat.stablehlo_serialization_available():
            try:
                payload = jax_compat.serialize_stablehlo(
                    jitted, *example_args)
                rung = "stablehlo"
            except RuntimeError as e:
                self._record("fallback", "stablehlo", plan_key,
                             reason=str(e))
        if payload is None:
            # Last resort below cold: the persistent compile cache
            # at least makes the next cold compile warm.
            self.enable_compile_cache()
            return None
        meta = {"key": key, "rung": rung,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "length": len(payload), "plan_key": plan_key,
                "donate": list(donate)}
        try:
            # Flight-recorder commit discipline: payload first, the
            # meta that makes the entry visible LAST — a kill between
            # the two leaves an invisible payload, not a torn entry.
            self._atomic_write(self._payload_path(key), payload)
            self._atomic_write(self._meta_path(key),
                               json.dumps(meta).encode())
        except OSError as e:
            self._record("fallback", rung, plan_key,
                         reason=f"entry write failed "
                                f"({type(e).__name__}: {e})")
            return None
        self.stats["saves"] += 1
        self._record("save", rung, plan_key, key=key,
                     bytes=len(payload))
        return rung

    def summary(self) -> dict:
        """The ``/v1/stats`` payload: counters per rung."""
        return {
            "path": self.path,
            "compile_cache": (self.compile_cache
                              if self._cache_enabled else ""),
            "hits": self.stats["hits"],
            "misses": self.stats["misses"],
            "saves": self.stats["saves"],
            "corrupt": self.stats["corrupt"],
            "rungs": dict(self.stats["rungs"]),
        }


# ------------------------------------------------- speculative compiler
class SpeculativeCompiler:
    """Background compilation of ADJACENT plans (round 21).

    The autoscale policy moves the active cap one level at a time, so
    the plans worth having warm are exactly the next configured bucket
    up and down from the current cap.  ``nudge(cap)`` (called from
    ``EnsembleServer.resize`` and at attach) wakes a worker thread
    that builds those buckets through the server's own ``_bucket``
    path — same build lock, same warm-pool save — so a later
    ``resize()`` to a not-yet-warm size stops paying jit at a segment
    boundary.  Headroom-refused targets are skipped with the same
    typed record ``resize`` writes (the satellite's one enforcement).
    """

    THREAD_NAME = "jaxstream-serve-speculator"

    def __init__(self, server):
        self._server = server
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._targets: List[int] = []
        self._lock = threading.Lock()
        self.built: List[tuple] = []
        self.skipped: List[dict] = []
        self._thread = threading.Thread(
            target=self._run, name=self.THREAD_NAME, daemon=True)
        self._thread.start()

    def nudge(self, cap: int) -> None:
        srv = self._server
        buckets = list(srv.buckets)
        try:
            i = buckets.index(int(cap))
        except ValueError:
            return
        adjacent = [buckets[j] for j in (i + 1, i - 1)
                    if 0 <= j < len(buckets)]
        with self._lock:
            self._targets = adjacent
        self._wake.set()

    def close(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout)

    def _run(self) -> None:
        while not self._stop.is_set():
            self._wake.wait()
            if self._stop.is_set():
                return
            self._wake.clear()
            with self._lock:
                targets, self._targets = self._targets, []
            for B in targets:
                if self._stop.is_set():
                    return
                try:
                    self._build(B)
                except Exception as e:
                    # A speculative compile failing must never hurt
                    # the server — the cold path still works.
                    log.warning(
                        "warmpool: speculative build of B=%d failed "
                        "(%s: %s)", B, type(e).__name__, e)

    def _build(self, B: int) -> None:
        srv = self._server
        for group in srv.warm_groups():
            if (group, B) in srv._buckets:
                continue
            refusal = srv.headroom_refusal(B)
            if refusal is not None:
                self.skipped.append(refusal)
                srv.record_headroom_refusal(
                    refusal, action="speculate_refused")
                continue
            srv._bucket(group, B)
            self.built.append((group, B))
