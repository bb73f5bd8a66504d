"""Thin wrappers over the narrow band of JAX APIs we use.

The repo targets one installation: jax/jaxlib 0.9.  Every wrapper here
exists to turn a missing or failing JAX surface into a typed
``RuntimeError`` (or a documented None) instead of AttributeError soup,
or to hold the one rule for a process-wide JAX setting (the compile
cache).
"""

from __future__ import annotations

import os

import jax

__all__ = ["compile_count", "copy_to_host_async",
           "default_compile_cache_dir",
           "deserialize_executable", "deserialize_stablehlo",
           "device_memory_stats", "enable_compile_cache",
           "executable_serialization_available",
           "memory_analysis", "named_scope", "place_compile_cache",
           "profiler_available", "profiler_recording",
           "serialize_executable",
           "serialize_stablehlo", "stablehlo_serialization_available",
           "start_profiler_trace", "stop_profiler_trace",
           "trace_annotation"]


def named_scope(name: str):
    """``jax.named_scope``: a trace-time name-stack annotation.

    Every op traced inside the context carries ``name`` as a prefix, so
    xprof/TensorBoard traces show the fused steppers as named regions
    (exchange start/finish, interior vs band RHS, RK stages, TT sweeps)
    instead of anonymous custom-call soup.  Zero runtime cost — the
    name lives in HLO metadata only.
    """
    return jax.named_scope(name)


#: ``profiler_recording()`` is True while a profiler session records.
#: The bare ``TraceAnnotation.is_enabled`` (no Python frame): a span
#: tests it before building an annotation, so with no session it costs
#: this one check.
profiler_recording = jax.profiler.TraceAnnotation.is_enabled


def trace_annotation(name: str, **attrs):
    """A ``jax.profiler.TraceAnnotation`` for one host span: ``name``,
    with ``attrs`` as event metadata, on the profiler's host timeline,
    the clock the device ops of the same capture run on."""
    return jax.profiler.TraceAnnotation(name, **attrs)


def profiler_available() -> bool:
    """True when this jax build exposes the on-demand trace profiler.

    ``jax.profiler.start_trace``/``stop_trace`` is the capture API on
    every supported line, but some stripped builds ship without the
    profiler extension — the gateway's ``POST /v1/profile`` degrades to
    a typed 501 instead of a 500 stack when this returns False.
    """
    prof = getattr(jax, "profiler", None)
    return (prof is not None and hasattr(prof, "start_trace")
            and hasattr(prof, "stop_trace"))


def start_profiler_trace(log_dir: str) -> None:
    """Begin a ``jax.profiler`` trace capture into ``log_dir``.

    Raises ``RuntimeError`` when the build has no profiler (callers
    map it to the typed 501) — never AttributeError soup.  One capture
    at a time is the profiler's own contract; the gateway serializes
    start/stop behind its profile state.
    """
    if not profiler_available():
        raise RuntimeError(
            "jax.profiler.start_trace is unavailable in this jax "
            "build; on-demand profiling is disabled")
    try:
        os.makedirs(log_dir, exist_ok=True)
        jax.profiler.start_trace(log_dir)
    except RuntimeError:
        raise
    except Exception as e:
        # An unwritable log dir (OSError) or a foreign profiler
        # session must surface as the typed RuntimeError the callers
        # map to their 501 contract, never an untyped 500.
        raise RuntimeError(
            f"profiler trace could not start in {log_dir!r}: "
            f"{type(e).__name__}: {e}")


def stop_profiler_trace() -> None:
    """End the in-flight ``jax.profiler`` trace capture."""
    if not profiler_available():
        raise RuntimeError(
            "jax.profiler.stop_trace is unavailable in this jax "
            "build; on-demand profiling is disabled")
    try:
        jax.profiler.stop_trace()
    except RuntimeError:
        raise
    except Exception as e:
        raise RuntimeError(
            f"profiler trace could not stop: {type(e).__name__}: {e}")


#: ``Compiled.memory_analysis()`` size attributes -> the short names the
#: cost stamps carry (``jaxstream.obs.perf``).  ``alias_size_in_bytes``
#: is excluded from ``total_bytes`` — aliased (donated) buffers are
#: already counted once in the argument bytes.
_MEMORY_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
)


def memory_analysis(compiled) -> dict:
    """XLA's static memory accounting of one compiled executable.

    Returns ``{"argument_bytes", "output_bytes", "temp_bytes",
    "generated_code_bytes", "alias_bytes", "total_bytes"}`` from
    ``Compiled.memory_analysis()`` — the per-plan footprint the
    performance observatory stamps on every measured stepper
    (``jaxstream.obs.perf``).  Raises ``RuntimeError`` (the typed
    "unavailable" the cost stamps record verbatim) on jax builds /
    backends that expose no memory analysis — never AttributeError
    soup, so a stamp on an exotic backend says *why* it has no bytes
    instead of crashing the build path.
    """
    ma = getattr(compiled, "memory_analysis", None)
    if ma is None:
        raise RuntimeError(
            "unavailable: this jax build exposes no "
            "Compiled.memory_analysis()")
    try:
        st = ma()
    except Exception as e:
        raise RuntimeError(
            f"unavailable: memory_analysis failed "
            f"({type(e).__name__}: {e})")
    out = {}
    for attr, key in _MEMORY_FIELDS:
        v = getattr(st, attr, None)
        if v is not None:
            out[key] = int(v)
    if not out:
        raise RuntimeError(
            "unavailable: memory_analysis returned no size fields "
            f"(got {type(st).__name__})")
    out["total_bytes"] = sum(v for k, v in out.items()
                             if k != "alias_bytes")
    return out


def compile_count(fn):
    """Compiled-executable count of one jitted callable, or None.

    The jit-cache introspection the serving stack's zero-steady-state-
    recompile proofs use (``EnsembleServer.compile_count``), promoted
    here (round 19) so the compile-event counters on the metrics
    scrape and the test assertions read the SAME private surface —
    ``fn._cache_size()`` on every supported jax line; None when the
    build exposes no cache introspection (callers decide how loudly to
    degrade).
    """
    cs = getattr(fn, "_cache_size", None)
    return None if cs is None else int(cs())


def device_memory_stats(device):
    """``device.memory_stats()`` as a dict, or None when the backend
    keeps no per-device allocator stats (CPU returns None; stripped
    builds may omit the method).  The MemoryWatcher's one read — a
    poll can never raise out of the serving loop.
    """
    ms = getattr(device, "memory_stats", None)
    if ms is None:
        return None
    try:
        return ms()
    except Exception:
        return None


def copy_to_host_async(tree):
    """Start device->host copies of every array leaf; returns ``tree``.

    The async-host-pipeline primitive (``jaxstream.io.async_pipeline``):
    enqueues a non-blocking d2h transfer per ``jax.Array`` leaf — the
    transfer is sequenced after the array's definition event, so calling
    this on the *future* a just-dispatched segment returned costs
    nothing on the dispatch path.  A later ``np.asarray`` on the same
    array resolves against the in-flight copy instead of starting a
    blocking one.  Spelled ``Array.copy_to_host_async()`` on every
    supported jax; leaves without the method (numpy arrays, python
    scalars) pass through untouched, so whole state pytrees can be
    handed over unfiltered.
    """
    def start(x):
        m = getattr(x, "copy_to_host_async", None)
        if m is not None:
            m()
        return x

    return jax.tree_util.tree_map(start, tree)


def default_compile_cache_dir() -> str:
    """The fixed in-checkout cache directory: ``<repo>/.jax_cache``.

    Fixed on purpose — the directory is part of what a process must
    find again, so it is never derived from a temp name, a pid or the
    time.  ``.gitignore`` lists it.
    """
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".jax_cache")


def place_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set (or a directory already set in
    this process): jax uses it, and nothing is set in code.  Otherwise
    the cache goes to :func:`default_compile_cache_dir`.  Only the
    directory is set — whether the cache is used stays jax's own
    ``jax_enable_compilation_cache`` (the tier-1 suite turns it off in
    ``tests/conftest.py``).  Called on package import, so every entry
    point (``Simulation``, the CLI, ``chip_smoke.py``) shares one
    cache.
    """
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = default_compile_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def enable_compile_cache(path: str) -> str:
    """Point jax's persistent compilation cache at ``path`` (created)
    and cache every executable, however quick its compile.

    The ``serve.compile_cache`` rung and ``bench.py --compile-report``
    call this; both leave a ``JAX_COMPILATION_CACHE_DIR`` set from
    outside in place (:meth:`jaxstream.serve.warmpool.WarmPool.
    enable_compile_cache`).  jax latches cache use once per process at
    the first compile, so the latch is reset here or a cache enabled
    after a compile stays empty.
    """
    from jax.experimental.compilation_cache import compilation_cache

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    return path


# ----------------------------------------------- executable serialization
# Round 21 (warm pools): the two serialization surfaces the
# jaxstream.serve.warmpool degradation ladder stands on.  Both are
# version-portable shims with typed RuntimeErrors — a build that lacks
# one rung must say so (the pool records the typed miss and drops to
# the next rung), never AttributeError soup.

def executable_serialization_available() -> bool:
    """True when this jax build can serialize a COMPILED executable
    (``jax.experimental.serialize_executable``) — the warm pool's top
    rung: a load skips trace, lower AND backend compile entirely."""
    try:
        from jax.experimental import serialize_executable as se

        return (hasattr(se, "serialize")
                and hasattr(se, "deserialize_and_load"))
    except Exception:
        return False


def serialize_executable(compiled) -> bytes:
    """One compiled executable -> portable bytes (pickled payload).

    ``jax.experimental.serialize_executable.serialize`` returns
    ``(unloaded_bytes, in_tree, out_tree)``; the pytree defs are part
    of the call contract, so the three are pickled together with the
    platform and the ids of the devices the executable was compiled
    for — ONE opaque payload ``deserialize_executable`` reverses.
    Raises the typed RuntimeError on builds without the API.
    """
    if not executable_serialization_available():
        raise RuntimeError(
            "unavailable: this jax build exposes no "
            "jax.experimental.serialize_executable")
    import pickle

    from jax.experimental import serialize_executable as se

    try:
        devices = compiled.runtime_executable().local_devices()
        return pickle.dumps((se.serialize(compiled),
                             devices[0].platform,
                             [d.id for d in devices]))
    except Exception as e:
        raise RuntimeError(
            f"unavailable: executable serialization failed "
            f"({type(e).__name__}: {e})")


def deserialize_executable(payload: bytes):
    """Bytes from :func:`serialize_executable` -> a loaded, callable
    ``Compiled`` — ZERO XLA compiles (the warm pool's zero-compile
    parity gate reads exactly this property).

    The executable is loaded onto the devices it was compiled for: on
    jax 0.9 ``deserialize_and_load`` otherwise loads onto EVERY device
    of the backend and the first call fails its shard count.  The
    payload must come from the same jaxlib/backend/device-count — the
    warm-pool cache key enforces that."""
    if not executable_serialization_available():
        raise RuntimeError(
            "unavailable: this jax build exposes no "
            "jax.experimental.serialize_executable")
    import pickle

    from jax.experimental import serialize_executable as se

    try:
        (blob, in_tree, out_tree), platform, ids = pickle.loads(payload)
        by_id = {d.id: d for d in jax.devices(platform)}
        return se.deserialize_and_load(
            blob, in_tree, out_tree, backend=platform,
            execution_devices=[by_id[i] for i in ids])
    except Exception as e:
        raise RuntimeError(
            f"unavailable: executable deserialization failed "
            f"({type(e).__name__}: {e})")


def stablehlo_serialization_available() -> bool:
    """True when this jax build has the ``jax.export`` StableHLO
    round-trip — the warm pool's middle rung: a load re-runs the
    backend compile but skips trace + lower."""
    try:
        import jax.export as jex

        return hasattr(jex, "export") and hasattr(jex, "deserialize")
    except Exception:
        return False


def serialize_stablehlo(jitted, *args, **kwargs) -> bytes:
    """Trace + lower ``jitted(*args)`` once and serialize the exported
    StableHLO module (``jax.export``) — portable across processes and
    (unlike the executable rung) across jaxlib patch versions."""
    if not stablehlo_serialization_available():
        raise RuntimeError(
            "unavailable: this jax build exposes no jax.export")
    import jax.export as jex

    try:
        return jex.export(jitted)(*args, **kwargs).serialize()
    except Exception as e:
        raise RuntimeError(
            f"unavailable: StableHLO export failed "
            f"({type(e).__name__}: {e})")


def deserialize_stablehlo(payload: bytes, donate_argnums=()):
    """Bytes from :func:`serialize_stablehlo` -> a jitted callable.

    The first call performs ONE backend compile (trace + lower are
    skipped — that is the rung's value); ``donate_argnums`` re-applies
    the original jit's donation, which the exported module does not
    carry on its own."""
    if not stablehlo_serialization_available():
        raise RuntimeError(
            "unavailable: this jax build exposes no jax.export")
    import jax.export as jex

    try:
        exported = jex.deserialize(bytearray(payload))
        return jax.jit(exported.call,
                       donate_argnums=tuple(donate_argnums))
    except Exception as e:
        raise RuntimeError(
            f"unavailable: StableHLO import failed "
            f"({type(e).__name__}: {e})")
