"""Tier-hygiene lint for the test suite (CI/tooling satellite, round 6).

Two invariants keep the fast tier-1 gate honest, both enforced here and
run *inside* the gate via ``tests/test_check_tiers.py`` (the tier-1
command is plain pytest, so a non-slow test wrapping this lint makes
every gate run self-checking):

1. **Marker registry**: every ``pytest.mark.<name>`` used under
   ``tests/`` must be registered in ``pytest.ini``'s ``markers`` section
   (or be a pytest builtin).  An unregistered marker is how a test
   silently escapes the ``-m "not slow"`` deselection — e.g. a typo'd
   ``@pytest.mark.slwo`` runs a 40 s parity in every fast gate.

2. **Subprocess device tests are slow**: any test module that launches a
   multi-device SUBPROCESS worker (the 24-virtual-device block-mesh and
   multi-process pod parities — detected as ``subprocess`` usage next to
   a worker-script reference or a forced host-device count) must carry
   ``pytest.mark.slow``.  These are the suite's most expensive items
   (~40-90 s each); the fast tier's time budget assumes they stay out.

3. **Telemetry tests stay tier-1** (round-8 observability satellite):
   a test module importing ``jaxstream.obs`` must carry NO ``slow``
   markers.  The observability acceptance criteria (buffer parity,
   guard firing, bitwise-unchanged carry) are what the fast gate
   certifies on every run — a slow-marked telemetry parity would
   silently drop that coverage from tier-1.  Put genuinely slow
   obs-adjacent tests in a module that exercises the feature through
   ``Simulation`` without importing ``jaxstream.obs`` directly.

4. **Async-pipeline tests stay tier-1** (round-9 satellite): the same
   rule for modules importing ``jaxstream.io.async_pipeline``.  The
   async/sync bitwise file parity, the writer backpressure bound, the
   flush-on-HealthError guarantee and the thread-leak check are the
   acceptance criteria of the overlap path — they must run in every
   fast gate, not rot in the slow tier.

5. **Precision-parity tests stay tier-1** (round-10 satellite): the
   same rule for modules importing ``jaxstream.ops.pallas.precision``.
   The precision ladder's acceptance criteria — policy-off bitwise
   identity, the measured bf16-stage truncation budgets, the re-fused
   del^4 parity — are exactly what certifies that a refactor didn't
   silently change which ops run reduced; they must run in every fast
   gate (a slow-marked parity would let a bad policy ship between
   offline TPU bench runs).

6. **Serving tests stay tier-1** (round-11 satellite): the same rule
   for modules importing ``jaxstream.serve``.  The continuous-batching
   server's acceptance criteria — packing/refill determinism, the
   B=1-request bitwise parity vs a plain Simulation run, eviction-
   under-injected-NaN, queue backpressure, and the zero-steady-state-
   recompile warm-bucket claim — must run in every fast gate (the real
   throughput numbers only exist on offline TPU bench runs; the fast
   gate is what certifies the machinery between them).

7. **Multichip-serving tests ride the in-process fake devices**
   (round-12 satellite): a module importing the serving placement
   surface (``jaxstream.serve.placement``) must not launch subprocess
   workers.  Rule 6 already keeps it non-slow; the remaining way to
   lose the coverage is a rewrite onto a subprocess device worker —
   which rule 2 would then force into the slow tier, silently dropping
   the member-parallel/panel-sharded parities from every fast gate.
   The conftest's 8 virtual CPU devices exist exactly so these tests
   run in-process.

8. **Contract-checker tests stay non-slow and in-process** (round-13
   static-analysis satellite): a module importing
   ``jaxstream.analysis`` must carry NO ``slow`` markers and must not
   launch subprocesses.  The contract checks (schedule totality, the
   traced-vs-plan collective counts, the seeded-broken fixtures
   failing loudly) are the machine-checked proof of the race-free
   claim — they must run in every fast gate, on the conftest's
   in-process virtual devices; a slow-marked or subprocess rewrite
   would silently drop the proof from the gate that cites it.

9. **Gateway/loadgen tests stay non-slow and bind loopback only**
   (round-14 network-front-door satellite): a module importing
   ``jaxstream.gateway`` or ``jaxstream.loadgen`` must carry NO
   ``slow`` markers — the typed-overload contract, the loopback byte
   parity, graceful drain, trace determinism and the autoscale
   hysteresis proofs are the acceptance criteria the fast gate
   certifies between offline runs — and must never reference a
   wildcard bind address (``0.0.0.0``): gateway tests run REAL
   listening sockets, and anything but 127.0.0.1 leaks an open port
   to the network from every CI run.

10. **Config sections stay documented; plan tests stay fast +
    in-process** (round-16 capability-plan satellite).  Two halves:
    (a) every section key in ``jaxstream/config.py``'s ``_SECTIONS``
    table must appear as a top-level key inside a fenced config block
    in ``docs/USAGE.md`` — a new config section whose docs never
    landed is exactly the drift the plan layer exists to prevent
    (the rule that rejects a knob should be one ``grep`` from the doc
    that explains it); (b) a test module importing ``jaxstream.plan``
    must carry NO ``slow`` markers and must not launch subprocesses —
    the rule-table rejections, the enumerated plan space and the
    proof-stamp checks are the static proof surface of the build
    pipeline and must run in every fast gate on the in-process
    virtual devices.

11. **Tracing/dashboard tests stay non-slow, in-process, loopback
    only** (round-17 observability satellite): a module importing the
    tracing surface (``jaxstream.obs.trace`` / ``jaxstream.obs.
    registry``) or the operator dashboard (``telemetry_dashboard``)
    must carry NO ``slow`` markers, must not launch subprocesses
    (drive the dashboard/report CLIs through their importable
    ``main()``), and must never reference a wildcard bind address —
    the span-completeness proof, the metrics scrape round-trip and
    the dashboard render are the operator-view acceptance criteria
    the fast gate certifies on every run, and their gateways open
    REAL listening sockets.

12. **Assimilation tests stay non-slow and in-process** (round-18
    EnKF satellite): a module importing ``jaxstream.da`` must carry
    NO ``slow`` markers and must not launch subprocesses — the
    closed-loop forecast claim (cycled RMSE beats the free ensemble
    through the HTTP gateway), the byte-determinism of the cycle
    outputs, the seeded spread-collapse guard and the raw-array
    restart round trip are the acceptance criteria the fast gate
    certifies on every run; drive ``scripts/assimilate.py`` through
    its importable ``main()``/``run()``.

13. **Perf-observatory tests stay non-slow, in-process, and
    CPU-honest; sink kinds stay rendered** (round-19 satellite).  Two
    halves: (a) a test module importing the performance observatory
    (``jaxstream.obs.perf`` or ``perf_ledger``) must carry NO
    ``slow`` markers, must not launch subprocesses (drive
    ``scripts/perf_ledger.py`` through its importable ``main()``),
    and must not gate on accelerator-only surfaces (``skipif`` on
    tpu/gpu platforms or ``jax.devices('tpu')`` probes) — the cost-
    stamp shapes, the typed memory_analysis fallback, the
    watcher-off byte-identity and the ledger's seeded-broken fixture
    are tier-1 acceptance criteria and must run on CPU in every fast
    gate; (b) every record kind registered in
    ``jaxstream/obs/sink.py``'s ``RECORD_KINDS`` must appear in BOTH
    ``scripts/telemetry_report.py``'s and
    ``scripts/telemetry_dashboard.py``'s ``RENDERED_KINDS`` sets —
    the loud unrendered-kinds footer contract only holds if a newly
    registered kind is actually taught to both tools (a registered-
    but-unrendered kind would scream "schema drift" on every
    operator view).

14. **Flight-recorder/postmortem tests stay non-slow and in-process;
    kill tests stay slow** (round-20 black-box satellite).  Two
    halves: (a) a test module importing the flight recorder
    (``jaxstream.obs.flight``) or the postmortem reconstructor
    (``scripts/postmortem.py`` via ``import postmortem``) must carry
    NO ``slow`` markers and must not launch subprocesses — the ring
    semantics, the atomic-bundle round trip, the torn-bundle
    rejection, the sink byte-identity claim and the resume-lineage
    proof are tier-1 acceptance criteria (drive the postmortem CLI
    through its importable ``main()``); (b) any test module that
    launches subprocesses AND references a hard kill
    (``SIGKILL``/``.kill(``) must carry ``pytest.mark.slow`` — the
    SIGKILL crash-forensics capstone spawns a real serving process
    and waits on it, which is exactly the cost profile the fast
    tier's budget excludes.

15. **Warm-pool tests stay non-slow and in-process** (round-21
    compile-tax satellite): a test module importing the warm-pool
    surface (``jaxstream.serve.warmpool``) must carry NO ``slow``
    markers and must not launch subprocesses — the cache-key
    invalidation proofs (rules-version bump / plan / toolchain string
    MISS, never a stale hit), the torn-entry detection and the
    zero-warm-compile restart claim are tier-1 acceptance criteria.
    (PR 21 dropped the rule's second half, which forced cross-process
    compile-cache tests slow against an older jaxlib's segfault: jax
    0.9 reads another process's CPU cache entries cleanly.)

Exit status 0 = clean; 1 = violations (listed on stdout).
"""

from __future__ import annotations

import configparser
import os
import re
import sys

#: Markers pytest defines itself — always legal without registration.
BUILTIN_MARKERS = {
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings", "tryfirst", "trylast",
}

_MARK_RE = re.compile(r"pytest\.mark\.([A-Za-z_][A-Za-z0-9_]*)")
_WORKER_RE = re.compile(
    r"(_worker\.py|worker\.py\b|xla_force_host_platform_device_count)")
_OBS_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.obs\b|import\s+jaxstream\.obs\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*obs\b)", re.MULTILINE)
_ASYNC_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.io\.async_pipeline\b"
    r"|import\s+jaxstream\.io\.async_pipeline\b"
    r"|from\s+jaxstream\.io\s+import\s+(\w+\s*,\s*)*async_pipeline\b)",
    re.MULTILINE)
_PRECISION_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.ops\.pallas\.precision\b"
    r"|import\s+jaxstream\.ops\.pallas\.precision\b"
    r"|from\s+jaxstream\.ops\.pallas\s+import\s+(\w+\s*,\s*)*precision\b)",
    re.MULTILINE)
_SERVE_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.serve\b|import\s+jaxstream\.serve\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*serve\b)",
    re.MULTILINE)
_PLACEMENT_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.serve\.placement\b"
    r"|import\s+jaxstream\.serve\.placement\b"
    r"|from\s+jaxstream\.serve\s+import\s+[^\n]*"
    r"\b(placement|plan_placement|placement_report|BucketPlan)\b)",
    re.MULTILINE)
_ANALYSIS_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.analysis\b|import\s+jaxstream\.analysis\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*analysis\b)",
    re.MULTILINE)
_NETWORK_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.(gateway|loadgen)\b"
    r"|import\s+jaxstream\.(gateway|loadgen)\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*(gateway|loadgen)\b)",
    re.MULTILINE)
#: Anchored so real addresses merely CONTAINING the substring
#: (10.0.0.0/8, 240.0.0.0) do not trip the lint.
_WILDCARD_BIND_RE = re.compile(r"(?<![\d.])0\.0\.0\.0(?![\d.])")
_PLAN_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.plan\b|import\s+jaxstream\.plan\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*plan\b)",
    re.MULTILINE)
_TRACE_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.obs\.(trace|registry)\b"
    r"|import\s+jaxstream\.obs\.(trace|registry)\b"
    r"|from\s+jaxstream\.obs\s+import\s+[^\n]*"
    r"\b(trace|registry|RequestTrace|MetricsRegistry"
    r"|parse_exposition|span_coverage|tree_complete)\b"
    r"|import\s+telemetry_dashboard\b"
    r"|from\s+telemetry_dashboard\s+import\b)",
    re.MULTILINE)
_DA_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.da\b|import\s+jaxstream\.da\b"
    r"|from\s+jaxstream\s+import\s+(\w+\s*,\s*)*da\b)",
    re.MULTILINE)
_PERF_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.obs\.perf\b"
    r"|import\s+jaxstream\.obs\.perf\b"
    r"|from\s+jaxstream\.obs\s+import\s+[^\n]*"
    r"\b(perf|CostStamp|MemoryWatcher|measure_cost|build_cost"
    r"|check_trajectory|load_bench_history)\b"
    r"|import\s+perf_ledger\b|from\s+perf_ledger\s+import\b)",
    re.MULTILINE)
#: Accelerator-only gating a tier-1 perf-obs module must not carry:
#: a platform skipif or an explicit tpu/gpu device probe would drop
#: the observatory's acceptance criteria from every CPU CI gate.
_ACCEL_ONLY_RE = re.compile(
    r"skipif\([^)]*[\"'](tpu|gpu)[\"']"
    r"|jax\.devices\(\s*[\"'](tpu|gpu)[\"']")
_FLIGHT_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.obs\.flight\b"
    r"|import\s+jaxstream\.obs\.flight\b"
    r"|from\s+jaxstream\.obs\s+import\s+[^\n]*"
    r"\b(flight|FlightRecorder|BundleWriter|read_bundle"
    r"|latest_bundle|TornBundleError)\b"
    r"|import\s+postmortem\b|from\s+postmortem\s+import\b)",
    re.MULTILINE)
_WARMPOOL_IMPORT_RE = re.compile(
    r"^\s*(from\s+jaxstream\.serve\.warmpool\b"
    r"|import\s+jaxstream\.serve\.warmpool\b"
    r"|from\s+jaxstream\.serve\s+import\s+[^\n]*"
    r"\b(warmpool|WarmPool|WarmExecutable|HeadroomRefused"
    r"|SpeculativeCompiler)\b)",
    re.MULTILINE)
#: A hard-kill reference next to subprocess usage marks the SIGKILL
#: crash-forensics capstone (and anything shaped like it) — those
#: must ride the slow tier.
_HARD_KILL_RE = re.compile(r"\bSIGKILL\b|\.kill\(")
#: Actual subprocess USAGE (an import or an attribute call), so a
#: docstring merely mentioning the word does not trip rule 10b.
_SUBPROC_USE_RE = re.compile(
    r"^\s*(import|from)\s+subprocess\b|subprocess\.\w+",
    re.MULTILINE)
#: The _SECTIONS table in jaxstream/config.py: "name": SomeConfig,
_SECTIONS_RE = re.compile(
    r"^_SECTIONS\s*=\s*\{(.*?)\}", re.MULTILINE | re.DOTALL)
_SECTION_KEY_RE = re.compile(r"\"(\w+)\"\s*:")
_FENCE_RE = re.compile(r"^```[a-z]*\n(.*?)^```", re.MULTILINE | re.DOTALL)


def config_sections(config_py: str):
    """The ``_SECTIONS`` keys of jaxstream/config.py (regex — this
    lint must stay import-light, no jax)."""
    with open(config_py) as fh:
        m = _SECTIONS_RE.search(fh.read())
    if not m:
        return None
    return _SECTION_KEY_RE.findall(m.group(1))


def documented_sections(usage_md: str):
    """Top-level ``key:`` names inside USAGE.md's fenced blocks."""
    with open(usage_md) as fh:
        text = fh.read()
    keys = set()
    for block in _FENCE_RE.findall(text):
        for line in block.splitlines():
            m = re.match(r"^([A-Za-z_][A-Za-z0-9_]*):", line)
            if m:
                keys.add(m.group(1))
    return keys


def lint_config_docs(root: str):
    """Rule 10a: every config section has a fenced USAGE.md block."""
    config_py = os.path.join(root, "jaxstream", "config.py")
    usage_md = os.path.join(root, "docs", "USAGE.md")
    if not (os.path.exists(config_py) and os.path.exists(usage_md)):
        return                      # repo layouts without the pair
    sections = config_sections(config_py)
    if sections is None:
        yield (f"{os.path.relpath(config_py)}: could not locate the "
               f"_SECTIONS table (rule 10a parses it textually — "
               f"keep the literal dict form)")
        return
    documented = documented_sections(usage_md)
    for name in sections:
        if name not in documented:
            yield (f"docs/USAGE.md: config section {name!r} "
                   f"(_SECTIONS in jaxstream/config.py) has no fenced "
                   f"``` config block showing a top-level '{name}:' "
                   f"key — every section the plan layer can reject "
                   f"must be documented where users write it")


#: The RECORD_KINDS table in jaxstream/obs/sink.py and the
#: RENDERED_KINDS sets in the two stdlib operator tools — parsed
#: textually (this lint must stay import-light, no jax).
_RECORD_KINDS_RE = re.compile(
    r"^RECORD_KINDS[^=]*=\s*\{(.*?)^\}", re.MULTILINE | re.DOTALL)
_KIND_KEY_RE = re.compile(r"^\s{4}\"(\w+)\":", re.MULTILINE)
_RENDERED_RE = re.compile(
    r"RENDERED_KINDS\s*=\s*frozenset\(\{(.*?)\}\)", re.DOTALL)
_QUOTED_RE = re.compile(r"\"(\w+)\"")


def lint_sink_kinds(root: str):
    """Rule 13b: every registered sink kind is rendered by BOTH
    operator tools (the loud unrendered-kinds footer contract)."""
    sink_py = os.path.join(root, "jaxstream", "obs", "sink.py")
    tools = [os.path.join(root, "scripts", name) for name in
             ("telemetry_report.py", "telemetry_dashboard.py")]
    if not os.path.exists(sink_py) or not all(
            os.path.exists(t) for t in tools):
        return                      # repo layouts without the trio
    with open(sink_py) as fh:
        m = _RECORD_KINDS_RE.search(fh.read())
    if not m:
        yield (f"{os.path.relpath(sink_py)}: could not locate the "
               f"RECORD_KINDS table (rule 13b parses it textually — "
               f"keep the literal dict form)")
        return
    kinds = set(_KIND_KEY_RE.findall(m.group(1)))
    for tool in tools:
        with open(tool) as fh:
            mm = _RENDERED_RE.search(fh.read())
        rendered = set(_QUOTED_RE.findall(mm.group(1))) if mm else set()
        for kind in sorted(kinds - rendered):
            yield (f"{os.path.relpath(tool)}: sink record kind "
                   f"{kind!r} (RECORD_KINDS in jaxstream/obs/sink.py) "
                   f"is not in this tool's RENDERED_KINDS — a "
                   f"registered kind the operator view cannot render "
                   f"lands in the loud unrendered-kinds footer as "
                   f"false schema drift; teach the tool the kind (and "
                   f"render it) when registering it")


def registered_markers(pytest_ini: str) -> set:
    """Marker names registered in pytest.ini's ``markers`` option."""
    cp = configparser.ConfigParser()
    cp.read(pytest_ini)
    raw = cp.get("pytest", "markers", fallback="")
    names = set()
    for line in raw.splitlines():
        line = line.strip()
        if not line:
            continue
        names.add(line.split(":", 1)[0].strip())
    return names


def lint_file(path: str, allowed: set):
    """Yield violation strings for one test module."""
    with open(path) as fh:
        src = fh.read()
    rel = os.path.relpath(path)
    used = set(_MARK_RE.findall(src))
    for name in sorted(used - allowed):
        yield (f"{rel}: pytest.mark.{name} is not registered in "
               f"pytest.ini (registered: {sorted(allowed - BUILTIN_MARKERS)}"
               f" + builtins) — unregistered markers escape the "
               f"-m 'not slow' tier gate")
    if "subprocess" in src and _WORKER_RE.search(src) \
            and "slow" not in used:
        yield (f"{rel}: launches a multi-device subprocess worker but "
               f"carries no pytest.mark.slow — subprocess device tests "
               f"must stay out of the fast tier")
    if _OBS_IMPORT_RE.search(src) and "slow" in used:
        yield (f"{rel}: imports jaxstream.obs but marks tests slow — "
               f"telemetry coverage must stay tier-1-clean (the fast "
               f"gate certifies the observability acceptance criteria "
               f"on every run); move the slow test to a module that "
               f"does not import jaxstream.obs")
    if _ASYNC_IMPORT_RE.search(src) and "slow" in used:
        yield (f"{rel}: imports jaxstream.io.async_pipeline but marks "
               f"tests slow — the async-pipeline acceptance criteria "
               f"(bitwise file parity, backpressure bound, "
               f"flush-on-exception, thread hygiene) must run in every "
               f"fast gate; move the slow test to a module that does "
               f"not import jaxstream.io.async_pipeline")
    if _PRECISION_IMPORT_RE.search(src) and "slow" in used:
        yield (f"{rel}: imports jaxstream.ops.pallas.precision but "
               f"marks tests slow — the precision-ladder parities "
               f"(policy-off bitwise, bf16-stage truncation budgets, "
               f"re-fused del^4) must run in every fast gate; move the "
               f"slow test to a module that does not import "
               f"jaxstream.ops.pallas.precision")
    if _SERVE_IMPORT_RE.search(src) and "slow" in used:
        yield (f"{rel}: imports jaxstream.serve but marks tests slow — "
               f"the serving acceptance criteria (packing/refill "
               f"determinism, B=1 bitwise parity vs Simulation, "
               f"eviction, backpressure, zero steady-state recompiles) "
               f"must run in every fast gate; move the slow test to a "
               f"module that does not import jaxstream.serve")
    if _PLACEMENT_IMPORT_RE.search(src) and "subprocess" in src:
        yield (f"{rel}: imports the serving placement surface "
               f"(jaxstream.serve.placement) but launches subprocesses "
               f"— multichip-serving parities must run IN-PROCESS on "
               f"the conftest's 8 virtual CPU devices (a subprocess "
               f"device worker would be forced slow by rule 2, "
               f"silently dropping member-parallel/panel-sharded "
               f"coverage from the fast gate)")
    if _NETWORK_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports jaxstream.gateway/loadgen but "
                   f"marks tests slow — the network front door's "
                   f"acceptance criteria (typed 429/503 overload, "
                   f"loopback byte parity, graceful drain, trace "
                   f"determinism, autoscale hysteresis) must run in "
                   f"every fast gate; move the slow test to a module "
                   f"that does not import jaxstream.gateway/loadgen")
        if _WILDCARD_BIND_RE.search(src):
            yield (f"{rel}: imports jaxstream.gateway/loadgen and "
                   f"references the wildcard bind address 0.0.0.0 — "
                   f"gateway tests open REAL listening sockets and "
                   f"must bind loopback (127.0.0.1) only, or every CI "
                   f"run exposes an open port to the network")
    if _PLAN_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports jaxstream.plan but marks tests "
                   f"slow — the capability-plan rejections, the "
                   f"enumerated plan space and the proof-stamp "
                   f"checks are the static proof surface of the "
                   f"build pipeline and must run in every fast gate; "
                   f"move the slow test to a module that does not "
                   f"import jaxstream.plan")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports jaxstream.plan but launches "
                   f"subprocesses — plan/pipeline tests must run "
                   f"IN-PROCESS on the conftest's virtual devices "
                   f"(a subprocess rewrite would be forced slow by "
                   f"rule 2, dropping the plan-space proof from the "
                   f"fast gate); drive scripts/plan.py through its "
                   f"importable main() instead")
    if _TRACE_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports the tracing/dashboard surface "
                   f"(jaxstream.obs.trace/registry or "
                   f"telemetry_dashboard) but marks tests slow — the "
                   f"span-completeness proof, the metrics scrape "
                   f"round-trip and the dashboard render are the "
                   f"operator-view acceptance criteria and must run "
                   f"in every fast gate; move the slow test to a "
                   f"module that does not import the tracing surface")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports the tracing/dashboard surface but "
                   f"launches subprocesses — tracing/dashboard tests "
                   f"must run IN-PROCESS (drive "
                   f"scripts/telemetry_dashboard.py and "
                   f"scripts/telemetry_report.py through their "
                   f"importable main() instead; a subprocess rewrite "
                   f"would be forced slow by rule 2, dropping the "
                   f"operator-view proof from the fast gate)")
        if _WILDCARD_BIND_RE.search(src):
            yield (f"{rel}: imports the tracing/dashboard surface and "
                   f"references the wildcard bind address 0.0.0.0 — "
                   f"traced-gateway tests open REAL listening sockets "
                   f"and must bind loopback (127.0.0.1) only")
    if _DA_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports jaxstream.da but marks tests slow "
                   f"— the assimilation acceptance criteria (the "
                   f"closed-loop gateway forecast claim, cycle byte "
                   f"determinism, the spread-collapse guard, the "
                   f"raw-array restart round trip) must run in every "
                   f"fast gate; move the slow test to a module that "
                   f"does not import jaxstream.da")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports jaxstream.da but launches "
                   f"subprocesses — assimilation tests must run "
                   f"IN-PROCESS on the conftest's virtual devices "
                   f"(drive scripts/assimilate.py through its "
                   f"importable main()/run(); a subprocess rewrite "
                   f"would be forced slow by rule 2, dropping the "
                   f"forecast-loop proof from the fast gate)")
    if _PERF_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports the performance observatory "
                   f"(jaxstream.obs.perf / perf_ledger) but marks "
                   f"tests slow — the cost-stamp shapes, the typed "
                   f"memory_analysis fallback, the watcher-off byte "
                   f"identity and the ledger's seeded-broken fixture "
                   f"must run in every fast gate; move the slow test "
                   f"to a module that does not import the observatory")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports the performance observatory but "
                   f"launches subprocesses — perf-obs tests must run "
                   f"IN-PROCESS (drive scripts/perf_ledger.py through "
                   f"its importable main(); a subprocess rewrite "
                   f"would be forced slow by rule 2, dropping the "
                   f"regression-ledger proof from the fast gate)")
        if _ACCEL_ONLY_RE.search(src):
            yield (f"{rel}: imports the performance observatory and "
                   f"gates on accelerator-only surfaces (a tpu/gpu "
                   f"skipif or device probe) — tier-1 runs on CPU, so "
                   f"an accelerator-only assert silently drops the "
                   f"observatory's acceptance criteria from every CI "
                   f"gate; use injectable stats_fn fakes and the "
                   f"typed unavailable fallbacks instead")
    if _FLIGHT_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports the flight recorder/postmortem "
                   f"surface (jaxstream.obs.flight or postmortem) but "
                   f"marks tests slow — the ring semantics, the "
                   f"atomic-bundle round trip, the torn-bundle "
                   f"rejection, the sink byte-identity claim and the "
                   f"resume-lineage proof are tier-1 acceptance "
                   f"criteria and must run in every fast gate; move "
                   f"the slow test to a module that does not import "
                   f"the flight surface")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports the flight recorder/postmortem "
                   f"surface but launches subprocesses — flight/"
                   f"postmortem tests must run IN-PROCESS (drive "
                   f"scripts/postmortem.py through its importable "
                   f"main(); the subprocess SIGKILL capstone lives in "
                   f"a module that reads the bundle JSON directly "
                   f"without importing the surface)")
    if _WARMPOOL_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports the warm-pool surface "
                   f"(jaxstream.serve.warmpool) but marks tests slow "
                   f"— the cache-key invalidation proofs, the "
                   f"torn-entry detection, the headroom refusals and "
                   f"the zero-warm-compile restart claim are tier-1 "
                   f"acceptance criteria and must run in every fast "
                   f"gate; move the slow test to a module that does "
                   f"not import the warm-pool surface")
        if _SUBPROC_USE_RE.search(src):
            yield (f"{rel}: imports the warm-pool surface but "
                   f"launches subprocesses — warm-pool tests must run "
                   f"IN-PROCESS (a child that imports jax would be "
                   f"forced slow by rule 2, dropping the compile-tax "
                   f"proofs from the fast gate)")
    if _SUBPROC_USE_RE.search(src) and _HARD_KILL_RE.search(src) \
            and "slow" not in used:
        yield (f"{rel}: launches subprocesses and references a hard "
               f"kill (SIGKILL/.kill() ) but carries no "
               f"pytest.mark.slow — process-kill forensics tests "
               f"spawn and wait on real serving processes, which the "
               f"fast tier's time budget excludes")
    if _ANALYSIS_IMPORT_RE.search(src):
        if "slow" in used:
            yield (f"{rel}: imports jaxstream.analysis but marks tests "
                   f"slow — the static contract checks (schedule "
                   f"totality, traced-vs-plan collective counts, the "
                   f"broken-fixture regressions) are the machine-"
                   f"checked proof of the race-free exchange claim and "
                   f"must run in every fast gate; move the slow test "
                   f"to a module that does not import "
                   f"jaxstream.analysis")
        if "subprocess" in src:
            yield (f"{rel}: imports jaxstream.analysis but launches "
                   f"subprocesses — contract checks must run "
                   f"IN-PROCESS on the conftest's virtual devices "
                   f"(a subprocess rewrite would be forced slow by "
                   f"rule 2, silently dropping the contract proof "
                   f"from the fast gate); drive scripts/analyze.py "
                   f"through its importable run()/main() instead")


def main(repo_root: str = None) -> int:
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    ini = os.path.join(root, "pytest.ini")
    if not os.path.exists(ini):
        print(f"check_tiers: no pytest.ini at {ini}")
        return 1
    allowed = registered_markers(ini) | BUILTIN_MARKERS
    tests_dir = os.path.join(root, "tests")
    violations = []
    for name in sorted(os.listdir(tests_dir)):
        if not name.endswith(".py") or not name.startswith("test_"):
            continue
        violations += list(lint_file(os.path.join(tests_dir, name),
                                     allowed))
    violations += list(lint_config_docs(root))
    violations += list(lint_sink_kinds(root))
    for v in violations:
        print("check_tiers:", v)
    if not violations:
        print(f"check_tiers: OK ({len(allowed - BUILTIN_MARKERS)} "
              f"registered markers; all subprocess device tests slow)")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
