"""The tier-hygiene lint runs inside the tier-1 gate (round 6).

``scripts/check_tiers.py`` asserts (1) every marker used under tests/
is registered in pytest.ini and (2) multi-device subprocess parities
carry ``slow``.  Wrapping it in a non-slow test makes the fast gate
self-checking — a typo'd marker or an unmarked subprocess test fails
the very gate it would otherwise silently bloat.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import check_tiers  # noqa: E402


def test_repo_is_tier_clean(capsys):
    rc = check_tiers.main(REPO)
    out = capsys.readouterr().out
    assert rc == 0, out


def test_unregistered_marker_detected(tmp_path):
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Concatenated so THIS module doesn't itself trip the lint's regex.
    (tests / "test_x.py").write_text(
        "import pytest\n@pytest." + "mark.slwo\ndef test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1


def test_subprocess_worker_without_slow_detected(tmp_path):
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_w.py").write_text(
        "import subprocess\n"
        "def test_pod():\n"
        "    subprocess.run(['python', 'mh_worker.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module with the marker is clean.
    (tests / "test_w.py").write_text(
        "import subprocess, pytest\n"
        "@pytest.mark.slow\n"
        "def test_pod():\n"
        "    subprocess.run(['python', 'mh_worker.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_builtin_markers_allowed(tmp_path):
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_b.py").write_text(
        "import pytest\n"
        "@pytest.mark.parametrize('x', [1])\n"
        "@pytest.mark.skipif(False, reason='no')\n"
        "def test_a(x):\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


@pytest.mark.parametrize("name", ["slow"])
def test_registered_markers_parsed(name):
    allowed = check_tiers.registered_markers(
        os.path.join(REPO, "pytest.ini"))
    assert name in allowed


def test_async_pipeline_module_with_slow_marker_detected(tmp_path):
    """Rule 4 (round-9 satellite): async-pipeline tests stay tier-1 —
    a module importing jaxstream.io.async_pipeline must carry no slow
    markers."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_ap.py").write_text(
        "import pytest\n"
        "from jaxstream.io.async_pipeline import BackgroundWriter\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module without the marker is clean.
    (tests / "test_ap.py").write_text(
        "from jaxstream.io import async_pipeline\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_obs_importing_module_with_slow_marker_detected(tmp_path):
    """Rule 3 (round-8 observability satellite): telemetry tests stay
    tier-1 — a module importing jaxstream.obs must carry no slow
    markers."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_t.py").write_text(
        "import pytest\n"
        "from jaxstream.obs import metrics\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module without the marker is clean...
    (tests / "test_t.py").write_text(
        "from jaxstream.obs import metrics\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # ...and slow markers elsewhere stay legal.
    (tests / "test_u.py").write_text(
        "import pytest\n"
        "@pytest." + "mark.slow\n"
        "def test_b():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_serve_module_with_slow_marker_detected(tmp_path):
    """Rule 6 (round-11 satellite): serving tests stay tier-1 — a
    module importing jaxstream.serve must carry no slow markers (the
    packing/refill/eviction/backpressure/zero-recompile criteria are
    what certify the server between offline TPU bench runs)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_s.py").write_text(
        "import pytest\n"
        "from jaxstream.serve import EnsembleServer\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module without the marker is clean.
    (tests / "test_s.py").write_text(
        "import jaxstream.serve\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_placement_module_with_subprocess_detected(tmp_path):
    """Rule 7 (round-12 satellite): multichip-serving tests stay in
    the fast tier BY CONSTRUCTION — a module importing the serving
    placement surface may not launch subprocess workers (rule 2 would
    then force it slow, dropping the member-parallel/panel-sharded
    parities from every fast gate); it must ride the conftest's
    in-process fake devices."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_mc.py").write_text(
        "import subprocess\n"
        "from jaxstream.serve.placement import plan_placement\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'mc_worker.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module without the subprocess launch is clean.
    (tests / "test_mc.py").write_text(
        "from jaxstream.serve.placement import plan_placement\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # ...and the `from jaxstream.serve import plan_placement` spelling
    # is caught too.
    (tests / "test_mc.py").write_text(
        "import subprocess\n"
        "from jaxstream.serve import plan_placement\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'mc_worker.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1


def test_analysis_module_rules_detected(tmp_path):
    """Rule 8 (round-13 satellite): contract-checker tests stay
    non-slow AND in-process — a module importing jaxstream.analysis
    may neither carry slow markers nor launch subprocesses (the
    static proof of the race-free schedule must ride every fast
    gate)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked analysis module trips the lint.
    (tests / "test_an.py").write_text(
        "import pytest\n"
        "from jaxstream.analysis import run_all\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess-launching analysis module trips it too.
    (tests / "test_an.py").write_text(
        "import subprocess\n"
        "import jaxstream.analysis\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'scripts/analyze.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module, unmarked and in-process, is clean.
    (tests / "test_an.py").write_text(
        "from jaxstream.analysis import contracts\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # The `from jaxstream import analysis` spelling is caught too.
    (tests / "test_an.py").write_text(
        "import pytest\n"
        "from jaxstream import analysis\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1


def test_gateway_module_rules_detected(tmp_path):
    """Rule 9 (round-14 satellite): gateway/loadgen tests stay
    non-slow and bind loopback only — a module importing
    jaxstream.gateway or jaxstream.loadgen may neither carry slow
    markers nor reference the wildcard bind address."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked gateway module trips the lint.
    (tests / "test_g.py").write_text(
        "import pytest\n"
        "from jaxstream.gateway import Gateway\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # A wildcard bind trips it too (concatenated so THIS module does
    # not itself contain the literal).
    (tests / "test_g.py").write_text(
        "from jaxstream.loadgen import run_load\n"
        "def test_a():\n"
        "    run_load('0.0." + "0.0', 80, [])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Loopback-bound, unmarked gateway+loadgen module is clean.
    (tests / "test_g.py").write_text(
        "from jaxstream.gateway import Gateway\n"
        "from jaxstream import loadgen\n"
        "def test_a():\n"
        "    Gateway(host='127.0.0.1')\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # Real addresses merely CONTAINING the substring stay clean
    # (anchored regex): 10.0.0.0/8 is not a wildcard bind.
    (tests / "test_g.py").write_text(
        "from jaxstream.gateway import Gateway\n"
        "PRIVATE_RANGE = '10.0." + "0.0/8'\n"
        "def test_a():\n"
        "    Gateway(host='127.0.0.1')\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_precision_module_with_slow_marker_detected(tmp_path):
    """Rule 5 (round-10 satellite): precision-parity tests stay tier-1
    — a module importing jaxstream.ops.pallas.precision must carry no
    slow markers (the policy-off bitwise / truncation-budget parities
    are what certify the ladder between offline TPU bench runs)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_p.py").write_text(
        "import pytest\n"
        "from jaxstream.ops.pallas.precision import encode_strips\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module without the marker is clean.
    (tests / "test_p.py").write_text(
        "from jaxstream.ops.pallas import precision\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_plan_module_rules_detected(tmp_path):
    """Rule 10b (round-16 satellite): plan/pipeline tests stay
    non-slow and in-process — a module importing jaxstream.plan may
    neither carry slow markers nor launch subprocesses (the rule-table
    rejections, the enumerated plan space and the proof-stamp checks
    must ride every fast gate)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_pl.py").write_text(
        "import pytest\n"
        "from jaxstream.plan import enumerate_plans\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess USAGE trips it too...
    (tests / "test_pl.py").write_text(
        "import subprocess\n"
        "from jaxstream.plan import plan_for\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'scripts/plan.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # ...but a docstring merely MENTIONING the word does not.
    (tests / "test_pl.py").write_text(
        '"""No subprocess startup cost here."""\n'
        "from jaxstream.plan import plan_for\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_trace_module_rules_detected(tmp_path):
    """Rule 11 (round-17 satellite): tracing/dashboard tests stay
    non-slow, in-process and loopback-only — a module importing
    jaxstream.obs.trace/registry or telemetry_dashboard may not carry
    slow markers, launch subprocesses, or reference a wildcard bind
    (the span-completeness proof and the metrics scrape round-trip
    must ride every fast gate)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked tracing module trips the lint.
    (tests / "test_tr.py").write_text(
        "import pytest\n"
        "from jaxstream.obs import trace as obs_trace\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess USAGE around the dashboard trips it too.
    (tests / "test_tr.py").write_text(
        "import subprocess\n"
        "import telemetry_dashboard\n"
        "def test_a():\n"
        "    subprocess.run(['python', "
        "'scripts/telemetry_dashboard.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # A wildcard bind trips it (concatenated so THIS module does not
    # itself contain the literal).
    (tests / "test_tr.py").write_text(
        "from jaxstream.obs.registry import parse_exposition\n"
        "def test_a():\n"
        "    parse_exposition('x{host=\"0.0." + "0.0\"} 1')\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Loopback-bound, unmarked, in-process tracing module is clean —
    # including the registry-name import form and the dashboard's
    # importable main().
    (tests / "test_tr.py").write_text(
        "from jaxstream.obs.registry import MetricsRegistry\n"
        "import telemetry_dashboard\n"
        "def test_a():\n"
        "    telemetry_dashboard.main(['s.jsonl', '--once',"
        " '--json'])\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # A module importing only non-tracing obs symbols is NOT claimed
    # by rule 11 (rule 3 still keeps it non-slow).
    (tests / "test_tr.py").write_text(
        "from jaxstream.obs.sink import read_records\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_da_module_rules_detected(tmp_path):
    """Rule 12 (round-18 satellite): assimilation tests stay non-slow
    and in-process — a module importing jaxstream.da may not carry
    slow markers or launch subprocesses (the closed-loop forecast
    claim and the cycle byte-determinism proof must ride every fast
    gate)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked da module trips the lint.
    (tests / "test_d.py").write_text(
        "import pytest\n"
        "from jaxstream.da import run_cycle\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess USAGE around the assimilate CLI trips it too.
    (tests / "test_d.py").write_text(
        "import subprocess\n"
        "import jaxstream.da\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'scripts/assimilate.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Unmarked, in-process da module is clean (incl. the
    # from-jaxstream import form).
    (tests / "test_d.py").write_text(
        "from jaxstream import da\n"
        "def test_a():\n    da.run_cycle\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # 'dashboard'-style names must not false-positive the da regex.
    (tests / "test_d.py").write_text(
        "from jaxstream.gateway import protocol\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_config_doc_drift_detected(tmp_path):
    """Rule 10a (round-16 satellite): every _SECTIONS key in
    jaxstream/config.py must appear as a top-level key in a fenced
    USAGE.md config block — a new config section whose docs never
    landed fails the gate."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    (tmp_path / "tests").mkdir()
    pkg = tmp_path / "jaxstream"
    pkg.mkdir()
    docs = tmp_path / "docs"
    docs.mkdir()
    (pkg / "config.py").write_text(
        '_SECTIONS = {\n    "grid": 1,\n    "serve": 2,\n}\n')
    (docs / "USAGE.md").write_text(
        "# guide\n\n```yaml\ngrid:\n  n: 96\n```\n")
    assert check_tiers.main(str(tmp_path)) == 1   # 'serve' undocumented
    (docs / "USAGE.md").write_text(
        "# guide\n\n```yaml\ngrid:\n  n: 96\n```\n\n"
        "```yaml\nserve:\n  buckets: '1,4'\n```\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # Repos without the config/docs pair skip rule 10a (the lint's
    # other rules still run on the synthetic tmp repos above).
    import os
    os.remove(str(docs / "USAGE.md"))
    assert check_tiers.main(str(tmp_path)) == 0


def test_real_repo_sections_all_documented():
    """Acceptance: the live tree passes rule 10a and the parsed
    section list matches the importable config surface."""
    sections = check_tiers.config_sections(
        os.path.join(REPO, "jaxstream", "config.py"))
    from jaxstream.config import _SECTIONS

    assert sections == list(_SECTIONS)
    documented = check_tiers.documented_sections(
        os.path.join(REPO, "docs", "USAGE.md"))
    assert set(sections) <= documented


def test_perf_obs_module_rules_detected(tmp_path):
    """Rule 13a (round-19 satellite): perf-observatory test modules
    stay non-slow, in-process, and CPU-honest (no accelerator-only
    gating)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_perf.py").write_text(
        "import pytest\nfrom jaxstream.obs import perf\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    (tests / "test_perf.py").write_text(
        "import subpro" + "cess\nimport jaxstream.obs.perf\n"
        "def test_a():\n    subpro" + "cess.run(['true'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    (tests / "test_perf.py").write_text(
        "import pytest\nimport jax\nfrom jaxstream.obs import "
        "measure_cost\n"
        "@pytest.mark.skipif(not jax.devices('tp" + "u'), "
        "reason='needs accelerator')\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    (tests / "test_perf.py").write_text(
        "import perf_ledger\nfrom jaxstream.obs import perf\n"
        "def test_a():\n    perf_ledger.main(['check'])\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_flight_module_rules_detected(tmp_path):
    """Rule 14 (round-20 satellite): flight-recorder/postmortem tests
    stay non-slow and in-process, while hard-kill forensics tests must
    ride the slow tier — a module importing jaxstream.obs.flight or
    postmortem may not carry slow markers or launch subprocesses, and
    a module that spawns subprocesses AND references a hard kill must
    carry pytest.mark.slow."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked flight module trips the lint (14a).
    (tests / "test_f.py").write_text(
        "import pytest\n"
        "from jaxstream.obs.flight import FlightRecorder\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess USAGE in a postmortem-importing module trips it too.
    (tests / "test_f.py").write_text(
        "import subprocess\n"
        "import postmortem\n"
        "def test_a():\n"
        "    subprocess.run(['python', 'scripts/postmortem.py'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Unmarked, in-process flight module is clean — including the
    # from-obs import form.
    (tests / "test_f.py").write_text(
        "from jaxstream.obs import flight\n"
        "def test_a():\n    flight.RECORDER.dump()\n")
    assert check_tiers.main(str(tmp_path)) == 0
    # The hard-kill half (14b): subprocess + SIGKILL without slow
    # trips (concatenated so THIS module's own marker set is not
    # what keeps it clean).
    (tests / "test_k.py").write_text(
        "import signal, subprocess, sys\n"
        "def test_a():\n"
        "    p = subprocess.Popen([sys.executable, 'scripts/serve.py'])\n"
        "    p.send_signal(signal.SIGK" + "ILL)\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # ...and the .kill( spelling is caught too.
    (tests / "test_k.py").write_text(
        "import subprocess, sys\n"
        "def test_a():\n"
        "    p = subprocess.Popen([sys.executable, 'scripts/serve.py'])\n"
        "    p.ki" + "ll()\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # The same module slow-marked is clean.
    (tests / "test_k.py").write_text(
        "import pytest, signal, subprocess, sys\n"
        "pytestmark = pytest." + "mark.slow\n"
        "def test_a():\n"
        "    p = subprocess.Popen([sys.executable, 'scripts/serve.py'])\n"
        "    p.send_signal(signal.SIGK" + "ILL)\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_warmpool_module_rules_detected(tmp_path):
    """Rule 15 (round-21 satellite): warm-pool tests stay non-slow
    and in-process — a module importing
    jaxstream.serve.warmpool may not carry slow markers or launch
    subprocesses."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    # Slow-marked warmpool module trips the lint (15a).
    (tests / "test_w.py").write_text(
        "import pytest\n"
        "from jaxstream.serve.warmpool import WarmPool\n"
        "@pytest." + "mark.slow\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Subprocess USAGE in a warmpool-importing module trips it too.
    (tests / "test_w.py").write_text(
        "import subprocess\n"
        "from jaxstream.serve import warmpool\n"
        "def test_a():\n"
        "    subprocess.run(['python', '-c', 'pass'])\n")
    assert check_tiers.main(str(tmp_path)) == 1
    # Unmarked, in-process warmpool module is clean — including the
    # from-serve symbol import forms.
    (tests / "test_w.py").write_text(
        "from jaxstream.serve import WarmPool, HeadroomRefused\n"
        "def test_a():\n    pass\n")
    assert check_tiers.main(str(tmp_path)) == 0


def test_sink_kind_rendering_drift_detected(tmp_path):
    """Rule 13b: a sink kind registered in RECORD_KINDS but missing
    from either operator tool's RENDERED_KINDS fails the gate (the
    loud unrendered-kinds footer contract)."""
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    slow: the slow tier\n")
    (tmp_path / "tests").mkdir()
    obs = tmp_path / "jaxstream" / "obs"
    obs.mkdir(parents=True)
    scripts = tmp_path / "scripts"
    scripts.mkdir()
    (obs / "sink.py").write_text(
        'RECORD_KINDS: dict = {\n    "segment": ("step",),\n'
        '    "memory": ("devices",),\n}\n')
    (scripts / "telemetry_report.py").write_text(
        'RENDERED_KINDS = frozenset({\n    "segment", "memory",\n})\n')
    # Dashboard missing 'memory' -> violation.
    (scripts / "telemetry_dashboard.py").write_text(
        'RENDERED_KINDS = frozenset({\n    "segment",\n})\n')
    assert check_tiers.main(str(tmp_path)) == 1
    (scripts / "telemetry_dashboard.py").write_text(
        'RENDERED_KINDS = frozenset({\n    "segment", "memory",\n})\n')
    assert check_tiers.main(str(tmp_path)) == 0


def test_real_repo_sink_kinds_all_rendered():
    """Acceptance: the live tree's RECORD_KINDS (memory/perf
    included) are rendered by both operator tools, per rule 13b."""
    assert list(check_tiers.lint_sink_kinds(REPO)) == []
    # importlib spelling: THIS module embeds literal slow-marker
    # strings for the rule tests above, so a plain obs import here
    # would (correctly) trip rule 3 on this very file.
    import importlib

    kinds = importlib.import_module("jaxstream.obs.sink").RECORD_KINDS
    assert "memory" in kinds and "perf" in kinds
