"""chip_smoke.py on the CPU: it refuses to run, and its phases still work.

The script itself never runs a phase off the TPU.  These tests drive
its phase functions at a tiny size with interpret-mode Pallas — the
first rehearsal of the on-chip-measurement guide — so an API change on
the main path breaks here, not on the chip.
"""

import json
import os

import pytest
import yaml

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code != 0
    captured = capsys.readouterr()
    assert "no TPU found" in captured.err
    assert '"ok"' not in captured.out


@pytest.fixture
def tiny(monkeypatch, tmp_path):
    """chip_smoke shrunk to C8 interpret mode on this host's CPU."""
    import jax

    cpu = {"backend": "pallas_interpret"}
    monkeypatch.setattr(chip_smoke, "_tpu_devices",
                        lambda count: jax.devices()[:count])
    monkeypatch.setattr(chip_smoke, "TC5_C384", chip_smoke._cfg(
        chip_smoke.TC5_C384, grid={"n": 8}, model=cpu,
        parallelization={"device_type": "cpu"}))
    monkeypatch.setattr(chip_smoke, "SERVE_C96", chip_smoke._cfg(
        chip_smoke.SERVE_C96, grid={"n": 8}, model=cpu,
        parallelization={"device_type": "cpu"}))
    monkeypatch.setattr(chip_smoke, "SEGMENT", 2)
    monkeypatch.setattr(chip_smoke, "FORECAST_SEGMENTS", 2)
    monkeypatch.setattr(chip_smoke, "GALEWSKY_STEPS", 2)
    with open(os.path.join(REPO, "examples", "configs",
                           "galewsky_c384.yaml")) as fh:
        g = yaml.safe_load(fh)
    g["grid"]["n"] = 8
    g["model"].update(cpu)
    g["parallelization"]["device_type"] = "cpu"
    cfg_dir = tmp_path / "examples" / "configs"
    cfg_dir.mkdir(parents=True)
    (cfg_dir / "galewsky_c384.yaml").write_text(yaml.safe_dump(g))
    monkeypatch.setattr(chip_smoke, "REPO", str(tmp_path))
    return tmp_path


def test_phases_pass_at_tiny_size(tiny, capsys):
    assert chip_smoke.main(["--out", str(tiny / "out")]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    for phase in ("forecast", "galewsky", "reference", "serve"):
        assert any(ln.startswith(f"{phase}: ok ") for ln in lines), phase
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
