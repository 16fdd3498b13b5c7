"""``chip_smoke.py`` rehearsed on the CPU: its phases at a small size with the
kernels in interpret mode, and its refusal to run anywhere but on a TPU."""
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_off_a_tpu(cs, tmp_path, capsys, monkeypatch):
    # keep main's process-wide settings (log dir, compile cache) out of this process
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "tpu_logs"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    assert cs.main(["--out", str(tmp_path)]) == 1
    out, err = capsys.readouterr()
    assert "JAX found no TPU" in err
    for line in out.splitlines():  # no result line
        assert not line.startswith("{")


def test_kernels_phase_small(cs):
    cs.phase_kernels(small=True, interpret=True)


def test_tune_phase_small(cs, tmp_path):
    cs.phase_tune(tmp_path, ("cpu", "cpu"), small=True, interpret=True)
    assert json.loads((tmp_path / "chip.json").read_text())["records"]


def test_serve_phase_tiny(cs, tmp_path):
    cs.phase_serve(tmp_path, tiny=True, prompt=16, gen=16)


def test_fleet_phase_small(cs):
    # one CPU device: every slot shares it, and the placement check still holds
    cs.phase_fleet(4, small=True, interpret=True)
