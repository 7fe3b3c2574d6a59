"""``chip_smoke.py``'s phases rehearsed on the CPU at reduced size (Pallas
in interpret mode), and its refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert "found platform 'cpu'" in p.stderr
    assert '"ok"' not in p.stdout


def _bf16_reduced(smoke):
    from repro.configs.base import get_config, reduced

    return reduced(get_config(smoke.ARCH), n_layers=4, dtype="bfloat16",
                   param_dtype="bfloat16")


@pytest.mark.parametrize("phase", [
    "serve-model", "model-consistency", "serve-pallas-tick", "fleet-scan"])
def test_phase_at_reduced_size(smoke, phase, capsys):
    if phase == "serve-model":
        smoke.serve_model(smoke.ARCH, True, 16, 256, smoke.SERVE_DURATION_S)
    elif phase == "model-consistency":
        smoke.model_consistency(_bf16_reduced(smoke), 4, 8,
                                smoke.CONSISTENCY_TOL)
    elif phase == "serve-pallas-tick":
        smoke.serve_pallas_tick(256, 0.5, (64, 1000), 4)
    else:
        smoke.fleet_scan(20, 2, 10.0)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith(f"[{phase}] ")]
    assert len(lines) == 1, lines


def test_failed_check_exits_nonzero(smoke):
    with pytest.raises(SystemExit) as e:
        smoke.check(False, "phase", "what")
    assert e.value.code != 0
