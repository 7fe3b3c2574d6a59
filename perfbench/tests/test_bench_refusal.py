"""The command refuses, printing no result, without a TPU, and in a
directory that holds only BENCHMARK.json and the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CMD = ["perfbench/run.py", "--workload", "fig7-fleet-scan", "--seed",
       "3000000000", "--seconds", "1", "--trace", "0"]


def run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *CMD], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def no_result(p):
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_without_a_tpu():
    p = run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    no_result(p)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    no_result(p)


def test_unknown_workload_is_refused():
    sys.path.insert(0, str(ROOT))
    from perfbench import core

    with pytest.raises(core.BenchError, match="no workload"):
        core.load_cell("no-such-cell", 1, 1.0, False)
