"""A second model configuration enters the serving harness with new files
and entries only.

A copy of the benchmark gains what a model configuration brings: its
configuration file, its reference module (a tiny Qwen3-MoE-style decoder,
``data/second_model/``), its traffic and limits, and entries in
``BENCHMARK.json``, the cell named in each serving metric's
``workloads``.  No file of the copy changes.  A CPU rehearsal through
``engine_cell`` then runs the cell correct, and the control of its
reference fails its limit."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "second_model"
CELL = "tiny-moe-steady"
ADDED = {"configs/tiny-moe.json": "tiny-moe.json",
         "reference/tiny_moe.py": "tiny_moe.py",
         "limits/tiny-moe-steady.json": "tiny-moe-steady.json",
         "traffic/steady-48t-x10.json": "steady-48t-x10.json"}

REHEARSAL = """
import json, time
from perfbench import control, core, engine_cell
cell = core.load_cell("tiny-moe-steady", 2 ** 35 + 3, 1.5, False)
core.emit(engine_cell.run(cell, time.perf_counter()))
print(json.dumps(control.readings(cell)))
"""


def files(root: Path) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def add_model(copy: Path) -> None:
    for dst, src in ADDED.items():
        shutil.copy(DATA / src, copy / "perfbench" / dst)
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-moe", "source": "https://huggingface.co/Qwen/"
        "Qwen3-30B-A3B", "file": "perfbench/configs/tiny-moe.json",
        "reduced": [], "why": "a CPU test"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-moe", "traffic": "steady-48t-x10",
        "chips": 1, "why": "a CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "slice-48t-steady" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_second_model_enters_with_files_only(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = files(tmp_path / "perfbench")
    add_model(tmp_path)
    after = files(tmp_path / "perfbench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(ADDED)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", REHEARSAL], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    run_line, readings = [json.loads(x) for x in
                          p.stdout.strip().splitlines()[-2:]]
    assert run_line["correct"] is True, run_line["checks"]
    assert run_line["failed"] == 0 and run_line["attempted"] > 0
    assert {"ttft_p90_ms", "tbt_p99_ms", "tokens_per_s",
            "setup_s"} <= set(run_line["metrics"])
    assert run_line["device"]["platform"] == "cpu"
    # the reference module is the copy's, and its float8 control fails
    limit = json.loads((DATA / "tiny-moe-steady.json").read_text())
    assert readings["decodes"] > 0
    assert readings["logit_gap"] <= limit["logit_gap"]
    assert readings["control"]["logit_gap"] > 10 * limit["logit_gap"]
