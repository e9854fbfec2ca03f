"""Cells are data: a configuration, a traffic mix and a metric reader added as
new files resolve without a code edit; and the entry point refuses to run
without a TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import loop, spec

REPO = Path(__file__).resolve().parents[2]

READER = '''"""Rows carried per step, a reader added as a file."""


def reduce(run):
    steps = [s for s in run.window.steps if s.q_lens is not None]
    return sum(int(s.q_lens.sum()) for s in steps) / len(steps) if steps else None
'''


def test_new_files_define_a_cell(tiny_root):
    bench = tiny_root / "bench"
    (bench / "configs" / "other.json").write_text(
        (bench / "configs" / "tiny-qwen3.json").read_text())
    (bench / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 9.0,
         "prompt": {"median": 8, "sigma": 0.1, "min": 4, "max": 16},
         "output": {"median": 4, "sigma": 0.1, "min": 2, "max": 8}}))
    (bench / "metrics" / "rows_per_step.py").write_text(READER)
    bj = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "other.bursty", "config": "other",
                            "traffic": "bursty", "chips": 1, "why": "t"})
    bj["per_layer"].append({"name": "rows_per_step.bursty", "unit": "rows",
                            "better": "higher", "source": "program_counter",
                            "layer": "engine step", "moves": "itl_mean_ms",
                            "workloads": ["other.bursty"]})
    bj["end_to_end"][1]["workloads"].append("other.bursty")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = spec.load_cell(tiny_root, "other.bursty")
    assert cell.traffic["rate_per_s"] == 9.0
    assert cell.config["hidden_size"] == 256
    assert [m.name for m in cell.reported(trace=False)] == ["setup_s", "ttft_p80_ms"]
    assert [m.name for m in cell.reported(trace=True)] == ["rows_per_step.bursty"]
    import numpy as np

    win = loop.Window(seconds=1.0, end=1.0, steps=[
        loop.Step(0, 1, 1, 16, np.array([16, 1, 0]), np.zeros(3, np.int32))])
    run = spec.Run(cell=cell, window=win, setup_s=3.0, peak={}, chips=1)
    assert spec.metric_values(run, trace=True) == {
        "rows_per_step.bursty": {"value": 17.0, "unit": "rows"}}
    assert spec.metric_values(run, trace=False)["setup_s"]["value"] == 3.0


def test_a_metric_without_a_reader_is_an_error(tiny_root):
    bj = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bj["per_layer"].append({"name": "nowhere.chat", "unit": "ms", "better": "lower",
                            "source": "program_span", "layer": "x", "moves": "itl_mean_ms"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bj))
    with pytest.raises(FileNotFoundError):
        spec.load_cell(tiny_root, "tiny-qwen3.tiny-open")


def test_the_repo_cells_resolve():
    bj = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bj["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        e2e = {m.name for m in cell.reported(trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        moves = {m["name"]: m["moves"] for m in bj["per_layer"]}
        layer = [m.name for m in cell.reported(trace=True)]
        assert layer and all(moves[n] in e2e for n in layer)


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-14b-10L.chat-poisson",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_refuses_a_cpu(tmp_path):
    p = _run(REPO)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]

    # the benchmark's files alone, without the program, give no result either
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
