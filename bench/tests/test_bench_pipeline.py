"""The pipeline cell's own parts: the stage imbalance reader on a hand-made
four-device trace, and a tiny four-stage cell run on four CPU devices, which
comes out correct when sound and not correct with each fault a pipeline can
have (``bench/tests/pp_cpu.py``)."""

import importlib.util
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import devtrace, spec
from bench.devtrace import STEP_SPAN, Op, Trace

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


def reader(name):
    path = HERE.parent / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"test_reader_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def four_stages(busy=(0.4, 0.3, 0.2, 0.1)):
    """Four devices that run in turn inside a 1 s window, each for its share
    of ``busy`` (and once more outside the window, which does not count)."""
    tr = Trace(spans=[(STEP_SPAN, 0.0, 1.0)])
    t = 0.0
    for i, b in enumerate(busy):
        if b:
            tr.devices[f"/device:TPU:{i}"] = [
                Op("fusion", t, t + b / 2, "fusion"),
                Op("fusion", t + b / 2, t + b, "fusion"),
                Op("copy", 1.5, 1.9, "copy")]
        t += b
    return tr


def test_busy_by_device_and_the_stage_imbalance():
    s = devtrace.summarize(four_stages(), {})
    assert s.busy_by_device == pytest.approx(
        {"/device:TPU:0": 0.4, "/device:TPU:1": 0.3, "/device:TPU:2": 0.2,
         "/device:TPU:3": 0.1})
    assert s.busy_s == pytest.approx(0.25)
    run = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=4, trace=s)
    # busiest 0.4 s over the mean 0.25 s
    assert reader("stage_imbalance").reduce(run) == pytest.approx(60.0)
    assert reader("idle_share").reduce(run) == pytest.approx(75.0)
    even = devtrace.summarize(four_stages((0.2,) * 4), {})
    run = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=4, trace=even)
    assert reader("stage_imbalance").reduce(run) == pytest.approx(0.0, abs=1e-9)


def test_a_chip_that_ran_nothing_counts_as_idle():
    s = devtrace.summarize(four_stages((0.3, 0.3, 0.3, 0.0)), {})
    assert len(s.busy_by_device) == 3
    run = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=4, trace=s)
    assert reader("stage_imbalance").reduce(run) == pytest.approx(
        100.0 * (0.3 / (0.9 / 4) - 1.0))


def test_one_chip_or_no_trace_reads_nothing():
    s = devtrace.summarize(four_stages((0.5,)), {})
    one = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=1, trace=s)
    untraced = spec.Run(cell=None, window=None, setup_s=0.0, peak={}, chips=4)
    assert reader("stage_imbalance").reduce(one) is None
    assert reader("stage_imbalance").reduce(untraced) is None


def test_a_pipeline_is_correct_only_when_sound(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    p = subprocess.run([sys.executable, "-m", "bench.tests.pp_cpu", str(tmp_path)],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    runs = {r["case"]: r for r in map(json.loads, (
        ln for ln in p.stdout.splitlines() if ln.startswith("{")))}
    assert list(runs) == ["sound", "state_unchanged", "half_batch_left_out",
                          "token_altered", "exchange_left_out"]
    stages = [int(m.group(1)) for m in re.finditer(r"(\d+) stage\(s\)", p.stdout)]
    assert len(stages) == len(runs) and min(stages) >= 2     # the plan spans chips
    for case, r in runs.items():
        assert r["correct"] is (case == "sound"), (case, r["check"])
        assert r["check"]["tokens_compared"]["value"] > 0
