"""A tiny 4-stage pipeline cell run on four CPU devices, sound and with each
fault a pipeline cell can have planted under its timed path; one JSON line
per run.  JAX must see four devices, so this runs in a process of its own:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.pp_cpu <empty dir>
"""

import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import run, spec
from bench.tests.test_bench_faults import (PEAK, half_batch_left_out,
                                           state_unchanged, token_altered)

HERE = Path(__file__).resolve().parent
CELL = "tiny-pp4.tiny-closed"


def exchange_left_out(eng):
    """Every stage after the first gets zeros in place of the hidden states
    the stage before it sent."""
    ex = eng.executor
    inner = ex._fn

    def fn(si):
        f = inner(si)
        if si == 0:
            return f
        return lambda sp, x, *rest: f(sp, jnp.zeros_like(x), *rest)

    ex._fn = fn


FAULTS = {"sound": None, "state_unchanged": state_unchanged,
          "half_batch_left_out": half_batch_left_out,
          "token_altered": token_altered, "exchange_left_out": exchange_left_out}


def checkout(root: Path):
    """The tiny pipeline cell as files, beside the repo's readers and
    families."""
    bench = root / "bench"
    for sub in ("metrics", "families"):
        shutil.copytree(HERE.parent / sub, bench / sub)
    for sub, name in (("configs", "tiny-pp4"), ("traffic", "tiny-closed")):
        (bench / sub).mkdir()
        shutil.copy(HERE / "data" / f"{name}.json", bench / sub / f"{name}.json")
    bj = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    bj["workloads"] = [{"name": CELL, "config": "tiny-pp4", "traffic": "tiny-closed",
                        "chips": 4, "why": "CPU test"}]
    for key in ("end_to_end", "per_layer"):
        bj[key] = [dict(m, workloads=[CELL]) for m in bj[key]
                   if "qwen3-14b-pp4.chat-closed" in m.get("workloads", [CELL])]
    (root / "BENCHMARK.json").write_text(json.dumps(bj))


def main(root: str) -> int:
    root = Path(root)
    checkout(root)
    cell = spec.load_cell(root, CELL)
    for name, fault in FAULTS.items():
        out = run.run_cell(cell, 2**31 + 303, 2.0, False, jax.devices()[:4], PEAK,
                           t_start=time.perf_counter(), fault=fault)
        print(json.dumps({"case": name, "correct": out["correct"],
                          "check": out["check"], "metrics": out["metrics"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
