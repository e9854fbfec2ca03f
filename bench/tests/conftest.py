import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

TINY_CELLS = {
    "tiny-qwen3.tiny-open": ("tiny-qwen3", "tiny-open"),
    "tiny-moe.tiny-closed": ("tiny-moe", "tiny-closed"),
}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding only the files found by name: the repo's
    metric readers and architecture families, the tiny configurations and
    mixes, and a BENCHMARK.json whose cells and metrics name them.  Nothing
    in the harness code knows these names."""
    bench = tmp_path / "bench"
    for sub in ("metrics", "families"):
        shutil.copytree(REPO / "bench" / sub, bench / sub)
    for sub in ("configs", "traffic"):
        (bench / sub).mkdir()
    bj = json.loads((REPO / "BENCHMARK.json").read_text())
    bj["workloads"] = []
    for cell, (conf, mix) in TINY_CELLS.items():
        shutil.copy(DATA / f"{conf}.json", bench / "configs" / f"{conf}.json")
        shutil.copy(DATA / f"{mix}.json", bench / "traffic" / f"{mix}.json")
        bj["workloads"].append({"name": cell, "config": conf, "traffic": mix,
                                "chips": 1, "why": "CPU test"})
    closed = {w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())[
        "workloads"] if "closed" in w["name"]}
    for key in ("end_to_end", "per_layer"):
        for m in bj[key]:
            if "workloads" in m:
                m["workloads"] = ["tiny-moe.tiny-closed" if w in closed
                                  else "tiny-qwen3.tiny-open" for w in m["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    return tmp_path
