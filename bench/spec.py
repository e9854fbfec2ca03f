"""A cell as data: its ``BENCHMARK.json`` entry, the configuration and traffic
files it names, and the reader of each metric it reports.  Everything is
found by name, never from a table in code:

* configuration ``<config>``: ``bench/configs/<config>.json``;
* traffic mix ``<traffic>``: ``bench/traffic/<traffic>.json``;
* metric ``<name>``: ``bench/metrics/<name>.py``, or, for a name with a
  suffix such as ``mixed_step_ms.batch``, the reader of its base
  ``bench/metrics/mixed_step_ms.py``.  A reader defines
  ``reduce(run) -> float | None`` (``None``: nothing to read in this run)
  and may define ``KERNELS``, a map of labels to regexes that name device
  operations in the trace;
* architecture family ``<model_type>`` (the configuration's own key):
  ``bench/families/<model_type>.py`` (see ``bench/families/__init__.py``).
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional


@dataclass
class Metric:
    name: str
    unit: str
    end_to_end: bool
    reader: ModuleType


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    metrics: List[Metric]

    def reported(self, trace: bool) -> List[Metric]:
        """End-to-end metrics in a plain run, per-layer ones in a traced run."""
        return [m for m in self.metrics if m.end_to_end != trace]

    def kernels(self) -> Dict[str, str]:
        out: Dict[str, str] = {}
        for m in self.reported(trace=True):
            out.update(getattr(m.reader, "KERNELS", {}))
        return out


@dataclass
class Run:
    """What a metric reader reads (see ``bench/run.py``)."""

    cell: Cell
    window: Any                     # bench.loop.Window
    setup_s: float
    peak: Dict[str, float]          # this chip's published peaks
    chips: int
    trace: Any = None               # bench.devtrace.Summary of a traced run


FAMILY_API = ("program_config", "experts_held", "layout", "head_dim",
              "active_matmul_params", "step_flops", "attention_call",
              "kv_bytes_per_token", "layer")
_families: Dict[str, ModuleType] = {}


def _load(path: Path, name: str, needs: tuple) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in needs if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{path} defines no {', '.join(missing)}")
    return mod


def _load_reader(bench: Path, name: str) -> ModuleType:
    for stem in (name, name.split(".")[0]):
        path = bench / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load(path, f"bench_metric_{stem}", ("reduce",))
    raise FileNotFoundError(f"no reader for metric {name!r} under {bench / 'metrics'}")


def load_family(bench: Path, model_type: str) -> ModuleType:
    """The family module ``bench/families/<model_type>.py``, loaded anew;
    ``family(model_type)`` returns it from then on."""
    path = Path(bench) / "families" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no family module for model_type {model_type!r}: {path} does not exist")
    _families[model_type] = _load(path, f"bench_family_{model_type}", FAMILY_API)
    return _families[model_type]


def family(model_type: str) -> ModuleType:
    """The family module of ``model_type``: the one last loaded, else the
    harness's own ``bench/families/<model_type>.py``."""
    if model_type not in _families:
        load_family(Path(__file__).resolve().parent, model_type)
    return _families[model_type]


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    root = Path(root)
    bench_json = _json(root / "BENCHMARK.json")
    (entry,) = [w for w in bench_json["workloads"] if w["name"] == name] or [None]
    if entry is None:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
    bench = root / "bench"
    metrics = []
    for key, e2e in (("end_to_end", True), ("per_layer", False)):
        for m in bench_json[key]:
            if name in m.get("workloads", [name]):
                metrics.append(Metric(m["name"], m["unit"], e2e,
                                      _load_reader(bench, m["name"])))
    config = _json(bench / "configs" / f"{entry['config']}.json")
    load_family(bench, config["model_type"])
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=_json(bench / "traffic" / f"{entry['traffic']}.json"),
        metrics=metrics,
    )


def metric_values(run: Run, trace: bool) -> Dict[str, Dict[str, Any]]:
    """Each reported metric that found something to read, with its unit."""
    out: Dict[str, Dict[str, Any]] = {}
    for m in run.cell.reported(trace):
        v: Optional[float] = m.reader.reduce(run)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out
