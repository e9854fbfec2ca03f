"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers the
metrics read: busy time, idle gaps by what the host was doing, the time of
one kernel, and the device operations that took most time.

The traced window runs from the start of the first benchmark step span to
the end of the last.  Device operations are the events of each device
plane's ``XLA Ops`` line; busy time is the union of their intervals inside
the window, averaged over the devices that ran anything.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

STEP_SPAN = "bench.step"         # one engine step (StepTraceAnnotation)
FORWARD_SPAN = "bench.forward"   # the executor's forward inside a step
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclass
class Op:
    name: str
    start: float                  # seconds on the trace's clock
    end: float
    text: str = ""                # name and string stats, for matching (the
                                  # HLO line is left out: it names operands)


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str) -> Trace:
    """Device operations and the benchmark's host spans of one trace."""
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    strs = " ".join(str(v) for _, v in e.stats if isinstance(v, str))
                    name = label(e.name)
                    ops.append(Op(name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  f"{name} {strs}"))
            tr.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (STEP_SPAN, FORWARD_SPAN):
                        tr.spans.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
    return tr


def label(name: str) -> str:
    """A device op's name as the trace gives it is its whole HLO line
    (``%fusion.23 = bf16[16,64,151936]{...} fusion(...)``): keep the op
    without its instance number, and its result shape."""
    lhs, _, rhs = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
    shape = rhs.split("{")[0].split(" ")[0] if rhs else ""
    return f"{base} {shape}".strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over active devices
    busy_by_device: Dict[str, float]    # device plane -> busy seconds
    kernel_s: Dict[str, float]          # pattern -> seconds, summed over devices
    kernel_calls: Dict[str, int]
    device_ops: List[List]              # [name, seconds] by time, top 10
    idle_gaps: List[List]               # [host activity, seconds], top 10


def _host_activity(spans, t: float) -> str:
    inside = [n for n, a, b in spans if a <= t <= b]
    if FORWARD_SPAN in inside:
        return "host in executor.forward"
    if STEP_SPAN in inside:
        return "host in engine step, outside forward"
    return "host between steps"


def summarize(tr: Trace, kernels: Dict[str, str]) -> Summary:
    """``kernels`` maps a label to a regex matched against each operation's
    name and string stats; a label that matches nothing raises."""
    steps = [(a, b) for n, a, b in tr.spans if n == STEP_SPAN]
    if not steps:
        raise ValueError("the trace holds no benchmark step span")
    w0, w1 = min(a for a, _ in steps), max(b for _, b in steps)
    busy: Dict[str, float] = {}
    by_name: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    ksec = {k: 0.0 for k in kernels}
    kcalls = {k: 0 for k in kernels}
    pats = {k: re.compile(p) for k, p in kernels.items()}
    for dev, ops in tr.devices.items():
        inside = [o for o in ops if o.end > w0 and o.start < w1]
        if not inside:
            continue
        merged = _union([(max(o.start, w0), min(o.end, w1)) for o in inside])
        busy[dev] = sum(b - a for a, b in merged)
        for o in inside:
            d = min(o.end, w1) - max(o.start, w0)
            by_name[o.name] = by_name.get(o.name, 0.0) + d
            for k, p in pats.items():
                if p.search(o.text):
                    ksec[k] += d
                    kcalls[k] += 1
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                lab = _host_activity(tr.spans, (a + b) / 2)
                gaps[lab] = gaps.get(lab, 0.0) + b - a
    n_dev = len(busy)
    if n_dev == 0:
        raise ValueError("no device operation ran inside the traced window")
    missing = [k for k, c in kcalls.items() if c == 0]
    if missing:
        raise ValueError(f"kernel(s) {missing} not found in the trace")
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=w1 - w0,
        busy_s=sum(busy.values()) / n_dev,
        busy_by_device=busy,
        kernel_s=ksec,
        kernel_calls=kcalls,
        device_ops=[[n, s / n_dev] for n, s in top],
        idle_gaps=[[n, s / n_dev] for n, s in
                   sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    )


def reduce_dir(log_dir: str, kernels: Dict[str, str]) -> Summary:
    return summarize(read(find_xplane(log_dir)), kernels)
