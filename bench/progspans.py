"""The serving program's own spans in a profiler trace, and the device idle
under each.

The program writes host spans named ``engine.*`` and ``executor.*``
(``repro.serving.tracing``): ``engine.step`` around a step, and inside it
``engine.admit``, ``engine.batch``, ``executor.forward`` (per stage
``executor.put``, ``executor.launch``, ``executor.wait``, with ``stage``
metadata), ``engine.sample`` and ``engine.retire``.  They share the
profiler's clock with the device's operations, so every idle gap of the
device can be put down to the innermost program span open at its midpoint
(``none`` where none is open).

The window, the busy union and the benchmark's step spans are
``bench/devtrace.py``'s, read from the same file; that module is left as it
is.  A program that writes none of these spans gives no forwards, and the
readers built on :func:`of_run` then report nothing.
"""

from __future__ import annotations

import bisect
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import devtrace

PREFIXES = ("engine.", "executor.")
NONE = "none"
FORWARD = "executor.forward"
LAUNCH = ("executor.put", "executor.launch")
TRACE_DIR = Path(__file__).resolve().parent / ".work" / "trace"   # run.py's


@dataclass
class Span:
    name: str
    start: float                  # seconds on the trace's clock
    end: float
    meta: Dict[str, int] = field(default_factory=dict)


def read_spans(path: str) -> List[Span]:
    """The program's spans of one trace, by start (outer first on a tie)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                                    dict(e.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """Nested spans (sorted as :func:`read_spans` gives them) cut into
    pieces that do not overlap, each named by the innermost span open over
    it."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = 0.0

    def close(until: float):
        nonlocal t
        while stack and stack[-1].end <= until:
            s = stack.pop()
            pieces.append((t, s.end, s.name))
            t = s.end

    for s in spans:
        close(s.start)
        if stack:
            pieces.append((t, s.start, stack[-1].name))
        stack.append(s)
        t = s.start
    close(float("inf"))
    return [p for p in pieces if p[1] > p[0]]


class _Lookup:
    """Which of some non-overlapping intervals holds a time."""

    def __init__(self, pieces):
        self.pieces = sorted(pieces)
        self.starts = [p[0] for p in self.pieces]

    def at(self, t: float):
        i = bisect.bisect_right(self.starts, t) - 1
        return self.pieces[i] if i >= 0 and self.pieces[i][1] >= t else None


@dataclass
class SpanSplit:
    window_s: float
    idle_s: Dict[str, float]            # device idle by innermost program span
    none_in_step_s: float               # idle under no program span, inside a bench.step
    forwards: int                       # executor.forward spans in the window
    launch_s: float                     # their executor.put + executor.launch time
    longest_step_s: float               # the longest bench.step in the window
    longest_step: Dict[str, float]      # its host time by innermost program span


def split(tr: devtrace.Trace, spans: List[Span]) -> SpanSplit:
    """Device idle inside the window (as ``devtrace.summarize`` draws it),
    by the innermost program span at each gap's midpoint, averaged over the
    devices that ran anything, as busy time is."""
    steps = sorted((a, b) for n, a, b in tr.spans if n == devtrace.STEP_SPAN)
    if not steps:
        raise ValueError("the trace holds no benchmark step span")
    w0, w1 = steps[0][0], max(b for _, b in steps)
    pieces = innermost(spans)
    label, in_step = _Lookup(pieces), _Lookup([(a, b, "") for a, b in steps])
    idle: Dict[str, float] = {}
    none_in_step, n_dev = 0.0, 0
    for ops in tr.devices.values():
        inside = [o for o in ops if o.end > w0 and o.start < w1]
        if not inside:
            continue
        n_dev += 1
        merged = devtrace._union([(max(o.start, w0), min(o.end, w1)) for o in inside])
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            p = label.at((a + b) / 2)
            name = NONE if p is None else p[2]
            idle[name] = idle.get(name, 0.0) + b - a
            if p is None and in_step.at((a + b) / 2) is not None:
                none_in_step += b - a
    n = max(n_dev, 1)
    inwin = [s for s in spans if s.start >= w0 and s.end <= w1]
    a, b = max(steps, key=lambda ab: ab[1] - ab[0])
    host: Dict[str, float] = {}
    for p0, p1, name in pieces:
        d = min(p1, b) - max(p0, a)
        if d > 0:
            host[name] = host.get(name, 0.0) + d
    host[NONE] = (b - a) - sum(host.values())
    return SpanSplit(
        window_s=w1 - w0,
        idle_s={k: v / n for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        none_in_step_s=none_in_step / n,
        forwards=sum(1 for s in inwin if s.name == FORWARD),
        launch_s=sum(s.end - s.start for s in inwin if s.name in LAUNCH),
        longest_step_s=b - a,
        longest_step=dict(sorted(host.items(), key=lambda kv: -kv[1])),
    )


def _ms(d: Dict[str, float]) -> Dict[str, float]:
    return {k: round(1e3 * v, 3) for k, v in d.items()}


def log(s: SpanSplit, busy_s: Optional[float] = None):
    """Two ``[bench]`` lines: the window's idle split and the longest step."""
    idle = sum(s.idle_s.values())
    check = "" if busy_s is None else (
        f" (window - busy {1e3 * (s.window_s - busy_s):.3f} ms)")
    print(f"[bench] program spans: device idle {1e3 * idle:.3f} ms{check} by "
          f"innermost program span, ms: {_ms(s.idle_s)}; none inside bench.step "
          f"{1e3 * s.none_in_step_s:.3f} ms; {s.forwards} forwards, put+launch "
          f"{1e3 * s.launch_s:.3f} ms", flush=True)
    print(f"[bench] longest traced step {1e3 * s.longest_step_s:.3f} ms, host "
          f"by innermost program span, ms: {_ms(s.longest_step)}", flush=True)


_cache: Dict[tuple, SpanSplit] = {}


def of_run(run) -> Optional[SpanSplit]:
    """The split of a traced run's trace (the one ``bench/run.py`` wrote),
    reduced and logged once; ``None`` for a run without a trace."""
    if run.trace is None:
        return None
    path = devtrace.find_xplane(str(TRACE_DIR))
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = split(devtrace.read(path), read_spans(path))
        log(_cache[key], run.trace.busy_s)
    return _cache[key]
