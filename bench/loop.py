"""The measured window: drive a serving engine with one traffic schedule and
record what its users and each layer see.

Pure Python against the engine's surface (``submit``, ``step``,
``executor.forward`` and the program's request objects), so the tests drive
it with a fake engine and a fake clock.  Every time is in seconds from the start of
the window.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile of all ``values`` (linear interpolation
    between order statistics); ``None`` for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclass
class Req:
    """One request of the schedule and what happened to it."""

    rid: int
    due: float                    # when the user sends it
    prompt: List[int]
    out_len: int
    submitted: Optional[float] = None
    admitted: Optional[float] = None    # start of the step that admitted it
    token_times: List[float] = field(default_factory=list)
    engine_req: object = None     # the program's Request
    done: bool = False
    shed: bool = False


@dataclass
class Step:
    t0: float
    t1: float
    fwd_s: float                  # host wall time inside executor.forward
    width: int                    # S of the [slots, S] step (0: no forward)
    q_lens: Optional[np.ndarray] = None
    cache_pos: Optional[np.ndarray] = None


@dataclass
class Window:
    """What the window recorded; the metric readers take it from here."""

    seconds: float                # the asked length
    end: float = 0.0              # the measured length (the last step may run over)
    reqs: List[Req] = field(default_factory=list)
    steps: List[Step] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)
    trace_steps: Optional[range] = None   # indices of the traced steps

    def due_in_window(self) -> List[Req]:
        return [r for r in self.reqs if 0.0 <= r.due <= self.end]

    def slots_held(self) -> tuple:
        """Requests holding a slot when the window opens, and the mean
        number over the window (admission to last token, or to the end)."""
        held, at_open = 0.0, 0
        for r in self.reqs:
            if r.admitted is None:
                continue
            last = r.token_times[-1] if r.done and r.token_times else self.end
            if r.admitted <= 0.0 < last:
                at_open += 1
            held += max(0.0, min(last, self.end) - max(r.admitted, 0.0))
        return at_open, held / self.end if self.end else 0.0

    def ttft(self) -> List[float]:
        """Due time to first token of every request due in the window; one
        without a token by the end counts at (end - due)."""
        return [
            (r.token_times[0] if r.token_times and r.token_times[0] <= self.end
             else self.end) - r.due
            for r in self.due_in_window()
        ]

    def queue_wait(self) -> List[float]:
        return [
            (r.admitted if r.admitted is not None else self.end) - r.due
            for r in self.due_in_window()
        ]

    def itl(self) -> List[float]:
        """Gaps between consecutive output tokens of every request, both
        tokens inside the window."""
        gaps = []
        for r in self.reqs:
            t = [x for x in r.token_times if 0.0 <= x <= self.end]
            gaps.extend(b - a for a, b in zip(t, t[1:]))
        return gaps

    def tokens_in_window(self) -> int:
        return sum(
            1 for r in self.reqs for x in r.token_times if 0.0 <= x <= self.end
        )


class Runner:
    """Runs the engine for one window and records steps, forwards and
    tokens.  ``make_request(rid, prompt, max_new)`` builds the program's
    request; ``span(name, step)`` opens a host span named ``"bench.step"``
    around each step and ``"bench.forward"`` around each forward."""

    def __init__(self, engine, make_request: Callable, *,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 span: Optional[Callable] = None):
        self.eng = engine
        self.make_request = make_request
        self.clock = clock
        self.sleep = sleep
        self.span = span
        self.t_start = 0.0
        self._fwd_s = 0.0
        self._fwd_in: Optional[tuple] = None
        self._inflight: List[Req] = []
        self._n_steps = 0
        inner = engine.executor.forward

        def forward(tokens, caches=None, cache_pos=None, **kw):
            t0 = clock()
            if span is None:
                out = inner(tokens, caches, cache_pos, **kw)
            else:
                with span("bench.forward", self._n_steps):
                    out = inner(tokens, caches, cache_pos, **kw)
            self._fwd_s += clock() - t0
            # q_lens is a device array: read it now, since one kept per step
            # would leave small buffers scattered among the KV pools
            q = kw.get("q_lens")
            self._fwd_in = (int(tokens.shape[1]),
                            None if q is None else np.asarray(q), cache_pos)
            return out

        engine.executor.forward = forward

    def now(self) -> float:
        return self.clock() - self.t_start

    def submit(self, r: Req, when: float):
        r.submitted = when
        r.engine_req = self.make_request(r.rid, r.prompt, r.out_len)
        self.eng.submit(r.engine_req)
        if getattr(r.engine_req, "rejected", False):
            r.shed = r.done = True
        else:
            self._inflight.append(r)

    def busy(self) -> bool:
        return bool(self._inflight)

    def step(self, win: Window):
        """One engine step, with its records."""
        self._fwd_s, self._fwd_in = 0.0, None
        self._n_steps = len(win.steps)
        t0 = self.now()
        if self.span is not None:
            with self.span("bench.step", self._n_steps):
                self.eng.step()
        else:
            self.eng.step()
        t1 = self.now()
        width, ql, cp = self._fwd_in or (0, None, None)
        win.steps.append(Step(t0, t1, self._fwd_s, width, ql, cp))
        still = []
        for r in self._inflight:
            er = r.engine_req
            if r.admitted is None and er.started:
                r.admitted = t0
            r.token_times.extend([t1] * (len(er.out_tokens) - len(r.token_times)))
            if er.done:
                r.done = True
                r.shed = er.state != "finished"
            else:
                still.append(r)
        self._inflight = still

    # ------------------------------------------------------------------
    def run_open(self, schedule: List[Req], seconds: float, *,
                 trace: Optional[tuple] = None) -> Window:
        """Open loop: each request is submitted at its due time, whatever
        the engine is doing.  The clock starts at the earliest due time:
        requests due before 0 (the pre-roll) fill the slots as the same
        arrivals would, so the window opens on a running engine; steps that
        start before 0 are not the window's.  ``trace=(seconds_traced,
        start, stop)`` calls ``start()`` between steps once that much of the
        window is left and ``stop()`` when the window ends."""
        win = Window(seconds=seconds, reqs=list(schedule))
        pre = Window(seconds=0.0)
        order = sorted(schedule, key=lambda r: r.due)
        nxt = 0
        self.t_start = self.clock() - min([0.0] + [r.due for r in order[:1]])
        while True:
            now = self.now()
            if now >= seconds:
                break
            self._maybe_trace(win, trace, now)
            while nxt < len(order) and order[nxt].due <= now + 1e-6:
                at = max(now, order[nxt].due)      # a sleep may stop a hair short
                win.lateness.append(at - order[nxt].due)
                self.submit(order[nxt], at)
                nxt += 1
            if not self.busy():
                due = order[nxt].due if nxt < len(order) else seconds
                self.sleep(max(0.0, min(due, seconds) - now))
                continue
            self.step(win if now >= 0.0 else pre)
        return self._close(win, trace)

    def run_closed(self, clients: List[Req], pool: Callable[[], Req],
                   seconds: float, *, trace: Optional[tuple] = None) -> Window:
        """Closed loop: ``clients`` are the requests in flight when the
        window opens (submitted during set-up); whenever one completes, its
        client sends ``pool()`` at once."""
        win = Window(seconds=seconds, reqs=list(clients))
        self.t_start = self.clock()
        for r in clients:       # what happened in set-up lies before the window
            r.due = r.submitted = 0.0
            r.token_times = [-1.0] * len(r.token_times)
            if r.admitted is not None:
                r.admitted = -1.0
        slots = list(clients)
        while True:
            now = self.now()
            if now >= seconds:
                break
            self._maybe_trace(win, trace, now)
            for i, r in enumerate(slots):
                if r.done:
                    n = pool()
                    n.due = now
                    win.reqs.append(n)
                    self.submit(n, now)
                    slots[i] = n
            self.step(win)
        return self._close(win, trace)

    def _maybe_trace(self, win: Window, trace, now: float):
        if trace is None or win.trace_steps is not None:
            return
        traced_s, start, _ = trace
        if now >= win.seconds - traced_s:
            start()
            win.trace_steps = range(len(win.steps), len(win.steps))

    def _close(self, win: Window, trace) -> Window:
        win.end = self.now()
        if trace is not None and win.trace_steps is not None:
            trace[2]()
            win.trace_steps = range(win.trace_steps.start, len(win.steps))
        return win
