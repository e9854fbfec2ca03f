"""The traffic generator and the measured window, against a fake engine on
a fake clock: no JAX, no chip."""

import numpy as np
import pytest

from bench import loop, traffic

CHAT = {"loop": "open", "rate_per_s": 2.0,
        "prompt": {"median": 512, "sigma": 0.8, "min": 32, "max": 2048},
        "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}
LONG = {"loop": "closed", "clients": 3, "block": 4,
        "prompt": {"median": 40, "sigma": 0.4, "min": 16, "max": 80},
        "output": {"median": 6, "sigma": 0.4, "min": 2, "max": 12}}
BIG_SEED = 2**31 + 977


def test_same_seed_same_schedule():
    a = traffic.open_schedule(CHAT, BIG_SEED, 30.0, 1000)
    b = traffic.open_schedule(CHAT, BIG_SEED, 30.0, 1000)
    assert [(r.due, r.prompt, r.out_len) for r in a] == \
        [(r.due, r.prompt, r.out_len) for r in b]


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = traffic.open_schedule(CHAT, 1, 30.0, 1000)
    b = traffic.open_schedule(CHAT, 2, 30.0, 1000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.out_len for r in a) == sorted(r.out_len for r in b)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [r.due for r in s]), 9))
    assert gaps(a) == gaps(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].due == pytest.approx(b[-1].due)


def test_lengths_clipped_and_window_filled():
    s = traffic.open_schedule(CHAT, 7, 60.0, 1000)
    assert len(s) == 120
    p = [len(r.prompt) for r in s]
    o = [r.out_len for r in s]
    assert min(p) >= 32 and max(p) == 2048 and min(o) >= 16 and max(o) == 512
    assert np.median(p) == pytest.approx(512, rel=0.05)
    assert 50.0 < s[-1].due < 62.0
    assert all(0 <= t < 1000 for r in s for t in r.prompt)


def test_preroll_arrivals_come_before_the_window():
    mix = dict(CHAT, preroll_s=20.0)
    s = traffic.open_schedule(mix, 7, 40.0, 1000)
    other = traffic.open_schedule(mix, 8, 40.0, 1000)
    for a in (s, other):
        pre = [r for r in a if r.due < 0]
        win = [r for r in a if r.due >= 0]
        assert len(pre) == 40 and len(win) == 80     # rate x pre-roll, rate x window
        assert -21.0 < min(r.due for r in pre) < -19.0
        assert max(r.due for r in win) < 42.0
    assert min(r.due for r in s) == pytest.approx(min(r.due for r in other))
    # the window gets the same work for every seed, and so does the pre-roll
    for part in (lambda r: r.due < 0, lambda r: r.due >= 0):
        assert sorted(r.out_len for r in s if part(r)) == \
            sorted(r.out_len for r in other if part(r))
        assert sorted(len(r.prompt) for r in s if part(r)) == \
            sorted(len(r.prompt) for r in other if part(r))
    assert len({r.rid for r in s}) == len(s)


def test_closed_pool_blocks_repeat_the_size_set():
    pool = traffic.closed_pool(LONG, 3, 100)
    first = [pool() for _ in range(8)]
    assert sorted(len(r.prompt) for r in first[:4]) == \
        sorted(len(r.prompt) for r in first[4:])
    assert len({r.rid for r in first}) == 8


# ----------------------------------------------------------------------
# the window, driven against a fake engine


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeReq:
    def __init__(self, rid, prompt, max_new):
        self.rid, self.prompt, self.max_new_tokens = rid, prompt, max_new
        self.out_tokens, self.done, self.started = [], False, False
        self.rejected, self.state = False, "pending"


class FakeEngine:
    """Slots; a prompt takes one step per 16 tokens, then one token per
    step.  ``stall`` maps a step index to extra seconds."""

    def __init__(self, clock, slots=4, step_s=0.01, stall=None):
        self.clock, self.slots, self.step_s = clock, slots, step_s
        self.stall = stall or {}
        self.queue, self.active, self.n = [], [None] * slots, 0
        self.left = {}
        eng = self

        class Ex:
            def forward(self, tokens, caches=None, cache_pos=None, **kw):
                eng.clock.t += eng.step_s + eng.stall.get(eng.n, 0.0)
                return None, None

        self.executor = Ex()

    def submit(self, r):
        self.queue.append(r)

    def step(self):
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                r = self.queue.pop(0)
                r.started = True
                self.active[i] = r
                self.left[r.rid] = -(-len(r.prompt) // 16)

        class T:
            shape = (self.slots, 16)

        self.executor.forward(T(), None, np.zeros(self.slots, np.int32),
                              q_lens=np.ones(self.slots, np.int32))
        self.n += 1
        for i, r in enumerate(self.active):
            if r is None:
                continue
            if self.left[r.rid] > 1:
                self.left[r.rid] -= 1
                continue
            self.left[r.rid] = 0
            r.out_tokens.append(1)
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done, r.state = True, "finished"
                self.active[i] = None


def run_open(stall=None, seconds=20.0, preroll=0.0, step_s=0.01):
    clock = Clock()
    eng = FakeEngine(clock, step_s=step_s, stall=stall)
    drv = loop.Runner(eng, FakeReq, clock=clock, sleep=clock.sleep)
    mix = dict(CHAT, rate_per_s=3.0, preroll_s=preroll,
               prompt={"median": 40, "sigma": 0.5, "min": 8, "max": 120},
               output={"median": 10, "sigma": 0.5, "min": 2, "max": 30})
    sched = traffic.open_schedule(mix, 11, seconds, 100)
    return drv.run_open(sched, seconds)


def test_open_loop_times_from_due_and_counts_lateness():
    w = run_open(stall={5: 3.0})
    assert w.end >= w.seconds
    for r in w.due_in_window():
        assert r.submitted >= r.due
        if r.token_times:
            assert r.token_times[0] - r.due >= r.submitted - r.due
    assert max(w.lateness) > 1.0        # requests due during the stall waited
    assert len(w.ttft()) == len(w.due_in_window())


def test_a_stalled_step_moves_every_tail():
    base = run_open()
    stalled = run_open(stall={i: 1.0 for i in range(20, 600, 40)})
    p = loop.percentile
    assert p(stalled.ttft(), 80) > p(base.ttft(), 80)
    assert p(stalled.queue_wait(), 80) > p(base.queue_wait(), 80)
    assert p(stalled.itl(), 99) > p(base.itl(), 99)
    assert max(stalled.itl()) >= 1.0


def test_preroll_opens_the_window_on_held_slots():
    cold, warm = run_open(step_s=0.05), run_open(preroll=5.0, step_s=0.05)
    assert cold.slots_held()[0] == 0
    held_open, held_mean = warm.slots_held()
    assert held_open >= 2 and held_mean > 1
    assert all(s.t0 >= 0.0 for s in warm.steps)      # pre-roll steps are set-up
    assert all(r.due >= 0.0 for r in warm.due_in_window())
    assert len(warm.due_in_window()) < len(warm.reqs)
    # pre-roll requests' tokens inside the window count, those before do not
    early = [r for r in warm.reqs if r.due < 0 and r.token_times]
    assert any(t < 0 for r in early for t in r.token_times)
    assert warm.tokens_in_window() == sum(
        1 for r in warm.reqs for t in r.token_times if 0.0 <= t <= warm.end)
    assert len(warm.ttft()) == len(warm.due_in_window())


def test_tails_are_over_all_samples():
    w = loop.Window(seconds=10.0, end=10.0)
    w.reqs = [loop.Req(0, 0.0, [1], 3, token_times=[1.0, 1.5, 3.5]),
              loop.Req(1, 2.0, [1], 3, token_times=[2.5, 2.6]),
              loop.Req(2, 9.0, [1], 3)]                 # no token by the end
    assert sorted(w.itl()) == pytest.approx([0.1, 0.5, 2.0])
    assert sorted(w.ttft()) == pytest.approx([0.5, 1.0, 1.0])
    assert loop.percentile(w.itl(), 50) == pytest.approx(0.5)
    assert w.tokens_in_window() == 5


def test_closed_loop_window_opens_full_and_refills():
    clock = Clock()
    eng = FakeEngine(clock, slots=3)
    drv = loop.Runner(eng, FakeReq, clock=clock, sleep=clock.sleep)
    pool = traffic.closed_pool(LONG, 5, 100)
    clients = [pool() for _ in range(3)]
    for r in clients:
        drv.submit(r, 0.0)
    drv.step(loop.Window(seconds=0.0))
    w = drv.run_closed(clients, pool, 2.0)
    assert len(w.reqs) > 3
    assert all(x >= 0 or x == -1.0 for r in w.reqs for x in r.token_times)
    assert w.tokens_in_window() > 0
    assert all(s.width == 16 for s in w.steps)
