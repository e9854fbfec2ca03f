"""The one traffic generator: reads a mix's parameters from its data file
and draws the requests from ``--seed``.

Every seed gets the same set of sizes and arrival gaps, in another order:
lengths are the lognormal's quantiles at evenly spaced probabilities, gaps
those of the exponential, each permuted by the seed.  So the work offered in
a window is the same for every seed, and only its order and the token ids
change.  A mix file holds::

    {"loop": "open", "rate_per_s": 0.5,              # Poisson arrivals
     "preroll_s": 30,       # arrivals served in set-up, before the window
     "prompt": {"median": 512, "sigma": 0.8, "min": 32, "max": 2048},
     "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512}}

or ``"loop": "closed", "clients": 8, "block": 16``: each client sends its
next request when the last completes, drawn from blocks of ``block``
requests (one full set of sizes per block).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Callable, Dict, Iterator, List

import numpy as np

from bench.loop import Req

_NORMAL = NormalDist()


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any non-negative seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def lognormal_set(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the lognormal's quantiles (i + 0.5) / n, clipped to
    [min, max], in ascending order."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = [
        math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n)) for i in range(n)
    ]
    return np.clip(np.rint(out), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps at the quantiles (i + 0.5) / n of Exp(rate)."""
    p = (np.arange(n) + 0.5) / n
    return -np.log1p(-p) / rate


def _requests(mix: Dict, rng: np.random.Generator, n: int, vocab: int,
              rid0: int) -> List[Req]:
    plen = rng.permutation(lognormal_set(mix["prompt"], n))
    olen = rng.permutation(lognormal_set(mix["output"], n))
    return [
        Req(rid=rid0 + i, due=0.0,
            prompt=rng.integers(0, vocab, size=int(p)).tolist(), out_len=int(o))
        for i, (p, o) in enumerate(zip(plen, olen))
    ]


def _arrivals(mix: Dict, rng: np.random.Generator, span: float, vocab: int,
              rid0: int) -> tuple:
    """round(rate x span) requests, one full set of sizes, and their
    arrival gaps, one full set of Exp(rate) quantiles, each in the seed's
    order."""
    rate = float(mix["rate_per_s"])
    n = max(1, round(rate * span))
    reqs = _requests(mix, rng, n, vocab, rid0)
    return reqs, np.cumsum(rng.permutation(exponential_gaps(rate, n)))


def open_schedule(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Req]:
    """Poisson arrivals at ``rate_per_s`` over the window, and over the
    ``preroll_s`` seconds before it: arrivals served in set-up, so that the
    window opens on a running engine.  Window and pre-roll each get their
    own full set of sizes and gaps, so every seed offers the window the same
    work; the pre-roll's gaps count back from 0, so it starts at the same
    time for every seed."""
    rng = rng_for(seed, 0)
    reqs, due = _arrivals(mix, rng, seconds, vocab, 0)
    for r, t in zip(reqs, due):
        r.due = float(t)
    lead = float(mix.get("preroll_s", 0.0))
    if lead > 0:
        pre, back = _arrivals(mix, rng, lead, vocab, len(reqs))
        for r, t in zip(pre, back):
            r.due = -float(t)
        reqs = pre + reqs
    return reqs


def closed_pool(mix: Dict, seed: int, vocab: int) -> Callable[[], Req]:
    """The stream the clients of a closed loop draw from, in order."""
    rng = rng_for(seed, 1)
    block = int(mix["block"])

    def gen() -> Iterator[Req]:
        rid = 0
        while True:
            yield from _requests(mix, rng, block, vocab, rid)
            rid += block

    it = gen()
    return lambda: next(it)

