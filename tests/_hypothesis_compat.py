"""Property-test shim: real ``hypothesis`` when installed, else a tiny
deterministic fallback so the suite collects and runs on a bare interpreter.

Usage in test modules::

    from _hypothesis_compat import HAVE_HYPOTHESIS, hypothesis, st

The fallback supports exactly the subset this repo's tests use —
``@hypothesis.settings(max_examples=..., deadline=...)`` and
``@hypothesis.example(name=value, ...)`` stacked on
``@hypothesis.given(name=st.integers(a, b), ...)`` with ``st.integers``,
``st.floats`` and ``st.sampled_from`` strategies.  It draws ``max_examples`` pseudo-random examples
from a per-test seed derived via crc32 of the test name (stable across runs
and interpreters, unlike ``hash()``), so failures reproduce.  It does NOT
shrink counterexamples; install the real package (requirements-dev.txt) for
that.
"""

from __future__ import annotations

import functools
import random
import types
import zlib

try:
    import hypothesis  # noqa: F401
    import hypothesis.strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    _DEFAULT_MAX_EXAMPLES = 10

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng: random.Random):
            return self._draw(rng)

    def _integers(min_value, max_value):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def _floats(min_value, max_value):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    def _sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rng: rng.choice(elements))

    def _given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_max_examples", _DEFAULT_MAX_EXAMPLES)
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = random.Random(seed)
                explicit = getattr(wrapper, "_examples", [])
                for i in range(len(explicit) + n):
                    drawn = (explicit[i] if i < len(explicit) else
                             {k: s.draw(rng) for k, s in strategies.items()})
                    try:
                        fn(*args, **drawn, **kwargs)
                    except Exception as e:
                        raise AssertionError(
                            f"falsifying example {fn.__qualname__}({drawn})"
                        ) from e

            # functools.wraps sets __wrapped__, which makes pytest resolve
            # the ORIGINAL signature and demand fixtures for drawn args
            del wrapper.__wrapped__
            return wrapper

        return deco

    def _settings(max_examples=_DEFAULT_MAX_EXAMPLES, deadline=None, **_ignored):
        def deco(fn):
            # @settings sits ABOVE @given, so it receives given's wrapper
            fn._max_examples = max_examples
            return fn

        return deco

    def _example(**values):
        def deco(fn):
            # @example sits ABOVE @given: given's wrapper runs these first
            fn._examples = [values] + getattr(fn, "_examples", [])
            return fn

        return deco

    hypothesis = types.SimpleNamespace(
        given=_given, settings=_settings, example=_example
    )
    st = types.SimpleNamespace(
        integers=_integers, floats=_floats, sampled_from=_sampled_from
    )

__all__ = ["HAVE_HYPOTHESIS", "hypothesis", "st"]
