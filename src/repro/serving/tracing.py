"""Program spans and the compile counter, on the profiler's clock.

``span(name, **meta)`` is :class:`jax.profiler.TraceAnnotation`: while a
profiler session is open the span lands in the trace beside JAX's own host
events and the device's operations, and costs a few hundred nanoseconds
when none is.  The profiler's buffer holds the spans and ``stop_trace``
writes them out, so nothing is stored here.  Names are fixed strings
(``engine.step``, ``executor.launch``, ...); per-call values such as the
stage index go in as metadata (``stage=si``).

The compile counter listens once, for the whole process, to JAX's
``/jax/core/compile/backend_compile_duration`` event (a persistent-cache
read counts as one).  :func:`compile_stats` gives the count and seconds so
far; the engine reports the difference since it was built.
"""

from __future__ import annotations

from typing import Tuple

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

span = jax.profiler.TraceAnnotation

_compiles = 0
_compile_s = 0.0


def _on_duration(event: str, duration: float, **_) -> None:
    global _compiles, _compile_s
    if event == COMPILE_EVENT:
        _compiles += 1
        _compile_s += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_stats() -> Tuple[int, float]:
    """Backend compiles in this process so far, and their seconds."""
    return _compiles, _compile_s
