"""Time to first token, 80th percentile over every request due in the
window, from its due time; one with no token by the end counts at
(end - due)."""

from bench.loop import percentile


def reduce(run):
    v = percentile(run.window.ttft(), 80)
    return None if v is None else v * 1e3
