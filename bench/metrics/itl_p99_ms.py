"""99th percentile of the gaps between consecutive output tokens, over all
requests."""

from bench.loop import percentile


def reduce(run):
    v = percentile(run.window.itl(), 99)
    return None if v is None else v * 1e3
